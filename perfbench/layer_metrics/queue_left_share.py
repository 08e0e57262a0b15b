"""Requests still in the server's queue as the window closes, over
those queued as it opened: what is left of a backlog cell's margin.
Near 0 the queue is about to be outlasted and `tokens_per_s` would
count a tail of emptying slots: re-size the cell (README, "Sizing a
cell") before a PR is refused for it. A sizing margin and not a
quality to improve: every gain in `tokens_per_s` serves more of the
queue and lowers it, so a fall beside a gain is the gain's companion
(`better` is `higher` only because 0 is the fault). Nothing where
nothing is queued at time 0."""


def read(run):
    if not run.queued_at_open:
        return None
    return 100.0 * run.pending_at_close / run.queued_at_open
