"""Tokens completed per second over whole dispatches: from the end of
the first dispatch in the window to the end of the last, all the work
and all the time between. Host clock."""

from perfbench import metrics


def read(run):
    events = run.dispatches()
    return metrics.tokens_per_s(events) if len(events) >= 2 else None
