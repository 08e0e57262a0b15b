"""Requests decoding at a tick, mean over the window's ticks."""


def read(run):
    ticks = run.window_ticks()
    return sum(t[3] for t in ticks) / len(ticks) if ticks else None
