"""Host clock around the loop's call of `_tick`, median. The call
ends in the [B,1] transfer, so the device has finished."""

from perfbench import metrics


def read(run):
    ticks = run.window_ticks()
    return metrics.median([t[1] - t[0] for t in ticks]) if ticks else None
