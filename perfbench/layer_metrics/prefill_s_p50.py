"""Host clock around `_admit` over the requests it seated, median
over the calls that seated any."""

from perfbench import metrics


def read(run):
    per = [(t1 - t0) / n for t0, t1, n in run.window_admits()]
    return metrics.median(per) if per else None
