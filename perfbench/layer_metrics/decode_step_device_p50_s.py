"""Device busy time inside one `tick` host span of the traced slice,
median over ticks. Admission has spans of its own (`admit`) and ends
in a transfer, so a tick holds the decode step alone. Device trace."""

from perfbench import metrics, xplane


def read(run):
    if run.trace is None:
        return None
    busy = xplane.busy_per_span(run.trace, "tick")
    return metrics.median(busy) if busy else None
