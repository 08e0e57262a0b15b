"""Device busy time inside the program's `paged.admit.seat` spans over
their summed duration, in the traced slice: how much of the time an
admission stalls every live slot the device is at work."""

from perfbench import program_spans, xplane


def read(run):
    seats = program_spans.traced(run, ("paged.admit.seat",))
    if not seats:
        return None
    busy = sum(xplane.overlap(run.trace.union, a, b) for a, b in seats)
    return 100.0 * busy / sum(b - a for a, b in seats)
