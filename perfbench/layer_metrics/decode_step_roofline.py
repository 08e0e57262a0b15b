"""The least time a chip could take for the decode step over the
time it took (decode_step_device_p50_s). Least time: the larger of
bytes (every weight once and the live K and V rows, from shapes and
the requests' depths) over the peak bandwidth and operations over the
peak rate, per chip; at these batches the bytes bound it."""

from perfbench import metrics, peaks, xplane


def read(run):
    ticks = run.window_ticks()
    if run.trace is None or not ticks:
        return None
    busy = xplane.busy_per_span(run.trace, "tick")
    if not busy:
        return None
    live = sum(t[3] for t in ticks) / len(ticks)
    rows = sum(t[4] for t in ticks) / len(ticks)
    least, _ = peaks.decode_step_least_s(
        run.weight_bytes, live, rows, run.model, run.peaks, run.chips
    )
    return 100.0 * least / metrics.median(busy)
