"""The least time a chip could take for the decode step over the
time it took (decode_step_device_p50_s). Least time: the larger of
bytes over the peak bandwidth and operations over the peak rate, per
chip, for each tick of the window from its live slots' depths, then
the mean over the ticks. The count is the family's own
`decode_step_counts` where `families/<family>.py` brings one, and the
dense GQA count of `peaks.py` otherwise (every weight once and the live
K and V rows; at these batches the bytes bound it)."""

from perfbench import metrics, peaks, xplane


def read(run):
    depths = run.window_tick_depths()
    if run.trace is None or not depths:
        return None
    busy = xplane.busy_per_span(run.trace, "tick")
    if not busy:
        return None
    counts = getattr(run.family, "decode_step_counts", peaks.decode_step_counts)
    least = [
        peaks.least_s(*counts(run.model, run.weight_bytes, d), run.peaks, run.chips)[0]
        for d in depths
    ]
    return 100.0 * sum(least) / len(least) / metrics.median(busy)
