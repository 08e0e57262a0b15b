"""The least time a chip could take for one call of the `gdn_step`
kernel (one recurrent layer's decode update for every slot) over the
time it took: the family's `gdn_step_counts(model, slots)` over the
peaks, the larger of bytes over bandwidth and operations over rate,
against the median device time of the operations named `gdn_step` in
the traced slice. The kernel runs over all `max_batch` slots whatever
is live, so that is the count. None where the family brings no such
count or the trace holds no such operation (a program without the
kernel). Device trace."""

from perfbench import metrics, peaks

KERNEL = "gdn_step"


def read(run):
    counts = getattr(run.family, "gdn_step_counts", None)
    if run.trace is None or counts is None:
        return None
    times = [s for name, _, s in run.trace.ops if KERNEL in name and s > 0]
    if not times:
        return None
    nbytes, ops = counts(run.model, run.server_args["max_batch"])
    least = peaks.least_s(nbytes, ops, run.peaks, 1)[0]
    return 100.0 * least / metrics.median(times)
