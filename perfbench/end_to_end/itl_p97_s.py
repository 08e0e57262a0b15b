"""97th percentile of the gaps between consecutive tokens of one
request, pooled over all requests, inside the window. Host clock.

Why the 97th: admission pads a prompt to a power of two, so the gaps
that hold a prefill form a staircase (at these lengths three treads of
a third each), and about a fifth of all gaps hold one. The 95th
percentile then lies within a point of a tread's edge and the 99th at
the edge of the gaps that hold two prefills; the 97th is mid-tread,
with some 180 gaps beyond it."""

from perfbench import metrics


def read(run):
    gaps = metrics.inter_token_gaps(run.window_stamps())
    return metrics.percentile(gaps, 97.0) if gaps else None
