"""97th percentile of the gaps between consecutive tokens of one
request, pooled over all requests, inside the window. Host clock.

Why the 97th: admission pads a prompt to a power of two, so the gaps
that hold a prefill form a staircase, at these lengths three treads
of a third each (a tick plus 36, 62 or 113 ms for the 256, 512 and
1024 buckets). The share of gaps that hold one is the arrival rate
times the gap: 1.76 requests/s x 0.085 s = 15% at PR 31's sizes (0.8
of the knee, a 72-80 ms tick), so the 1024 bucket's tread runs from
about p95.5 to where the gaps that hold two prefills begin, near
p99.3. In twelve runs p95 read on the edge (0.141-0.186 s), p96 to p98
inside (0.184-0.193 s: the tread's two levels are the step's two
rungs) and p99 now and then above it (0.192-0.246 s); the 97th is
mid-tread, with some 290 of 9700 gaps beyond it. The rate stands
still when a PR shortens the tick, and the tread's edge then climbs
towards p97: re-size the cell (README, "Sizing a cell")."""

from perfbench import metrics


def read(run):
    gaps = metrics.inter_token_gaps(run.window_stamps())
    return metrics.percentile(gaps, 97.0) if gaps else None
