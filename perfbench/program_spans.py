"""What the per-layer readers share to read the program's own spans.

The program keeps a bounded log of host spans (`defer_tpu/obs/spans.py`:
`paged.tick` and its phases, `paged.admit`, `paged.admit.seat`), stamped
with `time.perf_counter()`, the clock the harness stamps `Run.t_open`,
`Run.ticks` and `Run.admits` with. A reader filters by the window with
no conversion. To lay spans over the device trace, which is on the
profiler's clock, `offset` finds the difference between the clocks
from the harness's own `tick` annotations.

A program that has no span log (a commit before it) gives every reader
here nothing to read: each returns None and raises nothing.
"""

from __future__ import annotations

from perfbench import metrics, xplane

# The matched tick starts may disagree by this much before the readers
# that charge device time to a phase give up: a gap of a millisecond
# charged to the wrong phase is the size of what they measure.
OFFSET_TOLERANCE_S = 1e-3


def _snapshot(t_lo, t_hi):
    try:
        from defer_tpu.obs import spans
    except ImportError:
        return None
    return spans.snapshot(t_lo, t_hi)


def in_window(run, name: str):
    """The program's spans called `name` that ended inside the
    measured window, oldest first; None where the program keeps no
    span log or the log no longer reaches back to the window's
    opening."""
    snap = _snapshot(run.t_open, run.t_close)
    if snap is None or not snap.complete:
        return None
    return [r for r in snap.records if r.name == name]


def phase_p50(run, phases) -> float | None:
    """Median over the window's ticks of the summed durations of the
    named phases of `paged.tick`; a tick that lacks one (it straddles
    the window's edge) is left out."""
    per_tick = {}
    for name in phases:
        records = in_window(run, name)
        if records is None:
            return None
        for r in records:
            per_tick.setdefault(r.parent, []).append(r.t1 - r.t0)
    sums = [sum(v) for v in per_tick.values() if len(v) == len(phases)]
    return metrics.median(sums) if sums else None


def offset(run) -> float | None:
    """The profiler's clock minus `time.perf_counter()`: the median,
    over the traced ticks, of the trace's `tick` span start minus the
    harness's `t0` of the same call, matched from the last tick
    backwards (the harness stamps the one and enters the other back to
    back). None without a trace, or where the matched pairs disagree
    by more than OFFSET_TOLERANCE_S."""
    if run.trace is None:
        return None
    starts = [a for kind, a, _ in run.trace.spans if kind == "tick"]
    stamps = [t[0] for t in run.ticks]
    n = min(len(starts), len(stamps))
    if n == 0:
        return None
    diffs = [a - t for a, t in zip(starts[-n:], stamps[-n:])]
    mid = metrics.median(diffs)
    if max(abs(d - mid) for d in diffs) > OFFSET_TOLERANCE_S:
        return None
    return mid


def traced(run, names):
    """(start, end) on the profiler's clock of the program's spans
    with one of `names`, cut to the traced slice; None where there is
    no trace, no span log, or no trustworthy offset."""
    off = offset(run)
    if off is None:
        return None
    lo = run.trace.spans[0][1]
    hi = lo + run.trace.window_s
    snap = _snapshot(lo - off, None)
    if snap is None or not snap.complete:
        return None
    cut = [
        (max(r.t0 + off, lo), min(r.t1 + off, hi))
        for r in snap.records if r.name in names
    ]
    return sorted((a, b) for a, b in cut if b > a)


def idle_under(run, names) -> float | None:
    """Seconds the first device idled under the program's spans with
    one of `names`, in the traced slice."""
    spans = traced(run, names)
    if spans is None:
        return None
    return sum(xplane.overlap(run.trace.gaps, a, b) for a, b in spans)


def idle_share(run, names) -> float | None:
    """`idle_under` over the traced slice, in per cent: a part of
    `device_idle_share` on one chip."""
    idle = idle_under(run, names)
    if idle is None:
        return None
    return 100.0 * idle / run.trace.window_s
