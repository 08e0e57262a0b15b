#!/usr/bin/env python3
"""The program's spans and its decode step, read off the trace's clock.

`xplane.reduce_profile` keeps the harness's own host spans (`tick`,
`admit`) and the device's operations. The program's spans
(`defer_tpu/obs/spans.py`: `paged.tick` and its phases, `paged.admit`
and its seats, `paged.settle`, ...) lie in the same trace, on the same
clock: each is a `TraceAnnotation` whose event carries its record's
`id`, and its `parent` and `rid` where it has them (a program from
before that carries the name alone, and is read by name). This module
keeps them as the trace has them and charges each idle interval of the
first device to the innermost one open on the serving thread at that
instant: no shift between clocks, no stamp from the span log. It also
finds each execution of the decode step's program (`jit_<DECODE_STEP>`
of `defer_tpu.runtime.paged`) on the first device and its busy time.

`perfbench/harness.py` hands its readers the reduced trace alone, so
no per-layer metric reads this yet; wiring it in is an edit to
`read_trace` and `BENCHMARK.json`. Meanwhile

    python3 perfbench/program_trace.py --workload <name> --seed <n> \\
        --seconds <s> --trace <0|1>

runs the cell as `perfbench/run.py` does and prints a
`program_trace: {...}` line before the run's own two lines: in every
run the phases of the window's longest tick and admission (from the
span log), in a traced run also these readings.
"""

from __future__ import annotations

import dataclasses
import glob
import json
import os
import re
import sys
from typing import NamedTuple

if __package__ in (None, ""):
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from perfbench import metrics, program_spans, xplane  # noqa: E402

PREFIX = "paged."
TICK = "paged.tick"
ADMIT = "paged.admit"
# The phases of a tick before its step reaches the device, and those
# that read a step back and emit its tokens (a settle's sync and drain
# are its children).
LAUNCH = ("paged.tick.plan", "paged.tick.dispatch", "paged.tick.sample")
READBACK = ("paged.tick.sync", "paged.tick.drain", "paged.settle")
MODULES_LINE = "XLA Modules"
OUTSIDE = "none"
NAMES = (
    "idle_at_launch_share", "idle_at_readback_share", "idle_at_seat_share",
    "idle_outside_program_share", "step_device_s_p50",
)


class Span(NamedTuple):
    name: str
    start: float
    end: float
    id: int | None
    parent: int | None
    rid: object


@dataclasses.dataclass
class ProgramTrace:
    spans: list  # Span of the serving thread's line, by start
    # names of spans that hold a phase of their own at some time (a
    # span named after them: `paged.tick` holds `paged.tick.plan`)
    parents: frozenset
    # (start, end, busy seconds) of each execution of the decode step's
    # program on the first device that began in the traced slice
    steps: list


def step_name() -> str | None:
    """The decode step's function name; None for a program that does
    not name it."""
    try:
        from defer_tpu.runtime.paged import DECODE_STEP
    except ImportError:
        return None
    return DECODE_STEP


def _serving_line(profile):
    """The host line that holds the harness's `tick` spans (the thread
    that serves); where none does, the one with the most `paged.tick`."""
    best, key = None, (0, 0)
    for plane in profile.planes:
        if plane.name.startswith(xplane.DEVICE_PREFIX):
            continue
        for line in plane.lines:
            names = [e.name for e in line.events]
            k = (names.count("tick"), names.count(TICK))
            if k > key:
                best, key = line, k
    return best


def _nesting(spans) -> frozenset:
    parents, stack = set(), []
    for s in spans:
        while stack and stack[-1].end <= s.start:
            stack.pop()
        if stack and s.name.startswith(stack[-1].name + "."):
            parents.add(stack[-1].name)
        stack.append(s)
    return frozenset(parents)


def _steps(profile, name: str | None, lo: float, hi: float) -> list:
    """(start, end, busy seconds) of each execution of `jit_<name>`
    that began in [lo, hi) on the first device: an event of the
    plane's module line, and the union of the plane's ops inside it
    (the device runs one program at a time). A v5e's ops carry no
    `run_id` or `hlo_module`; its module events do."""
    planes = {
        p.name: p for p in profile.planes
        if p.name.startswith(xplane.DEVICE_PREFIX)
        and any(line.name == xplane.OPS_LINE and list(line.events) for line in p.lines)
    }
    if name is None or not planes:
        return []
    lines = {line.name: list(line.events) for line in planes[sorted(planes)[0]].lines}
    union = xplane._union(
        (e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9)
        for e in lines[xplane.OPS_LINE]
    )
    module = re.compile(rf"jit_{re.escape(name)}\b")
    found = []
    for e in lines.get(MODULES_LINE, []):
        a = e.start_ns * 1e-9
        b = a + e.duration_ns * 1e-9
        if module.match(e.name) and lo <= a < hi:
            found.append((a, b, xplane.overlap(union, a, b)))
    return sorted(found)


def reduce(profile, red: xplane.Reduced, name: str | None = None) -> ProgramTrace:
    """The program's spans on the serving thread and the decode step's
    executions in `red`'s slice. `name` is the step's function name,
    by default the program's own."""
    lo, hi = red.spans[0][1], red.spans[0][1] + red.window_s
    line = _serving_line(profile)
    spans = []
    for e in line.events if line is not None else ():
        if e.name.startswith(PREFIX):
            st = dict(e.stats)
            a = e.start_ns * 1e-9
            spans.append(Span(
                e.name, a, a + e.duration_ns * 1e-9,
                st.get("id"), st.get("parent"), st.get("rid"),
            ))
    spans.sort(key=lambda s: (s.start, -s.end))
    steps = _steps(profile, name or step_name(), lo, hi)
    return ProgramTrace(spans, _nesting(spans), steps)


def _segments(spans):
    """(start, end, names open, outermost first) of the stretches of
    the line in which the same spans are open; none where none is."""
    out, stack, t = [], [], None

    def emit(until):
        if stack and until > t:
            out.append((t, until, tuple(s.name for s in stack)))

    for s in spans:
        while stack and stack[-1].end <= s.start:
            emit(stack[-1].end)
            t = stack.pop().end
        emit(s.start)
        stack.append(s)
        t = s.start
    while stack:
        emit(stack[-1].end)
        t = stack.pop().end
    return out


def idle_by_stack(pt: ProgramTrace, gaps) -> list:
    """(seconds, names open) for each piece of the idle intervals
    `gaps`, by the program's spans open then; () where none was."""
    segs = _segments(pt.spans)
    out, j = [], 0
    for a, b in gaps:
        while j < len(segs) and segs[j][1] <= a:
            j += 1
        covered, k = 0.0, j
        while k < len(segs) and segs[k][0] < b:
            s0, s1, names = segs[k]
            piece = min(s1, b) - max(s0, a)
            if piece > 0:
                out.append((piece, names))
                covered += piece
            k += 1
        if b - a - covered > 0:
            out.append((b - a - covered, ()))
    return out


def idle_by_program_span(pt: ProgramTrace, gaps) -> dict:
    """Idle seconds by the innermost span open, `<name>/self` where a
    span that has phases was open and none of them, `none` outside
    every span."""
    out = {}
    for s, names in idle_by_stack(pt, gaps):
        key = OUTSIDE
        if names:
            key = names[-1] + ("/self" if names[-1] in pt.parents else "")
        out[key] = out.get(key, 0.0) + s
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))


def _phase(names) -> str:
    if not names:
        return "outside"
    if ADMIT in names:
        return "seat"
    for name in reversed(names):
        if name in LAUNCH:
            return "launch"
        if name in READBACK:
            return "readback"
    return "tick" if TICK in names else "other"


def idle_shares(pt: ProgramTrace, red: xplane.Reduced) -> dict:
    """The first device's idle time, in per cent of the traced slice,
    by the phase the serving thread was in: `launch` (plan, dispatch,
    sample), `readback` (sync, drain, settle), `seat` (`paged.admit`
    at any depth), `outside` (no program span), `tick` (`paged.tick`
    between its phases) and `other` (any other span). They add up to
    `device_idle_share` on one chip."""
    out = dict.fromkeys(("launch", "readback", "seat", "outside", "tick", "other"), 0.0)
    for s, names in idle_by_stack(pt, red.gaps):
        out[_phase(names)] += 100.0 * s / red.window_s
    return out


def step_device_s_p50(pt: ProgramTrace) -> float | None:
    """Median device busy seconds of one execution of the decode step's
    program; None where the trace holds none (a program that does not
    name its step)."""
    busy = [s for _, _, s in pt.steps]
    return metrics.median(busy) if busy else None


def readings(pt: ProgramTrace, red: xplane.Reduced) -> dict:
    """The five per-layer readings of a traced run, by metric name: all
    None where the trace holds none of the program's spans (a program
    without them), the step's None where it holds no step."""
    if not pt.spans:
        return dict.fromkeys(NAMES)
    shares = idle_shares(pt, red)
    return {
        "idle_at_launch_share": shares["launch"],
        "idle_at_readback_share": shares["readback"],
        "idle_at_seat_share": shares["seat"],
        "idle_outside_program_share": shares["outside"],
        "step_device_s_p50": step_device_s_p50(pt),
    }


def longest_call(run, name: str):
    """The window's longest `name` span in the program's span log: its
    seconds, when it began from the window's opening, and every span
    under it as [name, seconds] in the order they began; None where
    the log holds none."""
    calls = program_spans.in_window(run, name)
    if not calls:
        return None
    top = max(calls, key=lambda r: r.t1 - r.t0)
    snap = program_spans._snapshot(top.t0, top.t1)
    under, inside = [], {top.id}
    for r in sorted(snap.records, key=lambda r: r.t0):
        if r.parent in inside:
            inside.add(r.id)
            under.append([r.name, r.t1 - r.t0])
    return [top.t1 - top.t0, top.t0 - run.t_open, under]


def main(argv=None, *, root=None, devices=None, out=print) -> int:
    """`perfbench/run.py`'s run of a cell with the program's trace
    kept: the same harness, whose `read_trace` and `read_metrics` are
    wrapped to see the profile before it is deleted and the run."""
    from perfbench import harness, run

    seen = {}
    plain_trace, plain_metrics = harness.read_trace, harness.read_metrics

    def read_trace(trace_dir):
        import jax

        found = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
        if found:
            profile = jax.profiler.ProfileData.from_file(found[0])
            red = xplane.reduce_profile(profile)
            if red is not None:
                seen["trace"] = reduce(profile, red), red
        return plain_trace(trace_dir)

    def read_metrics(root, bench, group, folder, r):
        seen["run"] = r
        return plain_metrics(root, bench, group, folder, r)

    lines = []
    harness.read_trace, harness.read_metrics = read_trace, read_metrics
    try:
        rc = run.main(argv, root=root or run.ROOT, devices=devices, out=lines.append)
    finally:
        harness.read_trace, harness.read_metrics = plain_trace, plain_metrics
    r = seen["run"]
    ticks = r.window_ticks()
    last = [t for t in ticks if t[0] >= r.t_close - harness.TRACE_S]
    got = {
        "tick_p50_s": metrics.median([b - a for a, b, *_ in ticks]) if ticks else None,
        # the ticks a traced run's profiler saw, and the same stretch of
        # an untraced run: what a trace costs a tick
        "tick_p50_s_last_slice": metrics.median([b - a for a, b, *_ in last]) if last else None,
        "longest_tick": longest_call(r, TICK),
        "longest_admit": longest_call(r, ADMIT),
    }
    if "trace" in seen:
        pt, red = seen["trace"]
        got.update(readings(pt, red))
        got["idle_by_phase"] = idle_shares(pt, red)
        got["device_idle_share"] = 100.0 * (1.0 - red.busy_s / red.window_s)
        got["idle_by_program_span"] = idle_by_program_span(pt, red.gaps)
        got["steps"] = len(pt.steps)
        got["annotations_with_id"] = sum(s.id is not None for s in pt.spans)
        got["annotations"] = len(pt.spans)
    *before, result = lines
    for line in before:
        out(line)
    out("program_trace: " + json.dumps(got))
    out(result)
    return rc


if __name__ == "__main__":
    sys.exit(main())
