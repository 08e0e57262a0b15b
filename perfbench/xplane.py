"""The reduction from a profiler trace to numbers.

Takes what `jax.profiler.ProfileData` gives (planes, their lines,
events with `name`, `start_ns`, `duration_ns`) or anything shaped like
it, so a test can hand it a small synthetic plane. Device planes are
those named `/device:TPU:<n>`; their operations are on the line
`XLA Ops`. Host spans are the `jax.profiler.TraceAnnotation`s the
driver loop puts around its own calls (`tick`, `admit`); they are on
the same clock as the device's events.
"""

from __future__ import annotations

import dataclasses

DEVICE_PREFIX = "/device:"
OPS_LINE = "XLA Ops"
SPANS = ("tick", "admit")
NAME_MAX = 96  # an op's name may be its whole HLO line: keep its head
KERNEL_MARKS = ("custom-call", "custom_call")


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float  # mean over the device planes
    busy_by_device: dict
    ops: list  # (name, start_s, self_s) of the first device plane
    spans: list  # (kind, start_s, end_s), in time order
    gaps: list  # (start_s, end_s) idle intervals of the first device
    union: list  # (start_s, end_s) busy intervals of the first device


def _union(intervals):
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1][1] = b
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _clip(intervals, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in intervals if b > lo and a < hi]


def overlap(intervals, lo: float, hi: float) -> float:
    """Seconds of the sorted, disjoint `intervals` inside [lo, hi]."""
    return sum(min(b, hi) - max(a, lo) for a, b in intervals if b > lo and a < hi)


def _self_times(events):
    """(name, start, self seconds) for events of one line, where an
    event that lies inside another (the body of a `while`) is taken
    out of the outer one's time."""
    evs = sorted(events, key=lambda e: (e[1], -e[2]))
    self_s = [e[2] - e[1] for e in evs]
    stack = []  # indices of open events
    for i, (_, a, b) in enumerate(evs):
        while stack and evs[stack[-1]][2] <= a:
            stack.pop()
        if stack and b <= evs[stack[-1]][2]:
            self_s[stack[-1]] -= b - a
        stack.append(i)
    return [(e[0], e[1], max(s, 0.0)) for e, s in zip(evs, self_s)]


def describe(profile) -> dict:
    """Plane -> line -> number of events, for the details line: what
    the trace held, whatever the reduction made of it."""
    return {
        plane.name: {line.name: len(list(line.events)) for line in plane.lines}
        for plane in profile.planes
        if plane.name.startswith(DEVICE_PREFIX)
    }


def reduce_profile(profile) -> Reduced | None:
    """None where the trace has no host span or no device operation:
    there is nothing to read."""
    spans = []
    device_events = {}
    for plane in profile.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    device_events[plane.name] = [
                        (e.name, e.start_ns * 1e-9,
                         (e.start_ns + e.duration_ns) * 1e-9)
                        for e in line.events
                    ]
        else:
            for line in plane.lines:
                for e in line.events:
                    if e.name in SPANS:
                        spans.append(
                            (e.name, e.start_ns * 1e-9,
                             (e.start_ns + e.duration_ns) * 1e-9)
                        )
    device_events = {k: v for k, v in device_events.items() if v}
    if not spans or not device_events:
        return None
    spans.sort(key=lambda s: s[1])
    lo, hi = spans[0][1], max(s[2] for s in spans)
    busy = {}
    unions = {}
    for name, evs in device_events.items():
        unions[name] = _clip(_union((a, b) for _, a, b in evs), lo, hi)
        busy[name] = sum(b - a for a, b in unions[name])
    first = sorted(device_events)[0]
    union = unions[first]
    edges = [lo] + [x for ab in union for x in ab] + [hi]
    gaps = [
        (edges[i], edges[i + 1])
        for i in range(0, len(edges), 2)
        if edges[i + 1] > edges[i]
    ]
    ops = [
        (n, a, s) for n, a, s in _self_times(device_events[first])
        if lo <= a < hi
    ]
    return Reduced(
        window_s=hi - lo,
        busy_s=sum(busy.values()) / len(busy),
        busy_by_device=busy,
        ops=ops,
        spans=spans,
        gaps=gaps,
        union=union,
    )


def span_at(spans, t: float) -> str:
    """The kind of host span that holds time t, or `other`."""
    for kind, a, b in spans:
        if a <= t < b:
            return kind
    return "other"


def top_device_ops(red: Reduced, n: int = 10) -> list:
    """The operations with most device time, each named
    `<host span it started in>/<name as the trace prints it>`."""
    total = {}
    for name, start, self_s in red.ops:
        key = f"{span_at(red.spans, start)}/{name[:NAME_MAX]}"
        total[key] = total.get(key, 0.0) + self_s
    ranked = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v] for k, v in ranked]


def idle_by_span(red: Reduced) -> list:
    """The device's idle time summed under the host span it fell in."""
    total = {kind: 0.0 for kind in SPANS}
    idle = sum(b - a for a, b in red.gaps)
    for kind, a, b in red.spans:
        total[kind] += overlap(red.gaps, a, b)
    total["other"] = max(idle - sum(total.values()), 0.0)
    return [[k, v] for k, v in sorted(total.items(), key=lambda kv: -kv[1])]


def busy_per_span(red: Reduced, kind: str) -> list[float]:
    """Device busy seconds inside each host span of `kind`."""
    return [overlap(red.union, a, b) for k, a, b in red.spans if k == kind]


def time_share(red: Reduced, marks) -> float:
    """Device time of operations whose name holds one of `marks`, over
    the device time of all operations, in per cent."""
    total = sum(s for _, _, s in red.ops)
    if total <= 0:
        return 0.0
    hit = sum(s for n, _, s in red.ops if any(m in n for m in marks))
    return 100.0 * hit / total
