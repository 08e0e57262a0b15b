"""Always-on host spans: one bounded, process-wide log.

The counters in `obs/metrics.py` say how often; a span says when and
inside what. `span(name)` stamps `time.perf_counter()` at both ends
and appends one `Record` to a ring of `LOG_MAX` records. `parent` is
the span that was open on the same thread (servers run on threads
under `fleet/replica.py`), and spans of one request carry its `rid`.

Each span also enters `utils.profiling.annotate(name)`, so whenever a
`jax.profiler` trace is being captured the same spans lie on the
profiler's clock beside the device's operations; with no session that
is an atomic load.

Two exits: `snapshot(t_lo, t_hi)` for code (the benchmark's readers
filter by their own `perf_counter` window), `to_chrome_trace()` for a
person (Perfetto). `obs.reset()` clears the log.

Program builds come through one `jax.monitoring` listener, registered
when this module is first imported: each lowering, compilation and
compile-cache hit appends a `jax.build` record under the span that
was open on the thread and moves `defer_program_builds_total{kind=}`,
so a step that rebuilt a program is named from inside the program.
"""

from __future__ import annotations

import collections
import itertools
import os
import threading
import time
from typing import NamedTuple

import jax

from defer_tpu.obs.metrics import get_registry
from defer_tpu.utils.profiling import annotate

# Records kept. The paged server writes about 7 a tick and 8 a seated
# request, so at a few ticks a second the ring holds over an hour.
LOG_MAX = 1 << 15

BUILD_EVENTS = {
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lowered",
    "/jax/core/compile/backend_compile_duration": "compiled",
    "/jax/compilation_cache/cache_retrieval_time_sec": "from_cache",
}


class Record(NamedTuple):
    id: int
    parent: int | None  # the span open on the same thread, if any
    name: str
    t0: float  # time.perf_counter()
    t1: float
    rid: int | None
    counts: dict
    tid: int  # threading.get_ident(), a track for to_chrome_trace


class Snapshot(NamedTuple):
    records: list
    # False where the ring is full and its oldest record ended after
    # `t_lo` (with no `t_lo`: wherever it is full): records that ended
    # in the span asked for may have been pushed out.
    complete: bool


_clock = time.perf_counter
_ids = itertools.count(1)  # next() is one bytecode: atomic under the GIL
_open = threading.local()  # .stack: ids of the spans open on this thread
_lock = threading.Lock()
_log: collections.deque = collections.deque(maxlen=LOG_MAX)


def _stack() -> list:
    try:
        return _open.stack
    except AttributeError:
        _open.stack = []
        return _open.stack


def record(name: str, t0: float, t1: float, rid=None, **counts) -> None:
    """Append a span whose ends were stamped elsewhere (a request's
    life from its `submit` stamp, a build's reported duration). Its
    parent is the span open on this thread."""
    stack = _stack()
    rec = Record(
        next(_ids), stack[-1] if stack else None, name, t0, t1, rid,
        counts, threading.get_ident(),
    )
    with _lock:  # against snapshot()'s copy and reset()
        _log.append(rec)


class span:
    """`with span("paged.tick", live=3) as sp:` — `sp.counts` may be
    filled inside the block, and `sp.keep = False` leaves the record
    out (a poll that found nothing to do)."""

    __slots__ = (
        "name", "rid", "counts", "keep", "id", "_parent", "_stack",
        "_ann", "_t0",
    )

    def __init__(self, name: str, rid=None, **counts):
        self.name = name
        self.rid = rid
        self.counts = counts
        self.keep = True

    def __enter__(self):
        self._stack = stack = _stack()
        self._parent = stack[-1] if stack else None
        self.id = next(_ids)
        stack.append(self.id)
        self._ann = annotate(self.name)
        self._ann.__enter__()
        self._t0 = _clock()
        return self

    def __exit__(self, *exc):
        t1 = _clock()
        self._ann.__exit__(*exc)
        self._stack.pop()
        if self.keep:
            rec = Record(
                self.id, self._parent, self.name, self._t0, t1, self.rid,
                self.counts, threading.get_ident(),
            )
            with _lock:
                _log.append(rec)
        return False


def snapshot(t_lo: float | None = None, t_hi: float | None = None) -> Snapshot:
    """The records whose end lies in `(t_lo, t_hi]` on the
    `time.perf_counter` clock, oldest first, and whether they are all
    that ended there."""
    with _lock:
        records = list(_log)
    complete = len(records) < LOG_MAX or (
        t_lo is not None and records[0].t1 <= t_lo
    )
    if t_lo is not None:
        records = [r for r in records if r.t1 > t_lo]
    if t_hi is not None:
        records = [r for r in records if r.t1 <= t_hi]
    return Snapshot(records, complete)


def to_chrome_trace() -> list[dict]:
    """The log as a `traceEvents` list (complete events, microseconds):
    `json.dump({"traceEvents": to_chrome_trace()}, f)` loads in
    Perfetto, one track per thread."""
    pid = os.getpid()
    return [
        {
            "name": r.name, "ph": "X", "pid": pid, "tid": r.tid,
            "ts": r.t0 * 1e6, "dur": (r.t1 - r.t0) * 1e6,
            "args": {"id": r.id, "parent": r.parent, "rid": r.rid, **r.counts},
        }
        for r in snapshot().records
    ]


def reset() -> None:
    """Clear the log (test isolation); spans still open stay valid."""
    with _lock:
        _log.clear()


_builds = {
    kind: get_registry().counter(
        "defer_program_builds_total",
        "Programs lowered, compiled, or loaded from the compile cache "
        "in this process (jax.monitoring)",
        {"kind": kind},
    )
    for kind in BUILD_EVENTS.values()
}


def _on_build(event: str, duration: float, **_) -> None:
    kind = BUILD_EVENTS.get(event)
    if kind is None:
        return
    _builds[kind].inc()
    t1 = _clock()
    record("jax.build", t1 - duration, t1, kind=kind)


jax.monitoring.register_event_duration_secs_listener(_on_build)
