"""Tracing seam over jax.profiler.

The reference's only observability is `[DEBUG]` prints and wall-clock
throughput counters (reference src/test.py:30-41, SURVEY.md §5). Here
the framework exposes real device traces: `trace(dir)` captures a
TensorBoard-loadable profile, and `annotate(name)` labels host-side
regions (stage dispatch, feed, drain) so pipeline bubbles are visible
against device activity.

Both degrade to no-ops if profiling is unavailable on the platform, so
production paths can call them unconditionally.
"""

from __future__ import annotations

import contextlib
import os

import jax

from defer_tpu.utils.logging import get_logger

log = get_logger(__name__)

# Env var consumed by `trace()` and `WindowTrace` below: set to a
# directory to capture a device trace of the stream.
TRACE_ENV = "DEFER_TPU_TRACE"


@contextlib.contextmanager
def trace(trace_dir: str | None = None):
    """Capture a jax.profiler trace into `trace_dir` (or $DEFER_TPU_TRACE;
    no-op if neither is set or the profiler fails to start)."""
    target = trace_dir or os.environ.get(TRACE_ENV)
    if not target:
        yield None
        return
    try:
        jax.profiler.start_trace(target)
    except Exception as e:  # profiler can be unsupported per-platform
        log.warning("profiler trace unavailable: %s", e)
        yield None
        return
    try:
        yield target
    finally:
        try:
            jax.profiler.stop_trace()
            log.info("wrote device trace to %s", target)
        except Exception as e:
            log.warning("profiler stop failed: %s", e)


def annotate(name: str):
    """Named host-region annotation visible in captured traces."""
    try:
        return jax.profiler.TraceAnnotation(name)
    except Exception:
        return contextlib.nullcontext()


class WindowTrace:
    """Trace a bounded window of an unbounded loop.

    An open-ended `trace()` around a serving loop would buffer events
    for the whole process lifetime (multi-GB profiles TensorBoard can't
    load). This starts on the first `tick()` and stops after `limit`
    ticks — or at `close()`, whichever comes first. Inert unless
    $DEFER_TPU_TRACE (or trace_dir) is set.
    """

    def __init__(self, limit: int = 64, trace_dir: str | None = None):
        self.limit = limit
        self.target = trace_dir or os.environ.get(TRACE_ENV)
        self._ticks = 0
        self._cm = None  # the trace() context, entered on first tick
        self._active = False
        self._done = False

    def tick(self) -> None:
        if not self.target or self._done:
            return
        if not self._active:
            self._cm = trace(self.target)
            if self._cm.__enter__() is None:  # profiler unavailable
                self._cm.__exit__(None, None, None)
                self._done = True
                return
            self._active = True
        self._ticks += 1
        if self._ticks >= self.limit:
            self.close()

    def close(self) -> None:
        if self._active:
            self._cm.__exit__(None, None, None)
            self._active = False
        self._done = True
