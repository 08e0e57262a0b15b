"""Device synchronization for the streaming loops.

`hard_sync` is the completion barrier: `jax.block_until_ready` on every
leaf. It compiles nothing and gathers nothing, whatever the sharding of
its argument.

Design consequence for hot loops (see Pipeline.stream): never wait
per-item; sync once per window on one array, and retire the whole
prefix — device program order guarantees everything enqueued before the
synced item has also completed.
"""

from __future__ import annotations

import collections
import threading
from typing import Any, Callable

import jax


def hard_sync(*arrays: Any) -> None:
    """Block until every given value's computation has completed.
    Accepts pytrees — multi-tensor pipeline boundaries pass activation
    tuples."""
    # analysis: ignore[host-sync-in-hot-loop] this IS the sanctioned
    # barrier primitive — hot paths amortize it through Retirer
    # windows (one barrier per window)
    jax.block_until_ready(arrays)


# One in-flight wait per array: a timed-out hard_sync_timeout leaves its
# helper thread blocked until the array completes; a retry on the same
# array must join that wait, not spawn another thread.
_inflight_lock = threading.Lock()
_inflight: dict[int, threading.Event] = {}


def hard_sync_timeout(arr: jax.Array, timeout_s: float) -> bool:
    """hard_sync with a deadline (the wait runs in a helper thread).
    Returns False on timeout — the caller decides how to fail. An
    error raised by the wait (e.g. an XLA runtime failure) is
    re-raised here, not swallowed. Used by the streaming drain so a
    stuck stage trips the watchdog instead of hanging the host forever
    (the reference hangs, see reference src/node.py:102-103)."""
    key = id(arr)
    with _inflight_lock:
        done = _inflight.get(key)
        if done is None:
            done = threading.Event()
            done.error = None  # type: ignore[attr-defined]
            _inflight[key] = done

            def wait() -> None:
                try:
                    hard_sync(arr)
                except BaseException as e:  # noqa: BLE001 — relayed below
                    done.error = e  # type: ignore[attr-defined]
                finally:
                    with _inflight_lock:
                        _inflight.pop(key, None)
                    done.set()

            threading.Thread(target=wait, daemon=True).start()
    finished = done.wait(timeout_s)
    err = getattr(done, "error", None)
    if finished and err is not None:
        raise err
    return finished


class Retirer:
    """Windowed retire of async results, in order.

    The one implementation of the batched-barrier pattern every hot loop
    here uses (Pipeline.stream, DEFER.run_defer, run_local_inference):
    emit the known-ready prefix for free; under depth pressure take ONE
    barrier on the middle of the window and retire the whole prefix —
    device program order guarantees everything enqueued before the
    synced item has completed (see module docstring). Never wait
    per-item.

    `sync` is the barrier (default `hard_sync`); a caller may supply a
    timeout-aware one (DEFER's watchdog barrier). It must not mutate the
    queue — retirement is identity-based on the synced item, so a
    barrier that covers more (or fewer) items than the caller guessed
    still retires exactly the completed prefix.
    """

    def __init__(
        self,
        depth: int,
        sync: Callable[[Any], None] = hard_sync,
    ):
        self.depth = depth
        self.sync = sync
        self.pending: collections.deque[Any] = collections.deque()
        # Completed results rescued when a barrier raised mid-add —
        # returned by the next collect() instead of being lost.
        self._spill: list[Any] = []

    def __len__(self) -> int:
        return len(self.pending)

    def ready_count(self) -> int:
        """Length of the known-completed prefix (including any
        barrier-failure spill)."""
        n = len(self._spill)
        for item in self.pending:
            if not item.is_ready():
                break
            n += 1
        return n

    def _pop_through(self, target: Any) -> list[Any]:
        out = []
        while self.pending:
            done = self.pending[0] is target
            out.append(self.pending.popleft())
            if done:
                break
        return out

    def add(self, item: Any) -> list[Any]:
        """Enqueue one async result; returns items retired by pressure
        (ready prefix plus, at depth, one batched-barrier prefix)."""
        self.pending.append(item)
        out = self.collect()
        if len(self.pending) >= self.depth:
            target = self.pending[len(self.pending) // 2]
            try:
                self.sync(target)
            except BaseException:
                # The already-collected prefix is COMPLETED work; park
                # it so a recovering caller's next collect() emits it
                # rather than losing it with the raise.
                self._spill = out + self._spill
                raise
            out.extend(self._pop_through(target))
        return out

    def collect(self) -> list[Any]:
        """Retire the known-ready prefix (plus any barrier-failure
        spill) without blocking."""
        out = self._spill
        self._spill = []
        while self.pending and self.pending[0].is_ready():
            out.append(self.pending.popleft())
        return out

    def flush(self) -> list[Any]:
        """Barrier on the newest item and retire everything."""
        if self.pending:
            self.sync(self.pending[-1])
        out = self._spill + list(self.pending)
        self._spill = []
        self.pending.clear()
        return out

    def discard(self) -> int:
        """Drop every pending item WITHOUT syncing; returns the count.

        For failure recovery: in-flight results of a dead pipeline can
        neither complete nor be waited on — the caller re-dispatches
        and accepts the loss (the reference loses the same microbatches
        by hanging forever, reference src/node.py:102-103)."""
        n = len(self.pending)
        self.pending.clear()
        return n
