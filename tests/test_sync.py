"""Retirer / hard_sync_timeout unit tests (no device dependencies —
fake futures exercise the windowed-retire logic directly)."""

import threading
import time

import pytest

from defer_tpu.utils.sync import Retirer, hard_sync_timeout


class FakeFuture:
    def __init__(self, ready=False):
        self._ready = ready

    def is_ready(self):
        return self._ready


def test_retirer_emits_ready_prefix_in_order():
    done = [FakeFuture(True), FakeFuture(True), FakeFuture(False)]
    r = Retirer(depth=10, sync=lambda a: None)
    out = []
    for f in done:
        out.extend(r.add(f))
    assert out == done[:2]
    assert list(r.pending) == [done[2]]


def test_retirer_pressure_retires_through_synced_item():
    synced = []
    r = Retirer(depth=4, sync=synced.append)
    futs = [FakeFuture(False) for _ in range(4)]
    out = []
    for f in futs:
        out.extend(r.add(f))
    # At depth, one barrier on the middle of the window retires the
    # prefix through the synced item — no index math on a mutated queue.
    assert synced == [futs[2]]
    assert out == futs[:3]
    assert list(r.pending) == [futs[3]]


def test_retirer_survives_sync_that_marks_items_ready():
    # The regression from the review: a sync callback that causes items
    # to become ready (as the watchdog barrier does while waiting) must
    # not over-retire or raise.
    r = Retirer(depth=2, sync=lambda a: None)
    a, b = FakeFuture(False), FakeFuture(False)

    def sync(target):
        a._ready = b._ready = True

    r.sync = sync
    out = r.add(a)
    out += r.add(b)
    assert out == [a, b]
    assert not r.pending


def test_retirer_flush_returns_everything():
    r = Retirer(depth=100, sync=lambda a: None)
    futs = [FakeFuture(False) for _ in range(5)]
    for f in futs:
        r.add(f)
    assert r.flush() == futs
    assert r.flush() == []


def test_hard_sync_timeout_dedups_inflight_waits():
    # A slow array: repeated timed-out calls must share one helper
    # thread, and the wait must resolve once the array completes.
    release = threading.Event()

    class SlowArray:
        def block_until_ready(self):
            release.wait(5)
            return self

    arr = SlowArray()
    n0 = threading.active_count()
    assert hard_sync_timeout(arr, 0.05) is False
    assert hard_sync_timeout(arr, 0.05) is False
    assert hard_sync_timeout(arr, 0.05) is False
    # One helper thread, not three.
    assert threading.active_count() <= n0 + 1
    release.set()
    assert hard_sync_timeout(arr, 5.0) is True


def test_hard_sync_timeout_propagates_wait_errors():
    class BrokenArray:
        def block_until_ready(self):
            raise RuntimeError("xla runtime failure")

    with pytest.raises(RuntimeError, match="xla runtime failure"):
        hard_sync_timeout(BrokenArray(), 5.0)
        # The helper thread may need a beat to surface the error.
        time.sleep(0.1)
        hard_sync_timeout(BrokenArray(), 5.0)
