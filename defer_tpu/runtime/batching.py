"""Dynamic batching for the streaming serve path.

The reference streams batch-1 frames end to end (one image per queue
item, reference src/test.py:52-54) — fine for CPUs, ruinous on a TPU:
a batch-1 ResNet50 leaves most of the MXU idle. This adapter coalesces
adjacent queue items into one device batch under a latency SLO, and
splits the batched output back into per-item results, so the
reference's item-in/item-out queue contract survives while the MXU
sees real batches.

Enable via DeferConfig(dynamic_batch_size=N, batch_wait_s=SLO):
`DEFER.run_defer` then gathers up to N items per dispatch, waiting at
most `batch_wait_s` after the first item of a batch arrives.
"""

from __future__ import annotations

import queue as queue_mod
import time
from typing import Any

import jax.numpy as jnp
import numpy as np

from defer_tpu.obs.metrics import get_registry
from defer_tpu.runtime.host_io import STOP

# Leading-dim buckets 1..1024: one histogram bucket per pow2 compile
# bucket, so occupancy reads directly against the compile-cache story.
_ROW_BUCKETS = tuple(float(1 << i) for i in range(11))


class Deadline:
    """Monotonic SLO deadline: one start-time capture plus
    remaining-budget arithmetic, shared by every wait loop that blocks
    "at most X seconds after the first event" (the batch gatherer's
    flush SLO here, the fleet admission queues in
    fleet/admission.py). Centralizing it keeps the `time.monotonic`
    bookkeeping in one place — a wait loop that recomputes its own
    deadline from `time.time` or re-anchors per iteration silently
    stretches the SLO."""

    __slots__ = ("t0", "at")

    def __init__(self, budget_s: float):
        self.t0 = time.monotonic()
        self.at = self.t0 + budget_s

    def remaining(self) -> float:
        """Seconds of budget left (negative once expired)."""
        return self.at - time.monotonic()

    def expired(self) -> bool:
        return self.remaining() <= 0

    def elapsed(self) -> float:
        """Seconds since the deadline was armed."""
        return time.monotonic() - self.t0


class BatchGatherer:
    """Coalesce queue items (arrays with a leading batch dim) into one
    stacked batch per dispatch.

    Items with mismatched trailing shapes or dtypes are never mixed: a
    mismatch flushes the current batch and the odd item starts the
    next one (carried between calls).
    """

    def __init__(
        self, batch_size: int, max_wait_s: float, *, pad_to_buckets: bool = True
    ):
        if batch_size < 2:
            raise ValueError("dynamic batching needs batch_size >= 2")
        self.batch_size = batch_size
        self.max_wait_s = max_wait_s
        # Pad partial batches up to the next power-of-two bucket
        # (<= batch_size): every distinct leading dim is a fresh XLA
        # compile of the whole stage chain, so unbucketed bursty
        # traffic (256, 113, 41, 7, ...) would turn the ms-level SLO
        # into multi-second compile stalls. Buckets bound the compile
        # cache to log2(batch_size) shapes; split_output drops the pad
        # rows by construction (sizes sum to the real total).
        self.pad_to_buckets = pad_to_buckets
        self._carry: Any = None
        # Metric handles resolved once (obs/metrics.py); gather() then
        # pays one histogram observe + counter inc per FLUSH, nothing
        # per item.
        reg = get_registry()
        self._obs_rows = reg.histogram(
            "defer_batch_rows",
            "Device-batch occupancy (rows) per dispatch",
            _ROW_BUCKETS,
        )
        self._obs_wait = reg.histogram(
            "defer_batch_wait_seconds",
            "First item to flush (bounded by the batch_wait_s SLO)",
        )
        self._obs_flush = {
            reason: reg.counter(
                "defer_batch_flush_total",
                "Batches flushed, by why gathering stopped",
                {"reason": reason},
            )
            for reason in ("full", "timeout", "eos", "mismatch")
        }

    @staticmethod
    def _compatible(a: Any, b: Any) -> bool:
        return (
            getattr(a, "ndim", 0) >= 1
            and getattr(b, "ndim", 0) >= 1
            and a.shape[1:] == b.shape[1:]
            and a.dtype == b.dtype
        )

    def gather(
        self, input_stream: "queue_mod.Queue[Any]", poll_s: float = 0.05
    ) -> tuple[Any, list[int] | None, bool]:
        """Pull one batch. Returns (batch, sizes, eos):

        * batch: stacked array (or None if only the sentinel / nothing
          arrived); sizes: per-item leading-dim sizes for the splitter.
        * eos: the STOP/None sentinel was consumed.

        Blocks at most `poll_s` for the FIRST item (so the caller's
        idle loop keeps servicing results), then at most `max_wait_s`
        total for the rest of the batch.
        """
        items: list[Any] = []
        if self._carry is not None:
            items.append(self._carry)
            self._carry = None
        eos = False
        if not items:
            try:
                first = input_stream.get(timeout=poll_s)
            except queue_mod.Empty:
                return None, None, False
            if first is None or first is STOP:
                return None, None, True
            items.append(first)
        if getattr(items[0], "ndim", 0) < 1:
            raise ValueError(
                "dynamic batching requires queue items with a leading "
                f"batch dim; got shape {getattr(items[0], 'shape', ())} — "
                "disable dynamic_batch_size or add a batch axis"
            )
        # batch_size bounds ROWS (the device batch), not item count —
        # multi-row items fill it proportionally faster. An item that
        # would overflow the bound is carried to the next batch, so
        # the device batch never exceeds batch_size (unless a single
        # item is itself larger — items are atomic).
        total = int(items[0].shape[0])
        dl = Deadline(self.max_wait_s)
        reason = "full"  # loop exits via its condition when filled
        while total < self.batch_size:
            remaining = dl.remaining()
            if remaining <= 0:
                reason = "timeout"
                break
            try:
                nxt = input_stream.get(timeout=remaining)
            except queue_mod.Empty:
                reason = "timeout"
                break
            if nxt is None or nxt is STOP:
                eos = True
                reason = "eos"
                break
            if (
                not self._compatible(items[0], nxt)
                or total + int(nxt.shape[0]) > self.batch_size
            ):
                # Flush what we have; the odd item opens the next batch.
                self._carry = nxt
                reason = "mismatch"
                break
            items.append(nxt)
            total += int(nxt.shape[0])
        self._obs_rows.observe(float(total))
        self._obs_wait.observe(dl.elapsed())
        self._obs_flush[reason].inc()
        sizes = [int(x.shape[0]) for x in items]
        pad = 0
        if self.pad_to_buckets and total < self.batch_size:
            bucket = 1
            while bucket < total:
                bucket *= 2
            pad = min(bucket, self.batch_size) - total
        if pad:
            items.append(
                jnp.zeros((pad, *items[0].shape[1:]), items[0].dtype)
            )
        batch = (
            items[0]
            if len(items) == 1
            else jnp.concatenate(items, axis=0)
        )
        return batch, sizes, eos

    def pending(self) -> bool:
        return self._carry is not None


class TimedQueue:
    """Thread-safe FIFO that times each item from put() to pop() into a
    caller-supplied histogram — how long produced work sat waiting for
    its consumer. The disagg ingest path uses this to surface
    `defer_kv_ingest_wait_seconds` (disagg/ingest.py): prefill blocks
    landing faster than decode admits them shows up here as a growing
    wait, the early-warning signal for a prefill/decode capacity
    imbalance."""

    def __init__(self, histogram=None, maxsize: int = 0):
        self._q: "queue_mod.Queue[tuple[float, Any]]" = queue_mod.Queue(
            maxsize
        )
        self._hist = histogram

    def put(self, item: Any) -> None:
        self._q.put((time.monotonic(), item))

    def pop(self, timeout: float | None = None) -> Any:
        """Blocking get; raises queue.Empty on timeout like Queue.get."""
        t_in, item = self._q.get(timeout=timeout)
        if self._hist is not None:
            self._hist.observe(time.monotonic() - t_in)
        return item

    def qsize(self) -> int:
        return self._q.qsize()


def window_drain_order(valid_lens, width: int):
    """Tick-major iteration order for draining a fused-decode window
    buffer ([B, K] tokens plus per-slot valid lengths): yields (t, i)
    for every accepted token, sub-step first and slot second, so
    streaming callbacks fire in exactly the interleaving a
    decode_window=1 loop produces (all slots' token t before any
    slot's token t+1). Shared by both decode servers' window drains
    (runtime/decode_server.py / runtime/paged.py)."""
    for t in range(width):
        for i, n in enumerate(valid_lens):
            if t < n:
                yield t, i


def accept_lengths(props, preds):
    """Greedy speculative accept test, batched (the Leviathan/Chen
    rule at temperature 0): per row, the accepted length is the index
    of the FIRST draft token that disagrees with the target's argmax
    at the same position — or k when the whole proposal matches.
    `props` [B, k] draft proposals; `preds` [B, k] target argmax at
    the k proposal positions (verify-forward rows 0..k-1: row j is
    the target's choice GIVEN props[:j] accepted). Host-side numpy on
    already-fetched values — the single batched accept-test sync both
    speculative drivers (models/speculative.py solo loop,
    runtime/paged.py `spec_k`) share, so their accept semantics can
    never drift. Returns [B] int64."""
    # analysis: ignore[host-sync-in-hot-loop] no-op on the host numpy
    # both callers pass (their round's ONE batched transfer happens —
    # and is justified — at the fetch site)
    props = np.asarray(props)
    # analysis: ignore[host-sync-in-hot-loop] same: already host-side
    preds = np.asarray(preds)
    if props.shape != preds.shape or props.ndim != 2:
        raise ValueError(
            f"props/preds must be matching [B, k], got "
            f"{props.shape}/{preds.shape}"
        )
    mismatch = props != preds
    # argmax of an all-False row is 0; the any() mask routes those
    # (full-accept) rows to k.
    first_bad = mismatch.argmax(axis=1)
    return np.where(mismatch.any(axis=1), first_bad, props.shape[1])


def microbatch_groups(max_batch: int, num_groups: int) -> list[list[int]]:
    """Partition the slot indices [0, max_batch) into `num_groups`
    contiguous microbatch groups for pipelined decode
    (runtime/paged.py pp_stages=). Groups must tile the batch evenly:
    every group's state rides the same compiled stage programs, so a
    ragged tail group would double the traced shape set per stage."""
    if num_groups < 1:
        raise ValueError(f"num_groups must be >= 1, got {num_groups}")
    if max_batch % num_groups:
        raise ValueError(
            f"max_batch {max_batch} must divide evenly into "
            f"{num_groups} microbatch groups — pick max_batch a "
            f"multiple of the in-flight count (pp_inflight)"
        )
    g = max_batch // num_groups
    return [
        list(range(k * g, (k + 1) * g)) for k in range(num_groups)
    ]


def pp_schedule_occupancy(
    busy_slots: list[int], total_slots: int
) -> tuple[list[float], float]:
    """Per-stage occupancy and bubble fraction of one realized
    pipelined-decode window, from dispatch-slot accounting:
    `busy_slots[s]` = stage-step dispatches stage s actually issued,
    `total_slots` = schedule slots spanned from the first stage-0
    dispatch to the last final-stage dispatch. In the full GPipe
    schedule (M groups x W rounds, no early freezes) this recovers
    the closed-form bubble (S-1)/(S-1+M*W); groups that freeze or
    drain mid-window lower the measured occupancy below it. Schedule
    slots are logical dispatch positions, so the numbers are
    placement- and hardware-independent (the wall-clock win is the
    sweep's separate tokens/sec column)."""
    if total_slots <= 0:
        return [0.0] * len(busy_slots), 0.0
    occ = [min(b / total_slots, 1.0) for b in busy_slots]
    mean = sum(occ) / len(occ) if occ else 0.0
    return occ, 1.0 - mean


def split_output(out: Any, sizes: list[int]) -> list[Any]:
    """Invert the gather: slice the batched output back into per-item
    results (device-side slices; no host transfer). Pad rows beyond
    sum(sizes) — bucket padding — are dropped by construction."""
    if len(sizes) == 1:
        # Only skip the slice when there was no padding: a padded
        # single-item batch must not leak its garbage pad rows.
        if getattr(out, "ndim", 0) >= 1 and out.shape[0] == sizes[0]:
            return [out]
        return [out[: sizes[0]]]
    parts = []
    off = 0
    for s in sizes:
        parts.append(out[off : off + s])
        off += s
    return parts
