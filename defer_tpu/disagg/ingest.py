"""Decode-side KV ingest: receive payloads, seat them in the pool.

`KVBlockIngest` owns the result stream from a prefill worker
(`disagg/prefill_worker.py`) and splits the work across two threads by
MUTATION DOMAIN, not by convenience:

  * the DRAIN thread does transport work only — it iterates
    `wire.iter_kv_payloads`, validates each payload against the decode
    server's geometry, and parks it in a `batching.TimedQueue`. It
    never touches the pool.
  * the SERVING thread (whoever runs the decode loop) calls
    `pump()` between ticks: pop parked payloads — timing their queue
    wait into `defer_kv_ingest_wait_seconds` — and hand each to
    `PagedDecodeServer.deliver_kv`. Every pool/block-table mutation
    therefore stays on the serving thread, the same single-writer
    discipline the server's own admission path relies on.

The same split carries the quantized pool (runtime/paged.py
`kv_dtype="int8"`) for free: payloads stay in the wire's compute
dtype all the way to `deliver_kv`, and the requantize happens inside
`_admit`'s jitted scatter on the serving thread — the drain thread
never needs to know the pool dtype. The host-RAM spill tier
(`runtime/paged.py::HostKVSpill`) runs this exact mutation-domain
split in the other direction: its drain thread does device->host
copies only, while pool revival stays on the serving thread.

Speculative decode (`spec_k>0` on the decode server) rides the same
path untouched: the wire carries TARGET K/V only, and the serving
thread's `_admit_prefilled` seeds the DRAFT lane by re-prefilling it
locally from the prompt ids after the delivered blocks seat — the
ingest layer never sees draft state.

Failure protocol (the retry seam `disagg/api.py` drives): a transport
death flips `failed` and parks the drain thread; the orchestrator
drops the dead peer (`receiver.next_peer()`), respawns a worker,
re-dispatches whatever is still undelivered, then `resume()`s the
drain thread onto the fresh connection. Payload delivery is atomic
(wire.py), so "undelivered" is exactly the set to re-request — no
double-seating, no holes.
"""

from __future__ import annotations

import queue as queue_mod
import threading
from typing import Any

from defer_tpu.disagg import wire
from defer_tpu.obs.serving import DisaggMetrics
from defer_tpu.runtime.batching import TimedQueue
from defer_tpu.runtime.transport import ArrayReceiver, TransportError
from defer_tpu.utils.logging import get_logger

log = get_logger(__name__)


class IngestError(RuntimeError):
    """A payload failed validation — a protocol/config skew, not a
    transient transport fault; retrying the worker won't fix it."""


class KVBlockIngest:
    """Drain one worker result stream into a PagedDecodeServer."""

    def __init__(
        self,
        server: Any,
        receiver: ArrayReceiver,
        *,
        obs: DisaggMetrics | None = None,
    ):
        self.server = server
        self.receiver = receiver
        self.obs = obs if obs is not None else DisaggMetrics("decode")
        self._queue = TimedQueue(self.obs.ingest_wait)
        self.delivered: set[int] = set()
        self.failed = threading.Event()
        self.error: BaseException | None = None
        self.eof = threading.Event()
        self._resume = threading.Event()
        self._closed = False
        self._thread: threading.Thread | None = None

    # -- drain thread -----------------------------------------------------

    def start(self) -> None:
        """Start the drain thread. Must run BEFORE the worker is
        dispatched: the thread performs the blocking accept the
        worker's result connection lands on."""
        self._thread = threading.Thread(
            target=self._drain_loop, name="kv-ingest", daemon=True
        )
        self._thread.start()

    # analysis: domain(drain) owns the blocking receive; payloads park in _queue for the serving thread to pump
    def _drain_loop(self) -> None:
        while not self._closed:
            try:
                for payload in wire.iter_kv_payloads(
                    self.receiver, obs=self.obs
                ):
                    self._validate(payload)
                    self._queue.put(payload)
                self.eof.set()
                return
            except TransportError as e:
                # analysis: ignore[cross-domain-write] error/failed are an Event-mediated handoff: write error THEN set failed; readers check failed first
                self.error = e
                self.failed.set()
            except Exception as e:  # noqa: BLE001 — surfaced to the
                # orchestrator; a validation/shape error must not die
                # silently on a daemon thread
                # analysis: ignore[cross-domain-write] same Event-mediated handoff as the TransportError arm
                self.error = e
                self.failed.set()
                return
            # Transport fault: park until the orchestrator has rewired
            # the session (next_peer + respawned worker), then drain
            # the fresh connection.
            self._resume.wait()
            self._resume.clear()

    def _validate(self, payload: wire.KVPayload) -> None:
        srv = self.server
        cfg = srv.dec.cfg
        if payload.rid not in srv.pending_prefilled:
            raise IngestError(
                f"payload for unknown/already-admitted rid {payload.rid}"
            )
        t0 = srv.pending_prefilled[payload.rid]["prompt"].shape[1]
        if payload.t0 != t0:
            raise IngestError(
                f"payload t0 {payload.t0} != submitted prompt length "
                f"{t0} for rid {payload.rid}"
            )
        expect = (
            cfg.num_layers,
            -(-t0 // srv.bs),
            cfg.kv_heads,
            srv.bs,
            cfg.dh,
        )
        if tuple(payload.k.shape) != expect:
            raise IngestError(
                f"payload K shape {tuple(payload.k.shape)} != "
                f"{expect} — worker and server disagree on model "
                f"geometry or block_size"
            )

    # -- serving thread ---------------------------------------------------

    # analysis: domain(serving) the pop half of the park/pump handoff
    def pump(self) -> int:
        """Pop every parked payload and deliver it to the server
        (serving-thread-only, see module docstring). Returns payloads
        delivered. Raises the drain thread's error if it was fatal
        (IngestError); transport faults are left for the orchestrator
        to read via `failed`."""
        n = 0
        while True:
            try:
                payload = self._queue.pop(timeout=0)
            except queue_mod.Empty:
                break
            self.server.deliver_kv(
                payload.rid, payload.k, payload.v, payload.logits
            )
            self.delivered.add(payload.rid)
            n += 1
        if self.failed.is_set() and isinstance(self.error, IngestError):
            raise self.error
        return n

    def undelivered(self) -> list[int]:
        """Rids submitted as prefilled but not yet handed to the
        server — the set a retry must re-request. Call after pump():
        a payload parked in the queue is not yet delivered."""
        return [
            rid
            for rid in self.server._prefilled_order
            if rid not in self.delivered
        ]

    # analysis: domain(serving) orchestrator-side rewire path
    def resume(self) -> None:
        """Un-park the drain thread onto a rewired connection."""
        # analysis: ignore[cross-domain-write] the reverse leg of the Event handoff: drain is parked on _resume, so it cannot race this clear
        self.error = None
        self.failed.clear()
        self._resume.set()

    def close(self) -> None:
        self._closed = True
        self._resume.set()
