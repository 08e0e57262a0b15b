"""`serve_disagg()`: disaggregated prefill/decode serving, one call.

Runs a `PagedDecodeServer` locally and ships every request's prefill
to a prefill worker (`disagg/prefill_worker.py`) over the transport
seam; finished KV blocks stream back through `disagg/ingest.py`
straight into the paged pool. Greedy outputs are token-identical to
monolithic `serve_paged` (the worker's default prefill schedule is
bit-compatible — prefill_worker.py's parity contract), and with
`prefix_cache=True` the ingested blocks register in the radix cache,
so requests prefilled on ANOTHER HOST seed local prefix sharing.

Session lifecycle (ordering matters — each step unblocks the next):

    1. bind the result receiver (ephemeral port)
    2. start the ingest drain thread (it owns the blocking accept)
    3. spawn the worker (it binds and announces its dispatch port)
    4. dispatch hello/decoder/params + every request
    5. decode loop: pump ingest -> admit -> tick
    6. worker death mid-stream: drop peer, respawn, re-dispatch the
       undelivered tail (bounded by `worker_retries`)

Default worker placement is an in-process thread — the loopback proof
and the single-host split. Pass `spawn_worker` to place it anywhere
else (another process/host): it must return (host, port) of a
listening `serve_prefill`.
"""

from __future__ import annotations

import queue as queue_mod
import threading
import time
from typing import Any

import jax

from defer_tpu.disagg import wire
from defer_tpu.disagg.ingest import IngestError, KVBlockIngest
from defer_tpu.disagg.prefill_worker import serve_prefill
from defer_tpu.obs.serving import DisaggMetrics, ServerStats
from defer_tpu.runtime.paged import PagedDecodeServer
from defer_tpu.runtime.transport import ArrayReceiver, ArraySender, TransportError
from defer_tpu.utils.logging import get_logger

log = get_logger(__name__)


def _thread_worker_spawner(**serve_kwargs):
    """Default spawn_worker: serve_prefill on an in-process daemon
    thread, ephemeral port. Returns ("127.0.0.1", port) once the
    worker is listening."""

    def spawn() -> tuple[str, int]:
        ports: "queue_mod.Queue[int]" = queue_mod.Queue()
        t = threading.Thread(
            target=serve_prefill,
            kwargs={
                "listen_port": 0,
                "announce": ports.put,
                **serve_kwargs,
            },
            name="prefill-worker",
            daemon=True,
        )
        t.start()
        return "127.0.0.1", ports.get(timeout=30.0)

    return spawn


class _Session:
    """One worker session: the dispatch sender plus what was sent."""

    def __init__(
        self,
        host: str,
        port: int,
        *,
        result_port: int,
        dec,
        params,
        block_size: int,
        chunk_len: int | None,
        compress: bool,
        level: int,
        quantize: str | None,
        connect_timeout_s: float,
    ):
        self.sender = ArraySender(
            host,
            port,
            compress=compress,
            level=level,
            quantize=quantize,
            connect_timeout_s=connect_timeout_s,
        )
        self.dispatch_bytes = wire.send_hello(
            self.sender,
            result_host="127.0.0.1",
            result_port=result_port,
            block_size=block_size,
            chunk_len=chunk_len,
        )
        self.dispatch_bytes += wire.send_blob(
            self.sender,
            {"kind": "decoder", "version": wire.WIRE_VERSION,
             **wire.decoder_to_wire(dec)},
        )
        self.dispatch_bytes += wire.send_params(self.sender, params)

    def send_request(self, rid: int, prompt) -> None:
        self.dispatch_bytes += wire.send_prefill_request(
            self.sender, rid, prompt
        )

    def close(self) -> None:
        self.sender.close()


def serve_disagg(
    dec: Any,
    params: dict,
    requests: list[tuple[jax.Array, int]],
    *,
    num_blocks: int,
    block_size: int = 16,
    max_batch: int = 4,
    eos_id: int | None = None,
    prefix_cache: bool = False,
    attention: str = "gathered",
    kv_dtype: str = "fp",
    decode_window: int = 1,
    spec_k: int = 0,
    spec_draft: Any = None,
    spec_params: dict | None = None,
    sampling: list | None = None,
    stop: list | None = None,
    quantize: str | None = None,
    compress: bool = True,
    level: int = 3,
    chunk_len: int | None = None,
    worker_retries: int = 1,
    spawn_worker: Any = None,
    server: PagedDecodeServer | None = None,
    accept_timeout_s: float = 60.0,
    read_timeout_s: float | None = 60.0,
    connect_timeout_s: float = 30.0,
    constraints: dict | None = None,
) -> tuple[list[jax.Array], dict]:
    """Disaggregated serving; same contract as `serve_paged` (outputs
    in submission order + ServerStats) with the prefill phase running
    on a worker. `quantize="int8"` turns on lossy KV transfer (codec
    SCHEME_Q8; the logits row stays lossless either way — a lossy row
    would fork the first token). `server=` reuses an existing
    PagedDecodeServer so ingested prefix blocks survive into later
    local serving (cross-host prefix warm-up). `worker_retries` bounds
    mid-stream worker replacements before giving up.

    `kv_dtype="int8"` stores the decode pool quantized: `deliver_kv`'s
    jitted scatter requantizes the decoded wire blocks on landing, so
    a Q8 transfer (`quantize="int8"`) feeding an int8 pool never holds
    a widened copy beyond the ingest staging buffer — the wire format
    itself is unchanged.

    `spec_k>0` (with `spec_draft`/`spec_params`) speculates on the
    decode side: the worker ships TARGET K/V only, and each prefilled
    admission re-prefills the draft lane locally from the prompt ids
    (PagedDecodeServer._admit_prefilled) — the draft's prefill is the
    cheap side of the compute asymmetry the disagg split exists for,
    so recompute beats shipping a second KV stream. Greedy outputs
    stay token-identical to the non-speculative split.

    `constraints={name: TokenDFA}` registers compiled grammars on the
    DECODE side (defer_tpu/constrain/; per-request opt-in via
    `SamplingParams(constraint="name")`) — prefill ships plain K/V, so
    the worker needs no DFA tables."""
    srv = server
    if srv is None:
        srv = PagedDecodeServer(
            dec,
            params,
            num_blocks=num_blocks,
            block_size=block_size,
            max_batch=max_batch,
            eos_id=eos_id,
            prefix_cache=prefix_cache,
            attention=attention,
            kv_dtype=kv_dtype,
            decode_window=decode_window,
            spec_k=spec_k,
            spec_draft=spec_draft,
            spec_params=spec_params,
            constraints=constraints,
        )
    samps = sampling or [None] * len(requests)
    stops = stop or [None] * len(requests)
    if len(samps) != len(requests) or len(stops) != len(requests):
        raise ValueError(
            "sampling/stop must have one entry per request when given"
        )
    obs = DisaggMetrics("decode")
    recv = ArrayReceiver(
        0,
        host="127.0.0.1",
        accept_timeout_s=accept_timeout_s,
        read_timeout_s=read_timeout_s,
    )
    if spawn_worker is None:
        spawn_worker = _thread_worker_spawner(
            read_timeout_s=read_timeout_s,
            connect_timeout_s=connect_timeout_s,
        )
    ingest = KVBlockIngest(srv, recv, obs=obs)
    session: _Session | None = None
    restarts = 0
    dispatch_bytes_total = 0

    def open_session() -> _Session:
        host, port = spawn_worker()
        return _Session(
            host,
            port,
            result_port=recv.port,
            dec=srv.dec,
            params=srv.params,
            block_size=srv.bs,
            chunk_len=chunk_len,
            compress=compress,
            level=level,
            quantize=quantize,
            connect_timeout_s=connect_timeout_s,
        )

    try:
        # Drain thread first: it owns the blocking accept the worker's
        # result connection lands on (module docstring, step 2).
        ingest.start()
        session = open_session()
        rids = [
            srv.submit_prefilled(p, s, sampling=sp, stop=st)
            for (p, s), sp, st in zip(requests, samps, stops)
        ]
        for rid, (p, _) in zip(rids, requests):
            session.send_request(rid, p)

        while srv.pending_prefilled or srv.pending or any(srv.slots):
            if ingest.failed.is_set():
                err = ingest.error
                if isinstance(err, IngestError):
                    # Validation failure = protocol/config skew; a
                    # fresh worker would ship the same bad payload.
                    raise err
                if restarts >= worker_retries:
                    raise TransportError(
                        f"prefill worker died and {restarts} "
                        f"restart(s) were already spent: {err}"
                    )
                restarts += 1
                obs.worker_restarts.inc()
                log.warning(
                    "prefill worker session died (%s); restarting "
                    "(%d/%d)",
                    err,
                    restarts,
                    worker_retries,
                )
                # Deliver everything the dead session DID land before
                # computing the re-request set (the drain thread is
                # parked, so the queue is quiescent): a payload parked
                # but not yet pumped is delivered work, and
                # re-requesting it would hand the drain thread a
                # duplicate for an already-admitted rid — a fatal
                # validation error.
                ingest.pump()
                missing = ingest.undelivered()
                dispatch_bytes_total += session.dispatch_bytes
                session.close()
                # Drop the dead result peer BEFORE resuming the drain
                # thread, so its fresh accept can only land the NEW
                # worker's connection.
                recv.next_peer()
                ingest.resume()
                session = open_session()
                by_rid = dict(zip(rids, requests))
                for rid in missing:
                    session.send_request(rid, by_rid[rid][0])
            ingest.pump()
            srv._admit()
            if any(s is not None for s in srv.slots):
                srv._tick()
            else:
                # Nothing seated: we're waiting on the wire, not the
                # device — yield instead of spinning admit hot.
                time.sleep(1e-3)
        srv.settle()
        done = srv.done
    finally:
        if session is not None:
            dispatch_bytes_total += session.dispatch_bytes
            session.close()
        ingest.close()
        recv.close()

    n_req = max(len(requests), 1)
    stats = ServerStats.snapshot(
        srv.obs.registry,
        ticks=srv.ticks,
        attention=srv.attention,
        peak_blocks=srv.blocks_peak,
        pool_blocks=srv.num_blocks - 1,
        block_size=srv.bs,
        decode_window=srv.decode_window,
        host_dispatches=srv.dispatches,
        tokens_per_dispatch=(
            srv.window_tokens / srv.dispatches if srv.dispatches else 0.0
        ),
        cached_blocks=(
            srv.radix.cached_blocks if srv.radix is not None else 0
        ),
        prefill_tokens_saved=srv.prefill_tokens_saved,
        prefill_budget=srv.prefill_budget,
        prefill_stall_ticks=srv.prefill_stall_ticks_n,
        mixed_ticks=srv.mixed_ticks_n,
        mixed_prefill_tokens=srv.mixed_prefill_tokens_n,
        decode_stall_fraction=srv.decode_stall_fraction_last,
        kv_dtype=srv.kv_dtype,
        pool_bytes=srv.pool_bytes,
        spec_k=srv.spec_k,
        spec_rounds=srv.spec_rounds_n,
        spec_proposed=srv.spec_proposed_n,
        spec_accepted=srv.spec_accepted_n,
        spec_acceptance=(
            srv.spec_accepted_n / srv.spec_proposed_n
            if srv.spec_proposed_n
            else 0.0
        ),
        spec_draft_tokens=srv.spec_draft_tokens_n,
        disagg=True,
        quantize=quantize,
        kv_bytes_recv=recv.rx_frame_bytes,
        kv_bytes_recv_per_request=recv.rx_frame_bytes / n_req,
        dispatch_bytes_sent=dispatch_bytes_total,
        worker_restarts=restarts,
        constrained_tokens=srv.constrained_tokens_n,
        constraint_dead_ends=srv.constraint_dead_ends_n,
    )
    return [done[r] for r in rids], stats
