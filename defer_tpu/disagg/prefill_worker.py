"""Prefill worker: the compute-bound half of disaggregated serving.

Runs as its own process (CLI below) or an in-process thread (tests,
single-host splits): receives a decoder architecture + weights over
the dispatch stream, then for each prefill request runs (optionally
chunked) prefill and streams the finished KV blocks + first-token
logits back to the decode host's ingest (`disagg/ingest.py`), which
seats them directly in the paged pool. The session/stream shapes
mirror `runtime/remote_stage.py` (same listen-then-connect-back
contract); the payload format is `disagg/wire.py`.

Parity contract: with `chunk_len=None` the worker prefills each prompt
in ONE pow2-padded step — the exact shape schedule the monolithic
server's admission uses — so the K/V rows and the last-position logits
are bit-identical to what `serve_paged` would have computed locally,
and greedy decode is token-identical end to end. Chunked prefill
(`chunk_len=C`) bounds the compile-shape set and the per-dispatch
FLOPs for long prompts: full chunks run at EXACTLY C tokens (a padded
mid-chunk would advance the cache write head past real content and
corrupt every later row), only the tail chunk is pow2-padded.

Crash injection: `fail_after_requests=N` hard-closes both sockets
after N payloads without the STOP frame — the decode side sees a
mid-stream peer death, which is the retry path the worker-drop test
exercises.
"""

from __future__ import annotations

import numpy as np

from defer_tpu.disagg import wire
from defer_tpu.obs.serving import DisaggMetrics
from defer_tpu.runtime.transport import ArrayReceiver, ArraySender
from defer_tpu.utils.logging import get_logger

log = get_logger(__name__)


def prefill_schedule(t0: int, chunk_len: int | None) -> list[int]:
    """Chunk lengths covering t0 tokens: full chunks of exactly
    chunk_len, then a 1..chunk_len tail (the only chunk the runner may
    pad). chunk_len=None = one chunk = the monolithic schedule."""
    if t0 < 1:
        raise ValueError("need at least one prompt token")
    if chunk_len is None or chunk_len >= t0:
        return [t0]
    if chunk_len < 1:
        raise ValueError(f"chunk_len must be >= 1, got {chunk_len}")
    n_full = (t0 - 1) // chunk_len
    tail = t0 - n_full * chunk_len
    return [chunk_len] * n_full + [tail]


def run_prefill(
    dec,
    params: dict,
    prompt: np.ndarray,
    *,
    block_size: int,
    chunk_len: int | None = None,
) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Prefill one prompt and cut the cache into pool-shaped blocks.

    Returns (k_blocks, v_blocks, logits_row): [L, n_blocks, Hkv, bs,
    Dh] stacks covering rows 0..t0-1 (tail rows beyond t0 zero-padded
    — the decode server masks them, and its first decode write lands
    at row t0), plus the [1, V] logits row of the LAST REAL prompt
    position, which the decode side samples the first token from."""
    import jax.numpy as jnp

    from defer_tpu.parallel.transformer_stack import refuse_mechanisms

    refuse_mechanisms(dec.cfg, "disagg prefill (run_prefill)")
    t0 = int(prompt.shape[1])
    max_len = dec.cfg.max_len
    if t0 >= max_len:
        raise ValueError(f"prompt of {t0} leaves no room under max_len {max_len}")
    cache = dec.init_cache(1)
    step = dec.make_step()
    prompt_j = jnp.asarray(prompt, jnp.int32)
    logits_row = None
    pos = 0
    chunks = prefill_schedule(t0, chunk_len)
    for ci, chunk in enumerate(chunks):
        ids = prompt_j[:, pos : pos + chunk]
        if ci == len(chunks) - 1:
            # Tail: pow2-pad like the monolithic admission (the pad
            # rows are garbage past t0, masked until the first decode
            # write overwrites row t0).
            pad = 1 << (chunk - 1).bit_length()
            pad = min(pad, max_len - pos)
            if pad > chunk:
                ids = jnp.concatenate(
                    [ids, jnp.zeros((1, pad - chunk), jnp.int32)], axis=1
                )
        logits, cache = step(params, cache, ids)
        logits_row = logits[:, chunk - 1, :]
        pos += chunk
    L = dec.cfg.num_layers
    hkv = dec.cfg.kv_heads
    dh = dec.cfg.dh
    n_blocks = -(-t0 // block_size)
    # Host transfer of the finished cache — the whole point of the
    # worker: these rows ship to the decode host instead of living
    # here.
    k = np.asarray(cache["k"])[:, 0, :, :t0, :]  # [L, Hkv, t0, Dh]
    v = np.asarray(cache["v"])[:, 0, :, :t0, :]
    row_pad = n_blocks * block_size - t0
    if row_pad:
        k = np.pad(k, ((0, 0), (0, 0), (0, row_pad), (0, 0)))
        v = np.pad(v, ((0, 0), (0, 0), (0, row_pad), (0, 0)))
    k_blocks = k.reshape(L, hkv, n_blocks, block_size, dh).transpose(
        0, 2, 1, 3, 4
    )
    v_blocks = v.reshape(L, hkv, n_blocks, block_size, dh).transpose(
        0, 2, 1, 3, 4
    )
    return (
        np.ascontiguousarray(k_blocks),
        np.ascontiguousarray(v_blocks),
        np.asarray(logits_row),
    )


# analysis: domain(transport) one worker session per thread; all state is session-local, results cross by wire only
def serve_prefill(
    listen_port: int = 0,
    *,
    listen_host: str = "127.0.0.1",
    accept_timeout_s: float = 120.0,
    read_timeout_s: float | None = None,
    connect_timeout_s: float = 30.0,
    announce=None,
    fail_after_requests: int | None = None,
) -> int:
    """Run one prefill-worker session to completion; returns requests
    served. `announce(port)` fires once the listen socket is bound
    (drivers/tests learn the ephemeral port). Architecture, weights
    and every prompt arrive over the wire — the worker process needs
    no local model state at all."""
    recv = ArrayReceiver(
        listen_port,
        host=listen_host,
        accept_timeout_s=accept_timeout_s,
        read_timeout_s=read_timeout_s,
    )
    if announce is not None:
        announce(recv.port)
    obs = DisaggMetrics("prefill")
    sender = None
    count = 0
    try:
        it = iter(recv)
        hello = wire.expect_hello(it)
        dec = wire.decoder_from_wire(wire.expect_blob(it, "decoder"))
        params = wire.read_params(it)
        block_size = int(hello["block_size"])
        chunk_len = hello.get("chunk_len")
        log.info(
            "prefill worker ready: %d layers, block_size=%d, "
            "results -> %s:%d",
            dec.cfg.num_layers,
            block_size,
            hello["result_host"],
            hello["result_port"],
        )
        sender = ArraySender(
            hello["result_host"],
            hello["result_port"],
            compress=hello.get("compress", True),
            level=hello.get("level", 3),
            quantize=hello.get("quantize"),
            connect_timeout_s=connect_timeout_s,
        )
        while True:
            req = wire.read_blob(it)
            if req is None:
                break  # clean STOP from the dispatcher
            if req.get("kind") != "prefill":
                raise wire.TransportError(
                    f"expected 'prefill' blob, got {req.get('kind')!r}"
                )
            prompt = wire._next_frame(it, "prompt frame")
            k_blocks, v_blocks, logits_row = run_prefill(
                dec,
                params,
                np.asarray(prompt)[None]
                if np.asarray(prompt).ndim == 1
                else np.asarray(prompt),
                block_size=block_size,
                chunk_len=chunk_len,
            )
            wire.send_kv_payload(
                sender,
                wire.KVPayload(
                    rid=int(req["rid"]),
                    t0=int(np.asarray(prompt).shape[-1]),
                    k=k_blocks,
                    v=v_blocks,
                    logits=logits_row,
                ),
                obs=obs,
            )
            count += 1
            if (
                fail_after_requests is not None
                and count >= fail_after_requests
            ):
                # Simulated crash: kill both sockets with no STOP —
                # the decode side must see a mid-stream peer death.
                log.info(
                    "prefill worker: injected failure after %d "
                    "request(s)",
                    count,
                )
                sender._sock.close()
                sender = None
                return count
        sender.close()
        sender = None
        return count
    finally:
        if sender is not None:
            sender.close()
        recv.close()


def main(argv: list[str] | None = None) -> None:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--listen", type=int, default=5100)
    ap.add_argument("--listen-host", default="0.0.0.0")
    ap.add_argument("--accept-timeout", type=float, default=120.0)
    ap.add_argument(
        "--read-timeout",
        type=float,
        default=None,
        help="per-recv timeout on the dispatch stream (None = block)",
    )
    args = ap.parse_args(argv)
    n = serve_prefill(
        args.listen,
        listen_host=args.listen_host,
        accept_timeout_s=args.accept_timeout,
        read_timeout_s=args.read_timeout,
        announce=lambda p: print(f"LISTENING {p}", flush=True),
    )
    print(f"DONE {n}", flush=True)


if __name__ == "__main__":
    main()
