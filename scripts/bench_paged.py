#!/usr/bin/env python
"""Paged-decode attention microbench: tokens/sec and estimated K/V
bytes read per tick for each attention mode, printed as ONE JSON line.

The point being measured: a gather of every slot's whole table reads
O(B * max_blocks * block_size) rows a tick regardless of request
depth; the gathered path reads to the rung above the deepest live slot
and the block-native paths ("blockwise", "pallas") only live blocks —
the obs counters (defer_kv_rows_read_total vs the gathered
baseline) make the ratio exact, and this bench prices it per mode on
one identical request mix.

Standalone:

    JAX_PLATFORMS=cpu python scripts/bench_paged.py
    python scripts/bench_paged.py --modes gathered,blockwise,pallas

Importable: `run_microbench(devices) -> dict` — bench.py runs it as a
"paged_attention" extras section behind the supervisor/snapshot
deadline machinery, so a wedged compile cannot sink the headline.

Also here: `run_window_sweep(devices) -> dict` (`--window-sweep` on
the CLI) — the fused-decode-window sweep (decode_window = K in
{1,4,8,16}) pricing host dispatches per token against tokens/sec;
bench.py runs it as the "decode_window" extras section. And
`run_mixed_sweep(devices) -> dict` (`--mixed-sweep`) — the
mixed-mode continuous-batching sweep (prefill_budget = stall
baseline + {64,128,256,inf}, the same request mix offered open-loop
via runtime/batching.py::poisson_arrivals) pricing live slots' ITL
p50/p99, TTFT, tokens/sec and the decode-stall fraction per budget;
bench.py runs it as the "mixed_serving" extras section. And
`run_spec_sweep(devices) -> dict` (`--spec-sweep`) — the paged
speculative-decoding sweep (spec_k in {0,2,4} crossed with a DRAFT
AXIS: self | trunc:L/2 | trunc:L/4 | width:1/2, built with
models/transplant.py `make_draft`) pricing MEASURED acceptance,
tokens/sec and dispatches-per-token per (draft, k) — the
acceptance-vs-speedup frontier; bench.py runs it as the
"speculative" extras section. And `run_tp_sweep(devices) -> dict` (`--tp-sweep`) —
the tensor-parallel serving sweep (model_axis in {1,2,4,8} on a
{"model": m} mesh, runtime/paged.py `mesh=`) pricing tokens/sec,
tokens-per-dispatch and per-shard KV rows read per axis size;
bench.py runs it as the "tp_serving" extras section. And
`run_pp_sweep(devices) -> dict` (`--pp-sweep`) — the
pipeline-parallel serving sweep (pp_stages S in {1,2,4} crossed with
in-flight microbatch counts M, runtime/paged.py `pp_stages=`) pricing
tokens/sec, the MEASURED bubble fraction and per-stage occupancy of
the dispatch-slot schedule, and per-stage KV-pool bytes (~1/S each);
bench.py runs it as the "pp_serving" extras section. And
`run_kv_quant_sweep(devices) -> dict` (`--kv-quant-sweep`) — the
KV-quantization sweep (kv_dtype fp vs int8 over the same
over-subscribed Zipf prefix mix with the host-RAM spill tier on)
pricing tokens/sec, resident-requests-per-pool-MiB and the spill
revival rate; bench.py runs it as the "kv_quant" extras section. And
`run_constrain_sweep(devices) -> dict` (`--constrain-sweep`) — the
constrained-decoding sweep (defer_tpu/constrain/: the same request
mix served free vs regex-constrained vs JSON-schema-constrained)
pricing the on-device DFA mask fold (tokens/sec vs the free
baseline), host compile time, DFA table size and the mean
masked-vocabulary fraction; bench.py runs it as the "constrain"
extras section.

"pallas" is excluded by default off-TPU: the interpret-mode kernel is
functionally identical but interpreter-slow, which would price the
mode's dispatch overhead, not its bandwidth. Pass --modes to force it.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

_DEFAULT_MODES = ("gathered", "blockwise")


def _native_pallas() -> bool:
    from defer_tpu.ops.attention import _pallas_available

    return _pallas_available()


def run_microbench(
    devices=None,
    *,
    modes: tuple = (),
    num_layers: int = 4,
    dim: int = 256,
    num_heads: int = 8,
    num_kv_heads: int = 4,
    vocab_size: int = 2048,
    max_len: int = 512,
    num_blocks: int = 49,
    block_size: int = 16,
    max_batch: int = 4,
    num_requests: int = 8,
) -> dict:
    """Serve one fixed request mix through every attention mode;
    returns {config, modes: {mode: {tokens_per_sec, kv_rows_read,
    kv_rows_gathered_baseline, kv_read_ratio, est_kv_bytes_per_tick,
    ...}}}. Deliberately small defaults: the ratio, not the absolute
    throughput, is the headline off-TPU."""
    import jax
    import jax.numpy as jnp

    from defer_tpu import obs
    from defer_tpu.models.gpt import GptDecoder
    from defer_tpu.models.llama import llama_config
    from defer_tpu.runtime.paged import serve_paged

    if not modes:
        modes = _DEFAULT_MODES + (
            ("pallas",) if _native_pallas() else ()
        )
    cfg = llama_config(
        num_layers=num_layers,
        dim=dim,
        num_heads=num_heads,
        num_kv_heads=num_kv_heads,
        ffn_dim=dim * 2,
        vocab_size=vocab_size,
        max_len=max_len,
    )
    dec = GptDecoder(cfg, compute_dtype=jnp.bfloat16)
    params = dec.cast_params(dec.init(jax.random.key(0)))
    if devices:
        params = jax.device_put(params, devices[0])
    reqs = []
    for i in range(num_requests):
        t0 = 16 + (i * 23) % 112
        steps = 16 + (i * 11) % 48
        prompt = jax.random.randint(
            jax.random.fold_in(jax.random.key(1), i),
            (1, t0),
            0,
            cfg.vocab_size,
        )
        reqs.append((prompt, steps))
    total_tokens = sum(s for _, s in reqs)
    dh = cfg.dim // cfg.num_heads
    # Bytes behind one counted row unit: K+V, every layer, all KV
    # heads (the counters are layer/head-agnostic; obs/serving.py).
    bytes_per_row = (
        2 * cfg.num_layers * cfg.kv_heads * dh
        * jnp.dtype(dec.compute_dtype).itemsize
    )

    out: dict = {
        "config": {
            "num_layers": num_layers,
            "dim": dim,
            "heads": f"{num_heads}/{num_kv_heads}kv",
            "max_len": max_len,
            "num_blocks": num_blocks,
            "block_size": block_size,
            "max_batch": max_batch,
            "requests": num_requests,
            "total_tokens": total_tokens,
        },
        "modes": {},
    }
    lab = 'server="paged"'
    for mode in modes:
        def run():
            t0 = time.perf_counter()
            with obs.counter_deltas() as d:
                outs, stats = serve_paged(
                    dec,
                    params,
                    reqs,
                    num_blocks=num_blocks,
                    block_size=block_size,
                    max_batch=max_batch,
                    attention=mode,
                )
                jax.block_until_ready(outs[-1])
            return time.perf_counter() - t0, d, stats
        run()  # compile pass
        dt, deltas, stats = run()
        rows = deltas.get(f"defer_kv_rows_read_total{{{lab}}}", 0)
        base = deltas.get(
            f"defer_kv_rows_gathered_baseline_total{{{lab}}}", 0
        )
        ticks = max(1, stats["ticks"])
        out["modes"][mode] = {
            "tokens_per_sec": round(total_tokens / dt, 1),
            "ticks": stats["ticks"],
            "kv_rows_read": rows,
            "kv_rows_gathered_baseline": base,
            "kv_read_ratio": round(rows / max(1, base), 4),
            "est_kv_bytes_per_tick": int(
                rows / ticks * bytes_per_row
            ),
            "est_kv_bytes_per_tick_gathered": int(
                base / ticks * bytes_per_row
            ),
        }
    return out


def run_window_sweep(
    devices=None,
    *,
    windows: tuple = (1, 4, 8, 16),
    num_layers: int = 4,
    dim: int = 256,
    num_heads: int = 8,
    num_kv_heads: int = 4,
    vocab_size: int = 2048,
    max_len: int = 512,
    num_blocks: int = 49,
    block_size: int = 16,
    max_batch: int = 4,
    num_requests: int = 8,
) -> dict:
    """Fused-decode-window sweep: the same fixed request mix served at
    decode_window = K for each K, through the paged server's gathered
    path. Returns {config, windows: {K: {tokens_per_sec,
    host_dispatches, dispatches_per_token, tokens_per_dispatch,
    speedup_vs_k1}}}. The point being measured: every decode token
    costs one host dispatch at K=1; a window of K amortizes that fixed
    dispatch overhead over up to K tokens, so dispatches-per-token
    falls toward 1/K and small-model tokens/sec — dominated by
    dispatch overhead, not math — climbs with it."""
    import jax
    import jax.numpy as jnp

    from defer_tpu.models.gpt import GptDecoder
    from defer_tpu.models.llama import llama_config
    from defer_tpu.runtime.paged import serve_paged

    cfg = llama_config(
        num_layers=num_layers,
        dim=dim,
        num_heads=num_heads,
        num_kv_heads=num_kv_heads,
        ffn_dim=dim * 2,
        vocab_size=vocab_size,
        max_len=max_len,
    )
    dec = GptDecoder(cfg, compute_dtype=jnp.bfloat16)
    params = dec.cast_params(dec.init(jax.random.key(0)))
    if devices:
        params = jax.device_put(params, devices[0])
    reqs = []
    for i in range(num_requests):
        t0 = 16 + (i * 23) % 112
        steps = 16 + (i * 11) % 48
        prompt = jax.random.randint(
            jax.random.fold_in(jax.random.key(1), i),
            (1, t0),
            0,
            cfg.vocab_size,
        )
        reqs.append((prompt, steps))
    total_tokens = sum(s for _, s in reqs)
    out: dict = {
        "config": {
            "num_layers": num_layers,
            "dim": dim,
            "heads": f"{num_heads}/{num_kv_heads}kv",
            "max_len": max_len,
            "num_blocks": num_blocks,
            "block_size": block_size,
            "max_batch": max_batch,
            "requests": num_requests,
            "total_tokens": total_tokens,
        },
        "windows": {},
    }
    base_tps = None
    for K in windows:
        def run():
            t0 = time.perf_counter()
            outs, stats = serve_paged(
                dec,
                params,
                reqs,
                num_blocks=num_blocks,
                block_size=block_size,
                max_batch=max_batch,
                decode_window=K,
            )
            jax.block_until_ready(outs[-1])
            return time.perf_counter() - t0, stats
        run()  # compile pass
        dt, stats = run()
        tps = total_tokens / dt
        if base_tps is None:
            base_tps = tps
        out["windows"][K] = {
            "tokens_per_sec": round(tps, 1),
            "host_dispatches": stats["host_dispatches"],
            "dispatches_per_token": round(
                stats["host_dispatches"] / total_tokens, 4
            ),
            "tokens_per_dispatch": round(
                stats["tokens_per_dispatch"], 2
            ),
            "speedup_vs_k1": round(tps / base_tps, 3),
        }
    return out


def run_mixed_sweep(
    devices=None,
    *,
    budgets: tuple = (64, 128, 256, "inf"),
    arrival_rate: float = 16.0,
    arrival_seed: int = 0,
    num_layers: int = 4,
    dim: int = 256,
    num_heads: int = 8,
    num_kv_heads: int = 4,
    vocab_size: int = 2048,
    max_len: int = 512,
    num_blocks: int = 49,
    block_size: int = 16,
    max_batch: int = 4,
    num_requests: int = 12,
) -> dict:
    """Mixed-mode continuous-batching sweep: the same request mix
    offered OPEN-LOOP (runtime/batching.py::poisson_arrivals — a fixed
    seeded arrival trace that does not throttle itself when the server
    falls behind), served with prefill_budget = None (the stall
    baseline: every admission prefill preempts decode) and each value
    in `budgets` ("inf" = effectively unbounded). Returns {config,
    budgets: {stall|64|...|inf: {itl_p50_ms, itl_p99_ms, ttft_mean_ms,
    ttft_p95_ms, tokens_per_sec, prefill_stall_ticks, mixed_ticks,
    mixed_prefill_tokens, decode_stall_fraction}}}.

    The point being measured: with stall-mode admission, a prompt
    arriving mid-decode freezes every live slot for its whole prefill
    — the freeze lands directly in the live slots' inter-token
    latency tail (ITL p99). Mixed mode fuses up to `budget` prompt
    tokens into each decode dispatch, so decode never skips a tick
    and the p99 collapses toward the p50; the budget knob then trades
    TTFT (bigger chunks land prompts sooner) against per-tick decode
    latency (wider fused T costs more per dispatch)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from defer_tpu.models.gpt import GptDecoder
    from defer_tpu.models.llama import llama_config
    from defer_tpu.runtime.batching import poisson_arrivals
    from defer_tpu.runtime.paged import PagedDecodeServer

    cfg = llama_config(
        num_layers=num_layers,
        dim=dim,
        num_heads=num_heads,
        num_kv_heads=num_kv_heads,
        ffn_dim=dim * 2,
        vocab_size=vocab_size,
        max_len=max_len,
    )
    dec = GptDecoder(cfg, compute_dtype=jnp.bfloat16)
    params = dec.cast_params(dec.init(jax.random.key(0)))
    if devices:
        params = jax.device_put(params, devices[0])
    reqs = []
    for i in range(num_requests):
        t0 = 16 + (i * 23) % 112
        steps = 16 + (i * 11) % 48
        prompt = jax.random.randint(
            jax.random.fold_in(jax.random.key(1), i),
            (1, t0),
            0,
            cfg.vocab_size,
        )
        reqs.append((prompt, steps))
    total_tokens = sum(s for _, s in reqs)
    arrivals = poisson_arrivals(
        num_requests, arrival_rate, seed=arrival_seed
    )

    def run_point(budget):
        stamps: dict = {}

        def on_token(rid, tok, done):
            stamps.setdefault(rid, []).append(time.perf_counter())

        srv = PagedDecodeServer(
            dec,
            params,
            num_blocks=num_blocks,
            block_size=block_size,
            max_batch=max_batch,
            prefill_budget=budget,
            on_token=on_token,
        )
        submit_at: dict = {}
        nxt = 0
        t0 = time.perf_counter()
        while nxt < len(reqs) or srv.pending or any(
            s is not None for s in srv.slots
        ):
            now = time.perf_counter() - t0
            while nxt < len(reqs) and arrivals[nxt] <= now:
                rid = srv.submit(*reqs[nxt])
                submit_at[rid] = time.perf_counter()
                nxt += 1
            srv._admit()
            if any(s is not None for s in srv.slots):
                srv._tick()
            elif nxt < len(reqs):
                # Open-loop idle gap: nothing seated, next arrival
                # still in the future — sleep toward it instead of
                # spinning admit hot.
                time.sleep(
                    min(
                        5e-4,
                        max(
                            0.0,
                            arrivals[nxt]
                            - (time.perf_counter() - t0),
                        ),
                    )
                )
        dt = time.perf_counter() - t0
        gaps = [
            g
            for ts in stamps.values()
            for g in np.diff(ts)
            if len(ts) >= 2
        ]
        ttfts = [
            ts[0] - submit_at[rid] for rid, ts in stamps.items()
        ]
        return {
            "itl_p50_ms": round(
                float(np.percentile(gaps, 50)) * 1e3, 3
            ),
            "itl_p99_ms": round(
                float(np.percentile(gaps, 99)) * 1e3, 3
            ),
            "ttft_mean_ms": round(
                float(np.mean(ttfts)) * 1e3, 3
            ),
            "ttft_p95_ms": round(
                float(np.percentile(ttfts, 95)) * 1e3, 3
            ),
            "tokens_per_sec": round(total_tokens / dt, 1),
            "prefill_stall_ticks": srv.prefill_stall_ticks_n,
            "mixed_ticks": srv.mixed_ticks_n,
            "mixed_prefill_tokens": srv.mixed_prefill_tokens_n,
            "decode_stall_fraction": round(
                srv.decode_stall_fraction_last, 4
            ),
        }

    out: dict = {
        "config": {
            "num_layers": num_layers,
            "dim": dim,
            "heads": f"{num_heads}/{num_kv_heads}kv",
            "max_len": max_len,
            "num_blocks": num_blocks,
            "block_size": block_size,
            "max_batch": max_batch,
            "requests": num_requests,
            "total_tokens": total_tokens,
            "arrival_rate_rps": arrival_rate,
            "arrival_seed": arrival_seed,
        },
        "budgets": {},
    }
    # "inf" = a budget no single tick can exhaust: admission-window
    # prompts land as fast as chunk_cap/t_limit allow.
    points = [("stall", None)] + [
        (str(b), max_len if b == "inf" else int(b)) for b in budgets
    ]
    for key, budget in points:
        run_point(budget)  # compile pass
        out["budgets"][key] = run_point(budget)
    return out


def run_spec_sweep(
    devices=None,
    *,
    ks: tuple = (0, 2, 4),
    drafts: tuple = ("self", "trunc:L/2", "trunc:L/4", "width:1/2"),
    num_layers: int = 4,
    dim: int = 64,
    num_heads: int = 4,
    num_kv_heads: int = 2,
    vocab_size: int = 512,
    max_len: int = 256,
    num_blocks: int = 49,
    block_size: int = 16,
    max_batch: int = 4,
    num_requests: int = 8,
    decode_window: int = 1,
    late_scale: float = 0.25,
) -> dict:
    """Paged speculative-decoding sweep over a DRAFT AXIS: the same
    fixed request mix served at spec_k = k for each k and each draft
    construction (0 = the classic tick loop, the shared baseline).
    Returns {config, baseline, drafts: {label: {geometry, ks: {k:
    {tokens_per_sec, acceptance, spec_rounds, host_dispatches,
    dispatches_per_token, draft_tokens, speedup_vs_k0}}}}, ks} where
    the top-level `ks` keeps the old self-draft table shape
    (baseline row at 0) for existing readers.

    The draft axis is the acceptance-vs-speedup frontier: `self`
    (draft IS the target — acceptance 1.0, isolating the pure
    dispatch-amortization term), `trunc:L/2` / `trunc:L/4`
    (layer-truncated via models/transplant.py `make_draft(layers=)` —
    the residual stream after the shared prefix layers still
    correlates with the full forward, so acceptance lands BETWEEN 0
    and 1 and the sweep measures a real frontier point), and
    `width:1/2` (head/FFN-pruned via `make_draft(width=)`). Each
    draft's `acceptance` is MEASURED, not assumed; speculation wins
    exactly where `(1 + acceptance*k) / 2 > 1` dispatch-for-dispatch
    and the draft's forward is cheap enough to not eat the margin.

    `decode_window=W>1` prices the fused spec x window path: W whole
    draft+verify rounds per host dispatch (dispatches_per_token drops
    by ~W on top of the round amortization).

    `late_scale` shrinks the residual WRITE (wo/w2 + biases) of the
    late half of the target's stack after init. Trained checkpoints
    concentrate most of the logit-relevant residual mass in early
    layers — that is the property layer truncation banks on — but
    random init spreads it uniformly, which would price every real
    draft at acceptance ~ 0 and measure nothing. The shrink restores
    the trained-model shape; acceptance is still MEASURED, never
    assumed (set late_scale=1.0 to see the uniform-init floor).

    Defaults are deliberately SMALLER than the other sweeps':
    speculation only pays where per-dispatch overhead dominates
    compute — the regime small drafts / big targets occupy on real
    hardware, emulated here by shrinking the model."""
    import jax
    import jax.numpy as jnp

    from defer_tpu.models.gpt import GptDecoder
    from defer_tpu.models.llama import llama_config
    from defer_tpu.models.transplant import make_draft
    from defer_tpu.runtime.paged import serve_paged

    cfg = llama_config(
        num_layers=num_layers,
        dim=dim,
        num_heads=num_heads,
        num_kv_heads=num_kv_heads,
        ffn_dim=dim * 2,
        vocab_size=vocab_size,
        max_len=max_len,
    )
    dec = GptDecoder(cfg, compute_dtype=jnp.bfloat16)
    params = dec.init(jax.random.key(0))
    if late_scale != 1.0 and num_layers > 1:
        half = num_layers // 2
        ramp = jnp.asarray(
            [1.0 if l < half else late_scale for l in range(num_layers)]
        )
        st = dict(params["stack"])
        for key in ("wo", "w2"):
            st[key] = st[key] * ramp[:, None, None]
        for key in ("bo", "b2"):
            if key in st:
                st[key] = st[key] * ramp[:, None]
        params = {**params, "stack": st}
    params = dec.cast_params(params)
    if devices:
        params = jax.device_put(params, devices[0])
    reqs = []
    for i in range(num_requests):
        t0 = 16 + (i * 23) % 112
        steps = 16 + (i * 11) % 48
        prompt = jax.random.randint(
            jax.random.fold_in(jax.random.key(1), i),
            (1, t0),
            0,
            cfg.vocab_size,
        )
        reqs.append((prompt, steps))
    total_tokens = sum(s for _, s in reqs)

    def build_draft(label):
        """label -> (draft decoder, draft params). `trunc:L/n` slices
        the first num_layers//n layers; `width:p/q` prunes heads+FFN
        to the fraction p/q; `self` reuses the target."""
        if label == "self":
            return dec, params
        kind, _, arg = label.partition(":")
        if kind == "trunc":
            den = int(arg.split("/")[1])
            return make_draft(
                dec, params, layers=max(1, num_layers // den)
            )
        if kind == "width":
            num, den = arg.split("/")
            return make_draft(dec, params, width=float(num) / float(den))
        raise ValueError(f"unknown draft axis label {label!r}")

    def timed(**kwargs):
        def run():
            t0 = time.perf_counter()
            outs, stats = serve_paged(
                dec,
                params,
                reqs,
                num_blocks=num_blocks,
                block_size=block_size,
                max_batch=max_batch,
                decode_window=decode_window,
                **kwargs,
            )
            jax.block_until_ready(outs[-1])
            return time.perf_counter() - t0, stats

        run()  # compile pass
        return run()

    out: dict = {
        "config": {
            "num_layers": num_layers,
            "dim": dim,
            "heads": f"{num_heads}/{num_kv_heads}kv",
            "max_len": max_len,
            "num_blocks": num_blocks,
            "block_size": block_size,
            "max_batch": max_batch,
            "requests": num_requests,
            "total_tokens": total_tokens,
            "decode_window": decode_window,
            "drafts": list(drafts),
        },
        "drafts": {},
    }
    dt, stats = timed()
    base_tps = total_tokens / dt
    baseline = {
        "tokens_per_sec": round(base_tps, 1),
        "acceptance": 0.0,
        "spec_rounds": 0,
        "host_dispatches": stats["host_dispatches"],
        "dispatches_per_token": round(
            stats["host_dispatches"] / total_tokens, 4
        ),
        "draft_tokens": 0,
        "speedup_vs_k0": 1.0,
    }
    out["baseline"] = baseline
    for label in drafts:
        draft, dparams = build_draft(label)
        dcfg = draft.cfg
        per: dict = {
            "geometry": (
                f"{dcfg.num_layers}L/{dcfg.num_heads}h/"
                f"{dcfg.dim}d/{dcfg.ffn_dim}f"
            ),
            "ks": {},
        }
        for k in ks:
            if not k:
                continue
            dt, stats = timed(
                spec_draft=draft, spec_params=dparams, spec_k=k
            )
            tps = total_tokens / dt
            per["ks"][k] = {
                "tokens_per_sec": round(tps, 1),
                "acceptance": round(stats["spec_acceptance"], 4),
                "spec_rounds": stats["spec_rounds"],
                "host_dispatches": stats["host_dispatches"],
                "dispatches_per_token": round(
                    stats["host_dispatches"] / total_tokens, 4
                ),
                "draft_tokens": stats["spec_draft_tokens"],
                "speedup_vs_k0": round(tps / base_tps, 3),
            }
        out["drafts"][label] = per
    # Old table shape (self-draft, baseline at k=0) for readers that
    # predate the draft axis.
    if "self" in out["drafts"]:
        out["ks"] = {0: baseline, **out["drafts"]["self"]["ks"]}
    return out


def run_tp_sweep(
    devices=None,
    *,
    axes: tuple = (1, 2, 4, 8),
    num_layers: int = 4,
    dim: int = 256,
    num_heads: int = 8,
    num_kv_heads: int = 8,
    vocab_size: int = 2048,
    max_len: int = 512,
    num_blocks: int = 49,
    block_size: int = 16,
    max_batch: int = 4,
    num_requests: int = 8,
) -> dict:
    """Tensor-parallel serving sweep: the same fixed request mix served
    on a {"model": m} mesh for each axis size m that fits the visible
    devices (CPU runs force 8 host devices via XLA_FLAGS, the test
    rig's idiom). Returns {config, device_kind, axes: {m:
    {tokens_per_sec, host_dispatches, dispatches_per_token,
    tokens_per_dispatch, kv_rows_read_per_shard, kv_rows_scaling,
    tp_psums, mesh_shape}}}.

    The points being measured: host dispatches per token must NOT move
    with m (one dispatch drives all shards — the contract the
    counter-pinned test enforces), per-shard KV rows read must fall as
    1/m (each shard owns kv_heads/m heads of every block), and
    tokens/sec prices what the psum/all-gather chatter costs on this
    interconnect. `num_kv_heads` defaults to 8 so every swept axis
    divides it."""
    import jax
    import jax.numpy as jnp

    from defer_tpu import obs
    from defer_tpu.models.gpt import GptDecoder
    from defer_tpu.models.llama import llama_config
    from defer_tpu.parallel.mesh import describe_topology, make_mesh
    from defer_tpu.runtime.paged import serve_paged

    devs = list(devices) if devices else jax.devices()
    cfg = llama_config(
        num_layers=num_layers,
        dim=dim,
        num_heads=num_heads,
        num_kv_heads=num_kv_heads,
        ffn_dim=dim * 2,
        vocab_size=vocab_size,
        max_len=max_len,
    )
    dec = GptDecoder(cfg, compute_dtype=jnp.bfloat16)
    params = dec.cast_params(dec.init(jax.random.key(0)))
    reqs = []
    for i in range(num_requests):
        t0 = 16 + (i * 23) % 112
        steps = 16 + (i * 11) % 48
        prompt = jax.random.randint(
            jax.random.fold_in(jax.random.key(1), i),
            (1, t0),
            0,
            cfg.vocab_size,
        )
        reqs.append((prompt, steps))
    total_tokens = sum(s for _, s in reqs)
    topo = describe_topology()
    out: dict = {
        "config": {
            "num_layers": num_layers,
            "dim": dim,
            "heads": f"{num_heads}/{num_kv_heads}kv",
            "max_len": max_len,
            "num_blocks": num_blocks,
            "block_size": block_size,
            "max_batch": max_batch,
            "requests": num_requests,
            "total_tokens": total_tokens,
        },
        "device_kind": topo["device_kind"],
        "num_devices": len(devs),
        "skipped_axes": [m for m in axes if m > len(devs)],
        "axes": {},
    }
    base_rows = None
    for m in axes:
        if m > len(devs):
            continue
        mesh = make_mesh({"model": m}, devs[:m])
        mesh_shape = f"model={m}"
        lab = f'mesh="{mesh_shape}",server="paged"'

        def run():
            t0 = time.perf_counter()
            with obs.counter_deltas() as d:
                outs, stats = serve_paged(
                    dec,
                    params,
                    reqs,
                    num_blocks=num_blocks,
                    block_size=block_size,
                    max_batch=max_batch,
                    mesh=mesh,
                )
                jax.block_until_ready(outs[-1])
            return time.perf_counter() - t0, d, stats

        run()  # compile pass
        dt, deltas, stats = run()
        rows = deltas.get(f"defer_kv_rows_read_total{{{lab}}}", 0)
        if base_rows is None:
            base_rows = rows
        out["axes"][m] = {
            "tokens_per_sec": round(total_tokens / dt, 1),
            "host_dispatches": stats["host_dispatches"],
            "dispatches_per_token": round(
                stats["host_dispatches"] / total_tokens, 4
            ),
            "tokens_per_dispatch": round(
                stats["tokens_per_dispatch"], 2
            ),
            "kv_rows_read_per_shard": rows,
            "kv_rows_scaling": round(rows / max(1, base_rows), 4),
            "tp_psums": stats["tp_psums"],
            "mesh_shape": mesh_shape,
        }
    return out


def run_pp_sweep(
    devices=None,
    *,
    grid: tuple = ((1, 1), (2, 2), (4, 2), (4, 4)),
    decode_window: int = 8,
    num_layers: int = 4,
    dim: int = 128,
    num_heads: int = 4,
    num_kv_heads: int = 4,
    vocab_size: int = 1024,
    max_len: int = 256,
    num_blocks: int = 33,
    block_size: int = 8,
    max_batch: int = 4,
    num_requests: int = 8,
) -> dict:
    """Pipeline-parallel serving sweep: the same fixed request mix
    served with the layer stack cut into S stages (one device and one
    KV-pool slice per stage) at M in-flight microbatch groups, for
    each (S, M) in `grid`. Returns {config, device_kind, num_devices,
    skipped, grid: {"s{S}_m{M}": {tokens_per_sec, speedup_vs_s1,
    bubble_fraction, stage_occupancy, stage_dispatches,
    stage_pool_bytes, pool_bytes_vs_s1, cut_starts}}} — keys are
    flat "s2_m2" strings so budgets.toml bench_metric paths can
    navigate them.

    The points being measured: bubble_fraction is the MEASURED idle
    share of the dispatch-slot schedule (runtime/batching.py
    `pp_schedule_occupancy` over what the tick actually dispatched,
    last window) — (S-1)/(S-1 + chains) when every group stays live,
    shrinking as M and decode_window amortize the fill/drain ramps;
    per-stage pool bytes must sum to ~the S=1 pool (each stage holds
    ONLY its layers' slice); and tokens/sec prices the overlap.
    Wall-clock speedup needs real parallel hardware — stages on forced
    host devices share the machine's cores, so on a small CPU rig the
    schedule metrics, not tokens/sec, carry the claim (the ROADMAP's
    standing caution about absolute CPU numbers applies doubly here).
    (S, M) points needing more devices than visible are skipped and
    reported; M never exceeds max_batch."""
    import jax
    import jax.numpy as jnp

    from defer_tpu.models.gpt import GptDecoder
    from defer_tpu.models.llama import llama_config
    from defer_tpu.parallel.mesh import describe_topology
    from defer_tpu.runtime.paged import serve_paged

    devs = list(devices) if devices else jax.devices()
    cfg = llama_config(
        num_layers=num_layers,
        dim=dim,
        num_heads=num_heads,
        num_kv_heads=num_kv_heads,
        ffn_dim=dim * 2,
        vocab_size=vocab_size,
        max_len=max_len,
    )
    dec = GptDecoder(cfg, compute_dtype=jnp.bfloat16)
    params = dec.cast_params(dec.init(jax.random.key(0)))
    reqs = []
    for i in range(num_requests):
        t0 = 16 + (i * 23) % 112
        steps = 16 + (i * 11) % 48
        prompt = jax.random.randint(
            jax.random.fold_in(jax.random.key(1), i),
            (1, t0),
            0,
            cfg.vocab_size,
        )
        reqs.append((prompt, steps))
    total_tokens = sum(s for _, s in reqs)
    topo = describe_topology()
    out: dict = {
        "config": {
            "num_layers": num_layers,
            "dim": dim,
            "heads": f"{num_heads}/{num_kv_heads}kv",
            "max_len": max_len,
            "num_blocks": num_blocks,
            "block_size": block_size,
            "max_batch": max_batch,
            "requests": num_requests,
            "total_tokens": total_tokens,
            "decode_window": decode_window,
        },
        "device_kind": topo["device_kind"],
        "num_devices": len(devs),
        "skipped": [
            f"s{s}_m{m}"
            for s, m in grid
            if s > len(devs) or m > max_batch or max_batch % m
        ],
        "grid": {},
    }
    base_tps = None
    base_pool = None
    for s, m in grid:
        if s > len(devs) or m > max_batch or max_batch % m:
            continue
        pp = (
            {}
            if s == 1
            else {
                "pp_stages": s,
                "pp_inflight": m,
                "pp_devices": devs[:s],
            }
        )

        def run():
            t0 = time.perf_counter()
            outs, stats = serve_paged(
                dec,
                params,
                reqs,
                num_blocks=num_blocks,
                block_size=block_size,
                max_batch=max_batch,
                decode_window=decode_window,
                **pp,
            )
            jax.block_until_ready(outs[-1])
            return time.perf_counter() - t0, stats

        run()  # compile pass
        dt, stats = run()
        tps = total_tokens / dt
        if s == 1:
            base_tps = tps
            base_pool = stats["pool_bytes"]
        out["grid"][f"s{s}_m{m}"] = {
            "tokens_per_sec": round(tps, 1),
            "speedup_vs_s1": round(
                tps / base_tps if base_tps else 0.0, 3
            ),
            "bubble_fraction": round(stats["pp_bubble_fraction"], 4),
            "stage_occupancy": [
                round(o, 4) for o in stats["pp_stage_occupancy"]
            ],
            "stage_dispatches": stats["pp_stage_dispatches"],
            "stage_pool_bytes": stats["pp_stage_pool_bytes"],
            "pool_bytes_vs_s1": round(
                stats["pool_bytes"] / base_pool if base_pool else 0.0, 4
            ),
            "cut_starts": stats["pp_cut_starts"],
        }
    return out


def run_kv_quant_sweep(
    devices=None,
    *,
    dtypes: tuple = ("fp", "int8"),
    num_layers: int = 2,
    dim: int = 64,
    num_heads: int = 4,
    num_kv_heads: int = 2,
    vocab_size: int = 512,
    max_len: int = 256,
    num_blocks: int = 17,
    block_size: int = 4,
    max_batch: int = 2,
    num_requests: int = 12,
    num_prefixes: int = 4,
    prefix_len: int = 16,
    spill_bytes: int = 32 << 20,
) -> dict:
    """KV-quantization sweep: the same over-subscribed Zipf-prefix
    request mix served with a fp pool vs an int8+scales pool, both with
    the host-RAM spill tier on. Returns {config, dtypes: {d:
    {tokens_per_sec, pool_bytes, pool_bytes_vs_fp,
    resident_requests_per_pool_mib, spilled_blocks, spill_hits,
    spill_revival_rate, prefill_tokens, prefill_tokens_no_spill,
    prefill_tokens_saved}}}.

    The request mix is Zipf-ish over `num_prefixes` shared prefixes
    (popularity ~ 1/rank), dealt round-robin so a popular prefix's next
    request arrives only after the other prefixes' traffic has pushed
    its cached blocks out of the deliberately undersized pool — the
    over-subscription that makes eviction (and hence spill) happen at
    all. Three things are being priced: (1) capacity — int8 stores the
    same blocks in itemsize-fold fewer bytes (4x under fp32 compute,
    2x under this sweep's bf16, plus per-[layer,block,head] scales),
    so resident-requests-per-pool-MiB is the headline ratio; (2) the
    spill tier — spilled_blocks / spill_hits under pressure, with
    prefill_tokens vs the spill_bytes=0 baseline showing the prefill
    rows the revivals saved; (3) throughput — tokens/sec, which off-TPU
    mostly prices dispatch overhead (the HBM-bandwidth win needs real
    hardware; the obs row counters are dtype-agnostic by design).

    spill_revival_rate is spill_hits / spilled_blocks — the fraction of
    evicted-and-spilled blocks a later request actually revived (> 0 is
    the acceptance bar; ~1 means the spill store is doing real work)."""
    import jax
    import jax.numpy as jnp

    from defer_tpu import obs
    from defer_tpu.models.gpt import GptDecoder
    from defer_tpu.models.llama import llama_config
    from defer_tpu.runtime.paged import serve_paged

    cfg = llama_config(
        num_layers=num_layers,
        dim=dim,
        num_heads=num_heads,
        num_kv_heads=num_kv_heads,
        ffn_dim=dim * 2,
        vocab_size=vocab_size,
        max_len=max_len,
    )
    dec = GptDecoder(cfg, compute_dtype=jnp.bfloat16)
    params = dec.cast_params(dec.init(jax.random.key(0)))
    if devices:
        params = jax.device_put(params, devices[0])

    # Zipf-ish popularity: prefix r gets ~1/(r+1) of the traffic.
    weights = [1.0 / (r + 1) for r in range(num_prefixes)]
    wsum = sum(weights)
    counts = [
        max(1, round(num_requests * w / wsum)) for w in weights
    ]
    while sum(counts) > num_requests:
        counts[counts.index(max(counts))] -= 1
    while sum(counts) < num_requests:
        counts[0] += 1
    prefixes = [
        jax.random.randint(
            jax.random.fold_in(jax.random.key(7), r),
            (1, prefix_len),
            0,
            cfg.vocab_size,
        )
        for r in range(num_prefixes)
    ]
    # Deal round-robin: a prefix's next request lands only after the
    # other prefixes' traffic had a chance to evict its blocks.
    order = []
    for j in range(max(counts)):
        for r in range(num_prefixes):
            if counts[r] > j:
                order.append(r)
    reqs = []
    for i, r in enumerate(order):
        tail = 2 + (i * 3) % 4
        steps = 12 + (i * 7) % 12
        suffix = jax.random.randint(
            jax.random.fold_in(jax.random.key(11), i),
            (1, tail),
            0,
            cfg.vocab_size,
        )
        reqs.append((jnp.concatenate([prefixes[r], suffix], axis=1), steps))
    total_tokens = sum(s for _, s in reqs)
    # Mean per-request footprint in blocks, for the capacity metric.
    blocks_per_req = sum(
        -(-(p.shape[1] + s) // block_size) for p, s in reqs
    ) / len(reqs)
    out: dict = {
        "config": {
            "num_layers": num_layers,
            "dim": dim,
            "heads": f"{num_heads}/{num_kv_heads}kv",
            "max_len": max_len,
            "num_blocks": num_blocks,
            "block_size": block_size,
            "max_batch": max_batch,
            "requests": num_requests,
            "total_tokens": total_tokens,
            "prefix_mix": f"zipf({num_prefixes})x{prefix_len}tok",
            "spill_bytes": spill_bytes,
        },
        "dtypes": {},
    }
    lab = 'server="paged"'
    fp_pool_bytes = None
    for d in dtypes:

        def run(spill):
            t0 = time.perf_counter()
            with obs.counter_deltas() as deltas:
                outs, stats = serve_paged(
                    dec,
                    params,
                    reqs,
                    num_blocks=num_blocks,
                    block_size=block_size,
                    max_batch=max_batch,
                    prefix_cache=True,
                    kv_dtype=d,
                    spill_bytes=spill,
                )
                jax.block_until_ready(outs[-1])
            return time.perf_counter() - t0, deltas, stats

        run(spill_bytes)  # compile pass
        dt, deltas, stats = run(spill_bytes)
        _, base_deltas, _ = run(0)  # no-spill baseline: same mix
        if fp_pool_bytes is None:
            fp_pool_bytes = stats["pool_bytes"]
        prefill = deltas.get(f"defer_prefill_tokens_total{{{lab}}}", 0)
        prefill_base = base_deltas.get(
            f"defer_prefill_tokens_total{{{lab}}}", 0
        )
        spilled = deltas.get(f"defer_prefix_spilled_total{{{lab}}}", 0)
        out["dtypes"][d] = {
            "tokens_per_sec": round(total_tokens / dt, 1),
            "pool_bytes": stats["pool_bytes"],
            "pool_bytes_vs_fp": round(
                stats["pool_bytes"] / fp_pool_bytes, 4
            ),
            "resident_requests_per_pool_mib": round(
                ((num_blocks - 1) / blocks_per_req)
                / (stats["pool_bytes"] / (1 << 20)),
                2,
            ),
            "spilled_blocks": spilled,
            "spill_hits": stats["spill_hits"],
            "spill_revival_rate": round(
                stats["spill_hits"] / max(1, spilled), 4
            ),
            "prefill_tokens": prefill,
            "prefill_tokens_no_spill": prefill_base,
            "prefill_tokens_saved": stats["prefill_tokens_saved"],
        }
    return out


def run_constrain_sweep(
    devices=None,
    *,
    modes: tuple = ("free", "regex", "json"),
    decode_window: int = 1,
    num_layers: int = 2,
    dim: int = 64,
    num_heads: int = 4,
    num_kv_heads: int = 2,
    vocab_size: int = 128,
    max_len: int = 256,
    num_blocks: int = 33,
    block_size: int = 4,
    max_batch: int = 4,
    num_requests: int = 8,
) -> dict:
    """Constrained-decoding sweep (defer_tpu/constrain/): the same
    request mix served three ways — free (constraints registered but
    no request opts in: the pre-constraint programs must dispatch),
    regex-constrained (`[0-9]+(\\.[0-9]+)?`), and JSON-schema-
    constrained (an object with a boolean and a bounded integer
    array) — each at `decode_window` sub-steps per host dispatch.
    Returns {config, constraints: {mode: {tokens_per_sec,
    tps_vs_free, constrained_tokens, mean_masked_frac, dead_ends,
    compile_ms, dfa_states, dfa_table_kib}}}.

    Two prices being measured: (1) the host compiler — regex ->
    char DFA -> token lift -> dead-state prune, a one-off cost per
    (pattern, vocab) reported in compile_ms with the resulting
    stacked-table footprint (dfa_states, dfa_table_kib); (2) the
    device mask fold — one [B] gather + where + argmax riding the
    existing tick, so tps_vs_free near 1.0 is the acceptance bar
    (off-TPU the gap prices dispatch, not bandwidth). The vocabulary
    is synthetic char-level text (digits, letters, JSON punctuation,
    a few multi-char merges exercising the token lift), sized to the
    model's `vocab_size`; mean_masked_frac says how much of that
    vocabulary the grammar removed per emitted token — near 1.0
    means the DFA, not the model, is doing the choosing."""
    import jax
    import jax.numpy as jnp

    from defer_tpu import obs
    from defer_tpu.constrain import compile_json_schema, compile_regex
    from defer_tpu.models.gpt import GptDecoder, SamplingParams
    from defer_tpu.models.llama import llama_config
    from defer_tpu.runtime.paged import serve_paged

    # Char-level vocabulary: id 0 is the empty string and doubles as
    # eos; then chars the constraints below can spell, a few
    # multi-char merges (the token-lift cases), filler to size.
    chars = list(
        "0123456789abcdefghijklmnopqrstuvwxyz"
        "{}[]\",:.- eE+ABCDEFGHIJKLMNOPQRSTUVWXYZ"
    )
    vocab = [""] + chars + ["ab", "12", '":', "},", "true", "false"]
    if len(vocab) > vocab_size:
        raise ValueError(
            f"vocab_size {vocab_size} too small for the "
            f"{len(vocab)}-token constraint vocabulary"
        )
    vocab += [f"<u{i}>" for i in range(vocab_size - len(vocab))]

    pattern = r"[0-9]+(\.[0-9]+)?"
    schema = {
        "type": "object",
        "properties": {
            "ok": {"type": "boolean"},
            "ids": {
                "type": "array",
                "items": {"type": "integer"},
                "minItems": 1,
                "maxItems": 3,
            },
        },
    }
    compiled = {}
    for name, build in (
        ("regex", lambda: compile_regex(pattern, vocab)),
        ("json", lambda: compile_json_schema(schema, vocab)),
    ):
        t0 = time.perf_counter()
        dfa = build()
        compiled[name] = (dfa, (time.perf_counter() - t0) * 1e3)
    constraints = {n: d for n, (d, _) in compiled.items()}

    cfg = llama_config(
        num_layers=num_layers,
        dim=dim,
        num_heads=num_heads,
        num_kv_heads=num_kv_heads,
        ffn_dim=dim * 2,
        vocab_size=vocab_size,
        max_len=max_len,
    )
    dec = GptDecoder(cfg, compute_dtype=jnp.bfloat16)
    params = dec.cast_params(dec.init(jax.random.key(0)))
    if devices:
        params = jax.device_put(params, devices[0])
    reqs = []
    for i in range(num_requests):
        t0 = 4 + (i * 5) % 12
        steps = 16 + (i * 7) % 16
        prompt = jax.random.randint(
            jax.random.fold_in(jax.random.key(1), i),
            (1, t0),
            1,
            cfg.vocab_size,
        )
        reqs.append((prompt, steps))
    total_tokens = sum(s for _, s in reqs)
    out: dict = {
        "config": {
            "num_layers": num_layers,
            "dim": dim,
            "heads": f"{num_heads}/{num_kv_heads}kv",
            "vocab_size": vocab_size,
            "max_len": max_len,
            "num_blocks": num_blocks,
            "block_size": block_size,
            "max_batch": max_batch,
            "requests": num_requests,
            "total_tokens": total_tokens,
            "decode_window": decode_window,
            "pattern": pattern,
        },
        "constraints": {},
    }
    reg = obs.get_registry()
    frac_key = dict(server="paged")
    free_tps = None
    for mode in modes:
        sp = (
            None
            if mode == "free"
            else SamplingParams(constraint=mode)
        )

        def run():
            before = reg.value(
                "defer_constrain_masked_frac", **frac_key
            ) or {"count": 0, "sum": 0.0}
            t0 = time.perf_counter()
            outs, stats = serve_paged(
                dec,
                params,
                reqs,
                num_blocks=num_blocks,
                block_size=block_size,
                max_batch=max_batch,
                eos_id=0,
                decode_window=decode_window,
                constraints=constraints,
                sampling=[sp] * len(reqs),
            )
            jax.block_until_ready(outs[-1])
            dt = time.perf_counter() - t0
            after = reg.value(
                "defer_constrain_masked_frac", **frac_key
            ) or {"count": 0, "sum": 0.0}
            dcount = after["count"] - before["count"]
            dsum = after["sum"] - before["sum"]
            return dt, stats, (dsum / dcount if dcount else 0.0)

        run()  # compile pass
        dt, stats, mean_frac = run()
        # Constrained streams stop at eos when the grammar is
        # satisfied, so normalize throughput by tokens actually
        # emitted, not the step budget.
        emitted = stats["constrained_tokens"] or total_tokens
        tps = emitted / dt
        if mode == "free":
            free_tps = tps
        rec = {
            "tokens_per_sec": round(tps, 1),
            "tps_vs_free": round(
                tps / free_tps if free_tps else 0.0, 3
            ),
            "constrained_tokens": stats["constrained_tokens"],
            "mean_masked_frac": round(mean_frac, 4),
            "dead_ends": stats["constraint_dead_ends"],
        }
        if mode in compiled:
            dfa, ms = compiled[mode]
            rec.update(
                compile_ms=round(ms, 2),
                dfa_states=dfa.num_states,
                dfa_table_kib=round(
                    dfa.transitions.nbytes / 1024, 1
                ),
            )
        out["constraints"][mode] = rec
    return out


def main() -> None:
    ap = argparse.ArgumentParser(
        description="paged-decode attention microbench (one JSON line)"
    )
    ap.add_argument(
        "--modes",
        default="",
        help="comma-separated subset of gathered,blockwise,pallas "
        "(default: gathered,blockwise; +pallas on native TPU)",
    )
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--dim", type=int, default=256)
    ap.add_argument("--heads", type=int, default=8)
    ap.add_argument("--kv-heads", type=int, default=4)
    ap.add_argument("--vocab", type=int, default=2048)
    ap.add_argument("--max-len", type=int, default=512)
    ap.add_argument("--blocks", type=int, default=49)
    ap.add_argument("--block-size", type=int, default=16)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument(
        "--window-sweep",
        action="store_true",
        help="run the fused-decode-window sweep (decode_window = "
        "--windows) instead of the attention-mode microbench",
    )
    ap.add_argument(
        "--windows",
        default="1,4,8,16",
        help="comma-separated decode_window values for --window-sweep",
    )
    ap.add_argument(
        "--mixed-sweep",
        action="store_true",
        help="run the mixed-mode continuous-batching sweep "
        "(prefill_budget = stall baseline + --mixed-budgets, "
        "open-loop Poisson arrivals) instead of the attention "
        "microbench",
    )
    ap.add_argument(
        "--mixed-budgets",
        default="64,128,256,inf",
        help="comma-separated prefill_budget values for "
        "--mixed-sweep (inf = unbounded; the stall baseline is "
        "always included)",
    )
    ap.add_argument(
        "--mixed-rate",
        type=float,
        default=16.0,
        help="open-loop arrival rate (requests/sec) for "
        "--mixed-sweep",
    )
    ap.add_argument(
        "--spec-sweep",
        action="store_true",
        help="run the paged speculative-decoding sweep (spec_k = "
        "--spec-ks crossed with the --spec-drafts draft axis) "
        "instead of the attention microbench",
    )
    ap.add_argument(
        "--spec-ks",
        default="0,2,4",
        help="comma-separated spec_k values for --spec-sweep "
        "(0 = non-speculative baseline)",
    )
    ap.add_argument(
        "--spec-drafts",
        default="self,trunc:L/2,trunc:L/4,width:1/2",
        help="comma-separated draft constructions for --spec-sweep: "
        "self (acceptance 1), trunc:L/n (layer-truncated via "
        "make_draft), width:p/q (head/FFN-pruned)",
    )
    ap.add_argument(
        "--spec-window",
        type=int,
        default=1,
        help="decode_window for --spec-sweep (W>1 prices the fused "
        "spec x window path: W rounds per host dispatch)",
    )
    ap.add_argument(
        "--kv-quant-sweep",
        action="store_true",
        help="run the KV-quantization sweep (kv_dtype = --kv-dtypes, "
        "over-subscribed Zipf prefix mix with the spill tier on) "
        "instead of the attention microbench",
    )
    ap.add_argument(
        "--kv-dtypes",
        default="fp,int8",
        help="comma-separated kv_dtype values for --kv-quant-sweep",
    )
    ap.add_argument(
        "--constrain-sweep",
        action="store_true",
        help="run the constrained-decoding sweep (the same request "
        "mix served free vs regex- vs JSON-schema-constrained, "
        "defer_tpu/constrain/) instead of the attention microbench",
    )
    ap.add_argument(
        "--constrain-modes",
        default="free,regex,json",
        help="comma-separated subset of free,regex,json for "
        "--constrain-sweep",
    )
    ap.add_argument(
        "--constrain-window",
        type=int,
        default=1,
        help="decode_window for --constrain-sweep (W>1 prices the "
        "constrained fused-window path)",
    )
    ap.add_argument(
        "--pp-sweep",
        action="store_true",
        help="run the pipeline-parallel serving sweep (pp_stages x "
        "in-flight microbatches = --pp-grid; points needing more "
        "devices than visible are skipped and reported) instead of "
        "the attention microbench",
    )
    ap.add_argument(
        "--pp-grid",
        default="s1_m1,s2_m2,s4_m2,s4_m4",
        help="comma-separated s{S}_m{M} points for --pp-sweep",
    )
    ap.add_argument(
        "--pp-window",
        type=int,
        default=8,
        help="decode_window for --pp-sweep (W rounds ride inside "
        "each in-flight microbatch, amortizing the pipeline ramps)",
    )
    ap.add_argument(
        "--tp-sweep",
        action="store_true",
        help="run the tensor-parallel serving sweep (model_axis = "
        "--tp-axes, axes that exceed the visible devices are skipped "
        "and reported) instead of the attention microbench",
    )
    ap.add_argument(
        "--tp-axes",
        default="1,2,4,8",
        help="comma-separated model-axis sizes for --tp-sweep",
    )
    args = ap.parse_args()
    shared = dict(
        num_layers=args.layers,
        dim=args.dim,
        num_heads=args.heads,
        num_kv_heads=args.kv_heads,
        vocab_size=args.vocab,
        max_len=args.max_len,
        num_blocks=args.blocks,
        block_size=args.block_size,
        max_batch=args.batch,
        num_requests=args.requests,
    )
    if args.kv_quant_sweep:
        # Same default-dropping as --spec-sweep: the sweep's own model
        # and (deliberately undersized) pool defaults win unless a
        # flag was explicitly overridden.
        arg_of = {
            "num_layers": "layers",
            "dim": "dim",
            "num_heads": "heads",
            "num_kv_heads": "kv_heads",
            "vocab_size": "vocab",
            "max_len": "max_len",
            "num_blocks": "blocks",
            "block_size": "block_size",
            "max_batch": "batch",
            "num_requests": "requests",
        }
        shared = {
            k: v
            for k, v in shared.items()
            if v != ap.get_default(arg_of[k])
        }
        dtypes = tuple(d for d in args.kv_dtypes.split(",") if d)
        rec = run_kv_quant_sweep(dtypes=dtypes, **shared)
    elif args.constrain_sweep:
        # Same default-dropping as --spec-sweep: the sweep's own tiny
        # char-vocab model defaults win unless a flag was explicitly
        # overridden.
        arg_of = {
            "num_layers": "layers",
            "dim": "dim",
            "num_heads": "heads",
            "num_kv_heads": "kv_heads",
            "vocab_size": "vocab",
            "max_len": "max_len",
            "num_blocks": "blocks",
            "block_size": "block_size",
            "max_batch": "batch",
            "num_requests": "requests",
        }
        shared = {
            k: v
            for k, v in shared.items()
            if v != ap.get_default(arg_of[k])
        }
        modes = tuple(
            m for m in args.constrain_modes.split(",") if m
        )
        rec = run_constrain_sweep(
            modes=modes,
            decode_window=args.constrain_window,
            **shared,
        )
    elif args.pp_sweep:
        # Same default-dropping as --spec-sweep: run_pp_sweep's own
        # (smaller) model defaults win unless a flag was explicitly
        # overridden.
        arg_of = {
            "num_layers": "layers",
            "dim": "dim",
            "num_heads": "heads",
            "num_kv_heads": "kv_heads",
            "vocab_size": "vocab",
            "max_len": "max_len",
            "num_blocks": "blocks",
            "block_size": "block_size",
            "max_batch": "batch",
            "num_requests": "requests",
        }
        shared = {
            k: v
            for k, v in shared.items()
            if v != ap.get_default(arg_of[k])
        }
        grid = []
        for pt in args.pp_grid.split(","):
            if not pt:
                continue
            s_part, _, m_part = pt.strip().partition("_")
            grid.append((int(s_part.lstrip("s")), int(m_part.lstrip("m"))))
        rec = run_pp_sweep(
            grid=tuple(grid), decode_window=args.pp_window, **shared
        )
    elif args.tp_sweep:
        # Same default-dropping as --spec-sweep: run_tp_sweep's own
        # model defaults (kv_heads=8 so every axis divides) win unless
        # a flag was explicitly overridden.
        arg_of = {
            "num_layers": "layers",
            "dim": "dim",
            "num_heads": "heads",
            "num_kv_heads": "kv_heads",
            "vocab_size": "vocab",
            "max_len": "max_len",
            "num_blocks": "blocks",
            "block_size": "block_size",
            "max_batch": "batch",
            "num_requests": "requests",
        }
        shared = {
            k: v
            for k, v in shared.items()
            if v != ap.get_default(arg_of[k])
        }
        axes = tuple(int(m) for m in args.tp_axes.split(",") if m)
        rec = run_tp_sweep(axes=axes, **shared)
    elif args.spec_sweep:
        # Let run_spec_sweep's own (smaller) model defaults win unless
        # the user explicitly overrode a flag: entries still at the
        # parser default are dropped.
        arg_of = {
            "num_layers": "layers",
            "dim": "dim",
            "num_heads": "heads",
            "num_kv_heads": "kv_heads",
            "vocab_size": "vocab",
            "max_len": "max_len",
            "num_blocks": "blocks",
            "block_size": "block_size",
            "max_batch": "batch",
            "num_requests": "requests",
        }
        shared = {
            k: v
            for k, v in shared.items()
            if v != ap.get_default(arg_of[k])
        }
        ks = tuple(int(k) for k in args.spec_ks.split(",") if k)
        drafts = tuple(d for d in args.spec_drafts.split(",") if d)
        rec = run_spec_sweep(
            ks=ks,
            drafts=drafts,
            decode_window=args.spec_window,
            **shared,
        )
    elif args.mixed_sweep:
        budgets = tuple(
            b if b == "inf" else int(b)
            for b in args.mixed_budgets.split(",")
            if b
        )
        rec = run_mixed_sweep(
            budgets=budgets, arrival_rate=args.mixed_rate, **shared
        )
    elif args.window_sweep:
        windows = tuple(
            int(k) for k in args.windows.split(",") if k
        )
        rec = run_window_sweep(windows=windows, **shared)
    else:
        modes = tuple(m for m in args.modes.split(",") if m)
        rec = run_microbench(modes=modes, **shared)
    print(json.dumps(rec))


if __name__ == "__main__":
    main()
