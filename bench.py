#!/usr/bin/env python
"""Benchmark harness. Runs in this process and prints ONE JSON line on
stdout (diagnostics on stderr), or a traceback and a non-zero exit.

Protocol (mirrors the reference's measurement design, reference
src/test.py:30-41 and src/local_infer.py:16-23, adapted to TPU):

  * headline metric: ResNet50 images/sec streamed through the DEFER
    pipeline across every visible TPU device (one stage per device;
    on a 1-chip host that is a single stage).
  * baseline: the paper's comparison point is an 8-node CPU chain that
    beat one CPU device by +53% (reference README.md:12). We measure a
    single-CPU-device ResNet50 loop with this same framework in a
    subprocess, and BASELINE.json's north star is >= 8x that.
    vs_baseline = ours / (8 x single-CPU images/sec), so >= 1.0 beats
    the north star.
  * microbatch size is a tunable of our pipeline (the reference streams
    batch-1 frames); we sweep and report the best, with the sweep on
    stderr.
  * mfu: achieved FLOP/s over the chip's bf16 peak, from analytic IR
    FLOPs (utils/flops.py) — the honesty check raw images/sec lacks.
  * extras: a multi-STAGE pipeline datapoint (round-robin on one chip —
    the reference's headline is pipelined throughput, reference
    src/test.py:30-41) and a single-chip SPMD BERT-base datapoint.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def cpu_baseline_subprocess(duration_s: float = 6.0) -> float:
    """Single-CPU-device ResNet50 images/sec, measured in a fresh
    process. This process holds the chip, and a chip belongs to one
    process: the child is safe only because JAX_PLATFORMS=cpu keeps it
    from ever loading libtpu, which it checks before it reports."""
    code = (
        "import jax, json;"
        "from defer_tpu.api import run_local_inference;"
        "from defer_tpu.models import get_model;"
        f"r = run_local_inference(get_model('resnet50'), duration_s={duration_s});"
        "assert 'libtpu' not in open('/proc/self/maps').read();"
        "print(json.dumps(r))"
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True,
        text=True,
        env=env,
        cwd=os.path.dirname(os.path.abspath(__file__)),
        timeout=600,
    )
    if out.returncode != 0:
        log(f"cpu baseline failed:\n{out.stderr[-2000:]}")
        return float("nan")
    return json.loads(out.stdout.strip().splitlines()[-1])["items_per_sec"]


def _measure(pipe, batch: int, target_s: float = 4.0) -> dict:
    import jax.numpy as jnp

    # Feed bf16 end-to-end: the host pipeline emits bf16
    # (imagenet_preprocess out_dtype), so no per-microbatch fp32->bf16
    # cast pass over HBM.
    x = jnp.ones((batch, 224, 224, 3), jnp.bfloat16)
    probe = pipe.throughput(x, num_microbatches=32)
    num_mb = max(32, int(32 * target_s / max(probe["seconds"], 1e-6)))
    return (
        probe if num_mb <= 32 else pipe.throughput(x, num_microbatches=num_mb)
    )


def bench_vit(devices) -> dict:
    """Single-chip ViT-S/16 streamed-pipeline throughput + MFU (the
    attention-era vision counterpart of the resnet50 headline)."""
    import jax
    import jax.numpy as jnp

    from defer_tpu.config import DeferConfig
    from defer_tpu.models import get_model
    from defer_tpu.parallel.mesh import pipeline_devices
    from defer_tpu.parallel.pipeline import Pipeline
    from defer_tpu.utils.flops import graph_flops, peak_flops

    model = get_model("vit_s16")
    params = model.init(jax.random.key(0))
    pipe = Pipeline(
        [model.graph],
        params,
        pipeline_devices(1, devices[:1]),
        DeferConfig(compute_dtype=jnp.bfloat16, max_inflight=64),
    )
    batch = 128
    stats = _measure(pipe, batch)
    fl = graph_flops(model.graph, params, (1, 224, 224, 3))
    peak = peak_flops(devices[0].device_kind)
    rec = {
        "images_per_sec": round(stats["items_per_sec"], 1),
        "batch": batch,
        "mfu": round(stats["items_per_sec"] * fl / peak, 4) if peak else None,
    }
    log(f"vit-s16 single-chip: {rec}")
    return rec


def bench_gpt_decode(devices) -> dict:
    """KV-cache decode: steady-state ms/token and tokens/sec for a
    GPT-2-small-shaped decoder (batch 8)."""
    from defer_tpu.parallel.transformer_stack import TransformerConfig

    return _bench_decode(
        devices,
        TransformerConfig(
            num_layers=12,
            dim=768,
            num_heads=12,
            ffn_dim=3072,
            vocab_size=32000,
            max_len=512,
            norm_style="pre",
        ),
        "gpt-small",
    )


def bench_llama_decode(devices) -> dict:
    """Llama-architecture decode (RMSNorm + rotary + GQA + SwiGLU) at
    ~1B scale: the modern serving shape, with the KV cache narrowed to
    the GQA head count."""
    from defer_tpu.models.llama import llama_config

    return _bench_decode(
        devices,
        llama_config(
            num_layers=16,
            dim=2048,
            num_heads=16,
            num_kv_heads=4,
            ffn_dim=5632,
            vocab_size=32000,
            max_len=512,
        ),
        "llama-1b-gqa",
        with_int8=True,
    )


def _bench_decode(devices, cfg, label: str, with_int8: bool = False) -> dict:
    import jax
    import jax.numpy as jnp

    from defer_tpu.models.gpt import GptDecoder, sample_token
    from defer_tpu.utils.roofline import peak_bandwidth

    dec = GptDecoder(cfg, compute_dtype=jnp.bfloat16)
    init = dec.init(jax.random.key(0))
    batch, prompt_len, steps = 8, 128, 64
    step = dec.make_step()
    ids = jax.random.randint(
        jax.random.key(1), (batch, prompt_len), 0, cfg.vocab_size
    )
    dh = cfg.dim // cfg.num_heads
    # The decode step contracts over the FULL static [.., max_len, ..]
    # cache buffer every token (masking happens after the read), so
    # that is the KV traffic — not just the live prefix.
    kv_bytes = (
        2 * cfg.num_layers * batch * cfg.kv_heads * cfg.max_len * dh * 2
    )
    bw = peak_bandwidth(devices[0].device_kind)

    def measure(params) -> dict:
        # Warm both compiled shapes on a throwaway cache so the
        # timings measure compute, not XLA compilation.
        warm_cache = dec.init_cache(batch)
        _, warm_cache = step(params, warm_cache, ids)
        _, warm_cache = step(
            params, warm_cache, jnp.zeros((batch, 1), ids.dtype)
        )
        # Block on the SECOND step's cache so no warm-up work is
        # still queued when the prefill timer starts.
        jax.block_until_ready(warm_cache)
        rng = jax.random.key(2)
        cache = dec.init_cache(batch)
        t0 = time.perf_counter()
        logits, cache = step(params, cache, ids)
        logits.block_until_ready()
        prefill_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        for _ in range(steps):
            nxt, rng = sample_token(logits[:, -1:], rng, 0.0)
            logits, cache = step(params, cache, nxt.astype(ids.dtype))
        logits.block_until_ready()
        per_tok = (time.perf_counter() - t0) / steps
        # Decode is HBM-read bound: per step the chip reads every
        # weight once (shared by the batch) plus the live KV prefix.
        # Achieved GB/s against HBM peak is decode's MFU analogue.
        param_bytes = sum(
            a.size * a.dtype.itemsize
            for a in jax.tree_util.tree_leaves(params)
        )
        achieved = (param_bytes + kv_bytes) / per_tok
        return {
            "ms_per_token": round(per_tok * 1e3, 3),
            "tokens_per_sec": round(batch / per_tok, 1),
            "batch": batch,
            "prefill_s": round(prefill_s, 3),
            "achieved_gbps": round(achieved / 1e9, 1),
            "hbm_frac": round(achieved / bw, 3) if bw else None,
        }

    # Serving storage: bf16 params (decode reads every weight per
    # token; fp32 storage would double the HBM traffic that bounds it).
    rec = measure(jax.device_put(dec.cast_params(init), devices[0]))
    log(f"{label} decode single-chip: {rec}")
    if with_int8:
        # Weight-only int8 (models/quant.py): half the weight bytes
        # again; quantize from the fp32 init for faithful scales.
        from defer_tpu.models.quant import quantize_decoder_params

        qrec = measure(
            jax.device_put(quantize_decoder_params(init), devices[0])
        )
        qrec.pop("batch", None)
        rec["int8"] = qrec
        log(f"{label} int8 decode single-chip: {qrec}")
    return rec


def bench_decode_server(devices) -> dict:
    """Continuous batching (runtime/decode_server.py): a mixed stream
    of requests through 4 slots on the ~1B llama shape — the serving
    number a per-request loop cannot reach (`tick_sharing` = solo
    steps per batched weight read)."""
    import jax

    from defer_tpu.models.gpt import GptDecoder
    from defer_tpu.models.llama import llama_config
    from defer_tpu.runtime.decode_server import DecodeServer

    import jax.numpy as jnp

    cfg = llama_config(
        num_layers=16,
        dim=2048,
        num_heads=16,
        num_kv_heads=4,
        ffn_dim=5632,
        vocab_size=32000,
        max_len=512,
    )
    dec = GptDecoder(cfg, compute_dtype=jnp.bfloat16)
    params = jax.device_put(
        dec.cast_params(dec.init(jax.random.key(0))), devices[0]
    )

    def requests():
        reqs = []
        for i in range(12):
            t0 = 16 + (i * 23) % 112
            steps = 16 + (i * 11) % 48
            prompt = jax.random.randint(
                jax.random.fold_in(jax.random.key(1), i),
                (1, t0),
                0,
                cfg.vocab_size,
            )
            reqs.append((prompt, steps))
        return reqs

    def run() -> tuple[float, Any]:
        srv = DecodeServer(dec, params, max_batch=4)
        rids = [srv.submit(p, s) for p, s in requests()]
        t0 = time.perf_counter()
        done = srv.run()
        jax.block_until_ready(done[rids[-1]])
        return time.perf_counter() - t0, srv

    run()  # compile pass (prefill buckets + tick shape)
    dt, srv = run()
    total = srv.solo_steps
    rec = {
        "requests": 12,
        "slots": 4,
        "tokens_per_sec": round(total / dt, 1),
        "ticks": srv.ticks,
        "tick_sharing": round(total / max(1, srv.ticks), 2),
    }
    log(f"decode server (llama-1b, continuous batching): {rec}")
    return rec


def bench_paged_server(devices) -> dict:
    """Paged-KV serving (runtime/paged.py): the decode-server workload
    through a block pool at a fraction of the flat-lane rows — the
    serving-memory headline (cache rows scale with request budgets,
    not slots x max_len) with throughput recorded alongside."""
    import jax
    import jax.numpy as jnp

    from defer_tpu import obs
    from defer_tpu.models.gpt import GptDecoder
    from defer_tpu.models.llama import llama_config
    from defer_tpu.runtime.paged import serve_paged

    cfg = llama_config(
        num_layers=16,
        dim=2048,
        num_heads=16,
        num_kv_heads=4,
        ffn_dim=5632,
        vocab_size=32000,
        max_len=512,
    )
    dec = GptDecoder(cfg, compute_dtype=jnp.bfloat16)
    params = jax.device_put(
        dec.cast_params(dec.init(jax.random.key(0))), devices[0]
    )
    reqs = []
    for i in range(8):
        t0 = 16 + (i * 23) % 112
        steps = 16 + (i * 11) % 48
        prompt = jax.random.randint(
            jax.random.fold_in(jax.random.key(1), i),
            (1, t0),
            0,
            cfg.vocab_size,
        )
        reqs.append((prompt, steps))

    def run():
        # Zero the process registry so the latency distributions below
        # cover only this pass (the compile pass would skew TTFT).
        obs.reset()
        t0 = time.perf_counter()
        outs, stats = serve_paged(
            dec, params, reqs, num_blocks=49, block_size=16, max_batch=4
        )
        jax.block_until_ready(outs[-1])
        return time.perf_counter() - t0, stats

    run()  # compile pass
    dt, stats = run()
    total = sum(s for _, s in reqs)
    pool_rows = stats["pool_blocks"] * stats["block_size"]
    reg = obs.get_registry()
    lab = {"server": "paged"}
    ttft = reg.histogram("defer_ttft_seconds", labels=lab)
    itl = reg.histogram("defer_itl_seconds", labels=lab)
    rec = {
        "requests": len(reqs),
        "slots": 4,
        "tokens_per_sec": round(total / dt, 1),
        "pool_rows": pool_rows,
        "flat_rows": stats["flat_equivalent_rows"],
        "cache_mem_ratio": round(
            pool_rows / stats["flat_equivalent_rows"], 3
        ),
        "peak_blocks": stats["peak_blocks"],
        # Host-side dispatch latency (see ARCHITECTURE.md
        # "Observability" for the async-dispatch caveat).
        "ttft_p50_ms": round(1e3 * ttft.approx_quantile(0.5), 2),
        "itl_p50_ms": round(1e3 * itl.approx_quantile(0.5), 3),
        "tokens_counted": reg.value(
            "defer_tokens_generated_total", **lab
        ),
    }
    log(f"paged server (llama-1b, block pool): {rec}")
    return rec


def bench_paged_attention(devices) -> dict:
    """Paged-decode attention modes (scripts/bench_paged.py): the same
    request mix through gathered vs block-native attention, pricing
    tokens/sec and the per-tick K/V rows actually read. The ratio is
    the bandwidth story; the obs counters make it exact."""
    import importlib.util
    import os

    path = os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        "scripts",
        "bench_paged.py",
    )
    spec = importlib.util.spec_from_file_location("bench_paged", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    rec = mod.run_microbench(devices)
    log(f"paged attention modes: {rec}")
    return rec


def bench_decode_window(devices) -> dict:
    """Fused decode windows (scripts/bench_paged.py): the same request
    mix served at decode_window = K for K in {1,4,8,16}, pricing host
    dispatches per token against tokens/sec. Dispatches-per-token
    falls toward 1/K; on dispatch-bound tiers the tokens/sec follows."""
    import importlib.util
    import os

    path = os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        "scripts",
        "bench_paged.py",
    )
    spec = importlib.util.spec_from_file_location("bench_paged", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    rec = mod.run_window_sweep(devices)
    log(f"decode window sweep: {rec}")
    return rec


def bench_mixed_serving(devices) -> dict:
    """Mixed-mode continuous batching (scripts/bench_paged.py): the
    same request mix offered open-loop, served with stall-mode
    admission vs prefill_budget in {64,128,256,inf}, pricing the live
    slots' ITL p99 (where admission-prefill stalls land) against TTFT
    and the decode-stall fraction per budget."""
    import importlib.util
    import os

    path = os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        "scripts",
        "bench_paged.py",
    )
    spec = importlib.util.spec_from_file_location("bench_paged", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    rec = mod.run_mixed_sweep(devices)
    log(f"mixed serving sweep: {rec}")
    return rec


def bench_speculative(devices) -> dict:
    """Paged speculative decoding (scripts/bench_paged.py): the same
    request mix served at spec_k in {0,2,4} crossed with the draft
    axis (self | trunc:L/2 | trunc:L/4 | width:1/2, built with
    models/transplant.py make_draft), pricing MEASURED acceptance,
    tokens/sec and dispatches-per-token per (draft, k) — the
    acceptance-vs-speedup frontier. The self-draft column isolates
    the dispatch-amortization term (acceptance 1.0); the truncated/
    pruned columns price what a real small draft pays."""
    import importlib.util
    import os

    path = os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        "scripts",
        "bench_paged.py",
    )
    spec = importlib.util.spec_from_file_location("bench_paged", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    rec = mod.run_spec_sweep(devices)
    log(f"speculative sweep: {rec}")
    return rec


def bench_tp_serving(devices) -> dict:
    """Tensor-parallel paged serving (scripts/bench_paged.py): the
    same request mix on a {"model": m} mesh for m in {1,2,4,8},
    pricing tokens/sec and tokens-per-dispatch against per-shard KV
    rows read. Host dispatches per token must not move with m; KV rows
    per shard fall as 1/m — the mesh-labeled obs counters make both
    exact."""
    import importlib.util
    import os

    path = os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        "scripts",
        "bench_paged.py",
    )
    spec = importlib.util.spec_from_file_location("bench_paged", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    rec = mod.run_tp_sweep(devices)
    log(f"tp serving sweep: {rec}")
    return rec


def bench_pp_serving(devices) -> dict:
    """Pipeline-parallel paged serving (scripts/bench_paged.py): the
    same request mix with the layer stack cut into S stages — one
    device and one KV-pool slice each — at M in-flight microbatch
    groups, for (S, M) in {1,2,4} x {2,4}. Prices tokens/sec against
    the MEASURED dispatch-schedule bubble fraction and per-stage
    occupancy; per-stage pool bytes must sum to ~the S=1 pool. The
    [contract.pp] budget gates the s4_m4 bubble fraction."""
    import importlib.util
    import os

    path = os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        "scripts",
        "bench_paged.py",
    )
    spec = importlib.util.spec_from_file_location("bench_paged", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    rec = mod.run_pp_sweep(devices)
    log(f"pp serving sweep: {rec}")
    return rec


def bench_kv_quant(devices) -> dict:
    """KV quantization + spill tier (scripts/bench_paged.py): the same
    over-subscribed Zipf prefix mix served with a fp pool vs an
    int8+scales pool, spill tier on — pricing tokens/sec,
    resident-requests-per-pool-MiB (the capacity headline: int8 holds
    the same blocks in itemsize-fold fewer bytes) and the spill
    revival rate, with prefill tokens vs a no-spill baseline showing
    the rows revivals saved."""
    import importlib.util
    import os

    path = os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        "scripts",
        "bench_paged.py",
    )
    spec = importlib.util.spec_from_file_location("bench_paged", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    rec = mod.run_kv_quant_sweep(devices)
    log(f"kv quant sweep: {rec}")
    return rec


def bench_constrain(devices) -> dict:
    """Constrained decoding (scripts/bench_paged.py +
    defer_tpu/constrain/): the same request mix served free vs
    regex-constrained vs JSON-schema-constrained — pricing the
    on-device DFA mask fold against the free baseline, the one-off
    host compile (regex -> char DFA -> token lift -> prune) and the
    mean fraction of the vocabulary the grammar removed per token."""
    import importlib.util
    import os

    path = os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        "scripts",
        "bench_paged.py",
    )
    spec = importlib.util.spec_from_file_location("bench_paged", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    rec = mod.run_constrain_sweep(devices)
    log(f"constrain sweep: {rec}")
    return rec


def bench_disagg(devices) -> dict:
    """Disaggregated serving (scripts/bench_disagg.py): the same
    request mix through monolithic serve_paged and split serve_disagg
    (prefill worker over loopback), pricing tokens/sec and TTFT
    against the KV bytes shipped per request — lossless vs int8
    transfer. The split/monolithic ratio and the wire bytes are the
    headline; off-TPU the absolute throughput is noise."""
    import importlib.util
    import os

    path = os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        "scripts",
        "bench_disagg.py",
    )
    spec = importlib.util.spec_from_file_location("bench_disagg", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    rec = mod.run_microbench(devices)
    log(f"disaggregated serving: {rec}")
    return rec


def bench_fleet(devices) -> dict:
    """Fleet serving (scripts/bench_fleet.py): a bursty, prefix-shared
    request mix over N replica paged servers under prefix-aware vs
    round-robin routing, plus an overload flood against a tight SLO.
    Headlines: the radix hit-rate gap between the two policies (the
    value of routing on cache locality) and shed rate with bounded
    queue-wait p99 under overload (graceful degradation)."""
    import importlib.util
    import os

    path = os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        "scripts",
        "bench_fleet.py",
    )
    spec = importlib.util.spec_from_file_location("bench_fleet", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    rec = mod.run_microbench(devices)
    log(f"fleet serving: {rec}")
    return rec


def bench_bert(devices) -> dict:
    """Single-chip SPMD BERT-base forward throughput + MFU."""
    import jax
    import jax.numpy as jnp

    from defer_tpu.models.bert import SpmdBert
    from defer_tpu.parallel.mesh import make_mesh
    from defer_tpu.parallel.transformer_stack import TransformerConfig
    from defer_tpu.utils.flops import peak_flops, transformer_flops

    cfg = TransformerConfig(
        num_layers=12,
        dim=768,
        num_heads=12,
        ffn_dim=3072,
        vocab_size=30522,
        max_len=512,
    )
    mesh = make_mesh({"stage": 1}, devices[:1])
    sb = SpmdBert(mesh, cfg, compute_dtype=jnp.bfloat16)
    params = sb.init(jax.random.key(0))
    batch, seq, num_mb = 16, 128, 8
    ids = jax.random.randint(
        jax.random.key(1), (num_mb, batch, seq), 0, cfg.vocab_size
    )
    step = sb.make_step()
    step(params, ids).block_until_ready()  # compile
    t0 = time.perf_counter()
    iters = 10
    out = None
    for _ in range(iters):
        out = step(params, ids)
    out.block_until_ready()
    dt = time.perf_counter() - t0
    tokens_per_sec = iters * num_mb * batch * seq / dt
    flops = transformer_flops(
        num_layers=cfg.num_layers,
        dim=cfg.dim,
        ffn_dim=cfg.ffn_dim,
        seq_len=seq,
        batch=1,
    ) / seq  # per token
    peak = peak_flops(devices[0].device_kind)
    mfu = tokens_per_sec * flops / peak if peak else None
    rec = {
        "tokens_per_sec": round(tokens_per_sec, 1),
        "seq_len": seq,
        "batch": batch,
        "mfu": round(mfu, 4) if mfu is not None else None,
    }
    log(f"bert-base spmd single-chip: {rec}")
    return rec


def bench_pallas_attention(devices) -> dict:
    """Pallas flash attention vs the XLA attention path, long-sequence
    causal self-attention. Runs wherever the kernel compiles: a TPU."""
    import jax
    import jax.numpy as jnp

    from defer_tpu.ops.attention import multi_head_attention

    b, s, h, dh = 4, 2048, 16, 64
    keys = jax.random.split(jax.random.key(0), 3)
    q, k, v = (
        jax.random.normal(kk, (b, s, h * dh), jnp.bfloat16) for kk in keys
    )

    def timed(use_pallas: bool) -> float:
        fn = jax.jit(
            lambda q, k, v: multi_head_attention(
                q, k, v, num_heads=h, causal=True, use_pallas=use_pallas
            )
        )
        fn(q, k, v).block_until_ready()  # compile
        iters = 20
        t0 = time.perf_counter()
        out = None
        for _ in range(iters):
            out = fn(q, k, v)
        out.block_until_ready()
        return (time.perf_counter() - t0) / iters

    t_pallas = timed(True)
    t_xla = timed(False)
    rec = {
        "batch": b,
        "seq_len": s,
        "heads": h,
        "pallas_ms": round(t_pallas * 1e3, 3),
        "xla_ms": round(t_xla * 1e3, 3),
        "speedup": round(t_xla / t_pallas, 3),
    }
    log(f"pallas flash attention: {rec}")
    return rec


def run_bench() -> dict:
    import jax
    import jax.numpy as jnp

    from defer_tpu.config import DeferConfig
    from defer_tpu.graph.partition import partition
    from defer_tpu.models import get_model
    from defer_tpu.parallel.mesh import describe_topology, pipeline_devices
    from defer_tpu.parallel.pipeline import Pipeline
    from defer_tpu.utils.flops import graph_flops, peak_flops

    devices = jax.devices()
    log(f"backend: {jax.default_backend()}, devices: {devices}")
    topo = describe_topology()
    log(f"topology: {topo}")

    model = get_model("resnet50")
    params = model.init(jax.random.key(0))
    n_dev = topo["num_devices"]
    n_stages = max(n_dev, 1)
    cuts = model.default_cuts(n_stages)
    stages = partition(model.graph, cuts) if cuts else [model.graph]
    pipe = Pipeline(
        stages,
        params,
        pipeline_devices(n_stages),
        DeferConfig(compute_dtype=jnp.bfloat16, max_inflight=128),
    )
    log(f"pipeline: {n_stages} stage(s) over {n_dev} device(s), cuts={cuts}")

    from defer_tpu.utils.profiling import TRACE_ENV, trace

    if os.environ.get(TRACE_ENV):
        log(f"device tracing enabled -> {os.environ[TRACE_ENV]}")

    flops_per_image = graph_flops(model.graph, params, (1, 224, 224, 3))
    chip_peak = peak_flops(topo["device_kind"])
    try:
        # Analytic roofline triage (host-side only, no device work):
        # says WHY the MFU number is what it is. Byte accounting must
        # match the pipeline's actual dtypes (bf16 activations AND
        # params) or intensity is off 2x against the bf16 peak.
        from defer_tpu.parallel.pipeline import cast_params_to_storage
        from defer_tpu.utils.roofline import format_report, roofline_report

        log(
            format_report(
                roofline_report(
                    model.graph,
                    cast_params_to_storage(
                        params, DeferConfig(compute_dtype=jnp.bfloat16)
                    ),
                    (128, 224, 224, 3),
                    topo["device_kind"],
                    input_dtype=jnp.bfloat16,
                    top=4,
                )
            )
        )
    except Exception as e:  # noqa: BLE001 — diagnostics only
        log(f"roofline report failed ({type(e).__name__}: {e})")
    # The pipeline spans every device — achieved FLOP/s is aggregate,
    # so MFU divides by the aggregate peak.
    peak = chip_peak * max(n_dev, 1) if chip_peak else None
    log(
        f"resnet50 analytic fwd FLOPs/image: {flops_per_image / 1e9:.2f} G; "
        f"peak[{topo['device_kind']} x {n_dev}]: "
        + (f"{peak / 1e12:.0f} TFLOP/s" if peak else "unknown")
    )

    best_ips = 0.0
    best_batch = None
    for batch in (1, 8, 32, 64, 128, 256):
        try:
            stats = _measure(pipe, batch)
        except Exception as e:  # noqa: BLE001 — keep the best-so-far
            log(f"batch {batch} failed ({type(e).__name__}: {e}); "
                "keeping best so far")
            break
        mfu = stats["items_per_sec"] * flops_per_image / peak if peak else None
        log(
            f"batch {batch}: {stats['items_per_sec']:.1f} images/sec "
            f"({stats['microbatches']} microbatches in "
            f"{stats['seconds']:.2f}s)"
            + (f", mfu {mfu:.3f}" if mfu is not None else "")
        )
        if stats["items_per_sec"] > best_ips:
            best_ips = stats["items_per_sec"]
            best_batch = batch
        elif stats["items_per_sec"] < 0.9 * best_ips:
            log("throughput declining; stopping sweep")
            break
    if best_batch is None:
        raise RuntimeError("no batch size measured successfully")

    # chip_seconds_per_1k_images is the TPU-native stand-in for the
    # paper's per-node energy claim (reference README.md:12, -63%/node):
    # total chip time burned per 1000 images, lower is better.
    result = {
        "metric": (
            f"resnet50_images_per_sec_pipeline_{n_stages}stage"
            f"_batch{best_batch}"
        ),
        "value": round(best_ips, 2),
        "unit": "images/sec",
        "vs_baseline": None,
        "mfu": round(best_ips * flops_per_image / peak, 4) if peak else None,
        "chip_seconds_per_1k_images": round(n_dev * 1000.0 / best_ips, 2),
        "platform": topo["backend"],
        "multistage": None,
        "data_parallel": None,
        "stage_mfu": None,
        "bert_base": None,
        "vit_s16": None,
        "gpt_decode": None,
        "llama_decode": None,
        "decode_server": None,
        "paged_server": None,
        "paged_attention": None,
        "decode_window": None,
        "mixed_serving": None,
        "speculative": None,
        "tp_serving": None,
        "pp_serving": None,
        "disagg": None,
        "pallas_attention": None,
    }

    # The pipeline sweep's own result, before any other strategy can
    # take over the headline — the multistage datapoint below must
    # report THIS, not whichever strategy won.
    pipe_ips = best_ips
    pipe_batch = best_batch

    # Multi-chip: batch-sharded SPMD data parallelism (the idiomatic
    # TPU strategy when the model fits one chip) usually beats an
    # n-device pipeline for raw throughput — measure it and let the
    # best strategy carry the headline.
    if n_dev > 1:
        try:
            from defer_tpu.parallel.data_parallel import ShardedInference

            dp = ShardedInference(
                model.graph,
                params,
                devices,
                DeferConfig(compute_dtype=jnp.bfloat16, max_inflight=128),
            )
            dp_batch = best_batch * n_dev
            stats = _measure(dp, dp_batch)
            dp_ips = stats["items_per_sec"]
            result["data_parallel"] = {
                "shards": n_dev,
                "images_per_sec": round(dp_ips, 1),
                "batch": dp_batch,
                "mfu": round(dp_ips * flops_per_image / peak, 4)
                if peak
                else None,
            }
            log(f"data-parallel: {result['data_parallel']}")
            if dp_ips > best_ips:
                result["metric"] = (
                    f"resnet50_images_per_sec_dp{n_dev}shard_batch{dp_batch}"
                )
                result["value"] = round(dp_ips, 2)
                result["mfu"] = result["data_parallel"]["mfu"]
                result["chip_seconds_per_1k_images"] = round(
                    n_dev * 1000.0 / dp_ips, 2
                )
                best_ips = dp_ips
        except Exception as e:  # noqa: BLE001 — extra datapoint only
            log(f"data-parallel probe failed ({type(e).__name__}: {e})")

    # Per-stage latency probe, under a device trace when requested
    # ($DEFER_TPU_TRACE=dir captures a TensorBoard profile of it).
    # amortized_s leads: it is the pipeline-relevant per-call cost;
    # p50 includes a host sync round trip per call.
    try:
        from defer_tpu.utils.flops import flops_by_node

        per_node = flops_by_node(
            model.graph, params, (best_batch, 224, 224, 3)
        )
        stage_fl = [
            sum(per_node[n.name] for n in s.nodes if n.op != "input")
            for s in stages
        ]
        with trace():
            lat = pipe.probe_stage_latencies(
                jnp.ones((best_batch, 224, 224, 3), jnp.bfloat16), iters=20
            )
        stage_recs = []
        for r, fl in zip(lat, stage_fl):
            stage_mfu = (
                fl / r["amortized_s"] / chip_peak if chip_peak else None
            )
            stage_recs.append(
                {
                    "stage": r["stage"],
                    "amortized_ms": round(r["amortized_s"] * 1e3, 3),
                    "mfu": round(stage_mfu, 4)
                    if stage_mfu is not None
                    else None,
                }
            )
            log(
                f"stage {r['stage']} amortized "
                f"{r['amortized_s'] * 1e3:.2f} ms"
                + (f" (mfu {stage_mfu:.3f})" if stage_mfu is not None else "")
                + f" (sync p50 {r['p50_s'] * 1e3:.2f} ms "
                f"max {r['max_s'] * 1e3:.2f} ms) on {r['device']}"
            )
        result["stage_mfu"] = stage_recs
    except Exception as e:  # noqa: BLE001 — diagnostics only
        log(f"stage latency probe failed ({type(e).__name__}: {e})")

    # The pipelined measurement the reference headlines (multi-stage
    # chain, reference src/test.py:30-41): round-robin the stages over
    # the available chips to quantify multi-stage dispatch overhead
    # even on a 1-chip host.
    if n_dev == 1:
        try:
            ms_stages = 4
            ms_cuts = model.default_cuts(ms_stages)
            ms_pipe = Pipeline(
                partition(model.graph, ms_cuts),
                params,
                pipeline_devices(ms_stages),
                DeferConfig(compute_dtype=jnp.bfloat16, max_inflight=128),
            )
            stats = _measure(ms_pipe, best_batch)
            result["multistage"] = {
                "stages": ms_stages,
                "images_per_sec": round(stats["items_per_sec"], 1),
                "batch": best_batch,
            }
            log(f"multi-stage pipeline: {result['multistage']}")
        except Exception as e:  # noqa: BLE001 — extra datapoint only
            log(f"multi-stage probe failed ({type(e).__name__}: {e})")
    elif n_stages > 1:
        # The pipeline sweep itself was the multi-stage measurement.
        result["multistage"] = {
            "stages": n_stages,
            "images_per_sec": round(pipe_ips, 1),
            "batch": pipe_batch,
        }
    log("measuring single-CPU-device baseline (subprocess)...")
    cpu_ips = cpu_baseline_subprocess()
    log(f"cpu single-device: {cpu_ips:.2f} images/sec")
    north_star = 8.0 * cpu_ips if cpu_ips == cpu_ips else float("nan")
    if north_star == north_star:
        result["vs_baseline"] = round(best_ips / north_star, 3)

    # Attention-era extras last (newest sections).
    sections = [
        ("vit_s16", bench_vit),
        ("gpt_decode", bench_gpt_decode),
        ("llama_decode", bench_llama_decode),
        ("decode_server", bench_decode_server),
        ("paged_server", bench_paged_server),
        ("paged_attention", bench_paged_attention),
        ("decode_window", bench_decode_window),
        ("mixed_serving", bench_mixed_serving),
        ("speculative", bench_speculative),
        ("tp_serving", bench_tp_serving),
        ("pp_serving", bench_pp_serving),
        ("kv_quant", bench_kv_quant),
        ("constrain", bench_constrain),
        ("disagg", bench_disagg),
        ("fleet", bench_fleet),
        ("bert_base", bench_bert),
    ]
    # The Mosaic-kernel section runs where the kernel compiles.
    from defer_tpu.ops.attention import _pallas_available

    if _pallas_available():
        sections.append(("pallas_attention", bench_pallas_attention))
    # Every section's JSON records where it ran: device kind from
    # the live topology, mesh shape when the section itself swept
    # one (tp_serving), else explicit null — so a perf number can
    # never be read without its hardware context.
    for key, fn in sections:
        try:
            rec = fn(devices)
            if isinstance(rec, dict):
                rec.setdefault("device_kind", topo["device_kind"])
                rec.setdefault("mesh_shape", None)
            result[key] = rec
        except Exception as e:  # noqa: BLE001 — extra datapoint only
            log(f"{key} probe failed ({type(e).__name__}: {e})")

    # Static self-check rides along so the artifact records lint drift
    # next to the perf numbers (also published on the obs registry as
    # defer_analysis_findings_total{rule=...}). Sub-second, pure AST.
    # The perf-contract budgets cross-check against THIS round's
    # numbers (the in-memory result dict), so a regression the bench
    # just measured is flagged in the same artifact that measured it.
    try:
        from defer_tpu.analysis import analyze_paths
        from defer_tpu.analysis.runner import record_findings

        root = os.path.dirname(os.path.abspath(__file__))
        pkg = os.path.join(root, "defer_tpu")
        budgets = os.path.join(root, "budgets.toml")
        rep = analyze_paths(
            [pkg],
            strict=True,
            budget=budgets if os.path.exists(budgets) else None,
            bench=result,
        )
        record_findings(rep)
        result["analysis"] = {
            "findings": len(rep.findings),
            "suppressed": len(rep.suppressed),
            "counts": rep.counts,
            "suppressed_by_rule": rep.suppressed_by_rule,
        }
        if rep.budget is not None:
            result["analysis"]["budget"] = {
                c["contract"]: {
                    "status": c["status"], "value": c["value"],
                }
                for c in rep.budget["contracts"]
            }
    except Exception as e:  # noqa: BLE001 — extra datapoint only
        log(f"analysis probe failed ({type(e).__name__}: {e})")

    return result


def main() -> None:
    print(json.dumps(run_bench()), flush=True)


if __name__ == "__main__":
    main()
