#!/usr/bin/env python
"""The quickest proof that the system still starts on the chip.

    python chip_smoke.py            # one TPU chip, whatever the host has
    python chip_smoke.py --chips 4  # adds the tensor-parallel, pipeline-
                                    # parallel and four-stage legs

One process drives the two main paths once, through the entry points a
user calls, and checks what comes out by the repo's own means:

  kernels   every Pallas kernel in ops/pallas_attention.py, compiled by
            Mosaic (interpret=False) at the serving leg's shapes, against
            its XLA reference;
  serve     `serve_paged` over `GptDecoder(mistral_config(num_layers=L))`
            — Mistral-7B-v0.1's published widths, depth cut to the largest
            L that fits one chip beside the KV pool, bf16, random weights
            from a seed — answering 8 requests; then one request's prefill
            and decode logits against the decoder's un-cached full forward
            in float32, and the three attention paths against each other;
  pipeline  `DEFER().run_defer` streaming ResNet50 batches through one
            stage, against a plain `jax.jit` of the same graph.

Any failed check is an exception: a traceback, a non-zero exit and no
result line. Where JAX finds no TPU the script stops at its first act.
The last line of standard output is one JSON object with exactly two
keys, `{"ok": true, "device": {"platform", "kind", "count"}}`; the line
before it (`details: {...}`) carries L, the legs with their times and the
compile cache's directory and entry counts.
The seconds it prints are set-up information (first pass, compilation
included, and an identical second pass), not measurements of the system.

`--debug-cpu-tiny` runs the same legs at toy sizes with the kernels in
interpret mode, for debugging on a CPU; it prints no result line.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import queue
import sys
import threading
import time

SEED = 0

# Tolerances, as a share of the reference's largest magnitude
# (max|got - want| / max|want|), each with its reason.
#
# Kernels: inputs and outputs are bf16 (8 significant bits: one rounding
# is up to 2^-9 = 0.2%). The kernel rounds its output once; the MXU
# rounds the scaled f32 query to bf16 before the score matmul, so a
# score of magnitude 5 moves by up to 0.01 and its softmax weight by 1%.
# The float32 reference runs at "highest" precision and rounds nothing.
KERNEL_TOL = 2e-2
# The paged server against the float32 full forward: the same weights
# (rounded to bf16 once, then widened for the reference), but every
# activation of every layer is rounded to bf16 on the serving side.
# tests/test_kv_quant.py bounds a lossy int8 cache at 5% of the logit
# scale; bf16 activations through L layers must do no worse.
MODEL_TOL = 5e-2
# Attention paths, and tensor/pipeline-parallel runs, against the
# one-chip "gathered" run: same bf16 weights and activations, a
# different order of summation (block folds, psums), hence different
# bf16 roundings that later layers amplify.
PATH_TOL = 3e-2
# ResNet50 probabilities (bf16, 1000 classes, each below 1).
PROB_TOL = 2e-2


@dataclasses.dataclass(frozen=True)
class Sizes:
    """Everything that differs between the chip run and the CPU debug
    run. `model` overrides mistral_config's published widths."""

    model: dict
    prompts: tuple[int, ...]
    steps: tuple[int, ...]
    k_batch: int  # kernels leg: slots
    k_heads: tuple[int, int]  # (Hq, Hkv)
    k_cache: int  # rows of cache per slot
    k_flash: int  # flash_attention sequence length
    k_chunk: int  # paged_flash_prefill window
    image_batch: int
    image_batches: int


FULL = Sizes(
    model={},
    # Admission pads a prompt to a power of two: three prefill programs.
    prompts=(128, 160, 200, 256, 600, 768, 900, 1024),
    steps=(32, 40, 48, 64, 36, 56, 44, 64),
    k_batch=8,
    k_heads=(32, 8),
    k_cache=4096,
    k_flash=4096,
    k_chunk=128,
    image_batch=64,
    image_batches=4,
)
TINY = Sizes(
    model=dict(
        dim=128, num_heads=8, num_kv_heads=4, ffn_dim=256,
        vocab_size=256, max_len=128, window=128,
    ),
    prompts=(8, 12, 16, 24, 32, 40, 20, 10),
    steps=(4, 5, 6, 4, 3, 5, 6, 4),
    k_batch=2,
    k_heads=(8, 2),
    k_cache=256,
    k_flash=256,
    k_chunk=16,
    image_batch=2,
    image_batches=2,
)
BLOCK_SIZE = 16
MAX_BATCH = 8
HEAD_DIM = 128


def say(msg: str) -> None:
    print(msg, flush=True)


def rel_err(got, want) -> float:
    import numpy as np

    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    if got.shape != want.shape:
        raise AssertionError(f"shape {got.shape} != {want.shape}")
    if not np.isfinite(got).all():
        raise AssertionError("non-finite values")
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


def check(name: str, got, want, tol: float) -> float:
    err = rel_err(got, want)
    say(f"  {name}: max|d|/max|ref| = {err:.2e} (limit {tol:.0e})")
    if err > tol:
        raise AssertionError(f"{name}: {err:.3e} exceeds {tol:.0e}")
    return err


def cache_entries(path: str) -> int:
    if not os.path.isdir(path):
        return 0
    return sum(name.endswith("-cache") for name in os.listdir(path))


def result_line(devices) -> str:
    """The last line of standard output: `ok` and `device`, the device as
    JAX reports it, and no other key. Whoever reads the line checks its
    shape exactly; everything else goes in the `details:` line above it."""
    dev = devices[0]
    device = {
        "platform": dev.platform,
        "kind": dev.device_kind,
        "count": len(devices),
    }
    return json.dumps({"ok": True, "device": device})


def two_passes(label: str, where: str, fn):
    """Run `fn` twice; returns its second result and the two wall times
    (the first includes tracing and compilation)."""
    t0 = time.perf_counter()
    fn()
    first = time.perf_counter() - t0
    t0 = time.perf_counter()
    out = fn()
    warm = time.perf_counter() - t0
    say(f"  {label}: first pass {first:.2f}s, second pass {warm:.2f}s [{where}]")
    return out, first, warm


# -- kernels ---------------------------------------------------------------


def leg_kernels(sz: Sizes, interpret: bool, where: str) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from defer_tpu.models.quant import quantize_symmetric
    from defer_tpu.ops.attention import attention_reference
    from defer_tpu.ops.pallas_attention import (
        flash_attention,
        flash_decode,
        paged_flash_decode,
        paged_flash_prefill,
    )
    from defer_tpu.runtime.paged import (
        _blockwise_attend,
        _blockwise_attend_mt,
        _pool_arr,
    )

    b, (hq, hkv), d, bs = sz.k_batch, sz.k_heads, HEAD_DIM, BLOCK_SIZE
    g = hq // hkv
    s, mb = sz.k_cache, sz.k_cache // BLOCK_SIZE
    nb = b * mb + 1
    window = s  # Mistral's window equals the cache these shapes hold
    bf = jnp.bfloat16
    ks = iter(jax.random.split(jax.random.key(SEED), 16))

    def normal(shape):
        return jax.random.normal(next(ks), shape, bf)

    def f32(x):
        return x.astype(jnp.float32)

    def run(name, kernel, reference, *args):
        jitted = jax.jit(kernel)
        got, _, _ = two_passes(
            name, where, lambda: jax.block_until_ready(jitted(*args))
        )
        with jax.default_matmul_precision("highest"):
            want = jax.jit(reference)(*args)
        check(name, got, want, KERNEL_TOL)

    # flash_attention: causal prefill-shaped self-attention.
    sf = sz.k_flash
    q, k, v = (normal((1, hkv, sf, d)) for _ in range(3))
    run(
        f"flash_attention S={sf}",
        lambda q, k, v: flash_attention(
            q, k, v, causal=True, interpret=interpret
        ),
        lambda q, k, v: attention_reference(
            f32(q), f32(k), f32(v), causal=True
        ),
        q, k, v,
    )

    # Per-slot depths, from one block to the whole cache.
    pos = jnp.asarray(np.linspace(bs - 1, s - 1, b).astype(np.int32))

    # flash_decode: one query token per slot against a contiguous cache.
    q1 = normal((b, hq, d))
    kc, vc = normal((b, hkv, s, d)), normal((b, hkv, s, d))

    def decode_reference(q, k, v, pos):
        live = jnp.arange(s)[None, :] <= pos[:, None]
        bias = jnp.where(live, 0.0, -jnp.inf)[:, None, None, :]
        out = attention_reference(
            f32(q)[:, :, None, :],
            jnp.repeat(f32(k), g, axis=1),
            jnp.repeat(f32(v), g, axis=1),
            bias=bias,
        )
        return out[:, :, 0, :]

    run(
        f"flash_decode S={s}",
        lambda q, k, v, p: flash_decode(
            q, k, v, p, window=window, interpret=interpret
        ),
        decode_reference,
        q1, kc, vc, pos,
    )

    # The paged kernels read a shared pool through per-slot tables.
    pool_k, pool_v = normal((nb, hkv, bs, d)), normal((nb, hkv, bs, d))
    perm = np.random.default_rng(SEED).permutation(np.arange(1, nb))
    tables = jnp.asarray(perm.reshape(b, mb).astype(np.int32))

    def int8_pool(pool):
        qv, sc = quantize_symmetric(f32(pool), axis=(-2, -1))
        return {"q": qv, "s": sc}

    pools = {
        "bf16 pool": (pool_k, pool_v),
        "int8 pool": (int8_pool(pool_k), int8_pool(pool_v)),
    }
    t = sz.k_chunk
    qt = normal((2, hq, t, d))
    start = jnp.asarray([bs * 3 + 5, s - t - 3], jnp.int32)
    def scales(pk, pv):
        if isinstance(pk, dict):
            return dict(scale_k=pk["s"], scale_v=pv["s"])
        return {}

    for label, (pk, pv) in pools.items():
        run(
            f"paged_flash_decode {label}",
            lambda q, pk, pv, tb, p: paged_flash_decode(
                q, _pool_arr(pk), _pool_arr(pv), tb, p, window=window,
                interpret=interpret, **scales(pk, pv),
            ),
            lambda q, pk, pv, tb, p: _blockwise_attend(
                f32(q)[:, :, None, :], pk, pv, tb, p, bs,
                jnp.max(p) // bs + 1, window,
            ).reshape(b, hq, d),
            q1, pk, pv, tables, pos,
        )
        run(
            f"paged_flash_prefill {label} T={t}",
            lambda q, pk, pv, tb, p: paged_flash_prefill(
                q, _pool_arr(pk), _pool_arr(pv), tb, p, window=window,
                interpret=interpret, **scales(pk, pv),
            ),
            lambda q, pk, pv, tb, p: _blockwise_attend_mt(
                f32(q), pk, pv, tb, p, bs,
                (jnp.max(p) + t - 1) // bs + 1, window,
            ).reshape(2, t, hq, d).transpose(0, 2, 1, 3),
            qt, pk, pv, tables[:2], start,
        )


# -- serve -----------------------------------------------------------------


def pick_depth(cfg_one, num_blocks: int, bytes_limit: int) -> int:
    """The largest depth whose float32 initialisation, the bf16 copy
    `cast_params` makes beside it and the KV pool fit in 70% of the
    chip's memory; the rest is left to activations, the compiler's
    scratch and fragmentation. `dec.init` builds float32 (29 GB at
    Mistral's 32 layers), which this script does not work around."""
    d, f, v = cfg_one.dim, cfg_one.ffn_dim, cfg_one.vocab_size
    dkv = cfg_one.kv_heads * (d // cfg_one.num_heads)
    layer = 2 * d * d + 2 * d * dkv + 3 * d * f + 2 * d
    embed = v * d + d
    pool_layer = 2 * num_blocks * cfg_one.kv_heads * BLOCK_SIZE * (
        d // cfg_one.num_heads
    ) * 2
    # float32 (4 B) and bf16 (2 B) copies live together during the cast.
    depth = int((0.7 * bytes_limit - 6 * embed) // (6 * layer + pool_layer))
    if depth < 4:
        raise RuntimeError(
            f"only {depth} layers fit in {bytes_limit} bytes; need 4"
        )
    return depth


def trace_logits(dec, params, prompt, num_blocks, **server_kw):
    """One request through PagedDecodeServer, returning the logits row
    its first token was drawn from (prefill, last prompt position) and
    the row of the decode step that consumed that token — captured the
    way tests/test_kv_quant.py::_forced_trace does, by wrapping the
    server's own callables; nothing is added to the server."""
    import numpy as np

    from defer_tpu.runtime.paged import PagedDecodeServer

    srv = PagedDecodeServer(
        dec, params, num_blocks=num_blocks, block_size=BLOCK_SIZE,
        max_batch=MAX_BATCH, **server_kw,
    )
    rec: dict = {}
    first_token = srv._first_token

    def spy_first(i, samp, lrow, dtype, cid):
        rec["prefill"] = lrow
        rec["slot"] = i
        return first_token(i, samp, lrow, dtype, cid)

    srv._first_token = spy_first
    srv.submit(prompt, 2)
    srv._admit()
    if srv.pp > 1:
        last = srv._pp_stage_objs[-1]
        dispatch = last.pp_dispatch

        def spy_dispatch(*a):
            out = dispatch(*a)
            rec.setdefault("decode", out[:, -1, :])
            return out

        last.pp_dispatch = spy_dispatch
    else:
        srv._build()
        step = srv._step

        def spy_step(*a):
            logits, pk, pv = step(*a)
            rec["decode"] = logits[:, -1, :]
            return logits, pk, pv

        srv._step = spy_step
    first = int(np.asarray(srv._feed)[rec["slot"], 0])
    srv._tick()
    if srv.pp > 1:
        srv.close_pp()
    return {
        "prefill": np.asarray(rec["prefill"], np.float32)[0],
        "decode": np.asarray(rec["decode"], np.float32)[rec["slot"]],
        "first": first,
        "server": srv,
    }


def leg_serve(sz: Sizes, on_tpu: bool, where: str) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from defer_tpu.models.gpt import GptDecoder, _flash_decode_mode
    from defer_tpu.models.llama import mistral_config
    from defer_tpu.runtime.paged import serve_paged

    span = max(p + s for p, s in zip(sz.prompts, sz.steps))
    num_blocks = MAX_BATCH * -(-span // BLOCK_SIZE) + 1
    if on_tpu:
        limit = jax.devices()[0].memory_stats()["bytes_limit"]
        depth = pick_depth(
            mistral_config(num_layers=1, **sz.model), num_blocks, limit
        )
        if _flash_decode_mode() != "tpu":
            raise AssertionError(
                f"decode step would not compile flash_decode: "
                f"_flash_decode_mode() = {_flash_decode_mode()!r}"
            )
    else:
        depth = 4
    cfg = mistral_config(num_layers=depth, **sz.model)
    say(
        f"  model: mistral_config(num_layers={depth}) of Mistral-7B-v0.1's "
        f"32 — hidden {cfg.dim}, {cfg.num_heads} Q / {cfg.kv_heads} KV "
        f"heads of {cfg.dim // cfg.num_heads}, FFN {cfg.ffn_dim}, vocab "
        f"{cfg.vocab_size}, window {cfg.window}; bf16; output head TIED "
        f"to the embedding (dec.init), where Mistral's is untied"
    )
    dec = GptDecoder(cfg)
    params = dec.cast_params(dec.init(jax.random.key(SEED)))
    weight_bytes = sum(a.nbytes for a in jax.tree.leaves(params))
    say(f"  weights {weight_bytes / 2**30:.2f} GiB, pool {num_blocks} blocks")

    rng = np.random.default_rng(SEED)
    requests = [
        (jnp.asarray(rng.integers(1, cfg.vocab_size, (1, p)), jnp.int32), s)
        for p, s in zip(sz.prompts, sz.steps)
    ]
    kw = dict(
        num_blocks=num_blocks, block_size=BLOCK_SIZE, max_batch=MAX_BATCH
    )

    def serve():
        outs, stats = serve_paged(dec, params, requests, **kw)
        jax.block_until_ready(outs)
        return outs, stats

    (outs, stats), first_s, warm_s = two_passes(
        f"serve_paged, {len(requests)} requests", where, serve
    )
    for (prompt, steps), out in zip(requests, outs):
        ids = np.asarray(out)
        if ids.shape != (1, prompt.shape[1] + steps):
            raise AssertionError(
                f"request of {prompt.shape[1]}+{steps} returned {ids.shape}"
            )
        if ids.min() < 0 or ids.max() >= cfg.vocab_size:
            raise AssertionError("token id outside the vocabulary")
        if not (ids[:, : prompt.shape[1]] == np.asarray(prompt)).all():
            raise AssertionError("prompt not echoed")
    say(
        f"  {len(outs)} requests returned every token "
        f"({stats['ticks']} ticks, peak {stats['peak_blocks']} blocks)"
    )

    # Logits, not sampled tokens: random weights put many logits within
    # a bf16 rounding of each other, and a flipped near-tie says nothing.
    prompt = requests[0][0]
    t0 = prompt.shape[1]
    traces = {
        mode: trace_logits(dec, params, prompt, num_blocks, attention=mode)
        for mode in ("gathered", "blockwise", "pallas")
    }
    base = traces["gathered"]
    tokens = jnp.concatenate(
        [prompt, jnp.asarray([[base["first"]]], jnp.int32)], axis=1
    )
    ref_dec = GptDecoder(cfg, compute_dtype=jnp.float32)
    ref_params = ref_dec.cast_params(params)
    with jax.default_matmul_precision("highest"):
        ref = np.asarray(ref_dec.reference_logits(ref_params, tokens))
    del ref_params
    check("prefill logits vs float32 full forward",
          base["prefill"], ref[0, t0 - 1], MODEL_TOL)
    check("decode  logits vs float32 full forward",
          base["decode"], ref[0, t0], MODEL_TOL)
    for mode in ("blockwise", "pallas"):
        # Prefill is one program for all three, so the token fed to the
        # decode step is the same and the rows are comparable.
        if traces[mode]["first"] != base["first"]:
            raise AssertionError(f"attention={mode} drew another first token")
        check(f"decode  logits, attention={mode} vs gathered",
              traces[mode]["decode"], base["decode"], PATH_TOL)
    pool_bytes = base.pop("server").pool_bytes
    return {
        "L": depth, "first_s": first_s, "warm_s": warm_s,
        "dec": dec, "params": params, "prompt": prompt,
        "requests": requests, "num_blocks": num_blocks,
        "base": base, "weight_bytes": weight_bytes,
        "pool_bytes": pool_bytes,
    }


# -- pipeline --------------------------------------------------------------


def stream(defer, model, cuts, params, batches):
    """Feed `batches` through DEFER.run_defer the way a driver script
    does: N inputs, the None sentinel, the thread exits."""
    in_q: queue.Queue = queue.Queue()
    out_q: queue.Queue = queue.Queue()
    failure: list[BaseException] = []

    def body():
        try:
            defer.run_defer(model, cuts, in_q, out_q, params=params)
        except BaseException as e:  # noqa: BLE001 — re-raised below
            failure.append(e)

    thread = threading.Thread(target=body, daemon=True)
    thread.start()
    for x in batches:
        in_q.put(x)
    in_q.put(None)
    thread.join(timeout=900)
    if failure:
        raise failure[0]
    if thread.is_alive():
        raise AssertionError("run_defer did not exit after the sentinel")
    outs = []
    while not out_q.empty():
        outs.append(out_q.get_nowait())
    if len(outs) != len(batches):
        raise AssertionError(f"{len(outs)} outputs for {len(batches)} inputs")
    return outs


def check_probs(name: str, got, want) -> None:
    """Rows are probabilities; they match `want`; the top class is the
    same wherever `want`'s margin over its runner-up exceeds what bf16
    can resolve."""
    import numpy as np

    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    if not np.isfinite(got).all():
        raise AssertionError(f"{name}: non-finite probabilities")
    sums = got.sum(axis=-1)
    if np.abs(sums - 1.0).max() > PROB_TOL:
        raise AssertionError(f"{name}: rows sum to {sums.min()}..{sums.max()}")
    diff = float(np.abs(got - want).max())
    top2 = np.sort(want, axis=-1)[:, -2:]
    clear = (top2[:, 1] - top2[:, 0]) > 2 * diff + 1e-6
    same = got.argmax(-1) == want.argmax(-1)
    say(
        f"  {name}: max|dp| = {diff:.2e}, top-1 equal on "
        f"{int(same.sum())}/{len(same)} rows ({int(clear.sum())} with a "
        f"clear margin)"
    )
    if diff > PROB_TOL or not same[clear].all():
        raise AssertionError(f"{name}: disagrees with the reference")


def leg_pipeline(sz: Sizes, where: str) -> dict:
    import jax
    import jax.numpy as jnp

    from defer_tpu import DEFER, DeferConfig
    from defer_tpu.models import get_model
    from defer_tpu.obs.metrics import get_registry
    from defer_tpu.parallel.pipeline import cast_params_to_storage

    model = get_model("resnet50")
    params = model.init(jax.random.key(SEED))
    keys = jax.random.split(jax.random.key(SEED + 1), sz.image_batches)
    batches = [
        jax.random.normal(k, (sz.image_batch, *model.input_shape), jnp.bfloat16)
        for k in keys
    ]
    config = DeferConfig()
    defer = DEFER([jax.devices()[0]], config)
    outs, first_s, warm_s = two_passes(
        f"run_defer, resnet50, 1 stage, {len(batches)} x {sz.image_batch}",
        where,
        lambda: stream(defer, model, None, params, batches),
    )
    plain = jax.jit(model.graph.apply)
    stored = cast_params_to_storage(params, config)
    want = [plain(stored, x) for x in batches]
    for i, (got, ref) in enumerate(zip(outs, want)):
        check_probs(f"batch {i} vs jax.jit(model.graph.apply)", got, ref)
    reg = get_registry()
    for counter in ("defer_redispatch_total", "defer_inflight_dropped_total"):
        # A failed first submit is retried once by default
        # (DeferConfig.redispatch_attempts); a clean run has none.
        if reg.value(counter):
            raise AssertionError(f"{counter} = {reg.value(counter)}")
    return {
        "first_s": first_s, "warm_s": warm_s, "model": model,
        "params": params, "batches": batches, "outs": outs,
    }


# -- four chips ------------------------------------------------------------


def device_bytes(tree) -> dict:
    """Bytes each device holds of `tree`'s leaves."""
    import jax

    held: dict = {}
    for leaf in jax.tree.leaves(tree):
        for shard in leaf.addressable_shards:
            held[shard.device] = held.get(shard.device, 0) + shard.data.nbytes
    return held


def show_share(name: str, held: dict, total: int, lo: float, hi: float):
    for dev, n in sorted(held.items(), key=lambda kv: kv[0].id):
        say(f"    {name} on {dev}: {n / 2**20:.0f} MiB = {n / total:.3f} of one chip's")
        if not lo <= n / total <= hi:
            raise AssertionError(
                f"{name} on {dev}: share {n / total:.3f} outside [{lo}, {hi}]"
            )
    if len(held) != 4:
        raise AssertionError(f"{name} spread over {len(held)} devices, not 4")


def leg_four_chips(sz: Sizes, serve: dict, pipe: dict, where: str) -> dict:
    import jax

    from defer_tpu import DEFER, DeferConfig
    from defer_tpu.parallel.mesh import make_mesh
    from defer_tpu.runtime.paged import serve_paged

    devs = jax.devices()[:4]
    times: dict = {}

    # run_defer with four balanced stages, one per chip.
    defer = DEFER(devs, DeferConfig())
    t0 = time.perf_counter()
    outs = stream(defer, pipe["model"], "auto", pipe["params"], pipe["batches"])
    times["pipeline4_s"] = time.perf_counter() - t0
    pipeline = defer.last_pipeline
    homes = []
    for i, sp in enumerate(pipeline.stage_params):
        held = device_bytes(sp)
        if len(held) != 1:
            raise AssertionError(f"stage {i} params on {list(held)}")
        (dev, n), = held.items()
        homes.append(dev)
        say(f"    stage {i}: {n / 2**20:.1f} MiB of parameters on {dev}")
    if pipeline.num_stages != 4 or sorted(d.id for d in homes) != sorted(
        d.id for d in devs
    ):
        raise AssertionError(
            f"{pipeline.num_stages} stages on {homes}: want one per chip"
        )
    for got, ref in zip(outs, pipe["outs"]):
        check_probs("4 stages on 4 chips vs 1 stage on 1 chip", got, ref)
    say(f"  run_defer, resnet50, 4 stages: {times['pipeline4_s']:.2f}s [{where}]")

    # The paged server, tensor-parallel then pipeline-parallel.
    dec, params, base = serve["dec"], serve["params"], serve["base"]
    nblk = serve["num_blocks"]
    kw = dict(num_blocks=nblk, block_size=BLOCK_SIZE, max_batch=MAX_BATCH)
    subset = serve["requests"][:4]
    for name, extra in (
        ("tp", dict(mesh=make_mesh({"model": 4}, devs))),
        ("pp", dict(pp_stages=4)),
    ):
        t0 = time.perf_counter()
        outs, stats = serve_paged(dec, params, subset, **kw, **extra)
        jax.block_until_ready(outs)
        for (prompt, steps), out in zip(subset, outs):
            if out.shape != (1, prompt.shape[1] + steps):
                raise AssertionError(f"{name}: request returned {out.shape}")
        got = trace_logits(dec, params, serve["prompt"], nblk, **extra)
        times[f"serve_{name}4_s"] = time.perf_counter() - t0
        say(f"  serve_paged {name}=4: {times[f'serve_{name}4_s']:.2f}s [{where}]")
        check(f"prefill logits, {name}=4 vs one chip",
              got["prefill"], base["prefill"], PATH_TOL)
        # Another first token would make the decode rows incomparable.
        if got["first"] != base["first"]:
            raise AssertionError(f"{name}=4 drew another first token")
        check(f"decode  logits, {name}=4 vs one chip",
              got["decode"], base["decode"], PATH_TOL)
        srv = got["server"]
        if name == "tp":
            show_share("weights", device_bytes(srv.params),
                       serve["weight_bytes"], 0.24, 0.27)
            show_share("KV pool", device_bytes((srv.pool_k, srv.pool_v)),
                       serve["pool_bytes"], 0.249, 0.251)
        else:
            # The tied embedding sits on the first and the last stage.
            weights: dict = {}
            pools: dict = {}
            for st in srv._pp_stage_objs:
                for dev, n in device_bytes(st.params).items():
                    weights[dev] = weights.get(dev, 0) + n
                for dev, n in device_bytes((st.pk, st.pv)).items():
                    pools[dev] = pools.get(dev, 0) + n
            show_share("weights", weights, serve["weight_bytes"], 0.2, 0.32)
            show_share("KV pool", pools, serve["pool_bytes"], 0.249, 0.251)
        del srv, got
    for dev in devs:
        stats = dev.memory_stats() or {}
        say(
            f"    {dev}: {stats.get('bytes_in_use', 0) / 2**30:.2f} GiB in "
            f"use, peak {stats.get('peak_bytes_in_use', 0) / 2**30:.2f} GiB"
        )
    return times


# -- entry -----------------------------------------------------------------


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument(
        "--chips", type=int, choices=(1, 4), default=1,
        help="4 adds the four-chip legs and needs four visible devices",
    )
    ap.add_argument(
        "--debug-cpu-tiny", action="store_true",
        help="toy sizes, kernels interpreted; for debugging on a CPU only",
    )
    args = ap.parse_args(argv)
    t_start = time.perf_counter()

    import jax
    import jaxlib

    import defer_tpu  # noqa: F401 — places the compile cache

    devices = jax.devices()
    dev = devices[0]
    on_tpu = dev.platform == "tpu"
    if not on_tpu and not args.debug_cpu_tiny:
        raise RuntimeError(
            f"no TPU: jax.devices()[0] is {dev.platform!r} ({dev.device_kind})"
        )
    if on_tpu and args.debug_cpu_tiny:
        raise RuntimeError("--debug-cpu-tiny is for a CPU; this is a TPU")
    if len(devices) < args.chips:
        raise RuntimeError(
            f"--chips {args.chips} but JAX sees {len(devices)} device(s)"
        )
    from importlib import metadata

    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = "not installed"
    # Cache every program, however quickly it compiled, so that a second
    # run on the same machine adds no entry (JAX's default skips
    # compilations under a second, which is not the same set twice).
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    cache_dir = jax.config.jax_compilation_cache_dir
    before = cache_entries(cache_dir)
    where = f"{dev.device_kind} x1"
    say(
        f"device: {dev.platform}, {dev.device_kind}, {len(devices)} visible; "
        f"jax {jax.__version__}, jaxlib "
        f"{jaxlib.__version__}, libtpu {libtpu}; compile cache {cache_dir} "
        f"({before} entries)"
    )
    sz = FULL if on_tpu else TINY
    legs: dict = {}

    say("kernels leg")
    t0 = time.perf_counter()
    leg_kernels(sz, interpret=not on_tpu, where=where)
    legs["kernels"] = {"total_s": round(time.perf_counter() - t0, 2)}

    say("serve leg")
    t0 = time.perf_counter()
    serve = leg_serve(sz, on_tpu, where)
    legs["serve"] = {
        "first_s": round(serve["first_s"], 2),
        "warm_s": round(serve["warm_s"], 2),
        "total_s": round(time.perf_counter() - t0, 2),
    }

    say("pipeline leg")
    t0 = time.perf_counter()
    pipe = leg_pipeline(sz, where)
    legs["pipeline"] = {
        "first_s": round(pipe["first_s"], 2),
        "warm_s": round(pipe["warm_s"], 2),
        "total_s": round(time.perf_counter() - t0, 2),
    }

    if args.chips == 4:
        say("four-chip legs")
        t0 = time.perf_counter()
        times = leg_four_chips(sz, serve, pipe, f"{dev.device_kind} x4")
        legs["four_chips"] = {
            **{k: round(v, 2) for k, v in times.items()},
            "total_s": round(time.perf_counter() - t0, 2),
        }

    if args.debug_cpu_tiny:
        say("debug run passed (toy sizes on a CPU: not a result)")
        return 0
    details = {
        "chips": args.chips,
        "L": serve["L"],
        "legs": legs,
        "wall_s": round(time.perf_counter() - t_start, 2),
        "cache_dir": cache_dir,
        "cache_entries": {"before": before, "after": cache_entries(cache_dir)},
    }
    say(f"details: {json.dumps(details)}")
    say(result_line(devices))
    return 0


if __name__ == "__main__":
    sys.exit(main())
