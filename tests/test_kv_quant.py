"""Quantized paged KV pool (`kv_dtype="int8"`) + host-RAM spill tier.

The contracts pinned here, in order:

  * the shared symmetric-int8 convention (models/quant.py) degrades
    safely on zero/subnormal tensors and the codec's non-finite guard
    still fires — one helper, three consumers (weight leaves, wire
    frames, the pool);
  * pool bytes: int8 stores exactly fp_bytes/itemsize + the scale
    tensors — the residency win is arithmetic, not approximate;
  * accuracy: teacher-forced along the fp greedy trajectory, int8
    logits stay within a small fraction of the logit scale at EVERY
    decode step, for every attention mode × prefix_cache × tp — the
    bounded-logit-error contract (outputs are NOT bit-identical; the
    pool is lossy by design);
  * composition: decode_window and spec_k are exact rearrangements of
    the same tick math WITHIN a pool dtype, so int8+window and
    int8+spec must be token-identical to plain int8;
  * `kv_dtype="fp"` stays bit-identical to solo generate (the default
    cannot move);
  * `defer_kv_rows_read_total` counts rows, not bytes — identical for
    fp and int8 pools;
  * spill tier: an evicted prefix block revived from host RAM is
    token-identical to a resident radix hit, for both pool dtypes.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from defer_tpu import obs
from defer_tpu.models.gpt import tiny_gpt
from defer_tpu.models.quant import (
    dequantize_symmetric,
    quantize_symmetric,
)
from defer_tpu.parallel.mesh import make_mesh
from defer_tpu.runtime.codec import encode
from defer_tpu.runtime.paged import PagedDecodeServer, serve_paged


@pytest.fixture(scope="module")
def model():
    dec = tiny_gpt(64)
    params = dec.init(jax.random.key(0))
    return dec, params


def _requests(vocab):
    """Shared prefix on the first two (radix hits under prefix_cache)
    plus one longer independent prompt — the test_paged_tp.py mix."""
    rng = np.random.default_rng(3)
    base = jnp.asarray(rng.integers(1, vocab, size=(1, 6)), jnp.int32)
    ext = jnp.asarray(rng.integers(1, vocab, size=(1, 4)), jnp.int32)
    return [
        (base, 7),
        (jnp.concatenate([base, ext], axis=1), 5),
        (jnp.asarray(rng.integers(1, vocab, size=(1, 11)), jnp.int32), 6),
    ]


# -- the shared int8 convention -------------------------------------------


def test_quantize_symmetric_degenerate_and_bounds():
    """Zero and subnormal tensors clamp the scale to 1.0 (quantize to
    zeros, not clipped ±127 garbage); normal tensors round-trip within
    the per-axis amax/254 bound the scale granularity implies."""
    q, s = quantize_symmetric(np.zeros((3, 4), np.float32), axis=None, xp=np)
    assert q.dtype == np.int8 and not q.any()
    assert float(s) == 1.0
    # Smallest fp32 subnormal: amax/127 underflows to exactly 0, the
    # degenerate-scale clamp's other trigger besides the zero tensor.
    tiny = np.full((2, 2), np.float32(1.4e-45), np.float32)
    assert tiny.any()
    q, s = quantize_symmetric(tiny, axis=None, xp=np)
    assert not q.any() and float(s) == 1.0

    rng = np.random.default_rng(0)
    x = rng.standard_normal((5, 8, 16)).astype(np.float32)
    q, s = quantize_symmetric(x, axis=(-2, -1), keepdims=True, xp=np)
    back = dequantize_symmetric(q, s, np.float32, xp=np)
    amax = np.abs(x).max(axis=(-2, -1), keepdims=True)
    # Half a quantization step per element, per (leading-axis) scale.
    assert (np.abs(back - x) <= amax / 254 + 1e-7).all()


def test_codec_nonfinite_guard_matches_helper_consumers():
    """The codec refuses non-finite tensors BEFORE quantize_symmetric
    sees them (one NaN would corrupt the whole frame); the jitted pool
    writes rely on the same caller-side contract."""
    bad = np.array([1.0, np.nan], np.float32)
    with pytest.raises(ValueError, match="finite"):
        encode(bad, quantize="int8")
    with pytest.raises(ValueError, match="finite"):
        encode(np.array([np.inf], np.float64), quantize="int8")


# -- pool bytes -----------------------------------------------------------


def test_int8_pool_bytes_pinned(model):
    """The residency claim as arithmetic: the int8 pool is exactly
    fp_bytes/itemsize for the block data plus the two fp32 scale
    tensors — and the stats surface both dtype and bytes."""
    dec, params = model
    reqs = _requests(dec.cfg.vocab_size)
    kw = dict(num_blocks=16, block_size=4, max_batch=2)
    _, st_fp = serve_paged(dec, params, list(reqs), **kw)
    _, st_q8 = serve_paged(dec, params, list(reqs), kv_dtype="int8", **kw)
    assert st_fp["kv_dtype"] == "fp" and st_q8["kv_dtype"] == "int8"
    cfg = dec.cfg
    elems = (
        cfg.num_layers * 16 * cfg.kv_heads * 4 * (cfg.dim // cfg.num_heads)
    )
    itemsize = jnp.dtype(dec.compute_dtype).itemsize
    scales = cfg.num_layers * 16 * cfg.kv_heads * 4  # fp32, k and v
    assert st_fp["pool_bytes"] == 2 * elems * itemsize
    assert st_q8["pool_bytes"] == 2 * elems + 2 * scales
    assert st_q8["pool_bytes"] < st_fp["pool_bytes"] / itemsize + 2 * scales + 1


# -- accuracy: the bounded-logit-error parity matrix ----------------------


def _forced_trace(dec, params, prompt, steps, forced=None, **srv_kw):
    """Drive one request tick by tick, recording each step's logits
    row; with `forced`, override the greedy feed with a reference
    trajectory so fp and int8 runs score the SAME token sequence —
    after the first divergence, free-running logits are incomparable."""
    srv = PagedDecodeServer(
        dec, params, num_blocks=16, block_size=4, max_batch=1, **srv_kw
    )
    rid = srv.submit(prompt, steps)
    srv._admit()
    srv._build()
    orig = srv._step

    rec = []

    def spy(params, pk, pv, tables, pos, feed, *rest):
        # Step t feeds the trajectory's token t: the tick launches a
        # step before it reads the one in flight, so the feed is
        # replaced where the step takes it.
        t = len(rec)
        if forced is not None and 0 < t < len(forced):
            feed = jnp.asarray([[forced[t]]], jnp.int32)
        logits, pk, pv = orig(params, pk, pv, tables, pos, feed, *rest)
        rec.append(np.asarray(logits[:, -1, :]))
        return logits, pk, pv

    srv._step = spy
    toks = np.asarray(srv.run()[rid])[0, prompt.shape[1]:].tolist()
    return toks, rec


MATRIX = [
    ("gathered", False, 0),
    ("gathered", True, 0),
    ("blockwise", False, 0),
    ("blockwise", True, 0),
    ("pallas", False, 0),
    ("pallas", True, 0),
    ("gathered", False, 2),
    ("blockwise", True, 2),
    ("pallas", False, 2),
]


@pytest.mark.parametrize("attention,prefix_cache,tp", MATRIX)
def test_int8_logit_error_bounded(model, attention, prefix_cache, tp):
    """Teacher-forced along the fp greedy trajectory, every decode
    step's int8 logits stay within 5% of the fp logit scale — the
    accuracy contract of per-(layer, block, head) scales — and the
    error is nonzero (the quantized path actually ran)."""
    dec, params = model
    rng = np.random.default_rng(3)
    prompt = jnp.asarray(
        rng.integers(1, dec.cfg.vocab_size, size=(1, 11)), jnp.int32
    )
    kw = dict(attention=attention, prefix_cache=prefix_cache)
    if tp:
        kw["mesh"] = make_mesh({"model": tp}, jax.devices()[:tp])
    ftoks, flog = _forced_trace(dec, params, prompt, 8, **kw)
    _, qlog = _forced_trace(
        dec, params, prompt, 8, forced=ftoks, kv_dtype="int8", **kw
    )
    assert len(flog) == len(qlog) > 0
    scale = max(float(np.max(np.abs(a))) for a in flog)
    err = max(
        float(np.max(np.abs(a - b))) for a, b in zip(flog, qlog)
    )
    assert 0 < err < 0.05 * scale, (
        f"attention={attention} tp={tp}: max|Δlogit|={err} "
        f"vs logit scale {scale}"
    )


def test_int8_window_and_spec_token_identical_to_plain_int8(model):
    """decode_window and spec verify are exact rearrangements of the
    same tick math WITHIN a pool dtype: the fused window's per-column
    writes and the verify forward's row scatters requantize blocks in
    the same order the K=1 tick would, so int8 outputs cannot move."""
    dec, params = model
    reqs = _requests(dec.cfg.vocab_size)
    kw = dict(
        num_blocks=16, block_size=4, max_batch=2, kv_dtype="int8"
    )
    for attention in ("gathered", "blockwise", "pallas"):
        plain, _ = serve_paged(
            dec, params, list(reqs), attention=attention, **kw
        )
        windowed, _ = serve_paged(
            dec, params, list(reqs), attention=attention,
            decode_window=8, **kw,
        )
        for a, b in zip(plain, windowed):
            np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b),
                err_msg=f"decode_window=8 moved int8 {attention} output",
            )
    plain, _ = serve_paged(
        dec, params, list(reqs), attention="gathered", **kw
    )
    spec, st = serve_paged(
        dec, params, list(reqs), attention="gathered",
        spec_draft=dec, spec_params=params, spec_k=4, **kw,
    )
    assert st["spec_acceptance"] > 0.5  # self-draft: verify rows real
    for a, b in zip(plain, spec):
        np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b),
            err_msg="spec_k=4 moved int8 output",
        )


def test_fp_default_still_bit_identical(model):
    """The default pool is untouched: fp greedy outputs equal solo
    dec.generate exactly, with the quantization machinery imported and
    live in the same process."""
    dec, params = model
    reqs = _requests(dec.cfg.vocab_size)
    outs, stats = serve_paged(
        dec, params, list(reqs), num_blocks=16, block_size=4, max_batch=2
    )
    assert stats["kv_dtype"] == "fp"
    for (prompt, steps), got in zip(reqs, outs):
        want = dec.generate(params, prompt, steps)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_kv_rows_counter_is_dtype_agnostic(model):
    """`defer_kv_rows_read_total` means ROWS: an int8 pool reads the
    same row count as fp (the bytes halve, the counter must not)."""
    dec, params = model
    reqs = _requests(dec.cfg.vocab_size)
    kw = dict(
        num_blocks=16, block_size=4, max_batch=2, attention="blockwise"
    )
    with obs.counter_deltas() as d_fp:
        serve_paged(dec, params, list(reqs), **kw)
    with obs.counter_deltas() as d_q8:
        serve_paged(dec, params, list(reqs), kv_dtype="int8", **kw)
    key = 'defer_kv_rows_read_total{server="paged"}'
    assert d_fp[key] == d_q8[key] > 0


# -- host-RAM spill tier --------------------------------------------------


def _spill_workload(vocab):
    rng = np.random.default_rng(5)
    prefix = jnp.asarray(rng.integers(1, vocab, size=(1, 8)), jnp.int32)
    tails = [
        jnp.asarray(rng.integers(1, vocab, size=(1, n)), jnp.int32)
        for n in (3, 2)
    ]
    fillers = [
        jnp.asarray(rng.integers(1, vocab, size=(1, 9)), jnp.int32)
        for _ in range(3)
    ]
    return prefix, tails, fillers


def _run_phases(dec, params, *, num_blocks, spill_bytes, kv_dtype):
    """prefix warm-up -> pool-thrashing fillers -> same prefix again.
    With a big pool the second prefix request is a resident radix hit;
    with a tiny pool + spill tier it must come back via revival."""
    prefix, (ta, tb), fillers = _spill_workload(dec.cfg.vocab_size)
    srv = PagedDecodeServer(
        dec, params, num_blocks=num_blocks, block_size=4, max_batch=1,
        prefix_cache=True, kv_dtype=kv_dtype, spill_bytes=spill_bytes,
    )
    rid = srv.submit(jnp.concatenate([prefix, ta], axis=1), 4)
    srv.run()
    for f in fillers:
        srv.submit(f, 6)
        srv.run()
    if srv._spill is not None:
        srv._spill.flush()
    rid = srv.submit(jnp.concatenate([prefix, tb], axis=1), 5)
    out = np.asarray(srv.run()[rid])
    return out, srv


@pytest.mark.parametrize("kv_dtype", ["fp", "int8"])
def test_spill_revival_token_identical_to_resident_hit(model, kv_dtype):
    """An evicted prefix block revived from the host store produces
    the SAME tokens as the resident-hit run: revival re-uploads the
    stored bytes verbatim (no requantize round trip), so the pool
    state a revived chain presents is bit-identical to never having
    been evicted."""
    dec, params = model
    resident, srv_r = _run_phases(
        dec, params, num_blocks=64, spill_bytes=0, kv_dtype=kv_dtype
    )
    assert srv_r.spill_hits_n == 0
    revived, srv_s = _run_phases(
        dec, params, num_blocks=10, spill_bytes=1 << 20, kv_dtype=kv_dtype
    )
    assert srv_s.spill_hits_n > 0
    assert srv_s._spill.stored_blocks > 0
    # Revival saved the same prefill work a resident hit saves.
    assert srv_s.prefill_tokens_saved == srv_r.prefill_tokens_saved > 0
    np.testing.assert_array_equal(revived, resident)


def test_spill_counters_and_stats_surface(model):
    """Spill motion shows up in obs: blocks spilled and revived count
    on the server-labeled counters, occupancy lands in the gauge, and
    serve_paged's ServerStats carry the same numbers."""
    dec, params = model
    prefix, (ta, tb), fillers = _spill_workload(dec.cfg.vocab_size)
    reqs = (
        [(jnp.concatenate([prefix, ta], axis=1), 4)]
        + [(f, 6) for f in fillers]
        + [(jnp.concatenate([prefix, tb], axis=1), 5)]
    )
    with obs.counter_deltas() as d:
        _, st = serve_paged(
            dec, params, reqs, num_blocks=10, block_size=4, max_batch=1,
            prefix_cache=True, kv_dtype="int8", spill_bytes=1 << 20,
        )
    assert d['defer_prefix_spilled_total{server="paged"}'] > 0
    assert d['defer_prefix_spill_hits_total{server="paged"}'] > 0
    assert st["spill_hits"] > 0
    assert st["spilled_blocks"] > 0
    assert st["spill_stored_bytes"] > 0


def test_spill_requires_prefix_cache(model):
    dec, params = model
    with pytest.raises(ValueError, match="prefix_cache"):
        PagedDecodeServer(
            dec, params, num_blocks=8, block_size=4, max_batch=1,
            spill_bytes=1 << 20,
        )
    with pytest.raises(ValueError, match="kv_dtype"):
        PagedDecodeServer(
            dec, params, num_blocks=8, block_size=4, max_batch=1,
            kv_dtype="int4",
        )
