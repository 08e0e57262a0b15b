"""Block-native paged attention: the `blockwise` and `pallas` decode
paths must emit the same tokens as the `gathered` reference path.

Parity contract per path (runtime/paged.py module docstring): the
gathered path IS the flat decoder's block math, so it stays bit-exact
vs solo generate. The block-native paths share the exact projection
code (`_attn_qkv` / `_attn_out`) and differ only in softmax reduction
order, so logits may drift by float ulps; at these test scales no
argmax/sampling tie sits close enough for that to flip a token, and
the tests assert token-for-token equality — a mismatch means a real
indexing/masking bug, not tolerable drift.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from defer_tpu import obs
from defer_tpu.obs import spans
from defer_tpu.models.gpt import SamplingParams, tiny_gpt
from defer_tpu.models.llama import tiny_llama
from defer_tpu.runtime.paged import (
    PagedDecodeServer,
    pick_rung,
    serve_paged,
    span_rungs,
)


def _mixed_requests(vocab, rng_seed=5):
    """Five requests with a shared 16-token prefix on the first two
    (so prefix_cache=True actually shares blocks) and lengths that
    straddle block boundaries for both tested block sizes."""
    rng = np.random.default_rng(rng_seed)
    base = jnp.asarray(
        rng.integers(1, vocab, size=(1, 18)), jnp.int32
    )
    ext = jnp.asarray(rng.integers(1, vocab, size=(1, 5)), jnp.int32)
    return [
        (base, 6),
        (jnp.concatenate([base, ext], axis=1), 5),
        (jnp.asarray(rng.integers(1, vocab, size=(1, 3)), jnp.int32), 7),
        (jnp.asarray(rng.integers(1, vocab, size=(1, 9)), jnp.int32), 4),
        (jnp.asarray(rng.integers(1, vocab, size=(1, 2)), jnp.int32), 3),
    ]


# Greedy and sampled slots share ticks; the categorical draws consume
# the same logits, so a token mismatch here also catches drift that
# argmax alone would mask.
_MIXED_SAMPLING = [
    None,
    SamplingParams(temperature=0.9, seed=3),
    SamplingParams(temperature=1.2, top_k=5, seed=11),
    None,
    SamplingParams(temperature=1.0, top_p=0.9, seed=2),
]


def _serve(dec, params, reqs, *, attention, block_size, prefix_cache):
    outs, stats = serve_paged(
        dec, params, reqs,
        num_blocks=18, block_size=block_size, max_batch=2,
        prefix_cache=prefix_cache, sampling=_MIXED_SAMPLING,
        attention=attention,
    )
    return [np.asarray(o) for o in outs], stats


@pytest.mark.parametrize("block_size", [8, 16])
@pytest.mark.parametrize("prefix_cache", [False, True])
def test_blockwise_parity_matrix(block_size, prefix_cache):
    """blockwise == gathered token-for-token across block sizes x
    prefix-cache on/off, with mixed greedy+sampled slots and forced
    mid-stream finish/re-admit (5 requests through 2 slots). GQA
    model: the grouped-head reshape is the easiest thing to get
    subtly wrong."""
    dec = tiny_llama(64)
    params = dec.init(jax.random.key(0))
    reqs = _mixed_requests(dec.cfg.vocab_size)
    want, _ = _serve(
        dec, params, reqs, attention="gathered",
        block_size=block_size, prefix_cache=prefix_cache,
    )
    got, stats = _serve(
        dec, params, reqs, attention="blockwise",
        block_size=block_size, prefix_cache=prefix_cache,
    )
    assert stats["attention"] == "blockwise"
    for i, (a, b) in enumerate(zip(want, got)):
        np.testing.assert_array_equal(
            a, b,
            err_msg=f"request {i} bs={block_size} cache={prefix_cache}",
        )


def test_blockwise_matches_solo_generate_gpt():
    """Absolute (not just relative) correctness on the learned-
    positions family: blockwise greedy outputs equal each request's
    solo dec.generate."""
    dec = tiny_gpt(64)
    params = dec.init(jax.random.key(0))
    reqs = _mixed_requests(dec.cfg.vocab_size)
    outs, _ = serve_paged(
        dec, params, reqs, num_blocks=18, block_size=8, max_batch=2,
        attention="blockwise",
    )
    for (prompt, steps), got in zip(reqs, outs):
        want = dec.generate(params, prompt, steps)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_kv_rows_scale_with_depth_not_pool():
    """The acceptance criterion on the obs counters: against the
    baseline of B * max_blocks * block_size rows a tick, the gathered
    path reads to the rung above the deepest live slot and blockwise
    to the deepest live block — blockwise <= gathered < baseline on
    the same workload — and both read the SAME rows when the pool
    grows (reads scale with request depth, not pool size)."""
    dec = tiny_gpt(64)
    params = dec.init(jax.random.key(0))
    reqs = _mixed_requests(dec.cfg.vocab_size)

    def rows(attention, num_blocks):
        with obs.counter_deltas() as d:
            _, stats = serve_paged(
                dec, params, reqs, num_blocks=num_blocks,
                block_size=8, max_batch=2, attention=attention,
            )
        read = d.get('defer_kv_rows_read_total{server="paged"}', 0)
        base = d.get(
            'defer_kv_rows_gathered_baseline_total{server="paged"}', 0
        )
        return read, base, stats["ticks"]

    g_read, g_base, g_ticks = rows("gathered", 18)
    b_read, b_base, b_ticks = rows("blockwise", 18)
    assert b_ticks == g_ticks  # same schedule, comparable baselines
    assert b_base == g_base
    assert 0 < b_read <= g_read < g_base  # depth-scaled reads beat it
    # Growing the pool must not change what either reads: both pools
    # admit the whole mix immediately, so the schedule — and therefore
    # live depth per tick — is identical.
    for attention, read, ticks in [
        ("gathered", g_read, g_ticks), ("blockwise", b_read, b_ticks),
    ]:
        read2, _, ticks2 = rows(attention, 44)
        assert ticks2 == ticks
        assert read2 == read


# -- the gathered step's span ladder --------------------------------------


@pytest.mark.parametrize(
    "mb,bs", [(256, 16), (16, 4), (8, 8), (100, 16), (3, 8), (1, 16), (37, 2)]
)
def test_span_ladder_holds_every_depth(mb, bs):
    """Every position of the table maps to the lowest rung that holds
    it; rungs are whole blocks, at most 16, the last the whole table."""
    rungs = span_rungs(mb)
    assert rungs == tuple(sorted(set(rungs))) and len(rungs) <= 16
    assert rungs[0] >= 1 and rungs[-1] == mb
    assert all(isinstance(r, int) for r in rungs)
    for depth in range(mb * bs):
        nb = pick_rung(rungs, bs, depth)
        assert nb in rungs and nb * bs > depth
        assert all(r * bs <= depth for r in rungs if r < nb)
    if (mb, bs) == (256, 16):
        assert [r * bs for r in rungs] == [1024, 1536, 4096]


def _ladder_requests(vocab):
    """Through two slots of a 64-row table in blocks of 4 (rungs at 16,
    24 and 64 rows): a short request, one that climbs from 9 rows over
    all three rungs, and one that starts on the last rung and ends on
    the table's last row."""
    rng = np.random.default_rng(17)
    return [
        (jnp.asarray(rng.integers(1, vocab, size=(1, n)), jnp.int32), steps)
        for n, steps in [(3, 6), (9, 30), (50, 14), (2, 20)]
    ]


@pytest.fixture(scope="module")
def ladder():
    dec = tiny_gpt(64)
    params = dec.init(jax.random.key(0))
    reqs = _ladder_requests(dec.cfg.vocab_size)
    return dec, params, reqs, [dec.generate(params, p, s) for p, s in reqs]


def _tick_spans():
    return [
        r.counts["span_rows"] for r in spans.snapshot().records
        if r.name == "paged.tick" and r.counts["kind"] == "plain"
    ]


@pytest.mark.parametrize("kv_dtype", ["fp", "int8"])
def test_ladder_serves_what_generate_gives(ladder, kv_dtype):
    """Depths that cross rungs and reach the last one: the fp pool
    serves token-for-token what `dec.generate` gives (dropped columns
    were masked rows), and so does the int8 pool against itself on
    the whole table (its parity against fp is test_kv_quant.py's)."""
    dec, params, reqs, want = ladder
    kw = dict(num_blocks=40, block_size=4, max_batch=2, kv_dtype=kv_dtype)
    obs.reset()
    with obs.counter_deltas() as d:
        outs, _ = serve_paged(dec, params, reqs, **kw)
    rows = _tick_spans()
    assert set(rows) == {16, 24, 64}
    # The counters are the ticks' spans summed: the engagement share.
    assert d['defer_kv_rows_read_total{server="paged"}'] == 2 * sum(rows)
    assert d['defer_kv_rows_gathered_baseline_total{server="paged"}'] == (
        2 * 64 * len(rows)
    )
    if kv_dtype == "fp":
        for got, ref in zip(outs, want):
            np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
        return
    # The whole table every tick: a ladder of one rung.
    srv = PagedDecodeServer(dec, params, **kw)
    srv._build()
    srv._rungs = srv._rungs[-1:]
    rids = [srv.submit(p, s) for p, s in reqs]
    whole = srv.run()
    assert set(_tick_spans()[len(rows):]) == {64}
    for got, rid in zip(outs, rids):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(whole[rid]))


def test_no_program_is_built_while_depths_cross_rungs(ladder):
    """Every rung's program exists before the first tick: each is a
    `jax.build` record of kind `paged_step` under the first admission,
    and no tick lowers, compiles or loads a program in its plan or its
    dispatch though the ticks run on all three rungs."""
    dec, params, reqs, _ = ladder
    # A server of its own shape, so that no test before it built these.
    kw = dict(num_blocks=39, block_size=4, max_batch=2)
    obs.reset()
    serve_paged(dec, params, reqs, **kw)
    records = spans.snapshot().records
    by_id = {r.id: r for r in records}
    ticks = [r for r in records if r.name == "paged.tick"]
    assert {r.counts["span_rows"] for r in ticks} == {16, 24, 64}
    builds = [r for r in records if r.name == "jax.build"]
    rungs = [r for r in builds if r.counts["kind"] == "paged_step"]
    assert [r.counts["span_rows"] for r in rungs] == [16, 24, 64]
    assert all(r.t1 <= ticks[0].t0 for r in rungs)
    # What the listener saw of them lies inside them, and nowhere else
    # near the step: a tick's other builds are the small eager
    # programs of its sampling (PERF.md 7a).
    inside = [r for r in builds if r.parent in {g.id for g in rungs}]
    assert {r.counts["kind"] for r in inside} >= {"lowered"}
    under = {by_id[r.parent].name for r in builds if r.parent in by_id}
    assert not under & {"paged.tick.plan", "paged.tick.dispatch"}, under
    # A second server of the same shapes builds nothing: the programs
    # are memoised on the decoder.
    obs.reset()
    serve_paged(dec, params, reqs[:1], **kw)
    assert not [
        r for r in spans.snapshot().records
        if r.name == "jax.build" and r.counts["kind"] == "paged_step"
    ]


def test_unknown_attention_mode_raises():
    dec = tiny_gpt(32)
    params = dec.init(jax.random.key(0))
    with pytest.raises(ValueError, match="attention"):
        PagedDecodeServer(
            dec, params, num_blocks=8, block_size=8, max_batch=2,
            attention="flash-gordon",
        )


def test_sampler_release_resets_policy_rows():
    """SlotSampler.release clears row_sort and the temperature row at
    finish, so one departed top-k request stops taxing later ticks
    with the sorting draw path."""
    from defer_tpu.runtime.decode_server import SlotSampler

    s = SlotSampler(3)
    logits_row = jnp.linspace(0.0, 1.0, 16)[None, :]
    s.admit_first(
        1,
        SamplingParams(temperature=0.8, top_k=4, seed=7),
        logits_row,
        jnp.int32,
    )
    assert s.row_sort[1] and s.row_temp[1] == 0.8
    assert float(s.temp[1]) == pytest.approx(0.8)
    s.release(1)
    assert not any(s.row_sort)
    assert s.row_temp[1] == 0.0
    assert float(s.temp[1]) == 0.0


def test_paged_server_releases_policy_at_finish():
    """End-to-end: after a paged run with top-k slots, every policy
    row is back to the greedy fast path."""
    dec = tiny_gpt(64)
    params = dec.init(jax.random.key(0))
    srv = PagedDecodeServer(
        dec, params, num_blocks=12, block_size=8, max_batch=2,
    )
    reqs = _mixed_requests(dec.cfg.vocab_size)[:3]
    for (p, s), sp in zip(reqs, _MIXED_SAMPLING):
        srv.submit(p, s, sampling=sp)
    srv.run()
    assert not any(srv._sampler.row_sort)
    assert all(t == 0.0 for t in srv._sampler.row_temp)


def test_paged_flash_decode_kernel_matches_reference():
    """Kernel-level (interpret mode): paged_flash_decode over a block
    table with trash entries equals a dense gather + masked softmax
    reference, per slot and per grouped head."""
    from defer_tpu.ops.pallas_attention import paged_flash_decode

    b, hq, hkv, d, bs, mb, nb = 3, 4, 2, 16, 8, 3, 8
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((b, hq, d)), jnp.float32)
    pk = jnp.asarray(
        rng.standard_normal((nb, hkv, bs, d)), jnp.float32
    )
    pv = jnp.asarray(
        rng.standard_normal((nb, hkv, bs, d)), jnp.float32
    )
    # Slot 0: full table. Slot 1: one live block, rest trash block 0.
    # Slot 2: two live blocks. pos is the last valid key, inclusive.
    tables = jnp.asarray(
        [[1, 2, 3], [4, 0, 0], [5, 6, 0]], jnp.int32
    )
    pos = jnp.asarray([bs * 3 - 1, 2, bs + 4], jnp.int32)

    out = paged_flash_decode(q, pk, pv, tables, pos, interpret=True)

    g = hq // hkv
    scale = d ** -0.5
    for i in range(b):
        rows_k = np.concatenate(
            [np.asarray(pk[tables[i, j]]) for j in range(mb)], axis=1
        )  # [Hkv, MB*bs, D]
        rows_v = np.concatenate(
            [np.asarray(pv[tables[i, j]]) for j in range(mb)], axis=1
        )
        mask = np.arange(mb * bs) <= int(pos[i])
        for h in range(hq):
            kv = h // g  # q reshape(b, hkv, g, d) is kv-major
            s = (np.asarray(q[i, h]) @ rows_k[kv].T) * scale
            s = np.where(mask, s, -np.inf)
            w = np.exp(s - s.max())
            w /= w.sum()
            want = w @ rows_v[kv]
            np.testing.assert_allclose(
                np.asarray(out[i, h]), want, rtol=2e-5, atol=2e-5,
                err_msg=f"slot {i} head {h}",
            )


@pytest.mark.slow
@pytest.mark.parametrize("block_size", [8, 16])
def test_pallas_server_parity(block_size):
    """Interpret-mode pallas path == gathered token-for-token through
    the full server (mixed sampling, prefix cache, re-admits). Slow:
    the interpreter walks the grid in Python."""
    dec = tiny_llama(64)
    params = dec.init(jax.random.key(0))
    reqs = _mixed_requests(dec.cfg.vocab_size)
    want, _ = _serve(
        dec, params, reqs, attention="gathered",
        block_size=block_size, prefix_cache=True,
    )
    got, stats = _serve(
        dec, params, reqs, attention="pallas",
        block_size=block_size, prefix_cache=True,
    )
    assert stats["attention"] == "pallas"
    for i, (a, b) in enumerate(zip(want, got)):
        np.testing.assert_array_equal(
            a, b, err_msg=f"request {i} bs={block_size}"
        )
