"""Tensor-parallel paged serving: `PagedDecodeServer(mesh=...)` runs
the tick machinery over a model mesh axis, and nothing the user can
observe moves — greedy outputs are token-identical to `mesh=None`
across attention modes, windows, speculation, and chunked prefill
(runtime/paged.py module docstring has the sharding layout).

Counter contract (the perf claim in miniature, pinned here because a
parity test alone can't see it): per-shard `defer_kv_rows_read_total`
scales as 1/TP — each shard reads only its kv_heads/TP slice of the
pool — while `defer_host_dispatches_total` is unchanged, because the
host loop samples replicated post-psum logits and never dispatches
per shard. Runs on forced host devices (conftest.py sets
XLA_FLAGS=--xla_force_host_platform_device_count=8), so everything
here is CPU-testable and the same code path lights up on real chips.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from defer_tpu import obs
from defer_tpu.models.gpt import tiny_gpt
from defer_tpu.models.llama import tiny_llama
from defer_tpu.parallel.mesh import make_mesh
from defer_tpu.runtime.paged import PagedDecodeServer, serve_paged


@pytest.fixture(scope="module")
def model():
    dec = tiny_gpt(64)
    params = dec.init(jax.random.key(0))
    return dec, params


@pytest.fixture(scope="module")
def draft():
    """Same architecture, different weights — rejections every round
    (the test_spec_paged.py divergent-draft idiom)."""
    dec = tiny_gpt(64)
    params = dec.init(jax.random.key(7))
    return dec, params


def _requests(vocab):
    """Shared prefix on the first two (radix hits under prefix_cache),
    one prompt long enough that prefill_chunk=8 actually splits it."""
    rng = np.random.default_rng(3)
    base = jnp.asarray(rng.integers(1, vocab, size=(1, 6)), jnp.int32)
    ext = jnp.asarray(rng.integers(1, vocab, size=(1, 4)), jnp.int32)
    return [
        (base, 7),
        (jnp.concatenate([base, ext], axis=1), 5),
        (jnp.asarray(rng.integers(1, vocab, size=(1, 11)), jnp.int32), 6),
    ]


@pytest.fixture(scope="module")
def solo(model):
    """Greedy references: every TP config below must reproduce the
    plain decoder's own tokens, not merely agree with mesh=None."""
    dec, params = model
    reqs = _requests(dec.cfg.vocab_size)
    return reqs, [dec.generate(params, p, s) for p, s in reqs]


def _mesh(tp):
    return make_mesh({"model": tp}, jax.devices()[:tp])


# Curated cut of the (attention x prefix_cache x window x spec x
# chunked) space — every sharded tick body appears at least once, at
# tp=2 and two tp=4 points, without compiling the full product.
MATRIX = [
    ("gathered", False, 1, 0, None, 2),
    ("blockwise", True, 1, 0, None, 2),
    ("pallas", False, 1, 0, None, 2),
    ("gathered", False, 8, 0, None, 2),
    ("blockwise", False, 1, 4, None, 2),
    ("gathered", True, 1, 0, 8, 2),
    ("gathered", False, 8, 0, None, 4),
    ("blockwise", False, 1, 0, None, 4),
]


@pytest.mark.parametrize(
    "attention,prefix_cache,window,spec_k,chunk,tp", MATRIX
)
def test_tp_token_identical(
    model, draft, solo, attention, prefix_cache, window, spec_k, chunk, tp
):
    dec, params = model
    reqs, want = solo
    spec = (
        dict(spec_draft=draft[0], spec_params=draft[1], spec_k=spec_k)
        if spec_k
        else {}
    )
    outs, stats = serve_paged(
        dec, params, reqs, num_blocks=16, block_size=4, max_batch=2,
        attention=attention, prefix_cache=prefix_cache,
        decode_window=window, prefill_chunk=chunk, mesh=_mesh(tp),
        **spec,
    )
    for i, (got, ref) in enumerate(zip(outs, want)):
        np.testing.assert_array_equal(
            np.asarray(got), np.asarray(ref),
            err_msg=f"request {i} attention={attention} tp={tp}",
        )
    assert stats["mesh_shape"] == f"model={tp}"
    assert stats["tp_psums"] > 0


def test_tp_span_ladder_token_identical(model):
    """The gathered step's span ladder through `_jit_tick`'s
    shard_map on two devices: depths that start on the first rung and
    end on the last serve the plain decoder's own tokens, every rung
    is built before the first tick, and no tick's dispatch builds a
    program."""
    from defer_tpu.obs import spans

    dec, params = model
    rng = np.random.default_rng(23)
    reqs = [
        (jnp.asarray(rng.integers(1, dec.cfg.vocab_size, size=(1, n)), jnp.int32), s)
        for n, s in [(9, 30), (3, 5), (50, 14)]
    ]
    obs.reset()
    outs, _ = serve_paged(
        dec, params, reqs, num_blocks=37, block_size=4, max_batch=2,
        mesh=_mesh(2),
    )
    for (p, s), got in zip(reqs, outs):
        np.testing.assert_array_equal(
            np.asarray(got), np.asarray(dec.generate(params, p, s))
        )
    records = spans.snapshot().records
    by_id = {r.id: r for r in records}
    ticks = [r for r in records if r.name == "paged.tick"]
    assert {r.counts["span_rows"] for r in ticks} >= {16, 64}
    builds = [r for r in records if r.name == "jax.build"]
    rungs = [r for r in builds if r.counts["kind"] == "paged_step"]
    assert [r.counts["span_rows"] for r in rungs] == [16, 24, 64]
    assert all(r.t1 <= ticks[0].t0 for r in rungs)
    under = {by_id[r.parent].name for r in builds if r.parent in by_id}
    assert not under & {"paged.tick.plan", "paged.tick.dispatch"}, under


def test_size1_mesh_matches_mesh_none(model, solo):
    """A 1-device mesh runs the shard_map path end to end; tokens must
    match mesh=None exactly (the degenerate-mesh contract)."""
    dec, params = model
    reqs, _ = solo
    outs0, st0 = serve_paged(
        dec, params, reqs, num_blocks=16, block_size=4, max_batch=2
    )
    outs1, st1 = serve_paged(
        dec, params, reqs, num_blocks=16, block_size=4, max_batch=2,
        mesh=_mesh(1),
    )
    for a, b in zip(outs0, outs1):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert st0["mesh_shape"] is None and st0["tp_psums"] == 0
    assert st1["mesh_shape"] == "model=1" and st1["tp_psums"] > 0


def test_kv_rows_scale_dispatches_do_not(model, solo):
    """The counter pin: per-shard KV reads halve at tp=2, host
    dispatches per token do not move, and the collective count matches
    the server's own host-side mirror."""
    dec, params = model
    reqs, _ = solo
    kw = dict(
        num_blocks=16, block_size=4, max_batch=2, attention="blockwise"
    )
    with obs.counter_deltas() as d0:
        serve_paged(dec, params, reqs, **kw)
    with obs.counter_deltas() as d2:
        _, st2 = serve_paged(dec, params, reqs, mesh=_mesh(2), **kw)
    rows0 = d0['defer_kv_rows_read_total{server="paged"}']
    rows2 = d2['defer_kv_rows_read_total{mesh="model=2",server="paged"}']
    assert rows0 > 0 and rows2 * 2 == rows0
    disp0 = d0['defer_host_dispatches_total{server="paged"}']
    disp2 = d2['defer_host_dispatches_total{mesh="model=2",server="paged"}']
    assert disp0 == disp2 > 0
    psums = d2['defer_tp_psum_total{mesh="model=2",server="paged"}']
    assert psums == st2["tp_psums"] > 0
    assert d0.get('defer_tp_psum_total{server="paged"}', 0) == 0


def test_kv_head_shard_errors():
    """Satellite contract: both indivisibility failures are caught at
    construction with the fix spelled out, before any compile."""
    dec = tiny_llama(32)  # num_kv_heads=2
    params = dec.init(jax.random.key(0))
    with pytest.raises(ValueError, match="num_kv_heads=2 is smaller"):
        PagedDecodeServer(
            dec, params, num_blocks=8, block_size=4, max_batch=2,
            mesh=_mesh(4),
        )
    dec4 = tiny_gpt(32)  # 4 heads, MHA: kv_heads=4
    params4 = dec4.init(jax.random.key(0))
    with pytest.raises(ValueError, match="does not divide"):
        PagedDecodeServer(
            dec4, params4, num_blocks=8, block_size=4, max_batch=2,
            mesh=_mesh(3),
        )


def test_fleet_replicas_get_meshes(model, solo):
    """`model_axis_size=` turns every fleet replica into an N-chip
    mesh via the same ctor path; outputs stay token-identical and the
    per-replica stats carry the mesh shape. Default placement (no
    model_axis_size) spreads replicas over distinct single devices."""
    from defer_tpu.fleet.api import serve_fleet

    dec, params = model
    reqs, want = solo
    kw = dict(n_replicas=2, num_blocks=16, block_size=4, max_batch=2)
    outs, st = serve_fleet(dec, params, reqs, model_axis_size=2, **kw)
    for got, ref in zip(outs, want):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
    assert [r["mesh_shape"] for r in st["replicas"]] == ["model=2"] * 2
    outs1, st1 = serve_fleet(dec, params, reqs, **kw)
    for got, ref in zip(outs1, want):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
    assert all(r["mesh_shape"] is None for r in st1["replicas"])


def test_disagg_ingest_scatters_into_shards(model):
    """Disagg wire blobs are full-head (format unchanged); a meshed
    decode server splits them on the head axis at ingest. Delivering a
    real prefill worker blob must finish token-identical to the
    unmeshed server fed the same blob."""
    from defer_tpu.disagg.prefill_worker import run_prefill

    dec, params = model
    prompt = jnp.asarray([[3, 9, 27, 5, 11]], jnp.int32)
    k, v, lg = run_prefill(
        dec, params, np.asarray(prompt), block_size=4
    )
    outs = []
    for mesh in (None, _mesh(2)):
        srv = PagedDecodeServer(
            dec, params, num_blocks=16, block_size=4, max_batch=2,
            mesh=mesh,
        )
        rid = srv.submit_prefilled(prompt, 6)
        srv.deliver_kv(rid, k, v, lg)
        outs.append(srv.run()[rid])
    np.testing.assert_array_equal(
        np.asarray(outs[0]), np.asarray(outs[1])
    )
    want = dec.generate(params, prompt, 6)
    np.testing.assert_array_equal(np.asarray(outs[0]), np.asarray(want))
