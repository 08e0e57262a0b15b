"""The decode step attends through the block table: `table_flash_decode`
folds each slot's live pages of one layer straight out of the pool,
256 rows of every KV head a grid cell, and agrees with `flash_decode`
over the span the gathered step makes of the same table; the paged
server takes that path wherever the kernel runs on a floating-point
pool and a table of whole 256-row chunks, serves the tokens the
gathered path serves, and says so in its tick span and its K/V-row
counters.

Kernel agreement is held to float32 rounding: |table - flash| <=
1e-6 x max|flash| (both fold the same 256-row blocks in the same
order; bit-equal was read, in interpret mode and on a v5e)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from defer_tpu import obs
from defer_tpu.models import gpt
from defer_tpu.models.llama import mistral_config
from defer_tpu.obs import spans
from defer_tpu.ops.pallas_attention import (
    flash_decode,
    table_decode_pages,
    table_flash_decode,
)
from defer_tpu.parallel.mesh import make_mesh
from defer_tpu.runtime.paged import PagedDecodeServer

TOLERANCE = 1e-6


def pool_and_tables(rng, b, hkv, d, bs, cols, pos, dtype, layers=3):
    """A pool of `layers` layers whose blocks hold random rows, and a
    table per slot naming distinct blocks up to its depth; the rest of
    each row is trash block 0, as the server leaves it."""
    nb = b * cols + 1
    pool = [
        jnp.asarray(rng.standard_normal((layers, nb, hkv, bs, d)), dtype)
        for _ in range(2)
    ]
    free = rng.permutation(np.arange(1, nb))
    tables = np.zeros((b, cols), np.int32)
    for i, p in enumerate(pos):
        n = p // bs + 1
        tables[i, :n], free = free[:n], free[n:]
    return (*pool, jnp.asarray(tables))


def gathered_flash(q, pk, pv, layer, tables, pos, window):
    """What the gathered step attends: the table's pages of one layer
    made into a contiguous span, rows ahead of heads, then
    `flash_decode` over it."""
    b, cols = tables.shape
    _, _, hkv, bs, d = pk.shape
    span = [
        a[layer][tables].transpose(0, 2, 1, 3, 4).reshape(b, hkv, cols * bs, d)
        for a in (pk, pv)
    ]
    return flash_decode(q, *span, pos, window=window, interpret=True)


@pytest.mark.parametrize(
    "hq,hkv,d,qdt,pos,window",
    [
        # G = 4, padded to 8 rows, Dh 128: ragged depths, an idle slot
        # at position 0, a depth that ends mid-page and mid-chunk.
        (8, 2, 128, jnp.bfloat16, [0, 300, 511, 37], None),
        # G = 16, Dh 128, a window that binds (its first live page and
        # chunk lie past the table's start).
        (32, 2, 128, jnp.bfloat16, [5, 256, 257, 450], 100),
        # G = 8, Dh 256, float32 queries over a bf16 pool.
        (16, 2, 256, jnp.float32, [17, 255, 0, 498], None),
    ],
    ids=["g4-dh128", "g16-dh128-window", "g8-dh256-f32q"],
)
def test_the_table_kernel_is_flash_decode_over_the_gathered_span(
    hq, hkv, d, qdt, pos, window
):
    rng = np.random.default_rng(len(pos) + hq)
    bs, cols, layer = 16, 32, 1
    pk, pv, tables = pool_and_tables(
        rng, len(pos), hkv, d, bs, cols, pos, jnp.bfloat16
    )
    q = jnp.asarray(rng.standard_normal((len(pos), hq, d)), qdt)
    posv = jnp.asarray(pos, jnp.int32)
    got = table_flash_decode(
        q, pk, pv, jnp.int32(layer), tables, posv, window=window,
        interpret=True,
    )
    want = gathered_flash(q, pk, pv, layer, tables, posv, window)
    assert got.shape == want.shape and got.dtype == qdt
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert np.isfinite(got).all()
    np.testing.assert_allclose(
        got, want, rtol=0, atol=TOLERANCE * np.abs(want).max()
    )


def test_the_table_kernel_reads_the_layer_it_is_given():
    """The same table over another layer of the pool attends that
    layer's rows, and an f32 pool with blocks of 8 rows (32 pages a
    chunk) folds as the gathered span does."""
    rng = np.random.default_rng(7)
    pos = [40, 300, 3]
    pk, pv, tables = pool_and_tables(rng, 3, 2, 16, 8, 64, pos, jnp.float32)
    q = jnp.asarray(rng.standard_normal((3, 4, 16)), jnp.float32)
    posv = jnp.asarray(pos, jnp.int32)
    outs = [
        np.asarray(
            table_flash_decode(
                q, pk, pv, jnp.int32(layer), tables, posv, interpret=True
            )
        )
        for layer in (0, 2)
    ]
    assert not np.allclose(outs[0], outs[1])
    for layer, got in zip((0, 2), outs):
        want = np.asarray(gathered_flash(q, pk, pv, layer, tables, posv, None))
        np.testing.assert_allclose(
            got, want, rtol=0, atol=TOLERANCE * np.abs(want).max()
        )


@pytest.mark.parametrize(
    "bs,cols,pages",
    [(16, 64, 16), (16, 24, None), (16, 8, None), (8, 64, 32), (4, 64, 64),
     (32, 8, 8), (48, 16, None)],
)
def test_a_table_of_no_whole_chunks_is_refused(bs, cols, pages):
    """A table whose rows are no whole number of 256-row chunks (a
    rung of 384 rows, a table of 128) has no chunking, and the kernel
    refuses it by name; the server routes such a table to the
    gathered code (`test_the_tick_says_how_it_attended`)."""
    assert table_decode_pages(bs, cols) == pages
    if pages is None:
        pk = jnp.zeros((1, 4, 2, bs, 16), jnp.float32)
        with pytest.raises(ValueError, match="256-row chunks"):
            table_flash_decode(
                jnp.zeros((1, 4, 16)), pk, pk, 0,
                jnp.zeros((1, cols), jnp.int32), jnp.zeros((1,), jnp.int32),
                interpret=True,
            )


# -- the server ----------------------------------------------------------


def dense(dtype=jnp.float32):
    return gpt.GptDecoder(
        mistral_config(
            num_layers=2, dim=128, num_heads=4, num_kv_heads=2, ffn_dim=256,
            vocab_size=128, max_len=1024, window=1024,
        ),
        compute_dtype=dtype,
    )


def kinds_and_experts():
    """Family `cohere2_moe`: [sliding, sliding, sliding, full] with a
    window of 64 that binds, a parallel block, 4 held experts."""
    from tests.test_cohere2_moe import TOY, family

    return family.build_decoder({
        **TOY, "max_position_embeddings": 1024, "sliding_window": 64,
    })


def recurrent_and_attention():
    """Family `qwen3_next`: [linear, linear, linear, full] twice, gated
    attention, float32 activations."""
    import dataclasses

    from tests.test_qwen3_next import toy_decoder

    dec = toy_decoder()
    return gpt.GptDecoder(
        dataclasses.replace(dec.cfg, max_len=1024), compute_dtype=jnp.float32
    )


# Depths that start in the 256-row rung and, for the third, climb out
# of it onto the 384-row rung, which the gathered code takes.
REQUESTS = [(40, 12), (150, 20), (236, 30)]


def serve(make, *, attend=None, mesh=None, monkeypatch=None):
    """Greedy tokens of REQUESTS through three slots of a fresh
    decoder; `attend` pins the path."""
    if attend is not None:
        with monkeypatch.context() as m:
            m.setattr(PagedDecodeServer, "_attend", lambda self, cols: attend)
            return serve(make, mesh=mesh)
    dec = make()
    params = dec.init(jax.random.key(0))
    srv = PagedDecodeServer(
        dec, params, num_blocks=64, block_size=16, max_batch=3, mesh=mesh,
    )
    out = {}
    srv.on_token = lambda rid, tok, done: out.setdefault(rid, []).append(tok)
    rng = np.random.default_rng(3)
    rids = [
        srv.submit(
            jnp.asarray(rng.integers(1, 128, (1, n)), jnp.int32), steps
        )
        for n, steps in REQUESTS
    ]
    srv.run()
    return [out[r] for r in rids]


@pytest.mark.parametrize(
    "make", [dense, kinds_and_experts, recurrent_and_attention],
    ids=["dense", "cohere2_moe", "qwen3_next"],
)
def test_the_table_path_serves_the_gathered_paths_tokens(make, monkeypatch):
    monkeypatch.setenv("DEFER_TPU_PALLAS_INTERPRET", "1")
    obs.reset()
    table = serve(make)
    attends = {
        r.counts["attend"] for r in spans.snapshot().records
        if r.name == "paged.tick" and r.counts.get("kind") == "plain"
    }
    assert attends == {"table", "gathered"}
    gathered = serve(make, attend="gathered", monkeypatch=monkeypatch)
    assert table == gathered
    assert [len(t) for t in table] == [s for _, s in REQUESTS]


def test_the_table_path_runs_on_the_shards_local_heads(monkeypatch):
    """Under `mesh=` each shard's kernel reads its own KV heads of the
    pool: two shards serve the one-device server's tokens."""
    monkeypatch.setenv("DEFER_TPU_PALLAS_INTERPRET", "1")
    want = serve(dense)
    mesh = make_mesh({"model": 2}, jax.devices()[:2])
    obs.reset()
    assert serve(dense, mesh=mesh) == want
    assert "table" in {
        r.counts["attend"] for r in spans.snapshot().records
        if r.name == "paged.tick" and r.counts.get("kind") == "plain"
    }


def test_the_tick_says_how_it_attended(monkeypatch):
    """`paged.tick` carries `attend`, and `defer_kv_rows_read_total`
    counts what the kernel reads on the table path: each slot's live
    pages. The gathered path counts the rung's rows for every slot;
    where the kernel does not run (plain XLA here) every tick is
    gathered."""
    bs, b = 16, 3

    def ticks_and_rows():
        obs.reset()
        with obs.counter_deltas() as d:
            serve(dense)
        ticks = [
            r.counts for r in spans.snapshot().records
            if r.name == "paged.tick" and r.counts.get("kind") == "plain"
        ]
        return ticks, d['defer_kv_rows_read_total{server="paged"}'], d[
            'defer_kv_rows_gathered_baseline_total{server="paged"}'
        ]

    ticks, read, base = ticks_and_rows()
    assert {t["attend"] for t in ticks} == {"gathered"}
    assert read == sum(b * t["span_rows"] for t in ticks)
    monkeypatch.setenv("DEFER_TPU_PALLAS_INTERPRET", "1")
    ticks_k, read_k, base_k = ticks_and_rows()
    assert base_k == base and len(ticks_k) == len(ticks)
    table = [t for t in ticks_k if t["attend"] == "table"]
    assert table and all(t["span_rows"] == 256 for t in table)
    assert all(t["span_rows"] == 384 for t in ticks_k if t not in table)
    # The live pages of the table ticks are fewer than their rungs'.
    gathered_rows = sum(
        b * t["span_rows"] for t in ticks_k if t["attend"] == "gathered"
    )
    assert 0 < read_k - gathered_rows < sum(b * 256 for _ in table)
    assert (read_k - gathered_rows) % bs == 0
