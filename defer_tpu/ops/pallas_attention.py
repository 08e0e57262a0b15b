"""Pallas flash attention for TPU.

The hot op of the transformer stack (SURVEY.md §5 notes the reference has
no attention at all; BERT-base in BASELINE.json is served through the
pipeline, and long-context support is first-class here). This kernel
keeps the S×S score matrix out of HBM entirely: each (batch·head,
q-block) grid cell streams K/V blocks through VMEM with the online
softmax recurrence, so memory is O(S·D) instead of O(S²) and the two
matmuls per block land on the MXU back-to-back.

`multi_head_attention` (defer_tpu/ops/attention.py) dispatches here on
TPU for the shapes `flash_unsupported` accepts and takes the XLA einsum
path for the rest; tests run this kernel in interpreter mode on CPU
against that reference.

Grid semantics every kernel here declares to Mosaic: the batch and head
axes are "parallel" (cells share nothing), and the decode/prefill
kernels' innermost K-block axis is "arbitrary" — it must run in order
on one core, because the online-softmax carry lives in VMEM scratch
across it. `table_flash_decode` declares both of its axes "arbitrary":
each cell starts the copies of the next live cell, in its slot or the
next, so the whole grid runs in order.

Differentiable: a custom VJP recomputes attention with the XLA
reference implementation in the backward pass (flash-style
rematerialization — nothing but q/k/v is saved for backward).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Finite stand-in for -inf: keeps fully-masked rows NaN-free in the
# online-softmax recurrence (exp(MASK - MASK) would be NaN with -inf).
_MASK_VALUE = -0.7 * float(np.finfo(np.float32).max)


# What Mosaic fits of blocked operands into its default 16 MiB of scoped
# VMEM. Compiled ahead of time for a v5e: 14.75 MiB fit, 15.25 MiB fail
# with "Scoped allocation with size 16.25M and limit 16.00M".
_BLOCKED_VMEM_BYTES = 15 * 2**20


def _sublane(dtype) -> int:
    """Rows of one native (sublane, 128) tile: 8 for f32, 16 for bf16,
    32 for int8."""
    return 32 // jnp.dtype(dtype).itemsize


def _pick_block(s: int, preferred: int, sublane: int = 8) -> int | None:
    """Block length along an axis of `s` rows: the whole axis when it
    fits in `preferred`, else the largest divisor of `s` that is
    <= preferred and a multiple of `sublane` (the dtype's tile rows).
    Either way a multiple of 8 — Mosaic must prove that a
    `pl.ds(i * block, block)` row slice starts on a tile boundary.
    None when `s` has no such block."""
    if s <= preferred:
        return s if s % 8 == 0 else None
    b = preferred - preferred % sublane
    while b >= sublane:
        if s % b == 0:
            return b
        b -= sublane
    return None


def decode_k_block(s: int, dtype, block_k: int = 256) -> int | None:
    """The K-block length `flash_decode` tiles a cache of `s` rows
    with, or None when it cannot take that cache — what a caller asks
    before choosing the kernel."""
    return _pick_block(s, block_k, _sublane(dtype))


def _grid_params(*semantics: str):
    return pltpu.CompilerParams(dimension_semantics=semantics)


def _mha_kernel(
    q_ref,
    k_ref,
    v_ref,
    o_ref,
    *,
    sm_scale: float,
    causal: bool,
    block_k: int,
):
    q = q_ref[0].astype(jnp.float32) * sm_scale  # (bq, d)
    bq, d = q.shape
    s_k = k_ref.shape[1]
    q_start = pl.program_id(1) * bq

    def body(i, carry):
        m, l, acc = carry
        k = k_ref[0, pl.ds(i * block_k, block_k), :].astype(jnp.float32)
        v = v_ref[0, pl.ds(i * block_k, block_k), :].astype(jnp.float32)
        s = lax.dot_general(
            q,
            k,
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (bq, block_k)
        if causal:
            rows = q_start + lax.broadcasted_iota(
                jnp.int32, (bq, block_k), 0
            )
            cols = i * block_k + lax.broadcasted_iota(
                jnp.int32, (bq, block_k), 1
            )
            s = jnp.where(rows >= cols, s, _MASK_VALUE)
        m_new = jnp.maximum(m, s.max(axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_new = l * alpha + p.sum(axis=-1)
        acc_new = acc * alpha[:, None] + lax.dot_general(
            p,
            v,
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return m_new, l_new, acc_new

    num_k = s_k // block_k
    if causal:
        # Only blocks intersecting the causal triangle of this q block.
        num_k = jnp.minimum(
            num_k, (q_start + bq + block_k - 1) // block_k
        )
    init = (
        jnp.full((bq,), _MASK_VALUE, jnp.float32),
        jnp.zeros((bq,), jnp.float32),
        jnp.zeros((bq, d), jnp.float32),
    )
    _, l, acc = lax.fori_loop(0, num_k, body, init)
    o_ref[0] = (acc / l[:, None]).astype(o_ref.dtype)


def _flash_fwd_impl(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool,
    interpret: bool,
    block_q: int = 256,
    block_k: int = 256,
) -> jax.Array:
    reason = flash_unsupported(
        q.shape, k.shape, q.dtype,
        causal=causal, block_q=block_q, block_k=block_k,
    )
    if reason:
        raise ValueError(reason)
    b, h, s_q, d = q.shape
    s_k = k.shape[2]
    sub = _sublane(q.dtype)
    bq = _pick_block(s_q, block_q, sub)
    bk = _pick_block(s_k, block_k, sub)
    qf = q.reshape(b * h, s_q, d)
    kf = k.reshape(b * h, s_k, d)
    vf = v.reshape(b * h, s_k, d)
    kernel = functools.partial(
        _mha_kernel,
        sm_scale=d**-0.5,
        causal=causal,
        block_k=bk,
    )
    out = pl.pallas_call(
        kernel,
        grid=(b * h, s_q // bq),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda bh, qi: (bh, qi, 0)),
            pl.BlockSpec((1, s_k, d), lambda bh, qi: (bh, 0, 0)),
            pl.BlockSpec((1, s_k, d), lambda bh, qi: (bh, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda bh, qi: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, s_q, d), q.dtype),
        compiler_params=_grid_params("parallel", "parallel"),
        interpret=interpret,
        name="flash_fwd",
    )(qf, kf, vf)
    return out.reshape(b, h, s_q, d)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _flash(causal: bool, interpret: bool, q, k, v):
    return _flash_fwd_impl(q, k, v, causal=causal, interpret=interpret)


def _flash_fwd(causal, interpret, q, k, v):
    return _flash(causal, interpret, q, k, v), (q, k, v)


def _flash_bwd(causal, interpret, res, g):
    # Flash-style rematerialization: recompute attention with the XLA
    # reference implementation and differentiate that. Saves only q/k/v
    # for backward; XLA fuses the recompute into the backward matmuls.
    from defer_tpu.ops.attention import attention_reference

    q, k, v = res
    _, vjp = jax.vjp(
        lambda q_, k_, v_: attention_reference(q_, k_, v_, causal=causal),
        q,
        k,
        v,
    )
    return vjp(g)


_flash.defvjp(_flash_fwd, _flash_bwd)


def _decode_lo_hi(p_b, block_k: int, window: int | None):
    """First/last LIVE K-block (inclusive) for a sequence whose last
    valid key is `p_b`: blocks wholly outside [pos-window+1, pos] are
    dead. Shared by the kernel's compute gate and the index maps'
    DMA-clamping so the two can never disagree."""
    hi = p_b // block_k
    lo = (
        jnp.maximum(p_b - window + 1, 0) // block_k
        if window is not None
        else jnp.int32(0)
    )
    return lo, hi


def _decode_kernel(
    pos_ref,
    q_ref,
    k_ref,
    v_ref,
    o_ref,
    m_scr,
    l_scr,
    acc_scr,
    *,
    sm_scale: float,
    block_k: int,
    window: int | None,
    num_kb: int,
):
    """One (batch, kv-head, k-block) cell: the query GROUP (G rows
    sharing this KV head — GQA) folds one block_k-row K/V tile into the
    online-softmax carry held in VMEM scratch (the k-block axis is the
    innermost grid dim, so scratch persists across it per (batch,
    head)). VMEM residency is O(block_k), not O(max_len): the index
    maps stage only this cell's tile. Dead blocks — wholly outside
    [pos-window+1, pos] — are compute-gated off here AND clamped to a
    live block index in the index maps, so revisiting the same tile
    issues no new DMA; decode stays O(live rows) in both bandwidth and
    compute."""
    kb = pl.program_id(2)
    p_b = pos_ref[pl.program_id(0)]
    lo, hi = _decode_lo_hi(p_b, block_k, window)

    @pl.when(kb == 0)
    def _init():
        m_scr[:] = jnp.full(m_scr.shape, _MASK_VALUE, jnp.float32)
        l_scr[:] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[:] = jnp.zeros(acc_scr.shape, jnp.float32)

    @pl.when((kb >= lo) & (kb <= hi))
    def _fold():
        q = q_ref[0, 0].astype(jnp.float32) * sm_scale  # (G, d)
        g = q.shape[0]
        k = k_ref[0, 0].astype(jnp.float32)  # (block_k, d)
        v = v_ref[0, 0].astype(jnp.float32)
        s = lax.dot_general(
            q,
            k,
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (G, block_k)
        cols = kb * block_k + lax.broadcasted_iota(
            jnp.int32, (g, block_k), 1
        )
        mask = cols <= p_b
        if window is not None:
            mask &= cols > p_b - window
        s = jnp.where(mask, s, _MASK_VALUE)
        m = m_scr[:]
        m_new = jnp.maximum(m, s.max(axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[:, None])
        m_scr[:] = m_new
        l_scr[:] = l_scr[:] * alpha + p.sum(axis=-1)
        acc_scr[:] = acc_scr[:] * alpha[:, None] + lax.dot_general(
            p,
            v,
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(kb == num_kb - 1)
    def _emit():
        o_ref[0, 0] = (acc_scr[:] / l_scr[:][:, None]).astype(
            o_ref.dtype
        )


def flash_decode(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    pos: jax.Array,
    *,
    window: int | None = None,
    interpret: bool = False,
    block_k: int = 256,
) -> jax.Array:
    """Flash-decode: ONE query token per sequence against the KV cache
    — the serving hot op (decode is cache-bandwidth bound; this fuses
    mask + online softmax + weighted sum into one pass over the live
    cache rows and never materializes the [B, H, S] score matrix in
    HBM).

    q [B, Hq, Dh]; k/v [B, Hkv, S, Dh] (GQA: Hq = G*Hkv, the group
    attends its shared KV head); pos [B] int32 = index of each
    sequence's last valid key, INCLUSIVE (per-slot depths — continuous
    batching — are the native shape; broadcast a scalar for uniform
    batches). Returns [B, Hq, Dh].

    Query groups narrower than 8 rows are zero-padded to the TPU
    sublane tile and sliced back (padded rows attend garbage that is
    discarded). Positions ride scalar prefetch (SMEM): the K-block
    index maps read them to clamp dead blocks onto a live tile, so
    only O(block_k) K/V rows are ever VMEM-resident and dead grid
    cells issue no DMA.
    """
    b, hq, d = q.shape
    _, hkv, s, _ = k.shape
    if hq % hkv:
        raise ValueError(f"Hq={hq} must be a multiple of Hkv={hkv}")
    g = hq // hkv
    bk = decode_k_block(s, k.dtype, block_k)
    if bk is None:
        raise ValueError(f"no tile-aligned K block divides cache len {s}")
    num_kb = s // bk
    g_pad = max(g, 8)
    qg = q.reshape(b, hkv, g, d)
    if g_pad != g:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, g_pad - g), (0, 0)))
    pos1 = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (b,))
    kernel = functools.partial(
        _decode_kernel,
        sm_scale=d**-0.5,
        block_k=bk,
        window=window,
        num_kb=num_kb,
    )

    def kv_index(i, j, kb, pos_ref):
        lo, hi = _decode_lo_hi(pos_ref[i], bk, window)
        return (i, j, jnp.clip(kb, lo, hi), 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, hkv, num_kb),
        in_specs=[
            pl.BlockSpec(
                (1, 1, g_pad, d), lambda i, j, kb, pos_ref: (i, j, 0, 0)
            ),
            pl.BlockSpec((1, 1, bk, d), kv_index),
            pl.BlockSpec((1, 1, bk, d), kv_index),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, g_pad, d), lambda i, j, kb, pos_ref: (i, j, 0, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((g_pad,), jnp.float32),
            pltpu.VMEM((g_pad,), jnp.float32),
            pltpu.VMEM((g_pad, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, g_pad, d), q.dtype),
        compiler_params=_grid_params("parallel", "parallel", "arbitrary"),
        interpret=interpret,
        name="flash_decode",
    )(pos1, qg, k, v)
    return out[:, :, :g, :].reshape(b, hq, d)


def _slot_scales(scale: jax.Array, tables: jax.Array) -> jax.Array:
    """[NB, Hkv] per-(block, head) pool scales -> [B*Hkv, 1, MB] f32
    per-slot rows: entry tb of row i*Hkv + j is the scale of the pool
    block that slot i's table names in column tb, for KV head j. The
    kernels stage one such row per (slot, head) cell into SMEM — a
    scalar belongs there, and a (1, 1) VMEM block of the [NB, Hkv]
    tensor is below Mosaic's minimum tile — and read entry tb as a
    scalar. The gather is B*MB*Hkv floats; the pool itself is still
    read through the block table inside the kernel."""
    b, mb = tables.shape
    hkv = scale.shape[1]
    rows = jnp.asarray(scale, jnp.float32)[tables]  # [B, MB, Hkv]
    return rows.transpose(0, 2, 1).reshape(b * hkv, 1, mb)


def _scale_spec(hkv: int, mb: int) -> pl.BlockSpec:
    return pl.BlockSpec(
        (1, 1, mb),
        lambda i, j, tb, *_: (i * hkv + j, 0, 0),
        memory_space=pltpu.SMEM,
    )


def _paged_decode_kernel(
    tables_ref,
    pos_ref,
    q_ref,
    k_ref,
    v_ref,
    *rest,
    sm_scale: float,
    block_size: int,
    window: int | None,
    num_tb: int,
    quantized: bool,
):
    """One (batch, kv-head, table-column) cell of paged flash-decode:
    like `_decode_kernel`, but the K/V tile staged for column `tb` is
    whatever POOL block the slot's table names — the index maps do the
    block-table indirection, so the kernel never sees a contiguous
    cache and nothing is gathered in HBM. Dead columns (wholly outside
    [pos-window+1, pos]) are compute-gated off here AND clamped onto a
    live column's pool block in the index maps, so per slot only its
    LIVE blocks are ever fetched — the bandwidth contract the paged
    pool exists for. Unallocated table entries point at trash block 0
    (runtime/paged.py invariant); the clamp keeps them un-fetched and
    the position mask keeps block-`hi` rows past `pos` unattended.

    With `quantized`, k_ref/v_ref are int8 pool tiles and two extra
    SMEM refs follow: this (slot, head) cell's [1, 1, MB] row of
    per-(block, head) symmetric scales, gathered through the same
    block table (`_slot_scales`). The fold widens int8 -> f32 and
    multiplies entry tb in VMEM, so HBM sees one byte per element —
    bandwidth, not just residency, halves."""
    if quantized:
        ks_ref, vs_ref, o_ref, m_scr, l_scr, acc_scr = rest
    else:
        ks_ref = vs_ref = None
        o_ref, m_scr, l_scr, acc_scr = rest
    tb = pl.program_id(2)
    p_b = pos_ref[pl.program_id(0)]
    lo, hi = _decode_lo_hi(p_b, block_size, window)

    @pl.when(tb == 0)
    def _init():
        m_scr[:] = jnp.full(m_scr.shape, _MASK_VALUE, jnp.float32)
        l_scr[:] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[:] = jnp.zeros(acc_scr.shape, jnp.float32)

    @pl.when((tb >= lo) & (tb <= hi))
    def _fold():
        q = q_ref[0, 0].astype(jnp.float32) * sm_scale  # (G, d)
        g = q.shape[0]
        k = k_ref[0, 0].astype(jnp.float32)  # (block_size, d)
        v = v_ref[0, 0].astype(jnp.float32)
        if quantized:
            k = k * ks_ref[0, 0, tb]
            v = v * vs_ref[0, 0, tb]
        s = lax.dot_general(
            q,
            k,
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (G, block_size)
        cols = tb * block_size + lax.broadcasted_iota(
            jnp.int32, (g, block_size), 1
        )
        mask = cols <= p_b
        if window is not None:
            mask &= cols > p_b - window
        s = jnp.where(mask, s, _MASK_VALUE)
        m = m_scr[:]
        m_new = jnp.maximum(m, s.max(axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[:, None])
        m_scr[:] = m_new
        l_scr[:] = l_scr[:] * alpha + p.sum(axis=-1)
        acc_scr[:] = acc_scr[:] * alpha[:, None] + lax.dot_general(
            p,
            v,
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(tb == num_tb - 1)
    def _emit():
        o_ref[0, 0] = (acc_scr[:] / l_scr[:][:, None]).astype(
            o_ref.dtype
        )


def paged_flash_decode(
    q: jax.Array,
    pool_k: jax.Array,
    pool_v: jax.Array,
    tables: jax.Array,
    pos: jax.Array,
    *,
    window: int | None = None,
    interpret: bool = False,
    scale_k: jax.Array | None = None,
    scale_v: jax.Array | None = None,
) -> jax.Array:
    """Paged flash-decode: one query token per slot attending its
    BLOCK TABLE directly — no contiguous [B, Hkv, MB*bs, Dh] gather
    ever exists in HBM (the gather is runtime/paged.py's gathered-path
    cost this kernel deletes).

    q [B, Hq, Dh]; pool_k/pool_v [NB, Hkv, bs, Dh] — ONE layer of the
    shared block pool; tables [B, MB] int32 pool indices (unallocated
    entries = trash block 0); pos [B] int32 = each slot's last valid
    key, INCLUSIVE. Returns [B, Hq, Dh].

    Tables and positions ride scalar prefetch (SMEM): the K/V index
    maps resolve column tb of slot i to pool block tables[i, tb],
    clamped into the slot's live range so dead columns re-stage an
    already-resident tile instead of DMAing trash — per-slot bandwidth
    is O(live blocks), the paged-attention point. Query groups
    narrower than 8 rows are zero-padded to the TPU sublane tile and
    sliced back.

    For the int8 pool (runtime/paged.py kv_dtype="int8") pass
    scale_k/scale_v [NB, Hkv] f32 — per-(block, head) symmetric
    scales. The whole [NB, Hkv] tensor does not fit SMEM, so each
    slot's entries are gathered through its table first
    (`_slot_scales`) and staged one (slot, head) row at a time; the
    kernel dequantizes in VMEM — HBM reads of K/V stay int8."""
    b, hq, d = q.shape
    nb, hkv, bs, _ = pool_k.shape
    if (scale_k is None) != (scale_v is None):
        raise ValueError("pass both scale_k and scale_v, or neither")
    quantized = scale_k is not None
    if hq % hkv:
        raise ValueError(f"Hq={hq} must be a multiple of Hkv={hkv}")
    if tables.ndim != 2 or tables.shape[0] != b:
        raise ValueError(
            f"tables must be [B={b}, MB], got {tables.shape}"
        )
    g = hq // hkv
    mb = tables.shape[1]
    g_pad = max(g, 8)
    qg = q.reshape(b, hkv, g, d)
    if g_pad != g:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, g_pad - g), (0, 0)))
    tables = jnp.asarray(tables, jnp.int32)
    pos1 = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (b,))
    kernel = functools.partial(
        _paged_decode_kernel,
        sm_scale=d**-0.5,
        block_size=bs,
        window=window,
        num_tb=mb,
        quantized=quantized,
    )

    def kv_index(i, j, tb, tables_ref, pos_ref):
        lo, hi = _decode_lo_hi(pos_ref[i], bs, window)
        return (tables_ref[i, jnp.clip(tb, lo, hi)], j, 0, 0)

    in_specs = [
        pl.BlockSpec(
            (1, 1, g_pad, d),
            lambda i, j, tb, tables_ref, pos_ref: (i, j, 0, 0),
        ),
        pl.BlockSpec((1, 1, bs, d), kv_index),
        pl.BlockSpec((1, 1, bs, d), kv_index),
    ]
    operands = [qg, pool_k, pool_v]
    if quantized:
        in_specs += [_scale_spec(hkv, mb)] * 2
        operands += [
            _slot_scales(scale_k, tables),
            _slot_scales(scale_v, tables),
        ]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, hkv, mb),
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            (1, 1, g_pad, d),
            lambda i, j, tb, tables_ref, pos_ref: (i, j, 0, 0),
        ),
        scratch_shapes=[
            pltpu.VMEM((g_pad,), jnp.float32),
            pltpu.VMEM((g_pad,), jnp.float32),
            pltpu.VMEM((g_pad, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, g_pad, d), q.dtype),
        compiler_params=_grid_params("parallel", "parallel", "arbitrary"),
        interpret=interpret,
        name="paged_flash_decode",
    )(tables, pos1, *operands)
    return out[:, :, :g, :].reshape(b, hq, d)


# Rows of every KV head that one `table_flash_decode` cell folds: the
# K block `flash_decode` tiles a gathered span with, so that both fold
# the same rows in the same order.
_TABLE_ROWS = 256


def table_decode_pages(block_size: int, cols: int) -> int | None:
    """Pages of `block_size` rows that one `table_flash_decode` cell
    folds, for a block table of `cols` columns; None where the table is
    no whole number of such chunks, which the caller attends otherwise."""
    if _TABLE_ROWS % block_size or cols * block_size % _TABLE_ROWS:
        return None
    return _TABLE_ROWS // block_size


def _table_decode_kernel(
    layer_ref,
    tables_ref,
    pos_ref,
    q_ref,
    k_hbm,
    v_hbm,
    o_ref,
    k_buf,
    v_buf,
    sems,
    buf_ref,
    m_scr,
    l_scr,
    acc_scr,
    *,
    sm_scale: float,
    block_size: int,
    pages: int,
    window: int | None,
    num_chunks: int,
    batch: int,
):
    """One (slot, chunk) cell of `table_flash_decode`: every KV head's
    query group folds the chunk's 256 rows into its online-softmax
    carry, `_decode_kernel`'s fold for all heads at once (one batched
    product over the head axis: head by head, the products left the
    matrix unit idle between them and took twice the time). The
    chunk's pages are copied straight out of the pool, one copy a live
    page of all heads ([Hkv, bs, Dh], contiguous in the pool) into its
    rows of a [Hkv, 256, Dh] buffer; the next live cell's copies, in
    this slot or the next, start before this one is folded, into the
    other of two buffers (so the grid runs in order on one core). A
    dead chunk, wholly outside [pos-window+1, pos], copies and folds
    nothing. Rows of a chunk that no live page filled hold what the
    buffer held before: masked out of the scores, zeroed out of V."""
    i, c = pl.program_id(0), pl.program_id(1)
    rows = pages * block_size
    layer = layer_ref[0]
    p_b = pos_ref[i]
    lo, hi = _decode_lo_hi(p_b, rows, window)
    live = (c >= lo) & (c <= hi)

    def live_pages(j, chunk, buf, act):
        """`act` on the K and V copies of every live page of slot j's
        chunk `chunk` into buffer `buf`: a loop over the live ones, so
        that the kernel lowers to a few operations whatever a chunk's
        page count."""
        first, last = _decode_lo_hi(pos_ref[j], block_size, window)
        base = chunk * pages

        def page(p, carry):
            blk = tables_ref[j, base + p]
            at = pl.ds(pl.multiple_of(p * block_size, block_size), block_size)
            for kv, (src, dst) in enumerate(((k_hbm, k_buf), (v_hbm, v_buf))):
                act(
                    pltpu.make_async_copy(
                        src.at[layer, blk], dst.at[buf, :, at], sems.at[buf, kv]
                    )
                )
            return carry

        lax.fori_loop(
            jnp.clip(first - base, 0, pages),
            jnp.clip(last - base + 1, 0, pages),
            page,
            0,
        )

    def start(dma):
        dma.start()

    def wait(dma):
        dma.wait()

    @pl.when(c == 0)
    def _init():
        m_scr[...] = jnp.full(m_scr.shape, _MASK_VALUE, jnp.float32)
        l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    @pl.when(live & (i == 0) & (c == lo))
    def _first():
        buf_ref[0] = 0
        live_pages(i, c, 0, start)

    @pl.when(live)
    def _fold():
        cur = buf_ref[0]
        last = c == hi
        nxt = jnp.where(last, i + 1, i)

        @pl.when(nxt < batch)
        def _prefetch():
            j = jnp.minimum(nxt, batch - 1)
            first = _decode_lo_hi(pos_ref[j], rows, window)[0]
            live_pages(j, jnp.where(last, first, c + 1), 1 - cur, start)

        live_pages(i, c, cur, wait)
        buf_ref[0] = 1 - cur
        q = q_ref[0].astype(jnp.float32) * sm_scale  # (Hkv, G, d)
        k = k_buf[cur].astype(jnp.float32)  # (Hkv, rows, d)
        v = v_buf[cur].astype(jnp.float32)
        kept = c * rows + lax.broadcasted_iota(jnp.int32, v.shape[1:], 0)
        keep = kept <= p_b
        if window is not None:
            keep &= kept > p_b - window
        v = jnp.where(keep[None], v, 0.0)
        s = lax.dot_general(
            q,
            k,
            (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )  # (Hkv, G, rows)
        cols = c * rows + lax.broadcasted_iota(jnp.int32, s.shape[1:], 1)
        mask = cols <= p_b
        if window is not None:
            mask &= cols > p_b - window
        s = jnp.where(mask[None], s, _MASK_VALUE)
        m = m_scr[...]  # (Hkv, G, 1)
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        m_scr[...] = m_new
        l_scr[...] = l_scr[...] * alpha + p.sum(axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + lax.dot_general(
            p,
            v,
            (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )

    @pl.when(c == num_chunks - 1)
    def _emit():
        o_ref[0] = (acc_scr[...] / l_scr[...]).astype(o_ref.dtype)


def table_flash_decode(
    q: jax.Array,
    pool_k: jax.Array,
    pool_v: jax.Array,
    layer: jax.Array,
    tables: jax.Array,
    pos: jax.Array,
    *,
    window: int | None = None,
    interpret: bool = False,
) -> jax.Array:
    """Flash-decode through the block table, a chunk of 256 rows of
    every KV head a grid cell: one query token per slot attends its
    own pages of one layer of the whole pool, and no contiguous span
    of them is ever made in HBM.

    q [B, Hq, Dh], any float dtype (the fold is float32); pool_k /
    pool_v [L, NB, Hkv, bs, Dh], the whole floating-point pool, read in
    place (`memory_space=ANY`); layer: the pool's layer to read;
    tables [B, MB] int32 pool indices (unallocated entries = trash
    block 0), MB a whole number of 256-row chunks
    (`table_decode_pages`); pos [B] int32 = each slot's last valid
    key, INCLUSIVE, its row already written. Returns [B, Hq, Dh] in
    q.dtype.

    The grid is (slot, chunk); a cell copies the chunk's live pages
    and folds 256 rows per KV head for its query group (zero-padded
    to 8 rows and sliced back), in the order `flash_decode` folds a
    gathered span of the same table with its 256-row K blocks, with
    the same mask (`cols <= pos`, and `> pos - window` under a
    window) and the same live range (`_decode_lo_hi`). VMEM holds two
    K and two V buffers of [Hkv, 256, Dh]."""
    b, hq, d = q.shape
    _, _, hkv, bs, _ = pool_k.shape
    if hq % hkv:
        raise ValueError(f"Hq={hq} must be a multiple of Hkv={hkv}")
    if tables.ndim != 2 or tables.shape[0] != b:
        raise ValueError(f"tables must be [B={b}, MB], got {tables.shape}")
    cols = tables.shape[1]
    pages = table_decode_pages(bs, cols)
    if pages is None:
        raise ValueError(
            f"a table of {cols} columns of {bs} rows is no whole number "
            f"of {_TABLE_ROWS}-row chunks"
        )
    g = hq // hkv
    g_pad = max(g, 8)
    qg = q.reshape(b, hkv, g, d)
    if g_pad != g:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, g_pad - g), (0, 0)))
    num_chunks = cols // pages
    kernel = functools.partial(
        _table_decode_kernel,
        sm_scale=d**-0.5,
        block_size=bs,
        pages=pages,
        window=window,
        num_chunks=num_chunks,
        batch=b,
    )

    def slot_block(i, c, *_):
        return (i, 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, num_chunks),
        in_specs=[
            pl.BlockSpec((1, hkv, g_pad, d), slot_block),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, hkv, g_pad, d), slot_block),
        scratch_shapes=[
            pltpu.VMEM((2, hkv, _TABLE_ROWS, d), pool_k.dtype),
            pltpu.VMEM((2, hkv, _TABLE_ROWS, d), pool_v.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SMEM((1,), jnp.int32),
            pltpu.VMEM((hkv, g_pad, 1), jnp.float32),
            pltpu.VMEM((hkv, g_pad, 1), jnp.float32),
            pltpu.VMEM((hkv, g_pad, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, g_pad, d), q.dtype),
        compiler_params=_grid_params("arbitrary", "arbitrary"),
        interpret=interpret,
        name="table_flash_decode",
    )(
        jnp.asarray(layer, jnp.int32).reshape(1),
        jnp.asarray(tables, jnp.int32),
        jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (b,)),
        qg,
        pool_k,
        pool_v,
    )
    return out[:, :, :g, :].reshape(b, hq, d)


def _prefill_lo_hi(p0, t_q: int, block_size: int, window: int | None):
    """First/last LIVE K-block (inclusive) for a prefill window of
    `t_q` query tokens at absolute positions p0..p0+t_q-1: the last
    query attends through block (p0+t_q-1)//bs, the first one back to
    max(p0-window+1, 0). Shared by the compute gate and the index
    maps' DMA-clamping, mirroring `_decode_lo_hi`."""
    hi = (p0 + t_q - 1) // block_size
    lo = (
        jnp.maximum(p0 - window + 1, 0) // block_size
        if window is not None
        else jnp.int32(0)
    )
    return lo, hi


def _paged_prefill_kernel(
    tables_ref,
    start_ref,
    q_ref,
    k_ref,
    v_ref,
    *rest,
    sm_scale: float,
    block_size: int,
    group: int,
    window: int | None,
    num_tb: int,
    t_q: int,
    quantized: bool,
):
    """One (batch, kv-head, table-column) cell of paged flash-PREFILL:
    `_paged_decode_kernel` generalized from one query token to a
    window of T. The query tile is token-major — row r is query token
    r//G of group row r%G — so the causal mask is per ROW: row r
    attends keys at columns <= start + r//G (each window token sees
    the pool history plus its own predecessors in the window). K/V
    tiles still arrive through the block-table index maps: chunked
    prefill and the speculative verify forward read the pool directly,
    no contiguous gather. Rows padded past T*G attend a superset of
    live columns and are sliced off by the wrapper. With `quantized`,
    two SMEM scale rows follow k/v and the fold dequantizes int8 tiles
    in VMEM (see `_paged_decode_kernel`)."""
    if quantized:
        ks_ref, vs_ref, o_ref, m_scr, l_scr, acc_scr = rest
    else:
        ks_ref = vs_ref = None
        o_ref, m_scr, l_scr, acc_scr = rest
    tb = pl.program_id(2)
    p0 = start_ref[pl.program_id(0)]
    lo, hi = _prefill_lo_hi(p0, t_q, block_size, window)

    @pl.when(tb == 0)
    def _init():
        m_scr[:] = jnp.full(m_scr.shape, _MASK_VALUE, jnp.float32)
        l_scr[:] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[:] = jnp.zeros(acc_scr.shape, jnp.float32)

    @pl.when((tb >= lo) & (tb <= hi))
    def _fold():
        q = q_ref[0, 0].astype(jnp.float32) * sm_scale  # (R, d)
        r = q.shape[0]
        k = k_ref[0, 0].astype(jnp.float32)  # (block_size, d)
        v = v_ref[0, 0].astype(jnp.float32)
        if quantized:
            k = k * ks_ref[0, 0, tb]
            v = v * vs_ref[0, 0, tb]
        s = lax.dot_general(
            q,
            k,
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (R, block_size)
        cols = tb * block_size + lax.broadcasted_iota(
            jnp.int32, (r, block_size), 1
        )
        qpos = (
            p0
            + lax.broadcasted_iota(jnp.int32, (r, block_size), 0) // group
        )
        mask = cols <= qpos
        if window is not None:
            mask &= cols > qpos - window
        s = jnp.where(mask, s, _MASK_VALUE)
        m = m_scr[:]
        m_new = jnp.maximum(m, s.max(axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[:, None])
        m_scr[:] = m_new
        l_scr[:] = l_scr[:] * alpha + p.sum(axis=-1)
        acc_scr[:] = acc_scr[:] * alpha[:, None] + lax.dot_general(
            p,
            v,
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(tb == num_tb - 1)
    def _emit():
        o_ref[0, 0] = (acc_scr[:] / l_scr[:][:, None]).astype(
            o_ref.dtype
        )


def paged_flash_prefill(
    q: jax.Array,
    pool_k: jax.Array,
    pool_v: jax.Array,
    tables: jax.Array,
    start: jax.Array,
    *,
    window: int | None = None,
    interpret: bool = False,
    scale_k: jax.Array | None = None,
    scale_v: jax.Array | None = None,
) -> jax.Array:
    """Paged flash-prefill: a window of T query tokens per slot
    attending its block table directly — the prefill/verify companion
    to `paged_flash_decode`, closing the last full-pool gather in the
    serving path (chunked prefill and the speculative verify forward
    both route here).

    q [B, Hq, T, Dh] — T new tokens per slot, already rotated/projected
    for absolute positions start..start+T-1; pool_k/pool_v
    [NB, Hkv, bs, Dh] — ONE layer of the shared block pool, with the
    window's own K/V rows ALREADY scattered in (write-then-attend, the
    blockwise path's contract); tables [B, MB] int32 pool indices
    (unallocated entries = trash block 0); start [B] int32 = absolute
    position of each slot's FIRST window token. Returns [B, Hq, T, Dh].

    Causality is per window row: token t attends pool columns
    <= start+t, so rejected speculative rows left stale past `pos`
    are never read. The T*G query rows are zero-padded to the TPU
    sublane tile and sliced back; tables/start ride scalar prefetch so
    dead columns clamp onto live tiles exactly like the decode
    kernel. scale_k/scale_v [NB, Hkv] f32 enable the int8-pool path —
    same contract as `paged_flash_decode`."""
    b, hq, t_q, d = q.shape
    nb, hkv, bs, _ = pool_k.shape
    if (scale_k is None) != (scale_v is None):
        raise ValueError("pass both scale_k and scale_v, or neither")
    quantized = scale_k is not None
    if hq % hkv:
        raise ValueError(f"Hq={hq} must be a multiple of Hkv={hkv}")
    if tables.ndim != 2 or tables.shape[0] != b:
        raise ValueError(
            f"tables must be [B={b}, MB], got {tables.shape}"
        )
    g = hq // hkv
    mb = tables.shape[1]
    r = t_q * g
    r_pad = max(8, -(-r // 8) * 8)
    # Token-major query rows: row t*G + gi is window token t, group
    # row gi — the kernel recovers the token index as r//G.
    qg = (
        q.reshape(b, hkv, g, t_q, d)
        .transpose(0, 1, 3, 2, 4)
        .reshape(b, hkv, r, d)
    )
    if r_pad != r:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, r_pad - r), (0, 0)))
    tables = jnp.asarray(tables, jnp.int32)
    start1 = jnp.broadcast_to(
        jnp.asarray(start, jnp.int32).reshape(-1), (b,)
    )
    kernel = functools.partial(
        _paged_prefill_kernel,
        sm_scale=d**-0.5,
        block_size=bs,
        group=g,
        window=window,
        num_tb=mb,
        t_q=t_q,
        quantized=quantized,
    )

    def kv_index(i, j, tb, tables_ref, start_ref):
        lo, hi = _prefill_lo_hi(start_ref[i], t_q, bs, window)
        return (tables_ref[i, jnp.clip(tb, lo, hi)], j, 0, 0)

    in_specs = [
        pl.BlockSpec(
            (1, 1, r_pad, d),
            lambda i, j, tb, tables_ref, start_ref: (i, j, 0, 0),
        ),
        pl.BlockSpec((1, 1, bs, d), kv_index),
        pl.BlockSpec((1, 1, bs, d), kv_index),
    ]
    operands = [qg, pool_k, pool_v]
    if quantized:
        in_specs += [_scale_spec(hkv, mb)] * 2
        operands += [
            _slot_scales(scale_k, tables),
            _slot_scales(scale_v, tables),
        ]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, hkv, mb),
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            (1, 1, r_pad, d),
            lambda i, j, tb, tables_ref, start_ref: (i, j, 0, 0),
        ),
        scratch_shapes=[
            pltpu.VMEM((r_pad,), jnp.float32),
            pltpu.VMEM((r_pad,), jnp.float32),
            pltpu.VMEM((r_pad, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, r_pad, d), q.dtype),
        compiler_params=_grid_params("parallel", "parallel", "arbitrary"),
        interpret=interpret,
        name="paged_flash_prefill",
    )(tables, start1, *operands)
    out = out[:, :, :r, :].reshape(b, hkv, t_q, g, d)
    return out.transpose(0, 1, 3, 2, 4).reshape(b, hq, t_q, d)


def flash_unsupported(
    q_shape: tuple[int, ...],
    k_shape: tuple[int, ...],
    dtype,
    *,
    causal: bool = False,
    block_q: int = 256,
    block_k: int = 256,
) -> str | None:
    """Why `flash_attention` cannot take (B, H, S, Dh) operands of
    these shapes, or None when it can. `multi_head_attention` asks
    this BEFORE tracing the kernel, and the kernel raises the same
    reason, so the two can never disagree.

    The kernel keeps one head's whole K and V VMEM-resident (double
    buffered, as Mosaic stages every blocked operand), which caps the
    key length: at Dh=128, 15104 rows in bf16 and 7424 in f32."""
    if len(q_shape) != 4:
        return f"expected (B, H, S, Dh), got {tuple(q_shape)}"
    s_q, d = q_shape[2], q_shape[3]
    s_k = k_shape[2]
    if s_q < 8 or s_k < 8:
        return f"sequence too short for the TPU kernel: {s_q}x{s_k}"
    if causal and s_q != s_k:
        return "causal flash kernel requires s_q == s_k"
    sub = _sublane(dtype)
    bq = _pick_block(s_q, block_q, sub)
    if bq is None or _pick_block(s_k, block_k, sub) is None:
        return (
            f"no block of <= {block_q}/{block_k} rows that is a multiple "
            f"of {sub} divides seq lens {s_q}/{s_k}"
        )
    resident = 4 * (s_k + bq) * d * jnp.dtype(dtype).itemsize
    if resident > _BLOCKED_VMEM_BYTES:
        return (
            f"K and V of one head ({s_k} x {d} {jnp.dtype(dtype).name}, "
            f"double buffered) need {resident / 2**20:.2f} MiB of VMEM; "
            f"{_BLOCKED_VMEM_BYTES / 2**20:.0f} MiB fit"
        )
    return None


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    interpret: bool = False,
) -> jax.Array:
    """Flash attention on (B, H, S, Dh) tensors; returns (B, H, S, Dh).

    Raises ValueError with `flash_unsupported`'s reason for shapes the
    kernel cannot take.
    """
    return _flash(causal, interpret, q, k, v)
