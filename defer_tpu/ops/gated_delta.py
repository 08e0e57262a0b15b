"""The gated delta rule of a Gated DeltaNet layer, in two forms.

Per value head, with a state S in [dk, dv] (float32) and per token a
key k and query q in [dk], a value v in [dv], a log decay g <= 0 and a
write strength beta in (0, 1):

    S <- exp(g_t) S
    d  = beta_t (v_t - S^T k_t)
    S <- S + k_t d^T
    o_t = S^T q_t

`gdn_chunked` is that for T tokens at once, a chunk of rows
at a time: inside a chunk the tokens' writes d solve one unit lower
triangular system, and the state moves once a chunk. `gdn_step` is the
one-token form of the decode step, over a POOL of states [layers,
slots, heads, dk, dv] that it updates in place at one layer: on a TPU
one Pallas kernel (`gdn_step` in a device trace) that reads and writes
each slot's state once; elsewhere plain XLA.

A row with g = 0 and beta = 0 leaves the state as it was and writes
nothing: that is how a caller masks the padding of a bucket.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Rows of one chunk of `gdn_chunked`.
CHUNK = 64
_HI = lax.Precision.HIGHEST


def _unit_lower_inverse(a):
    """(I + a)^-1 for a strictly lower triangular `a` [..., C, C]: row
    i of the inverse from the rows above it (forward substitution), so
    no power of `a` is ever formed."""
    c = a.shape[-1]

    def row(i, m):
        # m holds the strictly lower part of the inverse, rows < i
        # final and the rest zero; a[i] is zero from column i on.
        r = -lax.dynamic_index_in_dim(a, i, axis=-2, keepdims=False)
        r = r + jnp.einsum("...m,...mj->...j", r, m, precision=_HI)
        return lax.dynamic_update_index_in_dim(m, r, i, axis=-2)

    return lax.fori_loop(1, c, row, jnp.zeros_like(a)) + jnp.eye(c, dtype=a.dtype)


def gdn_chunked(q, k, v, g, beta, s0, chunk: int = CHUNK):
    """The recurrence over q, k [B, T, H, dk], v [B, T, H, dv], g,
    beta [B, T, H] from s0 [B, H, dk, dv] (all float32), `chunk` rows
    at a time -> (o [B, T, H, dv], S after row T). With G the
    running sum of g inside a chunk and S0 the state before it, the
    chunk's writes D solve (I + A) D = beta (V - exp(G) K S0), A[t, s]
    = beta_t exp(G_t - G_s) k_t.k_s for s < t; then O = exp(G) Q S0 +
    (exp(G_t - G_s) q_t.k_s)_{s <= t} D and S = exp(G_C) S0 +
    (exp(G_C - G) K)^T D. Every exponent is <= 0."""
    b, t, h, dk = q.shape
    dv = v.shape[-1]
    pad = -t % chunk
    n = (t + pad) // chunk

    def chunks(a):
        a = jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
        a = a.reshape(b, n, chunk, *a.shape[2:])
        return jnp.moveaxis(a, 3, 1)  # [B, H, N, C, ...]

    q, k, v, g, beta = map(chunks, (q, k, v, g, beta))
    gc = jnp.cumsum(g, axis=-1)  # [B, H, N, C]
    i = jnp.arange(chunk)
    # exp(G_t - G_s) where s <= t, else 0 (the exponent is masked, not
    # the result: above the diagonal it is positive and may overflow).
    decay = jnp.exp(
        jnp.where(i[:, None] >= i[None, :], gc[..., :, None] - gc[..., None, :], -jnp.inf)
    )
    kk = jnp.einsum("bhntd,bhnsd->bhnts", k, k, precision=_HI)
    a = jnp.where(i[:, None] > i[None, :], beta[..., None] * decay * kk, 0.0)
    inv = _unit_lower_inverse(a)
    u = jnp.einsum("bhnts,bhnsd->bhntd", inv, beta[..., None] * v, precision=_HI)
    w = jnp.einsum(
        "bhnts,bhnsd->bhntd", inv, (beta * jnp.exp(gc))[..., None] * k,
        precision=_HI,
    )
    qk = decay * jnp.einsum("bhntd,bhnsd->bhnts", q, k, precision=_HI)
    q_in = jnp.exp(gc)[..., None] * q
    g_end = gc[..., -1:]  # [B, H, N, 1]
    k_out = jnp.exp(g_end - gc)[..., None] * k

    def one(s, c):
        u_c, w_c, qk_c, q_c, k_c, g_c = c
        d = u_c - jnp.einsum("bhtk,bhkv->bhtv", w_c, s, precision=_HI)
        o = jnp.einsum("bhtk,bhkv->bhtv", q_c, s, precision=_HI) + jnp.einsum(
            "bhts,bhsv->bhtv", qk_c, d, precision=_HI
        )
        s = jnp.exp(g_c)[..., None] * s + jnp.einsum(
            "bhtk,bhtv->bhkv", k_c, d, precision=_HI
        )
        return s, o

    per_chunk = jax.tree.map(
        lambda x: jnp.moveaxis(x, 2, 0), (u, w, qk, q_in, k_out, g_end)
    )
    s, o = lax.scan(one, s0, per_chunk)  # o [N, B, H, C, dv]
    o = jnp.moveaxis(o, 0, 2).reshape(b, h, n * chunk, dv)
    return jnp.moveaxis(o, 1, 2)[:, :t], s


# -- the decode step ---------------------------------------------------------------


def _step_kernel(layer_ref, rows_ref, cols_ref, s_ref, o_ref, s_out_ref, *, heads, ratio):
    """One (slot, head block) cell. `rows_ref` [1, 4, heads, dv]: v,
    exp(g), beta and k.q of each head, the three scalars repeated
    along the lanes; `cols_ref` [1, 1, dk, 2 * heads / ratio]: the
    block's q then k heads as COLUMNS, because the state's rows are
    the key lanes: S^T k is then a product along the lanes' rows and
    a sum over sublanes, and k d^T a column times a row."""
    del layer_ref
    cols = cols_ref[0, 0]
    nk = heads // ratio
    for h in range(heads):
        s = s_ref[0, 0, h]  # [dk, dv]
        q_col = cols[:, h // ratio : h // ratio + 1]
        k_col = cols[:, nk + h // ratio : nk + h // ratio + 1]
        v = rows_ref[0, 0, h : h + 1, :]
        decay = rows_ref[0, 1, h : h + 1, :]
        beta = rows_ref[0, 2, h : h + 1, :]
        kq = rows_ref[0, 3, h : h + 1, :]
        sk = jnp.sum(s * k_col, axis=0, keepdims=True)
        sq = jnp.sum(s * q_col, axis=0, keepdims=True)
        d = beta * (v - decay * sk)
        o_ref[0, h : h + 1, :] = decay * sq + kq * d
        s_out_ref[0, 0, h] = decay * s + k_col * d


def _head_block(hv: int, ratio: int) -> int:
    """Heads of one grid cell: 8 where the head count allows (a
    512 KiB block of state at 128 x 128), else all of them."""
    return 8 if hv % 8 == 0 and 8 % ratio == 0 else hv


def _gdn_step_pallas(pool, layer, q, k, v, g, beta, interpret):
    _, b, hv, dk, dv = pool.shape
    hk = q.shape[1]
    ratio = hv // hk
    hb = _head_block(hv, ratio)
    nblk = hv // hb
    rep = lambda a: jnp.broadcast_to(a[..., None], (b, hv, dv))  # noqa: E731
    kq = jnp.repeat(jnp.sum(q * k, axis=-1), ratio, axis=1)
    rows = jnp.stack([v, rep(jnp.exp(g)), rep(beta), rep(kq)], axis=1)
    # [B, nblk, dk, 2 * hb / ratio]: a block's q heads, then its k heads.
    cols = jnp.concatenate(
        [a.reshape(b, nblk, hb // ratio, dk) for a in (q, k)], axis=2
    ).transpose(0, 1, 3, 2)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, nblk),
        in_specs=[
            pl.BlockSpec((1, 4, hb, dv), lambda i, j, l: (i, 0, j, 0)),
            pl.BlockSpec(
                (1, 1, dk, 2 * hb // ratio), lambda i, j, l: (i, j, 0, 0)
            ),
            pl.BlockSpec((1, 1, hb, dk, dv), lambda i, j, l: (l[0], i, j, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, hb, dv), lambda i, j, l: (i, j, 0)),
            pl.BlockSpec((1, 1, hb, dk, dv), lambda i, j, l: (l[0], i, j, 0, 0)),
        ],
    )
    o, pool = pl.pallas_call(
        functools.partial(_step_kernel, heads=hb, ratio=ratio),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, hv, dv), jnp.float32),
            jax.ShapeDtypeStruct(pool.shape, pool.dtype),
        ],
        # The pool (operand 3, after the layer, rows and cols) is the
        # second output: the cells of one layer are rewritten where
        # they lie and the other layers are never touched.
        input_output_aliases={3: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")
        ),
        interpret=interpret,
        name="gdn_step",
    )(jnp.asarray(layer, jnp.int32).reshape(1), rows, cols, pool)
    return o, pool


def gdn_step(pool, layer, q, k, v, g, beta, mode: str | None = None):
    """One token for every slot: pool [layers, B, Hv, dk, dv] float32,
    `layer` an int or a traced scalar, q, k [B, Hk, dk] (a key head
    serves Hv / Hk consecutive value heads), v [B, Hv, dv], g, beta
    [B, Hv], float32 -> (o [B, Hv, dv], the pool with layer `layer`
    updated). `mode` "tpu" or "interpret" runs the Pallas kernel, which
    reads and writes each state once; None plain XLA (two reads)."""
    if pool.dtype != jnp.float32:
        raise ValueError(f"the state pool is float32, got {pool.dtype}")
    if mode is not None:
        return _gdn_step_pallas(
            pool, layer, q, k, v, g, beta, interpret=mode == "interpret"
        )
    ratio = v.shape[1] // q.shape[1]
    q, k = (jnp.repeat(a, ratio, axis=1) for a in (q, k))
    s = lax.dynamic_index_in_dim(pool, layer, axis=0, keepdims=False)
    decay = jnp.exp(g)
    sk = jnp.einsum("bhkv,bhk->bhv", s, k, precision=_HI)
    sq = jnp.einsum("bhkv,bhk->bhv", s, q, precision=_HI)
    d = beta[..., None] * (v - decay[..., None] * sk)
    o = decay[..., None] * sq + jnp.sum(q * k, axis=-1, keepdims=True) * d
    s = decay[..., None, None] * s + k[..., :, None] * d[..., None, :]
    return o, lax.dynamic_update_index_in_dim(pool, s, layer, axis=0)
