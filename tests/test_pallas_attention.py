"""Pallas flash attention vs the XLA reference, in interpreter mode.

The kernel itself targets TPU; `interpret=True` runs the exact same
Pallas program on the CPU test mesh so CI needs no hardware (SURVEY.md
§4's test strategy).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from defer_tpu.ops.attention import attention_reference, multi_head_attention
from defer_tpu.ops.pallas_attention import flash_attention


def _qkv(shape, seed=0, dtype=jnp.float32):
    ks = jax.random.split(jax.random.key(seed), 3)
    return tuple(jax.random.normal(k, shape, dtype) for k in ks)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize(
    "shape",
    [
        (1, 2, 128, 64),   # one k block
        (2, 4, 512, 64),   # multiple k blocks
        (1, 2, 384, 32),   # non-power-of-two seq -> odd block split
    ],
)
def test_flash_matches_reference(shape, causal):
    q, k, v = _qkv(shape)
    got = flash_attention(q, k, v, causal=causal, interpret=True)
    want = attention_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


def test_flash_bf16_inputs():
    q, k, v = _qkv((1, 2, 256, 64), dtype=jnp.bfloat16)
    got = flash_attention(q, k, v, interpret=True)
    want = attention_reference(q, k, v)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        got.astype(np.float32), want.astype(np.float32), atol=2e-2
    )


def test_flash_grad_matches_reference():
    q, k, v = _qkv((1, 2, 128, 32), seed=3)

    def loss_flash(q, k, v):
        return flash_attention(q, k, v, causal=True, interpret=True).sum()

    def loss_ref(q, k, v):
        return attention_reference(q, k, v, causal=True).sum()

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_flash, g_ref):
        np.testing.assert_allclose(a, b, atol=2e-5, rtol=2e-5)


def test_flash_rejects_short_sequences():
    q, k, v = _qkv((1, 1, 4, 16))
    with pytest.raises(ValueError):
        flash_attention(q, k, v, interpret=True)


def _decode_reference(q, k, v, pos, window=None):
    """Masked decode attention on [B, Hq, Dh] vs [B, Hkv, S, Dh]:
    GQA expand, mask j <= pos[b] (and the sliding window), fp32
    softmax — mirrors GptDecoder._block's einsum math."""
    b, hq, d = q.shape
    hkv, s = k.shape[1], k.shape[2]
    g = hq // hkv
    kx = jnp.repeat(k, g, axis=1)
    vx = jnp.repeat(v, g, axis=1)
    logits = jnp.einsum(
        "bhd,bhsd->bhs", q.astype(jnp.float32), kx.astype(jnp.float32)
    ) * (d**-0.5)
    j = jnp.arange(s)
    mask = j[None, None, :] <= pos[:, None, None]
    if window is not None:
        mask &= j[None, None, :] > pos[:, None, None] - window
    logits = jnp.where(mask, logits, -jnp.inf)
    w = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhs,bhsd->bhd", w, vx.astype(jnp.float32)).astype(
        q.dtype
    )


@pytest.mark.parametrize(
    "hq,hkv,s,pos,window",
    [
        (8, 8, 64, [63, 10], None),     # MHA, full + short slots
        (8, 2, 64, [31, 32], None),     # GQA g=4 (padded group rows)
        (16, 2, 128, [5, 100], None),   # block-boundary positions
        (8, 2, 64, [40, 63], 16),       # sliding window
        (32, 4, 64, [0, 63], None),     # g=8, no pad; pos extremes
    ],
)
def test_flash_decode_matches_reference(hq, hkv, s, pos, window):
    from defer_tpu.ops.pallas_attention import flash_decode

    d = 16
    b = len(pos)
    ks = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(ks[0], (b, hq, d))
    k = jax.random.normal(ks[1], (b, hkv, s, d))
    v = jax.random.normal(ks[2], (b, hkv, s, d))
    posv = jnp.asarray(pos, jnp.int32)
    got = flash_decode(
        q, k, v, posv, window=window, interpret=True, block_k=32
    )
    want = _decode_reference(q, k, v, posv, window=window)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5
    )


def test_flash_decode_scalar_pos_and_validation():
    from defer_tpu.ops.pallas_attention import flash_decode

    ks = jax.random.split(jax.random.key(1), 3)
    q = jax.random.normal(ks[0], (2, 4, 16))
    k = jax.random.normal(ks[1], (2, 2, 32, 16))
    v = jax.random.normal(ks[2], (2, 2, 32, 16))
    got = flash_decode(q, k, v, jnp.asarray(7), interpret=True, block_k=8)
    want = _decode_reference(q, k, v, jnp.full((2,), 7, jnp.int32))
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5
    )
    k3 = jax.random.normal(ks[1], (2, 3, 32, 16))
    with pytest.raises(ValueError, match="multiple"):
        flash_decode(q, k3, k3, jnp.asarray(7), interpret=True)


def test_decode_step_through_kernel_matches_einsum(monkeypatch):
    """DEFER_TPU_PALLAS_INTERPRET=1 routes GptDecoder's T=1 decode
    through the flash-decode kernel (interpreter): generation must
    match the einsum path token for token — GQA + rotary included."""
    from defer_tpu.models.llama import tiny_llama

    dec = tiny_llama(64)
    params = dec.init(jax.random.key(0))
    prompt = jax.random.randint(jax.random.key(1), (2, 5), 0, 64)
    want = dec.generate(params, prompt, 8)

    monkeypatch.setenv("DEFER_TPU_PALLAS_INTERPRET", "1")
    dec2 = tiny_llama(64)  # fresh decoder -> fresh compiled steps
    got = dec2.generate(params, prompt, 8)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_mha_auto_falls_back_off_tpu():
    # On the CPU test platform "auto" must take the XLA path and agree
    # with the reference exactly.
    b, s, d, h = 2, 64, 32, 4
    q, k, v = _qkv((b, s, d), seed=5)
    out = multi_head_attention(q, k, v, num_heads=h)
    assert out.shape == (b, s, d)


def test_mha_auto_decides_from_shapes_before_tracing(monkeypatch):
    """On a TPU "auto" asks `flash_unsupported` BEFORE calling the
    kernel: a shape the kernel cannot tile takes the XLA path, and for
    a shape it accepts nothing is caught — whatever the kernel raises
    while tracing surfaces (here: Mosaic cannot compile on the CPU)."""
    from defer_tpu.ops import attention
    from defer_tpu.ops.pallas_attention import flash_unsupported

    monkeypatch.setattr(attention, "_pallas_available", lambda: True)
    h, d = 2, 32
    # 1004 = 4 * 251 rows: no divisor is a multiple of 8.
    assert flash_unsupported((1, h, 1004, 16), (1, h, 1004, 16), jnp.float32)
    q, k, v = _qkv((1, 1004, d), seed=7)
    out = multi_head_attention(q, k, v, num_heads=h)
    want = multi_head_attention(q, k, v, num_heads=h, use_pallas=False)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))
    # 128 rows tile cleanly, so "auto" commits to the kernel.
    assert flash_unsupported(
        (1, h, 128, 16), (1, h, 128, 16), jnp.float32
    ) is None
    q, k, v = _qkv((1, 128, d), seed=7)
    with pytest.raises(Exception, match="interpret"):
        multi_head_attention(q, k, v, num_heads=h)


def test_flash_unsupported_reasons():
    """The predicate names each limit, and the kernel raises the same
    reason: block alignment per dtype, and one head's K/V against the
    scoped-VMEM limit (fits at 8192 x 128 bf16, not at 16384)."""
    from defer_tpu.ops.pallas_attention import _pick_block, flash_unsupported

    assert _pick_block(4096, 256, 16) == 256
    assert _pick_block(384, 256, 16) == 192
    assert _pick_block(2056, 256, 8) == 8
    assert _pick_block(2056, 256, 16) is None  # 2056 = 8 * 257
    assert _pick_block(100, 256, 8) is None  # whole axis, not 8-aligned
    assert _pick_block(200, 256, 16) == 200
    bf = jnp.bfloat16
    assert flash_unsupported((1, 1, 8192, 128), (1, 1, 8192, 128), bf) is None
    why = flash_unsupported((1, 1, 16384, 128), (1, 1, 16384, 128), bf)
    assert "VMEM" in why
    q, k, v = _qkv((1, 1, 2056, 16), dtype=bf)
    with pytest.raises(ValueError, match="multiple of 16"):
        flash_attention(q, k, v, interpret=True)


