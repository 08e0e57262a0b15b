"""Attention: XLA reference implementation + Pallas flash-attention hook.

`multi_head_attention` is the single entry point; the `mha` op and the
SPMD transformer pipeline both route through it. On TPU it dispatches
to the Pallas flash kernel (defer_tpu/ops/pallas_attention.py) for the
shapes that kernel takes; the XLA einsum path serves the rest and is
the numerical reference in tests.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp


def _split_heads(x: jax.Array, num_heads: int) -> jax.Array:
    b, s, d = x.shape
    return x.reshape(b, s, num_heads, d // num_heads).transpose(0, 2, 1, 3)


def _merge_heads(x: jax.Array) -> jax.Array:
    b, h, s, dh = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b, s, h * dh)


def attention_reference(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    bias: jax.Array | None = None,
    causal: bool = False,
    window: int | None = None,
) -> jax.Array:
    """Plain softmax attention on (B, H, S, Dh) tensors, fp32 softmax.

    window=W adds Mistral-style sliding-window masking: query position
    p attends key positions (p-W, p] only (requires causal=True)."""
    if window is not None and not causal:
        raise NotImplementedError("window requires causal attention")
    dh = q.shape[-1]
    logits = jnp.einsum(
        "bhqd,bhkd->bhqk", q, k, preferred_element_type=jnp.float32
    ) * (dh**-0.5)
    if bias is not None:
        logits = logits + bias.astype(jnp.float32)
    if causal:
        s_q, s_k = logits.shape[-2:]
        qpos = jnp.arange(s_q)[:, None] + (s_k - s_q)
        kpos = jnp.arange(s_k)[None, :]
        mask = kpos <= qpos
        if window is not None:
            mask &= kpos > qpos - window
        logits = jnp.where(mask, logits, -jnp.inf)
    weights = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", weights, v)


def _pallas_available() -> bool:
    """True iff the default backend compiles Mosaic kernels: a TPU."""
    return jax.default_backend() == "tpu"


def multi_head_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    num_heads: int,
    bias: jax.Array | None = None,
    causal: bool = False,
    window: int | None = None,
    use_pallas: Any = "auto",
    sp_axis: str | None = None,
    sp_strategy: str = "ring",
) -> jax.Array:
    """Attention on (B, S, D) projections; returns (B, S, D).

    use_pallas: True / False / "auto" (pallas iff running on TPU and
    `flash_unsupported` has no objection to the shape).

    window: sliding-window (Mistral-style) masking, causal only; the
    pallas path doesn't implement it, so it forces the XLA reference.

    sp_axis: mesh axis name for sequence parallelism — S is then the
    LOCAL sequence shard and attention runs ring / Ulysses over that
    axis (defer_tpu/parallel/sequence.py). Only valid inside shard_map.
    """
    qh, kh, vh = (_split_heads(t, num_heads) for t in (q, k, v))
    if sp_axis is not None:
        if bias is not None:
            raise NotImplementedError(
                "bias is not supported under sequence parallelism"
            )
        if window is not None:
            raise NotImplementedError(
                "sliding-window attention is not supported under "
                "sequence parallelism yet"
            )
        from defer_tpu.parallel.sequence import sequence_attention

        return _merge_heads(
            sequence_attention(
                qh, kh, vh,
                axis_name=sp_axis,
                strategy=sp_strategy,
                causal=causal,
            )
        )
    if use_pallas is True and window is not None:
        raise NotImplementedError(
            "the pallas flash kernel does not implement sliding-window "
            "masking; use use_pallas='auto' or False with window"
        )
    want_pallas = use_pallas is True or (
        use_pallas == "auto" and _pallas_available()
    )
    if want_pallas and bias is None and window is None:
        from defer_tpu.ops.pallas_attention import (
            flash_attention,
            flash_unsupported,
        )

        # "auto" decides from the shapes BEFORE tracing the kernel, so
        # an error raised while tracing it surfaces; an explicit True
        # lets flash_attention raise the same reason itself.
        if use_pallas is True or not flash_unsupported(
            qh.shape, kh.shape, qh.dtype, causal=causal
        ):
            return _merge_heads(flash_attention(qh, kh, vh, causal=causal))
    return _merge_heads(
        attention_reference(
            qh, kh, vh, bias=bias, causal=causal, window=window
        )
    )
