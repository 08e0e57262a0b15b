"""Llama-family causal decoder — beyond-reference model family.

The reference's zoo stops at Keras CNNs plus our BERT/GPT additions;
the dominant open-weights serving workload is the llama architecture:
RMSNorm, rotary position embeddings, grouped-query attention and a
SwiGLU FFN, all biasless. Here that is a CONFIGURATION of the shared
transformer stack (defer_tpu/parallel/transformer_stack.py), not a
fork: the same KV-cache decoder (defer_tpu/models/gpt.py) serves it,
the same SPMD machinery tensor-parallelizes it, and the GQA cache is
genuinely smaller ([L, B, H_kv, S, Dh] — the architecture's point).

Checkpoint interop mirrors the Keras transplant path the CNN zoo uses
(reference src/node.py:42): `from_hf_state_dict` maps a HuggingFace
`LlamaForCausalLM.state_dict()` onto the stack's pytree, numerically
validated against transformers' own forward in tests/test_llama.py.
"""

from __future__ import annotations

from typing import Any, Mapping

import jax.numpy as jnp
import numpy as np

from defer_tpu.models.gpt import GptDecoder, SpmdGptDecoder
from defer_tpu.parallel.transformer_stack import TransformerConfig


def llama_config(
    *,
    num_layers: int = 32,
    dim: int = 4096,
    num_heads: int = 32,
    num_kv_heads: int = 8,
    ffn_dim: int = 14336,
    vocab_size: int = 32000,
    max_len: int = 4096,
    rope_theta: float = 10000.0,
    eps: float = 1e-5,
    window: int | None = None,
) -> TransformerConfig:
    """The llama architecture as a TransformerConfig (defaults are
    7B-class shapes; tests use tiny ones)."""
    return TransformerConfig(
        num_layers=num_layers,
        dim=dim,
        num_heads=num_heads,
        num_kv_heads=num_kv_heads,
        ffn_dim=ffn_dim,
        vocab_size=vocab_size,
        max_len=max_len,
        layer_norm_eps=eps,
        norm_style="pre",
        norm_type="rms",
        ffn_style="swiglu",
        pos_style="rope",
        use_bias=False,
        rope_theta=rope_theta,
        causal=True,
        window=window,
    )


def mistral_config(**kw) -> TransformerConfig:
    """Mistral = the llama architecture + sliding-window attention
    (each position attends its last `window` predecessors; default
    4096 as in Mistral-7B). Checkpoints transplant through the same
    `from_hf_state_dict` — HF MistralForCausalLM uses identical
    parameter names."""
    kw.setdefault("window", 4096)
    return llama_config(**kw)


def tiny_llama(seq_len: int = 32) -> GptDecoder:
    """Small llama-shaped decoder for tests / CPU."""
    return GptDecoder(
        llama_config(
            num_layers=2,
            dim=64,
            num_heads=4,
            num_kv_heads=2,
            ffn_dim=128,
            vocab_size=96,
            max_len=seq_len,
        ),
        compute_dtype=jnp.float32,
    )


def spmd_llama(
    mesh: Any,
    cfg: TransformerConfig,
    *,
    compute_dtype: Any = jnp.bfloat16,
    tp_axis: str = "model",
    dp_axis: str | None = None,
) -> SpmdGptDecoder:
    """Tensor-parallel llama serving: head-group-sharded projections
    and GQA caches, vocab-sharded tied head — the SpmdGptDecoder
    machinery, which requires num_kv_heads % tp == 0."""
    return SpmdGptDecoder(
        cfg,
        compute_dtype=compute_dtype,
        mesh=mesh,
        tp_axis=tp_axis,
        dp_axis=dp_axis,
    )


def from_hf_state_dict(
    cfg: TransformerConfig, state_dict: Mapping[str, Any]
) -> dict:
    """Map a HuggingFace `LlamaForCausalLM.state_dict()` onto the
    decoder's param pytree.

    Torch Linear stores [out, in]; the stack computes x @ W with
    [in, out], so every projection transposes. The head is weight-tied
    (`token_embedding`), matching HF's tie_word_embeddings=True; a
    separate lm_head in the checkpoint is ignored with a warning-free
    contract (tied models simply don't ship one).
    """
    from defer_tpu.parallel.transformer_stack import refuse_mechanisms

    refuse_mechanisms(cfg, "from_hf_state_dict (llama checkpoints)")
    L = cfg.num_layers
    dh = cfg.dh

    from defer_tpu.models.transplant import tensor_to_numpy

    def t(name: str) -> np.ndarray:
        return tensor_to_numpy(state_dict[name])

    def proj(i: int, which: str) -> np.ndarray:
        return t(f"model.layers.{i}.self_attn.{which}.weight").T

    def mlp(i: int, which: str) -> np.ndarray:
        return t(f"model.layers.{i}.mlp.{which}.weight").T

    stack = {
        "wq": np.stack([proj(i, "q_proj") for i in range(L)]),
        "wk": np.stack([proj(i, "k_proj") for i in range(L)]),
        "wv": np.stack([proj(i, "v_proj") for i in range(L)]),
        "wo": np.stack([proj(i, "o_proj") for i in range(L)]),
        # w1 = gate (silu branch), w3 = up, w2 = down — the stack's
        # swiglu convention (transformer_stack.block_apply).
        "w1": np.stack([mlp(i, "gate_proj") for i in range(L)]),
        "w3": np.stack([mlp(i, "up_proj") for i in range(L)]),
        "w2": np.stack([mlp(i, "down_proj") for i in range(L)]),
        "ln1_scale": np.stack(
            [
                t(f"model.layers.{i}.input_layernorm.weight")
                for i in range(L)
            ]
        ),
        "ln2_scale": np.stack(
            [
                t(f"model.layers.{i}.post_attention_layernorm.weight")
                for i in range(L)
            ]
        ),
    }
    kv_dim = cfg.kv_heads * dh
    assert stack["wk"].shape == (L, cfg.dim, kv_dim), stack["wk"].shape
    params = {
        "token_embedding": jnp.asarray(t("model.embed_tokens.weight")),
        "final_ln_scale": jnp.asarray(t("model.norm.weight")),
        "stack": {k: jnp.asarray(v) for k, v in stack.items()},
    }
    # Untied checkpoints (tie_word_embeddings=False — real Llama-2/3
    # releases) carry a distinct output head; silently falling back to
    # the tied head would make every logit wrong. Tied checkpoints
    # often still LIST lm_head.weight (it aliases the embedding), so
    # only keep it when the values actually differ.
    if "lm_head.weight" in state_dict:
        head = t("lm_head.weight")
        if not np.array_equal(
            head, np.asarray(params["token_embedding"])
        ):
            params["lm_head"] = jnp.asarray(head)
    return params
