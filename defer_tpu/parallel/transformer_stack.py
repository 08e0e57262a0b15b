"""Homogeneous transformer-encoder stack with Megatron-style tensor
parallelism, for the SPMD pipeline.

The reference never needed this (its zoo is CNNs shipped whole to CPU
nodes), but BERT-base encoder inference is in its benchmark config list
(BASELINE.json "configs": "BERT-base encoder inference ... transformer
stages"). On TPU the idiomatic layout is: encoder blocks stacked on a
leading layer axis, layer axis sharded over the "stage" mesh axis
(pipeline), weight matrices sharded over a "model" mesh axis (tensor
parallel, partial-sum reductions via psum over ICI), batch sharded over
"data".

Q/K/V projections are separate [D, D] matrices (not a fused [D, 3D]):
under column sharding each tp shard then holds a contiguous head group
of each of q, k, v, so attention is purely local and only the out/ffn
row-parallel matmuls need a psum.

All parameters are plain pytrees of arrays with a leading [L] layer
axis; `stack_specs` gives the matching PartitionSpecs. The serving
decoder's hybrid stacks (`layer_kinds` with "linear" entries) stack a
leaf over the layers of the kind that has it (`leaf_group`).
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from defer_tpu.ops.attention import multi_head_attention


#: The entry of `TransformerConfig.layer_kinds` of a recurrent layer.
LINEAR = "linear"


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    num_layers: int = 12
    dim: int = 768
    num_heads: int = 12
    ffn_dim: int = 3072
    vocab_size: int = 30522
    max_len: int = 512
    layer_norm_eps: float = 1e-12
    # > 0 switches every block's FFN to a top-1-routed mixture of
    # experts (expert-parallel over an "expert" mesh axis).
    num_experts: int = 0
    # "post" = BERT-style residual-then-norm; "pre" = GPT/ViT-style
    # norm-then-sublayer (ln params then normalize the sublayer INPUT,
    # and the residual stream is never normalized in-block).
    norm_style: str = "post"
    # Causal (decoder-style) attention masking: with norm_style="pre"
    # this makes the SPMD stack a trainable GPT — the same params the
    # KV-cache decoder (defer_tpu/models/gpt.py) serves.
    causal: bool = False
    # Sliding-window (Mistral-style) causal attention: each position
    # attends at most `window` predecessors. None = full causal.
    window: int | None = None
    # Rematerialize each block on the backward pass (jax.checkpoint):
    # activation memory drops from O(layers) to O(1) blocks per stage
    # at the cost of one extra forward — the standard TPU trade when
    # HBM, not FLOPs, bounds the trainable model size.
    remat: bool = False
    # MoE dispatch: "dense" computes every local expert for every
    # token and masks (exact, no drops, E_local x the FLOPs); "a2a"
    # routes tokens to their expert's device with lax.all_to_all under
    # a static per-expert capacity (the scaling path for large expert
    # counts — tokens over capacity are dropped, Switch-style).
    moe_dispatch: str = "dense"
    capacity_factor: float = 1.25
    # Experts per token: 1 = Switch (output scaled by the raw top
    # gate), >1 = Mixtral-style (weights renormalized over the
    # selected experts).
    moe_top_k: int = 1
    # -- llama-family knobs (defaults preserve the BERT/GPT behavior;
    #    defer_tpu/models/llama.py sets the full combination) --------
    # Grouped-query attention: K/V project to this many heads (each
    # shared by num_heads/num_kv_heads query heads). None = MHA.
    num_kv_heads: int | None = None
    norm_type: str = "layer"  # "layer" | "rms" (scale-only, no mean)
    ffn_style: str = "gelu"  # "gelu" | "swiglu" (gate*up, biasless F)
    pos_style: str = "learned"  # "learned" table | "rope" (rotary q/k)
    use_bias: bool = True  # llama: no projection biases at all
    rope_theta: float = 10000.0
    # -- LoRA (parallel/lora.py) ------------------------------------
    # rank > 0 adds low-rank adapter factors {t}:a [in, r] / {t}:b
    # [r, out] for each target projection; the forward adds
    # scale * (x @ a) @ b to the frozen base matmul. b starts at zero,
    # so a freshly-initialized adapter is an exact identity.
    lora_rank: int = 0
    lora_targets: tuple = ("wq", "wv")
    lora_alpha: float | None = None  # scale = alpha / rank; None -> 1.0
    # -- decoder layer shapes beyond llama's (defaults keep every
    #    model above as it is) ---------------------------------------
    # A head's size where it is not dim // num_heads: wq is then
    # [dim, num_heads * head_dim] and wo its transpose's shape.
    head_dim: int | None = None
    # One norm a layer, attention and FFN both reading it:
    # y = x + attn(n) + ffn(n), n = norm(x); no ln2 leaves.
    parallel_block: bool = False
    # norm_type="layer" without the bias leaves (mean subtracted,
    # scale only).
    norm_bias: bool = True
    # Which lanes a rotary pair takes: "half" = (i, i + Dh/2), llama's
    # rotate-half; "interleaved" = (2i, 2i + 1), GPT-J's.
    rope_pairing: str = "half"
    # A kind per layer over ONE period of the stack, each entry
    # (window | None, rotary: bool); layer l is of kind l % period.
    # None = every layer takes `window` and `pos_style`, as before.
    layer_kinds: tuple | None = None
    # The expert layer of the serving decoder (`held_experts_ffn`):
    # `num_experts` is the PUBLISHED count, the router's width;
    # `experts_held` = (lo, hi) says which of them live on this chip
    # (None = all). "sigmoid" gates score each expert on its own.
    experts_held: tuple | None = None
    moe_gate: str = "softmax"
    expert_dim: int | None = None  # one expert's width; None = ffn_dim
    num_shared_experts: int = 0
    shared_combine: str = "mean"  # "mean" | "sum" of the shared experts
    # Each shared expert scaled by sigmoid(x @ sw_gate) before it is
    # added (leaf `sw_gate` [D, num_shared_experts]).
    shared_gate: bool = False
    # -- a hybrid decoder: gated attention layers among recurrent
    #    (Gated DeltaNet) ones. An entry of `layer_kinds` may be the
    #    string "linear": that layer has no keys and values but a
    #    state of fixed size (ops/gated_delta.py), `gdn_v_heads` x
    #    [gdn_k_dim, gdn_v_dim] float32 and the last `gdn_conv - 1`
    #    rows before its causal depthwise convolution. -----------------
    gdn_k_heads: int = 0
    gdn_v_heads: int = 0
    gdn_k_dim: int = 0
    gdn_v_dim: int = 0
    gdn_conv: int = 4
    # RMS norms scale by (1 + w), w starting at 0 (the block's two, the
    # final one and the q/k norms; the recurrent layer's gated norm
    # keeps a plain w).
    norm_offset: bool = False
    # q and k RMS-normed per head before the rotation (leaves
    # `q_norm_scale`, `k_norm_scale` [head_dim]).
    qk_norm: bool = False
    # wq is [dim, num_heads * 2 * head_dim], read per head as
    # [q | gate]: the attention output is scaled by sigmoid(gate).
    attn_gate: bool = False
    # Lanes of a head that rotate (the first `rotary_dim`, paired
    # within themselves); None = all of them.
    rotary_dim: int | None = None
    # An output head of its own (`lm_head` [V, D]) beside the embedding.
    untied_head: bool = False

    @property
    def kv_heads(self) -> int:
        return self.num_kv_heads or self.num_heads

    @property
    def dh(self) -> int:
        """A head's size: `head_dim`, else the quotient."""
        return self.head_dim or self.dim // self.num_heads

    @property
    def held(self) -> tuple[int, int]:
        """The [lo, hi) range of published experts held here."""
        return self.experts_held or (0, self.num_experts)

    def kind_of(self, layer_kind) -> tuple:
        """(window, rotary) of an attention layer: its entry of
        `layer_kinds`, or the stack's one `window` and `pos_style`
        where it has none."""
        if layer_kind is None:
            return self.window, self.pos_style == "rope"
        return layer_kind

    @property
    def kinds(self) -> tuple:
        """One period of layer kinds; (None,) for a homogeneous stack."""
        return self.layer_kinds or (None,)

    @property
    def has_linear(self) -> bool:
        return LINEAR in self.kinds

    def layers_of(self, group: str) -> int:
        """Layers of the stack in `group`: "linear" (the recurrent
        ones), "attn" (those with keys and values) or "all"."""
        kinds = self.kinds
        n = {
            "all": len(kinds),
            "linear": sum(k == LINEAR for k in kinds),
            "attn": sum(k != LINEAR for k in kinds),
        }[group]
        return self.num_layers // len(kinds) * n

    @property
    def gdn_channels(self) -> int:
        """Channels of a recurrent layer's convolution: q, k and v."""
        return 2 * self.gdn_k_heads * self.gdn_k_dim + self.gdn_v_heads * self.gdn_v_dim

    @property
    def lora_scale(self) -> float:
        if not self.lora_rank:
            return 0.0
        if self.lora_alpha is None:
            return 1.0
        return self.lora_alpha / self.lora_rank

    def __post_init__(self):
        if self.num_heads % self.kv_heads:
            raise ValueError(
                f"num_kv_heads={self.kv_heads} must divide "
                f"num_heads={self.num_heads}"
            )
        if self.window is not None and (
            self.window < 1 or not self.causal
        ):
            raise ValueError(
                f"window={self.window} needs causal=True and window >= 1"
            )
        if self.capacity_factor <= 0:
            raise ValueError(
                f"capacity_factor={self.capacity_factor} must be > 0 "
                "(non-positive values would silently drop almost every "
                "token to the residual path)"
            )
        if self.num_experts and not (
            1 <= self.moe_top_k <= self.num_experts
        ):
            raise ValueError(
                f"moe_top_k={self.moe_top_k} must be in "
                f"[1, num_experts={self.num_experts}]"
            )
        if self.experts_held is not None:
            lo, hi = self.experts_held
            if not 0 <= lo < hi <= self.num_experts:
                raise ValueError(
                    f"experts_held={self.experts_held} must be a "
                    f"non-empty range within num_experts={self.num_experts}"
                )
        if self.layer_kinds is not None:
            if not self.layer_kinds or self.num_layers % len(self.layer_kinds):
                raise ValueError(
                    f"layer_kinds has {len(self.layer_kinds)} entries: "
                    f"num_layers={self.num_layers} must be a whole "
                    "number of periods"
                )
            for kind in self.layer_kinds:
                if kind == LINEAR:
                    continue
                w, rotary = kind
                if w is not None and (w < 1 or not self.causal):
                    raise ValueError(
                        f"layer_kinds window {w} needs causal=True and "
                        "window >= 1"
                    )
                if rotary and self.pos_style != "rope":
                    raise ValueError(
                        "a rotary layer kind needs pos_style='rope'"
                    )
        if self.has_linear:
            sizes = (self.gdn_k_heads, self.gdn_v_heads, self.gdn_k_dim, self.gdn_v_dim)
            if min(sizes) < 1 or self.gdn_v_heads % self.gdn_k_heads or self.gdn_conv < 2:
                raise ValueError(
                    f"a 'linear' layer kind needs gdn_k_heads, gdn_v_heads "
                    f"(a multiple of it), gdn_k_dim, gdn_v_dim >= 1 and "
                    f"gdn_conv >= 2, got {sizes} and {self.gdn_conv}"
                )
        if self.rotary_dim is not None and not (
            0 < self.rotary_dim <= self.dh and self.rotary_dim % 2 == 0
        ):
            raise ValueError(
                f"rotary_dim={self.rotary_dim} must be even and within "
                f"the head size {self.dh}"
            )
        if self.shared_gate and not self.num_shared_experts:
            raise ValueError("shared_gate needs num_shared_experts >= 1")
        if self.norm_offset and self.norm_type != "rms":
            raise ValueError("norm_offset scales an RMS norm: norm_type='rms'")
        if self.lora_rank:
            if self.lora_rank < 1:
                raise ValueError(f"lora_rank={self.lora_rank} must be >= 1")
            valid = {"wq", "wk", "wv", "wo", "w1", "w2"}
            if self.ffn_style == "swiglu":
                valid.add("w3")
            if self.num_experts:
                # Expert FFN weights have an extra [E] axis the
                # two-factor adapter doesn't model.
                valid -= {"w1", "w2"}
            bad = set(self.lora_targets) - valid
            if bad:
                raise ValueError(
                    f"lora_targets {sorted(bad)} not adaptable for this "
                    f"config (valid: {sorted(valid)})"
                )
            if not self.lora_targets:
                raise ValueError("lora_rank set but lora_targets is empty")
        # Fail at construction, not as a KeyError deep inside jit
        # tracing (a typo'd knob would otherwise silently select the
        # wrong architecture or crash on a missing param key).
        for field, allowed in (
            ("norm_style", ("post", "pre")),
            ("norm_type", ("layer", "rms")),
            ("ffn_style", ("gelu", "swiglu")),
            ("pos_style", ("learned", "rope")),
            ("moe_dispatch", ("dense", "a2a")),
            ("rope_pairing", ("half", "interleaved")),
            ("moe_gate", ("softmax", "sigmoid")),
            ("shared_combine", ("mean", "sum")),
        ):
            v = getattr(self, field)
            if v not in allowed:
                raise ValueError(
                    f"{field}={v!r}: must be one of {allowed}"
                )


def refuse_mechanisms(cfg: TransformerConfig, option: str) -> None:
    """Raise, naming the mechanism and the option, where a path that
    computes one homogeneous dense stack is asked to serve a model with
    layer kinds, experts, a parallel block or recurrent layers (whose
    state is per slot: a position cannot roll it back, a block hash
    cannot share it, a block cannot spill it). The default paged path
    (`PagedDecodeServer` at its defaults) and the flat step serve them;
    nothing else has been held to the reference, and none may run such
    a model as a silently homogeneous stack."""
    named = [
        name
        for name, on in (
            ("layer kinds (cfg.layer_kinds)", cfg.layer_kinds is not None),
            ("experts (cfg.num_experts)", bool(cfg.num_experts)),
            ("a parallel block (cfg.parallel_block)", cfg.parallel_block),
            (
                "recurrent layers (cfg.layer_kinds 'linear'), whose state "
                "no position rolls back, no block hash shares and no "
                "block spills",
                cfg.has_linear,
            ),
        )
        if on
    ]
    if named:
        raise ValueError(
            f"{option} does not serve a model with {' and '.join(named)}: "
            "it computes one kind of layer with a dense FFN and two "
            "norms. Serve this model on PagedDecodeServer's default "
            "path (attention='gathered', no other option)."
        )


#: Projections whose INPUT axis is tp-sharded (Megatron row-parallel,
#: partial sums closed by the block's psum). Everything else adaptable
#: is column-parallel (output features sharded).
_ROW_PARALLEL = frozenset({"wo", "w2"})


def lora_target_dims(cfg: TransformerConfig) -> dict:
    """(in_dim, out_dim) for every projection an adapter can target."""
    D, F = cfg.dim, cfg.ffn_dim
    dq = cfg.num_heads * cfg.dh
    dkv = cfg.kv_heads * cfg.dh
    dims = {
        "wq": (D, dq),
        "wk": (D, dkv),
        "wv": (D, dkv),
        "wo": (dq, D),
        "w1": (D, F),
        "w2": (F, D),
    }
    if cfg.ffn_style == "swiglu":
        dims["w3"] = (D, F)
    return dims


def init_stack(
    rng: jax.Array, cfg: TransformerConfig, dtype: Any = jnp.float32
) -> dict:
    """Parameters for L stacked encoder blocks, leading axis = layer.

    The key set follows the config: GQA narrows wk/wv to the KV head
    width, use_bias=False drops every b*, norm_type="rms" drops the
    norm biases, ffn_style="swiglu" adds the w3 up-projection, a
    parallel block has no second norm, and SwiGLU experts (the serving
    decoder's, `held_experts_ffn`) are the HELD experts' matrices under
    a router of the published width, plus the shared experts'."""
    L, D, F = cfg.num_layers, cfg.dim, cfg.ffn_dim
    # The leaves of `ATTN_LEAVES` are stacked over the attention
    # layers alone and the `gdn_*` ones over the recurrent layers
    # (`leaf_group`); a stack with no recurrent layer has La == L.
    La, Ll = cfg.layers_of("attn"), cfg.layers_of("linear")
    dq = cfg.num_heads * cfg.dh
    dkv = cfg.kv_heads * cfg.dh
    ks = jax.random.split(rng, 8)
    s = D**-0.5
    norms = ("ln1",) if cfg.parallel_block else ("ln1", "ln2")
    # Under (1 + w) a norm's scale starts at 0.
    unit = jnp.zeros if cfg.norm_offset else jnp.ones
    p = {
        "wq": jax.random.normal(
            ks[0], (La, D, dq * (2 if cfg.attn_gate else 1)), dtype
        ) * s,
        "wk": jax.random.normal(ks[1], (La, D, dkv), dtype) * s,
        "wv": jax.random.normal(ks[2], (La, D, dkv), dtype) * s,
        "wo": jax.random.normal(ks[3], (La, dq, D), dtype) * dq**-0.5,
    }
    p.update({f"{n}_scale": unit((L, D), dtype) for n in norms})
    if cfg.qk_norm:
        p["q_norm_scale"] = unit((La, cfg.dh), dtype)
        p["k_norm_scale"] = unit((La, cfg.dh), dtype)
    if Ll:
        hk, hv, dk, dv = cfg.gdn_k_heads, cfg.gdn_v_heads, cfg.gdn_k_dim, cfg.gdn_v_dim
        kg = jax.random.split(jax.random.fold_in(rng, 200), 5)
        p.update(
            {
                # Per key head [q dk | k dk | v r dv | z r dv], r = hv / hk.
                "gdn_qkvz": jax.random.normal(
                    kg[0], (Ll, D, 2 * hk * dk + 2 * hv * dv), dtype
                ) * s,
                # Per key head [b r | a r].
                "gdn_ba": jax.random.normal(kg[1], (Ll, D, 2 * hv), dtype) * s,
                # Tap i multiplies the row gdn_conv - 1 - i back.
                "gdn_conv": jax.random.normal(
                    kg[2], (Ll, cfg.gdn_conv, cfg.gdn_channels), dtype
                ) * cfg.gdn_conv**-0.5,
                "gdn_A_log": jnp.log(
                    jax.random.uniform(kg[3], (Ll, hv), dtype, 1e-3, 16.0)
                ),
                "gdn_dt_bias": jnp.ones((Ll, hv), dtype),
                "gdn_norm_scale": jnp.ones((Ll, dv), dtype),
                "gdn_out": jax.random.normal(kg[4], (Ll, hv * dv, D), dtype)
                * (hv * dv) ** -0.5,
            }
        )
    if cfg.use_bias:
        p.update(
            {
                "bq": jnp.zeros((La, dq), dtype),
                "bk": jnp.zeros((La, dkv), dtype),
                "bv": jnp.zeros((La, dkv), dtype),
                "bo": jnp.zeros((La, D), dtype),
            }
        )
    if cfg.norm_type == "layer" and cfg.norm_bias:
        p.update({f"{n}_bias": jnp.zeros((L, D), dtype) for n in norms})
    if cfg.num_experts and cfg.ffn_style == "swiglu":
        lo, hi = cfg.held
        Fe = cfg.expert_dim or F
        kr, ksh = jax.random.split(ks[6])

        def experts(key, n):
            k1, k2, k3 = jax.random.split(key, 3)
            return (
                jax.random.normal(k1, (L, n, D, Fe), dtype) * s,
                jax.random.normal(k3, (L, n, D, Fe), dtype) * s,
                jax.random.normal(k2, (L, n, Fe, D), dtype) * Fe**-0.5,
            )

        p["router"] = jax.random.normal(kr, (L, D, cfg.num_experts), dtype) * s
        p["w1"], p["w3"], p["w2"] = experts(ks[4], hi - lo)
        if cfg.num_shared_experts:
            p["sw1"], p["sw3"], p["sw2"] = experts(ksh, cfg.num_shared_experts)
        if cfg.shared_gate:
            p["sw_gate"] = jax.random.normal(
                jax.random.fold_in(ksh, 1), (L, D, cfg.num_shared_experts), dtype
            ) * s
    elif cfg.num_experts:
        E = cfg.num_experts
        p.update(
            {
                "router": jax.random.normal(ks[6], (L, D, E), dtype) * s,
                "w1": jax.random.normal(ks[4], (L, E, D, F), dtype) * s,
                "b1": jnp.zeros((L, E, F), dtype),
                "w2": jax.random.normal(ks[5], (L, E, F, D), dtype)
                * (F**-0.5),
                "b2": jnp.zeros((L, E, D), dtype),
            }
        )
    else:
        p.update(
            {
                "w1": jax.random.normal(ks[4], (L, D, F), dtype) * s,
                "w2": jax.random.normal(ks[5], (L, F, D), dtype)
                * (F**-0.5),
            }
        )
        if cfg.ffn_style == "swiglu":
            p["w3"] = jax.random.normal(ks[7], (L, D, F), dtype) * s
        if cfg.use_bias:
            p["b1"] = jnp.zeros((L, F), dtype)
            p["b2"] = jnp.zeros((L, D), dtype)
    if cfg.lora_rank:
        r = cfg.lora_rank
        dims = lora_target_dims(cfg)
        for i, t in enumerate(cfg.lora_targets):
            din, dout = dims[t]
            p[f"{t}:a"] = (
                jax.random.normal(
                    jax.random.fold_in(rng, 100 + i), (L, din, r), dtype
                )
                * din**-0.5
            )
            # Zero b => a fresh adapter changes nothing: the fine-tune
            # starts exactly at the pretrained model.
            p[f"{t}:b"] = jnp.zeros((L, r, dout), dtype)
    return p


def stack_specs(
    stage_axis: str | None = "stage",
    tp_axis: str | None = None,
    *,
    ep_axis: str | None = None,
    moe: bool = False,
    cfg: TransformerConfig | None = None,
) -> dict:
    """PartitionSpecs matching init_stack: layer axis -> stage axis;
    q/k/v/ffn-in column-parallel, out/ffn-out row-parallel over tp; with
    moe=True the expert axis of the FFN weights shards over ep_axis.
    Pass `cfg` to tailor the key set to a llama-style stack (dropped
    biases, rms norms, swiglu w3 — all matching init_stack)."""
    st, tp, ep = stage_axis, tp_axis, ep_axis
    use_bias = cfg.use_bias if cfg is not None else True
    layer_norm = cfg.norm_type == "layer" if cfg is not None else True
    swiglu = cfg.ffn_style == "swiglu" if cfg is not None else False
    p = {
        "wq": P(st, None, tp),
        "wk": P(st, None, tp),
        "wv": P(st, None, tp),
        "wo": P(st, tp, None),
        "ln1_scale": P(st, None),
        "ln2_scale": P(st, None),
    }
    if use_bias:
        p.update(
            {
                "bq": P(st, tp),
                "bk": P(st, tp),
                "bv": P(st, tp),
                "bo": P(st, None),
            }
        )
    if layer_norm:
        p.update(
            {
                "ln1_bias": P(st, None),
                "ln2_bias": P(st, None),
            }
        )
    if swiglu:
        p["w3"] = P(st, None, tp)
    if moe:
        p.update(
            {
                "router": P(st, None, None),
                "w1": P(st, ep, None, tp),
                "b1": P(st, ep, tp),
                "w2": P(st, ep, tp, None),
                "b2": P(st, ep, None),
            }
        )
    else:
        p.update(
            {
                "w1": P(st, None, tp),
                "w2": P(st, tp, None),
            }
        )
        if use_bias:
            p["b1"] = P(st, tp)
            p["b2"] = P(st, None)
    if cfg is not None and cfg.lora_rank:
        for t in cfg.lora_targets:
            if t in _ROW_PARALLEL:
                # Input sharded like the base weight's rows; x @ a is a
                # partial sum the block's existing psum closes (the
                # low-rank path rides the same collective by linearity).
                p[f"{t}:a"] = P(st, tp, None)
                p[f"{t}:b"] = P(st, None, None)
            else:
                # Rank axis replicated, output features tp-sharded like
                # the base weight's columns.
                p[f"{t}:a"] = P(st, None, None)
                p[f"{t}:b"] = P(st, None, tp)
    return p


def first_free_divisible_dim(
    spec, dims, dp: int, *, offset: int = 0
) -> int | None:
    """Index (into `dims`) of the first dimension `spec` leaves
    unsharded and the axis size `dp` divides — THE placement rule
    shared by FSDP weight sharding (fsdp_plan, offset=1 to skip the
    stacked layer axis) and ZeRO-1 moment sharding
    (train.zero1_shardings). None if no dim qualifies."""
    spec = list(spec)
    for i, dim in enumerate(dims):
        ax = spec[i + offset] if i + offset < len(spec) else None
        if ax is None and dim % dp == 0 and dim >= dp:
            return i
    return None


def fsdp_plan(
    cfg: TransformerConfig, per_layer_specs: dict, dp: int
) -> dict:
    """FSDP placement: {param key -> per-layer dim index} to shard over
    the data axis (and to all-gather back on use).

    For each stack leaf, pick the first dimension the per-layer spec
    leaves unsharded whose size the data-axis size divides — shapes
    come from an eval_shape of init_stack, so every key the config
    produces (biases, norms, MoE experts, LoRA factors) is planned by
    the same rule. Leaves with no eligible dim (e.g. tp-sharded
    biases) stay as they are: FSDP is a per-leaf memory optimization,
    not an all-or-nothing mode.
    """
    if dp <= 1:
        return {}
    shapes = jax.eval_shape(
        lambda k: init_stack(k, cfg), jax.random.key(0)
    )
    plan: dict = {}
    for key, leaf in shapes.items():
        axis = first_free_divisible_dim(
            per_layer_specs[key], leaf.shape[1:], dp, offset=1
        )
        if axis is not None:
            plan[key] = axis
    return plan


def build_fsdp_plan(cfg: TransformerConfig, per_layer_specs: dict, mesh) -> dict:
    """Shared SpmdBert/SpmdVit fsdp=True setup: validate the mesh has
    a data axis to shard over, then plan per-leaf placement."""
    dp = mesh.shape.get("data", 1)
    if dp <= 1:
        raise ValueError(
            "fsdp=True needs a 'data' mesh axis of size > 1 "
            "(there is nothing to shard the weights over)"
        )
    return fsdp_plan(cfg, per_layer_specs, dp)


def fsdp_specs(per_layer_specs: dict, plan: dict, data_axis: str) -> dict:
    """Apply an fsdp_plan to per-layer PartitionSpecs: entry
    plan[key]+1 (after the layer axis) becomes the data axis."""
    out = dict(per_layer_specs)
    for key, axis in plan.items():
        spec = list(out[key])
        while len(spec) < axis + 2:
            spec.append(None)
        spec[axis + 1] = data_axis
        out[key] = P(*spec)
    return out


def moe_ffn(
    p: dict,
    x: jax.Array,
    *,
    tp_axis: str | None = None,
    ep_axis: str | None = None,
    top_k: int = 1,
) -> jax.Array:
    """Top-k mixture-of-experts FFN on (B, S, D) — dense dispatch.

    Expert parallelism by partition-of-experts: each device along
    ep_axis holds E_local experts, computes them for every token, and
    the top-k dispatch mask zeroes the rest before a psum over ep
    combines shards. Dense dispatch keeps shapes static (no capacity /
    token dropping) — the XLA-friendly formulation; a capacity-based
    all_to_all dispatch is the scaling path for large expert counts.

    The router is replicated; routing probabilities are computed over
    the GLOBAL expert count so results are identical for any ep layout.
    """
    dt = x.dtype
    e_local = p["w1"].shape[0]
    ep = 1 if ep_axis is None else lax.axis_size(ep_axis)
    ep_idx = 0 if ep_axis is None else lax.axis_index(ep_axis)

    idx, wts = _route_topk(p["router"], x, top_k)  # (B, S, k)
    _, gate = _dispatch_weights(idx, wts, ep * e_local)  # (B, S, E)
    # This device's expert columns of the global gate matrix.
    dispatch = lax.dynamic_slice_in_dim(
        gate, ep_idx * e_local, e_local, axis=-1
    )  # (B, S, E_local)

    h = (
        jnp.einsum("bsd,edf->ebsf", x, p["w1"].astype(dt))
        + p["b1"].astype(dt)[:, None, None, :]
    )
    h = jax.nn.gelu(h)
    y = jnp.einsum("ebsf,efd->ebsd", h, p["w2"].astype(dt))
    if tp_axis is not None:
        # w1 column- / w2 row-sharded over tp: partial sums, as in the
        # dense FFN.
        y = lax.psum(y, tp_axis)
    y = y + p["b2"].astype(dt)[:, None, None, :]
    out = jnp.einsum(
        "ebsd,bse->bsd", y.astype(jnp.float32), dispatch
    )
    if ep_axis is not None:
        out = lax.psum(out, ep_axis)
    return out.astype(dt)


def _route_topk(router: jax.Array, x: jax.Array, k: int):
    """Shared top-k routing (fp32 softmax over the GLOBAL expert
    count): returns (expert_indices [..., k], weights [..., k]). ONE
    definition for both dispatches — dense/a2a equivalence depends on
    the routing staying identical. k=1 keeps the Switch convention
    (raw top probability as the gate); k>1 renormalizes over the
    selected experts (Mixtral)."""
    logits = x.astype(jnp.float32) @ router.astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    w, idx = jax.lax.top_k(probs, k)
    if k > 1:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return idx, w


def _dispatch_weights(idx, w, e_global: int):
    """(member [..., E] in {0,1}, gate [..., E]) from top-k routing."""
    sel = jax.nn.one_hot(idx, e_global, dtype=jnp.float32)  # (..., k, E)
    member = sel.sum(axis=-2)
    gate = (sel * w[..., None]).sum(axis=-2)
    return member, gate


def moe_ffn_a2a(
    p: dict,
    x: jax.Array,
    *,
    capacity_factor: float = 1.25,
    tp_axis: str | None = None,
    ep_axis: str | None = None,
    top_k: int = 1,
) -> jax.Array:
    """Top-k MoE FFN with all-to-all expert dispatch on (B, S, D).

    The scaling path dense dispatch can't reach: each device along ep
    takes ITS OWN 1/ep slice of the token stream (tokens arrive
    replicated over ep in this stack, so the slice assigns real
    ownership), routes the slice into a static (E, C, D) capacity
    buffer (C = capacity_factor x slice_tokens / E, Switch-style;
    over-capacity tokens fall through on the residual path), moves
    each expert's slots to that expert's device with one
    `lax.all_to_all` over ICI — carrying DISTINCT tokens per sender —
    runs only the local experts, and returns outputs by the inverse
    all_to_all. Per-device expert compute is capacity-bounded
    (cf x N / E_global x E_local tokens) instead of dense's
    N x E_local, and one psum reassembles the replicated output —
    the same closing collective as the dense dispatch.

    Routing matches moe_ffn exactly (one shared _route_topk, per-token
    decisions), so with C large enough to drop nothing the two
    dispatches are numerically equivalent — that equivalence is the
    correctness test.
    """
    import math

    dt = x.dtype
    b, s, d = x.shape
    n = b * s
    e_local = p["w1"].shape[0]
    ep = 1 if ep_axis is None else lax.axis_size(ep_axis)
    e_global = ep * e_local
    if n % ep:
        raise ValueError(
            f"a2a dispatch needs tokens ({n} = {b}x{s}) divisible by "
            f"the expert axis size {ep}"
        )
    n_l = n // ep
    # Each token claims top_k slots, so capacity scales with k.
    cap = max(1, math.ceil(capacity_factor * top_k * n_l / e_global))

    xf = x.reshape(n, d)
    ep_idx = 0 if ep_axis is None else lax.axis_index(ep_axis)
    x_own = lax.dynamic_slice_in_dim(xf, ep_idx * n_l, n_l)  # (n_l, D)
    idx, wts = _route_topk(p["router"], x_own, top_k)  # (n_l, k)
    member, gate = _dispatch_weights(idx, wts, e_global)  # (n_l, E)

    # Arrival-order position of each token within each selected
    # expert's queue; positions >= cap are dropped (Switch-style).
    member_i = member.astype(jnp.int32)
    pos_in_e = jnp.cumsum(member_i, axis=0) - 1  # (n_l, E)
    keep = (pos_in_e < cap) & (member_i > 0)
    dispatch = (
        jax.nn.one_hot(pos_in_e, cap, dtype=jnp.float32)
        * keep[..., None]
    )  # (n_l, E, C)
    combine = dispatch * gate[..., None].astype(jnp.float32)

    xin = jnp.einsum("nd,nec->ecd", x_own.astype(jnp.float32), dispatch)
    if ep_axis is not None:
        # (E, C, D) -> (E_local, ep*C, D): expert-group rows k go to
        # device k (split over the expert axis); the received sender
        # chunks concatenate on the slot axis in sender order, so
        # slot block j belongs to device j for the inverse route.
        xin = lax.all_to_all(
            xin, ep_axis, split_axis=0, concat_axis=1, tiled=True
        )

    h = jnp.einsum("ecd,edf->ecf", xin.astype(dt), p["w1"].astype(dt))
    h = h + p["b1"].astype(dt)[:, None, :]
    h = jax.nn.gelu(h)
    y = jnp.einsum("ecf,efd->ecd", h, p["w2"].astype(dt))
    if tp_axis is not None:
        y = lax.psum(y, tp_axis)
    y = y + p["b2"].astype(dt)[:, None, :]

    if ep_axis is not None:
        # Inverse route: slot chunks return to their sender, expert
        # chunks stack back into global expert order.
        y = lax.all_to_all(
            y, ep_axis, split_axis=1, concat_axis=0, tiled=True
        )

    out_own = jnp.einsum(
        "ecd,nec->nd", y.astype(jnp.float32), combine
    )  # (n_l, D) — expert outputs for THIS device's token slice
    if ep_axis is None:
        return out_own.astype(dt).reshape(b, s, d)
    # Reassemble the replicated stream: each device contributes its
    # slice, one psum (dense's closing collective) sums the disjoint
    # contributions and returns the shard_map type to replicated.
    out = jnp.zeros((n, d), jnp.float32)
    out = lax.dynamic_update_slice(out, out_own, (ep_idx * n_l, 0))
    out = lax.psum(out, ep_axis)
    return out.astype(dt).reshape(b, s, d)


def act_einsum(spec: str, h: jax.Array, w: jax.Array) -> jax.Array:
    """`jnp.einsum(spec, h, w)` of activations `h` with a stored weight
    `w`, in h's dtype. Where h is float32 and w bf16 (float32
    activations served over bf16 weights) the product is exact to
    float32 in ONE bf16 pass of the matrix unit: h is split into three
    bf16 pieces (h = hi + mid + lo to 24 bits), the pieces are stacked
    as more rows against the one weight, and their three results
    summed. The general "highest" precision splits the weight too (six
    passes), of which a bf16 weight has nothing to give; a decode
    step's few rows ride one pass for nothing, a prefill's pay three.
    `spec` names h's operand first and may not use the letter P."""
    if h.dtype != jnp.float32 or w.dtype != jnp.bfloat16:
        return jnp.einsum(spec, h, w.astype(h.dtype))
    # Rounded by `reduce_precision`, not by a cast to bf16 and back: XLA
    # may keep a pair of converts in the wider type ("excess
    # precision"), and the pieces below the first would then be zero
    # (on the chip the served logits left the reference by 0.14; PR 34).
    def piece(a):
        return lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)

    hi = piece(h)
    mid = piece(h - hi)
    lo = piece(h - hi - mid)
    ins, out = spec.split("->")
    parts = jnp.einsum(
        f"P{ins}->P{out}", jnp.stack([hi, mid, lo]).astype(jnp.bfloat16), w,
        preferred_element_type=jnp.float32, precision=lax.Precision.DEFAULT,
    )
    return parts[0] + parts[1] + parts[2]


# Rows of one tile of an expert's tokens in `held_experts_ffn`: an
# expert's weights (100 MB at 4096 x 4096 SwiGLU in bf16) are read once
# a tile, so a tile must hold enough rows to pay for the read, and a
# step of fewer tokens than this is one tile an expert.
_EXPERT_TILE = 256
_DECODE_TILE = 32


#: The leaves of `held_experts_ffn` that a layer scan must NOT slice:
#: they stay layer-stacked and are indexed [layer, expert] where a
#: product reads them (see its docstring).
EXPERT_LEAVES = ("w1", "w3", "w2", "sw1", "sw3", "sw2")

#: The leaves only a layer with keys and values has.
ATTN_LEAVES = (
    "wq", "wk", "wv", "wo", "bq", "bk", "bv", "bo",
    "q_norm_scale", "k_norm_scale",
)


def leaf_group(name: str) -> str:
    """Which layers a stack leaf is stacked over: "attn" (those with
    keys and values), "linear" (the recurrent ones) or "all"; see
    `TransformerConfig.layers_of`."""
    if name.startswith("gdn_"):
        return "linear"
    return "attn" if name.split(":")[0] in ATTN_LEAVES else "all"


def held_experts_ffn(
    p: dict, x: jax.Array, cfg: TransformerConfig, live=None, layer=None
):
    """The serving decoder's expert layer on (B, T, D), told which
    experts it holds (`cfg.experts_held` of the `cfg.num_experts` the
    router scores): it routes over ALL published experts, normalises
    the chosen `moe_top_k` weights over the chosen whether held or not,
    and returns the HELD experts' part of the routed sum plus the
    shared experts' mean (or sum; with `cfg.shared_gate` each scaled
    by sigmoid(x @ sw_gate) first). What the absent experts would add
    is left out: on one chip the layer runs without its exchange.

    No capacity and no dropped token. The (token, expert) assignments
    are sorted by expert ONCE (stable, so an expert's tokens keep
    their order; assignments on absent experts go last), and ONE loop
    runs over tiles of `_EXPERT_TILE` assignments of one expert each,
    in expert order, as many tiles as each expert's count needs: the
    trip count is traced (an expert nobody chose computes nothing and
    its weights are not read), every shape is static, and the program
    is the same size whatever the number of experts held. One form
    for decode (a tile is then the whole batch) and prefill.

    With `layer` given, the `EXPERT_LEAVES` of `p` are still
    layer-stacked ([L, E, ...]) and `layer` (an int or a traced scalar)
    picks the layer: each product then slices its one matrix out of
    the whole stack inside the loop that uses it. A layer's experts
    sliced outside (a scan's xs) would be a loop operand, and XLA
    copies a loop's operands: 512 MB a leaf a layer at 16 x 4096 x
    4096, more than the step should read in all.

    `live` (B, T) bool marks the rows the counters count (None = all).
    Returns (y, stats): stats int32 [2] = the assignments that fell on
    held experts and the distinct held experts touched, live rows only.
    """
    dt = x.dtype
    b, t, d = x.shape
    n = b * t
    k = cfg.moe_top_k
    xf = x.reshape(n, d)
    lo, hi = cfg.held
    eh = hi - lo
    # A decode step's rows (t == 1) are a few an expert: tiles of
    # `_DECODE_TILE` keep the products under the weights' read.
    tile = min(n, _DECODE_TILE if t == 1 else _EXPERT_TILE)
    with jax.named_scope("moe_router"):
        logits = jnp.dot(
            xf.astype(jnp.float32),
            p["router"].astype(jnp.float32),
            precision=lax.Precision.HIGHEST,
        )
        scores = (
            jax.nn.sigmoid(logits)
            if cfg.moe_gate == "sigmoid"
            else jax.nn.softmax(logits, axis=-1)
        )
        w, idx = lax.top_k(scores, k)  # (N, k)
        if k > 1:
            w = w / jnp.sum(w, axis=-1, keepdims=True)
        # Assignment a = token a // k's a % k-th choice. Held experts
        # keep their number, absent ones sort behind them all.
        expert = (idx - lo).reshape(n * k)
        held = (expert >= 0) & (expert < eh)
        order = jnp.argsort(jnp.where(held, expert, eh), stable=True)
        token = order // k
        weight = w.reshape(n * k)[order]
        mine = jax.nn.one_hot(expert, eh, dtype=jnp.int32)  # 0 where absent
        count = mine.sum(axis=0)  # (eh,)
        first = jnp.cumsum(count) - count  # an expert's place in `order`
        tiles = -(-count // tile)
        tiles_end = jnp.cumsum(tiles)
        counted = mine if live is None else mine * jnp.repeat(
            live.reshape(n), k
        ).astype(jnp.int32)[:, None]
        counted = counted.sum(axis=0)
        stats = jnp.stack([counted.sum(), (counted > 0).sum()]).astype(jnp.int32)

    def weight_of(name, e=None):
        at = tuple(i for i in (layer, e) if i is not None)
        return p[name][at]

    def one_tile(j, out):
        # Tile j is tile i of expert e: the first whose tiles end past j.
        e = jnp.sum(tiles_end <= j)
        i = j - (tiles_end[e] - tiles[e])
        at = i * tile + jnp.arange(tile)
        # Past the expert's count the tile runs on into whatever
        # follows in the order: computed, and weighted 0.
        a = jnp.minimum(first[e] + at, n * k - 1)
        rows = token[a]
        wt = jnp.where(at < count[e], weight[a], 0.0)
        h = xf[rows]
        h = jax.nn.silu(act_einsum("nd,df->nf", h, weight_of("w1", e))) * (
            act_einsum("nd,df->nf", h, weight_of("w3", e))
        )
        y = act_einsum("nf,fd->nd", h, weight_of("w2", e))
        return out.at[rows].add(y.astype(jnp.float32) * wt[:, None])

    with jax.named_scope("moe_experts"):
        out = lax.fori_loop(
            0, tiles_end[-1], one_tile, jnp.zeros((n, d), jnp.float32)
        )
    if "sw1" in p:
        with jax.named_scope("moe_shared"):
            hs = jax.nn.silu(
                act_einsum("nd,sdf->snf", xf, weight_of("sw1"))
            ) * act_einsum("nd,sdf->snf", xf, weight_of("sw3"))
            if cfg.shared_gate:
                gate = jax.nn.sigmoid(
                    xf.astype(jnp.float32) @ p["sw_gate"].astype(jnp.float32)
                )  # (N, S)
                ys = act_einsum("snf,sfd->snd", hs, weight_of("sw2"))
                ys = jnp.sum(
                    ys.astype(jnp.float32) * gate.T[:, :, None], axis=0
                )
            else:
                ys = jnp.einsum(
                    "snf,sfd->nd", hs, weight_of("sw2").astype(dt),
                    preferred_element_type=jnp.float32,
                )
            if cfg.shared_combine == "mean":
                ys = ys / cfg.num_shared_experts
            out = out + ys
    return out.astype(dt).reshape(b, t, d), stats


def embed_lookup(
    table: Any, ids: jax.Array, tp_axis: str | None = None
) -> jax.Array:
    """Token-embedding gather shared by every decoder family (gpt,
    llama, t5).

    Plain [V, D] tables gather directly; int8 weight-only tables
    ({"q", "s"}, models/quant.py) gather the int8 rows and widen just
    the gathered [B, T, D] slice. With tp_axis set (inside shard_map)
    the table is vocab-ROW sharded (Megatron): this shard owns rows
    [v0, v0 + V_local), out-of-range ids contribute zeros, and one
    psum assembles full embeddings."""
    quant = isinstance(table, dict) and "q" in table
    rows = table["q"] if quant else table
    if tp_axis is None:
        emb = jnp.take(rows, ids, axis=0)
        if quant:
            emb = emb.astype(jnp.float32) * table["s"]
        return emb
    v_local = rows.shape[0]
    v0 = lax.axis_index(tp_axis) * v_local
    local_ids = ids - v0
    in_range = (local_ids >= 0) & (local_ids < v_local)
    emb = jnp.take(rows, jnp.clip(local_ids, 0, v_local - 1), axis=0)
    if quant:
        emb = emb.astype(jnp.float32) * table["s"]
    emb = jnp.where(in_range[..., None], emb, 0.0)
    return lax.psum(emb, tp_axis)


def _layer_norm(x, scale, bias, eps):
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mean), axis=-1, keepdims=True)
    out = (xf - mean) * lax.rsqrt(var + eps)
    out = out * scale.astype(jnp.float32)
    if bias is not None:
        out = out + bias.astype(jnp.float32)
    return out.astype(x.dtype)


def _rms_norm(x, scale, eps, offset: bool = False):
    """Scale-only RMS normalization (llama), fp32 statistics; with
    `offset` the scale is (1 + w)."""
    xf = x.astype(jnp.float32)
    out = xf * lax.rsqrt(jnp.mean(jnp.square(xf), -1, keepdims=True) + eps)
    scale = scale.astype(jnp.float32)
    return (out * (1.0 + scale if offset else scale)).astype(x.dtype)


def norm_apply(cfg: TransformerConfig, x, p: dict, which: str):
    """The config's normalization ("ln1"/"ln2" param group)."""
    if cfg.norm_type == "rms":
        return _rms_norm(
            x, p[f"{which}_scale"], cfg.layer_norm_eps, cfg.norm_offset
        )
    return _layer_norm(
        x, p[f"{which}_scale"], p.get(f"{which}_bias"), cfg.layer_norm_eps
    )


def apply_rope(
    x_flat: jax.Array,
    head_dim: int,
    positions: jax.Array,
    theta: float,
    pairing: str = "half",
    rotary_dim: int | None = None,
) -> jax.Array:
    """Rotary position embedding on a flat (B, T, H*Dh) projection.

    Rotation is per-head and head-independent, so reshaping to
    (B, T, H, Dh) handles any head count — the SAME helper serves full
    q, GQA-narrow k, and tensor-parallel local shards. Pairing is the
    rotate-half convention (first half with second half), matching HF
    transformers' llama so checkpoints transplant bit-compatibly.
    `positions` are the ABSOLUTE sequence positions of the T tokens:
    shape (T,) shared across the batch (decode passes cache_pos +
    arange(T); sequence-parallel shards pass their global offsets) or
    (B, T) per batch element (continuous batching, where every slot
    sits at its own depth). `pairing="interleaved"` rotates lanes
    (2i, 2i + 1) together instead (GPT-J's convention), at the same
    frequencies. With `rotary_dim` only a head's first `rotary_dim`
    lanes rotate, paired and timed within themselves (frequency theta
    ** (-2i / rotary_dim)); the rest pass."""
    b, t, d = x_flat.shape
    if rotary_dim is not None and rotary_dim != head_dim:
        x = x_flat.reshape(b, t, d // head_dim, head_dim)
        turned = apply_rope(
            x[..., :rotary_dim].reshape(b, t, -1), rotary_dim, positions,
            theta, pairing,
        ).reshape(b, t, -1, rotary_dim)
        return jnp.concatenate(
            [turned, x[..., rotary_dim:]], axis=-1
        ).reshape(b, t, d)
    x = x_flat.reshape(b, t, d // head_dim, head_dim)
    half = head_dim // 2
    if pairing == "interleaved":
        # Lane j turns with its neighbour j ^ 1 at the pair's frequency.
        lane = jnp.arange(head_dim)
        freqs = theta ** (-(lane // 2).astype(jnp.float32) * 2.0 / head_dim)
    else:
        freqs = theta ** (
            -jnp.arange(0, half, dtype=jnp.float32) * 2.0 / head_dim
        )
    ang = positions.astype(jnp.float32)[..., None] * freqs  # (..., T, lanes)
    if ang.ndim == 2:  # shared positions -> add the batch axis
        ang = ang[None]
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    if pairing == "interleaved":
        # The partner comes by a roll along the lanes: splitting them
        # into (Dh/2, 2) would leave a minor axis of 2, which the
        # TPU's (8, 128) tiles pad 64-fold.
        xf = x.astype(jnp.float32)
        partner = jnp.where(
            lane % 2 == 0, -jnp.roll(xf, -1, axis=-1), jnp.roll(xf, 1, axis=-1)
        )
        out = (xf * cos + partner * sin).astype(x_flat.dtype)
        return out.reshape(b, t, d)
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(
        jnp.float32
    )
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    ).astype(x_flat.dtype)
    return out.reshape(b, t, d)


def repeat_kv(x_flat: jax.Array, head_dim: int, groups: int) -> jax.Array:
    """Expand a flat (B, T, H_kv*Dh) K/V projection to (B, T, H*Dh) by
    repeating each KV head for its query-head group (GQA)."""
    if groups == 1:
        return x_flat
    b, t, d = x_flat.shape
    x = x_flat.reshape(b, t, d // head_dim, head_dim)
    x = jnp.repeat(x, groups, axis=2)
    return x.reshape(b, t, d * groups)


def block_apply(
    p: dict,
    x: jax.Array,
    cfg: TransformerConfig,
    *,
    tp_axis: str | None = None,
    sp_axis: str | None = None,
    sp_strategy: str = "ring",
    ep_axis: str | None = None,
) -> jax.Array:
    """One encoder block on (B, S, D) (post- or pre-LN per
    cfg.norm_style); params have no layer axis.

    Under shard_map with tp_axis set, the projections arrive
    column-sharded (local output features = one head group) and wo/w2
    row-sharded: local matmuls produce partial sums reduced with psum
    over the tp axis — the Megatron pattern, collectives on ICI.

    With sp_axis set, S is the LOCAL sequence shard and attention runs
    ring / Ulysses over that mesh axis (defer_tpu/parallel/sequence.py);
    everything else in the block is per-token and needs no collective.
    """
    if cfg.layer_kinds is not None or cfg.parallel_block or (
        cfg.num_experts and cfg.ffn_style == "swiglu"
    ):
        raise ValueError(
            "the training block computes one kind of layer, two norms "
            "and GELU experts: layer_kinds, parallel_block and SwiGLU "
            "experts are the serving decoder's (models/gpt.py)"
        )
    dt = x.dtype
    tp_size = 1 if tp_axis is None else lax.axis_size(tp_axis)
    local_heads = cfg.num_heads // tp_size
    dh = cfg.dh
    groups = cfg.num_heads // cfg.kv_heads
    pre = cfg.norm_style == "pre"

    def bias(h, name):
        return h + p[name].astype(dt) if name in p else h

    lora_scale = cfg.lora_scale

    def proj(h, name):
        """Base matmul plus the low-rank adapter path when present.
        Under tp the adapter factors are sharded to match the base
        weight (stack_specs), so no extra collective is needed."""
        y = h @ p[name].astype(dt)
        a = p.get(f"{name}:a")
        if a is not None:
            y = y + ((h @ a.astype(dt)) @ p[f"{name}:b"].astype(dt)) * lora_scale
        return y

    a_in = norm_apply(cfg, x, p, "ln1") if pre else x
    q = bias(proj(a_in, "wq"), "bq")
    k = bias(proj(a_in, "wk"), "bk")
    v = bias(proj(a_in, "wv"), "bv")
    if cfg.pos_style == "rope":
        s_local = q.shape[1]
        offset = (
            0 if sp_axis is None else lax.axis_index(sp_axis) * s_local
        )
        positions = offset + jnp.arange(s_local)
        q = apply_rope(q, dh, positions, cfg.rope_theta, cfg.rope_pairing)
        k = apply_rope(k, dh, positions, cfg.rope_theta, cfg.rope_pairing)
    # GQA: expand KV head groups AFTER rope so each query head in a
    # group attends its shared (rotated) KV head.
    k = repeat_kv(k, dh, groups)
    v = repeat_kv(v, dh, groups)
    attn = multi_head_attention(
        q,
        k,
        v,
        num_heads=local_heads,
        causal=cfg.causal,
        window=cfg.window,
        use_pallas="auto",
        sp_axis=sp_axis,
        sp_strategy=sp_strategy,
    )
    attn = proj(attn, "wo")
    if tp_axis is not None:
        attn = lax.psum(attn, tp_axis)
    attn = bias(attn, "bo")
    if pre:
        x = x + attn
        f_in = norm_apply(cfg, x, p, "ln2")
    else:
        x = norm_apply(cfg, x + attn, p, "ln1")
        f_in = x

    if "router" in p:
        if cfg.moe_dispatch == "a2a":
            h = moe_ffn_a2a(
                p,
                f_in,
                capacity_factor=cfg.capacity_factor,
                tp_axis=tp_axis,
                ep_axis=ep_axis,
                top_k=cfg.moe_top_k,
            )
        else:
            h = moe_ffn(
                p,
                f_in,
                tp_axis=tp_axis,
                ep_axis=ep_axis,
                top_k=cfg.moe_top_k,
            )
    elif cfg.ffn_style == "swiglu":
        # llama FFN: silu(gate) * up -> down (w1=gate, w3=up, w2=down).
        gate = jax.nn.silu(proj(f_in, "w1"))
        h = proj(gate * proj(f_in, "w3"), "w2")
        if tp_axis is not None:
            h = lax.psum(h, tp_axis)
    else:
        h = bias(proj(f_in, "w1"), "b1")
        h = jax.nn.gelu(h)
        h = proj(h, "w2")
        if tp_axis is not None:
            h = lax.psum(h, tp_axis)
        h = bias(h, "b2")
    if pre:
        return x + h
    return norm_apply(cfg, x + h, p, "ln2")


def layers_apply(
    stacked: dict,
    x: jax.Array,
    cfg: TransformerConfig,
    *,
    tp_axis: str | None = None,
    sp_axis: str | None = None,
    sp_strategy: str = "ring",
    ep_axis: str | None = None,
    fsdp_axis: str | None = None,
    fsdp_gather: dict | None = None,
) -> jax.Array:
    """Apply a [Llocal, ...]-stacked group of blocks via lax.scan (one
    compiled block body regardless of depth — compiler-friendly).
    cfg.remat wraps the block in jax.checkpoint: the scan then saves
    only each block's INPUT for the backward pass and recomputes the
    block internals, so activation memory per stage stays O(1) blocks
    (collectives inside the block — psum/all_to_all/ppermute — are
    replayed too, which XLA handles).

    With fsdp_axis set, each leaf named in fsdp_gather arrives sharded
    over that mesh axis on dim fsdp_gather[key] and is all-gathered
    JUST IN TIME inside the block body — classic FSDP: at-rest weight
    memory is 1/dp per chip, only the current block's weights are ever
    whole, and the gather's transpose is automatically the
    reduce-scatter the sharded gradients need. The gather sits inside
    the remat boundary, so cfg.remat re-gathers on the backward pass
    instead of keeping full weights alive."""

    def block(p_one, h):
        if fsdp_axis is not None and fsdp_gather:
            p_one = {
                k: (
                    lax.all_gather(
                        v, fsdp_axis, axis=fsdp_gather[k], tiled=True
                    )
                    if k in fsdp_gather
                    else v
                )
                for k, v in p_one.items()
            }
        return block_apply(
            p_one,
            h,
            cfg,
            tp_axis=tp_axis,
            sp_axis=sp_axis,
            sp_strategy=sp_strategy,
            ep_axis=ep_axis,
        )

    if cfg.remat:
        # prevent_cse=False: scan's staging already rules out the CSE
        # that flag guards against, and the default's optimization
        # barriers would block XLA fusion inside every block.
        block = jax.checkpoint(block, prevent_cse=False)

    def body(h, p_one):
        return block(p_one, h), None

    out, _ = lax.scan(body, x, stacked)
    return out
