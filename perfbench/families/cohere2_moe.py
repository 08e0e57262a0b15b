"""Family `cohere2_moe`: how a configuration file of this family becomes
the system under test, the family's plain reference, and its own count
of a decode step.

One layer, as the source's `config.json` and the catalog's description
give it (`h` = hidden size):

  n = LayerNorm(x)            mean subtracted, scale only, no bias
  y = x + Attn(n) + MoE(n)    one parallel block: one norm, one residual

  Attn   `num_attention_heads` Q heads and `num_key_value_heads` KV heads
         of `head_dim` (not h / heads), no biases, no QK norm, scale
         head_dim ** -0.5. `layer_types` gives each layer's kind: a
         `sliding_attention` layer rotates q and k (`rope_gptj`: lanes
         (2i, 2i + 1) together, `rope_theta`, every lane) and attends
         j <= i and j > i - `sliding_window`; a `full_attention` layer
         has no positions at all and attends every j <= i.
  MoE    s = sigmoid(n @ Wr) over all PUBLISHED experts; the
         `num_experts_per_tok` largest are chosen, their weights s_e
         over the sum of the chosen (`norm_topk_prob`); a routed expert
         is SwiGLU (silu(n W1) * (n W3)) W2 of width
         `intermediate_size`; `num_shared_experts` of the same shape
         see every token and their MEAN is added
         (`shared_expert_combination_strategy` "average").

Tied head, final LayerNorm, `logit_scale` 1. The file states the chip's
share: `experts_held` = [lo, hi) of the published experts live here
(`num_experts` = hi - lo), `vocab_size` rows of the vocabulary. Program
and reference alike route over all published experts, normalise over
the chosen whether held or not, and add the HELD experts' part only.

The reference is the benchmark's own: plain `jax.numpy`, float32 at
"highest" matmul precision, no cache, kernel, batching or program code;
one expert's float32 copy at a time, one KV group and 512 queries at a
time, so that it fits beside the served weights.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

QUERY_CHUNK = 512


def _held(model: dict) -> tuple[int, int]:
    lo, hi = model["experts_held"]
    if hi - lo != model["num_experts"]:
        raise ValueError(
            f"experts_held {model['experts_held']} is not num_experts "
            f"{model['num_experts']} experts"
        )
    return lo, hi


def _published_experts(model: dict) -> int:
    return model.get("published", model)["num_experts"]


def build_decoder(model: dict):
    """The decoder the server is given, from the configuration's own
    keys (Hugging Face names)."""
    from defer_tpu.models.gpt import GptDecoder
    from defer_tpu.parallel.transformer_stack import TransformerConfig
    from perfbench.contract import list_period

    if model["logit_scale"] != 1 or model["rotary_pct"] != 1:
        raise ValueError("the family computes logit_scale 1 and rotary_pct 1")
    if model["first_k_dense_replace"] or model["use_qk_norm"]:
        raise ValueError("the family has no leading dense layer and no QK norm")
    types = model["layer_types"]
    kinds = tuple(
        (model["sliding_window"], True) if t == "sliding_attention" else (None, False)
        for t in types[: list_period(types)]
    )
    return GptDecoder(
        TransformerConfig(
            num_layers=model["num_hidden_layers"],
            dim=model["hidden_size"],
            num_heads=model["num_attention_heads"],
            num_kv_heads=model["num_key_value_heads"],
            head_dim=model["head_dim"],
            ffn_dim=model["intermediate_size"],
            vocab_size=model["vocab_size"],
            max_len=model["max_position_embeddings"],
            layer_norm_eps=model["layer_norm_eps"],
            norm_style="pre",
            causal=True,
            norm_type="layer",
            norm_bias=False,
            parallel_block=model["use_parallel_block"],
            ffn_style="swiglu",
            use_bias=model["attention_bias"],
            pos_style="rope",
            rope_theta=float(model["rope_theta"]),
            rope_pairing={"rope_gptj": "interleaved"}[model["position_embedding_type"]],
            layer_kinds=kinds,
            num_experts=_published_experts(model),
            experts_held=_held(model),
            moe_top_k=model["num_experts_per_tok"],
            moe_gate=model["expert_selection_fn"],
            expert_dim=model["intermediate_size"],
            num_shared_experts=model["num_shared_experts"],
            shared_combine={"average": "mean", "sum": "sum"}[
                model["shared_expert_combination_strategy"]
            ],
        )
    )


def make_params(dec, seed: int, mesh=None):
    """bf16 weights made on the device, in one jitted call, from the
    seed, as family `mistral` makes them: the tree `dec.init` would
    build (shapes from `jax.eval_shape`: nothing float32 is allocated
    whole), each matrix normal * fan_in^-0.5, the embedding normal *
    0.02, norm scales 1. A stacked leaf is drawn one matrix at a time
    (a layer's experts are 1 GiB in float32), so the generator's
    temporaries stay one matrix's size."""
    if mesh is not None:
        raise ValueError("family cohere2_moe is served on one chip: mesh must be null")
    shapes = jax.eval_shape(dec.init, jax.random.key(0))
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    dtype = jnp.bfloat16

    def matrix(key, shape, scale):
        if len(shape) <= 2:
            return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)
        return jax.lax.map(
            lambda k: matrix(k, shape[1:], scale), jax.random.split(key, shape[0])
        )

    def one(key, path, shape):
        name = str(path[-1].key)
        if name.endswith("_scale"):
            return jnp.ones(shape, dtype)
        if name == "token_embedding":
            return matrix(key, shape, 0.02)
        return matrix(key, shape, shape[-2] ** -0.5)

    def build(key):
        return jax.tree_util.tree_unflatten(
            treedef,
            [
                one(jax.random.fold_in(key, i), path, s.shape)
                for i, (path, s) in enumerate(leaves)
            ],
        )

    return jax.jit(build)(jax.random.key(seed, impl="rbg"))


# -- the plain reference ----------------------------------------------------


def _layer_norm(x, scale, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * scale


def _rope_interleaved(x, theta):
    """x [T, H, Dh]; lanes (2i, 2i + 1) rotated together at positions
    0..T-1, frequency theta ** (-2i / Dh)."""
    t, _, dh = x.shape
    freqs = theta ** (-jnp.arange(dh // 2, dtype=jnp.float32) * 2.0 / dh)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., 0::2], x[..., 1::2]
    out = jnp.zeros_like(x)
    out = out.at[..., 0::2].set(a * cos - b * sin)
    return out.at[..., 1::2].set(b * cos + a * sin)


def _attention(model, n, p, kind, ignore_window=False):
    """Attn(n) for n [T, D]: one KV group and QUERY_CHUNK queries at a
    time."""
    hq, hkv, dh = (
        model["num_attention_heads"], model["num_key_value_heads"], model["head_dim"],
    )
    t = n.shape[0]
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    q = (n @ f32(p["wq"])).reshape(t, hq, dh)
    k = (n @ f32(p["wk"])).reshape(t, hkv, dh)
    v = (n @ f32(p["wv"])).reshape(t, hkv, dh)
    sliding = kind == "sliding_attention"
    if sliding:
        q = _rope_interleaved(q, float(model["rope_theta"]))
        k = _rope_interleaved(k, float(model["rope_theta"]))
    g = hq // hkv
    pad = -t % QUERY_CHUNK
    # [Hkv, chunks, Tc, g, Dh]: a KV group's queries, a chunk at a time.
    qs = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(-1, QUERY_CHUNK, hkv, g, dh)
    qs = qs.transpose(2, 0, 1, 3, 4)
    j = jnp.arange(t)[None, :]

    def group(args):
        qg, kg, vg = args  # [chunks, Tc, g, Dh], [T, Dh], [T, Dh]

        def chunk(args):
            qc, q0 = args
            i = q0 + jnp.arange(QUERY_CHUNK)[:, None]
            mask = j <= i
            if sliding and not ignore_window:
                mask &= j > i - model["sliding_window"]
            scores = jnp.einsum("qgd,kd->gqk", qc, kg) * dh**-0.5
            scores = jnp.where(mask[None], scores, -jnp.inf)
            return jnp.einsum("gqk,kd->qgd", jax.nn.softmax(scores, axis=-1), vg)

        return jax.lax.map(chunk, (qg, jnp.arange(qg.shape[0]) * QUERY_CHUNK))

    attn = jax.lax.map(group, (qs, k.transpose(1, 0, 2), v.transpose(1, 0, 2)))
    # [Hkv, chunks, Tc, g, Dh] -> [T, Hq * Dh]
    attn = attn.transpose(1, 2, 0, 3, 4).reshape(-1, hq * dh)[:t]
    return attn @ f32(p["wo"])


def _swiglu(n, w1, w3, w2):
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    return (jax.nn.silu(n @ f32(w1)) * (n @ f32(w3))) @ f32(w2)


def _experts(model, n, p, shared_sum=False):
    """The held experts' part of the routed sum plus the shared
    experts' mean, for n [T, D]; one expert's float32 copy at a time."""
    lo, hi = _held(model)
    s = jax.nn.sigmoid(n @ p["router"].astype(jnp.float32))  # [T, E published]
    w, idx = jax.lax.top_k(s, model["num_experts_per_tok"])
    w = w / jnp.sum(w, axis=-1, keepdims=True)

    def routed(acc, e):
        gate = jnp.sum(jnp.where(idx == lo + e, w, 0.0), axis=-1)  # [T]
        y = _swiglu(n, p["w1"][e], p["w3"][e], p["w2"][e])
        return acc + gate[:, None] * y, None

    out, _ = jax.lax.scan(routed, jnp.zeros_like(n), jnp.arange(hi - lo))

    def shared(acc, e):
        return acc + _swiglu(n, p["sw1"][e], p["sw3"][e], p["sw2"][e]), None

    n_shared = model["num_shared_experts"]
    both, _ = jax.lax.scan(shared, jnp.zeros_like(n), jnp.arange(n_shared))
    return out + (both if shared_sum else both / n_shared)


def _layer(model, x, p, kind, **faults):
    n = _layer_norm(x, p["ln1_scale"].astype(jnp.float32), model["layer_norm_eps"])
    attn = _attention(model, n, p, kind, faults.get("ignore_window", False))
    return x + attn + _experts(model, n, p, faults.get("shared_sum", False))


def reference_logits(model: dict, params: dict, ids, **faults) -> jax.Array:
    """Logits [T, V] of the full forward over ids [T], float32, for
    this chip's share. `faults` plants one for a control that must
    fail the comparison: `ignore_window` (every layer attends every
    j <= i), `shared_sum` (the shared experts summed, not averaged)."""
    types = model["layer_types"]
    layers = {
        kind: jax.jit(lambda x, p, kind=kind: _layer(model, x, p, kind, **faults))
        for kind in set(types)
    }
    with jax.default_matmul_precision("highest"):
        emb = params["token_embedding"]
        x = emb[jnp.asarray(ids)].astype(jnp.float32)
        for l in range(model["num_hidden_layers"]):
            kind = types[l % len(types)]
            x = layers[kind](x, {k: v[l] for k, v in params["stack"].items()})
        x = _layer_norm(
            x, params["final_ln_scale"].astype(jnp.float32), model["layer_norm_eps"]
        )
        head = jax.jit(lambda x, emb: x @ emb.astype(jnp.float32).T)
        return head(x, emb) * model["logit_scale"]


# -- the count of a decode step ------------------------------------------------


def decode_step_counts(model: dict, weight_bytes: int, depths) -> tuple[float, float]:
    """(bytes, operations): the least a decode step must read and
    compute with one live slot at each of `depths` cached rows.

    Bytes: every held weight once (attention, router, shared and held
    routed experts, the vocabulary slice) and the K and V rows a layer
    attends: `depth` in a full layer, `min(depth, sliding_window)` in a
    sliding one. Operations: per live token two per weight of the
    attention projections, the router, the shared experts,
    `num_experts_per_tok * held / published` routed experts (what
    uniform routing sends here) and the vocabulary slice, plus four per
    attended row, Q head and head dimension.

    Every held expert counts as read once whenever live slots x
    `num_experts_per_tok` reach the published count; with fewer, only
    the experts that many assignments could touch. That overstates the
    least by the share of held experts no token chose in a step, which
    `moe_experts_touched_share` reports (uniform routing at 32 slots:
    13%)."""
    d = model["hidden_size"]
    f = model["intermediate_size"]
    hq, hkv, dh = (
        model["num_attention_heads"], model["num_key_value_heads"], model["head_dim"],
    )
    held = model["num_experts"]
    published = _published_experts(model)
    k = model["num_experts_per_tok"]
    layers = model["num_hidden_layers"]
    types = model["layer_types"]
    live = len(depths)

    expert = 3 * d * f
    attention = 2 * d * hq * dh + 2 * d * hkv * dh
    touched = held if live * k >= published else min(held, live * k)
    unread = layers * (held - touched) * expert * 2  # bf16
    rows = sum(
        sum(
            min(depth, model["sliding_window"])
            if types[l % len(types)] == "sliding_attention" else depth
            for depth in depths
        )
        for l in range(layers)
    )
    nbytes = weight_bytes - unread + rows * 2 * hkv * dh * 2
    per_token = layers * (
        attention + d * published + model["num_shared_experts"] * expert
        + k * held / published * expert
    ) + model["vocab_size"] * d
    return nbytes, 2 * per_token * live + 4 * hq * dh * rows
