"""Family `qwen3_next`: how a configuration file of this family becomes
the system under test, the family's plain reference, and its own
counts of a decode step and of the `gdn_step` kernel.

The layers, as the source's `config.json` and the published
`qwen3_next` model code give them (`h` = hidden size):

  n(x) = x * rsqrt(mean(x^2) + rms_norm_eps) * (1 + w)      float32
  x = x + Mixer_l(n1(x));  x = x + MoE(n2(x))               two norms

Layer l is `full` where (l + 1) % `full_attention_interval` == 0, else
`linear`. Final norm the same, untied `lm_head`, no bias anywhere.

  full    gated attention. wq: h -> heads x 2 head_dim, per head
          [q | gate]; wk, wv: h -> kv heads x head_dim. q and k are
          RMS-normed per head with a (1 + w) scale, then rotated
          (`rope_theta`, rotate-half) on the first
          `partial_rotary_factor` x head_dim lanes, the rest pass;
          causal softmax attention, scale head_dim ** -0.5;
          o = wo(attn * sigmoid(gate)).
  linear  Gated DeltaNet. in_proj_qkvz: h -> per key head
          [q dk | k dk | v r dv | z r dv] (r = value heads / key
          heads), in_proj_ba: per key head [b r | a r].
          u = [q, k, v] over all heads goes through a causal depthwise
          convolution of width `linear_conv_kernel_dim` (no bias) and
          SiLU. beta = sigmoid(b), g = -exp(A_log) softplus(a +
          dt_bias). q and k are L2-normed per head (x * rsqrt(sum x^2
          + 1e-6)), q scaled by dk ** -0.5; a key head serves r
          consecutive value heads. Per value head, S in [dk, dv] from 0:
              S <- exp(g_t) S;  d = beta_t (v_t - S^T k_t)
              S <- S + k_t d^T; o_t = S^T q_t
          then per head o * rsqrt(mean(o^2) + eps) * w * silu(z) (a
          plain w) and out_proj.
  MoE     p = softmax(x Wr) over all PUBLISHED experts, float32; the
          `num_experts_per_tok` largest, their weights over the sum of
          the chosen (`norm_topk_prob`); a routed expert is (silu(x W1)
          * (x W3)) W2 of width `moe_intermediate_size`; one shared
          expert of width `shared_expert_intermediate_size`, scaled by
          sigmoid(x w_sg), is added.

The file states the chip's share: `experts_held` = [lo, hi) of the
published experts live here (`num_experts` = hi - lo), `vocab_size`
rows of the vocabulary. Program and reference alike route over all
published experts and add the HELD experts' part only.

The reference is the benchmark's own: plain `jax.numpy`, float32 at
"highest" matmul precision; the delta rule is the recurrence itself,
token by token in a `lax.scan`: no chunks, cache, kernel, batching or
program code; one expert's float32 copy at a time, one KV group and
512 queries at a time, so that it fits beside the served weights.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

QUERY_CHUNK = 512
L2_EPS = 1e-6
# What `scripts/chip_reference_check.py` plants in the reference, one
# at a time: each must leave the served logits by more than the
# tolerance.
CONTROLS = {
    "decay_ignored": {"no_decay": True},
    "attention_gate_left_out": {"no_attn_gate": True},
    "shared_expert_ungated": {"shared_ungated": True},
}


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


def _is_full(model: dict, l: int) -> bool:
    return (l + 1) % model["full_attention_interval"] == 0


def _rotary_dim(model: dict) -> int:
    return int(model["head_dim"] * model["partial_rotary_factor"])


def build_decoder(model: dict):
    """The decoder the server is given, from the configuration's own
    keys (Hugging Face names)."""
    import jax.numpy as jnp

    from defer_tpu.models.gpt import GptDecoder
    from defer_tpu.parallel.transformer_stack import TransformerConfig

    if model["decoder_sparse_step"] != 1 or model["mlp_only_layers"]:
        raise ValueError("the family computes an expert layer in every block")
    if model["use_sliding_window"] or model["rope_scaling"] is not None:
        raise ValueError("the family has no sliding window and no rope scaling")
    if not model["norm_topk_prob"] or model["hidden_act"] != "silu":
        raise ValueError("the family normalises the chosen weights and uses SiLU")
    if model["shared_expert_intermediate_size"] != model["moe_intermediate_size"]:
        raise ValueError("the shared expert has a routed expert's width")
    period = model["full_attention_interval"]
    return GptDecoder(
        TransformerConfig(
            num_layers=model["num_hidden_layers"],
            dim=model["hidden_size"],
            num_heads=model["num_attention_heads"],
            num_kv_heads=model["num_key_value_heads"],
            head_dim=model["head_dim"],
            ffn_dim=model["moe_intermediate_size"],
            vocab_size=model["vocab_size"],
            max_len=model["max_position_embeddings"],
            layer_norm_eps=model["rms_norm_eps"],
            norm_style="pre",
            causal=True,
            norm_type="rms",
            norm_offset=True,
            ffn_style="swiglu",
            use_bias=False,
            pos_style="rope",
            rope_theta=float(model["rope_theta"]),
            rotary_dim=_rotary_dim(model),
            qk_norm=True,
            attn_gate=True,
            untied_head=not model["tie_word_embeddings"],
            layer_kinds=("linear",) * (period - 1) + ((None, True),),
            gdn_k_heads=model["linear_num_key_heads"],
            gdn_v_heads=model["linear_num_value_heads"],
            gdn_k_dim=model["linear_key_head_dim"],
            gdn_v_dim=model["linear_value_head_dim"],
            gdn_conv=model["linear_conv_kernel_dim"],
            num_experts=_published_experts(model),
            experts_held=_held(model),
            moe_top_k=model["num_experts_per_tok"],
            moe_gate="softmax",
            expert_dim=model["moe_intermediate_size"],
            num_shared_experts=1,
            shared_combine="sum",
            shared_gate=True,
        ),
        # bf16 weights under float32 activations, every product to
        # float32's accuracy, K and V cached in bf16: see the
        # configuration's `assumed.activations`.
        compute_dtype=jnp.float32,
        matmul_precision="highest",
        cache_dtype=jnp.bfloat16,
    )


def make_params(dec, seed: int, mesh=None):
    """bf16 weights made on the device, in one jitted call, from the
    seed: the tree `dec.init` would build (shapes from
    `jax.eval_shape`: nothing float32 is allocated whole), each matrix
    normal * fan_in^-0.5, the embedding and the head normal * 0.02. A
    stacked leaf is drawn one matrix at a time (a layer's held experts
    are 0.8 GB in bf16), so the generator's temporaries stay one
    matrix's size. The published initialisation where the source
    states one: A_log = log(A), A uniform in (0, 16); dt_bias ones;
    norm scales 0 under (1 + w), 1 in the gated norm."""
    if mesh is not None:
        raise ValueError("family qwen3_next is served on one chip: mesh must be null")
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
        if name in ("gdn_norm_scale", "gdn_dt_bias"):
            return jnp.ones(shape, dtype)
        if name.endswith("_scale"):
            return jnp.zeros(shape, dtype)
        if name == "gdn_A_log":
            a = jax.random.uniform(key, shape, jnp.float32, 1e-3, 16.0)
            return jnp.log(a).astype(dtype)
        if name in ("token_embedding", "lm_head"):
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


def _f32(a):
    return a.astype(jnp.float32)


def _norm(x, w, eps):
    """RMS norm with a (1 + w) scale."""
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (1.0 + _f32(w))


def _rope_half(x, theta, rot):
    """x [T, H, Dh]: the first `rot` lanes rotated at positions 0..T-1,
    lane i with lane i + rot / 2 at frequency theta ** (-2i / rot); the
    other lanes pass."""
    t = x.shape[0]
    half = rot // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / rot)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :half], x[..., half:rot]
    return jnp.concatenate(
        [a * cos - b * sin, b * cos + a * sin, x[..., rot:]], axis=-1
    )


def _attention(model, n, p, no_attn_gate=False, all_lanes_rotate=False):
    """The gated attention mixer for n [T, D]: one KV group and
    QUERY_CHUNK queries at a time."""
    hq, hkv, dh = (
        model["num_attention_heads"], model["num_key_value_heads"], model["head_dim"],
    )
    eps = model["rms_norm_eps"]
    t = n.shape[0]
    qg = (n @ _f32(p["wq"])).reshape(t, hq, 2 * dh)
    q, gate = qg[..., :dh], qg[..., dh:]
    k = (n @ _f32(p["wk"])).reshape(t, hkv, dh)
    v = (n @ _f32(p["wv"])).reshape(t, hkv, dh)
    rot = dh if all_lanes_rotate else _rotary_dim(model)
    theta = float(model["rope_theta"])
    q = _rope_half(_norm(q, p["q_norm_scale"], eps), theta, rot)
    k = _rope_half(_norm(k, p["k_norm_scale"], eps), theta, rot)
    g = hq // hkv
    pad = -t % QUERY_CHUNK
    # [Hkv, chunks, Tc, g, Dh]: a KV group's queries, a chunk at a time.
    qs = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(-1, QUERY_CHUNK, hkv, g, dh)
    qs = qs.transpose(2, 0, 1, 3, 4)
    j = jnp.arange(t)[None, :]

    def group(args):
        qgr, kg, vg = args  # [chunks, Tc, g, Dh], [T, Dh], [T, Dh]

        def chunk(args):
            qc, q0 = args
            i = q0 + jnp.arange(QUERY_CHUNK)[:, None]
            scores = jnp.einsum("qgd,kd->gqk", qc, kg) * dh**-0.5
            scores = jnp.where((j <= i)[None], scores, -jnp.inf)
            return jnp.einsum("gqk,kd->qgd", jax.nn.softmax(scores, axis=-1), vg)

        return jax.lax.map(chunk, (qgr, jnp.arange(qgr.shape[0]) * QUERY_CHUNK))

    attn = jax.lax.map(group, (qs, k.transpose(1, 0, 2), v.transpose(1, 0, 2)))
    # [Hkv, chunks, Tc, g, Dh] -> [T, Hq, Dh]
    attn = attn.transpose(1, 2, 0, 3, 4).reshape(-1, hq, dh)[:t]
    if not no_attn_gate:
        attn = attn * jax.nn.sigmoid(gate)
    return attn.reshape(t, hq * dh) @ _f32(p["wo"])


def _delta_net(model, n, p, no_decay=False):
    """The Gated DeltaNet mixer for n [T, D]: the recurrence, a token
    at a time."""
    hk, hv = model["linear_num_key_heads"], model["linear_num_value_heads"]
    dk, dv = model["linear_key_head_dim"], model["linear_value_head_dim"]
    taps = model["linear_conv_kernel_dim"]
    r = hv // hk
    t = n.shape[0]
    qkvz = (n @ _f32(p["gdn_qkvz"])).reshape(t, hk, 2 * dk + 2 * r * dv)
    q, k = qkvz[..., :dk], qkvz[..., dk : 2 * dk]
    v = qkvz[..., 2 * dk : 2 * dk + r * dv]
    z = qkvz[..., 2 * dk + r * dv :].reshape(t, hv, dv)
    ba = (n @ _f32(p["gdn_ba"])).reshape(t, hk, 2 * r)
    beta = jax.nn.sigmoid(ba[..., :r].reshape(t, hv))
    g = -jnp.exp(_f32(p["gdn_A_log"])) * jax.nn.softplus(
        ba[..., r:].reshape(t, hv) + _f32(p["gdn_dt_bias"])
    )
    if no_decay:
        g = jnp.zeros_like(g)
    # u [T, C] after taps - 1 rows of zeros: y_t = sum_i w_i u_(t-taps+1+i).
    u = jnp.concatenate([a.reshape(t, -1) for a in (q, k, v)], axis=-1)
    rows = jnp.pad(u, ((taps - 1, 0), (0, 0)))
    w = _f32(p["gdn_conv"])
    y = jax.nn.silu(sum(rows[i : i + t] * w[i] for i in range(taps)))
    q = y[:, : hk * dk].reshape(t, hk, dk)
    k = y[:, hk * dk : 2 * hk * dk].reshape(t, hk, dk)
    v = y[:, 2 * hk * dk :].reshape(t, hv, dv)

    def unit(a):
        return a * jax.lax.rsqrt(jnp.sum(a * a, -1, keepdims=True) + L2_EPS)

    q = jnp.repeat(unit(q) * dk**-0.5, r, axis=1)  # [T, Hv, dk]
    k = jnp.repeat(unit(k), r, axis=1)

    def token(s, row):
        q_t, k_t, v_t, g_t, b_t = row
        s = s * jnp.exp(g_t)[:, None, None]
        d = b_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", s, k_t))
        s = s + k_t[:, :, None] * d[:, None, :]
        return s, jnp.einsum("hkv,hk->hv", s, q_t)

    _, o = jax.lax.scan(
        token, jnp.zeros((hv, dk, dv), jnp.float32), (q, k, v, g, beta)
    )
    o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + model["rms_norm_eps"])
    o = o * _f32(p["gdn_norm_scale"]) * jax.nn.silu(z)
    return o.reshape(t, hv * dv) @ _f32(p["gdn_out"])


def _swiglu(n, w1, w3, w2):
    return (jax.nn.silu(n @ _f32(w1)) * (n @ _f32(w3))) @ _f32(w2)


def _experts(model, n, p, shared_ungated=False):
    """The held experts' part of the routed sum plus the gated shared
    expert, for n [T, D]; one expert's float32 copy at a time."""
    lo, hi = _held(model)
    probs = jax.nn.softmax(n @ _f32(p["router"]), axis=-1)  # [T, E published]
    w, idx = jax.lax.top_k(probs, model["num_experts_per_tok"])
    w = w / jnp.sum(w, axis=-1, keepdims=True)

    def routed(acc, e):
        gate = jnp.sum(jnp.where(idx == lo + e, w, 0.0), axis=-1)  # [T]
        y = _swiglu(n, p["w1"][e], p["w3"][e], p["w2"][e])
        return acc + gate[:, None] * y, None

    out, _ = jax.lax.scan(routed, jnp.zeros_like(n), jnp.arange(hi - lo))
    shared = _swiglu(n, p["sw1"][0], p["sw3"][0], p["sw2"][0])
    if not shared_ungated:
        shared = shared * jax.nn.sigmoid(n @ _f32(p["sw_gate"]))
    return out + shared


def _layer(model, x, p, full, **faults):
    eps = model["rms_norm_eps"]
    n = _norm(x, p["ln1_scale"], eps)
    if full:
        x = x + _attention(
            model, n, p, faults.get("no_attn_gate", False),
            faults.get("all_lanes_rotate", False),
        )
    else:
        x = x + _delta_net(model, n, p, faults.get("no_decay", False))
    n = _norm(x, p["ln2_scale"], eps)
    return x + _experts(model, n, p, faults.get("shared_ungated", False))


# The leaves only a full layer has, stacked over the full layers; the
# `gdn_*` leaves are stacked over the linear layers; the rest over all.
_FULL_LEAVES = ("wq", "wk", "wv", "wo", "q_norm_scale", "k_norm_scale")


def layer_params(model: dict, stack: dict, l: int) -> dict:
    """Layer l's leaves out of a stack that is stacked by kind."""
    period = model["full_attention_interval"]
    at = {
        "full": l // period,
        "linear": l - l // period,
        "all": l,
    }
    full = _is_full(model, l)

    def group(name):
        if name.startswith("gdn_"):
            return "linear"
        return "full" if name in _FULL_LEAVES else "all"

    return {
        k: v[at[group(k)]]
        for k, v in stack.items()
        if group(k) in ("all", "full" if full else "linear")
    }


def reference_logits(model: dict, params: dict, ids, **faults) -> jax.Array:
    """Logits [T, V] of the full forward over ids [T], float32, for
    this chip's share. `faults` plants one for a control that must
    fail the comparison: `no_decay` (g = 0), `no_attn_gate` (the
    attention output is not gated), `shared_ungated` (the shared expert
    is added whole), `all_lanes_rotate` (rotary on every lane)."""
    layers = {
        full: jax.jit(lambda x, p, full=full: _layer(model, x, p, full, **faults))
        for full in (False, True)
    }
    with jax.default_matmul_precision("highest"):
        x = _f32(params["token_embedding"][jnp.asarray(ids)])
        for l in range(model["num_hidden_layers"]):
            x = layers[_is_full(model, l)](x, layer_params(model, params["stack"], l))
        head = jax.jit(
            lambda x, w, head: _norm(x, w, model["rms_norm_eps"]) @ _f32(head).T
        )
        return head(x, params["final_ln_scale"], params["lm_head"])


# -- the counts ---------------------------------------------------------------------


def _sizes(model: dict) -> dict:
    d = model["hidden_size"]
    hq, hkv, dh = (
        model["num_attention_heads"], model["num_key_value_heads"], model["head_dim"],
    )
    hk, hv = model["linear_num_key_heads"], model["linear_num_value_heads"]
    dk, dv = model["linear_key_head_dim"], model["linear_value_head_dim"]
    layers = model["num_hidden_layers"]
    full = layers // model["full_attention_interval"]
    channels = 2 * hk * dk + hv * dv
    return {
        "d": d, "hq": hq, "hkv": hkv, "dh": dh, "hv": hv, "dk": dk, "dv": dv,
        "layers": layers, "full": full, "linear": layers - full,
        "channels": channels, "taps": model["linear_conv_kernel_dim"],
        "expert": 3 * d * model["moe_intermediate_size"],
        # A full layer's q (with its gate), k, v and o; a linear
        # layer's two input projections, its convolution and out_proj.
        "attention": 2 * d * hq * dh + 2 * d * hkv * dh + hq * dh * d,
        "mixer": d * (2 * hk * dk + 2 * hv * dv) + d * 2 * hv
        + model["linear_conv_kernel_dim"] * channels + hv * dv * d,
    }


def decode_step_counts(model: dict, weight_bytes: int, depths) -> tuple[float, float]:
    """(bytes, operations): the least a decode step must read and
    compute with one live slot at each of `depths` cached rows.

    Bytes: every held weight once but the embedding (a step gathers a
    row a slot from it; the untied head is read whole), less the held
    experts no assignment could reach; the K and V rows of the FULL
    layers only; and per live slot and linear layer one read and one
    write of S and of the convolution's rows (float32).
    Operations: per live token two per weight of the mixers, the
    router, the shared expert with its gate, `num_experts_per_tok *
    held / published` routed experts (what uniform routing sends here)
    and the vocabulary slice; four per attended row, Q head and head
    lane; and per linear layer and value head 8 dk dv for the rule (as
    `gdn_step_counts`).

    Every held expert counts as read once whenever live slots x
    `num_experts_per_tok` reach the published count; with fewer, only
    the experts that many assignments could touch. That overstates the
    least by the share of held experts no token chose in a step, which
    `moe_experts_touched_share` reports."""
    z = _sizes(model)
    held = model["num_experts"]
    published = _published_experts(model)
    k = model["num_experts_per_tok"]
    live = len(depths)

    touched = held if live * k >= published else min(held, live * k)
    unread = z["layers"] * (held - touched) * z["expert"] * 2  # bf16
    embedding = model["vocab_size"] * z["d"] * 2
    rows = z["full"] * sum(depths)
    state = (z["hv"] * z["dk"] * z["dv"] + (z["taps"] - 1) * z["channels"]) * 4
    nbytes = (
        weight_bytes - embedding - unread
        + rows * 2 * z["hkv"] * z["dh"] * 2
        + live * z["linear"] * 2 * state
    )
    per_token = (
        z["full"] * z["attention"] + z["linear"] * z["mixer"]
        + z["layers"] * (
            z["d"] * published + z["expert"] + z["d"] + k * held / published * z["expert"]
        )
        + model["vocab_size"] * z["d"]
    )
    rule = z["linear"] * z["hv"] * 8 * z["dk"] * z["dv"]
    ops = (2 * per_token + rule) * live + 4 * z["hq"] * z["dh"] * rows
    return nbytes, ops


def gdn_step_counts(model: dict, slots: int) -> tuple[float, float]:
    """(bytes, operations) of ONE call of the `gdn_step` kernel: one
    linear layer's decode update for `slots` slots. Bytes: a read and a
    write of every slot's S in float32 (the vectors beside it are a
    thousandth of that and left out). Operations: per value head two
    products of S with a vector and one rank-one update with its
    decay, 2 dk dv each and 2 more for the decay: 8 dk dv."""
    z = _sizes(model)
    cells = slots * z["hv"] * z["dk"] * z["dv"]
    return 2.0 * cells * 4, 8.0 * cells
