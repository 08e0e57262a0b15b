"""Family `mistral`: how a configuration file of this family becomes
the system under test, and the family's plain reference.

The reference is the benchmark's own: Mistral's forward pass as
published (RMSNorm, rotary positions in the rotate-half pairing,
grouped-query attention under a causal sliding-window mask, SwiGLU,
output head tied to the embedding as the configuration states) in
plain `jax.numpy` and float32 at "highest" matmul precision, one
layer's float32 copy at a time cast from the served bf16 leaves. No
cache, no kernel, no batching, and no code of the program.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def build_decoder(model: dict):
    """The decoder the server is given, from the configuration's own
    keys (Hugging Face names)."""
    from defer_tpu.models.gpt import GptDecoder
    from defer_tpu.models.llama import mistral_config

    return GptDecoder(
        mistral_config(
            num_layers=model["num_hidden_layers"],
            dim=model["hidden_size"],
            num_heads=model["num_attention_heads"],
            num_kv_heads=model["num_key_value_heads"],
            ffn_dim=model["intermediate_size"],
            vocab_size=model["vocab_size"],
            max_len=model["max_position_embeddings"],
            rope_theta=model["rope_theta"],
            eps=model["rms_norm_eps"],
            window=model["sliding_window"],
        )
    )


def make_params(dec, seed: int, mesh=None):
    """bf16 weights made on the device, in one jitted call, from the
    seed: the tree `dec.init` would build (its shapes come from
    `jax.eval_shape`, so nothing float32 is ever allocated), each leaf
    normal * fan_in^-0.5, the embedding normal * 0.02, norm scales 1
    (dec.init's own scales). Stacked leaves are drawn a layer at a time
    so that the generator's temporaries stay a layer's size. With a
    mesh each leaf is created split over its last axis that divides,
    so no chip ever holds a whole copy; the server's own placement
    then moves what it wants elsewhere."""
    shapes = jax.eval_shape(dec.init, jax.random.key(0))
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    dtype = jnp.bfloat16

    def one(key, path, shape):
        name = str(path[-1].key)
        if name.endswith("_scale"):
            return jnp.ones(shape, dtype)
        if name == "token_embedding":
            return (jax.random.normal(key, shape, jnp.float32) * 0.02).astype(dtype)
        scale = shape[-2] ** -0.5
        if len(shape) < 3:
            return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)
        return jax.lax.map(
            lambda k: (
                jax.random.normal(k, shape[1:], jnp.float32) * scale
            ).astype(dtype),
            jax.random.split(key, shape[0]),
        )

    def build(key):
        return jax.tree_util.tree_unflatten(
            treedef,
            [
                one(jax.random.fold_in(key, i), path, s.shape)
                for i, (path, s) in enumerate(leaves)
            ],
        )

    out_shardings = None
    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec

        n = mesh.devices.size
        axes = tuple(mesh.axis_names)

        def split(s):
            spec = [None] * len(s.shape)
            for ax in reversed(range(len(s.shape))):
                if s.shape[ax] % n == 0 and s.shape[ax] >= n:
                    spec[ax] = axes
                    break
            return NamedSharding(mesh, PartitionSpec(*spec))

        out_shardings = jax.tree_util.tree_unflatten(
            treedef, [split(s) for _, s in leaves]
        )
    key = jax.random.key(seed, impl="rbg")
    return jax.jit(build, out_shardings=out_shardings)(key)


# -- the plain reference ----------------------------------------------------


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """x [T, H, Dh]; rotate-half pairing at positions 0..T-1."""
    t, _, dh = x.shape
    half = dh // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / dh)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _layer(model, x, p):
    """One block on x [T, D] in float32; p is the layer's leaves."""
    hq = model["num_attention_heads"]
    hkv = model["num_key_value_heads"]
    dh = model["hidden_size"] // hq
    eps = model["rms_norm_eps"]
    t = x.shape[0]
    h = _rms_norm(x, p["ln1_scale"], eps)
    q = _rope((h @ p["wq"]).reshape(t, hq, dh), model["rope_theta"])
    k = _rope((h @ p["wk"]).reshape(t, hkv, dh), model["rope_theta"])
    v = (h @ p["wv"]).reshape(t, hkv, dh)
    k = jnp.repeat(k, hq // hkv, axis=1)
    v = jnp.repeat(v, hq // hkv, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(jnp.float32(dh))
    i = jnp.arange(t)[:, None]
    j = jnp.arange(t)[None, :]
    mask = (j <= i) & (j > i - model["sliding_window"])
    scores = jnp.where(mask[None], scores, -jnp.inf)
    attn = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)
    x = x + attn.reshape(t, hq * dh) @ p["wo"]
    h2 = _rms_norm(x, p["ln2_scale"], eps)
    return x + (jax.nn.silu(h2 @ p["w1"]) * (h2 @ p["w3"])) @ p["w2"]


def reference_logits(model: dict, params: dict, ids) -> jax.Array:
    """Logits [T, V] of the full forward over ids [T], float32."""
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    layer = jax.jit(lambda x, p: _layer(model, x, jax.tree.map(f32, p)))
    with jax.default_matmul_precision("highest"):
        emb = f32(params["token_embedding"])
        x = emb[jnp.asarray(ids)]
        for l in range(model["num_hidden_layers"]):
            x = layer(x, {k: v[l] for k, v in params["stack"].items()})
        x = _rms_norm(x, f32(params["final_ln_scale"]), model["rms_norm_eps"])
        return x @ emb.T
