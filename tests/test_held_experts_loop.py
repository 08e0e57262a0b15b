"""`held_experts_ffn` as one loop over sorted assignments: a program
whose size does not grow with the experts held, and the results of the
form it replaced (one `fori_loop` per held expert, kept here as the
test's own oracle) to the bit, at the Command A+ toy shapes of
`tests/test_cohere2_moe.py`."""

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from defer_tpu.models import gpt
from defer_tpu.obs import metrics as obs_metrics
from defer_tpu.parallel import transformer_stack as ts
from defer_tpu.runtime.paged import PagedDecodeServer
from perfbench import harness

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
family = harness.load_module(
    os.path.join(REPO, "perfbench", "families", "cohere2_moe.py")
)
chip_check = harness.load_module(
    os.path.join(REPO, "scripts", "chip_reference_check.py")
)
cohere = harness.load_module(os.path.join(REPO, "tests", "test_cohere2_moe.py"))


def unrolled_held_experts_ffn(p, x, cfg, live=None, layer=None):
    """The parent's form: a stable argsort and a `fori_loop` over
    tiles for each held expert in turn, unrolled in Python."""
    dt = x.dtype
    b, t, d = x.shape
    n = b * t
    xf = x.reshape(n, d)
    lo, hi = cfg.held
    eh = hi - lo
    logits = jnp.dot(
        xf.astype(jnp.float32), p["router"].astype(jnp.float32),
        precision=lax.Precision.HIGHEST,
    )
    scores = (
        jax.nn.sigmoid(logits) if cfg.moe_gate == "sigmoid"
        else jax.nn.softmax(logits, axis=-1)
    )
    w, idx = lax.top_k(scores, cfg.moe_top_k)
    if cfg.moe_top_k > 1:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    sel = jax.nn.one_hot(idx - lo, eh, dtype=jnp.float32)
    gate = (sel * w[..., None]).sum(axis=1)
    chosen = gate > 0
    counted = chosen if live is None else chosen & live.reshape(n, 1)
    stats = jnp.stack([counted.sum(), counted.any(axis=0).sum()]).astype(jnp.int32)

    def weight(name, e=None):
        at = tuple(i for i in (layer, e) if i is not None)
        return p[name][at].astype(dt)

    def swiglu(rows, e):
        h = jax.nn.silu(rows @ weight("w1", e)) * (rows @ weight("w3", e))
        return h @ weight("w2", e)

    tile = min(n, ts._EXPERT_TILE)
    out = jnp.zeros((n, d), jnp.float32)
    for e in range(eh):
        mine = chosen[:, e]
        count = mine.sum()
        order = jnp.argsort(~mine, stable=True)
        if n % tile:
            order = jnp.pad(order, (0, tile - n % tile))

        def one_tile(i, out, e=e, order=order, count=count):
            rows = lax.dynamic_slice_in_dim(order, i * tile, tile)
            y = swiglu(xf[rows], e)
            wt = jnp.where(i * tile + jnp.arange(tile) < count, gate[rows, e], 0.0)
            return out.at[rows].add(y.astype(jnp.float32) * wt[:, None])

        out = lax.fori_loop(0, -(-count // tile), one_tile, out)
    if "sw1" in p:
        ys = jnp.einsum(
            "snf,sfd->nd",
            jax.nn.silu(jnp.einsum("nd,sdf->snf", xf, weight("sw1")))
            * jnp.einsum("nd,sdf->snf", xf, weight("sw3")),
            weight("sw2"), preferred_element_type=jnp.float32,
        )
        if cfg.shared_combine == "mean":
            ys = ys / cfg.num_shared_experts
        out = out + ys
    return out.astype(dt).reshape(b, t, d), stats


def count_eqns(jaxpr) -> int:
    return sum(
        1 + sum(count_eqns(sub) for sub in jax.core.jaxprs_in_params(e.params))
        for e in jaxpr.eqns
    )


def layer_of(held: int, n: int = 40, dtype=jnp.float32):
    """A layer with `held` of 128 experts held, and its input."""
    cfg = dataclasses.replace(
        cohere.toy_decoder().cfg, num_experts=128, experts_held=(0, held)
    )
    ks = jax.random.split(jax.random.key(held), 8)
    shape = {"router": (64, 128), "w1": (held, 64, 32), "w3": (held, 64, 32),
             "w2": (held, 32, 64), "sw1": (2, 64, 32), "sw3": (2, 64, 32),
             "sw2": (2, 32, 64)}
    p = {
        k: (jax.random.normal(ks[i], s, jnp.float32) * s[-2] ** -0.5).astype(dtype)
        for i, (k, s) in enumerate(shape.items())
    }
    return cfg, p, jax.random.normal(ks[7], (1, n, 64), jnp.float32).astype(dtype)


def test_the_program_does_not_grow_with_the_experts_held():
    sizes = {}
    for held in (8, 64):
        cfg, p, x = layer_of(held)
        new = jax.make_jaxpr(lambda p, x: ts.held_experts_ffn(p, x, cfg))(p, x)
        old = jax.make_jaxpr(lambda p, x: unrolled_held_experts_ffn(p, x, cfg))(p, x)
        sizes[held] = count_eqns(new.jaxpr), count_eqns(old.jaxpr)
    assert sizes[8][0] == sizes[64][0]
    # ... where the form it replaced grew by a loop an expert.
    assert sizes[64][1] > sizes[8][1] + 56 * 10


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("n", [7, 32, 600])
def test_one_layer_is_the_unrolled_form_to_the_bit(n, dtype):
    """A decode step's rows (one tile an expert), and a prefill's over
    several tiles where an expert's count passes one (600 rows, 16 of
    128 held, top-8: a held expert sees 37 of them; the planted router
    column sends every row to expert 1, which then takes three)."""
    cfg, p, x = layer_of(16, n, dtype)
    p = dict(p, router=p["router"].at[:, 1].add(jnp.asarray(4.0, dtype)))
    x = jnp.abs(x)
    live = (jnp.arange(n) % 3 != 0)[None, :]
    new, s_new = jax.jit(lambda p, x: ts.held_experts_ffn(p, x, cfg, live))(p, x)
    old, s_old = jax.jit(lambda p, x: unrolled_held_experts_ffn(p, x, cfg, live))(p, x)
    np.testing.assert_array_equal(np.asarray(new, np.float32), np.asarray(old, np.float32))
    assert s_new.tolist() == s_old.tolist()
    assert s_new[0] >= (2 * n) // 3  # expert 1's live rows at the least


def moe_counters():
    got = obs_metrics.get_registry().to_dict()["counters"]
    return {k: v for k, v in got.items() if "defer_moe_" in k}


def served(dec, params):
    before = moe_counters()
    prompt = np.random.default_rng(7).integers(1, 128, (1, cohere.PROMPT)).astype(np.int32)
    srv = PagedDecodeServer(dec, params, num_blocks=40, block_size=4, max_batch=4)
    rows, toks = chip_check.served_rows(srv, jnp.asarray(prompt), cohere.STEPS)
    return rows, toks, {k: v - before.get(k, 0) for k, v in moe_counters().items()}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
def test_served_tokens_logits_and_counters_are_the_unrolled_forms(monkeypatch, dtype):
    """The Command A+ toy through the paged server, prefill then decode
    past the window: tokens, every logits row and the `moe_*` counters
    are what the parent's layer gives, to the bit."""
    params = jax.tree.map(
        lambda a: a.astype(jnp.float32),
        family.make_params(family.build_decoder(cohere.TOY), 3),
    )
    dec = cohere.toy_decoder(dtype)
    p = dec.cast_params(params)
    rows, toks, moved = served(dec, p)
    monkeypatch.setattr(gpt, "held_experts_ffn", unrolled_held_experts_ffn)
    # A decoder of its own: the compiled steps are memoised on it.
    oracle = gpt.GptDecoder(dec.cfg, compute_dtype=dtype)
    rows0, toks0, moved0 = served(oracle, p)
    assert toks == toks0
    np.testing.assert_array_equal(rows, rows0)
    assert moved == moved0 and any(moved.values())
