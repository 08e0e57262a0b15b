"""A stack of window and full layers, a parallel block, a head size
that is not the quotient and an expert layer that is told which
experts it holds, served on the default paged path and held to the
plain reference of family `cohere2_moe`, at a toy of the same shape:
`head_dim` 16 where the quotient is 8, window 8, [s, s, s, f] twice,
16 experts top-2 with 4 held, 2 shared."""

import dataclasses
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from defer_tpu.models import gpt
from defer_tpu.obs import metrics as obs_metrics
from defer_tpu.obs import spans
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

TOY = {
    "family": "cohere2_moe", "attention_bias": False,
    "expert_selection_fn": "sigmoid", "first_k_dense_replace": 0,
    "head_dim": 16, "hidden_size": 64, "intermediate_size": 32,
    "layer_norm_eps": 1e-5,
    "layer_types": ["sliding_attention"] * 3 + ["full_attention"],
    "logit_scale": 1, "max_position_embeddings": 64,
    "num_attention_heads": 8, "num_experts": 4, "experts_held": [0, 4],
    "num_experts_per_tok": 2, "num_hidden_layers": 8,
    "num_key_value_heads": 2, "num_shared_experts": 2,
    "position_embedding_type": "rope_gptj", "rope_theta": 50000,
    "rotary_pct": 1, "shared_expert_combination_strategy": "average",
    "sliding_window": 8, "use_parallel_block": True, "use_qk_norm": False,
    "vocab_size": 128, "published": {"num_experts": 16},
}
PROMPT, STEPS = 13, 24  # 37 rows: every sliding layer's window binds
# float32 program against the float32 reference: summation order only
# (read 6e-7). bf16 program: every activation of every layer is
# rounded to bf16 where the reference rounds nothing (read 1.1e-2).
TOLERANCE = {jnp.float32: 1e-4, jnp.bfloat16: 3e-2}


def toy_decoder(dtype=jnp.float32, **changes):
    dec = family.build_decoder(TOY)
    cfg = dataclasses.replace(dec.cfg, **changes)
    return gpt.GptDecoder(cfg, compute_dtype=dtype)


@pytest.fixture(scope="module")
def params():
    return jax.tree.map(
        lambda a: a.astype(jnp.float32),
        family.make_params(family.build_decoder(TOY), 3),
    )


def served_rows(dec, params, prompt, steps):
    """The logits row each token was chosen from, the tokens and the
    server: prefill at admission, then decode steps through the pool
    (read as `scripts/chip_reference_check.py` reads them on the chip)."""
    srv = PagedDecodeServer(dec, params, num_blocks=40, block_size=4, max_batch=4)
    rows, toks = chip_check.served_rows(srv, jnp.asarray(prompt), steps)
    return rows, toks, srv


def distance(dec, params, seed=0):
    """max|d| / max|ref| of the served rows against the reference."""
    prompt = np.random.default_rng(seed).integers(1, 128, (1, PROMPT)).astype(np.int32)
    rows, toks, _ = served_rows(dec, params, prompt, STEPS)
    ids = np.concatenate([prompt[0], np.asarray(toks[:-1], np.int32)])
    ref = np.asarray(family.reference_logits(TOY, params, ids))[PROMPT - 1:]
    return float(np.max(np.abs(rows - ref)) / np.max(np.abs(ref)))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
def test_served_prefill_then_decode_past_the_window_is_the_reference(params, dtype):
    dec = toy_decoder(dtype)
    assert dec.cfg.dh == 16 != dec.cfg.dim // dec.cfg.num_heads
    p = dec.cast_params(params)
    assert distance(dec, p) <= TOLERANCE[dtype]


FULL = (None, False)
SLIDING = (8, True)
CONTROLS = {
    "window ignored": {"layer_kinds": ((None, True),) * 3 + (FULL,)},
    "rotate-half pairing": {"rope_pairing": "half"},
    "positions on the full layer": {"layer_kinds": (SLIDING,) * 3 + ((None, True),)},
    "softmax gate": {"moe_gate": "softmax"},
    "shared sum for mean": {"shared_combine": "sum"},
    "sequential for parallel block": {"parallel_block": False},
}


@pytest.mark.parametrize("name", CONTROLS)
def test_the_comparison_sees_each_mechanism(params, name):
    """The program with one mechanism computed another way must leave
    the reference by far more than the tolerance."""
    dec = toy_decoder(**CONTROLS[name])
    p = dict(params)
    if not dec.cfg.parallel_block:
        p["stack"] = dict(p["stack"], ln2_scale=p["stack"]["ln1_scale"])
    assert distance(dec, p) > 100 * TOLERANCE[jnp.float32]


def test_layer_kinds_on_a_dense_stack_serve_what_the_flat_decoder_generates():
    """Kinds without experts (the dense FFN's w1/w2/w3 are a layer's
    own, sliced by the scan): the paged path and the flat decoder's
    `generate` choose the same tokens past the window."""
    from defer_tpu.models.llama import mistral_config

    cfg = dataclasses.replace(
        mistral_config(num_layers=4, dim=64, num_heads=8, num_kv_heads=2,
                       ffn_dim=96, vocab_size=128, max_len=64, window=None),
        head_dim=16, layer_kinds=(SLIDING, FULL), rope_pairing="interleaved",
    )
    dec = gpt.GptDecoder(cfg, compute_dtype=jnp.float32)
    p = dec.init(jax.random.key(4))
    prompt = np.random.default_rng(4).integers(1, 128, (1, PROMPT)).astype(np.int32)
    _, toks, _ = served_rows(dec, p, prompt, STEPS)
    flat = dec.generate(p, jnp.asarray(prompt), STEPS)
    assert toks == np.asarray(flat)[0, PROMPT:].tolist()
    # ... and the window is seen: without it the tokens differ.
    wide = gpt.GptDecoder(
        dataclasses.replace(cfg, layer_kinds=((None, True), FULL)),
        compute_dtype=jnp.float32,
    )
    assert toks != np.asarray(wide.generate(p, jnp.asarray(prompt), STEPS))[0, PROMPT:].tolist()


# -- the expert layer -----------------------------------------------------------


def expert_layer(rng, n, held=(0, 16), **cfg_kw):
    """(cfg, one layer's leaves with all 16 experts, x [1, n, 64])."""
    cfg = dataclasses.replace(
        toy_decoder().cfg, experts_held=held, **cfg_kw
    )
    ks = jax.random.split(jax.random.key(rng), 8)
    shape = {"router": (64, 16), "w1": (16, 64, 32), "w3": (16, 64, 32),
             "w2": (16, 32, 64), "sw1": (2, 64, 32), "sw3": (2, 64, 32),
             "sw2": (2, 32, 64)}
    p = {
        k: jax.random.normal(ks[i], s, jnp.float32) * s[-2] ** -0.5
        for i, (k, s) in enumerate(shape.items())
    }
    x = jax.random.normal(ks[7], (1, n, 64), jnp.float32)
    return cfg, p, x


def share_of(p, lo, hi, shared=True):
    out = {k: v[lo:hi] if k in ("w1", "w3", "w2") else v for k, v in p.items()}
    if not shared:
        out = {k: v for k, v in out.items() if not k.startswith("sw")}
    return out


def test_the_shares_add_up_to_the_uncut_layer():
    """The routed parts of all four shares, plus what every chip
    computes alike (the shared experts) counted once, are the layer."""
    cfg, p, x = expert_layer(0, 40)
    whole, stats = ts.held_experts_ffn(p, x, cfg)
    assert stats.tolist() == [40 * 2, 16]  # every assignment is held here
    routed = []
    for lo in range(0, 16, 4):
        c = dataclasses.replace(cfg, experts_held=(lo, lo + 4))
        part, st = ts.held_experts_ffn(share_of(p, lo, lo + 4, shared=False), x, c)
        routed.append(part)
        both, _ = ts.held_experts_ffn(share_of(p, lo, lo + 4), x, c)
    shared_once = both - part
    np.testing.assert_allclose(
        sum(routed) + shared_once, whole, rtol=1e-5, atol=1e-5
    )
    # ... and the layer is the family's reference of it.
    model = dict(TOY, num_experts=16, experts_held=[0, 16])
    with jax.default_matmul_precision("highest"):
        ref = family._experts(model, x[0], p)
    np.testing.assert_allclose(whole[0], ref, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("n", [7, 256, 600])
def test_no_token_is_dropped_when_every_token_picks_one_held_expert(n):
    """Every token sends both its assignments to held experts 1 and 2:
    the layer computes all 2n of them, over several tiles where n
    passes one tile (600 is no whole number of tiles)."""
    cfg, p, x = expert_layer(1, n, held=(0, 4))
    x = jnp.abs(x)  # a positive input makes the planted columns win
    router = p["router"].at[:, 1].set(1.0).at[:, 2].set(0.9)
    p = dict(share_of(p, 0, 4), router=router)
    out, stats = ts.held_experts_ffn(p, x, cfg)
    assert stats.tolist() == [2 * n, 2]
    with jax.default_matmul_precision("highest"):
        ref = family._experts(dict(TOY), x[0], p)
    np.testing.assert_allclose(out[0], ref, rtol=1e-4, atol=1e-5)
    # The counters count live rows only.
    live = (jnp.arange(n) < 3)[None, :]
    assert ts.held_experts_ffn(p, x, cfg, live)[1].tolist() == [6, 2]


def test_layer_stacked_expert_leaves_are_indexed_by_layer():
    cfg, p, x = expert_layer(2, 9, held=(4, 8))
    p = share_of(p, 4, 8)
    stacked = {
        k: jnp.stack([jnp.zeros_like(v), v]) if k in ts.EXPERT_LEAVES else v
        for k, v in p.items()
    }
    a, sa = ts.held_experts_ffn(p, x, cfg)
    b, sb = ts.held_experts_ffn(stacked, x, cfg, None, 1)
    np.testing.assert_array_equal(a, b)
    assert sa.tolist() == sb.tolist()


def test_the_interleaved_pairing_is_the_references():
    x = jax.random.normal(jax.random.key(5), (1, 6, 4 * 16), jnp.float32)
    got = ts.apply_rope(x, 16, jnp.arange(6), 50000.0, "interleaved")
    ref = family._rope_interleaved(x[0].reshape(6, 4, 16), 50000.0)
    np.testing.assert_allclose(got[0], ref.reshape(6, 64), rtol=1e-6, atol=1e-6)
    half = ts.apply_rope(x, 16, jnp.arange(6), 50000.0)
    assert float(jnp.max(jnp.abs(half - got))) > 0.1


# -- prefill in pieces ----------------------------------------------------------


def shapes_in(jaxpr):
    """(shape, dtype) of every value of a jaxpr and those inside it."""
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            yield tuple(v.aval.shape), v.aval.dtype
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from shapes_in(sub)


def score_bytes(dec, t, s):
    """The most bytes of a float32 value with a [.., T', S] tail in
    the multi-token attention of one block over an `s`-row lane."""
    cfg = dec.cfg
    q = jnp.zeros((1, cfg.num_heads, t, cfg.dh), dec.compute_dtype)
    kv = jnp.zeros((1, cfg.kv_heads, t, cfg.dh), dec.compute_dtype)
    lane = jnp.zeros((1, cfg.kv_heads, s, cfg.dh), dec.compute_dtype)
    jaxpr = jax.make_jaxpr(
        lambda q, k, v, kc, vc: dec._attn_core(q, k, v, kc, vc, jnp.int32(0), q.dtype)
    )(q, kv, kv, lane, lane)
    return max(
        (int(np.prod(shape)) * 4 for shape, dtype in shapes_in(jaxpr.jaxpr)
         if dtype == jnp.float32 and len(shape) >= 2 and shape[-1] == s),
        default=0,
    )


def test_multi_token_attention_holds_no_scores_over_the_bound(monkeypatch, params):
    dec = toy_decoder()
    whole = score_bytes(dec, 32, 64)
    assert whole == 8 * 32 * 64 * 4  # [Hq, T, S] float32, all at once
    monkeypatch.setattr(gpt, "_SCORE_BYTES", whole // 4)
    # Rows over `_SOFTMAX_ROW` take their maximum behind a barrier.
    monkeypatch.setattr(gpt, "_SOFTMAX_ROW", 32)
    # One KV group's 4 heads and 16 of its 32 queries at a time.
    assert gpt._query_chunk(1, 4, 32, 64) == 16
    assert score_bytes(dec, 32, 64) == whole // 4
    # ... and the pieces are the whole: the served rows do not move.
    prompt = np.random.default_rng(1).integers(1, 128, (1, 32)).astype(np.int32)
    pieces = gpt.GptDecoder(dec.cfg, compute_dtype=jnp.float32)
    rows, toks, _ = served_rows(pieces, params, prompt, 2)
    monkeypatch.undo()
    rows0, toks0, _ = served_rows(toy_decoder(), params, prompt, 2)
    assert toks == toks0
    np.testing.assert_allclose(rows, rows0, rtol=1e-5, atol=1e-5)


def test_the_bound_leaves_a_dense_32_head_prefill_on_todays_path():
    # Mistral's 1024 bucket over its 4096-row lane is exactly the bound.
    assert 32 * 1024 * 4096 * 4 == gpt._SCORE_BYTES
    # 128 Q heads in 8 KV groups under an 8192-row table: a group's 16
    # heads take 1024 queries at a time, the 4096 bucket in four.
    assert gpt._query_chunk(1, 16, 1024, 8192) == 1024
    assert gpt._query_chunk(1, 16, 4096, 8192) == 1024
    assert gpt._query_chunk(1, 16, 8192, 8192) == 1024
    from defer_tpu.models.llama import mistral_config

    dense = gpt.GptDecoder(
        mistral_config(num_layers=1, dim=256, num_heads=32, num_kv_heads=8,
                       ffn_dim=64, vocab_size=64, max_len=64, window=64),
        compute_dtype=jnp.float32,
    )
    q = jnp.zeros((1, 32, 16, 8))
    kv = jnp.zeros((1, 8, 16, 8))
    lane = jnp.zeros((1, 8, 64, 8))
    jaxpr = jax.make_jaxpr(
        lambda q, k, v, kc, vc: dense._attn_core(q, k, v, kc, vc, jnp.int32(0), q.dtype)
    )(q, kv, kv, lane, lane)
    assert not [e for e in jaxpr.jaxpr.eqns if e.primitive.name in ("scan", "while")]


# -- every other path refuses the model by name ---------------------------------------


def _mesh():
    from defer_tpu.parallel.mesh import make_mesh

    return make_mesh({"model": 2}, jax.devices()[:2])


SERVER_OPTIONS = {
    "attention=blockwise": lambda: {"attention": "blockwise"},
    "attention=pallas": lambda: {"attention": "pallas"},
    "decode_window": lambda: {"decode_window": 2},
    "prefill_budget": lambda: {"prefill_budget": 8},
    "prefill_chunk": lambda: {"prefill_chunk": 8},
    "spec_k": lambda: {"spec_k": 2},
    "pp_stages": lambda: {"pp_stages": 2},
    "mesh": lambda: {"mesh": _mesh()},
    "kv_dtype=int8": lambda: {"kv_dtype": "int8"},
    "prefix_cache": lambda: {"prefix_cache": True},
    "prefix_ids": lambda: {"prefix_ids": jnp.ones((1, 4), jnp.int32)},
}
NAMES = r"layer kinds \(cfg\.layer_kinds\) and experts \(cfg\.num_experts\)"


@pytest.mark.parametrize("option", SERVER_OPTIONS)
def test_a_server_option_that_cannot_serve_the_model_says_so(params, option):
    with pytest.raises(ValueError, match=NAMES) as err:
        PagedDecodeServer(
            toy_decoder(), params, num_blocks=40, block_size=4, max_batch=4,
            **SERVER_OPTIONS[option](),
        )
    assert option.split("=")[0] in str(err.value)


def _rolling():
    gpt.GptDecoder(toy_decoder().cfg, rolling_cache=True)


def _make_draft(params):
    from defer_tpu.models.transplant import make_draft

    make_draft(toy_decoder(), params, layers=4)


def _from_hf():
    from defer_tpu.models.llama import from_hf_state_dict

    from_hf_state_dict(toy_decoder().cfg, {})


def _submit_prefilled(params):
    srv = PagedDecodeServer(
        toy_decoder(), params, num_blocks=40, block_size=4, max_batch=4
    )
    srv.submit_prefilled(np.ones((1, 4), np.int32), 2)


def _run_prefill(params):
    from defer_tpu.disagg.prefill_worker import run_prefill

    run_prefill(toy_decoder(), params, np.ones((1, 4), np.int32), block_size=4)


OTHER_PATHS = {
    "rolling_cache": (lambda params: _rolling(), "layer kinds"),
    "make_draft": (_make_draft, NAMES),
    "from_hf_state_dict": (lambda params: _from_hf(), NAMES),
    "disagg ingest": (_submit_prefilled, NAMES),
    "disagg prefill": (_run_prefill, NAMES),
}


@pytest.mark.parametrize("path", OTHER_PATHS)
def test_a_path_that_cannot_serve_the_model_says_so(params, path):
    call, says = OTHER_PATHS[path]
    with pytest.raises(ValueError, match=says) as err:
        call(params)
    assert path.split()[0] in str(err.value)


def test_the_training_block_and_gelu_experts_keep_to_their_side():
    with pytest.raises(ValueError, match="serving decoder"):
        ts.block_apply({}, jnp.zeros((1, 2, 64)), toy_decoder().cfg)
    cfg = dataclasses.replace(toy_decoder().cfg, ffn_style="gelu")
    with pytest.raises(ValueError, match="SwiGLU"):
        gpt.GptDecoder(cfg)
    for bad in ({"experts_held": (3, 17)}, {"layer_kinds": (SLIDING,) * 3},
                {"rope_pairing": "gptj"}, {"moe_gate": "top"}):
        with pytest.raises(ValueError):
            dataclasses.replace(toy_decoder().cfg, **bad)


# -- counters, span, and the family's count ---------------------------------------------


def moe_counters():
    got = obs_metrics.get_registry().to_dict()["counters"]
    return {k: v for k, v in got.items() if "moe_" in k or "window_masked" in k}


def test_the_host_adds_what_the_step_and_the_prefill_hand_back(params):
    before = moe_counters()
    prompt = np.random.default_rng(2).integers(1, 128, (1, PROMPT)).astype(np.int32)
    t_lo = time.perf_counter()
    _, toks, srv = served_rows(toy_decoder(), params, prompt, 5)
    moved = {k: v - before.get(k, 0) for k, v in moe_counters().items()}
    by = lambda name, phase: moved[  # noqa: E731
        f'defer_moe_{name}_total{{phase="{phase}",server="paged"}}'
    ]
    # One prefill and four decode steps of 8 expert layers each.
    assert by("layer_steps", "prefill") == 8 and by("layer_steps", "decode") == 32
    # A token's 2 assignments fall on the 4 held of 16 a quarter of the
    # time: at most 2 a live row a layer, and some in 13 rows x 8 layers.
    assert 0 < by("assignments_held", "prefill") <= 2 * PROMPT * 8
    assert 0 <= by("assignments_held", "decode") <= 2 * 32
    assert by("experts_touched", "decode") <= by("assignments_held", "decode")
    assert by("experts_touched", "prefill") <= 4 * 8
    # Rows behind the window of the 6 sliding layers: a slot at depth d
    # leaves d + 1 - 8 unread, d = 13..16 over the four ticks.
    assert moved['defer_kv_rows_window_masked_total{server="paged"}'] == 6 * sum(
        d + 1 - 8 for d in range(PROMPT, PROMPT + 4)
    )
    ticks = [r for r in spans.snapshot(t_lo).records if r.name == "paged.tick"]
    assert len(ticks) == 4 and all(r.counts["experts_held"] == 4 for r in ticks)


def test_decode_step_counts_are_the_shapes_worked_by_hand():
    with open(os.path.join(
        REPO, "perfbench", "configs", "command-a-plus-05-2026-ep8-l4.json"
    ), encoding="utf-8") as f:
        model = json.load(f)
    weights = 9_466_000_000
    # Two live slots, 100 and 5000 rows deep: three sliding layers
    # attend min(depth, 4096), the full layer every row.
    rows = 3 * (100 + 4096) + (100 + 5000)
    nbytes, ops = family.decode_step_counts(model, weights, (100, 5000))
    assert rows == 17688
    assert nbytes == weights + rows * 2 * 8 * 128 * 2 == weights + 72_450_048
    # A token: q and o 2 x 4096 x 16384, k and v 2 x 4096 x 1024, the
    # router 4096 x 128, 4 shared and 8 x 16 / 128 = 1 routed expert of
    # 3 x 4096 x 4096, in each of 4 layers; the slice's 32768 x 4096.
    per_token = 4 * (142_606_336 + 524_288 + 4 * 50_331_648 + 50_331_648) + 134_217_728
    assert per_token == 1_713_373_184
    assert ops == 2 * per_token * 2 + 4 * 128 * 128 * rows == 8_012_693_504
    # One live slot's 8 assignments can touch 8 of the 16 held experts:
    # the other 8 of each layer, 3 x 4096 x 4096 x 2 bytes each, stay unread.
    nbytes, _ = family.decode_step_counts(model, weights, (100,))
    assert nbytes == weights - 4 * 8 * 100_663_296 + 400 * 2 * 8 * 128 * 2
    # Sixteen slots and more reach every published expert: all are read.
    assert family.decode_step_counts(model, weights, (0,) * 16)[0] == weights
