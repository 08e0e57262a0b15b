"""A stack of recurrent (Gated DeltaNet) and gated-attention layers
served on the default paged path, a recurrent state a slot beside the
paged K/V pool, and held to the plain reference of family
`qwen3_next`, at a toy of the same shape: [linear, linear, linear,
full] twice, 2 key and 4 value heads of 8 in the linear mixer, 4 Q / 2
KV heads of 16 with 4 rotary lanes, 16 experts top-3 with 4 held and a
gated shared one, an untied head."""

import dataclasses
import json
import os
import time
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from defer_tpu.models import gpt
from defer_tpu.obs import metrics as obs_metrics
from defer_tpu.obs import spans
from defer_tpu.ops import gated_delta
from defer_tpu.parallel import transformer_stack as ts
from defer_tpu.runtime.paged import PagedDecodeServer
from perfbench import harness

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
family = harness.load_module(
    os.path.join(REPO, "perfbench", "families", "qwen3_next.py")
)
chip_check = harness.load_module(
    os.path.join(REPO, "scripts", "chip_reference_check.py")
)
CONFIG = os.path.join(
    REPO, "perfbench", "configs", "qwen3-next-80b-a3b-instruct-ep4-l8.json"
)

TOY = {
    "family": "qwen3_next", "decoder_sparse_step": 1,
    "full_attention_interval": 4, "head_dim": 16, "hidden_act": "silu",
    "hidden_size": 64, "intermediate_size": 160, "linear_conv_kernel_dim": 4,
    "linear_key_head_dim": 8, "linear_num_key_heads": 2,
    "linear_num_value_heads": 4, "linear_value_head_dim": 8,
    "max_position_embeddings": 256, "mlp_only_layers": [],
    "moe_intermediate_size": 32, "norm_topk_prob": True,
    "num_attention_heads": 4, "num_experts": 4, "experts_held": [0, 4],
    "num_experts_per_tok": 3, "num_hidden_layers": 8,
    "num_key_value_heads": 2, "partial_rotary_factor": 0.25,
    "rms_norm_eps": 1e-6, "rope_scaling": None, "rope_theta": 10000000,
    "shared_expert_intermediate_size": 32, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 128,
    "published": {"num_experts": 16},
}
# 75 rows pad to a bucket of 128: two chunks of the rule, the second
# ragged (11 real rows), and 53 rows of padding that must move no state.
PROMPT, STEPS = 75, 10
# float32 program against the float32 reference: summation order only,
# which six recurrent layers multiply (their output norm divides by the
# size of a sum that nearly cancels; read 7e-5).
TOLERANCE = 1e-3


def toy_decoder(dtype=jnp.float32, **changes):
    dec = family.build_decoder(TOY)
    cfg = dataclasses.replace(dec.cfg, **changes)
    return gpt.GptDecoder(cfg, compute_dtype=dtype)


@pytest.fixture(scope="module")
def params():
    """The family's weights in float32, every norm scale moved off its
    start so that (1 + w) and w differ."""

    def moved(path, a):
        name = str(path[-1].key)
        if not name.endswith("_scale"):
            return a.astype(jnp.float32)
        key = jax.random.key(zlib.crc32(name.encode()) % 1000)
        return a.astype(jnp.float32) + 0.3 * jax.random.normal(key, a.shape)

    return jax.tree_util.tree_map_with_path(
        moved, family.make_params(family.build_decoder(TOY), 3)
    )


def serve(dec, params, prompt, steps, **server):
    args = {"num_blocks": 80, "block_size": 4, "max_batch": 4, **server}
    srv = PagedDecodeServer(dec, params, **args)
    rows, toks = chip_check.served_rows(srv, jnp.asarray(prompt), steps)
    return rows, toks, srv


def prompt_of(seed, n=PROMPT):
    return np.random.default_rng(seed).integers(1, 128, (1, n)).astype(np.int32)


def distance(dec, params, seed=0, **faults):
    """max|d| / max|ref| of the served rows against the reference."""
    prompt = prompt_of(seed)
    rows, toks, _ = serve(dec, params, prompt, STEPS)
    ids = np.concatenate([prompt[0], np.asarray(toks[:-1], np.int32)])
    ref = np.asarray(family.reference_logits(TOY, params, ids, **faults))
    ref = ref[PROMPT - 1:]
    return float(np.max(np.abs(rows - ref)) / np.max(np.abs(ref)))


def test_served_prefill_then_decode_through_the_state_pool_is_the_reference(params):
    dec = toy_decoder()
    kinds = dec.cfg.layer_kinds
    assert kinds == ("linear",) * 3 + ((None, True),)
    assert dec.cfg.layers_of("linear") == 6 and dec.cfg.layers_of("attn") == 2
    assert distance(dec, params) <= TOLERANCE


def test_bf16_weights_under_float32_activations_are_the_reference(params):
    """The configuration as it is served: bf16 weights, float32
    activations at "highest" precision (on the CPU a float32 product is
    exact anyway; the chip's comparison holds the precision)."""
    dec = dataclasses.replace(toy_decoder(), matmul_precision="highest")
    p = jax.tree.map(lambda a: a.astype(jnp.bfloat16), params)
    assert distance(dec, p) <= TOLERANCE


def test_the_family_serves_what_its_configuration_states(params):
    """`build_decoder` as the benchmark calls it: float32 activations
    at "highest" precision over bf16 weights, the recurrent state in
    float32 and K and V cached in bf16, whose rounding is what is left
    (read 2.6e-3 at most over four seeds)."""
    dec = family.build_decoder(TOY)
    assert (dec.compute_dtype, dec.matmul_precision) == (jnp.float32, "highest")
    p = jax.tree.map(lambda a: a.astype(jnp.bfloat16), params)
    prompt = prompt_of(0)
    rows, toks, srv = serve(dec, p, prompt, STEPS)
    assert srv.pool_k.dtype == jnp.bfloat16
    assert {a.dtype for a in srv.pool_state} == {jnp.dtype(jnp.float32)}
    ids = np.concatenate([prompt[0], np.asarray(toks[:-1], np.int32)])
    ref = np.asarray(family.reference_logits(TOY, p, ids))[PROMPT - 1:]
    assert float(np.max(np.abs(rows - ref)) / np.max(np.abs(ref))) <= 1e-2


def test_a_bf16_step_runs_and_stays_nearer_the_reference_than_any_control(params):
    """bf16 activations: every dtype of the two pools and the carry is
    consistent, and the rows are the model's, if to bf16's accuracy,
    which these layers multiply: at toy widths a perturbation of 1e-3
    of a recurrent layer's input moves its output by up to 1.7e-2
    (`PERF.md`, PR 34), six times in a row."""
    dec = toy_decoder(jnp.bfloat16)
    p = dec.cast_params(params)
    prompt = prompt_of(0)
    rows, toks, srv = serve(dec, p, prompt, STEPS)
    assert srv.pool_state[0].dtype == jnp.float32
    assert srv.pool_state[1].dtype == jnp.bfloat16
    ids = np.concatenate([prompt[0], np.asarray(toks[:-1], np.int32)])

    def median_row(**faults):
        ref = np.asarray(family.reference_logits(TOY, p, ids, **faults))[PROMPT - 1:]
        return float(np.median(np.max(np.abs(rows - ref), -1)) / np.max(np.abs(ref)))

    served = median_row()
    assert served < 0.2
    for fault in family.CONTROLS.values():
        assert median_row(**fault) > 2 * served


CONTROLS = {
    # (what the program computes another way, what the reference does)
    "decay": ({}, {"no_decay": True}),
    "output gate": ({}, {"no_attn_gate": True}),
    "shared gate": ({"shared_gate": False}, {}),
    "partial rotary": ({"rotary_dim": None}, {}),
    "QK norm": ({"qk_norm": False}, {}),
    "(1 + w)": ({"norm_offset": False}, {}),
}


@pytest.mark.parametrize("name", CONTROLS)
def test_the_comparison_sees_each_mechanism(params, name):
    """With one mechanism computed another way, in the program or in
    the reference, the two part by far more than the tolerance."""
    changes, faults = CONTROLS[name]
    assert distance(toy_decoder(**changes), params, **faults) > 100 * TOLERANCE


def test_all_lanes_rotating_is_what_the_partial_rotary_control_computes(params):
    dec = toy_decoder(rotary_dim=None)
    assert distance(dec, params, all_lanes_rotate=True) <= TOLERANCE


def test_float32_activations_meet_a_bf16_weight_in_three_bf16_pieces():
    """`act_einsum`: float32's accuracy from one bf16 product of three
    stacked pieces, each rounded by `reduce_precision` (a cast to bf16
    and back XLA may keep in float32, and the lower pieces are then
    zero: on the chip that read 0.14 of the largest logit)."""
    h = jax.random.normal(jax.random.key(0), (5, 64), jnp.float32)
    w = jax.random.normal(jax.random.key(1), (64, 32)).astype(jnp.bfloat16)
    exact = np.asarray(h, np.float64) @ np.asarray(w.astype(jnp.float32), np.float64)
    got = jax.jit(lambda h, w: ts.act_einsum("nd,df->nf", h, w))(h, w)
    one_piece = h.astype(jnp.bfloat16).astype(jnp.float32) @ w.astype(jnp.float32)
    scale = np.max(np.abs(exact))
    assert np.max(np.abs(got - exact)) / scale < 1e-6
    assert np.max(np.abs(one_piece - exact)) / scale > 1e-3
    jaxpr = str(jax.make_jaxpr(lambda h, w: ts.act_einsum("nd,df->nf", h, w))(h, w))
    assert jaxpr.count("reduce_precision") == 3
    # Any other pair of dtypes is the plain product.
    for a, b in ((h, w.astype(jnp.float32)), (h.astype(jnp.bfloat16), w)):
        np.testing.assert_array_equal(
            np.asarray(ts.act_einsum("nd,df->nf", a, b), np.float32),
            np.asarray(a @ b.astype(a.dtype), np.float32),
        )


# -- the rule's forms -------------------------------------------------------


def gdn_recurrent(q, k, v, g, beta, s0):
    """q, k [B, T, H, dk], v [B, T, H, dv], g, beta [B, T, H], s0
    [B, H, dk, dv], all float32 -> (o [B, T, H, dv], S after row T)."""

    def step(s, row):
        q_t, k_t, v_t, g_t, b_t = row
        s = s * jnp.exp(g_t)[..., None, None]
        d = b_t[..., None] * (
            v_t - jnp.einsum("bhkv,bhk->bhv", s, k_t, precision="highest")
        )
        s = s + k_t[..., :, None] * d[..., None, :]
        return s, jnp.einsum("bhkv,bhk->bhv", s, q_t, precision="highest")

    rows = jax.tree.map(lambda a: jnp.moveaxis(a, 1, 0), (q, k, v, g, beta))
    s, o = lax.scan(step, s0, rows)
    return jnp.moveaxis(o, 0, 1), s


def rule_inputs(seed, b=2, t=150, hk=2, hv=4, dk=16, dv=8):
    ks = jax.random.split(jax.random.key(seed), 7)

    def unit(a):
        return a * jax.lax.rsqrt(jnp.sum(a * a, -1, keepdims=True) + 1e-6)

    q = unit(jax.random.normal(ks[0], (b, t, hk, dk))) * dk**-0.5
    k = unit(jax.random.normal(ks[1], (b, t, hk, dk)))
    v = jax.random.normal(ks[2], (b, t, hv, dv))
    a = jax.random.uniform(ks[3], (hv,), minval=0.01, maxval=16.0)
    g = -a * jax.nn.softplus(jax.random.normal(ks[4], (b, t, hv)) + 1.0)
    beta = jax.nn.sigmoid(jax.random.normal(ks[5], (b, t, hv)))
    s0 = jax.random.normal(ks[6], (b, hv, dk, dv))
    return q, k, v, g, beta, s0


def test_the_chunked_rule_is_the_recurrence():
    q, k, v, g, beta, s0 = rule_inputs(0)
    qq, kk = (jnp.repeat(a, 2, axis=2) for a in (q, k))
    o, s = gdn_recurrent(qq, kk, v, g, beta, s0)
    # 150 rows: two whole chunks and a ragged third, from a state that
    # is not zero.
    o2, s2 = jax.jit(gated_delta.gdn_chunked)(qq, kk, v, g, beta, s0)
    np.testing.assert_allclose(o2, o, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(s2, s, rtol=1e-4, atol=1e-5)
    # Rows with g = 0 and beta = 0 (a bucket's padding) move nothing:
    # the state is what the 100 real rows left.
    real = (jnp.arange(150) < 100)[None, :, None]
    _, s3 = gated_delta.gdn_chunked(
        qq, kk, v, jnp.where(real, g, 0.0), jnp.where(real, beta, 0.0), s0
    )
    _, s4 = gdn_recurrent(
        *(a[:, :100] for a in (qq, kk, v, g, beta)), s0
    )
    np.testing.assert_allclose(s3, s4, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("mode", [None, "interpret"], ids=["xla", "kernel"])
def test_the_decode_step_is_one_row_of_the_recurrence_at_one_layer_of_the_pool(mode):
    q, k, v, g, beta, s0 = rule_inputs(1, b=3, t=1, hk=4, hv=8, dk=128, dv=128)
    pool = jnp.stack([s0 + 1.0, s0, s0 * 2.0])
    row = (a[:, 0] for a in (q, k, v, g, beta))
    o, new = gated_delta.gdn_step(pool, jnp.int32(1), *row, mode)
    qq, kk = (jnp.repeat(a, 2, axis=2) for a in (q, k))
    o_ref, s_ref = gdn_recurrent(qq, kk, v, g, beta, s0)
    np.testing.assert_allclose(o, o_ref[:, 0], rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(new[1], s_ref, rtol=1e-5, atol=1e-6)
    # The other layers' states are not touched.
    np.testing.assert_array_equal(new[0], pool[0])
    np.testing.assert_array_equal(new[2], pool[2])


def test_the_kernel_is_the_step_the_server_runs_where_pallas_is_on(monkeypatch, params):
    """The served rows with the `gdn_step` kernel interpreted (and
    `flash_decode` with it) are the plain-XLA rows."""
    prompt = prompt_of(2, 21)
    rows, toks, _ = serve(toy_decoder(), params, prompt, 6)
    monkeypatch.setenv("DEFER_TPU_PALLAS_INTERPRET", "1")
    kernel = gpt.GptDecoder(toy_decoder().cfg, compute_dtype=jnp.float32)
    rows_k, toks_k, _ = serve(kernel, params, prompt, 6)
    assert toks_k == toks
    np.testing.assert_allclose(rows_k, rows, rtol=1e-4, atol=1e-5)


# -- slots and their states --------------------------------------------------------------


def run_all(srv, requests):
    out = {}
    srv.on_token = lambda rid, tok, done: out.setdefault(rid, []).append(tok)
    rids = [srv.submit(jnp.asarray(p), n) for p, n in requests]
    srv.run()
    return [out[r] for r in rids]


def test_neighbouring_slots_leave_each_others_state_alone(params):
    """Two requests of different lengths, seated side by side and
    decoded in one batch, each get the tokens they get alone."""
    dec = toy_decoder()
    a, b = (prompt_of(3, 19), 9), (prompt_of(4, 70), 14)
    both = run_all(
        PagedDecodeServer(dec, params, num_blocks=80, block_size=4, max_batch=2),
        [a, b],
    )
    alone = [
        run_all(
            PagedDecodeServer(dec, params, num_blocks=80, block_size=4, max_batch=2),
            [r],
        )[0]
        for r in (a, b)
    ]
    assert both == alone


def test_a_reused_slot_serves_its_second_request_as_a_fresh_server_would(params):
    """`_finish` clears no state: admission overwrites the slot's row
    of both pools, so what the first request left changes nothing."""
    dec = toy_decoder()
    first, second = (prompt_of(5, 66), 12), (prompt_of(6, 23), 12)
    srv = PagedDecodeServer(dec, params, num_blocks=80, block_size=4, max_batch=1)
    got = run_all(srv, [first, second])
    fresh = run_all(
        PagedDecodeServer(dec, params, num_blocks=80, block_size=4, max_batch=1),
        [second],
    )
    assert got[1] == fresh[0]
    # The one slot did hold another request's state in between.
    assert float(jnp.max(jnp.abs(srv.pool_state[0]))) > 0


# -- the shares add up ------------------------------------------------------------------------


def test_the_four_shares_add_up_to_the_uncut_layer():
    """The routed parts of the four EP4 shares, plus what every chip
    computes alike (the gated shared expert) counted once, are the
    uncut reference layer."""
    cfg = dataclasses.replace(toy_decoder().cfg, experts_held=(0, 16))
    ks = jax.random.split(jax.random.key(0), 9)
    shape = {"router": (64, 16), "w1": (16, 64, 32), "w3": (16, 64, 32),
             "w2": (16, 32, 64), "sw1": (1, 64, 32), "sw3": (1, 64, 32),
             "sw2": (1, 32, 64), "sw_gate": (64, 1)}
    p = {
        k: jax.random.normal(ks[i], s, jnp.float32) * s[-2] ** -0.5
        for i, (k, s) in enumerate(shape.items())
    }
    x = jax.random.normal(ks[8], (1, 40, 64), jnp.float32)
    routed = []
    for lo in range(0, 16, 4):
        c = dataclasses.replace(cfg, experts_held=(lo, lo + 4))
        share = {
            k: v[lo:lo + 4] if k in ("w1", "w3", "w2") else v for k, v in p.items()
        }
        both, stats = ts.held_experts_ffn(share, x, c)
        alone = {k: v for k, v in share.items() if not k.startswith("sw")}
        part, _ = ts.held_experts_ffn(
            alone, x, dataclasses.replace(c, num_shared_experts=0, shared_gate=False)
        )
        routed.append(part)
        assert 0 < stats[0] <= 40 * 3 and stats[1] <= 4
    shared_once = both - part
    model = dict(TOY, num_experts=16, experts_held=[0, 16])
    with jax.default_matmul_precision("highest"):
        ref = family._experts(model, x[0], p)
        ungated = family._experts(model, x[0], p, shared_ungated=True)
    np.testing.assert_allclose(
        (sum(routed) + shared_once)[0], ref, rtol=1e-4, atol=1e-5
    )
    assert float(jnp.max(jnp.abs(ungated - ref))) > 0.1


# -- every other path refuses the model by name ----------------------------------------------


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
NAMES = r"recurrent layers \(cfg\.layer_kinds 'linear'\)"


@pytest.mark.parametrize("option", SERVER_OPTIONS)
def test_a_server_option_that_cannot_serve_a_recurrent_layer_says_so(params, option):
    with pytest.raises(ValueError, match=NAMES) as err:
        PagedDecodeServer(
            toy_decoder(), params, num_blocks=40, block_size=4, max_batch=4,
            **SERVER_OPTIONS[option](),
        )
    assert option.split("=")[0] in str(err.value)


def _make_draft(params):
    from defer_tpu.models.transplant import make_draft

    make_draft(toy_decoder(), params, layers=4)


def _from_hf(params):
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


def _flat_server(params):
    from defer_tpu.runtime.decode_server import DecodeServer

    DecodeServer(toy_decoder(), params)


OTHER_PATHS = {
    "make_draft": _make_draft,
    "from_hf_state_dict": _from_hf,
    "disagg ingest": _submit_prefilled,
    "disagg prefill": _run_prefill,
    "DecodeServer": _flat_server,
    "stage_params": lambda params: toy_decoder().stage_params(params, 0, 4),
    "rolling_cache": lambda params: gpt.GptDecoder(
        toy_decoder().cfg, rolling_cache=True
    ),
}


@pytest.mark.parametrize("path", OTHER_PATHS)
def test_a_path_that_cannot_serve_a_recurrent_layer_says_so(params, path):
    says = "layer kinds" if path == "rolling_cache" else "recurrent layers"
    with pytest.raises(ValueError, match=says) as err:
        OTHER_PATHS[path](params)
    assert path.split()[0] in str(err.value)


def test_a_linear_kind_needs_its_sizes():
    cfg = toy_decoder().cfg
    for bad in ({"gdn_k_heads": 0}, {"gdn_v_heads": 3}, {"gdn_conv": 1},
                {"rotary_dim": 5}, {"rotary_dim": 32},
                {"norm_offset": True, "norm_type": "layer"}):
        with pytest.raises(ValueError):
            dataclasses.replace(cfg, **bad)


# -- spans, counters, gauges and the family's counts -------------------------------------------


def registry():
    kinds = obs_metrics.get_registry().to_dict()
    return {**kinds["counters"], **kinds["gauges"]}


def test_the_state_pool_has_its_span_its_counts_and_its_gauges(params):
    before = registry()
    t_lo = time.perf_counter()
    srv = PagedDecodeServer(
        toy_decoder(), params, num_blocks=80, block_size=4, max_batch=4
    )
    state_bytes = 6 * 4 * (4 * 8 * 8 * 4 + 3 * 64 * 4)  # S and 3 rows, float32
    assert srv.state_bytes == state_bytes
    assert registry()['defer_linear_state_pool_bytes{server="paged"}'] == state_bytes
    # The K/V pool's layer axis counts the two full layers.
    assert srv.pool_k.shape == (2, 80, 2, 4, 16)
    srv.submit(jnp.asarray(prompt_of(7)), 5)
    srv._admit()
    live = 'defer_linear_state_slots_live{server="paged"}'
    assert registry()[live] == 1
    while any(s is not None for s in srv.slots):
        srv._tick()
    assert registry()[live] == 0
    # The bucket's 128 rows are two chunks in each of six layers.
    chunks = 'defer_linear_prefill_chunks_total{server="paged"}'
    assert registry()[chunks] - before.get(chunks, 0) == 2 * 6
    records = spans.snapshot(t_lo).records
    (seat,) = [r for r in records if r.name == "paged.admit.seat"]
    (state,) = [r for r in records if r.name == "paged.admit.seat.state"]
    assert state.counts["state_bytes"] == state_bytes // 4
    assert seat.t0 <= state.t0 and state.t1 <= seat.t1
    ticks = [r for r in records if r.name == "paged.tick"]
    assert len(ticks) == 4 and all(r.counts["state_slots"] == 1 for r in ticks)
    assert all(r.counts["experts_held"] == 4 for r in ticks)


def test_the_named_scopes_of_the_mixer_and_the_gate_are_in_the_step():
    dec = toy_decoder()
    shapes = jax.eval_shape(dec.init, jax.random.key(0))
    cache = jax.eval_shape(lambda: dec.init_cache(1))
    text = jax.jit(dec._step_fn()).lower(
        shapes, cache, jax.ShapeDtypeStruct((1, 1), jnp.int32)
    ).as_text(debug_info=True)
    for scope in ("gdn_proj", "gdn_conv", "gdn_rule", "gdn_out", "attn_gate",
                  "moe_router", "moe_experts", "moe_shared"):
        assert scope in text, scope


def test_decode_step_counts_are_the_shapes_worked_by_hand():
    with open(CONFIG, encoding="utf-8") as f:
        model = json.load(f)
    # A linear layer but its experts: 2048 x 12288 + 2048 x 64 + 4 x
    # 8192 + 4096 x 2048; a full layer: 2048 x 8192, twice 2048 x 512
    # and 4096 x 2048; a block's router, shared expert and its gate.
    mixer, attention = 33_718_272, 27_262_976
    assert mixer == 2048 * 12288 + 2048 * 64 + 4 * 8192 + 4096 * 2048
    assert attention == 2048 * 8192 + 2 * 2048 * 512 + 4096 * 2048
    expert = 3 * 2048 * 512
    block = 2048 * 512 + expert + 2048
    weights = 2 * (
        6 * mixer + 2 * attention + 8 * (block + 128 * expert)
        + 2 * 37984 * 2048
    )
    # Two live slots, 100 and 3000 rows deep: K and V of the two full
    # layers only, and each slot's six states read and written.
    rows = 2 * (100 + 3000)
    state = (32 * 128 * 128 + 3 * 8192) * 4
    nbytes, ops = family.decode_step_counts(model, weights, (100, 3000))
    # Two slots' 20 assignments reach 20 of a layer's 128 held experts.
    assert nbytes == (
        weights - 37984 * 2048 * 2 - 8 * 108 * expert * 2
        + rows * 2 * 2 * 256 * 2 + 2 * 6 * 2 * state
    )
    per_token = (
        6 * mixer + 2 * attention + 8 * (block + 10 * 128 / 512 * expert)
        + 37984 * 2048
    )
    assert ops == 2 * (
        2 * per_token + 6 * 32 * 8 * 128 * 128
    ) + 4 * 16 * 256 * rows
    # 52 slots' 520 assignments could reach every published expert.
    full = family.decode_step_counts(model, weights, (0,) * 52)[0]
    assert full == weights - 37984 * 2048 * 2 + 52 * 6 * 2 * state
    # The kernel: one layer's states of every slot, read and written.
    assert family.gdn_step_counts(model, 128) == (
        2 * 128 * 32 * 128 * 128 * 4, 8 * 128 * 32 * 128 * 128
    )
