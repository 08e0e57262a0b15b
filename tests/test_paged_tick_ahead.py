"""The plain tick's launch ahead (`PagedDecodeServer._tick_plain`): a
tick dispatches the next step before it reads the tokens of the step
in flight. Greedy tokens of four families against their plain float32
references, with admissions and slots ending between ticks; the rows
a request that ended by eos left in flight; the slots that make a tick
settle first; and how often the launch ahead engages."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from test_cohere2_moe import TOY as COHERE2_MOE
from test_gigachat35 import TOY as GIGACHAT35
from test_qwen3_next import TOY as QWEN3_NEXT

from defer_tpu.constrain import compile_regex
from defer_tpu.models import gpt
from defer_tpu.models.gpt import SamplingParams, tiny_gpt
from defer_tpu.obs import metrics as obs_metrics
from defer_tpu.obs import spans
from defer_tpu.runtime.paged import PagedDecodeServer
from perfbench import harness

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MISTRAL = {
    "family": "mistral", "hidden_size": 64, "intermediate_size": 128,
    "num_attention_heads": 4, "num_key_value_heads": 2,
    "num_hidden_layers": 2, "vocab_size": 128,
    "max_position_embeddings": 128, "sliding_window": 128,
    "rope_theta": 10000.0, "rms_norm_eps": 1e-5,
}
TOYS = {
    "mistral": MISTRAL, "cohere2_moe": COHERE2_MOE,
    "qwen3_next": QWEN3_NEXT, "gigachat35": GIGACHAT35,
}
AHEAD = 'defer_tick_ahead_total{server="paged"}'
DISCARDED = 'defer_tick_ahead_rows_discarded_total{server="paged"}'
TICKS = 'defer_decode_ticks_total{server="paged"}'


def registry() -> dict:
    return obs_metrics.get_registry().to_dict()["counters"]


def moved(before: dict, name: str) -> float:
    return registry()[name] - before.get(name, 0)


def prompts(seed, lengths, vocab):
    rng = np.random.default_rng(seed)
    return [
        jnp.asarray(rng.integers(1, vocab, (1, n)), jnp.int32) for n in lengths
    ]


def drive(srv, requests, later=()):
    """Serve the way the benchmark's loop does: `_admit`, then `_tick`
    while a slot is live. The requests of `later` are submitted one
    before each tick. Returns each request's streamed tokens, in the
    order given, and how many requests were seated while a step was
    in flight."""
    out = {}
    srv.on_token = lambda rid, tok, done: out.setdefault(rid, []).append(tok)
    rids = [srv.submit(p, n) for p, n in requests]
    later = list(later)
    seated_in_flight = 0
    while srv.pending or later or any(s is not None for s in srv.slots):
        if later:
            rids.append(srv.submit(*later.pop(0)))
        waiting = len(srv.pending)
        in_flight = srv._inflight is not None
        srv._admit()
        seated_in_flight += in_flight * (waiting - len(srv.pending))
        if any(s is not None for s in srv.slots):
            srv._tick()
    srv.settle()
    return [out[r] for r in rids], seated_in_flight


@pytest.fixture(scope="module", params=list(TOYS))
def toy(request):
    model = TOYS[request.param]
    family = harness.load_module(
        os.path.join(REPO, "perfbench", "families", model["family"] + ".py")
    )
    built = family.build_decoder(model)
    dec = gpt.GptDecoder(built.cfg, compute_dtype=jnp.float32)
    params = jax.tree.map(
        lambda a: a.astype(jnp.float32), family.make_params(built, 3)
    )
    return model, family, dec, params


def test_greedy_tokens_are_the_references_with_slots_coming_and_going(toy):
    """Nine requests through three slots, answers of 2 to 6 tokens so
    that a slot ends by its count on most ticks, three of them
    submitted between ticks: every stream is the float32 reference's
    greedy one, token for token, and requests were seated while a step
    was in flight (for the recurrent-state and latent pools, an
    admission's insert queued behind the step launched ahead)."""
    model, family, dec, params = toy
    vocab = model["vocab_size"]
    lengths = [5, 12, 7, 16, 9, 6, 14, 8, 11]
    steps = [4, 2, 6, 3, 5, 2, 6, 3, 4]
    reqs = list(zip(prompts(5, lengths, vocab), steps))
    before = registry()
    srv = PagedDecodeServer(dec, params, num_blocks=48, block_size=4, max_batch=3)
    got, seated_in_flight = drive(srv, reqs[:6], later=reqs[6:])
    assert seated_in_flight >= 3
    assert moved(before, AHEAD) > 0
    for (prompt, n), toks in zip(reqs, got):
        assert len(toks) == n
        ids = np.concatenate([np.asarray(prompt)[0], np.asarray(toks[:-1], np.int32)])
        ref = np.asarray(family.reference_logits(model, params, ids))
        assert toks == ref[prompt.shape[1] - 1:].argmax(-1).tolist()
    assert srv.blocks_in_use == 0 and srv._inflight is None


@pytest.fixture(scope="module")
def tiny():
    dec = tiny_gpt(64)
    return dec, dec.init(jax.random.key(0))


def test_an_eos_row_launched_ahead_is_dropped_and_its_blocks_serve_the_next(tiny):
    """A request that ends by eos had a row in the step launched ahead
    of the one that made its eos: that row is dropped and counted, and
    the next request, seated in the same slot on the blocks it freed
    while that step is still in flight, gets the tokens it gets alone."""
    dec, params = tiny
    a, b = prompts(7, [6, 9], 64)
    free_run = np.asarray(dec.generate(params, a, 12))[0, 6:].tolist()
    # The first token that appears in the answer only from its third
    # place on ends it there, with steps to spare.
    eos = next(
        t for j, t in enumerate(free_run) if 2 <= j < 10 and t not in free_run[:j]
    )
    kw = dict(num_blocks=12, block_size=4, max_batch=1, eos_id=eos)
    srv = PagedDecodeServer(dec, params, **kw)
    before = registry()
    rid_a = srv.submit(a, 12)
    srv._admit()
    blocks_a = list(srv.slots[0]["blocks"])
    while srv.slots[0] is not None:
        srv._tick()
    assert srv._inflight is not None  # the row launched ahead for `a`
    rid_b = srv.submit(b, 8)
    srv._admit()
    assert set(srv.slots[0]["blocks"]) & set(blocks_a)
    while srv.slots[0] is not None:
        srv._tick()
    srv.settle()
    assert moved(before, DISCARDED) >= 1
    out_a = np.asarray(srv.done[rid_a])[0, 6:].tolist()
    assert out_a == free_run[: free_run.index(eos) + 1]
    alone = PagedDecodeServer(dec, params, **kw)
    rid = alone.submit(b, 8)
    np.testing.assert_array_equal(
        np.asarray(srv.done[rid_b]), np.asarray(alone.run()[rid])
    )


def settled_ticks(t_lo) -> list:
    """The plain ticks since `t_lo` that settled a step left in flight."""
    records = spans.snapshot(t_lo).records
    ticks = {r.id: r for r in records if r.name == "paged.tick"}
    return [
        ticks[r.parent] for r in records
        if r.name == "paged.settle" and r.parent in ticks
    ]


@pytest.mark.parametrize("kind", ["sampling", "constrained", "seat"])
def test_a_slot_the_launch_ahead_cannot_serve_settles_first(tiny, kind):
    """Two greedy requests decode ahead; a third arrives between ticks
    that samples, follows a grammar, or (under a prefill budget) takes
    a seat mid-prefill. What follows settles the step in flight before
    it runs, no step goes ahead beside that slot, and every stream is
    what it is alone."""
    dec, params = tiny
    a, b, c = prompts(11, [5, 9, 4], 64)
    server, submit = {}, {}
    if kind == "sampling":
        submit["sampling"] = SamplingParams(temperature=0.9, top_k=20, seed=3)
    if kind == "constrained":
        vocab = [""] + [str(i % 10) if i < 40 else f"<u{i}>" for i in range(1, 128)]
        server = {"eos_id": 0, "constraints": {"digits": compile_regex("[0-9]+", vocab)}}
        submit["sampling"] = SamplingParams(constraint="digits")
    if kind == "seat":
        server = {"prefill_budget": 4}
    kw = dict(num_blocks=32, block_size=4, max_batch=3, **server)

    def alone(prompt, steps, **extra):
        srv = PagedDecodeServer(dec, params, **kw)
        rid = srv.submit(prompt, steps, **extra)
        return np.asarray(srv.run()[rid])

    srv = PagedDecodeServer(dec, params, **kw)
    rids = [srv.submit(a, 12), srv.submit(b, 12)]
    srv._admit()
    while srv._seat_slots():
        srv._tick()
    srv._tick()
    srv._tick()
    assert srv._inflight is not None
    t_lo = spans.snapshot().records[-1].t1
    rids.append(srv.submit(c, 6, **submit))
    srv._admit()
    if kind == "seat":
        assert srv._inflight is None  # the seat's admission settled
    srv._tick()
    if kind != "seat":
        assert [t.counts.get("ahead") for t in settled_ticks(t_lo)] == [0]
        # Beside the third request no step goes ahead (a seat that
        # has flipped decodes greedily, and goes ahead again).
        while srv.slots[2] is not None:
            assert srv._inflight is None
            srv._admit()
            srv._tick()
    srv.run()
    for rid, (prompt, steps, extra) in zip(
        rids, [(a, 12, {}), (b, 12, {}), (c, 6, submit)]
    ):
        np.testing.assert_array_equal(
            np.asarray(srv.done[rid]), alone(prompt, steps, **extra),
            err_msg=f"{kind}: request {rid}",
        )


def test_nearly_every_step_of_a_steady_batch_goes_ahead(tiny):
    """Four slots that stay full: every step after the first is
    launched before the step ahead of it is read."""
    dec, params = tiny
    reqs = [(p, 20) for p in prompts(13, [4, 6, 8, 5], 64)]
    srv = PagedDecodeServer(dec, params, num_blocks=40, block_size=4, max_batch=4)
    before = registry()
    got, _ = drive(srv, reqs)
    assert [len(t) for t in got] == [20] * 4
    ticks = moved(before, TICKS)
    assert ticks == 19 and moved(before, AHEAD) / ticks >= 0.9
    assert moved(before, DISCARDED) == 0
