"""Mixed-mode continuous batching: `PagedDecodeServer(prefill_budget=N)`
fuses admission prefill into the decode dispatch (runtime/schedule.py
plans it, runtime/paged.py::_tick_mixed runs it), and nothing the user
can observe moves — outputs are token-identical to the stall path
(prefill_budget=None) across attention modes, prefix caching, fused
windows, tensor parallelism, sampling, eos and stop sequences.

The perf claim in miniature, pinned by counters because a parity test
alone can't see it: while a prompt prefills, every live decode slot
still advances exactly one token per tick, and the stall counter
(`defer_prefill_stall_ticks_total` — admission-prefill dispatches
issued with decode slots live) stays at zero in mixed mode.

Also here: the admission-queue deque pin (pop-from-head must be O(1),
not a list pop(0) that scans the tail of a deep backlog) and the
strict `_submit_t` ledger contract on both servers — a rid without a
submit timestamp is a loud KeyError, never a silently-zero queue
wait, and the ledger drains empty when serving completes (ttft pops
at the drain point, so ttft spans queue + prefill).
"""

import collections

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from defer_tpu.models.gpt import SamplingParams, tiny_gpt
from defer_tpu.parallel.mesh import make_mesh
from defer_tpu.runtime.decode_server import DecodeServer
from defer_tpu.runtime.paged import PagedDecodeServer, serve_paged
from defer_tpu.runtime.schedule import (
    PrefillSeat,
    plan_mixed_tick,
    pow2_bucket,
)


@pytest.fixture(scope="module")
def model():
    dec = tiny_gpt(64)
    params = dec.init(jax.random.key(0))
    return dec, params


def _requests(vocab):
    """Shared prefix on the first two (radix hits under prefix_cache),
    one prompt long enough to span several budgeted chunks."""
    rng = np.random.default_rng(3)
    base = jnp.asarray(rng.integers(1, vocab, size=(1, 6)), jnp.int32)
    ext = jnp.asarray(rng.integers(1, vocab, size=(1, 4)), jnp.int32)
    return [
        (base, 7),
        (jnp.concatenate([base, ext], axis=1), 5),
        (jnp.asarray(rng.integers(1, vocab, size=(1, 11)), jnp.int32), 6),
    ]


def _assert_identical(a, b):
    for i, (x, y) in enumerate(zip(a, b)):
        assert x.shape == y.shape and bool(jnp.all(x == y)), (
            i,
            np.asarray(x),
            np.asarray(y),
        )


# -- host-side planner -------------------------------------------------


def test_seat_chunk_progress():
    seat = PrefillSeat(rid=1, tokens=np.arange(5), base=8, keep_from=0)
    assert (seat.remaining, seat.pos, seat.finished) == (5, 8, False)
    assert list(seat.take(3)) == [0, 1, 2]
    assert (seat.remaining, seat.pos) == (2, 11)
    assert list(seat.take(2)) == [3, 4]
    assert seat.finished
    with pytest.raises(ValueError):
        seat.take(1)


def test_seat_rejects_empty_suffix():
    with pytest.raises(ValueError, match="at least one token"):
        PrefillSeat(rid=1, tokens=np.zeros((0,)), base=0, keep_from=0)


def test_plan_respects_budget_chunk_cap_and_t_limit():
    # Budget rations across seats in admission order.
    t, ns = plan_mixed_tick([10, 10], budget=6, chunk_cap=8, t_limit=8)
    assert ns == [6, 0] and t == 8  # pow2 bucket of 6
    # chunk_cap bounds any single seat's slice.
    t, ns = plan_mixed_tick([10, 10], budget=8, chunk_cap=3, t_limit=8)
    assert ns == [3, 3] and t == 4
    # t_limit clamps the bucketed T (lane-clamp invariant).
    t, ns = plan_mixed_tick([10], budget=8, chunk_cap=8, t_limit=5)
    assert ns == [5] and t == 5
    # No seats: decode rows still ride at T=1.
    t, ns = plan_mixed_tick([], budget=4, chunk_cap=4, t_limit=4)
    assert ns == [] and t == 1


def test_pow2_bucket():
    assert [pow2_bucket(n, 16) for n in (1, 2, 3, 5, 9, 17)] == [
        1, 2, 4, 8, 16, 16,
    ]


# -- token identity vs the stall path ----------------------------------

# attention x prefix_cache x decode_window; every mixed tick body
# composition appears at least once without the full product.
MATRIX = [
    ("gathered", False, 1),
    ("gathered", True, 1),
    ("gathered", False, 8),
    ("gathered", True, 8),
    ("blockwise", False, 1),
    ("blockwise", True, 8),
]


@pytest.mark.parametrize("attention,prefix_cache,window", MATRIX)
def test_mixed_token_identity(model, attention, prefix_cache, window):
    dec, params = model
    reqs = _requests(dec.cfg.vocab_size)
    kw = dict(
        num_blocks=32,
        block_size=4,
        max_batch=2,
        attention=attention,
        prefix_cache=prefix_cache,
        decode_window=window,
    )
    base, _ = serve_paged(dec, params, reqs, **kw)
    mixed, stats = serve_paged(
        dec, params, reqs, prefill_budget=4, **kw
    )
    _assert_identical(base, mixed)
    assert stats["mixed_ticks"] > 0
    assert stats["prefill_stall_ticks"] == 0
    assert stats["decode_stall_fraction"] == 0.0


def test_mixed_token_identity_tp2(model):
    dec, params = model
    reqs = _requests(dec.cfg.vocab_size)
    base, _ = serve_paged(
        dec, params, reqs, num_blocks=32, block_size=4, max_batch=2
    )
    mesh = make_mesh({"model": 2}, jax.devices()[:2])
    mixed, _ = serve_paged(
        dec,
        params,
        reqs,
        num_blocks=32,
        block_size=4,
        max_batch=2,
        prefill_budget=4,
        mesh=mesh,
    )
    _assert_identical(base, mixed)


def test_mixed_sampled_eos_stop_parity(model):
    """Seeded sampling, eos, and stop sequences on decode rows all
    fire identically while other seats are mid-prefill: the sampler's
    key stream is decode-row-driven, so budgeted prefill chunks must
    not consume draws."""
    dec, params = model
    rng = np.random.default_rng(7)
    prompts = [
        jnp.asarray(rng.integers(1, 64, size=(1, n)), jnp.int32)
        for n in (5, 9, 13, 4)
    ]
    steps = [12, 10, 8, 6]
    samp = [
        SamplingParams(temperature=0.8, top_k=8, seed=11),
        None,
        SamplingParams(temperature=1.0, top_p=0.9, seed=3),
        None,
    ]
    stops = [None, [[2, 5]], None, [[1]]]

    def run(budget):
        srv = PagedDecodeServer(
            dec,
            params,
            num_blocks=48,
            block_size=4,
            max_batch=2,
            eos_id=9,
            prefill_budget=budget,
        )
        rids = [
            srv.submit(p, s, sampling=sp, stop=st)
            for p, s, sp, st in zip(prompts, steps, samp, stops)
        ]
        while srv.pending or any(s is not None for s in srv.slots):
            srv._admit()
            if any(s is not None for s in srv.slots):
                srv._tick()
        return [srv.done[r] for r in rids]

    _assert_identical(run(None), run(3))


# -- the stall-free claim, pinned by counters --------------------------


def test_decode_never_skips_a_tick_while_prompt_prefills(model):
    """The tentpole claim: with a budget set, a decoding slot emits
    exactly one token on EVERY tick a prompt spends prefilling — no
    tick is surrendered to admission — and the stall counter stays 0."""
    dec, params = model
    rng = np.random.default_rng(5)
    srv = PagedDecodeServer(
        dec,
        params,
        num_blocks=48,
        block_size=4,
        max_batch=2,
        prefill_budget=2,
    )
    short = jnp.asarray(rng.integers(1, 64, size=(1, 4)), jnp.int32)
    long = jnp.asarray(rng.integers(1, 64, size=(1, 17)), jnp.int32)
    srv.submit(short, 32)
    srv._admit()
    # Run the first request's own admission prefill out (no decode
    # slot is live yet, so these ticks cannot stall anyone).
    while any(s is not None and "prefill" in s for s in srv.slots):
        srv._tick()
    (i0,) = [
        i for i, s in enumerate(srv.slots) if s is not None
    ]
    # A long prompt arrives mid-decode: with budget=2 its 17-token
    # suffix spans many ticks, every one of which must also advance
    # the decoding slot.
    srv.submit(long, 4)
    srv._admit()
    assert any(
        s is not None and "prefill" in s for s in srv.slots
    ), "long prompt should be seated mid-prefill"
    prefill_ticks = 0
    while any(s is not None and "prefill" in s for s in srv.slots):
        before = len(srv.slots[i0]["out"])
        srv._tick()
        assert len(srv.slots[i0]["out"]) == before + 1, (
            "decode slot skipped a tick while the prompt prefilled"
        )
        prefill_ticks += 1
    assert prefill_ticks >= 3  # the claim exercised, not vacuous
    assert srv.prefill_stall_ticks_n == 0
    assert srv.decode_stall_fraction_last == 0.0
    assert srv.mixed_prefill_tokens_n >= long.shape[1]
    # Drain; the ledger empties (strict-ttft drain contract below).
    while srv.pending or any(s is not None for s in srv.slots):
        srv._admit()
        if any(s is not None for s in srv.slots):
            srv._tick()
    assert srv._submit_t == {}


def test_stall_path_counts_stalls(model):
    """The baseline the budget removes: stall-mode admission of a
    prompt while a slot decodes increments the stall counters."""
    dec, params = model
    rng = np.random.default_rng(5)
    srv = PagedDecodeServer(
        dec, params, num_blocks=48, block_size=4, max_batch=2
    )
    srv.submit(
        jnp.asarray(rng.integers(1, 64, size=(1, 4)), jnp.int32), 16
    )
    srv._admit()
    srv._tick()
    srv.submit(
        jnp.asarray(rng.integers(1, 64, size=(1, 12)), jnp.int32), 4
    )
    srv._admit()  # stall-path prefill with a live decode slot
    assert srv.prefill_stall_ticks_n >= 1
    assert srv.decode_stall_fraction_last > 0.0


# -- construction contract ---------------------------------------------


def test_budget_rejects_speculation(model):
    dec, params = model
    with pytest.raises(ValueError, match="prefill_budget=None server"):
        PagedDecodeServer(
            dec,
            params,
            num_blocks=16,
            block_size=4,
            prefill_budget=8,
            spec_k=2,
            spec_draft=dec,
            spec_params=params,
        )


def test_budget_rejects_pipeline_stages(model):
    dec, params = model
    with pytest.raises(ValueError, match="pp_stages=1"):
        PagedDecodeServer(
            dec,
            params,
            num_blocks=16,
            block_size=4,
            prefill_budget=8,
            pp_stages=2,
        )


def test_budget_validation(model):
    dec, params = model
    with pytest.raises(ValueError, match="prefill_budget"):
        PagedDecodeServer(
            dec, params, num_blocks=16, block_size=4, prefill_budget=0
        )
    with pytest.raises(ValueError, match="prefill_lookahead"):
        PagedDecodeServer(
            dec,
            params,
            num_blocks=16,
            block_size=4,
            prefill_budget=4,
            prefill_lookahead=0,
        )


# -- admission queue + strict _submit_t ledger -------------------------


def test_pending_queues_are_deques(model):
    """Depth-scaling pin: admission pops the head once per freed seat,
    so the queue must be a deque (O(1) popleft) on BOTH servers — a
    list's pop(0) scans the whole tail of a deep backlog on every
    admission."""
    dec, params = model
    paged = PagedDecodeServer(dec, params, num_blocks=16, block_size=4)
    flat = DecodeServer(dec, params, max_batch=2)
    assert isinstance(paged.pending, collections.deque)
    assert isinstance(flat.pending, collections.deque)
    # Deep backlog drains head-first in submission order.
    rng = np.random.default_rng(0)
    prompts = [
        jnp.asarray(rng.integers(1, 64, size=(1, 3)), jnp.int32)
        for _ in range(64)
    ]
    rids = [paged.submit(p, 1) for p in prompts]
    seen = [paged.pending.popleft()[0] for _ in range(64)]
    assert seen == rids


@pytest.mark.parametrize("server", ["paged", "flat"])
def test_unknown_rid_is_loud(model, server):
    """A pending entry without a submit timestamp must raise at
    admission, not observe a silently-zero queue wait."""
    dec, params = model
    prompt = jnp.asarray([[3, 9, 27]], jnp.int32)
    if server == "paged":
        srv = PagedDecodeServer(
            dec, params, num_blocks=16, block_size=4, max_batch=1
        )
        srv.pending.append((999, prompt, 2, 0, None, None, 0))
        with pytest.raises(KeyError):
            srv._admit()
    else:
        srv = DecodeServer(dec, params, max_batch=1)
        srv.pending.append((999, prompt, 2, 0, None, None, 0))
        with pytest.raises(KeyError):
            srv._admit()


@pytest.mark.parametrize("budget", [None, 4])
def test_submit_ledger_drains_and_ttft_spans_queue(model, budget):
    """On both admit paths (stall and mixed): the ledger is empty once
    serving completes (every rid's timestamp popped exactly once, at
    first token), and each request's ttft >= its queue wait because
    ttft additionally spans the prefill."""
    from defer_tpu.obs import reset as obs_reset

    obs_reset()  # global registry: drop other tests' observations
    dec, params = model
    reqs = _requests(dec.cfg.vocab_size)
    srv = PagedDecodeServer(
        dec,
        params,
        num_blocks=32,
        block_size=4,
        max_batch=2,
        prefill_budget=budget,
    )
    rids = [srv.submit(p, s) for p, s in reqs]
    while srv.pending or any(s is not None for s in srv.slots):
        srv._admit()
        if any(s is not None for s in srv.slots):
            srv._tick()
    assert sorted(srv.done) == sorted(rids)
    assert srv._submit_t == {}
    reg = srv.obs.registry
    lab = {"server": "paged"}
    ttft = reg.value("defer_ttft_seconds", **lab)
    qw = reg.value("defer_queue_wait_seconds", **lab)
    assert ttft["count"] == len(reqs) == qw["count"]
    assert ttft["sum"] >= qw["sum"]


def test_submit_prefilled_bypasses_budget(model):
    """submit_prefilled ships landed KV — there is no prefill to
    budget, so prefilled admissions take a slot immediately even on a
    budgeted server and never touch the stall counters."""
    dec, params = model
    prompt = jnp.asarray([[3, 9, 27, 4]], jnp.int32)
    mono = PagedDecodeServer(
        dec, params, num_blocks=32, block_size=4, max_batch=1
    )
    r0 = mono.submit(prompt, 5)
    mono._admit()
    while any(s is not None for s in mono.slots):
        mono._tick()
    expect = mono.done[r0]

    from defer_tpu.disagg.prefill_worker import run_prefill

    srv = PagedDecodeServer(
        dec,
        params,
        num_blocks=32,
        block_size=4,
        max_batch=1,
        prefill_budget=2,
    )
    rid = srv.submit_prefilled(prompt, 5)
    k_blocks, v_blocks, logits_row = run_prefill(
        dec, params, np.asarray(prompt), block_size=4
    )
    srv.deliver_kv(rid, k_blocks, v_blocks, logits_row)
    srv._admit()
    assert any(
        s is not None and "prefill" not in s for s in srv.slots
    ), "prefilled admission must seat as a decoding slot immediately"
    while any(s is not None for s in srv.slots):
        srv._tick()
    _assert_identical([expect], [srv.done[rid]])
    assert srv.mixed_prefill_tokens_n == 0
