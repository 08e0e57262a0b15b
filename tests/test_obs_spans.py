"""The span log (`defer_tpu/obs/spans.py`) and the spans the paged
server writes into it on its default path."""

import collections
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from defer_tpu import obs
from defer_tpu.models.gpt import tiny_gpt
from defer_tpu.obs import spans
from defer_tpu.runtime.paged import PagedDecodeServer

TICK_PHASES = ["plan", "dispatch", "sample", "sync", "drain"]
# A tick that launches its step ahead reads the step before it between
# the step's dispatch and its sample.
AHEAD_PHASES = ["plan", "dispatch", "sync", "drain", "sample"]
SEAT_PHASES = ["plan", "prefill", "insert", "first_token"]


@pytest.fixture(autouse=True)
def clean_log():
    obs.reset()
    yield
    obs.reset()


def by_name(records):
    out = collections.defaultdict(list)
    for r in records:
        out[r.name].append(r)
    return out


def test_nesting_gives_the_parent_on_each_of_two_threads():
    inside = threading.Barrier(2, timeout=10)

    def work(tag):
        with spans.span(f"{tag}.outer", rid=7, n=1) as outer:
            inside.wait()  # both threads hold a span open at once
            with spans.span(f"{tag}.inner") as inner:
                inner.counts["late"] = tag
            inside.wait()
            spans.record(f"{tag}.stamped", 1.0, 2.0, rid=9, k=3)
        assert outer.id != inner.id

    threads = [threading.Thread(target=work, args=(t,)) for t in "ab"]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=10)
        assert not t.is_alive()
    got = by_name(spans.snapshot().records)
    assert len({r.id for rs in got.values() for r in rs}) == 6
    for tag in "ab":
        (outer,), (inner,) = got[f"{tag}.outer"], got[f"{tag}.inner"]
        (stamped,) = got[f"{tag}.stamped"]
        assert outer.parent is None and inner.parent == outer.id
        assert stamped.parent == outer.id
        assert (outer.rid, outer.counts) == (7, {"n": 1})
        assert inner.counts == {"late": tag}
        assert (stamped.t0, stamped.t1, stamped.rid) == (1.0, 2.0, 9)
        assert outer.t0 <= inner.t0 <= inner.t1 <= outer.t1


def test_many_threads_lose_no_record_and_keep_their_own_parents():
    import os
    import sys

    n_threads, n_each = min(4 * (os.cpu_count() or 4), 64), 200
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        def work(tag):
            for k in range(n_each):
                with spans.span("outer", rid=tag):
                    with spans.span("inner", rid=tag, k=k):
                        pass

        threads = [threading.Thread(target=work, args=(t,)) for t in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
            assert not t.is_alive()
    finally:
        sys.setswitchinterval(old)
    records = spans.snapshot().records
    assert len(records) == 2 * n_threads * n_each < spans.LOG_MAX
    assert len({r.id for r in records}) == len(records)
    outer = {r.id: r for r in records if r.name == "outer"}
    for r in records:
        if r.name == "inner":
            assert outer[r.parent].rid == r.rid
        else:
            assert r.parent is None


def test_a_span_can_be_left_out_and_an_error_closes_it():
    with spans.span("kept"):
        with spans.span("poll") as sp:
            sp.keep = False
        with pytest.raises(KeyError):
            with spans.span("failed"):
                raise KeyError("x")
        with spans.span("after"):
            pass
    got = by_name(spans.snapshot().records)
    assert "poll" not in got
    assert got["failed"][0].parent == got["after"][0].parent == got["kept"][0].id


def test_the_log_is_bounded_and_snapshot_says_when_it_wrapped(monkeypatch):
    monkeypatch.setattr(spans, "LOG_MAX", 8)
    monkeypatch.setattr(spans, "_log", collections.deque(maxlen=8))
    for k in range(5):
        spans.record("r", float(k), float(k) + 0.5)
    snap = spans.snapshot()
    assert snap.complete and len(snap.records) == 5
    inside = spans.snapshot(1.0, 3.5)  # ends in (1.0, 3.5]
    assert [r.t1 for r in inside.records] == [1.5, 2.5, 3.5]
    for k in range(5, 20):
        spans.record("r", float(k), float(k) + 0.5)
    snap = spans.snapshot()
    assert not snap.complete and len(snap.records) == 8
    assert [r.t0 for r in snap.records] == [float(k) for k in range(12, 20)]
    # The oldest record kept ended at 12.5: a window that opened after
    # it is whole, one that opened before is not.
    assert spans.snapshot(14.0, 99.0).complete
    assert not spans.snapshot(11.0, 99.0).complete


def test_reset_clears_the_log_and_chrome_trace_holds_every_record():
    with spans.span("a", rid=1, n=2):
        spans.record("b", 5.0, 5.25)
    assert [r.name for r in spans.snapshot().records] == ["b", "a"]
    obs.reset()
    assert spans.snapshot().records == []
    assert not hasattr(spans, "to_chrome_trace")  # the profiler's trace is the one export


def test_a_program_build_lands_under_the_span_that_was_open():
    before = {k: c.value for k, c in spans._builds.items()}
    with spans.span("outer") as outer:
        jax.jit(lambda x: x * 3 + 1)(jnp.arange(5)).block_until_ready()
    got = by_name(spans.snapshot().records)
    kinds = [r.counts["kind"] for r in got["jax.build"]]
    assert "lowered" in kinds and {r.parent for r in got["jax.build"]} == {outer.id}
    for r in got["jax.build"]:
        assert r.t0 <= r.t1 <= got["outer"][0].t1
    assert spans._builds["lowered"].value == before["lowered"] + kinds.count("lowered")
    reg = obs.get_registry()
    assert reg.value("defer_program_builds_total", kind="lowered") >= 1


@pytest.fixture(scope="module")
def served():
    """A few requests through the default path, some queued behind a
    full batch, with the log as the run left it."""
    dec = tiny_gpt(64)
    params = dec.init(jax.random.key(0))
    streamed = collections.defaultdict(list)
    srv = PagedDecodeServer(
        dec, params, num_blocks=32, block_size=4, max_batch=2,
        on_token=lambda rid, tok, done: streamed[rid].append(tok),
    )
    rng = np.random.default_rng(0)
    reqs = [
        (jnp.asarray(rng.integers(1, 64, (1, n)), jnp.int32), steps)
        for n, steps in [(5, 4), (9, 6), (3, 1), (7, 5)]
    ]
    obs.reset()
    srv._admit()  # a poll of an empty queue
    srv._tick()  # and of an empty server
    assert spans.snapshot().records == []
    rids = [srv.submit(p, s) for p, s in reqs]
    while srv.pending or any(s is not None for s in srv.slots):
        srv._admit()
        if any(s is not None for s in srv.slots):
            srv._tick()
    return dec, params, reqs, rids, srv, streamed, spans.snapshot().records


def test_every_tick_has_its_five_phases_in_order(served):
    """A tick that launches ahead has the five phases in the order
    that hides the read of the step before, after a launch of that
    step where none was in flight; one that does not runs its step
    through them in order, or only reads the step in flight where no
    slot decodes past it. Each token a drain emits is counted once."""
    dec, params, reqs, rids, srv, streamed, records = served
    got = by_name(records)
    assert got["paged.tick"]
    shapes = collections.Counter()
    drained = 0
    for tick in got["paged.tick"]:
        assert tick.counts["kind"] == "plain" and 1 <= tick.counts["live"] <= 2
        kids = [r for r in records if r.parent == tick.id]
        names = [k.name.removeprefix("paged.tick.") for k in kids]
        if tick.counts["ahead"]:
            primed = names[:3] == ["plan", "dispatch", "sample"]
            assert names == ["plan", "dispatch", "sample"] * primed + AHEAD_PHASES
            shapes["primed" if primed else "ahead"] += 1
        else:
            assert names in (TICK_PHASES, ["sync", "drain"])
            shapes[len(names)] += 1
        assert all(a.t1 <= b.t0 for a, b in zip(kids, kids[1:]))
        assert tick.t0 <= kids[0].t0 and kids[-1].t1 <= tick.t1
        assert sum(k.t1 - k.t0 for k in kids) <= tick.t1 - tick.t0
        (drain,) = [k for k in kids if k.name == "paged.tick.drain"]
        assert 1 <= drain.counts["tokens"] <= tick.counts["live"]
        drained += drain.counts["tokens"]
    assert shapes["primed"] and shapes["ahead"], shapes
    # An admission emits a request's first token, the drains the rest.
    assert drained + len(reqs) == sum(steps for _, steps in reqs)


def test_every_request_has_one_seat_one_finish_and_one_request_span(served):
    dec, params, reqs, rids, srv, streamed, records = served
    got = by_name(records)
    for rid, (prompt, steps) in zip(rids, reqs):
        (seat,) = [r for r in got["paged.admit.seat"] if r.rid == rid]
        (finish,) = [r for r in got["paged.finish"] if r.rid == rid]
        (request,) = [r for r in got["paged.request"] if r.rid == rid]
        t0 = prompt.shape[1]
        assert seat.counts == {"prompt_tokens": t0, "pad": 1 << (t0 - 1).bit_length()}
        kids = [r for r in records if r.parent == seat.id and r.name != "jax.build"]
        phases = [f"paged.admit.seat.{p}" for p in SEAT_PHASES]
        assert [k.name for k in kids if k.name in phases] == phases
        admit = next(r for r in got["paged.admit"] if r.id == seat.parent)
        assert admit.counts["seated"] >= 1
        assert finish.counts == {"tokens": steps}
        # A request of one token ends inside its admission; the others
        # in the drain of their last tick.
        parent = next(r for r in records if r.id == finish.parent)
        assert parent.name == ("paged.admit.seat" if steps == 1 else "paged.tick.drain")
        assert request.counts["prompt_tokens"] == t0
        assert request.counts["tokens"] == steps
        assert 0 <= request.counts["queue_s"] <= request.t1 - request.t0
        assert request.t0 <= seat.t0 and finish.t1 <= request.t1
    assert len(got["paged.request"]) == len(got["paged.finish"]) == len(rids)
    assert sum(a.counts["seated"] for a in got["paged.admit"]) == len(rids)


def test_each_annotation_carries_its_records_identity(tmp_path):
    """A profiler trace of a toy server's admissions and ticks: every
    program span's event carries its record's `id`, its `parent` where
    it has one and its `rid` where it has one, so each event joins its
    record (and the counts filled inside the block) with the name left
    as it was; the decode step's program is named `jit_<DECODE_STEP>`."""
    import glob

    from defer_tpu.runtime.paged import DECODE_STEP

    dec = tiny_gpt(64)
    params = dec.init(jax.random.key(0))
    srv = PagedDecodeServer(
        dec, params, num_blocks=32, block_size=4, max_batch=2,
        on_token=lambda rid, tok, done: None,
    )
    for n, steps in [(5, 3), (9, 4), (3, 2)]:
        srv.submit(jnp.arange(1, n + 1, dtype=jnp.int32)[None], steps)
    srv._admit()
    srv._tick()  # build and warm the programs outside the trace
    obs.reset()
    jax.profiler.start_trace(str(tmp_path))
    try:
        while srv.pending or any(s is not None for s in srv.slots):
            srv._admit()
            if any(s is not None for s in srv.slots):
                srv._tick()
    finally:
        jax.profiler.stop_trace()
    records = {r.id: r for r in spans.snapshot().records}
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb"))
    events, modules = [], collections.Counter()
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                stats = dict(e.stats)
                if e.name.startswith("paged."):
                    events.append((e.name, stats))
                modules[stats.get("hlo_module")] += 1
    joined = collections.Counter()
    for name, stats in events:
        assert {"id"} <= set(stats) <= {"id", "parent", "rid"}, (name, stats)
        rec = records.get(stats["id"])
        if rec is None:  # a poll the log left out (`keep = False`)
            assert name in ("paged.admit", "paged.tick")
            continue
        assert rec.name == name
        assert stats.get("parent") == rec.parent
        assert stats.get("rid") == rec.rid
        joined[name] += 1
    assert joined["paged.tick"] >= 2 and joined["paged.admit.seat"] == 1
    assert joined["paged.tick.sync"] and joined["paged.finish"] == 3
    spanned = [r for r in records.values() if r.name != "jax.build"]
    assert sum(joined.values()) == len(spanned) - sum(
        r.name == "paged.request" for r in spanned  # stamped, no annotation
    )
    assert modules[f"jit_{DECODE_STEP}"] > 0


def test_no_span_bears_a_name_the_harness_takes_as_its_own(served):
    names = {r.name for r in served[-1]}
    assert names >= {"paged.tick", "paged.admit", "paged.request"}
    assert not names & {"tick", "admit"}
    assert all(n.startswith("paged.") or n == "jax.build" for n in names)


def test_the_spans_touch_no_array(served):
    dec, params, reqs, rids, srv, streamed, _ = served
    for rid, (prompt, steps) in zip(rids, reqs):
        want = np.asarray(dec.generate(params, prompt, steps))
        np.testing.assert_array_equal(np.asarray(srv.done[rid]), want)
        assert streamed[rid] == want[0, prompt.shape[1]:].tolist()


@pytest.mark.parametrize("kind, kwargs", [
    ("window", {"decode_window": 2}),
    ("mixed", {"prefill_budget": 4}),
])
def test_other_ticks_get_the_outer_span_and_no_phases(kind, kwargs):
    dec = tiny_gpt(64)
    params = dec.init(jax.random.key(0))
    srv = PagedDecodeServer(
        dec, params, num_blocks=32, block_size=4, max_batch=2, **kwargs
    )
    rid = srv.submit(jnp.asarray([[3, 9, 27, 5, 1, 8, 2, 6, 4]], jnp.int32), 5)
    while srv.pending or any(s is not None for s in srv.slots):
        srv._admit()
        if any(s is not None for s in srv.slots):
            srv._tick()
    got = by_name(spans.snapshot().records)
    assert kind in {t.counts["kind"] for t in got["paged.tick"]}
    ids = {t.id for t in got["paged.tick"] if t.counts["kind"] != "plain"}
    assert not [r for r in spans.snapshot().records
                if r.parent in ids and r.name.startswith("paged.tick.")]
    assert [r.rid for r in got["paged.finish"]] == [rid]


def test_every_rung_says_what_its_step_keeps_beside_the_pool():
    """`defer_paged_step_temp_bytes{span_rows=}` is set for every rung
    when a server builds its programs, to the `temp_bytes` of the
    rung's `jax.build` span, and under one pool's bytes: the step
    holds no second pool. A second server of the same shapes builds
    nothing (the programs are the decoder's) and still sets it."""
    dec = tiny_gpt(64)
    params = dec.init(jax.random.key(0))
    reg = obs.get_registry()
    # A server of its own shape, so that no test before it built these.
    kw = dict(num_blocks=257, block_size=4, max_batch=2)

    def gauges(srv):
        return {
            nb * srv.bs: reg.value(
                "defer_paged_step_temp_bytes", span_rows=str(nb * srv.bs)
            )
            for nb in srv._rungs
        }

    srv = PagedDecodeServer(dec, params, **kw)
    assert set(gauges(srv).values()) <= {None, 0}  # absent, or reset
    srv._build()
    built = {
        r.counts["span_rows"]: r.counts["temp_bytes"]
        for r in spans.snapshot().records
        if r.name == "jax.build" and r.counts["kind"] == "paged_step"
    }
    assert sorted(built) == [16, 24, 64]
    assert gauges(srv) == built
    one_pool = srv.pool_k.size * srv.pool_k.dtype.itemsize
    assert all(0 < t < one_pool for t in built.values()), (built, one_pool)
    obs.reset()
    again = PagedDecodeServer(dec, params, **kw)
    again._build()
    assert not [r for r in spans.snapshot().records if r.name == "jax.build"]
    assert gauges(again) == built


# -- named scopes: op metadata a device trace can be charged to a layer by --


def lowered_text(program: str) -> str:
    """The lowered text, source locations included, of one of the
    programs the paged server runs on a tiny decoder."""
    from defer_tpu.models.gpt import sample_token_batched_nosort

    dec = tiny_gpt(64)
    params = dec.init(jax.random.key(0))
    attention = program if program in ("blockwise", "pallas") else "gathered"
    srv = PagedDecodeServer(
        dec, params, num_blocks=16, block_size=4, max_batch=2,
        attention=attention,
    )
    srv._build()
    flat = srv._flat_dec()
    cache = flat.init_cache(1)
    if program == "prefill":
        lowered = flat.make_step(donate=False).lower(
            srv.params, cache, jnp.zeros((1, 8), jnp.int32)
        )
    elif program == "insert":
        lowered = srv._insert.lower(
            srv.pool_k, srv.pool_v, cache["k"], cache["v"],
            jnp.asarray(srv.tables[0]),
        )
    elif program == "sample":
        sm = srv._sampler
        lowered = sample_token_batched_nosort.lower(
            jnp.zeros((2, dec.cfg.vocab_size)), sm.keys, sm.temp, sm.minp
        )
    else:
        lowered = srv._step.lower(
            srv.params, srv.pool_k, srv.pool_v, jnp.asarray(srv.tables),
            jnp.asarray(srv.pos.astype(np.int32)), srv._feed,
            jnp.asarray(srv.adapter),
        )
    return lowered.as_text(debug_info=True)


@pytest.mark.parametrize("program, scopes", [
    ("gathered", ["embed", "kv_gather", "attn_qkv", "attn_core", "attn_out",
                  "mlp", "kv_scatter", "logits"]),
    ("blockwise", ["embed", "attn_qkv", "kv_scatter", "attn_core", "attn_out",
                   "mlp", "logits"]),
    ("pallas", ["attn_qkv", "kv_scatter", "attn_core", "paged_flash_decode",
                "mlp", "logits"]),
    ("prefill", ["embed", "attn_qkv", "attn_core", "attn_out", "mlp", "logits"]),
    ("insert", ["kv_insert"]),
    ("sample", ["sample"]),
])
def test_the_programs_name_their_layers(program, scopes):
    text = lowered_text(program)
    missing = [s for s in scopes if f'{s}/' not in text and f'{s}"' not in text]
    assert not missing, missing
