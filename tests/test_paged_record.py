"""A slot's generated tokens are recorded on the host and joined there
(`PagedDecodeServer._emit_token`, the drains, `_finish`): `done[rid]`
is what it always was, no drain touches a device array per slot, no
finished request builds a program, and a server that nobody listens to
makes no transfer per tick."""

import collections

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from defer_tpu.models.gpt import tiny_gpt
from defer_tpu.obs import spans
from defer_tpu.runtime.paged import PagedDecodeServer

# What each tick kind adds to the default server. `late` is the share
# of a request's tokens that nobody reads before `_finish` on a server
# without eos, callback or stop sequence: all of them, but under
# speculation, whose accept test reads every token on the host anyway.
KINDS = {
    "plain": ({}, "all"),
    "radix": ({"prefix_cache": True}, "all"),
    "mixed": ({"prefill_budget": 4}, "all"),
    "window": ({"decode_window": 4}, "all"),
    "spec": ({"spec_k": 3}, "none"),
    "spec_window": ({"spec_k": 2, "decode_window": 2}, "none"),
    "pp": ({"pp_stages": 2, "max_batch": 4}, "all"),
}


@pytest.fixture(scope="module")
def model():
    dec = tiny_gpt(64)
    return dec, dec.init(jax.random.key(0))


def _requests(seed: int, lengths) -> list:
    rng = np.random.default_rng(seed)
    return [
        (jnp.asarray(rng.integers(1, 64, (1, t0)), jnp.int32), steps)
        for t0, steps in lengths
    ]


def _server(model, kind: str, on_token=None, **kw) -> PagedDecodeServer:
    dec, params = model
    extra = dict(KINDS[kind][0])
    if "spec_k" in extra:
        extra.update(spec_draft=dec, spec_params=params)
    base = dict(num_blocks=40, block_size=4, max_batch=3)
    return PagedDecodeServer(
        dec, params, on_token=on_token, **{**base, **extra, **kw}
    )


def _lowered() -> int:
    return spans._builds["lowered"].value


@pytest.mark.parametrize("listen", [True, False], ids=["on_token", "nobody"])
@pytest.mark.parametrize("kind", list(KINDS))
def test_done_is_the_prompt_and_the_tokens_joined_on_the_host(
    model, kind, listen
):
    dec, params = model
    streamed = collections.defaultdict(list)
    srv = _server(
        model, kind,
        (lambda rid, tok, done: streamed[rid].append(tok)) if listen else None,
    )
    # Prompt and answer lengths no other request of the run shares:
    # every finish joins a pair of lengths it has not seen.
    reqs = _requests(3, [(3, 7), (1, 4), (5, 9), (2, 2), (6, 1), (9, 12)])
    # One request first: what a finish builds once for the server
    # (speculation's release of a draft lane) is built here.
    srv.submit(*_requests(4, [(4, 3)])[0])
    srv.run()
    streamed.clear()
    built_in_finish = []
    finish = srv._finish

    def counted(i):
        before = _lowered()
        finish(i)
        built_in_finish.append(_lowered() - before)

    srv._finish = counted
    generated = srv.obs.tokens_generated.value
    late = srv.obs.tokens_resolved_at_finish.value
    spans.reset()
    rids = [srv.submit(p, n) for p, n in reqs]
    done = srv.run()
    if srv.pp > 1:
        srv.close_pp()
    generated = srv.obs.tokens_generated.value - generated
    late = srv.obs.tokens_resolved_at_finish.value - late

    for rid, (prompt, steps) in zip(rids, reqs):
        got = done[rid]
        assert isinstance(got, jax.Array)
        assert got.shape == (1, prompt.shape[1] + steps)
        assert got.dtype == prompt.dtype
        want = np.asarray(dec.generate(params, prompt, steps))
        np.testing.assert_array_equal(np.asarray(got), want, err_msg=kind)
        if listen:
            assert streamed[rid] == want[0, prompt.shape[1]:].tolist()
            assert all(type(t) is int for t in streamed[rid])
    assert generated == sum(n for _, n in reqs)
    if listen:
        assert late == 0
    else:
        assert late == {"all": generated, "none": 0}[KINDS[kind][1]]
    # No program under any `_finish`, by the count and by the span log.
    assert built_in_finish == [0] * len(reqs)
    records = spans.snapshot().records
    finishes = {r.id: r for r in records if r.name == "paged.finish"}
    assert len(finishes) == len(reqs)
    assert not [
        r for r in records if r.name == "jax.build" and r.parent in finishes
    ]
    assert sorted(r.counts["tokens"] for r in finishes.values()) == sorted(
        n for _, n in reqs
    )


def test_sampled_and_stopped_requests_join_what_was_streamed(model):
    """Per-request consumers on a server with no callback: a stop
    sequence makes the ticks transfer, a sampled slot draws on the
    device; both records join to what a listening server streams."""
    from defer_tpu.models.gpt import SamplingParams

    dec, params = model
    reqs = _requests(5, [(4, 10), (2, 8), (3, 6)])
    samp = [None, SamplingParams(temperature=0.8, seed=7), None]
    ref = dec.generate(params, reqs[0][0], reqs[0][1])
    stop = [np.asarray(ref)[0, 4 + 4 : 4 + 6].tolist()]
    streamed = collections.defaultdict(list)
    outs = []
    for on_token in (lambda rid, tok, done: streamed[rid].append(tok), None):
        srv = _server(model, "plain", on_token)
        rids = [
            srv.submit(p, n, sampling=s, stop=stop if j == 0 else None)
            for j, ((p, n), s) in enumerate(zip(reqs, samp))
        ]
        done = srv.run()
        outs.append([np.asarray(done[r]) for r in rids])
    for j, (a, b) in enumerate(zip(*outs)):
        np.testing.assert_array_equal(a, b)
        assert a[0, reqs[j][0].shape[1]:].tolist() == streamed[j]
    assert outs[0][0].shape[1] < 4 + 10  # cut at the stop sequence


class _Watch:
    """Counts, while `on`, what a drain must not do: an eager jax
    primitive (an index, a reshape or a cast of a device array is one
    or more), an upload through `jnp.asarray`, a fetch through
    `np.asarray` of a device array."""

    def __init__(self, monkeypatch):
        from jax._src import core

        self.on = False
        self.primitives = self.uploads = self.fetches = 0
        self.monkeypatch = monkeypatch
        bind = core.EvalTrace.process_primitive
        to_device, to_host = jnp.asarray, np.asarray

        def process_primitive(trace, prim, args, params):
            self.primitives += self.on
            return bind(trace, prim, args, params)

        def upload(a, *args, **kw):
            self.uploads += self.on
            return to_device(a, *args, **kw)

        def fetch(a, *args, **kw):
            self.fetches += self.on and isinstance(a, jax.Array)
            return to_host(a, *args, **kw)

        monkeypatch.setattr(core.EvalTrace, "process_primitive", process_primitive)
        monkeypatch.setattr(jnp, "asarray", upload)
        monkeypatch.setattr(np, "asarray", fetch)

    def during(self, name: str) -> None:
        """Count inside every program span called `name`."""
        watch = self

        class Span(spans.span):
            def __enter__(self):
                watch.on = watch.on or self.name == name
                return super().__enter__()

            def __exit__(self, *exc):
                if self.name == name:
                    watch.on = False
                return super().__exit__(*exc)

        self.monkeypatch.setattr(spans, "span", Span)


@pytest.mark.parametrize("listen", [True, False], ids=["on_token", "nobody"])
def test_the_drain_of_a_plain_tick_dispatches_nothing(
    model, monkeypatch, listen
):
    seen = []
    srv = _server(
        model, "plain",
        (lambda rid, tok, done: seen.append(tok)) if listen else None,
        max_batch=4,
    )
    for p, n in _requests(11, [(3, 30), (5, 30), (2, 30), (7, 30)]):
        srv.submit(p, n)
    srv._admit()
    assert sum(s is not None for s in srv.slots) == 4
    srv._tick()  # the step's program is built
    watch = _Watch(monkeypatch)
    watch.during("paged.tick.drain")
    before = len(seen)
    for _ in range(5):
        srv._tick()
    assert sum(s is not None for s in srv.slots) == 4  # no finish inside
    assert (watch.primitives, watch.uploads, watch.fetches) == (0, 0, 0)
    assert len(seen) - before == (20 if listen else 0)
    assert all(len(s["out"]) == 7 for s in srv.slots)


@pytest.mark.parametrize("listen", [True, False], ids=["on_token", "nobody"])
def test_a_tick_fetches_its_tokens_once_or_not_at_all(
    model, monkeypatch, listen
):
    srv = _server(
        model, "plain", (lambda rid, tok, done: None) if listen else None,
        max_batch=4,
    )
    for p, n in _requests(12, [(3, 30), (5, 30), (2, 30), (7, 30)]):
        srv.submit(p, n)
    srv._admit()
    srv._tick()
    watch = _Watch(monkeypatch)
    watch.on = True
    for _ in range(5):
        srv._tick()
    watch.on = False
    assert watch.fetches == (5 if listen else 0)
    # Without a listener the record holds where each token lies: the
    # tick's vector, shared by its four slots, and the slot's row.
    if not listen:
        last = [s["out"][-1] for s in srv.slots]
        assert all(vec is last[0][0] for vec, _ in last)
        assert [row for _, row in last] == [0, 1, 2, 3]
