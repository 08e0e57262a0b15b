"""Fused multi-token decode windows (`decode_window=K`): K>1 must be
token-identical to the K=1 tick-per-token loop in BOTH servers, across
attention paths, prefix caching, mixed greedy+sampled slots, eos
mid-window, stop sequences, and streaming — while issuing ~1/K the
host dispatches. Plus the trace-stability contract: a warmed windowed
`_tick` lowers nothing new.

Parity argument being pinned (runtime/decode_server.py /
runtime/paged.py `_build_window`): the window scans the SAME raw step
body the K=1 tick jits, pins positions with the same sub-step-start
active mask, and draws from the same per-slot key schedule — so every
accepted token is the token K=1 would have produced, and overshoot
past eos/budget/stop is discarded before it can reach outputs or the
stop-match history.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from defer_tpu import obs
from defer_tpu.models.gpt import SamplingParams, tiny_gpt
from defer_tpu.models.llama import tiny_llama
from defer_tpu.runtime.decode_server import DecodeServer, serve_greedy
from defer_tpu.runtime.paged import PagedDecodeServer, serve_paged


def _mixed_requests(vocab, rng_seed=5):
    """Same shape as test_paged_attention's mix: shared 16-token
    prefix on the first two (prefix_cache shares blocks), lengths
    straddling block boundaries, 5 requests through 2 slots so
    finish/re-admit happens mid-run — at K>1, at window boundaries."""
    rng = np.random.default_rng(rng_seed)
    base = jnp.asarray(
        rng.integers(1, vocab, size=(1, 18)), jnp.int32
    )
    ext = jnp.asarray(rng.integers(1, vocab, size=(1, 5)), jnp.int32)
    return [
        (base, 6),
        (jnp.concatenate([base, ext], axis=1), 5),
        (jnp.asarray(rng.integers(1, vocab, size=(1, 3)), jnp.int32), 7),
        (jnp.asarray(rng.integers(1, vocab, size=(1, 9)), jnp.int32), 4),
        (jnp.asarray(rng.integers(1, vocab, size=(1, 2)), jnp.int32), 3),
    ]


_MIXED_SAMPLING = [
    None,
    SamplingParams(temperature=0.9, seed=3),
    SamplingParams(temperature=1.2, top_k=5, seed=11),
    None,
    SamplingParams(temperature=1.0, top_p=0.9, seed=2),
]


@pytest.fixture(scope="module")
def llama():
    dec = tiny_llama(64)
    return dec, dec.init(jax.random.key(0))


@pytest.fixture(scope="module")
def gpt():
    dec = tiny_gpt(64)
    return dec, dec.init(jax.random.key(0))


def _serve(dec, params, reqs, **kw):
    outs, stats = serve_paged(
        dec, params, reqs,
        num_blocks=18, block_size=4, max_batch=2,
        sampling=_MIXED_SAMPLING, **kw,
    )
    return [np.asarray(o) for o in outs], stats


# -- paged parity matrix ----------------------------------------------


@pytest.mark.parametrize("attention", ["gathered", "blockwise"])
@pytest.mark.parametrize("prefix_cache", [False, True])
@pytest.mark.parametrize("K", [4, 8])
def test_paged_window_parity_matrix(llama, attention, prefix_cache, K):
    """decode_window=K is token-identical to K=1 across attention
    paths x prefix-cache on/off, with mixed greedy+sampled slots and
    mid-run finish/re-admit, at ~1/K the host dispatches."""
    dec, params = llama
    reqs = _mixed_requests(dec.cfg.vocab_size)
    want, base = _serve(
        dec, params, reqs,
        attention=attention, prefix_cache=prefix_cache,
    )
    got, stats = _serve(
        dec, params, reqs,
        attention=attention, prefix_cache=prefix_cache,
        decode_window=K,
    )
    for i, (w, g) in enumerate(zip(want, got)):
        assert w.shape == g.shape, f"req {i}: {w.shape} vs {g.shape}"
        assert (w == g).all(), f"req {i} diverged at K={K}"
    assert stats["decode_window"] == K
    assert stats["host_dispatches"] < base["host_dispatches"]
    # Each dispatch must be accepting multiple tokens on average.
    assert stats["tokens_per_dispatch"] > base["tokens_per_dispatch"]


@pytest.mark.parametrize("attention", ["gathered", "blockwise"])
def test_window8_dispatch_budget(llama, attention):
    """The numbers budgets.toml's two window contracts account for, on
    answers long enough to fill windows: at decode_window=8 at most
    one dispatch per four tokens (this mix reads 0.07, which leaves
    room for windows cut short; per-token dispatch reads 0.54) and at
    least one accepted token a dispatch."""
    dec, params = llama
    rng = np.random.default_rng(7)
    reqs = [
        (
            jnp.asarray(
                rng.integers(1, dec.cfg.vocab_size, size=(1, 3 + 2 * i)),
                jnp.int32,
            ),
            24 + 8 * i,
        )
        for i in range(4)
    ]
    total_tokens = sum(steps for _, steps in reqs)
    with obs.counter_deltas() as d:
        _, stats = serve_paged(
            dec, params, reqs,
            num_blocks=36, block_size=4, max_batch=2,
            attention=attention, decode_window=8,
        )
    assert d['defer_host_dispatches_total{server="paged"}'] == (
        stats["host_dispatches"]
    )
    assert stats["host_dispatches"] / total_tokens <= 0.25
    assert stats["tokens_per_dispatch"] >= 1.0


# -- flat server -------------------------------------------------------


@pytest.mark.parametrize("K", [4, 8])
def test_flat_window_parity(gpt, K):
    """Flat-server twin of the paged matrix: mixed greedy+sampled
    requests, bit-identical outputs, fewer dispatches."""
    dec, params = gpt
    reqs = _mixed_requests(dec.cfg.vocab_size)
    want, base = serve_greedy(
        dec, params, reqs, max_batch=2, sampling=_MIXED_SAMPLING,
    )
    got, stats = serve_greedy(
        dec, params, reqs, max_batch=2, sampling=_MIXED_SAMPLING,
        decode_window=K,
    )
    for w, g in zip(want, got):
        assert w.shape == g.shape
        assert (np.asarray(w) == np.asarray(g)).all()
    assert stats["host_dispatches"] < base["host_dispatches"]


def test_flat_window_prefix_cache_parity(gpt):
    """Windowed decode composes with the flat server's shared-prefix
    cache (suffix-only admissions feed the same window step)."""
    dec, params = gpt
    prefix = jnp.asarray([[9, 4, 2, 6, 1, 3, 8, 5]], jnp.int32)
    reqs = _mixed_requests(dec.cfg.vocab_size)[:3]
    want, _ = serve_greedy(
        dec, params, reqs, max_batch=2, prefix_ids=prefix,
    )
    got, _ = serve_greedy(
        dec, params, reqs, max_batch=2, prefix_ids=prefix,
        decode_window=4,
    )
    for w, g in zip(want, got):
        assert (np.asarray(w) == np.asarray(g)).all()


def test_decode_window_validation(gpt):
    dec, params = gpt
    with pytest.raises(ValueError, match="decode_window"):
        DecodeServer(dec, params, decode_window=0)
    with pytest.raises(ValueError, match="decode_window"):
        PagedDecodeServer(
            dec, params, num_blocks=12, block_size=4,
            decode_window=-1,
        )


# -- eos mid-window ----------------------------------------------------


def _harvest_eos(outs, reqs, window=4):
    """A token some request first generates MID-WINDOW, to use as eos:
    re-serving with it cuts that window short (deterministic — same
    seeds, same tokens). Admission emits generated token 0 and window
    n covers tokens n*K+1..(n+1)*K, so a first occurrence at index j
    truncates iff j % K != 0; greedy tiny_gpt repeats its first token
    for a while, so a fixed index would finish the request at
    admission instead."""
    for (prompt, steps), o in zip(reqs, outs):
        gen = np.asarray(o)[0, prompt.shape[1]:].tolist()
        for j, tok in enumerate(gen):
            if j % window and tok not in gen[:j]:
                return int(tok)
    raise AssertionError("no request first emits a token mid-window")


@pytest.mark.parametrize("server", ["flat", "paged"])
def test_eos_mid_window_truncates(gpt, server):
    """A request hitting eos mid-window freezes on device: outputs
    end with the eos exactly as at K=1 (overshoot discarded), and the
    truncation counter records the cut windows."""
    dec, params = gpt
    reqs = _mixed_requests(dec.cfg.vocab_size)

    def run(**kw):
        if server == "flat":
            return serve_greedy(dec, params, reqs, max_batch=2, **kw)
        return serve_paged(
            dec, params, reqs,
            num_blocks=18, block_size=4, max_batch=2, **kw,
        )

    plain, _ = run()
    eos = _harvest_eos(plain, reqs)
    want, _ = run(eos_id=eos)
    with obs.counter_deltas() as d:
        got, stats = run(eos_id=eos, decode_window=4)
    for w, g in zip(want, got):
        assert w.shape == g.shape
        assert (np.asarray(w) == np.asarray(g)).all()
    lab = f'server="{server}"'
    assert d.get(f"defer_window_truncated_total{{{lab}}}", 0) > 0


# -- stop sequences across windows ------------------------------------


@pytest.mark.parametrize("server", ["flat", "paged"])
def test_stop_sequence_window_parity(gpt, server):
    """Stop matching stays host-side: the window overshoots past the
    match, the drain truncates at it, and discarded overshoot never
    enters the match history — outputs identical to K=1."""
    dec, params = gpt
    reqs = _mixed_requests(dec.cfg.vocab_size)

    def run(stop, K):
        outs = []
        if server == "flat":
            srv = DecodeServer(
                dec, params, max_batch=2, decode_window=K,
            )
        else:
            srv = PagedDecodeServer(
                dec, params, num_blocks=18, block_size=4,
                max_batch=2, decode_window=K,
            )
        rids = [
            srv.submit(p, s, stop=stop) for p, s in reqs
        ]
        done = srv.run()
        return [np.asarray(done[r]) for r in rids]

    plain = run(None, 1)
    # A 2-token subsequence one request actually generates — every
    # run sharing it must stop there, mid-budget, whatever K is.
    p0, _ = reqs[0]
    gen = plain[0][0, p0.shape[1]:]
    assert len(gen) >= 3
    stop = [[int(gen[1]), int(gen[2])]]
    want = run(stop, 1)
    got = run(stop, 4)
    for w, g in zip(want, got):
        assert w.shape == g.shape
        assert (w == g).all()


# -- streaming ---------------------------------------------------------


def test_streaming_per_request_order_preserved(gpt):
    """on_token consumers see each request's tokens in order with
    done on the last — and within a window, tick-major interleaving
    (all slots' sub-step t before any slot's t+1), the K=1 order."""
    dec, params = gpt
    reqs = _mixed_requests(dec.cfg.vocab_size)

    def run(K):
        events = []
        srv = DecodeServer(
            dec, params, max_batch=2, decode_window=K,
            on_token=lambda rid, tok, done: events.append(
                (rid, tok, done)
            ),
        )
        rids = [srv.submit(p, s) for p, s in reqs]
        done = srv.run()
        return events, rids, done

    ev1, rids1, _ = run(1)
    evK, ridsK, doneK = run(4)

    def per_rid(events, rids):
        out = {r: [] for r in rids}
        for rid, tok, done in events:
            out[rid].append((tok, done))
        return out

    m1, mK = per_rid(ev1, rids1), per_rid(evK, ridsK)
    for r1, rK in zip(rids1, ridsK):
        assert m1[r1] == mK[rK]
        assert mK[rK][-1][1] is True  # done fires on the last token
    # Streamed tokens match the returned arrays (generated region).
    for (prompt, _), rK in zip(reqs, ridsK):
        t0 = prompt.shape[1]
        streamed = [t for t, _ in mK[rK]]
        assert streamed == np.asarray(doneK[rK])[0, t0:].tolist()


# -- trace stability ---------------------------------------------------


def test_windowed_tick_trace_stable_after_warmup(gpt):
    """The windowed `_tick` keeps the paged server's trace-stability
    contract: 3 post-warmup windows lower nothing new in any jitted
    callable the server or decoder holds (the window program is
    memoized on the decoder, where the sanitizer auto-watches it)."""
    from defer_tpu.analysis import trace_sanitizer as sanitize

    dec, params = gpt
    srv = PagedDecodeServer(
        dec, params, num_blocks=16, block_size=4, max_batch=2,
        decode_window=4,
    )
    srv.submit(jnp.asarray([[3, 9, 27]], jnp.int32), 25)
    srv.submit(jnp.asarray([[5, 1]], jnp.int32), 24)
    srv._admit()
    for _ in range(2):  # warmup: first window compiles the scan
        srv._tick()
    with sanitize(srv, dec) as rep:
        for _ in range(3):
            srv._tick()
    assert rep.retraces == 0
    assert rep.watched
