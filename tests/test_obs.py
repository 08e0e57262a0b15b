"""Metrics core semantics, export formats, and the end-to-end
contract that the serving runtimes report consistent numbers through
the process registry."""

import json
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from defer_tpu.models.gpt import tiny_gpt
from defer_tpu.obs import (
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    PeriodicDumper,
    ServerStats,
    get_registry,
    log_buckets,
)
from defer_tpu.obs import reset as obs_reset


# -- registry / instrument semantics ----------------------------------


def test_counter_gauge_basics():
    r = MetricsRegistry()
    c = r.counter("c_total", "help text")
    c.inc()
    c.inc(4)
    assert c.value == 5
    with pytest.raises(ValueError, match="cannot decrease"):
        c.inc(-1)
    g = r.gauge("g")
    g.set(10)
    g.dec(3)
    g.inc()
    assert g.value == 8
    # Same (name, labels) -> the same instrument object.
    assert r.counter("c_total") is c
    assert r.counter("x", labels={"a": "1"}) is not r.counter(
        "x", labels={"a": "2"}
    )
    # A name cannot change kind.
    with pytest.raises(ValueError, match="already registered"):
        r.gauge("c_total")


def test_counter_thread_safety_exact_count():
    """8 threads x 10k increments must land exactly — int += is not
    atomic under the GIL, the per-instrument lock is load-bearing."""
    r = MetricsRegistry()
    c = r.counter("hammer_total")
    h = r.histogram("hammer_seconds", buckets=[0.5, 1.0])
    n_threads, per = 8, 10_000

    def work():
        for _ in range(per):
            c.inc()
            h.observe(0.75)

    threads = [threading.Thread(target=work) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value == n_threads * per
    assert h.count == n_threads * per
    snap = h._snapshot()
    assert snap["buckets"][1][1] == n_threads * per  # le=1.0 cum


def test_histogram_bucket_edges_le_semantics():
    """Prometheus le semantics: bucket i counts v <= edges[i]; a value
    exactly on an edge lands in that edge's bucket; beyond the last
    edge lands only in +Inf."""
    r = MetricsRegistry()
    h = r.histogram("lat", buckets=[0.1, 1.0, 10.0])
    for v in (0.05, 0.1, 0.5, 1.0, 9.9, 10.0, 11.0):
        h.observe(v)
    snap = h._snapshot()
    assert snap["buckets"] == [
        [0.1, 2],       # 0.05, 0.1
        [1.0, 4],       # + 0.5, 1.0
        [10.0, 6],      # + 9.9, 10.0
        ["+Inf", 7],    # + 11.0
    ]
    assert snap["count"] == 7
    assert snap["sum"] == pytest.approx(sum((0.05, 0.1, 0.5, 1.0, 9.9, 10.0, 11.0)))
    # Weighted observe: one bisect, n counts.
    h.observe(0.5, n=3)
    assert h.count == 10
    assert h._snapshot()["buckets"][1][1] == 7


def test_log_buckets_shape_and_validation():
    edges = log_buckets(1e-3, 10.0, 4)
    assert edges == pytest.approx((1e-3, 1e-2, 1e-1, 1.0))
    with pytest.raises(ValueError):
        log_buckets(0.0, 2.0, 4)
    with pytest.raises(ValueError):
        log_buckets(1e-3, 1.0, 4)
    with pytest.raises(ValueError, match="ascending"):
        MetricsRegistry().histogram("h", buckets=[2.0, 1.0])


def test_reset_zeroes_in_place_keeping_handles():
    """reset() must zero values WITHOUT replacing instruments: hot
    paths cache handles at construction, and a swapped object would
    silently orphan them (the test-isolation contract)."""
    r = MetricsRegistry()
    c = r.counter("c_total")
    h = r.histogram("h_seconds", buckets=[1.0])
    c.inc(7)
    h.observe(0.5)
    r.reset()
    assert c.value == 0 and h.count == 0 and h.sum == 0.0
    assert r.counter("c_total") is c  # same object survives
    c.inc()  # the cached handle still feeds the registry
    assert r.value("c_total") == 1


def test_quantile_estimate():
    r = MetricsRegistry()
    h = r.histogram("q", buckets=[1.0, 2.0, 4.0])
    assert h.approx_quantile(0.5) is None
    for _ in range(100):
        h.observe(1.5)
    q = h.approx_quantile(0.5)
    assert 1.0 <= q <= 2.0


# -- export sinks -----------------------------------------------------


def test_prometheus_exposition_golden():
    """Pin the exact text exposition: HELP/TYPE headers, sorted label
    rendering, cumulative buckets with a trailing +Inf, _sum/_count."""
    r = MetricsRegistry()
    r.counter(
        "defer_requests_total", "Requests served", {"server": "flat"}
    ).inc(3)
    r.gauge("defer_pool_blocks_free", "Free blocks").set(5)
    h = r.histogram(
        "defer_ttft_seconds", "Time to first token", buckets=[0.1, 1.0]
    )
    # Powers of two: the _sum accumulates exactly, so the golden
    # string can pin it without float-formatting slack.
    h.observe(0.0625)
    h.observe(0.5)
    h.observe(2.0)
    golden = (
        '# HELP defer_pool_blocks_free Free blocks\n'
        '# TYPE defer_pool_blocks_free gauge\n'
        'defer_pool_blocks_free 5\n'
        '# HELP defer_requests_total Requests served\n'
        '# TYPE defer_requests_total counter\n'
        'defer_requests_total{server="flat"} 3\n'
        '# HELP defer_ttft_seconds Time to first token\n'
        '# TYPE defer_ttft_seconds histogram\n'
        'defer_ttft_seconds_bucket{le="0.1"} 1\n'
        'defer_ttft_seconds_bucket{le="1"} 2\n'
        'defer_ttft_seconds_bucket{le="+Inf"} 3\n'
        'defer_ttft_seconds_sum 2.5625\n'
        'defer_ttft_seconds_count 3\n'
    )
    assert r.to_prometheus() == golden


def test_to_dict_json_round_trip():
    r = MetricsRegistry()
    r.counter("a_total", labels={"k": "v"}).inc(2)
    r.histogram("b_seconds", buckets=[1.0]).observe(0.5)
    d = json.loads(json.dumps(r.to_dict()))
    assert d["counters"]['a_total{k="v"}'] == 2
    assert d["histograms"]["b_seconds"]["count"] == 1


def test_periodic_dumper_writes_file(tmp_path):
    r = MetricsRegistry()
    r.counter("dump_total").inc(9)
    path = tmp_path / "metrics.jsonl"
    d = PeriodicDumper(r, interval_s=60.0, path=str(path), fmt="json")
    d.dump_once()
    line = path.read_text().strip()
    assert json.loads(line)["counters"]["dump_total"] == 9
    with pytest.raises(ValueError, match="json|prometheus"):
        PeriodicDumper(r, fmt="xml")


def test_server_stats_dict_and_attr_access():
    s = ServerStats({"ticks": 4})
    assert s["ticks"] == 4 and s.ticks == 4
    s.extra = 1
    assert s["extra"] == 1
    with pytest.raises(AttributeError):
        s.missing
    assert isinstance(s, dict)  # legacy **stats / [key] call sites


# -- end-to-end: the serving runtimes report through the registry -----


def test_flat_server_metrics_consistency():
    """A small DecodeServer run must report: admitted == finished ==
    requests, tokens_generated == sum(step budgets), TTFT observations
    == admissions, and the ticks counter == the server's own tick
    count."""
    from defer_tpu.runtime.decode_server import serve_greedy

    obs_reset()
    dec = tiny_gpt(64)
    params = dec.init(jax.random.key(0))
    reqs = [
        (jnp.asarray([[3, 9, 27]], jnp.int32), 7),
        (jnp.asarray([[5]], jnp.int32), 4),
        (jnp.asarray([[11, 2, 8, 1, 6]], jnp.int32), 9),
    ]
    outs, stats = serve_greedy(dec, params, reqs, max_batch=2)
    reg = get_registry()
    lab = {"server": "flat"}
    assert reg.value("defer_requests_admitted_total", **lab) == len(reqs)
    assert reg.value("defer_requests_finished_total", **lab) == len(reqs)
    assert reg.value("defer_tokens_generated_total", **lab) == sum(
        s for _, s in reqs
    )
    assert reg.value("defer_prefill_tokens_total", **lab) == sum(
        p.shape[1] for p, _ in reqs
    )
    assert reg.value("defer_decode_ticks_total", **lab) == stats["ticks"]
    ttft = reg.value("defer_ttft_seconds", **lab)
    assert ttft["count"] == len(reqs)
    qw = reg.value("defer_queue_wait_seconds", **lab)
    assert qw["count"] == len(reqs)
    # The snapshot rides the stats return-channel too.
    snap = stats.metrics["counters"]
    assert snap['defer_tokens_generated_total{server="flat"}'] == sum(
        s for _, s in reqs
    )
    # Exposition renders the whole serving family without error.
    text = reg.to_prometheus()
    assert 'defer_ttft_seconds_bucket{le="+Inf",server="flat"}' in text


def test_paged_server_metrics_and_prefix_cache_counters():
    """Paged run with the radix cache: hit/miss counters must be
    consistent with the sharing scenario (first admission all misses,
    identical second prompt all hits), pool gauges must reconcile with
    the free list, and token/TTFT counts mirror the flat contract."""
    from defer_tpu.runtime.paged import serve_paged

    obs_reset()
    dec = tiny_gpt(64)
    params = dec.init(jax.random.key(0))
    bs = 4
    prompt = jnp.asarray([[7, 3, 1, 12, 9, 2, 4, 4, 11]], jnp.int32)
    reqs = [(prompt, 5), (prompt, 5)]
    outs, stats = serve_paged(
        dec, params, reqs, num_blocks=24, block_size=bs,
        max_batch=1, prefix_cache=True,
    )
    reg = get_registry()
    lab = {"server": "paged"}
    n_full = prompt.shape[1] // bs  # 2 full prompt blocks
    # Request 1: n_full misses; request 2 (same prompt, serialized by
    # max_batch=1): n_full hits against request 1's parked blocks.
    assert reg.value("defer_prefix_cache_misses_total", **lab) == n_full
    assert reg.value("defer_prefix_cache_hits_total", **lab) == n_full
    # Finishing parked each request's shared blocks at refcount 0;
    # request 2 revived request 1's parked blocks.
    assert reg.value("defer_prefix_cache_revivals_total", **lab) == n_full
    assert reg.value("defer_prefix_cache_parks_total", **lab) == 2 * n_full
    assert reg.value("defer_prefix_cache_evictions_total", **lab) == 0
    assert reg.value("defer_requests_admitted_total", **lab) == 2
    assert reg.value("defer_requests_finished_total", **lab) == 2
    assert reg.value("defer_tokens_generated_total", **lab) == 10
    assert reg.value("defer_ttft_seconds", **lab)["count"] == 2
    # Cached-prefix prefill skip shows up as fewer prefill tokens on
    # the second admission (only the suffix runs).
    assert (
        reg.value("defer_prefill_tokens_total", **lab)
        == 2 * prompt.shape[1] - stats["prefill_tokens_saved"]
    )
    # Pool gauges: all requests done, so nothing is held by slots.
    assert reg.value("defer_pool_blocks_used", **lab) == 0
    assert stats["cached_blocks"] == n_full
    # Both outputs identical (same prompt, greedy).
    np.testing.assert_array_equal(np.asarray(outs[0]), np.asarray(outs[1]))


def test_dispatch_efficiency_metrics():
    """The fused-window instruments (runtime/*.py `decode_window`):
    at K=1, defer_host_dispatches_total mirrors the tick counter and
    nothing truncates; at K>1, dispatches collapse by ~K while the
    token counters stay request-exact; an eos mid-window trips
    defer_window_truncated_total."""
    from defer_tpu.runtime.decode_server import serve_greedy

    dec = tiny_gpt(64)
    params = dec.init(jax.random.key(0))
    reqs = [
        (jnp.asarray([[3, 9, 27]], jnp.int32), 13),
        (jnp.asarray([[5]], jnp.int32), 11),
        (jnp.asarray([[11, 2, 8, 1, 6]], jnp.int32), 12),
    ]
    lab = {"server": "flat"}
    reg = get_registry()
    obs_reset()
    outs, st1 = serve_greedy(dec, params, reqs, max_batch=2)
    assert st1["decode_window"] == 1
    assert st1["host_dispatches"] == st1["ticks"]
    assert (
        reg.value("defer_host_dispatches_total", **lab)
        == reg.value("defer_decode_ticks_total", **lab)
        == st1["ticks"]
    )
    assert reg.value("defer_window_truncated_total", **lab) == 0
    assert reg.value("defer_tokens_per_dispatch", **lab) >= 1

    obs_reset()
    outs4, st4 = serve_greedy(
        dec, params, reqs, max_batch=2, decode_window=4
    )
    for a, b in zip(outs, outs4):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert st4["decode_window"] == 4
    assert st4["host_dispatches"] < st1["host_dispatches"]
    assert (
        reg.value("defer_host_dispatches_total", **lab)
        == st4["host_dispatches"]
    )
    # Window-exact tokens: however the budgets are windowed, the
    # accepted total equals the requested step budgets.
    assert reg.value("defer_tokens_generated_total", **lab) == sum(
        s for _, s in reqs
    )
    assert st4["tokens_per_dispatch"] > 1.0

    # eos mid-window: a token request 0 first generates mid-window
    # (admission emits generated token 0 and window n covers tokens
    # 4n+1..4n+4, so a first occurrence at index j cuts a window iff
    # j % 4 != 0). Not a fixed index: greedy tiny_gpt repeats its first
    # token for a while, and an eos equal to a request's FIRST token
    # finishes it at admission, before any window runs.
    gen = np.asarray(outs[0])[0, reqs[0][0].shape[1]:].tolist()
    eos = next(
        tok for j, tok in enumerate(gen) if j % 4 and tok not in gen[:j]
    )
    obs_reset()
    _, _ = serve_greedy(
        dec, params, reqs, max_batch=2, decode_window=4, eos_id=eos
    )
    assert reg.value("defer_window_truncated_total", **lab) > 0


def test_batch_gatherer_flush_reason_counters():
    """BatchGatherer flush accounting: a filled batch counts as
    "full", an SLO expiry as "timeout", a sentinel as "eos", an
    incompatible item as "mismatch"; occupancy lands in the rows
    histogram."""
    import queue

    from defer_tpu.runtime.batching import BatchGatherer
    from defer_tpu.runtime.host_io import STOP

    obs_reset()
    reg = get_registry()
    g = BatchGatherer(4, max_wait_s=0.02)
    q: "queue.Queue" = queue.Queue()

    # full: two 2-row items fill batch_size=4.
    q.put(np.zeros((2, 3), np.float32))
    q.put(np.zeros((2, 3), np.float32))
    batch, sizes, eos = g.gather(q)
    assert batch.shape[0] == 4 and not eos
    assert reg.value("defer_batch_flush_total", reason="full") == 1

    # timeout: one item, SLO expires.
    q.put(np.zeros((1, 3), np.float32))
    batch, sizes, eos = g.gather(q)
    assert sizes == [1] and not eos
    assert reg.value("defer_batch_flush_total", reason="timeout") == 1

    # mismatch: trailing-shape change flushes, odd item carries.
    q.put(np.zeros((1, 3), np.float32))
    q.put(np.zeros((1, 5), np.float32))
    g.gather(q)
    assert reg.value("defer_batch_flush_total", reason="mismatch") == 1
    assert g.pending()

    # eos: carried item flushes against the sentinel.
    q.put(STOP)
    batch, sizes, eos = g.gather(q)
    assert eos
    assert reg.value("defer_batch_flush_total", reason="eos") == 1

    rows = reg.value("defer_batch_rows")
    assert rows["count"] == 4  # one observation per flush


def test_codec_byte_counters_and_q8_no_double_count():
    """encode() books raw vs frame bytes once per public call — the
    Q8 path's inner lossless encode must NOT double-count."""
    from defer_tpu.runtime import codec

    obs_reset()
    reg = get_registry()
    a = np.linspace(-1, 1, 4096).astype(np.float32).reshape(64, 64)
    f1 = codec.encode(a, level=3)
    assert reg.value("defer_codec_raw_bytes_total") == a.nbytes
    assert reg.value("defer_codec_encoded_bytes_total") == len(f1)
    obs_reset()
    f2 = codec.encode(a, level=3, quantize="int8")
    # Exactly the original float bytes, not float + inner int8.
    assert reg.value("defer_codec_raw_bytes_total") == a.nbytes
    assert reg.value("defer_codec_encoded_bytes_total") == len(f2)
    np.testing.assert_allclose(
        codec.decode(f2), a, atol=2.0 / 127.0
    )


def test_disagg_metrics_names_and_serving_integration():
    """DisaggMetrics registers the disagg instrument family under the
    role label, serve_disagg drives them, and every name passes the
    obs-name-drift conventions (counters end _total, etc. — the
    analysis lint pins the same rules statically)."""
    from defer_tpu.obs import DisaggMetrics
    from defer_tpu.disagg import serve_disagg

    obs_reset()
    m = DisaggMetrics("prefill")
    snap = m.registry.to_dict()
    flat = {**snap["counters"], **snap["histograms"]}
    for name in (
        'defer_kv_blocks_shipped_total{role="prefill"}',
        'defer_kv_block_bytes_sent_total{role="prefill"}',
        'defer_kv_block_bytes_recv_total{role="prefill"}',
        'defer_kv_ingest_wait_seconds{role="prefill"}',
        'defer_disagg_worker_restarts_total{role="prefill"}',
    ):
        assert name in flat, name

    dec = tiny_gpt(64)
    params = dec.init(jax.random.key(0))
    reqs = [(jnp.asarray([[3, 9, 27, 1, 4]], jnp.int32), 4)]
    _, stats = serve_disagg(
        dec, params, reqs, num_blocks=8, block_size=4, max_batch=2
    )
    reg = m.registry
    shipped = reg.value(
        "defer_kv_blocks_shipped_total", role="prefill"
    )
    assert shipped == 2  # ceil(5 / 4) blocks for the one request
    sent = reg.value("defer_kv_block_bytes_sent_total", role="prefill")
    recvd = reg.value("defer_kv_block_bytes_recv_total", role="decode")
    assert sent == recvd == stats["kv_bytes_recv"] > 0
    # the payload waited in the ingest queue at least once
    hist = reg.value("defer_kv_ingest_wait_seconds", role="decode")
    assert hist["count"] == 1
