"""Fleet serving: routing over replicas must be invisible to outputs.

`serve_fleet` places each request on one of N paged replicas by cache
locality; per-slot decode independence means placement (and admission
timing) may not perturb a single greedy token — n_replicas=1 AND
n_replicas=2 must be TOKEN-IDENTICAL to `serve_paged`. Around that
contract: the router's decision ladder is deterministic (equal load
breaks ties by index, every run), replica death re-routes queued work
and fails in-flight work loudly, shedding is a synchronous typed
rejection (never a hang), and prefix migration moves real KV blocks
without changing tokens."""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from defer_tpu.disagg import wire
from defer_tpu.fleet import (
    AdmissionController,
    AdvertisementBoard,
    FleetFrontend,
    PrefixRouter,
    ReplicaDeadError,
    ShedError,
    chain_digests,
    serve_fleet,
)
from defer_tpu.models.gpt import SamplingParams, tiny_gpt
from defer_tpu.obs import FleetMetrics
from defer_tpu.runtime.paged import PagedDecodeServer, serve_paged
from defer_tpu.runtime.transport import ArrayReceiver, ArraySender


@pytest.fixture(scope="module")
def model():
    dec = tiny_gpt(64)
    params = dec.init(jax.random.key(0))
    return dec, params


def _requests(vocab):
    return [
        (jnp.asarray([[3, 9, 27, 1, 4, 4, 2, 8]], jnp.int32) % vocab, 7),
        (jnp.asarray([[5, 1]], jnp.int32), 4),
        (jnp.asarray([[11, 2, 8, 1, 6]], jnp.int32) % vocab, 6),
        (jnp.asarray([[3, 9, 27, 1, 4, 4, 2, 8]], jnp.int32) % vocab, 5),
    ]


def _fresh_obs(n: int) -> FleetMetrics:
    """FleetMetrics over the process-global registry with the load
    gauges zeroed — unit tests must not inherit a previous test's
    parting gauge values (the same reset FleetFrontend does)."""
    obs = FleetMetrics(n)
    for i in range(n):
        obs.queue_depth[i].set(0)
        obs.inflight[i].set(0)
        obs.pool_free[i].set(0)
    return obs


def _hold_all(fe):
    """Set hold_admissions on every replica AND outwait the idle
    blocking pop: a replica already parked inside its 1ms
    `try_pop(timeout=...)` when the flag flips can still take one item
    submitted into that window — settle past it so 'held' means held."""
    for r in fe.replicas:
        r.hold_admissions = True
    time.sleep(0.05)


def _wait_until(pred, timeout=10.0, msg="condition"):
    t0 = time.monotonic()
    while time.monotonic() - t0 < timeout:
        if pred():
            return
        time.sleep(0.005)
    raise AssertionError(f"timed out waiting for {msg}")


# -- token-identity with serve_paged ----------------------------------


@pytest.mark.parametrize("prefix_cache", [False, True])
@pytest.mark.parametrize("n_replicas", [1, 2])
def test_fleet_token_identical_to_serve_paged(
    model, n_replicas, prefix_cache
):
    """The acceptance bar: greedy outputs equal serve_paged's at one
    replica (same class, nothing to route) AND at two (placement may
    not perturb a token — per-slot decode independence)."""
    dec, params = model
    reqs = _requests(dec.cfg.vocab_size)
    kw = dict(
        num_blocks=16, block_size=4, max_batch=2,
        prefix_cache=prefix_cache,
    )
    mono, _ = serve_paged(dec, params, reqs, **kw)
    outs, stats = serve_fleet(
        dec, params, reqs, n_replicas=n_replicas, **kw
    )
    for i, (a, b) in enumerate(zip(mono, outs)):
        np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b),
            err_msg=f"n_replicas={n_replicas} "
                    f"prefix_cache={prefix_cache} request {i}",
        )
    assert stats["n_replicas"] == n_replicas
    assert sum(stats["routed"].values()) == len(reqs)
    assert stats["shed"] == {"queue_full": 0, "slo": 0}
    assert len(stats["replicas"]) == n_replicas
    assert all(r["dead"] is None for r in stats["replicas"])


def test_fleet_sampled_request_parity(model):
    """Seeded sampling rides the routed request; streams must match
    monolithic serving per request."""
    dec, params = model
    reqs = _requests(dec.cfg.vocab_size)
    samps = [
        SamplingParams(temperature=0.8, top_k=8, seed=11),
        None,
        SamplingParams(temperature=1.1, top_p=0.9, seed=3),
        None,
    ]
    kw = dict(num_blocks=16, block_size=4, max_batch=2)
    mono, _ = serve_paged(dec, params, reqs, sampling=samps, **kw)
    outs, _ = serve_fleet(
        dec, params, reqs, n_replicas=2, sampling=samps, **kw
    )
    for i, (a, b) in enumerate(zip(mono, outs)):
        np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b), err_msg=f"request {i}"
        )


# -- digest advertisement seam (runtime/paged.py satellite) -----------


def test_resident_digests_generation_and_keys(model):
    """`resident_digests` snapshots exactly the radix key set, and the
    generation moves only when the resident KEY SET changes — the one
    int the replica's advertisement fast path compares."""
    dec, params = model
    srv = PagedDecodeServer(
        dec, params, num_blocks=16, block_size=4, max_batch=2,
        prefix_cache=True,
    )
    gen0, d0 = srv.resident_digests()
    assert d0 == frozenset()
    prompt = jnp.asarray([[3, 9, 27, 1, 4, 4, 2, 8]], jnp.int32)
    rid = srv.submit(prompt, 3)
    while rid not in srv.done:
        srv._admit()
        srv._tick()
    gen1, d1 = srv.resident_digests()
    assert gen1 > gen0
    # The prompt's two full blocks are keyed by the router's own
    # chaining — bit-for-bit, or every fleet lookup would miss.
    assert set(chain_digests(prompt, 2, 4)) <= d1
    evicted = srv.radix.evict(1)
    assert evicted
    gen2, d2 = srv.resident_digests()
    assert gen2 > gen1 and len(d2) == len(d1) - 1


def test_resident_digests_without_radix(model):
    dec, params = model
    srv = PagedDecodeServer(
        dec, params, num_blocks=8, block_size=4, max_batch=1
    )
    assert srv.resident_digests() == (0, frozenset())


# -- router decision ladder -------------------------------------------


def _router(n=2, **kw):
    obs = _fresh_obs(n)
    board = AdvertisementBoard(n)
    return PrefixRouter(board, obs, **kw), board, obs


def _toks(n_tokens=8):
    return np.arange(n_tokens, dtype=np.int64).reshape(1, -1)


def test_router_tie_break_is_deterministic():
    """Equal depth + equal load must pick the SAME replica every call
    (lower index) — reproducible placement under a balanced fleet."""
    router, board, _ = _router()
    keys = chain_digests(_toks(), 2, 4)
    board.publish(0, 1, frozenset(keys))
    board.publish(1, 1, frozenset(keys))
    for _ in range(5):
        d = router.route(_toks(), 2, 4, [True, True])
        assert (d.replica, d.reason, d.depth) == (0, "prefix", 2)
        assert d.keys == keys


def test_router_routes_least_loaded_when_no_prefix():
    router, _, obs = _router()
    d = router.route(_toks(), 2, 4, [True, True])
    assert (d.replica, d.reason) == (0, "load")  # tie -> lower index
    obs.queue_depth[0].set(3)
    d = router.route(_toks(), 2, 4, [True, True])
    assert (d.replica, d.reason) == (1, "load")


def test_router_dead_holder_is_fallback_not_load():
    router, board, _ = _router()
    board.publish(0, 1, frozenset(chain_digests(_toks(), 2, 4)))
    d = router.route(_toks(), 2, 4, [False, True])
    assert (d.replica, d.reason, d.depth) == (1, "fallback", 2)


def test_router_migrates_off_overloaded_holder():
    router, board, obs = _router(migrate_gap=4)
    keys = chain_digests(_toks(), 2, 4)
    board.publish(0, 1, frozenset(keys))
    obs.queue_depth[0].set(10)
    d = router.route(_toks(), 2, 4, [True, True])
    assert (d.replica, d.reason, d.source) == (1, "migrate", 0)
    assert d.keys == keys
    # Below the gap the holder keeps the request.
    obs.queue_depth[0].set(3)
    d = router.route(_toks(), 2, 4, [True, True])
    assert (d.replica, d.reason) == (0, "prefix")


def test_router_migrate_disabled_falls_back():
    router, board, obs = _router(migrate=False)
    board.publish(0, 1, frozenset(chain_digests(_toks(), 2, 4)))
    obs.queue_depth[0].set(10)
    d = router.route(_toks(), 2, 4, [True, True])
    assert (d.replica, d.reason) == (1, "fallback")


def test_router_round_robin_rotates_over_live():
    router, _, _ = _router(policy="round_robin")
    seq = [
        router.route(_toks(), 2, 4, [True, True]).replica
        for _ in range(4)
    ]
    assert seq == [0, 1, 0, 1]
    assert router.route(_toks(), 2, 4, [False, True]).replica == 1


def test_router_rejects_bad_policy_and_empty_fleet():
    with pytest.raises(ValueError, match="policy"):
        _router(policy="random")
    router, _, _ = _router()
    with pytest.raises(RuntimeError, match="no live replicas"):
        router.route(_toks(), 2, 4, [False, False])


# -- admission + shedding ---------------------------------------------


def test_admission_rolling_p99_and_pop():
    ctl = AdmissionController(1, _fresh_obs(1), slo_s=None)
    assert ctl.wait_p99(0) == 0.0
    assert ctl.try_pop(0) is None
    ctl.admit(0, "a")
    assert ctl.depth(0) == 1
    assert ctl.try_pop(0) == "a"
    assert ctl.depth(0) == 0
    ctl2 = AdmissionController(1, _fresh_obs(1))
    for w in [0.01] * 99 + [5.0]:
        ctl2.record_wait(0, w)
    assert ctl2.wait_p99(0) == 5.0  # the tail sample IS the p99


def test_shed_on_slo_is_synchronous(model):
    """Once the rolling queue-wait p99 exceeds the SLO, submit()
    raises a typed ShedError immediately — and the shed request can
    never be waited on into a hang."""
    dec, params = model
    fe = FleetFrontend(
        dec, params, n_replicas=2, num_blocks=16, block_size=4,
        max_batch=2, slo_s=0.01,
    )
    try:
        for i in range(2):
            fe.controller.record_wait(i, 0.5)
        t0 = time.monotonic()
        with pytest.raises(ShedError) as ei:
            fe.submit(jnp.asarray([[5, 1]], jnp.int32), 4)
        assert time.monotonic() - t0 < 1.0
        assert ei.value.reason == "slo"
        assert ei.value.wait_p99_s == pytest.approx(0.5)
        assert fe.stats()["shed"]["slo"] == 1
        with pytest.raises(KeyError):
            fe.result(0)  # the shed request's future was torn down
    finally:
        fe.close()


def test_shed_on_full_queue_never_hangs(model):
    """Held replicas + bounded queues: the overflow submit is rejected
    within the enqueue deadline, and the admitted backlog still drains
    once the replicas resume."""
    dec, params = model
    fe = FleetFrontend(
        dec, params, n_replicas=2, num_blocks=16, block_size=4,
        max_batch=2, max_queue=1, enqueue_wait_s=0.05,
    )
    try:
        _hold_all(fe)
        reqs = _requests(dec.cfg.vocab_size)
        g0 = fe.submit(*reqs[0])
        g1 = fe.submit(*reqs[1])
        t0 = time.monotonic()
        with pytest.raises(ShedError) as ei:
            fe.submit(*reqs[2])
        assert time.monotonic() - t0 < 5.0
        assert ei.value.reason == "queue_full"
        for r in fe.replicas:
            r.hold_admissions = False
        mono, _ = serve_paged(
            dec, params, reqs[:2], num_blocks=16, block_size=4,
            max_batch=2,
        )
        np.testing.assert_array_equal(
            np.asarray(fe.result(g0, timeout=60)), np.asarray(mono[0])
        )
        np.testing.assert_array_equal(
            np.asarray(fe.result(g1, timeout=60)), np.asarray(mono[1])
        )
    finally:
        fe.close()


# -- replica death ----------------------------------------------------


def test_replica_death_reroutes_queued_requests(model):
    """Requests still parked in a dead replica's admission queue were
    never touched — they must re-route and complete with the exact
    tokens a healthy fleet produces."""
    dec, params = model
    reqs = _requests(dec.cfg.vocab_size)
    fe = FleetFrontend(
        dec, params, n_replicas=2, num_blocks=16, block_size=4,
        max_batch=2,
    )
    try:
        _hold_all(fe)
        gid = fe.submit(*reqs[0])
        victim = next(
            i for i in range(2) if fe.controller.depth(i) == 1
        )
        survivor = 1 - victim
        fe.replicas[victim].inject_failure(RuntimeError("boom"))
        # The dying thread sets `dead` and then runs the frontend's
        # death protocol, which drops the replica from routing.
        _wait_until(
            lambda: fe.replicas[victim].dead is not None
            and not fe.alive[victim],
            msg="replica death",
        )
        fe.replicas[survivor].hold_admissions = False
        mono, _ = serve_paged(
            dec, params, reqs[:1], num_blocks=16, block_size=4,
            max_batch=2,
        )
        np.testing.assert_array_equal(
            np.asarray(fe.result(gid, timeout=60)), np.asarray(mono[0])
        )
        # The fleet keeps serving minus the dead replica ...
        g2 = fe.submit(*reqs[1])
        fe.result(g2, timeout=60)
        stats = fe.stats()
        assert stats["replicas"][victim]["dead"] is not None
        assert stats["replicas"][survivor]["dead"] is None
        # ... and a cross-thread op against the corpse is loud.
        with pytest.raises(ReplicaDeadError):
            fe.replicas[victim].call(lambda srv: srv.ticks)
    finally:
        fe.close()


def test_replica_death_fails_inflight_requests(model):
    """In-flight requests died with the server's pool — they surface
    as ReplicaDeadError from result(), never a silent retry."""
    dec, params = model
    fe = FleetFrontend(
        dec, params, n_replicas=2, num_blocks=32, block_size=4,
        max_batch=2,
    )
    try:
        gid = fe.submit(jnp.asarray([[5, 1, 7, 2]], jnp.int32), 50)
        victim = None

        def seated():
            nonlocal victim
            for i, r in enumerate(fe.replicas):
                if r.inflight_gids:
                    victim = i
                    return True
            return False

        _wait_until(seated, msg="request in flight")
        fe.replicas[victim].inject_failure(RuntimeError("pool gone"))
        with pytest.raises(ReplicaDeadError, match="pool gone"):
            fe.result(gid, timeout=60)
    finally:
        fe.close()


def test_last_replica_death_fails_queued_requests(model):
    """With no survivors, re-routing has nowhere to go: queued
    requests fail typed instead of waiting forever."""
    dec, params = model
    fe = FleetFrontend(
        dec, params, n_replicas=1, num_blocks=16, block_size=4,
        max_batch=2,
    )
    try:
        _hold_all(fe)
        gid = fe.submit(jnp.asarray([[5, 1]], jnp.int32), 4)
        fe.replicas[0].inject_failure(RuntimeError("boom"))
        with pytest.raises((RuntimeError, ReplicaDeadError)):
            fe.result(gid, timeout=60)
    finally:
        fe.close()


# -- prefix routing + migration end to end ----------------------------


def _holder(fe, timeout=10.0):
    """Index of the replica whose advertisement is non-empty."""
    box = {}

    def some():
        for i, (_, dig, _) in enumerate(fe.board.snapshot()):
            if dig:
                box["idx"] = i
                return True
        return False

    _wait_until(some, timeout, "a digest advertisement")
    return box["idx"]


def test_prefix_routing_follows_the_cache(model):
    """After one request seeds a replica's radix cache and the advert
    lands, a same-prefix request routes to the holder by reason
    'prefix' — the routing signal the whole subsystem exists for."""
    dec, params = model
    fe = FleetFrontend(
        dec, params, n_replicas=2, num_blocks=16, block_size=4,
        max_batch=2, prefix_cache=True,
    )
    shared = jnp.asarray([[3, 9, 27, 1, 4, 4, 2, 8]], jnp.int32)
    try:
        fe.result(fe.submit(shared, 5), timeout=60)
        holder = _holder(fe)
        saved0 = fe.replicas[holder].srv.prefill_tokens_saved
        p2 = jnp.concatenate(
            [shared, jnp.asarray([[7, 7]], jnp.int32)], axis=1
        )
        fe.result(fe.submit(p2, 4), timeout=60)
        assert fe.routed["prefix"] == 1
        # The routed request actually reused the resident blocks.
        assert fe.replicas[holder].srv.prefill_tokens_saved > saved0
    finally:
        fe.close()


def test_migration_moves_blocks_and_keeps_tokens(model):
    """An overloaded holder's prefix chain ships to the least-loaded
    replica (disagg wire payload, real pool writes on both ends) and
    the rerouted request's tokens are unchanged."""
    dec, params = model
    shared = jnp.asarray([[3, 9, 27, 1, 4, 4, 2, 8]], jnp.int32)
    p2 = jnp.concatenate(
        [shared, jnp.asarray([[7, 7]], jnp.int32)], axis=1
    )
    ref, _ = serve_paged(
        dec, params, [(p2, 4)], num_blocks=16, block_size=4,
        max_batch=2, prefix_cache=True,
    )
    fe = FleetFrontend(
        dec, params, n_replicas=2, num_blocks=16, block_size=4,
        max_batch=2, prefix_cache=True, migrate_gap=4,
    )
    try:
        fe.result(fe.submit(shared, 5), timeout=60)
        holder = _holder(fe)
        # Fake a deep backlog on the holder: the queue_depth gauge is
        # admission-owned, so the replica loop won't overwrite it.
        fe.obs.queue_depth[holder].set(10)
        out = fe.result(fe.submit(p2, 4), timeout=60)
        assert fe.routed["migrate"] == 1
        assert fe.migrated_blocks == 2  # the prompt's two full blocks
        np.testing.assert_array_equal(
            np.asarray(out), np.asarray(ref[0])
        )
        # The chain is now resident on BOTH replicas.
        gen, dig = fe.replicas[1 - holder].srv.resident_digests()
        assert set(chain_digests(shared, 2, 4)) <= dig
    finally:
        fe.close()


# -- prefix payload wire format ---------------------------------------


def test_prefix_payload_loopback_round_trip():
    """Token bytes and lossless K/V block stacks survive real sockets
    bit-exactly (a migrated block becomes shared cache state — lossy
    transport would skew every future sharer)."""
    rng = np.random.default_rng(5)
    toks = [
        np.arange(4, dtype=np.int64).tobytes(),
        np.arange(4, 8, dtype=np.int64).tobytes(),
    ]
    pay = wire.PrefixPayload(
        toks=toks,
        k=rng.standard_normal((3, 2, 2, 4, 8)).astype(np.float32),
        v=rng.standard_normal((3, 2, 2, 4, 8)).astype(np.float32),
    )
    recv = ArrayReceiver(0, host="127.0.0.1", accept_timeout_s=10.0)
    got = []
    import threading

    def drain():
        it = iter(recv)
        got.append(wire.read_prefix_payload(it, recv))

    t = threading.Thread(target=drain)
    t.start()
    send = ArraySender("127.0.0.1", recv.port)
    n = wire.send_prefix_payload(send, pay)
    send.close()
    t.join(timeout=10)
    recv.close()
    out = got[0]
    assert out.toks == toks
    np.testing.assert_array_equal(out.k, pay.k)
    np.testing.assert_array_equal(out.v, pay.v)
    assert out.wire_bytes == n == recv.rx_frame_bytes


def test_prefix_payload_toks_shape_mismatch_is_loud():
    pay = wire.PrefixPayload(
        toks=[b"x"],
        k=np.zeros((1, 2, 1, 4, 2), np.float32),
        v=np.zeros((1, 2, 1, 4, 2), np.float32),
    )
    with pytest.raises(ValueError, match="token blobs"):
        wire.send_prefix_payload(object(), pay)
