"""Device-pinned pipeline runtime for heterogeneous stage chains.

This is the TPU-native replacement for the reference's entire data plane:
its per-node recv/compute/send thread pairs (reference src/node.py:97-133),
bounded hand-off queues (src/node.py:139), TCP framing
(src/node_state.py:43-101) and ZFP+LZ4 codec (src/node.py:93-96) all
collapse into:

  * one jit-compiled XLA program per stage, pinned to its own TPU core
    (parameters committed there once at load, like the reference's
    one-time weight dispatch, src/dispatcher.py:47-63);
  * `jax.device_put` core-to-core activation transfers that ride ICI —
    no serialization, no compression, no sockets;
  * JAX's asynchronous dispatch as the pipelining engine: the host
    enqueues microbatch t on stage 0 while stage k still computes
    microbatch t-k, so all stages overlap exactly as the reference's
    thread pipeline does, minus the Python in the hot loop.

Backpressure (the reference's bounded queues, src/test.py:44) becomes a
cap on in-flight microbatches enforced by blocking on the oldest result.
"""

from __future__ import annotations

import time
from typing import Any, Iterable, Iterator, Sequence

import jax
import jax.numpy as jnp

from defer_tpu.config import DeferConfig
from defer_tpu.graph.ir import Graph, GraphParams
from defer_tpu.graph.partition import StageGraph, stage_params
from defer_tpu.obs.metrics import get_registry
from defer_tpu.utils.logging import get_logger
from defer_tpu.utils.profiling import annotate
from defer_tpu.utils.sync import Retirer, hard_sync

log = get_logger(__name__)


def cast_params_to_storage(params: Any, config: DeferConfig) -> Any:
    """Cast floating-point param leaves to config.storage_dtype once at
    placement time — casting inside every stage call would cost an
    extra HBM pass per microbatch (~10% ResNet50 throughput on v5e)."""
    sd = config.storage_dtype
    if not jnp.issubdtype(sd, jnp.floating):
        return params
    return jax.tree_util.tree_map(
        lambda a: a.astype(sd)
        if jnp.issubdtype(a.dtype, jnp.floating)
        else a,
        params,
    )


def probe_latency(fn: Any, *args: Any, iters: int = 10) -> dict[str, Any]:
    """Synchronous latency sample for one compiled callable — the
    timing core `Pipeline.probe_stage_latencies` reports per stage,
    extracted so other stage chains (the paged server's pp layer
    probe) measure with identical methodology. Runs one untimed call
    first (compile), then `iters` hard-synced calls for the p50, then
    one amortized window (dispatch `iters`, one barrier)."""
    hard_sync(fn(*args))  # ensure compiled
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        out = fn(*args)
        hard_sync(out)
        times.append(time.perf_counter() - t0)
    times.sort()
    t0 = time.perf_counter()
    outs = [fn(*args) for _ in range(iters)]
    hard_sync(outs[-1])
    amortized = (time.perf_counter() - t0) / iters
    return {
        "p50_s": times[len(times) // 2],
        "p99_s": times[int(len(times) * 0.99)] if len(times) >= 100 else None,
        "max_s": times[-1],
        "min_s": times[0],
        "amortized_s": amortized,
    }


def balance_stage_cuts(costs: Sequence[float], num_stages: int) -> list[int]:
    """Contiguous min-max partition of per-layer costs into
    `num_stages` stages: returns the stage START indices
    (cuts[0] == 0), chosen so the most expensive stage is as cheap as
    possible. Exact O(L^2 * S) DP — layer counts are tens, not
    thousands. Every stage is non-empty, so num_stages must not
    exceed len(costs)."""
    L = len(costs)
    if num_stages < 1:
        raise ValueError(f"num_stages must be >= 1, got {num_stages}")
    if num_stages > L:
        raise ValueError(
            f"cannot split {L} layers into {num_stages} non-empty "
            "stages"
        )
    prefix = [0.0]
    for c in costs:
        prefix.append(prefix[-1] + float(c))

    def span(i: int, j: int) -> float:
        return prefix[j] - prefix[i]

    # best[s][j] = minimal max-stage-cost splitting costs[:j] into s
    # stages; cut[s][j] = start of the last stage in that optimum.
    INF = float("inf")
    best = [[INF] * (L + 1) for _ in range(num_stages + 1)]
    cut = [[0] * (L + 1) for _ in range(num_stages + 1)]
    best[0][0] = 0.0
    for s in range(1, num_stages + 1):
        for j in range(s, L + 1):
            for i in range(s - 1, j):
                cand = max(best[s - 1][i], span(i, j))
                if cand < best[s][j]:
                    best[s][j] = cand
                    cut[s][j] = i
    starts: list[int] = []
    j = L
    for s in range(num_stages, 0, -1):
        i = cut[s][j]
        starts.append(i)
        j = i
    starts.reverse()
    return starts


class StreamMeasure:
    """Shared warmup/throughput for anything with __call__ + stream
    (Pipeline, ShardedInference, ReplicatedPipeline) — one definition
    of the measurement protocol, the analogue of the reference's timed
    result counting (reference src/test.py:33-41)."""

    def warmup(self, x: Any) -> jax.Array:
        """Compile (first XLA compile is slow; do it before timing —
        the analogue of the reference's settling sleep, reference
        src/dispatcher.py:126, but deterministic)."""
        out = self(x)
        hard_sync(out)
        return out

    def throughput(
        self, x: Any, num_microbatches: int = 256
    ) -> dict[str, float]:
        self.warmup(x)
        t0 = time.perf_counter()
        n = 0
        last = None
        for out in self.stream(x for _ in range(num_microbatches)):
            last = out
            n += 1
        # A true completion barrier: device program order guarantees the
        # last output retires after every earlier same-program execution
        # (replicated runtimes warm every replica above, and their last
        # round covers each replica's tail).
        hard_sync(last)
        dt = time.perf_counter() - t0
        batch = int(x.shape[0]) if hasattr(x, "shape") and x.ndim > 0 else 1
        return {
            "microbatches": n,
            "seconds": dt,
            "microbatches_per_sec": n / dt,
            "items_per_sec": n * batch / dt,
        }


class Pipeline(StreamMeasure):
    """A chain of jit-compiled stages, each pinned to one device."""

    def __init__(
        self,
        stages: Sequence[Graph | StageGraph],
        params: GraphParams,
        devices: Sequence[jax.Device],
        config: DeferConfig | None = None,
    ):
        if len(devices) != len(stages):
            raise ValueError(
                f"{len(stages)} stages need {len(stages)} devices, "
                f"got {len(devices)}"
            )
        self.config = config or DeferConfig()
        self.stages = list(stages)
        self.devices = list(devices)
        cd = self.config.compute_dtype

        self.stage_params: list[Any] = []
        self.stage_fns: list[Any] = []
        # Non-donating twins, used where an input must survive the call
        # (latency probing re-times the same activation repeatedly).
        self._plain_fns: list[Any] = []
        for i, (stage, dev) in enumerate(zip(self.stages, self.devices)):
            sp = jax.device_put(
                cast_params_to_storage(stage_params(params, stage), self.config),
                dev,
            )
            self.stage_params.append(sp)

            def stage_apply(p, x, _stage=stage, _cd=cd):
                # Integer inputs (token ids) must keep their dtype.
                # x may be a tuple (multi-tensor boundary).
                x = jax.tree_util.tree_map(
                    lambda a: a.astype(_cd)
                    if jnp.issubdtype(a.dtype, jnp.floating)
                    else a,
                    x,
                )
                return _stage.apply(p, x)

            # Stage 0's input is caller-owned (device_put of an array
            # already on the device aliases it) — never donate that.
            # Later stages consume pipeline-owned transfer buffers.
            donate = (1,) if self.config.donate_activations and i > 0 else ()
            # analysis: ignore[fresh-closure-jit] one jit per STAGE at
            # construction, held in stage_fns for the pipeline's
            # lifetime — never rebuilt per call
            self.stage_fns.append(jax.jit(stage_apply, donate_argnums=donate))
            # analysis: ignore[fresh-closure-jit] same: built once,
            # cached on the instance
            self._plain_fns.append(jax.jit(stage_apply))
        # One shared counter across every Pipeline (incl. the ones a
        # ReplicatedPipeline builds per replica): total microbatches
        # dispatched process-wide.
        self._obs_microbatches = get_registry().counter(
            "defer_pipeline_microbatches_total",
            "Microbatches dispatched through a stage chain",
        )

    @property
    def num_stages(self) -> int:
        return len(self.stages)

    # -- execution -------------------------------------------------------

    @staticmethod
    def _place(x: Any, dev: jax.Device) -> Any:
        """device_put only when an array isn't already resident on
        `dev` — a redundant device_put of a host-uncommitted array
        re-transfers the whole buffer from the host. Tree-aware for
        multi-tensor boundary tuples."""
        return jax.tree_util.tree_map(
            lambda a: a
            if isinstance(a, jax.Array) and a.sharding.device_set == {dev}
            else jax.device_put(a, dev),
            x,
        )

    def __call__(self, x: jax.Array) -> jax.Array:
        """Push one microbatch through the chain (async — the returned
        array is a future; block_until_ready() to wait)."""
        self._obs_microbatches.inc()
        h = self._place(x, self.devices[0])
        for i, (fn, p) in enumerate(zip(self.stage_fns, self.stage_params)):
            with annotate(f"defer:stage{i}"):
                if i > 0:
                    h = self._place(h, self.devices[i])
                h = fn(p, h)
        return h

    # Uniform submission point for stream loops: replicated runtimes
    # override this to fan successive microbatches across replicas.
    submit = __call__

    def stream(
        self,
        inputs: Iterable[Any],
        *,
        max_inflight: int | None = None,
    ) -> Iterator[jax.Array]:
        """Stream microbatches through the pipeline with bounded
        in-flight depth; yields outputs in order.

        The analogue of the reference's steady-state hot loop
        (SURVEY.md §3.3): feed thread + per-node threads + result
        server, here a single loop over async dispatches.
        """
        depth = max_inflight or self.config.max_inflight
        retirer = Retirer(depth)
        for x in inputs:
            # Backpressure: Retirer emits the known-ready prefix for
            # free and, at depth, takes one batched barrier on the
            # middle of the window — never waits per item
            # (utils/sync.py).
            yield from retirer.add(self(x))
        yield from retirer.flush()

    # -- measurement (warmup/throughput come from StreamMeasure) ---------

    def probe_stage_latencies(
        self, x: Any, iters: int = 10
    ) -> list[dict[str, Any]]:
        """Per-stage latency in seconds, measured synchronously
        (BASELINE.json's metric asks for per-stage p50). Run outside the
        streaming loop so probing doesn't break overlap. `p99_s` is only
        reported when iters >= 100 — below that the 99th percentile of
        the sample IS its max, so `max_s` carries it honestly instead."""
        h = self._place(x, self.devices[0])
        results = []
        for i, (fn, p) in enumerate(zip(self._plain_fns, self.stage_params)):
            if i > 0:
                h = self._place(h, self.devices[i])
                hard_sync(h)
            # Amortized half excludes the per-call host sync round
            # trip (probe_latency docstring has the methodology).
            sample = probe_latency(fn, p, h, iters=iters)
            amortized = sample["amortized_s"]
            results.append(
                {"stage": i, "device": str(self.devices[i]), **sample}
            )
            # Cold path: registry lookup per probe is fine here.
            reg = get_registry()
            labels = {"stage": str(i)}
            reg.gauge(
                "defer_stage_amortized_seconds",
                "Amortized per-microbatch stage time (last probe)",
                labels,
            ).set(amortized)
            reg.gauge(
                "defer_stage_p50_seconds",
                "Synchronous p50 stage latency (last probe)",
                labels,
            ).set(sample["p50_s"])
            h = fn(p, h)
        return results
