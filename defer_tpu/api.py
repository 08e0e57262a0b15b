"""The DEFER facade — the reference's user-facing API, TPU-native.

Reference usage (src/test.py:20-21,44-50):

    defer = DEFER(['192.168.31.225', '192.168.31.215'])
    defer.run_defer(model, ["add_8"], input_q, output_q)   # in a thread

Here:

    defer = DEFER()                          # TPU mesh auto-discovered
    defer.run_defer(model, ["add_8"], input_q, output_q)

`run_defer` keeps the reference's blocking, queue-driven contract
(reference src/dispatcher.py:120-129) so driver scripts port unchanged,
but "dispatch" is partition + per-core jit compile + parameter placement
instead of sockets, and the stream loop is the async pipeline.
"""

from __future__ import annotations

import collections
import queue
import threading
import time
from typing import Any, Sequence

import jax
import jax.numpy as jnp

from defer_tpu.config import DeferConfig, normalize_cuts
from defer_tpu.graph.ir import Graph, GraphParams
from defer_tpu.graph.partition import partition
from defer_tpu.models import Model
from defer_tpu.obs.metrics import get_registry
from defer_tpu.parallel.mesh import pipeline_devices
from defer_tpu.parallel.pipeline import Pipeline
from defer_tpu.runtime.batching import split_output
from defer_tpu.runtime.host_io import STOP, ProgressMonitor
from defer_tpu.utils import profiling
from defer_tpu.utils.logging import get_logger
from defer_tpu.utils.memo import jit_cached
from defer_tpu.utils.sync import Retirer, hard_sync, hard_sync_timeout

log = get_logger(__name__)


class DEFER:
    """Pipeline-parallel inference orchestrator.

    Replaces the reference's dispatcher (reference src/dispatcher.py:22):
    instead of an IP list it takes an optional explicit device list
    (default: every device JAX can see — the TPU slice).
    """

    def __init__(
        self,
        devices: Sequence[jax.Device] | None = None,
        config: DeferConfig | None = None,
    ):
        self.devices = list(devices) if devices is not None else None
        self.config = config or DeferConfig()
        self._stop = threading.Event()
        self.last_pipeline: Pipeline | None = None
        # Filled by run_defer when config.probe_every > 0.
        self.last_stage_latencies: list[dict[str, float]] | None = None

    # -- construction ----------------------------------------------------

    def build_pipeline(
        self,
        model: Model | Graph | str,
        partition_layers: Sequence[str | Sequence[str]] | str | None,
        *,
        params: GraphParams | None = None,
        rng: jax.Array | None = None,
        batch_size: int = 1,
        replicas: int = 1,
    ) -> tuple[Any, Any]:
        """Partition + compile; returns (pipeline, example_input).

        The analogue of `_partition` + `_dispatchModels` (reference
        src/dispatcher.py:30-73): cut points become stage graphs, weight
        shipping becomes `device_put` of each stage's param slice.

        partition_layers="auto" picks FLOPs-balanced boundaries from
        the discovered candidates, one stage per device — the cut list
        the reference makes the user find by hand (reference
        src/test.py:24-28).

        replicas > 1 composes data parallelism with the stage chain:
        the whole pipeline is replicated that many times (over
        replicas x stages devices) and the stream fans microbatches
        across replicas round-robin — the scaling axis the reference
        doesn't have (its only lever is a deeper chain).
        """
        auto = (
            isinstance(partition_layers, str) and partition_layers == "auto"
        )
        cuts = () if auto else normalize_cuts(partition_layers)
        if isinstance(model, str):
            # The reference's wire format: a Keras model.to_json()
            # string (reference src/dispatcher.py:52).
            from defer_tpu.graph.keras_import import model_from_keras

            model, _ = model_from_keras(model)
        if isinstance(model, Model):
            graph = model.graph
            example = model.example_input(batch_size)
        else:
            graph = model
            example = None
        if params is None:
            if not isinstance(model, Model):
                raise ValueError("params required when passing a raw Graph")
            params = model.init(
                rng if rng is not None else jax.random.key(0),
                batch_size=batch_size,
                # Init in fp32 (stable RNG/statistics); Pipeline casts
                # to the storage dtype at placement.
                param_dtype=jnp.float32,
            )
        if auto:
            from defer_tpu.graph.partition import chain_boundaries
            from defer_tpu.utils.flops import balanced_cuts

            n_dev = len(
                self.devices if self.devices is not None else jax.devices()
            )
            cands = (
                model.cut_candidates
                if isinstance(model, Model) and model.cut_candidates
                else chain_boundaries(graph)
            )
            # Each replica needs its own stage slots: claiming all
            # n_dev for one replica's stages would make _compile wrap
            # further replicas round-robin onto the SAME chips —
            # contention, not throughput.
            n_stages = min(max(1, n_dev // max(1, replicas)), len(cands) + 1)
            if example is None:
                raise ValueError(
                    'partition_layers="auto" needs a Model (a raw Graph '
                    "has no input shape to balance FLOPs against)"
                )
            ex_leaf = jax.tree_util.tree_leaves(example)[0]
            cuts = tuple(
                balanced_cuts(
                    graph,
                    params,
                    tuple(int(d) for d in ex_leaf.shape),
                    n_stages,
                    cands,
                    input_dtype=ex_leaf.dtype,
                )
            )
            log.info("auto cuts (%d stages): %s", n_stages, cuts)
        stages = partition(graph, cuts) if cuts else [graph]
        pipe = self._compile(stages, params, replicas, None)
        self.last_pipeline = pipe
        # Retained for elastic re-dispatch after a stage failure.
        self._build_state = (stages, params, replicas)
        return pipe, example

    def _compile(
        self,
        stages: Sequence[Any],
        params: GraphParams,
        replicas: int,
        device_pool: Sequence[jax.Device] | None,
    ) -> Pipeline:
        pool = device_pool if device_pool is not None else self.devices
        n_phys = len(pool if pool is not None else jax.devices())
        if len(stages) * replicas > n_phys:
            log.warning(
                "%d stages x %d replicas oversubscribes %d physical "
                "devices; replicas will share chips",
                len(stages),
                replicas,
                n_phys,
            )
        if replicas > 1:
            from defer_tpu.parallel.data_parallel import ReplicatedPipeline

            devices = pipeline_devices(len(stages) * replicas, pool)
            log.info(
                "built %d stages x %d replicas over devices %s",
                len(stages),
                replicas,
                devices,
            )
            return ReplicatedPipeline(
                stages, params, devices, self.config, num_replicas=replicas
            )
        devices = pipeline_devices(len(stages), pool)
        log.info("built %d stages over devices %s", len(stages), devices)
        return Pipeline(stages, params, devices, self.config)

    # -- elastic recovery -------------------------------------------------

    def _healthy_devices(self, timeout_s: float = 10.0) -> list[jax.Device]:
        """Probe every candidate device with a tiny computation; a
        device that errors or misses the deadline is excluded from
        re-dispatch. Probes run concurrently under ONE shared deadline
        (hard_sync_timeout waits in helper threads and dedupes by
        array), so n hung devices cost max(timeout), not n*timeout."""
        devs = self.devices if self.devices is not None else jax.devices()
        probes: list[tuple[jax.Device, Any]] = []
        healthy: list[jax.Device] = []
        for d in devs:
            try:
                probes.append(
                    (d, jax.device_put(jnp.zeros((), jnp.float32), d) + 1.0)
                )
            except Exception as e:  # noqa: BLE001 — exclusion is the point
                log.warning("device %s failed the health probe: %s", d, e)
        for _, probe in probes:  # start every wait thread
            try:
                hard_sync_timeout(probe, 0.0)
            except Exception:  # noqa: BLE001 — surfaced in the wait below
                pass
        deadline = time.monotonic() + timeout_s
        for d, probe in probes:
            try:
                if hard_sync_timeout(
                    probe, max(0.0, deadline - time.monotonic())
                ):
                    healthy.append(d)
                else:
                    log.warning("device %s missed the health deadline", d)
            except Exception as e:  # noqa: BLE001 — exclusion is the point
                log.warning("device %s failed the health probe: %s", d, e)
        return healthy

    def _redispatch(self, cause: BaseException) -> Pipeline:
        """Rebuild the pipeline on the devices that still pass a health
        probe — the recovery the reference lacks entirely (node death
        hangs it forever, reference src/node.py:102-103)."""
        get_registry().counter(
            "defer_redispatch_total",
            "Elastic-recovery pipeline rebuilds after a device failure",
        ).inc()
        healthy = self._healthy_devices()
        if not healthy:
            raise RuntimeError(
                "re-dispatch impossible: no device passed the health probe"
            ) from cause
        stages, params, replicas = self._build_state
        log.warning(
            "re-dispatching %d stages (x%d replicas) onto %d healthy "
            "device(s) after: %s",
            len(stages),
            replicas,
            len(healthy),
            cause,
        )
        pipe = self._compile(stages, params, replicas, healthy)
        self.last_pipeline = pipe
        return pipe

    # -- streaming (the reference's run_defer contract) ------------------

    def run_defer(
        self,
        model: Model | Graph | str,
        partition_layers: Sequence[str | Sequence[str]] | str | None,
        input_stream: "queue.Queue[Any]",
        output_stream: "queue.Queue[Any]",
        *,
        params: GraphParams | None = None,
        rng: jax.Array | None = None,
        replicas: int = 1,
    ) -> None:
        """Blocking stream loop: consume input_stream, produce
        output_stream. Ends on a None/STOP sentinel or `stop()`.

        Signature mirrors reference src/dispatcher.py:120; `replicas`
        adds the data-parallel axis (see build_pipeline).
        """
        self._stop.clear()
        pipe, _ = self.build_pipeline(
            model, partition_layers, params=params, rng=rng,
            replicas=replicas,
        )
        monitor = ProgressMonitor(self.config.collective_timeout_s)

        def watchdog_sync(arr: Any) -> None:
            # Barrier with a deadline so a stuck stage trips the
            # watchdog instead of hanging forever (utils/sync.py).
            # A barrier may cover many microbatches; on timeout we only
            # raise if the completed prefix stopped growing — genuinely
            # zero progress, matching collective_timeout_s semantics for
            # slow-but-healthy pipelines.
            last_ready = -1
            while not hard_sync_timeout(
                arr, self.config.collective_timeout_s
            ):
                ready = retirer.ready_count()
                if ready <= last_ready:
                    raise TimeoutError(
                        f"pipeline made no progress for "
                        f"{self.config.collective_timeout_s:.0f}s — a stage "
                        "or transfer is stuck"
                    )
                last_ready = ready

        # Replicated runtimes supply their own retirer bank: the shared
        # windowed-barrier trick is only sound within one device program
        # (see ReplicaRetirer in parallel/data_parallel.py).
        make = getattr(pipe, "make_retirer", None)
        retirer = (
            make(self.config.max_inflight, watchdog_sync)
            if make is not None
            else Retirer(self.config.max_inflight, sync=watchdog_sync)
        )

        # Dynamic batching: coalesce queue items into device batches
        # (runtime/batching.py) and split outputs back per item.
        # `splits` mirrors the dispatch FIFO: one sizes-list per
        # submitted batch, popped as its output retires.
        gatherer = None
        splits: "collections.deque[list[int]]" = collections.deque()
        if self.config.dynamic_batch_size > 1:
            from defer_tpu.runtime.batching import BatchGatherer

            gatherer = BatchGatherer(
                self.config.dynamic_batch_size, self.config.batch_wait_s
            )

        obs_items = get_registry().counter(
            "defer_stream_items_total",
            "Results delivered to the output stream by run_defer",
        )

        def emit(items: Sequence[Any]) -> None:
            for out in items:
                monitor.completed()
                if gatherer is None:
                    output_stream.put(out)
                    obs_items.inc()
                else:
                    for part in split_output(out, splits.popleft()):
                        output_stream.put(part)
                        obs_items.inc()

        # Unlike Pipeline.stream (pull-based), this loop must keep
        # emitting results while the input queue idles — the reference's
        # feed and result paths are independent threads for the same
        # reason (src/dispatcher.py:93-118).
        # Trace only a bounded window of the (potentially unbounded)
        # serving loop — an open-ended trace grows without limit.
        tracer = profiling.WindowTrace()
        try:
            self._stream_loop(
                pipe, input_stream, emit, retirer, monitor, tracer,
                gatherer, splits,
            )
        finally:
            tracer.close()

    def _stream_loop(
        self, pipe, input_stream, emit, retirer, monitor, tracer,
        gatherer=None, splits=None,
    ):
        since_probe = 0
        retries_left = self.config.redispatch_attempts
        eos = False
        while not self._stop.is_set() and not eos:
            if gatherer is None:
                try:
                    item = input_stream.get(timeout=0.05)
                except queue.Empty:
                    emit(retirer.collect())
                    monitor.check()
                    continue
                if item is None or item is STOP:
                    break
                sizes = None
            else:
                item, sizes, eos = gatherer.gather(input_stream)
                if item is None:
                    if eos:
                        break
                    emit(retirer.collect())
                    monitor.check()
                    continue
            monitor.submitted()
            tracer.tick()
            while True:
                try:
                    if sizes is not None:
                        splits.append(sizes)
                    emit(retirer.add(pipe.submit(item)))
                    break
                except Exception as e:  # noqa: BLE001 — recovery below
                    if retries_left <= 0:
                        raise
                    retries_left -= 1
                    # Completed results (including the barrier-failure
                    # spill) are still valid — emit them before
                    # dropping what can no longer finish.
                    try:
                        emit(retirer.collect())
                    except Exception:  # noqa: BLE001 — dead buffers
                        pass
                    lost = retirer.discard()
                    if splits is not None:
                        # Everything un-emitted was just discarded; the
                        # retry below re-appends this batch's sizes.
                        splits.clear()
                    if lost:
                        log.warning(
                            "dropping %d in-flight results of the failed "
                            "pipeline",
                            lost,
                        )
                        monitor.dropped(lost)
                        get_registry().counter(
                            "defer_inflight_dropped_total",
                            "In-flight results lost to pipeline failures",
                        ).inc(lost)
                    pipe = self._redispatch(e)
            monitor.check()
            since_probe += 1
            if (
                self.config.probe_every
                and since_probe >= self.config.probe_every
            ):
                # Synchronous per-stage latency probe; drain first so it
                # doesn't interleave with (and distort) in-flight work.
                since_probe = 0
                emit(retirer.flush())
                self.last_stage_latencies = pipe.probe_stage_latencies(
                    item, iters=3
                )
        # (A carried mismatch item can never survive to the sentinel:
        # gather() prepends the carry before it can consume STOP, so a
        # pending carry here means stop() interrupted the stream — and
        # after an explicit stop we must not submit new device work.)
        emit(retirer.flush())

    def stop(self) -> None:
        self._stop.set()


def run_local_inference(
    model: Model,
    *,
    batch_size: int = 1,
    duration_s: float = 10.0,
    params: GraphParams | None = None,
    compute_dtype: Any = None,
    example: Any = None,
) -> dict[str, float]:
    """Single-device baseline: jit the whole model on one core and loop.

    The analogue of the reference's `local_infer.py` (reference
    src/local_infer.py:16-23: preprocess one real image, loop
    `model.predict` for 10 min, count results) — this defines the
    denominator of every speedup claim. `example` supplies the looped
    input (e.g. a preprocessed real image batch); default is a ones
    tensor of the model's input shape.
    """
    cfg = DeferConfig()
    if compute_dtype is not None:
        cfg = cfg.replace(compute_dtype=compute_dtype)
    if params is None:
        params = model.init(jax.random.key(0), batch_size=batch_size)
    # Commit the example to device once — a host numpy example would
    # otherwise re-transfer every iteration and skew the baseline.
    x = (
        jax.device_put(jnp.asarray(example))
        if example is not None
        else model.example_input(batch_size)
    )
    # Count what actually runs — a caller-supplied example's leading
    # dim is the real batch; trusting batch_size would silently scale
    # the baseline metric.
    batch_size = int(x.shape[0]) if getattr(x, "ndim", 0) > 0 else 1

    def apply(p, v):
        if jnp.issubdtype(v.dtype, jnp.floating):
            v = v.astype(cfg.compute_dtype)
        return model.graph.apply(p, v)

    # `apply` is a fresh closure per call: plain jax.jit here re-traced
    # the whole model every time a bench re-entered (the memo.py
    # hazard). Zoo models share the entry by name (same name -> same
    # graph structure); anonymous models key on identity, which is
    # safe because the cached closure keeps `model` alive, so its id
    # can never be recycled onto a different model.
    ident = getattr(model, "name", None) or id(model)
    fn = jit_cached(
        apply, ("run_local_inference", ident, str(cfg.compute_dtype))
    )
    hard_sync(fn(params, x))  # compile

    count = 0
    t0 = time.perf_counter()
    retirer = Retirer(depth=16)
    while time.perf_counter() - t0 < duration_s:
        retirer.add(fn(params, x))
        count += 1
    # True completion barrier; device program order covers the rest.
    retirer.flush()
    dt = time.perf_counter() - t0
    return {
        "count": count,
        "seconds": dt,
        "batches_per_sec": count / dt,
        "items_per_sec": count * batch_size / dt,
    }
