"""One run of one cell: set-up, the measured window, the result line.

The harness is driven by data. `BENCHMARK.json` names the cell's
configuration, traffic mix and metrics; each is a file of its own
under the benchmark's directories, found by that name:

  <path>/configs/<config>.json        sizes, `family`, server arguments
  <path>/families/<family>.py         decoder, weights, plain reference,
                                      its own count of a decode step
  <path>/traffic/<traffic>.json       parameters of the one generator
  <path>/cells/<workload>.json        what belongs to config x traffic
  <path>/end_to_end/<metric>.py       read(run) -> number | None
  <path>/layer_metrics/<metric>.py    read(run) -> number | None

From the program it takes the system under test and these names only:
`PagedDecodeServer(dec, params, num_blocks=, block_size=, max_batch=,
mesh=, on_token=)`, `submit`, `_admit`, `_tick`, `slots`, `pending`,
`blocks_peak`, the `obs` counters `host_dispatches` and
`tokens_generated`, the metrics registry `obs.metrics.get_registry()`
by exported name, `make_mesh`, and what perfbench/families/ names.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import gc
import glob
import importlib.util
import json
import math
import os
import shutil
import sys
import tempfile
import time

import numpy as np

from perfbench import metrics, peaks, program_spans, traffic, xplane

clock = time.perf_counter

# The traced slice: the last seconds of the window.
TRACE_S = 5.0
# chip_smoke.py's MODEL_TOL and its reason: the server rounds every
# activation of every layer to bf16 where the float32 reference rounds
# nothing, so a logit may differ by up to 5% of the largest magnitude.
MODEL_TOL = 5e-2
CHECK_PROMPT = 256
CHECK_STEPS = 8

_BUILD_EVENTS = {
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lowered",
    "/jax/core/compile/backend_compile_duration": "compiled",
    "/jax/compilation_cache/cache_retrieval_time_sec": "from_cache",
}


class BuildCounter:
    """Counts programs built in this process, through jax.monitoring:
    `lowered` moves for every new program whether the compile cache
    had it or not, so inside the window it must stand still."""

    def __init__(self):
        self.n = dict.fromkeys(_BUILD_EVENTS.values(), 0)

    def listen(self, event, duration, **_):
        key = _BUILD_EVENTS.get(event)
        if key is not None:
            self.n[key] += 1

    def snapshot(self) -> dict:
        return dict(self.n)


class Recorder:
    """What a streaming client would see: a stamp per token, taken in
    the server's `on_token` callback."""

    def __init__(self):
        self.stamps: dict[int, list[float]] = {}
        self.tokens: dict[int, list] = {}
        self.done: set[int] = set()
        self.depth: dict[int, int] = {}  # live request -> cached rows
        self.prompt_len: dict[int, int] = {}
        self.n = 0

    def on_token(self, rid, tok, done):
        self.stamps.setdefault(rid, []).append(clock())
        self.tokens.setdefault(rid, []).append(tok)
        self.n += 1
        if done:
            self.done.add(rid)
            self.depth.pop(rid, None)
        else:
            self.depth[rid] = self.depth.get(rid, self.prompt_len[rid]) + 1


@dataclasses.dataclass
class Run:
    """Everything the metric readers may read."""

    workload: dict
    model: dict
    family: object  # the module families/<family>.py
    server_args: dict
    traffic: dict
    cell: dict
    chips: int
    peaks: dict
    weight_bytes: int
    pool_bytes: int
    seconds: float
    t_start: float
    t_open: float = 0.0
    t_close: float = 0.0
    setup_s: float = 0.0
    rec: Recorder | None = None
    due: dict = dataclasses.field(default_factory=dict)  # rid -> due, in window
    wanted: dict = dataclasses.field(default_factory=dict)  # rid -> tokens asked
    late: list = dataclasses.field(default_factory=list)
    # (t0, t1, tokens, live_slots, live_kv_rows) of each _tick call
    ticks: list = dataclasses.field(default_factory=list)
    # beside each entry of `ticks`: its live slots' cached rows
    tick_depths: list = dataclasses.field(default_factory=list)
    # (t0, t1, requests seated) of each _admit call that seated any
    admits: list = dataclasses.field(default_factory=list)
    counters_open: dict = dataclasses.field(default_factory=dict)
    counters_close: dict = dataclasses.field(default_factory=dict)
    # the program's whole metrics registry, exported name -> value
    registry_open: dict = dataclasses.field(default_factory=dict)
    registry_close: dict = dataclasses.field(default_factory=dict)
    builds_open: dict = dataclasses.field(default_factory=dict)
    builds_close: dict = dataclasses.field(default_factory=dict)
    blocks_peak: int = 0
    # Requests due as the window opens (a backlog's queue), and those
    # still in `srv.pending` as it closes.
    queued_at_open: int = 0
    pending_at_close: int = 0
    trace: xplane.Reduced | None = None

    def in_window(self, t: float) -> bool:
        return self.t_open < t <= self.t_close

    def window_stamps(self) -> dict:
        """rid -> stamps inside the window."""
        out = {}
        for rid, stamps in self.rec.stamps.items():
            inside = [t for t in stamps if self.in_window(t)]
            if inside:
                out[rid] = inside
        return out

    def ttft_p50(self) -> float | None:
        """Median over requests due in the window of first-token stamp
        minus due time; None where no request was due, or where so
        many went unserved that the median is infinite."""
        first = {rid: s[0] for rid, s in self.rec.stamps.items() if rid in self.due}
        waits = metrics.ttft_per_request(self.due, first)
        if not waits:
            return None
        p50 = metrics.median(waits)
        return p50 if math.isfinite(p50) else None

    def window_ticks(self) -> list:
        return [t for t in self.ticks if self.in_window(t[1])]

    def window_tick_depths(self) -> list:
        return [
            d for t, d in zip(self.ticks, self.tick_depths) if self.in_window(t[1])
        ]

    def window_admits(self) -> list:
        return [a for a in self.admits if self.in_window(a[1])]

    def dispatches(self) -> list:
        """(t_end, tokens) of every call that emitted tokens."""
        events = [(t1, n) for _, t1, n, _, _ in self.ticks]
        events += [(t1, n) for _, t1, n in self.admits]
        return metrics.whole_dispatches(events, self.t_open, self.t_close)


# -- finding the cell's files ----------------------------------------------


def load_json(path: str):
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def find(root: str, bench: dict, *parts: str) -> str:
    for p in bench["paths"]:
        path = os.path.join(root, p, *parts)
        if os.path.exists(path):
            return path
    raise FileNotFoundError(
        f"{os.path.join(*parts)} under none of {bench['paths']}"
    )


def load_module(path: str):
    name = "perfbench_file_" + "".join(
        c if c.isalnum() else "_" for c in os.path.relpath(path)
    )
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell_metrics(bench: dict, group: str, workload: str) -> list[dict]:
    """The metrics of `group` this cell reports: those with no
    `workloads` key, and those that list it."""
    return [
        m for m in bench[group]
        if "workloads" not in m or workload in m["workloads"]
    ]


def read_metrics(root, bench, group, folder, run) -> dict:
    out = {}
    for m in cell_metrics(bench, group, run.workload["name"]):
        reader = load_module(find(root, bench, folder, m["name"] + ".py"))
        value = reader.read(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


# -- the driver loop ---------------------------------------------------------


def drive(srv, run: Run, schedule, until: float, span) -> int:
    """The seam fleet/replica.py and disagg/api.py use, in one place:
    submit what is due, `_admit`, `_tick` while a slot is live. Runs
    until `until` (or, where that is None, until nothing is left).
    `schedule` is a deque of (due, prompt, steps, note) consumed from
    the front. Returns how many submissions raised."""
    rec = run.rec
    failed = 0
    while True:
        now = clock()
        if until is not None and now >= until:
            break
        while schedule and schedule[0][0] <= now:
            due, prompt, steps, note = schedule.popleft()
            try:
                rid = srv.submit(prompt, steps)
            except ValueError:
                failed += 1
                continue
            rec.prompt_len[rid] = int(prompt.shape[1])
            note(rid, due, clock(), steps)
        n0 = rec.n
        t0 = clock()
        with span("admit"):
            srv._admit()
        t1 = clock()
        if rec.n > n0:
            run.admits.append((t0, t1, rec.n - n0))
        if any(s is not None for s in srv.slots):
            n1 = rec.n
            depths = tuple(rec.depth.values())
            t2 = clock()
            with span("tick"):
                srv._tick()
            run.ticks.append((t2, clock(), rec.n - n1, len(depths), sum(depths)))
            run.tick_depths.append(depths)
        elif until is None and not schedule and not srv.pending:
            break
        else:
            wait = 1e-3
            if schedule:
                wait = min(wait, max(schedule[0][0] - clock(), 0.0))
            time.sleep(wait)
    return failed


def serve_now(srv, run, prompts_steps, span) -> list[int]:
    """Set-up traffic: submit these now and serve them to the end."""
    rids = []
    sched = collections.deque(
        (0.0, p, s, lambda rid, due, t, steps: rids.append(rid))
        for p, s in prompts_steps
    )
    drive(srv, run, sched, None, span)
    return rids


# -- set-up ------------------------------------------------------------------


def pick_devices(chips: int, devices):
    """The chips the cell runs on. Anything but a TPU is refused,
    unless a test injects its devices."""
    import jax

    if devices is None:
        devices = jax.devices()
        if devices[0].platform != "tpu":
            raise SystemExit(
                f"perfbench measures on a TPU; JAX found {devices[0].platform!r}"
            )
    if len(devices) < chips:
        raise SystemExit(
            f"the cell needs {chips} chips; JAX found {len(devices)}"
        )
    return devices


def check_correct(srv, run, family, params, seed, span):
    """Through the public output only: one seeded prompt served for a
    few greedy tokens; at each position the token the server chose
    must have a reference logit within MODEL_TOL of max|ref| of the
    reference's best. With random weights a handful of the vocabulary
    passes that, so wrong mathematics fails and a near-tie does not."""
    import jax.numpy as jnp

    model = run.model
    t0 = min(
        model.get("check_prompt_tokens", CHECK_PROMPT),
        model["max_position_embeddings"] // 2,
    )
    rng = np.random.default_rng([seed, 2])
    prompt = rng.integers(1, model["vocab_size"], (1, t0)).astype(np.int32)
    (rid,) = serve_now(srv, run, [(jnp.asarray(prompt), CHECK_STEPS)], span)
    toks = run.rec.tokens.get(rid, [])
    if len(toks) != CHECK_STEPS or rid not in run.rec.done:
        return False, {"reason": f"served {len(toks)} of {CHECK_STEPS} tokens"}
    if min(toks) < 0 or max(toks) >= model["vocab_size"]:
        return False, {"reason": "token id outside the vocabulary"}
    ids = np.concatenate([prompt[0], np.asarray(toks[:-1], np.int32)])
    ref = np.asarray(family.reference_logits(model, params, ids))[t0 - 1 :]
    scale = float(np.max(np.abs(ref)))
    behind = [float(ref[i].max() - ref[i, t]) / scale for i, t in enumerate(toks)]
    passing = int(np.mean(np.sum(ref >= ref.max(-1, keepdims=True) - MODEL_TOL * scale, -1)))
    ok = bool(np.isfinite(ref).all() and max(behind) <= MODEL_TOL)
    return ok, {
        "behind_best_max": max(behind), "tolerance": MODEL_TOL,
        "tokens_passing_mean": passing, "prompt_tokens": t0,
    }


def read_trace(trace_dir: str):
    """What the trace held (for the details line) and its reduction;
    the trace itself is deleted."""
    import jax

    lines, reduced = None, None
    found = glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")
    )
    if found:
        profile = jax.profiler.ProfileData.from_file(found[0])
        lines, reduced = xplane.describe(profile), xplane.reduce_profile(profile)
    shutil.rmtree(trace_dir, ignore_errors=True)
    return lines, reduced


def count_failed(run: Run, vocab: int) -> int:
    """Requests due in the window that finished short or returned a
    token id outside the vocabulary."""
    failed = 0
    for rid in run.due:
        toks = run.rec.tokens.get(rid, [])
        bad_id = any(t is None or not 0 <= t < vocab for t in toks)
        short = rid in run.rec.done and len(toks) != run.wanted[rid]
        failed += bad_id or short
    return failed


def memory_peak(devices) -> int:
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def counters(srv) -> dict:
    return {
        "host_dispatches": srv.obs.host_dispatches.value,
        "tokens_generated": srv.obs.tokens_generated.value,
    }


def registry() -> dict:
    """Every instrument of the program's metrics registry by its
    exported name (labels inline): a counter's or gauge's value, a
    histogram's count, sum and buckets."""
    from defer_tpu.obs import metrics as program_metrics

    kinds = program_metrics.get_registry().to_dict()
    return {**kinds["counters"], **kinds["gauges"], **kinds["histograms"]}


# -- one run -----------------------------------------------------------------


def run_cell(args, *, root: str, t_start: float, devices=None, out=print) -> int:
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if args.workload not in cells:
        raise SystemExit(f"no workload {args.workload!r} in BENCHMARK.json")
    workload = cells[args.workload]
    config = load_json(find(root, bench, "configs", workload["config"] + ".json"))
    mix = load_json(find(root, bench, "traffic", workload["traffic"] + ".json"))
    cell = load_json(find(root, bench, "cells", workload["name"] + ".json"))
    if args.knee_per_s is not None:
        cell["knee_per_s"] = args.knee_per_s
    family = load_module(find(root, bench, "families", config["family"] + ".py"))

    import jax
    import jax.numpy as jnp

    devices = pick_devices(workload["chips"], devices)
    used = list(devices[: workload["chips"]])
    peak_table = peaks.peaks_for(used[0].device_kind)
    builds = BuildCounter()
    jax.monitoring.register_event_duration_secs_listener(builds.listen)
    # Small programs too go to the persistent cache, so that a later
    # run of the cell loads them instead of compiling.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    from defer_tpu.runtime.paged import PagedDecodeServer

    server_args = dict(config["server"])
    mesh = None
    if server_args.pop("mesh"):
        from defer_tpu.parallel.mesh import make_mesh

        mesh = make_mesh(config["server"]["mesh"], used)
    dec = family.build_decoder(config)
    with jax.default_device(used[0]):
        params = family.make_params(dec, args.seed, mesh)
    weight_bytes = sum(a.nbytes for a in jax.tree.leaves(params))
    rec = Recorder()
    srv = PagedDecodeServer(
        dec, params, **server_args, mesh=mesh, on_token=rec.on_token
    )
    run = Run(
        workload=workload, model=config, family=family,
        server_args=config["server"],
        traffic=mix, cell=cell, chips=workload["chips"], peaks=peak_table,
        weight_bytes=weight_bytes, pool_bytes=srv.pool_bytes,
        seconds=float(args.seconds), t_start=t_start, rec=rec,
    )
    tracing = bool(args.trace)
    span = jax.profiler.TraceAnnotation if tracing else (
        lambda name: contextlib.nullcontext()
    )

    correct, check_detail = check_correct(srv, run, family, params, args.seed, span)
    del params
    t_check = clock()

    requests = traffic.generate(
        mix, cell, seconds=run.seconds, max_batch=server_args["max_batch"],
        seed=args.seed,
    )
    ids = traffic.token_ids(
        requests, config["vocab_size"], mix["shared_prefix_tokens"], args.seed
    )
    prompts = [jnp.asarray(a) for a in ids]
    # Warm up this cell's shapes and no others. The program builds
    # small programs for every new prompt length, and a run's lengths
    # are nearly all different: so one request of a single token for
    # each prompt length (its prefill bucket and the programs keyed on
    # the length; it ends at admission and the decode step is warm
    # from the check). What this costs is in `setup_s`.
    by_length = {r.prompt_tokens: p for p, r in zip(prompts, requests)}
    serve_now(srv, run, [(by_length[t], 1) for t in sorted(by_length)], span)
    t_warm = clock()

    # The standing population, seated now so that the window opens in
    # the steady regime; then whatever is due at time 0.
    standing = [(p, r) for p, r in zip(prompts, requests) if r.standing]
    arrivals = [(p, r) for p, r in zip(prompts, requests) if not r.standing]
    for p, r in standing:
        rid = srv.submit(p, r.output_tokens)
        rec.prompt_len[rid] = r.prompt_tokens
    while srv.pending and any(s is None for s in srv.slots):
        before = len(srv.pending)
        srv._admit()
        if len(srv.pending) == before:
            break  # the pool is full: the rest waits in the queue
    run.ticks.clear()
    run.tick_depths.clear()
    run.admits.clear()
    run.registry_open = registry()
    gc.collect()
    gc.freeze()

    run.t_open = clock()
    run.t_close = run.t_open + run.seconds
    run.setup_s = setup_s = run.t_open - t_start

    def note(rid, due, t, steps):
        run.due[rid] = due
        run.wanted[rid] = steps
        run.late.append(t - due)

    schedule = collections.deque(
        (run.t_open + r.due_s, p, r.output_tokens, note) for p, r in arrivals
    )
    run.queued_at_open = sum(1 for _, r in arrivals if r.due_s <= 0)
    run.counters_open = counters(srv)
    run.builds_open = builds.snapshot()
    raised = 0
    trace_dir = trace_lines = None
    if tracing:
        raised += drive(srv, run, schedule, run.t_close - TRACE_S, span)
        trace_dir = tempfile.mkdtemp(prefix="perfbench_trace_")
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        raised += drive(srv, run, schedule, run.t_close, span)
    finally:
        if tracing:
            jax.profiler.stop_trace()
    run.t_close = min(run.t_close, clock())
    run.counters_close = counters(srv)
    run.registry_close = registry()
    run.builds_close = builds.snapshot()
    run.blocks_peak = srv.blocks_peak
    run.pending_at_close = len(srv.pending)
    # Live slots at the last tick; 0 where the server stands idle.
    idle = all(s is None for s in srv.slots)
    live_at_close = 0 if idle or not run.ticks else run.ticks[-1][3]
    # A request due after the window closed was never attempted.
    run.due = {rid: d for rid, d in run.due.items() if d <= run.t_close}

    if trace_dir is not None:
        trace_lines, run.trace = read_trace(trace_dir)
    failed = raised + count_failed(run, config["vocab_size"])

    if tracing:
        got = read_metrics(root, bench, "per_layer", "layer_metrics", run)
    else:
        got = read_metrics(root, bench, "end_to_end", "end_to_end", run)

    device = {
        "platform": used[0].platform,
        "kind": used[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": memory_peak(used),
    }
    result = {
        "correct": correct,
        "attempted": len(run.due) + raised,
        "failed": failed,
        "metrics": got,
        "device": device,
    }
    if tracing and run.trace is not None:
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s
        result["breakdown"] = {
            "device_ops": xplane.top_device_ops(run.trace),
            "idle_gaps": xplane.idle_by_span(run.trace),
        }
    # How much of its queue the run used: a backlog served dry reads
    # `pending_at_close` 0, no live slot and an idle tail of seconds,
    # and its traced slice may hold no device operation at all.
    stamps = run.window_stamps()
    last_token = max((s[-1] for s in stamps.values()), default=run.t_open)
    admits = run.window_admits()
    last_seated = max((a[1] - run.t_open for a in admits), default=None)
    result["sizing"] = {
        "queued_at_open": run.queued_at_open,
        "pending_at_close": run.pending_at_close,
        "last_seated_s": last_seated,
        "live_at_close": live_at_close,
        "idle_tail_s": run.t_close - last_token,
    }
    # Each number `correct` compared, beside its limit: last in the
    # result line, and the last lines of standard error.
    result["compared"] = compared = {
        "behind_best_max": {
            "value": check_detail.get("behind_best_max"), "limit": MODEL_TOL,
        },
    }
    gaps = metrics.inter_token_gaps(stamps)
    ticks = run.window_ticks()
    fifths = [run.t_open + run.seconds * k / 5 for k in range(1, 6)]
    first = {rid: s[0] for rid, s in rec.stamps.items()}
    tick_spans = program_spans.in_window(run, "paged.tick")
    calls = sorted([k[:2] for k in ticks] + [a[:2] for a in admits])
    details = {
        "workload": workload["name"], "seed": args.seed,
        "seconds": run.t_close - run.t_open, "setup_s": setup_s,
        "rate_per_s": traffic.offered_rate(mix, cell),
        "requests_due": len(run.due),
        "requests_finished_in_window": sum(
            1 for rid in rec.done if run.in_window(rec.stamps[rid][-1])
        ),
        "standing": len(standing),
        "queued_at_open": run.queued_at_open,
        "pending_at_close": run.pending_at_close,
        # Requests due and not yet answered, and live slots, at each
        # fifth of the window: a queue that grows says the rate is
        # over the knee.
        "waiting_at_fifths": [
            sum(1 for rid, d in run.due.items() if d <= t < first.get(rid, math.inf))
            for t in fifths
        ],
        "live_at_fifths": [
            next((k[3] for k in reversed(ticks) if k[0] <= t), None) for t in fifths
        ],
        # When the last request was seated, from the window's opening:
        # a backlog that lasts seats one in the last seconds.
        "last_seated_s": last_seated,
        "ttft_p50_s": run.ttft_p50(),
        "itl_mean_s": sum(gaps) / len(gaps) if gaps else None,
        # The longest tick and when it began, from the window's opening.
        "tick_s_max": max(
            ((k[1] - k[0], k[0] - run.t_open) for k in ticks), default=None
        ),
        # The longest admission that seated a request, and the longest
        # stretch between two calls that emitted tokens (the loop, and
        # `_admit` calls that seated nothing), each with when it began:
        # with `tick_s_max` they say where a second-long stall fell.
        "admit_s_max": max(
            ((a[1] - a[0], a[0] - run.t_open) for a in admits), default=None
        ),
        "between_calls_s_max": max(
            ((b[0] - a[1], a[1] - run.t_open) for a, b in zip(calls, calls[1:])),
            default=None,
        ),
        # The window's ticks by the rung of the span ladder the program
        # says it ran them on: the order of a queue moves this share.
        "ticks_by_span_rows": dict(
            collections.Counter(str(r.counts.get("span_rows")) for r in tick_spans)
        ) if tick_spans is not None else None,
        "warm_up_s": t_warm - t_check,
        "prompt_lengths": len(by_length),
        "late_s_p50": metrics.median(run.late) if run.late else None,
        "late_s_max": max(run.late) if run.late else None,
        "samples": {
            "tpot": len(metrics.tpot_per_request(stamps)),
            "itl": len(gaps),
            "ttft": len(run.due),
            "ticks": len(ticks),
            "admits": len(admits),
            "dispatches": len(run.dispatches()),
        },
        "itl_s": {
            f"p{q}": metrics.percentile(gaps, q) for q in (50, 90, 95, 96, 97, 98, 99)
        } if gaps else None,
        "pool_blocks": server_args["num_blocks"],
        "blocks_peak": run.blocks_peak,
        "weight_gib": weight_bytes / 2**30,
        "pool_gib": srv.pool_bytes / 2**30,
        "memory_peak_gib": device["memory_peak_bytes"] / 2**30,
        "programs_built": {
            "setup": run.builds_open, "window": {
                k: run.builds_close[k] - run.builds_open[k] for k in run.builds_open
            },
        },
        "correct": check_detail,
        "trace_lines": trace_lines,
    }
    out("details: " + json.dumps(details))
    out(json.dumps(result))
    if tracing and run.trace is None:
        queue = (
            f"queue dry at {last_seated} s" if not run.pending_at_close
            else f"{run.pending_at_close} still queued"
        )
        print(
            f"traced slice empty: last token at {last_token - run.t_open} s"
            f" of {run.t_close - run.t_open} s, {queue}",
            file=sys.stderr,
        )
    for name, c in compared.items():
        print(f"compared: {name} {c['value']} limit {c['limit']}", file=sys.stderr)
    return 0
