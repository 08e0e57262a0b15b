#!/usr/bin/env python3
"""Logits of the paged server against a family's plain reference, at a
configuration's own sizes.

    python3 scripts/chip_reference_check.py --config perfbench/configs/<c>.json \
        [--prompt-tokens 6000] [--steps 8] [--seed 1] [--tolerance 2.5e-2]

One seeded prompt goes through `PagedDecodeServer` as the benchmark
builds it (the configuration's `server` arguments): the flat prefill at
admission, the rows paged into the pool, then decode steps through the
pool. The logits row every token was chosen from (the prefill's last
row, then each decode step's) is held to the family's
`reference_logits` over the same ids: max|d| / max|ref| over those rows
must stay inside the tolerance, and every control must fall outside it.
The family's file names its controls (`CONTROLS`: a label and the fault
its `reference_logits` plants for it); a family that names none gets
`cohere2_moe`'s two. The last control is the same for every family:

  window ignored   the reference attends every j <= i in every layer
  shared sum       the reference adds the shared experts' sum, not mean
  (qwen3_next)     the decay ignored (g = 0), the attention output's
                   gate left out, the shared expert added ungated
  int8 weights     the reference computes with every matrix rounded to
                   int8 (symmetric, a scale per output channel): one
                   precision below the bf16 the configuration states

The tolerance, 2.5e-2, lies between two readings on the chip (PR 28,
`command-a-plus-05-2026-ep8-l4`, 6000 tokens then 8): the served bf16
program reads 8.2e-3 (every activation of every layer is rounded to
bf16 where the reference rounds nothing, and a top-k choice may flip at
a near-tie), the int8 control 9.8e-2; three times of room on one side
and four on the other. The other controls read 0.21 and 0.79.

The logits are read by wrapping the server's own `_first_token` and
`_step`, as chip_smoke.py does; nothing is added to the server. The
last line of standard output is one JSON object; the exit code is 0
only where the served logits pass and every control fails.
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def served_rows(srv, prompt, steps: int):
    """(logits rows [steps, V] float32, the tokens chosen from them)."""
    import numpy as np

    rows, toks = [], []
    first_token, slot_of = srv._first_token, {}

    def spy_first(i, samp, lrow, dtype, cid):
        rows.append(np.asarray(lrow, np.float32)[0])
        slot_of["i"] = i
        return first_token(i, samp, lrow, dtype, cid)

    srv._first_token = spy_first
    srv.on_token = lambda rid, tok, done: toks.append(tok)
    srv.submit(prompt, steps)
    srv._admit()
    srv._build()
    step = srv._step

    def spy_step(*a):
        out = step(*a)
        logits = out[0][0] if isinstance(out[0], tuple) else out[0]
        rows.append(np.asarray(logits[slot_of["i"], -1], np.float32))
        return out

    srv._step = spy_step
    while any(s is not None for s in srv.slots):
        srv._tick()
    return np.stack(rows[:steps]), toks


def fake_int8(params):
    """Every matrix rounded to int8 and back: symmetric, one scale per
    output channel (the last axis), over its input axis. One matrix at
    a time and each leaf in place of the one it is made from: a second
    copy of the weights does not fit the chip."""
    import jax
    import jax.numpy as jnp

    def matrix(a):
        if a.ndim > 2:
            return jax.lax.map(matrix, a)
        f = a.astype(jnp.float32)
        s = jnp.max(jnp.abs(f), axis=-2, keepdims=True) / 127.0
        s = jnp.where(s == 0, 1.0, s)
        return (jnp.round(f / s) * s).astype(a.dtype)

    rounded = jax.jit(matrix, donate_argnums=0)
    leaves, treedef = jax.tree_util.tree_flatten_with_path(params)
    del params
    out = []
    while leaves:
        path, a = leaves.pop(0)
        # A matrix has two axes of its own: under "stack" a leaf's
        # first axis counts layers (a norm's scale, a decay per head
        # are [L, n]: no matrix).
        own = a.ndim - (str(path[0].key) == "stack")
        scale_only = own < 2 or str(path[-1].key).endswith("_scale")
        out.append(a if scale_only else rounded(a))
        del a
    return jax.tree_util.tree_unflatten(treedef, out)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--prompt-tokens", type=int, default=6000)
    ap.add_argument("--steps", type=int, default=8)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--tolerance", type=float, default=2.5e-2)
    ap.add_argument("--float32", action="store_true",
                    help="serve float32 weights in float32 (a CPU rehearsal)")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from defer_tpu.runtime.paged import PagedDecodeServer
    from perfbench import harness

    with open(args.config, encoding="utf-8") as f:
        model = json.load(f)
    family = harness.load_module(
        os.path.join(ROOT, "perfbench", "families", model["family"] + ".py")
    )
    dec = family.build_decoder(model)
    params = family.make_params(dec, args.seed)
    if args.float32:
        import dataclasses

        dec = dataclasses.replace(dec, compute_dtype=jnp.float32)
        params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    server_args = {k: v for k, v in model["server"].items() if k != "mesh"}
    srv = PagedDecodeServer(dec, params, **server_args)
    rng = np.random.default_rng([args.seed, 3])
    prompt = rng.integers(
        1, model["vocab_size"], (1, args.prompt_tokens)
    ).astype(np.int32)
    t0 = time.perf_counter()
    rows, toks = served_rows(srv, jnp.asarray(prompt), args.steps)
    served_s = time.perf_counter() - t0
    ids = np.concatenate([prompt[0], np.asarray(toks[:-1], np.int32)])

    def against(label, ref_params, **faults):
        t = time.perf_counter()
        ref = np.asarray(
            family.reference_logits(model, ref_params, ids, **faults)
        )[args.prompt_tokens - 1 :]
        rel = float(np.max(np.abs(rows - ref)) / np.max(np.abs(ref)))
        print(
            f"{label}: max|d|/max|ref| {rel:.4g} "
            f"({time.perf_counter() - t:.1f} s)", flush=True,
        )
        return rel

    served = against("served against the reference", params)
    planted = getattr(family, "CONTROLS", {
        "window_ignored": {"ignore_window": True},
        "shared_sum": {"shared_sum": True},
    })
    controls = {
        name: against(f"control, {name}", params, **fault)
        for name, fault in planted.items()
    }
    # The rounded weights take the place of the served ones, which the
    # server holds too: it goes first.
    memory_peak = harness.memory_peak(jax.devices()[:1])
    del srv
    params = fake_int8(params)
    controls["int8_weights"] = against("control, int8 weights", params)
    out = {
        "served": served,
        "tolerance": args.tolerance,
        "controls": controls,
        "prompt_tokens": args.prompt_tokens,
        "steps": args.steps,
        "served_s": served_s,
        "device": {
            "platform": jax.devices()[0].platform,
            "kind": jax.devices()[0].device_kind,
        },
        "memory_peak_bytes": memory_peak,
    }
    out["ok"] = out["served"] <= args.tolerance and all(
        v > args.tolerance for v in out["controls"].values()
    )
    print(json.dumps(out))
    return 0 if out["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
