#!/usr/bin/env python
"""Long-context attention demo: ring vs Ulysses sequence parallelism.

The reference has no notion of a sequence axis at all (SURVEY.md §5);
this driver shows the framework's long-context path: a sequence far
too big for one device's O(S^2) score matrix, sharded over a `seq`
mesh axis, attended with ring attention (K/V blocks rotating on ICI
with a streaming-softmax accumulator) or Ulysses (all_to_all to
head-sharding and back), and checked against the unsharded reference
when it fits.

    # 8-way CPU emulation (no hardware needed):
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/long_context.py --seq 8192 --strategy ring
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir))

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from defer_tpu.parallel.mesh import make_mesh
from defer_tpu.parallel.sequence import make_sharded_attention


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seq", type=int, default=8192)
    ap.add_argument("--heads", type=int, default=8)
    ap.add_argument("--head-dim", type=int, default=64)
    ap.add_argument("--batch", type=int, default=1)
    ap.add_argument("--strategy", choices=["ring", "ulysses"], default="ring")
    ap.add_argument("--causal", action="store_true")
    ap.add_argument(
        "--check",
        action="store_true",
        help="compare against unsharded attention (needs the full S^2 "
        "score matrix on one device — only for small --seq)",
    )
    args = ap.parse_args()

    devs = jax.devices()
    n = len(devs)
    if args.seq % n:
        raise SystemExit(f"--seq {args.seq} must divide by {n} devices")
    mesh = make_mesh({"seq": n}, devs)
    print(
        f"{args.strategy} attention over {n} devices "
        f"({devs[0].device_kind}); S={args.seq} "
        f"(S_local={args.seq // n}), H={args.heads}, Dh={args.head_dim}"
    )

    shape = (args.batch, args.heads, args.seq, args.head_dim)
    kq, kk, kv = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(kq, shape, jnp.bfloat16)
    k = jax.random.normal(kk, shape, jnp.bfloat16)
    v = jax.random.normal(kv, shape, jnp.bfloat16)

    attn = make_sharded_attention(
        mesh, strategy=args.strategy, causal=args.causal
    )
    out = attn(q, k, v)
    out.block_until_ready()  # compile
    t0 = time.perf_counter()
    iters = 5
    for _ in range(iters):
        out = attn(q, k, v)
    out.block_until_ready()
    dt = (time.perf_counter() - t0) / iters
    toks = args.batch * args.seq
    print(
        f"{dt * 1e3:.1f} ms/step, {toks / dt:,.0f} tokens/sec; "
        f"score matrix never materialized "
        f"({args.seq}^2 x {args.heads} heads would be "
        f"{args.seq**2 * args.heads * 4 / 1e9:.1f} GB in fp32)"
    )

    if args.check:
        from defer_tpu.ops.attention import attention_reference

        want = attention_reference(
            q.astype(jnp.float32),
            k.astype(jnp.float32),
            v.astype(jnp.float32),
            causal=args.causal,
        )
        err = float(
            jnp.max(jnp.abs(out.astype(jnp.float32) - want))
        )
        print(f"max abs err vs unsharded reference: {err:.4f}")
        assert err < 0.05, "sequence-parallel attention diverged"
        print("matches unsharded reference")


if __name__ == "__main__":
    main()
