#!/usr/bin/env python
"""Autoregressive generation demo: KV-cache decode, optionally
tensor-parallel.

    # single device
    python examples/generate.py --steps 32
    # tensor-parallel over an emulated 4-device mesh
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=4 \
        python examples/generate.py --tp 4 --steps 32

Prints prefill latency, per-token decode latency, and tokens/sec —
the numbers a serving deployment cares about. (Random weights: the
tokens are noise; the machinery is the demo.)
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir))

import argparse
import time

import jax
import jax.numpy as jnp

from defer_tpu.models.gpt import GptDecoder, SpmdGptDecoder
from defer_tpu.parallel.mesh import make_mesh
from defer_tpu.parallel.transformer_stack import TransformerConfig


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--layers", type=int, default=12)
    ap.add_argument("--dim", type=int, default=512)
    ap.add_argument("--heads", type=int, default=8)
    ap.add_argument("--ffn", type=int, default=2048)
    ap.add_argument("--vocab", type=int, default=32000)
    ap.add_argument("--max-len", type=int, default=512)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--batch", type=int, default=1)
    ap.add_argument("--steps", type=int, default=64)
    ap.add_argument("--tp", type=int, default=1)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--top-p", type=float, default=1.0)
    ap.add_argument("--min-p", type=float, default=0.0)
    ap.add_argument("--rep-penalty", type=float, default=1.0)
    ap.add_argument(
        "--family",
        choices=("gpt", "llama"),
        default="gpt",
        help="llama = RMSNorm + rotary + grouped-query attention + "
        "SwiGLU (biasless), with the KV cache sized by --kv-heads",
    )
    ap.add_argument(
        "--kv-heads",
        type=int,
        default=None,
        help="GQA kv head count, any family (llama default: heads/4; "
        "gpt default: MHA)",
    )
    ap.add_argument(
        "--speculate",
        type=int,
        default=0,
        metavar="K",
        help="after the plain loop, run greedy speculative decoding "
        "with a 1-layer draft proposing K tokens per target forward "
        "(needs --tp 1 --batch 1)",
    )
    args = ap.parse_args()

    if args.prompt_len + args.steps + 1 > args.max_len:
        raise SystemExit(
            f"--prompt-len {args.prompt_len} + --steps {args.steps} + 1 "
            f"exceeds --max-len {args.max_len}: the cache would clamp and "
            "benchmark degenerate work"
        )

    if args.family == "llama":
        from defer_tpu.models.llama import llama_config

        cfg = llama_config(
            num_layers=args.layers,
            dim=args.dim,
            num_heads=args.heads,
            num_kv_heads=args.kv_heads or max(1, args.heads // 4),
            ffn_dim=args.ffn,
            vocab_size=args.vocab,
            max_len=args.max_len,
        )
    else:
        # GQA is a shared-stack knob, not llama-exclusive: honor
        # --kv-heads here too instead of silently ignoring it.
        cfg = TransformerConfig(
            num_layers=args.layers,
            dim=args.dim,
            num_heads=args.heads,
            num_kv_heads=args.kv_heads,
            ffn_dim=args.ffn,
            vocab_size=args.vocab,
            max_len=args.max_len,
            norm_style="pre",
        )
    # Serving storage: params in the compute dtype (decode reads every
    # weight per token — fp32 storage would double the HBM traffic).
    if args.tp > 1:
        mesh = make_mesh({"model": args.tp}, jax.devices()[: args.tp])
        dec = SpmdGptDecoder(cfg, mesh=mesh)
        params = dec.shard_params(dec.cast_params(dec.init(jax.random.key(0))))
        print(f"tensor-parallel decode over {args.tp} devices "
              f"({jax.devices()[0].device_kind})")
    else:
        dec = GptDecoder(cfg)
        params = dec.cast_params(dec.init(jax.random.key(0)))
        print(f"single-device decode ({jax.devices()[0].device_kind})")

    prompt = jax.random.randint(
        jax.random.key(1), (args.batch, args.prompt_len), 0, args.vocab
    )
    step = dec.make_step()
    cache = dec.init_cache(args.batch)

    t0 = time.perf_counter()
    logits, cache = step(params, cache, prompt)
    logits.block_until_ready()
    t_prefill_compile = time.perf_counter() - t0

    from defer_tpu.models.gpt import (
        repetition_penalty,
        sample_token,
        seen_tokens_mask,
    )

    rng = jax.random.key(7)
    seen = (
        seen_tokens_mask(prompt, logits.shape[-1])
        if args.rep_penalty != 1.0
        else None
    )

    def pick(logits_last, rng, seen):
        lg = logits_last[:, -1, :]
        if seen is not None:
            lg = repetition_penalty(lg, seen, args.rep_penalty)
        tok, rng = sample_token(
            lg,
            rng,
            args.temperature,
            top_k=args.top_k,
            top_p=args.top_p,
            min_p=args.min_p,
        )
        if seen is not None:
            seen = seen.at[jnp.arange(tok.shape[0]), tok].set(True)
        return tok[:, None].astype(prompt.dtype), rng, seen

    nxt, rng, seen = pick(logits, rng, seen)
    t0 = time.perf_counter()
    logits, cache = step(params, cache, nxt)
    logits.block_until_ready()
    t_decode_compile = time.perf_counter() - t0

    t0 = time.perf_counter()
    for _ in range(args.steps):
        nxt, rng, seen = pick(logits, rng, seen)
        logits, cache = step(params, cache, nxt)
    logits.block_until_ready()
    dt = time.perf_counter() - t0

    per_tok = dt / args.steps
    print(
        f"prefill({args.prompt_len} tok) incl. compile: "
        f"{t_prefill_compile * 1e3:.0f} ms; decode compile: "
        f"{t_decode_compile * 1e3:.0f} ms"
    )
    print(
        f"steady decode: {per_tok * 1e3:.2f} ms/token, "
        f"{args.batch / per_tok:,.1f} tokens/sec"
        f" (batch {args.batch})"
    )

    if args.speculate and args.tp == 1 and args.batch == 1:
        if args.rep_penalty != 1.0:
            print(
                "note: --rep-penalty is not applied on the speculative "
                "path (its acceptance math covers the filtered softmax "
                "policy only), so the two decodes sample different "
                "policies"
            )
        import dataclasses

        from defer_tpu.models.speculative import speculative_generate

        # Draft shape: derive heads first, then round dim up to a
        # multiple so the head split always divides.
        d_heads = max(1, args.heads // 4)
        d_dim = -(-max(32, args.dim // 4) // d_heads) * d_heads
        draft_cfg = dataclasses.replace(
            cfg, num_layers=1, dim=d_dim,
            num_heads=d_heads,
            num_kv_heads=None,
            ffn_dim=max(64, args.ffn // 4),
        )
        draft = GptDecoder(draft_cfg)
        dparams = draft.cast_params(draft.init(jax.random.key(1)))
        keep = cfg.max_len - args.steps - args.speculate
        if keep < 1:
            raise SystemExit(
                f"--speculate {args.speculate} + --steps {args.steps} "
                f"leaves no prompt room in --max-len {cfg.max_len}"
            )
        short = prompt[:, : min(args.prompt_len, keep)]
        t0 = time.perf_counter()
        out, stats = speculative_generate(
            dec, params, draft, dparams, short, args.steps,
            k=args.speculate,
            temperature=args.temperature,
            top_k=args.top_k,
            top_p=args.top_p,
            min_p=args.min_p,
        )
        jax.block_until_ready(out)
        dt = time.perf_counter() - t0
        print(
            f"speculative (k={args.speculate}, 1-layer random draft): "
            f"{stats['target_steps']} target forwards for "
            f"{stats['plain_steps']} tokens, acceptance "
            f"{stats['acceptance']:.2f}, {dt / args.steps * 1e3:.2f} "
            "ms/token incl. compile (random drafts agree rarely; a "
            "trained draft is where the win comes from)"
        )
    elif args.speculate:
        print("--speculate needs --tp 1 and --batch 1; skipped")


if __name__ == "__main__":
    main()
