"""Package CLI: `python -m defer_tpu <command>`.

The reference has no tooling surface at all (drivers are edited by
hand, reference src/test.py:13-28); these subcommands cover the
workflows its users actually performed manually:

    info         topology + registered models/ops
    partition    compute a cut list (the reference documents its own
                 in a comment, src/test.py:24-28)
    roofline     analytic perf triage for a zoo model
    serve-stage  run a remote stage worker (the `node.py` analogue)
"""

from __future__ import annotations

import argparse
import json


def cmd_info(args: argparse.Namespace) -> None:
    from defer_tpu.models import model_names
    from defer_tpu.ops.registry import op_names
    from defer_tpu.parallel.mesh import describe_topology

    print(json.dumps(
        {
            "topology": describe_topology(),
            "models": model_names(),
            "num_ops": len(op_names()),
        },
        indent=2,
    ))


def cmd_partition(args: argparse.Namespace) -> None:
    import jax

    from defer_tpu.graph.partition import partition
    from defer_tpu.models import get_model
    from defer_tpu.utils.flops import balanced_cuts, flops_by_node

    model = get_model(args.model)
    params = model.init(jax.random.key(0))
    shape = (1, *model.input_shape)
    if args.auto:
        cuts = balanced_cuts(
            model.graph,
            params,
            shape,
            args.stages,
            model.cut_candidates or None,
            input_dtype=model.input_dtype,
        )
    else:
        cuts = model.default_cuts(args.stages)
    stages = partition(model.graph, cuts) if cuts else [model.graph]
    per = flops_by_node(
        model.graph, params, shape, input_dtype=model.input_dtype
    )
    total = sum(per.values())
    print(f"{args.model}: {args.stages} stages, cuts = {list(cuts)}")
    for i, s in enumerate(stages):
        fl = sum(per[n.name] for n in s.nodes if n.op != "input")
        print(
            f"  stage {i}: {len(s.nodes):4d} nodes, "
            f"{fl / 1e9:8.2f} GFLOP ({fl / total:5.1%})"
        )


def cmd_roofline(args: argparse.Namespace) -> None:
    import jax
    import jax.numpy as jnp

    from defer_tpu.config import DeferConfig
    from defer_tpu.models import get_model
    from defer_tpu.parallel.pipeline import cast_params_to_storage
    from defer_tpu.utils.roofline import format_report, roofline_report

    model = get_model(args.model)
    params = model.init(jax.random.key(0))
    dtype = jnp.bfloat16 if args.dtype == "bfloat16" else jnp.float32
    if model.input_dtype is not None and not jnp.issubdtype(
        model.input_dtype, jnp.floating
    ):
        in_dtype = model.input_dtype  # token ids stay integral
    else:
        in_dtype = dtype
    params = cast_params_to_storage(
        params, DeferConfig(compute_dtype=dtype)
    )
    kind = args.device_kind
    if kind is None:
        devs = jax.devices()
        kind = devs[0].device_kind if devs else "unknown"
    print(
        format_report(
            roofline_report(
                model.graph,
                params,
                (args.batch, *model.input_shape),
                kind,
                input_dtype=in_dtype,
                top=args.top,
            )
        )
    )


def cmd_serve_stage(args: argparse.Namespace) -> None:
    from defer_tpu.runtime.remote_stage import main as serve_main

    argv = ["--listen", str(args.listen), "--next", args.next]
    if args.accept_timeout is not None:
        argv += ["--accept-timeout", str(args.accept_timeout)]
    if args.handoff_timeout is not None:
        argv += ["--handoff-timeout", str(args.handoff_timeout)]
    if args.expect_peer:
        argv += ["--expect-peer"]
    serve_main(argv)


def main(argv: list[str] | None = None) -> None:
    ap = argparse.ArgumentParser(prog="defer_tpu", description=__doc__)
    sub = ap.add_subparsers(dest="cmd", required=True)

    sub.add_parser("info", help="topology + registered models/ops")

    p = sub.add_parser("partition", help="compute and describe a cut list")
    p.add_argument("model")
    p.add_argument("--stages", type=int, default=2)
    p.add_argument(
        "--auto",
        action="store_true",
        help="FLOPs-balanced cuts instead of the model's defaults",
    )

    p = sub.add_parser("roofline", help="analytic perf triage")
    p.add_argument("model")
    p.add_argument("--batch", type=int, default=128)
    p.add_argument("--dtype", choices=["bfloat16", "float32"],
                   default="bfloat16")
    p.add_argument(
        "--device-kind",
        default=None,
        help="e.g. 'TPU v5 lite'; default: the first visible device",
    )
    p.add_argument("--top", type=int, default=8)

    p = sub.add_parser(
        "serve-stage", help="run a remote stage worker (node.py analogue)"
    )
    p.add_argument("--listen", type=int, default=5000)
    p.add_argument("--next", required=True)
    p.add_argument("--accept-timeout", type=float, default=None)
    p.add_argument("--handoff-timeout", type=float, default=None)
    p.add_argument(
        "--expect-peer",
        action="store_true",
        help="mid-chain worker: a missing upstream activation peer is "
        "a hard error, not a clean zero-work exit",
    )

    args = ap.parse_args(argv)
    {
        "info": cmd_info,
        "partition": cmd_partition,
        "roofline": cmd_roofline,
        "serve-stage": cmd_serve_stage,
    }[args.cmd](args)


if __name__ == "__main__":
    main()
