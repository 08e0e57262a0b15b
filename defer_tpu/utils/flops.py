"""Analytic FLOPs accounting + TPU peak-FLOPs table -> MFU.

The reference reports raw images/sec only (reference src/test.py:40-41);
absolute hardware efficiency is invisible. Here the benchmark derives
model FLOPs analytically from the IR (one node walk over inferred
shapes) and divides achieved FLOP/s by the chip's peak to report MFU —
the number that says how much of the TPU the pipeline actually uses.
"""

from __future__ import annotations

from typing import Any, Sequence

from defer_tpu.graph.ir import Graph, GraphParams

# Per-chip dense peak FLOP/s by `jax.Device.device_kind` substring,
# bf16 (the benchmark compute dtype). Public figures from Google's TPU
# system documentation.
_PEAK_BF16: tuple[tuple[str, float], ...] = (
    ("v5 lite", 197e12),  # v5e
    ("v5e", 197e12),
    ("v5p", 459e12),
    ("v6 lite", 918e12),  # Trillium
    ("v6e", 918e12),
    ("v4 lite", 138e12),  # v4i
    ("v4", 275e12),
    ("v3", 123e12),  # per chip (2 cores)
    ("v2", 45e12),
)


def lookup_device_table(
    device_kind: str, table: tuple[tuple[str, float], ...]
) -> float | None:
    """First (substring, value) match for a device kind — the one
    lookup shared by the peak-FLOPs and peak-bandwidth tables (order
    matters: more specific keys like 'v4 lite' come before 'v4')."""
    kind = device_kind.lower()
    for key, val in table:
        if key in kind:
            return val
    return None


def peak_flops(device_kind: str) -> float | None:
    """Dense bf16 peak FLOP/s for a TPU device kind; None if unknown
    (e.g. the CPU backend — MFU is then not reported)."""
    return lookup_device_table(device_kind, _PEAK_BF16)


# Parameters that act as one side of a contraction: FLOPs = 2 x
# (output spatial/batch positions) x (param elements). Holds for conv
# (kernel HWIO, grouped or not), depthwise (HW1C), separable (dw + pw
# summed), and dense ((in, out)).
_CONTRACTION_PARAMS = ("kernel", "dw_kernel", "pw_kernel")


def node_flops(
    op: str,
    node_params: dict[str, Any],
    out_shape: Sequence[int],
) -> float:
    """Forward FLOPs of one node given its output shape."""
    import numpy as np

    out_elems = float(np.prod(out_shape)) if out_shape else 1.0
    if op == "dense":
        k = node_params.get("kernel")
        if k is None:
            return out_elems
        in_features = k.shape[0]
        return 2.0 * out_elems * in_features
    if op == "mha" and "wq" in node_params:
        # Head-count invariant: 4 QKVO projections at 2*B*S*D*D each
        # + the two S x S contractions at 2*B*S*S*D each.
        b, s, d = out_shape[-3], out_shape[-2], out_shape[-1]
        return 2.0 * b * s * (4.0 * d * d + 2.0 * s * d)
    kernels = [
        node_params[p] for p in _CONTRACTION_PARAMS if p in node_params
    ]
    if kernels and op in ("conv", "depthwise_conv", "separable_conv"):
        out_positions = out_elems / out_shape[-1]
        total = 0.0
        for k in kernels:
            # kernel [kh, kw, cin/groups, cout]: each output position
            # contracts kh*kw*(cin/groups) per channel -> 2 x positions
            # x kernel.size MACs-as-FLOPs.
            total += 2.0 * out_positions * float(k.size)
        return total
    # Everything else (BN folded at inference, activations, pools, adds,
    # softmax) is a small constant per output element.
    return out_elems


def flops_by_node(
    graph: Graph,
    params: GraphParams,
    input_shape: Sequence[int],
    input_dtype: Any = None,
    *,
    specs: Any = None,
) -> dict[str, float]:
    """Per-node forward FLOPs for one input of `input_shape` (batch dim
    included), from the IR's single source of shape truth. `specs`
    short-circuits shape inference when the caller already ran it."""
    import jax.numpy as jnp

    if specs is None:
        specs = graph.infer_shapes(
            params,
            input_shape,
            dtype=jnp.float32 if input_dtype is None else input_dtype,
        )
    return {
        node.name: node_flops(
            node.op, params.get(node.name, {}), specs[node.name].shape
        )
        for node in graph.nodes
    }


def balanced_cuts(
    graph: Graph,
    params: GraphParams,
    input_shape: Sequence[int],
    num_stages: int,
    candidates: Sequence[Any] | None = None,
    input_dtype: Any = None,
) -> list[Any]:
    """Pick num_stages-1 boundaries that split the graph into stages of
    near-equal FLOPs (not equal candidate COUNT — the index-even picks
    of Model.default_cuts give ResNet50's early high-resolution convs
    far more work than the tail). Candidates default to
    chain_boundaries(graph); each is scored by the cumulative FLOPs of
    everything at or before its last member, and the picks closest to
    the i/num_stages fractions win (kept strictly increasing).
    """
    from defer_tpu.graph.partition import chain_boundaries

    if num_stages < 1:
        raise ValueError("num_stages must be >= 1")
    if num_stages == 1:
        return []
    if candidates is None:
        candidates = chain_boundaries(graph)
    if num_stages - 1 > len(candidates):
        raise ValueError(
            f"{len(candidates)} candidate boundaries cannot make "
            f"{num_stages} stages"
        )
    per_node = flops_by_node(graph, params, input_shape, input_dtype)
    cum: dict[str, float] = {}
    running = 0.0
    for node in graph.nodes:
        running += per_node[node.name]
        cum[node.name] = running
    total = running

    def score(cand) -> float:
        members = (cand,) if isinstance(cand, str) else cand
        return max(cum[m] for m in members)

    scores = [score(c) for c in candidates]
    picks: list[int] = []
    prev = -1
    remaining = num_stages - 1
    for k in range(1, num_stages):
        target = total * k / num_stages
        # Best candidate for this fraction that still leaves room for
        # the remaining picks and stays after the previous one.
        lo = prev + 1
        hi = len(candidates) - (remaining - len(picks) - 1)
        best = min(
            range(lo, hi), key=lambda i: abs(scores[i] - target)
        )
        picks.append(best)
        prev = best
    return [candidates[i] for i in picks]
