"""defer_tpu — a TPU-native pipeline-parallel DNN inference framework.

Built from scratch in JAX/XLA with the capabilities of DEFER
(arXiv 2201.06769; reference impl at /root/reference). The reference's
dispatcher/compute-node/TCP-socket architecture (reference
src/dispatcher.py, src/node.py, src/node_state.py) is replaced by a
single-controller JAX program: a model is partitioned at named cut-points
into jit-compiled stages, each pinned to one TPU core, and activations
flow core-to-core over ICI instead of ZFP+LZ4-compressed sockets.

Public API (mirrors the reference's user model, reference src/test.py:21,47):

    from defer_tpu import DEFER
    defer = DEFER()                       # discovers the TPU mesh
    defer.run_defer(model, ["add_8"], input_q, output_q)
"""

import os

import jax


def _place_compile_cache() -> None:
    """The one place this package chooses JAX's persistent compile
    cache: where JAX_COMPILATION_CACHE_DIR is set JAX reads it and
    nothing is touched; otherwise the cache is `.jax_cache/` at the
    root of the checkout. The path is part of the cache key, so it is
    derived from this file and is the same for every process."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        jax.config.update(
            "jax_compilation_cache_dir", os.path.join(root, ".jax_cache")
        )


_place_compile_cache()

from defer_tpu.api import DEFER, run_local_inference  # noqa: E402
from defer_tpu.config import DeferConfig
from defer_tpu.graph.ir import Graph, GraphBuilder, OpNode
from defer_tpu.graph.partition import (
    PartitionError,
    partition,
    stage_params,
    validate_cut_points,
)
from defer_tpu.graph.serialize import graph_from_json, graph_to_json
from defer_tpu import obs
from defer_tpu.parallel import (
    Pipeline,
    ReplicatedPipeline,
    ShardedInference,
    make_mesh,
)

__version__ = "0.5.0"

__all__ = [
    "DEFER",
    "DeferConfig",
    "Graph",
    "GraphBuilder",
    "OpNode",
    "PartitionError",
    "Pipeline",
    "ReplicatedPipeline",
    "ShardedInference",
    "graph_from_json",
    "graph_to_json",
    "make_mesh",
    "obs",
    "partition",
    "run_local_inference",
    "stage_params",
    "validate_cut_points",
]
