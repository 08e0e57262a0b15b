"""The one call site of `jax.shard_map`.

Every sharded step in the serving stack goes through this wrapper so
the spec checker (analysis/shardcheck.py) has a single name to resolve
and the callers keep one spelling of the replication-check switch.
"""

from __future__ import annotations

from typing import Any

import jax


def shard_map(
    f: Any,
    mesh: Any,
    *,
    in_specs: Any,
    out_specs: Any,
    check_rep: bool = True,
) -> Any:
    """`jax.shard_map` over `mesh`. Bodies that end in an explicit
    collective whose output replication the checker cannot infer (e.g.
    a tiled `all_gather` of vocab-sharded logits) pass check_rep=False;
    everything else keeps the checker on."""
    return jax.shard_map(
        f,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=out_specs,
        check_vma=check_rep,
    )
