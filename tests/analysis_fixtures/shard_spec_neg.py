"""NEGATIVE fixture: shard-spec.

The same shapes written correctly: specs match the body arity, every
literal axis exists on the literally-constructed mesh, the one
``check_rep=False`` carries its justification ignore, and a dynamic
mesh (``self.mesh``) is skipped rather than guessed at.
"""

from jax.sharding import Mesh, PartitionSpec as P
from defer_tpu.utils.compat import shard_map


def build(devs):
    mesh = Mesh(devs, ("model",))

    def body(a, b):
        return a + b

    f = shard_map(
        body,
        mesh,
        in_specs=(P("model"), P()),
        out_specs=P("model"),
    )
    g = shard_map(
        lambda a: a * 2,
        mesh,
        in_specs=(P("model"),),
        out_specs=P("model"),
        # analysis: ignore[shard-spec] body ends in a tiled all_gather whose replication the checker cannot infer
        check_rep=False,
    )
    return f, g


class Dynamic:
    def run(self, xs):
        # Mesh held on the instance: axis names are not statically
        # knowable, so the axis check must stay silent here.
        return shard_map(
            lambda a: a,
            self.mesh,
            in_specs=(P("heads"),),
            out_specs=P("heads"),
        )(xs)
