"""POSITIVE fixture: shard-spec.

Three ways a hand-maintained shard_map call drifts from reality:

  * ``in_specs`` arity != the body's positional signature (traces as
    an opaque pytree error at runtime; one line here);
  * a PartitionSpec naming an axis the (literally constructed) mesh
    does not have;
  * ``check_rep=False`` with no justification ignore.

Expected: 3 findings.
"""

from jax.sharding import Mesh, PartitionSpec as P
from defer_tpu.utils.compat import shard_map


def build(devs):
    mesh = Mesh(devs, ("model",))

    def body(a, b):
        return a + b

    f = shard_map(  # arity: 1 spec for a 2-parameter body
        body,
        mesh,
        in_specs=(P("model"),),
        out_specs=P("model"),
    )
    g = shard_map(
        body,
        mesh,
        in_specs=(P("model"), P("data")),  # "data" is not a mesh axis
        out_specs=P("model"),
        check_rep=False,  # and no ignore says why
    )
    return f, g
