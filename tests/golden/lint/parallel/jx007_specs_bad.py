"""JX007 positive (parallel/ scope): undeclared axes in shard_map specs and
in the build-a-spec-then-splat PartitionSpec idiom."""
import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax import shard_map


def make_mesh(devices):
    return Mesh(np.array(devices), ("data",))


def shard_rows(mesh, arr, row_axis):
    spec = [None] * arr.ndim
    spec[row_axis] = "rows"  # JX007: "rows" not declared (splatted into P)
    return jax.device_put(arr, NamedSharding(mesh, P(*spec)))


def wrap(f, mesh):
    return shard_map(
        f,
        mesh=mesh,
        in_specs=(P(None, "data"), "model"),  # JX007: bare "model" literal
        out_specs=P("data"),
    )
