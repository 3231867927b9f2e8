"""JX007 negative (parallel/ scope): every spec axis matches the Mesh."""
import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax import shard_map


def make_mesh(devices):
    return Mesh(np.array(devices), ("data",))


def shard_rows(mesh, arr, row_axis):
    spec = [None] * arr.ndim
    spec[row_axis] = "data"  # declared: clean
    return jax.device_put(arr, NamedSharding(mesh, P(*spec)))


def wrap(f, mesh):
    return shard_map(
        f,
        mesh=mesh,
        in_specs=(P(None, "data"), P("data")),
        out_specs=P("data"),
    )
