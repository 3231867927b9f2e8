"""JX011 bad fixture: a scalar-prefetch grid spec whose index maps forget the
prefetch operands, whose kernel reads a grid axis that is not there, and
which is invoked without one of its scalar operands."""
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

FB = 8
LO = 8


def _kernel(slot_ref, live_ref, bins_ref, vt_ref, out_ref, *, hi_n):
    c = pl.program_id(2)  # BAD: the grid spec's grid has rank 2

    @pl.when(c < live_ref[0])
    def _chunk():
        out_ref[0] += (bins_ref[:] * vt_ref[:]).astype(jnp.float32)


def bad_call(slot, bins, vt, n_chunks, C, K, HI, W):
    return pl.pallas_call(
        functools.partial(_kernel, hi_n=HI),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(4, n_chunks),
            in_specs=[
                # BAD: two grid axes and two prefetch operands make four
                pl.BlockSpec((FB, C), lambda f8, c: (f8, c)),
                pl.BlockSpec((K, C), lambda f8, c, slot, live: (0, c)),
            ],
            out_specs=pl.BlockSpec(
                (None, FB, LO, HI), lambda f8, c, slot, live: (slot[c], f8, 0, 0)
            ),
        ),
        out_shape=jax.ShapeDtypeStruct((W, 32, LO, HI), jnp.float32),
    )(slot, bins, vt)  # BAD: one scalar operand short
