"""The routed Pallas histogram kernel compiled for a v5e that is described,
not attached: Mosaic accepts the lane-dense body at the largest chunk the
``_BYTES_PER_COL`` table allows (what interpret mode cannot show). Nothing
runs, so nothing here is a device number. The topology is described inside
a fixture: one worker loads the TPU compiler, and only when it is given
this file."""
import os

import jax
import jax.numpy as jnp
import pytest

from lightgbm_tpu.ops import hist_pallas


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler here, or another process has it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize(
    "dtype_name,num_bins,lanes",
    [("float32", 255, 0), ("bfloat16", 255, 0), ("float32", 63, 8)],
)
def test_mosaic_accepts_the_largest_chunk(one_chip, dtype_name, num_bins, lanes):
    cap = hist_pallas._max_chunk_for("pallas")
    F, N = 16, 2 * cap
    lead = (lanes,) if lanes else ()
    bins = jax.ShapeDtypeStruct(lead + (F, N), jnp.uint8, sharding=one_chip)
    vals = jax.ShapeDtypeStruct(lead + (N, 3), jnp.float32, sharding=one_chip)

    def one(b, v):
        return hist_pallas._histogram_pallas_fb(
            b, v, num_bins, chunk=cap, dtype_name=dtype_name
        )

    fn = jax.vmap(one) if lanes else one
    compiled = jax.jit(fn).lower(bins, vals).compile()
    assert "tpu_custom_call" in compiled.as_text()
