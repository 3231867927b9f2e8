"""``make_bucket_kernels``' batched segment histogram where a lane's segment
is over half the rows: the lanes are then taken one at a time (W lanes of such
a bucket gathered at once do not fit a chip at 1M x 968), and every lane reads
what its own W=1 pass reads, bit for bit."""
import jax.numpy as jnp
import numpy as np
import pytest

from lightgbm_tpu.ops.grow import bucket_sizes, make_bucket_kernels

N, F, B = 3000, 5, 16


def kernels():
    rng = np.random.RandomState(3)
    bins = jnp.asarray(rng.randint(0, B, (F, N)).astype(np.uint8))
    meta = {"num_bin": jnp.full((F,), B, jnp.int32), "missing_type": jnp.zeros((F,), jnp.int32),
            "default_bin": jnp.zeros((F,), jnp.int32)}
    vals = jnp.asarray(np.concatenate([rng.randn(N, 2), np.ones((N, 1))], axis=1).astype(np.float32))
    order = jnp.asarray(rng.permutation(N).astype(np.int32))
    return make_bucket_kernels(bins, meta, B, kb=4), vals, order


@pytest.mark.parametrize("cnt", [(2000, 500, 0), (N, 0, 0), (1400, 1600, 0), (700, 900, 1400)])
def test_lanes_of_a_bucket_over_half_the_rows_equal_their_own_passes(cnt):
    kern, vals, order = kernels()
    half = min(s for s in bucket_sizes(N) if 2 * s >= N)
    begin = np.concatenate([[0], np.cumsum(cnt)[:-1]]).astype(np.int32)
    cnt = np.asarray(cnt, np.int32)
    together = np.asarray(kern.segment_histogram_batch(vals, order, jnp.asarray(begin), jnp.asarray(cnt)))
    assert together.shape == (3, F, B, 3)
    for j in range(3):
        alone = np.asarray(kern.segment_histogram_batch(
            vals, order, jnp.asarray(begin[j:j + 1]), jnp.asarray(cnt[j:j + 1])))[0]
        assert np.array_equal(together[j], alone)
        rows = np.asarray(order)[begin[j]: begin[j] + cnt[j]]
        assert together[j][0, :, 2].sum() == len(rows)
    assert (cnt.max() > half) == (tuple(cnt) != (700, 900, 1400))
