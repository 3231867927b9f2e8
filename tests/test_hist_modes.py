"""Bucketed (segment-gather) vs masked (full-N) histogram growth equivalence.

The bucketed path is the perf-critical default: a DataPartition-style row
permutation (data_partition.hpp:20) with size-lattice gathered buckets makes
per-split histogram cost track leaf size, like the reference's ordered-index
kernels (dense_bin.hpp:71). The masked path is the simple oracle; both must
produce identical trees and row->leaf assignments.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from lightgbm_tpu.config import Config
from lightgbm_tpu.dataset import construct_dataset
from lightgbm_tpu.ops.grow import COUNTER_NAMES, grow_tree
from lightgbm_tpu.ops.split import SplitParams

PARAMS = SplitParams(0.0, 0.0, 0.0, 5, 1e-3, 0.0)


def _grow_both(X, y, bag=None, max_bin=63, leaves=31, mono=None):
    cfg = Config.from_params({"max_bin": max_bin, "objective": "binary"})
    ds = construct_dataset(
        X, cfg, label=y,
    )
    if mono is not None:
        ds.monotone_constraints = mono
    meta = {k: jnp.asarray(v) for k, v in ds.feature_meta_arrays().items()}
    n = ds.num_data
    grad = jnp.asarray((0.5 - y).astype(np.float32))
    hess = jnp.full((n,), 0.25, jnp.float32)
    bagm = jnp.ones((n,), jnp.float32) if bag is None else jnp.asarray(bag)
    fmask = jnp.ones((ds.num_features,), bool)
    kw = dict(
        num_leaves=leaves, max_depth=-1, num_bins=ds.max_num_bin, params=PARAMS,
        chunk=256,
    )
    bins = jnp.asarray(ds.bins)
    tm, lm = grow_tree(bins, grad, hess, bagm, fmask, meta, hist_mode="masked", **kw)
    tb, lb = grow_tree(bins, grad, hess, bagm, fmask, meta, hist_mode="bucketed", **kw)
    return tm, lm, tb, lb


def _assert_trees_equal(tm, tb, in_bag=None):
    for name in tm._fields:
        a, b = np.asarray(getattr(tm, name)), np.asarray(getattr(tb, name))
        if name == "counters":
            # how the tree was grown, not what it is: the two modes need the
            # same rows and the masked one passes over all N for each split;
            # under a row sample the masked one needs every row of a leaf, in
            # the bag or not, and the bucketed one, rooted at the sample, the
            # in-bag rows alone
            cm, cb = dict(zip(COUNTER_NAMES, a)), dict(zip(COUNTER_NAMES, b))
            for same in ("steps", "splits") + (
                    () if in_bag else ("hist_rows_needed", "part_rows_needed",
                                       "root_rows")):
                assert cm[same] == cb[same], same
            if in_bag:
                assert cb["root_rows"] == in_bag < cm["root_rows"]
                assert cb["hist_rows_needed"] < cm["hist_rows_needed"]
                assert cb["part_rows_needed"] < cm["part_rows_needed"]
            assert cm["hist_rows_streamed"] >= cb["hist_rows_streamed"]
            continue
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6, err_msg=name)


@pytest.mark.parametrize("seed", [0, 1])
def test_bucketed_matches_masked(seed):
    rng = np.random.RandomState(seed)
    X = rng.randn(3000, 7)
    X[::7, 2] = np.nan
    X[::5, 3] = 0.0
    y = (np.nan_to_num(X[:, 0]) + 0.5 * X[:, 1] > 0).astype(np.float64)
    tm, lm, tb, lb = _grow_both(X, y)
    _assert_trees_equal(tm, tb)
    np.testing.assert_array_equal(np.asarray(lm), np.asarray(lb))


def test_bucketed_matches_masked_with_bagging():
    rng = np.random.RandomState(2)
    X = rng.randn(2500, 6)
    y = (X[:, 0] > 0).astype(np.float64)
    bag = (rng.rand(2500) > 0.4).astype(np.float32)
    tm, lm, tb, lb = _grow_both(X, y, bag=bag)
    _assert_trees_equal(tm, tb, in_bag=int(bag.sum()))
    np.testing.assert_array_equal(np.asarray(lm), np.asarray(lb))


def test_bucketed_matches_masked_monotone():
    rng = np.random.RandomState(4)
    X = rng.randn(2000, 5)
    y = (X[:, 0] + 0.3 * X[:, 1] > 0).astype(np.float64)
    tm, lm, tb, lb = _grow_both(X, y, mono=[1, -1, 0, 0, 0])
    _assert_trees_equal(tm, tb)
    np.testing.assert_array_equal(np.asarray(lm), np.asarray(lb))


def test_bucketed_non_pow2_and_tiny():
    rng = np.random.RandomState(3)
    for n in (777, 1025, 4097):
        X = rng.randn(n, 4)
        y = (X[:, 0] > 0).astype(np.float64)
        tm, lm, tb, lb = _grow_both(X, y, leaves=7)
        _assert_trees_equal(tm, tb)
        np.testing.assert_array_equal(np.asarray(lm), np.asarray(lb))


def test_hist_impl_env_override():
    """LIGHTGBM_TPU_HIST_IMPL is frozen at import (histogram._ENV_IMPL) so
    routing is deterministic per process — the escape hatch bench.py pulls
    when Mosaic lowering fails re-execs the worker, so set-before-import is
    the contract. supported() itself is a pure shape+backend predicate."""
    import subprocess
    import sys

    from lightgbm_tpu.ops import hist_pallas

    # env acts only through the frozen routing constant, never supported()
    assert hist_pallas.supported(64, backend="tpu")
    assert not hist_pallas.supported(64, backend="cpu")

    code = (
        "from lightgbm_tpu.ops import histogram\n"
        "assert histogram._ENV_IMPL == 'xla', histogram._ENV_IMPL\n"
        "import numpy as np, jax.numpy as jnp\n"
        "bins = jnp.zeros((2, 512), jnp.int32)\n"
        "vals = jnp.ones((512, 3), jnp.float32)\n"
        "h = histogram.leaf_histogram(bins, vals, 16)\n"
        "assert np.asarray(h)[0, 0, 2] == 512\n"
        "print('ENV_ROUTED_OK')\n"
    )
    import os

    env = dict(os.environ)
    env["LIGHTGBM_TPU_HIST_IMPL"] = "xla"
    env.setdefault("JAX_PLATFORMS", "cpu")
    out = subprocess.run(
        [sys.executable, "-c", code], env=env, capture_output=True, text=True
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert "ENV_ROUTED_OK" in out.stdout
