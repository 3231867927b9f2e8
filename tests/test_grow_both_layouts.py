"""The serial learner hands ``grow_tree`` the bin matrix in both layouts
(``bins`` ``[F, N]`` and ``bins_nf`` ``[N, F]``): the segment gathers read
the rows of the second, the partition the columns of the first. Handed one
or both, the speculative and the sequential grower give the same tree and
the same leaf ids, on a table with missing values and on one with an EFB
bundle (where the partition's column is the group's row of ``bins``). What
the TPU compiler makes of the two forms is held by
tests/test_hist_pallas_tpu_compile.py."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from scipy import sparse

import lightgbm_tpu.ops.grow as grow_mod
from lightgbm_tpu.config import Config
from lightgbm_tpu.dataset import construct_dataset
from lightgbm_tpu.ops.split import SplitParams


def _nan_table():
    rng = np.random.RandomState(3)
    n = 3000
    X = rng.randn(n, 8)
    X[:, 3] = rng.randint(0, 8, n)
    X[rng.rand(n, 8) < 0.3] = np.nan
    y = X[:, 0] * 2 + np.nan_to_num(X[:, 1] * X[:, 2]) + np.isnan(X[:, 4])
    return X, (y + 0.3 * rng.randn(n) > 0.5), {}


def _efb_table():
    rng = np.random.RandomState(9)
    n = 3000
    hot = rng.randint(0, 12, n)
    Xs = np.zeros((n, 12))
    Xs[np.arange(n), hot] = 1.0
    X = np.hstack([rng.randn(n, 4), Xs])
    y = X[:, 0] + (hot % 3 == 0) + 0.3 * rng.randn(n) > 0.5
    # a sparse table is what the dataset bundles: the 12 exclusive columns
    return sparse.csr_matrix(X), y, {"max_conflict_rate": 0.0}


TABLES = {"nan": _nan_table, "efb": _efb_table}


@pytest.fixture
def grow_mode(monkeypatch):
    def set_mode(mode):
        monkeypatch.setattr(grow_mod, "_ENV_GROW", mode)
        jax.clear_caches()

    yield set_mode
    jax.clear_caches()


@pytest.mark.parametrize("table", sorted(TABLES))
@pytest.mark.parametrize("mode", ["seq", "spec"])
def test_one_layout_or_both_grow_the_same_tree(grow_mode, mode, table):
    X, y, extra = TABLES[table]()
    ds = construct_dataset(
        X, Config.from_params(dict(extra, max_bin=63, objective="binary")),
        label=y.astype(np.float32),
    )
    assert ds.is_bundled == (table == "efb")
    meta = {k: jnp.asarray(v) for k, v in ds.feature_meta_arrays().items()}
    if table == "nan":
        assert np.any(np.asarray(meta["missing_type"]) != 0)
    n = ds.num_data
    bins = jnp.asarray(ds.bins)
    grow_mode(mode)

    def grow(bins_nf):
        tree, leaf_id = grow_mod.grow_tree(
            bins, jnp.asarray(0.5 - y, jnp.float32),
            jnp.full((n,), 0.25, jnp.float32), jnp.ones((n,), jnp.float32),
            jnp.ones((ds.num_features,), bool), meta, num_leaves=31,
            max_depth=-1, num_bins=ds.max_num_bin,
            num_group_bins=ds.max_group_bins if ds.is_bundled else None,
            params=SplitParams(0.0, 0.0, 0.0, 5, 1e-3, 0.0), bins_nf=bins_nf,
        )
        assert grow_mod._LAST_GROW_MODE == mode
        return tree, np.asarray(leaf_id)

    one, one_leaf = grow(None)
    both, both_leaf = grow(jnp.asarray(np.ascontiguousarray(ds.bins.T)))
    assert int(one.num_leaves) == 31
    for name in one._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(one, name)), np.asarray(getattr(both, name)),
            err_msg=name,
        )
    np.testing.assert_array_equal(one_leaf, both_leaf)
