"""The bucketed grower is rooted at the row sample (``ops/grow.grow_tree``):
``order`` starts with the in-bag rows and the root segment is theirs alone.
Held against the mask form it replaces (the masked mode, which passes over all
N rows with zeros for the rows out of the bag), for GOSS, plain bagging and
rf, on the speculative and the sequential grower; the rows out of the bag get
the leaf a walk down the finished tree gives them; a mask of ones is the
identity; one executable serves sampled and unsampled trees; and the booster's
record of its draws."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import lightgbm_tpu as lgb
import lightgbm_tpu.models.gbdt as gbdt_mod
import lightgbm_tpu.ops.grow as grow_mod
from lightgbm_tpu.config import Config
from lightgbm_tpu.dataset import construct_dataset
from lightgbm_tpu.obs import trace
from lightgbm_tpu.ops.predict import make_predict_tree, tree_predict_leaf
from lightgbm_tpu.ops.split import SplitParams

from test_grow_both_layouts import TABLES

SAMPLERS = {
    "goss": {"boosting": "goss", "learning_rate": 0.5, "top_rate": 0.2, "other_rate": 0.1},
    "bagging": {"bagging_fraction": 0.5, "bagging_freq": 1},
    "rf": {"boosting": "rf", "bagging_fraction": 0.5, "bagging_freq": 1},
}
# A leaf's sums run over the same rows in both forms, grouped otherwise: the
# masked form adds all N products (zeros among them) chunk by chunk, the rooted
# one the in-bag rows of a gathered segment. A float32 sum of n terms is exact
# to about sqrt(n) ulps of its largest partial sum; with at most a few thousand
# rows a leaf here, a leaf value (a ratio of two such sums) and a score (a sum
# of 6 leaf values) agree to well under 1e-5 relative.
FLOAT32_BOUND = dict(rtol=1e-5, atol=1e-6)


@pytest.fixture
def grow_mode(monkeypatch):
    def set_mode(mode):
        monkeypatch.setattr(grow_mod, "_ENV_GROW", mode)
        jax.clear_caches()

    yield set_mode
    jax.clear_caches()


def _rows(seed=0, n=4000):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, 10)
    X[rng.rand(n, 10) < 0.1] = np.nan
    y = (X[:, 0] * 2 + np.nan_to_num(X[:, 1]) + 0.5 * rng.randn(n) > 0).astype(float)
    return X, y


def _trained(params, rounds=6, **more):
    X, y = _rows()
    bst = lgb.train(dict({"objective": "binary", "verbosity": -1, "num_leaves": 15,
                          "min_data_in_leaf": 5}, **params, **more),
                    lgb.Dataset(X, label=y), num_boost_round=rounds)
    return bst, bst._gbdt.trees(), np.asarray(bst._gbdt.scores)


@pytest.mark.parametrize("mode", ["seq", "spec"])
@pytest.mark.parametrize("sampler", sorted(SAMPLERS))
def test_rooted_at_the_sample_grows_the_masks_trees(grow_mode, mode, sampler):
    grow_mode(mode)
    trace.reset()
    bst, rooted, rooted_scores = _trained(SAMPLERS[sampler])
    assert grow_mod._LAST_GROW_MODE == mode
    bst.model_to_string()
    roots = [e["args"]["root_rows"] for e in trace.events() if e["name"] == "grow.counters"]
    in_bag = [int(d["in_bag"].sum()) for d in bst.sample_draws()]
    assert len(in_bag) == (4 if sampler == "goss" else 6) and set(in_bag) == {
        1200 if sampler == "goss" else 2000}
    assert roots == [4000.0] * (6 - len(in_bag)) + [float(n) for n in in_bag]
    _, masked, masked_scores = _trained(SAMPLERS[sampler], tpu_hist_mode="masked")
    assert len(rooted) == len(masked) == 6
    for a, b in zip(rooted, masked):
        assert a.num_leaves == b.num_leaves > 2
        for exact in ("split_feature", "threshold_bin", "left_child", "right_child",
                      "leaf_count", "internal_count"):
            np.testing.assert_array_equal(getattr(a, exact), getattr(b, exact), err_msg=exact)
        np.testing.assert_allclose(a.leaf_value, b.leaf_value, **FLOAT32_BOUND)
    np.testing.assert_allclose(rooted_scores, masked_scores, **FLOAT32_BOUND)


@pytest.mark.parametrize("table", sorted(TABLES))
@pytest.mark.parametrize("mode", ["seq", "spec"])
def test_rows_out_of_the_bag_get_the_leaf_a_walk_down_the_tree_gives(grow_mode, mode, table):
    """Missing values and a categorical column, and an EFB bundle, whose
    split columns are decoded from the group's."""
    X, y, extra = TABLES[table]()
    ds = construct_dataset(
        X, Config.from_params(dict(extra, max_bin=63, objective="binary",
                                   categorical_feature=[3] if table == "nan" else [])),
        label=y.astype(np.float32))
    assert ds.is_bundled == (table == "efb")
    meta = {k: jnp.asarray(v) for k, v in ds.feature_meta_arrays().items()}
    assert ("is_categorical" in meta) == (table == "nan")
    n = ds.num_data
    rng = np.random.RandomState(5)
    bag = jnp.asarray((rng.rand(n) < 0.3).astype(np.float32))
    bins = jnp.asarray(ds.bins)
    bins_nf = jnp.asarray(np.ascontiguousarray(ds.bins.T))
    grad = jnp.asarray((0.5 - y) * (1 + rng.rand(n)), jnp.float32) * bag
    grow_mode(mode)

    def grow(hist_mode):
        return grow_mod.grow_tree(
            bins, grad, jnp.full((n,), 0.25, jnp.float32), bag,
            jnp.ones((ds.num_features,), bool),
            meta, num_leaves=31, max_depth=-1, num_bins=ds.max_num_bin,
            num_group_bins=ds.max_group_bins if ds.is_bundled else None,
            params=SplitParams(0.0, 0.0, 0.0, 5, 1e-3, 0.0), bins_nf=bins_nf,
            hist_mode=hist_mode)

    tree, leaf_id = grow("bucketed")
    assert grow_mod._LAST_GROW_MODE == mode and int(tree.num_leaves) > 8
    counters = dict(zip(grow_mod.COUNTER_NAMES, np.asarray(tree.counters)))
    assert counters["root_rows"] == float(bag.sum()) < n
    walked = tree_predict_leaf(bins_nf, make_predict_tree(tree, meta))
    np.testing.assert_array_equal(np.asarray(leaf_id), np.asarray(walked))
    by_mask, mask_leaf = grow("masked")
    np.testing.assert_array_equal(np.asarray(leaf_id), np.asarray(mask_leaf))
    np.testing.assert_array_equal(np.asarray(tree.leaf_count), np.asarray(by_mask.leaf_count))


def _tree_sections(text):
    return text.split("\nTree=")[1:]


def test_a_mask_of_ones_is_the_identity(grow_mode):
    """GOSS's first 1 / learning_rate iterations hand the grower a mask of
    ones: their trees are plain boosting's, byte for byte, and the next tree
    is not."""
    grow_mode("spec")
    plain = _tree_sections(_trained({"learning_rate": 0.25})[0].model_to_string())
    sampled = _tree_sections(_trained(dict(SAMPLERS["goss"], learning_rate=0.25))[0]
                             .model_to_string())
    assert plain[:4] == sampled[:4]
    assert plain[4] != sampled[4]


def test_one_executable_serves_sampled_and_unsampled_trees(grow_mode):
    grow_mode("spec")
    bst = _trained(SAMPLERS["goss"])[0]
    assert [d["iteration"] for d in bst.sample_draws()] == [2, 3, 4, 5]
    assert grow_mod.grow_tree._cache_size() == 1


def test_the_record_of_draws_keeps_the_law_and_its_bound(monkeypatch):
    trace.reset()
    bst, _, _ = _trained(SAMPLERS["goss"])
    draws = bst.sample_draws()
    assert [d["iteration"] for d in draws] == [2, 3, 4, 5]
    for d in draws:
        assert d["in_bag"].shape == d["amplified"].shape == (4000,)
        assert d["in_bag"].sum() == 800 + 400 and d["amplified"].sum() == 400
        assert not np.any(d["amplified"] & ~d["in_bag"])
        assert d["multiplier"] == (4000 - 800) / 400
    noted = [e["args"] for e in trace.events() if e["name"] == "sample.counters"]
    assert [(c["iteration"], c["in_bag"], c["top_k"], c["other_k"], c["multiplier"])
            for c in noted] == [(0, 4000, 4000, 0, 1.0), (1, 4000, 4000, 0, 1.0)] + [
                (k, 1200, 800, 400, 8.0) for k in (2, 3, 4, 5)]
    assert sum(e["name"] == "train.sample" for e in trace.events()) == 6
    # two draws' bits (2 x 500 bytes each) fit, a third drops the oldest
    monkeypatch.setattr(gbdt_mod, "DRAW_STORE_BYTES", 2000)
    bst, _, _ = _trained(SAMPLERS["goss"])
    assert [d["iteration"] for d in bst.sample_draws()] == [4, 5]
    # a booster that draws no rows keeps none, and notes none
    trace.reset()
    assert _trained({})[0].sample_draws() == []
    assert not [e for e in trace.events() if e["name"] in ("sample.counters", "train.sample")]
