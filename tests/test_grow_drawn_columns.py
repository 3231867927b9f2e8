"""Under ``feature_fraction`` the serial grower is handed the tree's drawn
columns and not a mask over all of them (``models/gbdt.py`` ``_train_tree``,
``_take_columns``, ``_table_columns``): held against the mask form it
replaces, which every case that cannot take the drawn form still runs
(``GBDT.column_draw_fallback_reason``). Sorted indices keep the scan's
tie-break, so on one backend the two forms grow the same trees, byte for byte.
Nothing here is a device number."""
import functools
import json

import jax
import numpy as np
import pytest

import lightgbm_tpu as lgb
import lightgbm_tpu.models.gbdt as gbdt_mod
import lightgbm_tpu.ops.grow as grow_mod
import lightgbm_tpu.ops.histogram as hist_mod
from lightgbm_tpu.obs import trace
from lightgbm_tpu.ops import hist_pallas

F = 20
BASE = {"verbosity": -1, "num_leaves": 15, "min_data_in_leaf": 5}
KINDS = {
    "binary": ({"objective": "binary"}, False),
    "three_classes": ({"objective": "multiclass", "num_class": 3}, False),
    # every column has a NaN bin: the missing types ride a gathered feature_meta
    "nan_columns": ({"objective": "binary"}, True),
    # rooted at its sampled rows *and* handed its drawn columns
    "goss": ({"objective": "binary", "boosting": "goss", "learning_rate": 0.5}, True),
}


def _table(nan, classes=2, n=3000, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, F)
    if nan:
        X[rng.rand(n, F) < 0.1] = np.nan
    logit = 2 * np.nan_to_num(X[:, 0]) + np.nan_to_num(X[:, 5]) - np.nan_to_num(X[:, 11])
    y = np.digitize(logit + 0.3 * rng.randn(n), np.quantile(logit, np.arange(1, classes) / classes))
    return X, y.astype(float)


def _train(params, X, y, rounds=6, **more):
    trace.reset()
    bst = lgb.train(dict(BASE, **params), lgb.Dataset(X, label=y), rounds, **more)
    text = bst.model_to_string()  # materialises the trees: one event each
    counted = [e["args"] for e in trace.events() if e["name"] == "grow.counters"]
    return bst, text, counted


@pytest.fixture
def as_a_mask(monkeypatch):
    """The mask form on the drawn form's own ground, for the comparison."""
    def hold():
        monkeypatch.setattr(gbdt_mod.GBDT, "column_draw_fallback_reason",
                            lambda self: "held as a mask by the test")
    return hold


def _host_stream(seed, features, k, draws):
    rng = np.random.RandomState(seed & 0x7FFFFFFF)
    return [np.sort(rng.choice(features, size=k, replace=False)) for _ in range(draws)]


def _names_only_its_draws(bst):
    draws = bst.feature_draws()
    trees = bst._gbdt.trees()
    assert [d["tree"] for d in draws] == list(range(len(trees)))
    for d, t in zip(draws, trees):
        assert set(t.split_feature[: t.num_leaves - 1]) <= set(d["columns"].tolist())
    return draws


@pytest.mark.parametrize("fraction", [0.8, 0.3])
@pytest.mark.parametrize("kind", sorted(KINDS))
def test_the_drawn_form_grows_the_mask_forms_trees(as_a_mask, kind, fraction):
    params, nan = KINDS[kind]
    classes = params.get("num_class", 2)
    X, y = _table(nan, classes)
    params = dict(params, feature_fraction=fraction)
    k = int(fraction * F)
    bst, drawn, counted = _train(params, X, y)
    assert bst._gbdt.column_draw_fallback_reason() is None
    assert [c["hist_columns"] for c in counted] == [k] * len(counted)
    draws = _names_only_its_draws(bst)
    assert all(len(d["columns"]) == k for d in draws)
    if kind == "goss":      # the sampled trees are rooted at their 30% of the rows
        assert [c["root_rows"] for c in counted][-1] == 0.3 * len(y)
    as_a_mask()
    masked, text, counted = _train(params, X, y)
    assert [c["hist_columns"] for c in counted] == [F] * len(counted)
    assert drawn == text
    assert [d["columns"].tolist() for d in masked.feature_draws()] == [
        d["columns"].tolist() for d in draws]


def test_the_drawn_form_through_the_kernels_flat_pass(monkeypatch, as_a_mask):
    """The cells' path (speculative grower, Pallas histogram, interpreted
    here) at a column count that is not the table's."""
    def interpreted(real):
        @functools.wraps(real)
        def call(*args, **kwargs):
            return real(*args, **dict(kwargs, interpret=True))
        return call

    monkeypatch.setattr(hist_mod, "_ENV_IMPL", "pallas")
    monkeypatch.setattr(grow_mod, "_ENV_GROW", "spec")
    for name in ("histogram_pallas", "histogram_pallas_slots"):
        monkeypatch.setattr(hist_pallas, name, interpreted(getattr(hist_pallas, name)))
    jax.clear_caches()
    try:
        X, y = _table(True, n=1500)
        params = {"objective": "binary", "feature_fraction": 0.8}
        _, drawn, counted = _train(params, X, y, rounds=3)
        assert grow_mod._LAST_GROW_MODE == "spec" and grow_mod._LAST_SPEC_HIST == "flat"
        assert [c["hist_columns"] for c in counted] == [16] * 3
        as_a_mask()
        assert _train(params, X, y, rounds=3)[1] == drawn
    finally:
        monkeypatch.undo()
        jax.clear_caches()


def test_without_a_draw_nothing_is_gathered_and_the_text_is_the_parents(monkeypatch):
    def no_gather(*args):
        raise AssertionError("feature_fraction >= 1 gathers no column")

    X, y = _table(True)
    _, plain, _ = _train({"objective": "binary"}, X, y)
    monkeypatch.setattr(gbdt_mod, "_take_columns", no_gather)
    monkeypatch.setattr(gbdt_mod, "_table_columns", no_gather)
    bst, text, counted = _train({"objective": "binary", "feature_fraction": 1.0}, X, y)
    assert text == plain
    assert bst.feature_draws() == []
    assert [c["hist_columns"] for c in counted] == [F] * len(counted)
    names = {e["name"] for e in trace.events()}
    assert not names & {"feature.counters", "train.feature_sample"}


@pytest.mark.parametrize("kind", ["binary", "three_classes"])
def test_feature_draws_are_what_the_host_stream_drew_in_tree_order(kind):
    params, nan = KINDS[kind]
    classes = params.get("num_class", 2)
    X, y = _table(nan, classes)
    bst, _, _ = _train(dict(params, feature_fraction=0.8, feature_fraction_seed=11), X, y,
                       rounds=4)
    draws = bst.feature_draws()
    per = 1 if classes == 2 else classes
    assert [(d["tree"], d["iteration"]) for d in draws] == [
        (t, t // per) for t in range(4 * per)]
    for d, want in zip(draws, _host_stream(11, F, 16, 4 * per)):
        assert d["columns"].dtype == np.int32 and d["columns"].tolist() == want.tolist()
    events = [e["args"] for e in trace.events() if e["name"] == "feature.counters"]
    assert [(e["tree"], e["columns"], e["drawn"]) for e in events] == [
        (t, F, 16) for t in range(4 * per)]
    spans = [e for e in trace.events() if e["name"] == "train.feature_sample"]
    assert len(spans) == 4 * per


def test_the_replay_of_the_stream_on_a_reloaded_model_lines_up(tmp_path):
    X, y = _table(False)
    params = {"objective": "binary", "feature_fraction": 0.8}
    whole, text, _ = _train(params, X, y, rounds=7)
    first, _, _ = _train(params, X, y, rounds=3)
    path = str(tmp_path / "first.txt")
    first.save_model(path)
    more, continued, _ = _train(params, X, y, rounds=4, init_model=path)
    assert continued == text
    assert [(d["tree"], d["columns"].tolist()) for d in more.feature_draws()] == [
        (d["tree"], d["columns"].tolist()) for d in whole.feature_draws()[3:]]


def _bundled_table(n=3000, seed=2):
    from scipy import sparse

    rng = np.random.RandomState(seed)
    X = sparse.random(n, F, density=0.03, format="csr", random_state=rng, dtype=np.float64)
    signal = np.asarray(X[:, :8].sum(axis=1)).ravel() + 0.05 * rng.randn(n)
    return X, (signal > np.median(signal)).astype(float)


def _forced(tmp_path):
    path = tmp_path / "forced.json"
    path.write_text(json.dumps({"feature": 5, "threshold": 0.25}))
    return {"forcedsplits_filename": str(path)}


MASK_KEEPERS = {
    "efb_bundle": "group space",
    "forced_splits": "forced splits",
    "tree_learner_feature": "sharded",
    "device_chunk_size_4": "chunk",
}


@pytest.mark.parametrize("case", sorted(MASK_KEEPERS))
def test_what_cannot_take_the_drawn_form_keeps_the_mask(tmp_path, case):
    """Still trains, says why by mechanism, and names only drawn columns
    (a forced split is the user's own and stands outside the draw)."""
    X, y = _bundled_table() if case == "efb_bundle" else _table(False)
    extra = {"efb_bundle": {}, "forced_splits": _forced(tmp_path),
             "tree_learner_feature": {"tree_learner": "feature"},
             "device_chunk_size_4": {"device_chunk_size": 4}}[case]
    bst, _, counted = _train(dict({"objective": "binary", "feature_fraction": 0.5}, **extra),
                             X, y, rounds=6)
    gbdt = bst._gbdt
    assert MASK_KEEPERS[case] in gbdt.column_draw_fallback_reason()
    if case == "efb_bundle":
        assert gbdt.train_set.is_bundled
    else:       # the histograms ran over every column (the column shards' padding too)
        assert min(c["hist_columns"] for c in counted) >= F
    assert bst.num_trees() == 6
    draws = bst.feature_draws()
    assert [d["tree"] for d in draws] == list(range(6))
    for d, t in zip(draws, gbdt.trees()):
        assert len(d["columns"]) == gbdt.train_set.num_features // 2
        free = t.split_feature[int(case == "forced_splits"): t.num_leaves - 1]
        assert set(free) <= set(d["columns"].tolist())
