"""Dataset/Booster API-surface parity with the reference python package
(python-package/lightgbm/basic.py): the long tail of accessors the core
paths don't exercise — set/get_field, reference re-pointing, ref chains,
add_features_from, dump_text, attributes, eval-on-any-dataset,
shuffle_models, split-value histograms, network shims.
"""
import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.utils.log import LightGBMError

PARAMS = {"objective": "binary", "num_leaves": 7, "verbosity": -1,
          "max_bin": 31, "min_data_in_leaf": 5}


def _data(n=400, f=4, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, f)
    y = (X[:, 0] + 0.5 * X[:, 1] > 0).astype(float)
    return X, y


class TestDatasetSurface:
    def test_set_get_field_roundtrip(self):
        X, y = _data()
        ds = lgb.Dataset(X)
        ds.set_field("label", y)
        ds.set_field("weight", np.ones(len(y)))
        np.testing.assert_array_equal(ds.get_field("label"), y)
        assert ds.get_field("weight") is not None
        with pytest.raises(LightGBMError):
            ds.set_field("nope", y)
        with pytest.raises(LightGBMError):
            ds.get_field("nope")

    def test_set_categorical_feature_before_and_after_construct(self):
        X, y = _data()
        ds = lgb.Dataset(X, label=y)
        ds.set_categorical_feature([1])
        assert ds.categorical_feature == [1]
        ds.construct()
        ds.set_categorical_feature([1])  # unchanged: no-op, binning kept
        assert ds._binned is not None
        # retained raw data: changing the spec re-bins on next construct
        ds.set_categorical_feature([2])
        assert ds._binned is None
        ds.construct()
        assert ds._binned.mappers[2].bin_type == 1  # BIN_CATEGORICAL
        # without raw data the change is impossible
        frozen = lgb.Dataset(X, label=y)
        frozen.construct()
        frozen.data = None
        with pytest.raises(LightGBMError):
            frozen.set_categorical_feature([1])

    def test_set_reference_and_ref_chain(self):
        X, y = _data()
        train = lgb.Dataset(X, label=y)
        valid = lgb.Dataset(X, label=y)
        valid.set_reference(train)
        assert valid.reference is train
        chain = valid.get_ref_chain()
        assert chain == {valid, train}
        valid.construct()
        # retained raw data: re-pointing re-bins with the new reference
        other = lgb.Dataset(X, label=y).construct()
        valid.set_reference(other)
        assert valid._binned is None and valid.reference is other
        valid.construct()
        # without raw data the change is impossible
        valid.data = None
        third = lgb.Dataset(X, label=y)
        with pytest.raises(LightGBMError):
            valid.set_reference(third)

    def test_set_feature_name_validates_length(self):
        X, y = _data(f=4)
        ds = lgb.Dataset(X, label=y).construct()
        ds.set_feature_name(["a", "b", "c", "d"])
        assert ds._binned.feature_names == ["a", "b", "c", "d"]
        with pytest.raises(LightGBMError):
            ds.set_feature_name(["a"])

    def test_get_data_respects_subset(self):
        X, y = _data()
        ds = lgb.Dataset(X, label=y)
        sub = ds.subset(np.arange(0, 100))
        np.testing.assert_array_equal(np.asarray(sub.get_data()), X[:100])

    def test_get_data_and_dump_text_on_dataframe_subset(self, tmp_path):
        pd = pytest.importorskip("pandas")
        X, y = _data()
        df = pd.DataFrame(X, columns=["c%d" % i for i in range(X.shape[1])])
        ds = lgb.Dataset(df, label=y)
        sub = ds.subset(np.arange(5, 25))
        got = sub.get_data()
        np.testing.assert_array_equal(np.asarray(got), X[5:25])
        out = str(tmp_path / "sub.txt")
        sub.dump_text(out)
        np.testing.assert_allclose(
            np.loadtxt(out, delimiter=","), X[5:25], rtol=1e-15
        )

    def test_monotone_and_penalty_accessors(self):
        X, y = _data(f=3)
        ds = lgb.Dataset(
            X, label=y,
            params={"monotone_constraints": [1, -1, 0],
                    "feature_contri": [0.5, 1.0, 1.0]},
        ).construct()
        np.testing.assert_array_equal(ds.get_monotone_constraints(), [1, -1, 0])
        np.testing.assert_array_equal(ds.get_feature_penalty(), [0.5, 1.0, 1.0])
        plain = lgb.Dataset(X, label=y).construct()
        assert plain.get_monotone_constraints() is None
        assert plain.get_feature_penalty() is None

    def test_add_features_from(self):
        X, y = _data(f=3)
        rng = np.random.RandomState(9)
        X2 = rng.randn(len(y), 2)
        a = lgb.Dataset(X, label=y, feature_name=["a0", "a1", "a2"],
                        params={"enable_bundle": False}).construct()
        b = lgb.Dataset(X2, feature_name=["b0", "a1"],
                        params={"enable_bundle": False}).construct()
        a.add_features_from(b)
        assert a.num_feature() == 5
        assert a._binned.feature_names == ["a0", "a1", "a2", "b0", "a1_1"]
        assert a._binned.bins.shape[0] == len(a._binned.mappers)
        # the appended columns train: feature importance can reach them
        bst = lgb.train(PARAMS, a, num_boost_round=3)
        assert bst.num_trees() == 3
        # row-count mismatch refuses
        c = lgb.Dataset(rng.randn(10, 1), params={"enable_bundle": False}).construct()
        with pytest.raises(LightGBMError):
            a.add_features_from(c)

    def test_dump_text(self, tmp_path):
        X, y = _data(n=50)
        ds = lgb.Dataset(X, label=y)
        out = str(tmp_path / "dump.txt")
        ds.dump_text(out)
        got = np.loadtxt(out, delimiter=",")
        np.testing.assert_allclose(got, X, rtol=1e-15)


class TestBoosterSurface:
    def test_attrs(self):
        X, y = _data()
        bst = lgb.train(PARAMS, lgb.Dataset(X, label=y), num_boost_round=2)
        assert bst.attr("note") is None
        bst.set_attr(note="hello", run="7")
        assert bst.attr("note") == "hello"
        bst.set_attr(note=None)
        assert bst.attr("note") is None
        with pytest.raises(LightGBMError):
            bst.set_attr(bad=3)

    def test_eval_any_dataset_and_train_data_name(self):
        X, y = _data()
        train = lgb.Dataset(X, label=y)
        bst = lgb.train(PARAMS, train, num_boost_round=3)
        bst.set_train_data_name("mytrain")
        res = bst.eval_train()
        assert res and res[0][0] == "mytrain"
        other = lgb.Dataset(X[:200], label=y[:200], reference=train)
        res2 = bst.eval(other, "probe")
        assert res2 and res2[0][0] == "probe"
        # idempotent: evaluating the same set again reuses its slot
        res3 = bst.eval(other, "probe")
        assert len(bst._valid_datasets) == 1
        assert res3[0][1] == res2[0][1]
        # the trained trees were replayed into the new valid score — the
        # logloss must match a direct evaluation of the model's predictions,
        # not a zero-score model (ScoreUpdater-replays-existing-models parity)
        import math

        p = np.clip(bst.predict(X[:200]), 1e-15, 1 - 1e-15)
        want = -np.mean(y[:200] * np.log(p) + (1 - y[:200]) * np.log1p(-p))
        got = dict((r[1], r[2]) for r in res2)["binary_logloss"]
        assert math.isclose(got, want, rel_tol=1e-5), (got, want)

    def test_shuffle_models_preserves_full_prediction(self):
        X, y = _data()
        bst = lgb.train(PARAMS, lgb.Dataset(X, label=y), num_boost_round=8)
        before = bst.predict(X)
        trees_before = [t for t in bst._gbdt.trees()]
        bst.shuffle_models()
        after = bst.predict(X)
        np.testing.assert_allclose(after, before, rtol=1e-9)
        trees_after = [t for t in bst._gbdt.trees()]
        moved = any(a is not b for a, b in zip(trees_before, trees_after))
        assert moved, "seeded shuffle of 8 trees left order identical"

    def test_split_value_histogram(self):
        X, y = _data()
        bst = lgb.train(PARAMS, lgb.Dataset(X, label=y), num_boost_round=5)
        counts, edges = bst.get_split_value_histogram(0)
        assert counts.sum() > 0  # feature 0 drives the label; it must split
        assert len(edges) == len(counts) + 1
        by_name = bst.get_split_value_histogram(bst.feature_name()[0])
        np.testing.assert_array_equal(by_name[0], counts)
        with pytest.raises(LightGBMError):
            bst.get_split_value_histogram("no_such_feature")

    def test_eval_after_free_dataset_uses_fresh_slot(self):
        """free_dataset clears booster-side tracking but not the GBDT's valid
        lists; a later eval must not hand back a stale slot's metrics."""
        X, y = _data()
        train = lgb.Dataset(X, label=y)
        bst = lgb.train(PARAMS, train, num_boost_round=3)
        easy = lgb.Dataset(X[:150], label=y[:150], reference=train)
        bst.eval(easy, "easy")
        bst.free_dataset()
        # a deliberately WRONG-labeled set: its logloss must be terrible,
        # not the easy set's
        anti = lgb.Dataset(X[:150], label=1 - y[:150], reference=train)
        res = bst.eval(anti, "anti")
        got = dict((r[1], r[2]) for r in res)["binary_logloss"]
        ya = 1 - y[:150]
        p = np.clip(bst.predict(X[:150]), 1e-15, 1 - 1e-15)
        want = -np.mean(ya * np.log(p) + (1 - ya) * np.log1p(-p))
        assert abs(got - want) < 1e-5, (got, want)

    def test_free_dataset_and_network_shims(self):
        X, y = _data()
        bst = lgb.train(PARAMS, lgb.Dataset(X, label=y), num_boost_round=2)
        bst.set_network(machines="a:1,b:2", num_machines=2)
        assert bst._network_initialized
        bst.free_network()
        assert not bst._network_initialized
        bst.free_dataset()
        assert bst._train_dataset is None
        # model remains fully usable
        p = bst.predict(X)
        assert p.shape == (len(y),)
        s = bst.model_to_string()
        # model_from_string replaces the model in place
        bst2 = lgb.train(PARAMS, lgb.Dataset(X, label=y), num_boost_round=1)
        bst2.model_from_string(s)
        np.testing.assert_allclose(bst2.predict(X), p, rtol=1e-12)


    def test_feature_draws_is_the_record_of_the_column_draws(self):
        """Beside ``sample_draws``: one dict a drawn tree, the drawn columns
        of the table in rising order; empty where no tree draws."""
        X, y = _data()
        F = X.shape[1]
        bst = lgb.train(dict(PARAMS, feature_fraction=0.5), lgb.Dataset(X, label=y),
                        num_boost_round=3)
        draws = bst.feature_draws()
        assert [sorted(d) for d in draws] == [["columns", "iteration", "tree"]] * 3
        assert [d["tree"] for d in draws] == [d["iteration"] for d in draws] == [0, 1, 2]
        for d, t in zip(draws, bst._gbdt.trees()):
            cols = d["columns"]
            assert len(cols) == max(1, int(0.5 * F)) and np.all(np.diff(cols) > 0)
            assert 0 <= cols[0] and cols[-1] < F
            assert set(t.split_feature[: t.num_leaves - 1]) <= set(cols.tolist())
        assert bst.sample_draws() == []
        plain = lgb.train(PARAMS, lgb.Dataset(X, label=y), num_boost_round=2)
        assert plain.feature_draws() == []
        assert "column draws" in lgb.Booster.feature_draws.__doc__


#: obs/ modules that still import the training path: debts (ROADMAP.md,
#: "Debts left by PR 30"). This list can only shrink.
_OBS_IMPORTS_TRAINING = {"tune", "irscan", "memwatch"}


def test_obs_does_not_import_the_training_path():
    """The arrow points one way: ops/ and models/ publish into obs/, and no
    module of obs/ but the listed debts imports them back — at module level
    or inside a function — so no instrument can hold the grower's shape."""
    import ast
    import os

    obs_dir = os.path.join(os.path.dirname(lgb.__file__), "obs")
    offenders = {}
    for fname in sorted(os.listdir(obs_dir)):
        mod = fname[:-3]
        if not fname.endswith(".py") or mod in _OBS_IMPORTS_TRAINING:
            continue
        with open(os.path.join(obs_dir, fname), encoding="utf-8") as fh:
            tree = ast.parse(fh.read())
        for node in ast.walk(tree):
            if isinstance(node, ast.ImportFrom):
                # inside lightgbm_tpu/obs/, level 2 is the package root
                if node.level == 2:
                    base = node.module or ""
                elif node.level == 0:
                    base = (node.module or "").partition("lightgbm_tpu.")[2]
                else:
                    continue
                names = [base] if base else [a.name for a in node.names]
            elif isinstance(node, ast.Import):
                names = [a.name.partition("lightgbm_tpu.")[2]
                         for a in node.names]
            else:
                continue
            for name in names:
                if name.split(".")[0] in ("ops", "models"):
                    offenders.setdefault(mod, set()).add(name)
    assert not offenders, offenders
