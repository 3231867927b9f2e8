"""The cell of softmax boosting (``epsilon-255bin-multiclass.train-serial-pallas``)
at a tiny copy on the CPU: its configuration is the Epsilon cell's with five
classes; its table is Epsilon's, value for value, with a label of five
classes; the tiny copy goes through ``run.drive`` and ``run.run_cell`` with
five trees an iteration; the two readers read 5.0 there and nothing on a
ring of one tree an iteration, an empty ring, or a program without the
counter."""
import numpy as np
import pytest

from benchmarks import datagen, model_text, run, spans
from benchmarks.manifest import DEFAULT_FAULTS, Manifest

from bh_util import TINY_ROWS, tiny_copy

CELL = "epsilon-255bin-multiclass.train-serial-pallas"
READERS = ["multiclass.trees_per_iter", "multiclass.grower_calls_per_iter"]
K = 5


@pytest.fixture(scope="module")
def man(tmp_path_factory):
    return tiny_copy(str(tmp_path_factory.mktemp("multiclass_cell")))


@pytest.fixture(scope="module")
def driven(man):
    """One training of the tiny cell through the harness's own call, and
    what its ring holds."""
    import lightgbm_tpu as lgb
    from lightgbm_tpu.obs import trace

    spec = man.workload(CELL)
    config, traffic = man.config(spec["config"]), man.traffic(spec["traffic"])
    params = run.train_params(config, traffic)
    X, y, extras = datagen.make(config, 2 ** 31 + 51, man.bench_dir)
    ds = lgb.Dataset(X, label=y, params=params, **extras).construct()
    trace.reset()
    out = run.drive(lgb, params, ds, traffic, seconds=0.3)
    return {"out": out, "events": trace.events(), "y": y, "traffic": traffic,
            "config": config}


def test_the_cell_is_epsilons_with_five_classes(man):
    config = man.config("epsilon-255bin-multiclass")
    plain = man.config("epsilon-255bin")
    assert config["reference"] == "gbdt_multiclass"
    assert config["generator"] == "epsilon_like_multiclass"
    assert config["generator_args"] == dict(plain["generator_args"], classes=K)
    assert config["params"] == dict(plain["params"], objective="multiclass", num_class=K)
    assert (config["rows"], config["features"], config["reduced"]) == (
        plain["rows"], plain["features"], plain["reduced"])
    assert config["published"] == dict(plain["published"], num_class=K)
    assert Manifest().faults(CELL) == list(DEFAULT_FAULTS) + [
        "ova_gradients", "classes_in_sequence", "class_rotated"]
    assert man.workload(CELL)["traffic"] == man.workload(
        "epsilon-255bin.train-serial-pallas")["traffic"]
    entries = {m["name"]: m for m in Manifest().data["per_layer"]}
    for name in READERS:
        assert entries[name]["workloads"] == [CELL]
        assert entries[name]["source"] == "program_counter"


def test_the_table_is_epsilons_value_for_value_with_five_classes():
    by = datagen.generator("epsilon_like_multiclass")
    X, y = by.make(40000, 30, 5)
    Xe, ye = datagen.generator("epsilon_like").make(40000, 30, 5)
    assert np.array_equal(X, Xe) and not np.array_equal(y, ye)
    assert set(np.unique(y)) == set(range(K))
    assert np.all(np.abs(np.bincount(y.astype(int)) / len(y) - 1 / K) < 0.04)
    # a row's class goes with it whatever the seed's order
    X6, y6 = by.make(40000, 30, 6)
    (r5, c5), (r6, c6) = datagen.order(40000, 30, 5), datagen.order(40000, 30, 6)
    assert np.array_equal(y[r5], y6[r6])
    assert np.array_equal(X[r5][:, np.argsort(c5)], X6[r6][:, np.argsort(c6)])
    # the classes' weights lie on epsilon_like's informative columns
    w = by.class_weights(300, 7, K)
    assert np.allclose(np.linalg.norm(w, axis=1), 1)
    on = np.flatnonzero(datagen.generator("epsilon_like").weights(300, 7))
    assert set(np.flatnonzero(np.any(w != 0, axis=0))) == set(on)
    # the scores decide the class more often than not
    Xr, yr = by.make(20000, 300, 1)
    _, cols = datagen.order(20000, 300, 1)
    assert np.mean(np.argmax(Xr @ w[:, cols].T, axis=1) == yr) > 0.5
    X3, y3 = by.make(40000, 30, 5, classes=3)
    assert np.array_equal(X3, Xe) and set(np.unique(y3)) == {0, 1, 2}


def test_the_tiny_cell_grows_five_trees_an_iteration(driven):
    produced, win = driven["out"]["produced"], driven["out"]["win"]
    assert model_text.trees_per_iteration(produced["text"]) == K
    trees = model_text.parse_trees(produced["text"])
    assert len(trees) == K * produced["iterations_run"] == K * (win.warmup + win.iterations)
    assert produced["final_scores"].shape == (K, TINY_ROWS)
    assert [s.shape for s in produced["warm_scores"]] == [(K, TINY_ROWS)] * win.warmup
    assert win.compiles_in_window == 0


def test_the_readers_read_five_on_the_tiny_cell(man, driven, monkeypatch):
    monkeypatch.setattr(spans, "events", lambda: driven["events"])
    ctx = {"traffic": driven["traffic"], "iterations": driven["out"]["win"].iterations,
           "config": driven["config"]}
    assert [man.reader(r)(ctx) for r in READERS] == [5.0, 5.0]
    spans_of_class = [e for e in driven["events"] if e["name"] == "train.class_tree"]
    assert len(spans_of_class) == K * driven["out"]["produced"]["iterations_run"]


def test_the_readers_on_a_run_of_one_tree_an_iteration(man):
    import lightgbm_tpu as lgb
    from lightgbm_tpu.obs import trace

    rng = np.random.RandomState(0)
    X = rng.randn(3000, 8)
    y = (X[:, 0] > 0).astype(float)
    trace.reset()
    lgb.train({"objective": "binary", "num_leaves": 7, "verbosity": -1},
              lgb.Dataset(X, label=y), num_boost_round=6).model_to_string()
    ctx = {"traffic": {"warmup_iterations": 2}, "iterations": 3, "config": {}}
    assert [man.reader(r)(ctx) for r in READERS] == [None, None]


@pytest.mark.parametrize("reader", READERS)
@pytest.mark.parametrize("ring", ["empty", "none", "older_program", "outside_the_window"])
def test_the_readers_report_nothing_where_the_events_lack(monkeypatch, man, reader, ring):
    """An empty ring, a program with no read-out, the parent's events (no
    such counter), and counters of iterations outside the window: None,
    never 0."""
    older = [{"name": "grow.counters", "args": {"tree": k, "iteration": k // K, "steps": 5.0}}
             for k in range(30)]
    outside = [{"name": "class.counters", "args": {
        "iteration": k, "classes": K, "trees": K, "grower_calls": K}} for k in (0, 1, 5, 6)]
    held = {"empty": [], "none": None, "older_program": older,
            "outside_the_window": outside}[ring]
    monkeypatch.setattr(spans, "events", lambda: held)
    ctx = {"traffic": {"warmup_iterations": 2}, "iterations": 3, "config": {}}
    assert man.reader(reader)(ctx) is None


def test_the_readers_mean_over_the_window_s_iterations(monkeypatch, man):
    noted = [{"name": "class.counters", "args": {
        "iteration": k, "classes": K, "trees": t, "grower_calls": c}}
        for k, t, c in ((1, 5, 5), (2, 5, 1), (3, 4, 1), (4, 3, 1), (5, 5, 5))]
    monkeypatch.setattr(spans, "events", lambda: noted)
    ctx = {"traffic": {"warmup_iterations": 2}, "iterations": 3, "config": {}}
    assert man.reader(READERS[0])(ctx) == 4.0
    assert man.reader(READERS[1])(ctx) == 1.0
