"""The cell of column-sampled boosting
(``epsilon-255bin-featfrac.train-serial-pallas``) at a tiny copy on the CPU,
under the cell's own limits file: the program comes out correct by the
reference that holds each tree to its draw; every tree of the run is a drawn
one and the grower's histograms run over the drawn columns; each planted
fault and the control come out not correct; the reference of full-column
boosting cannot stand in; the generator; the two readers."""
import numpy as np
import pytest

from benchmarks import correct, datagen, model_text, run, spans
from benchmarks.manifest import DEFAULT_FAULTS, Manifest
from benchmarks.references import gbdt_binary

from bh_util import TINY_FEATURES, tiny_copy

CELL = "epsilon-255bin-featfrac.train-serial-pallas"
FAULTS = Manifest().faults(CELL)
MECHANISM = ["draw_ignored", "draw_frozen", "index_not_mapped"]
READERS = ["featfrac.drawn_pct", "featfrac.hist_columns_pct"]
DRAWN = int(TINY_FEATURES * 0.8)
# the number each fault has to break, whatever else it breaks
BREAKS = {"state_unchanged": "loss_gap", "half_batch": "exact_mismatch",
          "altered_answer": "leaf_value_gap", "draw_ignored": "draw_mismatch",
          "draw_frozen": "draw_mismatch", "index_not_mapped": "exact_mismatch"}


@pytest.fixture(scope="module")
def man(tmp_path_factory):
    return tiny_copy(str(tmp_path_factory.mktemp("featfrac")))


@pytest.fixture(scope="module")
def driven(man):
    """One training of the tiny cell, with what the comparisons need."""
    import lightgbm_tpu as lgb

    spec = man.workload(CELL)
    config, traffic = man.config(spec["config"]), man.traffic(spec["traffic"])
    params = run.train_params(config, traffic)
    reference = man.reference(config)
    X, y, extras = datagen.make(config, 2 ** 31 + 21, man.bench_dir)
    ds = lgb.Dataset(X, label=y, params=params, **extras).construct()
    out = run.drive(lgb, params, ds, traffic, seconds=0.3, collect=reference.collect)
    return {"config": config, "traffic": traffic, "params": params, "X": X, "y": y,
            "edges": correct.bin_edges(ds, config["features"]), "limits": man.limits(CELL),
            "produced": out["produced"], "win": out["win"], "reference": reference,
            "follow": run.followed(man.limits(CELL), out["win"], out["produced"]["text"])}


def broken(result):
    return {k for k, c in result["compared"].items() if not c["value"] <= c["limit"]}


def test_the_cell_names_its_own_reference_faults_and_traffic(man):
    config = man.config("epsilon-255bin-featfrac")
    plain = man.config("epsilon-255bin")
    assert config["reference"] == "gbdt_binary_featfrac"
    assert (config["generator"], config["generator_args"]) == (
        plain["generator"] + "_column_order",
        dict(plain["generator_args"], column_order="recipe"))
    extra = {"feature_fraction": 0.8, "feature_fraction_seed": 2}
    assert config["params"] == dict(plain["params"], **extra)
    assert (config["rows"], config["features"], config["reduced"]) == (
        plain["rows"], plain["features"], plain["reduced"])
    assert config["published"]["feature_fraction"] == 0.8
    assert FAULTS == list(DEFAULT_FAULTS) + MECHANISM
    assert man.workload(CELL)["traffic"] == man.workload(
        "epsilon-255bin.train-serial-pallas")["traffic"]


def test_the_table_is_epsilons_with_its_columns_in_the_sets_own_order():
    """Value for value ``epsilon_like``'s table of the same recipe; the seed
    orders the rows alone, so every seed's draws name the same columns."""
    by = datagen.generator("epsilon_like_column_order").make

    def make(rows, features, seed, recipe):
        return by(rows, features, seed, recipe=recipe, column_order="recipe")

    X5, y5 = make(40000, 12, 5, recipe=7)
    X6, y6 = make(40000, 12, 6, recipe=7)
    rows5, cols5 = datagen.order(40000, 12, 5)
    rows6, _ = datagen.order(40000, 12, 6)
    assert not np.array_equal(X5, X6)
    assert np.array_equal(X5[rows5], X6[rows6]) and np.array_equal(y5[rows5], y6[rows6])
    Xs, ys = datagen.generator("epsilon_like").make(40000, 12, 5, recipe=7)
    assert np.array_equal(Xs[:, np.argsort(cols5)], X5) and np.array_equal(ys, y5)
    assert not np.array_equal(make(40000, 12, 5, recipe=8)[1], y5)
    # left to the seed, as every generator's default is, it is epsilon_like itself
    assert np.array_equal(by(40000, 12, 5)[0], Xs)
    with pytest.raises(ValueError):
        by(1000, 12, 5, column_order="mine")


def test_the_program_is_correct_and_every_tree_is_grown_over_its_draw(man, driven):
    logged = []
    numbers = driven["reference"].compare(
        driven["produced"], {"X": driven["X"], "y": driven["y"]}, driven["edges"],
        driven["params"], driven["follow"], log=logged.append)["program"]
    judged = correct.judge(dict(numbers, compiles_in_window=0.0), driven["limits"]["limits"])
    assert all(c["ok"] for c in judged.values()), judged
    assert numbers["exact_mismatch"] == numbers["draw_mismatch"] == 0
    assert 0 < numbers["draw_ks"] < 0.1
    win = driven["win"]
    assert win.warmup == 3 and win.iterations >= 1 and win.compiles_in_window == 0
    assert driven["follow"] == [0, 3 + win.iterations - 1]
    # both followed trees searched over their draws, not over the table
    assert sum("followed by its histograms over %d drawn columns" % DRAWN in l
               for l in logged) == 2
    draws = driven["produced"]["collected"]
    trees = model_text.parse_trees(driven["produced"]["text"])
    assert [d["tree"] for d in draws] == list(range(len(trees)))
    for d, t in zip(draws, trees):
        assert len(d["columns"]) == DRAWN and np.all(np.diff(d["columns"]) > 0)
        assert set(t["split_feature"].tolist()) <= set(d["columns"].tolist())
    assert len({tuple(d["columns"]) for d in draws}) == len(draws)


def test_the_control_is_not_correct(driven):
    numbers = driven["reference"].compare(
        driven["produced"], {"X": driven["X"], "y": driven["y"]}, driven["edges"],
        driven["params"], driven["follow"],
        control_dtype=driven["traffic"]["precision"]["control"])
    program = dict(numbers["program"], compiles_in_window=0.0)
    low = dict(program, **dict(numbers["control"], bin_width=program["bin_width"]))
    assert not all(c["ok"] for c in correct.judge(low, driven["limits"]["limits"]).values())


@pytest.mark.parametrize("fault", FAULTS)
def test_each_planted_fault_is_not_correct(man, fault):
    with man.fault(fault)():
        result = run.run_cell(man, CELL, seed=9, seconds=0.0, trace=False)
    assert result["correct"] is False
    assert BREAKS[fault] in broken(result), result["compared"]
    if fault == "draw_frozen":      # sound trees of a draw that is none: nothing else sees it
        assert broken(result) == {"draw_mismatch"}
    if fault in DEFAULT_FAULTS:     # the draws are sound: the inherited faults leave them be
        assert result["compared"]["draw_mismatch"]["value"] == 0


@pytest.mark.parametrize("fault", MECHANISM)
def test_a_fault_of_the_mechanism_planted_in_the_window_is_not_correct(man, fault):
    with man.fault(fault)(iteration=4):
        result = run.run_cell(man, CELL, seed=2 ** 31 + 10, seconds=1.0, trace=False)
    assert result["attempted"] >= 3, "the window has to reach past the planted tree"
    assert result["correct"] is False
    assert BREAKS[fault] in broken(result), result["compared"]


def test_the_faults_of_the_mechanism_leave_the_program_as_it_was(man):
    from lightgbm_tpu.models import gbdt

    def state():
        return (gbdt.GBDT._train_tree, gbdt.GBDT._draw_columns, gbdt.GBDT._columns_mask,
                gbdt.GBDT.column_draw_fallback_reason, gbdt._table_columns)

    before = state()
    for fault in MECHANISM:
        with pytest.raises(RuntimeError):
            with man.fault(fault)():
                raise RuntimeError("inside")
    assert state() == before


def test_the_reference_of_full_column_boosting_cannot_stand_in(driven):
    """``gbdt_binary`` searches every column: a sound tree of a draw lies
    below a column it was never offered."""
    numbers = gbdt_binary.compare(
        driven["produced"], {"X": driven["X"], "y": driven["y"]}, driven["edges"],
        driven["params"], driven["follow"])["program"]
    assert numbers["split_gap"] > driven["limits"]["limits"]["split_gap"]


def test_a_run_that_recorded_no_draws_is_not_correct(driven):
    """``collect`` was called and came back empty: every tree breaks the law
    and every split is one nobody offered."""
    produced = dict(driven["produced"], collected=[])
    numbers = driven["reference"].compare(
        produced, {"X": driven["X"], "y": driven["y"]}, driven["edges"], driven["params"],
        [])["program"]
    trees = model_text.parse_trees(produced["text"])
    splits = sum(int(t["num_leaves"]) - 1 for t in trees)
    assert numbers["draw_mismatch"] == len(trees) + splits
    assert numbers["exact_mismatch"] == splits


def test_driven_without_collect_a_tree_is_held_to_what_needs_no_draw(driven):
    """``tests/benchmark_harness/test_bh_run.py`` drives every cell without
    ``collect`` for its control: every tree is followed by its sums, no tree's
    candidates are searched, and the log says so."""
    logged = []
    produced = dict(driven["produced"], collected=None)
    args = ({"X": driven["X"], "y": driven["y"]}, driven["edges"], driven["params"])
    numbers = driven["reference"].compare(produced, *args, driven["follow"],
                                          log=logged.append)["program"]
    judged = correct.judge(dict(numbers, compiles_in_window=0.0), driven["limits"]["limits"])
    assert all(c["ok"] for c in judged.values()), judged
    assert numbers["draw_ks"] == numbers["draw_mismatch"] == numbers["split_gap"] == 0.0
    assert any("were not collected" in l for l in logged)
    assert not any("followed by its histograms" in l for l in logged)
    # what needs no draw still bites: a row count, a score
    tree = model_text.parse_trees(produced["text"])[0]
    was = "internal_count=%d " % tree["internal_count"][0]
    text = produced["text"].replace(was, "internal_count=%d " % (tree["internal_count"][0] + 1), 1)
    assert text != produced["text"]
    assert driven["reference"].compare(dict(produced, text=text), *args, [])[
        "program"]["exact_mismatch"] == 1
    off = produced["final_scores"] + np.float32(0.01) * (np.arange(len(driven["y"])) == 7)
    assert driven["reference"].compare(dict(produced, final_scores=off), *args, [])[
        "program"]["score_gap"] > 1e-3


def test_the_readers_on_a_real_run(man, monkeypatch):
    """The names are a contract between the program and the readers."""
    import lightgbm_tpu as lgb
    from lightgbm_tpu.models.gbdt import GBDT
    from lightgbm_tpu.obs import trace

    rng = np.random.RandomState(0)
    X = rng.randn(4000, 10)
    y = (X[:, 0] + 0.3 * rng.randn(4000) > 0).astype(np.float32)
    ctx = {"traffic": {"warmup_iterations": 3}, "iterations": 3,
           "config": {"rows": 4000, "features": 10}, "trace": None}

    def read(params):
        trace.reset()
        lgb.train(dict({"objective": "binary", "num_leaves": 7, "verbosity": -1}, **params),
                  lgb.Dataset(X, label=y), num_boost_round=6).model_to_string()
        return [man.reader(r)(ctx) for r in READERS]

    assert read({"feature_fraction": 0.8}) == [80.0, 80.0]
    assert read({}) == [None, 100.0]
    # the draw as a mask over every column: drawn 80, built over 100
    monkeypatch.setattr(GBDT, "column_draw_fallback_reason", lambda self: "a mask, for the test")
    assert read({"feature_fraction": 0.8}) == [80.0, 100.0]


@pytest.mark.parametrize("reader", READERS)
@pytest.mark.parametrize("ring", ["empty", "none", "older_program"])
def test_the_readers_report_nothing_where_the_events_lack(monkeypatch, man, reader, ring):
    """An empty ring, a program with no read-out, and the parent's events,
    which have neither counter: None, never 0."""
    older = [{"name": "grow.counters", "args": {
        "tree": k, "iteration": k, "steps": 5.0, "slots_computed": 9.0, "splits": 8.0,
        "hist_rows_streamed": 6000.0, "hist_rows_needed": 2100.0, "root_rows": 1000.0}}
        for k in range(6)]
    held = {"empty": [], "none": None, "older_program": older}[ring]
    monkeypatch.setattr(spans, "events", lambda: held)
    ctx = {"traffic": {"warmup_iterations": 2}, "iterations": 3,
           "config": {"rows": 1000, "features": 10}}
    assert man.reader(reader)(ctx) is None
