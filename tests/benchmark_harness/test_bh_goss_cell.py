"""The cell of sampled boosting
(``epsilon-255bin-goss.train-serial-pallas-warm12``) at a tiny copy on the
CPU, under the cell's own limits file: the program comes out correct by the
reference that holds each draw to the law; the window holds sampled iterations
alone; each planted fault comes out not correct by the number it is meant to
break; the reference of full-row boosting cannot stand in; the two readers."""
import numpy as np
import pytest

from benchmarks import correct, datagen, model_text, run, spans
from benchmarks.manifest import DEFAULT_FAULTS, Manifest
from benchmarks.references import gbdt_binary

from bh_util import TINY_ROWS, tiny_copy

CELL = "epsilon-255bin-goss.train-serial-pallas-warm12"
FAULTS = Manifest().faults(CELL)
READERS = ["goss.in_bag_pct", "goss.root_rows_pct"]
# the number each fault has to break, whatever else it breaks
BREAKS = {"state_unchanged": "loss_gap", "half_batch": "exact_mismatch",
          "altered_answer": "leaf_value_gap", "amp_dropped": "leaf_value_gap",
          "oob_not_scored": "score_gap", "others_by_rank": "other_ks"}


@pytest.fixture(scope="module")
def man(tmp_path_factory):
    return tiny_copy(str(tmp_path_factory.mktemp("goss")))


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
    config = man.config("epsilon-255bin-goss")
    plain = man.config("epsilon-255bin")
    assert config["reference"] == "gbdt_binary_goss"
    assert (config["generator"], config["generator_args"]) == (
        plain["generator"] + "_row_order", dict(plain["generator_args"], row_order="recipe"))
    extra = {"boosting": "goss", "top_rate": 0.2, "other_rate": 0.1}
    assert config["params"] == dict(plain["params"], **extra)
    assert FAULTS == list(DEFAULT_FAULTS) + ["amp_dropped", "oob_not_scored", "others_by_rank"]
    traffic, base = man.traffic("train-serial-pallas-warm12"), man.traffic("train-serial-pallas")
    assert traffic["warmup_iterations"] == 12 > 1 / config["params"]["learning_rate"]
    assert {k: v for k, v in traffic.items() if k not in ("why", "warmup_iterations")} == {
        k: v for k, v in base.items() if k not in ("why", "warmup_iterations")}


def test_the_table_is_epsilons_with_its_rows_in_the_sets_own_order():
    """Value for value ``epsilon_like``'s table of the same recipe; the seed
    orders the columns alone, so every seed draws the same samples."""
    by = datagen.generator("epsilon_like_row_order").make

    def make(rows, features, seed, recipe):
        return by(rows, features, seed, recipe=recipe, row_order="recipe")

    X5, y5 = make(40000, 12, 5, recipe=7)
    X6, y6 = make(40000, 12, 6, recipe=7)
    assert np.array_equal(y5, y6) and not np.array_equal(X5, X6)
    _, cols5 = datagen.order(40000, 12, 5)
    _, cols6 = datagen.order(40000, 12, 6)
    assert np.array_equal(X5[:, np.argsort(cols5)], X6[:, np.argsort(cols6)])
    Xs, ys = datagen.generator("epsilon_like").make(40000, 12, 5, recipe=7)
    row_at, _ = datagen.order(40000, 12, 5)
    assert np.array_equal(Xs[row_at], X5) and np.array_equal(ys[row_at], y5)
    assert not np.array_equal(make(40000, 12, 5, recipe=8)[1], y5)
    # left to the seed, as every generator's default is, it is epsilon_like itself
    assert np.array_equal(by(40000, 12, 5)[0], Xs)
    with pytest.raises(ValueError):
        by(1000, 12, 5, row_order="mine")


def test_the_program_is_correct_and_every_window_iteration_is_a_sampled_one(man, driven):
    logged = []
    numbers = driven["reference"].compare(
        driven["produced"], {"X": driven["X"], "y": driven["y"]}, driven["edges"],
        driven["params"], driven["follow"], log=logged.append)["program"]
    judged = correct.judge(dict(numbers, compiles_in_window=0.0), driven["limits"]["limits"])
    assert all(c["ok"] for c in judged.values()), judged
    assert numbers["exact_mismatch"] == numbers["sample_mismatch"] == 0
    assert 0 < numbers["other_ks"] < 0.1
    win = driven["win"]
    assert win.warmup == 12 and win.iterations >= 1 and win.compiles_in_window == 0
    assert driven["follow"] == [0, 12 + win.iterations - 1]
    # one unsampled tree and one sampled one followed by their histograms
    assert sum("followed by its histograms on %d rows" % TINY_ROWS in l for l in logged) == 1
    assert sum("followed by its histograms on %d rows" % (TINY_ROWS * 3 // 10) in l
               for l in logged) == 1
    draws = driven["produced"]["collected"]
    assert [d["iteration"] for d in draws] == list(range(10, 12 + win.iterations))
    trees = model_text.parse_trees(driven["produced"]["text"])
    roots = [int(t["internal_count"][0]) for t in trees]
    assert roots == [TINY_ROWS] * 10 + [TINY_ROWS * 3 // 10] * (len(trees) - 10)


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
    """Planted from iteration 10 on, where sampling starts: inside warm-up,
    two iterations before the window."""
    with man.fault(fault)(iteration=10):
        result = run.run_cell(man, CELL, seed=9, seconds=0.0, trace=False)
    assert result["correct"] is False
    assert BREAKS[fault] in broken(result), result["compared"]
    if fault == "others_by_rank":            # a sound tree of its rows: nothing else sees it
        assert broken(result) == {"other_ks"}
        assert result["compared"]["other_ks"]["value"] == pytest.approx(0.875)


@pytest.mark.parametrize("fault", ["amp_dropped", "oob_not_scored", "others_by_rank"])
def test_a_fault_of_the_mechanism_planted_in_the_window_is_not_correct(man, fault):
    with man.fault(fault)(iteration=13):
        result = run.run_cell(man, CELL, seed=2 ** 31 + 10, seconds=1.0, trace=False)
    assert result["attempted"] >= 3, "the window has to reach past the planted tree"
    assert result["correct"] is False
    assert BREAKS[fault] in broken(result), result["compared"]


def test_the_faults_of_the_mechanism_leave_the_program_as_it_was(man):
    from lightgbm_tpu.models import goss
    from lightgbm_tpu.models.gbdt import GBDT

    before = (goss.GOSS._bagging, goss.goss_sample, goss._draw_others, GBDT._finish_tree)
    for fault in ("amp_dropped", "oob_not_scored", "others_by_rank"):
        with pytest.raises(RuntimeError):
            with man.fault(fault)():
                raise RuntimeError("inside")
    assert (goss.GOSS._bagging, goss.goss_sample, goss._draw_others,
            GBDT._finish_tree) == before


def test_the_reference_of_full_row_boosting_cannot_stand_in(driven):
    """``gbdt_binary`` counts every row into every node: on a sampled tree it
    reads the sound program as wrong."""
    numbers = gbdt_binary.compare(
        driven["produced"], {"X": driven["X"], "y": driven["y"]}, driven["edges"],
        driven["params"], [])["program"]
    assert numbers["exact_mismatch"] > 0


def test_a_run_that_recorded_no_draws_is_not_correct(driven):
    """``collect`` was called and came back empty: every sampled iteration
    breaks the law, and its tree is counted against every row."""
    produced = dict(driven["produced"], collected=[])
    numbers = driven["reference"].compare(
        produced, {"X": driven["X"], "y": driven["y"]}, driven["edges"], driven["params"],
        [])["program"]
    assert numbers["sample_mismatch"] == driven["produced"]["iterations_run"] - 10
    assert numbers["exact_mismatch"] > 0


def test_driven_without_collect_a_sampled_tree_is_held_to_what_needs_no_draw(driven):
    """``tests/benchmark_harness/test_bh_run.py`` drives every cell without
    ``collect`` for its control: the unsampled trees are followed in full,
    the sampled ones by their thresholds, their root's count and the score
    update of every row, and the log says so."""
    logged = []
    produced = dict(driven["produced"], collected=None)
    args = ({"X": driven["X"], "y": driven["y"]}, driven["edges"], driven["params"])
    numbers = driven["reference"].compare(produced, *args, driven["follow"],
                                          log=logged.append)["program"]
    judged = correct.judge(dict(numbers, compiles_in_window=0.0), driven["limits"]["limits"])
    assert all(c["ok"] for c in judged.values()), judged
    assert numbers["other_ks"] == 0.0 and any("were not collected" in l for l in logged)
    # what needs no draw still bites: a row count, a score
    text = produced["text"].replace("internal_count=6000 ", "internal_count=6001 ", 1)
    assert text != produced["text"]
    assert driven["reference"].compare(dict(produced, text=text), *args, [])[
        "program"]["exact_mismatch"] == 1
    off = produced["final_scores"] + np.float32(0.01) * (np.arange(TINY_ROWS) == 7)
    assert driven["reference"].compare(dict(produced, final_scores=off), *args, [])[
        "program"]["score_gap"] > 1e-3


def test_the_readers_on_a_real_run(man):
    """The names are a contract between the program and the readers."""
    import lightgbm_tpu as lgb
    from lightgbm_tpu.obs import trace

    rng = np.random.RandomState(0)
    X = rng.randn(4000, 7)
    y = (X[:, 0] + 0.3 * rng.randn(4000) > 0).astype(np.float32)
    ctx = {"traffic": {"warmup_iterations": 3}, "iterations": 3,
           "config": {"rows": 4000}, "trace": None}
    for params, want in (({"boosting": "goss", "learning_rate": 0.5}, 30.0), ({}, None)):
        trace.reset()
        lgb.train(dict({"objective": "binary", "num_leaves": 7, "verbosity": -1}, **params),
                  lgb.Dataset(X, label=y), num_boost_round=6).model_to_string()
        assert man.reader("goss.in_bag_pct")(ctx) == want
        assert man.reader("goss.root_rows_pct")(ctx) == (want or 100.0)
    # the lead-in alone: a window that sat in it reads 100
    assert man.reader("goss.root_rows_pct")(dict(ctx, traffic={"warmup_iterations": 0})) == 100.0


@pytest.mark.parametrize("reader", READERS)
@pytest.mark.parametrize("ring", ["empty", "none", "older_program"])
def test_the_readers_report_nothing_where_the_events_lack(monkeypatch, man, reader, ring):
    """An empty ring, a program with no read-out, and the parent's events,
    which have neither counter: None, never 0."""
    older = [{"name": "grow.counters", "args": {
        "tree": k, "iteration": k, "steps": 5.0, "slots_computed": 9.0, "splits": 8.0,
        "hist_rows_streamed": 6000.0, "hist_rows_needed": 2100.0}} for k in range(6)]
    held = {"empty": [], "none": None, "older_program": older}[ring]
    monkeypatch.setattr(spans, "events", lambda: held)
    ctx = {"traffic": {"warmup_iterations": 2}, "iterations": 3, "config": {"rows": 1000}}
    assert man.reader(reader)(ctx) is None
