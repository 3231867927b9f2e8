"""The cell of missing-value boosting (``bosch-255bin.train-serial-pallas``)
at a tiny copy on the CPU, under the cell's own limits file: the program
comes out correct by the reference that knows the NaN bin, both scans and the
default direction; the bfloat16 control and each of the five planted faults
come out not correct; the reference of the table without missing values
cannot stand in; the two readers of the default direction's counters."""
import numpy as np
import pytest

from benchmarks import correct, datagen, model_text, run, spans
from benchmarks.manifest import DEFAULT_FAULTS, Manifest
from benchmarks.references import gbdt_binary

from bh_util import tiny_copy

CELL = "bosch-255bin.train-serial-pallas"
FAULTS = Manifest().faults(CELL)
READERS = ["grower.default_routed_pct", "grower.default_left_pct"]
# the number each fault has to break, whatever else it breaks
BREAKS = {"state_unchanged": "loss_gap", "half_batch": "exact_mismatch",
          "altered_answer": "leaf_value_gap", "default_ignored": "exact_mismatch",
          "scan_one_way": "split_gap"}


@pytest.fixture(scope="module")
def man(tmp_path_factory):
    return tiny_copy(str(tmp_path_factory.mktemp("bosch")))


@pytest.fixture(scope="module")
def driven(man):
    """One training of the tiny cell, with what the comparisons need."""
    import lightgbm_tpu as lgb

    spec = man.workload(CELL)
    config, traffic = man.config(spec["config"]), man.traffic(spec["traffic"])
    params = run.train_params(config, traffic)
    X, y, extras = datagen.make(config, 2 ** 31 + 21, man.bench_dir)
    ds = lgb.Dataset(X, label=y, params=params, **extras).construct()
    out = run.drive(lgb, params, ds, traffic, seconds=0.0)
    return {"config": config, "traffic": traffic, "params": params, "X": X, "y": y,
            "edges": correct.bin_edges(ds, config["features"]), "limits": man.limits(CELL),
            "produced": out["produced"], "win": out["win"],
            "follow": run.followed(man.limits(CELL), out["win"], out["produced"]["text"])}


def broken(result):
    return {k for k, c in result["compared"].items() if not c["value"] <= c["limit"]}


def test_the_cell_names_its_own_reference_faults_and_table(man):
    config = man.config("bosch-255bin")
    assert config["reference"] == "gbdt_binary_nan" and config["generator"] == "bosch_like"
    assert config["generator_args"] == {"recipe": 7, "empty": "nan", "row_order": "recipe"}
    assert FAULTS == list(DEFAULT_FAULTS) + ["default_ignored", "scan_one_way"]
    assert set(man.reference(config).NUMBERS) == set(gbdt_binary.NUMBERS)


def test_the_tiny_table_has_the_cells_mechanism(driven):
    X, y = driven["X"], driven["y"]
    assert 0.17 < 1 - np.isnan(X).mean() < 0.21 and 80 <= y.sum() <= 160
    trees = model_text.parse_trees(driven["produced"]["text"])
    assert all(int(t["num_leaves"]) >= 4 for t in trees)       # min_sum_hessian_in_leaf=5 leaves room
    ref = Manifest().reference(driven["config"])
    kinds = np.concatenate(ref.decisions(driven["produced"]["text"]))
    assert set((kinds >> 2) & 3) == {2}                        # every split feature has the NaN type
    assert {0, 2} == set(kinds & 2)                            # and both directions were taken


def test_the_program_is_correct_and_followed_in_both_directions(man, driven):
    logged = []
    numbers = man.reference(driven["config"]).compare(
        driven["produced"], {"X": driven["X"], "y": driven["y"]}, driven["edges"],
        driven["params"], driven["follow"], log=logged.append)["program"]
    judged = correct.judge(dict(numbers, compiles_in_window=0.0), driven["limits"]["limits"])
    assert all(c["ok"] for c in judged.values()), judged
    assert numbers["exact_mismatch"] == 0
    assert driven["follow"] == [0, driven["win"].warmup + driven["win"].iterations - 1]
    assert sum("histograms in both directions" in line for line in logged) == 2


def test_the_control_is_not_correct(man, driven):
    numbers = man.reference(driven["config"]).compare(
        driven["produced"], {"X": driven["X"], "y": driven["y"]}, driven["edges"],
        driven["params"], driven["follow"],
        control_dtype=driven["traffic"]["precision"]["control"])
    program = dict(numbers["program"], compiles_in_window=0.0)
    limits = driven["limits"]["limits"]
    # the lower precision alone, then the wider bins alone, by this reference's own look
    low = dict(program, **dict(numbers["control"], bin_width=program["bin_width"]))
    assert not all(c["ok"] for c in correct.judge(low, limits).values())
    wide = dict(program, bin_width=numbers["control"]["bin_width_valued"])
    assert [k for k, c in correct.judge(wide, limits).items() if not c["ok"]] == ["bin_width"]
    assert numbers["control"]["bin_width_valued"] > 3 * program["bin_width"]


@pytest.mark.parametrize("fault", FAULTS)
def test_each_planted_fault_is_not_correct(man, fault):
    with man.fault(fault)():
        result = run.run_cell(man, CELL, seed=9, seconds=0.0, trace=False)
    assert result["correct"] is False
    assert BREAKS[fault] in broken(result), result["compared"]


@pytest.mark.parametrize("fault", ["default_ignored", "scan_one_way"])
def test_a_fault_of_the_mechanism_planted_in_the_window_is_not_correct(man, fault):
    """Warm-up is sound; ``scan_one_way`` shows in the window's last tree
    alone, the one followed by its histograms."""
    with man.fault(fault)(iteration=4):
        result = run.run_cell(man, CELL, seed=2 ** 31 + 10, seconds=1.0, trace=False)
    assert result["attempted"] >= 2
    assert result["correct"] is False
    assert BREAKS[fault] in broken(result), result["compared"]


def test_the_faults_of_the_mechanism_leave_the_program_as_it_was(man):
    from lightgbm_tpu.models.gbdt import GBDT

    before = GBDT._train_tree
    for fault in ("default_ignored", "scan_one_way"):
        with pytest.raises(RuntimeError):
            with man.fault(fault)():
                assert GBDT._train_tree is not before
                raise RuntimeError("inside")
    assert GBDT._train_tree is before


def test_the_reference_without_a_nan_bin_cannot_stand_in(driven):
    """``gbdt_binary`` partitions by the threshold alone and scans one way:
    on this table it reads the sound program as wrong."""
    numbers = gbdt_binary.compare(
        driven["produced"], {"X": driven["X"], "y": driven["y"]}, driven["edges"],
        driven["params"], [])["program"]
    assert numbers["exact_mismatch"] > 0


def test_the_readers_of_the_default_direction_on_a_real_run(man):
    """The names are a contract between the program and the readers: a
    training on a table with missing values leaves what both look for, one on
    a table without leaves 0."""
    import lightgbm_tpu as lgb
    from lightgbm_tpu.obs import trace

    ctx = {"traffic": {"warmup_iterations": 2}, "iterations": 3}
    X = datagen.generator("bosch_like").make(4000, 28, 3, empty="nan")[0]
    y = (np.isnan(X[:, 0]) ^ (np.nan_to_num(X[:, 5]) > 0.1)).astype(np.float32)
    for table, routed in ((X, True), (np.nan_to_num(X), False)):
        trace.reset()
        lgb.train({"objective": "binary", "num_leaves": 7, "verbosity": -1},
                  lgb.Dataset(table, label=y), num_boost_round=5).model_to_string()
        got = {r: man.reader(r)(ctx) for r in READERS}
        if routed:
            assert 50 < got["grower.default_routed_pct"] < 100       # four rows in five
            assert 0 < got["grower.default_left_pct"] <= 100
        else:
            assert got == {r: 0.0 for r in READERS}


@pytest.mark.parametrize("reader", READERS)
@pytest.mark.parametrize("ring", ["empty", "none", "older_program"])
def test_the_readers_report_nothing_where_the_events_lack(monkeypatch, man, reader, ring):
    """An empty ring, a program with no read-out, and the parent's counters,
    which have neither of the two: None, never 0."""
    older = [{"name": "grow.counters", "args": {
        "tree": k, "iteration": k, "steps": 5.0, "slots_computed": 9.0, "splits": 8.0,
        "hist_rows_streamed": 6000.0, "hist_rows_needed": 2100.0,
        "part_rows_streamed": 4000.0, "part_rows_needed": 3000.0}} for k in range(6)]
    held = {"empty": [], "none": None, "older_program": older}[ring]
    monkeypatch.setattr(spans, "events", lambda: held)
    ctx = {"traffic": {"warmup_iterations": 2}, "iterations": 3}
    assert man.reader(reader)(ctx) is None
    if ring == "older_program":
        monkeypatch.setattr(spans, "events", lambda: [
            dict(e, args=dict(e["args"], part_rows_missing=1500.0, splits_default_left=2.0))
            for e in older])
        want = {"grower.default_routed_pct": 50.0, "grower.default_left_pct": 25.0}[reader]
        assert man.reader(reader)(ctx) == pytest.approx(want)
