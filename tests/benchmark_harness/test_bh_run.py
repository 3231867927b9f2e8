"""The harness end to end on the CPU at a tiny size, the chip check skipped:
the window callback, the result line, ``correct`` on a sound run and under
each planted fault, the control, and the refusal to run without a TPU."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmarks import correct, datagen, faults, model_text, reference, run
from benchmarks.manifest import ROOT, Manifest

from bh_util import TINY_ROWS, tiny_copy

CELLS = [w["name"] for w in Manifest().data["workloads"]]
CONTRACT_KEYS = ["correct", "attempted", "failed", "metrics", "device", "compared"]


@pytest.fixture(scope="module")
def man(tmp_path_factory):
    return tiny_copy(str(tmp_path_factory.mktemp("bench")))


@pytest.fixture(scope="module")
def sound(man):
    """One sound run of each cell, set aside for the tests below."""
    return {cell: run.run_cell(man, cell, seed=2 ** 31 + 77, seconds=0.3, trace=False)
            for cell in CELLS}


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct_and_reports_the_cells_metrics(sound, man, cell):
    result = sound[cell]
    assert result["correct"] is True, result["compared"]
    assert list(result) == CONTRACT_KEYS          # `compared` comes last
    assert result["failed"] == 0 and result["attempted"] >= 1
    want = [m["name"] for m in man.metrics("end_to_end", cell)]
    assert sorted(result["metrics"]) == sorted(want)
    for name, m in result["metrics"].items():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    assert set(result["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}
    assert result["device"]["count"] == 1
    for c in result["compared"].values():
        assert set(c) == {"value", "limit"}
    assert result["compared"]["exact_mismatch"]["value"] == 0
    assert result["compared"]["compiles_in_window"]["value"] == 0
    json.dumps(result)


def test_window_stops_on_an_iteration_boundary_and_counts_right(man):
    import lightgbm_tpu as lgb

    cell = man.workload(CELLS[0])
    config, traffic = man.config(cell["config"]), man.traffic(cell["traffic"])
    params = run.train_params(config, traffic)
    X, y = datagen.make(config, 5, man.bench_dir)
    ds = lgb.Dataset(X, label=y, params=params).construct()
    out = run.drive(lgb, params, ds, traffic, seconds=0.25)
    win = out["win"]
    trees = model_text.parse_trees(out["text"])
    assert win.iterations >= 1 and win.traced_iterations == 0
    assert len(trees) == out["iterations_run"] == win.warmup + win.iterations
    assert len(out["warm_scores"]) == win.warmup == traffic["warmup_iterations"]
    assert win.window_s >= 0.25                      # at or after --seconds
    assert win.t_warm < win.t_block <= win.t_fetch
    assert len(win.host_gaps_s) == win.iterations     # one gap per iteration
    assert win.compiles_in_window == 0
    # the window closed on the last iteration's scores: they are the model's
    applied = out["final_scores"].reshape(-1)
    assert applied.shape == (TINY_ROWS,) and np.isfinite(applied).all()
    # zero seconds: the window is the one iteration after warm-up
    again = run.drive(lgb, params, ds, traffic, seconds=0.0)
    assert again["win"].iterations == 1


@pytest.mark.parametrize("fault", faults.FAULTS)
def test_a_broken_timed_path_comes_out_not_correct(man, fault):
    with faults.planted(fault):
        result = run.run_cell(man, CELLS[0], seed=9, seconds=0.0, trace=False)
    assert result["correct"] is False
    broken = {k for k, c in result["compared"].items() if not c["value"] <= c["limit"]}
    expect = {"state_unchanged": "loss_gap", "half_batch": "exact_mismatch",
              "altered_answer": "leaf_value_gap"}[fault]
    assert expect in broken, result["compared"]


@pytest.mark.parametrize("fault", faults.FAULTS)
def test_a_path_broken_inside_the_window_comes_out_not_correct(man, fault):
    """The fault starts in the window's second tree, which the reference
    follows by its sums alone: warm-up is sound."""
    with faults.planted(fault, iteration=4):
        result = run.run_cell(man, CELLS[0], seed=2 ** 31 + 10, seconds=1.0, trace=False)
    assert result["attempted"] >= 3, "the window has to reach past the planted tree"
    assert result["correct"] is False
    broken = {k for k, c in result["compared"].items() if not c["value"] <= c["limit"]}
    expect = {"state_unchanged": "score_gap", "half_batch": "exact_mismatch",
              "altered_answer": "leaf_value_gap"}[fault]
    assert expect in broken, result["compared"]


def test_faults_leave_the_program_as_it_was():
    from lightgbm_tpu.models.gbdt import GBDT

    before = (GBDT._finish_tree, GBDT._train_tree)
    for fault in faults.FAULTS:
        with pytest.raises(RuntimeError):
            with faults.planted(fault):
                raise RuntimeError("inside")
    assert (GBDT._finish_tree, GBDT._train_tree) == before
    with pytest.raises(KeyError):
        with faults.planted("no_such_fault"):
            pass


@pytest.mark.parametrize("cell", CELLS)
def test_the_control_comes_out_not_correct(man, cell):
    """The reference in the program's place, one precision step down
    from what the cell states (bfloat16 operands under float32), fails a
    limit that the program's own numbers hold."""
    import lightgbm_tpu as lgb

    spec = man.workload(cell)
    config, traffic = man.config(spec["config"]), man.traffic(spec["traffic"])
    limits = man.limits(cell)
    params = run.train_params(config, traffic)
    X, y = datagen.make(config, 21, man.bench_dir)
    ds = lgb.Dataset(X, label=y, params=params).construct()
    out = run.drive(lgb, params, ds, traffic, seconds=0.0)
    edges = correct.bin_edges(ds, config["features"])
    numbers = correct.compare(
        out["text"], out["warm_scores"], out["final_scores"], out["iterations_run"],
        X, y, edges, params,
        correct.follow_indices(limits["follow"], out["win"].warmup, out["win"].iterations),
        control_dtype=traffic["precision"]["control"])
    program = dict(numbers["program"], compiles_in_window=0.0)
    assert all(c["ok"] for c in correct.judge(program, limits["limits"]).values())
    control = dict(program, **numbers["control"])
    judged = correct.judge(control, limits["limits"])
    assert not all(c["ok"] for c in judged.values()), judged
    # and bins four times as wide fail the look at the edges alone
    wide = reference.Follower(X, y, correct.coarser(edges), params).bin_width
    judged = correct.judge(dict(program, bin_width=wide), limits["limits"])
    assert [k for k, c in judged.items() if not c["ok"]] == ["bin_width"]


def test_a_number_that_is_not_finite_holds_no_limit():
    limits = {"split_gap": 0.5, "loss_gap": 0.5}
    judged = correct.judge({"split_gap": float("nan"), "loss_gap": float("inf")}, limits)
    assert not judged["split_gap"]["ok"] and not judged["loss_gap"]["ok"]
    assert correct.judge({"split_gap": 0.5, "loss_gap": 0.0}, limits)["split_gap"]["ok"]


def test_last_line_has_exactly_the_contracts_keys(man, monkeypatch, capsys):
    monkeypatch.setattr(run, "Manifest", lambda: man)
    monkeypatch.setattr(run, "need_chips", lambda chips: None)
    monkeypatch.setattr(run, "place_cache", lambda: "(not placed in a test)")
    for key in man.traffic(man.workload(CELLS[-1])["traffic"]).get("env", {}):
        monkeypatch.setenv(key, "")  # so that what main() sets is undone after the test
    assert run.main(["--workload", CELLS[-1], "--seed", str(2 ** 31 + 3),
                     "--seconds", "0.2", "--trace", "0"]) == 0
    captured = capsys.readouterr()
    last = json.loads(captured.out.strip().splitlines()[-1])
    assert list(last) == CONTRACT_KEYS
    assert set(last["metrics"]) == {"train_iter_s", "setup_s"}
    # each number compared stands beside its limit at the end of standard error
    tail = captured.err.strip().splitlines()[-len(last["compared"]):]
    for line, name in zip(tail, last["compared"]):
        assert line.startswith("bench: compared %s = " % name) and "limit" in line


def test_list_names_what_the_manifest_holds(capsys):
    assert run.main(["--list"]) == 0
    listing = json.loads(capsys.readouterr().out)
    assert listing["workloads"] == CELLS


def test_command_exits_non_zero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        Manifest().data["command"] + ["--workload", CELLS[0], "--seed", "1",
                                      "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode == run.NO_CHIP_EXIT != 0
    assert proc.stdout.strip() == ""                 # no result is printed
    assert "TPU" in proc.stderr


def test_command_exits_non_zero_where_only_the_benchmark_is(tmp_path):
    """In a directory that holds only BENCHMARK.json and the files under
    ``paths`` there is no program to measure."""
    tiny_copy(str(tmp_path))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH="")
    proc = subprocess.run(
        [sys.executable, "-m", "benchmarks.run", "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=str(tmp_path), env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0 and proc.stdout.strip() == ""


# -- the reference's own arithmetic ----------------------------------------

def test_floor_float32_keeps_the_comparison_exact():
    rng = np.random.default_rng(0)
    edges = rng.standard_normal(2000) * 10.0 ** rng.integers(-3, 4, 2000)
    floored = reference.floor_float32(edges)
    assert floored.dtype == np.float32
    assert np.all(floored.astype(np.float64) <= edges)
    assert np.all(np.nextafter(floored, np.float32(np.inf)).astype(np.float64) > edges)
    assert reference.floor_float32(np.array([np.inf]))[0] == np.inf


def test_logloss_matches_its_definition():
    s = np.array([-2.0, 0.0, 3.0])
    y = np.array([0.0, 1.0, 1.0])
    p = 1 / (1 + np.exp(-s))
    want = -np.mean(y * np.log(p) + (1 - y) * np.log(1 - p))
    assert abs(reference.logloss(s, y) - want) < 1e-12


def test_split_gains_against_a_loop():
    rng = np.random.default_rng(3)
    nodes, F, B = 3, 4, 16
    hist = np.zeros((nodes, F, B, 3))
    num_bin = np.array([16, 12, 9, 2])
    for n in range(nodes):
        for f in range(F):
            c = rng.integers(0, 40, num_bin[f]).astype(float)
            c *= 400.0 / max(c.sum(), 1)                    # same rows in every feature
            hist[n, f, : num_bin[f], 2] = c
            hist[n, f, : num_bin[f], 1] = 0.25 * c
            hist[n, f, : num_bin[f], 0] = rng.standard_normal(num_bin[f]) * np.sqrt(c)
        hist[n, :, :, 0] *= 0  # then give every feature the same total gradient
        for f in range(F):
            g = rng.standard_normal(num_bin[f]) * np.sqrt(hist[n, f, : num_bin[f], 2])
            hist[n, f, : num_bin[f], 0] = g - g.sum() / num_bin[f] + 5.0 / num_bin[f]
    p = {"min_data_in_leaf": 20, "min_sum_hessian_in_leaf": 1e-3, "lambda_l2": 0.0}
    got = reference.split_gains(hist, num_bin, p)
    for n in range(nodes):
        G, H, C = hist[n, 0].sum(axis=0)
        for f in range(F):
            for t in range(B):
                gl, hl, cl = hist[n, f, : t + 1].sum(axis=0)
                gr, hr, cr = G - gl, H - hl, C - cl
                allowed = (t <= num_bin[f] - 2 and cl >= 20 and cr >= 20
                           and hl >= 1e-3 and hr >= 1e-3)
                gain = (gl * gl / hl + gr * gr / hr - G * G / H) if allowed else -np.inf
                if allowed and gain > 0:
                    assert abs(got[n, f, t] - gain) < 1e-9
                else:
                    assert got[n, f, t] == -np.inf


def test_node_order_puts_leaves_after_the_internal_nodes():
    tree = {"num_leaves": 4, "left_child": np.array([1, -1, -3]),
            "right_child": np.array([-2, 2, -4])}
    assert reference.node_order(tree).tolist() == [[1, 4], [3, 2], [5, 6]]


@pytest.mark.parametrize("hessian, strict, lenient", [
    (100.02, True, True),      # clear of the minimum: offered and allowed
    (100.005, False, True),    # within the margin above it: allowed, not offered
    (99.995, False, True),     # within the margin below it: not held against the program
    (99.98, False, False),     # under it: ruled out
])
def test_a_candidate_on_the_hessian_minimum_is_neither_offered_nor_held_against(
        hessian, strict, lenient):
    # one node, one feature, two bins: the left child holds `hessian`
    hist = np.zeros((1, 1, 2, 3))
    hist[0, 0, 0] = [-30.0, hessian, 500]
    hist[0, 0, 1] = [40.0, 300.0, 1500]
    p = {"min_data_in_leaf": 1, "min_sum_hessian_in_leaf": 100.0}
    nb = np.array([2])
    margin = reference.HESSIAN_MARGIN
    assert np.isfinite(reference.split_gains(hist, nb, p, margin)[0, 0, 0]) == strict
    assert np.isfinite(reference.split_gains(hist, nb, p, -margin)[0, 0, 0]) == lenient
    assert reference.split_gains(hist, nb, p)[0, 0, 1] == -np.inf   # the last bin closes nothing
