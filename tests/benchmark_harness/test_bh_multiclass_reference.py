"""The reference of softmax boosting (``references/gbdt_multiclass.py``):
its arithmetic against plain loops, its count of work, and on the cell's tiny
copy under the cell's own limits a sound run inside them, the control outside,
each planted fault caught, and the three faults of the class loop told from
the three inherited ones by what they break."""
import numpy as np
import pytest

from benchmarks import correct, datagen, run, work
from benchmarks.manifest import DEFAULT_FAULTS, Manifest
from benchmarks.references import gbdt_binary
from benchmarks.references import gbdt_multiclass as reference

from bh_util import tiny_copy

CELL = "epsilon-255bin-multiclass.train-serial-pallas"
FAULTS = Manifest().faults(CELL)
MECHANISM = ["ova_gradients", "classes_in_sequence", "class_rotated"]


@pytest.fixture(scope="module")
def man(tmp_path_factory):
    return tiny_copy(str(tmp_path_factory.mktemp("multiclass_reference")))


def broken(result):
    return {k for k, c in result["compared"].items() if not c["value"] <= c["limit"]}


@pytest.fixture(scope="module")
def faulted(man):
    """One run of the tiny cell under each planted fault, from iteration 1 on."""
    out = {}
    for fault in FAULTS:
        with man.fault(fault)():
            out[fault] = run.run_cell(man, CELL, seed=9, seconds=0.0, trace=False)
    return out


def test_softmax_gradients_and_loss_against_loops():
    rng = np.random.default_rng(0)
    K, N = 4, 50
    s = rng.standard_normal((K, N)) * 3
    label = rng.integers(0, K, N)
    g, h = reference.gradients(s, label)
    loss = 0.0
    for i in range(N):
        e = np.exp(s[:, i] - s[:, i].max())
        p = e / e.sum()
        loss -= np.log(p[label[i]]) / N
        for k in range(K):
            assert g[k, i] == pytest.approx(p[k] - (label[i] == k), abs=1e-12)
            assert h[k, i] == pytest.approx(2 * p[k] * (1 - p[k]), abs=1e-12)
    assert reference.logloss(s, label) == pytest.approx(loss, rel=1e-12)
    # two classes at scores (0, s) are the binary log-loss of s
    two = np.stack([np.zeros(N), s[0]])
    y = (label % 2).astype(float)
    assert reference.logloss(two, y.astype(int)) == pytest.approx(
        gbdt_binary.logloss(s[0], y), rel=1e-12)


def test_the_follower_starts_every_class_at_its_prior():
    rng = np.random.default_rng(1)
    X = rng.standard_normal((1000, 3)).astype(np.float32)
    y = np.repeat([0, 1, 2, 3], [100, 200, 300, 400]).astype(np.float32)
    edges = [np.append(np.quantile(X[:, f], [0.25, 0.5, 0.75]), np.inf) for f in range(3)]
    params = {"min_data_in_leaf": 1, "min_sum_hessian_in_leaf": 1.0, "learning_rate": 0.1,
              "max_bin": 4}
    f = reference.Follower(X, y, edges, params, 5)           # class 4 holds no row
    assert f.scores.shape == f.applied.shape == (5, 1000)
    assert f.init_scores[:4] == pytest.approx(np.log([0.1, 0.2, 0.3, 0.4]))
    assert f.init_scores[4] == np.log(reference.K_EPSILON)
    assert f.bin_width == gbdt_binary.Follower(X, (y > 1).astype(float), edges, params).bin_width
    with pytest.raises(ValueError):
        reference.Follower(X, y, edges, params, 3)


def test_the_work_of_a_tree_is_the_binary_trees_and_its_share_of_the_softmax_pass():
    tree = {"num_leaves": 3, "left_child": np.array([-1, -2]), "right_child": np.array([1, -3]),
            "leaf_count": np.array([400, 250, 350]), "internal_count": np.array([1000, 600])}
    config = {"rows": 1000, "features": 7, "params": {"max_bin": 15}}
    plain, own = work.of_config(tree, config), reference.work(tree, config)
    assert own["ops"] == plain["ops"] and own["hist_rows"] == plain["hist_rows"]
    assert own["bytes"] == plain["bytes"] + (4 + 8) * 1000


def test_a_run_of_another_objective_is_refused():
    produced = {"text": "tree\nnum_class=1\n\nTree=0\nnum_leaves=1\nleaf_value=0\n\nend of trees\n",
                "warm_scores": [], "final_scores": np.zeros(3), "iterations_run": 1}
    data = {"X": np.zeros((3, 1), np.float32), "y": np.zeros(3)}
    with pytest.raises(ValueError, match="softmax"):
        reference.compare(produced, data, [np.array([np.inf])], {"objective": "binary"}, [])


def test_a_sound_run_is_correct(man):
    result = run.run_cell(man, CELL, seed=2 ** 31 + 41, seconds=0.3, trace=False)
    assert result["correct"] is True, result["compared"]
    assert result["compared"]["exact_mismatch"]["value"] == 0
    assert result["compared"]["score_gap"]["value"] == 0


def test_the_control_is_not_correct(man):
    import lightgbm_tpu as lgb

    spec = man.workload(CELL)
    config, traffic = man.config(spec["config"]), man.traffic(spec["traffic"])
    limits, params = man.limits(CELL), run.train_params(config, traffic)
    X, y, _ = datagen.make(config, 2 ** 31 + 43, man.bench_dir)
    ds = lgb.Dataset(X, label=y, params=params).construct()
    out = run.drive(lgb, params, ds, traffic, seconds=0.0)
    follow = run.followed(limits, out["win"], out["produced"]["text"])
    assert follow == list(range(15, 20))        # every class's tree of the window's last iteration
    logged = []
    numbers = reference.compare(out["produced"], {"X": X, "y": y},
                                correct.bin_edges(ds, config["features"]), params, follow,
                                control_dtype=traffic["precision"]["control"], log=logged.append)
    assert sum("followed by its histograms" in line for line in logged) == 5
    program = dict(numbers["program"], compiles_in_window=0.0)
    assert all(c["ok"] for c in correct.judge(program, limits["limits"]).values())
    control = dict(program, **numbers["control"])
    failed = [k for k, c in correct.judge(control, limits["limits"]).items() if not c["ok"]]
    assert {"loss_gap", "bin_width"} <= set(failed), failed


@pytest.mark.parametrize("fault", FAULTS)
def test_each_planted_fault_is_not_correct(faulted, fault):
    assert faulted[fault]["correct"] is False
    assert broken(faulted[fault]) & {"exact_mismatch", "leaf_value_gap", "loss_gap",
                                     "score_gap"}, faulted[fault]["compared"]


@pytest.mark.parametrize("fault", MECHANISM)
def test_a_fault_of_the_class_loop_is_told_from_the_inherited_ones(faulted, fault):
    """Sound trees of the wrong gradients, or in the wrong place, each moving
    the class whose place it holds: the partition and the score update hold,
    the leaf values and the loss do not. Each inherited fault breaks the
    partition (``half_batch``) or the scores' agreement with the model text
    (``state_unchanged``, ``altered_answer``)."""
    assert {"leaf_value_gap", "loss_gap"} <= broken(faulted[fault])
    assert not broken(faulted[fault]) & {"exact_mismatch", "score_gap"}
    for inherited in DEFAULT_FAULTS:
        assert broken(faulted[inherited]) & {"exact_mismatch", "score_gap"}, inherited


def test_the_faults_of_the_class_loop_leave_the_program_as_it_was(man):
    from lightgbm_tpu.models.gbdt import GBDT

    before = (GBDT._compute_gradients, GBDT._train_tree)
    for fault in MECHANISM:
        with pytest.raises(RuntimeError):
            with man.fault(fault)():
                raise RuntimeError("inside")
    assert (GBDT._compute_gradients, GBDT._train_tree) == before


@pytest.mark.parametrize("fault", MECHANISM)
def test_a_fault_of_the_class_loop_planted_in_the_window_is_not_correct(man, fault):
    with man.fault(fault)(iteration=4):
        result = run.run_cell(man, CELL, seed=2 ** 31 + 10, seconds=1.0, trace=False)
    assert result["attempted"] >= 3, "the window has to reach past the planted iteration"
    assert result["correct"] is False
    assert "leaf_value_gap" in broken(result), result["compared"]
