"""The reference of column-sampled boosting
(``references/gbdt_binary_featfrac.py``) against plain loops at a tiny size:
the law of the draw on hand-made draws and each way of breaking it, the
distance of the pooled columns from the uniform law, a tree's candidates
searched over its drawn columns alone, and its count of work."""
import numpy as np
import pytest

from benchmarks import correct, work
from benchmarks.references import gbdt_binary
from benchmarks.references import gbdt_binary_featfrac as reference

PARAMS = {"feature_fraction": 0.8, "learning_rate": 0.1, "min_data_in_leaf": 1,
          "min_sum_hessian_in_leaf": 1e-3, "lambda_l2": 0.0, "max_bin": 255}
F, K, TREES = 40, 32, 12


def stump(feature):
    return {"num_leaves": 2, "split_feature": np.array([feature])}


def sound(rng, trees=TREES):
    """Draws that keep the law, and trees that split on a drawn column."""
    draws = {t: np.sort(rng.choice(F, K, replace=False)) for t in range(trees)}
    return draws, [stump(draws[t][t % K]) for t in range(trees)]


def test_the_count_is_the_laws():
    assert reference.drawn_count(2000, PARAMS) == 1600
    assert reference.drawn_count(28, PARAMS) == 22
    assert reference.drawn_count(3, dict(PARAMS, feature_fraction=0.1)) == 1


def test_sound_draws_keep_the_law():
    draws, trees = sound(np.random.default_rng(0))
    law = reference.draw_law(draws, trees, F, PARAMS)
    assert law["mismatch"] == 0 and law["outside"] == [0] * TREES
    assert 0 < law["ks"] < 0.05


@pytest.mark.parametrize("broken, counted", [
    ("one_column_short", 1), ("one_repeated", 1), ("one_out_of_range", 1),
    ("out_of_order", 1), ("a_tree_without_a_draw", 2), ("a_draw_without_its_tree", 1),
    ("a_split_out_of_its_draw", 1), ("a_frozen_draw", TREES - 1),
    ("the_first_k_columns_every_tree", TREES - 1)])
def test_each_way_of_breaking_the_law_is_counted(broken, counted):
    rng = np.random.default_rng(1)
    draws, trees = sound(rng)
    if broken == "one_column_short":
        draws[3] = np.delete(draws[3], (3 % K + 1) % K)
    elif broken == "one_repeated":          # the count kept: one column twice, one gone
        at = (5 % K + 1) % K or 1
        draws[5][at] = draws[5][at - 1]
        trees[5] = stump(draws[5][0])
    elif broken == "one_out_of_range":      # rising still: the last one past the table
        draws[2][-1] = F
        trees[2] = stump(draws[2][0])
    elif broken == "out_of_order":
        draws[4][[0, 1]] = draws[4][[1, 0]]
    elif broken == "a_tree_without_a_draw":     # and its one split, which nobody offered
        del draws[7]
    elif broken == "a_draw_without_its_tree":
        draws[TREES] = draws[0]
    elif broken == "a_split_out_of_its_draw":
        trees[6] = stump(np.setdiff1d(np.arange(F), draws[6])[0])
    elif broken == "a_frozen_draw":
        draws = {t: draws[0] for t in draws}
        trees = [stump(draws[0][t % K]) for t in range(TREES)]
    else:
        draws = {t: np.arange(K) for t in draws}
        trees = [stump(t % K) for t in range(TREES)]
    law = reference.draw_law(draws, trees, F, PARAMS)
    assert law["mismatch"] == counted
    if broken == "the_first_k_columns_every_tree":
        assert law["ks"] == pytest.approx(1 - K / F)
    if broken == "a_split_out_of_its_draw":
        assert law["outside"] == [0] * 6 + [1] + [0] * (TREES - 7)


def test_the_distance_from_the_uniform_law_against_a_loop():
    rng = np.random.default_rng(2)
    cols = rng.integers(0, F, 300)
    want = max(abs(np.mean(cols <= c) - (c + 1) / F) for c in range(F))
    assert reference.uniform_distance(cols, F) == pytest.approx(want, abs=1e-12)
    assert reference.uniform_distance(np.arange(F), F) == 0.0
    assert reference.uniform_distance(np.zeros(0, np.int64), F) == 1.0
    # 1600 of 2000 over 30 trees, as the cell draws them: far under the limit
    pooled = np.concatenate([rng.choice(2000, 1600, replace=False) for _ in range(30)])
    assert reference.uniform_distance(pooled, 2000) < 0.01


def _table(rng, rows=1000, features=6):
    X = rng.standard_normal((rows, features)).astype(np.float32)
    y = (X[:, 0] + 0.8 * X[:, 3] + 0.5 * rng.standard_normal(rows) > 0).astype(np.float32)
    edges = [np.append(np.quantile(X[:, f].astype(np.float64), np.arange(1, 8) / 8), np.inf)
             for f in range(features)]
    return X, y, edges


def test_a_trees_candidates_are_searched_over_its_drawn_columns_alone():
    rng = np.random.default_rng(3)
    X, y, edges = _table(rng)
    ref = reference.Follower(X, y, edges, PARAMS)
    tree = {"num_leaves": 3, "split_feature": np.array([3, 1]),
            "threshold": np.array([edges[3][3], edges[1][2]]),
            "left_child": np.array([1, -1]), "right_child": np.array([-3, -2])}
    whole = ref.follow(tree, False, histograms=True)
    # column 0 carries the most gain: over every column the root's split on 3 lies below it
    assert whole["split_gap"][0] > 0.1
    drawn = ref.follow(tree, False, histograms=True, draw=np.array([1, 2, 3, 5]))
    # of the draw, column 3 offers the best: what is left is the threshold beside the best one
    assert 0.0 <= drawn["split_gap"][0] < 0.1
    assert drawn["chosen_gain"] == pytest.approx(whole["chosen_gain"], rel=1e-12)
    for key in ("leaf", "leaf_count", "internal_count", "thr_bin"):
        assert np.array_equal(drawn[key], whole[key])
    assert np.array_equal(drawn["leaf_values"], whole["leaf_values"])
    # the best of the draw, by gbdt_binary's own search over those columns of a copy
    held = gbdt_binary.Follower(X[:, [1, 2, 3, 5]], y, [edges[f] for f in (1, 2, 3, 5)], PARAMS)
    inside = dict(tree, split_feature=np.array([2, 0]))
    assert held.follow(inside, False)["frontier_best"] == pytest.approx(
        drawn["frontier_best"], rel=1e-12)
    # a split on a column out of the draw was offered by nobody
    out = ref.follow(tree, False, histograms=True, draw=np.array([0, 1, 2, 5]))
    assert np.isinf(out["split_gap"][0]) and np.isfinite(out["split_gap"][1])
    # followed by its sums alone, a draw changes nothing
    assert np.array_equal(ref.follow(tree, False, histograms=False, draw=np.array([1]))["leaf"],
                          whole["leaf"])


def test_the_numbers_are_the_six_and_the_draws_two():
    assert set(reference.NUMBERS) == set(gbdt_binary.NUMBERS) | {"draw_mismatch", "draw_ks"}
    assert reference.NUMBERS["draw_mismatch"]["limit"] == "exact"
    assert reference.NUMBERS["draw_ks"]["limit"] == "gap"
    assert correct.private_names(reference.collect) == []


def test_work_counts_the_drawn_width_and_the_gather():
    tree = {"num_leaves": 3, "left_child": np.array([-1, -2]), "right_child": np.array([1, -3]),
            "leaf_count": np.array([400, 250, 350]), "internal_count": np.array([1000, 600])}
    config = {"rows": 1000, "features": 10, "params": {"max_bin": 15, "feature_fraction": 0.8}}
    drawn = reference.work(tree, config)
    at_eight = work.tree_work(tree, 8, 16)
    assert drawn["hist_rows"] == at_eight["hist_rows"] == 1000 + 400 + 250
    assert drawn["ops"] == at_eight["ops"] < work.of_config(tree, config)["ops"]
    assert drawn["bytes"] == at_eight["bytes"] + 2 * 1000 * 8
    assert reference.work(dict(tree, num_leaves=1), config)["bytes"] == 0.0
    assert work.counter(reference) is reference.work
