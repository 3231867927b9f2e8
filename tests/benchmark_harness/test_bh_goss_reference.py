"""The reference of sampled boosting (``references/gbdt_binary_goss.py``)
against plain loops at a tiny size: the law of the draw and each way of
breaking it, the Kolmogorov distance of the drawn rows, a tree's sums over
the rows of its draw with the drawn ones at the multiplier, the score update
of every row, and its count of work."""
import numpy as np
import pytest

from benchmarks import correct, work
from benchmarks.references import gbdt_binary
from benchmarks.references import gbdt_binary_goss as reference

PARAMS = {"boosting": "goss", "top_rate": 0.2, "other_rate": 0.1, "learning_rate": 0.1,
          "min_data_in_leaf": 1, "min_sum_hessian_in_leaf": 1e-3, "lambda_l2": 0.0,
          "max_bin": 255}
N = 1000


def sound_draw(gh, rng, iteration=10):
    top_k, other_k, _ = reference.sizes(len(gh), PARAMS)
    order = np.argsort(-gh, kind="stable")
    drawn = np.zeros(len(gh), bool)
    drawn[rng.choice(order[top_k:], other_k, replace=False)] = True
    in_bag = drawn.copy()
    in_bag[order[:top_k]] = True
    return {"iteration": iteration, "in_bag": in_bag, "amplified": drawn,
            "multiplier": (len(gh) - top_k) / other_k}


@pytest.fixture
def gh():
    return np.random.default_rng(0).random(N) ** 2


def test_the_sizes_are_the_laws():
    assert reference.sizes(200000, PARAMS) == (40000, 20000, 10)
    assert reference.sizes(20000, dict(PARAMS, learning_rate=0.3)) == (4000, 2000, 3)


def test_a_sound_draw_keeps_the_law(gh):
    law = reference.sample_law(sound_draw(gh, np.random.default_rng(1)), gh, 10, PARAMS)
    assert law["mismatch"] == 0 and law["edge"] == 0.0
    assert 0 < law["ks"] < 0.15       # 100 of 800: sqrt(1 / 100) is 0.1


def test_no_draw_before_sampling_starts_and_one_from_then_on(gh):
    draw = sound_draw(gh, np.random.default_rng(1))
    assert reference.sample_law(None, gh, 9, PARAMS) == {"mismatch": 0, "ks": 0.0, "edge": 0.0}
    assert reference.sample_law(draw, gh, 9, PARAMS)["mismatch"] == 1
    assert reference.sample_law(None, gh, 10, PARAMS)["mismatch"] == 1


@pytest.mark.parametrize("broken, least", [
    ("one_more_in_bag", 1), ("one_more_multiplied", 1), ("multiplied_out_of_the_bag", 1),
    ("multiplier", 1), ("a_small_row_at_weight_one", 2), ("a_top_row_left_out", 2)])
def test_each_way_of_breaking_the_law_is_counted(gh, broken, least):
    d = sound_draw(gh, np.random.default_rng(2))
    order = np.argsort(-gh, kind="stable")
    out = np.flatnonzero(~d["in_bag"])
    if broken == "one_more_in_bag":
        d["in_bag"][out[0]] = True          # at weight 1, yet near the bottom: counted twice
    elif broken == "one_more_multiplied":
        d["in_bag"][out[0]] = d["amplified"][out[0]] = True
    elif broken == "multiplied_out_of_the_bag":
        d["amplified"][out[0]] = True
    elif broken == "multiplier":
        d["multiplier"] = 1.0
    elif broken == "a_small_row_at_weight_one":     # counts kept: a top row makes room
        d["in_bag"][order[0]] = False
        d["in_bag"][order[-1]] = True
    else:
        d["in_bag"][order[0]] = False
        d["in_bag"][out[0]] = d["amplified"][out[0]] = True
        d["amplified"][np.flatnonzero(d["amplified"])[0]] = False
    assert reference.sample_law(d, gh, 10, PARAMS)["mismatch"] >= least


def test_a_row_within_the_margin_of_the_top_may_stand_on_either_side(gh):
    order = np.argsort(-gh, kind="stable")
    top_k = reference.sizes(N, PARAMS)[0]
    gh[order[top_k]] = gh[order[top_k - 1]] * (1 - reference.TOP_MARGIN / 2)
    d = sound_draw(gh, np.random.default_rng(3))
    d["in_bag"][order[top_k - 1]] = d["amplified"][order[top_k]] = False
    d["in_bag"][order[top_k]] = True              # the two change places
    d["amplified"][np.flatnonzero(~d["in_bag"])[0]] = d["in_bag"][np.flatnonzero(~d["in_bag"])[0]] = True
    law = reference.sample_law(d, gh, 10, PARAMS)
    assert 0 < law["edge"] < reference.TOP_MARGIN
    gh[order[top_k]] = gh[order[top_k - 1]] * (1 - 3 * reference.TOP_MARGIN)
    assert reference.sample_law(d, gh, 10, PARAMS)["mismatch"] >= 1


def test_the_kolmogorov_distance_against_a_loop(gh):
    rng = np.random.default_rng(4)
    a, b = rng.random(40), rng.random(300) ** 2
    want = max(abs(np.mean(a <= x) - np.mean(b <= x)) for x in np.concatenate([a, b]))
    assert reference.kolmogorov(a, b) == pytest.approx(want, abs=1e-12)
    assert reference.kolmogorov(b, b) == 0.0
    # the next other_k rows by rank in place of a draw: 1 - other_k / (n - top_k)
    top_k, other_k, _ = reference.sizes(N, PARAMS)
    order = np.argsort(-gh, kind="stable")
    d = sound_draw(gh, rng)
    d["in_bag"][:] = d["amplified"][:] = False
    d["in_bag"][order[: top_k + other_k]] = d["amplified"][order[top_k: top_k + other_k]] = True
    law = reference.sample_law(d, gh, 10, PARAMS)
    assert law["mismatch"] == 0 and law["ks"] == pytest.approx(1 - other_k / (N - top_k))


def _table(rng, rows=N):
    X = rng.standard_normal((rows, 3)).astype(np.float32)
    y = (X[:, 0] + 0.5 * rng.standard_normal(rows) > 0).astype(np.float32)
    edges = [np.append(np.quantile(X[:, f].astype(np.float64), np.arange(1, 8) / 8), np.inf)
             for f in range(3)]
    return X, y, edges


def test_a_trees_sums_run_over_the_draw_with_the_drawn_rows_multiplied():
    rng = np.random.default_rng(5)
    X, y, edges = _table(rng)
    ref = reference.Follower(X, y, edges, PARAMS)
    ref.scores = ref.scores + 0.3 * rng.standard_normal(N)
    g, h = gbdt_binary.gradients(ref.scores, ref.y)
    draw = sound_draw(np.abs(g * h), rng)
    tree = {"num_leaves": 3, "split_feature": np.array([0, 1]),
            "threshold": np.array([edges[0][3], edges[1][2]]),
            "left_child": np.array([1, -1]), "right_child": np.array([-3, -2])}
    f = ref.follow(tree, False, histograms=True, draw=draw)
    leaf = np.where(X[:, 0] > edges[0][3], 2, np.where(X[:, 1] > edges[1][2], 1, 0))
    assert f["leaf"].tolist() == leaf.tolist()             # every row, in the bag or not
    w = np.where(draw["amplified"], draw["multiplier"], 1.0) * draw["in_bag"]
    for l in range(3):
        at = leaf == l
        assert f["leaf_count"][l] == int((at & draw["in_bag"]).sum())
        assert f["leaf_values"][l] == pytest.approx(
            -np.sum(g[at] * w[at]) / np.sum(h[at] * w[at]) * 0.1, rel=1e-12)
    assert f["internal_count"].tolist() == [int(draw["in_bag"].sum()),
                                            int((draw["in_bag"] & (leaf < 2)).sum())]
    # the root's chosen gain, by a loop over the in-bag rows
    b = np.searchsorted(gbdt_binary.floor_float32(edges[0]), X[:, 0], side="left")
    gl, hl = np.sum((g * w)[b <= 3]), np.sum((h * w)[b <= 3])
    G, H = np.sum(g * w), np.sum(h * w)
    want = gl ** 2 / hl + (G - gl) ** 2 / (H - hl) - G ** 2 / H
    assert f["split_gap"].shape == (2,) and np.all(f["split_gap"] >= 0)
    full = ref.follow(tree, False, histograms=True)        # no draw: every row at weight 1
    assert full["leaf_count"].sum() == N and not np.allclose(full["leaf_values"], f["leaf_values"])
    blocks = ref.on_rows(np.flatnonzero(draw["in_bag"]))._block(
        0, 3, tree, gbdt_binary.node_order(tree), leaf[draw["in_bag"]] * ref.width,
        [(g * w)[draw["in_bag"]], (h * w)[draw["in_bag"]]], ref.threshold_bins(tree))
    assert blocks["chosen"][1][0] == pytest.approx(want, rel=1e-9)


def test_the_numbers_are_the_six_and_the_draws_two():
    assert set(reference.NUMBERS) == set(gbdt_binary.NUMBERS) | {"sample_mismatch", "other_ks"}
    assert reference.NUMBERS["sample_mismatch"]["limit"] == "exact"
    assert reference.NUMBERS["other_ks"]["limit"] == "gap"
    assert correct.private_names(reference.collect) == []


def test_work_counts_the_sample_and_two_passes_over_the_table():
    tree = {"num_leaves": 3, "left_child": np.array([-1, -2]), "right_child": np.array([1, -3]),
            "leaf_count": np.array([120, 75, 105]), "internal_count": np.array([300, 180])}
    config = {"rows": 1000, "features": 7, "params": {"max_bin": 15}}
    plain, sampled = work.of_config(tree, config), reference.work(tree, config)
    assert sampled["hist_rows"] == plain["hist_rows"] == 300 + 120 + 75
    assert sampled["ops"] == plain["ops"]
    assert sampled["bytes"] == plain["bytes"] + 2 * 1000 * 8
    # a tree grown on every row is counted as gbdt_binary's
    whole = dict(tree, leaf_count=np.array([400, 250, 350]), internal_count=np.array([1000, 600]))
    assert reference.work(whole, config) == work.of_config(whole, config)
    assert work.counter(reference) is reference.work
