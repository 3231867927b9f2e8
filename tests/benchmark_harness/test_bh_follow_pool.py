"""``benchmarks/follow_pool.py``: a followed tree's histograms and candidates
in fewer passes and by worker processes, held to ``gbdt_binary``'s own
``Follower._block``, value for value (the same float64 operations in the same
order), with and without the control's lower-precision sums."""
import numpy as np
import pytest

from benchmarks import follow_pool
from benchmarks.references import gbdt_binary as plain

N, F, L = 3000, 21, 15


@pytest.fixture(scope="module")
def followed():
    """A follower of a random table and a random tree of ``L`` leaves."""
    rng = np.random.default_rng(3)
    X = rng.standard_normal((N, F)).astype(np.float32)
    y = (X[:, 0] + rng.standard_normal(N) > 0).astype(np.float32)
    edges = [np.append(np.quantile(X[:, f], np.linspace(0, 1, 33)[1:-1]), np.inf)
             for f in range(F)]
    params = {"min_data_in_leaf": 3, "min_sum_hessian_in_leaf": 5.0, "learning_rate": 0.1,
              "max_bin": 32, "lambda_l2": 0.5, "min_gain_to_split": 0.0}
    ref = plain.Follower(X, y, edges, params)
    # split a random leaf at a random edge, L - 1 times
    left, right, feat, thr, parent_of = [], [], [], [], {0: None}
    for i in range(L - 1):
        leaf = sorted(parent_of)[rng.integers(len(parent_of))]
        at = parent_of.pop(leaf)
        if at is not None:
            (left if at[1] == 0 else right)[at[0]] = i
        f = int(rng.integers(F))
        feat.append(f)
        thr.append(edges[f][rng.integers(len(edges[f]) - 1)])
        left.append(-(leaf + 1))
        right.append(-(i + 2))
        parent_of[leaf], parent_of[i + 1] = (i, 0), (i, 1)
    tree = {"num_leaves": L, "split_feature": np.array(feat), "threshold": np.array(thr),
            "left_child": np.array(left), "right_child": np.array(right)}
    thr_bin = ref.threshold_bins(tree)
    leaf = ref.leaves(tree, np.maximum(thr_bin, 0))
    weights = list(plain.gradients(ref.scores, ref.y))
    return {"ref": ref, "tree": tree, "thr_bin": thr_bin, "base": leaf * ref.width,
            "kids": plain.node_order(tree), "weights": weights,
            "control": weights + [plain.rounded(w, "bfloat16") for w in weights]}


def _same(a, b):
    assert set(a) == set(b)
    assert np.array_equal(a["chosen"][0], b["chosen"][0])
    assert np.array_equal(a["chosen"][1], b["chosen"][1])
    for key in set(a) - {"chosen"}:
        assert np.array_equal(a[key], b[key]), key


@pytest.mark.parametrize("sets", ["weights", "control"])
@pytest.mark.parametrize("span", [(0, 8), (5, 6), (8, 21)])
def test_search_gives_the_binary_blocks_numbers(followed, sets, span):
    ref, args = followed["ref"], (followed["tree"], followed["kids"], followed["base"],
                                  followed[sets], followed["thr_bin"])
    want = ref._block(*span, *args)
    got = follow_pool.search(ref.bins, ref.num_bin, ref.width, ref.params, *span, *args)
    _same(want, got)
    assert np.isfinite(want["best"]).any()


@pytest.mark.parametrize("workers", [1, 2])
def test_the_pool_gives_every_block_in_the_features_order(followed, workers):
    ref = followed["ref"]
    args = (followed["tree"], followed["kids"], followed["base"], followed["control"],
            followed["thr_bin"])
    with follow_pool.FollowPool(ref, workers=workers) as pool:
        got = pool.blocks(*args)
    spans = [(a, min(a + follow_pool.BLOCK_FEATURES, F))
             for a in range(0, F, follow_pool.BLOCK_FEATURES)]
    assert len(got) == len(spans) == 3
    for (f0, f1), block in zip(spans, got):
        _same(ref._block(f0, f1, *args), block)
