"""The reference of missing-value boosting (``references/gbdt_binary_nan.py``)
against plain loops at a tiny size: the NaN bin, both scans and what each
offers, the NaN feature of two bins, the margins at the hessian minimum and at
a gain of nothing, the partition by the default direction, what it reads of
the model text, its look at the bin edges and its count of work."""
import numpy as np
import pytest

from benchmarks import correct, work
from benchmarks.references import gbdt_binary
from benchmarks.references import gbdt_binary_nan as reference

NAN = float("nan")
P = {"min_data_in_leaf": 1, "min_sum_hessian_in_leaf": 1e-3, "lambda_l2": 0.0}


def histograms(rng, nodes, num_bin, B):
    """[nodes, F, B, 3] with the same rows, gradient and hessian in every
    feature of a node, as one set of rows binned F ways has."""
    hist = np.zeros((nodes, len(num_bin), B, 3))
    for n in range(nodes):
        rows = 500 + 100 * n
        g = rng.standard_normal(rows)
        h = rng.uniform(0.1, 0.3, rows)
        for f, nb in enumerate(num_bin):
            b = rng.integers(0, nb, rows)
            for k, w in enumerate((g, h, np.ones(rows))):
                hist[n, f, :nb, k] = np.bincount(b, weights=w, minlength=nb)
    return hist


def gain_by_loop(cells, left_bins, p):
    """One candidate's gain from the node's [bins, 3] cells and the set of
    bins that go left; None where a side is empty of rows."""
    left = cells[sorted(left_bins)].sum(axis=0)
    right = cells.sum(axis=0) - left
    if left[2] < p["min_data_in_leaf"] or right[2] < p["min_data_in_leaf"]:
        return None
    if min(left[1], right[1]) < p["min_sum_hessian_in_leaf"]:
        return None
    total = cells.sum(axis=0)
    return left[0] ** 2 / left[1] + right[0] ** 2 / right[1] - total[0] ** 2 / total[1]


def test_both_scans_against_a_loop():
    rng = np.random.default_rng(5)
    num_bin = np.array([12, 7, 2, 9, 2, 3])
    nan_type = np.array([True, True, True, False, False, True])
    B = 12
    hist = histograms(rng, 3, num_bin, B)
    got = reference.split_gains(hist, num_bin, nan_type, P)
    assert got.shape == (2, 3, 6, B)
    seen = 0
    for n in range(3):
        for f, nb in enumerate(num_bin):
            cells = hist[n, f, :nb]
            real = nb - 1 if nan_type[f] else nb              # the bins that hold values
            for t in range(B):
                lower = set(range(min(t + 1, real)))
                if nan_type[f] and nb > 2:                    # scanned both ways
                    want = {0: gain_by_loop(cells, lower, P) if t <= real - 1 else None,
                            1: gain_by_loop(cells, lower | {nb - 1}, P) if t <= real - 2 else None}
                elif nan_type[f]:                             # one real bin and the NaN bin
                    want = {0: gain_by_loop(cells, {0}, P) if t == 0 else None, 1: None}
                else:                                         # no missing type: one scan, either name
                    one = gain_by_loop(cells, lower, P) if t <= nb - 2 else None
                    want = {0: one, 1: one}
                for scan in (0, 1):
                    if want[scan] is not None and want[scan] > 0:
                        assert got[scan, n, f, t] == pytest.approx(want[scan], abs=1e-9)
                        seen += 1
                    else:
                        assert got[scan, n, f, t] == -np.inf, (scan, n, f, t)
    assert seen > 100


def test_what_each_scan_offers_of_a_nan_feature():
    """Five real bins and the NaN bin: missing to the right is offered up to
    the last real bin (the valued against the missing), missing to the left up
    to the last real bin but one, the NaN bin's own threshold by neither."""
    hist = histograms(np.random.default_rng(1), 1, np.array([6]), 6)
    gains = reference.split_gains(hist, np.array([6]), np.array([True]), P)[:, 0, 0]
    assert np.isfinite(gains[0]).tolist() == [True, True, True, True, True, False]
    assert np.isfinite(gains[1]).tolist() == [True, True, True, True, False, False]
    cells = hist[0, 0]
    # the valued against the missing, and the same threshold with the missing on the left
    assert gains[0, 4] == pytest.approx(gain_by_loop(cells, {0, 1, 2, 3, 4}, P))
    assert gains[1, 2] == pytest.approx(gain_by_loop(cells, {0, 1, 2, 5}, P))
    assert gains[0, 2] == pytest.approx(gain_by_loop(cells, {0, 1, 2}, P))
    assert gains[0, 2] != gains[1, 2]


def test_a_nan_feature_of_two_bins_is_offered_once_with_the_missing_right():
    hist = histograms(np.random.default_rng(2), 1, np.array([2]), 4)
    gains = reference.split_gains(hist, np.array([2]), np.array([True]), P)[:, 0, 0]
    assert np.isfinite(gains[0]).tolist() == [True, False, False, False]
    assert not np.isfinite(gains[1]).any()
    assert gains[0, 0] == pytest.approx(gain_by_loop(hist[0, 0, :2], {0}, P))


@pytest.mark.parametrize("hessian, offered, allowed", [
    (5.002, True, True),       # clear of the minimum
    (5.0002, False, True),     # within the margin above it: allowed, not offered
    (4.9997, False, True),     # within the margin below it: not held against the program
    (4.998, False, False),     # under it
])
def test_the_margin_at_the_hessian_minimum_is_by_scan(hessian, offered, allowed):
    # one feature, two real bins and the NaN bin; the left real bin holds `hessian`
    hist = np.zeros((1, 1, 3, 3))
    hist[0, 0, 0] = [-3.0, hessian, 900]
    hist[0, 0, 1] = [2.0, 40.0, 7000]
    hist[0, 0, 2] = [1.5, 30.0, 5000]
    p = dict(P, min_sum_hessian_in_leaf=5.0)
    parts = reference.scans(hist, np.array([3]), np.array([True]), p)
    # missing right at threshold 0: the left side is the bin alone
    assert np.isfinite(reference.within(*parts, p, 1.0)[0, 0, 0, 0]) == offered
    assert np.isfinite(reference.within(*parts, p, -1.0)[0, 0, 0, 0]) == allowed
    # missing left at threshold 0 puts 30 more on the left: clear of the minimum
    assert np.isfinite(reference.within(*parts, p, 1.0)[1, 0, 0, 0])


def test_a_split_that_gains_nothing_is_neither_offered_nor_held_against():
    """A leaf of one label: every row has the same gradient and hessian, every
    split gains 0 up to rounding; a share of the parent's term decides."""
    hist = np.zeros((1, 1, 4, 3))
    rows = np.array([300.0, 500.0, 200.0, 4000.0])
    hist[0, 0, :, 0], hist[0, 0, :, 1], hist[0, 0, :, 2] = 0.0058 * rows, 0.00577 * rows, rows
    parts = reference.scans(hist, np.array([4]), np.array([True]), P)
    gain, parent = parts[0], parts[3]
    assert np.all(np.abs(gain[:, 0, 0, :2]) < 1e-9 * parent[0, 0, 0])
    assert not np.isfinite(reference.within(*parts, P, 1.0)).any()
    assert np.isfinite(reference.within(*parts, P, -1.0)[0, 0, 0, :3]).all()
    # a gain of a thousandth of the parent's term is offered
    hist[0, 0, 0, 0] *= 1.5
    parts = reference.scans(hist, np.array([4]), np.array([True]), P)
    assert np.isfinite(reference.within(*parts, P, 1.0)[0, 0, 0, 0])


# -- the table, the partition and the model text -------------------------------

EDGES = [np.array([-1.0, 0.5, 2.0, np.inf, NAN]),      # four real bins and the NaN bin
         np.array([0.0, np.inf]),                       # no missing type
         np.array([np.inf, NAN])]                       # one real bin and the NaN bin
X = np.array([[-2.0, -1.0, 7.0], [0.5, 0.0, NAN], [NAN, 3.0, 1.0], [9.0, NAN, NAN],
              [1.0, 0.5, 2.0], [NAN, -4.0, NAN]], np.float32)
Y = np.array([0, 1, 0, 1, 0, 0], np.float32)
PARAMS = dict(P, max_bin=255, learning_rate=0.1)


def test_rows_are_binned_with_the_nan_bin_last():
    ref = reference.Follower(X, Y, EDGES, PARAMS)
    assert ref.nan_type.tolist() == [True, False, True]
    assert ref.nan_bin.tolist() == [4, reference.NO_BIN, 1]
    assert ref.bins[0].tolist() == [0, 1, 4, 3, 2, 4]
    assert ref.bins[1].tolist() == [0, 0, 1, 0, 1, 0]          # no NaN bin: a NaN counts as 0
    assert ref.bins[2].tolist() == [0, 1, 0, 1, 0, 1]


def tree_of(feature, threshold, decision_type):
    """One split, rows to leaf 0 on the left and leaf 1 on the right."""
    return reference.with_decisions(
        {"num_leaves": 2, "split_feature": np.array([feature]), "threshold": np.array([threshold]),
         "left_child": np.array([-1]), "right_child": np.array([-2])},
        np.array([decision_type]))


@pytest.mark.parametrize("decision_type, leaves", [
    (8, [0, 0, 1, 1, 1, 1]),      # NaN type, missing to the right
    (10, [0, 0, 0, 1, 1, 0]),     # NaN type, missing to the left
])
def test_the_partition_goes_by_the_default_direction(decision_type, leaves):
    ref = reference.Follower(X, Y, EDGES, PARAMS)
    tree = tree_of(0, 0.5, decision_type)
    thr = ref.threshold_bins(tree)
    assert thr.tolist() == [1]
    assert ref.leaves(tree, thr).tolist() == leaves
    assert ref.misnamed(tree) == 0


def test_thresholds_a_scan_offers_and_those_it_does_not():
    ref = reference.Follower(X, Y, EDGES, PARAMS)
    # the last real bin of a NaN feature parts the valued from the missing; the
    # model text writes its edge at infinity as 1e300
    assert ref.threshold_bins(tree_of(0, 1e300, 8)).tolist() == [3]
    assert ref.threshold_bins(tree_of(2, 1e300, 8)).tolist() == [0]
    assert ref.threshold_bins(tree_of(1, 0.0, 2)).tolist() == [0]
    assert ref.threshold_bins(tree_of(1, 1e300, 2)).tolist() == [-1]    # no missing type: the last bin closes nothing
    assert ref.threshold_bins(tree_of(0, 0.7, 8)).tolist() == [-1]      # no edge
    # a decision_type that names another missing type than the edges show, or a category
    assert ref.misnamed(tree_of(0, 0.5, 2)) == 1
    assert ref.misnamed(tree_of(1, 0.0, 8)) == 1
    assert ref.misnamed(tree_of(0, 0.5, 9)) == 1
    assert ref.misnamed(tree_of(1, 0.0, 0)) == 0 and ref.misnamed(tree_of(1, 0.0, 2)) == 0


def test_decisions_are_read_from_the_model_text():
    text = ("tree\nversion=v2\n\nTree=0\nnum_leaves=3\nsplit_feature=1 0\n"
            "decision_type=10 8\nleft_child=1 -1\n\n\nTree=1\nnum_leaves=1\nleaf_value=0.5\n\n\n"
            "end of trees\n")
    first, second = reference.decisions(text)
    assert first.tolist() == [10, 8] and second.tolist() == []
    tree = reference.with_decisions({}, first)
    assert tree["default_left"].tolist() == [True, False]
    assert tree["missing_type"].tolist() == [2, 2] and not tree["categorical"].any()


def test_the_look_at_the_edges_counts_the_valued_rows_alone():
    rng = np.random.default_rng(3)
    rows = 40000
    col = rng.standard_normal(rows).astype(np.float32)
    few = rng.choice(np.array([-1.0, 0.0, 2.0], np.float32), rows, p=[0.6, 0.3, 0.1])
    table = np.stack([col, few], axis=1)
    table[rng.random((rows, 2)) < 0.8] = NAN
    valued = np.sort(col[~np.isnan(table[:, 0])])
    edges = [np.append(np.quantile(valued, np.arange(1, 32) / 32), [np.inf, NAN]),
             np.array([-0.5, 1.0, np.inf, NAN])]
    # 32 equal bins of the valued rows read 1 at max_bin 32, whatever share is
    # missing; a bin of one value, however full, is no wider than it has to be
    assert reference.bin_width(table, edges, 32) == pytest.approx(1.0, abs=0.02)
    assert reference.bin_width(table[:, :1], correct.coarser(edges[:1]), 32) == pytest.approx(
        4.0, abs=0.05)
    assert gbdt_binary.Follower(table, np.zeros(rows), edges, dict(PARAMS, max_bin=32)
                                ).bin_width > 0.75 * 32         # the other reference counts the NaN bin
    # two values in one bin: the bin counts
    assert reference.bin_width(table, [edges[0], np.array([1.0, np.inf, NAN])], 32) > 0.85 * 32


def test_work_doubles_the_scan():
    tree = {"num_leaves": 3, "left_child": np.array([-1, -2]), "right_child": np.array([1, -3]),
            "leaf_count": np.array([400, 250, 350]), "internal_count": np.array([1000, 600])}
    config = {"rows": 1000, "features": 7, "params": {"max_bin": 15}}
    plain, own = work.of_config(tree, config), reference.work(tree, config)
    scan = 2 * 2 * 7 * 16 * work.SCAN_OPS_PER_BIN
    assert own["ops"] == plain["ops"] + scan and plain["ops"] - scan == 1650 * 7 * 6
    assert own["bytes"] == plain["bytes"] and own["hist_rows"] == plain["hist_rows"]
    stump = dict(tree, num_leaves=1)
    assert reference.work(stump, config) == work.of_config(stump, config)
    assert work.counter(reference) is reference.work


def test_the_reference_refuses_what_it_does_not_cover():
    with pytest.raises(ValueError, match="lambda_l1"):
        reference.Follower(X, Y, EDGES, dict(PARAMS, lambda_l1=0.5))
    with pytest.raises(ValueError, match="NaN"):
        reference.Follower(X, Y, EDGES, dict(PARAMS, zero_as_missing=True))
    produced = {"text": "tree\n\nend of trees\n", "warm_scores": [], "final_scores": [],
                "iterations_run": 0}
    with pytest.raises(ValueError, match="weight"):
        reference.compare(produced, {"X": X, "y": Y, "weight": Y}, EDGES, PARAMS, [])
