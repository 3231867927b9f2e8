"""The grower's two counters of the default direction (ops/grow.py
COUNTER_NAMES: ``part_rows_missing``, ``splits_default_left``) against counts
made by numpy from the model text and the bins; on a table with no missing
value both read 0 and the trees are the ones the grower made before it
counted them."""
import numpy as np

import lightgbm_tpu as lgb
from lightgbm_tpu.obs import trace
from lightgbm_tpu.ops.grow import COUNTER_NAMES

PARAMS = {"objective": "binary", "num_leaves": 7, "max_bin": 31, "verbosity": -1}
ROUNDS = 3
# the three trees of the table without missing values, as the parent commit of
# the PR that added the counters grew them (the model texts were equal byte
# for byte on the CPU backend)
BEFORE = [
    ([0, 1, 1, 0, 0, 0], [1384, 287, 403, 286, 334, 175, 1131]),
    ([0, 1, 1, 0, 1, 0], [996, 462, 69, 1417, 430, 419, 207]),
    ([0, 1, 1, 0, 0, 0], [1002, 319, 456, 1098, 281, 462, 382]),
]


def table(missing: bool):
    rng = np.random.RandomState(7)
    X = rng.randn(4000, 8).astype(np.float32)
    y = (X[:, 0] * 2 + X[:, 1] - X[:, 2] * X[:, 3] + 0.5 * rng.randn(4000) > 0).astype(np.float32)
    if missing:
        X[rng.rand(4000, 8) < 0.5] = np.nan
    return X, y


def trained(missing: bool):
    """The booster's trees as parsed text fields, the bins and the counters."""
    X, y = table(missing)
    trace.reset()
    ds = lgb.Dataset(X, label=y, params=PARAMS).construct()
    bst = lgb.train(PARAMS, ds, num_boost_round=ROUNDS)
    trees = []
    for section in bst.model_to_string().split("\nTree=")[1:]:
        fields = dict(line.partition("=")[::2] for line in section.splitlines() if "=" in line)
        tree = {k: np.array(fields[k].split(), float).astype(int)
                for k in ("split_feature", "decision_type", "left_child", "right_child",
                          "leaf_count")}
        trees.append(dict(tree, threshold=np.array(fields["threshold"].split(), float)))
    counters = [e["args"] for e in trace.events() if e["name"] == "grow.counters"]
    return trees, ds._binned, counters


def rows_sent_by_default(tree, binned):
    """By numpy: over the tree's splits, the rows of the split node whose bin
    is the split feature's NaN bin; the partition is the model text's own
    (threshold, default direction), and has to give its row counts."""
    bins = np.asarray(binned.bins)
    node_rows = {0: np.arange(bins.shape[1])}
    sent = 0
    for i, f in enumerate(tree["split_feature"]):
        j = list(binned.used_feature_idx).index(f)
        mapper = binned.mappers[j]
        rows = node_rows.pop(i)
        b = bins[j, rows]
        missing = b == mapper.num_bin - 1
        sent += int(missing.sum())
        go_left = np.where(missing, bool(tree["decision_type"][i] & 2),
                           b <= mapper.value_to_bin(tree["threshold"][i]))
        for child, side in ((tree["left_child"][i], go_left), (tree["right_child"][i], ~go_left)):
            if child >= 0:
                node_rows[int(child)] = rows[side]
            else:
                assert len(rows[side]) == tree["leaf_count"][-(child + 1)]
    return sent


def test_the_two_counters_ride_with_the_seven():
    assert COUNTER_NAMES[7:9] == ("part_rows_missing", "splits_default_left")
    assert COUNTER_NAMES[9:] == ("root_rows", "hist_columns")


def test_counters_equal_the_counts_by_numpy_on_a_table_with_missing_values():
    trees, binned, counters = trained(missing=True)
    assert len(trees) == len(counters) == ROUNDS
    for tree, counted in zip(trees, counters):
        nan_typed = (tree["decision_type"] >> 2) & 3 == 2
        assert nan_typed.all()
        assert counted["splits_default_left"] == int(np.sum((tree["decision_type"] & 2) > 0))
        assert 0 < counted["splits_default_left"] < counted["splits"]
        sent = rows_sent_by_default(tree, binned)
        assert counted["part_rows_missing"] == sent
        assert 0 < sent < counted["part_rows_needed"]


def test_a_table_with_no_missing_value_counts_none_and_grows_the_trees_it_grew_before():
    trees, _, counters = trained(missing=False)
    for counted in counters:
        assert counted["part_rows_missing"] == 0 and counted["splits_default_left"] == 0
        assert counted["part_rows_needed"] > 0 and counted["splits"] == 6
    got = [(t["split_feature"].tolist(), t["leaf_count"].tolist()) for t in trees]
    assert got == BEFORE


def test_find_bins_span_says_how_many_features_have_a_nan_bin():
    X, y = table(True)
    X[:, 3] = np.nan_to_num(X[:, 3])                       # one column without a missing value
    trace.reset()
    lgb.Dataset(X, label=y, params=PARAMS).construct()
    (span,) = [e for e in trace.events() if e["name"] == "dataset.find_bins"]
    assert span["args"]["columns"] == 8 and span["args"]["nan_features"] == 7


def test_a_float32_matrix_is_binned_as_its_float64_copy():
    """A NaN table handed over in float32, as the benchmark's generator makes
    it: the same edges, the same bins."""
    X, y = table(True)
    assert X.dtype == np.float32
    trace.reset()
    as32 = lgb.Dataset(X, label=y, params=PARAMS).construct()._binned
    as64 = lgb.Dataset(X.astype(np.float64), label=y, params=PARAMS).construct()._binned
    assert np.array_equal(np.asarray(as32.bins), np.asarray(as64.bins))
    for a, b in zip(as32.mappers, as64.mappers):
        assert np.array_equal(a.bin_upper_bound, b.bin_upper_bound, equal_nan=True)
        assert a.missing_type == b.missing_type
