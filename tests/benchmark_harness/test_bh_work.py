"""The work counter behind ``step.mfu_pct`` against a hand count, the model
text reader, and the table of peaks."""
import pytest

from benchmarks import model_text, peaks, work

# Three splits over 1000 rows:  node0 -> (node1, leaf1[400]);
# node1 -> (leaf0[350], node2[250]);  node2 -> (leaf2[100], leaf3[150])
TEXT = """tree
version=v2
num_class=1

Tree=0
num_leaves=4
num_cat=0
split_feature=2 0 1
split_gain=30 20 10
threshold=0.5 -1.25 3
decision_type=2 2 2
left_child=1 -1 -3
right_child=-2 2 -4
leaf_value=0.1 -0.2 0.3 0.05
leaf_count=350 400 100 150
internal_value=0 0.01 0.02
internal_count=1000 600 250
shrinkage=0.1


Tree=1
num_leaves=1
num_cat=0
leaf_value=0.5
shrinkage=1


end of trees

feature importances:
"""


def test_model_text_reader():
    trees = model_text.parse_trees(TEXT)
    assert [t["num_leaves"] for t in trees] == [4, 1]
    t = trees[0]
    assert t["split_feature"].tolist() == [2, 0, 1]
    assert t["threshold"].tolist() == [0.5, -1.25, 3.0]
    assert t["left_child"].tolist() == [1, -1, -3]
    assert t["leaf_count"].tolist() == [350, 400, 100, 150]
    assert t["internal_count"].tolist() == [1000, 600, 250]
    assert t["shrinkage"] == 0.1
    assert trees[1]["leaf_value"].tolist() == [0.5] and len(trees[1]["left_child"]) == 0


def test_work_of_a_three_split_tree_by_hand():
    tree = model_text.parse_trees(TEXT)[0]
    # smaller children: min(600, 400) + min(350, 250) + min(100, 150)
    assert work.smaller_child_rows(tree) == 400 + 250 + 100
    N, F, B = 1000, 28, 256
    w = work.tree_work(tree, N, F, B)
    hist_rows = 1000 + 750
    assert w["hist_rows"] == hist_rows
    assert w["bytes"] == hist_rows * (28 + 12) + 1000 * (28 + 8)
    assert w["ops"] == hist_rows * 28 * 3 * 2 + 3 * 2 * 28 * 256 * 20


def test_a_tree_that_did_not_split_needs_no_work():
    stump = model_text.parse_trees(TEXT)[1]
    assert work.tree_work(stump, 1000, 28, 256) == {"bytes": 0.0, "ops": 0.0, "hist_rows": 0.0}


def test_least_seconds_names_its_bound():
    tree = model_text.parse_trees(TEXT)[0]
    peak = peaks.peaks("TPU v5 lite")
    least = work.least_seconds([tree, tree], 1000, 28, 256, peak)
    one = work.tree_work(tree, 1000, 28, 256)
    assert least["bytes"] == 2 * one["bytes"] and least["ops"] == 2 * one["ops"]
    assert least["seconds"] == max(least["bytes"] / 819e9, least["ops"] / 197e12)
    assert least["bound"] == ("bytes" if least["bytes"] / 819e9 >= least["ops"] / 197e12
                              else "operations")
    fast_memory = {"flops": 1.0, "bytes_per_s": 1e30}
    assert work.least_seconds([tree], 1000, 28, 256, fast_memory)["bound"] == "operations"


@pytest.mark.parametrize("kind", ["TPU v5 lite", "TPU v5e", "tpu_v5_lite"])
def test_v5e_peaks(kind):
    assert peaks.peaks(kind) == {"flops": 197e12, "bytes_per_s": 819e9, "hbm_bytes": 16e9}


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", "TPU v5p", ""])
def test_a_device_not_in_the_table_is_an_error(kind):
    with pytest.raises(KeyError):
        peaks.peaks(kind)
