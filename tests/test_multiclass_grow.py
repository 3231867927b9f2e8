"""K trees an iteration through the serial grower (``models/gbdt.py``
``train_one_iter``'s class loop), held against a float64 softmax follower
written here from the mathematics alone: each iteration's gradients of every
class from the follower's own scores before any tree, tree ``i K + k`` on
class ``k``'s, moving class ``k`` alone. The follower takes the program's
trees (teacher forcing) and routes the rows by their raw values against the
trees' thresholds. Also the class loop's trace: one ``class.counters`` event
an iteration and one ``train.class_tree`` span a class, none of either on one
tree an iteration. Nothing here is a device number."""
import numpy as np
import pytest

import lightgbm_tpu as lgb
import lightgbm_tpu.ops.grow as grow_mod
from benchmarks import model_text
from lightgbm_tpu.obs import trace

K, F, ROUNDS = 5, 12, 4
PARAMS = {"objective": "multiclass", "num_class": K, "num_leaves": 15, "learning_rate": 0.3,
          "min_data_in_leaf": 5, "min_sum_hessian_in_leaf": 1.0, "verbosity": -1}


def _table(n=3000, classes=K, seed=0):
    """float32 values (as the program bins them) and a label from five noisy
    linear scores."""
    rng = np.random.RandomState(seed)
    X = rng.randn(n, F).astype(np.float32).astype(np.float64)
    W = rng.randn(classes, F)
    y = np.argmax(X @ W.T + 0.7 * rng.randn(n, classes), axis=1)
    return X, y.astype(float)


def _train(X, y, params=PARAMS, rounds=ROUNDS):
    trace.reset()
    bst = lgb.train(params, lgb.Dataset(X, label=y), rounds)
    return bst, bst.model_to_string()


def _leaves(tree, X):
    """The leaf of every row, by raw value against the tree's thresholds."""
    node = np.zeros(len(X), np.int64)
    live = np.arange(len(X))
    while live.size:
        at = node[live]
        go_left = X[live, tree["split_feature"][at]] <= tree["threshold"][at]
        nxt = np.where(go_left, tree["left_child"][at], tree["right_child"][at])
        node[live] = nxt
        live = live[nxt >= 0]
    return -(node + 1)


def _follow(text, X, y, rate):
    """What a float64 softmax booster makes of the program's trees: every
    tree's node counts and leaf values, and the scores at the end."""
    trees = model_text.parse_trees(text)
    label = y.astype(np.int64)
    n = len(y)
    init = np.log(np.maximum(1e-15, np.bincount(label, minlength=K) / n))
    scores = np.repeat(init[:, None], n, axis=1)
    onehot = np.eye(K)[label].T
    out = []
    for i in range(len(trees) // K):
        p = np.exp(scores - scores.max(axis=0))
        p /= p.sum(axis=0)
        grad, hess = p - onehot, 2.0 * p * (1.0 - p)      # every class, before any tree
        for k in range(K):
            tree = trees[i * K + k]
            L = tree["num_leaves"]
            leaf = _leaves(tree, X)
            G = np.bincount(leaf, weights=grad[k], minlength=L)
            H = np.bincount(leaf, weights=hess[k], minlength=L)
            values = -G / H * rate
            scores[k] += values[leaf]
            out.append({"leaf_count": np.bincount(leaf, minlength=L),
                        "leaf_value": values + (init[k] if i == 0 else 0.0)})
    return trees, out, scores


@pytest.fixture(params=["seq", "spec"])
def grow_mode(request, monkeypatch):
    """The sequential grower (the CPU's default) and the speculative one (the
    chip's) on the scatter histogram."""
    import jax

    monkeypatch.setattr(grow_mod, "_ENV_GROW", request.param)
    jax.clear_caches()
    yield request.param
    monkeypatch.undo()
    jax.clear_caches()


def test_five_classes_agree_with_a_float64_softmax_follower(grow_mode):
    X, y = _table()
    bst, text = _train(X, y)
    assert grow_mod._LAST_GROW_MODE == grow_mode
    assert model_text.trees_per_iteration(text) == K
    trees, followed, scores = _follow(text, X, y, PARAMS["learning_rate"])
    assert len(trees) == K * ROUNDS
    for t, (tree, f) in enumerate(zip(trees, followed)):
        assert tree["num_leaves"] == PARAMS["num_leaves"], t
        assert np.array_equal(tree["leaf_count"], f["leaf_count"]), t
        # every internal node's count is the sum of its subtree's leaves'
        assert tree["internal_count"][0] == len(y)
        scale = np.maximum(np.abs(f["leaf_value"]), np.median(np.abs(f["leaf_value"])))
        assert np.max(np.abs(tree["leaf_value"] - f["leaf_value"]) / scale) < 1e-4, t
    raw = bst.predict(X, raw_score=True)
    assert raw.shape == (len(y), K)
    assert np.max(np.abs(raw.T - scores)) < 1e-4 * np.max(np.abs(scores))
    assert np.max(np.abs(np.asarray(bst._gbdt.scores) - scores)) < 1e-4 * np.max(np.abs(scores))


def test_a_tree_in_another_class_s_place_is_seen_by_the_follower():
    """The follower is not blind to the class loop: two classes' trees of one
    iteration swapped in the model text break their leaf values."""
    X, y = _table()
    _, text = _train(X, y)
    sections = text.split("\nTree=")
    head, body = sections[0], sections[1:]
    body[K + 1], body[K + 2] = body[K + 2], body[K + 1]        # iteration 1, classes 1 and 2
    swapped = "\nTree=".join([head] + body)
    trees, followed, _ = _follow(swapped, X, y, PARAMS["learning_rate"])
    worst = max(np.max(np.abs(t["leaf_value"] - f["leaf_value"]) / np.abs(f["leaf_value"]).max())
                for t, f in zip(trees, followed))
    assert worst > 1e-2


def test_the_class_loop_notes_each_iteration_once_and_each_class_s_tree():
    X, y = _table()
    _train(X, y)
    events = trace.events()
    noted = [e["args"] for e in events if e["name"] == "class.counters"]
    assert [(c["iteration"], c["classes"], c["trees"], c["grower_calls"]) for c in noted] == [
        (i, K, K, K) for i in range(ROUNDS)]
    spans = [e for e in events if e["name"] == "train.class_tree"]
    assert [(s["args"]["iteration"], s["args"]["class_id"]) for s in spans] == [
        (i, k) for i in range(ROUNDS) for k in range(K)]
    by_id = {e["id"]: e for e in events}
    assert all(by_id[s["parent"]]["name"] == "train.iteration" for s in spans)
    # each class's grow and finish dispatch lie in its span
    inside = {e["name"] for e in events if e.get("parent") in {s["id"] for s in spans}}
    assert {"tree growth", "renew+score update"} <= inside


@pytest.mark.parametrize("params", [{"objective": "binary"}, {"objective": "regression"}],
                         ids=["binary", "regression"])
def test_one_tree_an_iteration_notes_no_class_loop(params):
    X, y = _table(classes=2)
    _train(X, y, dict({"num_leaves": 7, "verbosity": -1}, **params))
    names = {e["name"] for e in trace.events()}
    assert "train.iteration" in names
    assert not names & {"class.counters", "train.class_tree"}
