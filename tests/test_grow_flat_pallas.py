"""The speculative grower's batch under the Pallas histogram (ops/grow.py
``segment_histogram_flat`` over ``hist_pallas.histogram_pallas_slots``): one
flat pass, slot by slot, whose trees are the sequential grower's and whose
rows follow the computing slots, not 8 lanes of the largest one's bucket.
The kernel runs in interpret mode, steered here in the test as
tests/test_pallas_integration.py steers it. Nothing here is a device number.
"""
import functools

import jax
import numpy as np
import pytest

import lightgbm_tpu as lgb
import lightgbm_tpu.ops.grow as grow_mod
import lightgbm_tpu.ops.histogram as hist_mod
from lightgbm_tpu.obs import trace
from lightgbm_tpu.ops import hist_pallas


@pytest.fixture
def pallas_grower(monkeypatch):
    """``set_mode(grow, spec_hist="")``: the cells' histogram impl with the
    kernel interpreted, the grower and the batch's form as asked. All three
    choices are read at import, so they are steered here."""
    calls = {"slots": 0, "one": 0}

    def interpreted(real, key):
        @functools.wraps(real)
        def call(*args, **kwargs):
            calls[key] += 1
            return real(*args, **dict(kwargs, interpret=True))
        return call

    monkeypatch.setattr(hist_mod, "_ENV_IMPL", "pallas")
    monkeypatch.setattr(hist_pallas, "histogram_pallas",
                        interpreted(hist_pallas.histogram_pallas, "one"))
    monkeypatch.setattr(hist_pallas, "histogram_pallas_slots",
                        interpreted(hist_pallas.histogram_pallas_slots, "slots"))

    def set_mode(grow, spec_hist=""):
        monkeypatch.setattr(grow_mod, "_ENV_GROW", grow)
        monkeypatch.setattr(grow_mod, "_ENV_SPEC_HIST", spec_hist)
        jax.clear_caches()
        return calls

    yield set_mode
    monkeypatch.undo()
    jax.clear_caches()


def _table(n=1500, f=10, seed=3):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, f)
    X[:, 3] = rng.randint(0, 8, n)
    X[rng.rand(n, f) < 0.05] = np.nan  # every column has a NaN bin
    y = (X[:, 0] * 2 + np.nan_to_num(X[:, 1] * X[:, 2])
         + 0.3 * rng.randn(n) > 0).astype(float)
    return X, y


def _train(params, X, y, rounds):
    trace.reset()
    bst = lgb.train(dict(params, verbosity=-1), lgb.Dataset(X, label=y), rounds)
    text = bst.model_to_string()  # materialises the trees: one event each
    counted = [e["args"] for e in trace.events() if e["name"] == "grow.counters"]
    return bst, text, counted


def _padded_recount(tree, rows, unit):
    """The root's rows and every split's smaller child padded to ``unit``."""
    def count(child):
        return (tree.leaf_count[-(child + 1)] if child < 0
                else tree.internal_count[child])

    splits = tree.num_leaves - 1
    return rows + sum(
        -(-min(count(int(l)), count(int(r))) // unit) * unit
        for l, r in zip(tree.left_child[:splits], tree.right_child[:splits]))


BINARY = dict(objective="binary", num_leaves=15, max_bin=63, min_data_in_leaf=5)


@pytest.mark.parametrize("hist_dtype", ["float32", "bfloat16"])
def test_flat_engages_and_grows_the_sequential_trees(pallas_grower, hist_dtype):
    X, y = _table()
    params = dict(BINARY, tpu_hist_dtype=hist_dtype)
    pallas_grower("seq")
    _, reference, _ = _train(params, X, y, 3)
    assert grow_mod._LAST_GROW_MODE == "seq"

    calls = pallas_grower("spec")
    bst, flat_text, flat = _train(params, X, y, 3)
    assert (grow_mod._LAST_GROW_MODE, grow_mod._LAST_SPEC_HIST) == ("spec", "flat")
    assert calls["slots"] > 0  # the grouped entry, not the one-hot scan
    assert flat_text == reference

    # at this size every branch's chunk is 512 rows, so what a tree streams
    # is its root and each computed slot's segment padded to 512: from the
    # tree alone where no computed slot went unapplied, at least that else
    assert {c for _, c in grow_mod.flat_branches(len(X), 8)} == {512}
    for c, tree in zip(flat, bst._gbdt.trees()):
        want = _padded_recount(tree, len(X), 512)
        assert (c["hist_rows_streamed"] - len(X)) % 512 == 0
        assert c["hist_rows_streamed"] >= want
        if c["slots_computed"] == c["splits"]:
            assert c["hist_rows_streamed"] == want

    if hist_dtype == "bfloat16":
        return
    pallas_grower("spec", "lanes")
    _, lanes_text, lanes = _train(params, X, y, 3)
    assert grow_mod._LAST_SPEC_HIST == "lanes"
    assert lanes_text == reference
    for f, l in zip(flat, lanes):
        assert f["hist_rows_needed"] == l["hist_rows_needed"]
        assert f["steps"] == l["steps"]
        assert (f["hist_rows_streamed"] / f["hist_rows_needed"]
                < l["hist_rows_streamed"] / l["hist_rows_needed"])


def test_a_goss_training_goes_through_the_flat_pass(pallas_grower):
    """Rooted at its sample: the root segment takes the one-slot pass at
    ``root_sizes``, the batch the flat one, over the in-bag rows alone."""
    X, y = _table(n=2000)
    params = dict(BINARY, boosting="goss", learning_rate=0.5, top_rate=0.3,
                  other_rate=0.2, seed=5)
    pallas_grower("seq")
    _, reference, _ = _train(params, X, y, 4)
    pallas_grower("spec")
    _, text, counted = _train(params, X, y, 4)
    assert grow_mod._LAST_SPEC_HIST == "flat"
    assert text == reference
    assert [c["root_rows"] for c in counted] == [2000, 2000, 1000, 1000]
    for c in counted[2:]:  # the root's one slot at the table's rows, then chunks
        assert c["hist_rows_streamed"] >= c["hist_rows_needed"]
        assert (c["hist_rows_streamed"] - 2000) % 512 == 0


def test_the_chunk_follows_the_branch_and_the_branches_do_not_grow():
    # the kernel's cap in whole groups of its loop (no tail to lower)
    group = hist_pallas._UNROLL * hist_pallas.SUB
    cap = hist_pallas._max_chunk_for("pallas") // group * group
    for n in (5000, 60_000, 200_000, 750_000, 1_000_000):
        branches = grow_mod.flat_branches(n, 8)
        assert len(branches) <= len(grow_mod.bucket_sizes(n))
        chunks = [c for _, c in branches]
        assert chunks == sorted(chunks) and chunks[0] == 512 and chunks[-1] <= cap
        assert all(c % 512 == 0 and rows % c == 0 for rows, c in branches)
        assert all(c <= group or c % group == 0 for c in chunks)
        # the last branch holds any batch: every row and a chunk a slot
        assert branches[-1][0] >= n + 8 * branches[-1][1]
    # what the chip's readings of whole batches put first (PERF.md, PR 34)
    assert grow_mod.flat_chunk(8192, 8) == 1024
    assert grow_mod.flat_chunk(131072, 8) == 4096
    assert grow_mod.flat_chunk(393216, 8) == 6144
    assert grow_mod.flat_chunk(10 ** 9, 8) == cap
