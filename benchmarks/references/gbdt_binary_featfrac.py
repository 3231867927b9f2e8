"""The plain reference of binary boosting under column sampling
(``feature_fraction``).

``gbdt_binary`` with upstream's column sampling (``docs/Parameters.rst``:
``feature_fraction``, aliases ``sub_feature``, ``colsample_bytree``;
``src/treelearner/serial_tree_learner.cpp`` ``BeforeTrain``): before each tree
``k = int(F x feature_fraction)`` of the table's ``F`` columns are drawn, the
tree's histograms are built over those alone and no split of it names another.
Rows, gradients, leaf values and the score update are full-row boosting's.

Like ``gbdt_binary`` it imports nothing of the program, is numpy and float64
throughout, and *follows* the program's trees (teacher forcing). A draw is
random, so it cannot be recomputed: every tree's draw is taken from the
program (``collect``: the drawn columns of the table, by tree) and **held to
the law** (``draw_law``): exactly ``k`` distinct columns of the table in rising
order, no split of the tree on a column out of its draw, no two consecutive
trees on one draw (``draw_mismatch``), and the drawn columns pooled over the
run uniform over ``[0, F)`` (``draw_ks``). It grows nothing itself: every
tree is followed by its sums as ``gbdt_binary`` follows it (the partition by
the tree's own thresholds, every node's count, every leaf's value, its own
score update), and on the trees followed by their histograms every
candidate's gain **over the tree's drawn columns alone**: the best any open
leaf offered is the best among the draw, which is what upstream's learner
searches. A split on a column out of its draw was offered by nobody: its
``split_gap`` reads inf, and it counts in ``exact_mismatch`` too.

A benchmark run and ``readings`` always hand the draws over (``run.drive``
calls ``collect``), and a tree without one is then a mismatch. A caller that
drives the program without ``collect`` (``produced["collected"]`` is None: the
harness's own test of the control does) leaves the reference no draw to
search: every tree is then held to what needs none (thresholds, every node's
count, every leaf's value, the score update, the loss), no tree's candidates
are searched, and the comparison says so in its log.

Departures from upstream, which the program shares: upstream draws among the
columns that are not trivial (``valid_feature_indices_``), each rank of a
distributed run from its own stream; here one host stream
(``feature_fraction_seed``) draws over all ``F`` columns the table is trained
on. Upstream's ``used_feature_cnt`` is at least 1, as the program's is; at any
size a cell runs that changes nothing.
"""
from __future__ import annotations

import time
from typing import Callable, Dict, List, Optional, Sequence

import numpy as np

from benchmarks import correct, model_text, work as work_mod
from benchmarks.references import gbdt_binary as plain

NUMBERS = {
    "exact_mismatch": {"limit": "exact", "what": (
        "over every tree of the run: nodes whose row count differs from the reference's "
        "partition by the tree's own thresholds, thresholds that are no bin edge, splits on "
        "a column out of the tree's draw, and trees missing or beyond the iterations run")},
    "split_gap": {"limit": "gap", "what": (
        "widest, over the splits of the trees followed by histograms, share by which the gain "
        "of the program's split lies below the best gain any open leaf offered over the "
        "tree's drawn columns alone, both by the reference's float64 histograms")},
    "leaf_value_gap": dict(plain.NUMBERS["leaf_value_gap"]),
    "loss_gap": dict(plain.NUMBERS["loss_gap"]),
    "score_gap": dict(plain.NUMBERS["score_gap"]),
    "bin_width": dict(plain.NUMBERS["bin_width"]),
    "draw_mismatch": {"limit": "exact", "what": (
        "over every tree of the run, what breaks the law of the draw: a tree without a draw "
        "or a draw without its tree, columns more or fewer than int(F x feature_fraction), a "
        "column out of [0, F), a column that does not rise over the one before it (repeated "
        "or out of order), a split on a column out of its tree's draw, and a tree whose draw "
        "is the draw of the tree before it")},
    "draw_ks": {"limit": "gap", "what": (
        "Kolmogorov distance between the drawn columns pooled over the run and the uniform "
        "law over [0, F): uniform draws of 1600 of 2000 columns over 30 trees stay under "
        "0.01, the first 1600 columns every tree read 0.2")},
}


def collect(booster, dataset) -> List[dict]:
    """Every drawn tree's draw, from the program's public record of them:
    ``tree``, ``iteration`` and ``columns`` (the drawn columns of the table)."""
    return booster.feature_draws()


def drawn_count(features: int, params: dict) -> int:
    """How many columns a tree draws."""
    return max(1, int(features * float(params["feature_fraction"])))


def uniform_distance(columns: np.ndarray, features: int) -> float:
    """sup |F_columns - F_uniform| over the columns 0 .. features - 1."""
    if not len(columns):
        return 1.0
    inside = columns[(columns >= 0) & (columns < features)]
    share = np.cumsum(np.bincount(inside, minlength=features)) / len(columns)
    return float(np.max(np.abs(share - np.arange(1, features + 1) / features)))


def draw_law(draws: Dict[int, np.ndarray], trees: Sequence[dict], features: int,
             params: dict) -> Dict[str, float]:
    """The run's draws held to the law (``NUMBERS``'s ``draw_mismatch`` and
    ``draw_ks``); ``outside`` is, tree by tree, how many splits name a column
    out of the tree's draw (every split of a tree that has none)."""
    k = drawn_count(features, params)
    wrong = len([t for t in draws if t >= len(trees)])
    outside = []
    before = None
    for t, tree in enumerate(trees):
        splits = tree["split_feature"][: max(int(tree["num_leaves"]) - 1, 0)]
        cols = draws.get(t)
        if cols is None:
            wrong += 1
            outside.append(len(splits))
            before = None
            continue
        cols = np.asarray(cols, np.int64)
        wrong += abs(len(cols) - k)
        wrong += int(np.sum((cols < 0) | (cols >= features)))
        wrong += int(np.sum(np.diff(cols) <= 0))
        wrong += int(before is not None and np.array_equal(before, cols))
        outside.append(int(np.sum(~np.isin(splits, cols))))
        before = cols
    pooled = np.concatenate([np.asarray(draws[t], np.int64) for t in sorted(draws)]
                            or [np.zeros(0, np.int64)])
    return {"mismatch": float(wrong + sum(outside)), "outside": outside,
            "ks": uniform_distance(pooled, features) if draws else 0.0}


class _OnColumns(plain.Follower):
    """A follower's view of some of its columns: the rows, scores, leaves and
    thresholds of the whole table, the histograms of these columns alone."""

    def __init__(self, whole: plain.Follower, cols: np.ndarray, leaf: np.ndarray,
                 thr_bin: np.ndarray) -> None:
        self.__dict__.update(whole.__dict__)
        self.bins, self.num_bin, self.features = whole.bins[cols], whole.num_bin[cols], len(cols)
        self._leaf, self._thr_bin = leaf, thr_bin

    def threshold_bins(self, tree: dict) -> np.ndarray:
        return self._thr_bin

    def leaves(self, tree: dict, thr_bin: np.ndarray) -> np.ndarray:
        return self._leaf


class Follower(plain.Follower):
    """``gbdt_binary``'s follower, a tree's candidates searched over the
    columns of its draw alone."""

    def follow(self, tree: dict, is_first: bool, histograms: bool = True,
               operand_dtype: Optional[str] = None, workers: int = 8,
               draw: Optional[np.ndarray] = None) -> Dict[str, object]:
        """``gbdt_binary.Follower.follow``, the histograms over the columns of
        ``draw`` (every column where it is None). A split on a column out of
        the draw is one nobody offered: its ``split_gap`` is inf."""
        if draw is None or not histograms:
            return super().follow(tree, is_first, histograms, operand_dtype, workers)
        cols = np.asarray(draw, np.int64)
        cols = np.unique(cols[(cols >= 0) & (cols < self.features)])
        thr_bin = self.threshold_bins(tree)
        leaf = self.leaves(tree, np.maximum(thr_bin, 0))
        at = np.minimum(np.searchsorted(cols, tree["split_feature"]), len(cols) - 1)
        inside = np.where(cols[at] == tree["split_feature"], at, -1)
        view = _OnColumns(self, cols, leaf, thr_bin)
        return plain.Follower.follow(view, dict(tree, split_feature=inside), is_first, True,
                                     operand_dtype, workers)


def compare(produced: dict, data: dict, edges: Sequence[np.ndarray], params: dict,
            follow: Sequence[int], control_dtype: Optional[str] = None,
            log: Callable[[str], None] = lambda msg: None) -> Dict[str, Dict[str, float]]:
    """``{"program": {number: value}, "control": {...}}`` (``NUMBERS``), as
    ``gbdt_binary.compare`` gives them, each tree's candidates over the columns
    of its draw (``produced["collected"]``: what ``collect`` took)."""
    text, warm_scores = produced["text"], produced["warm_scores"]
    final_scores, iterations_run = produced["final_scores"], produced["iterations_run"]
    X, y = data["X"], data["y"]
    extras, per_iteration = sorted(set(data) - {"X", "y"}), model_text.trees_per_iteration(text)
    if extras or per_iteration != 1:
        raise ValueError("the reference covers one tree an iteration; this run has %d and "
                         "the extras %s" % (per_iteration, extras))
    if not 0.0 < float(params.get("feature_fraction", 1.0)) < 1.0:
        raise ValueError("the reference covers feature_fraction inside (0, 1) alone")
    unseen = produced.get("collected") is None      # driven without ``collect``
    draws = {int(d["tree"]): np.asarray(d["columns"])
             for d in produced.get("collected") or []}
    trees = model_text.parse_trees(text)
    t0 = time.perf_counter()
    ref = Follower(X, y, edges, params)
    log("reference: rows binned in %.1fs" % (time.perf_counter() - t0))

    law = {"mismatch": 0.0, "ks": 0.0, "outside": [0] * iterations_run} if unseen else \
        draw_law(draws, trees[:iterations_run], ref.features, params)
    mismatch = abs(len(trees) - iterations_run) + sum(law["outside"])
    split_gap = leaf_gap = loss_gap = 0.0
    c_split = c_leaf = c_loss = 0.0
    loss_prev_p = loss_prev_r = plain.logloss(
        np.full(1, ref.init_score), np.array([np.mean(y, dtype=np.float64)]))
    for t, tree in enumerate(trees[:iterations_run]):
        if int(tree["num_leaves"]) < 2:
            mismatch += 1
            ref.add_programs(None, tree["leaf_value"])
            continue
        full = t in follow
        searched = full and t in draws
        f = ref.follow(tree, t == 0, histograms=searched,
                       operand_dtype=control_dtype if full else None, draw=draws.get(t))
        mismatch += int(np.sum(f["thr_bin"] < 0))
        mismatch += int(np.sum(f["leaf_count"] != tree["leaf_count"]))
        mismatch += int(np.sum(f["internal_count"] != tree["internal_count"]))
        leaf_gap = max(leaf_gap, plain._leaf_gap(tree["leaf_value"], f["leaf_values"]))
        if searched:
            split_gap = max(split_gap, float(np.max(f["split_gap"])))
        first = ref.init_score if t == 0 else 0.0
        c = f.get("control")
        if c is not None:
            if "split_gap" in c:
                c_split = max(c_split, float(np.max(c["split_gap"])))
            c_leaf = max(c_leaf, plain._leaf_gap(c["leaf_values"], f["leaf_values"]))
            loss_c = plain.logloss(ref.moved(f["leaf"], c["leaf_values"] - first), y)
            before = plain.logloss(ref.scores, y)
        ref.advance(f["leaf"], f["leaf_values"] - first)
        ref.add_programs(f["leaf"], tree["leaf_value"])
        if c is not None:
            step = plain.logloss(ref.scores, y) - before
            c_loss = max(c_loss, abs((loss_c - before) - step) / abs(step))
        seen = (warm_scores[t] if t < len(warm_scores)
                else final_scores if t == iterations_run - 1 else None)
        if seen is not None:
            loss_r = plain.logloss(ref.scores, y)
            loss_p = plain.logloss(np.asarray(seen).reshape(-1), y)
            step_r = loss_r - loss_prev_r
            loss_gap = max(loss_gap, abs((loss_p - loss_prev_p) - step_r) / abs(step_r))
            loss_prev_p, loss_prev_r = loss_p, loss_r
        if searched:
            log("reference: tree %d followed by its histograms over %d drawn columns by %.1fs"
                % (t, len(draws[t]), time.perf_counter() - t0))

    moved = np.sqrt(np.mean((ref.applied.astype(np.float64) - ref.init_score) ** 2))
    score_gap = float(np.max(np.abs(
        np.asarray(final_scores, np.float64).reshape(-1) - ref.applied)) / moved)
    log("reference: %d trees followed, %d of them with a draw of %d columns of %d, by %.1fs"
        % (len(trees), len(draws), drawn_count(ref.features, params), ref.features,
           time.perf_counter() - t0))
    if unseen:
        log("reference: the draws were not collected: every tree was held to its thresholds, "
            "its nodes' counts, its leaves' values and the score update alone, and no tree's "
            "candidates were searched")
    out = {"program": {"exact_mismatch": float(mismatch), "split_gap": split_gap,
                       "leaf_value_gap": leaf_gap, "loss_gap": loss_gap,
                       "score_gap": score_gap, "bin_width": float(ref.bin_width),
                       "draw_mismatch": law["mismatch"], "draw_ks": law["ks"]}}
    if control_dtype is not None:
        wide = plain.Follower(X[:plain.CONTROL_BIN_ROWS], y[:plain.CONTROL_BIN_ROWS],
                              correct.coarser(edges), params)
        out["control"] = {"split_gap": c_split, "leaf_value_gap": c_leaf,
                          "loss_gap": c_loss, "bin_width": float(wide.bin_width)}
    return out


def work(tree: Dict[str, np.ndarray], config: dict) -> Dict[str, float]:
    """``work.tree_work`` of the tree at the drawn width (its histograms and
    scans run over the draw's columns alone) and, a tree, one read and one
    write of the drawn columns of every row: the gather that hands them over."""
    drawn = drawn_count(config["features"], config["params"])
    out = work_mod.tree_work(tree, drawn, int(config["params"]["max_bin"]) + 1)
    if int(tree["num_leaves"]) > 1:
        out["bytes"] += 2.0 * config["rows"] * drawn
    return out
