"""The plain reference of multi-class softmax boosting (``objective =
multiclass``).

``gbdt_binary`` with the mathematics of upstream's v2.2.4
(``src/objective/multiclass_objective.hpp`` ``MulticlassSoftmax``,
``src/boosting/gbdt.cpp`` ``TrainOneIter`` and ``BoostFromAverage``), K
classes and K trees an iteration:

- class k starts at ``s_k = log(max(eps, pi_k))``, ``pi_k`` its share of the
  labels; the first iteration's tree of class k carries it in its leaves;
- each iteration takes ``p = softmax_k(s)`` of every row once and from it
  ``g_k = p_k - [y = k]`` and ``h_k = 2 p_k (1 - p_k)`` of every class, before
  any tree of the iteration is grown;
- tree ``t K + k`` is class k's: grown on ``(g_k, h_k)``, its leaf value
  ``-lr G / (H + lambda_l2)``, it moves ``s_k`` alone;
- the loss is the mean over rows of ``-log p_y``.

Like ``gbdt_binary`` it imports nothing of the program, is numpy and float64
throughout, and *follows* the program (teacher forcing): every tree of the run
by its sums (the partition by the tree's own thresholds, every node's count,
every leaf's value from its class's gradients, its own update of that class's
scores), the trees of the followed iterations by their histograms too, each
against its own class's gradients. Every number reads over all K trees of an
iteration: a tree moved into another class's place, or grown on gradients
that are not its class's or not the iteration's, breaks the leaf values of
the class whose place it holds. It needs nothing of the program but what a
run produces.
"""
from __future__ import annotations

import contextlib
import time
from typing import Callable, Dict, Optional, Sequence, Tuple

import numpy as np

from benchmarks import correct, model_text, work as work_mod
from benchmarks.follow_pool import FollowPool
from benchmarks.references import gbdt_binary as plain

K_EPSILON = 1e-15
# a tree's share of the iteration's softmax pass: the K float32 scores of a
# row read and its K gradients and K hessians written, a K-th of it a tree
SOFTMAX_BYTES_PER_ROW = 4 + 2 * 4

NUMBERS = {
    "exact_mismatch": {"limit": "exact", "what": (
        "over every tree of the run, K an iteration: nodes whose row count differs from the "
        "reference's partition by the tree's own thresholds, thresholds that are no bin edge, "
        "and trees missing or beyond K times the iterations run")},
    "split_gap": {"limit": "gap", "what": (
        "widest, over the splits of the trees followed by histograms (every class's tree of "
        "the followed iterations), share by which the gain of the program's split lies below "
        "the best gain any open leaf offered, by the reference's float64 histograms of the "
        "tree's own class's gradients")},
    "leaf_value_gap": {"limit": "gap", "what": (
        "worst leaf of every tree of the run: |program - reference| over the larger of the "
        "reference's value and its median leaf's, the reference's from the gradients of the "
        "tree's class by the softmax of its own scores at the start of the iteration")},
    "loss_gap": {"limit": "gap", "what": (
        "worst of each warm-up iteration and of the rest of the run taken together: gap "
        "between the program's and the reference's change of the training multi-class "
        "log-loss (the mean of -log p_y), over the reference's change; an iteration that "
        "leaves the scores as they were reads 1")},
    "score_gap": {"limit": "gap", "what": (
        "worst row and class when the run stopped: |the program's [K, N] score - the "
        "program's own leaf values added up, class by class, over the reference's partition| "
        "over the root mean square the trees moved the scores by")},
    "bin_width": dict(plain.NUMBERS["bin_width"]),
}


def softmax(scores: np.ndarray) -> np.ndarray:
    """[K, N] float64: each row's class probabilities."""
    e = np.exp(scores - scores.max(axis=0, keepdims=True))
    return e / e.sum(axis=0, keepdims=True)


def logloss(scores: np.ndarray, label: np.ndarray) -> float:
    """Mean multi-class log-loss of [K, N] raw scores, float64."""
    s = np.asarray(scores, np.float64)
    top = s.max(axis=0)
    lse = top + np.log(np.exp(s - top).sum(axis=0))
    return float(np.mean(lse - s[label, np.arange(s.shape[1])]))


def gradients(scores: np.ndarray, label: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """[K, N] gradient and hessian of every class: ``p - [y = k]`` and
    ``2 p (1 - p)``."""
    p = softmax(scores)
    onehot = np.zeros_like(p)
    onehot[label, np.arange(p.shape[1])] = 1.0
    return p - onehot, 2.0 * p * (1.0 - p)


class Follower(plain.Follower):
    """``gbdt_binary``'s follower of K classes: [K, N] scores, and a tree
    followed against the gradients it is handed."""

    def __init__(self, X: np.ndarray, y: np.ndarray, edges: Sequence[np.ndarray],
                 params: dict, classes: int) -> None:
        # the binary follower's binning and its look at the edges, handed
        # neutral labels: its one score a row is replaced below
        super().__init__(X, np.full(len(y), 0.5), edges, params)
        self.label = np.asarray(y).astype(np.int64)
        if len(self.label) and (self.label.min() < 0 or self.label.max() >= classes):
            raise ValueError("labels outside [0, %d)" % classes)
        prior = np.bincount(self.label, minlength=classes) / max(self.rows, 1)
        self.init_scores = np.log(np.maximum(K_EPSILON, prior))
        self.scores = np.repeat(self.init_scores[:, None], self.rows, axis=1)
        self.applied = np.zeros((classes, self.rows), np.float32)

    def follow_class(self, tree: dict, k: int, grad: np.ndarray, hess: np.ndarray,
                     is_first: bool, pool: Optional[FollowPool] = None,
                     operand_dtype: Optional[str] = None) -> Dict[str, object]:
        """``gbdt_binary.Follower.follow`` of class ``k``'s tree on its
        gradient and hessian: the same keys, from these weights and, on the
        first iteration, the class's own starting score. With a ``pool``
        (this follower's) also by its histograms."""
        L = int(tree["num_leaves"])
        M = L - 1
        thr_bin = self.threshold_bins(tree)
        leaf = self.leaves(tree, np.maximum(thr_bin, 0))
        weights = [grad, hess]
        if operand_dtype is not None:
            weights += [plain.rounded(w, operand_dtype) for w in weights]
        kids = plain.node_order(tree)
        total = self._totals(leaf, L, kids, weights)
        l2 = float(self.params.get("lambda_l2", 0.0))
        rate = float(self.params["learning_rate"])
        first = self.init_scores[k] if is_first else 0.0

        def leaf_values(t):
            return -t[M:, 0] / (t[M:, 1] + l2) * rate + first

        out = {
            "leaf": leaf, "thr_bin": thr_bin,
            "leaf_values": leaf_values(total[0]),
            "leaf_count": np.rint(total[0, M:, 2]).astype(np.int64),
            "internal_count": np.rint(total[0, :M, 2]).astype(np.int64),
        }
        if operand_dtype is not None:
            out["control"] = {"leaf_values": leaf_values(total[1])}
        if pool is None:
            return out

        blocks = pool.blocks(tree, kids, leaf * self.width, weights, thr_bin)
        best = np.max([b["best"] for b in blocks], axis=0)
        chosen = np.full(M, -np.inf)
        for b in blocks:
            chosen[b["chosen"][0]] = b["chosen"][1]
        # the nodes that were leaves when split i was made: made by an
        # earlier split (or the root) and not yet split themselves
        made_by = np.full(2 * L - 1, -1, np.int64)
        for i in range(M):
            made_by[kids[i]] = i
        split_at = np.concatenate([np.arange(M), np.full(L, M + 1)])
        frontier_best = np.array([best[(made_by < i) & (split_at >= i)].max()
                                  for i in range(M)])
        with np.errstate(invalid="ignore", divide="ignore"):
            out["split_gap"] = (frontier_best - chosen) / frontier_best
        if operand_dtype is not None:
            which = np.argmax([b["low_best"] for b in blocks], axis=0)
            picked = np.array([blocks[w]["low_picked"][i] for i, w in enumerate(which)])
            with np.errstate(invalid="ignore", divide="ignore"):
                out["control"]["split_gap"] = (frontier_best - picked) / frontier_best
        return out

    def moved(self, k: int, leaf: np.ndarray, values: np.ndarray) -> np.ndarray:
        """The reference's scores with ``values`` added to class ``k``'s by leaf."""
        out = self.scores.copy()
        out[k] += values[leaf]
        return out

    def advance(self, k: int, leaf: np.ndarray, values: np.ndarray) -> None:
        self.scores[k] += values[leaf]

    def add_programs(self, k: int, leaf: Optional[np.ndarray], values: np.ndarray) -> None:
        """``gbdt_binary``'s float32 adds of the program's own leaf values,
        into class ``k``'s row of ``applied``."""
        v = values.astype(np.float32)
        self.applied[k] = self.applied[k] + (v[0] if leaf is None else v[leaf])


def compare(produced: dict, data: dict, edges: Sequence[np.ndarray], params: dict,
            follow: Sequence[int], control_dtype: Optional[str] = None,
            log: Callable[[str], None] = lambda msg: None) -> Dict[str, Dict[str, float]]:
    """``{"program": {number: value}, "control": {...}}`` (``NUMBERS``), as
    ``gbdt_binary.compare`` gives them, K trees an iteration: iteration ``i``'s
    gradients are taken once, from the reference's scores as iteration ``i - 1``
    left them, and tree ``i K + k`` is followed on class ``k``'s."""
    text, warm_scores = produced["text"], produced["warm_scores"]
    final_scores, iterations_run = produced["final_scores"], produced["iterations_run"]
    X, y = data["X"], data["y"]
    extras, K = sorted(set(data) - {"X", "y"}), model_text.trees_per_iteration(text)
    if extras or params.get("objective") != "multiclass" or K != int(params["num_class"]) \
            or K < 2:
        raise ValueError("the reference covers the softmax objective, num_class trees an "
                         "iteration; this run has %d, objective %r, the extras %s"
                         % (K, params.get("objective"), extras))
    trees = model_text.parse_trees(text)
    t0 = time.perf_counter()
    ref = Follower(X, y, edges, params, K)
    label = ref.label
    log("reference: rows binned in %.1fs" % (time.perf_counter() - t0))

    mismatch = abs(len(trees) - iterations_run * K)
    split_gap = leaf_gap = loss_gap = 0.0
    c_split = c_leaf = c_loss = 0.0
    loss_prev_p = loss_prev_r = logloss(ref.scores, label)
    # the followed trees' histograms by worker processes, spawned once
    with FollowPool(ref) if follow else contextlib.nullcontext() as pool:
        for it in range(iterations_run):
            grad, hess = gradients(ref.scores, label)
            for k, tree in enumerate(trees[it * K: (it + 1) * K]):
                t = it * K + k
                if int(tree["num_leaves"]) < 2:
                    mismatch += 1
                    ref.add_programs(k, None, tree["leaf_value"])
                    continue
                full = t in follow
                f = ref.follow_class(tree, k, grad[k], hess[k], it == 0, pool if full else None,
                                     control_dtype if full else None)
                mismatch += int(np.sum(f["thr_bin"] < 0))
                mismatch += int(np.sum(f["leaf_count"] != tree["leaf_count"]))
                mismatch += int(np.sum(f["internal_count"] != tree["internal_count"]))
                leaf_gap = max(leaf_gap, plain._leaf_gap(tree["leaf_value"], f["leaf_values"]))
                if full:
                    split_gap = max(split_gap, float(np.max(f["split_gap"])))
                first = ref.init_scores[k] if it == 0 else 0.0
                c = f.get("control")
                if c is not None:
                    c_split = max(c_split, float(np.max(c["split_gap"])))
                    c_leaf = max(c_leaf, plain._leaf_gap(c["leaf_values"], f["leaf_values"]))
                    loss_c = logloss(ref.moved(k, f["leaf"], c["leaf_values"] - first), label)
                    before = logloss(ref.scores, label)
                ref.advance(k, f["leaf"], f["leaf_values"] - first)
                ref.add_programs(k, f["leaf"], tree["leaf_value"])
                if c is not None:
                    step = logloss(ref.scores, label) - before
                    c_loss = max(c_loss, abs((loss_c - before) - step) / abs(step))
                if full:
                    log("reference: tree %d (class %d) followed by its histograms by %.1fs"
                        % (t, k, time.perf_counter() - t0))
            # the program's scores are seen after each warm-up iteration and at
            # the end: the loss's change over each of those stretches
            seen = (warm_scores[it] if it < len(warm_scores)
                    else final_scores if it == iterations_run - 1 else None)
            if seen is not None:
                loss_r = logloss(ref.scores, label)
                loss_p = logloss(np.asarray(seen, np.float64).reshape(K, -1), label)
                step_r = loss_r - loss_prev_r
                loss_gap = max(loss_gap, abs((loss_p - loss_prev_p) - step_r) / abs(step_r))
                loss_prev_p, loss_prev_r = loss_p, loss_r

    moved = np.sqrt(np.mean((ref.applied.astype(np.float64) - ref.init_scores[:, None]) ** 2))
    score_gap = float(np.max(np.abs(
        np.asarray(final_scores, np.float64).reshape(K, -1) - ref.applied)) / moved)
    log("reference: %d trees of %d classes followed by %.1fs"
        % (len(trees), K, time.perf_counter() - t0))
    out = {"program": {"exact_mismatch": float(mismatch), "split_gap": split_gap,
                       "leaf_value_gap": leaf_gap, "loss_gap": loss_gap,
                       "score_gap": score_gap, "bin_width": float(ref.bin_width)}}
    if control_dtype is not None:
        rows = plain.CONTROL_BIN_ROWS
        wide = Follower(X[:rows], y[:rows], correct.coarser(edges), params, K)
        out["control"] = {"split_gap": c_split, "leaf_value_gap": c_leaf,
                          "loss_gap": c_loss, "bin_width": float(wide.bin_width)}
    return out


def work(tree: Dict[str, np.ndarray], config: dict) -> Dict[str, float]:
    """``work.tree_work`` of the tree and its share of the iteration's
    softmax pass (``SOFTMAX_BYTES_PER_ROW`` a row)."""
    out = work_mod.of_config(tree, config)
    out["bytes"] += float(SOFTMAX_BYTES_PER_ROW * config["rows"])
    return out
