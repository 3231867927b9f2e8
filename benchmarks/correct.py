"""The comparison that decides ``correct``.

What the timed path produced, at the timed size, is held against the plain
reference (``reference.py``): the model text of every tree of the run, warm-up
and window alike, the scores after each warm-up iteration and the scores when
the run stopped. The reference follows every tree by its sums (partition,
rows, leaf values, its own score update) and the trees named in ``follow``
also by their histograms: the first tree, which the window's own call and
compiled programs made before the window opened, and the last tree of the
timed window. Numbers compared, one limit each (``limits/<cell>.json``):

exact_mismatch   over every tree of the run: nodes whose row count differs
                 from the reference's partition by the tree's own thresholds,
                 thresholds that are no bin edge, and trees missing or beyond
                 the iterations run (partition, the loop's bookkeeping); an
                 exact comparison, limit 0
split_gap        widest, over the splits of the trees followed by histograms,
                 share by which the gain of the program's split lies below
                 the best gain any open leaf offered, both by the reference's
                 float64 histograms (gradients, histogram build, split
                 finding, leaf-wise order)
leaf_value_gap   worst leaf of every tree of the run: |program - reference|
                 over the larger of the reference's value and its median
                 leaf's (leaf values, and through them gradients and hessians,
                 from the reference's own scores all the way)
loss_gap         worst of each warm-up iteration and of the rest of the run
                 taken together: gap between the program's and the
                 reference's change of the training log-loss, over the
                 reference's change; an iteration that leaves the scores as
                 they were reads 1 (score update, the loop)
score_gap        worst row when the run stopped: |the program's score - the
                 program's own leaf values added up over the reference's
                 partition| over the root mean square the trees moved the
                 scores by (score update over every iteration of the run)
bin_width        largest share of the rows that any bin of any feature holds,
                 times max_bin, by the reference's own binning of the raw
                 columns with the program's edges (set-up's binning: an
                 equal-count binning reads 1, a quarter of the bins reads 4)
"""
from __future__ import annotations

import time
from typing import Callable, Dict, List, Optional, Sequence

import numpy as np

from . import model_text, reference


def _leaf_gap(program: np.ndarray, ref: np.ndarray) -> float:
    scale = np.maximum(np.abs(ref), np.median(np.abs(ref)))
    return float(np.max(np.abs(program - ref) / scale))


def follow_indices(names: Sequence[str], warmup: int, window_iterations: int) -> List[int]:
    """The trees a limits file's ``follow`` names: ``first`` is tree 0,
    ``window_last`` the last tree grown inside the timed window."""
    at = {"first": 0, "window_last": warmup + window_iterations - 1}
    return sorted({at[n] for n in names})


def compare(text: str, warm_scores: Sequence[np.ndarray], final_scores: np.ndarray,
            iterations_run: int, X: np.ndarray, y: np.ndarray,
            edges: Sequence[np.ndarray], params: dict, follow: Sequence[int],
            control_dtype: Optional[str] = None,
            log: Callable[[str], None] = lambda msg: None) -> Dict[str, Dict[str, float]]:
    """``{"program": {number: value}, "control": {...}}``; the control's
    numbers only where ``control_dtype`` names the lower precision, and then
    from the trees in ``follow`` alone."""
    trees = model_text.parse_trees(text)
    t0 = time.perf_counter()
    ref = reference.Follower(X, y, edges, params)
    log("reference: rows binned in %.1fs" % (time.perf_counter() - t0))

    mismatch = abs(len(trees) - iterations_run)
    split_gap = leaf_gap = loss_gap = 0.0
    c_split = c_leaf = c_loss = 0.0
    loss_prev_p = loss_prev_r = reference.logloss(
        np.full(1, ref.init_score), np.array([np.mean(y, dtype=np.float64)]))
    for t, tree in enumerate(trees[:iterations_run]):
        if int(tree["num_leaves"]) < 2:
            mismatch += 1
            ref.add_programs(None, tree["leaf_value"])
            continue
        full = t in follow
        f = ref.follow(tree, t == 0, histograms=full,
                       operand_dtype=control_dtype if full else None)
        mismatch += int(np.sum(f["thr_bin"] < 0))
        mismatch += int(np.sum(f["leaf_count"] != tree["leaf_count"]))
        mismatch += int(np.sum(f["internal_count"] != tree["internal_count"]))
        leaf_gap = max(leaf_gap, _leaf_gap(tree["leaf_value"], f["leaf_values"]))
        if full:
            split_gap = max(split_gap, float(np.max(f["split_gap"])))
        first = ref.init_score if t == 0 else 0.0
        c = f.get("control")
        if c is not None:
            c_split = max(c_split, float(np.max(c["split_gap"])))
            c_leaf = max(c_leaf, _leaf_gap(c["leaf_values"], f["leaf_values"]))
            loss_c = reference.logloss(ref.moved(f["leaf"], c["leaf_values"] - first), y)
            before = reference.logloss(ref.scores, y)
        ref.advance(f["leaf"], f["leaf_values"] - first)
        ref.add_programs(f["leaf"], tree["leaf_value"])
        if c is not None:
            step = reference.logloss(ref.scores, y) - before
            c_loss = max(c_loss, abs((loss_c - before) - step) / abs(step))
        # the program's scores are seen after each warm-up iteration and at
        # the end: the loss's change over each of those stretches
        seen = (warm_scores[t] if t < len(warm_scores)
                else final_scores if t == iterations_run - 1 else None)
        if seen is not None:
            loss_r = reference.logloss(ref.scores, y)
            loss_p = reference.logloss(np.asarray(seen).reshape(-1), y)
            step_r = loss_r - loss_prev_r
            loss_gap = max(loss_gap, abs((loss_p - loss_prev_p) - step_r) / abs(step_r))
            loss_prev_p, loss_prev_r = loss_p, loss_r
        if full:
            log("reference: tree %d followed by its histograms by %.1fs"
                % (t, time.perf_counter() - t0))

    moved = np.sqrt(np.mean((ref.applied.astype(np.float64) - ref.init_score) ** 2))
    score_gap = float(np.max(np.abs(
        np.asarray(final_scores, np.float64).reshape(-1) - ref.applied)) / moved)

    log("reference: %d trees followed by %.1fs" % (len(trees), time.perf_counter() - t0))
    out = {"program": {"exact_mismatch": float(mismatch), "split_gap": split_gap,
                       "leaf_value_gap": leaf_gap, "loss_gap": loss_gap,
                       "score_gap": score_gap, "bin_width": float(ref.bin_width)}}
    if control_dtype is not None:
        out["control"] = {"split_gap": c_split, "leaf_value_gap": c_leaf,
                          "loss_gap": c_loss}
    return out


def judge(numbers: Dict[str, float], limits: Dict[str, float]) -> Dict[str, dict]:
    """Each number beside its limit, and whether it holds; a number that is
    not finite holds no limit."""
    return {name: {"value": float(numbers[name]), "limit": float(limits[name]),
                   "ok": bool(np.isfinite(numbers[name])
                              and numbers[name] <= limits[name])}
            for name in limits}


def bin_edges(dataset, features: int) -> List[np.ndarray]:
    """Set-up's one table the reference is given: each feature's bin upper
    bounds (the candidate thresholds), by original column."""
    binned = dataset._binned
    edges = [np.array([np.inf])] * features
    for j, f in enumerate(binned.used_feature_idx):
        edges[int(f)] = np.asarray(binned.mappers[j].bin_upper_bound, np.float64)
    return edges


def coarser(edges: Sequence[np.ndarray], by: int = 4) -> List[np.ndarray]:
    """The control of ``bin_width``: every ``by`` bins of each feature made
    one, the last edge kept."""
    return [np.append(np.asarray(e)[by - 1:-1:by], np.asarray(e)[-1]) for e in edges]
