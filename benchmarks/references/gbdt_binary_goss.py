"""The plain reference of binary boosting under gradient-based one-side
sampling (GOSS).

``gbdt_binary`` with the mathematics of Algorithm 2 of Ke et al., "LightGBM: A
Highly Efficient Gradient Boosting Decision Tree" (NIPS 2017, section 3;
upstream's ``src/boosting/goss.hpp``, ``boosting=goss``). From iteration
``int(1 / learning_rate)`` on, each iteration keeps the ``top_k = int(n x
top_rate)`` rows with the largest ``|gradient x hessian|`` at weight 1, draws
``other_k = int(n x other_rate)`` of the rest uniformly, multiplies the drawn
rows' gradient and hessian by ``(n - top_k) / other_k``, grows its tree on those
``top_k + other_k`` rows alone and moves **every** row's score by it. Before
that iteration a tree is grown on every row, as ``gbdt_binary`` has it.

Like ``gbdt_binary`` it imports nothing of the program, is numpy and float64
throughout, and *follows* the program's trees (teacher forcing). A draw is
random, so it cannot be recomputed: each sampled iteration's draw is taken
from the program (``collect``: the in-bag rows and which of them carry the
multiplier) and **held to the law** from the reference's own float64 scores
(``sample_law``): the counts and the multiplier exactly, the rows at weight 1
against the reference's own ``|g x h|`` of every row, the drawn rows'
``|g x h|`` against that of the rows they were drawn from (``other_ks``). It
grows nothing itself: on the in-bag rows, with the amplified values, it
recomputes every node's count, every leaf's value and, on the trees followed
by their histograms, every candidate's gain; the score update runs over all
rows by the tree's own thresholds. What does not depend on the sampling (the
log-loss and its gradients, the binning, the gains, the way up the tree, the
margin at the hessian minimum) is ``gbdt_binary``'s own code.

A benchmark run and ``readings`` always hand the draws over (``run.drive``
calls ``collect``), and a sampled iteration without one is then a mismatch.
A caller that drives the program without ``collect`` (``produced["collected"]``
is None: the harness's own test of the control does) leaves the reference
nothing to take a sampled tree's rows from: such a tree is then held to what
needs no draw (its thresholds, its root's count of ``top_k + other_k`` rows,
the score update of every row), the reference's scores move by the program's
own leaf values, and the comparison says so in its log.

Departures from upstream, which the program shares: upstream's ``goss.hpp``
draws per thread block (each block keeps its own top and draws its own
others); this reference and the program draw over the whole table, as the
paper's Algorithm 2 does. Upstream takes ``top_k`` and ``other_k`` at least 1;
at any size a cell runs that changes nothing.
"""
from __future__ import annotations

import copy
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Callable, Dict, List, Optional, Sequence

import numpy as np

from benchmarks import correct, model_text, work as work_mod
from benchmarks.references import gbdt_binary as plain

NUMBERS = {
    "exact_mismatch": {"limit": "exact", "what": (
        "over every tree of the run: nodes whose row count differs from the count of the "
        "in-bag rows the reference's partition by the tree's own thresholds sends there (every "
        "row before sampling starts), thresholds that are no bin edge, and trees missing or "
        "beyond the iterations run")},
    "split_gap": dict(plain.NUMBERS["split_gap"]),
    "leaf_value_gap": dict(plain.NUMBERS["leaf_value_gap"]),
    "loss_gap": dict(plain.NUMBERS["loss_gap"]),
    "score_gap": dict(plain.NUMBERS["score_gap"]),
    "bin_width": dict(plain.NUMBERS["bin_width"]),
    "sample_mismatch": {"limit": "exact", "what": (
        "over every iteration of the run, what breaks the law of the draw: a draw before "
        "iteration int(1 / learning_rate) or none from it on, in-bag rows other than top_k + "
        "other_k, multiplied rows other than other_k or out of the bag, a multiplier other than "
        "(n - top_k) / other_k, a row at weight 1 whose |g x h| lies more than TOP_MARGIN under "
        "the top_k-th largest by the reference's own scores, and a row more than TOP_MARGIN "
        "over it that is not at weight 1")},
    "other_ks": {"limit": "gap", "what": (
        "worst sampled iteration's Kolmogorov distance between the |g x h| of the drawn rows and "
        "of the rows they were drawn from (all but those at weight 1), by the reference's own "
        "scores: a uniform draw of 20,000 from 160,000 stays near 0.01, the next other_k rows "
        "by rank read 0.87")},
}
# The top_k-th largest |g x h| is a comparison of the program's float32 scores
# with the reference's float64 ones: a row within this share of it may stand on
# either side. The scores drift apart by the trees' leaf-value gaps (1e-4 of a
# leaf, summed over the run), |g x h| by about as much of itself: on the chip
# the worst row of 32 trees stood 3.3e-6 of it on the other side (PERF.md
# section 4.1).
TOP_MARGIN = 1e-4


def collect(booster, dataset) -> List[dict]:
    """Each sampled iteration's draw, from the program's public record of
    them: ``iteration``, ``in_bag`` and ``amplified`` ([N] bool) and
    ``multiplier``."""
    return booster.sample_draws()


def sizes(rows: int, params: dict):
    """(top_k, other_k, first sampled iteration) of the configuration."""
    return (int(rows * float(params["top_rate"])), int(rows * float(params["other_rate"])),
            int(1.0 / float(params["learning_rate"])))


def kolmogorov(sample: np.ndarray, pool: np.ndarray) -> float:
    """sup |F_sample - F_pool| of two sets of values."""
    both = np.sort(np.concatenate([sample, pool]))
    return float(np.max(np.abs(
        np.searchsorted(np.sort(sample), both, side="right") / len(sample)
        - np.searchsorted(np.sort(pool), both, side="right") / len(pool))))


def sample_law(draw: Optional[dict], gh: np.ndarray, iteration: int, params: dict) -> Dict:
    """One iteration's draw held to the law, by the reference's own ``|g x
    h|`` of every row: how many things break it (``NUMBERS``'s
    ``sample_mismatch``), the Kolmogorov distance of the drawn rows
    (``other_ks``), and how far inside ``TOP_MARGIN`` the worst row at the
    top_k-th largest stood."""
    n = len(gh)
    top_k, other_k, start = sizes(n, params)
    if iteration < start or draw is None:
        return {"mismatch": int((draw is not None) != (iteration >= start)), "ks": 0.0,
                "edge": 0.0}
    in_bag, drawn = np.asarray(draw["in_bag"], bool), np.asarray(draw["amplified"], bool)
    kept = in_bag & ~drawn
    wrong = (abs(int(in_bag.sum()) - top_k - other_k) + abs(int(drawn.sum()) - other_k)
             + int(np.sum(drawn & ~in_bag))
             + int(abs(float(draw["multiplier"]) - (n - top_k) / other_k) > 1e-9))
    kth = np.partition(gh, n - top_k)[n - top_k]
    under = kept & (gh < kth * (1.0 - TOP_MARGIN))
    over = ~kept & (gh > kth * (1.0 + TOP_MARGIN))
    wrong += int(under.sum()) + int(over.sum())
    edge = max(float(np.max(kth - gh[kept], initial=0.0)),
               float(np.max(gh[~kept] - kth, initial=0.0))) / kth
    ks = kolmogorov(gh[drawn], gh[~kept]) if drawn.any() and (~kept).any() else 1.0
    return {"mismatch": wrong, "ks": ks, "edge": edge}


class Follower(plain.Follower):
    """``gbdt_binary``'s follower, a tree's sums and histograms taken over the
    rows of its draw alone, the drawn ones at the multiplier."""

    def on_rows(self, rows: np.ndarray) -> "Follower":
        """A copy that holds the bins of these rows alone, for ``_block``."""
        held = copy.copy(self)
        held.bins = self.bins[:, rows]
        return held

    def follow(self, tree: dict, is_first: bool, histograms: bool = True,
               operand_dtype: Optional[str] = None, workers: int = 8,
               draw: Optional[dict] = None) -> Dict[str, object]:
        """``gbdt_binary.Follower.follow`` on the rows of ``draw`` (every row
        where it is None). ``leaf`` is every row's leaf, in the bag or not:
        the score update is over all of them."""
        if draw is None:
            return super().follow(tree, is_first, histograms, operand_dtype, workers)
        L = int(tree["num_leaves"])
        M = L - 1
        p = self.params
        thr_bin = self.threshold_bins(tree)
        leaf_all = self.leaves(tree, np.maximum(thr_bin, 0))
        rows = np.flatnonzero(draw["in_bag"])
        times = np.where(np.asarray(draw["amplified"])[rows], float(draw["multiplier"]), 1.0)
        weights = [w[rows] * times for w in plain.gradients(self.scores, self.y)]
        if operand_dtype is not None:
            weights += [plain.rounded(w, operand_dtype) for w in weights]
        leaf = leaf_all[rows]
        kids = plain.node_order(tree)
        total = self._totals(leaf, L, kids, weights)

        l2 = float(p.get("lambda_l2", 0.0))
        rate = float(p["learning_rate"])
        first = self.init_score if is_first else 0.0

        def leaf_values(t):
            return -t[M:, 0] / (t[M:, 1] + l2) * rate + first

        out = {
            "leaf": leaf_all, "thr_bin": thr_bin,
            "leaf_values": leaf_values(total[0]),
            "leaf_count": np.rint(total[0, M:, 2]).astype(np.int64),
            "internal_count": np.rint(total[0, :M, 2]).astype(np.int64),
        }
        if operand_dtype is not None:
            out["control"] = {"leaf_values": leaf_values(total[1])}
        if not histograms:
            return out

        held = self.on_rows(rows)
        base = leaf * self.width
        step = max(1, min(32, (1 << 21) // (self.width * L)))
        spans = [(a, min(a + step, self.features))
                 for a in range(0, self.features, step)]
        with ThreadPoolExecutor(workers) as pool:
            blocks = list(pool.map(
                lambda ab: held._block(ab[0], ab[1], tree, kids, base, weights, thr_bin),
                spans))
        best = np.max([b["best"] for b in blocks], axis=0)
        chosen = np.full(M, -np.inf)
        for b in blocks:
            chosen[b["chosen"][0]] = b["chosen"][1]
        # which nodes were leaves when split i was made (``gbdt_binary``)
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


def compare(produced: dict, data: dict, edges: Sequence[np.ndarray], params: dict,
            follow: Sequence[int], control_dtype: Optional[str] = None,
            log: Callable[[str], None] = lambda msg: None) -> Dict[str, Dict[str, float]]:
    """``{"program": {number: value}, "control": {...}}`` (``NUMBERS``), as
    ``gbdt_binary.compare`` gives them, each tree on the rows of its
    iteration's draw (``produced["collected"]``: what ``collect`` took)."""
    text, warm_scores = produced["text"], produced["warm_scores"]
    final_scores, iterations_run = produced["final_scores"], produced["iterations_run"]
    X, y = data["X"], data["y"]
    extras, per_iteration = sorted(set(data) - {"X", "y"}), model_text.trees_per_iteration(text)
    if extras or per_iteration != 1:
        raise ValueError("the reference covers one tree an iteration; this run has %d and "
                         "the extras %s" % (per_iteration, extras))
    if params.get("boosting") != "goss":
        raise ValueError("the reference covers boosting=goss alone")
    unseen = produced.get("collected") is None      # driven without ``collect``
    draws = {int(d["iteration"]): d for d in produced.get("collected") or []}
    top_k, other_k, start = sizes(len(y), params)
    trees = model_text.parse_trees(text)
    t0 = time.perf_counter()
    ref = Follower(X, y, edges, params)
    log("reference: rows binned in %.1fs" % (time.perf_counter() - t0))

    mismatch = abs(len(trees) - iterations_run)
    sample_mismatch = len([t for t in draws if t >= iterations_run])
    split_gap = leaf_gap = loss_gap = other_ks = edge = 0.0
    c_split = c_leaf = c_loss = 0.0
    loss_prev_p = loss_prev_r = plain.logloss(
        np.full(1, ref.init_score), np.array([np.mean(y, dtype=np.float64)]))
    for t, tree in enumerate(trees[:iterations_run]):
        draw = draws.get(t)
        if unseen and t >= start and int(tree["num_leaves"]) >= 2:
            thr_bin = ref.threshold_bins(tree)
            leaf = ref.leaves(tree, np.maximum(thr_bin, 0))
            mismatch += int(np.sum(thr_bin < 0))
            mismatch += int(tree["internal_count"][0] != top_k + other_k)
            ref.advance(leaf, tree["leaf_value"])
            ref.add_programs(leaf, tree["leaf_value"])
            continue
        g, h = plain.gradients(ref.scores, ref.y)
        law = sample_law(draw, np.abs(g * h), t, params)
        sample_mismatch += law["mismatch"]
        other_ks, edge = max(other_ks, law["ks"]), max(edge, law["edge"])
        if int(tree["num_leaves"]) < 2:
            mismatch += 1
            ref.add_programs(None, tree["leaf_value"])
            continue
        full = t in follow
        f = ref.follow(tree, t == 0, histograms=full,
                       operand_dtype=control_dtype if full else None, draw=draw)
        mismatch += int(np.sum(f["thr_bin"] < 0))
        mismatch += int(np.sum(f["leaf_count"] != tree["leaf_count"]))
        mismatch += int(np.sum(f["internal_count"] != tree["internal_count"]))
        leaf_gap = max(leaf_gap, plain._leaf_gap(tree["leaf_value"], f["leaf_values"]))
        if full:
            split_gap = max(split_gap, float(np.max(f["split_gap"])))
        first = ref.init_score if t == 0 else 0.0
        c = f.get("control")
        if c is not None:
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
        if full:
            log("reference: tree %d followed by its histograms on %d rows by %.1fs"
                % (t, int(np.sum(f["leaf_count"])), time.perf_counter() - t0))

    moved = np.sqrt(np.mean((ref.applied.astype(np.float64) - ref.init_score) ** 2))
    score_gap = float(np.max(np.abs(
        np.asarray(final_scores, np.float64).reshape(-1) - ref.applied)) / moved)
    log("reference: %d trees followed, %d of them on a draw, by %.1fs; the row nearest the "
        "top_k-th largest |g x h| on the other side of it stood %.2e of it away (margin %.0e)"
        % (len(trees), len(draws), time.perf_counter() - t0, edge, TOP_MARGIN))
    if unseen:
        log("reference: the draws were not collected: the trees from iteration %d on were held "
            "to their thresholds, their root's count and the score update alone" % start)
    out = {"program": {"exact_mismatch": float(mismatch), "split_gap": split_gap,
                       "leaf_value_gap": leaf_gap, "loss_gap": loss_gap,
                       "score_gap": score_gap, "bin_width": float(ref.bin_width),
                       "sample_mismatch": float(sample_mismatch), "other_ks": other_ks}}
    if control_dtype is not None:
        wide = plain.Follower(X[:plain.CONTROL_BIN_ROWS], y[:plain.CONTROL_BIN_ROWS],
                              correct.coarser(edges), params)
        out["control"] = {"split_gap": c_split, "leaf_value_gap": c_leaf,
                          "loss_gap": c_loss, "bin_width": float(wide.bin_width)}
    return out


def work(tree: Dict[str, np.ndarray], config: dict) -> Dict[str, float]:
    """``work.tree_work`` of the tree by its own node counts, which are the
    in-bag rows' (the root's rows are the sample, not the table), and for a
    tree grown on a sample two passes more over all of the table's rows, 8
    bytes a row each: the draw reads every row's gradient and hessian, and the
    score update reaches the rows out of the bag too."""
    out = work_mod.of_config(tree, config)
    if 0 < np.sum(tree["leaf_count"]) < config["rows"]:
        out["bytes"] += 2.0 * config["rows"] * work_mod.PASS_BYTES_PER_ROW_EXTRA
    return out
