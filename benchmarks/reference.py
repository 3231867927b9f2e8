"""The plain reference of one boosting iteration, and what follows a run.

It imports nothing of the program. From the program it is given the model
text (the output that is compared) and, of set-up's tables, only the bin
edges of each feature, which define the candidate thresholds a split may
take; it bins the raw matrix with them itself. Everything else it works out
from the raw rows and labels: the starting score, the gradients of the
binary log-loss, each node's histogram, every candidate split's gain, the
partition, the leaf values and the score update.

It *follows* the program (teacher forcing): the rows of a node are those the
program's own earlier splits sent there, so one near-tie decided the other way
does not make every later number incomparable. Every tree of a run is followed
by its sums: the partition, each node's rows, gradient and hessian, the leaf
values, and the reference's own score update (one pass over the rows a tree).
The trees the comparison names are also followed by their histograms: every
candidate's gain at every node (2000 columns x 509 nodes x 255 bins a tree,
which is what takes the time). It is numpy on the host and float64 throughout
(scores, gradients, every sum, gain and leaf value); it touches no device.
Only ``Follower.applied`` adds in float32, to make the very adds the
program's score update makes.

The bin edges are held against the raw columns' own quantiles:
``Follower.bin_width`` is the largest share of the rows that any bin of any
feature holds, times ``max_bin``; an equal-count binning reads 1, one of a
quarter of the bins reads 4.

``operand_dtype`` rounds gradient and hessian before they are summed into the
histograms: None is the reference; the name of a narrower float type gives
the control, one step below what the cell states (``bfloat16`` under float32
operands, ``float8_e4m3fn`` under bfloat16 ones).
"""
from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor
from typing import Dict, Optional, Sequence

import numpy as np


def logloss(scores: np.ndarray, y: np.ndarray) -> float:
    """Mean binary log-loss of raw scores, float64."""
    s = np.asarray(scores, np.float64)
    return float(np.mean(np.logaddexp(0.0, s) - np.asarray(y, np.float64) * s))


def floor_float32(edges: np.ndarray) -> np.ndarray:
    """The largest float32 not above each float64 edge: for a float32 ``x``,
    ``x <= edge`` holds in float64 exactly when ``x <= floor_float32(edge)``."""
    e32 = edges.astype(np.float32)
    over = e32.astype(np.float64) > edges
    e32[over] = np.nextafter(e32[over], np.float32(-np.inf))
    return e32


def bin_rows(X: np.ndarray, edges32: Sequence[np.ndarray], workers: int = 8) -> np.ndarray:
    """[F, N] uint8: for each value the first edge it does not exceed."""
    out = np.empty((X.shape[1], X.shape[0]), np.uint8)

    def one(f: int) -> None:
        out[f] = np.searchsorted(edges32[f], X[:, f], side="left")

    with ThreadPoolExecutor(workers) as pool:
        list(pool.map(one, range(X.shape[1])))
    return out


def leaf_of(bins: np.ndarray, feat: np.ndarray, thr: np.ndarray, left: np.ndarray,
            right: np.ndarray) -> np.ndarray:
    """Walks every row from the root: left where its bin of the node's
    feature is at most the node's threshold bin; a child < 0 is leaf
    -(child + 1)."""
    node = np.zeros(bins.shape[1], np.int64)
    rows = np.arange(bins.shape[1])
    while rows.size:
        at = node[rows]
        nxt = np.where(bins[feat[at], rows] <= thr[at], left[at], right[at])
        node[rows] = nxt
        rows = rows[nxt >= 0]
    return -(node + 1)


def gradients(scores: np.ndarray, y: np.ndarray):
    """Gradient and hessian of the binary log-loss (sigmoid 1)."""
    p = 1.0 / (1.0 + np.exp(-scores))
    return p - y, p * (1.0 - p)


def rounded(values: np.ndarray, dtype: str) -> np.ndarray:
    """``values`` rounded to a narrower float type, as float64."""
    import ml_dtypes

    return values.astype(np.float32).astype(getattr(ml_dtypes, dtype)).astype(np.float64)


# The hessian minimum is a comparison of a float32 sum in the program and of
# a float64 sum here. A tree grown under min_sum_hessian_in_leaf=100 is full of
# candidates that sit on it, so a candidate within this share of the minimum
# is one that either side may allow or rule out: the reference does not count
# it among what was offered, and does not hold it against the program.
HESSIAN_MARGIN = 1e-4


def candidates(hist: np.ndarray, num_bin: np.ndarray, p: dict):
    """From [nodes, F, B, 3] histograms (gradient, hessian, rows): the gain of
    every candidate (left = bins <= t) as LightGBM defines it,
    G_l^2/(H_l+l2) + G_r^2/(H_r+l2) - G^2/(H+l2), [nodes, F, B] float64;
    whether all of the configuration's constraints but the hessian minimum
    allow it; and the smaller of its two hessian sums."""
    l2 = float(p.get("lambda_l2", 0.0))
    left = np.cumsum(hist, axis=2)
    total = left[:, :1, -1:, :]                     # every feature holds all rows
    gl, hl, cl = left[..., 0], left[..., 1], left[..., 2]
    gr, hr, cr = total[..., 0] - gl, total[..., 1] - hl, total[..., 2] - cl
    with np.errstate(divide="ignore", invalid="ignore"):
        gain = gl * gl / (hl + l2) + gr * gr / (hr + l2) \
            - total[..., 0] ** 2 / (total[..., 1] + l2)
    ok = ((np.arange(hist.shape[2])[None, None, :] <= (num_bin[None, :, None] - 2))
          & (cl >= p["min_data_in_leaf"]) & (cr >= p["min_data_in_leaf"])
          & (gain > float(p.get("min_gain_to_split", 0.0))))
    return gain, ok, np.minimum(hl, hr)


def split_gains(hist: np.ndarray, num_bin: np.ndarray, p: dict,
                margin: float = 0.0) -> np.ndarray:
    """[nodes, F, B] float64: every candidate's gain, and -inf where the
    configuration's constraints rule it out; the hessian minimum is taken
    ``1 + margin`` times as large."""
    gain, ok, least = candidates(hist, num_bin, p)
    ok &= least >= p["min_sum_hessian_in_leaf"] * (1.0 + margin)
    return np.where(ok, gain, -np.inf)


def node_order(tree: dict) -> np.ndarray:
    """Internal node i is row i, leaf l is row (leaves - 1) + l; returns for
    each internal node the rows of its two children."""
    M = int(tree["num_leaves"]) - 1
    kids = np.stack([tree["left_child"], tree["right_child"]], axis=1).astype(np.int64)
    return np.where(kids < 0, M - (kids + 1), kids)


class Follower:
    """Bins the rows by the given edges and follows trees one by one."""

    def __init__(self, X: np.ndarray, y: np.ndarray, edges: Sequence[np.ndarray],
                 params: dict) -> None:
        for key in ("lambda_l1", "max_delta_step"):
            if float(params.get(key, 0.0)) != 0.0:
                raise ValueError("the reference does not cover %s != 0" % key)
        self.params = params
        self.rows, self.features = X.shape
        self.num_bin = np.array([len(e) for e in edges], np.int64)
        self.width = int(max(self.num_bin.max(), 2))
        self.edges64 = [np.asarray(e, np.float64) for e in edges]
        self.bins = bin_rows(X, [floor_float32(e) for e in self.edges64])
        fullest = max(int(np.bincount(b, minlength=1).max()) for b in self.bins)
        self.bin_width = fullest / self.rows * int(params["max_bin"])
        self.applied = np.zeros(self.rows, np.float32)
        self.y = np.asarray(y, np.float64)
        mean = float(self.y.mean())
        self.init_score = float(np.log(mean / (1.0 - mean)))
        self.scores = np.full(self.rows, self.init_score, np.float64)

    # -- the program's tree in the reference's terms -----------------------

    def threshold_bins(self, tree: dict) -> np.ndarray:
        """The bin each real threshold of the tree closes, -1 where the
        threshold is no edge of its feature."""
        out = np.full(len(tree["threshold"]), -1, np.int64)
        for i, (f, t) in enumerate(zip(tree["split_feature"], tree["threshold"])):
            e = self.edges64[int(f)]
            b = int(np.searchsorted(e, t, side="left"))
            if b < len(e) - 1 and e[b] == t:
                out[i] = b
        return out

    def leaves(self, tree: dict, thr_bin: np.ndarray) -> np.ndarray:
        return leaf_of(self.bins, tree["split_feature"], thr_bin,
                       tree["left_child"], tree["right_child"])

    def _block(self, f0: int, f1: int, tree: dict, kids: np.ndarray, base: np.ndarray,
               weights: Sequence[np.ndarray], thr_bin: np.ndarray) -> dict:
        """Features ``[f0, f1)``: their histograms by leaf, summed up the
        tree to every node, every candidate's gain, and of those what the
        comparison needs. ``weights`` is (gradient, hessian) and, for the
        control, the same two rounded to the lower precision."""
        L = int(tree["num_leaves"])
        M, W, k = L - 1, self.width, f1 - f0
        sets = len(weights) // 2
        hist = np.empty((sets, 2 * L - 1, k, W, 3), np.float64)
        for j, f in enumerate(range(f0, f1)):
            at = base + self.bins[f]
            rows = np.bincount(at, minlength=L * W).reshape(L, W)
            for c, w in enumerate(weights):
                hist[c // 2, M:, j, :, c % 2] = np.bincount(
                    at, weights=w, minlength=L * W).reshape(L, W)
            hist[:, M:, j, :, 2] = rows
        for i in range(M - 1, -1, -1):   # a child that is a node has a larger index
            hist[:, i] = hist[:, kids[i, 0]] + hist[:, kids[i, 1]]
        nb = self.num_bin[f0:f1]
        least_h = self.params["min_sum_hessian_in_leaf"]
        gain, ok, least = candidates(hist[0], nb, self.params)
        offered = np.where(ok & (least >= least_h * (1 + HESSIAN_MARGIN)), gain, -np.inf)
        allowed = np.where(ok & (least >= least_h * (1 - HESSIAN_MARGIN)), gain, -np.inf)
        mine = np.flatnonzero((tree["split_feature"] >= f0) & (tree["split_feature"] < f1)
                              & (thr_bin >= 0))
        out = {
            "best": offered.reshape(2 * L - 1, -1).max(axis=1),
            "chosen": (mine, allowed[mine, tree["split_feature"][mine] - f0, thr_bin[mine]]),
        }
        if sets == 2:
            # what the lower precision puts first by its own sums and its own
            # reading of the constraints, judged by the reference's gain of it
            low = split_gains(hist[1, :M], nb, self.params).reshape(M, -1)
            pick = low.argmax(axis=1)
            out["low_best"] = low[np.arange(M), pick]
            out["low_picked"] = np.where(ok[:M], gain[:M], -np.inf).reshape(M, -1)[
                np.arange(M), pick]
        return out

    def _totals(self, leaf: np.ndarray, L: int, kids: np.ndarray,
                weights: Sequence[np.ndarray]) -> np.ndarray:
        """[sets, nodes, 3]: each node's gradient, hessian and rows, from the
        leaves' sums added up the tree."""
        M = L - 1
        total = np.zeros((len(weights) // 2, 2 * L - 1, 3), np.float64)
        for c, w in enumerate(weights):
            total[c // 2, M:, c % 2] = np.bincount(leaf, weights=w, minlength=L)
        total[:, M:, 2] = np.bincount(leaf, minlength=L)
        for i in range(M - 1, -1, -1):   # a child that is a node has a larger index
            total[:, i] = total[:, kids[i, 0]] + total[:, kids[i, 1]]
        return total

    def follow(self, tree: dict, is_first: bool, histograms: bool = True,
               operand_dtype: Optional[str] = None, workers: int = 8) -> Dict[str, object]:
        """Follows one tree of the program from the reference's current
        scores and returns what the comparison needs; ``advance`` then moves
        the reference's scores by its own leaf values. With ``histograms``
        also every candidate's gain at every node, and from them how far the
        program's split lies below the best that was open. With
        ``operand_dtype`` also the control's numbers: the leaf values that
        sums of gradients and hessians rounded to that type give and, with
        ``histograms``, at each node the split that such histograms put
        first, judged by the reference's gains."""
        L = int(tree["num_leaves"])
        M = L - 1
        p = self.params
        thr_bin = self.threshold_bins(tree)
        leaf = self.leaves(tree, np.maximum(thr_bin, 0))
        weights = list(gradients(self.scores, self.y))
        if operand_dtype is not None:
            weights += [rounded(w, operand_dtype) for w in weights]
        kids = node_order(tree)
        total = self._totals(leaf, L, kids, weights)

        l2 = float(p.get("lambda_l2", 0.0))
        rate = float(p["learning_rate"])
        first = self.init_score if is_first else 0.0

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
        if not histograms:
            return out

        base = leaf * self.width
        step = max(1, min(32, (1 << 21) // (self.width * L)))
        spans = [(a, min(a + step, self.features))
                 for a in range(0, self.features, step)]
        with ThreadPoolExecutor(workers) as pool:
            blocks = list(pool.map(
                lambda ab: self._block(ab[0], ab[1], tree, kids, base, weights, thr_bin),
                spans))
        best = np.max([b["best"] for b in blocks], axis=0)
        chosen = np.full(M, -np.inf)
        for b in blocks:
            chosen[b["chosen"][0]] = b["chosen"][1]

        # which nodes were leaves when split i was made: those made by an
        # earlier split (or the root) and not yet split themselves
        made_by = np.full(2 * L - 1, -1, np.int64)  # the root: before split 0
        for i in range(M):
            made_by[kids[i]] = i
        split_at = np.concatenate([np.arange(M), np.full(L, M + 1)])
        frontier_best = np.array([best[(made_by < i) & (split_at >= i)].max()
                                  for i in range(M)])
        with np.errstate(invalid="ignore", divide="ignore"):
            out["split_gap"] = (frontier_best - chosen) / frontier_best
        out["chosen_gain"], out["frontier_best"] = chosen, frontier_best
        if operand_dtype is not None:
            which = np.argmax([b["low_best"] for b in blocks], axis=0)
            picked = np.array([blocks[w]["low_picked"][i] for i, w in enumerate(which)])
            with np.errstate(invalid="ignore", divide="ignore"):
                out["control"]["split_gap"] = (frontier_best - picked) / frontier_best
        return out

    def moved(self, leaf: np.ndarray, values: np.ndarray) -> np.ndarray:
        """The reference's scores with ``values`` added by leaf."""
        return self.scores + values[leaf]

    def advance(self, leaf: np.ndarray, values: np.ndarray) -> None:
        self.scores = self.moved(leaf, values)

    def add_programs(self, leaf: Optional[np.ndarray], values: np.ndarray) -> None:
        """Adds the program's own leaf values, by the reference's partition,
        to ``applied``: float32 adds from 0, tree by tree, as the program's
        score update makes them (tree 0's leaves carry the starting score).
        ``leaf`` is None for a tree that did not split."""
        v = values.astype(np.float32)
        self.applied = self.applied + (v[0] if leaf is None else v[leaf])
