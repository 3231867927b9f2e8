"""The plain reference of binary boosting on a table with missing values.

``gbdt_binary`` with the mathematics of ``feature_histogram.hpp``'s two scans
in place of its one. A feature whose last bin edge is NaN has the NaN missing
type: its last bin holds the rows that have no value, and a split of it also
says where those rows go (the model text's ``decision_type``: bit 1 the
default direction, bits 2-3 the missing type). At every node such a feature
is scanned both ways:

- missing to the right (``default_left`` off): the left sums are accumulated
  from the left over the real bins, the right side is the node less the left,
  so it holds the NaN bin. Thresholds up to the last real bin: that one parts
  the rows that have a value from those that have none;
- missing to the left (``default_left`` on): the right sums are accumulated
  from the right with the NaN bin left out, the left side is the node less
  the right, so the NaN bin's mass arrives there by subtraction. Thresholds
  up to the last real bin but one: the last would leave the right side empty.

The NaN bin's own threshold is offered by neither. A NaN feature of two bins
(one real bin and the NaN bin) is scanned once, its real bin to the left and
the missing to the right, as ``feature_histogram.hpp`` has it. A feature with
no missing type is scanned once and may be written with either direction: no
row of it is sent by one.

Like ``gbdt_binary`` it imports nothing of the program, follows the program's
trees (teacher forcing), the partition by the tree's own thresholds and
default directions, and is float64 throughout; what does not depend on the
mathematics (the log-loss and its gradients, the way up the tree, the score
update, the margin at the hessian minimum) is ``gbdt_binary``'s own code. The
raw matrix is binned in blocks of rows: at 1M x 968 one float64 copy of it
would be 7.7 GB.
"""
from __future__ import annotations

import time
from concurrent.futures import ThreadPoolExecutor
from typing import Callable, Dict, List, Optional, Sequence

import numpy as np

from benchmarks import correct, model_text, work as work_mod
from benchmarks.references import gbdt_binary as plain

NUMBERS = {
    "exact_mismatch": {"limit": "exact", "what": (
        "over every tree of the run: nodes whose row count differs from the reference's "
        "partition by the tree's own thresholds and default directions, thresholds that are "
        "no bin edge a scan offers, nodes whose decision_type names another missing type than "
        "the feature's edges show, and trees missing or beyond the iterations run")},
    "split_gap": {"limit": "gap", "what": (
        "widest, over the splits of the trees followed by histograms, share by which the gain "
        "of the program's split, in the direction it names, lies below the best gain any open "
        "leaf offered in either direction, both by the reference's float64 histograms")},
    "leaf_value_gap": dict(plain.NUMBERS["leaf_value_gap"]),
    "loss_gap": dict(plain.NUMBERS["loss_gap"]),
    "score_gap": dict(plain.NUMBERS["score_gap"]),
    "bin_width": {"limit": "ratio", "between": (1, 4), "what": (
        "largest share of a feature's rows that have a value which one of its bins holds, "
        "times max_bin, over the bins that hold more than one distinct value, by the "
        "reference's own binning of the first BIN_WIDTH_ROWS raw rows with the program's "
        "edges (four fifths of all rows sit in the NaN bin by construction; an equal-count "
        "binning of the rest reads 1, a quarter of the bins reads 4)")},
}
BIN_WIDTH_ROWS = 1 << 18     # a look at the edges; the seed's order makes any rows a sample
BLOCK_ROWS = 1 << 15
# Features a run of the histogram follow holds at once. At 255 leaves and bins
# a run's largest array is 3.1 MB a feature and set of weights: with four it
# stays under the 32 MB up to which malloc hands freed memory out again. Whole
# blocks of 32 features made and dropped 100 MB arrays at 1 GB/s, which the
# chip's host (a sandbox that is slow to give freed pages back) counted up to
# its 40 GiB limit (PERF.md, PR 28).
RUN_FEATURES = 4
NO_BIN = 256                 # the NaN bin of a feature that has none: no uint8 bin equals it
NAN_TYPE = 2                 # decision_type's bits 2-3
AVOID_INF = 1e300            # how the model text writes an edge at infinity
# The gain is a difference, G_l^2/H_l + G_r^2/H_r less the parent's G^2/H, of a
# float32 histogram's sums in the program and of float64 sums here. Where 0.6%
# of the labels are positive many a leaf holds one label alone, every split of
# it gains nothing, and what a float32 difference of two equal terms leaves
# decides whether the program splits it. So, as at the hessian minimum
# (``gbdt_binary.HESSIAN_MARGIN``), a candidate whose gain lies within this
# share of the parent's term of min_gain_to_split is one that either side may
# allow or rule out: not counted among what was offered, not held against the
# program.
GAIN_MARGIN = 1e-4


def decisions(text: str) -> List[np.ndarray]:
    """Each tree's ``decision_type`` line, which ``model_text.parse_trees``
    leaves out; empty for a tree that did not split."""
    out = []
    for section in text.split("\nTree=")[1:]:
        line = [l for l in section.splitlines() if l.startswith("decision_type=")]
        out.append(np.array(line[0].partition("=")[2].split() if line else [], np.int64))
    return out


def real_edges(edges: np.ndarray) -> np.ndarray:
    """The upper bounds of the bins that hold values: all but a last NaN."""
    return edges[:-1] if len(edges) and np.isnan(edges[-1]) else edges


def bin_rows(X: np.ndarray, edges32: Sequence[np.ndarray], nan_type: np.ndarray,
             workers: int = 8) -> np.ndarray:
    """[F, N] uint8: for each value the first real edge it does not exceed;
    NaN sorts above every edge, which is the NaN bin where the feature has
    one; where it has none a NaN counts as 0, as upstream folds it."""
    out = np.empty((X.shape[1], X.shape[0]), np.uint8)

    def one(a: int) -> None:
        block = np.ascontiguousarray(X[a: a + BLOCK_ROWS].T)
        for f, e in enumerate(edges32):
            v = block[f] if nan_type[f] else np.nan_to_num(block[f], nan=0.0)
            out[f, a: a + BLOCK_ROWS] = np.searchsorted(e, v, side="left")

    with ThreadPoolExecutor(workers) as pool:
        list(pool.map(one, range(0, X.shape[0], BLOCK_ROWS)))
    return out


def bin_width(X: np.ndarray, edges: Sequence[np.ndarray], max_bin: int,
              workers: int = 8) -> float:
    """``NUMBERS["bin_width"]`` of these rows under these edges. A bin that
    holds one distinct value cannot be made narrower, whatever share it holds."""
    def one(f: int) -> float:
        v = X[:, f]
        v = np.sort(v[~np.isnan(v)])
        if not len(v):
            return 0.0
        b = np.searchsorted(plain.floor_float32(real_edges(np.asarray(edges[f], np.float64))),
                            v, side="left")
        ends = np.append(np.flatnonzero(np.diff(b)) + 1, len(v))
        starts = np.append(0, ends[:-1])
        mixed = v[starts] != v[ends - 1]
        return float(np.max((ends - starts)[mixed], initial=0)) / len(v)

    with ThreadPoolExecutor(workers) as pool:
        return max(pool.map(one, range(X.shape[1]))) * int(max_bin)


def leaf_of(bins: np.ndarray, nan_bin: np.ndarray, tree: dict, thr: np.ndarray) -> np.ndarray:
    """Parts the rows node by node from the root (a child that is a node has
    a larger index than its parent): a row in the node's feature's NaN bin goes
    where the node's default direction says, any other left where its bin is
    at most the node's threshold bin; a child < 0 is leaf -(child + 1)."""
    leaf = np.empty(bins.shape[1], np.int64)
    rows_of = {0: np.arange(bins.shape[1])}
    for i, f in enumerate(tree["split_feature"]):
        rows = rows_of.pop(i)
        b = bins[f][rows]
        go_left = np.where(b == nan_bin[f], tree["default_left"][i], b <= thr[i])
        for child, side in ((tree["left_child"][i], go_left), (tree["right_child"][i], ~go_left)):
            if child >= 0:
                rows_of[int(child)] = rows[side]
            else:
                leaf[rows[side]] = -(child + 1)
    return leaf


def scans(hist: np.ndarray, num_bin: np.ndarray, nan_type: np.ndarray, p: dict):
    """From [nodes, F, B, 3] histograms (gradient, hessian, rows), for the
    two scans (0: missing to the right, 1: missing to the left; the module's
    docstring): every candidate's gain, G_l^2/(H_l+l2) + G_r^2/(H_r+l2) -
    G^2/(H+l2), [2, nodes, F, B] float64; whether the scan offers it and the
    configuration's minimum of rows allows it; the smaller of its two hessian
    sums; and the parent's term G^2/(H+l2), [nodes, 1, 1]."""
    l2 = float(p.get("lambda_l2", 0.0))
    t = np.arange(hist.shape[2])
    both = nan_type & (num_bin > 2)
    nan_at = (both[:, None] & (t[None, :] == (num_bin - 1)[:, None]))[None, :, :, None]
    total = hist[:, :1].sum(axis=2, keepdims=True)          # every feature holds all rows
    with np.errstate(divide="ignore", invalid="ignore"):
        parent = total[..., 0] ** 2 / (total[..., 1] + l2)
    from_left = np.cumsum(np.where(nan_at, 0.0, hist), axis=2)
    missing = np.where(nan_at, hist, 0.0).sum(axis=2, keepdims=True)
    # the last threshold each scan offers; a feature scanned once is offered in
    # scan 0, and also in scan 1 where it has no missing type
    last = np.stack([num_bin - 2, np.where(both, num_bin - 3,
                                           np.where(nan_type, -1, num_bin - 2))])
    out = []
    for scan, left in enumerate((from_left, from_left + missing)):
        right = total - left
        gl, hl, cl = left[..., 0], left[..., 1], left[..., 2]
        gr, hr, cr = right[..., 0], right[..., 1], right[..., 2]
        with np.errstate(divide="ignore", invalid="ignore"):
            gain = gl * gl / (hl + l2) + gr * gr / (hr + l2) - parent
        ok = ((t[None, None, :] <= last[scan][None, :, None])
              & (cl >= p["min_data_in_leaf"]) & (cr >= p["min_data_in_leaf"]))
        out.append((gain, ok, np.minimum(hl, hr)))
    return tuple(np.stack(x) for x in zip(*out)) + (parent,)


def within(gain, ok, least, parent, p: dict, margin: float) -> np.ndarray:
    """The gains of the candidates that the constraints allow, -inf elsewhere,
    with the hessian minimum ``1 + HESSIAN_MARGIN * margin`` times as large
    and the least gain ``GAIN_MARGIN * margin`` of the parent's term higher:
    ``margin`` 1 gives what is clearly offered, -1 what is not clearly ruled
    out, 0 the constraints as they stand."""
    least_h = p["min_sum_hessian_in_leaf"] * (1.0 + plain.HESSIAN_MARGIN * margin)
    least_gain = float(p.get("min_gain_to_split", 0.0)) + GAIN_MARGIN * margin * parent
    return np.where(ok & (least >= least_h) & (gain > least_gain), gain, -np.inf)


def split_gains(hist: np.ndarray, num_bin: np.ndarray, nan_type: np.ndarray,
                p: dict) -> np.ndarray:
    """[2, nodes, F, B] float64: every candidate's gain in each scan, -inf
    where the scan does not offer it or the configuration's constraints rule
    it out."""
    return within(*scans(hist, num_bin, nan_type, p), p, 0.0)


class Follower(plain.Follower):
    """``gbdt_binary``'s follower with the NaN bin, the default direction in
    the partition and both scans at a node followed by its histograms. A
    tree carries ``default_left`` and ``missing_type`` beside what
    ``model_text.parse_trees`` gives (``with_decisions``)."""

    def __init__(self, X: np.ndarray, y: np.ndarray, edges: Sequence[np.ndarray],
                 params: dict) -> None:
        for key in ("lambda_l1", "max_delta_step"):
            if float(params.get(key, 0.0)) != 0.0:
                raise ValueError("the reference does not cover %s != 0" % key)
        if params.get("zero_as_missing") or not params.get("use_missing", True):
            raise ValueError("the reference covers NaN as the missing value alone")
        self.params = params
        self.rows, self.features = X.shape
        self.edges64 = [np.asarray(e, np.float64) for e in edges]
        self.num_bin = np.array([len(e) for e in edges], np.int64)
        self.nan_type = np.array([bool(len(e)) and bool(np.isnan(e[-1])) for e in self.edges64])
        self.nan_bin = np.where(self.nan_type, self.num_bin - 1, NO_BIN)
        self.width = int(max(self.num_bin.max(), 2))
        self.bins = bin_rows(X, [plain.floor_float32(real_edges(e)) for e in self.edges64],
                             self.nan_type)
        self.bin_width = bin_width(X[:BIN_WIDTH_ROWS], self.edges64, params["max_bin"])
        self.applied = np.zeros(self.rows, np.float32)
        self.y = np.asarray(y, np.float64)
        mean = float(self.y.mean())
        self.init_score = float(np.log(mean / (1.0 - mean)))
        self.scores = np.full(self.rows, self.init_score, np.float64)

    def threshold_bins(self, tree: dict) -> np.ndarray:
        """The bin each threshold of the tree closes, -1 where it is no edge
        that a scan of its feature offers as a threshold."""
        out = np.full(len(tree["threshold"]), -1, np.int64)
        for i, (f, t) in enumerate(zip(tree["split_feature"], tree["threshold"])):
            e = real_edges(self.edges64[int(f)])
            t = np.inf if t >= AVOID_INF else t
            b = int(np.searchsorted(e, t, side="left"))
            # a NaN feature's last real bin parts the valued from the missing
            if b < len(e) - (0 if self.nan_type[int(f)] else 1) and e[b] == t:
                out[i] = b
        return out

    def misnamed(self, tree: dict) -> int:
        """Nodes whose ``decision_type`` is categorical or names another
        missing type than the feature's edges show."""
        want = np.where(self.nan_type[tree["split_feature"]], NAN_TYPE, 0)
        return int(np.sum((tree["missing_type"] != want) | tree["categorical"]))

    def leaves(self, tree: dict, thr_bin: np.ndarray) -> np.ndarray:
        return leaf_of(self.bins, self.nan_bin, tree, thr_bin)

    def follow(self, tree: dict, is_first: bool, histograms: bool = True,
               operand_dtype: Optional[str] = None, workers: int = 8) -> Dict[str, object]:
        """``gbdt_binary``'s, and where no open leaf clearly offered a split
        (``GAIN_MARGIN``) any split that is not clearly ruled out lies 0 below
        the best."""
        out = super().follow(tree, is_first, histograms, operand_dtype, workers)
        if histograms:
            none = np.isneginf(out["frontier_best"])
            out["split_gap"] = np.where(
                none, np.where(np.isneginf(out["chosen_gain"]), np.inf, 0.0), out["split_gap"])
            if operand_dtype is not None:
                out["control"]["split_gap"] = np.where(none, 0.0, out["control"]["split_gap"])
        return out

    def _block(self, f0: int, f1: int, tree: dict, kids: np.ndarray, base: np.ndarray,
               weights: Sequence[np.ndarray], thr_bin: np.ndarray) -> dict:
        """``gbdt_binary``'s block with every candidate in both scans, taken in
        runs of ``RUN_FEATURES`` features (``_run``) and put together as
        ``gbdt_binary`` puts its blocks together."""
        W = self.width
        # a leaf's sums: the rows that have no value are not passed over, the
        # NaN bin holds what the leaf's real bins leave of them
        whole = np.stack([np.bincount(base // W, weights=w, minlength=int(tree["num_leaves"]))
                          for w in (*weights, np.ones(len(base)))])
        runs = [self._run(a, min(a + RUN_FEATURES, f1), tree, kids, base, weights, thr_bin, whole)
                for a in range(f0, f1, RUN_FEATURES)]
        out = {"best": np.max([r["best"] for r in runs], axis=0),
               "chosen": tuple(np.concatenate([r["chosen"][k] for r in runs]) for k in (0, 1))}
        if "low_best" in runs[0]:
            which = np.argmax([r["low_best"] for r in runs], axis=0)
            for key in ("low_best", "low_picked"):
                out[key] = np.array([runs[w][key][i] for i, w in enumerate(which)])
        return out

    def _run(self, f0: int, f1: int, tree: dict, kids: np.ndarray, base: np.ndarray,
             weights: Sequence[np.ndarray], thr_bin: np.ndarray, whole: np.ndarray) -> dict:
        """Features ``[f0, f1)``: the program's split is looked up in the scan
        its default direction names."""
        L = int(tree["num_leaves"])
        M, W, k = L - 1, self.width, f1 - f0
        sets = len(weights) // 2
        hist = np.empty((sets, 2 * L - 1, k, W, 3), np.float64)
        for j, f in enumerate(range(f0, f1)):
            valued = np.flatnonzero(self.bins[f] != self.nan_bin[f])
            at = base[valued] + self.bins[f][valued]
            cells = np.stack([np.bincount(at, weights=w[valued], minlength=L * W)
                              for w in weights] + [np.bincount(at, minlength=L * W)]
                             ).reshape(-1, L, W)
            if self.nan_type[f]:
                cells[:, :, self.nan_bin[f]] = whole - cells.sum(axis=2)
            for c in range(len(weights)):
                hist[c // 2, M:, j, :, c % 2] = cells[c]
            hist[:, M:, j, :, 2] = cells[-1]
        for i in range(M - 1, -1, -1):   # a child that is a node has a larger index
            hist[:, i] = hist[:, kids[i, 0]] + hist[:, kids[i, 1]]
        nb, nan_type = self.num_bin[f0:f1], self.nan_type[f0:f1]
        gain, ok, least, parent = scans(hist[0], nb, nan_type, self.params)
        offered = within(gain, ok, least, parent, self.params, 1.0)
        allowed = within(gain, ok, least, parent, self.params, -1.0)
        mine = np.flatnonzero((tree["split_feature"] >= f0) & (tree["split_feature"] < f1)
                              & (thr_bin >= 0))
        out = {
            "best": offered.transpose(1, 0, 2, 3).reshape(2 * L - 1, -1).max(axis=1),
            "chosen": (mine, allowed[tree["default_left"][mine].astype(np.int64), mine,
                                     tree["split_feature"][mine] - f0, thr_bin[mine]]),
        }
        if sets == 2:
            low = split_gains(hist[1, :M], nb, nan_type, self.params)
            low = low.transpose(1, 0, 2, 3).reshape(M, -1)
            pick = low.argmax(axis=1)
            out["low_best"] = low[np.arange(M), pick]
            out["low_picked"] = np.where(ok[:, :M], gain[:, :M], -np.inf).transpose(
                1, 0, 2, 3).reshape(M, -1)[np.arange(M), pick]
        return out


def with_decisions(tree: dict, decision_type: np.ndarray) -> dict:
    """The parsed tree with its ``decision_type`` taken apart."""
    return dict(tree, categorical=(decision_type & 1) > 0,
                default_left=(decision_type & 2) > 0, missing_type=(decision_type >> 2) & 3)


def compare(produced: dict, data: dict, edges: Sequence[np.ndarray], params: dict,
            follow: Sequence[int], control_dtype: Optional[str] = None,
            log: Callable[[str], None] = lambda msg: None) -> Dict[str, Dict[str, float]]:
    """``{"program": {number: value}, "control": {...}}`` (``NUMBERS``); the
    control's numbers only where ``control_dtype`` names the lower precision,
    and then from the trees in ``follow`` alone. The control's ``bin_width``
    is ``gbdt_binary``'s look (every row counted, the NaN bin's among them) at
    bins made four times as wide, which is what the harness's own test holds
    every cell's control to; this module's look at the same wide bins stands
    beside it as ``bin_width_valued``."""
    text, warm_scores = produced["text"], produced["warm_scores"]
    final_scores, iterations_run = produced["final_scores"], produced["iterations_run"]
    X, y = data["X"], data["y"]
    extras, per_iteration = sorted(set(data) - {"X", "y"}), model_text.trees_per_iteration(text)
    if extras or per_iteration != 1:
        raise ValueError("the reference covers one tree an iteration, grown on every row as "
                         "it stands; this run has %d and the extras %s"
                         % (per_iteration, extras))
    trees = [with_decisions(t, d) for t, d in zip(model_text.parse_trees(text), decisions(text))]
    t0 = time.perf_counter()
    ref = Follower(X, y, edges, params)
    log("reference: rows binned in %.1fs, %d of %d features with a NaN bin"
        % (time.perf_counter() - t0, int(ref.nan_type.sum()), ref.features))

    mismatch = abs(len(trees) - iterations_run)
    split_gap = leaf_value_gap = loss_gap = 0.0
    c_split = c_leaf = c_loss = 0.0
    loss_prev_p = loss_prev_r = plain.logloss(
        np.full(1, ref.init_score), np.array([np.mean(y, dtype=np.float64)]))
    for t, tree in enumerate(trees[:iterations_run]):
        if int(tree["num_leaves"]) < 2:
            mismatch += 1
            ref.add_programs(None, tree["leaf_value"])
            continue
        full = t in follow
        f = ref.follow(tree, t == 0, histograms=full,
                       operand_dtype=control_dtype if full else None)
        mismatch += int(np.sum(f["thr_bin"] < 0)) + ref.misnamed(tree)
        mismatch += int(np.sum(f["leaf_count"] != tree["leaf_count"]))
        mismatch += int(np.sum(f["internal_count"] != tree["internal_count"]))
        leaf_value_gap = max(leaf_value_gap, plain._leaf_gap(tree["leaf_value"], f["leaf_values"]))
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
        # the program's scores are seen after each warm-up iteration and at
        # the end: the loss's change over each of those stretches
        seen = (warm_scores[t] if t < len(warm_scores)
                else final_scores if t == iterations_run - 1 else None)
        if seen is not None:
            loss_r = plain.logloss(ref.scores, y)
            loss_p = plain.logloss(np.asarray(seen).reshape(-1), y)
            step_r = loss_r - loss_prev_r
            loss_gap = max(loss_gap, abs((loss_p - loss_prev_p) - step_r) / abs(step_r))
            loss_prev_p, loss_prev_r = loss_p, loss_r
        if full:
            log("reference: tree %d followed by its histograms in both directions by %.1fs"
                % (t, time.perf_counter() - t0))

    moved = np.sqrt(np.mean((ref.applied.astype(np.float64) - ref.init_score) ** 2))
    score_gap = float(np.max(np.abs(
        np.asarray(final_scores, np.float64).reshape(-1) - ref.applied)) / moved)

    log("reference: %d trees followed by %.1fs" % (len(trees), time.perf_counter() - t0))
    out = {"program": {"exact_mismatch": float(mismatch), "split_gap": split_gap,
                       "leaf_value_gap": leaf_value_gap, "loss_gap": loss_gap,
                       "score_gap": score_gap, "bin_width": float(ref.bin_width)}}
    if control_dtype is not None:
        rows = slice(plain.CONTROL_BIN_ROWS)
        wide = correct.coarser(edges)
        out["control"] = {
            "split_gap": c_split, "leaf_value_gap": c_leaf, "loss_gap": c_loss,
            "bin_width": float(plain.Follower(X[rows], y[rows], wide, params).bin_width),
            "bin_width_valued": bin_width(X[rows], wide, params["max_bin"])}
    return out


def work(tree: Dict[str, np.ndarray], config: dict) -> Dict[str, float]:
    """``work.tree_work``'s count with the split scans doubled: every
    feature of this table is scanned in two directions at every split."""
    counted = work_mod.of_config(tree, config)
    splits = int(tree["num_leaves"]) - 1
    if splits < 1:
        return counted
    scan = (splits * 2 * config["features"] * (int(config["params"]["max_bin"]) + 1)
            * work_mod.SCAN_OPS_PER_BIN)
    return dict(counted, ops=counted["ops"] + float(scan))
