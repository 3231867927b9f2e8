"""A tree's histograms and candidates for a reference, by worker processes.

A reference that follows a tree by its histograms (``gbdt_binary.Follower``'s
``_block``) sums them feature by feature with ``np.bincount``, which holds the
interpreter lock, and then weighs every candidate of every node in a dozen
passes over arrays of ``nodes x features x bins``: at 255 leaves, 2000 columns
and 255 bins about 10 s a tree on a chip's host, so that a comparison that
follows several trees this way (K trees of each followed iteration, where a
model grows K an iteration) took minutes of a run's time limit. ``search``
gives ``_block``'s numbers, the same float64 operations in the same order, in
a few passes over arrays laid out a quantity at a time; ``FollowPool`` spawns
its workers once a comparison and hands each a share of the blocks of
features: the follower's binned rows through a file that every worker maps
(written once), a tree's partition, gradients and thresholds with each task.
"""
from __future__ import annotations

import multiprocessing
import os
import shutil
import tempfile
from concurrent.futures import ProcessPoolExecutor
from typing import List, Sequence

import numpy as np

from benchmarks.references import gbdt_binary as plain

# features a block: a worker holds one block's arrays at a time (about 60 MB
# at 255 leaves and 256 bins)
BLOCK_FEATURES = 8
MAX_WORKERS = 12


def search(bins: np.ndarray, num_bin: np.ndarray, width: int, params: dict, f0: int, f1: int,
           tree: dict, kids: np.ndarray, base: np.ndarray, weights: Sequence[np.ndarray],
           thr_bin: np.ndarray) -> dict:
    """``gbdt_binary.Follower._block`` of features ``[f0, f1)``: the same
    keys and values. ``bins`` is the follower's ``[F, N]`` binned rows,
    ``base`` each row's leaf times ``width``."""
    L = int(tree["num_leaves"])
    M, W, k = L - 1, width, f1 - f0
    sets = len(weights) // 2
    # [set, quantity (gradient, hessian, rows), node, feature, bin]
    hist = np.empty((sets, 3, 2 * L - 1, k, W))
    for j, f in enumerate(range(f0, f1)):
        at = base + bins[f]
        rows = np.bincount(at, minlength=L * W).reshape(L, W)
        for c, w in enumerate(weights):
            hist[c // 2, c % 2, M:, j] = np.bincount(at, weights=w, minlength=L * W).reshape(L, W)
        hist[:, 2, M:, j] = rows
    for i in range(M - 1, -1, -1):      # a child that is a node has a larger index
        hist[:, :, i] = hist[:, :, kids[i, 0]] + hist[:, :, kids[i, 1]]
    np.cumsum(hist, axis=4, out=hist)   # left of each candidate: bins <= t
    nb = num_bin[f0:f1]
    least_h = params["min_sum_hessian_in_leaf"]
    gain, ok, least = _candidates(hist[0], nb, params)
    mine = np.flatnonzero((tree["split_feature"] >= f0) & (tree["split_feature"] < f1)
                          & (thr_bin >= 0))
    at = (mine, tree["split_feature"][mine] - f0, thr_bin[mine])
    allowed = ok[at] & (least[at] >= least_h * (1 - plain.HESSIAN_MARGIN))
    out = {"chosen": (mine, np.where(allowed, gain[at], -np.inf))}
    if sets == 2:
        # what the lower precision puts first by its own sums and its own
        # reading of the constraints, judged by the reference's gain of it
        low, low_ok, low_least = _candidates(hist[1, :, :M], nb, params)
        low_ok &= low_least >= least_h
        low = np.where(low_ok, low, -np.inf).reshape(M, -1)
        pick = low.argmax(axis=1)
        out["low_best"] = low[np.arange(M), pick]
        out["low_picked"] = np.where(ok[:M], gain[:M], -np.inf).reshape(M, -1)[
            np.arange(M), pick]
    ok &= least >= least_h * (1 + plain.HESSIAN_MARGIN)
    gain[~ok] = -np.inf
    out["best"] = gain.reshape(2 * L - 1, -1).max(axis=1)
    return out


def _candidates(left: np.ndarray, num_bin: np.ndarray, p: dict):
    """``gbdt_binary.candidates`` of ``[3, nodes, F, B]`` cumulated sums:
    every candidate's gain, whether all of the constraints but the hessian
    minimum allow it, and the smaller of its two hessian sums; the gain's
    operations are ``candidates``' in its order, so its float64 rounding."""
    l2 = float(p.get("lambda_l2", 0.0))
    gl, hl, cl = left
    g, h, c = left[:, :, :1, -1:]        # every feature holds all rows
    hr = h - hl
    with np.errstate(divide="ignore", invalid="ignore"):
        gain = gl * gl
        gain /= hl + l2
        right = g - gl
        right *= right
        right /= hr + l2
        gain += right
        gain -= g ** 2 / (h + l2)
    ok = (np.arange(left.shape[3]) <= (num_bin[:, None] - 2))[None]
    ok = ok & (cl >= p["min_data_in_leaf"]) & (c - cl >= p["min_data_in_leaf"])
    ok &= gain > float(p.get("min_gain_to_split", 0.0))
    return gain, ok, np.minimum(hl, hr, out=hr)


class FollowPool:
    """Worker processes for ``follower``'s histograms; a context manager
    that removes its file and stops its workers on leaving."""

    def __init__(self, follower: plain.Follower, workers: int = 0) -> None:
        self.features = follower.features
        self.workers = workers or max(1, min(os.cpu_count() or 1, MAX_WORKERS))
        self._dir = tempfile.mkdtemp(prefix="follow_pool_")
        path = os.path.join(self._dir, "bins.npy")
        np.save(path, follower.bins)
        self._state = (path, follower.num_bin, follower.width, follower.params)
        self._pool = ProcessPoolExecutor(self.workers,
                                         mp_context=multiprocessing.get_context("spawn"))

    def blocks(self, tree: dict, kids: np.ndarray, base: np.ndarray,
               weights: Sequence[np.ndarray], thr_bin: np.ndarray) -> List[dict]:
        """``search`` of every block of features, in the features' order."""
        spans = [(a, min(a + BLOCK_FEATURES, self.features))
                 for a in range(0, self.features, BLOCK_FEATURES)]
        shares = min(self.workers, len(spans))
        futures = [self._pool.submit(_search_spans, self._state, spans[i::shares], tree,
                                     kids, base, list(weights), thr_bin)
                   for i in range(shares)]
        done = [f.result() for f in futures]
        return [done[j % shares][j // shares] for j in range(len(spans))]

    def close(self) -> None:
        self._pool.shutdown()
        shutil.rmtree(self._dir, ignore_errors=True)

    def __enter__(self) -> "FollowPool":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


def _search_spans(state: tuple, spans: Sequence[tuple], tree: dict, kids: np.ndarray,
                  base: np.ndarray, weights: List[np.ndarray], thr_bin: np.ndarray
                  ) -> List[dict]:
    """In a worker: ``search`` of each span, on the mapped rows."""
    path, num_bin, width, params = state
    bins = np.load(path, mmap_mode="r")
    return [search(bins, num_bin, width, params, f0, f1, tree, kids, base, weights, thr_bin)
            for f0, f1 in spans]
