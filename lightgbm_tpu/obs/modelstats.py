"""Model statistics: importance evolution, bin occupancy, leaf shape.

The second piece of the model/data observability tier (docs/Observability.md
§Model & data observability). Everything here is derived from HOST state —
materialized trees (models/tree.py) and the numpy binned matrix — so it never
touches the jitted programs: enabling it cannot retrace, and the trained
model is bitwise-unaffected.

Three surfaces, all pull-based and disabled by default
(``LIGHTGBM_TPU_MODELSTATS=1`` or the ``model_stats`` training parameter):

  * **importance evolution** — cumulative gain/split feature importance
    sampled along the boosting sequence (building on
    ``GBDT.feature_importance``), answering "when did feature 7 take over".
  * **train bin occupancy** — per-feature histograms of the binned training
    matrix, computed once from the host bins; the reference distribution
    the serve-time drift monitor (serve/drift.py) compares live traffic to.
  * **leaf shape** — leaf-depth and split-gain distributions over the trees.

``publish(booster)`` sets registry gauges (``model_feature_importance``,
``model_leaf_depth``, ``model_split_gain``, ``model_trees``) and registers a
``model_stats`` run-report section so run reports and /metrics carry the
same numbers.
"""
from __future__ import annotations

import os
from typing import Dict, List, Optional

import numpy as np

from ..utils import log
from . import registry as registry_mod

ENV_MODELSTATS = "LIGHTGBM_TPU_MODELSTATS"

#: features kept in the labeled importance gauges / report tables
TOP_K_FEATURES = 10
#: sample points along the boosting sequence for the evolution series
EVOLUTION_POINTS = 10


def env_enabled() -> bool:
    return os.environ.get(ENV_MODELSTATS, "") not in ("", "0")


# ---------------------------------------------------------------------------
# derivations (pure host numpy)
# ---------------------------------------------------------------------------

def importance_evolution(
    gbdt, points: int = EVOLUTION_POINTS, top_k: int = TOP_K_FEATURES
) -> List[Dict]:
    """Cumulative feature importance sampled at ``points`` iteration marks:
    ``[{"iteration": i, "gain": {feat: v, ...}, "split": {...}}, ...]``.
    One pass over the trees — O(total splits), not points x trees."""
    trees = gbdt.trees()
    K = max(gbdt.num_tree_per_iteration, 1)
    n_iter = len(trees) // K
    if n_iter == 0:
        return []
    F = gbdt.max_feature_idx + 1
    marks = sorted({
        max(1, round(n_iter * (p + 1) / points)) for p in range(points)
    })
    gain = np.zeros(F, np.float64)
    split = np.zeros(F, np.float64)
    out: List[Dict] = []
    mi = 0
    for it in range(n_iter):
        for k in range(K):
            t = trees[it * K + k]
            if t is None or t.num_leaves <= 1:
                continue
            n1 = t.num_leaves - 1
            np.add.at(gain, t.split_feature[:n1], t.split_gain[:n1].astype(np.float64))
            np.add.at(split, t.split_feature[:n1], 1.0)
        while mi < len(marks) and it + 1 == marks[mi]:
            out.append({
                "iteration": it + 1,
                "gain": _top(gain, top_k),
                "split": _top(split, top_k),
            })
            mi += 1
    return out


def _top(arr: np.ndarray, k: int) -> Dict[str, float]:
    idx = np.argsort(-arr)[:k]
    return {
        str(int(i)): round(float(arr[i]), 6) for i in idx if arr[i] > 0
    }


def train_bin_occupancy(binned) -> Optional[List[np.ndarray]]:
    """Per used-feature bin-count histograms of the training matrix, from
    the host bins (one bincount per feature — ~N*F int reads, done once).
    Returns None for EFB-bundled datasets (bins are group-encoded there;
    decoding per-feature occupancy would rebuild the bundler's remap)."""
    if binned is None or getattr(binned, "is_bundled", False):
        return None
    bins = np.asarray(binned.bins)
    out: List[np.ndarray] = []
    for f, m in enumerate(binned.mappers):
        out.append(np.bincount(bins[f].astype(np.int64), minlength=m.num_bin))
    return out


def occupancy_summary(hists: Optional[List[np.ndarray]], binned) -> List[Dict]:
    """Compact per-feature occupancy digest for the report section: bins
    used, top-bin share, normalized entropy (1.0 = uniform over used bins)."""
    if hists is None or binned is None:
        return []
    out: List[Dict] = []
    names = binned.feature_names
    for f, h in enumerate(hists):
        total = float(h.sum())
        if total <= 0:
            continue
        p = h[h > 0] / total
        ent = float(-(p * np.log(p)).sum())
        norm = float(np.log(len(p))) if len(p) > 1 else 1.0
        orig = binned.used_feature_idx[f]
        out.append({
            "feature": names[orig] if orig < len(names) else str(orig),
            "bins_used": int((h > 0).sum()),
            "num_bin": int(len(h)),
            "top_bin_share": round(float(h.max()) / total, 4),
            "entropy_ratio": round(ent / norm if norm else 1.0, 4),
        })
    return out


def leaf_stats(trees) -> Dict[str, object]:
    """Leaf-depth and split-gain distributions over the materialized trees."""
    depths: List[int] = []
    gains: List[float] = []
    leaves: List[int] = []
    for t in trees:
        if t is None or t.num_leaves <= 1:
            continue
        depths.extend(int(d) for d in t.leaf_depths())
        gains.extend(float(g) for g in t.split_gain[: t.num_leaves - 1])
        leaves.append(int(t.num_leaves))
    if not leaves:
        return {"trees_with_splits": 0}
    d = np.asarray(depths, np.float64)
    g = np.asarray(gains, np.float64)
    return {
        "trees_with_splits": len(leaves),
        "leaves_mean": round(float(np.mean(leaves)), 2),
        "depth_mean": round(float(d.mean()), 3),
        "depth_max": int(d.max()),
        "depth_p90": float(np.percentile(d, 90)),
        "gain_total": round(float(g.sum()), 4),
        "gain_max": round(float(g.max()), 4),
        "gain_p50": round(float(np.percentile(g, 50)), 6),
    }


# ---------------------------------------------------------------------------
# publication (gauges + run-report section)
# ---------------------------------------------------------------------------

def publish(booster, registry=None, top_k: int = TOP_K_FEATURES) -> Dict:
    """Compute the model-stats block ONCE, publish gauges, and register the
    ``model_stats`` run-report section over the precomputed block. The
    section closes over the (small) dict, NOT the booster: pinning the
    booster in the process-wide registry would keep its whole training set
    alive for the process lifetime and re-derive every stat per scrape.
    Returns the block for callers that embed it."""
    reg = registry if registry is not None else registry_mod.REGISTRY
    gbdt = booster._gbdt
    try:
        block = stats_block(booster, top_k=top_k)
    except Exception as e:  # observability must never fail training
        log.warning("modelstats: derivation failed: %r" % (e,))
        return {}
    names = _feature_names(gbdt)
    g_imp = reg.gauge("model_feature_importance")
    for typ in ("gain", "split"):
        for fid, v in (block.get("importance_%s_top" % typ) or {}).items():
            label = names.get(fid, fid)
            g_imp.set(v, feature=label, type=typ)
    ls = block.get("leaf_stats") or {}
    if ls.get("trees_with_splits"):
        reg.gauge("model_leaf_depth").set(ls["depth_mean"], stat="mean")
        reg.gauge("model_leaf_depth").set(ls["depth_max"], stat="max")
        reg.gauge("model_split_gain").set(ls["gain_total"], stat="total")
        reg.gauge("model_split_gain").set(ls["gain_max"], stat="max")
    reg.gauge("model_trees").set(block.get("num_trees", 0))
    reg.register_report_section("model_stats", lambda: block)
    return block


def stats_block(booster, top_k: int = TOP_K_FEATURES) -> Dict:
    """The JSON-able model_stats section (run_report / flight summary)."""
    gbdt = booster._gbdt
    trees = gbdt.trees()
    names = _feature_names(gbdt)

    def named(d: Dict[str, float]) -> Dict[str, float]:
        return {names.get(k, k): v for k, v in d.items()}

    gain = gbdt.feature_importance("gain")
    split = gbdt.feature_importance("split")
    evo = importance_evolution(gbdt, top_k=top_k)
    ds = getattr(gbdt, "train_set", None)
    occ = occupancy_summary(
        gbdt.train_bin_occupancy()
        if hasattr(gbdt, "train_bin_occupancy")
        else train_bin_occupancy(ds),
        ds,
    )
    return {
        "num_trees": len(trees),
        "importance_gain_top": named(_top(gain, top_k)),
        "importance_split_top": named(_top(split, top_k)),
        "importance_evolution": [
            dict(e, gain=named(e["gain"]), split=named(e["split"]))
            for e in evo
        ],
        "leaf_stats": leaf_stats(trees),
        "train_bin_occupancy": occ,
    }


def _feature_names(gbdt) -> Dict[str, str]:
    ds = getattr(gbdt, "train_set", None)
    names = getattr(ds, "feature_names", None) if ds is not None else None
    if not names:
        names = getattr(gbdt, "feature_names", None) or []
    return {str(i): str(n) for i, n in enumerate(names)}
