"""Serve-time feature-drift monitor: PSI of live traffic vs training data.

Production GBDT serving without input-drift monitoring is flying blind: the
model keeps emitting confident scores while the feature distribution walks
away from what it was trained on. This module closes that gap with ZERO
change to the jitted kernels: the packed dispatch path already converts every
incoming row to integer ranks against the model's own threshold lattice
(serve/packed.py ``model_lattice`` — the bins that decide every split), so
drift detection is a host-side bincount over tensors the server computes
anyway, accumulated on the batcher worker thread.

Per numerical feature, the monitor keeps a streaming occupancy histogram
over lattice ranks and compares it to a REFERENCE histogram via the
Population Stability Index::

    PSI(p, q) = sum_b (p_b - q_b) * ln(p_b / q_b)        (eps-smoothed)

Rule of thumb: < 0.1 stable, 0.1-0.25 moderate shift, > 0.25 major shift.
The default alert threshold is 0.2.

Reference sources, in order of preference:

  1. **Sidecar** ``<model>.drift.json`` — emitted next to the model by
     ``Booster.save_model`` under ``LIGHTGBM_TPU_DRIFT_SIDECAR=1`` (or
     explicitly via ``Booster.save_drift_reference``): the training set's
     bin occupancy mapped through the model lattice. Fingerprint-checked —
     a sidecar from a different model is ignored loudly.
  2. **Self-calibration** — absent a sidecar, the first
     ``calibration_rows`` served rows become the baseline (standard
     practice for drift monitors on loaded models whose training data is
     gone); the snapshot labels the reference ``source="self"``.

Surfaces: ``serve_drift_psi{model=,feature=}`` gauges on /metrics, the
``/drift`` endpoint (per-feature PSI + alert state), a ``warn_once`` + the
``serve_drift_alerts_total{feature=}`` counter when a feature crosses the
threshold.

Categorical features are not tracked (their codes are raw category values,
not lattice ranks — an unbounded domain PSI over a dense histogram cannot
represent); the snapshot lists them as untracked.
"""
from __future__ import annotations

import json
import math
import os
import threading
from typing import Dict, List, Optional

import numpy as np

from ..models.model_text import model_fingerprint
from ..models.tree import K_ZERO_THRESHOLD
from ..obs import registry as registry_mod
from ..obs import sanitize as sanitize_mod
from ..utils import log

ENV_DRIFT = "LIGHTGBM_TPU_DRIFT"
ENV_SIDECAR = "LIGHTGBM_TPU_DRIFT_SIDECAR"

DEFAULT_THRESHOLD = 0.2
DEFAULT_MIN_COUNT = 500
DEFAULT_CALIBRATION_ROWS = 2000
_EPS = 1e-6
SIDECAR_SUFFIX = ".drift.json"
SIDECAR_VERSION = 1


def env_enabled() -> bool:
    return os.environ.get(ENV_DRIFT, "") not in ("", "0")


def sidecar_path(model_path: str) -> str:
    return model_path + SIDECAR_SUFFIX


def drift_edges(bounds: np.ndarray) -> np.ndarray:
    """The drift-histogram bin edges for one feature: the model lattice
    WITHOUT the +/-kZeroThreshold missing-zero sentinels. The sentinels are
    the one pair of lattice edges that fall strictly INSIDE training bins
    (every real threshold IS a bin boundary), so histogramming against the
    full lattice would systematically split zero-adjacent mass differently
    between the training reference and live traffic — a structural PSI
    offset that reads as drift on perfectly in-distribution data. Merging
    the zero window keeps both sides binned identically."""
    b = np.asarray(bounds, np.float64)
    return b[(b != K_ZERO_THRESHOLD) & (b != -K_ZERO_THRESHOLD)]


def code_to_drift_bin(bounds: np.ndarray) -> np.ndarray:
    """Lookup from a full-lattice rank code (what the exact serving path
    computes per row, ``PackedEnsemble._host_codes``) to the drift bin:
    code c means x in (bounds[c-1], bounds[c]], and since the drift edges
    are a subset of the lattice every lattice cell maps into exactly one
    drift cell."""
    de = drift_edges(bounds)
    out = np.empty(len(bounds) + 1, np.int64)
    out[: len(bounds)] = np.searchsorted(de, bounds, side="left")
    out[len(bounds)] = len(de)
    return out


def psi(p_counts: np.ndarray, q_counts: np.ndarray) -> float:
    """Population Stability Index between two count histograms (same
    length); eps-smoothed so empty bins don't blow up to inf."""
    p = p_counts.astype(np.float64)
    q = q_counts.astype(np.float64)
    pt, qt = p.sum(), q.sum()
    if pt <= 0 or qt <= 0:
        return 0.0
    p = p / pt + _EPS
    q = q / qt + _EPS
    p /= p.sum()
    q /= q.sum()
    return float(np.sum((p - q) * np.log(p / q)))


class DriftMonitor:
    """Streaming per-feature occupancy vs a reference, PSI-scored.

    ``edges[f]`` is feature f's model lattice (sorted float64 thresholds);
    codes live in ``[0, len(edges[f])]`` — exactly the ranks the exact
    serving path computes in ``PackedEnsemble._host_codes``.
    """

    def __init__(
        self,
        edges: List[np.ndarray],
        is_cat: np.ndarray,
        feature_names: Optional[List[str]] = None,
        ref_counts: Optional[List[Optional[np.ndarray]]] = None,
        threshold: float = DEFAULT_THRESHOLD,
        min_count: int = DEFAULT_MIN_COUNT,
        calibration_rows: int = DEFAULT_CALIBRATION_ROWS,
        model: str = "",
        registry=None,
    ) -> None:
        self.edges = edges
        self.is_cat = np.asarray(is_cat, bool)
        F = len(edges)
        names = list(feature_names or [])
        self.feature_names = [
            names[f] if f < len(names) and names[f] else "Column_%d" % f
            for f in range(F)
        ]
        self.threshold = float(threshold)
        self.min_count = int(min_count)
        self.model = model
        self.registry = registry
        # drift histograms run over the SENTINEL-FREE lattice (see
        # drift_edges): per feature, a precomputed lookup folds the serving
        # path's full-lattice codes into drift bins
        self._drift_edges = [drift_edges(edges[f]) for f in range(F)]
        self._code_map = [code_to_drift_bin(edges[f]) for f in range(F)]
        # tracked = numerical features the model actually thresholds; a
        # never-split feature has zero drift edges (one bin — PSI is
        # identically 0, so tracking it would only report false stability)
        self.tracked = [
            f for f in range(F)
            if not self.is_cat[f] and len(self._drift_edges[f]) > 0
        ]
        self._nbins = [len(self._drift_edges[f]) + 1 for f in range(F)]
        self._lock = sanitize_mod.make_lock("serve.drift")
        tracked = set(self.tracked)
        self._live = [
            np.zeros(self._nbins[f], np.int64) if f in tracked else None
            for f in range(F)
        ]
        self._rows = 0
        self.source = "sidecar" if ref_counts is not None else "self"
        self.calibration_rows = int(calibration_rows)
        self._ref: Optional[List[Optional[np.ndarray]]] = None
        if ref_counts is not None:
            self._ref = [
                None if c is None else np.asarray(c, np.int64)
                for c in ref_counts
            ]
        self._alerted: set = set()  # mutated/read under _lock (snapshot races)
        # PSI scoring is O(tracked features x bins): run the alert check at
        # a row stride, not per batch, so a wide model's batcher thread
        # doesn't pay the full scan on every dispatch forever
        self._next_check_rows = self.min_count

    # -- accumulation (batcher worker thread; host-side only) --------------

    def observe_codes(self, codes: np.ndarray) -> None:
        """Accumulate a batch of lattice-rank codes ([N, F] int32 — the
        exact path's ``_host_codes`` output, free of extra work); each
        code folds through the per-feature lookup into its drift bin."""
        if codes.ndim != 2 or codes.shape[1] != len(self.edges):
            return
        upd = []
        for f in self.tracked:
            cmap = self._code_map[f]
            ranks = cmap[
                np.clip(codes[:, f].astype(np.int64), 0, len(cmap) - 1)
            ]
            upd.append((f, np.bincount(ranks, minlength=self._nbins[f])))
        with self._lock:
            self._rows += int(codes.shape[0])
            for f, c in upd:
                self._live[f] += c
            self._maybe_freeze_calibration()
        self._check_alerts()

    def observe_rows(self, X: np.ndarray) -> None:
        """Accumulate raw float rows (the fused path, which bins on device):
        ranks are recomputed host-side with the same float64 searchsorted
        the exact path uses. Host cost only — the dispatch is untouched."""
        X = np.asarray(X, np.float64)
        if X.ndim != 2 or X.shape[1] != len(self.edges):
            return
        upd = []
        for f in self.tracked:
            col = np.where(np.isnan(X[:, f]), 0.0, X[:, f])
            ranks = np.searchsorted(self._drift_edges[f], col, side="left")
            upd.append((f, np.bincount(ranks, minlength=self._nbins[f])))
        with self._lock:
            self._rows += int(X.shape[0])
            for f, c in upd:
                self._live[f] += c
            self._maybe_freeze_calibration()
        self._check_alerts()

    def _maybe_freeze_calibration(self) -> None:
        """Self-calibration (no sidecar): the first calibration_rows rows
        become the reference; live counters restart. Caller holds _lock."""
        if self._ref is not None or self._rows < self.calibration_rows:
            return
        self._ref = [None if c is None else c.copy() for c in self._live]
        self._live = [
            None if c is None else np.zeros_like(c) for c in self._live
        ]
        self._rows = 0
        # re-arm the alert stride with the row counter: calibration advanced
        # it past ~calibration_rows, and without the reset a shift right
        # after calibration would go unreported until that many NEW rows
        self._next_check_rows = self.min_count
        log.info(
            "drift: model %r self-calibrated on %d rows (no sidecar)"
            % (self.model, self.calibration_rows)
        )

    # -- scoring -----------------------------------------------------------

    def psi_by_feature(self) -> Dict[str, float]:
        with self._lock:
            if self._ref is None:
                return {}
            pairs = [
                (f, self._live[f].copy(), self._ref[f])
                for f in self.tracked
                if self._ref[f] is not None
            ]
            rows = self._rows
        if rows <= 0:
            return {}
        return {
            self.feature_names[f]: round(psi(live, ref), 6)
            for f, live, ref in pairs
        }

    #: alert re-check stride in rows once past min_count (ALERT_CHECK_EVERY)
    ALERT_CHECK_EVERY = 256

    def _check_alerts(self) -> None:
        with self._lock:
            rows = self._rows
            if rows < self._next_check_rows:
                return
            self._next_check_rows = rows + self.ALERT_CHECK_EVERY
        if rows < self.min_count:
            return
        for name, v in self.psi_by_feature().items():
            with self._lock:
                if v <= self.threshold or name in self._alerted:
                    continue
                self._alerted.add(name)
            self._count_alert(name, v)
            log.warn_once(
                "serve-drift-%s-%s" % (self.model, name),
                "drift: feature %r PSI %.3f crossed threshold %.3f on model "
                "%r over %d rows — live traffic has shifted away from the "
                "%s reference distribution"
                % (name, v, self.threshold, self.model, rows, self.source),
            )

    def _count_alert(self, name: str, value: float) -> None:
        """Record the crossing on the app registry AND the process-wide one:
        the app registry backs /metrics, while bringup artifacts embed the
        GLOBAL registry's run_report — without the mirror a reader of that
        report could never see an alert. The global PSI gauge
        holds the value AT crossing time (the app-registry gauges stay
        scrape-fresh via publish())."""
        counted = []
        for reg in (self.registry, registry_mod.REGISTRY):
            if reg is None or any(reg is c for c in counted):
                continue
            counted.append(reg)
            try:
                reg.counter("serve_drift_alerts").inc(feature=name)
                reg.gauge("serve_drift_psi").set(
                    value, model=self.model, feature=name
                )
            except Exception as e:
                log.debug("drift: alert record failed: %r" % (e,))

    def publish(self, registry=None) -> None:
        """Set serve_drift_psi{model=,feature=} gauges (scrape-time pull)."""
        reg = registry if registry is not None else self.registry
        if reg is None:
            return
        g = reg.gauge("serve_drift_psi")
        for name, v in self.psi_by_feature().items():
            g.set(v, model=self.model, feature=name)

    def snapshot(self) -> Dict[str, object]:
        """The /drift endpoint's per-model block."""
        scores = self.psi_by_feature()
        with self._lock:
            rows = self._rows
            calibrating = self._ref is None
            alerted = sorted(self._alerted)  # copy under lock: the batcher
            # thread mutates the set mid-scrape otherwise
        feats = {}
        for f in range(len(self.edges)):
            name = self.feature_names[f]
            if self.is_cat[f]:
                feats[name] = {"tracked": False, "kind": "categorical"}
                continue
            v = scores.get(name)
            feats[name] = {
                "tracked": True,
                "psi": v,
                "bins": self._nbins[f],
                "alert": bool(
                    v is not None and v > self.threshold
                    and rows >= self.min_count
                ),
            }
        return {
            "rows": rows,
            "threshold": self.threshold,
            "min_count": self.min_count,
            "source": self.source,
            "calibrating": calibrating,
            "alerts": alerted,
            "features": feats,
        }


# ---------------------------------------------------------------------------
# reference construction (train side) + sidecar IO
# ---------------------------------------------------------------------------

def reference_from_training(gbdt) -> Optional[Dict[str, object]]:
    """The train-time reference: the binned training matrix's per-feature
    occupancy, mapped into the MODEL's lattice-rank space (each training
    bin lands at the rank of its representative value — the same
    searchsorted the serving path applies to raw rows). Returns the
    JSON-able sidecar body, or None when it cannot be built (no live train
    set, or an EFB-bundled matrix whose per-feature bins are group-encoded)."""
    from .packed import model_lattice

    ds = getattr(gbdt, "train_set", None)
    if ds is None or getattr(ds, "is_bundled", False):
        return None
    trees = gbdt.trees()
    if not trees:
        return None
    F = gbdt.max_feature_idx + 1
    feat_bounds, is_cat = model_lattice(trees, F)
    occupancy = (
        gbdt.train_bin_occupancy()
        if hasattr(gbdt, "train_bin_occupancy")
        else None
    )
    names = list(ds.feature_names)
    features: List[Dict[str, object]] = []
    used = {orig: f for f, orig in enumerate(ds.used_feature_idx)}
    for orig in range(F):
        name = names[orig] if orig < len(names) else "Column_%d" % orig
        entry: Dict[str, object] = {"index": orig, "name": name}
        if is_cat[orig]:
            entry["kind"] = "categorical"
            features.append(entry)
            continue
        entry["kind"] = "numerical"
        edges = drift_edges(feat_bounds[orig])
        counts = np.zeros(len(edges) + 1, np.int64)
        f = used.get(orig)
        if f is not None and occupancy is not None:
            occ = occupancy[f]
            mapper = ds.mappers[f]
            for b, c in enumerate(occ):
                if c == 0:
                    continue
                v = mapper.bin_to_value(int(b))
                if math.isnan(v):
                    v = 0.0  # the serving path's NaN->0.0 convention
                rank = int(np.searchsorted(edges, v, side="left"))
                counts[min(rank, len(counts) - 1)] += int(c)
        else:
            # trivial (constant) feature: every training row is its one
            # value; the serving path would code the constant 0.0-ish value
            counts[int(np.searchsorted(edges, 0.0, side="left"))] = ds.num_data
        entry["counts"] = counts.tolist()
        features.append(entry)
    return {
        "version": SIDECAR_VERSION,
        "rows": int(ds.num_data),
        "num_features": F,
        "features": features,
    }


def write_sidecar(model_path: str, booster) -> Optional[str]:
    """Emit ``<model_path>.drift.json`` for the booster (stamped with the
    model fingerprint so serving can refuse a stale sidecar). Returns the
    sidecar path, or None when no reference could be built."""
    from ..resil.atomic import atomic_write_text

    body = reference_from_training(booster._gbdt)
    if body is None:
        log.warning(
            "drift: no sidecar for %r (model has no live train set, or the "
            "training matrix is EFB-bundled)" % model_path
        )
        return None
    # same bare-text fingerprint pack_booster stamps on the ensemble (no
    # pandas_categorical trailer), so the serve-side match is exact
    from ..models.model_text import save_model_to_string

    body["fingerprint"] = model_fingerprint(
        save_model_to_string(booster._gbdt, 0, -1)
    )
    path = sidecar_path(model_path)
    atomic_write_text(path, json.dumps(body))
    return path


def load_sidecar(
    model_path: str, fingerprint: str, feat_bounds: List[np.ndarray]
) -> Optional[List[Optional[np.ndarray]]]:
    """Read and validate the model's drift sidecar; returns per-feature
    reference counts aligned to ``feat_bounds`` (None entries untracked),
    or None when absent/stale/mismatched (the monitor then self-calibrates)."""
    path = sidecar_path(model_path)
    try:
        with open(path, encoding="utf-8") as fh:
            body = json.load(fh)
    except OSError:
        return None
    except ValueError:
        log.warning("drift: sidecar %r is not valid JSON; ignoring" % path)
        return None
    if body.get("fingerprint") != fingerprint:
        log.warning(
            "drift: sidecar %r was built for a different model "
            "(fingerprint mismatch); self-calibrating instead" % path
        )
        return None
    out: List[Optional[np.ndarray]] = [None] * len(feat_bounds)
    for entry in body.get("features", []):
        idx = entry.get("index")
        counts = entry.get("counts")
        if counts is None or not isinstance(idx, int):
            continue
        if (
            0 <= idx < len(feat_bounds)
            and len(counts) == len(drift_edges(feat_bounds[idx])) + 1
        ):
            out[idx] = np.asarray(counts, np.int64)
        else:
            log.warning(
                "drift: sidecar %r feature %s histogram width mismatch; "
                "feature untracked" % (path, idx)
            )
    return out


def monitor_from_model(
    ensemble,
    model_path: str,
    model_name: str = "",
    threshold: float = DEFAULT_THRESHOLD,
    min_count: int = DEFAULT_MIN_COUNT,
    calibration_rows: int = DEFAULT_CALIBRATION_ROWS,
    registry=None,
) -> DriftMonitor:
    """Build the monitor for a served model: lattice from the packed
    ensemble, reference from the sidecar when present + matching."""
    ref = load_sidecar(model_path, ensemble.fingerprint, ensemble.feat_bounds)
    return DriftMonitor(
        edges=ensemble.feat_bounds,
        is_cat=ensemble.is_cat_feat,
        feature_names=ensemble.feature_names,
        ref_counts=ref,
        threshold=threshold,
        min_count=min_count,
        calibration_rows=calibration_rows,
        model=model_name,
        registry=registry,
    )
