"""Training flight recorder: a JSONL log of what each boosting round learned.

The system-observability tier (trace/retrace/memwatch, PR 4) answers "where
did the time go"; this module answers "what did the MODEL do": one compact
record per iteration/chunk boundary (eval-history values, wall time), one
record per materialized tree (gain totals, leaf shape, top gain features —
the same per-node ``split_gain``/counts the reference exposes in its model
text), and run-boundary events (early stop, no-split stop, resume
provenance). The file opens with a run manifest (config digest, dataset
shape + label digest, jax/backend versions) so two flight logs are diffable
without the repos that produced them.

Enablement — disabled by default, zero work when off:

  * ``LIGHTGBM_TPU_FLIGHT=<path>`` environment variable, or
  * ``flight_record=<path>`` training parameter (engine.train pops it so the
    model's parameters footer stays byte-identical with/without recording).

Recording only READS host-side state (eval tuples, materialized numpy tree
arrays, perf_counter deltas); it never touches the jitted programs, so the
final model is bitwise-identical and the retrace watchdog stays silent with
recording on (tests/test_model_obs.py proves both).

Read a log back with :func:`load` — it groups records by event kind for
programmatic diffing::

    rec = flight.load("run.jsonl")
    rec["manifest"]["config_digest"], rec["iterations"], rec["trees"]

Format: line 1 is the manifest (``event="manifest"``), every later line one
event object; ``seq`` is a monotonically increasing record index and ``t_s``
the perf_counter offset from recorder start. Torn tails (a killed run's last
partial line) are skipped by :func:`load`, never fatal — a flight log is
evidence, not state the trainer depends on.
"""
from __future__ import annotations

import hashlib
import json
import os
import threading
import time
from typing import Any, Dict, List, Optional

import numpy as np

from ..utils import log
from . import sanitize as sanitize_mod

ENV_FLIGHT = "LIGHTGBM_TPU_FLIGHT"

#: top-k gain features recorded per tree (keeps tree records compact even at
#: num_leaves=255 on wide datasets)
TREE_TOP_K = 5


def env_path() -> Optional[str]:
    """The env-gated flight-log path (read per call: tests flip it)."""
    return os.environ.get(ENV_FLIGHT) or None


class FlightRecorder:
    """One training run's JSONL event stream (thread-safe appends)."""

    def __init__(self, path: str) -> None:
        self.path = path
        self._lock = sanitize_mod.make_lock("obs.flight")
        self._seq = 0
        self._t0 = time.perf_counter()
        d = os.path.dirname(os.path.abspath(path))
        if d:
            os.makedirs(d, exist_ok=True)
        # plain buffered text; NOT the atomic publisher — a flight log is an
        # append-only event stream whose torn tail load() tolerates, and the
        # whole point is having the records a crashed run got to write
        self._fh = open(path, "w", encoding="utf-8")

    def record(self, event: str, **fields: Any) -> None:
        rec = {"event": event, "seq": 0,
               "t_s": round(time.perf_counter() - self._t0, 6)}
        rec.update(fields)
        rec["event"], rec["seq"] = event, 0  # keys win over field collisions
        with self._lock:
            rec["seq"] = self._seq
            self._seq += 1
            self._fh.write(json.dumps(rec, default=_jsonable) + "\n")

    def close(self) -> str:
        with self._lock:
            try:
                self._fh.flush()
                os.fsync(self._fh.fileno())
            except (OSError, ValueError):
                pass
            self._fh.close()
        return self.path


def _jsonable(obj):
    if isinstance(obj, (np.integer,)):
        return int(obj)
    if isinstance(obj, (np.floating,)):
        return float(obj)
    if isinstance(obj, np.ndarray):
        return obj.tolist()
    raise TypeError("flight record value %r is not JSON-serializable" % (obj,))


# ---------------------------------------------------------------------------
# module-level active recorder (engine.train scopes it per run, like trace)
# ---------------------------------------------------------------------------

_ACTIVE: Optional[FlightRecorder] = None


def active() -> Optional[FlightRecorder]:
    return _ACTIVE


def start(path: str, manifest: Dict[str, Any]) -> Optional[FlightRecorder]:
    """Open a recorder at ``path`` and write the run manifest. Returns None
    (recording stays off) when the file cannot be opened — observability
    must never fail the training run it observes."""
    global _ACTIVE
    if _ACTIVE is not None:
        # nested/overlapping train() calls: the outer run keeps the log
        log.warn_once(
            "flight-nested",
            "flight recorder already active (%s); nested run not recorded"
            % _ACTIVE.path,
        )
        return None
    try:
        rec = FlightRecorder(path)
        rec.record("manifest", **manifest)
    except OSError as e:
        log.warning("flight: cannot open %r (%s); recording disabled"
                    % (path, e))
        return None
    _ACTIVE = rec
    return rec


def stop(summary: Optional[Dict[str, Any]] = None) -> Optional[str]:
    """Write the end record (with ``summary`` fields), close, return path."""
    global _ACTIVE
    rec = _ACTIVE
    if rec is None:
        return None
    _ACTIVE = None
    try:
        rec.record("end", **(summary or {}))
        return rec.close()
    except (OSError, ValueError) as e:
        log.warning("flight: close failed: %r" % (e,))
        return rec.path


# ---------------------------------------------------------------------------
# manifest / record builders (host-side reads only)
# ---------------------------------------------------------------------------

def config_digest(config) -> str:
    """THE digest resil/checkpoint.py stamps (imported, not reimplemented),
    so a flight log and a checkpoint taken from one run agree on the config
    identity by construction."""
    from ..resil.checkpoint import _config_digest

    return _config_digest(config)


def manifest_digest(manifest: Dict[str, Any]) -> str:
    """Stable identity of one recorded run: sha1 over the sorted-key JSON of
    its manifest record (recorder bookkeeping fields excluded, so the digest
    recomputed from a flight file on disk matches the one computed from the
    in-memory manifest at train time). The continuous-training controller
    journals this next to the published model — a serving-side rollback
    decision can then name exactly which training run produced the bytes it
    is about to drop (docs/ContinuousTraining.md)."""
    body = {k: v for k, v in manifest.items()
            if k not in ("event", "seq", "t_s")}
    return hashlib.sha1(
        json.dumps(body, sort_keys=True, default=_jsonable).encode("utf-8")
    ).hexdigest()


def build_manifest(
    booster,
    num_boost_round: int,
    init_iteration: int,
    resume_from: Optional[str] = None,
    checkpoint_path: Optional[str] = None,
    parent_fingerprint: Optional[str] = None,
) -> Dict[str, Any]:
    """Run-identity header: config digest, dataset shape + label digest,
    jax/backend versions, resume provenance (PR 5 checkpoints)."""
    gbdt = booster._gbdt
    ds = gbdt.train_set
    label = getattr(ds.metadata, "label", None) if ds is not None else None
    label_digest = (
        hashlib.sha1(np.ascontiguousarray(label).tobytes()).hexdigest()[:16]
        if label is not None else ""
    )
    versions: Dict[str, str] = {}
    backend = ""
    try:
        import jax

        versions["jax"] = getattr(jax, "__version__", "")
        backend = jax.default_backend()
    except Exception as e:  # manifest must never fail the run
        log.debug("flight: backend/version probe failed: %r" % (e,))
    # THE process-identity helper (obs/dist.py) — one rank-determination
    # rule shared with the pod-wide snapshot merge
    from . import dist as dist_mod

    process_index, process_count = dist_mod.process_info()
    # mesh provenance (resil/checkpoint's ONE mesh descriptor): pod ranks'
    # flight logs are load()-joinable by iteration only if each records
    # which shard layout produced it
    mesh = None
    try:
        from ..resil.checkpoint import _mesh_desc

        mesh = _mesh_desc(gbdt)
    except Exception as e:
        log.debug("flight: mesh probe failed: %r" % (e,))
    man: Dict[str, Any] = {
        "config_digest": config_digest(gbdt.config),
        "objective": gbdt.config.objective,
        "num_class": int(gbdt.num_class),
        "num_data": int(ds.num_data) if ds is not None else 0,
        "num_features": int(ds.num_features) if ds is not None else 0,
        "num_total_features": (
            int(ds.num_total_features) if ds is not None else 0
        ),
        "label_digest": label_digest,
        "num_boost_round": int(num_boost_round),
        "init_iteration": int(init_iteration),
        "backend": backend,
        "versions": versions,
        "process_index": process_index,
        "process_count": process_count,
        "started_at": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
    }
    if mesh is not None:
        man["mesh"] = mesh
    # the frozen histogram tune route (ops/histogram.HistRoute, ISSUE 13):
    # the digest IS the run's routing identity — two flight logs with equal
    # digests trained under byte-identical kernel routing
    # (docs/HistogramRouting.md)
    route = getattr(gbdt, "_hist_route", None)
    if route is not None:
        man["hist_route_digest"] = route.digest
        man["hist_tune_source"] = route.source
    if resume_from:
        man["resume_from"] = str(resume_from)
        man["resumed_at_iteration"] = int(gbdt.iter_)
    if checkpoint_path:
        man["checkpoint_path"] = str(checkpoint_path)
    if parent_fingerprint:
        # continued training (init_model): which model this run grew from —
        # the lineage edge the serve side surfaces (docs/ContinuousTraining.md)
        man["parent_fingerprint"] = str(parent_fingerprint)
    return man


def note_boundary(
    iteration: int, done: int, dt_s: float, evaluation_result_list
) -> None:
    """One record per iteration/chunk boundary (no-op when not recording)."""
    rec = _ACTIVE
    if rec is None:
        return
    evals = [
        [str(d), str(m), float(v)]
        for (d, m, v, _b) in (evaluation_result_list or [])
    ]
    rec.record(
        "iteration", iteration=int(iteration), chunk=int(done),
        dt_s=round(float(dt_s), 6), evals=evals,
    )


def note_event(event: str, **fields: Any) -> None:
    """Run-boundary events: early_stop, no_split_stop, checkpoint, ..."""
    rec = _ACTIVE
    if rec is None:
        return
    rec.record(event, **fields)


def tree_record(tree, index: int, class_id: int) -> Dict[str, Any]:
    """Compact stats of one materialized host Tree (models/tree.py): the
    per-node split_gain / leaf shape the reference model text carries,
    reduced to totals + the top-k gain features."""
    n1 = max(tree.num_leaves - 1, 0)
    gains = np.asarray(tree.split_gain[:n1], np.float64)
    feats = np.asarray(tree.split_feature[:n1], np.int64)
    rec: Dict[str, Any] = {
        "tree": int(index),
        "class": int(class_id),
        "num_leaves": int(tree.num_leaves),
        "max_depth": int(tree.max_depth()),
        "total_gain": round(float(gains.sum()), 6) if n1 else 0.0,
        "max_gain": round(float(gains.max()), 6) if n1 else 0.0,
        "shrinkage": float(tree.shrinkage),
    }
    if n1:
        per_feat: Dict[int, float] = {}
        for f, g in zip(feats, gains):
            per_feat[int(f)] = per_feat.get(int(f), 0.0) + float(g)
        top = sorted(per_feat.items(), key=lambda kv: -kv[1])[:TREE_TOP_K]
        rec["top_gain_features"] = [[f, round(g, 6)] for f, g in top]
        leaf_counts = np.asarray(tree.leaf_count[: tree.num_leaves], np.int64)
        rec["min_leaf_count"] = int(leaf_counts.min())
        rec["max_leaf_count"] = int(leaf_counts.max())
    return rec


def finish_training(booster) -> Optional[str]:
    """Materialize the model, emit one ``tree`` record per tree and the end
    summary, close the log. Called by engine.train when recording."""
    rec = _ACTIVE
    if rec is None:
        return None
    try:
        gbdt = booster._gbdt
        trees = gbdt.trees()  # materializes (deterministic, model unchanged)
        K = max(gbdt.num_tree_per_iteration, 1)
        for i, t in enumerate(trees):
            if t is None:
                continue
            rec.record("tree", **tree_record(t, i, i % K))
        summary = {
            "num_trees": len(trees),
            "iterations": int(gbdt.current_iteration),
            "best_iteration": int(booster.best_iteration),
            "stopped": bool(getattr(gbdt, "_stopped", False)),
        }
    except Exception as e:  # recording must never fail training
        log.warning("flight: tree harvest failed: %r" % (e,))
        summary = {"error": repr(e)}
    return stop(summary)


# ---------------------------------------------------------------------------
# reader
# ---------------------------------------------------------------------------

def load(path: str) -> Dict[str, Any]:
    """Parse a flight log into {"manifest", "iterations", "trees",
    "events", "end"} for programmatic diffing. Torn trailing lines (a
    SIGKILLed run's final partial record) are skipped."""
    manifest: Dict[str, Any] = {}
    iterations: List[Dict] = []
    trees: List[Dict] = []
    events: List[Dict] = []
    end: Optional[Dict] = None
    with open(path, encoding="utf-8") as fh:
        for line in fh:
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except ValueError:
                continue  # torn tail of a crashed run
            kind = rec.get("event")
            if kind == "manifest":
                manifest = rec
            elif kind == "iteration":
                iterations.append(rec)
            elif kind == "tree":
                trees.append(rec)
            elif kind == "end":
                end = rec
            else:
                events.append(rec)
    return {
        "manifest": manifest,
        "iterations": iterations,
        "trees": trees,
        "events": events,
        "end": end,
    }
