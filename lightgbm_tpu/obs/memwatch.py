"""Device-memory accounting: snapshots at named points + shape-math attribution.

HBM is the binding resource on chip: the histogram carry, the spec-mode
right-child cache (``spec_rhist``), the ``[K, N]`` score matrix and the
packed serving tensors together decide whether a shape fits. This module
makes their footprint visible per run instead of rediscovered by advisors:

 * :func:`snapshot` — record device ``memory_stats()`` (bytes_in_use /
   peak_bytes_in_use where the backend reports them; the CPU backend
   reports None) plus the live-buffer census from ``jax.live_arrays()``
   at a named point. Training takes one post-bin (models/gbdt.py);
   serving exposes the device gauges on every /metrics scrape. Automatic per-chunk snapshots are opt-in via
   ``LIGHTGBM_TPU_MEMWATCH=1`` (``auto_snapshot``) — ``light=True`` skips
   the live-buffer walk so chunk boundaries stay cheap.
 * shape-math attribution — :func:`attribute_training` /
   :func:`attribute_packed` compute the KNOWN large carries' sizes from
   their shapes alone (hist buffer, spec_rhist, scores, bin matrix, packed
   ensemble tensors), so a memory regression names its tensor.
   tests/test_obs.py pins the shape math to the actual buffer sizes.

Registry wiring: every snapshot sets ``device_bytes_in_use`` /
``device_peak_bytes`` / ``live_buffer_bytes`` gauges on the default
registry; obs/__init__.py additionally registers ``device_peak_bytes`` as a
pull gauge so a /metrics scrape is always current. jax is imported lazily —
importing this module never touches a backend.
"""
from __future__ import annotations

import os
import threading

from . import sanitize as sanitize_mod
import time
from collections import deque
from typing import Dict, List, Optional

from . import registry as registry_mod

ENV_MEMWATCH = "LIGHTGBM_TPU_MEMWATCH"

_SNAPSHOTS: deque = deque(maxlen=256)
_LOCK = sanitize_mod.make_lock("obs.memwatch")

F32_BYTES = 4


def memwatch_enabled() -> bool:
    return os.environ.get(ENV_MEMWATCH, "") not in ("", "0")


def _device_stats() -> List[Dict[str, float]]:
    """Per-device memory_stats dicts (empty on backends that report none)."""
    import jax

    out = []
    try:
        devices = jax.local_devices()
    except RuntimeError:
        return out
    for d in devices:
        try:
            stats = d.memory_stats()
        except (AttributeError, NotImplementedError):
            stats = None
        if stats:
            out.append({
                "device": str(d),
                "bytes_in_use": int(stats.get("bytes_in_use", 0)),
                "peak_bytes_in_use": int(stats.get("peak_bytes_in_use", 0)),
                "bytes_limit": int(stats.get("bytes_limit", 0)),
            })
    return out


def live_buffer_bytes() -> Dict[str, int]:
    """Census of live device arrays: {count, bytes} via jax.live_arrays()."""
    import jax

    n = 0
    total = 0
    try:
        for a in jax.live_arrays():
            n += 1
            try:
                total += int(a.nbytes)
            except (AttributeError, TypeError):
                pass
    except RuntimeError:
        pass  # backend not initialized yet: nothing lives on it either
    return {"count": n, "bytes": total}


def peak_device_bytes() -> float:
    """Max per-device peak_bytes_in_use, falling back to the live-buffer
    total where the backend keeps no allocator stats (CPU). The /metrics
    ``device_peak_bytes`` gauge pulls this."""
    stats = _device_stats()
    if stats:
        return float(max(s["peak_bytes_in_use"] for s in stats))
    return float(live_buffer_bytes()["bytes"])


def snapshot(tag: str, registry=None, light: bool = False) -> Dict[str, object]:
    """Record device memory at a named point; returns (and stores) the record.

    ``light=True`` skips the live-buffer walk (allocator stats only) for
    points inside hot loops (per-chunk boundaries)."""
    reg = registry if registry is not None else registry_mod.REGISTRY
    rec: Dict[str, object] = {"tag": tag, "t": time.time()}
    stats = _device_stats()
    if stats:
        rec["bytes_in_use"] = max(s["bytes_in_use"] for s in stats)
        rec["peak_bytes_in_use"] = max(s["peak_bytes_in_use"] for s in stats)
        rec["devices"] = stats
    if not light:
        live = live_buffer_bytes()
        rec["live_buffer_count"] = live["count"]
        rec["live_buffer_bytes"] = live["bytes"]
        reg.gauge("live_buffer_bytes").set(live["bytes"])
    if "bytes_in_use" in rec:
        reg.gauge("device_bytes_in_use").set(rec["bytes_in_use"])
        reg.gauge("device_peak_bytes").set(rec["peak_bytes_in_use"])
    elif "live_buffer_bytes" in rec:
        # CPU backend: the live census is the only footprint signal
        reg.gauge("device_peak_bytes").set(rec["live_buffer_bytes"])
    with _LOCK:
        _SNAPSHOTS.append(rec)
    return rec


def auto_snapshot(tag: str, light: bool = False) -> Optional[Dict[str, object]]:
    """``snapshot`` gated on LIGHTGBM_TPU_MEMWATCH — the hook training code
    calls unconditionally at its named points."""
    if not memwatch_enabled():
        return None
    try:
        return snapshot(tag, light=light)
    except Exception:
        return None  # accounting must never take training down


def snapshots() -> List[Dict[str, object]]:
    with _LOCK:
        return list(_SNAPSHOTS)


def reset() -> None:
    with _LOCK:
        _SNAPSHOTS.clear()


# --------------------------------------------------------------------------
# shape-math attribution of the known large carries
# --------------------------------------------------------------------------

def hist_carry_bytes(rows: int, num_features: int, num_bins: int) -> int:
    """[rows, F, B, 3] f32 histogram carry (rows = pool slots or num_leaves)."""
    return rows * num_features * num_bins * 3 * F32_BYTES


def spec_rhist_bytes(num_leaves: int, num_features: int, num_bins: int) -> int:
    """[M, F, B, 3] f32 spec-mode right-child cache — same shape family as
    the hist carry, i.e. spec mode ~doubles the histogram-carry footprint
    (ADVICE round-5 #2). Donated across trees since the obs PR."""
    return num_leaves * num_features * num_bins * 3 * F32_BYTES


def scores_bytes(num_class: int, num_data: int) -> int:
    return num_class * num_data * F32_BYTES


def attribute_training(gbdt) -> Dict[str, object]:
    """Shape-math footprint of a GBDT trainer's resident device carries.

    Reads shapes (never data) defensively — works mid-training and on
    loaded boosters missing the training attributes."""
    out: Dict[str, object] = {}
    meta = getattr(gbdt, "feature_meta", None)
    cfg = getattr(gbdt, "config", None)
    if meta is None or cfg is None:
        return out
    F = int(meta["num_bin"].shape[0])
    B = int(getattr(gbdt, "num_bins", 0))
    M = int(cfg.num_leaves)
    slots = gbdt._hist_pool_slots()
    rows = slots if slots is not None else M
    out["hist_carry"] = {
        "shape": [rows, F, B, 3],
        "bytes": hist_carry_bytes(rows, F, B),
        "donated": getattr(gbdt, "_hist_buf", None) is not None,
    }
    from ..ops.grow import spec_batch_slots
    from ..ops.histogram import route_rows_variant

    kb = spec_batch_slots(
        M,
        hist_mode=cfg.tpu_hist_mode,
        has_lazy_cegb=gbdt.cegb_params.has_lazy,
        pooled=slots is not None and slots < M,
        cegb_on=gbdt.cegb_params.enabled,
        route_rows_variant=route_rows_variant(
            getattr(gbdt, "_hist_route", None),
            num_bins=getattr(gbdt, "num_group_bins", None) or B,
            hist_dtype=cfg.tpu_hist_dtype,
            n_rows=getattr(gbdt, "num_data", None),
        ),
    )
    if kb:
        out["spec_rhist"] = {
            "shape": [M, F, B, 3],
            "bytes": spec_rhist_bytes(M, F, B),
            "donated": getattr(gbdt, "_spec_buf", None) is not None,
            "spec_k": kb,
        }
    K = int(getattr(gbdt, "num_tree_per_iteration", 1))
    N = int(getattr(gbdt, "num_data", 0))
    out["scores"] = {"shape": [K, N], "bytes": scores_bytes(K, N)}
    # the serial learner holds the matrix in both layouts (bins_dev_nf)
    for key, attr in (("bins", "bins_dev"), ("bins_nf", "bins_dev_nf")):
        bins = getattr(gbdt, attr, None)
        if bins is not None:
            out[key] = {
                "shape": list(bins.shape), "bytes": int(bins.nbytes),
            }
    out["total_bytes"] = sum(
        v["bytes"] for v in out.values() if isinstance(v, dict)
    )
    return out


def attribute_packed(ensemble) -> Dict[str, object]:
    """Per-tensor footprint of a PackedEnsemble's device arrays."""
    packed = ensemble.packed
    fields: Dict[str, int] = {}
    total = 0
    for name, arr in zip(packed._fields, packed):
        b = int(arr.nbytes)
        fields[name] = b
        total += b
    return {
        "num_trees": int(ensemble.num_trees),
        "fields_bytes": fields,
        "total_bytes": total,
    }
