"""Mesh-aware distributed observability (docs/Observability.md §Distributed).

Training is multi-device and, under ``jax.distributed``, multi-process; the
registry and the flight recorder are per process. This module is what joins
them, two pieces:

 1. **Pod-wide aggregation** — :func:`snapshot` captures a
    ``MetricsRegistry`` as a JSON-able blob; :func:`gather_snapshots`
    allgathers blobs across ``jax.distributed`` processes (host-side; the
    single-host fallback is the file-based :func:`write_snapshot` /
    :func:`merge_snapshot_files` pair); :func:`merge_snapshots` folds them
    into ONE registry whose counters are the per-process SUMS and whose
    gauges keep per-process provenance labels (``process=``), rendered via
    the ordinary ``prometheus_text()`` / ``run_report()``. The Chrome-trace
    twin is ``python -m lightgbm_tpu.obs.trace merge`` (obs/trace.py).
    :func:`gather_payloads` is also the transport of the checkpoint digest
    barrier (resil/coord.py).

 2. **Shard skew** — per-shard valid row counts
    (``train_shard_rows{device=}``, published once at sharded-chunk setup,
    pure host math).

Import cost: stdlib + numpy + the obs registry module; jax is imported lazily
inside the gather, so ``flight.py`` and the merge helpers can use this module
from jax-free processes.
"""
from __future__ import annotations

import glob as glob_mod
import json
import sys
from typing import Dict, List, Optional, Tuple

import numpy as np

from ..utils import log
from . import registry as registry_mod


# ---------------------------------------------------------------------------
# process identity (jax-lazy: only consults an already-imported jax)
# ---------------------------------------------------------------------------

def process_info() -> Tuple[int, int]:
    """(process_index, process_count) — (0, 1) when jax is not imported or
    jax.distributed is uninitialized (both report through the same API)."""
    jx = sys.modules.get("jax")
    if jx is None:
        return 0, 1
    try:
        return int(jx.process_index()), int(jx.process_count())
    except Exception:
        return 0, 1


# ---------------------------------------------------------------------------
# pod-wide registry aggregation
# ---------------------------------------------------------------------------

def snapshot(registry: Optional[registry_mod.MetricsRegistry] = None) -> Dict:
    """This process's registry state as a JSON-able blob, stamped with its
    process index — the unit :func:`merge_snapshots` folds."""
    reg = registry if registry is not None else registry_mod.REGISTRY
    snap = reg.snapshot()
    idx, cnt = process_info()
    snap["process"] = idx
    snap["processes"] = cnt
    return snap


def merge_snapshots(snaps: List[Dict]) -> registry_mod.MetricsRegistry:
    """Fold per-process snapshots into ONE registry: counters SUM over
    identical (name, labels) — the merged exposition's counter values equal
    the per-process sums — while gauges (and rates, re-published as gauges)
    keep per-process provenance via an added ``process=`` label. Histogram
    summaries surface as ``{name}{stat=,process=}`` gauges plus a summed
    ``{name}_count`` counter. Render with the ordinary
    ``prometheus_text()`` / ``run_report()``."""
    merged = registry_mod.MetricsRegistry()
    for snap in snaps:
        p = str(snap.get("process", 0))
        for name, entries in (snap.get("counters") or {}).items():
            c = merged.counter(name)
            for labels, v in entries:
                c.inc(float(v), **dict(labels))
        for name, entries in (snap.get("gauges") or {}).items():
            g = merged.gauge(name)
            for labels, v in entries:
                lab = dict(labels)
                lab["process"] = p
                g.set(float(v), **lab)
        for name, rate in (snap.get("rates") or {}).items():
            merged.gauge(name).set(float(rate), process=p)
        for name, stats in (snap.get("summaries") or {}).items():
            if not stats or not stats.get("count"):
                continue
            g = merged.gauge(name)
            for key in ("p50", "p95", "p99", "max", "mean"):
                if key in stats:
                    g.set(float(stats[key]), stat=key, process=p)
            merged.counter(name + "_count").inc(float(stats["count"]))
    return merged


def merged_run_report(snaps: List[Dict]) -> Dict:
    """One run-report block for the whole pod: the merged registry's
    counters/gauges plus per-process provenance."""
    merged = merge_snapshots(snaps)
    out = merged.run_report()
    out["process_count"] = len(snaps)
    out["processes"] = sorted(int(s.get("process", 0)) for s in snaps)
    return out


def _device_allgather(rows_np: np.ndarray) -> np.ndarray:
    """All-gather one int32 row per device across the whole
    ``jax.distributed`` world; returns the full [D, W] matrix on every
    process. Rides the SAME collective machinery the data-parallel trainer
    uses (shard_map + lax.all_gather over the declared 'data' axis —
    multihost_utils.process_allgather jits on process-local arrays, which
    the CPU backend refuses)."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from ..parallel.data_parallel import shard_map

    devices = jax.devices()
    mesh = Mesh(np.array(devices), ("data",))
    sharding = NamedSharding(mesh, P("data", None))
    arr = jax.make_array_from_process_local_data(sharding, rows_np)
    fn = jax.jit(shard_map(
        lambda x: jax.lax.all_gather(x, "data", axis=0, tiled=True),
        mesh=mesh, in_specs=P("data", None), out_specs=P(),
        check_vma=False,
    ))
    out = fn(arr)
    # replicated output: every process reads its own addressable shard
    return np.asarray(out.addressable_shards[0].data)


def gather_payloads(payload: bytes) -> List[bytes]:
    """Allgather one opaque byte payload per process (host-side, over the
    ``jax.distributed`` runtime): all ranks call this collectively, all
    ranks receive the full process-ordered list. With one process (or no
    distributed init) the local payload is returned alone — the
    single-host path needs no collective. Variable-length blobs ride a
    two-phase gather (lengths first, then max-padded bytes), with each
    process's payload carried by its first local device. Also the
    transport of the checkpoint digest barrier (resil/coord.py)."""
    import jax

    world = int(jax.process_count())
    if world <= 1:
        return [payload]
    blob = np.frombuffer(bytes(payload), np.uint8)
    devices = jax.devices()
    me = int(jax.process_index())
    owner_row: Dict[int, int] = {}
    for i, d in enumerate(devices):
        owner_row.setdefault(int(d.process_index), i)
    local_rows = [
        i for i, d in enumerate(devices) if int(d.process_index) == me
    ]
    my_row = owner_row[me]

    lens_local = np.zeros((len(local_rows), 1), np.int32)
    for j, i in enumerate(local_rows):
        if i == my_row:
            lens_local[j, 0] = len(blob)
    lens_all = _device_allgather(lens_local)
    width = int(lens_all.max())

    padded = np.zeros((len(local_rows), width), np.int32)
    for j, i in enumerate(local_rows):
        if i == my_row:
            padded[j, : len(blob)] = blob.astype(np.int32)
    data_all = _device_allgather(padded)

    out: List[bytes] = []
    for p in range(world):
        row = owner_row[p]
        n = int(lens_all[row, 0])
        out.append(bytes(data_all[row, :n].astype(np.uint8)))
    return out


def gather_snapshots(snap: Optional[Dict] = None) -> List[Dict]:
    """Allgather every process's registry snapshot (the JSON round-trip
    over :func:`gather_payloads`); all ranks receive the full
    process-ordered list."""
    if snap is None:
        snap = snapshot()
    return [
        json.loads(raw.decode("utf-8"))
        for raw in gather_payloads(json.dumps(snap).encode("utf-8"))
    ]


def write_snapshot(path: str,
                   registry: Optional[registry_mod.MetricsRegistry] = None,
                   ) -> str:
    """File-based fallback for single-host multi-process runs: each process
    writes ``<path>.rank<N>.json`` and any later process (or the driver)
    merges with :func:`merge_snapshot_files`."""
    from ..resil.atomic import atomic_write_text

    idx, _ = process_info()
    out = "%s.rank%d.json" % (path, idx)
    # atomic publish: a sibling rank polling for this file must never read
    # a torn half-written blob
    atomic_write_text(out, json.dumps(snapshot(registry)) + "\n")
    return out


def merge_snapshot_files(pattern_or_paths) -> List[Dict]:
    """Load snapshot blobs from a glob pattern or explicit path list,
    ordered by recorded process index (unreadable files are skipped — a
    half-written rank must not take the merge down)."""
    if isinstance(pattern_or_paths, str):
        paths = sorted(glob_mod.glob(pattern_or_paths))
    else:
        paths = list(pattern_or_paths)
    snaps = []
    for p in paths:
        try:
            with open(p, encoding="utf-8") as fh:
                snaps.append(json.load(fh))
        except (OSError, ValueError) as e:
            log.warning("dist: skipping snapshot %r (%s)" % (p, e))
    return sorted(snaps, key=lambda s: int(s.get("process", 0)))


# ---------------------------------------------------------------------------
# shard skew
# ---------------------------------------------------------------------------

def shard_valid_counts(num_data: int, num_shards: int) -> List[int]:
    """Per-shard VALID (unpadded) row counts under the ONE padding rule
    (parallel/mesh.shard_rows: zero-padding appended at the tail, so
    trailing shards absorb it). N=1003 over 8 -> seven shards of 126 and
    one of 121."""
    n_loc = -(-num_data // num_shards)
    return [
        int(min(max(num_data - i * n_loc, 0), n_loc))
        for i in range(num_shards)
    ]


def publish_shard_rows(mesh, counts: List[int], registry=None) -> None:
    """``train_shard_rows{device=}`` gauges: how many REAL rows each mesh
    device holds. Pure host math — no device reads, no jit traces."""
    reg = registry if registry is not None else registry_mod.REGISTRY
    g = reg.gauge(
        "train_shard_rows",
        "valid (unpadded) training rows per mesh device",
    )
    for dev, cnt in zip(np.asarray(mesh.devices).flat, counts):
        g.set(float(cnt), device=str(dev))
