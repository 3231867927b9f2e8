"""Mesh-aware distributed observability (docs/Observability.md §Distributed).

PR 8 made training genuinely multi-device (`tree_learner=data` composes with
`device_chunk_size` via shard_map + psum), but the obs stack was single-
process and mesh-blind. This module is the distributed spine, three pieces:

 1. **Compute-vs-collective attribution** — the sharded data-parallel
    grower re-run as separately-dispatched, ``block_until_ready``-fenced
    shard_map sub-steps (the sharded twin of obs/prof.py): local histogram
    build, the ``_combine`` psum, the root grad/hess/count reduction, the
    split scan, the score-finish step. :func:`profile_sharded_growth`
    proves the segmented path bitwise-identical to the fused
    ``grow_tree_data_parallel`` program on identical inputs;
    :func:`segmented_train_chunk` drives a whole training chunk through the
    fenced dispatches (model strings AND score carries proven identical to
    the fused sharded chunk — helpers/dist_obs_smoke.py). Results land as
    ``growth_segment_seconds_total{segment=,collective=}`` gauges, a
    ``comms_fraction`` scalar, and estimated collective payload bytes
    (histogram shape × dtype, cross-checked against the live array nbytes).

 2. **Pod-wide aggregation** — :func:`snapshot` captures a
    ``MetricsRegistry`` as a JSON-able blob; :func:`gather_snapshots`
    allgathers blobs across ``jax.distributed`` processes (host-side; the
    single-host fallback is the file-based :func:`write_snapshot` /
    :func:`merge_snapshot_files` pair); :func:`merge_snapshots` folds them
    into ONE registry whose counters are the per-process SUMS and whose
    gauges keep per-process provenance labels (``process=``), rendered via
    the ordinary ``prometheus_text()`` / ``run_report()``. The Chrome-trace
    twin is ``python -m lightgbm_tpu.obs.trace merge`` (obs/trace.py).

 3. **Shard-skew and straggler detection** — per-shard valid row counts
    (``train_shard_rows{device=}``, published once at sharded-chunk setup,
    pure host math) and per-device dispatch-completion offsets
    (``train_shard_wait_seconds{device=}``, measured by fencing each output
    shard in device order — ONLY under ``LIGHTGBM_TPU_DIST_PROF=1`` or
    inside a profile run; zero overhead and zero new jit traces when off),
    with a ``warn_once`` on sustained imbalance.

Import cost: stdlib + numpy + the obs registry/trace modules; jax is
imported lazily inside the profiling entry points, so ``flight.py`` and the
merge helpers can use this module from jax-free processes.
"""
from __future__ import annotations

import glob as glob_mod
import json
import os
import sys
import threading

from . import sanitize as sanitize_mod
import time
from typing import Dict, List, Optional, Tuple

import numpy as np

from ..utils import log
from ..utils.log import LightGBMError
from . import registry as registry_mod
from . import trace as trace_mod
from .prof import SegmentBook, _trees_equal

ENV_DIST_PROF = "LIGHTGBM_TPU_DIST_PROF"

#: segments that ARE cross-device collectives — everything the ICI carries.
#: hist_combine is the HistogramSource psum (ops/histogram.py `_combine`);
#: root_reduce the root grad/hess/count scalar psums.
COLLECTIVE_SEGMENTS = frozenset({"hist_combine", "root_reduce"})

#: process-wide accumulator for sharded segment seconds (profile runs merge in)
DIST_SEGMENTS = SegmentBook()

_LAST_RECORD: Dict[str, object] = {}
_SECTION_REGISTERED = False

# comms seconds accumulated since the last flight-recorder boundary
# (flight.note_boundary drains it via take_boundary_comms)
_BOUNDARY = {"comms_s": 0.0}
_BOUNDARY_LOCK = sanitize_mod.make_lock("obs.dist.boundary")

_STRAGGLER = {"streak": 0, "calls": 0}


def _costs_enabled() -> bool:
    from . import costs as costs_mod

    return costs_mod.enabled()


def wait_profiling_enabled() -> bool:
    """True when per-device dispatch-wait fencing is requested
    (``LIGHTGBM_TPU_DIST_PROF=1``). Read per call so tests can flip it;
    the disabled cost is one environ lookup per chunk boundary."""
    return os.environ.get(ENV_DIST_PROF, "") not in ("", "0")


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


def take_boundary_comms() -> float:
    """Drain the comms-seconds accumulator (flight.note_boundary's hook:
    each chunk-boundary record carries the collective seconds the segmented
    profiler measured since the previous boundary; 0.0 when idle)."""
    with _BOUNDARY_LOCK:
        v = _BOUNDARY["comms_s"]
        _BOUNDARY["comms_s"] = 0.0
    return v


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
# shard skew + straggler detection
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


def note_dispatch_waits(arr, registry=None) -> Dict[str, float]:
    """Fence each shard of ``arr`` and record the completion offset from
    the fence start as ``train_shard_wait_seconds{device=}`` gauges. The
    offsets are observed host-side in sequence, so every fence after the
    first absorbs earlier waits — and a slow FIRST-fenced device would
    flatten the spread entirely. The fence order therefore ROTATES across
    calls (device-id order, shifted by a call counter), so a persistent
    straggler is fenced non-first on most chunks and shows up as a
    sustained spread, which warns once. Profiling mode only (the caller
    gates on :func:`wait_profiling_enabled`)."""
    import jax

    try:
        shards = sorted(arr.addressable_shards, key=lambda s: s.device.id)
    except Exception:
        return {}
    rot = _STRAGGLER["calls"] % max(len(shards), 1)
    _STRAGGLER["calls"] += 1
    shards = shards[rot:] + shards[:rot]
    reg = registry if registry is not None else registry_mod.REGISTRY
    g = reg.gauge(
        "train_shard_wait_seconds",
        "per-device dispatch-completion offset (profiling mode)",
    )
    t0 = time.perf_counter()
    waits: Dict[str, float] = {}
    for sh in shards:
        jax.block_until_ready(sh.data)
        waits[str(sh.device)] = time.perf_counter() - t0
    for dev, w in waits.items():
        g.set(w, device=dev)
    if len(waits) > 1:
        vals = sorted(waits.values())
        spread = vals[-1] - vals[0]
        if spread > 0.005 and spread > 0.5 * max(vals[0], 1e-9):
            _STRAGGLER["streak"] += 1
            if _STRAGGLER["streak"] >= 3:
                worst = max(waits, key=waits.get)
                log.warn_once(
                    "dist-straggler",
                    "sustained shard imbalance: device %s completes %.1fms "
                    "after the fastest shard (3+ consecutive dispatches); "
                    "check shard row skew (train_shard_rows) or a slow chip"
                    % (worst, spread * 1e3),
                )
        else:
            _STRAGGLER["streak"] = 0
    return waits


# ---------------------------------------------------------------------------
# sharded segment profiler (the obs/prof.py twin for the data-parallel mesh)
# ---------------------------------------------------------------------------

def sharded_unsupported_reason(gbdt) -> Optional[str]:
    """Why the sharded segment profiler cannot reproduce this trainer's
    data-parallel grower bitwise (None = supported). Mirrors
    obs/prof.unsupported_reason plus the mesh-specific gates."""
    cfg = getattr(gbdt, "config", None)
    if cfg is None or getattr(gbdt, "train_set", None) is None:
        return "no training setup (loaded model?)"
    if gbdt._learner_kind() != "data":
        return "tree_learner %r is not the mesh data-parallel learner" % (
            cfg.tree_learner,
        )
    if gbdt.objective is None:
        return "custom objective (host-computed gradients)"
    if gbdt.train_set.num_features <= 0:
        return "no usable features"
    if cfg.num_leaves <= 1:
        return "num_leaves <= 1 grows no splits"
    if cfg.tpu_hist_mode != "bucketed":
        return "hist_mode %r (segments exist only for the bucketed layout)" % (
            cfg.tpu_hist_mode,
        )
    if gbdt.cegb_params.enabled:
        return "CEGB re-ranks candidates per split (order-dependent)"
    if gbdt._forced_splits:
        return "forced-splits preamble"
    slots = gbdt._hist_pool_slots()
    if slots is not None and slots < cfg.num_leaves:
        return "histogram pool (per-split slot state)"
    if gbdt.num_group_bins is not None:
        return "EFB-bundled bins (group remap not segmented)"
    from ..ops.grow import _ENV_SPLIT_IMPL

    if _ENV_SPLIT_IMPL == "pallas":
        return "LIGHTGBM_TPU_SPLIT_IMPL=pallas (kernelized split scan)"
    return None


def _build_kernels(gbdt):
    """Jitted shard_map sub-step kernels for the data-parallel grower.

    Local-compute segments are shard_map programs with NO collectives whose
    per-shard partials come out STACKED (``P('data', ...)``); each
    collective is its own shard_map wrapping exactly the psum the fused
    program runs (the HistogramSource seam, ops/histogram.py), so the
    combined values are the identical reduction. Replicated sub-steps
    (wiring, subtraction, split scan) are plain jits on post-psum state.
    The replicated bodies mirror obs/prof.py's sequential kernels op for
    op — profile_sharded_growth's bitwise assertion pins the mirror, so
    any drift between this copy and the fused grower is a loud failure,
    never a silent mis-attribution."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from ..ops.grow import (
        PackedTree,
        _BEST_I,
        _LAUX_MAX,
        _LAUX_MIN,
        _LAUX_ND,
        _LAUX_SG,
        _LAUX_SH,
        _NODE_I_COLS,
        _pack_best,
        _unpack_tree,
        make_bucket_kernels,
    )
    from ..ops.histogram import histogram_source, leaf_histogram, leaf_values
    from ..ops.split import calculate_leaf_output, find_best_split
    from ..parallel.data_parallel import shard_map

    cfg = gbdt.config
    mesh = gbdt._mesh()
    feature_meta = gbdt.feature_meta
    meta_keys = sorted(feature_meta.keys())
    meta_vals = tuple(feature_meta[k] for k in meta_keys)
    n_meta = len(meta_keys)
    params = gbdt.split_params
    two_way = gbdt._two_way
    M = cfg.num_leaves
    B = gbdt.num_bins
    F = feature_meta["num_bin"].shape[0]
    max_depth = cfg.max_depth
    chunk = cfg.tpu_hist_chunk
    hist_dtype = cfg.tpu_hist_dtype
    # the run's FROZEN histogram route: every per-shard segment must trace
    # the exact kernels the fused data-parallel program routed to, or the
    # bitwise-identity proof against it compares different arithmetic
    hist_route = getattr(gbdt, "_hist_route", None)
    f32 = jnp.float32
    neg_inf = jnp.float32(-jnp.inf)
    mono_arr = feature_meta["monotone"].astype(jnp.int32)
    src = histogram_source("data")

    row = P("data")
    rep = P()
    col = P(None, "data")
    stk = P("data", None)

    def smap(body, in_specs, out_specs):
        return jax.jit(shard_map(
            body, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
            check_vma=False,
        ))

    # ---- root: local build, then the two collectives ---------------------
    def root_local_body(grad, hess, bag, bins_l):
        vals_all = leaf_values(grad, hess, bag)
        lhist = leaf_histogram(
            bins_l, vals_all, B, chunk=chunk, hist_dtype=hist_dtype,
            route=hist_route,
        )
        lsum = jnp.stack([
            jnp.sum(grad * bag), jnp.sum(hess * bag), jnp.sum(bag),
        ])
        n_loc = grad.shape[0]
        order0 = jnp.arange(n_loc, dtype=jnp.int32)
        lb0 = jnp.zeros((M,), jnp.int32)
        lp0 = jnp.zeros((M,), jnp.int32).at[0].set(n_loc)
        return vals_all, lhist[None], lsum[None], order0, lb0[None], lp0[None]

    root_local = smap(
        root_local_body,
        in_specs=(row, row, row, col),
        out_specs=(stk, P("data", None, None, None), stk, row, stk, stk),
    )

    # the _combine psum of ops/histogram.py as its OWN fenced dispatch:
    # each shard psums its stacked partial — the identical collective the
    # fused program's HistogramSource seam runs
    hist_combine = smap(
        lambda p: src.combine(p[0]),
        in_specs=(P("data", None, None, None),),
        out_specs=rep,
    )

    def root_reduce_body(s1):
        s = s1[0]
        return jnp.stack([
            src.combine(s[0]), src.combine(s[1]), src.combine(s[2]),
        ])

    root_reduce = smap(root_reduce_body, in_specs=(stk,), out_specs=rep)

    # ---- replicated sub-steps (post-psum state; mirror obs/prof.py) ------
    def root_setup_fn(root_hist, root_sums, fmask):
        root_g, root_h, root_n = root_sums[0], root_sums[1], root_sums[2]
        no_con_min = jnp.full((M,), -jnp.inf, f32)
        no_con_max = jnp.full((M,), jnp.inf, f32)
        tree0 = PackedTree(
            num_leaves=jnp.int32(1),
            node_f=jnp.zeros((M, 3), f32),
            node_i=jnp.zeros((M, 4), jnp.int32),
            node_b=jnp.zeros((M, 1 + B), bool),
            leaf_f=jnp.zeros((M, 3), f32).at[0].set(
                jnp.stack([
                    calculate_leaf_output(root_g, root_h, params),
                    root_n, root_h,
                ])
            ),
            leaf_i=jnp.concatenate(
                [jnp.full((M, 1), -1, jnp.int32),
                 jnp.zeros((M, 1), jnp.int32)],
                axis=1,
            ),
        )
        hist0 = jnp.zeros((M, F, B, 3), f32).at[0].set(root_hist)
        laux0 = jnp.stack(
            [
                jnp.zeros((M,), f32).at[0].set(root_g),
                jnp.zeros((M,), f32).at[0].set(root_h),
                jnp.zeros((M,), f32).at[0].set(root_n),
                no_con_min,
                no_con_max,
            ],
            axis=-1,
        )
        root_split = find_best_split(
            root_hist, root_g, root_h, root_n, no_con_min[0], no_con_max[0],
            feature_meta, fmask, params, two_way=two_way,
        )
        pk = _pack_best(root_split)
        f0 = jnp.zeros((M, pk.f.shape[-1]), f32).at[:, 0].set(-jnp.inf)
        best_f = f0.at[0].set(pk.f)
        best_i = jnp.zeros((M, len(_BEST_I)), jnp.int32).at[0].set(pk.i)
        best_b = jnp.zeros((M, pk.b.shape[-1]), bool).at[0].set(pk.b)
        return tree0, best_f, best_i, best_b, laux0, hist0

    def select_fn(best_f):
        return (
            jnp.argmax(best_f[:, 0]).astype(jnp.int32),
            jnp.max(best_f[:, 0]),
        )

    def wiring_fn(tree, laux, best_f, best_i, best_b, best_leaf, new_leaf):
        t = tree
        node = new_leaf - 1  # sequential invariant: it == num_leaves - 1
        f = best_i[best_leaf, 0]
        thr = best_i[best_leaf, 1]
        child_idx = jnp.stack([best_leaf, new_leaf])
        parent = t.leaf_i[best_leaf, 0]
        prow = jnp.where(parent >= 0, parent, M - 1)
        enc_old = -(best_leaf + 1)
        old_plc = t.node_i[prow, 2]
        old_prc = t.node_i[prow, 3]
        new_plc = jnp.where((parent >= 0) & (old_plc == enc_old), node, old_plc)
        new_prc = jnp.where((parent >= 0) & (old_prc == enc_old), node, old_prc)
        depth_child = t.leaf_i[best_leaf, 1] + 1
        parent_aux = laux[best_leaf]
        parent_value = calculate_leaf_output(
            parent_aux[_LAUX_SG], parent_aux[_LAUX_SH], params
        )
        node_i = t.node_i.at[
            jnp.stack([node, node, node, node, prow, prow]),
            _NODE_I_COLS,
        ].set(
            jnp.stack([
                f, thr, -(best_leaf + 1), -(new_leaf + 1), new_plc, new_prc,
            ])
        )
        tree2 = PackedTree(
            num_leaves=t.num_leaves + 1,
            node_f=t.node_f.at[node].set(
                jnp.stack([best_f[best_leaf, 0], parent_value,
                           parent_aux[_LAUX_ND]])
            ),
            node_i=node_i,
            node_b=t.node_b.at[node].set(best_b[best_leaf].astype(bool)),
            leaf_f=t.leaf_f.at[child_idx].set(
                jnp.stack([
                    jnp.stack([best_f[best_leaf, 7], best_f[best_leaf, 3],
                               best_f[best_leaf, 2]]),
                    jnp.stack([best_f[best_leaf, 8], best_f[best_leaf, 6],
                               best_f[best_leaf, 5]]),
                ])
            ),
            leaf_i=t.leaf_i.at[child_idx].set(
                jnp.stack([
                    jnp.stack([node, depth_child]),
                    jnp.stack([node, depth_child]),
                ])
            ),
        )
        mono_f = mono_arr[f]
        mid = (best_f[best_leaf, 7] + best_f[best_leaf, 8]) / 2.0
        pmin = parent_aux[_LAUX_MIN]
        pmax = parent_aux[_LAUX_MAX]
        l_min = jnp.where(mono_f < 0, mid, pmin)
        l_max = jnp.where(mono_f > 0, mid, pmax)
        r_min = jnp.where(mono_f > 0, mid, pmin)
        r_max = jnp.where(mono_f < 0, mid, pmax)
        laux2 = laux.at[child_idx].set(
            jnp.stack([
                jnp.stack([best_f[best_leaf, 1], best_f[best_leaf, 2],
                           best_f[best_leaf, 3], l_min, l_max]),
                jnp.stack([best_f[best_leaf, 4], best_f[best_leaf, 5],
                           best_f[best_leaf, 6], r_min, r_max]),
            ])
        )
        return tree2, laux2, depth_child

    def subtract_fn(hist, small_hist, best_f, best_leaf, new_leaf):
        left_smaller = best_f[best_leaf, 3] <= best_f[best_leaf, 6]
        small_idx = jnp.where(left_smaller, best_leaf, new_leaf)
        large_idx = jnp.where(left_smaller, new_leaf, best_leaf)
        parent_hist = hist[best_leaf]
        large_hist = parent_hist - small_hist
        return hist.at[jnp.stack([small_idx, large_idx])].set(
            jnp.stack([small_hist, large_hist])
        )

    def depth_gate(gain, depth):
        if max_depth > 0:
            return jnp.where(depth >= max_depth, neg_inf, gain)
        return gain

    def scan_fn(best_fio, hist, laux, fmask, best_leaf, new_leaf, depth_child):
        best_fa, best_ia, best_ba = best_fio
        child_idx = jnp.stack([best_leaf, new_leaf])
        ch_hist = hist[child_idx]
        ch_aux = laux[child_idx]
        ch_split = jax.vmap(
            lambda h, sg, sh, nd, mn, mx: find_best_split(
                h, sg, sh, nd, mn, mx, feature_meta, fmask, params,
                two_way=two_way,
            )
        )(ch_hist, ch_aux[:, _LAUX_SG], ch_aux[:, _LAUX_SH],
          ch_aux[:, _LAUX_ND], ch_aux[:, _LAUX_MIN], ch_aux[:, _LAUX_MAX])
        ch_gain = depth_gate(ch_split.gain, depth_child)
        pb2 = _pack_best(ch_split._replace(gain=ch_gain))
        return (
            best_fa.at[child_idx].set(pb2.f),
            best_ia.at[child_idx].set(pb2.i),
            best_ba.at[child_idx].set(pb2.b),
        )

    # ---- per-shard sub-steps (shard_map over the local row blocks) -------
    def partition_body(order, lb1, lp1, best_i, best_b, best_leaf, new_leaf,
                       bins_l, *meta_flat):
        meta = dict(zip(meta_keys, meta_flat))
        kern = make_bucket_kernels(
            bins_l, meta, B, num_group_bins=None, bins_nf=None,
            chunk=chunk, hist_dtype=hist_dtype, kb=0,
            hist_route=hist_route,
        )
        lb = lb1[0]
        lp = lp1[0]
        f = best_i[best_leaf, 0]
        thr = best_i[best_leaf, 1]
        dleft = best_b[best_leaf, 0]
        member = best_b[best_leaf, 1:]
        pbegin = lb[best_leaf]
        pphys = lp[best_leaf]
        order2, left_cnt, _ = kern.partition_batch(
            order, pbegin[None], pphys[None], f[None], thr[None],
            dleft[None], member[None],
        )
        left_phys = left_cnt[0]
        lb2 = lb.at[new_leaf].set(pbegin + left_phys)
        lp2 = lp.at[best_leaf].set(left_phys).at[new_leaf].set(
            pphys - left_phys
        )
        return order2, lb2[None], lp2[None]

    partition = smap(
        partition_body,
        in_specs=(row, stk, stk, rep, rep, rep, rep, col)
        + (rep,) * n_meta,
        out_specs=(row, stk, stk),
    )

    def hist_local_body(vals_all, order, lb1, lp1, best_f, best_leaf,
                        new_leaf, bins_l, *meta_flat):
        meta = dict(zip(meta_keys, meta_flat))
        kern = make_bucket_kernels(
            bins_l, meta, B, num_group_bins=None, bins_nf=None,
            chunk=chunk, hist_dtype=hist_dtype, kb=0,
            hist_route=hist_route,
        )
        lb = lb1[0]
        lp = lp1[0]
        pbegin = lb[best_leaf]
        left_phys = lp[best_leaf]
        right_phys = lp[new_leaf]
        # the smaller-child choice uses the GLOBAL bagged counts (best_f
        # cols 3/6) so every shard histograms the SAME child before the
        # psum; begin/count are this shard's local segment
        left_smaller = best_f[best_leaf, 3] <= best_f[best_leaf, 6]
        small_begin = jnp.where(left_smaller, pbegin, pbegin + left_phys)
        small_cnt = jnp.where(left_smaller, left_phys, right_phys)
        return kern.segment_histogram_batch(
            vals_all, order, small_begin[None], small_cnt[None]
        )

    hist_local = smap(
        hist_local_body,
        in_specs=(stk, row, stk, stk, rep, rep, rep, col) + (rep,) * n_meta,
        out_specs=P("data", None, None, None),
    )

    def final_leaf_body(order, lb1, lp1):
        # leaf-id reconstruction, verbatim from grow_tree's bucketed tail,
        # over this shard's local rows
        lb = lb1[0]
        lp = lp1[0]
        n_loc = order.shape[0]
        key = jnp.where(
            lp > 0, lb, n_loc + jnp.arange(M, dtype=jnp.int32)
        )
        ordl = jnp.argsort(key)
        slot = jnp.searchsorted(
            key[ordl], jnp.arange(n_loc, dtype=jnp.int32), side="right"
        ) - 1
        pos_leaf = ordl[jnp.clip(slot, 0, M - 1)].astype(jnp.int32)
        return jnp.zeros((n_loc,), jnp.int32).at[order].set(pos_leaf)

    final_leaf = smap(final_leaf_body, in_specs=(row, stk, stk),
                      out_specs=row)

    jit = jax.jit
    return {
        "root_local": root_local,
        "hist_combine": hist_combine,
        "root_reduce": root_reduce,
        "root_setup": jit(root_setup_fn, donate_argnums=(0,)),
        "select": jit(select_fn),
        "partition": partition,
        "wiring": jit(wiring_fn, donate_argnums=(0, 1)),
        "hist_local": hist_local,
        "subtract": jit(subtract_fn, donate_argnums=(0, 1)),
        "scan": jit(scan_fn, donate_argnums=(0,)),
        "final_tree": jit(lambda tree: _unpack_tree(tree, M)),
        "final_leaf": final_leaf,
        "_meta_vals": meta_vals,
        "_meta": {
            "key": _kernel_key(gbdt),
            # per-combine collective payload via the HistogramSource seam
            # (F x B x 3 f32 — the [F, B, 3] partial each shard psums)
            "hist_payload_bytes": src.payload_bytes((F, B, 3), 4),
        },
    }


def _kernel_key(gbdt):
    cfg = gbdt.config
    return (
        gbdt._mesh(), cfg.num_leaves, gbdt.num_bins, cfg.max_depth,
        cfg.tpu_hist_chunk, cfg.tpu_hist_dtype, gbdt._two_way,
        gbdt.split_params,
    )


def _get_kernels(gbdt):
    kernels = getattr(gbdt, "_dist_seg_kernels", None)
    if kernels is None or kernels["_meta"]["key"] != _kernel_key(gbdt):
        kernels = _build_kernels(gbdt)
        gbdt._dist_seg_kernels = kernels
    return kernels


def _timed(book: SegmentBook, name: str, fn, *args, waits=None, wait_idx=0):
    """One fenced sub-step: dispatch, (optionally) fence each shard of
    output ``wait_idx`` in device order recording per-device completion
    offsets, then block on everything. Collective segments also feed the
    flight-recorder boundary accumulator."""
    import jax

    with trace_mod.span("dist.%s" % name, cat="dist.segment"):
        t0 = time.perf_counter()
        out = fn(*args)
        if waits is not None:
            target = out[wait_idx] if isinstance(out, (tuple, list)) else out
            try:
                shards = sorted(
                    target.addressable_shards, key=lambda s: s.device.id
                )
            except Exception:
                shards = []
            for sh in shards:
                jax.block_until_ready(sh.data)
                dev = str(sh.device)
                waits[dev] = waits.get(dev, 0.0) + (time.perf_counter() - t0)
        jax.block_until_ready(out)
        dt = time.perf_counter() - t0
        book.add(name, dt)
    if name in COLLECTIVE_SEGMENTS:
        with _BOUNDARY_LOCK:
            _BOUNDARY["comms_s"] += dt
    return out


def _segmented_sharded_tree(gbdt, kernels, bins_s, grad_s, hess_s, bag_s,
                            fmask, book: SegmentBook, waits=None):
    """Grow ONE tree on the sharded inputs via the fenced shard_map
    sub-steps; returns (TreeArrays, leaf_id [Np] row-sharded, splits) —
    bitwise-equal to ``grow_tree_data_parallel`` on the same inputs."""
    meta_vals = kernels["_meta_vals"]
    M = gbdt.config.num_leaves

    with trace_mod.span("dist.segmented_tree", cat="dist"):
        vals, lhist, lsums, order, lb, lp = _timed(
            book, "root_init", kernels["root_local"],
            grad_s, hess_s, bag_s, bins_s, waits=waits, wait_idx=1,
        )
        root_hist = _timed(book, "hist_combine", kernels["hist_combine"],
                           lhist)
        root_sums = _timed(book, "root_reduce", kernels["root_reduce"],
                           lsums)
        tree, best_f, best_i, best_b, laux, hist = _timed(
            book, "root_scan", kernels["root_setup"],
            root_hist, root_sums, fmask,
        )
        it = 0
        while it < M - 1:
            best_leaf, gain = _timed(book, "select", kernels["select"],
                                     best_f)
            if not float(np.asarray(gain)) > 0.0:
                break
            new_leaf = it + 1  # sequential invariant (host int)
            order, lb, lp = _timed(
                book, "partition", kernels["partition"],
                order, lb, lp, best_i, best_b, best_leaf, new_leaf,
                bins_s, *meta_vals,
            )
            tree, laux, depth_child = _timed(
                book, "leaf_update", kernels["wiring"],
                tree, laux, best_f, best_i, best_b, best_leaf, new_leaf,
            )
            small_part = _timed(
                book, "hist_build", kernels["hist_local"],
                vals, order, lb, lp, best_f, best_leaf, new_leaf,
                bins_s, *meta_vals, waits=waits,
            )
            small_hist = _timed(book, "hist_combine",
                                kernels["hist_combine"], small_part)
            hist = _timed(
                book, "hist_subtract", kernels["subtract"],
                hist, small_hist, best_f, best_leaf, new_leaf,
            )
            best_f, best_i, best_b = _timed(
                book, "split_scan", kernels["scan"],
                (best_f, best_i, best_b), hist, laux, fmask, best_leaf,
                new_leaf, depth_child,
            )
            it += 1
        ta = _timed(book, "finalize", kernels["final_tree"], tree)
        leaf_id = _timed(book, "finalize", kernels["final_leaf"],
                         order, lb, lp)
    return ta, leaf_id, it


def segmented_train_chunk(gbdt, n: int, book: Optional[SegmentBook] = None):
    """Run up to ``n`` boosting iterations through the FENCED segmented
    sharded dispatches — the profiling twin of the fused sharded
    ``train_chunk``. Reuses the trainer's own per-iteration machinery
    (gradients, bagging stream, finish step, deferred-stop bookkeeping) so
    the trained model and score carries are bitwise-identical to the fused
    chunk path (helpers/dist_obs_smoke.py proves model strings AND score
    carries); only tree GROWTH is swapped for the segmented grower, and
    ``grad`` / ``score_finish`` are timed around the original steps.
    Returns (iterations_run, stopped). The first-ever iteration must
    already have run (it is host-side: boost_from_average)."""
    import jax

    reason = sharded_unsupported_reason(gbdt)
    if reason is None:
        reason = gbdt.device_chunk_fallback_reason()
    if reason is not None:
        raise LightGBMError(
            "segmented sharded chunk unsupported here: %s" % reason
        )
    if not gbdt._device_trees:
        raise LightGBMError(
            "segmented sharded chunk needs the sequential first iteration "
            "(run one update() first, like train_chunk does)"
        )
    local = book if book is not None else SegmentBook()
    kernels = _get_kernels(gbdt)
    orig_finish = gbdt._finish_tree
    orig_grad = gbdt._compute_gradients

    def seg_train_tree(grad_k, hess_k):
        fmask = gbdt._sample_features()
        bins_s, grad_s, hess_s, bag_s = gbdt._shard_rows(grad_k, hess_k)
        ta, leaf_id, _ = _segmented_sharded_tree(
            gbdt, kernels, bins_s, grad_s, hess_s, bag_s, fmask, local
        )
        return ta, leaf_id[: gbdt.num_data]

    def timed_finish(tree_arrays, leaf_id, k, nl_dev):
        t0 = time.perf_counter()
        out = orig_finish(tree_arrays, leaf_id, k, nl_dev)
        jax.block_until_ready(gbdt.scores)
        local.add("score_finish", time.perf_counter() - t0)
        return out

    def timed_grad(init_scores):
        t0 = time.perf_counter()
        grad, hess = orig_grad(init_scores)
        jax.block_until_ready((grad, hess))
        local.add("grad", time.perf_counter() - t0)
        return grad, hess

    gbdt._train_tree = seg_train_tree
    gbdt._finish_tree = timed_finish
    gbdt._compute_gradients = timed_grad
    done = 0
    stopped = False
    try:
        for _ in range(max(n, 1)):
            stopped = gbdt.train_one_iter()
            if stopped:
                break
            done += 1
    finally:
        # the instance attributes shadow the class methods; deleting them
        # restores the original bound methods
        for name in ("_train_tree", "_finish_tree", "_compute_gradients"):
            gbdt.__dict__.pop(name, None)
    if book is None:
        DIST_SEGMENTS.merge(local)
    return done, stopped


def profile_sharded_growth(booster_or_gbdt, iters: int = 1,
                           registry=None) -> Dict[str, object]:
    """Run ``iters`` profiling iterations on the data-parallel mesh: per
    class, grow one tree FUSED (``grow_tree_data_parallel``, timed as the
    reference) and once SEGMENTED (fenced shard_map sub-steps, timed per
    segment), from identical sharded inputs, and verify the trees are
    bitwise-identical. Never mutates the trainer. Returns the attribution
    record (``comms_fraction``, per-segment seconds, collective payload
    bytes, per-device rows/waits) and publishes the gauges."""
    import jax

    from ..parallel.data_parallel import grow_tree_data_parallel

    gbdt = getattr(booster_or_gbdt, "_gbdt", booster_or_gbdt)
    reason = sharded_unsupported_reason(gbdt)
    if reason is not None:
        raise LightGBMError(
            "sharded segment profiler unsupported here: %s" % reason
        )
    gbdt._unshard_chunk_carries()
    cfg = gbdt.config
    K = gbdt.num_tree_per_iteration
    grad_all, hess_all = gbdt._compute_gradients([0.0] * K)
    if cfg.feature_fraction >= 1.0:
        fmask = gbdt._fmask_all
    else:
        # draw WITHOUT consuming the trainer's RNG stream (obs/prof.py)
        state = gbdt._feat_rng.get_state()
        fmask = gbdt._sample_features()
        gbdt._feat_rng.set_state(state)
    mesh = gbdt._mesh()
    D = int(mesh.shape["data"])
    common = dict(
        num_leaves=cfg.num_leaves, max_depth=cfg.max_depth,
        num_bins=gbdt.num_bins, num_group_bins=gbdt.num_group_bins,
        params=gbdt.split_params, chunk=cfg.tpu_hist_chunk,
        hist_dtype=cfg.tpu_hist_dtype, hist_mode=cfg.tpu_hist_mode,
        two_way=gbdt._two_way, forced_splits=gbdt._forced_splits,
        cegb=gbdt.cegb_params, cegb_state=None,
        hist_pool_slots=gbdt._hist_pool_slots(),
    )
    kernels = _get_kernels(gbdt)
    payload = kernels["_meta"]["hist_payload_bytes"]
    book = SegmentBook()
    warm = SegmentBook()  # warmup pass: compiles land here, not the record
    waits: Dict[str, float] = {}
    fused_s = 0.0
    bitwise = True
    splits_total = 0
    trees = 0
    for i in range(max(iters, 1) + 1):
        timed = i > 0
        if i == 1:
            # the warmup pass's collective segments included their shard_map
            # COMPILES; discard them from the flight-boundary accumulator so
            # comms_s never misreports compilation as ICI time (the record's
            # seconds already exclude warmup via the separate warm book)
            take_boundary_comms()
        for k in range(K if timed else 1):
            grad_k, hess_k = grad_all[k], hess_all[k]
            bins_s, grad_s, hess_s, bag_s = gbdt._shard_rows(grad_k, hess_k)
            with trace_mod.span("dist.fused_tree", cat="dist"):
                t0 = time.perf_counter()
                ta_f, lid_f = grow_tree_data_parallel(
                    mesh, bins_s, grad_s, hess_s, bag_s, fmask,
                    gbdt.feature_meta, **common,
                )
                jax.block_until_ready((ta_f, lid_f))
                if timed:
                    fused_s += time.perf_counter() - t0
            ta_s, lid_s, splits = _segmented_sharded_tree(
                gbdt, kernels, bins_s, grad_s, hess_s, bag_s, fmask,
                book if timed else warm, waits=waits if timed else None,
            )
            bitwise = bitwise and _trees_equal(ta_f, lid_f, ta_s, lid_s)
            if timed:
                splits_total += splits
                trees += 1
    DIST_SEGMENTS.merge(book)

    if _costs_enabled():
        # LIGHTGBM_TPU_COSTS=1: put the collective's measured cost analysis
        # (flops/bytes of the psum executable) in the cost book next to the
        # shape-math payload estimate — harvest declines gracefully when
        # the backend cannot lower the sharded program ahead of time
        from . import costs as costs_mod

        F = gbdt.feature_meta["num_bin"].shape[0]
        costs_mod.COSTS.harvest(
            "dist.hist_combine", kernels["hist_combine"],
            (jax.ShapeDtypeStruct((D, int(F), gbdt.num_bins, 3),
                                  np.float32),),
        )

    per_tree = {
        name: round(s / max(trees, 1), 6)
        for name, s in sorted(book.seconds.items())
    }
    seg_sum = sum(book.seconds.values()) / max(trees, 1)
    comms = sum(
        s for n_, s in book.seconds.items() if n_ in COLLECTIVE_SEGMENTS
    ) / max(trees, 1)
    fused_per_tree = fused_s / max(trees, 1)
    counts = dict(sorted(book.counts.items()))
    hist_combines = counts.get("hist_combine", 0) / max(trees, 1)
    root_reduces = counts.get("root_reduce", 0) / max(trees, 1)
    row_counts = shard_valid_counts(gbdt.num_data, D)
    per_device = [
        {
            "device": str(dev),
            "rows": int(row_counts[i]),
            "wait_s": round(waits.get(str(dev), 0.0) / max(trees, 1), 6),
        }
        for i, dev in enumerate(np.asarray(mesh.devices).flat)
    ]
    record: Dict[str, object] = {
        "devices": D,
        "iters": iters,
        "trees": trees,
        "rows": int(gbdt.num_data),
        "num_leaves": int(cfg.num_leaves),
        "splits_per_tree": round(splits_total / max(trees, 1), 2),
        "segments_per_tree_s": per_tree,
        "segment_counts": counts,
        "collective_segments": sorted(COLLECTIVE_SEGMENTS),
        "segment_sum_s_per_tree": round(seg_sum, 6),
        "comms_s_per_tree": round(comms, 6),
        "comms_fraction": round(comms / max(seg_sum, 1e-12), 4),
        "collective_bytes_per_split": payload,
        "collective_bytes_per_tree": int(
            hist_combines * payload + root_reduces * 3 * 4
        ),
        "fused_growth_s_per_tree": round(fused_per_tree, 6),
        "segment_sum_ratio": round(seg_sum / max(fused_per_tree, 1e-12), 4),
        "bitwise_identical": bool(bitwise),
        "per_device": per_device,
    }
    publish_shard_rows(mesh, row_counts, registry=registry)
    _publish(record, book, registry)
    return record


def _report_section():
    return dict(_LAST_RECORD) if _LAST_RECORD else {}


def _publish(record: Dict[str, object], book: SegmentBook,
             registry=None) -> None:
    global _SECTION_REGISTERED
    reg = registry if registry is not None else registry_mod.REGISTRY
    g = reg.gauge("growth_segment_seconds_total")
    for name, secs in DIST_SEGMENTS.seconds.items():
        # sharded="true" keeps these entries disjoint from the serial
        # profiler's (obs/prof.py publishes the same segment names for the
        # unsharded grower; without the label the later run would clobber
        # the other's attribution)
        g.set(
            secs, segment=name, sharded="true",
            collective="true" if name in COLLECTIVE_SEGMENTS else "false",
        )
    reg.gauge("comms_fraction").set(float(record["comms_fraction"]))
    reg.gauge("dist_collective_bytes_total").set(
        float(record["collective_bytes_per_tree"]) * record["trees"]
    )
    wg = reg.gauge("train_shard_wait_seconds")
    for ent in record.get("per_device") or []:
        if ent.get("wait_s"):
            wg.set(float(ent["wait_s"]), device=ent["device"])
    _LAST_RECORD.clear()
    _LAST_RECORD.update(record)
    if reg is not registry_mod.REGISTRY:
        reg.register_report_section("dist_segments", _report_section)
    elif not _SECTION_REGISTERED:
        _SECTION_REGISTERED = True
        reg.register_report_section("dist_segments", _report_section)


def last_record() -> Dict[str, object]:
    return dict(_LAST_RECORD)


def reset() -> None:
    DIST_SEGMENTS.reset()
    _LAST_RECORD.clear()
    _STRAGGLER["streak"] = 0
    with _BOUNDARY_LOCK:
        _BOUNDARY["comms_s"] = 0.0
