"""Segment profiler: tree growth as separately-dispatched, fenced sub-steps.

BENCH_r05's breakdown ends at "tree growth = 95% of the iteration" — one
opaque fused XLA program. This module re-runs that program as SIX
separately-jitted, ``block_until_ready``-fenced dispatches per split, timing
each, so device time inside tree growth finally has names:

  * ``root_init``      — per-tree setup: [N, 3] accumulands, full-N root
                         histogram, root split scan
  * ``select``         — argmax over cached per-leaf best gains (+ the
                         host sync that reads the loop condition)
  * ``partition``      — node partition: the segment-permutation split
                         (DataPartition::Split analogue)
  * ``leaf_update``    — leaf-value/tree wiring scatters + leaf aux and
                         monotone windows (the gather-based score add is
                         the separate "renew+score update" phase the
                         engine timers already record)
  * ``hist_build``     — smaller-child segment histogram
  * ``hist_subtract``  — sibling-histogram subtraction + the 2-row
                         histogram-carry commit
  * ``split_scan``     — both children's split-gain scan + candidate
                         refresh

The segmented loop is built from the SAME kernels the fused grower traces —
``ops.grow.make_bucket_kernels`` (the segment seams) plus verbatim copies
of the sequential body's wiring — and :func:`profile_growth` runs the fused
``grow_tree`` on identical inputs and asserts the final models are
BITWISE-identical, so the breakdown is proven to measure the real
computation, not a lookalike.

Scope: the sequential bucketed path (the r5 default everywhere except
spec mode's batching, whose applied-split sequence is identical by design).
Configs the segmented loop does not reproduce — CEGB, histogram pools,
forced splits, EFB bundling, masked mode, parallel learners, the native
host learner, the Pallas split kernel — are refused via
:func:`unsupported_reason`; the fused path is NEVER altered by this module.

Env gating: ``LIGHTGBM_TPU_PROF_SEGMENTS=N`` makes ``engine.train`` run N
profiling iterations after training (1 when set to a non-integer truthy
value); bench.py calls :func:`profile_growth` directly. Results land in the default registry as
``growth_segment_seconds_total{segment=...}`` gauges, in ``run_report()``
as a ``growth_segments_s`` section, and as ``prof.*`` Chrome-trace spans
whenever the obs tracer is live (docs/Observability.md).
"""
from __future__ import annotations

import os
import threading
import time
from typing import Dict, Optional

import numpy as np

from ..utils.log import LightGBMError
from . import registry as registry_mod
from . import sanitize as sanitize_mod
from . import trace as trace_mod

ENV_SEGMENTS = "LIGHTGBM_TPU_PROF_SEGMENTS"

#: the per-split segments (root_init/select ride alongside)
CORE_SEGMENTS = (
    "partition", "leaf_update", "hist_build", "hist_subtract", "split_scan",
)


def segments_enabled() -> bool:
    return os.environ.get(ENV_SEGMENTS, "") not in ("", "0")


def segments_iters(default: int = 1) -> int:
    """Profiling-iteration count from the env var (``=3`` -> 3 iterations;
    any non-integer truthy value -> ``default``)."""
    raw = os.environ.get(ENV_SEGMENTS, "")
    try:
        return max(int(raw), 1)
    except ValueError:
        return default


class SegmentBook:
    """Accumulated seconds/counts per segment name (thread-safe)."""

    def __init__(self) -> None:
        self.seconds: Dict[str, float] = {}
        self.counts: Dict[str, int] = {}
        self._lock = sanitize_mod.make_lock("obs.prof.segments")

    def add(self, name: str, dt: float) -> None:
        with self._lock:
            self.seconds[name] = self.seconds.get(name, 0.0) + dt
            self.counts[name] = self.counts.get(name, 0) + 1

    def merge(self, other: "SegmentBook") -> None:
        with other._lock:
            items = list(other.seconds.items())
            counts = dict(other.counts)
        with self._lock:
            for k, v in items:
                self.seconds[k] = self.seconds.get(k, 0.0) + v
                self.counts[k] = self.counts.get(k, 0) + counts.get(k, 0)

    def reset(self) -> None:
        with self._lock:
            self.seconds.clear()
            self.counts.clear()


#: process-wide accumulator (every profile_growth run merges in)
SEGMENTS = SegmentBook()

#: the most recent profile_growth record — run_report()'s
#: ``growth_segments_s`` section reads it
_LAST_RECORD: Dict[str, object] = {}
_SECTION_REGISTERED = False


def _report_section():
    return dict(_LAST_RECORD.get("segments_per_tree_s") or {})


def unsupported_reason(gbdt) -> Optional[str]:
    """Why the segmented profiler cannot reproduce this trainer's grower
    bitwise (None = supported). Mirrors the gates grow_tree itself keys
    on; anything here runs the fused path untouched."""
    cfg = getattr(gbdt, "config", None)
    if cfg is None or getattr(gbdt, "train_set", None) is None:
        return "no training setup (loaded model?)"
    if gbdt.objective is None:
        return "custom objective (host-computed gradients)"
    if gbdt.train_set.num_features <= 0:
        return "no usable features"
    if cfg.num_leaves <= 1:
        return "num_leaves <= 1 grows no splits"
    if gbdt._learner_kind() != "serial":
        return "parallel learner (%s)" % gbdt._learner_kind()
    from ..ops import grow_native

    if (
        grow_native.unsupported_reason(
            cfg, gbdt.feature_meta, gbdt._forced_splits, gbdt.cegb_params,
            gbdt.num_bins, gbdt.num_group_bins,
        )
        is None
    ):
        return "native host learner in use (device_type=cpu)"
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


# --------------------------------------------------------------------------
# segment kernels: jitted sub-steps mirroring grow_tree's sequential body
# --------------------------------------------------------------------------

def _build_kernels(gbdt):
    """Build (once per trainer) the jitted segment functions. Bodies mirror
    grow_tree's sequential bucketed path op for op — the partition and
    segment-histogram kernels are literally shared via make_bucket_kernels,
    and profile_growth's bitwise check pins the rest."""
    import functools

    import jax
    import jax.numpy as jnp

    from ..ops.grow import (
        PackedBest,
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
    from ..ops.histogram import leaf_histogram, leaf_values
    from ..ops.split import calculate_leaf_output, find_best_split

    cfg = gbdt.config
    bins = gbdt.bins_dev
    bins_nf = gbdt.bins_dev_nf
    feature_meta = gbdt.feature_meta
    params = gbdt.split_params
    two_way = gbdt._two_way
    M = cfg.num_leaves
    B = gbdt.num_bins
    N = bins.shape[1]
    max_depth = cfg.max_depth
    chunk = cfg.tpu_hist_chunk
    hist_dtype = cfg.tpu_hist_dtype
    # the run's FROZEN histogram route: the segmented kernels must resolve
    # every shape class to the same impl the fused grower traced, or the
    # bitwise-identity proof below would compare different arithmetic
    hist_route = getattr(gbdt, "_hist_route", None)
    f32 = jnp.float32
    neg_inf = jnp.float32(-jnp.inf)
    mono_arr = feature_meta["monotone"].astype(jnp.int32)

    kern = make_bucket_kernels(
        bins, feature_meta, B, num_group_bins=None, bins_nf=bins_nf,
        chunk=chunk, hist_dtype=hist_dtype, kb=0, hist_route=hist_route,
    )

    def depth_gate(gain, depth):
        if max_depth > 0:
            return jnp.where(depth >= max_depth, neg_inf, gain)
        return gain

    def best_scan(hist2, sg2, sh2, nd2, mn2, mx2, fmask):
        return jax.vmap(
            lambda h, sg, sh, nd, mn, mx: find_best_split(
                h, sg, sh, nd, mn, mx, feature_meta, fmask, params,
                two_way=two_way,
            )
        )(hist2, sg2, sh2, nd2, mn2, mx2)

    def root_fn(grad, hess, bag_mask, fmask):
        vals_all = leaf_values(grad, hess, bag_mask)
        root_hist = leaf_histogram(
            bins, vals_all, B, chunk=chunk, hist_dtype=hist_dtype,
            route=hist_route,
        )
        root_g = jnp.sum(grad * bag_mask)
        root_h = jnp.sum(hess * bag_mask)
        root_n = jnp.sum(bag_mask)
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
                [jnp.full((M, 1), -1, jnp.int32), jnp.zeros((M, 1), jnp.int32)],
                axis=1,
            ),
        )
        hist0 = jnp.zeros((M, bins.shape[0], B, 3), f32).at[0].set(root_hist)
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
        row = _pack_best(root_split)
        f0 = jnp.zeros((M, row.f.shape[-1]), f32).at[:, 0].set(-jnp.inf)
        best0 = PackedBest(
            f0.at[0].set(row.f),
            jnp.zeros((M, len(_BEST_I)), jnp.int32).at[0].set(row.i),
            jnp.zeros((M, row.b.shape[-1]), bool).at[0].set(row.b),
        )
        order0 = jnp.arange(N, dtype=jnp.int32)
        leaf_begin0 = jnp.zeros((M,), jnp.int32)
        leaf_phys0 = jnp.zeros((M,), jnp.int32).at[0].set(N)
        return vals_all, tree0, best0, laux0, hist0, order0, leaf_begin0, leaf_phys0

    def select_fn(best_f):
        return (
            jnp.argmax(best_f[:, 0]).astype(jnp.int32),
            jnp.max(best_f[:, 0]),
        )

    def partition_fn(order, leaf_begin, leaf_phys, best_i, best_b,
                     best_leaf, new_leaf):
        f = best_i[best_leaf, 0]
        thr = best_i[best_leaf, 1]
        dleft = best_b[best_leaf, 0]
        member = best_b[best_leaf, 1:]
        pbegin = leaf_begin[best_leaf]
        pphys = leaf_phys[best_leaf]
        order2, left_cnt, _ = kern.partition_batch(
            order, pbegin[None], pphys[None], f[None], thr[None],
            dleft[None], member[None],
        )
        left_phys = left_cnt[0]
        right_phys = pphys - left_phys
        leaf_begin2 = leaf_begin.at[new_leaf].set(pbegin + left_phys)
        leaf_phys2 = (
            leaf_phys.at[best_leaf].set(left_phys).at[new_leaf].set(right_phys)
        )
        return order2, leaf_begin2, leaf_phys2

    def wiring_fn(tree, laux, best_f, best_i, best_b, best_leaf, new_leaf):
        # exactly apply_split's tree-wiring + leaf-aux block (ops/grow.py)
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

    def hist_fn(vals_all, order, leaf_begin, leaf_phys, best_f, best_leaf,
                new_leaf):
        pbegin = leaf_begin[best_leaf]
        left_phys = leaf_phys[best_leaf]
        right_phys = leaf_phys[new_leaf]
        left_smaller = best_f[best_leaf, 3] <= best_f[best_leaf, 6]
        small_begin = jnp.where(left_smaller, pbegin, pbegin + left_phys)
        small_cnt = jnp.where(left_smaller, left_phys, right_phys)
        return kern.segment_histogram_batch(
            vals_all, order, small_begin[None], small_cnt[None]
        )[0]

    def subtract_fn(hist, small_hist, best_f, best_leaf, new_leaf):
        left_smaller = best_f[best_leaf, 3] <= best_f[best_leaf, 6]
        small_idx = jnp.where(left_smaller, best_leaf, new_leaf)
        large_idx = jnp.where(left_smaller, new_leaf, best_leaf)
        parent_hist = hist[best_leaf]
        large_hist = parent_hist - small_hist
        return hist.at[jnp.stack([small_idx, large_idx])].set(
            jnp.stack([small_hist, large_hist])
        )

    def scan_fn(best_fio, hist, laux, fmask, best_leaf, new_leaf, depth_child):
        best_fa, best_ia, best_ba = best_fio
        child_idx = jnp.stack([best_leaf, new_leaf])
        ch_hist = hist[child_idx]
        ch_aux = laux[child_idx]
        ch_split = best_scan(
            ch_hist, ch_aux[:, _LAUX_SG], ch_aux[:, _LAUX_SH],
            ch_aux[:, _LAUX_ND], ch_aux[:, _LAUX_MIN], ch_aux[:, _LAUX_MAX],
            fmask,
        )
        ch_gain = depth_gate(ch_split.gain, depth_child)
        pb2 = _pack_best(ch_split._replace(gain=ch_gain))
        return (
            best_fa.at[child_idx].set(pb2.f),
            best_ia.at[child_idx].set(pb2.i),
            best_ba.at[child_idx].set(pb2.b),
        )

    def final_fn(tree, order, leaf_begin, leaf_phys):
        # leaf-id reconstruction, verbatim from grow_tree's bucketed tail
        key = jnp.where(
            leaf_phys > 0,
            leaf_begin,
            N + jnp.arange(M, dtype=jnp.int32),
        )
        ordl = jnp.argsort(key)
        slot = jnp.searchsorted(
            key[ordl], jnp.arange(N, dtype=jnp.int32), side="right"
        ) - 1
        pos_leaf = ordl[jnp.clip(slot, 0, M - 1)].astype(jnp.int32)
        out_leaf_id = jnp.zeros((N,), jnp.int32).at[order].set(pos_leaf)
        return _unpack_tree(tree, M), out_leaf_id

    jit = jax.jit
    return {
        "root": jit(root_fn),
        "select": jit(select_fn),
        "partition": jit(partition_fn, donate_argnums=(0, 1, 2)),
        "wiring": jit(wiring_fn, donate_argnums=(0, 1)),
        "hist": jit(hist_fn),
        "subtract": jit(subtract_fn, donate_argnums=(0,)),
        "scan": jit(scan_fn, donate_argnums=(0,)),
        "final": jit(final_fn),
        "_meta": {
            "key": (M, N, B, max_depth, chunk, hist_dtype, two_way, params),
        },
    }


def _timed(book: SegmentBook, name: str, fn, *args):
    import jax

    with trace_mod.span("prof.%s" % name, cat="prof.segment"):
        t0 = time.perf_counter()
        out = fn(*args)
        jax.block_until_ready(out)
        book.add(name, time.perf_counter() - t0)
    return out


def segmented_grow_tree(gbdt, grad, hess, bag_mask, fmask,
                        book: Optional[SegmentBook] = None):
    """Grow ONE tree via the fenced segment dispatches; returns
    (TreeArrays, leaf_id [N]) bitwise-equal to the fused grower's, with the
    per-segment seconds accumulated into ``book`` (and SEGMENTS)."""
    reason = unsupported_reason(gbdt)
    if reason is not None:
        raise LightGBMError("segment profiler unsupported here: %s" % reason)
    cfg = gbdt.config
    key = (
        cfg.num_leaves, gbdt.bins_dev.shape[1], gbdt.num_bins, cfg.max_depth,
        cfg.tpu_hist_chunk, cfg.tpu_hist_dtype, gbdt._two_way,
        gbdt.split_params,
    )
    kernels = getattr(gbdt, "_prof_seg_kernels", None)
    if kernels is None or kernels["_meta"]["key"] != key:
        kernels = _build_kernels(gbdt)
        gbdt._prof_seg_kernels = kernels
    local = book if book is not None else SegmentBook()
    M = cfg.num_leaves

    with trace_mod.span("prof.segmented_tree", cat="prof"):
        (vals_all, tree, best, laux, hist, order, leaf_begin,
         leaf_phys) = _timed(
            local, "root_init", kernels["root"], grad, hess, bag_mask, fmask
        )
        best_f, best_i, best_b = best
        it = 0
        while it < M - 1:
            best_leaf, gain = _timed(local, "select", kernels["select"], best_f)
            if not float(np.asarray(gain)) > 0.0:
                break
            # == tree.num_leaves on the sequential path; a host int, NOT the
            # device scalar aliasing the donated tree carry (donate(a), a)
            new_leaf = it + 1
            order, leaf_begin, leaf_phys = _timed(
                local, "partition", kernels["partition"],
                order, leaf_begin, leaf_phys, best_i, best_b, best_leaf,
                new_leaf,
            )
            tree, laux, depth_child = _timed(
                local, "leaf_update", kernels["wiring"],
                tree, laux, best_f, best_i, best_b, best_leaf, new_leaf,
            )
            small_hist = _timed(
                local, "hist_build", kernels["hist"],
                vals_all, order, leaf_begin, leaf_phys, best_f, best_leaf,
                new_leaf,
            )
            hist = _timed(
                local, "hist_subtract", kernels["subtract"],
                hist, small_hist, best_f, best_leaf, new_leaf,
            )
            best_f, best_i, best_b = _timed(
                local, "split_scan", kernels["scan"],
                (best_f, best_i, best_b), hist, laux, fmask, best_leaf,
                new_leaf, depth_child,
            )
            it += 1
        ta, leaf_id = _timed(
            local, "finalize", kernels["final"], tree, order, leaf_begin,
            leaf_phys,
        )
    if book is None:
        SEGMENTS.merge(local)
    return ta, leaf_id, it, local


def _trees_equal(ta_a, lid_a, ta_b, lid_b) -> bool:
    # the work counters say how a tree was grown, not what it is
    for a, b in zip(ta_a._replace(counters=None),
                    ta_b._replace(counters=None)):
        if not np.array_equal(np.asarray(a), np.asarray(b)):
            return False
    return bool(np.array_equal(np.asarray(lid_a), np.asarray(lid_b)))


def profile_growth(booster_or_gbdt, iters: int = 2,
                   registry=None) -> Dict[str, object]:
    """Run ``iters`` profiling iterations: per iteration, grow one tree
    FUSED (timed as the reference) and once SEGMENTED (timed per segment),
    from identical inputs, and verify the two models are bitwise-identical.

    Never mutates the trainer: gradients come from the current scores, no
    tree is appended and no score is updated, so profiling can run after a
    bench/training pass without perturbing its state. Returns the record
    (also stored for run_report()'s ``growth_segments_s`` section and
    published as registry gauges). Raises LightGBMError when
    :func:`unsupported_reason` says the config cannot be segmented.
    """
    import jax

    from ..ops.grow import grow_tree, spec_batch_slots
    from ..ops.histogram import leaf_histogram
    from . import costs as costs_mod

    gbdt = getattr(booster_or_gbdt, "_gbdt", booster_or_gbdt)
    reason = unsupported_reason(gbdt)
    if reason is not None:
        raise LightGBMError("segment profiler unsupported here: %s" % reason)
    cfg = gbdt.config
    K = gbdt.num_tree_per_iteration
    grad_all, hess_all = gbdt._compute_gradients([0.0] * K)
    bag = gbdt._bag_mask
    if cfg.feature_fraction >= 1.0:
        fmask = gbdt._fmask_all
    else:
        # draw a mask WITHOUT consuming the trainer's RNG stream — the
        # never-mutates guarantee includes the feature-sampling position
        # (the checkpoint layer snapshots it for byte-identical resume)
        state = gbdt._feat_rng.get_state()
        fmask = gbdt._sample_features()
        gbdt._feat_rng.set_state(state)
    common = dict(
        num_leaves=cfg.num_leaves, max_depth=cfg.max_depth,
        num_bins=gbdt.num_bins, num_group_bins=None,
        params=gbdt.split_params, chunk=cfg.tpu_hist_chunk,
        hist_dtype=cfg.tpu_hist_dtype, hist_mode="bucketed",
        two_way=gbdt._two_way, bins_nf=gbdt.bins_dev_nf,
        hist_route=getattr(gbdt, "_hist_route", None),
    )
    from ..ops.histogram import route_rows_variant as _rrv

    kb = spec_batch_slots(
        cfg.num_leaves, hist_mode="bucketed",
        route_rows_variant=_rrv(
            getattr(gbdt, "_hist_route", None), num_bins=gbdt.num_bins,
            hist_dtype=cfg.tpu_hist_dtype,
            n_rows=int(gbdt.bins_dev.shape[1]),
        ),
    )
    book = SegmentBook()
    warm_book = SegmentBook()  # warmup pass: compiles land here, not in the record
    fused_s = 0.0
    bitwise = True
    splits_total = 0
    trees = 0
    # pass 0 is an UNTIMED warmup: it compiles the fused program and every
    # segment kernel, so the recorded seconds are steady-state device+dispatch
    # time — the quantity the 15%-of-fused acceptance bound is about
    for i in range(max(iters, 1) + 1):
        timed = i > 0
        for k in range(K if timed else 1):
            grad, hess = grad_all[k], hess_all[k]
            with trace_mod.span("prof.fused_tree", cat="prof"):
                t0 = time.perf_counter()
                ta_f, lid_f = grow_tree(
                    gbdt.bins_dev, grad, hess, bag, fmask, gbdt.feature_meta,
                    **common,
                )
                jax.block_until_ready((ta_f, lid_f))
                if timed:
                    fused_s += time.perf_counter() - t0
            ta_s, lid_s, splits, _ = segmented_grow_tree(
                gbdt, grad, hess, bag, fmask,
                book=book if timed else warm_book,
            )
            bitwise = bitwise and _trees_equal(ta_f, lid_f, ta_s, lid_s)
            if timed:
                splits_total += splits
                trees += 1
    SEGMENTS.merge(book)

    if costs_mod.enabled():
        costs_mod.COSTS.harvest(
            "ops.grow_tree", grow_tree,
            (gbdt.bins_dev, grad_all[0], hess_all[0], bag, fmask,
             gbdt.feature_meta),
            common,
        )
        costs_mod.COSTS.harvest(
            "ops.leaf_histogram", leaf_histogram,
            (gbdt.bins_dev,
             jax.ShapeDtypeStruct((gbdt.bins_dev.shape[1], 3),
                                  np.float32),
             gbdt.num_bins),
            dict(chunk=cfg.tpu_hist_chunk, hist_dtype=cfg.tpu_hist_dtype,
                 route=getattr(gbdt, "_hist_route", None)),
        )

    per_tree = {
        name: round(s / max(trees, 1), 6)
        for name, s in sorted(book.seconds.items())
    }
    seg_sum = sum(book.seconds.values()) / max(trees, 1)
    fused_per_tree = fused_s / max(trees, 1)
    record: Dict[str, object] = {
        "iters": iters,
        "trees": trees,
        "rows": int(gbdt.bins_dev.shape[1]),
        "num_leaves": int(cfg.num_leaves),
        "splits_per_tree": round(splits_total / max(trees, 1), 2),
        "grow_mode": "spec" if kb else "seq",
        "segments_per_tree_s": per_tree,
        "segment_counts": dict(sorted(book.counts.items())),
        "segment_sum_s_per_tree": round(seg_sum, 6),
        "fused_growth_s_per_tree": round(fused_per_tree, 6),
        "segment_sum_ratio": round(seg_sum / max(fused_per_tree, 1e-12), 4),
        "bitwise_identical": bool(bitwise),
    }
    _publish(record, registry)
    return record


def _publish(record: Dict[str, object], registry=None) -> None:
    global _SECTION_REGISTERED
    reg = registry if registry is not None else registry_mod.REGISTRY
    g = reg.gauge("growth_segment_seconds_total")
    for name, secs in SEGMENTS.seconds.items():
        # the serial profiler's segments are all on-device compute; the
        # sharded profiler (obs/dist.py) publishes its psum segments into
        # the same family with collective="true"
        g.set(secs, segment=name, collective="false")
    reg.gauge("growth_segment_sum_ratio").set(
        float(record.get("segment_sum_ratio") or 0.0)
    )
    reg.gauge("growth_segments_bitwise_ok").set(
        1.0 if record.get("bitwise_identical") else 0.0
    )
    _LAST_RECORD.clear()
    _LAST_RECORD.update(record)
    # register the report section on the SAME registry the gauges landed on
    # (the default registers once; a custom registry gets its own hookup)
    if reg is not registry_mod.REGISTRY:
        reg.register_report_section("growth_segments_s", _report_section)
    elif not _SECTION_REGISTERED:
        _SECTION_REGISTERED = True
        reg.register_report_section("growth_segments_s", _report_section)


def last_record() -> Dict[str, object]:
    return dict(_LAST_RECORD)


def reset() -> None:
    SEGMENTS.reset()
    _LAST_RECORD.clear()
