"""Jitted leaf-wise (best-first) tree growth.

TPU-native counterpart of SerialTreeLearner::Train
(/root/reference/src/treelearner/serial_tree_learner.cpp:173-237) and its split loop.
Differences from the reference are architectural, not semantic:

 * Leaf membership lives in one of two static modes. The default ``bucketed``
   mode keeps a DataPartition-style row permutation (data_partition.hpp:20):
   each split stably partitions the leaf's contiguous segment inside a
   gathered bucket from a {2^k} + {3*2^k} size lattice (``lax.switch`` over
   sizes), so per-split histogram cost tracks leaf size like the
   reference's ordered-index kernels. A row sample (bagging, GOSS, rf) is
   work saved there: the root segment is the in-bag rows, as the
   reference's learner is handed the in-bag indices (SetBaggingData), and the
   rows out of the bag get their leaf from the finished tree.
   The ``masked`` mode is the simple oracle — a per-row ``leaf_id`` vector
   updated with ``where`` and full-N masked histogram passes — kept for
   differential testing (tests/test_hist_modes.py) and for lazy-CEGB, which
   needs full-row masks.
 * The whole num_leaves-1 split loop runs inside one ``lax.while_loop`` so a tree
   trains without host round-trips.
 * The smaller/larger-leaf histogram subtraction trick (serial_tree_learner.cpp:510,
   feature_histogram.hpp:75 Subtract) is kept: per split, one masked histogram pass
   over the smaller child; the larger child's histogram is parent minus smaller.
 * Monotone-constraint windows per leaf mirror serial_tree_learner.cpp:841-850.
 * Forced splits (ForceSplits, serial_tree_learner.cpp:597-757) are a statically
   unrolled preamble: the JSON's BFS order fixes each forced split's leaf index at
   trace time; each applies under ``lax.cond`` with the reference's
   abort-on-worsening-gain semantics.
 * CEGB (cost-effective gradient boosting) penalties re-rank candidate splits; with
   coupled/lazy feature penalties the grower re-scans every leaf per iteration
   (the reference instead patches its cached splits_per_leaf_,
   serial_tree_learner.cpp:757-775 — same fixpoint, different mechanics).
   Under a histogram pool only slot-RESIDENT leaves rescan; evicted leaves
   keep their cached candidate with the reference's coupled-gain patch.
   Custom split searches (voting) supply a batched ``cegb_rescan`` hook.
 * With ``axis_name`` set (under shard_map), rows are sharded across the mesh and
   the histogram/root sums are combined with psum — the data-parallel learner's
   dataflow (data_parallel_tree_learner.cpp:149-257) collapsed onto XLA collectives.

Output is a flat-array tree in *bin space*; the host Tree object (models/tree.py)
converts thresholds to real values with the BinMappers for prediction on raw data.
"""
from __future__ import annotations

import functools
from typing import Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..obs import retrace as retrace_mod
from ..utils.platform import env_choice, env_int
from .histogram import (
    _default_backend,
    histogram_source,
    impl_supported,
    leaf_histogram,
    leaf_values,
    route_effective_impls,
)
from .split import (
    MISSING_NAN,
    MISSING_NONE,
    MISSING_ZERO,
    CegbParams,
    SplitParams,
    SplitResult,
    calculate_leaf_output,
    find_best_split,
    gather_info_for_threshold,
)


# Bucket-lattice override, resolved ONCE at import (like histogram._ENV_IMPL:
# a trace-time env read would silently keep stale routing for already-compiled
# shapes). "pow2" drops the 3·2^k family; "coarse" keeps every other power of
# two — both cap the lax.switch branch count for compile-time-sensitive runs
# (first TPU contact). Unknown values fall back to the full lattice, loudly.
_ENV_LATTICE = env_choice("LIGHTGBM_TPU_LATTICE", ("pow2", "coarse"))

# Opt-in single-launch Pallas kernel for the two-child split scan
# (ops/split_pallas.py). Mosaic refuses it on the v5e, so it runs in
# interpret mode only (split_pallas.supported()). Default: XLA scan.
_ENV_SPLIT_IMPL = env_choice("LIGHTGBM_TPU_SPLIT_IMPL", ("pallas",))

# Speculative top-k batched growth ("spec" mode): each while_loop step
# batches the partition/histogram/scan work of the top-k candidate leaves
# and applies the longest prefix the sequential gain order would have
# chosen — measured 3.7x fewer sequential loop steps at k=8 on real split
# sequences (r5 study), attacking the dominant per-split fixed cost of the
# 2026-07-31 on-chip breakdown (PERF.md §Before this round). "spec"/"seq" force the mode on
# any backend (tests use monkeypatch + clear_caches like _ENV_SPLIT_IMPL);
# the default is spec on TPU, sequential elsewhere.
_ENV_GROW = env_choice("LIGHTGBM_TPU_GROW", ("spec", "seq"))
_ENV_SPEC_K = env_int("LIGHTGBM_TPU_SPEC_K", 8, lo=2, hi=64)

# Spec-mode batched-histogram form: "flat" (one concatenated pass, each slot
# padded to whole chunks: work follows the segments' TOTAL rows) or "lanes"
# (vmapped lanes, every one at the largest computing slot's bucket: work
# follows KB x the largest segment). Default: flat where the effective
# histogram impl has a flat kernel whose sums of a segment do not depend on
# the batch: the Pallas radix kernel (hist_pallas.histogram_pallas_slots:
# a fixed unit of summation at segment-relative offsets) and the XLA one-hot
# (fixed chunk boundaries); lanes, which run the impl verbatim a lane, for
# the rest (the CPU's scatter). The variable is the tests' override.
_ENV_SPEC_HIST = env_choice("LIGHTGBM_TPU_SPEC_HIST", ("flat", "lanes"))

def spec_batch_slots(
    num_leaves: int,
    hist_mode: str = "bucketed",
    has_lazy_cegb: bool = False,
    pooled: bool = False,
    cegb_on: bool = False,
    use_subtract: bool = True,
    custom_split: bool = False,
    route_rows_variant: bool = False,
) -> int:
    """Speculative-batch width grow_tree will trace with (0 = sequential).

    The SINGLE source of truth for the spec-mode gate: grow_tree derives its
    KB from this, and callers that allocate the donated ``spec_buf`` carry
    (models/gbdt.py) or attribute its HBM footprint (obs/memwatch.py) call
    it with the same arguments so they can never disagree with the trace.

    ``route_rows_variant`` (histogram.route_rows_variant of the run's frozen
    tune route) declines spec mode: the spec batch histograms candidates at
    the batch-max bucket size, so a route whose impl choice varies with the
    row bucket would let the same logical segment take different kernels in
    the batch and in the W=1 pass — and the speculative and the sequential
    grower could then grow different trees (docs/HistogramRouting.md
    §Exactness).
    """
    bucketed = hist_mode == "bucketed" and not has_lazy_cegb and num_leaves > 1
    spec_ok = (
        bucketed and not pooled and not cegb_on and use_subtract
        and not custom_split and not route_rows_variant
        and _ENV_SPLIT_IMPL != "pallas"
    )
    if _ENV_GROW == "seq":
        kb = 0
    elif _ENV_GROW == "spec":
        kb = _ENV_SPEC_K
    else:
        kb = _ENV_SPEC_K if _default_backend() == "tpu" else 0
    kb = min(kb, num_leaves - 1) if spec_ok else 0
    return kb if kb >= 2 else 0


# which mode the most recent grow_tree TRACE resolved to ("spec"/"seq"),
# and which batched-histogram form ("flat"/"lanes") — set at trace time, so
# only meaningful right after a cache-cleared call; tests use these to
# prove the intended path actually engaged
_LAST_GROW_MODE = None
_LAST_SPEC_HIST = None


class TreeArrays(NamedTuple):
    """Flat-array decision tree (bin-space thresholds), mirroring tree.h:58-522."""

    num_leaves: jax.Array  # scalar int32: leaves actually grown
    split_feature: jax.Array  # [M-1] int32 (used-feature index)
    threshold_bin: jax.Array  # [M-1] int32
    default_left: jax.Array  # [M-1] bool
    left_child: jax.Array  # [M-1] int32 (node idx, or -(leaf+1) for leaves)
    right_child: jax.Array  # [M-1] int32
    split_gain: jax.Array  # [M-1] f32
    internal_value: jax.Array  # [M-1] f32
    internal_count: jax.Array  # [M-1] f32
    leaf_value: jax.Array  # [M] f32
    leaf_count: jax.Array  # [M] f32
    leaf_weight: jax.Array  # [M] f32 (sum of hessians)
    leaf_parent: jax.Array  # [M] int32
    leaf_depth: jax.Array  # [M] int32
    cat_member: jax.Array  # [M-1, B] bool: left-side bin membership bitsets
    # [len(COUNTER_NAMES)] f32: how the grower worked for this tree, not part
    # of the model (None from the native host learner)
    counters: Optional[jax.Array] = None


#: The grower's work counters, per tree, in ``TreeArrays.counters``' order.
#: float32: at 10.5M rows x 8 lanes an int32 overflows within one tree.
#: ``steps``: passes of the while_loop body; ``slots_computed``: candidate
#: slots whose partition and histogram were computed (one per step when
#: sequential); ``splits``: splits applied. ``hist_rows_streamed``: per
#: histogram call, the row extent passed over times the lanes it ran for (the
#: bucket the lattice switch chose, x KB for the speculative lanes; the flat
#: form's concatenated length; N for the root and in masked mode);
#: ``hist_rows_needed``: the same calls' live rows. ``part_rows_*``: the same
#: pair for the partition; ``part_rows_missing``: of ``part_rows_needed``, the
#: rows whose bin is the split feature's missing bin, which go where the
#: split's default direction says; ``splits_default_left``: of ``splits``,
#: those that send missing to the left on a feature that has a missing type.
#: ``root_rows``: the rows of the root segment, which every later pass is a
#: part of: the in-bag rows where the grower is rooted at the sample, N where
#: the sample is a mask (masked mode, the sharded growers).
#: ``hist_columns``: the columns this tree's histograms were built over, the
#: static width of the matrix the grower was handed: a feature_fraction draw's
#: where it was handed the drawn columns, the table's (its bundles') where the
#: draw is a mask.
#: ``hist_rows_gathered``: the rows the histogram's input gather reads: the
#: switch branch's static rows in the flat form (the lattice's round-up the
#: kernel skips), what ``hist_rows_streamed`` books elsewhere, N for the
#: whole-table root pass and in masked mode. ``part_rows_taken``: the rows
#: the partition's take of its split columns asks for, W x N a call
#: whatever the rows the call splits (N a split in the sequential grower).
#: Under shard_map the largest shard's counts.
COUNTER_NAMES = (
    "steps", "slots_computed", "splits", "hist_rows_streamed",
    "hist_rows_needed", "part_rows_streamed", "part_rows_needed",
    "part_rows_missing", "splits_default_left", "root_rows", "hist_columns",
    "hist_rows_gathered", "part_rows_taken",
)


def _counted(counters: jax.Array, **delta) -> jax.Array:
    """``counters`` plus ``delta``, given by name."""
    return counters + jnp.stack([
        jnp.asarray(delta.get(name, 0.0), jnp.float32)
        for name in COUNTER_NAMES
    ])


def _carry_rows(buf: jax.Array, idx: jax.Array) -> jax.Array:
    """``buf[idx]`` for a few rows of a large loop carry, read row by row."""
    # not buf[idx]: the TPU compiler cuts a gather this large into column
    # pieces and copies the whole carry each step; not a stacked unroll: that
    # relayouts it (tests/test_hist_pallas_tpu_compile.py holds the HLO to this)
    return jax.lax.map(
        lambda r: jax.lax.dynamic_index_in_dim(buf, r, 0, keepdims=False), idx
    )


class PackedBest(NamedTuple):
    """Per-leaf best-split candidates, packed so each split's refresh is 3
    scatters instead of 28 chained single-field updates (the dominant fixed
    cost per split on CPU once the histogram work is bucketed; on TPU each
    scatter is a separate fused kernel launch). Column order is
    _BEST_F / _BEST_I below; ``b`` is [default_left | cat_bitset]."""

    f: jax.Array  # [M, 9] f32
    i: jax.Array  # [M, 3] int32
    b: jax.Array  # [M, 1 + B] bool


_BEST_F = (
    "gain", "left_sum_grad", "left_sum_hess", "left_count",
    "right_sum_grad", "right_sum_hess", "right_count",
    "left_output", "right_output",
)
_BEST_I = ("feature", "threshold", "num_cat")


def _pack_best(res: SplitResult) -> PackedBest:
    """SplitResult with any (shared) leading shape -> PackedBest."""
    f = jnp.stack(
        [jnp.asarray(getattr(res, n), jnp.float32) for n in _BEST_F], axis=-1
    )
    i = jnp.stack(
        [jnp.asarray(getattr(res, n), jnp.int32) for n in _BEST_I], axis=-1
    )
    b = jnp.concatenate(
        [jnp.asarray(res.default_left, bool)[..., None],
         jnp.asarray(res.cat_bitset, bool)],
        axis=-1,
    )
    return PackedBest(f, i, b)


def _unpack_best_row(pb: PackedBest, idx) -> SplitResult:
    """One packed row -> a scalar-field SplitResult."""
    f, i, b = pb.f[idx], pb.i[idx], pb.b[idx]
    kw = {n: f[k] for k, n in enumerate(_BEST_F)}
    kw.update({n: i[k] for k, n in enumerate(_BEST_I)})
    return SplitResult(default_left=b[0], cat_bitset=b[1:], **kw)


# leaf-auxiliary column order: sums + monotone windows, [M, 5] f32
_LAUX_SG, _LAUX_SH, _LAUX_ND, _LAUX_MIN, _LAUX_MAX = range(5)


class PackedTree(NamedTuple):
    """Internal packed tree carry: the ~21 single-element wiring scatters per
    split collapse into 5 (one per array). Node arrays carry M rows; real
    nodes occupy [0, M-1) and row M-1 is the write-off target for the
    parent child-pointer update when the split leaf is the root
    (parent == -1). Unpacked into TreeArrays once, after the grow loop."""

    num_leaves: jax.Array  # scalar int32
    node_f: jax.Array  # [M, 3] f32: split_gain, internal_value, internal_count
    node_i: jax.Array  # [M, 4] i32: split_feature, threshold, left/right child
    node_b: jax.Array  # [M, 1 + B] bool: default_left | cat_member
    leaf_f: jax.Array  # [M, 3] f32: leaf_value, leaf_count, leaf_weight
    leaf_i: jax.Array  # [M, 2] i32: leaf_parent, leaf_depth
    counters: Optional[jax.Array] = None  # TreeArrays.counters, COUNTER_NAMES


def _unpack_tree(pt: PackedTree, M: int) -> TreeArrays:
    return TreeArrays(
        num_leaves=pt.num_leaves,
        split_feature=pt.node_i[: M - 1, 0],
        threshold_bin=pt.node_i[: M - 1, 1],
        default_left=pt.node_b[: M - 1, 0],
        left_child=pt.node_i[: M - 1, 2],
        right_child=pt.node_i[: M - 1, 3],
        split_gain=pt.node_f[: M - 1, 0],
        internal_value=pt.node_f[: M - 1, 1],
        internal_count=pt.node_f[: M - 1, 2],
        leaf_value=pt.leaf_f[:, 0],
        leaf_count=pt.leaf_f[:, 1],
        leaf_weight=pt.leaf_f[:, 2],
        leaf_parent=pt.leaf_i[:, 0],
        leaf_depth=pt.leaf_i[:, 1],
        cat_member=pt.node_b[: M - 1, 1:],
        counters=pt.counters,
    )


class GrowState(NamedTuple):
    it: jax.Array
    leaf_id: jax.Array  # [N] int32 (masked mode; [1] dummy when bucketed)
    tree: PackedTree
    best: PackedBest  # per-leaf best splits, packed
    laux: jax.Array  # [M, 5] f32: sum_grad, sum_hess, num_data, min/max_con
    hist: jax.Array  # [M, F, B, 3] ([P, F, B, 3] when the pool is capped)
    feature_used: jax.Array  # [F] bool (CEGB coupled bookkeeping)
    unused_cnt: jax.Array  # [M, F] rows-not-yet-charged counts (CEGB lazy)
    used_in_data: jax.Array  # [F, N] bool when lazy CEGB else [1, 1] dummy
    # bucketed mode: DataPartition-style segment layout (data_partition.hpp:20)
    order: jax.Array  # [N] int32 row permutation grouped by leaf ([1] dummy)
    leaf_begin: jax.Array  # [M] int32 segment starts ([1] dummy)
    leaf_phys: jax.Array  # [M] int32 physical rows per leaf ([1] dummy)
    # HistogramPool LRU state (feature_histogram.hpp:654); [1] dummies unpooled
    slot_of: jax.Array  # [M] int32: leaf -> pool slot, -1 = evicted
    slot_leaf: jax.Array  # [P] int32: slot -> leaf, -1 = free
    slot_age: jax.Array  # [P] int32 LRU stamps (0 = never used)
    # spec-mode speculation cache (dummies otherwise): a speculated-but-
    # unapplied split's children results are kept so its heavy work happens
    # exactly once. The LEFT child's histogram is committed straight into
    # the hist carry at cache time (the parent histogram's only use —
    # subtraction — is over by then); the right child has no leaf slot yet,
    # so its histogram parks here keyed by the parent leaf.
    spec_flag: jax.Array  # [M] bool: leaf's pending split is cached
    spec_lphys: jax.Array  # [M] int32: cached left physical count
    spec_rhist: jax.Array  # [M, F, B, 3] cached right-child histograms


def _decision_go_left(col, threshold, default_left, missing_type, default_bin, nan_bin, is_cat=None, member_val=None):
    """Bin-space split decision (dense_bin.hpp Split / CategoricalDecisionInner).

    ``member_val`` is the split's left-side membership ALREADY LOOKED UP at
    ``col`` (the caller gathers from its [B]-bool bitset — per-segment, per
    vmapped lane, or per flat row); categorical decisions are that pure
    bitset lookup — no default-direction logic (tree.h:275). ``is_cat``
    None is the numerical form, for a table that has no categorical column:
    no membership is asked for.
    """
    go_left = jnp.where(
        _in_missing_bin(col, missing_type, default_bin, nan_bin, is_cat),
        default_left, col <= threshold,
    )
    if is_cat is None:
        return go_left
    return jnp.where(is_cat, member_val, go_left)


def _in_missing_bin(col, missing_type, default_bin, nan_bin, is_cat=None):
    """Rows that ``_decision_go_left`` sends by the default direction: their
    bin is the feature's missing bin (the zero bin under missing=Zero, the
    NaN bin under missing=NaN)."""
    missing = (
        ((missing_type == MISSING_ZERO) & (col == default_bin))
        | ((missing_type == MISSING_NAN) & (col == nan_bin))
    )
    return missing if is_cat is None else ~is_cat & missing


def _ceil_log2(n: int) -> int:
    return max(int(n - 1).bit_length(), 0)


MIN_BUCKET_LOG2 = 8  # smallest gathered-segment bucket (256 rows)


def bucket_sizes(N: int) -> Tuple[int, ...]:
    """The gathered-segment bucket lattice for an ``N``-row dataset: the
    {2^k} ∪ {3·2^(k-1)} family (x1.33/x1.5 steps, capping round-up waste at
    33% where pure powers of two waste up to 2x), honoring the import-time
    LIGHTGBM_TPU_LATTICE compile-cost knob.

    THE shape distribution the bucketed grower emits histogram calls at —
    shared by ``make_bucket_kernels`` (the lax.switch branch set) and the
    histogram autotuner's sweep (obs/tune.py), which must measure exactly
    these shapes for its routing table to describe real work."""
    step = 2 if _ENV_LATTICE == "coarse" else 1
    sizes = {
        min(1 << b, N)
        for b in range(MIN_BUCKET_LOG2, _ceil_log2(N) + 1, step)
    }
    if _ENV_LATTICE == "":
        sizes |= {
            min(3 << b, N)
            for b in range(MIN_BUCKET_LOG2 - 1, _ceil_log2(N) + 1)
        }
    return tuple(sorted(sizes | {N}))


def _branch_steps(cap: int):
    """Branch-size family up to ``cap``, honoring the same
    LIGHTGBM_TPU_LATTICE compile-cost knob as the bucket lattice:
    branches execute ALL their lanes, so the default {2^k, 3*2^(k-1)}
    family caps round-up waste at 33% (pure powers of two allow 2x),
    while pow2/coarse trade waste for fewer compiled branches."""
    fam = set()
    k = 0
    while (1 << k) < cap * 2:
        if _ENV_LATTICE != "coarse" or k % 2 == 0:
            fam.add(1 << k)
        if _ENV_LATTICE == "":
            fam.add(3 << k)
        k += 1
    return sorted({min(v, cap) for v in fam} | {cap})


def _lattice_index(sizes_arr: jax.Array, n) -> jax.Array:
    """Index of the smallest lattice size that holds ``n``: the branch a
    ``lax.switch`` over the lattice takes, and the extent a counter books."""
    return jnp.clip(
        jnp.searchsorted(sizes_arr, n, side="left"), 0, sizes_arr.shape[0] - 1
    )


#: rows of histogram work that cost the flat Pallas pass what one more grid
#: step costs (one v5e: 0.24 us a step against 0.51 ns a row of 8 columns;
#: PERF.md section 6, PR 34)
FLAT_STEP_ROWS = 480


def flat_chunk(rows: int, slots: int) -> int:
    """Rows a grid step of the flat Pallas pass takes in the switch branch
    that holds ``rows`` over ``slots`` slots: the multiple of 512 nearest
    ``sqrt(2 * FLAT_STEP_ROWS * rows / slots)``, between 512 and the
    kernel's VMEM cap; past one unrolled group of the kernel's loop, a
    whole number of groups, so that no branch's kernel has a tail to
    lower.

    From what the code can observe (the branch's static row count, the
    batch's width, the cap), not from a table: a branch of ``rows`` in
    chunks of C takes ``rows / C`` grid steps, each worth FLAT_STEP_ROWS
    rows of work whatever it holds, and pads every slot by C / 2 rows on
    average; the sum is least at the C above. The table's width F
    multiplies both sides alike and drops out."""
    from . import hist_pallas

    c = (2 * FLAT_STEP_ROWS * rows / slots) ** 0.5
    group = hist_pallas._UNROLL * hist_pallas.SUB
    unit = group if c > group else hist_pallas.SUB
    cap = hist_pallas._max_chunk_for("pallas") // unit * unit
    return min(max(int(c / unit + 0.5) * unit, hist_pallas.SUB), cap)


def flat_branches(n_rows: int, slots: int) -> Tuple[Tuple[int, int], ...]:
    """The flat Pallas pass's switch branches for segments of ``n_rows``
    rows in all over ``slots`` slots: ``(rows, chunk)`` pairs, ``rows`` a
    whole number of ``chunk``s, on the bucket lattice's family over
    512-row units (so LIGHTGBM_TPU_LATTICE cuts them like the rest). A
    branch serves a batch whose slots, each padded to its chunk, fit its
    rows; the last fits any batch: every row and a chunk a slot."""
    n512 = -(-n_rows // 512) * 512
    # past every row a branch only holds more padding: the chunk stays
    top = n512 + slots * flat_chunk(n512, slots)
    out = {}
    for units in _branch_steps(top // 512):
        c = flat_chunk(min(units * 512, n512), slots)
        out[-(-units * 512 // c) * c] = c
    return tuple(sorted(out.items()))


class BucketKernels(NamedTuple):
    """The bucketed grower's per-split partition and segment-histogram
    kernels for one dataset layout, with the extents its work counters
    book (``make_bucket_kernels`` builds them, ``grow_tree`` calls them)."""

    #: (order, begin[W], pcnt[W], feat[W], thr[W], dleft[W], member[W, B])
    #: -> (new order, left physical counts [W])
    partition_batch: Callable
    #: (vals_all [N, 3], order, begin[W], cnt[W], sizes=sizes)
    #: -> [W, F, B_hist, 3]
    segment_histogram_batch: Callable
    sizes: Tuple[int, ...]  # gathered-segment bucket lattice
    #: the few of ``sizes`` a sampled tree's root segment is passed at: one
    #: pass a tree, and a switch branch is a second or so of the build
    root_sizes: Tuple[int, ...]
    part_sizes: Tuple[int, ...]  # flat-partition branch lattice
    #: (cnt[W], sizes=sizes) -> rows EACH of the W lanes of
    #: segment_histogram_batch passes
    hist_extent: Callable
    #: pcnt[W] -> rows the one flat pass of partition_batch runs over
    part_extent: Callable


def make_bucket_kernels(
    bins: jax.Array,
    feature_meta: Dict[str, jax.Array],
    num_bins: int,
    num_group_bins: Optional[int] = None,
    bins_nf: Optional[jax.Array] = None,
    chunk: int = 4096,
    hist_dtype: str = "float32",
    feature_sharded: bool = False,
    kb: int = 0,
    hist_route=None,
) -> BucketKernels:
    """Build the bucketed partition / segment-histogram kernels for one
    dataset layout. ``kb`` is the speculative-batch width the caller will
    trace with (it only widens the flat-partition branch lattice's cap; 0
    for the sequential grower). One caller family: ``grow_tree``, on one
    device or per shard inside the data-parallel grower's shard_map.

    ``hist_route`` is the run's frozen histogram tune route
    (ops/histogram.HistRoute): each bucket branch's leaf_histogram call
    resolves its impl from the route at trace time, keyed on that branch's
    static segment size (docs/HistogramRouting.md)."""
    N = bins.shape[1]
    B = num_bins
    F = feature_meta["num_bin"].shape[0]
    f32 = jnp.float32
    num_bin_arr = feature_meta["num_bin"].astype(jnp.int32)
    missing_arr = feature_meta["missing_type"].astype(jnp.int32)
    default_bin_arr = feature_meta["default_bin"].astype(jnp.int32)
    # the static "no cat -> no cat code" switch, as in ops/split.py: the
    # dataset writes the key only when a column is categorical
    has_cat = "is_categorical" in feature_meta
    if has_cat:
        is_cat_arr = feature_meta["is_categorical"].astype(bool)
    bundled = "group_id" in feature_meta
    if bundled:
        gid_arr = feature_meta["group_id"].astype(jnp.int32)  # [F]
        off_arr = feature_meta["bin_offset"].astype(jnp.int32)  # [F]
        B_hist = num_group_bins if num_group_bins is not None else B

        def decode_col(group_col, f):
            """Group-encoded column -> feature f's sub-bins (efb.decode_subbin)."""
            r = group_col - off_arr[f]
            in_range = (r >= 0) & (r < num_bin_arr[f] - 1)
            s = r + (r >= default_bin_arr[f]).astype(jnp.int32)
            return jnp.where(in_range, s, default_bin_arr[f])
    else:
        B_hist = B

    # gathered-segment bucket sizes for the bucketed partition/histogram:
    # the {2^k} ∪ {3·2^k} lattice (x1.33/x1.5 steps) caps round-up waste at
    # 33% where pure powers of two waste up to 2x — worth ~15% of total
    # histogram work at large shapes for ~1.6x the switch branches.
    # _ENV_LATTICE (import-time, like histogram._ENV_IMPL) trades bounded
    # histogram over-work for lax.switch branch count and therefore
    # first-contact compile time (20-40s+ per branch class on TPU).
    # bucket_sizes is also the autotuner's sweep distribution (obs/tune.py).
    SIZES = bucket_sizes(N)
    # powers of 4 from 4096 up, and N
    ROOT_SIZES = tuple(
        S for S in SIZES if S == N or (S >= 4096 and S.bit_count() == 1
                                       and S.bit_length() % 2)
    )
    _half_bucket = min(S for S in SIZES if 2 * S >= N)

    # flat-partition branch lattice over 256-row units, up to the worst
    # case (every row plus per-slot 256-alignment)
    _part_cap = -(-N // 256) * 256 + max(kb, 1) * 256
    _part_sizes = [
        u * 256 for u in _branch_steps(-(-_part_cap // 256))
    ]
    _part_sizes_arr = jnp.asarray(_part_sizes, jnp.int32)

    def _part_padded(pcnt):
        return ((pcnt + 255) // 256) * 256

    def partition_batch(order, begin, pcnt, feat, thr, dleft, member):
        """Stably partition W disjoint leaf segments in ONE flat segmented
        pass; returns (new order, left physical counts [W], the rows that
        went by their split's default direction, over all W). The W axis is
        the leading axis of every operand; W=1 is the sequential grower's
        per-split partition, W=KB a speculative batch — one implementation,
        so the two modes cannot drift, and arithmetic is proportional to the
        segments' TOTAL rows (a vmapped common-max form would pay
        W x max(segment)).

        Layout after a partition (DataPartition::Split, data_partition.hpp:111):
        [pre-segment | left | right | post-segment], stably, via a segmented
        prefix-sum rank — O(L) scatter instead of an O(L log L) stable sort.
        Integer-exact and idempotent: re-partitioning an already-partitioned
        segment yields the same layout, so work done for a speculated-but-
        unapplied split stays valid when that leaf wins later.

        A lane pays an element gather only where its index is random: its
        bin in its slot's split column. Its row is no gather: inside a slot
        the index into ``order`` rises by one a lane, so slot k's lanes are
        the window of ``order`` that starts at ``begin[k] - offs[k]``, W
        slices chosen by the lane's slot. And the categorical split's
        membership lookup is traced only for a table that has a categorical
        column (``"is_categorical" in feature_meta``, static); without one
        the decision is the numerical comparison alone and ``member`` is
        not read."""
        W = begin.shape[0]
        with jax.named_scope("partition/lanes"):
            miss = missing_arr[feat]
            dbin = default_bin_arr[feat]
            nanb = num_bin_arr[feat] - 1
            # a categorical split's left-side bitset and which slots have one
            cat = (is_cat_arr[feat], member) if has_cat else ()
        with jax.named_scope("partition/split_columns"):
            rows_of = (gid_arr[feat] if bundled else feat).astype(jnp.int32)
            # the W split features' columns, laid flat OUTSIDE the lattice
            # switch: a branch that flattens the whole [F, N] matrix for its
            # gather rebuilds that copy at every call (on a v5e 58 ms a call
            # at 968 x 750K, 72% of an iteration there; PERF.md, PR 28).
            # Read as W slices of one row, not by a take: the compiler sank
            # ``jnp.take(bins, rows_of, axis=0)`` into every branch and cut
            # it into full-height ``mini-gather-slice`` pieces, 23 of
            # u8[968,32640] a branch at 968 x 750K, so that a step read the
            # whole matrix for its W rows; and the slices laid flat by one
            # concatenate, not stacked by a loop, whose writes of one row
            # into a [W, N] buffer cost a v5e more than the reads (PERF.md,
            # PR 38). But a matrix GSPMD shards along the features keeps the
            # take: its slices at a traced row would all-gather the whole
            # matrix to every device. Always from ``bins``, never
            # ``bins_nf``: a matrix that both this read and the segment
            # gathers read gets one layout in the loop's carry, and the side
            # it does not suit relays the whole of it out at every step
            # (PERF.md, PR 31)
            if feature_sharded:
                cols = jnp.take(bins, rows_of, axis=0).reshape(-1)
            else:
                cols = jnp.concatenate([
                    jax.lax.dynamic_index_in_dim(
                        bins, rows_of[k], 0, keepdims=False)
                    for k in range(W)
                ])  # [W * N]

        with jax.named_scope("partition/lanes"):
            padded = _part_padded(pcnt)  # [W]
            ends = jnp.cumsum(padded)
            offs = ends - padded
            branch_idx = _lattice_index(_part_sizes_arr, ends[-1])

        def make_branch(Lb):
            def branch(order, begin, pcnt, offs, ends, cols, feat, thr,
                       dleft, miss, dbin, nanb, *cat):
                t = jnp.arange(Lb, dtype=jnp.int32)
                j = jnp.minimum(
                    jnp.searchsorted(ends, t, side="right").astype(jnp.int32),
                    W - 1,
                )
                q = t - offs[j]
                valid = q < pcnt[j]
                # lane t of slot k reads order[begin[k] + t - offs[k]]: a
                # slice a slot, not a gather (on a v5e 4.98 ms a call at
                # 752,128 lanes; PERF.md, PR 36). Padded by Lb on both
                # sides no start is cut; the lanes past a slot's pcnt read
                # its neighbours or the padding and are never written
                order_p = jnp.pad(order, (Lb, Lb))
                starts = begin - offs + Lb
                rows = jax.lax.dynamic_slice(order_p, (starts[0],), (Lb,))
                for k in range(1, W):
                    rows = jnp.where(
                        j == k,
                        jax.lax.dynamic_slice(order_p, (starts[k],), (Lb,)),
                        rows,
                    )
                # per-row feature column through ONE flat gather (each row's
                # slot picks its own split feature's column)
                colraw = jnp.take(cols, j * N + rows).astype(jnp.int32)
                colv = decode_col(colraw, feat[j]) if bundled else colraw
                cat_j = ()  # the lane's (is categorical, is a member)
                if has_cat:
                    iscat, member = cat
                    cat_j = (iscat[j], member[j, jnp.clip(colv, 0, B - 1)])
                gl = _decision_go_left(
                    colv, thr[j], dleft[j], miss[j], dbin[j], nanb[j], *cat_j
                )
                is_left = valid & gl
                is_right = valid & ~gl
                by_default = jnp.sum(valid & _in_missing_bin(
                    colv, miss[j], dbin[j], nanb[j], *cat_j[:1]
                ), dtype=jnp.int32)
                # segmented inclusive count of lefts (resets at slot starts);
                # int adds are reassociation-exact
                seg_start = t == offs[j]

                def comb(a, b):
                    av, af = a
                    bv, bf = b
                    return jnp.where(bf, bv, av + bv), af | bf

                lc_inc, _ = jax.lax.associative_scan(
                    comb, (is_left.astype(jnp.int32), seg_start)
                )
                # lefts per slot = inclusive count at the slot's last lane
                # (pad lanes contribute 0); zero-width slots read a stale
                # lane and are masked to 0
                left_cnt = jnp.where(
                    padded > 0, lc_inc[jnp.maximum(ends - 1, 0)], 0
                )
                tgt_local = jnp.where(
                    is_left,
                    lc_inc - 1,
                    left_cnt[j] + q - lc_inc,
                )
                write = is_left | is_right
                gt = jnp.where(write, begin[j] + tgt_local, N + t)
                with jax.named_scope("partition/scatter"):
                    order2 = order.at[gt].set(rows, unique_indices=True)
                return order2, left_cnt, by_default

            return branch

        with jax.named_scope("partition/lanes"):
            return jax.lax.switch(
                branch_idx,
                [make_branch(Lb) for Lb in _part_sizes],
                order, begin, pcnt, offs, ends, cols, feat, thr, dleft, miss,
                dbin, nanb, *cat,
            )

    def segment_histogram_batch(vals_all, order, begin, cnt, sizes=SIZES,
                                phases=("hist_build/feed", "hist_build/kernel")):
        """[W, F, B, 3] histograms of W disjoint segments via ONE lattice-
        switch launch (over ``sizes``, a part of the lattice that ends in
        N): one fused gather for all segments, then a vmapped chunked pass
        in which every lane runs the routed impl verbatim at the largest
        segment's bucket. W=1 is the sequential per-split histogram and a
        sampled tree's root pass; W=KB is the speculative batch's ``lanes``
        form, which serves the impls that have no flat kernel
        (``segment_histogram_flat`` in ``grow_tree`` serves the rest).
        ``phases``: the scopes (obs/trace.SCOPES) of the gathers and of the
        pass; the root's pass books both under one.

        Cost tracks leaf size like the reference's ordered-index histograms
        (dense_bin.hpp:71); one gather from the precomputed [N, 3]
        (grad*bag, hess*bag, bag) instead of three masked takes — bag/valid
        are exact {0,1} multipliers so the product order cannot change f32
        results."""
        Frows = bins.shape[0]

        def make_branch(S):
            def lanes(vals_all, order, begin, cnt):
                W = begin.shape[0]

                def geo(begin_j, cnt_j):
                    # zero-based (NOT the clamped _segment_slice window):
                    # real rows sit at positions [0, cnt) so chunk
                    # boundaries are segment-relative — the invariant that
                    # makes the flat batched form bitwise-identical
                    pos = jnp.arange(S, dtype=jnp.int32)
                    seg = order[jnp.clip(begin_j + pos, 0, N - 1)]
                    return seg, pos < cnt_j

                with jax.named_scope(phases[0]):
                    seg, valid = jax.vmap(geo)(begin, cnt)  # [W, S]
                    flat = seg.reshape(-1)
                    vals = jnp.take(vals_all, flat, axis=0).reshape(W, S, 3)
                    vals = vals * valid[..., None].astype(f32)
                    if bins_nf is not None:
                        b_seg = jnp.take(bins_nf, flat, axis=0).reshape(
                            W, S, Frows
                        ).transpose(0, 2, 1)
                    else:
                        b_seg = jnp.take(bins, flat, axis=1).reshape(
                            Frows, W, S
                        ).transpose(1, 0, 2)
                return jax.vmap(
                    lambda b, v: leaf_histogram(
                        b, v, B_hist, chunk=chunk, hist_dtype=hist_dtype,
                        feature_sharded=feature_sharded, route=hist_route,
                    )
                )(b_seg, vals)

            def branch(vals_all, order, begin, cnt):
                if begin.shape[0] == 1 or S <= _half_bucket:
                    return lanes(vals_all, order, begin, cnt)
                # a bucket over half the rows: the lanes one after another,
                # each the W=1 pass. The W segments gathered at once are
                # W x S x F bytes, 7.7 GB at 8 x 1M x 968, more than the
                # chip grants one program, and a smaller child is this large
                # only under row sampling or an uneven shard (PERF.md, PR 28)
                return jax.lax.map(
                    lambda bc: lanes(vals_all, order, bc[0][None], bc[1][None])[0],
                    (begin, cnt),
                )

            return branch

        with jax.named_scope(phases[0]):
            branch_idx = _lattice_index(
                jnp.asarray(sizes, jnp.int32), jnp.max(cnt))
        with jax.named_scope(phases[1]):
            return jax.lax.switch(
                branch_idx, [make_branch(S) for S in sizes], vals_all, order,
                begin, cnt
            )

    def hist_extent(cnt, sizes=SIZES):
        sizes = jnp.asarray(sizes, jnp.int32)
        return sizes[_lattice_index(sizes, jnp.max(cnt))]

    return BucketKernels(
        partition_batch=partition_batch,
        segment_histogram_batch=segment_histogram_batch,
        sizes=SIZES,
        root_sizes=ROOT_SIZES,
        part_sizes=tuple(_part_sizes),
        hist_extent=hist_extent,
        part_extent=lambda pcnt: _part_sizes_arr[
            _lattice_index(_part_sizes_arr, jnp.sum(_part_padded(pcnt)))],
    )


# node_i column indices for apply_split's fused 6-element scatter (numpy so
# the module builds it once without touching the jax backend at import)
_NODE_I_COLS = np.array([0, 1, 2, 3, 2, 3], np.int32)


@functools.partial(
    jax.jit,
    static_argnames=(
        "num_leaves", "max_depth", "num_bins", "params", "num_group_bins",
        "chunk", "axis_name", "split_fn", "psum_hist", "forced_splits", "cegb",
        "cegb_rescan", "hist_mode", "hist_dtype", "two_way", "feature_sharded",
        "hist_pool_slots", "use_subtract", "hist_route",
    ),
    donate_argnames=("hist_buf", "spec_buf"),
)
def grow_tree(
    bins: jax.Array,  # [F, N] uint8/int32
    grad: jax.Array,  # [N] f32 (GOSS: amplified on the drawn rows)
    hess: jax.Array,  # [N] f32
    bag_mask: jax.Array,  # [N] f32 (> 0 = in bag; all ones without a sample)
    feature_mask: jax.Array,  # [F] bool (feature_fraction sample)
    feature_meta: Dict[str, jax.Array],
    num_leaves: int,
    max_depth: int,
    num_bins: int,
    params: SplitParams,
    num_group_bins: Optional[int] = None,
    chunk: int = 4096,
    axis_name: Optional[str] = None,
    split_fn=None,
    psum_hist: bool = True,
    forced_splits: Tuple = (),
    cegb: CegbParams = CegbParams(),
    cegb_state: Optional[Tuple[jax.Array, jax.Array]] = None,
    cegb_rescan=None,
    hist_mode: str = "bucketed",
    hist_dtype: str = "float32",
    two_way: bool = True,
    feature_sharded: bool = False,
    hist_buf: Optional[jax.Array] = None,
    bins_nf: Optional[jax.Array] = None,
    hist_pool_slots: Optional[int] = None,
    use_subtract: bool = True,
    spec_buf: Optional[jax.Array] = None,
    hist_route=None,
):
    """Grow one tree; returns (TreeArrays, leaf_id [N]).

    ``bag_mask`` is the row sample of this tree (bagging, GOSS, rf). The
    bucketed grower is rooted at it: ``order`` starts as the stable partition
    of the rows by ``bag_mask > 0`` and the root segment is the in-bag rows
    alone, a traced length like every other segment's, so the root
    histogram, every partition and every bucket choice pass over the sample
    and one compiled program serves sampled and unsampled trees. The rows out
    of the bag take no part in the growth and get their ``leaf_id`` from the
    finished tree (``leaves_by_tree``). A mask of ones is the identity order
    and the whole-table root pass. The masked mode and the sharded growers
    (``axis_name``, ``feature_sharded``: a sort of the rows or a take of the
    split columns would gather the sharded matrix) keep the sample as a mask
    of zeros over all N rows.

    ``split_fn(hist, sum_g, sum_h, num_data, min_c, max_c, feature_meta,
    feature_mask, params) -> SplitResult`` overrides the best-split search —
    the hook where the voting-parallel learner's top-k vote + reduced psum
    plugs in (voting_parallel_tree_learner.cpp:262-375). With ``axis_name``
    set and ``psum_hist=False``, per-leaf histograms stay shard-local (only
    root totals are psum'd); the split_fn is then responsible for combining
    shard histograms.

    ``forced_splits``: BFS-ordered static tuple of (leaf_idx, used_feature_idx,
    threshold_bin) applied before best-gain growth (ForceSplits).
    ``hist_mode``: "bucketed" (default — segment-permutation histograms whose
    cost tracks leaf size) or "masked" (full-N masked passes; the differential
    oracle, also used automatically for lazy CEGB).
    ``feature_sharded``: set True when ``bins`` is GSPMD-sharded along the
    feature axis (the feature-parallel learner) — selects the row-chunked
    histogram scatter; the default per-feature scan formulation would force
    an all-gather of the bin matrix.
    ``hist_pool_slots``: cap the histogram carry to this many LRU slots
    (HistogramPool, feature_histogram.hpp:654). A split whose parent has been
    evicted runs the reference's use_subtract=false branch: both children are
    summed directly from data (serial_tree_learner.cpp:455-473). None or
    >= num_leaves keeps the full [M, F, B, 3] carry.
    ``use_subtract=False`` disables the smaller-child subtraction trick
    everywhere — the differential oracle for the pool's miss path.
    ``bins_nf``: optional transposed copy of ``bins`` ([N, F]); when given,
    the bucketed segment gathers read it instead of ``bins`` — row gathers
    are contiguous there, ~3x faster on CPU caches — while the partition
    reads its split features' columns from ``bins`` always. The serial
    learner gives it on every backend: on a TPU one matrix read both ways
    is relaid out whole at every step (PERF.md, PR 31). The sharded grower
    gives none.
    ``cegb``: static CegbParams; per-feature penalty vectors ride in
    ``feature_meta["cegb_coupled"/"cegb_lazy"]``. ``cegb_state`` is the
    (feature_used [F] bool, used_in_data [F, N] bool) pair carried across trees
    — the reference initializes these once per *training*, not per tree
    (serial_tree_learner.cpp:107-115), so acquisition penalties amortize. When
    ``cegb.enabled`` the return is (tree, leaf_id, new_cegb_state).
    ``spec_buf``: optional donated [M, F, B, 3] scratch for the spec-mode
    right-child cache (``spec_rhist``) — like ``hist_buf`` it skips the
    full-buffer zeros write per tree (stale contents are safe: every read
    is gated on the ``spec_flag`` carry, which starts all-False). Returned
    aliased as the LAST output element so the caller can re-donate;
    allocate it only when :func:`spec_batch_slots` says spec mode engages.
    ``hist_route``: the run's frozen histogram tune route
    (ops/histogram.HistRoute, frozen at GBDT._setup_train) — static, so
    the compiled program's identity includes the table it routed under;
    every leaf_histogram this tree traces resolves its impl from it
    (docs/HistogramRouting.md).
    """
    retrace_mod.note_trace("ops.grow_tree")  # runs once per real XLA trace
    N = bins.shape[1]
    F = feature_meta["num_bin"].shape[0]
    M = num_leaves
    B = num_bins
    f32 = jnp.float32

    # EFB bundling (efb.py): bins is [num_groups, N] with the offset encoding;
    # histograms run over groups at group width, then remap to feature space.
    bundled = "group_id" in feature_meta
    if bundled:
        with jax.named_scope("tree_init"):
            gid_arr = feature_meta["group_id"].astype(jnp.int32)  # [F]
            off_arr = feature_meta["bin_offset"].astype(jnp.int32)  # [F]
        B_hist = num_group_bins if num_group_bins is not None else B
    else:
        B_hist = B

    if split_fn is None:
        split_fn = find_best_split
    hist_axis = axis_name if psum_hist else None
    cegb_on = cegb.enabled
    if cegb_on and split_fn is not find_best_split and cegb_rescan is None:
        # API contract, not a feature gap: every learner that customizes the
        # split search ships its batched rescan (the voting learner's
        # vote+elect, parallel/voting_parallel.py) — CEGB re-ranks cached
        # candidates per split, so the two hooks must agree on semantics.
        raise ValueError(
            "CEGB with a custom split_fn requires a matching batched "
            "cegb_rescan(hist, lsg, lsh, lnd, mn, mx, pen, feature_meta, "
            "feature_mask, params) -> SplitResult[M]"
        )
    if hist_mode not in ("bucketed", "masked"):
        raise ValueError(
            "hist_mode must be 'bucketed' or 'masked', got %r" % (hist_mode,)
        )
    # lazy CEGB charges per (row, feature) and needs full-row leaf masks
    bucketed = hist_mode == "bucketed" and not cegb.has_lazy and M > 1

    # HistogramPool cap (feature_histogram.hpp:654): with fewer slots than
    # leaves, the [*, F, B, 3] carry holds P LRU slots; an evicted parent
    # disables the subtraction trick for that split and both children are
    # constructed directly (use_subtract = parent_leaf_histogram_array_ !=
    # nullptr, serial_tree_learner.cpp:455).
    pooled = hist_pool_slots is not None and hist_pool_slots < M
    P = int(hist_pool_slots) if pooled else M
    if pooled and P < 2:
        raise ValueError("histogram pool needs at least 2 slots, got %d" % P)
    if pooled and forced_splits and P < len(forced_splits) + 2:
        raise ValueError(
            "histogram pool too small for the forced-splits preamble: "
            "need >= %d slots" % (len(forced_splits) + 2)
        )

    # ---- speculative top-k batching (spec mode) -------------------------
    # Exactness argument: a leaf's cached best split and its children's
    # histograms depend only on that leaf's own segment and histogram, so
    # the work for the top-k candidates is computable in parallel; the
    # applied prefix reproduces argmax's (higher gain, lower slot) order, so
    # the applied split sequence — node numbering included — equals the
    # sequential one. Gated off for CEGB (penalties are order-dependent),
    # histogram pools (slot state is per-split), custom split searches
    # (may contain collectives that don't vmap), masked mode, and the
    # use_subtract=False oracle.
    # the impl set THIS run's reachable bucket classes resolve to under the
    # frozen route ({default} when no route / env pinned): >1 impl gates
    # spec mode off, and a uniform single impl decides the flat-vs-lanes
    # spec histogram below (flat hardcodes the xla one-hot arithmetic)
    _route_impls = route_effective_impls(hist_route, B_hist, hist_dtype, N)
    KB = spec_batch_slots(
        M,
        hist_mode=hist_mode,
        has_lazy_cegb=cegb.has_lazy,
        pooled=pooled,
        cegb_on=cegb_on,
        use_subtract=use_subtract,
        custom_split=split_fn is not find_best_split,
        route_rows_variant=len(_route_impls) > 1,
    )
    from .histogram import _ENV_IMPL as _hist_env

    # the effective impl: env override first, else the route's uniform impl
    # (which is the backend default when no route is active)
    eff_impl = _hist_env or (
        next(iter(_route_impls)) if len(_route_impls) == 1 else ""
    )
    # the flat form's arithmetic: the Pallas kernel's slot-grouped entry
    # where leaf_histogram would run that kernel, else the XLA one-hot scan
    # (leaf_histogram's own fallback at a width the kernel does not serve)
    flat_pallas = eff_impl == "pallas" and impl_supported(
        "pallas", B_hist, ignore_backend=True
    )
    if _ENV_SPEC_HIST:
        use_flat = _ENV_SPEC_HIST == "flat"
    else:
        use_flat = eff_impl in ("pallas", "xla")
    global _LAST_GROW_MODE, _LAST_SPEC_HIST  # trace-time test introspection
    _LAST_GROW_MODE = "spec" if KB else "seq"
    _LAST_SPEC_HIST = ("flat" if use_flat else "lanes") if KB else None

    # the per-feature tables in the forms the tree's work reads them
    with jax.named_scope("tree_init"):
        num_bin_arr = feature_meta["num_bin"].astype(jnp.int32)
        missing_arr = feature_meta["missing_type"].astype(jnp.int32)
        default_bin_arr = feature_meta["default_bin"].astype(jnp.int32)
        mono_arr = feature_meta["monotone"].astype(jnp.int32)
        if bundled:
            # feature-space gather plan for the [G, B_hist] -> [F, B] remap:
            # sub-bin s != default maps to group bin off + (s - (s >
            # default)); the default row is recovered from leaf totals
            # (efb.py encoding)
            s_iota = jnp.arange(B, dtype=jnp.int32)[None, :]  # [1, B]
            s0_col = default_bin_arr[:, None]
            efb_valid = (s_iota < num_bin_arr[:, None]) & (s_iota != s0_col)  # [F, B]
            efb_gidx = jnp.where(
                efb_valid, off_arr[:, None] + s_iota - (s_iota > s0_col), 0
            )
            f_iota = jnp.arange(F, dtype=jnp.int32)
        is_cat_arr = feature_meta.get("is_categorical")
        if is_cat_arr is None:
            is_cat_arr = jnp.zeros((F,), bool)
        else:
            is_cat_arr = is_cat_arr.astype(bool)

    if bundled:

        @jax.named_scope("hist_build/relayout")
        def remap_hist(group_hist, sum_g, sum_h, sum_n):
            """[G, B_hist, 3] group histogram -> [F, B, 3] feature histogram.

            The default-bin row is leaf totals minus the feature's
            non-default rows. The remap is affine-linear in (hist, totals),
            so it commutes with cross-shard psum: remapping each shard with
            its SHARD-LOCAL totals and summing equals remapping the global
            histogram with global totals — the voting-parallel learner's
            shard-local mode relies on this (its elected-feature psum then
            runs in feature space)."""
            fh = group_hist[gid_arr[:, None], efb_gidx]  # [F, B, 3]
            fh = fh * efb_valid[:, :, None].astype(fh.dtype)
            totals = jnp.stack(
                [sum_g.astype(fh.dtype), sum_h.astype(fh.dtype), sum_n.astype(fh.dtype)]
            )
            rest = totals[None, :] - jnp.sum(fh, axis=1)  # [F, 3]
            return fh.at[f_iota, default_bin_arr].set(rest)

        @jax.named_scope("hist_build/relayout")
        def remap_hist_local(group_hist):
            """Shard-local remap: totals recovered from the group histogram
            itself — every row lands in exactly one bin of every group, so
            any group's bins sum to the (local) leaf totals."""
            t = jnp.sum(group_hist[0], axis=0)  # [3]
            return remap_hist(group_hist, t[0], t[1], t[2])

        def decode_col(group_col, f):
            """Group-encoded column -> feature f's sub-bins (efb.decode_subbin)."""
            r = group_col - off_arr[f]
            in_range = (r >= 0) & (r < num_bin_arr[f] - 1)
            s = r + (r >= default_bin_arr[f]).astype(jnp.int32)
            return jnp.where(in_range, s, default_bin_arr[f])

    # Bucketed partition / segment-histogram kernels (make_bucket_kernels
    # above), shared by the sequential and the speculative body.
    if bucketed:
        # the tables it builds; its kernels scope their own calls
        with jax.named_scope("tree_init"):
            _kern = make_bucket_kernels(
                bins, feature_meta, B, num_group_bins=num_group_bins,
                bins_nf=bins_nf, chunk=chunk, hist_dtype=hist_dtype,
                feature_sharded=feature_sharded, kb=KB, hist_route=hist_route,
            )
        partition_batch = jax.named_scope("partition")(_kern.partition_batch)
        hist_extent, part_extent = _kern.hist_extent, _kern.part_extent

        @jax.named_scope("hist_build")
        def segment_histogram_batch(order, begin, cnt, sizes=_kern.sizes,
                                    **phases):
            # vals_all (the per-tree [N, 3] accumulands) binds below, before
            # the first call
            return _kern.segment_histogram_batch(
                vals_all, order, begin, cnt, sizes, **phases)

        def partition_segment(order, begin, pcnt, f, threshold, default_left, member):
            """One split's partition — the W=1 case of partition_batch."""
            order2, left_cnt, by_default = partition_batch(
                order, begin[None], pcnt[None], f[None], threshold[None],
                default_left[None], member[None],
            )
            return order2, left_cnt[0], by_default

        def segment_histogram(order, begin, cnt):
            """One segment's histogram — the W=1 case of the batch launch."""
            return segment_histogram_batch(order, begin[None], cnt[None])[0]

    if KB:
        from . import hist_pallas
        from .histogram import _pick_chunk, onehot_chunk_partial

        # the flat form's switch branches, (rows, chunk) each. Every slot is
        # padded to whole chunks of its branch, so a chunk lies inside one
        # slot and zero-valued pad rows are fp-exact no-ops (x + 0 == x).
        # Pallas: the chunk follows the branch's rows (flat_chunk); the
        # kernel adds a segment's rows in 512-row units at segment-relative
        # offsets whatever the chunk, so the batched histogram is BITWISE
        # the per-slot one. XLA one-hot: ONE chunk for all branches, the one
        # the per-slot path would use (the F/B budget cap, un-shrunk by
        # segment size), so chunk boundaries coincide with that path's.
        _Frows = bins.shape[0]
        if flat_pallas:
            _flat_branches = flat_branches(N, KB)
        else:
            C_FLAT = _pick_chunk(_Frows, B_hist, chunk, 1 << 60)
            _flat_branches = tuple(
                (n * C_FLAT, C_FLAT)
                for n in _branch_steps(-(-N // C_FLAT) + KB)
            )
        _flat_rows = jnp.asarray([b[0] for b in _flat_branches], jnp.int32)
        _flat_chunks = jnp.asarray([b[1] for b in _flat_branches], jnp.int32)

        def _flat_plan(cnt):
            """The branch the flat switch takes for these segments (the
            first whose rows hold every slot padded to its chunk; the last
            holds any), and the rows its pass streams: the chunks that hold
            a slot's rows for the kernel, which skips the branch's round-up,
            and the whole branch for the one-hot scan, which does not (the
            gather that feeds either pays the branch's rows)."""
            c = _flat_chunks[:, None]
            need = jnp.sum((cnt[None, :] + c - 1) // c * c, axis=1)
            idx = jnp.argmax(need <= _flat_rows)
            return idx, (need if flat_pallas else _flat_rows)[idx]

        def flat_extent(cnt):
            return _flat_plan(cnt)[1]

        @jax.named_scope("hist_build")
        def segment_histogram_flat(order, begin, cnt):
            """[KB, F, B, 3] histograms of KB disjoint segments via ONE flat
            concatenated pass: arithmetic follows the segments' TOTAL padded
            rows, where the vmapped-lane form pays KB x the largest
            segment's bucket, idle lanes too.

            Layout: slot j owns flat rows [off_j, off_j + ceil_C(cnt_j)),
            C the branch's chunk; each chunk lies inside exactly one slot,
            so a chunk's partial goes to its slot's row: through the out
            block's index map in the Pallas kernel
            (hist_pallas.histogram_pallas_slots), with one dynamic-index
            add in the one-hot scan. One gather through ``order`` and one
            switch serve both; only the arithmetic is the impl's."""

            def make_branch(Lb, C):
                nsteps = Lb // C

                def branch(order, begin, cnt):
                    with jax.named_scope("hist_build/feed"):
                        padded = (cnt + C - 1) // C * C  # [KB]
                        ends = jnp.cumsum(padded)
                        offs = ends - padded
                        t = jnp.arange(Lb, dtype=jnp.int32)
                        j = jnp.searchsorted(ends, t, side="right").astype(jnp.int32)
                        j = jnp.minimum(j, KB - 1)
                        q = t - offs[j]
                        valid = q < cnt[j]
                        src = jnp.clip(begin[j] + jnp.minimum(q, jnp.maximum(cnt[j] - 1, 0)), 0, N - 1)
                        rows = order[src]
                        vals = jnp.take(vals_all, rows, axis=0) * valid[:, None].astype(f32)
                        b_seg = (
                            jnp.take(bins_nf, rows, axis=0).T
                            if bins_nf is not None
                            else jnp.take(bins, rows, axis=1)
                        )  # [Frows, Lb]
                    if flat_pallas:
                        # its own scopes split the kernel from its relayout
                        return hist_pallas.histogram_pallas_slots(
                            b_seg, vals, ends, B_hist, chunk=C,
                            dtype_name=hist_dtype,
                        )
                    slot_of_chunk = jnp.searchsorted(
                        ends, jnp.arange(nsteps, dtype=jnp.int32) * C,
                        side="right",
                    ).astype(jnp.int32)
                    slot_of_chunk = jnp.minimum(slot_of_chunk, KB - 1)
                    bins_c = b_seg.reshape(_Frows, nsteps, C).transpose(1, 0, 2)
                    vals_c = vals.reshape(nsteps, C, 3)
                    op_dtype = (
                        jnp.bfloat16 if hist_dtype == "bfloat16" else jnp.float32
                    )

                    def step(acc, xs):
                        bc, vc, sl = xs  # [Frows, C], [C, 3], scalar
                        part = onehot_chunk_partial(bc, vc, B_hist, op_dtype)
                        return acc.at[sl].add(part), None

                    acc0 = jnp.zeros((KB, _Frows, B_hist, 3), f32)
                    acc, _ = jax.lax.scan(
                        step, acc0, (bins_c, vals_c, slot_of_chunk)
                    )
                    return acc

                return branch

            with jax.named_scope("hist_build/feed"):
                branch_idx = _flat_plan(cnt)[0]
            with jax.named_scope("hist_build/kernel"):
                return jax.lax.switch(
                    branch_idx,
                    [make_branch(Lb, C) for Lb, C in _flat_branches],
                    order, begin, cnt,
                )

    coupled_arr = feature_meta.get("cegb_coupled")
    lazy_arr = feature_meta.get("cegb_lazy")

    @jax.named_scope("split_find")
    def split2(hist2, sg2, sh2, nd2, mn2, mx2):
        """Best splits for the two children. vmapped over the child axis for
        the plain scan; custom split_fns stay unrolled (they may contain
        collectives, which don't vmap under shard_map)."""
        if split_fn is find_best_split:
            if _ENV_SPLIT_IMPL == "pallas":
                from .histogram import _default_backend
                from .split_pallas import find_best_split_pair_pallas, supported

                backend = _default_backend()
                if supported(feature_meta, backend):
                    return find_best_split_pair_pallas(
                        hist2, sg2, sh2, nd2, mn2, mx2, feature_meta,
                        feature_mask, params, two_way=two_way,
                        interpret=True,  # Mosaic refuses it (supported())
                    )
            return jax.vmap(
                lambda h, sg, sh, nd, mn, mx: find_best_split(
                    h, sg, sh, nd, mn, mx, feature_meta, feature_mask, params,
                    two_way=two_way,
                )
            )(hist2, sg2, sh2, nd2, mn2, mx2)
        results = [
            split_fn(
                hist2[k], sg2[k], sh2[k], nd2[k], mn2[k], mx2[k],
                feature_meta, feature_mask, params,
            )
            for k in range(2)
        ]
        return SplitResult(
            *[jnp.stack([getattr(r, n) for r in results]) for n in SplitResult._fields]
        )

    def masked_values(mask_f32):
        return leaf_values(grad, hess, mask_f32 * bag_mask)

    # [N, 3] (grad*bag, hess*bag, bag) computed once per tree — the bucketed
    # branches gather rows of this instead of three separate takes
    if bucketed:
        with jax.named_scope("tree_init"):
            vals_all = leaf_values(grad, hess, bag_mask)

    neg_inf = jnp.float32(-jnp.inf)

    def depth_gate(gain, depth):
        if max_depth > 0:
            return jnp.where(depth >= max_depth, neg_inf, gain)
        return gain

    # ---- CEGB penalty machinery -----------------------------------------
    def leaf_penalties(lnd_all, feature_used, unused_cnt):
        """[M, F] gain penalties (serial_tree_learner.cpp:537-543,568-573)."""
        pen = cegb.tradeoff * cegb.penalty_split * lnd_all[:, None]
        pen = jnp.broadcast_to(pen, (M, F)).astype(f32)
        if cegb.has_coupled:
            pen = pen + cegb.tradeoff * coupled_arr[None, :] * (
                ~feature_used
            )[None, :].astype(f32)
        if cegb.has_lazy:
            pen = pen + cegb.tradeoff * lazy_arr[None, :] * unused_cnt
        return pen

    @jax.named_scope("split_find")
    def rescan_all(tree, hist, lsg, lsh, lnd, mn, mx, feature_used, unused_cnt):
        """Re-rank every leaf's best split under current CEGB penalties.

        The reference keeps splits_per_leaf_ cached and patches gains when a
        coupled feature first gets used (Split, serial_tree_learner.cpp:757-775);
        re-scanning from the (resident) histograms reaches the same fixpoint.
        A custom ``cegb_rescan`` (the voting learner's batched vote+elect) takes
        over when the split search itself is custom.
        """
        pen = leaf_penalties(lnd, feature_used, unused_cnt)
        if cegb_rescan is not None:
            res = cegb_rescan(
                hist, lsg, lsh, lnd, mn, mx, pen, feature_meta, feature_mask,
                params,
            )
        else:
            res = jax.vmap(
                lambda h, sg, sh, nd, mn1, mx1, pr: find_best_split(
                    h, sg, sh, nd, mn1, mx1, feature_meta, feature_mask, params,
                    pr, two_way=two_way,
                )
            )(hist, lsg, lsh, lnd, mn, mx, pen)
        exists = jnp.arange(M, dtype=jnp.int32) < tree.num_leaves
        gain = jnp.where(exists, res.gain, neg_inf)
        gain = depth_gate(gain, tree.leaf_i[:, 1])
        return res._replace(gain=gain)

    @jax.named_scope("split_find")
    def rescan_resident(
        tree, hist, slot_leaf, slot_age, laux, feature_used, unused_cnt,
        old_best, prev_feature_used, split_f,
    ):
        """Pooled CEGB: re-rank only slot-RESIDENT leaves from their resident
        histograms; evicted leaves keep their cached candidate, gain-patched
        when this split newly paid a coupled feature — exactly the staleness
        the reference's cached splits_per_leaf_ has (Split,
        serial_tree_learner.cpp:757-775: only the gain of cached splits on the
        newly-used feature is adjusted, no re-argmax)."""
        pen = leaf_penalties(laux[:, _LAUX_ND], feature_used, unused_cnt)
        lv = jnp.maximum(slot_leaf, 0)  # [P] leaf of each slot (0 for free)
        if cegb_rescan is not None:
            # custom split search (the voting learner's batched vote+elect)
            # over the RESIDENT slot rows — it is leading-axis polymorphic
            # and its collectives run uniformly across shards because slot
            # state is a pure function of the replicated split sequence;
            # free-slot rows compute garbage that the `occupied` mask drops
            res = cegb_rescan(
                hist, laux[lv, _LAUX_SG], laux[lv, _LAUX_SH],
                laux[lv, _LAUX_ND], laux[lv, _LAUX_MIN],
                laux[lv, _LAUX_MAX], pen[lv], feature_meta, feature_mask,
                params,
            )
        else:
            res = jax.vmap(
                lambda h, sg, sh, nd, mn1, mx1, pr: find_best_split(
                    h, sg, sh, nd, mn1, mx1, feature_meta, feature_mask,
                    params, pr, two_way=two_way,
                )
            )(
                hist, laux[lv, _LAUX_SG], laux[lv, _LAUX_SH],
                laux[lv, _LAUX_ND],
                laux[lv, _LAUX_MIN], laux[lv, _LAUX_MAX], pen[lv],
            )
        occupied = (slot_leaf >= 0) & (slot_age > 0) & (lv < tree.num_leaves)
        gain = jnp.where(occupied, res.gain, neg_inf)
        gain = depth_gate(gain, tree.leaf_i[lv, 1])
        pk = _pack_best(res._replace(gain=gain))  # [P, ...]
        base = old_best
        if cegb.has_coupled and split_f is not None:
            # the split just paid for split_f: cached candidates on that
            # feature are no longer charged its acquisition penalty
            newly = ~prev_feature_used[split_f]
            patch = jnp.where(
                newly
                & (old_best.i[:, 0] == split_f)
                & (old_best.f[:, 0] > neg_inf),
                cegb.tradeoff * coupled_arr[split_f],
                jnp.float32(0.0),
            )
            base = old_best._replace(f=old_best.f.at[:, 0].add(patch))
        # scatter resident results into their leaf rows; row M (out of range)
        # drops the write for free slots (JAX scatter OOB-drop semantics)
        rows = jnp.where(occupied, slot_leaf, M)
        return PackedBest(
            base.f.at[rows].set(pk.f),
            base.i.at[rows].set(pk.i),
            base.b.at[rows].set(pk.b),
        )

    # the tree's setup; the root's pass, its split and the carries' first
    # rows carry their own phases
    with jax.named_scope("tree_init"):
        # ---- root ----------------------------------------------------------
        # with a bucketed partition the [N, 3] values tensor already exists
        # (vals_all); masked_values(ones) would rebuild the identical array
        # (ones * bag_mask == bag_mask) — ~6ms/tree on TPU at 1M
        root_vals = vals_all if bucketed else masked_values(jnp.ones((N,), f32))

        @jax.named_scope("hist_build/root")
        def root_whole():
            return leaf_histogram(
                bins, root_vals, B_hist, chunk=chunk, axis_name=hist_axis,
                hist_dtype=hist_dtype, feature_sharded=feature_sharded,
                route=hist_route,
            )

        # the bucketed grower on one device, its matrix whole, is rooted at the
        # sample: the root segment is the in-bag rows, first in ``order`` and in
        # their own order
        rooted = bucketed and axis_name is None and not feature_sharded
        if rooted:
            in_bag = bag_mask > 0
            n_root = jnp.sum(in_bag, dtype=jnp.int32)
            sampled = n_root < N

            def root_sample():
                order = jnp.argsort(~in_bag, stable=True).astype(jnp.int32)
                hist = segment_histogram_batch(
                    order, jnp.zeros((1,), jnp.int32), n_root[None],
                    _kern.root_sizes,
                    phases=("hist_build/root", "hist_build/root"))[0]
                return order, hist, hist_extent(n_root[None], _kern.root_sizes)

            # every row in the bag: the identity order and one pass over the
            # table as it lies, with no gather of it
            order0, root_hist, root_streamed = jax.lax.cond(
                sampled, root_sample,
                lambda: (jnp.arange(N, dtype=jnp.int32), root_whole(),
                         jnp.int32(N)),
            )
        else:
            n_root = root_streamed = N
            root_hist = root_whole()
            if bucketed:
                order0 = jnp.arange(N, dtype=jnp.int32)
        # Root totals from the histogram of feature 0 would miss rows in padded bins;
        # sum the mask directly instead (psum'd under shard_map like GBDT's root sync,
        # serial_tree_learner.cpp:271 BeforeTrain).
        root_g = jnp.sum(grad * bag_mask)
        root_h = jnp.sum(hess * bag_mask)
        root_n = jnp.sum(bag_mask)
        if axis_name is not None:
            # shard-linear root reductions ride the same partial-accumulation
            # seam as the histograms (HistogramSource, ops/histogram.py)
            _root_src = histogram_source(axis_name)
            root_g = _root_src.combine(root_g)
            root_h = _root_src.combine(root_h)
            root_n = _root_src.combine(root_n)
        if bundled:
            if axis_name is not None and not psum_hist:
                # voting-parallel shard-local mode: remap with LOCAL totals (the
                # linearity argument on remap_hist); the split_fn's elected psum
                # then combines feature-space histograms exactly
                root_hist = remap_hist_local(root_hist)
            else:
                root_hist = remap_hist(root_hist, root_g, root_h, root_n)

        no_con_min = jnp.full((M,), -jnp.inf, f32)
        no_con_max = jnp.full((M,), jnp.inf, f32)

        if cegb_state is not None:
            feature_used0, used_in_data0 = cegb_state
        else:
            feature_used0 = jnp.zeros((F,), bool)
            used_in_data0 = jnp.zeros((F, N) if cegb.has_lazy else (1, 1), bool)
        if cegb.has_lazy:
            root_unused = (~used_in_data0).astype(f32) @ bag_mask  # [F]
            if axis_name is not None:
                root_unused = jax.lax.psum(root_unused, axis_name)
            unused0 = jnp.zeros((M, F), f32).at[0].set(root_unused)
        else:
            unused0 = jnp.zeros((M, F), f32)

        def expand_packed(res: SplitResult, idx: int) -> PackedBest:
            """Scatter one leaf's SplitResult into [M]-leading packed arrays
            (gain initialized to -inf everywhere else)."""
            row = _pack_best(res)
            f0 = jnp.zeros((M, row.f.shape[-1]), f32).at[:, 0].set(-jnp.inf)
            return PackedBest(
                f0.at[idx].set(row.f),
                jnp.zeros((M, row.i.shape[-1]), jnp.int32).at[idx].set(row.i),
                jnp.zeros((M, row.b.shape[-1]), bool).at[idx].set(row.b),
            )

        tree0 = PackedTree(
            num_leaves=jnp.int32(1),
            node_f=jnp.zeros((M, 3), f32),
            node_i=jnp.zeros((M, 4), jnp.int32),
            node_b=jnp.zeros((M, 1 + B), bool),
            leaf_f=jnp.zeros((M, 3), f32).at[0].set(
                jnp.stack(
                    [calculate_leaf_output(root_g, root_h, params), root_n, root_h]
                )
            ),
            # leaf_parent -1, leaf_depth 0 (root depth 0, tree.cpp ctor)
            leaf_i=jnp.concatenate(
                [jnp.full((M, 1), -1, jnp.int32), jnp.zeros((M, 1), jnp.int32)],
                axis=1,
            ),
            # the root's histogram pass reads every row of its segment once
            counters=_counted(
                jnp.zeros((len(COUNTER_NAMES),), f32),
                hist_rows_streamed=root_streamed, hist_rows_needed=n_root,
                root_rows=n_root, hist_columns=bins.shape[0],
                hist_rows_gathered=root_streamed,
            ),
        )

        # The [M, F, B, 3] carry only needs slice 0 initialized: every other
        # leaf's slice is written (smaller-pass + subtraction) when that leaf is
        # created, before any read. A caller-donated scratch buffer therefore
        # skips the 22MB-at-bench-shape zeros write every tree; its stale contents
        # are finite floats whose garbage candidate gains are masked by the
        # leaf-exists checks. Returned (aliased, zero-copy) when donated so the
        # caller can re-donate it for the next tree.
        with jax.named_scope("carry_write"):
            if hist_buf is not None:
                hist0 = hist_buf.at[0].set(root_hist)
            else:
                hist0 = jnp.zeros((P, F, B, 3), f32).at[0].set(root_hist)
        if pooled:
            slot_of0 = jnp.full((M,), -1, jnp.int32).at[0].set(0)
            slot_leaf0 = jnp.full((P,), -1, jnp.int32).at[0].set(0)
            slot_age0 = jnp.zeros((P,), jnp.int32).at[0].set(1)
        else:
            slot_of0 = jnp.zeros((1,), jnp.int32)
            slot_leaf0 = jnp.zeros((1,), jnp.int32)
            slot_age0 = jnp.zeros((1,), jnp.int32)

        # [M, 5] leaf aux: sums at col 0-2, monotone windows at col 3-4 — one
        # scatter per split updates all five (vs five chained pairs)
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

        if cegb_on and pooled:
            empty = PackedBest(
                jnp.zeros((M, len(_BEST_F)), f32).at[:, 0].set(-jnp.inf),
                jnp.zeros((M, len(_BEST_I)), jnp.int32),
                jnp.zeros((M, 1 + B), bool),
            )
            best0 = rescan_resident(
                tree0, hist0, slot_leaf0, slot_age0, laux0, feature_used0, unused0,
                empty, feature_used0, None,
            )
        elif cegb_on:
            root_best = rescan_all(
                tree0, hist0,
                laux0[:, _LAUX_SG], laux0[:, _LAUX_SH], laux0[:, _LAUX_ND],
                no_con_min, no_con_max, feature_used0, unused0,
            )
            best0 = _pack_best(root_best)
        else:
            root_kw = {"two_way": two_way} if split_fn is find_best_split else {}
            with jax.named_scope("split_find"):
                root_split = split_fn(
                    root_hist, root_g, root_h, root_n,
                    no_con_min[0], no_con_max[0],
                    feature_meta, feature_mask, params, **root_kw,
                )
            best0 = expand_packed(root_split, 0)

        state0 = GrowState(
            it=jnp.int32(0),
            leaf_id=jnp.zeros((1,) if bucketed else (N,), jnp.int32),
            tree=tree0,
            best=best0,
            laux=laux0,
            hist=hist0,
            feature_used=feature_used0,
            unused_cnt=unused0,
            used_in_data=used_in_data0,
            order=order0 if bucketed else jnp.zeros((1,), jnp.int32),
            leaf_begin=jnp.zeros((M,) if bucketed else (1,), jnp.int32),
            leaf_phys=(
                jnp.zeros((M,), jnp.int32).at[0].set(n_root)
                if bucketed
                else jnp.zeros((1,), jnp.int32)
            ),
            slot_of=slot_of0,
            slot_leaf=slot_leaf0,
            slot_age=slot_age0,
            spec_flag=jnp.zeros((M,) if KB else (1,), bool),
            spec_lphys=jnp.zeros((M,) if KB else (1,), jnp.int32),
            # donated scratch (like hist_buf): stale contents are read only
            # through spec_flag-gated selects and spec_flag starts all-False,
            # so skipping the [M, F, B, 3] zeros write per tree is safe
            spec_rhist=(
                (spec_buf if spec_buf is not None else jnp.zeros((M, F, B, 3), f32))
                if KB
                else jnp.zeros((1, 1, 1, 1), f32)
            ),
        )

    # scopes: what is not inside partition / hist_build / split_find /
    # carry_write below is the tree's bookkeeping, and reads as apply_split
    @jax.named_scope("apply_split")
    def apply_split(s: GrowState, best_leaf, rec: SplitResult) -> GrowState:
        """Apply one split of ``best_leaf`` by ``rec`` (Split,
        serial_tree_learner.cpp:757-851 + the next iteration's FindBestSplits)."""
        node = s.it
        new_leaf = s.tree.num_leaves

        f = rec.feature
        if bucketed:
            leaf_id = s.leaf_id  # dummy; reconstructed from order at the end
            pbegin = s.leaf_begin[best_leaf]
            pphys = s.leaf_phys[best_leaf]
            order, left_phys, by_default = partition_segment(
                s.order, pbegin, pphys, f, rec.threshold, rec.default_left,
                rec.cat_bitset,
            )
            right_phys = pphys - left_phys
            leaf_begin = s.leaf_begin.at[new_leaf].set(pbegin + left_phys)
            leaf_phys = (
                s.leaf_phys.at[best_leaf].set(left_phys).at[new_leaf].set(right_phys)
            )
            # [streamed, needed, sent by default, taken] of the partition
            part_rows = (part_extent(pphys[None]), pphys, by_default, N)
        else:
            with jax.named_scope("partition/split_columns"):
                row = gid_arr[f] if bundled else f
                col = jax.lax.dynamic_slice(bins, (row, 0), (1, N))[0].astype(jnp.int32)
            with jax.named_scope("partition/lanes"):
                if bundled:
                    col = decode_col(col, f)
                go_left = _decision_go_left(
                    col,
                    rec.threshold,
                    rec.default_left,
                    missing_arr[f],
                    default_bin_arr[f],
                    num_bin_arr[f] - 1,
                    is_cat_arr[f],
                    rec.cat_bitset[jnp.clip(col, 0, B - 1)],
                )
                in_leaf = s.leaf_id == best_leaf
                leaf_id = jnp.where(in_leaf & ~go_left, new_leaf, s.leaf_id)
                order, leaf_begin, leaf_phys = s.order, s.leaf_begin, s.leaf_phys
                part_rows = (N, jnp.sum(in_leaf), jnp.sum(in_leaf & _in_missing_bin(
                    col, missing_arr[f], default_bin_arr[f], num_bin_arr[f] - 1,
                    is_cat_arr[f],
                )), N)

        # ---- wire the tree (5 scatters, PackedTree) ----------------------
        t = s.tree
        child_idx = jnp.stack([best_leaf, new_leaf])
        parent = t.leaf_i[best_leaf, 0]
        # row M-1 is the write-off target when the split leaf is the root
        prow = jnp.where(parent >= 0, parent, M - 1)
        enc_old = -(best_leaf + 1)
        old_plc = t.node_i[prow, 2]
        old_prc = t.node_i[prow, 3]
        new_plc = jnp.where((parent >= 0) & (old_plc == enc_old), node, old_plc)
        new_prc = jnp.where((parent >= 0) & (old_prc == enc_old), node, old_prc)

        depth_child = t.leaf_i[best_leaf, 1] + 1
        parent_aux = s.laux[best_leaf]  # [5]
        parent_value = calculate_leaf_output(
            parent_aux[_LAUX_SG], parent_aux[_LAUX_SH], params
        )
        # (row, col) pairs are distinct: prow < node always (parents are
        # older nodes), and the write-off row M-1 exceeds every node index
        node_i = t.node_i.at[
            jnp.stack([node, node, node, node, prow, prow]),
            _NODE_I_COLS,
        ].set(
            jnp.stack([
                f, rec.threshold, -(best_leaf + 1), -(new_leaf + 1),
                new_plc, new_prc,
            ])
        )
        tree = PackedTree(
            counters=t.counters,  # this split's work is booked below
            num_leaves=t.num_leaves + 1,
            node_f=t.node_f.at[node].set(
                jnp.stack([rec.gain, parent_value, parent_aux[_LAUX_ND]])
            ),
            node_i=node_i,
            node_b=t.node_b.at[node].set(
                jnp.concatenate([rec.default_left[None], rec.cat_bitset])
            ),
            leaf_f=t.leaf_f.at[child_idx].set(
                jnp.stack([
                    jnp.stack([rec.left_output, rec.left_count,
                               rec.left_sum_hess]),
                    jnp.stack([rec.right_output, rec.right_count,
                               rec.right_sum_hess]),
                ])
            ),
            leaf_i=t.leaf_i.at[child_idx].set(
                jnp.stack([
                    jnp.stack([node, depth_child]),
                    jnp.stack([node, depth_child]),
                ])
            ),
        )

        # ---- leaf aggregates + monotone windows (one [2,5] scatter) ------
        # (serial_tree_learner.cpp:841-850)
        mono_f = mono_arr[f]
        mid = (rec.left_output + rec.right_output) / 2.0
        pmin = parent_aux[_LAUX_MIN]
        pmax = parent_aux[_LAUX_MAX]
        # increasing (+1): left <= right  -> left.max = mid, right.min = mid
        # decreasing (-1): left >= right  -> left.min = mid, right.max = mid
        l_min = jnp.where(mono_f < 0, mid, pmin)
        l_max = jnp.where(mono_f > 0, mid, pmax)
        r_min = jnp.where(mono_f > 0, mid, pmin)
        r_max = jnp.where(mono_f < 0, mid, pmax)
        laux = s.laux.at[child_idx].set(
            jnp.stack(
                [
                    jnp.stack([rec.left_sum_grad, rec.left_sum_hess,
                               rec.left_count, l_min, l_max]),
                    jnp.stack([rec.right_sum_grad, rec.right_sum_hess,
                               rec.right_count, r_min, r_max]),
                ]
            )
        )

        # ---- CEGB bookkeeping --------------------------------------------
        feature_used = s.feature_used
        used_in_data = s.used_in_data
        unused_cnt = s.unused_cnt
        if cegb.has_coupled:
            feature_used = feature_used.at[f].set(True)
        if cegb.has_lazy:
            # rows of the split leaf have now paid for feature f — only rows in
            # the bag: the reference inserts rows from the data partition, i.e.
            # the bagged subset (serial_tree_learner.cpp:772)
            used_in_data = used_in_data.at[f].set(
                used_in_data[f] | (in_leaf & (bag_mask > 0))
            )
            not_used = (~used_in_data).astype(f32)  # [F, N]
            lmask = (bag_mask * (leaf_id == best_leaf)).astype(f32)
            rmask = (bag_mask * (leaf_id == new_leaf)).astype(f32)
            left_unused = not_used @ lmask
            right_unused = not_used @ rmask
            if axis_name is not None:
                left_unused = jax.lax.psum(left_unused, axis_name)
                right_unused = jax.lax.psum(right_unused, axis_name)
            unused_cnt = unused_cnt.at[best_leaf].set(left_unused).at[new_leaf].set(
                right_unused
            )

        # ---- histograms: smaller child pass + subtraction ----------------
        # smaller-child choice uses the global (bagged) counts from the split
        # record: under shard_map the physical counts are shard-local and
        # shards must all histogram the SAME child before the psum
        left_smaller = rec.left_count <= rec.right_count
        small_idx = jnp.where(left_smaller, best_leaf, new_leaf)
        large_idx = jnp.where(left_smaller, new_leaf, best_leaf)
        if bucketed:
            small_begin = jnp.where(left_smaller, pbegin, pbegin + left_phys)
            small_cnt = jnp.where(left_smaller, left_phys, right_phys)
            small_hist = segment_histogram(order, small_begin, small_cnt)
            if hist_axis is not None:
                # collective AFTER the bucket switch: shards may pick different
                # bucket branches, so no psum may live inside them
                with jax.named_scope("hist_build/kernel"):
                    small_hist = histogram_source(hist_axis).combine(small_hist)
            # [streamed, needed] of the smaller child's pass and of the
            # larger child's, where that one is summed from data too
            small_rows = (hist_extent(small_cnt[None]), small_cnt)
            large_cnt = pphys - small_cnt
            large_rows = (hist_extent(large_cnt[None]), large_cnt)
        else:
            with jax.named_scope("hist_build/feed"):
                small_mask = (leaf_id == small_idx).astype(f32)
                small_vals = masked_values(small_mask)
            with jax.named_scope("hist_build/kernel"):
                small_hist = leaf_histogram(
                    bins, small_vals, B_hist, chunk=chunk,
                    axis_name=hist_axis, hist_dtype=hist_dtype,
                    feature_sharded=feature_sharded, route=hist_route,
                )
            small_rows = (N, jnp.sum(small_mask))
            large_rows = (N, jnp.sum(leaf_id == large_idx))
        if bundled:
            if hist_axis is None and axis_name is not None:
                # shard-local histograms: local remap (rec sums are global)
                small_hist = remap_hist_local(small_hist)
            else:
                small_hist = remap_hist(
                    small_hist,
                    jnp.where(left_smaller, rec.left_sum_grad, rec.right_sum_grad),
                    jnp.where(left_smaller, rec.left_sum_hess, rec.right_sum_hess),
                    jnp.where(left_smaller, rec.left_count, rec.right_count),
                )
        def large_direct():
            """Both-children path: the larger child summed from data — the
            reference's use_subtract=false branch (ConstructHistograms,
            serial_tree_learner.cpp:473)."""
            if bucketed:
                lg_begin = jnp.where(left_smaller, pbegin + left_phys, pbegin)
                lg_cnt = jnp.where(left_smaller, right_phys, left_phys)
                h = segment_histogram(order, lg_begin, lg_cnt)
                if hist_axis is not None:
                    with jax.named_scope("hist_build/kernel"):
                        h = histogram_source(hist_axis).combine(h)
            else:
                with jax.named_scope("hist_build/feed"):
                    lvals = masked_values((leaf_id == large_idx).astype(f32))
                with jax.named_scope("hist_build/kernel"):
                    h = leaf_histogram(
                        bins, lvals, B_hist, chunk=chunk,
                        axis_name=hist_axis, hist_dtype=hist_dtype,
                        feature_sharded=feature_sharded, route=hist_route,
                    )
            if bundled:
                if hist_axis is None and axis_name is not None:
                    h = remap_hist_local(h)
                else:
                    h = remap_hist(
                        h,
                        jnp.where(left_smaller, rec.right_sum_grad, rec.left_sum_grad),
                        jnp.where(left_smaller, rec.right_sum_hess, rec.left_sum_hess),
                        jnp.where(left_smaller, rec.right_count, rec.left_count),
                    )
            return h

        if pooled:
            # HistogramPool::Get: the predicate is identical on every shard
            # (slot state is a pure function of the replicated split sequence),
            # so the collective inside the miss branch executes uniformly.
            pslot = s.slot_of[best_leaf]
            cached = (pslot >= 0) if use_subtract else jnp.asarray(False)
            with jax.named_scope("split_find/prep"):
                parent_hist = s.hist[jnp.maximum(pslot, 0)]
                large_hist = jax.lax.cond(
                    cached, lambda: parent_hist - small_hist, large_direct
                )
            direct = (~cached).astype(f32)  # the larger child read its rows
            # slots: the larger child inherits the parent's slot on a hit
            # (the reference's in-place Subtract); otherwise evict the LRU.
            ages = s.slot_age
            slots_iota = jnp.arange(P, dtype=jnp.int32)
            lru0 = jnp.argmin(ages).astype(jnp.int32)
            large_slot = jnp.where(cached, pslot, lru0)
            big = jnp.int32(2**30)
            small_slot = jnp.argmin(
                ages + (slots_iota == large_slot) * big
            ).astype(jnp.int32)
            # invalidate evicted occupants, then map the children
            occ = jnp.stack([s.slot_leaf[large_slot], s.slot_leaf[small_slot]])
            leaves_iota = jnp.arange(M, dtype=jnp.int32)
            slot_of = jnp.where(
                (leaves_iota == occ[0]) | (leaves_iota == occ[1]), -1, s.slot_of
            )
            slot_of = (
                slot_of.at[small_idx].set(small_slot).at[large_idx].set(large_slot)
            )
            slot_pair = jnp.stack([small_slot, large_slot])
            # clear any OTHER slot still mapping to a child (the parent's old
            # slot when a resident parent took the miss path, e.g. the
            # use_subtract=False oracle): a stale entry would later evict as
            # `occ` and wrongly clear the live child's slot_of
            slot_leaf = jnp.where(
                (s.slot_leaf == small_idx) | (s.slot_leaf == large_idx),
                -1,
                s.slot_leaf,
            )
            slot_leaf = slot_leaf.at[slot_pair].set(
                jnp.stack([small_idx, large_idx])
            )
            stamp = s.it + 2  # > the root's stamp of 1; free slots stay 0
            slot_age = ages.at[slot_pair].set(jnp.stack([stamp, stamp]))
            with jax.named_scope("carry_write"):
                hist = s.hist.at[slot_pair].set(
                    jnp.stack([small_hist, large_hist]))
            child_rows = jnp.stack(
                [
                    jnp.where(left_smaller, small_slot, large_slot),
                    jnp.where(left_smaller, large_slot, small_slot),
                ]
            )
        else:
            with jax.named_scope("split_find/prep"):
                parent_hist = s.hist[best_leaf]
                if use_subtract:
                    large_hist = parent_hist - small_hist
                else:
                    large_hist = large_direct()
            direct = 0.0 if use_subtract else 1.0
            slot_of, slot_leaf, slot_age = s.slot_of, s.slot_leaf, s.slot_age
            # ONE stacked scatter, not two chained .at[].set: XLA updates the
            # [M, F, B, 3] carry in place for a single scatter but inserts a
            # full-buffer copy per chained update (~2 x 22MB per split at
            # M=255/F=28/B=256 — measured 40x slower on CPU, and HBM traffic
            # that would cost ~14ms/iter on TPU)
            with jax.named_scope("carry_write"):
                hist = s.hist.at[jnp.stack([small_idx, large_idx])].set(
                    jnp.stack([small_hist, large_hist])
                )
            child_rows = None  # hist rows ARE leaf rows; set below

        # ---- next-round candidate refresh --------------------------------
        if cegb_on and pooled:
            best = rescan_resident(
                tree, hist, slot_leaf, slot_age, laux, feature_used,
                unused_cnt, s.best, s.feature_used, f,
            )
        elif cegb_on:
            best = _pack_best(
                rescan_all(
                    tree, hist,
                    laux[:, _LAUX_SG], laux[:, _LAUX_SH], laux[:, _LAUX_ND],
                    laux[:, _LAUX_MIN], laux[:, _LAUX_MAX],
                    feature_used, unused_cnt,
                )
            )
        else:
            if child_rows is None:
                child_rows = child_idx  # unpooled: hist rows are leaf rows
            with jax.named_scope("split_find/prep"):
                # leaf rows unpooled, slot rows pooled
                ch_hist = hist[child_rows]
                ch_aux = laux[child_idx]  # [2, 5]
            ch_split = split2(
                ch_hist, ch_aux[:, _LAUX_SG], ch_aux[:, _LAUX_SH],
                ch_aux[:, _LAUX_ND], ch_aux[:, _LAUX_MIN], ch_aux[:, _LAUX_MAX],
            )
            ch_gain = depth_gate(ch_split.gain, depth_child)
            pb2 = _pack_best(ch_split._replace(gain=ch_gain))
            best = PackedBest(
                s.best.f.at[child_idx].set(pb2.f),
                s.best.i.at[child_idx].set(pb2.i),
                s.best.b.at[child_idx].set(pb2.b),
            )

        tree = tree._replace(counters=_counted(
            tree.counters, splits=1,
            hist_rows_streamed=small_rows[0] + direct * large_rows[0],
            hist_rows_needed=small_rows[1] + direct * large_rows[1],
            # the W=1 pass gathers the rows it streams
            hist_rows_gathered=small_rows[0] + direct * large_rows[0],
            part_rows_streamed=part_rows[0], part_rows_needed=part_rows[1],
            part_rows_missing=part_rows[2], part_rows_taken=part_rows[3],
            splits_default_left=rec.default_left & (missing_arr[f] != MISSING_NONE),
        ))
        return GrowState(
            it=s.it + 1,
            leaf_id=leaf_id,
            tree=tree,
            best=best,
            laux=laux,
            hist=hist,
            feature_used=feature_used,
            unused_cnt=unused_cnt,
            used_in_data=used_in_data,
            order=order,
            leaf_begin=leaf_begin,
            leaf_phys=leaf_phys,
            slot_of=slot_of,
            slot_leaf=slot_leaf,
            slot_age=slot_age,
            spec_flag=s.spec_flag,
            spec_lphys=s.spec_lphys,
            spec_rhist=s.spec_rhist,
        )

    # ---- forced splits preamble (ForceSplits) ---------------------------
    state = state0
    if forced_splits:
        with jax.named_scope("apply_split"):
            aborted = jnp.asarray(False)
            for (leaf_i, feat_i, thr_i) in forced_splits[: M - 1]:
                if pooled:
                    # P >= len(forced_splits)+2 is enforced above, so preamble
                    # leaves are never evicted before their forced split applies
                    hist_slice = state.hist[
                        jnp.maximum(state.slot_of[leaf_i], 0), feat_i
                    ]
                else:
                    hist_slice = state.hist[leaf_i, feat_i]
                if axis_name is not None and not psum_hist:
                    # voting-parallel keeps shard-local histograms; a forced split
                    # needs the global column (the elected-slice psum's little sibling)
                    hist_slice = jax.lax.psum(hist_slice, axis_name)
                with jax.named_scope("split_find"):
                    rec = gather_info_for_threshold(
                        hist_slice,
                        state.laux[leaf_i, _LAUX_SG],
                        state.laux[leaf_i, _LAUX_SH],
                        state.laux[leaf_i, _LAUX_ND],
                        jnp.int32(thr_i),
                        num_bin_arr[feat_i],
                        missing_arr[feat_i],
                        default_bin_arr[feat_i],
                        is_cat_arr[feat_i],
                        params,
                    )._replace(feature=jnp.int32(feat_i))
                valid = rec.gain > neg_inf
                if max_depth > 0:
                    valid &= state.tree.leaf_i[leaf_i, 1] < max_depth
                can = (~aborted) & valid
                applied = apply_split(state, jnp.int32(leaf_i), rec)
                state = jax.tree_util.tree_map(
                    lambda a, b: jnp.where(can, a, b), applied, state
                )
                aborted = aborted | ~valid

    # ---- best-gain loop --------------------------------------------------
    @jax.named_scope("apply_split")
    def cond(s: GrowState):
        return (s.it < M - 1) & (jnp.max(s.best.f[:, 0]) > 0.0)

    @jax.named_scope("apply_split")
    def body(s: GrowState) -> GrowState:
        best_leaf = jnp.argmax(s.best.f[:, 0]).astype(jnp.int32)
        rec = _unpack_best_row(s.best, best_leaf)
        s = apply_split(s, best_leaf, rec)
        return s._replace(tree=s.tree._replace(
            counters=_counted(s.tree.counters, steps=1, slots_computed=1)))

    @jax.named_scope("apply_split")
    def body_spec(s: GrowState) -> GrowState:
        """One speculative batch: compute the top-KB candidates' split work
        (skipping slots whose results are cached from an earlier batch),
        apply the longest sequential-order prefix, and CACHE the rest — so
        each split's partition/histogram work happens exactly once no matter
        how often it is speculated."""
        it0 = s.it
        nl0 = s.tree.num_leaves
        kb_iota = jnp.arange(KB, dtype=jnp.int32)

        # top-k by cached gain; lax.top_k breaks ties toward lower indices,
        # matching the sequential argmax's first-max choice
        g_top, b_idx = jax.lax.top_k(s.best.f[:, 0], KB)
        b_top = b_idx.astype(jnp.int32)
        rf = s.best.f[b_top]  # [KB, 9]
        ri = s.best.i[b_top]  # [KB, 3]
        rb = s.best.b[b_top]  # [KB, 1 + B]
        feat, thr = ri[:, 0], ri[:, 1]
        dleft, member = rb[:, 0].astype(bool), rb[:, 1:].astype(bool)
        pbegin = s.leaf_begin[b_top]
        pphys = s.leaf_phys[b_top]
        cached = s.spec_flag[b_top]  # [KB]
        # slots already cached, or with no live split (gain <= 0, incl. the
        # -inf filler the tail of every tree's top-k carries), contribute
        # zero-size segments: the lattice switch keys on the largest slot
        # actually COMPUTING, their lanes carry no histogram mass, and a
        # dead slot's garbage record (feat may be -1) never drives work
        compute = (~cached) & (g_top > 0.0)

        pphys_c = jnp.where(compute, pphys, 0)
        order2, left_phys_c, by_default = partition_batch(
            s.order, pbegin, pphys_c, feat, thr, dleft, member
        )
        left_phys = jnp.where(cached, s.spec_lphys[b_top], left_phys_c)
        right_phys = pphys - left_phys

        # smaller-child choice from the GLOBAL counts in the cached record
        # (shard-uniform under shard_map, like the sequential path)
        l_cnt, r_cnt = rf[:, 3], rf[:, 6]
        left_smaller = l_cnt <= r_cnt
        small_begin = jnp.where(left_smaller, pbegin, pbegin + left_phys)
        small_cnt = jnp.where(
            compute, jnp.where(left_smaller, left_phys, right_phys), 0
        )
        small_hist = (
            segment_histogram_flat if use_flat else segment_histogram_batch
        )(order2, small_begin, small_cnt)
        if hist_axis is not None:
            # ONE collective for the whole batch (vs one per split)
            with jax.named_scope("hist_build/kernel"):
                small_hist = histogram_source(hist_axis).combine(small_hist)
        if bundled:
            small_hist = jax.vmap(remap_hist)(
                small_hist,
                jnp.where(left_smaller, rf[:, 1], rf[:, 4]),
                jnp.where(left_smaller, rf[:, 2], rf[:, 5]),
                jnp.where(left_smaller, l_cnt, r_cnt),
            )
        # for a cached slot, hist row b_j already holds the LEFT child's
        # histogram (committed at cache time) and the right child's parks in
        # spec_rhist; for computing slots it still holds the parent's
        with jax.named_scope("split_find/prep"):
            parent_hist = _carry_rows(s.hist, b_top)
            large_hist = parent_hist - small_hist
            ls4 = left_smaller[:, None, None, None]
            c4 = cached[:, None, None, None]
            lhist = jnp.where(
                c4, parent_hist, jnp.where(ls4, small_hist, large_hist)
            )
            rhist = jnp.where(
                c4, _carry_rows(s.spec_rhist, b_top),
                jnp.where(ls4, large_hist, small_hist),
            )

        # ---- children: aux, monotone windows, one batched scan ----------
        mono_f = mono_arr[feat]
        mid = (rf[:, 7] + rf[:, 8]) * 0.5
        pmin = s.laux[b_top, _LAUX_MIN]
        pmax = s.laux[b_top, _LAUX_MAX]
        l_min = jnp.where(mono_f < 0, mid, pmin)
        l_max = jnp.where(mono_f > 0, mid, pmax)
        r_min = jnp.where(mono_f > 0, mid, pmin)
        r_max = jnp.where(mono_f < 0, mid, pmax)

        with jax.named_scope("split_find/prep"):
            ch_hist = jnp.concatenate([lhist, rhist], axis=0)  # [2KB, F, B, 3]
        with jax.named_scope("split_find"):
            ch_res = jax.vmap(
                lambda h, sg, sh, nd, mn, mx: find_best_split(
                    h, sg, sh, nd, mn, mx, feature_meta, feature_mask, params,
                    two_way=two_way,
                )
            )(
                ch_hist,
                jnp.concatenate([rf[:, 1], rf[:, 4]]),
                jnp.concatenate([rf[:, 2], rf[:, 5]]),
                jnp.concatenate([l_cnt, r_cnt]),
                jnp.concatenate([l_min, r_min]),
                jnp.concatenate([l_max, r_max]),
            )
        depth_child = s.tree.leaf_i[b_top, 1] + 1  # [KB]
        ch_gain = depth_gate(
            ch_res.gain, jnp.concatenate([depth_child, depth_child])
        )

        # ---- sequential-prefix validation -------------------------------
        # slot j applies iff (gain, slot) lex-beats every child produced by
        # the batch so far — exactly the argmax order the sequential loop
        # would follow (higher gain wins; equal gain -> lower slot wins).
        gl, gr = ch_gain[:KB], ch_gain[KB:]
        new_slot = nl0 + kb_iota  # child slot ids along the applied prefix
        pair_g = jnp.maximum(gl, gr)
        pair_s = jnp.where(gl >= gr, b_top, new_slot)  # tie -> lower (left)
        big = jnp.int32(2 ** 30)
        run_g, run_s = neg_inf, big
        cm_g, cm_s = [], []
        for j in range(KB):  # exclusive lexicographic running max (tiny)
            cm_g.append(run_g)
            cm_s.append(run_s)
            beats = (pair_g[j] > run_g) | (
                (pair_g[j] == run_g) & (pair_s[j] < run_s)
            )
            run_g = jnp.where(beats, pair_g[j], run_g)
            run_s = jnp.where(beats, pair_s[j], run_s)
        cm_g, cm_s = jnp.stack(cm_g), jnp.stack(cm_s)
        ok = (g_top > cm_g) | ((g_top == cm_g) & (b_top < cm_s))
        valid = (g_top > 0.0) & ok & (it0 + kb_iota < M - 1)
        applied = jnp.cumprod(valid.astype(jnp.int32)).astype(bool)
        p = jnp.sum(applied.astype(jnp.int32))

        # ---- apply the prefix (batched scatters; row M drops) -----------
        drop = jnp.int32(M)
        node_idx = it0 + kb_iota
        nrow = jnp.where(applied, node_idx, drop)
        lrow = jnp.where(applied, b_top, drop)
        rrow = jnp.where(applied, new_slot, drop)
        ch_rows = jnp.concatenate([lrow, rrow])
        # computed-but-unapplied slots with a live split become cache entries
        cache_set = compute & (~applied)
        crow = jnp.where(cache_set, b_top, drop)

        t = s.tree
        # parent pointers: each applied leaf's encoding appears in exactly
        # one existing node row; remap it BEFORE writing the new node rows
        # (whose own left-child encoding is that same value). No write-off
        # row needed: a root split's encoding matches nothing.
        node_ch = t.node_i[:, 2:4]
        for j in range(KB):
            node_ch = jnp.where(
                applied[j] & (node_ch == -(b_top[j] + 1)),
                node_idx[j], node_ch,
            )
        node_i = jnp.concatenate([t.node_i[:, :2], node_ch], axis=1)
        node_i = node_i.at[nrow].set(
            jnp.stack([feat, thr, -(b_top + 1), -(new_slot + 1)], axis=1)
        )
        parent_aux = s.laux[b_top]  # [KB, 5]
        parent_value = calculate_leaf_output(
            parent_aux[:, _LAUX_SG], parent_aux[:, _LAUX_SH], params
        )
        tree = PackedTree(
            counters=_counted(
                t.counters, steps=1, slots_computed=jnp.sum(compute),
                splits=p,
                # lanes: every one of the KB lanes passes the largest
                # computing slot's bucket; flat: one concatenated pass,
                # whose gather reads the whole switch branch
                hist_rows_streamed=(
                    flat_extent(small_cnt) if use_flat
                    else hist_extent(small_cnt) * KB
                ),
                hist_rows_gathered=(
                    _flat_rows[_flat_plan(small_cnt)[0]] if use_flat
                    else hist_extent(small_cnt) * KB
                ),
                hist_rows_needed=jnp.sum(small_cnt),
                part_rows_streamed=part_extent(pphys_c),
                part_rows_needed=jnp.sum(pphys_c),
                part_rows_taken=KB * N,
                part_rows_missing=by_default,
                splits_default_left=jnp.sum(
                    applied & dleft & (missing_arr[feat] != MISSING_NONE)
                ),
            ),
            num_leaves=nl0 + p,
            node_f=t.node_f.at[nrow].set(
                jnp.stack(
                    [rf[:, 0], parent_value, parent_aux[:, _LAUX_ND]], axis=1
                )
            ),
            node_i=node_i,
            node_b=t.node_b.at[nrow].set(rb.astype(bool)),
            leaf_f=t.leaf_f.at[ch_rows].set(
                jnp.concatenate([
                    jnp.stack([rf[:, 7], rf[:, 3], rf[:, 2]], axis=1),
                    jnp.stack([rf[:, 8], rf[:, 6], rf[:, 5]], axis=1),
                ])
            ),
            leaf_i=t.leaf_i.at[ch_rows].set(
                jnp.concatenate(
                    [jnp.stack([node_idx, depth_child], axis=1)] * 2
                )
            ),
        )
        laux = s.laux.at[ch_rows].set(
            jnp.concatenate([
                jnp.stack([rf[:, 1], rf[:, 2], rf[:, 3], l_min, l_max], axis=1),
                jnp.stack([rf[:, 4], rf[:, 5], rf[:, 6], r_min, r_max], axis=1),
            ])
        )
        leaf_begin = s.leaf_begin.at[rrow].set(pbegin + left_phys)
        leaf_phys = s.leaf_phys.at[ch_rows].set(
            jnp.concatenate([left_phys, right_phys])
        )
        # the LEFT child's histogram lands in row b_j both on apply and on
        # cache (the parent histogram there is dead once its children are
        # built); the right child's goes to its new slot on apply, or parks
        # in spec_rhist keyed by the parent on cache
        lrow_hist = jnp.where(applied | cache_set, b_top, drop)
        with jax.named_scope("carry_write"):
            hist = s.hist.at[jnp.concatenate([lrow_hist, rrow])].set(
                jnp.concatenate([lhist, rhist])
            )
            spec_rhist = s.spec_rhist.at[crow].set(rhist)
        spec_lphys = s.spec_lphys.at[crow].set(left_phys)
        spec_flag = (
            s.spec_flag.at[crow].set(True)
            .at[lrow].set(False)  # applied: children start uncached
            .at[rrow].set(False)
        )
        pb2 = _pack_best(ch_res._replace(gain=ch_gain))  # [2KB, ...]
        best = PackedBest(
            s.best.f.at[ch_rows].set(pb2.f),
            s.best.i.at[ch_rows].set(pb2.i),
            s.best.b.at[ch_rows].set(pb2.b),
        )
        return GrowState(
            it=it0 + p,
            leaf_id=s.leaf_id,
            tree=tree,
            best=best,
            laux=laux,
            hist=hist,
            feature_used=s.feature_used,
            unused_cnt=s.unused_cnt,
            used_in_data=s.used_in_data,
            order=order2,
            leaf_begin=leaf_begin,
            leaf_phys=leaf_phys,
            slot_of=s.slot_of,
            slot_leaf=s.slot_leaf,
            slot_age=s.slot_age,
            spec_flag=spec_flag,
            spec_lphys=spec_lphys,
            spec_rhist=spec_rhist,
        )

    @jax.named_scope("oob_leaf")
    def leaves_by_tree(t: PackedTree) -> jax.Array:
        """[N] int32: the leaf every row of the table falls in by the
        finished tree's own splits (AddPredictionToScore over the
        out-of-bag indices, gbdt.cpp:484), with no walk down the tree: a
        row is in the leaf all of whose ancestors' decisions it shares, so
        one [leaves, nodes] x [nodes, rows] product of +-1 matrices counts a
        row's agreements with each leaf's path, and the leaf whose count is
        its depth holds the row. Reads the tree's M - 1 split columns once,
        in blocks of rows."""
        nodes = M - 1
        feat, thr = t.node_i[:nodes, 0], t.node_i[:nodes, 1]
        live = jnp.arange(nodes, dtype=jnp.int32) < t.num_leaves - 1
        # node i is item i, leaf l item nodes + l (a child < 0 is leaf -(c+1))
        kids = t.node_i[:nodes, 2:4]
        kids = jnp.where(kids >= 0, kids, nodes - (kids + 1))
        item = jnp.arange(2 * M - 1, dtype=jnp.int32)[:, None]
        # [items, nodes]: +1 on a node's left child, -1 on its right child
        side = jnp.where(
            live[None, :],
            (item == kids[None, :, 0]).astype(f32)
            - (item == kids[None, :, 1]).astype(f32),
            0.0,
        )
        # item a reaches item b: b is a or one of a's ancestors; a path has
        # under M steps, and squaring doubles the steps covered
        reach = jnp.eye(2 * M - 1, dtype=f32) + jnp.pad(
            jnp.abs(side), ((0, 0), (0, M)))
        for _ in range(_ceil_log2(M)):
            reach = jnp.minimum(reach @ reach, 1.0)
        # [M, nodes]: +1 where the leaf lies under the node's left child, -1
        # under its right; a row of zeros for a leaf the tree has not grown
        path = (reach @ side)[nodes:]
        depth = jnp.sum(jnp.abs(path), axis=1)
        grown = jnp.arange(M, dtype=jnp.int32) < t.num_leaves

        block_rows = min(N, 1 << 15)
        blocks = -(-N // block_rows)
        cols = jnp.take(
            bins, (gid_arr[feat] if bundled else feat).astype(jnp.int32), axis=0
        )
        cols = jnp.pad(cols, ((0, 0), (0, blocks * block_rows - N)))

        def of_node(per_feature):  # [nodes, 1]: down a column of the block
            return per_feature[feat][:, None]

        def block(col):  # [nodes, block_rows] bins of each node's own feature
            colv = col.astype(jnp.int32)
            if bundled:
                colv = decode_col(colv, feat[:, None])
            member = (
                jnp.take_along_axis(
                    t.node_b[:nodes, 1:], jnp.clip(colv, 0, B - 1), axis=1)
                if "is_categorical" in feature_meta
                else False
            )
            go_left = _decision_go_left(
                colv, thr[:, None], t.node_b[:nodes, :1], of_node(missing_arr),
                of_node(default_bin_arr), of_node(num_bin_arr) - 1,
                of_node(is_cat_arr), member,
            )
            # +-1 and 0 are exact in bfloat16, their sums in float32
            agree = jnp.dot(
                path.astype(jnp.bfloat16),
                jnp.where(go_left, 1.0, -1.0).astype(jnp.bfloat16),
                preferred_element_type=f32,
            )
            return jnp.argmax(
                (agree == depth[:, None]) & grown[:, None], axis=0
            ).astype(jnp.int32)

        return jax.lax.map(
            block, cols.reshape(nodes, blocks, block_rows).transpose(1, 0, 2)
        ).reshape(-1)[:N]

    if M > 1:
        final = jax.lax.while_loop(cond, body_spec if KB else body, state)
    else:
        final = state

    if bucketed:
        with jax.named_scope("leaf_ids"):
            # reconstruct per-row leaf ids from the segment layout: position
            # -> owning segment (empty leaves keyed past N so they claim
            # nothing), then scatter through the permutation.
            key = jnp.where(
                final.leaf_phys > 0,
                final.leaf_begin,
                N + jnp.arange(M, dtype=jnp.int32),
            )
            ordl = jnp.argsort(key)
            slot = jnp.searchsorted(key[ordl], jnp.arange(N, dtype=jnp.int32), side="right") - 1
            pos_leaf = ordl[jnp.clip(slot, 0, M - 1)].astype(jnp.int32)
            out_leaf_id = jnp.zeros((N,), jnp.int32).at[final.order].set(pos_leaf)
            if rooted:
                # the rows past the root segment are in no leaf's segment
                out_leaf_id = jax.lax.cond(
                    sampled,
                    lambda: jnp.where(in_bag, out_leaf_id, leaves_by_tree(final.tree)),
                    lambda: out_leaf_id,
                )
    else:
        out_leaf_id = final.leaf_id

    with jax.named_scope("tree_out"):
        out_tree = _unpack_tree(final.tree, M)
        if axis_name is not None:
            # per-shard counts: the slowest shard sets the time
            out_tree = out_tree._replace(
                counters=jax.lax.pmax(out_tree.counters, axis_name))
    out = (out_tree, out_leaf_id)
    if cegb_on:
        out = out + ((final.feature_used, final.used_in_data),)
    if hist_buf is not None:
        out = out + (final.hist,)  # aliases the donated buffer (zero-copy)
    if spec_buf is not None:
        # aliased like hist: the caller re-adopts it for the next tree. A
        # seq-mode trace (KB == 0) hands the untouched donation back so the
        # donated input still has an aliasable output.
        out = out + (final.spec_rhist if KB else spec_buf,)
    return out


# Scan-invocable entry: the UNDECORATED grow body, for embedding inside an
# outer jit — the device-resident boosting loop (models/gbdt.py train_chunk)
# calls it from a lax.scan body, where the grow must trace into the caller's
# program instead of standing alone behind its own jit/donation boundary.
# jax.jit preserves the wrapped function via functools.wraps; every "static"
# argument is then an ordinary Python value closed over at trace time, and
# ``hist_buf`` donation does not apply (pass None — XLA reuses the per-
# iteration scratch across scan steps on its own).
grow_tree_scan = grow_tree.__wrapped__
