"""Vectorized best-split search over leaf histograms.

TPU-native counterpart of FeatureHistogram's per-feature threshold scans
(/root/reference/src/treelearner/feature_histogram.hpp:91-650). The reference walks
each feature's bins twice (right-to-left then left-to-right) with early-exit
branches; here both directions become cumulative sums over the bin axis for ALL
features at once, with every constraint (min_data_in_leaf, min_sum_hessian_in_leaf,
min_gain_to_split, L1/L2, max_delta_step, monotone clamps, missing-value bin
exclusions) expressed as masks — no data-dependent control flow, so the whole scan
jits into one fused XLA program.

Semantics preserved exactly (including kEpsilon placements, feature_histogram.hpp:87
and the scan accumulator seeds, and scan-order tie-breaking):

 * missing_type None (or num_bin<=2): single right-to-left scan, default_left=True
   (flipped to False when missing_type is NaN and num_bin<=2).
 * missing_type Zero: both scans skip the default(zero) bin — its mass lands on the
   complement side, i.e. zeros follow the default direction.
 * missing_type NaN: the last bin is the NaN bin; it is excluded from explicit
   accumulation so NaNs follow the default direction.
 * dir=-1 prefers the largest threshold among equal gains, dir=+1 the smallest, and
   dir=+1 must strictly beat dir=-1 (strict '>' updates in the reference loops).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, NamedTuple

import jax
import jax.numpy as jnp

K_EPSILON = 1e-15  # meta.h:42
K_MIN_SCORE = -jnp.inf

MISSING_NONE = 0
MISSING_ZERO = 1
MISSING_NAN = 2


class SplitParams(NamedTuple):
    """Static split hyperparameters (subset of Config used by the scan)."""

    lambda_l1: float
    lambda_l2: float
    max_delta_step: float
    min_data_in_leaf: int
    min_sum_hessian_in_leaf: float
    min_gain_to_split: float
    # categorical split knobs (config.h:510-540); trailing defaults keep older
    # positional constructions working
    max_cat_to_onehot: int = 4
    cat_smooth: float = 10.0
    cat_l2: float = 10.0
    max_cat_threshold: int = 32
    min_data_per_group: int = 100


class CegbParams(NamedTuple):
    """Static CEGB (cost-effective gradient boosting) switches (config.h:389-405).

    The per-feature penalty vectors travel in ``feature_meta`` as
    ``cegb_coupled``/``cegb_lazy`` [F]; these flags gate the (costly) per-leaf
    rescan path in the grower.
    """

    tradeoff: float = 1.0
    penalty_split: float = 0.0
    has_coupled: bool = False
    has_lazy: bool = False

    @property
    def enabled(self) -> bool:
        return self.penalty_split != 0.0 or self.has_coupled or self.has_lazy


def threshold_l1(s: jax.Array, l1: float) -> jax.Array:
    """ThresholdL1 (feature_histogram.hpp:446)."""
    if l1 == 0.0:
        return s
    return jnp.sign(s) * jnp.maximum(jnp.abs(s) - l1, 0.0)


def calculate_leaf_output(sum_grad, sum_hess, p: SplitParams):
    """CalculateSplittedLeafOutput without monotone clamp (feature_histogram.hpp:451)."""
    ret = -threshold_l1(sum_grad, p.lambda_l1) / (sum_hess + p.lambda_l2)
    if p.max_delta_step > 0.0:
        ret = jnp.clip(ret, -p.max_delta_step, p.max_delta_step)
    return ret


def _leaf_output_constrained(sum_grad, sum_hess, p: SplitParams, min_c, max_c):
    return jnp.clip(calculate_leaf_output(sum_grad, sum_hess, p), min_c, max_c)


def _gain_given_output(sum_grad, sum_hess, output, p: SplitParams):
    """GetLeafSplitGainGivenOutput (feature_histogram.hpp:505)."""
    sg_l1 = threshold_l1(sum_grad, p.lambda_l1)
    return -(2.0 * sg_l1 * output + (sum_hess + p.lambda_l2) * output * output)


def leaf_split_gain(sum_grad, sum_hess, p: SplitParams):
    """GetLeafSplitGain (feature_histogram.hpp:498): parent gain, unconstrained."""
    out = calculate_leaf_output(sum_grad, sum_hess, p)
    return _gain_given_output(sum_grad, sum_hess, out, p)


class SplitResult(NamedTuple):
    gain: jax.Array  # scalar f32, already minus gain_shift; <=0 means no split
    feature: jax.Array  # int32 index into used features; -1 if none
    threshold: jax.Array  # int32 bin threshold (left: bin <= threshold)
    default_left: jax.Array  # bool
    left_sum_grad: jax.Array
    left_sum_hess: jax.Array
    left_count: jax.Array
    right_sum_grad: jax.Array
    right_sum_hess: jax.Array
    right_count: jax.Array
    left_output: jax.Array
    right_output: jax.Array
    # categorical bitset split (SplitInfo::cat_threshold, split_info.hpp):
    # num_cat = 0 for numerical; >= 1 means "row goes left iff its bin is a
    # member of cat_bitset" (CategoricalDecisionInner, tree.h:275)
    num_cat: Any = 0  # scalar int32
    cat_bitset: Any = False  # [B] bool bin membership


def _bin_prefix(contrib: jax.Array) -> jax.Array:
    """Inclusive prefix over the bin axis (axis=1 of [..., B, 3]).

    On CPU this is a lax.scan left fold — the same sequential accumulation
    order as the reference's per-bin loops, and ~2x faster than XLA:CPU's
    O(B^2) reduce-window lowering of cumsum. Elsewhere (TPU) a 256-step
    sequential scan would serialize, so jnp.cumsum's reduce-window stays.
    The two differ by ~1ulp of f32 reassociation; each backend is
    self-consistent, which is what the dense-vs-EFB tree-equality tests
    require (any mixed-order scheme flips argmax tie-breaks — a reassociated
    associative_scan measurably broke tests/test_sparse_efb.py).

    The choice keys off the PROCESS-DEFAULT backend at trace time, not the
    computation's actual placement: a CPU-placed grow in a TPU-default
    process traces the reduce-window path (correct, just without the CPU
    speedup). Per-process platform pinning — what tests/conftest.py and the
    bench worker do — is the supported way to select the CPU fold.
    """
    if jax.default_backend() != "cpu":
        return jnp.cumsum(contrib, axis=1)
    xs = jnp.moveaxis(contrib, 1, 0)

    def step(carry, row):
        carry = carry + row
        return carry, carry

    _, ys = jax.lax.scan(step, jnp.zeros_like(xs[0]), xs)
    return jnp.moveaxis(ys, 0, 1)


def missing_flags(num_bin, missing):
    """(multi_bin, use_na, skip_def, single_scan) per feature — the
    missing-direction scan selectors shared by the XLA scan and the Pallas
    split kernel (split_pallas.py)."""
    multi_bin = num_bin > 2
    use_na = (missing == MISSING_NAN) & multi_bin
    skip_def = (missing == MISSING_ZERO) & multi_bin
    return multi_bin, use_na, skip_def, ~(use_na | skip_def)


def excluded_bins(bins, num_bin, default_bin, use_na, skip_def):
    """[F, B] mask of bins excluded from explicit accumulation (padding,
    the zero bin under missing=Zero, the NaN bin under missing=NaN)."""
    nan_bin = (num_bin - 1)[:, None]
    excl = bins >= num_bin[:, None]
    excl |= skip_def[:, None] & (bins == default_bin[:, None])
    excl |= use_na[:, None] & (bins == nan_bin)
    return excl


def candidate_gains(
    lg, lh, rg, rh, lc, rc, valid, mono_b, min_c, max_c, min_gain_shift, p
):
    """Masked split gains for one scan direction. Broadcast-polymorphic:
    the XLA scan calls it at [F, B] with scalar constraints, the Pallas
    kernel at [2, F, B] with [2, 1, 1] constraints — all reference gates
    (min_data/min_hess/monotone/min_gain, feature_histogram.hpp:91-650)
    live HERE exactly once."""
    ok = (
        valid
        & (lc >= p.min_data_in_leaf)
        & (rc >= p.min_data_in_leaf)
        & (lh >= p.min_sum_hessian_in_leaf)
        & (rh >= p.min_sum_hessian_in_leaf)
    )
    lo = _leaf_output_constrained(lg, lh, p, min_c, max_c)
    ro = _leaf_output_constrained(rg, rh, p, min_c, max_c)
    g = _gain_given_output(lg, lh, lo, p) + _gain_given_output(rg, rh, ro, p)
    mono_bad = ((mono_b > 0) & (lo > ro)) | ((mono_b < 0) & (lo < ro))
    g = jnp.where(mono_bad, 0.0, g)
    ok &= g > min_gain_shift
    return jnp.where(ok, g, K_MIN_SCORE)


def valid_pos_mask(thresholds, num_bin_b, default_bin_b, skip_def_b, not_single_b):
    """dir=+1 candidate validity (runs only for missing-handling scans)."""
    v = thresholds <= (num_bin_b - 2)
    v &= ~(skip_def_b & (thresholds == default_bin_b))
    return v & not_single_b


def valid_neg_mask(thresholds, num_bin_b, default_bin_b, skip_def_b, use_na_b):
    """dir=-1 candidate validity (excludes the NaN bin's threshold)."""
    v = thresholds <= (num_bin_b - 2 - use_na_b.astype(jnp.int32))
    return v & ~(skip_def_b & (thresholds == default_bin_b - 1))


class _ScanOut(NamedTuple):
    """Per-feature best candidates + side-sum arrays for recovery."""

    g_best: jax.Array  # [F]
    t_best: jax.Array  # [F]
    dl_best: jax.Array  # [F]
    use_pos: jax.Array  # [F]
    is_cat: jax.Array  # [F]
    lg_pos: jax.Array  # [F, B]
    lh_pos: jax.Array
    lc_pos: jax.Array
    lg_neg: jax.Array
    lh_neg: jax.Array
    lc_neg: jax.Array
    # categorical best per feature (already reduced over candidates)
    cat_lg: jax.Array  # [F] left sums of the best categorical candidate
    cat_lh: jax.Array  # [F] (includes +kEpsilon)
    cat_lc: jax.Array  # [F]
    cat_member: jax.Array  # [F, B] bool: left-side bin membership
    cat_ncat: jax.Array  # [F] int32 number of categories on the left
    cat_use_ctr: jax.Array  # [F] bool: True when the CTR path (cat_l2) won
    min_gain_shift: jax.Array


def _scan_candidates(
    hist: jax.Array,  # [F, B, 3] (sum_grad, sum_hess, count)
    sum_grad: jax.Array,  # leaf totals (scalars)
    sum_hess: jax.Array,
    num_data: jax.Array,
    min_constraint: jax.Array,  # monotone constraint window for this leaf
    max_constraint: jax.Array,
    feature_meta: Dict[str, jax.Array],  # num_bin/missing_type/default_bin/monotone [F]
    params: SplitParams,
    two_way: bool = True,
) -> _ScanOut:
    """Per-feature threshold scan; the shared core of find_best_split and the
    voting-parallel local stage (voting_parallel_tree_learner.cpp:337).

    ``two_way=False`` is a trace-time guarantee that every feature is
    single-scan (missing_type None or num_bin<=2), so the dir=+1 pass — whose
    candidates would all be masked invalid anyway — is skipped entirely.
    Results are identical to ``two_way=True`` whenever the guarantee holds
    (differentially tested in tests/test_micro_exact.py)."""
    F, B, _ = hist.shape
    p = params
    num_bin = feature_meta["num_bin"].astype(jnp.int32)  # [F]
    missing = feature_meta["missing_type"].astype(jnp.int32)
    default_bin = feature_meta["default_bin"].astype(jnp.int32)
    mono = feature_meta["monotone"].astype(jnp.int32)

    sum_hess_eff = sum_hess + 2 * K_EPSILON  # feature_histogram.hpp:87

    gain_shift = leaf_split_gain(sum_grad, sum_hess_eff, p)
    min_gain_shift = gain_shift + p.min_gain_to_split

    multi_bin, use_na, skip_def, single_scan = missing_flags(num_bin, missing)

    bins = jnp.arange(B, dtype=jnp.int32)[None, :]  # [1, B]
    excl = excluded_bins(bins, num_bin, default_bin, use_na, skip_def)
    contrib = hist * (~excl)[:, :, None].astype(hist.dtype)  # [F, B, 3]

    prefix = _bin_prefix(contrib)
    total = prefix[:, -1, :]  # [F, 3] sums over included bins

    thresholds = jnp.arange(B, dtype=jnp.int32)[None, :]  # threshold t -> left bins <= t

    def side_stats(left_g, left_h_raw, left_c):
        left_h = left_h_raw + K_EPSILON
        right_g = sum_grad - left_g
        right_h = sum_hess_eff - left_h
        right_c = num_data - left_c
        return left_h, right_g, right_h, right_c

    def gains_for(left_g, left_h, right_g, right_h, left_c, right_c, valid):
        return candidate_gains(
            left_g, left_h, right_g, right_h, left_c, right_c, valid,
            mono[:, None], min_constraint, max_constraint, min_gain_shift, p,
        )

    # ---- dir = +1 (left-to-right; default_left = False) ------------------
    lg_pos = prefix[:, :, 0]
    lh_pos_raw = prefix[:, :, 1]
    lc_pos = prefix[:, :, 2]
    lh_pos, rg_pos, rh_pos, rc_pos = side_stats(lg_pos, lh_pos_raw, lc_pos)
    if two_way:
        valid_pos = valid_pos_mask(
            thresholds, num_bin[:, None], default_bin[:, None],
            skip_def[:, None], (~single_scan)[:, None],
        )
        gains_pos = gains_for(lg_pos, lh_pos, rg_pos, rh_pos, lc_pos, rc_pos, valid_pos)
    else:
        gains_pos = None  # every candidate would be masked invalid

    # ---- dir = -1 (right-to-left; default_left = True) -------------------
    rg_neg_raw = total[:, None, 0] - prefix[:, :, 0]
    rh_neg_raw = total[:, None, 1] - prefix[:, :, 1]
    rc_neg = total[:, None, 2] - prefix[:, :, 2]
    rh_neg = rh_neg_raw + K_EPSILON
    lg_neg = sum_grad - rg_neg_raw
    lh_neg = sum_hess_eff - rh_neg
    lc_neg = num_data - rc_neg
    valid_neg = valid_neg_mask(
        thresholds, num_bin[:, None], default_bin[:, None],
        skip_def[:, None], use_na[:, None],
    )
    gains_neg = gains_for(lg_neg, lh_neg, rg_neg_raw, rh_neg, lc_neg, rc_neg, valid_neg)

    # ---- categorical candidates -----------------------------------------
    # FindBestThresholdCategorical (feature_histogram.hpp:118-279). Features
    # with num_bin <= max_cat_to_onehot use the one-hot branch (left = one
    # bin); the rest use the CTR-sorted many-vs-many branch: bins with count
    # >= cat_smooth, sorted by sum_grad/(sum_hess+cat_smooth), scanned from
    # both ends with cat_l2 regularization and min_data_per_group grouping.
    is_cat = feature_meta.get("is_categorical")
    has_cat = is_cat is not None  # key presence = static trace-time switch
    if not has_cat:
        is_cat = jnp.zeros((F,), bool)
        zf = jnp.zeros((F,), hist.dtype)
        cat_lg = cat_lh = cat_lc = zf
        cat_member = jnp.zeros((F, B), bool)
        cat_ncat = jnp.zeros((F,), jnp.int32)
        cat_use_ctr = jnp.zeros((F,), bool)
        g_cat = jnp.full((F,), K_MIN_SCORE, hist.dtype)
        t_cat = jnp.zeros((F,), jnp.int32)
    else:
        is_cat = is_cat.astype(bool)
        used_bin = num_bin + jnp.where(missing == MISSING_NONE, 0, -1)  # [F]

        # one-hot branch: left = the single bin t, right = rest; default_left=False
        oh_lg = hist[:, :, 0]
        oh_lh_raw = hist[:, :, 1]
        oh_lc = hist[:, :, 2]
        oh_lh = oh_lh_raw + K_EPSILON
        oh_rg = sum_grad - oh_lg
        oh_rh = sum_hess_eff - oh_lh
        oh_rc = num_data - oh_lc
        oh_valid = thresholds < used_bin[:, None]
        oh_valid &= (oh_lc >= p.min_data_in_leaf) & (oh_rc >= p.min_data_in_leaf)
        oh_valid &= (oh_lh_raw >= p.min_sum_hessian_in_leaf) & (
            oh_rh >= p.min_sum_hessian_in_leaf
        )
        oh_lo = _leaf_output_constrained(oh_lg, oh_lh, p, min_constraint, max_constraint)
        oh_ro = _leaf_output_constrained(oh_rg, oh_rh, p, min_constraint, max_constraint)
        oh_g = _gain_given_output(oh_lg, oh_lh, oh_lo, p) + _gain_given_output(
            oh_rg, oh_rh, oh_ro, p
        )
        oh_valid &= oh_g > min_gain_shift
        gains_oh = jnp.where(oh_valid, oh_g, K_MIN_SCORE)
        t_oh = jnp.argmax(gains_oh, axis=1).astype(jnp.int32)  # smallest t wins ties
        g_oh = jnp.take_along_axis(gains_oh, t_oh[:, None], axis=1)[:, 0]
        oh_sel = t_oh[:, None]
        oh_best_lg = jnp.take_along_axis(oh_lg, oh_sel, axis=1)[:, 0]
        oh_best_lh = jnp.take_along_axis(oh_lh, oh_sel, axis=1)[:, 0]
        oh_best_lc = jnp.take_along_axis(oh_lc, oh_sel, axis=1)[:, 0]

        # CTR-sorted branch (cat_l2 folded into l2 for gains AND leaf outputs)
        p_cat = p._replace(lambda_l2=p.lambda_l2 + p.cat_l2)
        cnt_b = hist[:, :, 2]
        bin_valid = (bins < used_bin[:, None]) & (cnt_b >= p.cat_smooth)  # [F, B]
        ctr = hist[:, :, 0] / (hist[:, :, 1] + p.cat_smooth)
        sort_idx = jnp.argsort(jnp.where(bin_valid, ctr, jnp.inf), axis=1)  # [F, B]
        rank = jnp.argsort(sort_idx, axis=1)  # inverse permutation: bin -> position
        used_ctr = jnp.sum(bin_valid, axis=1).astype(jnp.int32)  # [F]
        max_num_cat = jnp.minimum(p.max_cat_threshold, (used_ctr + 1) // 2)  # [F]
        i_pos = jnp.arange(B, dtype=jnp.int32)[None, :]

        hist_sorted = jnp.take_along_axis(hist, sort_idx[:, :, None], axis=1)

        def _ctr_dir(h_dir):
            """Candidate gains for one traversal direction over the sorted bins.

            ``h_dir`` is [F, B, 3] in traversal order; candidate i takes the first
            i+1 bins as the left side. min_data_per_group grouping is sequential
            (the group counter resets only on an emitted candidate) -> lax.scan.
            """
            pref = _bin_prefix(h_dir)  # one scan for all 3 channels
            lg = pref[:, :, 0]
            lh = pref[:, :, 1] + K_EPSILON
            lc = pref[:, :, 2]
            rg = sum_grad - lg
            rh = sum_hess - lh
            rc = num_data - lc
            left_ok = (lc >= p.min_data_in_leaf) & (lh >= p.min_sum_hessian_in_leaf)
            right_ok = (
                (rc >= p.min_data_in_leaf)
                & (rc >= p.min_data_per_group)
                & (rh >= p.min_sum_hessian_in_leaf)
            )

            def step(gcnt, x):
                c_i, ok_i = x
                gcnt = gcnt + c_i
                emit = ok_i & (gcnt >= p.min_data_per_group)
                return jnp.where(emit, 0.0, gcnt), emit

            _, emit = jax.lax.scan(
                step,
                jnp.zeros((F,), hist.dtype),
                (h_dir[:, :, 2].T, (left_ok & right_ok).T),
            )
            emit = emit.T  # [F, B]
            lo = _leaf_output_constrained(lg, lh, p_cat, min_constraint, max_constraint)
            ro = _leaf_output_constrained(rg, rh, p_cat, min_constraint, max_constraint)
            g = _gain_given_output(lg, lh, lo, p_cat) + _gain_given_output(
                rg, rh, ro, p_cat
            )
            ok = emit & (i_pos < used_ctr[:, None]) & (i_pos < max_num_cat[:, None])
            ok &= g > min_gain_shift
            return jnp.where(ok, g, K_MIN_SCORE), lg, lh, lc

        g_fwd, lg_fwd, lh_fwd, lc_fwd = _ctr_dir(hist_sorted)
        # reverse traversal starts at sorted position used_ctr-1 and walks down
        rev_pos = jnp.clip(used_ctr[:, None] - 1 - i_pos, 0, B - 1)
        g_rev, lg_rev, lh_rev, lc_rev = _ctr_dir(
            jnp.take_along_axis(hist_sorted, rev_pos[:, :, None], axis=1)
        )
        # candidate order = (dir=+1, i asc) then (dir=-1, i asc), strict-> updates:
        # first max of the concatenation reproduces the reference's tie-breaking
        g_all = jnp.concatenate([g_fwd, g_rev], axis=1)  # [F, 2B]
        j_best = jnp.argmax(g_all, axis=1).astype(jnp.int32)
        g_ctr = jnp.take_along_axis(g_all, j_best[:, None], axis=1)[:, 0]
        fwd_won = j_best < B
        i_best = jnp.where(fwd_won, j_best, j_best - B)
        i_sel = i_best[:, None]

        def _pick_dir(a_fwd, a_rev):
            return jnp.where(
                fwd_won,
                jnp.take_along_axis(a_fwd, i_sel, axis=1)[:, 0],
                jnp.take_along_axis(a_rev, i_sel, axis=1)[:, 0],
            )

        ctr_lg = _pick_dir(lg_fwd, lg_rev)
        ctr_lh = _pick_dir(lh_fwd, lh_rev)
        ctr_lc = _pick_dir(lc_fwd, lc_rev)
        member_ctr = jnp.where(
            fwd_won[:, None],
            rank <= i_sel,
            rank >= (used_ctr[:, None] - 1 - i_sel),
        ) & bin_valid

        # per-feature winner: one-hot vs CTR is decided by num_bin, not by gain
        use_onehot = num_bin <= p.max_cat_to_onehot  # [F]
        g_cat = jnp.where(use_onehot, g_oh, g_ctr)
        t_cat = jnp.where(use_onehot, t_oh, i_best)
        cat_member = jnp.where(use_onehot[:, None], bins == t_oh[:, None], member_ctr)
        cat_ncat = jnp.where(use_onehot, 1, i_best + 1).astype(jnp.int32)
        cat_lg = jnp.where(use_onehot, oh_best_lg, ctr_lg)
        cat_lh = jnp.where(use_onehot, oh_best_lh, ctr_lh)
        cat_lc = jnp.where(use_onehot, oh_best_lc, ctr_lc)
        cat_use_ctr = ~use_onehot

    # ---- per-feature best with scan-order tie-breaking -------------------
    # dir=-1 prefers the LARGEST threshold among equal gains.
    t_neg_rev = jnp.argmax(gains_neg[:, ::-1], axis=1)
    t_neg = B - 1 - t_neg_rev
    g_neg = jnp.take_along_axis(gains_neg, t_neg[:, None], axis=1)[:, 0]
    if two_way:
        # dir=+1 prefers the smallest threshold; must strictly beat dir=-1.
        t_pos = jnp.argmax(gains_pos, axis=1)
        g_pos = jnp.take_along_axis(gains_pos, t_pos[:, None], axis=1)[:, 0]
        use_pos = g_pos > g_neg
        g_best = jnp.where(use_pos, g_pos, g_neg)
        t_best = jnp.where(use_pos, t_pos, t_neg)
    else:
        use_pos = jnp.zeros((F,), bool)
        g_best = g_neg
        t_best = t_neg
    dl_best = ~use_pos  # default_left = (dir == -1)
    # 2-bin NaN features keep default_left=False (feature_histogram.hpp:108-111)
    two_bin_nan = (missing == MISSING_NAN) & ~multi_bin
    dl_best = jnp.where(two_bin_nan, False, dl_best)

    # categorical features use the categorical candidates exclusively
    g_best = jnp.where(is_cat, g_cat, g_best)
    t_best = jnp.where(is_cat, t_cat, t_best)
    dl_best = jnp.where(is_cat, False, dl_best)
    use_pos = jnp.where(is_cat, True, use_pos)  # pick() reads the prefix arrays

    return _ScanOut(
        g_best=g_best,
        t_best=t_best,
        dl_best=dl_best,
        use_pos=use_pos,
        is_cat=is_cat,
        lg_pos=lg_pos,
        lh_pos=lh_pos,
        lc_pos=lc_pos,
        lg_neg=lg_neg,
        lh_neg=lh_neg,
        lc_neg=lc_neg,
        cat_lg=cat_lg,
        cat_lh=cat_lh,
        cat_lc=cat_lc,
        cat_member=cat_member,
        cat_ncat=cat_ncat,
        cat_use_ctr=cat_use_ctr,
        min_gain_shift=min_gain_shift,
    )


def gather_info_for_threshold(
    hist_f: jax.Array,  # [B, 3] one feature's histogram
    sum_grad: jax.Array,
    sum_hess: jax.Array,
    num_data: jax.Array,
    threshold: jax.Array,  # bin threshold (int32 scalar)
    num_bin: jax.Array,
    missing_type: jax.Array,
    default_bin: jax.Array,
    is_cat: jax.Array,
    params: SplitParams,
) -> SplitResult:
    """SplitInfo for a FORCED (feature, threshold) split
    (FeatureHistogram::GatherInfoForThreshold, feature_histogram.hpp:281-420).

    Numerical: right side = bins in [max(threshold,1), last real bin], skipping
    the default bin when missing=Zero and the NaN bin when missing=NaN;
    default_left=True. Categorical one-hot: left side = the single bin;
    default_left=False. Gain <= min_gain_shift yields -inf (the caller skips the
    forced split and aborts the rest of its BFS, serial_tree_learner.cpp:666).
    """
    p = params
    B = hist_f.shape[0]
    bins = jnp.arange(B, dtype=jnp.int32)
    use_na = missing_type == MISSING_NAN
    skip_def = missing_type == MISSING_ZERO

    gain_shift = leaf_split_gain(sum_grad, sum_hess, p)
    min_gain_shift = gain_shift + p.min_gain_to_split

    # ---- numerical ------------------------------------------------------
    right_mask = (bins >= jnp.maximum(threshold, 1)) & (bins <= num_bin - 1 - use_na)
    right_mask &= ~(skip_def & (bins == default_bin))
    rm = right_mask.astype(hist_f.dtype)[:, None]
    right = jnp.sum(hist_f * rm, axis=0)  # [3]
    num_rg, num_rh, num_rc = right[0], right[1] + K_EPSILON, right[2]
    num_lg = sum_grad - num_rg
    num_lh = sum_hess - num_rh
    num_lc = num_data - num_rc

    # ---- categorical one-hot -------------------------------------------
    left_mask = (bins == threshold).astype(hist_f.dtype)[:, None]
    cleft = jnp.sum(hist_f * left_mask, axis=0)
    cat_lg, cat_lh, cat_lc = cleft[0], cleft[1] + K_EPSILON, cleft[2]
    used_bin = num_bin + jnp.where(missing_type == MISSING_NONE, 0, -1)
    cat_ok = threshold < used_bin

    lg = jnp.where(is_cat, cat_lg, num_lg)
    lh = jnp.where(is_cat, cat_lh, num_lh)
    lc = jnp.where(is_cat, cat_lc, num_lc)
    rg = sum_grad - lg
    rh = sum_hess - lh
    rc = num_data - lc

    current_gain = leaf_split_gain(lg, lh, p) + leaf_split_gain(rg, rh, p)
    ok = (current_gain > min_gain_shift) & jnp.where(is_cat, cat_ok, True)
    ok &= ~jnp.isnan(current_gain)

    left_out = calculate_leaf_output(lg, lh, p)
    right_out = calculate_leaf_output(rg, rh, p)
    gain = jnp.where(ok, current_gain - min_gain_shift, K_MIN_SCORE)
    return SplitResult(
        gain=gain.astype(jnp.float32),
        feature=jnp.int32(-1),  # caller fills the (static) feature index
        threshold=threshold.astype(jnp.int32),
        default_left=jnp.where(is_cat, False, True),
        left_sum_grad=lg,
        left_sum_hess=lh - K_EPSILON,
        left_count=lc,
        right_sum_grad=rg,
        right_sum_hess=rh - K_EPSILON,
        right_count=rc,
        left_output=left_out,
        right_output=right_out,
        num_cat=jnp.where(is_cat, 1, 0).astype(jnp.int32),
        cat_bitset=bins == threshold,
    )


def per_feature_best_gain(
    hist: jax.Array,
    sum_grad: jax.Array,
    sum_hess: jax.Array,
    num_data: jax.Array,
    min_constraint: jax.Array,
    max_constraint: jax.Array,
    feature_meta: Dict[str, jax.Array],
    feature_mask: jax.Array,
    params: SplitParams,
    two_way: bool = True,
) -> jax.Array:
    """[F] best gain per feature (-inf where none) — the voting-parallel
    local-voting stage (LightSplitInfo gains, voting_parallel_tree_learner.cpp:337)."""
    sc = _scan_candidates(
        hist, sum_grad, sum_hess, num_data, min_constraint, max_constraint,
        feature_meta, params, two_way=two_way,
    )
    return jnp.where(feature_mask, sc.g_best, K_MIN_SCORE)


@functools.partial(jax.jit, static_argnames=("params", "two_way"))
def find_best_split(
    hist: jax.Array,  # [F, B, 3] (sum_grad, sum_hess, count)
    sum_grad: jax.Array,  # leaf totals (scalars)
    sum_hess: jax.Array,
    num_data: jax.Array,
    min_constraint: jax.Array,  # monotone constraint window for this leaf
    max_constraint: jax.Array,
    feature_meta: Dict[str, jax.Array],  # num_bin/missing_type/default_bin/monotone [F]
    feature_mask: jax.Array,  # [F] bool: feature_fraction sample & usable
    params: SplitParams,
    penalty: Any = None,  # optional [F] CEGB gain penalty per feature
    two_way: bool = True,
) -> SplitResult:
    """Best split for one leaf across all features (FindBestThresholdNumerical)."""
    p = params
    sum_hess_eff = sum_hess + 2 * K_EPSILON  # feature_histogram.hpp:87
    sc = _scan_candidates(
        hist, sum_grad, sum_hess, num_data, min_constraint, max_constraint,
        feature_meta, params, two_way=two_way,
    )
    (g_best, t_best, dl_best, use_pos, is_cat) = (
        sc.g_best, sc.t_best, sc.dl_best, sc.use_pos, sc.is_cat,
    )
    (lg_pos, lh_pos, lc_pos, lg_neg, lh_neg, lc_neg) = (
        sc.lg_pos, sc.lh_pos, sc.lc_pos, sc.lg_neg, sc.lh_neg, sc.lc_neg,
    )
    min_gain_shift = sc.min_gain_shift

    g_best = jnp.where(feature_mask, g_best, K_MIN_SCORE)
    if penalty is not None:
        # CEGB: penalties land on the shifted gain (serial_tree_learner.cpp:537-543),
        # i.e. after min_gain_shift subtraction; shift them into the raw scale here
        # so the argmax and the final reported gain both see penalized values.
        g_best = g_best - penalty

    best_f = jnp.argmax(g_best)  # first max wins ties (feature index order)
    best_gain_raw = g_best[best_f]
    best_t = t_best[best_f]
    best_dl = dl_best[best_f]
    has_split = best_gain_raw > K_MIN_SCORE

    # Recover the chosen candidate's side sums.
    has_cat = "is_categorical" in feature_meta  # static: no cat -> no cat code
    best_is_cat = is_cat[best_f] if has_cat else jnp.asarray(False)

    def pick(arr_pos, arr_neg, cat_v):
        pos_v = arr_pos[best_f, best_t]
        neg_v = arr_neg[best_f, best_t]
        num_v = jnp.where(use_pos[best_f], pos_v, neg_v)
        return jnp.where(best_is_cat, cat_v, num_v) if has_cat else num_v

    left_g = pick(lg_pos, lg_neg, sc.cat_lg[best_f])
    left_h = pick(lh_pos, lh_neg, sc.cat_lh[best_f])  # includes +eps
    left_c = pick(lc_pos, lc_neg, sc.cat_lc[best_f])
    right_g = sum_grad - left_g
    right_h = sum_hess_eff - left_h
    right_c = num_data - left_c

    left_out = _leaf_output_constrained(left_g, left_h, p, min_constraint, max_constraint)
    right_out = _leaf_output_constrained(right_g, right_h, p, min_constraint, max_constraint)
    if has_cat and p.cat_l2 != 0.0:
        # the CTR branch regularizes leaf outputs with lambda_l2 + cat_l2
        # (feature_histogram.hpp:246-255 passes the augmented l2)
        p_cat = p._replace(lambda_l2=p.lambda_l2 + p.cat_l2)
        use_ctr = best_is_cat & sc.cat_use_ctr[best_f]
        left_out = jnp.where(
            use_ctr,
            _leaf_output_constrained(left_g, left_h, p_cat, min_constraint, max_constraint),
            left_out,
        )
        right_out = jnp.where(
            use_ctr,
            _leaf_output_constrained(right_g, right_h, p_cat, min_constraint, max_constraint),
            right_out,
        )

    gain = jnp.where(has_split, best_gain_raw - min_gain_shift, K_MIN_SCORE)
    B = hist.shape[1]
    bins_r = jnp.arange(B, dtype=jnp.int32)
    if has_cat:
        num_cat = jnp.where(best_is_cat, sc.cat_ncat[best_f], 0)
        cat_bitset = jnp.where(best_is_cat, sc.cat_member[best_f], bins_r == best_t)
    else:
        num_cat = jnp.int32(0)
        cat_bitset = bins_r == best_t
    return SplitResult(
        gain=gain.astype(jnp.float32),
        feature=jnp.where(has_split, best_f.astype(jnp.int32), -1),
        threshold=best_t.astype(jnp.int32),
        default_left=best_dl,
        left_sum_grad=left_g,
        left_sum_hess=left_h - K_EPSILON,
        left_count=left_c,
        right_sum_grad=right_g,
        right_sum_hess=right_h - K_EPSILON,
        right_count=right_c,
        left_output=left_out,
        right_output=right_out,
        num_cat=num_cat.astype(jnp.int32),
        cat_bitset=cat_bitset,
    )
