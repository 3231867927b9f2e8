"""Pallas kernel for the two-child best-split scan (interpret mode only).

The serial grower's per-split fixed cost on TPU is dominated by the
~100-150 tiny XLA kernels of the vectorized threshold scan
(ops/split.py find_best_split) — each launch is latency-bound on [F, B]
tensors that fit VMEM ~200x over. This kernel runs the NUMERICAL scan for
both children of a split in ONE launch, everything VMEM-resident.

Formulation changes vs the XLA scan (semantics preserved, f32
accumulation order not):
 * the inclusive bin prefix is a matmul against a lower-triangular ones
   matrix (MXU, precision=HIGHEST) instead of a reduce-window cumsum —
   reassociated f32, so gains can differ by ~1 ulp and near-exact ties
   may resolve differently than the XLA path (the same caveat the CPU
   fold vs TPU reduce-window already carries, ops/split.py _bin_prefix);
 * argmax tie-breaking uses iota-select reductions (no gathers: Mosaic
   has no cheap dynamic gather) — dir=-1 prefers the largest threshold,
   dir=+1 and the feature argmax the smallest index, exactly like the
   reference's strict-update loops;
 * the winner's side sums are recovered with one-hot masked reductions
   instead of dynamic indexing.

Scope (the routing gate, ``supported()``): numerical features only (no
``is_categorical`` in the meta), no CEGB penalty, monotone constraints
fine. OFF by default, and not offered on a TPU: Mosaic refuses the kernel
(see ``supported()``, which chip_smoke.py asks before compiling it).
``LIGHTGBM_TPU_SPLIT_IMPL=pallas`` with
``LIGHTGBM_TPU_SPLIT_INTERPRET=1`` runs it in interpret mode
(tests/test_split_pallas.py).

Reference semantics carried over from feature_histogram.hpp:91-650 via
ops/split.py; cite: kEpsilon seeds (:87), missing-direction scans, the
default_left rules (:108-111).
"""
from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .split import (
    K_EPSILON,
    MISSING_NAN,
    SplitParams,
    SplitResult,
    _leaf_output_constrained,
    candidate_gains,
    excluded_bins,
    leaf_split_gain,
    missing_flags,
    valid_neg_mask,
    valid_pos_mask,
)

# python scalars, not jnp values: traced jnp module constants would be
# captured by the kernel closure, which pallas_call rejects
NEG = float("-inf")
BIG_I = 1 << 30


def _kernel(
    hist_ref, sums_ref, cons_ref, nb_ref, ms_ref, db_ref, mono_ref, fm_ref,
    outf_ref, outi_ref,
    *, params: SplitParams, two_way: bool, B: int,
):
    p = params
    hist = hist_ref[:]  # [2, F, B, 3] f32
    two, F = hist.shape[0], hist.shape[1]
    sums = sums_ref[:]  # [2, 3]: sum_grad, sum_hess, num_data
    cons = cons_ref[:]  # [2, 2]: min_c, max_c
    num_bin = nb_ref[:]  # [F] i32
    missing = ms_ref[:]
    default_bin = db_ref[:]
    mono = mono_ref[:]
    fmask = fm_ref[:] != 0  # [F]

    sum_grad = sums[:, 0][:, None, None]  # [2, 1, 1]
    sum_hess = sums[:, 1][:, None, None]
    num_data = sums[:, 2][:, None, None]
    min_c = cons[:, 0][:, None, None]
    max_c = cons[:, 1][:, None, None]
    sum_hess_eff = sum_hess + 2 * K_EPSILON

    gain_shift = leaf_split_gain(sums[:, 0], sums[:, 1] + 2 * K_EPSILON, p)
    min_gain_shift = (gain_shift + p.min_gain_to_split)[:, None, None]  # [2,1,1]

    multi_bin, use_na, skip_def, single_scan = missing_flags(num_bin, missing)

    bins = jax.lax.broadcasted_iota(jnp.int32, (F, B), 1)  # [F, B]
    excl = excluded_bins(bins, num_bin, default_bin, use_na, skip_def)
    contrib = hist * (~excl)[None, :, :, None].astype(hist.dtype)  # [2,F,B,3]

    # inclusive prefix over bins as ONE matmul: prefix[.., t, c] =
    # sum_b tri[b, t] * contrib[.., b, c] with tri = (b <= t)
    tri = (
        jax.lax.broadcasted_iota(jnp.int32, (B, B), 0)
        <= jax.lax.broadcasted_iota(jnp.int32, (B, B), 1)
    ).astype(jnp.float32)
    lhs = contrib.transpose(0, 1, 3, 2).reshape(two * F * 3, B)
    prefix = (
        jax.lax.dot_general(
            lhs, tri, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST,
        )
        .reshape(two, F, 3, B)
        .transpose(0, 1, 3, 2)
    )  # [2, F, B, 3]
    total = prefix[:, :, B - 1, :]  # [2, F, 3]

    thresholds = bins[None]  # [1, F, B]

    def gains_for(lg, lh, rg, rh, lc, rc, valid):
        return candidate_gains(
            lg, lh, rg, rh, lc, rc, valid, mono[None, :, None],
            min_c, max_c, min_gain_shift, p,
        )

    # ---- dir = +1 --------------------------------------------------------
    lg_pos = prefix[:, :, :, 0]
    lh_pos = prefix[:, :, :, 1] + K_EPSILON
    lc_pos = prefix[:, :, :, 2]
    rg_pos = sum_grad - lg_pos
    rh_pos = sum_hess_eff - lh_pos
    rc_pos = num_data - lc_pos
    if two_way:
        valid_pos = valid_pos_mask(
            thresholds, num_bin[None, :, None], default_bin[None, :, None],
            skip_def[None, :, None], (~single_scan)[None, :, None],
        )
        gains_pos = gains_for(lg_pos, lh_pos, rg_pos, rh_pos, lc_pos, rc_pos, valid_pos)

    # ---- dir = -1 --------------------------------------------------------
    # (a mixed None/int index traces to a gather, which Mosaic refuses)
    rg_neg = total[:, :, 0][:, :, None] - prefix[:, :, :, 0]
    rh_neg = total[:, :, 1][:, :, None] - prefix[:, :, :, 1] + K_EPSILON
    rc_neg = total[:, :, 2][:, :, None] - prefix[:, :, :, 2]
    lg_neg = sum_grad - rg_neg
    lh_neg = sum_hess_eff - rh_neg
    lc_neg = num_data - rc_neg
    valid_neg = valid_neg_mask(
        thresholds, num_bin[None, :, None], default_bin[None, :, None],
        skip_def[None, :, None], use_na[None, :, None],
    )
    gains_neg = gains_for(lg_neg, lh_neg, rg_neg, rh_neg, lc_neg, rc_neg, valid_neg)

    # ---- per-feature best, scan-order tie-breaks (no gathers) ------------
    g_neg = jnp.max(gains_neg, axis=2)  # [2, F]
    # dir=-1 prefers the LARGEST threshold among equal gains
    t_neg = jnp.max(
        jnp.where(gains_neg >= g_neg[:, :, None], thresholds, -1), axis=2
    ).astype(jnp.int32)
    if two_way:
        g_pos = jnp.max(gains_pos, axis=2)
        # dir=+1 prefers the SMALLEST threshold
        t_pos = jnp.min(
            jnp.where(gains_pos >= g_pos[:, :, None], thresholds, BIG_I), axis=2
        ).astype(jnp.int32)
        use_pos = g_pos > g_neg  # strict: +1 must beat -1
        g_f = jnp.where(use_pos, g_pos, g_neg)
        t_f = jnp.where(use_pos, t_pos, t_neg)
    else:
        use_pos = jnp.zeros((two, F), bool)
        g_f = g_neg
        t_f = t_neg
    dl_f = ~use_pos
    two_bin_nan = (missing == MISSING_NAN) & ~multi_bin
    dl_f = jnp.where(two_bin_nan[None, :], False, dl_f)
    g_f = jnp.where(fmask[None, :], g_f, NEG)

    # ---- feature argmax (first max wins ties = smallest index) -----------
    g_best = jnp.max(g_f, axis=1)  # [2]
    f_iota = jax.lax.broadcasted_iota(jnp.int32, (two, F), 1)
    f_best = jnp.min(jnp.where(g_f >= g_best[:, None], f_iota, BIG_I), axis=1)
    f_best = jnp.where(g_best > NEG, f_best, 0).astype(jnp.int32)
    has_split = g_best > NEG

    # winner row one-hot picks (masked reductions instead of dynamic index)
    fsel = (f_iota == f_best[:, None])  # [2, F]
    t_best = jnp.sum(jnp.where(fsel, t_f, 0), axis=1).astype(jnp.int32)
    dl_best = jnp.sum(jnp.where(fsel, dl_f.astype(jnp.int32), 0), axis=1) > 0
    upos_best = jnp.sum(jnp.where(fsel, use_pos.astype(jnp.int32), 0), axis=1) > 0

    cell = fsel[:, :, None] & (thresholds == t_best[:, None, None])  # [2, F, B]

    def pick(a_pos, a_neg):
        v = jnp.where(upos_best[:, None, None], a_pos, a_neg)
        return jnp.sum(jnp.where(cell, v, 0.0), axis=(1, 2))  # [2]

    left_g = pick(lg_pos, lg_neg)
    left_h = pick(lh_pos, lh_neg)  # includes +eps
    left_c = pick(lc_pos, lc_neg)
    right_g = sums[:, 0] - left_g
    right_h = (sums[:, 1] + 2 * K_EPSILON) - left_h
    right_c = sums[:, 2] - left_c
    left_out = _leaf_output_constrained(left_g, left_h, p, cons[:, 0], cons[:, 1])
    right_out = _leaf_output_constrained(right_g, right_h, p, cons[:, 0], cons[:, 1])
    gain = jnp.where(has_split, g_best - min_gain_shift[:, 0, 0], NEG)

    outf_ref[:] = jnp.stack(
        [
            gain, left_g, left_h - K_EPSILON, left_c,
            right_g, right_h - K_EPSILON, right_c,
            left_out, right_out,
        ],
        axis=-1,
    ).astype(jnp.float32)  # [2, 9] — ops/grow.py _BEST_F order
    outi_ref[:] = jnp.stack(
        [
            jnp.where(has_split, f_best, -1),
            t_best,
            jnp.zeros((two,), jnp.int32),  # num_cat (numerical only)
            dl_best.astype(jnp.int32),
        ],
        axis=-1,
    )  # [2, 4]: _BEST_I order + default_left


@functools.partial(jax.jit, static_argnames=("params", "two_way", "interpret"))
def find_best_split_pair_pallas(
    hist2: jax.Array,  # [2, F, B, 3]
    sum_g2: jax.Array,  # [2]
    sum_h2: jax.Array,
    num_d2: jax.Array,
    min_c2: jax.Array,
    max_c2: jax.Array,
    feature_meta: Dict[str, jax.Array],
    feature_mask: jax.Array,  # [F] bool
    params: SplitParams,
    two_way: bool = True,
    interpret: bool = False,
) -> SplitResult:
    """Both children's best splits in one kernel launch; SplitResult [2]."""
    _, F, B, _ = hist2.shape
    sums = jnp.stack([sum_g2, sum_h2, num_d2], axis=-1).astype(jnp.float32)
    cons = jnp.stack([min_c2, max_c2], axis=-1).astype(jnp.float32)
    kernel = functools.partial(_kernel, params=params, two_way=two_way, B=B)
    vm = pltpu.VMEM
    outf, outi = pl.pallas_call(
        kernel,
        in_specs=[pl.BlockSpec(memory_space=vm)] * 8,
        out_specs=[pl.BlockSpec(memory_space=vm)] * 2,
        out_shape=[
            jax.ShapeDtypeStruct((2, 9), jnp.float32),
            jax.ShapeDtypeStruct((2, 4), jnp.int32),
        ],
        interpret=interpret,
    )(
        hist2.astype(jnp.float32),
        sums,
        cons,
        feature_meta["num_bin"].astype(jnp.int32),
        feature_meta["missing_type"].astype(jnp.int32),
        feature_meta["default_bin"].astype(jnp.int32),
        feature_meta["monotone"].astype(jnp.int32),
        feature_mask.astype(jnp.int32),
    )
    t_best = outi[:, 1]
    bins_r = jnp.arange(B, dtype=jnp.int32)[None, :]
    return SplitResult(
        gain=outf[:, 0],
        feature=outi[:, 0],
        threshold=t_best,
        default_left=outi[:, 3] > 0,
        left_sum_grad=outf[:, 1],
        left_sum_hess=outf[:, 2],
        left_count=outf[:, 3],
        right_sum_grad=outf[:, 4],
        right_sum_hess=outf[:, 5],
        right_count=outf[:, 6],
        left_output=outf[:, 7],
        right_output=outf[:, 8],
        num_cat=outi[:, 2],
        cat_bitset=bins_r == t_best[:, None],
    )


def supported(feature_meta: Dict, backend: str) -> bool:
    """Routing gate: numerical-only metas, and interpret mode only.

    On a TPU the kernel is WITHDRAWN: Mosaic (jax 0.9.0 / libtpu 0.0.34,
    v5e, PR 21 chip runs) refuses it — first "Only 2D gather is supported"
    (the mixed None/int indices, since rewritten), then "infer-vector-layout:
    unsupported shape cast vector<28xi1> -> vector<28x1xi1>": every [F]
    vector in the body needs a 2-D layout, which is a rewrite, not a repair
    (ROADMAP C3). Off a TPU it would run in the Python-interpreter pallas
    mode — orders of magnitude slower than the XLA scan — so training
    declines it there too unless LIGHTGBM_TPU_SPLIT_INTERPRET=1 (tests and
    debugging). Either way LIGHTGBM_TPU_SPLIT_IMPL=pallas falls back to the
    XLA scan, saying so once."""
    import os

    from ..utils import log

    if "is_categorical" in feature_meta:
        return False
    if backend == "tpu":
        log.warn_once(
            "split-pallas-withdrawn",
            "the Pallas split kernel is not offered on a TPU: Mosaic does "
            "not compile it (unsupported shape cast of its [F] vectors); "
            "the XLA scan is used and LIGHTGBM_TPU_SPLIT_IMPL=pallas has "
            "no effect",
        )
        return False
    if os.environ.get("LIGHTGBM_TPU_SPLIT_INTERPRET") != "1":
        log.warn_once(
            "split-pallas-interpret",
            "LIGHTGBM_TPU_SPLIT_IMPL=pallas ignored on a %r backend (the "
            "kernel would run in Python interpret mode); using the XLA "
            "scan. Set LIGHTGBM_TPU_SPLIT_INTERPRET=1 to force interpret "
            "mode for tests/debugging." % backend,
        )
        return False
    return True
