"""Data-parallel tree learning over a device mesh.

TPU-native counterpart of DataParallelTreeLearner
(/root/reference/src/treelearner/data_parallel_tree_learner.cpp): rows are sharded
over the mesh 'data' axis; each shard builds local histograms for ALL features and
the shard histograms are combined with one XLA collective (psum — subsuming the
reference's ReduceScatter of HistogramBinEntry at :161 plus its feature-ownership
bookkeeping at :76-117, which exists only because CPU ranks must split scan work);
every shard then finds the identical global best split, applies the identical
partition update, and no SyncUpGlobalBestSplit record exchange is needed
(:241 becomes a no-op by construction).

Two execution modes:
 * GSPMD (default): the caller simply places bins/grad/hess with a row-sharded
   NamedSharding and jits the ordinary grow_tree — XLA inserts the collectives.
 * shard_map (explicit): this module wraps grow_tree per-shard with psum on the
   histogram/root sums, which pins the communication pattern (used by the
   multi-chip dryrun and as the template for voting-parallel).
"""
from __future__ import annotations

from typing import Dict

import jax
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from ..ops.grow import grow_tree
from ..ops.split import CegbParams, SplitParams

# jitted shard_map wrappers keyed by every trace-time constant the local
# closure bakes in. A fresh jax.jit per call (the old form) compiled a NEW
# executable for EVERY tree — the per-iteration data-parallel path paid a
# full XLA compile per dispatch. Mirrors models/gbdt.py's _chunk_fns cache.
_FN_CACHE: Dict = {}


def grow_tree_data_parallel(
    mesh: Mesh,
    bins: jax.Array,  # [F, N] sharded P(None, 'data') (or host array)
    grad: jax.Array,  # [N]
    hess: jax.Array,
    bag_mask: jax.Array,
    feature_mask: jax.Array,
    feature_meta: Dict[str, jax.Array],
    num_leaves: int,
    max_depth: int,
    num_bins: int,
    params: SplitParams,
    num_group_bins=None,
    chunk: int = 4096,
    hist_dtype: str = "float32",
    hist_mode: str = "bucketed",
    forced_splits=(),
    cegb: CegbParams = CegbParams(),
    cegb_state=None,
    two_way: bool = True,
    hist_pool_slots=None,
    hist_route=None,
):
    """Explicit shard_map data-parallel growth; returns (TreeArrays, leaf_id).

    TreeArrays come out replicated; leaf_id stays row-sharded. With CEGB
    enabled, also returns the carried (feature_used, used_in_data) state —
    feature_used replicated, used_in_data row-sharded alongside bins.
    """
    meta_keys = sorted(feature_meta.keys())
    meta_vals = tuple(feature_meta[k] for k in meta_keys)
    cegb_on = cegb.enabled
    if cegb_on and cegb_state is None:
        F, N = bins.shape
        import jax.numpy as jnp

        cegb_state = (
            jnp.zeros((F,), bool),
            jnp.zeros((F, N) if cegb.has_lazy else (1, 1), bool),
        )

    key = (
        mesh, tuple(meta_keys), num_leaves, max_depth, num_bins,
        num_group_bins, params, chunk, hist_dtype, hist_mode, forced_splits,
        cegb, two_way, hist_pool_slots, hist_route,
    )
    fn = _FN_CACHE.get(key)
    if fn is None:

        def local(bins_l, grad_l, hess_l, bag_l, fmask, fu, uid, *meta_flat):
            meta = dict(zip(meta_keys, meta_flat))
            return grow_tree(
                bins_l,
                grad_l,
                hess_l,
                bag_l,
                fmask,
                meta,
                num_leaves=num_leaves,
                max_depth=max_depth,
                num_bins=num_bins,
                num_group_bins=num_group_bins,
                params=params,
                chunk=chunk,
                hist_dtype=hist_dtype,
                hist_mode=hist_mode,
                two_way=two_way,
                axis_name="data",
                forced_splits=forced_splits,
                cegb=cegb,
                hist_pool_slots=hist_pool_slots,
                cegb_state=(fu, uid) if cegb_on else None,
                hist_route=hist_route,
            )

        row = P("data")
        rep = P()
        uid_spec = P(None, "data") if cegb.has_lazy else rep
        state_out = ((rep, uid_spec),) if cegb_on else ()
        fn = jax.jit(shard_map(
            local,
            mesh=mesh,
            in_specs=(P(None, "data"), row, row, row, rep, rep, uid_spec)
            + (rep,) * len(meta_vals),
            out_specs=(rep, row) + state_out,
            check_vma=False,
        ))
        _FN_CACHE[key] = fn
    if not cegb_on:
        import jax.numpy as jnp

        dummy = (jnp.zeros((1,), bool), jnp.zeros((1, 1), bool))
        fu_in, uid_in = dummy
    else:
        fu_in, uid_in = cegb_state
    return fn(
        bins, grad, hess, bag_mask, feature_mask, fu_in, uid_in, *meta_vals
    )
