"""GBDT boosting loop.

TPU-native counterpart of the reference GBDT (/root/reference/src/boosting/gbdt.cpp,
gbdt.h). The training loop structure is preserved — gradients from the objective,
bagging, per-class tree training, optional leaf renewal, shrinkage, score update,
metric eval with early stopping, boost-from-average folded into the first trees'
leaves (gbdt.cpp:308-413) — while the mechanics are TPU-shaped:

 * scores live on device as ``[num_class, N]`` f32; the tree learner returns the
   per-row leaf assignment so the score update is a gather (no re-traversal),
   matching ScoreUpdater::AddScore-with-learner-partition (score_updater.hpp:80).
 * a row sample (bagging, GOSS, rf: exactly floor(bagging_fraction*N) rows, or
   GOSS's top_k + other_k) is drawn on the device and handed to the learner as
   a per-row mask; the serial grower roots the tree at the in-bag rows
   (ops/grow.py), as gbdt.cpp:179-240 hands its learner the in-bag indices,
   and scores the rows out of the bag by the finished tree. What stays on
   the host per iteration: whether and when to draw, and the record of each
   draw (``sample_draws``).
 * trees stay as device TreeArrays during training and convert to host model Trees
   lazily (for save/predict); validation scores update by on-device traversal.
"""
from __future__ import annotations

import collections
import contextlib
import functools
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..config import Config
from ..dataset import BinnedDataset
from ..metric import Metric
from ..obs import costs as costs_mod
from ..obs import sanitize as sanitize_mod
from ..obs import dist as dist_mod
from ..obs import memwatch, retrace as retrace_mod, trace as trace_mod
from ..objective import ObjectiveFunction
from ..ops import grow_native
from ..ops.grow import (
    COUNTER_NAMES, grow_tree, grow_tree_scan, spec_batch_slots,
)
from ..resil import faults as faults_mod
from ..resil import watchdog as watchdog_mod
from ..ops.histogram import route_rows_variant as hist_route_rows_variant
from ..ops.predict import PredictTree, make_predict_tree, tree_predict_value
from ..ops.split import CegbParams, SplitParams
from ..utils import log
from ..utils.vfile import vopen
from .tree import Tree

K_EPSILON = 1e-15


@functools.partial(jax.jit, static_argnames=("n", "bag_cnt"))
def _device_bag_mask(key, n: int, bag_cnt: int) -> jax.Array:
    """Exactly bag_cnt rows in-bag, drawn on device (gbdt.cpp:179-240)."""
    perm = jax.random.permutation(key, n)
    return jnp.zeros((n,), jnp.float32).at[perm[:bag_cnt]].set(1.0)


@jax.jit
def _packed_rows(mask: jax.Array) -> jax.Array:
    """[1, ceil(N / 8)] uint8: the in-bag rows of a mask, packed to bits."""
    return jnp.packbits(mask > 0)[None, :]


@jax.jit
def _take_columns(bins: jax.Array, meta: Dict[str, jax.Array], cols: jax.Array):
    """The drawn columns of the bin matrix in both layouts ([k, N] and
    [N, k]) and of every per-feature table of ``meta``: what the grower is
    handed for a tree grown on a feature_fraction draw."""
    with jax.named_scope("column_take"):
        drawn = jnp.take(bins, cols, axis=0)
        return drawn, drawn.T, {
            name: jnp.take(v, cols, axis=0) for name, v in meta.items()
        }


@jax.jit
def _table_columns(split_feature: jax.Array, num_leaves: jax.Array,
                   cols: jax.Array) -> jax.Array:
    """A tree's ``split_feature`` mapped from positions in its draw back to
    columns of the table (the nodes past the last split stay as they are)."""
    made = jnp.arange(split_feature.shape[0]) < num_leaves - 1
    return jnp.where(made, cols[split_feature], split_feature)


#: the record of row draws (``GBDT.sample_draws``) holds at most this many
#: bytes of packed bits, the oldest iteration dropped first: 2 x N / 8 bytes a
#: GOSS iteration, so some 670 iterations at 200K rows and 12 at 10.5M
DRAW_STORE_BYTES = 32 << 20


def _leaf_output_np(sum_grad, sum_hess, l1: float, l2: float, max_delta_step: float):
    """CalculateSplittedLeafOutput (feature_histogram.hpp:451) in numpy."""
    num = -np.sign(sum_grad) * np.maximum(np.abs(sum_grad) - l1, 0.0)
    out = num / (sum_hess + l2)
    if max_delta_step > 0:
        out = np.clip(out, -max_delta_step, max_delta_step)
    return out


class GBDT:
    """Gradient Boosting Decision Tree trainer/model (gbdt.h:37-501)."""

    #: whether the training score carry is a plain ordered f32 sum of the
    #: stored trees — the precondition for the bit-exact warm-start replay
    #: (warmstart_scores). DART sets this False: it re-drops and rescales
    #: PAST trees per iteration, so no per-tree replay can reproduce its
    #: carry. RF is excluded via ``average_output`` instead.
    _carry_is_tree_sum = True

    def __init__(
        self,
        config: Config,
        train_set: Optional[BinnedDataset],
        objective: Optional[ObjectiveFunction],
        training_metrics: Optional[List[Metric]] = None,
    ) -> None:
        self.config = config
        self.objective = objective
        self.train_set = train_set
        self.training_metrics = training_metrics or []
        self.iter_ = 0
        self.models: List[Tree] = []  # host-side trees (lazy)
        self._device_trees: List[Tuple] = []  # (TreeArrays, class_id)
        self.num_class = config.num_class
        self.num_tree_per_iteration = (
            objective.num_model_per_iteration if objective is not None else config.num_class
        )
        self.shrinkage_rate = config.learning_rate
        self.max_feature_idx = 0
        self.label_idx = 0
        self.average_output = False
        self._early_stop_best: Dict = {}
        self._es_counter = 0
        # value-keyed cache of explicitly-uploaded f32 scalars (_f32_dev):
        # scalar operands in the boosting loop must not be per-iteration
        # implicit host->device transfers (obs/sanitize.py transfer mode)
        self._f32_dev_cache: Dict[float, jax.Array] = {}
        self.best_iteration = -1
        self.valid_sets: List[BinnedDataset] = []
        self.valid_metrics: List[List[Metric]] = []
        self.valid_names: List[str] = []
        self._eval_history: Dict[str, Dict[str, List[float]]] = {}
        # frozen per-run histogram routing (ops/histogram.HistRoute); set by
        # _setup_train — predict-only boosters keep None (no histograms)
        self._hist_route = None

        if train_set is not None:
            self._setup_train(train_set)

    # ------------------------------------------------------------------
    def _setup_train(self, train_set: BinnedDataset) -> None:
        cfg = self.config
        self.num_data = train_set.num_data
        self.max_feature_idx = train_set.num_total_features - 1
        if self._learner_kind() == "data":
            # data-parallel learner: the [F, N] matrix lands DIRECTLY as
            # per-device row shards (dist_loader.shard_binned_rows ->
            # parallel/mesh.shard_rows, trailing shard zero-padded) — an
            # unsharded device copy never materializes, which is what lets
            # the binned matrix exceed one device's HBM at pod scale
            from ..dist_loader import shard_binned_rows

            self.bins_dev = shard_binned_rows(train_set, self._mesh())
            self._sharded_bins = self.bins_dev
            self.bins_dev_nf = None
        else:
            self.bins_dev = jnp.asarray(train_set.bins)
            # the serial grower holds the matrix in both layouts, the
            # [N, F] one made on the device once a training: its segment
            # gathers read whole rows ([N, F]; contiguous, ~3x faster on
            # CPU caches) and its partition reads whole columns ([F, N]).
            # One matrix for both is one layout in the grower loop's carry,
            # and on a TPU the side it does not suit copies the whole of it
            # to the other layout at every step (PERF.md, PR 31)
            self.bins_dev_nf = (
                jax.jit(jnp.transpose)(self.bins_dev)
                if self._learner_kind() == "serial"
                else None
            )
        meta_np = train_set.feature_meta_arrays()
        self.feature_meta = {k: jnp.asarray(v) for k, v in meta_np.items()}
        self._feature_meta_np = meta_np  # host copies for the native learner
        # trace-time specialization: the dir=+1 split scan exists only for
        # missing-value handling, so datasets with no missing-typed multi-bin
        # feature compile the single-direction program (ops/split.py two_way)
        self._two_way = bool(
            np.any(
                (np.asarray(meta_np["missing_type"]) != 0)
                & (np.asarray(meta_np["num_bin"]) > 2)
            )
        )
        self.num_bins = int(train_set.max_num_bin)
        # EFB: histograms run at the bundled group width (dataset.max_group_bins)
        self.num_group_bins = (
            int(train_set.max_group_bins) if train_set.is_bundled else None
        )
        # FREEZE the histogram tune route for this training run: a pure
        # function of (call shape, this object) from here on — the tune
        # cache being rewritten mid-process (a bringup window racing a
        # training job) can never change a run that already set up. The
        # frozen object rides every grow_tree/train_chunk jit static key
        # and is stamped (digest) into the flight manifest
        # (docs/HistogramRouting.md).
        self._hist_route = self._resolve_hist_route()
        self.split_params = SplitParams(
            lambda_l1=cfg.lambda_l1,
            lambda_l2=cfg.lambda_l2,
            max_delta_step=cfg.max_delta_step,
            min_data_in_leaf=cfg.min_data_in_leaf,
            min_sum_hessian_in_leaf=cfg.min_sum_hessian_in_leaf,
            min_gain_to_split=cfg.min_gain_to_split,
            max_cat_to_onehot=cfg.max_cat_to_onehot,
            cat_smooth=cfg.cat_smooth,
            cat_l2=cfg.cat_l2,
            max_cat_threshold=cfg.max_cat_threshold,
            min_data_per_group=cfg.min_data_per_group,
        )
        K = self.num_tree_per_iteration
        init = train_set.metadata.init_score
        self.scores = jnp.zeros((K, self.num_data), jnp.float32)
        self._has_init_score = init is not None
        if init is not None:
            arr = np.asarray(init, np.float64).reshape(-1)
            if len(arr) == self.num_data:
                arr = np.tile(arr, (K, 1)) if K > 1 else arr[None, :]
            else:
                arr = arr.reshape(K, self.num_data)
            self.scores = jnp.asarray(arr, jnp.float32)
        if self.objective is not None:
            self.objective.init(train_set.metadata, self.num_data)
        for m in self.training_metrics:
            m.init(train_set.metadata, self.num_data)
        from ..utils.timer import PhaseTimers

        self.timers = PhaseTimers()  # TIMETAG analogue (utils/timer.py)
        self._bag_key = jax.random.PRNGKey(cfg.bagging_seed & 0x7FFFFFFF)
        self._feat_rng = np.random.RandomState(cfg.feature_fraction_seed & 0x7FFFFFFF)
        self._bag_mask = jnp.ones((self.num_data,), jnp.float32)
        self._bagging_active = False
        self._draws = collections.deque()  # (iteration, multiplier, bits): sample_draws
        self._column_draws = collections.deque()  # (tree, int32[k]): feature_draws
        self._fmask_drawn = None  # all-true [k] mask of a tree handed its drawn columns
        self._finish_fns = {}  # jitted renew+shrink+score-update steps per class
        self._pending_stop = None  # last iteration's device num_leaves scalars
        self._pending_chunk = None  # last chunk's stacked [n, K] num_leaves
        self._chunk_fns = {}  # jitted n-iteration boosting scans (train_chunk)
        self._stopped = False
        # variants with state-mutating _after_train_iter hooks set this False
        # to run the no-split stop check synchronously (see train_one_iter)
        self._defer_stop_check = type(self)._after_train_iter is GBDT._after_train_iter
        self._fmask_all = jnp.ones((self.train_set.num_features or 1,), bool)
        # all-true per-row operand for the chunk scan's FMA pin (the select
        # in _finish_step); cached so chunks never re-upload it
        self._pin_all = jnp.ones((self.num_data,), bool)
        self.class_need_train = [
            self.objective.class_need_train(k) if self.objective is not None else True
            for k in range(K)
        ]
        self._is_constant_hessian = (
            self.objective.is_constant_hessian if self.objective is not None else False
        )
        self._setup_cegb(train_set)
        self._forced_splits = self._parse_forced_splits(train_set)
        # named memwatch point: the binned matrix + training carries are now
        # resident (gated on LIGHTGBM_TPU_MEMWATCH; obs/memwatch.py)
        memwatch.auto_snapshot("post_bin")

    def _resolve_hist_route(self):
        """Load + freeze the measured histogram routing table for this run.

        Source precedence: the ``hist_tune`` param (explicit path — load
        failures raise), then the LIGHTGBM_TPU_HIST_TUNE env var (ambient
        adoption, e.g. bench/bringup — failures warn once and fall back to
        static routing); ``hist_tune="off"`` disables both. The loaded
        table is filtered to this backend + device family and to impls
        that can actually serve each shape (ops/histogram.resolve_route).
        """
        from ..obs import tune as tune_mod
        from ..ops import histogram as hist_mod

        table, src = tune_mod.active_table(
            getattr(self.config, "hist_tune", "")
        )
        if table is None:
            return None
        return hist_mod.resolve_route(table, source=src)

    def _setup_cegb(self, train_set: BinnedDataset) -> None:
        """CEGB penalty vectors mapped onto used features (config.h:389-405)."""
        cfg = self.config
        F = train_set.num_features
        coupled = list(cfg.cegb_penalty_feature_coupled or [])
        lazy = list(cfg.cegb_penalty_feature_lazy or [])
        for name, vec in (("coupled", coupled), ("lazy", lazy)):
            if vec and len(vec) != train_set.num_total_features:
                log.fatal(
                    "cegb_penalty_feature_%s has %d entries but the data has %d "
                    "total features" % (name, len(vec), train_set.num_total_features)
                )
        self.cegb_params = CegbParams(
            tradeoff=cfg.cegb_tradeoff,
            penalty_split=cfg.cegb_penalty_split,
            has_coupled=bool(coupled),
            has_lazy=bool(lazy),
        )
        if coupled:
            arr = np.array([coupled[j] for j in train_set.used_feature_idx], np.float32)
            self.feature_meta["cegb_coupled"] = jnp.asarray(arr)
        if lazy:
            arr = np.array([lazy[j] for j in train_set.used_feature_idx], np.float32)
            self.feature_meta["cegb_lazy"] = jnp.asarray(arr)
        if self.cegb_params.enabled:
            # per-TRAINING acquisition state (serial_tree_learner.cpp:107-115):
            # features/rows already paid for stay paid across trees
            self._cegb_state = (
                jnp.zeros((F,), bool),
                jnp.zeros((F, self.num_data) if self.cegb_params.has_lazy else (1, 1), bool),
            )
        else:
            self._cegb_state = None

    def _parse_forced_splits(self, train_set: BinnedDataset) -> tuple:
        """forcedsplits_filename JSON -> static BFS tuple of
        (leaf_idx, used_feature_idx, threshold_bin) (ForceSplits,
        serial_tree_learner.cpp:597: left child keeps the leaf index, right
        child takes the next one, exactly the grower's numbering)."""
        fname = self.config.forcedsplits_filename
        if not fname:
            return ()
        import json as _json

        with vopen(fname) as fh:
            root = _json.load(fh)
        if not root:
            return ()
        feat_to_used = {j: i for i, j in enumerate(train_set.used_feature_idx)}
        out = []
        queue = [(root, 0)]
        next_leaf = 1
        while queue:
            node, leaf = queue.pop(0)
            f_orig = int(node["feature"])
            thr = float(node["threshold"])
            if f_orig not in feat_to_used:
                # abort the ENTIRE remaining BFS, not just this subtree: the
                # reference sets aborted_last_force_split when a node's split
                # info is unavailable and stops forcing (ForceSplits,
                # serial_tree_learner.cpp:597-757)
                log.warning(
                    "Forced split on trivial/unknown feature %d aborts the "
                    "remaining forced splits" % f_orig
                )
                break
            f_used = feat_to_used[f_orig]
            mapper = train_set.mappers[f_used]
            thr_bin = int(mapper.value_to_bin(thr))
            out.append((leaf, f_used, thr_bin))
            right_leaf = next_leaf
            next_leaf += 1
            if isinstance(node.get("left"), dict):
                queue.append((node["left"], leaf))
            if isinstance(node.get("right"), dict):
                queue.append((node["right"], right_leaf))
        return tuple(out)

    def add_valid(
        self,
        valid_set: BinnedDataset,
        metrics: List[Metric],
        name: str,
        raw_data=None,
    ) -> None:
        """Attach an eval set; already-trained trees are replayed into its
        score like the reference's ScoreUpdater constructor does
        (score_updater.hpp: adds every existing model on AddValidDataset).
        ``raw_data`` (the unbinned rows, or a zero-arg callable returning
        them) is only consulted when the model holds host-only trees
        (loaded/merged/refit) that cannot be replayed from bins."""
        for m in metrics:
            m.init(valid_set.metadata, valid_set.num_data)
        self.valid_sets.append(valid_set)
        self.valid_metrics.append(metrics)
        self.valid_names.append(name)
        K = self.num_tree_per_iteration
        score = jnp.zeros((K, valid_set.num_data), jnp.float32)
        init = valid_set.metadata.init_score
        if init is not None:
            arr = np.asarray(init, np.float64).reshape(-1)
            if len(arr) == valid_set.num_data:
                arr = np.tile(arr, (K, 1)) if K > 1 else arr[None, :]
            else:
                arr = arr.reshape(K, valid_set.num_data)
            score = jnp.asarray(arr, jnp.float32)
        bins_t = jnp.asarray(valid_set.bins.T)
        if self._device_trees:
            # host-only non-trivial trees (device arrays dropped: loaded /
            # merged / refit models) can't replay from bins — they need raw
            host_needed = any(
                ta is None
                and self.models[mi] is not None
                and self.models[mi].num_leaves > 1
                for mi, (ta, _) in enumerate(self._device_trees)
            )
            if host_needed:
                if callable(raw_data):
                    raw_data = raw_data()
                if raw_data is None:
                    log.fatal(
                        "add_valid on a model with host-only trees needs the "
                        "validation set's raw data (pass the unbinned rows, "
                        "or add eval sets before continued training)"
                    )
                raw_np = np.asarray(raw_data, np.float64)
                ws = self.warmstart_scores(raw_np)
                if ws is not None:
                    # per-tree f32 replay: the valid carry gets the exact
                    # bits a run that attached this set from iteration 0
                    # would hold, so eval values — and early-stopping
                    # decisions — stay bit-identical across a warm start
                    score = score + jnp.asarray(ws)
                else:
                    raw = self.predict_raw(raw_np)
                    raw = raw.T if raw.ndim == 2 else raw[None, :]
                    score = score + jnp.asarray(raw, jnp.float32)
            else:
                for mi, (ta, cid) in enumerate(self._device_trees):
                    if ta is not None:
                        ptree = make_predict_tree(ta, self.feature_meta)
                        score = score.at[cid].add(tree_predict_value(bins_t, ptree))
                    else:
                        tree = self.models[mi]
                        if tree is not None and tree.num_leaves == 1 and tree.leaf_value[0] != 0.0:
                            score = score.at[cid].add(np.float32(tree.leaf_value[0]))
        if not hasattr(self, "valid_scores"):
            self.valid_scores: List[jax.Array] = []
            self._valid_bins_t: List[jax.Array] = []
        self.valid_scores.append(score)
        self._valid_bins_t.append(bins_t)

    # ------------------------------------------------------------------
    def _f32_dev(self, x) -> jax.Array:
        """``np.float32(x)`` as an EXPLICITLY-uploaded device scalar, cached
        by value. Passing raw numpy scalars into eager score updates or
        jitted dispatches is an implicit host->device transfer every call —
        exactly what the runtime sanitizer's transfer mode (obs/sanitize.py)
        disallows inside the boosting dispatch scope. The aval is identical
        (f32[]), so every computation stays bitwise-unchanged."""
        v = float(np.float32(x))
        a = self._f32_dev_cache.get(v)
        if a is None:
            # device_put is jax's one EXPLICIT upload API (jnp.asarray of a
            # 0-d numpy scalar still routes through the implicit
            # convert_element_type path and would trip the guard)
            a = self._f32_dev_cache[v] = jax.device_put(np.float32(x))
        return a

    # ------------------------------------------------------------------
    def _boost_from_average(self, class_id: int) -> float:
        """gbdt.cpp:308-331."""
        cfg = self.config
        if self.models or self._device_trees or self._has_init_score or self.objective is None:
            return 0.0
        if cfg.boost_from_average or self.train_set.num_features == 0:
            init_score = self.objective.boost_from_score(class_id)
            if abs(init_score) > K_EPSILON:
                # audited eager poke (runs once per class, first iteration):
                # the python-int index uploads implicitly, which the
                # transfer sanitizer would otherwise flag (obs/sanitize.py)
                with sanitize_mod.allow_transfers("boost_from_average"):
                    self.scores = self.scores.at[class_id].add(self._f32_dev(init_score))
                    if hasattr(self, "valid_scores"):
                        for i in range(len(self.valid_scores)):
                            self.valid_scores[i] = self.valid_scores[i].at[class_id].add(
                                self._f32_dev(init_score)
                            )
                log.info("Start training from score %f" % init_score)
                return init_score
        elif self.objective.name in ("regression_l1", "quantile", "mape"):
            log.warning(
                "Disabling boost_from_average in %s may cause the slow convergence"
                % self.objective.name
            )
        return 0.0

    def _compute_gradients(self, init_scores) -> Tuple[jax.Array, jax.Array]:
        """Boosting() (gbdt.cpp:148): objective gradients at the current scores."""
        K = self.num_tree_per_iteration
        # reshape, not scores[0]: eager integer indexing converts-and-uploads
        # its index scalar EVERY iteration (the transfer sanitizer flags it);
        # the [1, N] -> [N] reshape is metadata-only and value-identical
        with jax.named_scope("gradients"):
            grad, hess = self.objective.get_gradients(
                self.scores if K > 1 else self.scores.reshape(-1)
            )
        if K == 1:
            grad, hess = grad[None, :], hess[None, :]
        return grad, hess

    def _before_train_iter(self, init_scores) -> None:
        """Hook for boosting variants (DART's tree dropping)."""

    def _after_train_iter(self) -> None:
        """Hook for boosting variants (DART's normalization)."""

    def _bagging(self, iter_: int, grad, hess) -> Tuple[jax.Array, jax.Array]:
        """The iteration's row sample (gbdt.cpp:179-240), left in
        ``_bag_mask`` for the learner.

        The mask is drawn on device (jax.random.permutation) — no per-iteration
        host RNG + transfer of an N-sized array. Returns possibly-modified
        gradients (GOSS rescales sampled rows)."""
        cfg = self.config
        if cfg.bagging_freq <= 0 or cfg.bagging_fraction >= 1.0:
            return grad, hess
        self._bagging_active = True
        with trace_mod.span("train.sample", cat="train"):
            bag_cnt = int(cfg.bagging_fraction * self.num_data)
            bits = None
            if iter_ % cfg.bagging_freq == 0:
                key = jax.random.fold_in(self._bag_key, iter_)
                self._bag_mask = _device_bag_mask(key, self.num_data, bag_cnt)
                bits = _packed_rows(self._bag_mask)
            self._note_sample(iter_, bag_cnt, 0, 1.0, bits)
        return grad, hess

    def _note_sample(self, iter_: int, top_k: int, other_k: int,
                     multiplier: float, bits=None) -> None:
        """One ``sample.counters`` event for the iteration's sample (``top_k``
        rows at weight 1, ``other_k`` at ``multiplier``) and, where rows were
        drawn, the draw's packed ``bits`` ([1 or 2, ceil(N / 8)] uint8 on the
        device: the in-bag rows and, for GOSS, those that carry the
        multiplier) into the record ``sample_draws`` reads. No host sync: the
        bits stay device arrays until they are asked for."""
        trace_mod.counters(
            "sample.counters", cat="train", iteration=iter_,
            rows=self.num_data, in_bag=top_k + other_k, top_k=top_k,
            other_k=other_k, multiplier=multiplier)
        if bits is None:
            return
        draws = self._draws
        while draws and draws[-1][0] >= iter_:  # rolled back and drawn again
            draws.pop()
        draws.append((iter_, multiplier, bits))
        while len(draws) > 1 and sum(d[2].nbytes for d in draws) > DRAW_STORE_BYTES:
            draws.popleft()

    def sample_draws(self) -> List[Dict]:
        """The row draws this training made, oldest first, one dict a drawn
        iteration: ``iteration``, ``in_bag`` ([N] bool: the rows the tree was
        grown on), ``amplified`` ([N] bool: of those, the rows whose gradient
        and hessian were multiplied; none under plain bagging) and
        ``multiplier``. An iteration that grew on every row (no sampling
        configured, GOSS's first 1/learning_rate iterations) or on the
        previous draw (``bagging_freq`` > 1) has no entry, nor has one of the
        fused path (``train_chunk``), which draws inside its scan. Bounded by
        ``DRAW_STORE_BYTES``: the oldest draws of a long training are gone."""
        out = []
        for it, multiplier, bits in getattr(self, "_draws", ()):
            rows = np.unpackbits(np.asarray(bits), axis=1)[:, : self.num_data] > 0
            out.append({"iteration": it, "in_bag": rows[0],
                        "amplified": rows[1] if len(rows) > 1 else np.zeros_like(rows[0]),
                        "multiplier": float(multiplier)})
        return out

    def _draw_columns(self, tree: int) -> Optional[np.ndarray]:
        """Tree ``tree``'s feature_fraction draw, from the host stream: the
        ``k = max(1, int(feature_fraction x F))`` drawn used-feature indices
        in rising order (int32[k]), noted for ``feature_draws`` and as one
        ``feature.counters`` event. None at ``feature_fraction >= 1``: no
        draw is made and the stream does not move."""
        cfg = self.config
        F = self.train_set.num_features
        if cfg.feature_fraction >= 1.0:
            return None
        k = max(1, int(cfg.feature_fraction * F))
        cols = np.sort(self._feat_rng.choice(F, size=k, replace=False)).astype(np.int32)
        trace_mod.counters(
            "feature.counters", cat="train", tree=tree,
            iteration=tree // max(self.num_tree_per_iteration, 1),
            columns=F, drawn=k)
        draws = self._column_draws
        while draws and draws[-1][0] >= tree:  # rolled back and drawn again
            draws.pop()
        draws.append((tree, cols))
        while len(draws) > 1 and sum(d[1].nbytes for d in draws) > DRAW_STORE_BYTES:
            draws.popleft()
        return cols

    def feature_draws(self) -> List[Dict]:
        """The column draws this training made (``feature_fraction`` < 1),
        oldest first, one dict a drawn tree that is still in the model:
        ``tree``, ``iteration`` and ``columns`` (int32[k]: the drawn columns
        of the table, in rising order). Empty where no tree draws. Bounded by
        ``DRAW_STORE_BYTES``: the oldest draws of a long training are gone."""
        if not getattr(self, "_column_draws", None):
            return []
        K = max(self.num_tree_per_iteration, 1)
        used = np.asarray(self.train_set.used_feature_idx, np.int32)
        return [{"tree": t, "iteration": t // K, "columns": used[cols]}
                for t, cols in self._column_draws if t < len(self.models)]

    def column_draw_fallback_reason(self) -> Optional[str]:
        """Why a feature_fraction draw reaches the learner as a mask over all
        F columns (None: the grower is handed the drawn columns alone and the
        tree costs the drawn share of them). Every condition names state that
        lives in the table's column space and that a gather of the drawn
        columns would have to follow."""
        if self._learner_kind() != "serial":
            return "the %s-parallel learner's matrix is sharded" % self._learner_kind()
        if self.train_set.is_bundled:
            return "EFB: the histograms live in group space"
        if self._forced_splits:
            return "forced splits name columns of the table"
        if self.cegb_params.enabled:
            return "CEGB carries per-column acquisition state across trees"
        if self.device_chunk() > 1:
            return "the fused chunk path pre-draws masks inside its scan"
        if self._native_decline() is None:
            return "native host learner in use (device_type=cpu)"
        return None

    def _native_decline(self) -> Optional[str]:
        """Why the native host learner (device_type=cpu, ops/grow_native.py)
        does not grow this training's trees; None where it does."""
        return grow_native.unsupported_reason(
            self.config, self.feature_meta, self._forced_splits, self.cegb_params,
            self.num_bins, self.num_group_bins,
        )

    def _columns_mask(self, cols: Optional[np.ndarray]) -> jax.Array:
        """[F] bool: the draw as a mask over every column (all true for no draw)."""
        if cols is None:
            return self._fmask_all  # cached: no per-iter host->device upload
        mask = np.zeros(self.train_set.num_features, bool)
        mask[cols] = True
        return jnp.asarray(mask)

    def _sample_features(self) -> jax.Array:
        return self._columns_mask(self._draw_columns(len(self.models)))

    def _sample_feature_masks(self, n: int) -> jax.Array:
        """The next ``n`` iterations' feature_fraction masks pre-drawn with
        the SAME host RNG stream and draw order the per-iteration path uses
        (iteration-major, class-minor — so tree sequences stay bit-exact)
        and uploaded ONCE as a stacked [n, K, F] bool array: one transfer
        per chunk instead of one per tree, and none at feature_fraction=1
        (the cached all-ones mask broadcasts without a host copy)."""
        cfg = self.config
        F = self.train_set.num_features
        K = self.num_tree_per_iteration
        if cfg.feature_fraction >= 1.0:
            return jnp.broadcast_to(self._fmask_all, (n, K, F))
        masks = np.zeros((n, K, F), bool)
        base = len(self.models)
        for i in range(n):
            for c in range(K):
                masks[i, c, self._draw_columns(base + i * K + c)] = True
        return jnp.asarray(masks)

    # ------------------------------------------------------------------
    def train_one_iter(
        self, gradients: Optional[np.ndarray] = None, hessians: Optional[np.ndarray] = None
    ) -> bool:
        """One boosting iteration; returns True if training should stop
        (TrainOneIter, gbdt.cpp:332-413).

        The no-more-splits stop check is DEFERRED by one call: reading the
        grown tree's num_leaves on the host blocks until the tree is grown,
        which would serialize host and device every iteration. Instead the num_leaves scalar starts an async host copy
        and is inspected at the START of the next call, by which time it has
        long arrived; the iteration that failed to split contributed exactly
        zero to the scores (the score update masks on num_leaves > 1 on
        device), and its K placeholder trees are popped on detection — the
        same end state as the reference's immediate check."""
        cfg = self.config
        K = self.num_tree_per_iteration
        # a sequential iteration after sharded chunks (the tail shorter
        # than a chunk) addresses the canonical [.., N] carries
        self._unshard_chunk_carries()
        if self._consume_pending_stop() or self._stopped:
            return True
        timers = self.timers
        init_scores = [0.0] * K
        if gradients is None or hessians is None:
            with timers.phase("boosting(grad)"):
                for k in range(K):
                    init_scores[k] = self._boost_from_average(k)
                self._before_train_iter(init_scores)
                grad, hess = self._compute_gradients(init_scores)
        else:
            grad = jnp.asarray(np.asarray(gradients, np.float32).reshape(K, self.num_data))
            hess = jnp.asarray(np.asarray(hessians, np.float32).reshape(K, self.num_data))

        with timers.phase("bagging"):
            grad, hess = self._bagging(self.iter_, grad, hess)

        pending = []
        for k in range(K):
            tree_arrays = None
            leaf_id = None
            if self.class_need_train[k] and self.train_set.num_features > 0:
                # ph.mark records host dispatch time; it only BLOCKS under
                # the LIGHTGBM_TPU_TIMERS=sync opt-in — an always-on sync
                # here serialized every phase whenever timing was enabled,
                # destroying the pipelining being measured (utils/timer.py)
                with timers.phase("tree growth") as ph:
                    tree_arrays, leaf_id = self._train_tree(grad[k], hess[k])
                    ph.mark(tree_arrays)
            if tree_arrays is not None:
                nl_dev = tree_arrays.num_leaves
                with timers.phase("renew+score update") as ph:
                    # one jitted dispatch: renew + shrink + masked score add
                    tree_arrays = self._finish_tree(tree_arrays, leaf_id, k, nl_dev)
                    ph.mark(self.scores)
                with timers.phase("valid scores"):
                    self._update_valid_scores(tree_arrays, k)
                if abs(init_scores[k]) > K_EPSILON:
                    tree_arrays = tree_arrays._replace(
                        leaf_value=tree_arrays.leaf_value + self._f32_dev(init_scores[k])
                    )
                self._device_trees.append((tree_arrays, k))
                self.models.append(None)  # lazily converted
                try:
                    nl_dev.copy_to_host_async()
                except AttributeError:
                    # plain numpy / non-jax arrays have no async copy; the
                    # blocking int() in _consume_pending_stop still works
                    pass
                pending.append((nl_dev, k, init_scores[k]))
            else:
                if len(self.models) < K:
                    output = 0.0
                    if not self.class_need_train[k]:
                        if self.objective is not None:
                            output = self.objective.boost_from_score(k)
                    else:
                        output = init_scores[k]
                    t = Tree(1)
                    t.leaf_value[0] = output
                    self.models.append(t)
                    self._device_trees.append((None, k))
                    if output != 0.0:
                        # audited eager poke: untrained-class constant tree,
                        # at most K times per run (obs/sanitize.py)
                        with sanitize_mod.allow_transfers("constant_tree"):
                            self.scores = self.scores.at[k].add(self._f32_dev(output))
                            if hasattr(self, "valid_scores"):
                                for i in range(len(self.valid_scores)):
                                    self.valid_scores[i] = (
                                        self.valid_scores[i].at[k].add(self._f32_dev(output))
                                    )
                else:
                    # keep models_ aligned per iteration
                    t = Tree(1)
                    self.models.append(t)
                    self._device_trees.append((None, k))

        if pending and not self._defer_stop_check:
            # boosting variants whose _after_train_iter mutates model state
            # (DART's Normalize rescales dropped trees) cannot defer the
            # stop check: rolling the iteration back later would leave that
            # mutation behind. Pay the host sync here instead.
            self._pending_stop = pending
            self.iter_ += 1  # _consume_pending_stop un-counts it on stop
            if self._consume_pending_stop():
                return True
            self.iter_ -= 1  # not stopped: recounted below
        elif pending:
            self._pending_stop = pending
        else:
            # no class trained at all (e.g. zero usable features): the
            # deferred check has nothing to inspect — stop immediately with
            # the constant trees this iteration appended (gbdt.cpp:375-400)
            log.warning(
                "Stopped training because there are no more leaves that meet"
                " the split requirements"
            )
            if len(self.models) > K:
                for _ in range(K):
                    self.models.pop()
                    self._device_trees.pop()
            self._stopped = True
            return True
        self._after_train_iter()
        self.iter_ += 1
        return False

    def _consume_pending_stop(self) -> bool:
        """Inspect the previous iteration's (async-copied) num_leaves scalars;
        roll back that iteration and stop if no class managed a split —
        the deferred twin of gbdt.cpp:375-400. Chunked boosting
        (train_chunk) generalizes the record to a [n, K] num_leaves array:
        the first iteration where NO class split starts the rollback, and
        everything from it to the chunk's end is popped (those trailing
        iterations would never have run sequentially)."""
        chunk_pend = getattr(self, "_pending_chunk", None)
        if chunk_pend is not None:
            self._pending_chunk = None
            nl_dev, n = chunk_pend
            K = self.num_tree_per_iteration
            with trace_mod.span("train.wait_prev_tree", cat="train"):
                nl = np.asarray(nl_dev).reshape(n, K)
            grew = (nl > 1).any(axis=1)
            if bool(grew.all()):
                return False
            drop = n - int(np.argmax(~grew))
            log.warning(
                "Stopped training because there are no more leaves that meet"
                " the split requirements"
            )
            # a chunk never contains the first-ever iteration (train_chunk
            # runs it sequentially), so there are always >= K earlier trees
            # and the first-iteration init-score re-add cannot apply here
            for _ in range(drop * K):
                self.models.pop()
                self._device_trees.pop()
            self.iter_ -= drop
            self._stopped = True
            return True
        # getattr: model-string-loaded boosters skip the training __init__
        pend = getattr(self, "_pending_stop", None)
        if not pend:
            return False
        self._pending_stop = None
        # the one place the host waits for the device in the loop: the read
        # blocks until the previous tree is grown
        with trace_mod.span("train.wait_prev_tree", cat="train"):
            grew = any(int(nl) > 1 for nl, _, _ in pend)
        if grew:
            return False
        K = self.num_tree_per_iteration
        log.warning(
            "Stopped training because there are no more leaves that meet the split requirements"
        )
        self.iter_ -= 1  # the rolled-back iteration does not count
        if len(self.models) > K:
            for _ in range(K):
                self.models.pop()
                self._device_trees.pop()
        else:
            # first iteration: the kept 1-leaf trees carry the init score in
            # their leaf (reference keeps constant trees AND re-adds the
            # output to the scores, gbdt.cpp:375-395) — only for the classes
            # that actually TRAINED; untrained classes' constant-tree branch
            # already added its own output
            for _, k, init in pend:
                if abs(init) > K_EPSILON:
                    # audited eager poke: no-split-stop rollback, runs once
                    # at the stop boundary (obs/sanitize.py)
                    with sanitize_mod.allow_transfers("no_split_stop"):
                        self.scores = self.scores.at[k].add(self._f32_dev(init))
                        if hasattr(self, "valid_scores"):
                            for i in range(len(self.valid_scores)):
                                self.valid_scores[i] = (
                                    self.valid_scores[i].at[k].add(self._f32_dev(init))
                                )
        self._stopped = True
        return True

    # ------------------------------------------------------------------
    # device-resident chunked boosting (TrainOneIter x n as ONE dispatch)
    # ------------------------------------------------------------------

    def device_chunk_fallback_reason(self) -> Optional[str]:
        """Why train_chunk must run iterations one at a time (None = the
        chunked lax.scan can engage). Every condition names per-iteration
        HOST state the scan body cannot carry; the chunk=1 path stays the
        reference semantics and the two are bit-exact where both apply
        (tests/test_device_chunk.py). What the boosters that override the
        per-iteration hooks keep on the host: GOSS the test of the iteration
        against its unsampled lead-in, the dispatch of its draw and the
        draw's record (``_note_sample``: the rows themselves are drawn, kept
        and grown on the device); rf its constant-score gradients and the
        init bias folded into every tree; DART its drop set and per-tree
        weights, which rescale past trees."""
        cfg = self.config
        if cfg.device_chunk_size <= 1:
            return "device_chunk_size <= 1"
        if type(self) is not GBDT:
            return "%s overrides per-iteration hooks" % type(self).__name__
        if self.objective is None:
            return "custom objective (host-computed gradients)"
        if not getattr(self.objective, "supports_device_chunk", False):
            return "objective %r keeps host state per iteration" % (
                self.objective.name,
            )
        if self.train_set is None or self.train_set.num_features == 0:
            return "no usable features (constant-tree path is host-side)"
        if not all(self.class_need_train):
            return "untrained constant class (class_need_train=False)"
        if self.cegb_params.enabled:
            return "CEGB carries cross-tree acquisition state on the host"
        lk = self._learner_kind()
        if lk in ("feature", "voting"):
            return "%s-parallel learner (sharding is applied per dispatch)" % lk
        if lk == "data":
            # the data-parallel learner COMPOSES with the chunked scan: the
            # whole chunk runs under one shard_map dispatch with psum over
            # ICI (docs/DataParallel.md). Only objectives whose gradient is
            # elementwise over rows can evaluate per shard.
            if self.objective.is_renew_tree_output:
                return (
                    "renew objective %r needs a global per-leaf order "
                    "statistic the row shards cannot compute locally"
                    % self.objective.name
                )
            if not getattr(self.objective, "supports_row_sharding", True):
                return (
                    "objective %r reads cross-row state that does not "
                    "row-shard" % self.objective.name
                )
            return None
        if self._native_decline() is None:
            return "native host learner in use (device_type=cpu)"
        return None

    def device_chunk(self) -> int:
        """Effective chunk size for the engine's boosting loop (1 = the
        per-iteration host loop; reasons via device_chunk_fallback_reason)."""
        if self.device_chunk_fallback_reason() is not None:
            return 1
        return self.config.device_chunk_size

    def train_chunk(self, n: int, sync_stop: bool = False):
        """Run up to ``n`` boosting iterations; returns (iterations_run,
        stopped).

        When the chunked path is available (device_chunk_fallback_reason is
        None) and ``n > 1``, the whole block — gradients, bagging draw, tree
        growth, renew/shrink/score update, for every iteration and class —
        executes as ONE jitted ``lax.scan`` dispatch, eliminating the
        per-iteration host dispatches train_one_iter pays. Arithmetic and RNG streams are
        identical to the sequential path, so the produced trees and scores
        are bit-exact (tests/test_device_chunk.py).

        The no-split stop check generalizes from 1 deferred iteration to
        the chunk boundary: the [n, K] num_leaves array starts a host-async
        copy here and is inspected at the NEXT boundary, unless
        ``sync_stop=True`` (set when an eval follows at this boundary) or
        validation sets are attached — then it resolves before returning so
        rolled-back trees can never touch evaluation state. Iterations a
        chunk runs PAST a mid-chunk stop contribute exact zeros on device
        (the scan body's ``stopped`` carry forces the finish step's
        num_leaves mask), so train scores stay bitwise equal to the
        sequential path even across stops (docs/DeviceResidentBoosting.md)."""
        if n <= 1 or self.device_chunk_fallback_reason() is not None:
            return 1, self.train_one_iter()
        if self._consume_pending_stop() or self._stopped:
            return 0, True
        if not self._device_trees:
            # the FIRST iteration keeps the sequential path: boost_from_average,
            # init-score leaf folding and zero-feature constant trees are
            # host-side decisions that exist only there (gbdt.cpp:308-413)
            return 1, self.train_one_iter()
        K = self.num_tree_per_iteration
        timers = self.timers
        with timers.phase("chunked boosting") as ph:
            fmasks = self._sample_feature_masks(n)
            # data-parallel learner: the chunk runs under ONE shard_map
            # dispatch — build/convert the mesh-resident inputs first so
            # _chunk_fn can close over the same row-state triples
            extra = (
                self._sharded_chunk_args()
                if self._learner_kind() == "data"
                # serial scan: the all-true pin operand (see _finish_step)
                else (self._pin_all,)
            )
            fn = self._chunk_fn(n)
            # snapshot avals BEFORE the donating call (obs/costs.py)
            # iteration counter as an EXPLICIT device scalar: jnp.int32 of
            # a python int routes through the implicit-transfer path the
            # sanitizer's guarded dispatch below disallows (obs/sanitize.py)
            it_dev = jax.device_put(np.int32(self.iter_))
            harvest = None
            if costs_mod.enabled():
                harvest = costs_mod.sds_args(
                    (self.scores, self._bag_mask, it_dev,
                     fmasks, self._finish_scalar(0)) + tuple(extra),
                    {},
                )
            sharded = self._learner_kind() == "data"
            guard = (
                watchdog_mod.collective_deadline("gbdt.train_chunk")
                if sharded else contextlib.nullcontext()
            )
            with guard, sanitize_mod.transfer_scope("gbdt.train_chunk"):
                if sharded:
                    # the one fault site on the collective path, INSIDE the
                    # watchdog scope: a `hang` action here is the
                    # deadlocked-psum simulation the watchdog tests drive
                    # (docs/FaultTolerance.md)
                    faults_mod.maybe_fire("dist.collective")
                self.scores, self._bag_mask, trees_out, nl_dev = fn(
                    self.scores, self._bag_mask, it_dev, fmasks,
                    self._finish_scalar(0), *extra,
                )
            if harvest is not None:
                costs_mod.COSTS.harvest(
                    "gbdt.train_chunk", fn, harvest[0], harvest[1]
                )
            ph.mark(nl_dev)
        try:
            nl_dev.copy_to_host_async()  # [n, K]
        except AttributeError:
            pass
        # per-chunk peak accounting (allocator stats only — no buffer walk
        # inside the training loop; gated on LIGHTGBM_TPU_MEMWATCH)
        memwatch.auto_snapshot("chunk", light=True)
        base = len(self._device_trees)
        for idx, ta in enumerate(trees_out):  # iteration-major, class-minor
            self._device_trees.append((ta, idx % K))
            self.models.append(None)  # lazily converted
        self.iter_ += n
        self._pending_chunk = (nl_dev, n)
        if sync_stop or hasattr(self, "valid_scores"):
            # the dispatch above is async on real backends: a deadlocked
            # collective actually blocks HERE, at the first host readback —
            # so the sharded path bounds this fence with the same deadline
            with (watchdog_mod.collective_deadline("gbdt.chunk_boundary")
                  if sharded else contextlib.nullcontext()):
                stopped = self._consume_pending_stop()
            with timers.phase("valid scores"):
                # the SURVIVING trees of this chunk (a stop pops its no-split
                # tail first, so rolled-back trees never touch valid scores;
                # the sequential path's popped trees contributed exact zeros)
                for ta, k in self._device_trees[base:]:
                    self._update_valid_scores(ta, k)
            if stopped:
                return n, True
        return n, False

    def _sharded_chunk_args(self):
        """Mesh-resident inputs of the SHARDED chunk program (the
        data-parallel learner's train_chunk), built once per training and
        cached: the row-validity mask (False on shard padding) and the
        objective's per-row device arrays, each zero-padded to the mesh
        multiple and row-sharded (parallel/mesh.shard_rows). Also converts
        the score/bag carries to their padded sharded layout — shape-driven,
        so a checkpoint restore or a sequential tail iteration transparently
        re-enters the sharded domain on the next chunk."""
        from ..parallel import mesh as mesh_mod

        mesh = self._mesh()
        N = self.num_data
        pad = mesh_mod.row_pad(mesh, N)
        Np = N + pad
        if getattr(self, "_sharded_bins", None) is None:
            self._sharded_bins = mesh_mod.shard_rows(mesh, self.bins_dev, 1)
        cached = getattr(self, "_chunk_shard_cache", None)
        if cached is None:
            valid = np.zeros(Np, np.bool_)
            valid[:N] = True
            valid_s = mesh_mod.shard_rows(mesh, jnp.asarray(valid), 0)
            triples = self.objective.row_state()
            row_args = tuple(
                mesh_mod.shard_rows(mesh, arr, arr.ndim - 1)
                for _, _, arr in triples
            )
            cached = (triples, (self._sharded_bins, valid_s) + row_args)
            self._chunk_shard_cache = cached
            # shard-skew observability: per-device VALID row counts, once
            # per training (pure host math on the padding rule — no device
            # reads, no jit traces; obs/dist.py)
            dist_mod.publish_shard_rows(
                mesh, dist_mod.shard_valid_counts(N, int(mesh.shape["data"]))
            )
        if (
            self.scores.shape[1] != Np
            or not getattr(self, "_chunk_carries_placed", False)
        ):
            from jax.sharding import NamedSharding, PartitionSpec as P

            s = self.scores
            if s.shape[1] != Np:
                s = jnp.pad(s, ((0, 0), (0, pad)))
            self.scores = jax.device_put(
                s, NamedSharding(mesh, P(None, "data"))
            )
            b = self._bag_mask
            if b.shape[0] != Np:
                b = jnp.pad(b, (0, pad))
            self._bag_mask = jax.device_put(b, NamedSharding(mesh, P("data")))
            self._chunk_carries_placed = True
        return cached[1]

    def _unshard_chunk_carries(self) -> None:
        """Return the score/bag carries to their canonical [.., N] layout:
        the per-iteration paths (sequential tail, rollback) and every host
        consumer address unpadded rows. Slicing is exact — the padded tail
        never held real data (the finish step's validity select keeps it at
        zero), so chunked-then-sequential training stays bit-identical to
        the all-sequential run."""
        if getattr(self, "_chunk_carries_placed", False):
            N = self.num_data
            if self.scores.shape[1] != N:
                self.scores = self.scores[:, :N]
            if self._bag_mask.shape[0] != N:
                self._bag_mask = self._bag_mask[:N]
            self._chunk_carries_placed = False

    def scores_canonical_np(self) -> np.ndarray:
        """The train score carry as [K, N] numpy with any sharded-chunk row
        padding dropped — the canonical form checkpoints store, so the
        artifact bytes do not depend on the mesh that produced them."""
        return np.asarray(self.scores)[:, : self.num_data]

    def _chunk_fn(self, n: int):
        """Build (and cache) the jitted ``n``-iteration boosting scan. The
        cache key pins every trace-time constant the closure bakes in, so a
        reset_parameter between train() calls can never reuse a stale
        program. ``scores`` and the bag mask are donated — the caller
        re-adopts both from the outputs.

        With the data-parallel learner the SAME scan body runs once per
        shard under ONE shard_map dispatch: bins/scores/bag/gradient state
        arrive row-sharded, per-shard histograms combine with one psum per
        split level inside the grower (ops/histogram.py HistogramSource),
        and every shard applies the identical global split — the
        reference's SyncUpGlobalBestSplit record exchange
        (data_parallel_tree_learner.cpp:241) is a no-op by construction.
        RNG draws (bagging permutation, feature masks) are computed in the
        GLOBAL row space and sliced per shard, so tree sequences stay
        bit-identical to the per-iteration chunk=1 path on the same mesh
        (docs/DataParallel.md)."""
        cfg = self.config
        K = self.num_tree_per_iteration
        N = self.num_data
        bag_on = cfg.bagging_freq > 0 and cfg.bagging_fraction < 1.0
        if bag_on:
            self._bagging_active = True
        bag_cnt = int(cfg.bagging_fraction * N) if bag_on else N
        freq = cfg.bagging_freq
        finish = [self._finish_step(k) for k in range(K)]
        slots = self._hist_pool_slots()
        sharded = self._learner_kind() == "data"
        mesh = self._mesh() if sharded else None
        key = (
            n, K, N, bag_on, bag_cnt, freq, slots,
            tuple(fk for fk, _ in finish),
            cfg.num_leaves, cfg.max_depth, self.num_bins, self.num_group_bins,
            self.split_params, cfg.tpu_hist_chunk, cfg.tpu_hist_dtype,
            cfg.tpu_hist_mode, self._two_way, self._forced_splits,
            self._hist_route,
            ("data", int(mesh.shape["data"])) if sharded else None,
        )
        fn = self._chunk_fns.get(key)
        if fn is not None:
            return fn
        obj = self.objective
        feature_meta = self.feature_meta
        bag_key = self._bag_key
        steps = [s for _, s in finish]
        grow_kwargs = dict(
            num_leaves=cfg.num_leaves, max_depth=cfg.max_depth,
            num_bins=self.num_bins, num_group_bins=self.num_group_bins,
            params=self.split_params, chunk=cfg.tpu_hist_chunk,
            hist_dtype=cfg.tpu_hist_dtype, hist_mode=cfg.tpu_hist_mode,
            two_way=self._two_way, forced_splits=self._forced_splits,
            cegb=self.cegb_params, cegb_state=None, hist_buf=None,
            bins_nf=None if sharded else self.bins_dev_nf,
            hist_pool_slots=slots, hist_route=self._hist_route,
        )
        if sharded:
            grow_kwargs["axis_name"] = "data"

        n_shards = int(mesh.shape["data"]) if sharded else 1

        def make_body(bins, valid, meta, rate, pin=None):
            """The n-iteration scan body over ONE shard's rows (the whole
            row space when not sharded: bins [F, N], valid None, pin the
            all-true FMA-pin operand; sharded: valid set, pin None)."""

            def body(carry, xs):
                scores, bag, stopped = carry
                it, fmask_k = xs
                # _compute_gradients' exact shape logic, on the carry scores
                with jax.named_scope("gradients"):
                    grad, hess = obj.get_gradients(
                        scores if K > 1 else scores[0])
                if K == 1:
                    grad, hess = grad[None, :], hess[None, :]
                if valid is not None:
                    # shard-padding rows must carry EXACT zeros: the
                    # objective saw arbitrary (zero) labels there, and a
                    # NaN/inf gradient would poison the bag-masked histogram
                    # products (NaN * 0 == NaN). Real rows pass the select
                    # untouched — bitwise identity with the unsharded path.
                    grad = jnp.where(valid[None, :], grad, jnp.float32(0.0))
                    hess = jnp.where(valid[None, :], hess, jnp.float32(0.0))
                if bag_on:
                    # same draw the sequential _bagging makes, keyed by the
                    # global iteration counter (fold_in is integer-exact, so
                    # the mask sequence is bit-identical). Under shard_map
                    # every shard draws the GLOBAL [N] mask and slices its
                    # own window — redundant arithmetic, zero communication,
                    # and exactly the per-iteration path's padded slices.
                    def draw():
                        full = _device_bag_mask(
                            jax.random.fold_in(bag_key, it), N, bag_cnt
                        )
                        if valid is None:
                            return full
                        L = bag.shape[0]
                        n_pad = L * n_shards - N
                        if n_pad:
                            full = jnp.pad(full, (0, n_pad))
                        start = jax.lax.axis_index("data") * L
                        return jax.lax.dynamic_slice(full, (start,), (L,))

                    bag = jax.lax.cond(it % freq == 0, draw, lambda: bag)
                trees = []
                for k in range(K):
                    ta, leaf_id = grow_tree_scan(
                        bins, grad[k], hess[k], bag, fmask_k[k], meta,
                        **grow_kwargs,
                    )
                    # once an earlier iteration of this chunk failed to split
                    # in every class, the sequential loop would have stopped:
                    # force the finish step's num_leaves mask so every later
                    # iteration contributes EXACT zeros — scores stay bitwise
                    # equal to the sequential path across mid-chunk stops
                    # (the trees themselves are popped by the boundary check)
                    nl_eff = jnp.where(stopped, jnp.int32(1), ta.num_leaves)
                    out = steps[k](
                        scores, ta.leaf_value, ta.internal_value, leaf_id,
                        bag, nl_eff, rate, valid, pin,
                    )
                    # the step's 4th (pin) output is dead inside the scan
                    # and DCE'd — here the plain add is pinned by the
                    # valid/pin per-row select instead (measured; the
                    # quick-tier bit-identity suites re-prove it every run)
                    scores, leaf_value, internal_value = out[0], out[1], out[2]
                    trees.append(
                        ta._replace(
                            leaf_value=leaf_value, internal_value=internal_value
                        )
                    )
                stopped = stopped | jnp.all(
                    jnp.stack([t.num_leaves for t in trees]) <= 1
                )
                stacked_k = jax.tree_util.tree_map(
                    lambda *xs: jnp.stack(xs), *trees
                )
                return (scores, bag, stopped), stacked_k

            return body

        def unstack(stacked):
            # unstack INSIDE the jit: one dispatch yields n*K per-tree
            # output buffers (iteration-major), instead of n*K*15 tiny
            # host-issued slice dispatches per chunk boundary
            return [
                jax.tree_util.tree_map(lambda a: a[i, k], stacked)
                for i in range(n)
                for k in range(K)
            ]

        if not sharded:
            bins = self.bins_dev

            def chunk_fn(scores, bag_mask, it0, fmasks, rate, pin):
                retrace_mod.note_trace("gbdt.train_chunk")  # per XLA trace
                its = it0 + jnp.arange(n, dtype=jnp.int32)
                (scores, bag_mask, _), stacked = jax.lax.scan(
                    make_body(bins, None, feature_meta, rate, pin),
                    (scores, bag_mask, jnp.bool_(False)), (its, fmasks),
                )
                return scores, bag_mask, unstack(stacked), stacked.num_leaves

            fn = jax.jit(chunk_fn, donate_argnums=(0, 1))
            self._chunk_fns[key] = fn
            return fn

        # ---- data-parallel: the WHOLE chunk under one shard_map ----------
        from jax.sharding import PartitionSpec as P

        from ..parallel.data_parallel import shard_map

        cache = getattr(self, "_chunk_shard_cache", None)
        triples = cache[0] if cache else self.objective.row_state()
        meta_keys = sorted(feature_meta.keys())
        meta_vals = tuple(feature_meta[kk] for kk in meta_keys)
        n_meta = len(meta_keys)

        def shard_body(scores, bag, it0, fmasks, rate, bins, valid, *rest):
            meta = dict(zip(meta_keys, rest[:n_meta]))
            row_loc = rest[n_meta:]
            # swap the objective's per-row device arrays for this shard's
            # blocks for the duration of the TRACE (restored in finally):
            # get_gradients is elementwise over rows (supports_row_sharding
            # gates the fallback), so the same program runs on [.., N/D]
            saved = [(ow, name, getattr(ow, name)) for ow, name, _ in triples]
            try:
                for (ow, name, _), loc in zip(triples, row_loc):
                    setattr(ow, name, loc)
                its = it0 + jnp.arange(n, dtype=jnp.int32)
                (scores, bag, _), stacked = jax.lax.scan(
                    make_body(bins, valid, meta, rate),
                    (scores, bag, jnp.bool_(False)), (its, fmasks),
                )
                return scores, bag, stacked, stacked.num_leaves
            finally:
                for ow, name, old in saved:
                    setattr(ow, name, old)

        row = P("data")
        rep = P()
        col = P(None, "data")
        state_specs = tuple(
            P(*([None] * (arr.ndim - 1) + ["data"]))
            for _, _, arr in triples
        )
        fn_sm = shard_map(
            shard_body,
            mesh=mesh,
            in_specs=(col, row, rep, rep, rep, col, row)
            + (rep,) * n_meta
            + state_specs,
            out_specs=(col, row, rep, rep),
            check_vma=False,
        )

        def chunk_fn(scores, bag_mask, it0, fmasks, rate, bins_s, valid_s,
                     *row_state):
            retrace_mod.note_trace("gbdt.train_chunk")  # once per XLA trace
            scores, bag_mask, stacked, nl = fn_sm(
                scores, bag_mask, it0, fmasks, rate, bins_s, valid_s,
                *meta_vals, *row_state,
            )
            return scores, bag_mask, unstack(stacked), nl

        fn = jax.jit(chunk_fn, donate_argnums=(0, 1))
        self._chunk_fns[key] = fn
        return fn

    def _finish_tree(self, tree_arrays, leaf_id, k: int, nl_dev):
        """Renew + shrinkage + num_leaves-masked score update as ONE jitted
        dispatch. The previous eager chain (np scalar uploads + 4 separate
        dispatches) cost a host dispatch per op;
        fusing makes the whole post-grow step a single async launch. The
        mask keeps a splitless tree's contribution at exactly zero so the
        deferred stop check (train_one_iter) can run an iteration behind.
        Boosting variants customize only the step body + scalar via
        _finish_step/_finish_scalar (rf.py)."""
        key, step = self._finish_step(k)
        fn = self._finish_fns.get(key)
        if fn is None:
            fn = jax.jit(step, donate_argnums=(0,))
            self._finish_fns[key] = fn
        with sanitize_mod.transfer_scope("gbdt.finish_tree"):
            out = fn(
                self.scores,
                tree_arrays.leaf_value,
                tree_arrays.internal_value,
                leaf_id,
                self._bag_mask,
                nl_dev,
                self._finish_scalar(k),
            )
        # the step carries a 4th output (the materialized add vector — the
        # per-iteration FMA-contraction pin, see _finish_step); unused here
        self.scores, leaf_value, internal_value = out[0], out[1], out[2]
        return tree_arrays._replace(
            leaf_value=leaf_value, internal_value=internal_value
        )

    def _finish_step(self, k: int):
        """(cache key, step fn) for _finish_tree's jitted post-grow step."""
        obj = self.objective
        renew = (
            obj.renew_leaf_outputs_device
            if (obj is not None and obj.is_renew_tree_output)
            else None
        )
        use_bag = self._bagging_active
        M = self.config.num_leaves
        # EVERY learner pins the score update to PLAIN f32 adds of the
        # shrunk leaf values — an FMA-contracted carry cannot be reproduced
        # from the saved model text (the text stores the rounded product),
        # which would break the warm-start replay contract
        # (warmstart_scores, docs/ContinuousTraining.md). In a standalone
        # per-iteration program the pin is the materialized `add` OUTPUT:
        # without it, XLA's CPU loop fusion recomputes the shrink-multiply
        # inside the score-add kernel and LLVM contracts it into an FMA
        # (jax.lax.optimization_barrier is stripped before fusion,
        # measured — PR 8 first hit this on the data learner). Inside a
        # scan that output is DCE'd, so the chunk path's pin is the
        # per-row select on `valid`/`pin` below.

        @jax.named_scope("score_update")
        def step(scores, leaf_value, internal_value, lid, bag, nl, rate,
                 valid=None, pin=None):
            if renew is not None:
                leaf_value = renew(
                    scores[k], lid, bag if use_bag else None, M, leaf_value
                )
            leaf_value = jnp.where(nl > 1, leaf_value * rate, jnp.float32(0.0))
            internal_value = internal_value * rate
            add = leaf_value[lid]
            if valid is not None:
                # sharded chunk path: shard-padding rows stay EXACTLY zero
                # forever — real rows pass through the select untouched, so
                # the masked add equals the unmasked one bitwise on [0, N)
                add = jnp.where(valid, add, jnp.float32(0.0))
            elif pin is not None:
                # all-true [N] runtime operand: value-identical, but the
                # per-row select between the gather and the score add is
                # what keeps XLA CPU fusion from recomputing the shrink-
                # multiply inside the add kernel and FMA-contracting it.
                # Inside a scan the materialized-output pin below is DCE'd,
                # a scalar-predicate select is contracted through, and
                # optimization_barrier is stripped before fusion (all
                # measured) — this is the one form that pins the serial
                # scan to the plain f32 adds the per-iteration program and
                # the warm-start replay (warmstart_scores) perform; the
                # chunk=1-vs-K suites re-prove it every run.
                add = jnp.where(pin, add, jnp.float32(0.0))
            scores = scores.at[k].add(add)
            # `add` as a program output IS the per-iteration FMA pin (see
            # the block comment above); scan bodies drop it (DCE)
            return scores, leaf_value, internal_value, add

        return (k, renew is not None, use_bag), step

    def _finish_scalar(self, k: int):
        return self._f32_dev(self.shrinkage_rate)

    def _train_tree(self, grad_k: jax.Array, hess_k: jax.Array):
        cfg = self.config
        learner = self._learner_kind()
        # what the learner is handed: the table and an all-true mask or,
        # under feature_fraction, the tree's draw: the drawn columns alone
        # where the grower can work in their space, else a mask over all
        bins, bins_nf, meta = self.bins_dev, self.bins_dev_nf, self.feature_meta
        fmask, cols_dev = self._fmask_all, None
        if cfg.feature_fraction < 1.0:
            with trace_mod.span("train.feature_sample", cat="train"):
                cols = self._draw_columns(len(self.models))
                if self.column_draw_fallback_reason() is None:
                    cols_dev = jnp.asarray(cols)
                    bins, bins_nf, meta = _take_columns(bins, meta, cols_dev)
                    if self._fmask_drawn is None:
                        self._fmask_drawn = jnp.ones((len(cols),), bool)
                    fmask = self._fmask_drawn
                else:
                    fmask = self._columns_mask(cols)
        common = dict(
            num_leaves=cfg.num_leaves,
            max_depth=cfg.max_depth,
            num_bins=self.num_bins,
            num_group_bins=self.num_group_bins,
            params=self.split_params,
            chunk=cfg.tpu_hist_chunk,
            hist_dtype=cfg.tpu_hist_dtype,
            hist_mode=cfg.tpu_hist_mode,
            two_way=self._two_way,
            hist_route=self._hist_route,
        )
        cegb_on = self.cegb_params.enabled
        # the columns the learner's histograms are built over
        F = meta["num_bin"].shape[0]
        # LRU pool cap, honored by every learner (the reference's
        # HistogramPool lives in SerialTreeLearner, which the parallel
        # learners inherit)
        slots = self._hist_pool_slots(F)
        if learner == "serial":
            native_decline = self._native_decline()
            if native_decline is None:
                # device_type=cpu: the native host learner (grow_native.py)
                # — the analogue of the reference's C++ CPU tree learner;
                # the XLA/Pallas grower below is the device (TPU) path
                return self._train_tree_host(grad_k, hess_k, fmask)
            if cfg.device_type == "cpu" and not getattr(
                self, "_warned_native_decline", False
            ):
                # the engine identity must never change silently: the user
                # asked for the native CPU learner and is getting XLA
                self._warned_native_decline = True
                log.warning(
                    "device_type=cpu: native host learner declined — %s; "
                    "falling back to the XLA grower" % native_decline
                )
            # donated scratch for the [P|M, F, B, 3] histogram carry: grow_tree
            # reuses and returns it (aliased), skipping a full-buffer zeros
            # write per tree
            M = cfg.num_leaves
            rows = slots if slots is not None else M
            buf = getattr(self, "_hist_buf", None)
            if buf is None or buf.shape != (rows, F, self.num_bins, 3):
                buf = jnp.zeros((rows, F, self.num_bins, 3), jnp.float32)
            self._hist_buf = None  # consumed by donation below
            # spec mode carries a SECOND histogram-sized buffer (the right-
            # child cache, ADVICE r5 #2): donate it the same way so it stops
            # being re-zeroed every tree. spec_batch_slots is the same gate
            # grow_tree traces with, so the buffer exists iff spec engages.
            sbuf = None
            if spec_batch_slots(
                M, hist_mode=cfg.tpu_hist_mode,
                has_lazy_cegb=self.cegb_params.has_lazy,
                pooled=slots is not None and slots < M, cegb_on=cegb_on,
                route_rows_variant=hist_route_rows_variant(
                    self._hist_route,
                    num_bins=self.num_group_bins or self.num_bins,
                    hist_dtype=cfg.tpu_hist_dtype, n_rows=self.num_data,
                ),
            ):
                sbuf = getattr(self, "_spec_buf", None)
                if sbuf is None or sbuf.shape != (M, F, self.num_bins, 3):
                    sbuf = jnp.zeros((M, F, self.num_bins, 3), jnp.float32)
                self._spec_buf = None  # consumed by donation below
            grow_kwargs = dict(
                forced_splits=self._forced_splits, cegb=self.cegb_params,
                cegb_state=self._cegb_state, hist_buf=buf,
                bins_nf=bins_nf, hist_pool_slots=slots,
                spec_buf=sbuf, **common,
            )
            # measured cost analysis (obs/costs.py, LIGHTGBM_TPU_COSTS=1):
            # snapshot the avals BEFORE the call — donation consumes buf/sbuf
            harvest = None
            if costs_mod.enabled():
                harvest = costs_mod.sds_args(
                    (bins, grad_k, hess_k, self._bag_mask, fmask, meta),
                    grow_kwargs,
                )
            with sanitize_mod.transfer_scope("ops.grow_tree"):
                out = grow_tree(
                    bins, grad_k, hess_k, self._bag_mask, fmask, meta,
                    **grow_kwargs,
                )
            if harvest is not None:
                costs_mod.COSTS.harvest(
                    "ops.grow_tree", grow_tree, harvest[0], harvest[1]
                )
            if sbuf is not None:
                out, self._spec_buf = out[:-1], out[-1]
            out, self._hist_buf = out[:-1], out[-1]
            if cols_dev is not None:
                # grown in the draw's space: the tree names table columns
                # before anything reads it by column
                out = (out[0]._replace(split_feature=_table_columns(
                    out[0].split_feature, out[0].num_leaves, cols_dev)),
                ) + tuple(out[1:])
            if cegb_on:
                tree, leaf_id, self._cegb_state = out
                return tree, leaf_id
            return out
        mesh = self._mesh()
        if learner == "feature":
            from ..parallel.feature_parallel import grow_tree_feature_parallel

            out = grow_tree_feature_parallel(
                mesh, self.bins_dev, grad_k, hess_k, self._bag_mask, fmask,
                self.feature_meta, forced_splits=self._forced_splits,
                cegb=self.cegb_params, cegb_state=self._cegb_state,
                hist_pool_slots=slots, **common,
            )
            if cegb_on:
                tree, leaf_id, self._cegb_state = out
                return tree, leaf_id
            return out
        from ..parallel.data_parallel import grow_tree_data_parallel
        from ..parallel.voting_parallel import grow_tree_voting_parallel

        bins_s, grad_s, hess_s, bag_s = self._shard_rows(grad_k, hess_k)
        if learner == "voting":
            out = grow_tree_voting_parallel(
                mesh, bins_s, grad_s, hess_s, bag_s, fmask, self.feature_meta,
                top_k=cfg.top_k, forced_splits=self._forced_splits,
                cegb=self.cegb_params,
                cegb_state=self._cegb_state_sharded(mesh),
                hist_pool_slots=slots, **common,
            )
            if cegb_on:
                tree, leaf_id, self._cegb_state = out
            else:
                tree, leaf_id = out
        else:
            out = grow_tree_data_parallel(
                mesh, bins_s, grad_s, hess_s, bag_s, fmask, self.feature_meta,
                forced_splits=self._forced_splits, cegb=self.cegb_params,
                cegb_state=self._cegb_state_sharded(mesh),
                hist_pool_slots=slots, **common,
            )
            if cegb_on:
                tree, leaf_id, st = out
                self._cegb_state = st
            else:
                tree, leaf_id = out
        # drop shard-padding rows so score updates stay [N]-shaped
        return tree, leaf_id[: self.num_data]

    def _train_tree_host(self, grad_k, hess_k, fmask):
        """Native host growth (device_type=cpu): numpy/C++ loops over the
        same jitted split scan; see ops/grow_native.py."""
        cfg = self.config
        F = self.feature_meta["num_bin"].shape[0]
        st = getattr(self, "_native_state", None)
        if st is None or st.hist.shape[:3] != (cfg.num_leaves, F, self.num_bins):
            st = grow_native._HostState(
                np.asarray(self.bins_dev), cfg.num_leaves, self.num_bins,
                bins_nf=np.asarray(self.bins_dev_nf)
                if self.bins_dev_nf is not None
                else None,
                num_features=F,
                num_group_bins=self.num_group_bins,
            )
            self._native_state = st
        tree, leaf_id = grow_native.grow_tree_native(
            st,
            np.asarray(grad_k), np.asarray(hess_k), np.asarray(self._bag_mask),
            fmask, self.feature_meta, self._feature_meta_np,
            cfg.num_leaves, cfg.max_depth, self.num_bins, self.split_params,
            two_way=self._two_way, num_group_bins=self.num_group_bins,
        )
        return tree, jnp.asarray(leaf_id)

    def _hist_pool_slots(self, F: Optional[int] = None):
        """histogram_pool_size (MB) -> LRU slot count over ``F`` columns (the
        table's where none is given), or None for unlimited
        (SerialTreeLearner ctor, serial_tree_learner.cpp:56-69)."""
        cfg = self.config
        if cfg.histogram_pool_size <= 0:
            return None
        if F is None:
            F = self.feature_meta["num_bin"].shape[0]
        per_leaf = F * self.num_bins * 3 * 4  # f32 (sum_grad, sum_hess, count)
        slots = int(cfg.histogram_pool_size * 1024 * 1024 / max(per_leaf, 1))
        slots = max(2 + len(self._forced_splits), slots)
        return slots if slots < cfg.num_leaves else None

    def _cegb_state_sharded(self, mesh):
        """Row-shard the lazy used_in_data to match the sharded bins."""
        if self._cegb_state is None:
            return None
        from jax.sharding import NamedSharding, PartitionSpec as P

        fu, uid = self._cegb_state
        if self.cegb_params.has_lazy:
            n_sh = mesh.shape["data"]
            pad = (-self.num_data) % n_sh
            if uid.shape[1] == self.num_data and pad:
                uid = jnp.pad(uid, ((0, 0), (0, pad)))
            uid = jax.device_put(uid, NamedSharding(mesh, P(None, "data")))
        fu = jax.device_put(fu, NamedSharding(mesh, P()))
        return (fu, uid)

    def _learner_kind(self) -> str:
        """tree_learner dispatch (TreeLearner::CreateTreeLearner,
        tree_learner.cpp:13-36): parallel learners engage when >1 device."""
        kind = self.config.tree_learner
        if kind in ("data", "feature", "voting") and len(jax.devices()) > 1:
            return kind
        return "serial"

    def _mesh(self):
        if getattr(self, "_mesh_cache", None) is None:
            from ..parallel.feature_parallel import feature_mesh
            from ..parallel.mesh import data_mesh

            if self._learner_kind() == "feature":
                self._mesh_cache = feature_mesh()
            else:
                # num_machines > 1 caps the data mesh to that many devices —
                # the TPU-native reading of the reference's parallel world
                # size (config.h num_machines); the default uses every
                # local device
                nm = self.config.num_machines
                self._mesh_cache = data_mesh(
                    num_devices=nm if nm and nm > 1 else None
                )
        return self._mesh_cache

    def _shard_rows(self, grad_k, hess_k):
        """Row-shard bins/grad/hess/bag over the data mesh via the ONE
        padding rule (parallel/mesh.shard_rows: trailing shard zero-padded;
        padded rows carry zero bag weight so they are inert)."""
        from ..parallel import mesh as mesh_mod

        mesh = self._mesh()
        if getattr(self, "_sharded_bins", None) is None:
            self._sharded_bins = mesh_mod.shard_rows(mesh, self.bins_dev, 1)
        return (
            self._sharded_bins,
            mesh_mod.shard_rows(mesh, grad_k, 0),
            mesh_mod.shard_rows(mesh, hess_k, 0),
            mesh_mod.shard_rows(mesh, self._bag_mask, 0),
        )

    def _update_valid_scores(self, tree_arrays, class_id: int) -> None:
        if not hasattr(self, "valid_scores"):
            return
        ptree = make_predict_tree(tree_arrays, self.feature_meta)
        for i, bins_t in enumerate(self._valid_bins_t):
            val = tree_predict_value(bins_t, ptree)
            self.valid_scores[i] = self.valid_scores[i].at[class_id].add(val)

    def _train_score_np(self) -> np.ndarray:
        # slice off any sharded-chunk row padding (no-op when unpadded)
        s = np.asarray(self.scores, np.float64)[:, : self.num_data]
        return s[0] if self.num_tree_per_iteration == 1 else s

    def _valid_score_np(self, i: int) -> np.ndarray:
        s = np.asarray(self.valid_scores[i], np.float64)
        return s[0] if self.num_tree_per_iteration == 1 else s

    # ------------------------------------------------------------------
    # model materialization / prediction
    # ------------------------------------------------------------------

    def _materialize(self) -> None:
        # a deferred no-split iteration must roll back before its placeholder
        # trees can leak into model output (train_one_iter's deferred check)
        self._consume_pending_stop()
        K = max(self.num_tree_per_iteration, 1)
        for i, (ta, k) in enumerate(self._device_trees):
            if self.models[i] is None:
                self.models[i] = Tree.from_device(ta, self.train_set)
                self.models[i].shrinkage = self.shrinkage_rate
                if ta.counters is not None and trace_mod.recording("grow"):
                    # the grower's work counters reach the trace here, with
                    # the tree's other arrays: no fetch of their own in the loop
                    trace_mod.counters(
                        "grow.counters", cat="grow", tree=i, iteration=i // K,
                        **dict(zip(COUNTER_NAMES,
                                   np.asarray(ta.counters).tolist())))

    def num_trees(self) -> int:
        return len(self.models)

    @property
    def current_iteration(self) -> int:
        return len(self.models) // max(self.num_tree_per_iteration, 1)

    def trees(self) -> List[Tree]:
        self._materialize()
        return self.models

    def warmstart_scores(self, X: np.ndarray) -> Optional[np.ndarray]:
        """Raw scores ``[K, N]`` float32, accumulated ONE TREE AT A TIME in
        f32 in boosting order — the same add sequence (and therefore the
        same IEEE roundings) the training score carry performed, so
        continued training seeded from this array reproduces the parent
        run's carry bit for bit (the init_model warm-start bedrock,
        docs/ContinuousTraining.md). ``predict_raw``'s f64 accumulation
        rounds once at the end instead and lands 1 ulp away on a fraction
        of rows — enough to flip a gradient's histogram bin and fork every
        later tree of the continued run. Returns None when the carry is
        not a plain ordered sum of the stored trees (random forest
        averages; DART re-drops and rescales past trees mid-run), in which
        case callers fall back to the f64 path."""
        if self.average_output or not self._carry_is_tree_sum:
            return None
        self._materialize()
        X = np.asarray(X, np.float64)
        K = max(self.num_tree_per_iteration, 1)
        out = np.zeros((K, X.shape[0]), np.float32)
        for i, t in enumerate(self.models):
            if t is None:
                continue
            # %.*g(20) model text round-trips the device f32 leaf values
            # exactly, so this cast recovers the very bits training added
            out[i % K] += t.predict_fast(X).astype(np.float32)
        return out

    def predict_raw(
        self, X: np.ndarray, num_iteration: int = -1, early_stop=None
    ) -> np.ndarray:
        """Raw scores [N] or [N, K] (PredictRaw, gbdt_prediction.cpp:13-51).

        ``early_stop`` is a PredictionEarlyStopInstance; every round_period
        iterations, rows whose margin passes the threshold stop accumulating
        trees (the reference's per-row callback, vectorized as an active mask).
        """
        self._materialize()
        X = np.asarray(X, np.float64)
        N = X.shape[0]
        K = self.num_tree_per_iteration
        use = len(self.models)
        if num_iteration is not None and num_iteration > 0:
            use = min(use, num_iteration * K)
        out = np.zeros((K, N), np.float64)
        if early_stop is None or early_stop.round_period >= (use + K - 1) // K:
            for i in range(use):
                out[i % K] += self.models[i].predict_fast(X)
        else:
            active = np.arange(N)
            counter = 0
            for it in range(use // K + (1 if use % K else 0)):
                Xa = X[active]
                for k in range(K):
                    i = it * K + k
                    if i >= use:
                        break
                    out[k, active] += self.models[i].predict_fast(Xa)
                counter += 1
                if counter == early_stop.round_period:
                    stop = early_stop.callback(out[:, active].T)
                    active = active[~stop]
                    counter = 0
                    if len(active) == 0:
                        break
        if self.average_output and use > 0:
            out /= max(use // K, 1)
        return out[0] if K == 1 else out.T

    def predict(
        self,
        X: np.ndarray,
        num_iteration: int = -1,
        raw_score: bool = False,
        early_stop=None,
    ) -> np.ndarray:
        raw = self.predict_raw(X, num_iteration, early_stop=early_stop)
        if raw_score or self.objective is None:
            return raw
        return self.objective.convert_output(raw)

    def predict_leaf_index(self, X: np.ndarray, num_iteration: int = -1) -> np.ndarray:
        self._materialize()
        X = np.asarray(X, np.float64)
        use = len(self.models)
        if num_iteration is not None and num_iteration > 0:
            use = min(use, num_iteration * self.num_tree_per_iteration)
        return np.stack(
            [self.models[i].predict_leaf_fast(X) for i in range(use)], axis=1
        ).astype(np.int32)

    def predict_contrib(self, X: np.ndarray, num_iteration: int = -1) -> np.ndarray:
        """SHAP feature contributions (GBDT::PredictContrib, gbdt.cpp:566-585).

        Returns [N, F+1] for single-class models or [N, K*(F+1)] for multiclass,
        last column per class block = expected value; rows sum to the raw score.
        """
        self._materialize()
        X = np.asarray(X, np.float64)
        N = X.shape[0]
        K = self.num_tree_per_iteration
        F = self.max_feature_idx + 1
        use = len(self.models)
        if num_iteration is not None and num_iteration > 0:
            use = min(use, num_iteration * K)
        out = np.zeros((K, N, F + 1), np.float64)
        for i in range(use):
            t = self.models[i]
            if t is None:
                continue
            out[i % K] += t.predict_contrib(X, F)
        if self.average_output and use > 0:
            out /= max(use // K, 1)
        if K == 1:
            return out[0]
        return out.transpose(1, 0, 2).reshape(N, K * (F + 1))

    def merge_models_from(self, other: "GBDT") -> None:
        """Append the predictor's trees to this trainer's — GBDT::MergeFrom
        (the reference appends other's models; the Booster.refit flow calls
        this on a freshly created empty trainer, where append == copy-in,
        basic.py:2320)."""
        import copy as _copy

        other._materialize()
        K = max(self.num_tree_per_iteration, 1)
        base = len(self.models)
        if base == 0:
            # fresh trainer (the refit flow): inherit the predictor's training
            # state too — the reference gets this via CreateFromModelfile
            self.shrinkage_rate = other.shrinkage_rate
            self.average_output = other.average_output
        self.models = self.models + [_copy.deepcopy(t) for t in other.models]
        self._device_trees = self._device_trees + [
            (None, (base + i) % K) for i in range(len(other.models))
        ]
        self.iter_ = len(self.models) // K

    def refit(self, leaf_preds: np.ndarray, decay_rate: Optional[float] = None) -> None:
        """Refit leaf values on this trainer's dataset, keeping tree structure.

        GBDT::RefitTree (gbdt.cpp:262-285): iterate stored trees in boosting
        order; per iteration, gradients come from the objective at the current
        (progressively rebuilt) scores; per tree, leaf grad/hess sums give
        FitByExistingTree's regularized output (serial_tree_learner.cpp:239-268)
        blended with the old value by ``refit_decay_rate``.
        """
        cfg = self.config
        if decay_rate is None:
            decay_rate = cfg.refit_decay_rate
        self._materialize()
        K = self.num_tree_per_iteration
        N = self.num_data
        leaf_preds = np.asarray(leaf_preds)
        if leaf_preds.ndim == 1:
            leaf_preds = leaf_preds.reshape(N, -1)
        if leaf_preds.shape[0] != N:
            raise ValueError(
                "leaf_preds has %d rows, dataset has %d" % (leaf_preds.shape[0], N)
            )
        if leaf_preds.shape[1] != len(self.models):
            raise ValueError(
                "leaf_preds has %d trees, model has %d"
                % (leaf_preds.shape[1], len(self.models))
            )
        # scores rebuild from zero on the refit dataset (fresh ScoreUpdater)
        self.scores = jnp.zeros((K, N), jnp.float32)
        self._chunk_carries_placed = False
        num_iterations = len(self.models) // K
        for it in range(num_iterations):
            grad, hess = self._compute_gradients([0.0] * K)
            grad_np = np.asarray(grad, np.float64)
            hess_np = np.asarray(hess, np.float64)
            for k in range(K):
                mi = it * K + k
                tree = self.models[mi]
                nl = tree.num_leaves
                lp = leaf_preds[:, mi].astype(np.int64)
                sum_g = np.bincount(lp, weights=grad_np[k], minlength=nl)
                sum_h = np.bincount(lp, weights=hess_np[k], minlength=nl) + K_EPSILON
                out = _leaf_output_np(
                    sum_g, sum_h, cfg.lambda_l1, cfg.lambda_l2, cfg.max_delta_step
                )
                new_out = out * tree.shrinkage
                tree.leaf_value = (
                    decay_rate * tree.leaf_value + (1.0 - decay_rate) * new_out
                )
                self._device_trees[mi] = (None, k)
                self.scores = self.scores.at[k].add(
                    jnp.asarray(tree.leaf_value[lp], jnp.float32)
                )

    def shuffle_models(self, start_iter: int = 0, end_iter: int = -1) -> None:
        """Shuffle the iteration order of trained trees in [start, end)
        (GBDT::ShuffleModels, gbdt.cpp). Whole iterations move together so
        multiclass class alignment is preserved; predictions over the full
        model are unchanged (scores are sums), while num_iteration-limited
        prediction and continued training see a decorrelated prefix."""
        self._materialize()
        K = self.num_tree_per_iteration
        n_iter = len(self.models) // K
        if end_iter < 0 or end_iter > n_iter:
            end_iter = n_iter
        start_iter = max(0, start_iter)
        if end_iter - start_iter <= 1:
            return
        perm = np.arange(start_iter, end_iter)
        rng = np.random.RandomState(self.config.seed & 0x7FFFFFFF)
        rng.shuffle(perm)
        new_models = list(self.models)
        new_dev = list(self._device_trees)
        for dst, src in enumerate(perm, start=start_iter):
            for k in range(K):
                new_models[dst * K + k] = self.models[src * K + k]
                new_dev[dst * K + k] = self._device_trees[src * K + k]
        self.models = new_models
        self._device_trees = new_dev

    def rollback_one_iter(self) -> None:
        """RollbackOneIter (gbdt.cpp:415-431)."""
        if self.iter_ <= 0:
            return
        self._unshard_chunk_carries()
        if getattr(self, "_pending_chunk", None) is not None:
            # resolve the chunk's deferred check first: a no-split tail always
            # includes the last iteration, so when it fires the rollback this
            # call was asked for has already happened (and more, as the
            # sequential path would never have trained past the stop)
            if self._consume_pending_stop():
                return
        # a pending deferred stop check refers to the iteration being rolled
        # back — consuming it later would pop a SECOND (healthy) iteration
        self._pending_stop = None
        K = self.num_tree_per_iteration
        for k in range(K):
            idx = len(self._device_trees) - K + k
            ta, cid = self._device_trees[idx]
            if ta is not None:
                # subtract this tree's contribution from train/valid scores
                ptree = make_predict_tree(ta, self.feature_meta)
                val = tree_predict_value(self._train_bins_t_dev(), ptree)
                self.scores = self.scores.at[cid].add(-val)
                if hasattr(self, "valid_scores"):
                    for i, bins_t in enumerate(self._valid_bins_t):
                        v = tree_predict_value(bins_t, ptree)
                        self.valid_scores[i] = self.valid_scores[i].at[cid].add(-v)
        for _ in range(K):
            self.models.pop()
            self._device_trees.pop()
        self.iter_ -= 1

    # ------------------------------------------------------------------
    def feature_importance(self, importance_type: str = "split", num_iteration: int = -1) -> np.ndarray:
        self._materialize()
        n = self.max_feature_idx + 1
        out = np.zeros(n, np.float64)
        use = len(self.models)
        if num_iteration is not None and num_iteration > 0:
            use = min(use, num_iteration * self.num_tree_per_iteration)
        for t in self.models[:use]:
            if t is None or t.num_leaves <= 1:
                continue
            if importance_type == "gain":
                out += t.feature_importance_gains(n)
            else:
                out += t.feature_importance_counts(n)
        return out

    def eval_history(self) -> Dict:
        return self._eval_history

    def train_bin_occupancy(self):
        """Cached per-feature bin-occupancy histograms of the binned
        training matrix (host bincounts, computed once on first use): the
        data-distribution reference shared by the model-stats tier
        (obs/modelstats.py) and the serve drift sidecar (serve/drift.py).
        None when there is no live train set or the matrix is EFB-bundled."""
        if not hasattr(self, "_bin_occupancy_cache"):
            from ..obs import modelstats

            # getattr: model-string-loaded boosters skip the training
            # __init__ and carry no train_set attribute at all
            self._bin_occupancy_cache = modelstats.train_bin_occupancy(
                getattr(self, "train_set", None)
            )
        return self._bin_occupancy_cache

    def _train_bins_t_dev(self) -> jax.Array:
        """Cached row-major [N, F] bin matrix on device for traversals."""
        if getattr(self, "_train_bins_t_cache", None) is None:
            self._train_bins_t_cache = jnp.asarray(self.train_set.bins.T)
        return self._train_bins_t_cache

    def _merge_from(self, other: "GBDT") -> None:
        """Continued training (init_model): keep the predictor's trees in front
        (gbdt.h num_init_iteration_ semantics; init scores already seeded via
        the dataset's predictor-generated init_score)."""
        other._materialize()
        K = max(self.num_tree_per_iteration, 1)
        self.models = list(other.models) + self.models
        self._device_trees = [(None, i % K) for i in range(len(other.models))] + self._device_trees
        self.num_init_iteration = len(other.models) // max(other.num_tree_per_iteration, 1)
        # continued training CONTINUES the parent run's RNG streams — the
        # warm-start bit-identity contract (train N, save, warm-start, train
        # M must equal one uninterrupted N+M run; tests/test_warmstart.py):
        #  * bagging is stateless fold_in(seed, iteration), so positioning
        #    iter_ past the merged iterations resumes that stream exactly;
        #  * the feature_fraction host RNG is stateful, so replay the draws
        #    the parent consumed (iteration-major, class-minor — the same
        #    order _sample_feature_masks pre-draws chunks in).
        self.iter_ = self.num_init_iteration
        cfg = self.config
        if (cfg.feature_fraction < 1.0 and self.train_set is not None
                and self.train_set.num_features > 0):
            F = self.train_set.num_features
            k = max(1, int(cfg.feature_fraction * F))
            # only TRAINED classes draw (train_one_iter gates on
            # class_need_train before _sample_features) — and a config with
            # an untrained class disables device chunking, so the parent's
            # stream advanced by exactly this per-iteration count
            draws_per_iter = sum(
                1 for need in self.class_need_train if need
            )
            for _ in range(self.num_init_iteration * draws_per_iter):
                self._feat_rng.choice(F, size=k, replace=False)

    def reset_parameter(self, params: Dict) -> None:
        """reset_parameter callback support (ResetConfig path)."""
        self.config = self.config.update(params)
        self.shrinkage_rate = self.config.learning_rate
        cfg = self.config
        self.split_params = SplitParams(
            lambda_l1=cfg.lambda_l1,
            lambda_l2=cfg.lambda_l2,
            max_delta_step=cfg.max_delta_step,
            min_data_in_leaf=cfg.min_data_in_leaf,
            min_sum_hessian_in_leaf=cfg.min_sum_hessian_in_leaf,
            min_gain_to_split=cfg.min_gain_to_split,
            max_cat_to_onehot=cfg.max_cat_to_onehot,
            cat_smooth=cfg.cat_smooth,
            cat_l2=cfg.cat_l2,
            max_cat_threshold=cfg.max_cat_threshold,
            min_data_per_group=cfg.min_data_per_group,
        )
