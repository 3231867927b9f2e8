"""train() / cv() drivers.

Mirrors /root/reference/python-package/lightgbm/engine.py:19 (train) and :343 (cv):
callback orchestration, early stopping, init_model continuation, evals_result
recording, stratified/group k-fold cross validation.
"""
from __future__ import annotations

import collections
import copy
import os
from typing import Any, Callable, Dict, List, Optional, Union

import numpy as np

from . import callback as callback_mod
from .basic import Booster, Dataset
from .obs import flight as flight_mod
from .obs import podwatch as podwatch_mod
from .obs import registry as obs_registry
from .obs import sanitize as sanitize_mod
from .obs import trace as trace_mod
from .resil import faults
from .resil import preempt as preempt_mod
from .utils import timer as timer_mod
from . import config as config_mod
from .config import Config
from .utils import log
from .utils.log import LightGBMError


def train(
    params: Dict,
    train_set: Dataset,
    num_boost_round: int = 100,
    valid_sets: Optional[List[Dataset]] = None,
    valid_names: Optional[List[str]] = None,
    fobj: Optional[Callable] = None,
    feval: Optional[Callable] = None,
    init_model=None,
    feature_name: str = "auto",
    categorical_feature: str = "auto",
    early_stopping_rounds: Optional[int] = None,
    evals_result: Optional[Dict] = None,
    verbose_eval: Union[bool, int] = True,
    learning_rates=None,
    keep_training_booster: bool = False,
    callbacks: Optional[List[Callable]] = None,
    checkpoint_path: Optional[str] = None,
    checkpoint_rounds: int = 0,
    resume_from: Optional[str] = None,
    checkpoint_keep: int = 0,
    preempt_exit: Optional[bool] = None,
    flex_plan: Optional[str] = None,
) -> Booster:
    trace_mod.watch_compiles()
    # every span begun below with __enter__() (train.init here, the loop's
    # train.boundary) is closed by the finally, whatever leaves this function
    open_spans = trace_mod.open_depth()
    preempt_watcher = flight_rec = telemetry_rec = None
    try:
        # from entry to the loop's first pass: Booster/GBDT set-up, the
        # binned matrix's way to the device, valid sets, resume
        init_span = trace_mod.span("train.init", cat="setup").__enter__()
        params = dict(params) if params else {}
        params = Config.canonicalize(params)
        if "num_iterations" in params:
            num_boost_round = int(params.pop("num_iterations"))
        if "early_stopping_round" in params and early_stopping_rounds is None:
            early_stopping_rounds = int(params.pop("early_stopping_round"))
        # resilience params (docs/FaultTolerance.md) may ride in via params;
        # explicit kwargs win. They are POPPED so the Booster's Config (and the
        # model's parameters footer) stays independent of where a run was
        # checkpointed/resumed — the footer byte-identity the crash tests assert.
        if "checkpoint_path" in params:
            v = str(params.pop("checkpoint_path"))
            checkpoint_path = checkpoint_path or v
        if "checkpoint_rounds" in params:
            v = int(params.pop("checkpoint_rounds"))
            checkpoint_rounds = checkpoint_rounds if checkpoint_rounds > 0 else v
        if "resume_from" in params:
            v = str(params.pop("resume_from"))
            resume_from = resume_from or v
        if "checkpoint_keep" in params:
            v = int(params.pop("checkpoint_keep"))
            checkpoint_keep = checkpoint_keep if checkpoint_keep > 0 else v
        if "preempt_exit" in params:
            v = config_mod.coerce_bool(params.pop("preempt_exit"))
            preempt_exit = v if preempt_exit is None else preempt_exit
        if preempt_exit is None:
            preempt_exit = preempt_mod.env_enabled()
        # fleet orchestration (lightgbm_tpu/flex/): same pop discipline. An
        # EXPLICIT flex_plan="" disarms the env, mirroring preempt_exit=false.
        if "flex_plan" in params:
            v = str(params.pop("flex_plan"))
            flex_plan = v if flex_plan is None else flex_plan
        flex_dead_after_s = 60.0
        if "flex_dead_after_s" in params:
            flex_dead_after_s = float(params.pop("flex_dead_after_s"))
        # controller-only flex knobs ride along when the flex CLI passes its
        # whole argv to the child; pop them so the model footer stays clean
        for _k in ("flex_world", "flex_min_world", "flex_max_restarts",
                   "flex_backoff_base_s", "flex_backoff_max_s",
                   "flex_force_cpu", "flex_seed", "flex_max_launches",
                   "flex_journal"):
            params.pop(_k, None)
        if flex_plan is None:
            # the ONE env read flexctl costs when off (the inertness contract
            # tests/test_flex.py pins); the name mirrors flex/capacity.ENV_PLAN
            flex_plan = os.environ.get("LIGHTGBM_TPU_FLEX_PLAN")
        flex_plan = flex_plan or None
        # model/data observability params (docs/Observability.md): POPPED like
        # the resil params so the model's parameters footer stays byte-identical
        # with recording on or off — the bitwise-identity contract the
        # flight-recorder tests assert
        flight_path = None
        if "flight_record" in params:
            flight_path = str(params.pop("flight_record")) or None
        flight_path = flight_path or flight_mod.env_path()
        model_stats = False
        if "model_stats" in params:
            model_stats = config_mod.coerce_bool(params.pop("model_stats"))
        if resume_from and not checkpoint_path:
            # a resumed run keeps checkpointing to the file it resumed from: the
            # crash that made the checkpoint necessary can strike again, and a
            # second preemption must not throw away all post-resume progress
            checkpoint_path = resume_from
        if checkpoint_path and checkpoint_rounds <= 0:
            # snapshot_freq parity: the reference's snapshot cadence doubles as
            # the checkpoint cadence when no explicit rounds are given; absent
            # both, default to ~10 checkpoints per run — a checkpoint serializes
            # the full model text + score carries (+fsync), so a cadence of 1
            # would turn a long run I/O-bound
            snap = int(params.get("snapshot_freq", -1) or -1)
            checkpoint_rounds = snap if snap > 0 else max(1, num_boost_round // 10)
        if resume_from and init_model is not None:
            raise LightGBMError(
                "resume_from and init_model are mutually exclusive: a checkpoint "
                "already carries its full model"
            )
        if fobj is not None:
            params["objective"] = "none"
        # continued training
        predictor = None
        if init_model is not None:
            if isinstance(init_model, str):
                predictor = Booster(model_file=init_model)
            elif isinstance(init_model, Booster):
                predictor = init_model
        init_iteration = predictor.current_iteration if predictor is not None else 0

        if feature_name != "auto":
            train_set.feature_name = feature_name
        if categorical_feature != "auto":
            train_set.categorical_feature = categorical_feature
        if predictor is not None:
            train_set.set_predictor(predictor)

        booster = Booster(params=params, train_set=train_set)
        if predictor is not None:
            booster._gbdt._merge_from(predictor._gbdt)

        is_valid_contain_train = False
        train_data_name = "training"
        if valid_sets is not None:
            if valid_names is None:
                valid_names = ["valid_%d" % i for i in range(len(valid_sets))]
            for i, vset in enumerate(valid_sets):
                if vset is train_set:
                    is_valid_contain_train = True
                    train_data_name = valid_names[i]
                    continue
                if vset.reference is None:
                    vset.reference = train_set
                booster.add_valid(vset, valid_names[i])

        # callbacks
        cbs = set(callbacks or [])
        if verbose_eval is True:
            cbs.add(callback_mod.print_evaluation())
        elif isinstance(verbose_eval, int) and verbose_eval > 0:
            cbs.add(callback_mod.print_evaluation(verbose_eval))
        if early_stopping_rounds is not None and early_stopping_rounds > 0:
            cbs.add(
                callback_mod.early_stopping(
                    early_stopping_rounds, bool(params.get("first_metric_only", False)),
                    verbose=bool(verbose_eval),
                )
            )
        if learning_rates is not None:
            cbs.add(callback_mod.reset_parameter(learning_rate=learning_rates))
        if evals_result is not None:
            cbs.add(callback_mod.record_evaluation(evals_result))
        cbs_before = {c for c in cbs if getattr(c, "before_iteration", False)}
        cbs_after = cbs - cbs_before
        cbs_before = sorted(cbs_before, key=lambda c: getattr(c, "order", 0))
        cbs_after = sorted(cbs_after, key=lambda c: getattr(c, "order", 0))

        # crash-safe checkpoint/resume (resil/checkpoint.py). Restore happens
        # AFTER valid sets attach (their score carries come from the checkpoint,
        # not a tree replay) and after callbacks exist (the early-stopping bests
        # restore into the live stoppers).
        start_iteration = init_iteration
        ckpt_writer = None
        if resume_from or checkpoint_path:
            from .resil import checkpoint as ckpt_mod

            if resume_from:
                ckpt = ckpt_mod.restore(booster, resume_from, cbs_after)
                init_iteration = ckpt.begin_iteration
                start_iteration = ckpt.iteration
                # num_boost_round is a train() ARGUMENT, so restore()'s
                # config-digest warning cannot catch a mismatched end bound —
                # check it against the manifest's end_iteration here
                ckpt_end = int(ckpt.manifest["end_iteration"])
                live_end = init_iteration + num_boost_round
                if live_end < start_iteration:
                    raise LightGBMError(
                        "resume_from: num_boost_round=%d ends the run at "
                        "iteration %d, BEFORE the checkpoint's position %d — "
                        "nothing would train and the returned model would carry "
                        "more iterations than requested; pass the original run's "
                        "num_boost_round (%d)"
                        % (num_boost_round, live_end, start_iteration,
                           ckpt_end - init_iteration)
                    )
                if live_end != ckpt_end:
                    log.warning(
                        "resume: num_boost_round=%d ends the run at iteration %d "
                        "but the checkpointed run ended at %d; the resumed run "
                        "will NOT be bit-identical to the original"
                        % (num_boost_round, live_end, ckpt_end)
                    )
            if checkpoint_path:
                # refuse unsupported configs (dart) NOW, not at the first cadence
                # boundary checkpoint_rounds iterations in
                ckpt_mod.check_checkpointable(booster._gbdt)
                ckpt_writer = ckpt_mod.CheckpointWriter(
                    checkpoint_path, checkpoint_rounds, cbs_after,
                    keep=max(checkpoint_keep, 1),
                )

        # Device-resident chunked boosting (GBDT.train_chunk): up to
        # device_chunk_size iterations fuse into one jitted dispatch; callbacks,
        # eval and early stopping then observe chunk BOUNDARIES only
        # (docs/DeviceResidentBoosting.md). Custom objectives and
        # before-iteration callbacks (reset_parameter mutates per-iteration
        # config) force the per-iteration loop; early stopping clamps the chunk
        # so a stop can never overshoot its detection window.
        chunk = 1
        if fobj is None and not cbs_before:
            chunk = booster._gbdt.device_chunk()
            if chunk > 1 and early_stopping_rounds is not None and early_stopping_rounds > 0:
                chunk = min(chunk, early_stopping_rounds)
            # an early_stopping() instance handed in via callbacks= carries its
            # window as an attribute — clamp to it too, or the stop check would
            # run at chunk granularity instead of the requested one
            for cb in cbs_after:
                sr = getattr(cb, "stopping_rounds", 0)
                if chunk > 1 and isinstance(sr, int) and sr > 0:
                    chunk = min(chunk, sr)

        # training flight recorder (obs/flight.py): run manifest now — the
        # checkpoint restore above already positioned a resumed run, so the
        # manifest's provenance fields are final. start() returning None (bad
        # path, nested run) silently leaves recording off.
        flight_rec = None
        if flight_path:
            parent_fp = None
            if predictor is not None:
                # lineage edge for the manifest: the warm-start parent's
                # fingerprint — the FILE's bytes when init_model was a path
                # (matching the serve registry's file_sha), else the live
                # booster's bare model-text fingerprint
                from .models.model_text import model_fingerprint

                try:
                    if isinstance(init_model, str):
                        from .utils.vfile import vopen

                        with vopen(init_model) as fh:
                            parent_fp = model_fingerprint(fh.read())
                    else:
                        parent_fp = model_fingerprint(predictor.model_to_string())
                except Exception as e:  # lineage must never fail the run
                    log.debug("flight: parent fingerprint failed: %r" % (e,))
            flight_rec = flight_mod.start(
                flight_path,
                flight_mod.build_manifest(
                    booster, num_boost_round, init_iteration,
                    resume_from=resume_from, checkpoint_path=checkpoint_path,
                    parent_fingerprint=parent_fp,
                ),
            )

        # preemption-aware training (resil/preempt.py): SIGTERM latches a flag
        # the boost loop honors at the next chunk boundary — emergency
        # checkpoint, then TrainingPreempted (exit code 75 at the process entry
        # points). Mirrors serve/__main__.py's drain contract for the trainer.
        preempt_watcher = None
        if preempt_exit:
            if ckpt_writer is None:
                log.warning(
                    "preempt: preempt_exit armed without checkpoint_path — a "
                    "SIGTERM will exit with the preemption code but WITHOUT an "
                    "emergency checkpoint to resume from"
                )
            preempt_watcher = preempt_mod.PreemptionWatcher()
            preempt_watcher.install()

        # live fleet telemetry (obs/podwatch.py): per-rank boundary recorder
        # (LIGHTGBM_TPU_TELEMETRY=<dir>) + opt-in scrape endpoint
        # (LIGHTGBM_TPU_TELEMETRY_PORT). Both unset costs one env read per
        # gate here and nothing in the loop; the trained model is bitwise
        # independent of telemetry either way (host-side sampling only).
        telemetry_rec = podwatch_mod.maybe_start(preempt_watcher=preempt_watcher)

        # fleet orchestration (lightgbm_tpu/flex/): a capacity plan arms a
        # boundary-driven watcher that latches the SAME chunk-boundary latch
        # preemption uses, with reason "drain" (exit RESHARD_EXIT_CODE so the
        # flexctl controller relaunches at the new capacity). Threadless: its
        # whole runtime cost is one check_boundary call per chunk boundary.
        # flex_plan unset costs exactly the one env read above — no import, no
        # latch, no objects (the inertness contract).
        latch = preempt_watcher
        flex_watcher = None
        if flex_plan:
            from .flex import watch as flexwatch_mod
            from .obs import dist as dist_mod
            from .resil import checkpoint as ckpt_mod

            if ckpt_writer is None:
                log.warning(
                    "flex: flex_plan armed without checkpoint_path — a drain "
                    "will exit with the reshard code but WITHOUT a checkpoint "
                    "for the relaunch to resume from"
                )
            rank, procs = dist_mod.process_info()
            hb_base = None
            if procs > 1:
                # dead-rank evidence: the telemetry heartbeats refresh every
                # boundary when podwatch is armed; the checkpoint-side ones
                # only at checkpoint cadence (still usable, just coarser)
                hb_base = (podwatch_mod.heartbeat_base(telemetry_rec.out_dir)
                           if telemetry_rec is not None else checkpoint_path)
            if latch is None:
                latch = preempt_mod.BoundaryLatch()
            flex_watcher = flexwatch_mod.maybe_watch(
                flex_plan, latch,
                checkpoint_path=checkpoint_path or flex_plan,
                live_world=ckpt_mod.mesh_world_of(booster._gbdt),
                procs=procs, rank=rank, hb_base=hb_base,
                dead_after_s=flex_dead_after_s,
            )

        evaluation_result_list: List = []
        init_span.note(bytes=int(booster._gbdt.bins_dev.nbytes))
        init_span.close()
        with timer_mod.maybe_profile():
            try:
                evaluation_result_list = _boost_loop(
                    booster, params, fobj, feval, valid_sets,
                    is_valid_contain_train, train_data_name, init_iteration,
                    num_boost_round, cbs_before, cbs_after, chunk,
                    start_iteration=start_iteration, ckpt_writer=ckpt_writer,
                    preempt_watcher=latch, flex_watcher=flex_watcher,
                )
            except Exception as e:
                # compose with the collective watchdog instead of racing
                # it: when flex is armed, a named collective deadline is a
                # capacity event (a peer is gone) — drain so the
                # controller reshards onto the survivors
                detail = (flex_watcher.drain_reason_for(e)
                          if flex_watcher is not None else None)
                if detail is None:
                    raise
                flex_watcher.note_failure_drain(detail)
                log.warning(
                    "flex: %s — draining so the orchestrator reshards "
                    "onto the survivors (exiting with the reshard code, "
                    "%d); the last periodic checkpoint is the recovery "
                    "point" % (detail, preempt_mod.RESHARD_EXIT_CODE)
                )
                raise preempt_mod.TrainingDrained(
                    "training drained after %s" % detail,
                    checkpoint_path=getattr(ckpt_writer, "path", None),
                    detail=detail,
                ) from e
        return _finish_train(
            booster, evaluation_result_list, flight_rec, model_stats
        )
    finally:
        trace_mod.close_to(open_spans)
        if preempt_watcher is not None:
            preempt_watcher.uninstall()
        # a crashed/interrupted run (anywhere — the loop, the deferred stop
        # readback, the harvest) still closes its flight log:
        # the records up to the failure are exactly the evidence wanted,
        # and a leaked _ACTIVE recorder would silently disable recording
        # for every later train() in the process
        if flight_rec is not None and flight_mod.active() is flight_rec:
            flight_mod.note_event("aborted")
            flight_mod.stop()
        # same leak rule for the telemetry recorder; the scrape listener
        # (if armed) deliberately stays up across train() calls
        if (telemetry_rec is not None
                and podwatch_mod.active() is telemetry_rec):
            podwatch_mod.stop()


def _finish_train(booster, evaluation_result_list, flight_rec, model_stats):
    """Post-loop bookkeeping (split from train() so its flight-recorder
    finally can distinguish a clean finish from an abort)."""
    # resolve the deferred no-split check before handing the booster back:
    # a stop inside the FINAL chunk (or final iteration) would otherwise
    # leave rolled-back-to-be trees visible to num_trees/current_iteration
    # until something materializes the model
    booster._gbdt._consume_pending_stop()
    booster._gbdt.timers.report()
    # same numbers, machine-readable: phase totals land in the metrics
    # registry so /metrics and bringup reports agree
    booster._gbdt.timers.publish()

    booster.best_score = collections.defaultdict(collections.OrderedDict)
    for (dname, ename, v, _) in evaluation_result_list or []:
        booster.best_score[dname][ename] = v
    if booster.best_iteration <= 0:
        booster.best_iteration = booster.current_iteration

    # model/data observability tier (docs/Observability.md): both read only
    # host state — the trained model is bitwise-unaffected and nothing new
    # compiles. modelstats also engages whenever a flight log was recorded
    # (one opt-in should yield the whole model-observability picture).
    if flight_rec is not None:
        flight_mod.finish_training(booster)
    from .obs import modelstats as modelstats_mod

    if model_stats or flight_rec is not None or modelstats_mod.env_enabled():
        modelstats_mod.publish(booster)
    return booster


def _boost_loop(
    booster, params, fobj, feval, valid_sets, is_valid_contain_train,
    train_data_name, init_iteration, num_boost_round, cbs_before, cbs_after,
    chunk: int = 1, start_iteration: Optional[int] = None, ckpt_writer=None,
    preempt_watcher=None, flex_watcher=None,
):
    """The boosting iteration loop; returns the last evaluation result list.

    ``chunk > 1`` steps by device-resident chunks (Booster.update_chunk):
    eval and after-iteration callbacks run once per chunk boundary with
    ``iteration`` = the last completed iteration; ``chunk=1`` is the classic
    per-iteration loop, byte-identical to the pre-chunking behavior.

    ``start_iteration`` positions a RESUMED loop past the checkpointed
    iterations while ``init_iteration`` keeps the original run's begin (so
    callback windows and the end bound replay identically); ``ckpt_writer``
    (resil/checkpoint.py) saves the full training state at its cadence
    boundaries."""
    evaluation_result_list: List = []
    needs_eval = valid_sets is not None or bool(
        params.get("is_provide_training_metric")
    )
    i = init_iteration if start_iteration is None else start_iteration
    end = init_iteration + num_boost_round
    if booster._gbdt._stopped:
        # a checkpoint taken AT a no-split stop boundary restores
        # stopped=True: nothing is left to train, and one more loop pass
        # would re-run eval + callbacks the uninterrupted run never had
        return evaluation_result_list
    iter_counter = obs_registry.REGISTRY.counter("train_iterations")
    flight_on = flight_mod.active() is not None
    telemetry_on = podwatch_mod.active() is not None
    # train.boundary: from update's return to the next train.iteration, so
    # the next pass's fault site and before-callbacks lie in it; left open
    # by an exception, engine.train's finally closes it
    boundary = trace_mod.span("train.boundary", cat="train")  # none open yet
    t_boundary_us = trace_mod.now_us()
    while i < end:
        # named fault site: the crash tests SIGKILL here mid-run and prove
        # resume_from replays to a byte-identical model (resil/faults.py)
        faults.maybe_fire("train.iteration")
        for cb in cbs_before:
            cb(
                callback_mod.CallbackEnv(
                    model=booster,
                    params=params,
                    iteration=i,
                    begin_iteration=init_iteration,
                    end_iteration=end,
                    evaluation_result_list=None,
                )
            )
        # the transfer sanitizer's guarded scopes live at the JITTED
        # dispatch seams this loop drives (gbdt.train_chunk, ops.grow_tree,
        # gbdt.finish_tree, serve's bucketed dispatch) rather than around
        # the whole boundary: the sequential path's eager gradient/bagging
        # math legitimately materializes python/numpy scalar constants,
        # which jax uploads through the same implicit path the guard
        # polices (obs/sanitize.py)
        boundary.close()
        if chunk > 1 and end - i >= chunk:
            with trace_mod.span("train.chunk", cat="train", iteration=i,
                                chunk=chunk):
                done, finished = booster.update_chunk(
                    chunk, sync_stop=needs_eval
                )
            if done == 0:
                break
        else:
            # the tail shorter than a chunk runs per-iteration: a tail-sized
            # scan would trace + XLA-compile a whole second boosting program
            # to save at most chunk-1 host round-trips
            with trace_mod.span("train.iteration", cat="train", iteration=i):
                finished = booster.update(fobj=fobj)
            done = 1
        i += done
        boundary = trace_mod.span("train.boundary", cat="train",
                                  iteration=i - 1).__enter__()
        iter_counter.inc(done)
        if sanitize_mod.NAN:
            # boundary tripwire: a non-finite score carry fails HERE, named,
            # instead of surfacing iterations later as a metric collapse
            sanitize_mod.check_scores(booster._gbdt, i - 1)

        evaluation_result_list = []
        if needs_eval:
            if is_valid_contain_train:
                evaluation_result_list.extend(
                    [(train_data_name, n, v, b) for (_, n, v, b) in booster.eval_train(feval)]
                )
            evaluation_result_list.extend(booster.eval_valid(feval))
            hist = booster._gbdt._eval_history
            for (dname, mname, val, _) in evaluation_result_list:
                hist.setdefault(dname, {}).setdefault(mname, []).append(val)
        if flight_on or telemetry_on:
            # one record per boundary: the boundary's wall time (host clock
            # only — the dispatch is async either way, so this is
            # dispatch+eval time, not a fence), shared by the flight
            # recorder and the telemetry ring so both attribute the SAME
            # seconds to the same boundary: from the start of the last
            # boundary to the start of this one, on train.boundary's own
            # clock read (the tracer's clock where recording is off)
            now_us = boundary.t0_us or trace_mod.now_us()
            dt_boundary = (now_us - t_boundary_us) / 1e6
            t_boundary_us = now_us
            if flight_on:
                flight_mod.note_boundary(
                    i - 1, done, dt_boundary, evaluation_result_list
                )
            if telemetry_on:
                podwatch_mod.note_boundary(
                    i - 1, done, dt_boundary, gbdt=booster._gbdt
                )
        try:
            # a span of its own: a callback may block (the benchmark's
            # does), and that is not the loop's time
            with trace_mod.span("train.callbacks", cat="train"):
                for cb in cbs_after:
                    cb(
                        callback_mod.CallbackEnv(
                            model=booster,
                            params=params,
                            iteration=i - 1,
                            begin_iteration=init_iteration,
                            end_iteration=end,
                            evaluation_result_list=evaluation_result_list,
                            chunk=done,
                        )
                    )
        except callback_mod.EarlyStopException as es:
            booster.best_iteration = es.best_iteration + 1
            evaluation_result_list = es.best_score
            if flight_on:
                flight_mod.note_event(
                    "early_stop", iteration=i - 1,
                    best_iteration=es.best_iteration + 1,
                )
            break
        wrote_boundary = False
        if ckpt_writer is not None and ckpt_writer.due(i, done):
            # after the boundary's eval + callbacks, so the early-stopping
            # bests captured are exactly the ones a resumed run needs next
            try:
                ckpt_writer.write(booster, init_iteration, end)
                wrote_boundary = True
                if flight_on:
                    flight_mod.note_event("checkpoint", iteration=i)
            except LightGBMError:
                raise  # structural refusal (e.g. dart): a config error, loud
            except Exception as e:
                # a failed write (ENOSPC, NFS blip) must not kill the run it
                # exists to protect: the last good checkpoint is intact on
                # disk (atomic publish), so warn and keep training
                obs_registry.REGISTRY.counter("resil_checkpoint_errors").inc()
                log.warning(
                    "checkpoint: write failed (%s: %s); continuing — the "
                    "last good checkpoint is intact"
                    % (type(e).__name__, str(e)[:200])
                )
        if flex_watcher is not None:
            # the flex capacity watcher runs at the same boundary the
            # latch is honored at, so a plan change seen NOW drains NOW
            # (single-process; a pod takes one more boundary to reach
            # marker consensus — flex/watch.py documents the protocol)
            flex_watcher.check_boundary(i)
        if (preempt_watcher is not None and preempt_watcher.requested()
                and i < end and not finished):
            # a latched SIGTERM (reason "preempt") or flex drain (reason
            # "drain") is honored HERE, at a chunk boundary — the one
            # place the full training state is checkpointable — but NOT
            # when this boundary just finished the run (i == end, or the
            # deferred no-split stop resolved): the trained model is
            # complete in memory, and exiting 75/76 would throw it away
            # just to retrain it on resume. Fault site train.preempt lets
            # the crash tests SIGKILL between the signal and the emergency
            # write (the last periodic checkpoint must carry the resume).
            reason = getattr(preempt_watcher, "reason", "preempt")
            no_barrier = getattr(preempt_watcher, "no_barrier", False)
            faults.maybe_fire("train.preempt")
            ck_path = None
            if ckpt_writer is not None:
                from .obs import dist as dist_mod

                multiproc = dist_mod.process_info()[1] > 1
                if wrote_boundary:
                    # this boundary's periodic checkpoint IS the state an
                    # emergency save would capture — don't publish it twice
                    ck_path = ckpt_writer.path
                elif multiproc and reason == "preempt":
                    # multi-process world: the emergency save would run the
                    # coordinated digest barrier, but SIGTERM latch timing
                    # is per-rank — a peer whose signal landed one boundary
                    # later is inside its next collective, and waiting for
                    # it would burn the whole kill grace window. The
                    # periodic BARRIER checkpoints are the pod-coherent
                    # recovery points; exit on the last one. (A planned
                    # DRAIN is different: the marker protocol latches every
                    # rank at the same boundary, so its coordinated save
                    # below CAN barrier.)
                    log.warning(
                        "preempt: multi-process world — skipping the "
                        "emergency checkpoint (per-rank signal timing "
                        "cannot run the coordinated save barrier); the "
                        "last periodic checkpoint is the recovery point"
                    )
                elif multiproc and no_barrier:
                    # dead-rank drain: the digest barrier can never reach
                    # consensus with a participant gone — survivors exit
                    # on the last periodic checkpoint
                    log.warning(
                        "flex: drain without barrier (%s) — skipping the "
                        "coordinated emergency checkpoint; the last "
                        "periodic checkpoint is the recovery point"
                        % (getattr(preempt_watcher, "detail", "") or reason)
                    )
                else:
                    try:
                        ck_path = ckpt_writer.write(
                            booster, init_iteration, end, emergency=True
                        )
                    except Exception as e:
                        # the grace window is running out either way: exit
                        # preempted on the last good periodic checkpoint
                        log.warning(
                            "preempt: emergency checkpoint failed (%s: %s); "
                            "exiting on the last periodic checkpoint"
                            % (type(e).__name__, str(e)[:200])
                        )
            if reason == "drain":
                detail = getattr(preempt_watcher, "detail", "") or "drain"
                if flight_on:
                    flight_mod.note_event(
                        "drained", iteration=i - 1, checkpoint=ck_path
                    )
                log.warning(
                    "flex: drain (%s) honored at iteration %d; checkpoint "
                    "%s; exiting with the reshard code (%d)"
                    % (detail, i, ck_path or "<none>",
                       preempt_mod.RESHARD_EXIT_CODE)
                )
                raise preempt_mod.TrainingDrained(
                    "training drained for reshard (%s) at iteration %d"
                    % (detail, i),
                    checkpoint_path=ck_path, iteration=i, detail=detail,
                )
            if flight_on:
                flight_mod.note_event(
                    "preempted", iteration=i - 1, checkpoint=ck_path
                )
            log.warning(
                "preempt: signal %d honored at iteration %d; emergency "
                "checkpoint %s; exiting with the preemption code (%d)"
                % (preempt_watcher.signum, i, ck_path or "<none>",
                   preempt_mod.PREEMPT_EXIT_CODE)
            )
            raise preempt_mod.TrainingPreempted(
                "training preempted by signal %d at iteration %d"
                % (preempt_watcher.signum, i),
                checkpoint_path=ck_path, iteration=i,
                signum=preempt_watcher.signum,
            )
        if finished:
            # the deferred no-split stop (models/gbdt.py) resolved at this
            # boundary: the splitless iteration was rolled back already
            if flight_on:
                flight_mod.note_event("no_split_stop", iteration=i - 1)
            break
    boundary.close()
    return evaluation_result_list


class CVBooster:
    def __init__(self) -> None:
        self.boosters: List[Booster] = []
        self.best_iteration = -1

    def _append(self, booster: Booster) -> None:
        self.boosters.append(booster)

    def __getattr__(self, name):
        def handler_function(*args, **kwargs):
            return [getattr(b, name)(*args, **kwargs) for b in self.boosters]

        return handler_function


def _make_n_folds(full_data: Dataset, folds, nfold, params, seed, stratified, shuffle, config):
    full_data.construct(config)
    num_data = full_data.num_data()
    binned = full_data._binned
    if folds is not None:
        if not hasattr(folds, "__iter__") and hasattr(folds, "split"):
            group = binned.metadata.query_boundaries
            group_info = None
            if group is not None:
                qid = np.zeros(num_data, np.int64)
                for q in range(len(group) - 1):
                    qid[group[q] : group[q + 1]] = q
                group_info = qid
            folds = folds.split(X=np.zeros(num_data), y=binned.metadata.label, groups=group_info)
    else:
        rng = np.random.RandomState(seed)
        if binned.metadata.query_boundaries is not None:
            # group-aware folds: split whole queries
            nq = binned.metadata.num_queries
            qperm = rng.permutation(nq) if shuffle else np.arange(nq)
            fold_qs = np.array_split(qperm, nfold)
            qb = binned.metadata.query_boundaries
            folds = []
            for fq in fold_qs:
                test_idx = np.concatenate(
                    [np.arange(qb[q], qb[q + 1]) for q in sorted(fq)]
                ) if len(fq) else np.array([], np.int64)
                train_idx = np.setdiff1d(np.arange(num_data), test_idx)
                folds.append((train_idx, test_idx))
        elif stratified:
            label = binned.metadata.label.astype(np.int64)
            folds = []
            fold_assign = np.zeros(num_data, np.int64)
            for cls in np.unique(label):
                idx = np.nonzero(label == cls)[0]
                if shuffle:
                    idx = idx[rng.permutation(len(idx))]
                fold_assign[idx] = np.arange(len(idx)) % nfold
            for k in range(nfold):
                test_idx = np.nonzero(fold_assign == k)[0]
                train_idx = np.nonzero(fold_assign != k)[0]
                folds.append((train_idx, test_idx))
        else:
            perm = rng.permutation(num_data) if shuffle else np.arange(num_data)
            chunks = np.array_split(perm, nfold)
            folds = [
                (np.setdiff1d(np.arange(num_data), c), np.sort(c)) for c in chunks
            ]
    return folds


def cv(
    params: Dict,
    train_set: Dataset,
    num_boost_round: int = 100,
    folds=None,
    nfold: int = 5,
    stratified: bool = True,
    shuffle: bool = True,
    metrics=None,
    fobj=None,
    feval=None,
    init_model=None,
    feature_name: str = "auto",
    categorical_feature: str = "auto",
    early_stopping_rounds: Optional[int] = None,
    fpreproc=None,
    verbose_eval=None,
    show_stdv: bool = True,
    seed: int = 0,
    callbacks=None,
    eval_train_metric: bool = False,
) -> Dict[str, List[float]]:
    params = Config.canonicalize(dict(params) if params else {})
    if "num_iterations" in params:
        num_boost_round = int(params.pop("num_iterations"))
    if "early_stopping_round" in params and early_stopping_rounds is None:
        early_stopping_rounds = int(params.pop("early_stopping_round"))
    if metrics is not None:
        params["metric"] = metrics
    if params.get("objective") in ("binary",) or str(params.get("objective", "")).startswith("multiclass"):
        pass
    else:
        stratified = False
    config = Config.from_params(params)

    folds = _make_n_folds(train_set, folds, nfold, params, seed, stratified, shuffle, config)

    results = collections.defaultdict(list)
    cvboosters = []
    fold_data = []
    for train_idx, test_idx in folds:
        tr = train_set.subset(np.sort(train_idx))
        te = train_set.subset(np.sort(test_idx))
        booster = Booster(params=params, train_set=tr)
        booster.add_valid(te, "valid")
        cvboosters.append(booster)
        fold_data.append((tr, te))

    cbs = set(callbacks or [])
    if early_stopping_rounds is not None and early_stopping_rounds > 0:
        cbs.add(callback_mod.early_stopping(early_stopping_rounds, verbose=False))
    if verbose_eval is True:
        cbs.add(callback_mod.print_evaluation(show_stdv=show_stdv))
    elif isinstance(verbose_eval, int) and verbose_eval:
        cbs.add(callback_mod.print_evaluation(verbose_eval, show_stdv))
    cbs = sorted(cbs, key=lambda c: getattr(c, "order", 0))

    best_iteration = -1
    for i in range(num_boost_round):
        agg: Dict[str, List[float]] = collections.defaultdict(list)
        for booster in cvboosters:
            booster.update(fobj=fobj)
            for (dname, ename, v, b) in booster.eval_valid(feval):
                agg[("%s %s" % (dname, ename), b)].append(v)
        res_list = []
        for (key, bigger), vals in agg.items():
            mean, std = float(np.mean(vals)), float(np.std(vals))
            results[key.split(" ", 1)[1] + "-mean"].append(mean)
            results[key.split(" ", 1)[1] + "-stdv"].append(std)
            res_list.append(("cv_agg", key.split(" ", 1)[1], mean, bigger, std))
        try:
            for cb in cbs:
                cb(
                    callback_mod.CallbackEnv(
                        model=None,
                        params=params,
                        iteration=i,
                        begin_iteration=0,
                        end_iteration=num_boost_round,
                        evaluation_result_list=res_list,
                    )
                )
        except callback_mod.EarlyStopException as es:
            best_iteration = es.best_iteration + 1
            for key in list(results.keys()):
                results[key] = results[key][:best_iteration]
            break
    return dict(results)
