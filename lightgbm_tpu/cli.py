"""Command-line application: train / predict from config files.

TPU-native counterpart of the reference Application
(/root/reference/src/application/application.cpp, src/main.cpp): parses
``key=value`` argv tokens plus an optional ``config=`` file (argv wins,
application.cpp:48-81), dispatches on ``task`` (train/predict, config.h:26),
loads train/valid data with sidecar weight/query files, runs the boosting loop
with per-iteration metric output, and saves/loads LightGBM-format models.

Usage:  python -m lightgbm_tpu task=train config=train.conf [key=value ...]
"""
from __future__ import annotations

import sys
from typing import Dict, List, Optional

import numpy as np

from . import callback as callback_mod
from .basic import Booster, Dataset
from .obs import trace as trace_mod
from .config import Config, load_config_file
from .engine import train as train_api
from .io import load_sidecar, load_text_file
from .resil.atomic import atomic_write_text
from .resil.preempt import PREEMPT_EXIT_CODE, TrainingPreempted
from .utils import log
from .utils.vfile import vopen
from .utils.log import LightGBMError
from .utils.platform import place_compile_cache


def parse_args(argv: List[str]) -> Dict[str, str]:
    params = Config.kv2map(argv)
    if "config" in params:
        file_params = load_config_file(params["config"])
        for k, v in file_params.items():
            params.setdefault(k, v)  # CLI overrides file
    return params


def _load_dataset(path: str, config: Config, reference: Optional[Dataset] = None) -> Dataset:
    from .dataset import is_binary_dataset_file

    if is_binary_dataset_file(path):
        # binary fast path (LoadFromBinFile, dataset_loader.cpp:268)
        log.info("Loading binned dataset from binary file %s" % path)
        return Dataset(path, reference=reference, params={})
    # valid files must come out as wide as the train set (sparse libsvm rows
    # may never reach the highest train feature index)
    ref_width = reference.num_feature() if reference is not None else None
    X, y, names = load_text_file(
        path,
        has_header=config.header,
        label_column=config.label_column,
        model_num_features=ref_width,
    )
    weight = load_sidecar(path, "weight")
    group = load_sidecar(path, "query")
    init_score = load_sidecar(path, "init")
    ds = Dataset(
        X,
        label=y,
        weight=weight,
        group=None if group is None else group.astype(np.int64),
        init_score=init_score,
        reference=reference,
        feature_name=names if names else "auto",
        params={},
    )
    return ds


def run_train(config: Config, params: Dict[str, str]) -> None:
    if not config.data:
        log.fatal("No training data specified (data=...)")
    log.info("Loading train data from %s" % config.data)
    train_set = _load_dataset(config.data, config)
    if config.save_binary:
        train_set.params.update(params)
        train_set.save_binary(config.data + ".bin")
        log.info("Saved binned dataset to %s.bin" % config.data)
    valid_sets = []
    valid_names = []
    for i, v in enumerate(config.valid):
        log.info("Loading validation data from %s" % v)
        valid_sets.append(_load_dataset(v, config, reference=train_set))
        valid_names.append("valid_%d" % (i + 1))

    params = dict(params)
    params.pop("config", None)
    params.pop("task", None)
    params.pop("data", None)
    params.pop("valid", None)
    params.pop("output_model", None)
    callbacks = []
    if config.snapshot_freq > 0:
        # periodic model snapshots next to the output model (gbdt.cpp:254-258)
        freq, path = config.snapshot_freq, config.output_model

        def _snapshot(env):
            if (env.iteration + 1) % freq == 0:
                snap = "%s.snapshot_iter_%d" % (path, env.iteration + 1)
                env.model.save_model(snap)
                log.info("Saved snapshot to %s" % snap)

        _snapshot.order = 100
        callbacks.append(_snapshot)
    # crash-safe full-state checkpoints (beyond the model-only snapshots
    # above): checkpoint_path=... [checkpoint_rounds=N] resume_from=...
    # restart a SIGKILLed run bit-identically (docs/FaultTolerance.md);
    # engine.train pops these from params so the model footer stays clean
    booster = train_api(
        params,
        train_set,
        num_boost_round=config.num_iterations,
        valid_sets=valid_sets or None,
        valid_names=valid_names or None,
        init_model=config.input_model or None,
        early_stopping_rounds=config.early_stopping_round or None,
        verbose_eval=config.metric_freq if config.verbosity >= 1 else False,
        callbacks=callbacks or None,
        checkpoint_path=config.checkpoint_path or None,
        checkpoint_rounds=max(config.checkpoint_rounds, 0),
        resume_from=config.resume_from or None,
        # checkpoint_keep / preempt_exit deliberately NOT passed as kwargs:
        # they ride the params map engine.train pops, where an EXPLICIT
        # preempt_exit=false wins over LIGHTGBM_TPU_PREEMPT=1 — a
        # `config.preempt_exit or None` kwarg would collapse that false to
        # "unset" and the env would re-arm the job
    )
    booster.save_model(config.output_model)
    log.info("Finished training; model saved to %s" % config.output_model)


def run_predict(config: Config, params: Dict[str, str]) -> None:
    if not config.data:
        log.fatal("No prediction data specified (data=...)")
    if not config.input_model:
        log.fatal("No model file specified (input_model=...)")
    booster = Booster(model_file=config.input_model)
    X, _, _ = load_text_file(
        config.data,
        has_header=config.header,
        label_column=config.label_column,
        model_num_features=booster.num_feature(),
    )
    preds = booster.predict(
        X,
        num_iteration=config.num_iteration_predict,
        raw_score=config.predict_raw_score,
        pred_leaf=config.predict_leaf_index,
        pred_contrib=config.predict_contrib,
        pred_early_stop=config.pred_early_stop,
        pred_early_stop_freq=config.pred_early_stop_freq,
        pred_early_stop_margin=config.pred_early_stop_margin,
    )
    out = np.asarray(preds)
    with vopen(config.output_result, "w") as fh:
        # the per-value "%.18g" formatting beats np.savetxt ~2.3x at 1M rows
        # (savetxt re-parses its row format per line; measured r4); chunked
        # joins keep peak memory bounded on huge prediction files
        if out.ndim == 1:
            step = 1 << 17
            for i in range(0, out.shape[0], step):
                fh.write("\n".join(map("%.18g".__mod__, out[i:i + step].tolist())))
                fh.write("\n")
        else:
            for row in out:
                fh.write("\t".join("%.18g" % v for v in row) + "\n")
    log.info("Finished prediction; results saved to %s" % config.output_result)


def run_convert_model(config: Config, params: Dict[str, str]) -> None:
    """task=convert_model (application.cpp:258-262): model file → standalone
    C++ source (if-else codegen)."""
    if not config.input_model:
        log.fatal("No model file specified (input_model=...)")
    from .models.model_codegen import save_model_to_ifelse

    booster = Booster(model_file=config.input_model)
    code = save_model_to_ifelse(booster._gbdt, num_iteration=-1)
    atomic_write_text(config.convert_model, code)
    log.info("Finished converting model; source saved to %s" % config.convert_model)


def run_serve(config: Config, params: Dict[str, str]) -> None:
    """task=serve: stand up the inference server on ``input_model``
    (lightgbm_tpu/serve). Extra knobs ride in as raw params:
    serve_host / serve_port / serve_mode / max_batch_rows / max_delay_ms."""
    if not config.input_model:
        log.fatal("No model file specified (input_model=...)")
    from .serve.__main__ import main as serve_main

    argv = [config.input_model,
            "--host", params.get("serve_host", "127.0.0.1"),
            "--port", params.get("serve_port", "8080"),
            "--mode", params.get("serve_mode", "exact")]
    if "max_batch_rows" in params:
        argv += ["--max-batch-rows", params["max_batch_rows"]]
    if "max_delay_ms" in params:
        argv += ["--max-delay-ms", params["max_delay_ms"]]
    serve_main(argv)


def run_refit(config: Config, params: Dict[str, str]) -> None:
    """task=refit (application.cpp:214-239): load model, predict leaves on
    data, refit leaf values on its labels, save."""
    if not config.data:
        log.fatal("No refit data specified (data=...)")
    if not config.input_model:
        log.fatal("No model file specified (input_model=...)")
    booster = Booster(model_file=config.input_model, params=dict(params))
    X, y, _ = load_text_file(
        config.data,
        has_header=config.header,
        label_column=config.label_column,
        model_num_features=booster.num_feature(),
    )
    if y is None:
        log.fatal("Refit data must contain a label column")
    refitted = booster.refit(X, y, decay_rate=config.refit_decay_rate)
    refitted.save_model(config.output_model)
    log.info("Finished RefitTree; model saved to %s" % config.output_model)


def main(argv: Optional[List[str]] = None) -> int:
    argv = argv if argv is not None else sys.argv[1:]
    try:
        params = parse_args(argv)
        config = Config.from_params(params)
        # the grower is a multi-minute compile on a TPU: keep it across runs
        # (JAX_COMPILATION_CACHE_DIR when set, else <checkout>/.jax_cache)
        place_compile_cache()
        # task-level obs span: with LIGHTGBM_TPU_TRACE set, the whole CLI
        # task becomes the root span the training/serving spans nest under
        with trace_mod.span("cli.%s" % config.task, cat="cli"):
            if config.task == "train":
                run_train(config, params)
            elif config.task in ("predict", "prediction", "test"):
                run_predict(config, params)
            elif config.task == "convert_model":
                run_convert_model(config, params)
            elif config.task == "refit":
                run_refit(config, params)
            elif config.task == "serve":
                run_serve(config, params)
            else:
                log.fatal("Unknown task: %s" % config.task)
    except TrainingPreempted as e:
        # the boundary-latch contracts (docs/FaultTolerance.md): a durable
        # checkpoint was published at the last boundary, and the DISTINCT
        # exit code tells orchestrators what kind of relaunch is wanted —
        # 75 "resume me as I was" (preempt; loop restart), 76 "relaunch me
        # at current capacity" (flexctl drain, §Fleet orchestrator)
        if getattr(e, "reason", "preempt") == "drain":
            log.warning(
                "train drained for reshard (%s); checkpoint: %s — the "
                "flex controller relaunches at the new capacity; exiting %d"
                % (e, e.checkpoint_path or "<none>", e.exit_code)
            )
            return e.exit_code
        log.warning(
            "train preempted (%s); emergency checkpoint: %s — re-run with "
            "resume_from to continue; exiting %d"
            % (e, e.checkpoint_path or "<none>", PREEMPT_EXIT_CODE)
        )
        return PREEMPT_EXIT_CODE
    except LightGBMError as e:
        # application_main's catch block ("Met Exceptions", main.cpp): a clean
        # message + nonzero exit, not a traceback
        print("Met Exceptions:\n%s" % e, file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
