"""One run of one cell: ``python3 -m benchmarks.run --workload <cell> --seed <n>
--seconds <s> --trace <0|1>`` from the root of a checkout, on a machine that
holds the cell's chips.

One process and one ``lgb.train`` call. Set-up runs from the start of the
process to the end of the warm-up iterations (data from the seed, binning,
transfer, tracing and compiling or loading the programs); the window callback
(``window.py``) times what follows. After the window the device's peak memory
is read, the program's state is freed, and the reference decides ``correct``
(``correct.py``). The last line of standard output is the result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

import numpy as np  # noqa: E402

from . import correct, datagen, model_text, peaks, trace_reduce, window  # noqa: E402
from .manifest import Manifest, ManifestError  # noqa: E402

NO_CHIP_EXIT = 3
TRACE_DIR = ".bench_trace"


def say(msg: str) -> None:
    print("bench: " + msg, file=sys.stderr, flush=True)


def need_chips(chips: int) -> None:
    """Exits, before any data is made, unless JAX runs on a TPU with at
    least the chips the cell asks for."""
    import jax

    if jax.default_backend() != "tpu" or len(jax.devices()) < chips:
        say("needs %d TPU chip(s); JAX reports backend %r with %d device(s)"
            % (chips, jax.default_backend(), len(jax.devices())))
        sys.exit(NO_CHIP_EXIT)


def place_cache() -> str:
    """The persistent compilation cache at the program's fixed place in the
    checkout (or where ``JAX_COMPILATION_CACHE_DIR`` says), holding every
    program of a run, the small ones too, so that a second run compiles
    nothing."""
    import jax

    from lightgbm_tpu.utils.platform import place_compile_cache

    path = place_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def memory_stats() -> list:
    """``memory_stats()`` of every device, in ``jax.devices()`` order."""
    import jax

    return [d.memory_stats() or {} for d in jax.devices()]


def train_params(config: dict, traffic: dict) -> dict:
    """The configuration's parameters with the traffic mix's over them."""
    return dict(config["params"], **traffic["params"], verbosity=-1)


def drive(lgb, params: dict, ds, traffic: dict, seconds: float,
          trace_dir: str = None) -> dict:
    """The one ``lgb.train`` call under the window callback, and what the
    timed path produced: the model text, the scores after each warm-up
    iteration and the scores when the run stopped."""
    import jax

    warm_scores = []
    win = window.Window(
        warmup=traffic["warmup_iterations"], seconds=seconds,
        stop_exception=lgb.callback.EarlyStopException,
        observe_warmup=lambda done, scores: warm_scores.append(np.asarray(scores)),
        trace_dir=trace_dir, trace_iters=traffic.get("trace_iterations", 2))
    if trace_dir:
        shutil.rmtree(trace_dir, ignore_errors=True)
    jax.monitoring.register_event_duration_secs_listener(win.on_event)
    try:
        bst = lgb.train(params, ds, num_boost_round=traffic["max_iterations"],
                        callbacks=[win], verbose_eval=False,
                        keep_training_booster=True)
    finally:
        jax.monitoring.unregister_event_duration_listener(win.on_event)
    if win.t_fetch is None:
        raise RuntimeError("training ended before the window closed")
    stats = memory_stats()
    return {"win": win, "text": bst.model_to_string(), "warm_scores": warm_scores,
            "final_scores": np.asarray(bst._gbdt.scores), "memory_stats": stats,
            "iterations_run": win.warmup + win.iterations + win.traced_iterations}


def run_cell(man: Manifest, workload: str, seed: int, seconds: float, trace: bool,
             dump_trace: str = None) -> dict:
    """Everything of a run but the look for a chip; returns the result line
    as a dict."""
    import jax

    import lightgbm_tpu as lgb

    cell = man.workload(workload)
    config = man.config(cell["config"])
    traffic = man.traffic(cell["traffic"])
    limits = man.limits(workload)
    if traffic["kind"] != "train":
        raise ManifestError("traffic kind %r is not one this harness drives"
                            % traffic["kind"])
    devices = jax.devices()[: cell["chips"]]
    params = train_params(config, traffic)

    t = time.perf_counter()
    X, y = datagen.make(config, seed, man.bench_dir)
    t_data = time.perf_counter() - t
    say("%d x %d rows made from seed %d in %.1fs" % (X.shape[0], X.shape[1], seed, t_data))
    t = time.perf_counter()
    ds = lgb.Dataset(X, label=y, params=params, free_raw_data=True).construct()
    t_bin = time.perf_counter() - t
    say("Dataset built in %.1fs; training" % t_bin)

    run = drive(lgb, params, ds, traffic, seconds,
                os.path.join(man.root, TRACE_DIR) if trace else None)
    win = run["win"]
    setup_s = win.t_warm - T_START
    stats = run["memory_stats"][: len(devices)]
    peak_bytes = max(int(s.get("peak_bytes_in_use", 0)) for s in stats)
    say("set-up %.1fs (data %.1fs, Dataset %.1fs, train to end of warm-up %.1fs); "
        "window %.3fs by fetch, %.3fs by block_until_ready, %d iterations"
        % (setup_s, t_data, t_bin, setup_s - t_data - t_bin, win.window_s,
           win.t_block - win.t_warm, win.iterations))
    say("the callback was entered at " + " ".join("%.2f" % t for t in win.entries_s)
        + " s of the window")

    # what the timed path produced; then its state goes, so that the
    # reference neither shares the device with it nor sets the peak
    edges = correct.bin_edges(ds, config["features"])
    del ds
    gc.collect()

    trees = model_text.parse_trees(run["text"])
    # what a metric's reader (metrics/<name>.py) is handed; `trace` is the
    # dict trace_reduce.reduce returns plus `traced_iterations`, None untraced
    ctx = {
        "config": config, "traffic": traffic, "cell": cell,
        "setup_s": setup_s, "window_s": win.window_s, "iterations": win.iterations,
        "host_gaps_s": win.host_gaps_s,
        "window_trees": trees[win.warmup: win.warmup + win.iterations],
        "peak": peaks.peaks(devices[0].device_kind) if devices[0].platform == "tpu" else None,
        "memory_peak_bytes": peak_bytes,
        "trace": None,
    }
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": peak_bytes}
    result = {"correct": False, "attempted": win.iterations, "failed": 0}
    if trace:
        path = trace_reduce.find_xplane(win.trace_dir)
        rows = path and trace_reduce.load(path, keep_host=window.HOST_SPANS)
        reduced = rows and trace_reduce.reduce(rows, host_spans=window.HOST_SPANS)
        if dump_trace and rows:
            trace_reduce.dump(rows, dump_trace)
        shutil.rmtree(win.trace_dir, ignore_errors=True)
        if not reduced:
            raise RuntimeError("the trace holds no device operation")
        reduced["traced_iterations"] = win.traced_iterations
        ctx["trace"] = reduced
        device["busy_s"], device["window_s"] = reduced["busy_s"], reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    metrics = {}
    for m in man.metrics("per_layer" if trace else "end_to_end", workload):
        value = man.reader(m["name"])(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    result["metrics"] = metrics
    result["device"] = device

    t = time.perf_counter()
    numbers = correct.compare(
        run["text"], run["warm_scores"], run["final_scores"], run["iterations_run"],
        X, y, edges, params,
        correct.follow_indices(limits["follow"], win.warmup, win.iterations),
        log=say)["program"]
    numbers["compiles_in_window"] = float(win.compiles_in_window)
    compared = correct.judge(numbers, limits["limits"])
    say("reference and comparison %.1fs" % (time.perf_counter() - t))
    result["correct"] = all(c["ok"] for c in compared.values())
    result["compared"] = {k: {"value": c["value"], "limit": c["limit"]}
                          for k, c in compared.items()}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--dump-trace", metavar="FILE",
                    help="with --trace 1: write the trace's rows, cut down, as JSON")
    ap.add_argument("--list", action="store_true",
                    help="print what the manifest holds and exit")
    args = ap.parse_args(argv)
    man = Manifest()
    if args.list:
        print(json.dumps(man.listing()))
        return 0
    if args.workload is None:
        ap.error("--workload is required")
    cell = man.workload(args.workload)
    seconds = args.seconds if args.seconds is not None else man.data["run_seconds"]
    # a traffic mix may pin one of the program's paths by the variables the
    # program itself reads when it is imported: set before that import
    os.environ.update(man.traffic(cell["traffic"]).get("env", {}))
    need_chips(cell["chips"])
    say("compile cache at %s" % place_cache())
    result = run_cell(man, args.workload, args.seed, seconds, bool(args.trace),
                      dump_trace=args.dump_trace)
    for name, c in result["compared"].items():
        say("compared %s = %.6g (limit %.6g)" % (name, c["value"], c["limit"]))
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
