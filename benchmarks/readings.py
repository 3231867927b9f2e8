"""The readings the limits of ``correct`` are set from, on the chip at the
cell's own size, several seeds in one process (set-up is long):

    python3 -m benchmarks.readings --workload <cell> --seeds 1,2:8,3:9 \\
        [--control-seeds 3] [--fault-seeds 1] --out chiprun_out/readings.json

A seed may name another recipe (``seed:recipe``), that is another table of
the configuration's shape. For each seed: the data, one training through the
window's own call for the warm-up iterations and ``--window-iterations`` more
(training's readings need no measured window), and the program's numbers
against the reference (the lower readings), judged by the cell's limits. On
the first ``--control-seeds`` seeds also the control's numbers, judged alike:
the reference put in the program's place with gradient and hessian rounded to
the traffic mix's ``precision.control`` before the histograms sum them, and
the bins made four times as wide. On the first ``--fault-seeds`` seeds also
one training under each planted fault (``faults.py``). Not part of a
benchmark run.
"""
from __future__ import annotations

import argparse
import contextlib
import gc
import json
import os
import sys
import time

from . import correct, datagen, faults, reference
from .manifest import Manifest
from .run import drive, need_chips, place_cache, say, train_params


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--fault-seeds", type=int, default=1)
    ap.add_argument("--window-seconds", type=float, default=0.0)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    man = Manifest()
    cell = man.workload(args.workload)
    config = man.config(cell["config"])
    traffic = man.traffic(cell["traffic"])
    os.environ.update(traffic.get("env", {}))  # before the program is imported
    need_chips(cell["chips"])
    import lightgbm_tpu as lgb

    place_cache()
    limits = man.limits(args.workload)
    params = train_params(config, traffic)
    records = []
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    for i, spec in enumerate(args.seeds.split(",")):
        seed, _, recipe = spec.partition(":")
        seed = int(seed)
        made = dict(config)
        if recipe:
            made["generator_args"] = dict(config.get("generator_args", {}),
                                          recipe=int(recipe))
        t0 = time.perf_counter()
        X, y = datagen.make(made, seed, man.bench_dir)
        ds = lgb.Dataset(X, label=y, params=params).construct()
        edges = correct.bin_edges(ds, config["features"])
        plans = [None] + (list(faults.FAULTS) if i < args.fault_seeds else [])
        for fault in plans:
            with faults.planted(fault) if fault else contextlib.nullcontext():
                run = drive(lgb, params, ds, traffic, seconds=args.window_seconds)
            gc.collect()
            win = run["win"]
            control = traffic["precision"]["control"] \
                if fault is None and i < args.control_seeds else None
            numbers = correct.compare(
                run["text"], run["warm_scores"], run["final_scores"],
                run["iterations_run"], X, y, edges, params,
                correct.follow_indices(limits["follow"], win.warmup, win.iterations),
                control_dtype=control)
            program = dict(numbers["program"], compiles_in_window=float(win.compiles_in_window))
            judged = correct.judge(program, limits["limits"])
            rec = {"seed": seed, "recipe": made.get("generator_args", {}).get("recipe"),
                   "fault": fault, "program": program,
                   "ok": all(c["ok"] for c in judged.values()),
                   "failed": sorted(k for k, c in judged.items() if not c["ok"]),
                   "iterations": win.iterations,
                   "iter_s": win.window_s / max(win.iterations, 1)}
            if control is not None:
                # the control in the program's place: its own numbers where it
                # makes them, the program's elsewhere
                wide = reference.Follower(X[:20000], y[:20000], correct.coarser(edges), params)
                as_run = dict(program, **numbers["control"], bin_width=wide.bin_width)
                judged = correct.judge(as_run, limits["limits"])
                rec["control"] = dict(numbers["control"], bin_width=wide.bin_width)
                rec["control_ok"] = all(c["ok"] for c in judged.values())
                rec["control_failed"] = sorted(k for k, c in judged.items() if not c["ok"])
            rec["seconds"] = time.perf_counter() - t0
            records.append(rec)
            say(json.dumps(rec))
            with open(args.out, "w", encoding="utf-8") as fh:
                json.dump(records, fh, indent=1)
        del ds, X, y
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
