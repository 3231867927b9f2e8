"""Device-count scaling bench for the data-parallel sharded-chunk path.

Sweeps ``tree_learner=data + device_chunk_size`` over a list of device
counts and records a devices-vs-iters/s scaling curve — the ISSUE-8 proof
artifact for pod-scale data-parallel training (ROADMAP item 1: the paper's
Higgs-1M-on-v5e-8 target is a scaling claim, so the scaling curve is the
headline evidence). Two modes:

  * ``--sweep 1,4,8``: each device count needs its own process (the jax
    device world is fixed at backend init), so the sweep — which never
    touches jax itself — re-execs this file once per count, one child at a
    time, and emits ONE summary JSON line
    (``RESULT {...}``) whose record carries a ``metric`` key — the shape
    obs/report.load_bench_records adopts, so MULTICHIP_r*.json charts next
    to the BENCH_r* series in the HTML run report.
  * ``--devices D``: one measurement on the real devices, the mesh capped
    with ``num_machines=D``; fewer than D devices is an error. Only an
    explicit ``JAX_PLATFORMS=cpu`` gets D virtual CPU devices (a dry run,
    whose rates are not device numbers).

Stays importable without jax until a single-measurement run starts.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def measure(devices: int, rows: int, iters: int, chunk: int, leaves: int) -> dict:
    sys.path.insert(0, REPO)
    from lightgbm_tpu.utils.platform import ensure_virtual_devices

    # the real devices — too few is an error — unless JAX_PLATFORMS=cpu
    # asks for a virtual-device dry run by name
    jax = ensure_virtual_devices(devices)
    import numpy as np

    import lightgbm_tpu as lgb
    from helpers.bench_data import make_higgs_like
    from lightgbm_tpu.models.model_text import model_fingerprint

    X, y = make_higgs_like(rows, 28)
    params = {
        "objective": "binary", "num_leaves": leaves, "max_bin": 255,
        "learning_rate": 0.1, "verbosity": -1,
        "tree_learner": "data" if devices > 1 else "serial",
        "num_machines": devices, "device_chunk_size": chunk,
    }
    ds = lgb.Dataset(X, label=y)
    bst = lgb.Booster(params=params, train_set=ds)

    def run(count: int) -> None:
        i = 0
        while i < count:
            if chunk > 1:
                done, _ = bst.update_chunk(min(chunk, count - i))
                i += max(done, 1)
            else:
                bst.update()
                i += 1

    # warmup compiles both programs the timed loop uses: the sequential
    # first iteration and the full chunk-sized scan
    t0 = time.time()
    run(chunk + 1)
    _ = float(np.ravel(np.asarray(bst._gbdt.scores))[0])
    compile_s = time.time() - t0
    t0 = time.time()
    run(iters)
    _ = float(np.ravel(np.asarray(bst._gbdt.scores))[0])
    dt = time.time() - t0
    rec = {
        "devices": devices,
        "iters_per_sec": round(iters / dt, 4),
        "first_dispatch_s": round(compile_s, 2),
        "model_hash": model_fingerprint(bst.model_to_string()),
        "platform": jax.default_backend(),
        "fallback_reason": bst._gbdt.device_chunk_fallback_reason(),
    }
    if devices > 1:
        # compute-vs-collective attribution (obs/dist.py): the segmented
        # sharded profile says WHY scaling bends — comms_fraction,
        # per-segment seconds, per-device rows/waits; its bitwise check
        # re-proves the fused program was measured, not a lookalike.
        # Never fatal to the bench measurement itself.
        try:
            from lightgbm_tpu.obs import dist as dist_mod

            prof = dist_mod.profile_sharded_growth(bst, iters=1)
            rec["comms_fraction"] = prof["comms_fraction"]
            rec["dist_segments"] = prof["segments_per_tree_s"]
            rec["dist_collective"] = prof["collective_segments"]
            rec["collective_bytes_per_split"] = prof[
                "collective_bytes_per_split"
            ]
            rec["per_device"] = prof["per_device"]
            rec["dist_bitwise"] = prof["bitwise_identical"]
        except Exception as e:
            rec["dist_prof_error"] = repr(e)[:200]
    return rec


def sweep(counts, rows, iters, chunk, leaves) -> dict:
    points = []
    for d in counts:
        env = dict(os.environ)
        if env.get("LIGHTGBM_TPU_TRACE"):
            # per-worker trace files: the sweep's children inherit one env
            # path and would clobber each other at exit; the driver merges
            # them back with `python -m lightgbm_tpu.obs.trace merge`
            env["LIGHTGBM_TPU_TRACE"] = "%s.dev%d" % (
                env["LIGHTGBM_TPU_TRACE"], d,
            )
        # a fresh process per device count: the jax device world is fixed
        # at backend init, so the sweep cannot reconfigure in-process
        out = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--devices", str(d),
             "--rows", str(rows), "--iters", str(iters), "--chunk",
             str(chunk), "--leaves", str(leaves)],
            env=env, capture_output=True, text=True, cwd=REPO,
        )
        rec = None
        for line in (out.stdout or "").splitlines():
            if line.startswith("RESULT "):
                rec = json.loads(line[len("RESULT "):])
        if rec is None:
            rec = {"devices": d, "error": (out.stderr or "")[-400:],
                   "rc": out.returncode}
        points.append(rec)
        print("multichip: devices=%s -> %s" % (d, rec), file=sys.stderr,
              flush=True)
    good = [p for p in points if p.get("iters_per_sec")]
    base = next((p for p in good if p["devices"] == 1), None)
    summary = {
        "metric": "higgs_multichip_iters_per_sec",
        "unit": "iters/s",
        "value": good[-1]["iters_per_sec"] if good else 0.0,
        "rows": rows, "iters": iters, "chunk": chunk, "leaves": leaves,
        "scaling": points,
        "platform": good[-1].get("platform") if good else "unknown",
        "ok": bool(good),
    }
    if base and len(good) > 1:
        summary["speedup_vs_1dev"] = round(
            good[-1]["iters_per_sec"] / base["iters_per_sec"], 3
        )
        # scaling efficiency vs the sweep's OWN n=1 point: measured
        # iters/s over the ideal linear D x base — the MULTICHIP series'
        # regression signal (helpers/bench_diff.py WARNs on drops)
        eff = [
            [p["devices"],
             round(p["iters_per_sec"]
                   / (p["devices"] * base["iters_per_sec"]), 4)]
            for p in sorted(good, key=lambda p: p["devices"])
        ]
        summary["efficiency_by_devices"] = eff
        summary["scaling_efficiency"] = eff[-1][1]
    # adopt the attribution block of the widest profiled point so the
    # MULTICHIP record itself says why scaling bends (obs/dist.py)
    profiled = [p for p in good if p.get("comms_fraction") is not None]
    if profiled:
        top = profiled[-1]
        for key in ("comms_fraction", "dist_segments", "dist_collective",
                    "collective_bytes_per_split", "per_device",
                    "dist_bitwise"):
            if key in top:
                summary[key] = top[key]
    return summary


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--devices", type=int, default=0)
    ap.add_argument("--sweep", type=str, default="")
    ap.add_argument("--rows", type=int, default=0)
    ap.add_argument("--iters", type=int, default=0)
    ap.add_argument("--chunk", type=int, default=0)
    ap.add_argument("--leaves", type=int, default=0)
    args = ap.parse_args()
    on_chip = os.environ.get("JAX_PLATFORMS") != "cpu"
    rows = args.rows or (1_000_000 if on_chip else 20_000)
    iters = args.iters or (16 if on_chip else 8)
    chunk = args.chunk or (16 if on_chip else 4)
    leaves = args.leaves or (255 if on_chip else 31)
    if args.sweep:
        counts = [int(x) for x in args.sweep.split(",") if x]
        summary = sweep(counts, rows, iters, chunk, leaves)
        print(json.dumps(summary), flush=True)
        return 0 if summary.get("ok") else 1
    rec = measure(max(args.devices, 1), rows, iters, chunk, leaves)
    print("RESULT " + json.dumps(rec), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
