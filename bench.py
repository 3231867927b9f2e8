"""Benchmark: Higgs-shaped binary classification training throughput.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...}.

Workload mirrors the reference's headline Higgs experiment
(/root/reference/docs/Experiments.rst:103-128): binary objective, 28 features,
255 leaves, 255 bins, lr=0.1 — on 1M synthetic Higgs-like rows (the north-star
"Higgs-1M" size from BASELINE.json; the tabular feature distributions are
synthetic but binning/shape-equivalent).

Baseline: LightGBM CPU trains the real 10.5M-row Higgs at 500 iters / 238.5 s =
2.096 iters/s on 16 Xeon threads (Experiments.rst:103-115). LightGBM histogram
training is linear in rows, so the 1M-row equivalent CPU baseline is
2.096 * 10.5 = 22.0 iters/s. vs_baseline = ours / 22.0 (>1 beats the reference
CPU; the BASELINE.json target is >= 4).

One process, and it needs a TPU: with any other backend it says so and exits
non-zero before any data is made. It starts no child, selects no platform and
reads no earlier record. A phase whose failure is caught (phase breakdown,
roofline, predict, profilers) still prints its ``*_error`` field in the JSON
line, and the run then exits non-zero. A watchdog thread emits a failure line
and hard-exits if the whole run stalls.
"""
from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

BASELINE_ITERS_PER_SEC_1M = 2.096 * 10.5  # LightGBM CPU, scaled to 1M rows

N_ROWS = int(os.environ.get("BENCH_N_ROWS", 1_000_000))
N_FEATURES = 28
NUM_LEAVES = int(os.environ.get("BENCH_NUM_LEAVES", 255))
MAX_BIN = 255
WARMUP_ITERS = 3
BENCH_ITERS = int(os.environ.get("BENCH_ITERS", 30))

METRIC_NAME = "higgs1m_boost_iters_per_sec"
UNIT = "iters/s (binary, 1M x 28, 255 leaves, 255 bins)"


def _emit(value: float, vs_baseline: float, **extra) -> None:
    line = {"metric": METRIC_NAME, "value": value, "unit": UNIT, "vs_baseline": vs_baseline}
    line.update(extra)
    print(json.dumps(line), flush=True)


# shared with helpers/prof_grow.py and chip_smoke.py (helpers/bench_data holds
# the one definition; re-exported here so `from bench import make_higgs_like`
# call sites keep working)
from helpers.bench_data import make_higgs_like  # noqa: E402,F401


def _watchdog(limit_s: float) -> None:
    """Emit the failure JSON line and hard-exit if the bench stalls."""
    import threading

    def fire():
        _emit(0.0, 0.0, error="watchdog fired after %.0fs" % limit_s)
        print("bench watchdog fired after %.0fs - hang?" % limit_s, file=sys.stderr)
        os._exit(2)

    t = threading.Timer(limit_s, fire)
    t.daemon = True
    t.start()


def _run() -> None:
    try:
        # XLA's recursive HLO passes can blow the default 8MB stack on the
        # large grow_tree program (flaky SIGSEGV inside backend_compile)
        import resource

        soft, hard = resource.getrlimit(resource.RLIMIT_STACK)
        if hard == resource.RLIM_INFINITY or hard >= 256 * 1024 * 1024:
            resource.setrlimit(resource.RLIMIT_STACK, (256 * 1024 * 1024, hard))
    except (ImportError, OSError, ValueError):
        # no resource module (non-unix) or a container refusing the raise:
        # the stack bump is a best-effort crash-avoidance, not a requirement
        pass
    # measured cost-analysis harvest (obs/costs.py): ON by default in the
    # bench — the roofline's "measured" tier depends on it, and the
    # persistent compilation cache below absorbs the harvest's second XLA
    # compile. LIGHTGBM_TPU_COSTS=0 opts out.
    os.environ.setdefault("LIGHTGBM_TPU_COSTS", "1")

    import jax

    from lightgbm_tpu.utils.platform import place_compile_cache

    # the grow_tree program is a multi-minute compile (one histogram +
    # partition subprogram per bucket-lattice size); re-runs skip it
    cache_dir = place_compile_cache()
    platform = jax.default_backend()
    if platform != "tpu":
        sys.exit(
            "bench: needs a TPU and jax.default_backend() is %r — a number "
            "from any other backend is not this benchmark's metric. Nothing "
            "was run." % platform
        )
    device_kind = jax.devices()[0].device_kind

    import lightgbm_tpu as lgb
    from lightgbm_tpu.metric import AUCMetric
    from lightgbm_tpu.obs import costs as costs_mod

    print(
        "bench: running on platform=%s devices=%s compile cache %s"
        % (platform, jax.devices(), cache_dir), file=sys.stderr, flush=True,
    )
    failed = []  # phases whose failure was caught; any entry fails the run
    X, y = make_higgs_like(N_ROWS, N_FEATURES)
    print("bench: data ready", file=sys.stderr, flush=True)

    params = {
        "objective": "binary",
        "num_leaves": NUM_LEAVES,
        "max_bin": MAX_BIN,
        "learning_rate": 0.1,
        "metric": "auc",
        "verbosity": -1,
    }
    t0 = time.time()
    ds = lgb.Dataset(X, label=y)
    booster = lgb.Booster(params=params, train_set=ds)
    bin_time = time.time() - t0
    print("bench: binned in %.1fs" % bin_time, file=sys.stderr, flush=True)

    def run_iters(count: int) -> None:
        for _ in range(count):
            booster.update()

    t0 = time.time()
    run_iters(WARMUP_ITERS)
    jax.block_until_ready(booster._gbdt.scores)
    warmup_time = time.time() - t0
    print("bench: warmed up in %.1fs" % warmup_time, file=sys.stderr, flush=True)

    t0 = time.time()
    run_iters(BENCH_ITERS)
    # close the window with block_until_ready AND a value fetch: the loop
    # above is fully async (the per-iteration num_leaves sync is deferred),
    # and chip_smoke.py measured the two to agree on this access path
    jax.block_until_ready(booster._gbdt.scores)
    float(np.asarray(jax.numpy.ravel(booster._gbdt.scores)[0]))
    bench_time = time.time() - t0

    iters_per_sec = BENCH_ITERS / bench_time

    # AUC of the model whose throughput was just measured — BEFORE the phase
    # breakdown below advances the booster by 3 more iterations
    score = booster._gbdt._train_score_np()
    auc_metric = AUCMetric(booster.config)
    auc_metric.init(ds._binned.metadata, ds.num_data())
    auc = auc_metric.eval(score, booster._gbdt.objective)[0][1]

    # ---- phase breakdown + roofline model ---------------------------------
    # Phases from a few extra iterations under the SYNC timer opt-in
    # (utils/timer.py): per phase, `dispatch` is the host wall time spent
    # issuing the work and `seconds` the synced total — their gap is the
    # device-compute share, making dispatch overhead a first-class number.
    # Sync serializes phases, so this runs OUTSIDE the headline timing loop.
    phases = {}
    phases_dispatch = {}
    phases_error = None
    phase_iters = 3
    try:
        gbdt = booster._gbdt
        gbdt.timers.enabled = True
        gbdt.timers.sync = True
        gbdt.timers.seconds.clear()
        gbdt.timers.counts.clear()
        gbdt.timers.dispatch_seconds.clear()
        run_iters(phase_iters)
        # close the async pipeline before reading the timers
        float(np.asarray(jax.numpy.ravel(booster._gbdt.scores)[0]))
        phases = {
            k: round(v / phase_iters, 4) for k, v in gbdt.timers.seconds.items()
        }
        phases_dispatch = {
            k: round(v / phase_iters, 4)
            for k, v in gbdt.timers.dispatch_seconds.items()
        }
        gbdt.timers.enabled = False
        gbdt.timers.sync = False
    except Exception as e:
        # surface the failure in the emitted JSON — the r4 TPU capture lost
        # its phase row silently and the artifact read as "never instrumented"
        phases_error = "%s: %s" % (type(e).__name__, str(e)[:200])
        failed.append("phases")
        print("bench: phase breakdown failed: %s" % e, file=sys.stderr)
    # Roofline: MEASURED flops/bytes from the XLA cost analysis of the very
    # executable the timed loop dispatched (obs/costs.py harvest, keyed by
    # the retrace names) against the per-device_kind peak table (an unknown
    # device_kind is an error there) — falling back to the analytic work
    # model, LABELED, never silently (roofline_source below). The analytic
    # model is always computed too, as the cross-check column: histogram
    # rows = sum over splits of the smaller child (subtraction trick),
    # flops = rows x F x K x 2, bytes = hist rows x (F bins u8 + K f32
    # values) + one partition gather pass.
    mfu_estimate = None
    roofline = {}
    roofline_source = "analytic"
    roofline_error = None
    try:
        peaks = costs_mod.chip_peaks(device_kind, platform=platform)
        peak_flops, peak_bw = peaks["peak_flops"], peaks["peak_bw"]
        roofline_chip = peaks["chip"]
        iter_s = bench_time / BENCH_ITERS
        meas_name = "ops.grow_tree"
        meas = costs_mod.COSTS.get(meas_name)
        if meas and meas.get("flops"):
            meas_flops = float(meas["flops"])
            meas_bytes = float(meas.get("bytes_accessed") or 0.0)
            roofline_source = "measured"
            mfu_estimate = round(meas_flops / iter_s / peak_flops, 6)
            roofline = {
                "measured_executable": meas_name,
                "measured_flops_per_iter": meas_flops,
                "measured_bytes_per_iter": meas_bytes,
                "hbm_utilization": round(meas_bytes / iter_s / peak_bw, 4),
                "roofline_chip": roofline_chip,
            }
        gbdt._materialize()
        trees = [t for t in gbdt.models if t is not None and t.num_leaves > 1]
        if trees:
            t = trees[-1]
            import numpy as _np

            counts = _np.asarray(t.internal_count, _np.float64)
            left, right = _np.asarray(t.left_child), _np.asarray(t.right_child)
            leaf_counts = _np.asarray(t.leaf_count, _np.float64)
            nsplit = t.num_leaves - 1

            def child_count(c):
                return leaf_counts[-(c + 1)] if c < 0 else counts[c]

            small_rows = sum(
                min(child_count(left[i]), child_count(right[i]))
                for i in range(nsplit)
            )
            F, K, Bn = N_FEATURES, 3, MAX_BIN + 1
            hist_flops = small_rows * F * K * 2
            scan_flops = nsplit * 2 * F * Bn * 20  # two-direction cumsum scans
            hist_bytes = small_rows * (F + K * 4) + N_ROWS * (F + 8)
            roofline["hist_small_rows_per_iter"] = int(small_rows)
            roofline["model_flops_per_iter"] = float(hist_flops + scan_flops)
            roofline["model_bytes_per_iter"] = float(hist_bytes)
            roofline.setdefault("roofline_chip", roofline_chip)
            if roofline_source == "analytic":
                mfu_estimate = round(
                    (hist_flops + scan_flops) / iter_s / peak_flops, 6
                )
                roofline["hbm_utilization"] = round(
                    hist_bytes / iter_s / peak_bw, 4
                )
    except Exception as e:
        roofline_error = "%s: %s" % (type(e).__name__, str(e)[:200])
        failed.append("roofline")
        print("bench: roofline model failed: %s" % e, file=sys.stderr)

    # ---- packed-inference serving bench (lightgbm_tpu/serve, ISSUE 3) ----
    # rows/s of the fused single-dispatch predictor at a big batch, plus
    # p50/p99 dispatch latency for mixed 200-1024-row batches through the
    # pow2 bucket cache AFTER warmup — the steady-state serving numbers.
    predict_rec = {}
    try:
        import jax.numpy as jnp

        from lightgbm_tpu.serve.cache import BucketedDispatcher

        t0 = time.time()
        pk = booster.to_packed()
        pack_s = time.time() - t0
        big = min(N_ROWS, 1 << 17)
        xd = jax.device_put(jnp.asarray(X[:big].astype(np.float32)))
        out = pk.fused_scores(xd)
        _ = float(np.asarray(jnp.ravel(out))[0])  # compile + close pipeline
        reps = 5
        t0 = time.time()
        for _ in range(reps):
            out = pk.fused_scores(xd)
        _ = float(np.asarray(jnp.ravel(out))[0])
        pred_rows_per_sec = big * reps / (time.time() - t0)
        disp = BucketedDispatcher(
            lambda x: np.asarray(pk.fused_scores(jnp.asarray(x))), min_rows=256
        )
        for b in (256, 512, 1024):  # warm every bucket the loop can hit
            disp(X[:b].astype(np.float32))
        warm_traces = disp.retraces
        lat = []
        lrng = np.random.RandomState(0)
        for _ in range(40):
            nb = int(lrng.randint(200, 1025))
            t1 = time.time()
            disp(X[:nb].astype(np.float32))
            lat.append(time.time() - t1)
        lat.sort()
        predict_rec = {
            "mode": "fused",
            "pack_s": round(pack_s, 2),
            "rows_per_sec": round(pred_rows_per_sec, 1),
            "throughput_batch_rows": big,
            "p50_ms": round(lat[len(lat) // 2] * 1e3, 3),
            "p99_ms": round(lat[min(int(len(lat) * 0.99), len(lat) - 1)] * 1e3, 3),
            "retraces_after_warmup": disp.retraces - warm_traces,
            "num_trees": pk.num_trees,
        }
    except Exception as e:
        predict_rec = {"error": "%s: %s" % (type(e).__name__, str(e)[:200])}
        failed.append("predict")
        print("bench: predict bench failed: %s" % e, file=sys.stderr)

    # ---- segment profiler: named device time inside tree growth ----------
    # (obs/prof.py): fused + segmented growth on identical inputs, fenced
    # per-segment timings, and a bitwise-identity proof of the segmented
    # model. BENCH_PROF=0 skips; runs only when >=300s of the watchdog
    # budget remain (it compiles a second grower program).
    growth_prof = None
    if os.environ.get("BENCH_PROF", "1") not in ("", "0"):
        try:
            from lightgbm_tpu.obs import prof as prof_mod

            remaining = float(os.environ.get("BENCH_TIMEOUT_S", 2400)) - (
                time.time() - _WATCHDOG_T0
            )
            reason = prof_mod.unsupported_reason(booster._gbdt)
            if remaining < 300:
                growth_prof = {
                    "skipped": "tight budget (%.0fs left)" % remaining
                }
            elif reason is not None:
                growth_prof = {"skipped": reason}
            else:
                growth_prof = prof_mod.profile_growth(
                    booster, iters=int(os.environ.get("BENCH_PROF_ITERS", "2"))
                )
            print(
                "bench: growth segments -> %s" % json.dumps(growth_prof),
                file=sys.stderr, flush=True,
            )
        except Exception as e:
            growth_prof = {"error": "%s: %s" % (type(e).__name__, str(e)[:200])}
            failed.append("growth_prof")
            print("bench: segment profiler failed: %s" % e, file=sys.stderr)

    # ---- device-timeline audit (obs/devprof.py, ISSUE 14) ----------------
    # a short profiled window of already-compiled iterations, parsed into
    # op-level attribution + the host/device/transfer-bound verdict —
    # device_busy_fraction and transfer_seconds land in the record (and
    # bench_diff WARNs on their drift). BENCH_DEVPROF=0 skips; the capture
    # is a temp dir, never the operator's LIGHTGBM_TPU_PROFILE target.
    devprof_rec = None
    if os.environ.get("BENCH_DEVPROF", "1") not in ("", "0"):
        try:
            import tempfile

            from lightgbm_tpu.obs import devprof as devprof_mod

            remaining = float(os.environ.get("BENCH_TIMEOUT_S", 2400)) - (
                time.time() - _WATCHDOG_T0
            )
            if remaining < 120:
                devprof_rec = {
                    "skipped": "tight budget (%.0fs left)" % remaining
                }
            else:
                dp_iters = int(os.environ.get("BENCH_DEVPROF_ITERS", "3"))
                with tempfile.TemporaryDirectory(
                    prefix="lgbtpu_devprof_"
                ) as td:
                    with devprof_mod.capture(td):
                        run_iters(dp_iters)
                        float(np.asarray(
                            jax.numpy.ravel(booster._gbdt.scores)[0]))
                    devprof_rec = devprof_mod.analyze_dir(
                        td, device_kind=device_kind, platform=platform,
                        iters=dp_iters,
                    )
                devprof_mod.publish(devprof_rec)
                print(
                    "bench: devprof verdict -> %s"
                    % json.dumps(devprof_rec.get("verdict")),
                    file=sys.stderr, flush=True,
                )
        except Exception as e:
            devprof_rec = {"error": "%s: %s" % (type(e).__name__,
                                                str(e)[:200])}
            failed.append("devprof")
            print("bench: devprof failed: %s" % e, file=sys.stderr)

    extra = {"platform": platform, "device_kind": device_kind,
             "train_auc": round(float(auc), 6)}
    extra["n_devices"] = len(jax.devices())
    extra["tree_learner"] = params.get("tree_learner", "serial")
    # histogram routing provenance (ISSUE 13): bench_diff WARNs (never
    # FAILs) when two records were measured under different routing — a
    # tune-table flip must read as a routing change, not a code regression
    from lightgbm_tpu.ops import histogram as _hist_mod

    _route = getattr(booster._gbdt, "_hist_route", None)
    extra["hist_routing"] = {
        "impl_default": _hist_mod.default_impl(),
        "env_impl": _hist_mod._ENV_IMPL or None,
        "tune_digest": _route.digest if _route is not None else None,
        "tune_source": (
            os.path.basename(_route.source)
            if _route is not None and _route.source else None
        ),
    }
    if predict_rec:
        extra["predict"] = predict_rec
    # the shared structured run report (obs/registry.py): phase gauges, jit
    # trace counts, bucket retraces, device-memory gauges
    from lightgbm_tpu.obs import REGISTRY as _obs_registry
    from lightgbm_tpu.obs import memwatch as _memwatch
    from lightgbm_tpu.obs import podwatch as _podwatch

    booster._gbdt.timers.publish()
    snap = _memwatch.snapshot("post_bench")
    extra["obs_report"] = _obs_registry.run_report()
    extra["memwatch"] = {
        k: v for k, v in snap.items() if k not in ("devices", "t")
    }
    extra["memwatch"]["attribution"] = _memwatch.attribute_training(
        booster._gbdt
    )
    # fleet-telemetry stamp (obs/podwatch.py): when this bench ran with
    # LIGHTGBM_TPU_TELEMETRY armed, fold the pod view + verdicts into the
    # record so bench_diff can WARN on straggler/skew drift across rounds
    _tdir = _podwatch.env_dir()
    if _tdir:
        extra["podwatch"] = _podwatch.pod_summary(_tdir)
    if phases:
        extra["phases_s"] = phases
        extra["phases_dispatch_s"] = phases_dispatch
    elif phases_error:
        extra["phases_error"] = phases_error
    # provenance stamp: downstream BENCH_r*.json comparisons (bench_diff)
    # must know whether mfu/bytes came from XLA cost analysis or the model
    extra["roofline_source"] = roofline_source
    if roofline_error:
        extra["roofline_error"] = roofline_error
    if mfu_estimate is not None:
        extra["mfu_estimate"] = mfu_estimate
        extra.update(roofline)
    if growth_prof:
        extra["growth_prof"] = growth_prof
        if growth_prof.get("segments_per_tree_s"):
            extra["growth_segments_s"] = growth_prof["segments_per_tree_s"]
    if devprof_rec:
        extra["device_timeline"] = devprof_rec
        # headline fields bench_diff's WARN row reads (never a FAIL:
        # busy-fraction drift is a diagnosis pointer, not a regression)
        if devprof_rec.get("device_busy_fraction") is not None:
            extra["device_busy_fraction"] = devprof_rec[
                "device_busy_fraction"]
        tr_total = (devprof_rec.get("transfers") or {}).get("total_seconds")
        if tr_total is not None:
            extra["transfer_seconds"] = tr_total
    book = costs_mod.COSTS.report()
    if book:
        extra["cost_analysis"] = book
    if failed:
        extra["failed_phases"] = failed
    _emit(
        round(iters_per_sec, 4),
        round(iters_per_sec / BASELINE_ITERS_PER_SEC_1M, 4),
        **extra,
    )
    print(
        "bench detail: platform=%s rows=%d bin=%.1fs warmup(%d)=%.1fs bench(%d)=%.1fs train-AUC=%.5f"
        % (platform, N_ROWS, bin_time, WARMUP_ITERS, warmup_time, BENCH_ITERS, bench_time, auc),
        file=sys.stderr,
    )
    if failed:
        sys.exit("bench: phase(s) failed: %s" % ", ".join(failed))


_WATCHDOG_T0 = time.time()  # updated in main() when the watchdog arms


def main() -> None:
    global _WATCHDOG_T0
    _WATCHDOG_T0 = time.time()
    _watchdog(float(os.environ.get("BENCH_TIMEOUT_S", 2400)))
    try:
        _run()
    except Exception as e:  # the driver still gets its one JSON line
        import traceback

        traceback.print_exc()
        _emit(0.0, 0.0, error="%s: %s" % (type(e).__name__, str(e)[:300]))
        sys.exit(1)


if __name__ == "__main__":
    main()
