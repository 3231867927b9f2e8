"""Device-timeline smoke: capture -> parse -> verdict in ONE invocation.

Wired as ``helpers/check.sh --devprof``. What it proves, end to end, on whatever backend is present:

 1. a scoped ``devprof.capture()`` window around real (already-compiled)
    boosting iterations emits a parseable XLA profile;
 2. the stdlib parser reconstructs a NON-EMPTY timeline with lanes
    (``/device:`` lanes on TPU; the documented host-executor proxy on
    CPU) and attributes device self-time to named TraceAnnotation
    segments — a majority of it, since the capture runs with the obs
    tracer live;
 3. the bound-ness verdict comes back with its evidence numbers;
 4. ``devprof_*`` gauges land in the one MetricsRegistry, the
    ``device_timeline`` section lands in run_report(), and obs/report.py
    renders the section into HTML.

Exit 0 and a final compact JSON line on success; exit 1 with the reason
otherwise.
"""
import json
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

ROWS = int(os.environ.get("DEVPROF_SMOKE_ROWS", 6000))
ITERS = int(os.environ.get("DEVPROF_SMOKE_ITERS", 4))


def fail(msg):
    print("devprof_smoke: FAIL: %s" % msg, file=sys.stderr)
    print(json.dumps({"ok": False, "error": msg[:300]}), flush=True)
    sys.exit(1)


def main():
    import numpy as np

    import jax
    import lightgbm_tpu as lgb
    from lightgbm_tpu.obs import REGISTRY
    from lightgbm_tpu.obs import devprof
    from lightgbm_tpu.obs import report as report_mod

    rng = np.random.RandomState(11)
    X = rng.rand(ROWS, 10).astype(np.float32)
    y = (X[:, 0] + 0.3 * rng.rand(ROWS) > 0.65).astype(np.float32)
    params = {"objective": "binary", "num_leaves": 15, "max_bin": 63,
              "learning_rate": 0.1, "verbosity": -1}
    booster = lgb.Booster(params=params,
                          train_set=lgb.Dataset(X, label=y))
    for _ in range(2):  # compile outside the window
        booster.update()
    jax.block_until_ready(booster._gbdt.scores)

    with tempfile.TemporaryDirectory(prefix="lgbtpu_devprof_smoke_") as td:
        cap_dir = os.path.join(td, "profile")
        with devprof.capture(cap_dir) as target:
            for _ in range(ITERS):
                booster.update()
            jax.block_until_ready(booster._gbdt.scores)
        files = devprof.find_trace_files(target)
        if not files:
            fail("capture emitted no trace files under %s" % target)
        try:
            kind = jax.devices()[0].device_kind
        except Exception:
            kind = None
        rec = devprof.analyze_dir(target, device_kind=kind,
                                  platform=jax.default_backend(),
                                  iters=ITERS)

    # -- a real, non-empty timeline ---------------------------------------
    if not rec.get("events"):
        fail("parsed timeline is empty")
    if rec.get("lanes_source") not in ("device", "host_executor"):
        fail("no usable lanes (lanes_source=%r)" % rec.get("lanes_source"))
    segs = rec.get("segments") or {}
    named = {k: v for k, v in segs.items() if k != "unattributed"}
    if not named:
        fail("attribution produced no named segments (segments=%r)"
             % sorted(segs))
    verdict = (rec.get("verdict") or {})
    if verdict.get("bound") not in ("host-bound", "device-bound",
                                    "transfer-bound"):
        fail("no bound-ness verdict (%r)" % verdict)
    if not verdict.get("evidence"):
        fail("verdict carries no evidence block")
    if not rec.get("top_ops"):
        fail("no top-op attribution rows")

    # -- publication: gauges + run-report section + HTML page -------------
    devprof.publish(rec)
    rr = REGISTRY.run_report()
    if "devprof_device_busy_fraction" not in (rr.get("gauges") or {}):
        fail("devprof gauges missing from the registry")
    if "device_timeline" not in rr:
        fail("device_timeline section missing from run_report()")
    html = report_mod.render(metrics=rr, title="devprof smoke")
    if "Device timeline" not in html:
        fail("report.py did not render the Device timeline section")

    out = {
        "ok": True,
        "verdict": verdict.get("bound"),
        "device_busy_fraction": rec.get("device_busy_fraction"),
        "transfer_seconds": (rec.get("transfers") or {}).get(
            "total_seconds"),
        "attributed_fraction": rec.get("attributed_fraction"),
        "lanes_source": rec.get("lanes_source"),
        "events": rec.get("events"),
        "top_segment": next(iter(named), None),
        "report_bytes": len(html),
    }
    print("devprof_smoke: PASS — verdict=%s busy=%.3f attributed=%.0f%%"
          % (out["verdict"], out["device_busy_fraction"] or 0.0,
             100 * (out["attributed_fraction"] or 0.0)), file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
