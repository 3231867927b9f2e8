"""Observability smoke: traced mini-train + serve, then validate the outputs.

What `helpers/check.sh --obs` runs. In-process, on CPU:

  1. trains a tiny booster with ``LIGHTGBM_TPU_TRACE`` pointed at a temp
     file, runs one packed-serving predict through a ServeApp, and stops
     the tracer;
  2. validates the emitted Chrome-trace JSON structurally — pid/tid/ph/ts
     on every event, >= 3 distinct training-phase spans, >= 1 serve request
     span, and phase spans time-nested inside an iteration span;
  3. validates the Prometheus exposition: parses every sample line, and
     requires latency quantiles, qps, the jit-trace gauges and the
     device-memory gauge to be present;
  4. checks memwatch shape math against the actual donated hist buffer.

``--drift`` (what `helpers/check.sh --drift` runs) validates the MODEL/data
observability tier (docs/Observability.md §Model & data observability):
a flight-recorded train whose JSONL schema must parse (manifest + one
record per boundary + one per tree), a drift-monitored serve where
covariate-shifted traffic must drive PSI above threshold (alert counter
fires) while in-distribution traffic stays below, and an HTML run report
that must render non-empty with learning-curve/importance SVG charts.

Exit 0 on success with an OK line; any failure raises (nonzero exit).
"""
from __future__ import annotations

import json
import os
import re
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

_PROM_LINE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^}]*\})? [0-9eE\+\-\.]+$"
)


def main() -> int:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    trace_path = os.path.join(tempfile.mkdtemp(prefix="lgbtpu_obs_"), "trace.json")
    os.environ["LIGHTGBM_TPU_TRACE"] = trace_path

    import numpy as np

    import lightgbm_tpu as lgb
    from lightgbm_tpu.obs import memwatch, trace
    from lightgbm_tpu.serve.server import ServeApp

    rng = np.random.RandomState(0)
    X = rng.randn(500, 4)
    y = (X[:, 0] > 0).astype(float)
    bst = lgb.train(
        {"objective": "binary", "num_leaves": 7, "verbosity": -1},
        lgb.Dataset(X, label=y), num_boost_round=3,
    )

    model_path = os.path.join(os.path.dirname(trace_path), "m.txt")
    bst.save_model(model_path)
    app = ServeApp(max_delay_ms=1.0, min_bucket_rows=8)
    app.registry.load("m", model_path)
    out, _ = app.predict(rng.randn(5, 4))
    assert out.shape[0] == 5

    # --- trace structure ---------------------------------------------------
    path = trace.stop()
    assert path == trace_path, (path, trace_path)
    doc = json.load(open(path))
    events = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
    assert events, "no complete events in the trace"
    for e in events:
        for field in ("pid", "tid", "ph", "ts", "dur", "name"):
            assert field in e, (field, e)
    names = {e["name"] for e in events}
    phases = names & {
        "boosting(grad)", "bagging", "tree growth", "renew+score update",
    }
    assert len(phases) >= 3, "phase spans missing: %s" % sorted(names)
    assert "train.iteration" in names
    assert "serve.request" in names, sorted(names)
    # nesting: some phase span lies inside an iteration span on one thread
    iters = [e for e in events if e["name"] == "train.iteration"]
    nested = any(
        it["ts"] <= e["ts"] and e["ts"] + e["dur"] <= it["ts"] + it["dur"]
        and e["tid"] == it["tid"]
        for it in iters
        for e in events
        if e["name"] in phases
    )
    assert nested, "no phase span nests inside an iteration span"

    # --- prometheus exposition --------------------------------------------
    text = app.prometheus_metrics()
    app.close()
    for line in text.strip().splitlines():
        if line.startswith("#") or not line:
            continue
        assert _PROM_LINE.match(line), "bad exposition line: %r" % line
    for needle in (
        'lgbtpu_request_latency_seconds{quantile="0.5"}',
        "lgbtpu_qps",
        "lgbtpu_jit_traces_total",
        "lgbtpu_device_peak_bytes",
        "lgbtpu_requests_total",
    ):
        assert needle in text, "missing %r in /metrics exposition" % needle

    # --- memwatch shape math ----------------------------------------------
    attr = memwatch.attribute_training(bst._gbdt)
    hist = bst._gbdt._hist_buf
    assert hist is not None and attr["hist_carry"]["bytes"] == hist.nbytes

    print("obs smoke OK: %d trace events, phases=%s" % (
        len(events), sorted(phases),
    ))
    return 0


def drift_main() -> int:
    """Model/data observability smoke (check.sh --drift): flight JSONL
    schema, drift PSI separation (shifted vs in-distribution traffic),
    non-empty HTML run report."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    work = tempfile.mkdtemp(prefix="lgbtpu_drift_")
    flight_path = os.path.join(work, "run.jsonl")
    os.environ["LIGHTGBM_TPU_FLIGHT"] = flight_path

    import numpy as np

    import lightgbm_tpu as lgb
    from lightgbm_tpu.obs import REGISTRY, flight, report
    from lightgbm_tpu.serve.server import ServeApp

    rng = np.random.RandomState(7)
    n, f, rounds = 3000, 6, 8
    X = rng.randn(n, f)
    y = (X[:, 0] + 0.5 * X[:, 1] > 0).astype(float)
    bst = lgb.train(
        {"objective": "binary", "num_leaves": 15, "verbosity": -1},
        lgb.Dataset(X, label=y), num_boost_round=rounds,
        valid_sets=[lgb.Dataset(X[:500], label=y[:500])],
        verbose_eval=False,
    )
    os.environ.pop("LIGHTGBM_TPU_FLIGHT")

    # --- flight JSONL schema ----------------------------------------------
    rec = flight.load(flight_path)
    man = rec["manifest"]
    for key in ("config_digest", "num_data", "num_features", "label_digest",
                "num_boost_round", "backend"):
        assert man.get(key) not in (None, ""), (key, man)
    assert man["num_data"] == n and man["num_boost_round"] == rounds
    assert len(rec["iterations"]) == rounds, len(rec["iterations"])
    for it in rec["iterations"]:
        assert "iteration" in it and "evals" in it and it["evals"], it
    assert len(rec["trees"]) == bst.num_trees(), (
        len(rec["trees"]), bst.num_trees(),
    )
    for t in rec["trees"]:
        for key in ("num_leaves", "max_depth", "total_gain", "max_gain"):
            assert key in t, (key, t)
    assert rec["end"] and rec["end"]["num_trees"] == bst.num_trees()

    # --- drift separation: shifted traffic alerts, in-dist does not -------
    model_path = os.path.join(work, "m.txt")
    os.environ["LIGHTGBM_TPU_DRIFT_SIDECAR"] = "1"
    bst.save_model(model_path)
    os.environ.pop("LIGHTGBM_TPU_DRIFT_SIDECAR")
    assert os.path.exists(model_path + ".drift.json"), "sidecar missing"

    app = ServeApp(max_delay_ms=1.0, min_bucket_rows=8, drift=True)
    try:
        app.registry.load("m", model_path)
        X_in = np.random.RandomState(8).randn(1500, f)
        app.predict(X_in)
        snap = app.drift_snapshot()["models"]["m"]
        assert snap["source"] == "sidecar", snap["source"]
        in_psis = [
            v["psi"] for v in snap["features"].values()
            if v.get("psi") is not None
        ]
        assert in_psis and max(in_psis) < snap["threshold"], (
            "in-distribution traffic drifted: %s" % in_psis
        )
        assert not snap["alerts"], snap["alerts"]

        X_shift = np.random.RandomState(9).randn(1500, f) + np.r_[
            3.0, 3.0, np.zeros(f - 2)
        ]
        app.predict(X_shift)
        snap = app.drift_snapshot()["models"]["m"]
        alert_psis = [
            v["psi"] for v in snap["features"].values() if v.get("alert")
        ]
        assert alert_psis and max(alert_psis) > snap["threshold"], snap
        assert snap["alerts"], "alert list empty after shifted traffic"
        alerts = app.metrics.registry.counter("serve_drift_alerts").values()
        assert sum(alerts.values()) >= 1, alerts
        prom = app.prometheus_metrics()
        assert "lgbtpu_serve_drift_psi" in prom
        assert "lgbtpu_serve_drift_alerts_total" in prom
        drift_snapshot = app.drift_snapshot()
    finally:
        app.close()

    # --- HTML run report ---------------------------------------------------
    html = report.render(
        flight=rec, metrics={"obs_report": REGISTRY.run_report()},
        drift=drift_snapshot,
    )
    out = os.path.join(work, "report.html")
    with open(out, "w", encoding="utf-8") as fh:
        fh.write(html)
    assert len(html) > 2000, len(html)
    for needle in ("<svg", "Learning curves", "Run manifest",
                   "Serve drift", "ALERT"):
        assert needle in html, "report missing %r" % needle

    print(
        "drift smoke OK: flight %d iters / %d trees, in-dist psi<thr, "
        "shifted alerts=%s, report %d bytes (%s)"
        % (len(rec["iterations"]), len(rec["trees"]),
           snap["alerts"], len(html), out)
    )
    return 0


if __name__ == "__main__":
    if "--drift" in sys.argv[1:]:
        sys.exit(drift_main())
    sys.exit(main())
