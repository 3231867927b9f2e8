"""Serve-time feature-drift monitor (serve/drift.py, docs/Serving.md).

Acceptance criteria covered here:
  * the monitor is DISCRIMINATIVE: covariate-shifted traffic drives
    serve_drift_psi above threshold (warn + counter fire) while
    in-distribution traffic stays below;
  * drift is a no-op when disabled (default), and adds ZERO jit traces
    when enabled (host-side bincounts only) — watchdog-verified;
  * the training sidecar round-trips and is fingerprint-checked; without
    it the monitor self-calibrates on the first served rows;
  * /drift and /metrics surface the state over real HTTP.
"""
import http.client
import json
import threading

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.obs import retrace
from lightgbm_tpu.serve import drift as drift_mod
from lightgbm_tpu.serve.server import ServeApp, make_server
from lightgbm_tpu.utils import log

N_FEAT = 5


def _train_model(tmp_path, sidecar=True, seed=5):
    rng = np.random.RandomState(seed)
    X = rng.randn(2000, N_FEAT)
    y = (X[:, 0] + 0.5 * X[:, 1] > 0).astype(float)
    bst = lgb.train(
        {"objective": "binary", "num_leaves": 15, "verbosity": -1},
        lgb.Dataset(X, label=y), 6,
    )
    path = str(tmp_path / "m.txt")
    bst.save_model(path)
    if sidecar:
        assert bst.save_drift_reference(path) == path + ".drift.json"
    return bst, path


def _rows(seed, n=1200, shift=0.0):
    X = np.random.RandomState(seed).randn(n, N_FEAT)
    if shift:
        X[:, 0] += shift
        X[:, 1] += shift
    return X


# ---------------------------------------------------------------------------
# scoring primitives
# ---------------------------------------------------------------------------

def test_psi_zero_for_identical_large_for_disjoint():
    a = np.array([100, 200, 300, 50], np.int64)
    assert drift_mod.psi(a, a) == pytest.approx(0.0, abs=1e-9)
    b = np.array([0, 0, 0, 650], np.int64)
    assert drift_mod.psi(a, b) > 1.0


def test_drift_edges_strip_zero_sentinels():
    from lightgbm_tpu.models.tree import K_ZERO_THRESHOLD

    bounds = np.array(
        [-1.5, -K_ZERO_THRESHOLD, K_ZERO_THRESHOLD, 0.7], np.float64
    )
    de = drift_mod.drift_edges(bounds)
    assert de.tolist() == [-1.5, 0.7]
    cmap = drift_mod.code_to_drift_bin(bounds)
    # lattice cells: (-inf,-1.5] (-1.5,-eps] (-eps,eps] (eps,0.7] (0.7,inf)
    # fold into:     (-inf,-1.5] (-1.5,0.7] x3              (0.7,inf)
    assert cmap.tolist() == [0, 1, 1, 1, 2]


# ---------------------------------------------------------------------------
# monitor behavior through the app
# ---------------------------------------------------------------------------

def test_drift_separates_shifted_from_in_distribution(tmp_path):
    _, path = _train_model(tmp_path)
    app = ServeApp(max_delay_ms=1.0, min_bucket_rows=8, drift=True)
    try:
        app.registry.load("m", path)
        app.predict(_rows(seed=21))
        snap = app.drift_snapshot()["models"]["m"]
        assert snap["source"] == "sidecar"
        in_psis = [
            v["psi"] for v in snap["features"].values()
            if v.get("psi") is not None
        ]
        assert in_psis, "no tracked features scored"
        assert max(in_psis) < snap["threshold"], in_psis
        assert not snap["alerts"]

        app.predict(_rows(seed=22, shift=3.0))
        snap = app.drift_snapshot()["models"]["m"]
        assert snap["alerts"], snap
        alerted = [
            v for v in snap["features"].values() if v.get("alert")
        ]
        assert alerted and max(a["psi"] for a in alerted) > snap["threshold"]
        counts = app.metrics.registry.counter("serve_drift_alerts").values()
        assert sum(counts.values()) == len(snap["alerts"])
        # alerts mirror into the PROCESS-WIDE registry too: that is the
        # report bringup artifacts embed — without the mirror a reader of
        # it could never see an alert
        from lightgbm_tpu.obs import REGISTRY as global_reg

        gcounts = global_reg.counter("serve_drift_alerts").values()
        for key in counts:
            assert gcounts.get(key, 0) >= counts[key], (key, gcounts)
        prom = app.prometheus_metrics()
        assert "lgbtpu_serve_drift_psi" in prom
        assert "lgbtpu_serve_drift_alerts_total" in prom
    finally:
        app.close()
        log.reset_warn_once()


def test_drift_fused_path_accumulates(tmp_path):
    _, path = _train_model(tmp_path)
    app = ServeApp(max_delay_ms=1.0, min_bucket_rows=8, drift=True)
    try:
        app.registry.load("m", path)
        app.predict(_rows(seed=23, n=64), fused=True)
        snap = app.drift_snapshot()["models"]["m"]
        assert snap["rows"] == 64
    finally:
        app.close()


def test_drift_disabled_by_default(tmp_path):
    _, path = _train_model(tmp_path, sidecar=False)
    app = ServeApp(max_delay_ms=1.0, min_bucket_rows=8)
    try:
        app.registry.load("m", path)
        app.predict(_rows(seed=24, n=16))
        snap = app.drift_snapshot()
        assert snap["enabled"] is False and snap["models"] == {}
        assert app.registry.get("m").drift is None
    finally:
        app.close()


def test_drift_self_calibration_without_sidecar(tmp_path):
    _, path = _train_model(tmp_path, sidecar=False)
    app = ServeApp(max_delay_ms=1.0, min_bucket_rows=8, drift=True)
    try:
        app.registry.load("m", path)
        m = app.registry.get("m").drift
        assert m is not None and m.source == "self"
        # calibration window: the first rows become the baseline
        app.predict(_rows(seed=25, n=drift_mod.DEFAULT_CALIBRATION_ROWS))
        snap = app.drift_snapshot()["models"]["m"]
        assert snap["calibrating"] is False
        app.predict(_rows(seed=26, shift=3.0))
        snap = app.drift_snapshot()["models"]["m"]
        assert snap["alerts"], snap
    finally:
        app.close()
        log.reset_warn_once()


def test_drift_zero_new_traces_when_enabled(tmp_path):
    """Acceptance: drift monitoring must never compile anything — warmed
    serve traffic with drift on stays retrace-free under the armed
    watchdog."""
    _, path = _train_model(tmp_path)
    app = ServeApp(max_delay_ms=1.0, min_bucket_rows=8, drift=True)
    try:
        app.registry.load("m", path)
        app.predict(_rows(seed=27))  # warms the row bucket
        retrace.arm()
        app.predict(_rows(seed=28, shift=3.0))  # same shape, shifted values
        assert retrace.retraces_after_warmup() == {}
    finally:
        retrace.disarm()
        app.close()
        log.reset_warn_once()


# ---------------------------------------------------------------------------
# sidecar IO
# ---------------------------------------------------------------------------

def test_sidecar_fingerprint_mismatch_ignored(tmp_path):
    bst, path = _train_model(tmp_path)
    ens = bst.to_packed()
    good = drift_mod.load_sidecar(path, ens.fingerprint, ens.feat_bounds)
    assert good is not None and any(c is not None for c in good)
    assert drift_mod.load_sidecar(path, "not-the-model", ens.feat_bounds) is None


def test_sidecar_reference_counts_cover_all_rows(tmp_path):
    bst, path = _train_model(tmp_path)
    body = json.load(open(path + ".drift.json"))
    assert body["version"] == drift_mod.SIDECAR_VERSION
    assert body["rows"] == 2000
    for entry in body["features"]:
        if entry["kind"] == "numerical" and "counts" in entry:
            assert sum(entry["counts"]) == 2000, entry


def test_save_model_env_gate_emits_sidecar(tmp_path, monkeypatch):
    bst, _ = _train_model(tmp_path, sidecar=False)
    monkeypatch.setenv("LIGHTGBM_TPU_DRIFT_SIDECAR", "1")
    p2 = str(tmp_path / "auto.txt")
    bst.save_model(p2)
    assert (tmp_path / "auto.txt.drift.json").exists()


# ---------------------------------------------------------------------------
# HTTP surface
# ---------------------------------------------------------------------------

def test_drift_endpoint_over_http(tmp_path):
    _, path = _train_model(tmp_path)
    app = ServeApp(max_delay_ms=1.0, min_bucket_rows=8, drift=True)
    srv = make_server("127.0.0.1", 0, app)
    port = srv.server_address[1]
    t = threading.Thread(target=srv.serve_forever, daemon=True)
    t.start()
    try:
        app.registry.load("m", path)
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
        conn.request(
            "POST", "/predict",
            json.dumps({"rows": _rows(seed=30, n=32).tolist()}),
            {"Content-Type": "application/json"},
        )
        assert conn.getresponse().status == 200
        conn.request("GET", "/drift")
        r = conn.getresponse()
        assert r.status == 200
        body = json.loads(r.read().decode("utf-8"))
        conn.close()
        assert body["enabled"] is True
        assert body["models"]["m"]["rows"] == 32
        assert "features" in body["models"]["m"]
    finally:
        srv.shutdown()
        srv.server_close()
        app.close()
