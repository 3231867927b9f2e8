"""Closed-loop continuous-training smoke: the REAL serve stack end to end
(check.sh --loop).

One invocation proves the whole loop (docs/ContinuousTraining.md), with the
runtime sanitizer armed (``LIGHTGBM_TPU_SAN=transfer,nan,locks``) so a full
cycle — bootstrap train, drift detection, warm-started retrain, holdout
gate, atomic publish, hot swap — is also a sanitizer-clean certification:

  1. the controller BOOTSTRAPS the live model (publish + drift sidecar +
     lineage) and a real ``ThreadingHTTPServer`` serve stack loads it with
     drift monitoring on;
  2. in-distribution traffic through ``POST /predict`` leaves ``/drift``
     quiet; DRIFT-SHIFTED traffic raises a real PSI alert;
  3. the controller's observe pass sees the alert over HTTP, retrains
     warm-started from the live model on the shifted data, the candidate
     passes the AUC gate, publishes through resil/atomic and hot-swaps the
     replica via ``POST /models`` — after which ``/predict`` answers from
     the NEW version carrying lineage (parent fingerprint + flight manifest
     digest) and ``/drift`` runs against the REFRESHED sidecar;
  4. a seeded mid-publish SIGKILL (``loop.publish:3:kill`` — occurrence 1
     is the bootstrap's rename window, 2 the publish-step entry, 3 INSIDE
     the promote's atomic rename window) kills a second controller world;
     the restarted controller converges with the journaled cycle completed
     exactly once.

Run: JAX_PLATFORMS=cpu python helpers/loop_smoke.py
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile
import threading
import urllib.request

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("LIGHTGBM_TPU_SAN", "transfer,nan,locks")

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import numpy as np  # noqa: E402

F = 5
SHIFT = 1.6


def _provider(cycle: int):
    """Deterministic per cycle: base distribution for the bootstrap, the
    drift-shifted one for every retrain cycle."""
    rng = np.random.RandomState(100 + cycle)
    n = 600
    shift = 0.0 if cycle == 0 else SHIFT
    X = rng.randn(n, F) + shift
    y = ((X[:, 0] - shift) + 0.3 * rng.randn(n) > 0).astype(float)
    Xh = rng.randn(200, F) + shift
    yh = ((Xh[:, 0] - shift) > 0).astype(float)
    return X, y, Xh, yh


def _post(base, path, body, timeout=120):
    req = urllib.request.Request(
        base + path, data=json.dumps(body).encode("utf-8"),
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return json.loads(resp.read().decode("utf-8"))


def _get(base, path, timeout=30):
    with urllib.request.urlopen(base + path, timeout=timeout) as resp:
        return json.loads(resp.read().decode("utf-8"))


def serve_acts(td: str, result: dict) -> bool:
    from lightgbm_tpu.loop import (
        HttpDriftSource, HttpReplica, LoopConfig, LoopController,
    )
    from lightgbm_tpu.serve.server import make_server

    live = os.path.join(td, "live.txt")
    cfg = LoopConfig(
        model_path=live,
        workdir=os.path.join(td, "wd"),
        params={"objective": "binary", "num_leaves": 15, "verbosity": -1,
                "device_chunk_size": 4},
        num_boost_round=8,
        data_provider=_provider,
        poll_interval_s=0.2,
        observe_budget_s=30.0,
        jitter_seed=7,
    )
    ctl = LoopController(cfg)
    ctl.ensure_bootstrap()
    assert os.path.exists(live + ".drift.json"), "bootstrap drift sidecar"

    server = make_server(
        port=0, drift=True, drift_min_count=200, warmup_rows=64,
    )
    base = "http://127.0.0.1:%d" % server.server_address[1]
    app = server.app
    t = threading.Thread(target=server.serve_forever, daemon=True)
    t.start()
    try:
        app.registry.load(cfg.model_name, live)
        v1 = _get(base, "/models")["models"][0]
        result["v1"] = {"version": v1["version"], "file_sha": v1["file_sha"]}

        rng = np.random.RandomState(0)
        # act 2a: in-distribution traffic -> /drift stays quiet
        for _ in range(4):
            rows = rng.randn(100, F).tolist()
            _post(base, "/predict", {"rows": rows})
        drift = _get(base, "/drift")
        quiet = not drift["models"][cfg.model_name]["alerts"]
        result["in_dist_quiet"] = quiet
        # act 2b: drift-shifted traffic -> a real PSI alert
        for _ in range(6):
            rows = (rng.randn(100, F) + SHIFT).tolist()
            _post(base, "/predict", {"rows": rows})
        drift = _get(base, "/drift")
        alerts = drift["models"][cfg.model_name]["alerts"]
        result["drift_alerts"] = alerts
        if not (quiet and alerts):
            result["error"] = "drift separation failed"
            return False

        # act 3: the controller's observe pass sees the alert over HTTP
        # and drives the full cycle against the real server
        cfg.drift_source = HttpDriftSource(base)
        cfg.replicas = [HttpReplica(base)]
        outcome = ctl.run_cycle()
        result["cycle_outcome"] = outcome
        if outcome != "promoted":
            result["error"] = "cycle outcome %r" % outcome
            return False
        pred = _post(base, "/predict",
                     {"rows": (rng.randn(3, F) + SHIFT).tolist()})
        v2 = _get(base, "/models")["models"][0]
        result["v2"] = {
            "version": v2["version"], "file_sha": v2["file_sha"],
            "parent_fingerprint": v2["parent_fingerprint"],
            "manifest_digest": v2["manifest_digest"],
        }
        drift2 = _get(base, "/drift")["models"][cfg.model_name]
        result["post_swap_drift_source"] = drift2.get("source")
        ok = (
            v2["version"] == v1["version"] + 1
            and v2["file_sha"] != v1["file_sha"]
            and v2["parent_fingerprint"] == v1["file_sha"]
            and bool(v2["manifest_digest"])
            and pred["parent_fingerprint"] == v1["file_sha"]
            and pred["manifest_digest"] == v2["manifest_digest"]
            and drift2.get("source") == "sidecar"  # refreshed per swap
        )
        if not ok:
            result["error"] = "post-swap verification failed"
        return ok
    finally:
        server.shutdown()
        app.drain(timeout_s=10.0)


_KILL_CHILD = """
import os, sys
sys.path.insert(0, %r)
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import numpy as np
from lightgbm_tpu.loop import AppReplica, LoopConfig, LoopController
from lightgbm_tpu.serve.server import ModelRegistry

wd = sys.argv[1]
live = os.path.join(wd, "live.txt")

def provider(cycle):
    rng = np.random.RandomState(100 + cycle)
    shift = 0.0 if cycle == 0 else 1.6
    X = rng.randn(300, 5) + shift
    y = ((X[:, 0] - shift) + 0.3 * rng.randn(300) > 0).astype(float)
    Xh = rng.randn(120, 5) + shift
    yh = ((Xh[:, 0] - shift) > 0).astype(float)
    return X, y, Xh, yh

ctl = LoopController(LoopConfig(
    model_path=live, workdir=wd,
    params={"objective": "binary", "num_leaves": 8, "verbosity": -1},
    num_boost_round=5, data_provider=provider,
    replicas=[AppReplica(ModelRegistry())],
))
ctl.ensure_bootstrap()
out = ctl.run_cycle(force=True)
print("KILL-CHILD outcome=%%s sha=%%s" %% (out, ctl._file_sha(live)))
""" % REPO


def kill_act(result: dict) -> bool:
    """Seeded mid-publish SIGKILL (inside the atomic rename window), then a
    restart that must converge on the journaled cycle."""
    with tempfile.TemporaryDirectory() as wd:
        # the child bootstraps AND cycles in one process, so loop.publish
        # occurrences are: 1 = bootstrap's rename window, 2 = the publish
        # step's entry fire, 3 = the promote's atomic rename window — the
        # hardest crash point, which is the one this act seeds
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   LIGHTGBM_TPU_FAULTS="loop.publish:3:kill")
        r = subprocess.run(
            [sys.executable, "-c", _KILL_CHILD, wd],
            cwd=REPO, env=env, capture_output=True, text=True, timeout=420,
        )
        if r.returncode != -9 or "KILL-CHILD outcome" in r.stdout:
            result["error"] = ("kill child not SIGKILLed (rc=%s)"
                               % r.returncode)
            result["kill_stderr"] = r.stderr[-500:]
            return False
        env.pop("LIGHTGBM_TPU_FAULTS")
        r = subprocess.run(
            [sys.executable, "-c", _KILL_CHILD, wd],
            cwd=REPO, env=env, capture_output=True, text=True, timeout=420,
        )
        if r.returncode != 0:
            result["error"] = "restart failed"
            result["kill_stderr"] = r.stderr[-800:]
            return False
        out = r.stdout.split("outcome=")[1].split()[0]
        journal = json.load(
            open(os.path.join(wd, "loop_journal.json"))
        )
        result["kill_recovered_outcome"] = out
        ok = (
            out == "promoted"
            and journal["state"] == "observe"
            and journal["cycle"] == 1
            and sum(journal["outcomes"].values()) == 1
        )
        if not ok:
            result["error"] = "kill recovery inconsistent"
        return ok


def main() -> int:
    result: dict = {"san": os.environ.get("LIGHTGBM_TPU_SAN", "")}
    with tempfile.TemporaryDirectory() as td:
        ok = serve_acts(td, result)
    ok = kill_act(result) and ok
    result["ok"] = ok
    result["loop_smoke"] = "PASS" if ok else "FAIL"
    # ONE compact line: a driver reads the last JSON line of stdout
    print(json.dumps(result))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
