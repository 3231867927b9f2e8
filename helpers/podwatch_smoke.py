#!/usr/bin/env python
"""Fleet-telemetry smoke: live scrape + straggler verdict on a REAL pod.

ONE invocation proves the whole podwatch chain (docs/Observability.md
§Fleet telemetry, obs/podwatch.py) end to end:

  1. a real 2-process CPU training world (jax.distributed, one rank per
     process) runs with the telemetry ring + heartbeats armed
     (LIGHTGBM_TPU_TELEMETRY) and the scrape endpoint up on rank 0
     (LIGHTGBM_TPU_TELEMETRY_PORT); rank 1 carries a seeded per-boundary
     sleep — the straggler the aggregator must later name;
  2. the parent scrapes rank 0 LIVE, mid-run: /health must answer with a
     mid-run iteration, /metrics must expose the lgbtpu_* families, and
     /timeline must already hold boundary samples;
  3. after the pod drains, ``python -m lightgbm_tpu.obs.podwatch <dir>
     --json`` folds both ranks' shards + heartbeats and the straggler
     verdict must name rank 1 with its diverging segment and the factor/
     threshold evidence;
  4. telemetry-off byte-identity: the same single-process training run
     with and without LIGHTGBM_TPU_TELEMETRY must produce byte-identical
     model text (the recorder samples host state only).

The parent stays jax-free (subprocesses do all jax work) so the driver can
run on any box.
"""
import hashlib
import json
import os
import socket
import subprocess
import sys
import tempfile
import textwrap
import time
import urllib.request

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: seeded per-boundary sleep (seconds) — rank 1 is the straggler
LAG_RANK0 = 0.05
LAG_RANK1 = 0.35

WORKER = textwrap.dedent(
    """
    import os, sys, json, time, hashlib
    os.environ["JAX_PLATFORMS"] = "cpu"
    rank, world = int(sys.argv[1]), int(sys.argv[2])
    coord_port, http_port, outdir, lag = (
        sys.argv[3], sys.argv[4], sys.argv[5], float(sys.argv[6])
    )
    os.environ["LIGHTGBM_TPU_TELEMETRY"] = outdir
    os.environ["LIGHTGBM_TPU_TIMETAG"] = "1"
    if rank == 0:
        os.environ["LIGHTGBM_TPU_TELEMETRY_PORT"] = http_port
    import jax
    jax.config.update("jax_platforms", "cpu")
    jax.distributed.initialize(coordinator_address="127.0.0.1:" + coord_port,
                               num_processes=world, process_id=rank)
    sys.path.insert(0, "@REPO@")
    import numpy as np
    import lightgbm_tpu as lgb

    rng = np.random.RandomState(7)
    X = rng.randn(1200, 10)
    y = (X[:, 0] + 0.5 * X[:, 3] + 0.2 * rng.randn(1200) > 0).astype(np.float64)
    ds = lgb.Dataset(X, label=y)

    def laggard(env):  # seeded per-boundary sleep (after-iteration)
        time.sleep(lag)
    laggard.order = 100

    booster = lgb.train(
        {"objective": "binary", "num_leaves": 15, "learning_rate": 0.1,
         "min_data_in_leaf": 5, "device_chunk_size": 4},
        ds, num_boost_round=80, callbacks=[laggard], verbose_eval=False,
    )
    sha = hashlib.sha256(booster.model_to_string().encode()).hexdigest()
    print("RESULT " + json.dumps({"rank": rank, "model_sha": sha,
                                  "iters": booster.current_iteration}),
          flush=True)
    # barrier exit: rank 0 hosts the coordinator, and leaving early would
    # tear it down under the still-training straggler
    jax.distributed.shutdown()
    """
).replace("@REPO@", REPO)

IDENTITY_WORKER = textwrap.dedent(
    """
    import os, sys, hashlib
    os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path.insert(0, "@REPO@")
    import numpy as np
    import lightgbm_tpu as lgb

    rng = np.random.RandomState(11)
    X = rng.randn(600, 8)
    y = (X[:, 1] - X[:, 2] > 0).astype(np.float64)
    ds = lgb.Dataset(X, label=y)
    booster = lgb.train(
        {"objective": "binary", "num_leaves": 15, "device_chunk_size": 4},
        ds, num_boost_round=24, verbose_eval=False,
    )
    print("SHA " + hashlib.sha256(
        booster.model_to_string().encode()).hexdigest(), flush=True)
    """
).replace("@REPO@", REPO)


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _get(url, timeout=5.0):
    with urllib.request.urlopen(url, timeout=timeout) as resp:
        return resp.status, resp.read().decode("utf-8")


def _fail(msg):
    print("podwatch_smoke: FAIL — %s" % msg, flush=True)
    return 1


def _scrape_live(http_port, procs, deadline_s=300.0):
    """Poll /health until rank 0 is mid-run, then scrape all three
    endpoints. Returns (ok, detail)."""
    base = "http://127.0.0.1:%d" % http_port
    t0 = time.monotonic()
    health = None
    while time.monotonic() - t0 < deadline_s:
        if any(p.poll() is not None and p.returncode != 0 for p in procs):
            return False, "a worker died before the live scrape"
        if procs[0].poll() is not None:
            return False, "rank 0 finished before a mid-run scrape landed"
        try:
            code, body = _get(base + "/health", timeout=2.0)
        except OSError:
            time.sleep(0.05)
            continue
        if code != 200:
            time.sleep(0.05)
            continue
        health = json.loads(body)
        if health.get("telemetry_armed") and health.get("iteration", 0) > 0:
            break
        time.sleep(0.02)
    else:
        return False, "no mid-run /health answer within %.0fs" % deadline_s
    if health["iteration"] >= 80:
        return False, "scrape landed post-run (iteration %d)" % health["iteration"]
    if health.get("rank") != 0 or health.get("world") != 2:
        return False, "unexpected /health identity: %r" % (health,)

    code, prom = _get(base + "/metrics", timeout=5.0)
    if code != 200 or "lgbtpu_train_iterations_total" not in prom:
        return False, "/metrics missing lgbtpu_train_iterations_total"
    if "# TYPE lgbtpu_train_iterations_total counter" not in prom:
        return False, "/metrics missing the TYPE line"

    code, tl = _get(base + "/timeline", timeout=5.0)
    timeline = json.loads(tl)
    if code != 200 or not timeline.get("telemetry_armed"):
        return False, "/timeline not armed"
    samples = timeline.get("samples") or []
    if not samples or timeline.get("rank") != 0:
        return False, "/timeline empty mid-run"
    s = samples[-1]
    for key in ("iteration", "chunk", "dt_s", "it_per_s", "counters"):
        if key not in s:
            return False, "/timeline sample missing %r" % key
    print("podwatch_smoke: live scrape OK at iteration %d "
          "(%d timeline samples)" % (health["iteration"], len(samples)),
          flush=True)
    return True, ""


def _run_pod(tmp, attempt):
    """One coordinated 2-process run; None on a coordinator port race."""
    outdir = os.path.join(tmp, "telemetry%d" % attempt)
    os.makedirs(outdir, exist_ok=True)
    worker = os.path.join(tmp, "worker.py")
    with open(worker, "w") as fh:
        fh.write(WORKER)
    coord_port, http_port = _free_port(), _free_port()
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)  # no virtual devices: one real proc per rank
    errs = [open(os.path.join(tmp, "err_a%d_r%d.log" % (attempt, r)), "w+")
            for r in range(2)]
    procs = []
    try:
        for r, lag in ((0, LAG_RANK0), (1, LAG_RANK1)):
            procs.append(subprocess.Popen(
                [sys.executable, worker, str(r), "2", str(coord_port),
                 str(http_port), outdir, str(lag)],
                env=env, stdout=subprocess.PIPE, stderr=errs[r], text=True,
            ))
        ok, detail = _scrape_live(http_port, procs)
        results = []
        for r, p in enumerate(procs):
            out, _ = p.communicate(timeout=600)
            errs[r].seek(0)
            err_text = errs[r].read()
            if p.returncode != 0:
                low = err_text.lower()
                if "address already in use" in low or "failed to bind" in low:
                    return None  # port race: retry on fresh ports
                raise AssertionError(
                    "rank %d rc=%d\n%s" % (r, p.returncode, err_text[-2000:])
                )
            line = next(l for l in out.splitlines() if l.startswith("RESULT "))
            results.append(json.loads(line[len("RESULT "):]))
        if not ok:
            raise AssertionError("live scrape failed: %s" % detail)
        return outdir, results
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.wait()
        for fh in errs:
            fh.close()


def _aggregate(outdir):
    """python -m lightgbm_tpu.obs.podwatch <dir> --json in a fresh process
    (the operator's invocation, not an in-process shortcut)."""
    proc = subprocess.run(
        [sys.executable, "-m", "lightgbm_tpu.obs.podwatch", outdir, "--json"],
        cwd=REPO, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=120,
    )
    if proc.returncode != 0:
        raise AssertionError("aggregator rc=%d\n%s"
                             % (proc.returncode, proc.stderr[-2000:]))
    return json.loads(proc.stdout)


def _identity_sha(tmp, tag, telemetry_dir):
    script = os.path.join(tmp, "identity.py")
    with open(script, "w") as fh:
        fh.write(IDENTITY_WORKER)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("LIGHTGBM_TPU_TELEMETRY", None)
    env.pop("LIGHTGBM_TPU_TELEMETRY_PORT", None)
    if telemetry_dir:
        env["LIGHTGBM_TPU_TELEMETRY"] = telemetry_dir
    proc = subprocess.run([sys.executable, script], env=env,
                          capture_output=True, text=True, timeout=600)
    if proc.returncode != 0:
        raise AssertionError("identity run (%s) rc=%d\n%s"
                             % (tag, proc.returncode, proc.stderr[-2000:]))
    line = next(l for l in proc.stdout.splitlines() if l.startswith("SHA "))
    return line.split()[1]


def main():
    tmp = tempfile.mkdtemp(prefix="podwatch_smoke_")
    print("podwatch_smoke: workdir %s" % tmp, flush=True)

    # -- 1+2: the 2-process world, scraped live ----------------------------
    pod = None
    for attempt in range(2):
        pod = _run_pod(tmp, attempt)
        if pod is not None:
            break
    if pod is None:
        return _fail("coordinator port bind failed twice")
    outdir, results = pod
    print("podwatch_smoke: pod drained: %s" % json.dumps(results), flush=True)
    if any(r["iters"] != 80 for r in results):
        return _fail("a rank did not finish all 80 iterations: %r" % results)

    # -- 3: aggregate + the seeded straggler named -------------------------
    summary = _aggregate(outdir)
    print("podwatch_smoke: verdicts: %s"
          % json.dumps(summary["verdicts"]), flush=True)
    if summary.get("world") != 2 or len(summary.get("ranks", {})) != 2:
        return _fail("aggregator did not see both ranks: %r"
                     % summary.get("ranks"))
    stragglers = [v for v in summary["verdicts"]
                  if v["verdict"] == "straggler"]
    if not stragglers:
        return _fail("no straggler verdict for the seeded slow rank")
    v = stragglers[0]
    if v["rank"] != 1:
        return _fail("straggler verdict blamed rank %r, seeded rank 1"
                     % v["rank"])
    ev = v.get("evidence") or {}
    if not ev.get("segment"):
        return _fail("straggler verdict carries no diverging segment")
    if float(ev.get("factor", 0)) < float(ev.get("threshold", 1.5)):
        return _fail("straggler factor %r below its own threshold %r"
                     % (ev.get("factor"), ev.get("threshold")))
    # the seeded sleep lives in a callback — time no TIMETAG phase claims —
    # so the honest attribution is the synthetic host bucket
    if v["rank"] == 1 and ev["segment"] != "host_other":
        print("podwatch_smoke: note — diverging segment %r (expected "
              "host_other for a callback sleep)" % ev["segment"], flush=True)
    print("podwatch_smoke: straggler rank 1 named (%.2fx, segment %s)"
          % (float(ev["factor"]), ev["segment"]), flush=True)

    # -- 4: telemetry-off byte-identity ------------------------------------
    sha_on = _identity_sha(tmp, "armed", os.path.join(tmp, "id_telemetry"))
    sha_off = _identity_sha(tmp, "off", None)
    if sha_on != sha_off:
        return _fail("model bytes differ with telemetry armed: %s vs %s"
                     % (sha_on, sha_off))
    print("podwatch_smoke: telemetry-off byte-identity holds (%s)"
          % sha_off[:12], flush=True)

    print("podwatch_smoke: PASS", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
