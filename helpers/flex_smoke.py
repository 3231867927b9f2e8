"""check.sh --flex: the flexctl chaos smoke, ONE invocation.

Drives the elastic fleet orchestrator (lightgbm_tpu/flex) through a
scripted capacity storm on forced-multi-CPU-device children and gates on
the exactness taxonomy docs/FaultTolerance.md documents:

  leg A — **capacity chaos, in-process controller**. A scripted plan
     shrinks the world 8 -> 2 after iteration 4 and grows it back 2 -> 8
     after iteration 7; launch #3 additionally gets a fault-injected
     SIGKILL mid-chunk (``train.iteration:2:kill``). Expected run:
     child 1 (world 8) drains at the shrink boundary and exits 76,
     child 2 (world 2) drains at the grow boundary and exits 76,
     child 3 (world 8) is murdered mid-chunk (rc -9, a plain crash),
     child 4 (world 8) resumes and finishes. Gates: exactly 2 reshards
     with the scripted {from,to,reason} labels on ``flex_reshards``,
     exactly 1 crash restart, the loud ulp-drift warning EXACTLY once
     per world change, final model structurally identical to the
     uninterrupted reference with the pre-drain tree prefix byte-exact
     and every leaf within ulp tolerance (the world changed twice —
     byte-identity is NOT claimed, measured impossible).
  leg B — **same storm class, no world change, real CLI**. The
     ``python -m lightgbm_tpu.flex`` entry point supervises a run whose
     plan never changes and whose first child is SIGKILLed mid-run:
     one crash restart, zero reshards, and — because the row world
     size never changed — a final model BYTE-identical to the
     uninterrupted reference.

HARD FAILURES: wrong reshard count/labels, wrong restart count, a missing
or duplicated ulp warning, structural divergence or prefix/byte mismatch,
or a controller that does not finish with rc 0.

The last stdout line is a JSON result.
"""
import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

ROUNDS = 12
CKPT_ROUNDS = 3
CHILD_TIMEOUT_S = 420.0

BASE_PARAMS = {
    "task": "train",
    "objective": "binary",
    "num_leaves": "15",
    "verbosity": "-1",
    "bagging_freq": "2",
    "bagging_fraction": "0.8",
    "feature_fraction": "0.8",
    "tree_learner": "data",
    "device_chunk_size": "3",
    "num_iterations": str(ROUNDS),
}


def _fail(msg, *tails):
    print("flex_smoke FAILED: %s" % msg, flush=True)
    for t in tails:
        if t:
            print(t[-1500:], flush=True)
    print(json.dumps({"ok": False, "error": msg}), flush=True)
    return 1


def _write_data(path):
    import numpy as np

    rng = np.random.RandomState(7)
    n, f = 1003, 6
    X = rng.randn(n, f)
    y = (X[:, 0] + 0.3 * rng.randn(n) > 0).astype(float)
    np.savetxt(path, np.column_stack([y, X]), fmt="%.10g", delimiter="\t")


def _cli_env(world):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=%d" % world
    env.pop("LIGHTGBM_TPU_FAULTS", None)
    return env


def _train_ref(data, out):
    kv = dict(BASE_PARAMS, data=data, output_model=out)
    argv = [sys.executable, "-m", "lightgbm_tpu"]
    argv += ["%s=%s" % (k, v) for k, v in kv.items()]
    r = subprocess.run(argv, env=_cli_env(8), cwd=REPO, capture_output=True,
                       text=True, timeout=CHILD_TIMEOUT_S)
    if r.returncode != 0:
        print(r.stdout[-1500:])
        print(r.stderr[-1500:])
        raise RuntimeError("reference training failed rc=%d" % r.returncode)


def _model_body(path):
    with open(path, encoding="utf-8") as fh:
        return fh.read().split("parameters:")[0]


def _trees(path):
    """(split_feature tuple, threshold tuple, leaf_value tuple) per tree —
    structural + value comparisons without trusting float formatting."""
    import re

    out = []
    for block in _model_body(path).split("\nTree=")[1:]:
        f = {}
        for line in block.splitlines():
            m = re.match(r"(split_feature|threshold|leaf_value)=(.*)", line)
            if m:
                f[m.group(1)] = m.group(2).split()
        out.append((tuple(f.get("split_feature", [])),
                    tuple(f.get("threshold", [])),
                    tuple(float(v) for v in f.get("leaf_value", []))))
    return out


def _tree_blocks(path):
    return _model_body(path).split("\nTree=")[1:]


def _ulp_close(a, b):
    return abs(a - b) <= 2e-4 * max(abs(a), abs(b), 1e-6) + 2e-6


class _TimedChild:
    """Popen wrapper whose wait() cannot wedge the smoke: a child that
    outlives the per-launch budget is SIGKILLed and reported as a crash."""

    def __init__(self, proc):
        self.proc = proc

    def wait(self):
        try:
            return self.proc.wait(timeout=CHILD_TIMEOUT_S)
        except subprocess.TimeoutExpired:
            self.proc.kill()
            return self.proc.wait()


def _leg_a(work, data):
    """Scripted shrink/grow storm + a mid-chunk SIGKILL, controller
    in-process so the kill can be injected into EXACTLY one launch."""
    from lightgbm_tpu.flex import CapacityPlan, FlexController, marker_path
    from lightgbm_tpu.flex.__main__ import child_env
    from lightgbm_tpu.obs.registry import REGISTRY
    from lightgbm_tpu.utils import log as tlog

    ckpt = os.path.join(work, "a.ckpt")
    out = os.path.join(work, "a_model.txt")
    plan_path = os.path.join(work, "a_plan.json")
    with open(plan_path, "w", encoding="utf-8") as fh:
        json.dump({"world": 8, "steps": [
            {"after_iteration": 4, "world": 2, "reason": "shrink"},
            {"after_iteration": 7, "world": 8, "reason": "grow"},
        ]}, fh)

    kill_attempt = 3
    iters = []  # (attempt, world) receipts, for the storm-shape report

    def launch(world, attempt):
        kv = dict(BASE_PARAMS, data=data, output_model=out,
                  flex_plan=plan_path, checkpoint_path=ckpt,
                  checkpoint_rounds=str(CKPT_ROUNDS))
        if os.path.exists(ckpt):
            kv["resume_from"] = ckpt
        env = child_env(dict(os.environ), world, True)
        env.pop("LIGHTGBM_TPU_FAULTS", None)
        if attempt == kill_attempt:
            env["LIGHTGBM_TPU_FAULTS"] = "train.iteration:2:kill"
        argv = [sys.executable, "-m", "lightgbm_tpu"]
        argv += ["%s=%s" % (k, v) for k, v in kv.items()]
        iters.append((attempt, world))
        lp = os.path.join(work, "a_launch%d.log" % attempt)
        fh = open(lp, "w")
        return _TimedChild(subprocess.Popen(
            argv, env=env, cwd=REPO, stdout=fh, stderr=fh))

    ulp_warnings = []
    tlog.register_callback(
        lambda line: ulp_warnings.append(line) if "ulp level" in line
        else sys.stderr.write(line))
    try:
        ctl = FlexController(
            launch, CapacityPlan(plan_path),
            os.path.join(work, "a.flex.journal.json"),
            marker=marker_path(ckpt), initial_world=8,
            min_healthy_s=1.0, backoff_base_s=0.2, backoff_max_s=2.0,
            seed=7,
        )
        rc = ctl.run(max_launches=8)
    finally:
        tlog.register_callback(None)
    s = ctl.summary()
    if rc != 0:
        return None, "leg A controller rc=%d (summary %s)" % (rc, s)

    if int(s["reshards"]) != 2:
        return None, "leg A expected 2 reshards, got %s" % s["reshards"]
    want_log = [{"from": 8, "to": 2, "reason": "shrink", "exact": False},
                {"from": 2, "to": 8, "reason": "grow", "exact": False}]
    if list(s["reshard_log"]) != want_log:
        return None, "leg A reshard_log %s != %s" % (s["reshard_log"],
                                                     want_log)
    c = REGISTRY.counter("flex_reshards")
    for fw, tw, why in ((8, 2, "shrink"), (2, 8, "grow")):
        got = c.value(**{"from": str(fw), "to": str(tw), "reason": why})
        if got != 1:
            return None, ("leg A flex_reshards{from=%d,to=%d,reason=%s} "
                          "= %s, expected 1" % (fw, tw, why, got))
    if int(s["restarts"]) != 1:
        return None, "leg A expected 1 crash restart, got %s" % s["restarts"]
    if len(ulp_warnings) != 2:
        return None, ("leg A expected the ulp-drift warning exactly once "
                      "per world change (2), saw %d" % len(ulp_warnings))
    worlds = [w for _, w in iters]
    if worlds != [8, 2, 8, 8]:
        return None, "leg A launch worlds %s != [8, 2, 8, 8]" % worlds
    return {"out": out, "launches": s["launches"], "worlds": worlds}, None


def _leg_b(work, data):
    """The real ``python -m lightgbm_tpu.flex`` CLI, constant-world plan,
    first child SIGKILLed mid-run: crash restart + byte-identity."""
    ckpt = os.path.join(work, "b.ckpt")
    out = os.path.join(work, "b_model.txt")
    plan_path = os.path.join(work, "b_plan.json")
    with open(plan_path, "w", encoding="utf-8") as fh:
        json.dump({"world": 8}, fh)

    # occurrence 4 of train.iteration lands after a periodic checkpoint
    # exists (the elastic_smoke-measured shape at 12 rounds / chunk 3);
    # the RESUMED child replays fewer than 4 passes, so the inherited
    # fault spec can never re-fire and the relaunch completes
    env = dict(os.environ)
    env["LIGHTGBM_TPU_FAULTS"] = "train.iteration:4:kill"
    argv = [sys.executable, "-m", "lightgbm_tpu.flex",
            "flex_plan=%s" % plan_path, "checkpoint_path=%s" % ckpt,
            "flex_force_cpu=true", "flex_max_launches=4", "flex_seed=3",
            "data=%s" % data, "output_model=%s" % out,
            "checkpoint_rounds=%d" % CKPT_ROUNDS]
    argv += ["%s=%s" % (k, v) for k, v in BASE_PARAMS.items()]
    r = subprocess.run(argv, env=env, cwd=REPO, capture_output=True,
                       text=True, timeout=4 * CHILD_TIMEOUT_S)
    summary = None
    for line in reversed(r.stdout.splitlines()):
        if line.startswith("{"):
            try:
                summary = json.loads(line)
                break
            except ValueError:
                continue
    if r.returncode != 0 or not summary or not summary.get("ok"):
        return None, ("leg B flexctl rc=%d summary=%s\n%s\n%s"
                      % (r.returncode, summary, r.stdout[-1000:],
                         r.stderr[-1000:]))
    if int(summary.get("reshards") or 0) != 0:
        return None, "leg B expected 0 reshards, got %s" % summary
    if int(summary.get("restarts") or 0) != 1:
        return None, "leg B expected 1 restart, got %s" % summary
    return {"out": out, "summary": summary}, None


def main() -> int:
    import tempfile

    work = tempfile.mkdtemp(prefix="flex_smoke_")
    data = os.path.join(work, "train.tsv")
    ref_out = os.path.join(work, "ref_model.txt")
    _write_data(data)
    t0 = time.time()

    _train_ref(data, ref_out)
    t_ref = time.time() - t0
    print("flex_smoke: reference trained (8 devices, %.1fs — %.2f it/s)"
          % (t_ref, ROUNDS / t_ref), flush=True)

    t1 = time.time()
    a, err = _leg_a(work, data)
    if err:
        return _fail(err)
    t_a = time.time() - t1
    print("flex_smoke: leg A storm complete — worlds %s, 2 reshards "
          "(8->2 shrink, 2->8 grow), 1 crash restart, ulp warning once "
          "per change (%.1fs)" % (a["worlds"], t_a), flush=True)

    ref_trees, a_trees = _trees(ref_out), _trees(a["out"])
    if len(a_trees) != ROUNDS or len(ref_trees) != ROUNDS:
        return _fail("leg A tree count %d vs reference %d (want %d)"
                     % (len(a_trees), len(ref_trees), ROUNDS))
    for i, (rt, at) in enumerate(zip(ref_trees, a_trees)):
        if rt[0] != at[0] or rt[1] != at[1]:
            return _fail("leg A tree %d structure diverged from the "
                         "uninterrupted reference" % i)
        for rv, av in zip(rt[2], at[2]):
            if not _ulp_close(rv, av):
                return _fail("leg A tree %d leaf drift beyond ulp "
                             "tolerance: %r vs %r" % (i, rv, av))
    prefix = 0
    for rb, ab in zip(_tree_blocks(ref_out), _tree_blocks(a["out"])):
        if rb != ab:
            break
        prefix += 1
    if prefix < 4:
        return _fail("leg A pre-drain prefix only %d trees byte-exact "
                     "(the shrink latched after iteration 4, so >= 4 "
                     "trees predate any world change)" % prefix)
    print("flex_smoke: leg A exactness — structure identical, %d-tree "
          "prefix byte-exact, all leaves ulp-close" % prefix, flush=True)

    t2 = time.time()
    b, err = _leg_b(work, data)
    if err:
        return _fail(err)
    print("flex_smoke: leg B flexctl CLI survived the SIGKILL — 1 restart,"
          " 0 reshards (%.1fs)" % (time.time() - t2), flush=True)
    if _model_body(b["out"]) != _model_body(ref_out):
        return _fail("leg B model differs from the uninterrupted reference"
                     " — same-world resume must be byte-identical")
    print("flex_smoke: leg B byte-identity holds (world never changed)",
          flush=True)

    elapsed = time.time() - t0
    print("flex_smoke: PASS (%.1fs)" % elapsed, flush=True)
    print(json.dumps({"ok": True, "elapsed_s": round(elapsed, 1),
                      "legA": {"worlds": a["worlds"],
                               "launches": a["launches"],
                               "prefix_trees": prefix},
                      "legB": b["summary"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
