"""flexctl: the elastic fleet orchestrator (lightgbm_tpu/flex, ISSUE 20).

Four layers under test:

  * the capacity plane — plan parsing (live + scripted forms, garbage
    degradation), heartbeat-judged dead ranks, the reason-carrying
    boundary latch and its 75/76 exit-code contract;
  * the in-train watcher — single-process drains, the two-phase marker
    consensus on a pod, dead-rank drains without a barrier, watchdog
    composition, and the provably-inert off path;
  * the controller — reshard/restart supervision over fake children in
    virtual time, including the flap guard that keeps a flapping plan
    from busy-looping the relaunch loop (ISSUE 20 satellite 3);
  * the engine round trip — a scripted 8 -> 2 -> 8 storm on one
    checkpoint pinning the exactness taxonomy per leg (prefix
    byte-identity, per-leg ``resil_reshards`` increments, the loud ulp
    warning exactly once per world change).

The end-to-end chain with REAL subprocess children (exit codes crossing
process boundaries, SIGKILL mid-chunk, the flexctl CLI) lives in
helpers/flex_smoke.py (check.sh --flex).
"""
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu import engine
from lightgbm_tpu.flex import capacity, watch
from lightgbm_tpu.flex.controller import FlexController, FlexJournal, \
    FlexStateError
from lightgbm_tpu.obs.registry import REGISTRY
from lightgbm_tpu.resil import backoff, checkpoint as ckpt_mod, coord, \
    preempt, watchdog

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _no_ambient_flex(monkeypatch):
    monkeypatch.delenv(capacity.ENV_PLAN, raising=False)


def _plan(tmp_path, body, name="plan.json"):
    p = tmp_path / name
    p.write_text(json.dumps(body))
    return str(p)


def _hb(base, rank, age_s, now=None):
    """A heartbeat blob whose wall stamp is ``age_s`` old."""
    now = time.time() if now is None else now
    path = coord.heartbeat_path(base, rank)
    with open(path, "w", encoding="utf-8") as fh:
        json.dump({"rank": rank, "iteration": 5, "pid": 1,
                   "time": now - age_s}, fh)
    return path


# ---------------------------------------------------------------------------
# capacity plan
# ---------------------------------------------------------------------------

def test_plan_live_form(tmp_path):
    plan = capacity.CapacityPlan(
        _plan(tmp_path, {"world": 4, "reason": "spot-grant"}))
    assert plan.initial_world() == 4
    step = plan.desired(0, 8)
    assert step == capacity.PlanStep(4, "spot-grant", 0)
    # a plan naming the current world is not a change
    assert plan.desired(0, 4) is None


def test_plan_scripted_form(tmp_path):
    plan = capacity.CapacityPlan(_plan(tmp_path, {
        "world": 8,
        "steps": [{"after_iteration": 4, "world": 2},
                  {"after_iteration": 7, "world": 8, "reason": "grow"}],
    }))
    assert plan.initial_world() == 8
    assert plan.desired(3, 8) is None  # no step in force yet
    s = plan.desired(5, 8)
    assert (s.world, s.after_iteration) == (2, 4)
    assert s.reason == "shrink"  # default reason derived by comparison
    # the LATEST step in force wins; asking for the current world is a no-op
    assert plan.desired(9, 8) is None
    assert plan.desired(9, 2) == capacity.PlanStep(8, "grow", 7)


def test_plan_degrades_on_garbage_and_missing(tmp_path):
    bad = tmp_path / "bad.json"
    bad.write_text("{not json")
    plan = capacity.CapacityPlan(str(bad))
    assert plan.desired(5, 8) is None
    assert plan.initial_world(default=3) == 3
    gone = capacity.CapacityPlan(str(tmp_path / "nope.json"))
    assert gone.desired(5, 8) is None
    # a step asking for world 0 is nonsense, not a drain to nothing
    zero = capacity.CapacityPlan(_plan(tmp_path, {
        "steps": [{"after_iteration": 0, "world": 0}]}, "zero.json"))
    assert zero.desired(5, 8) is None


def test_dead_ranks_need_a_heartbeat_first(tmp_path):
    base = str(tmp_path / "ck")
    _hb(base, 0, age_s=1.0)
    _hb(base, 1, age_s=120.0)
    # rank 2 never wrote one: startup-ambiguous, NOT dead
    dead = capacity.dead_ranks(base, 3, 60.0)
    assert [d.rank for d in dead] == [1]
    assert dead[0].age == pytest.approx(120.0, abs=5.0)


# ---------------------------------------------------------------------------
# boundary latch + exit-code contract
# ---------------------------------------------------------------------------

def test_latch_reasons_and_exit_codes():
    assert preempt.RESHARD_EXIT_CODE == 76
    assert preempt.RESHARD_EXIT_CODE != preempt.PREEMPT_EXIT_CODE
    latch = preempt.BoundaryLatch()
    assert not latch.requested()
    assert latch.request("drain", detail="shrink: 8 -> 2")
    assert latch.requested() and latch.reason == "drain"
    assert not latch.request("drain", detail="again")  # first drain wins
    # a real SIGTERM upgrades a pending drain: the kill grace window is
    # the harder deadline
    assert latch.request("preempt", signum=15)
    assert latch.reason == "preempt" and latch.signum == 15
    assert not latch.request("drain", detail="too late")
    assert latch.reason == "preempt"

    e = preempt.TrainingPreempted("x", iteration=3)
    assert e.exit_code == preempt.PREEMPT_EXIT_CODE
    d = preempt.TrainingDrained("y", iteration=3, detail="shrink")
    assert isinstance(d, preempt.TrainingPreempted)  # one except clause
    assert d.reason == "drain" and d.exit_code == preempt.RESHARD_EXIT_CODE


def test_cli_maps_drain_to_reshard_exit_code(monkeypatch):
    from lightgbm_tpu import cli

    def drained(config, params):
        raise preempt.TrainingDrained("drained", checkpoint_path="ck",
                                      iteration=4, detail="shrink")

    monkeypatch.setattr(cli, "run_train", drained)
    assert cli.main(["task=train", "data=unused"]) == 76

    def preempted(config, params):
        raise preempt.TrainingPreempted("preempted", checkpoint_path="ck")

    monkeypatch.setattr(cli, "run_train", preempted)
    assert cli.main(["task=train", "data=unused"]) == 75


# ---------------------------------------------------------------------------
# the boundary watcher
# ---------------------------------------------------------------------------

def test_watch_single_process_drain(tmp_path):
    latch = preempt.BoundaryLatch()
    marker = str(tmp_path / "ck.flex.drain.json")
    w = watch.BoundaryWatch(
        latch, capacity.CapacityPlan(_plan(tmp_path, {
            "steps": [{"after_iteration": 4, "world": 2}]})),
        live_world=8, marker=marker)
    w.check_boundary(3)
    assert not latch.requested() and not os.path.exists(marker)
    w.check_boundary(4)
    assert latch.requested() and latch.reason == "drain"
    assert "shrink" in latch.detail and not latch.no_barrier
    m = watch.read_marker(marker)
    assert (m["world"], m["from_world"], m["reason"]) == (2, 8, "shrink")
    assert m["drain_after"] == 4 and m["posted_by"] == 0


def test_watch_two_phase_marker_consensus(tmp_path):
    """On a pod the poster does NOT latch at the posting boundary: every
    rank — poster included — latches at its first boundary PAST the
    marker's drain_after, so the coordinated emergency save has all its
    barrier participants (flex/watch.py documents the lockstep proof)."""
    plan_path = _plan(tmp_path, {
        "steps": [{"after_iteration": 2, "world": 1, "reason": "shrink"}]})
    marker = str(tmp_path / "ck.flex.drain.json")
    latches = [preempt.BoundaryLatch() for _ in range(2)]
    ranks = [watch.BoundaryWatch(
        latches[r], capacity.CapacityPlan(plan_path), live_world=2,
        marker=marker, procs=2, rank=r) for r in range(2)]

    ranks[0].check_boundary(2)  # posts, does not latch
    assert os.path.exists(marker) and not latches[0].requested()
    ranks[1].check_boundary(2)  # adopts the marker, does not latch
    assert not latches[1].requested()
    ranks[0].check_boundary(4)
    ranks[1].check_boundary(4)
    assert latches[0].requested() and latches[1].requested()
    for latch in latches:
        assert latch.reason == "drain" and "drain posted at iteration 2" \
            in latch.detail


def test_watch_dead_rank_drains_survivors_without_barrier(tmp_path):
    base = str(tmp_path / "ck")
    _hb(base, 1, age_s=300.0)
    latch = preempt.BoundaryLatch()
    marker = str(tmp_path / "ck.flex.drain.json")
    w = watch.BoundaryWatch(
        latch, capacity.CapacityPlan(_plan(tmp_path, {"world": 2})),
        live_world=2, marker=marker, procs=2, rank=0, hb_base=base,
        dead_after_s=60.0)
    # the sweep is throttled to every DEAD_CHECK_EVERY-th boundary
    for i in range(1, watch.DEAD_CHECK_EVERY + 1):
        w.check_boundary(i)
    assert latch.requested() and latch.reason == "drain"
    assert latch.no_barrier, "a dead peer can never join the save barrier"
    assert "dead_rank" in latch.detail
    m = watch.read_marker(marker)
    assert (m["world"], m["reason"]) == (1, "dead_rank")


def test_watch_never_raises_into_training(tmp_path, monkeypatch):
    latch = preempt.BoundaryLatch()
    w = watch.BoundaryWatch(
        latch, capacity.CapacityPlan(_plan(tmp_path, {"world": 2})),
        live_world=8, marker=str(tmp_path / "m.json"))
    monkeypatch.setattr(w.plan, "desired",
                        lambda *a: (_ for _ in ()).throw(OSError("disk")))
    w.check_boundary(5)  # must degrade to "keep training", not crash
    assert not latch.requested()


def test_drain_reason_for_claims_only_collective_deadlines(tmp_path):
    w = watch.BoundaryWatch(
        preempt.BoundaryLatch(),
        capacity.CapacityPlan(str(tmp_path / "p.json")), live_world=2,
        marker=str(tmp_path / "m.json"))
    got = w.drain_reason_for(watchdog.CollectiveDeadlineError("rank 1"))
    assert got is not None and got.startswith("collective_deadline")
    assert w.drain_reason_for(ValueError("boom")) is None


# ---------------------------------------------------------------------------
# backoff: decorrelated jitter
# ---------------------------------------------------------------------------

def test_decorrelated_backoff_bounds_and_determinism():
    a = [d for _, d in zip(range(50), backoff.decorrelated(1.0, 60.0,
                                                           seed=5))]
    b = [d for _, d in zip(range(50), backoff.decorrelated(1.0, 60.0,
                                                           seed=5))]
    assert a == b, "seeded generators must replay identically"
    assert all(1.0 <= d <= 60.0 for d in a)
    assert max(a) > 5.0, "the jitter must actually grow from its base"
    capped = [d for _, d in zip(range(30),
                                backoff.decorrelated(10.0, 12.0, seed=1))]
    assert all(10.0 <= d <= 12.0 for d in capped)
    with pytest.raises(ValueError):
        next(backoff.decorrelated(0.0))


# ---------------------------------------------------------------------------
# the controller (fake children, virtual time)
# ---------------------------------------------------------------------------

class _Child:
    def __init__(self, rc, lifetime, clock, before=None):
        self.rc, self.lifetime, self.clock, self.before = \
            rc, lifetime, clock, before

    def wait(self):
        if self.before:
            self.before()
        self.clock.t += self.lifetime
        return self.rc


class _Clock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def _controller(tmp_path, launch, clock, plan_body=None, **kw):
    plan = capacity.CapacityPlan(
        _plan(tmp_path, plan_body or {"world": 8}, "ctl_plan.json"))
    kw.setdefault("sleep", lambda s: None)
    kw.setdefault("initial_world", 8)
    return FlexController(
        launch, plan, str(tmp_path / "flex.journal.json"),
        marker=str(tmp_path / "ck.flex.drain.json"),
        clock=clock, seed=11, **kw)


def test_controller_reshard_sequence(tmp_path):
    clock = _Clock()
    marker = str(tmp_path / "ck.flex.drain.json")
    script = [(76, {"world": 2, "reason": "shrink"}),
              (76, {"world": 8, "reason": "grow"}),
              (0, None)]
    worlds = []

    def launch(world, attempt):
        worlds.append(world)
        rc, m = script[attempt - 1]
        before = None
        if m is not None:
            before = lambda m=m: open(marker, "w").write(json.dumps(m))
        return _Child(rc, 30.0, clock, before)

    c = REGISTRY.counter("flex_reshards")
    pre_s = c.value(**{"from": "8", "to": "2", "reason": "shrink"})
    pre_g = c.value(**{"from": "2", "to": "8", "reason": "grow"})
    ctl = _controller(tmp_path, launch, clock)
    assert ctl.run() == 0
    assert worlds == [8, 2, 8]
    s = ctl.summary()
    assert s["state"] == "done" and s["launches"] == 3
    assert s["reshards"] == 2 and s["restarts"] == 0
    assert s["reshard_log"] == [
        {"from": 8, "to": 2, "reason": "shrink", "exact": False},
        {"from": 2, "to": 8, "reason": "grow", "exact": False}]
    assert c.value(**{"from": "8", "to": "2",
                      "reason": "shrink"}) == pre_s + 1
    assert c.value(**{"from": "2", "to": "8", "reason": "grow"}) == pre_g + 1
    assert not os.path.exists(marker), "the controller consumes the marker"


def test_controller_flapping_plan_cannot_busy_loop(tmp_path):
    """ISSUE 20 satellite 3: a plan that grows/shrinks at every boundary
    makes every child exit young — the controller must pace those
    relaunches through decorrelated backoff and then STOP, exactly like a
    crash loop."""
    clock = _Clock()
    marker = str(tmp_path / "ck.flex.drain.json")
    flip = {"n": 0}

    def launch(world, attempt):
        flip["n"] += 1
        m = {"world": 2 if flip["n"] % 2 else 8, "reason": "flap"}
        return _Child(76, 0.1, clock,
                      lambda: open(marker, "w").write(json.dumps(m)))

    sleeps = []
    ctl = _controller(tmp_path, launch, clock, max_rapid_restarts=3,
                      min_healthy_s=5.0, backoff_base_s=0.5,
                      backoff_max_s=4.0, sleep=sleeps.append)
    assert ctl.run() == 1
    j = FlexJournal.load(str(tmp_path / "flex.journal.json"))
    assert j.state == "failed" and "flapping" in j.get("fail_reason")
    # rapid exits 1..3 back off; the 4th trips the guard — and every
    # pause is a REAL decorrelated delay, not a zero-sleep spin
    assert len(sleeps) == 3
    assert all(0.5 <= d <= 4.0 for d in sleeps)
    assert ctl.summary()["launches"] == 4


def test_controller_crash_with_dead_rank_shrinks_to_survivors(tmp_path):
    clock = _Clock()
    base = str(tmp_path / "ck")
    script = iter([3, 0])  # crash rc, then clean finish

    def launch(world, attempt):
        return _Child(next(script), 60.0, clock)

    _hb(base, 3, age_s=900.0)  # rank 3 heartbeat went stale long ago
    ctl = _controller(tmp_path, launch, clock, plan_body={"world": 4},
                      initial_world=4, hb_base=base, dead_after_s=60.0)
    assert ctl.run() == 0
    s = ctl.summary()
    assert s["restarts"] == 1
    assert s["reshard_log"] == [
        {"from": 4, "to": 3, "reason": "dead_rank", "exact": False}]
    assert s["world"] == 3


def test_controller_preempt_relaunches_same_world(tmp_path):
    clock = _Clock()
    script = iter([75, 0])
    worlds = []

    def launch(world, attempt):
        worlds.append(world)
        return _Child(next(script), 60.0, clock)

    ctl = _controller(tmp_path, launch, clock)
    assert ctl.run() == 0
    assert worlds == [8, 8]
    s = ctl.summary()
    assert s["restarts"] == 1 and s["reshards"] == 0


def test_flex_journal_edges(tmp_path):
    j = FlexJournal(str(tmp_path / "j.json"))
    assert j.state == "idle"
    j.transition("running", world=8)
    j.transition("resharding")
    j.transition("running")
    with pytest.raises(FlexStateError, match="illegal"):
        j.transition("idle")
    j.transition("done")
    # terminal: a reloaded journal still refuses to move
    j2 = FlexJournal.load(str(tmp_path / "j.json"))
    assert j2.state == "done"
    with pytest.raises(FlexStateError):
        j2.transition("running")


# ---------------------------------------------------------------------------
# engine integration: the scripted 8 -> 2 -> 8 round trip (ISSUE 20 S4)
# ---------------------------------------------------------------------------

_STORM = {  # the elastic hard case: data learner + chunking + bagging
    "objective": "binary", "num_leaves": 7, "verbosity": -1,
    "tree_learner": "data", "device_chunk_size": 3,
    "bagging_freq": 2, "bagging_fraction": 0.8,
}


def _data(seed=3, n=400):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, 5)
    y = (X[:, 0] + 0.3 * rng.randn(n) > 0).astype(float)
    return X, y


def _train_storm(nm, rounds, **kw):
    X, y = _data(11)
    params = dict(_STORM, num_machines=nm)
    params.update(kw.pop("params", {}))
    return engine.train(params, lgb.Dataset(X, label=y), rounds,
                        verbose_eval=False, **kw)


def test_engine_storm_8_2_8_taxonomy(tmp_path, capfd):
    """One scripted plan drives the full drain/reshard round trip
    in-process: 8 drains at the shrink step, 2 drains at the grow step,
    8 completes — with the per-leg ``resil_reshards`` increments, the ulp
    warning EXACTLY once per world change, prefix byte-identity up to the
    first drain and structural identity throughout. The same storm with
    real subprocess children and exit codes runs in
    test_storm_subprocess_legs / helpers/flex_smoke.py."""
    ck = str(tmp_path / "storm.ckpt")
    plan_path = _plan(tmp_path, {"world": 8, "steps": [
        {"after_iteration": 1, "world": 2, "reason": "shrink"},
        {"after_iteration": 3, "world": 8, "reason": "grow"}]})
    ref = _train_storm(8, 6)
    ref_trees = ref._gbdt.trees()

    # leg 1: the shrink step latches a drain at the first boundary
    with pytest.raises(preempt.TrainingDrained) as ei:
        _train_storm(8, 6, checkpoint_path=ck, checkpoint_rounds=2,
                     flex_plan=plan_path)
    e1 = ei.value
    assert e1.exit_code == 76 and e1.reason == "drain"
    assert 1 <= e1.iteration < 6
    assert e1.checkpoint_path and os.path.exists(ck)
    it1 = ckpt_mod.load_checkpoint(ck).iteration
    assert it1 == e1.iteration, "the emergency save IS the drain boundary"
    m = watch.read_marker(watch.marker_path(ck))
    assert (m["world"], m["from_world"], m["reason"]) == (2, 8, "shrink")

    c = REGISTRY.counter("resil_reshards")
    shrink_l = {"from": "data@8", "to": "data@2"}
    grow_l = {"from": "data@2", "to": "data@8"}
    pre_s, pre_g = c.value(**shrink_l), c.value(**grow_l)

    # leg 2: resume at 2 — loud reshard in, grow step drains out
    capfd.readouterr()
    with pytest.raises(preempt.TrainingDrained) as ei:
        _train_storm(2, 6, resume_from=ck, checkpoint_path=ck,
                     checkpoint_rounds=2, flex_plan=plan_path,
                     params={"verbosity": 0})
    err = capfd.readouterr().err
    assert "resharding data@8" in err
    assert err.count("ulp") == 1, "the drift warning fires ONCE per change"
    assert c.value(**shrink_l) == pre_s + 1
    e2 = ei.value
    assert it1 < e2.iteration < 6
    m = watch.read_marker(watch.marker_path(ck))
    assert (m["world"], m["reason"]) == (8, "grow")

    # leg 3: resume at 8 — the grow step is satisfied; runs to completion
    capfd.readouterr()
    got = _train_storm(8, 6, resume_from=ck, flex_plan=plan_path,
                       params={"verbosity": 0})
    err = capfd.readouterr().err
    assert "resharding data@2" in err and err.count("ulp") == 1
    assert c.value(**grow_l) == pre_g + 1

    trees = got._gbdt.trees()
    assert len(trees) == len(ref_trees) == 6
    for i, (a, b) in enumerate(zip(ref_trees, trees)):
        assert np.array_equal(a.split_feature, b.split_feature), i
        assert np.array_equal(np.asarray(a.threshold),
                              np.asarray(b.threshold)), i
        if i < it1:
            assert np.array_equal(a.leaf_value, b.leaf_value), (
                "pre-drain tree %d must be byte-exact" % i)
        else:
            np.testing.assert_allclose(a.leaf_value, b.leaf_value,
                                       rtol=2e-4, atol=2e-6)


def test_engine_watchdog_composition(tmp_path, monkeypatch):
    """A collective deadline under an armed flex watcher becomes a DRAIN
    (the controller reshards onto the survivors) instead of a crash —
    and stays a plain crash when flex is off (no racing, no claiming)."""
    plan_path = _plan(tmp_path, {"world": 1})
    ck = str(tmp_path / "wd.ckpt")

    def hang(*a, **kw):
        raise watchdog.CollectiveDeadlineError("allreduce: rank 1 silent")

    monkeypatch.setattr(engine, "_boost_loop", hang)
    X, y = _data()
    params = {"objective": "binary", "num_leaves": 4, "verbosity": -1}
    with pytest.raises(watchdog.CollectiveDeadlineError):
        engine.train(dict(params), lgb.Dataset(X, label=y), 2,
                     verbose_eval=False)
    with pytest.raises(preempt.TrainingDrained) as ei:
        engine.train(dict(params, flex_plan=plan_path),
                     lgb.Dataset(X, label=y), 2, verbose_eval=False,
                     checkpoint_path=ck, checkpoint_rounds=1)
    assert ei.value.detail.startswith("collective_deadline")
    m = watch.read_marker(watch.marker_path(ck))
    assert m["world"] == 0, "target unknown: consult liveness evidence"
    assert m["reason"] == "collective_deadline"


# ---------------------------------------------------------------------------
# inertness: flex off must cost one env read and nothing else
# ---------------------------------------------------------------------------

class _CountingEnviron:
    def __init__(self, real):
        self._real = real
        self.reads = {}

    def get(self, key, default=None):
        self.reads[key] = self.reads.get(key, 0) + 1
        return self._real.get(key, default)

    def __getitem__(self, key):
        return self._real[key]

    def __contains__(self, key):
        return key in self._real


class _OsProxy:
    def __init__(self, real, environ):
        self._real = real
        self.environ = environ

    def __getattr__(self, name):
        return getattr(self._real, name)


def test_inert_when_off_bytes_and_env_reads(tmp_path, monkeypatch):
    """The inertness contract: with flex unset, engine.train pays exactly
    ONE env read of the arming variable — no flex import, no watcher, no
    marker — and an armed-but-no-change plan trains byte-identical
    model bodies. (The fresh-interpreter no-module-import proof is
    test_inert_subprocess_no_flex_import.)"""
    X, y = _data()
    params = {"objective": "binary", "num_leaves": 4, "verbosity": -1}

    env = _CountingEnviron(dict(os.environ))
    monkeypatch.setattr(engine, "os", _OsProxy(os, env))
    off = engine.train(dict(params), lgb.Dataset(X, label=y), 2,
                       verbose_eval=False)
    assert env.reads.get(capacity.ENV_PLAN) == 1

    # armed with a plan that never asks for a different world: same bytes
    plan_path = _plan(tmp_path, {"world": 1})  # serial mesh world is 1
    on = engine.train(dict(params, flex_plan=plan_path),
                      lgb.Dataset(X, label=y), 2, verbose_eval=False)
    body = lambda b: b.model_to_string().split("parameters:")[0]  # noqa
    assert body(off) == body(on)
    assert not os.path.exists(watch.marker_path(plan_path))

    # an EXPLICIT flex_plan="" disarms an ambient env plan
    monkeypatch.setenv(capacity.ENV_PLAN, str(tmp_path / "ambient.json"))

    def must_not_arm(*a, **kw):
        raise AssertionError("flex armed despite flex_plan=''")

    monkeypatch.setattr(watch, "maybe_watch", must_not_arm)
    off2 = engine.train(dict(params, flex_plan=""),
                        lgb.Dataset(X, label=y), 2, verbose_eval=False)
    assert body(off2) == body(off)


def test_inert_subprocess_no_flex_import(tmp_path):
    """Fresh interpreter: an unarmed training must never import
    lightgbm_tpu.flex (quick twin:
    test_inert_when_off_bytes_and_env_reads pins the env-read count and
    byte-identity in-process)."""
    code = r"""
import sys
sys.path.insert(0, %(repo)r)
from lightgbm_tpu.utils.platform import force_cpu_devices
force_cpu_devices(1)
import numpy as np
import lightgbm_tpu as lgb
from lightgbm_tpu import engine
rng = np.random.RandomState(3)
X = rng.randn(200, 4)
y = (X[:, 0] > 0).astype(float)
engine.train({"objective": "binary", "num_leaves": 4, "verbosity": -1},
             lgb.Dataset(X, label=y), 2, verbose_eval=False)
assert not any(m.startswith("lightgbm_tpu.flex") for m in sys.modules), \
    sorted(m for m in sys.modules if "flex" in m)
print("INERT-OK")
""" % {"repo": REPO}
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=300,
                       env=dict(os.environ, JAX_PLATFORMS="cpu",
                                XLA_FLAGS="--xla_force_host_platform_"
                                "device_count=1"))
    assert r.returncode == 0 and "INERT-OK" in r.stdout, (
        r.stdout[-500:], r.stderr[-800:])


# ---------------------------------------------------------------------------
# subprocess storm legs (heavy; slow-listed — quick twin:
# test_engine_storm_8_2_8_taxonomy)
# ---------------------------------------------------------------------------

_LEG = r"""
import os, sys
sys.path.insert(0, %(repo)r)
from lightgbm_tpu.utils.platform import force_cpu_devices
force_cpu_devices(int(sys.argv[1]))
import numpy as np
import lightgbm_tpu as lgb
from lightgbm_tpu import engine
from lightgbm_tpu.resil.preempt import TrainingPreempted
rng = np.random.RandomState(11)
X = rng.randn(400, 5)
y = (X[:, 0] + 0.3 * rng.randn(400) > 0).astype(float)
params = {"objective": "binary", "num_leaves": 7, "verbosity": -1,
          "tree_learner": "data", "device_chunk_size": 3}
kw = {"checkpoint_path": sys.argv[2], "checkpoint_rounds": 2,
      "flex_plan": sys.argv[3]}
if os.path.exists(sys.argv[2]):
    kw["resume_from"] = sys.argv[2]
try:
    bst = engine.train(params, lgb.Dataset(X, label=y), 6,
                       verbose_eval=False, **kw)
except TrainingPreempted as e:
    print("DRAINED iter=%%d" %% e.iteration, flush=True)
    sys.exit(e.exit_code)
print("TREES %%d" %% len(bst._gbdt.trees()), flush=True)
sys.exit(0)
"""


def test_storm_subprocess_legs(tmp_path):
    """The 8 -> 2 -> 8 storm with REAL process boundaries: each leg is a
    fresh interpreter at a different forced device count, and the 76 exit
    code crosses the process boundary exactly as the flexctl controller
    sees it."""
    ck = str(tmp_path / "sub.ckpt")
    plan_path = _plan(tmp_path, {"world": 8, "steps": [
        {"after_iteration": 1, "world": 2, "reason": "shrink"},
        {"after_iteration": 3, "world": 8, "reason": "grow"}]})
    code = _LEG % {"repo": REPO}

    def leg(ndev, expect_rc):
        # XLA_FLAGS is set EXPLICITLY: force_cpu_devices setdefaults it, so
        # a child inheriting the conftest's 8-device flag would keep 8
        r = subprocess.run(
            [sys.executable, "-c", code, str(ndev), ck, plan_path],
            capture_output=True, text=True, timeout=300,
            env=dict(os.environ, JAX_PLATFORMS="cpu",
                     XLA_FLAGS="--xla_force_host_platform_device_count=%d"
                     % ndev))
        assert r.returncode == expect_rc, (ndev, r.returncode,
                                           r.stdout[-300:], r.stderr[-600:])
        return r

    leg(8, 76)
    m = watch.read_marker(watch.marker_path(ck))
    assert (m["world"], m["reason"]) == (2, "shrink")
    leg(2, 76)
    m = watch.read_marker(watch.marker_path(ck))
    assert (m["world"], m["reason"]) == (8, "grow")
    r = leg(8, 0)
    assert "TREES 6" in r.stdout
