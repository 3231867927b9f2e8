"""podwatch: the live fleet-telemetry plane (obs/podwatch.py, ISSUE 19).

Three layers under test:

  * the per-rank recorder + module lifecycle — boundary samples into a
    bounded ring persisted through resil/atomic, enriched heartbeats,
    provably-off off-path (no threads, no instance, byte-identical models);
  * the opt-in scrape endpoint — /metrics, /health, /timeline answered
    LIVE against a real in-process training run;
  * the aggregator + verdicts — golden fixtures (tests/golden/podwatch/)
    drive EXACT straggler/stall/skew/dead numbers with pinned clocks, and
    a seeded 2-rank programmatic layout exercises the recorder→aggregator
    path end to end.

The 2-process world variant (live scrape of a separate process, straggler
seeded by a real sleep, CLI aggregation in a fresh interpreter) lives in
helpers/podwatch_smoke.py (check.sh --podwatch).
"""
import json
import os
import threading
import urllib.error
import urllib.request

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.obs import podwatch
from lightgbm_tpu.obs import registry as registry_mod
from lightgbm_tpu.resil import coord

GOLDEN = os.path.join(os.path.dirname(__file__), "golden", "podwatch")

#: every golden heartbeat carries time=1000.0 (the dead fixture's stale
#: rank 900.0); judged at NOW the fresh ones are 30s old — inside the 60s
#: default — and the stale one is 130s old
NOW = 1030.0


@pytest.fixture(autouse=True)
def _podwatch_pristine(monkeypatch):
    """Every test starts with telemetry off and leaves nothing armed."""
    monkeypatch.delenv(podwatch.ENV_TELEMETRY, raising=False)
    monkeypatch.delenv(podwatch.ENV_TELEMETRY_PORT, raising=False)
    yield
    podwatch.stop()
    podwatch.shutdown_server()


def _verdicts(summary, kind):
    return [v for v in summary["verdicts"] if v["verdict"] == kind]


# ---------------------------------------------------------------------------
# golden fixtures: exact verdict numbers, pinned clock, no training
# ---------------------------------------------------------------------------

def test_golden_healthy_pod_no_verdicts():
    summary = podwatch.pod_summary(os.path.join(GOLDEN, "healthy"), now=NOW)
    assert summary["world"] == 2
    assert summary["verdicts"] == []
    assert summary["iteration_spread"] == 0
    for r in ("0", "1"):
        rec = summary["ranks"][r]
        assert rec["samples"] == 14
        assert rec["iteration"] == 52
        assert rec["chunk_s"] == pytest.approx(0.1)
        assert rec["heartbeat"]["last_chunk_s"] == pytest.approx(0.1)


def test_golden_straggler_named_with_diverging_segment():
    summary = podwatch.pod_summary(os.path.join(GOLDEN, "straggler"), now=NOW)
    assert [v["verdict"] for v in summary["verdicts"]] == ["straggler"]
    v = summary["verdicts"][0]
    assert v["rank"] == 1
    ev = v["evidence"]
    # 0.4s vs the healthy rank's 0.1s: the LOWER pod median keeps the
    # judgement anchored to the healthy rank in a 2-rank pod
    assert ev["rank_chunk_s"] == pytest.approx(0.4)
    assert ev["pod_median_chunk_s"] == pytest.approx(0.1)
    assert ev["factor"] == pytest.approx(4.0)
    assert ev["threshold"] == podwatch.STRAGGLER_FACTOR
    # the 0.3s/boundary only rank 1 spends is tree growth
    assert ev["segment"] == "tree growth"
    assert ev["segment_rank_s"] == pytest.approx(0.3)
    assert ev["segment_pod_s"] == pytest.approx(0.0)
    assert "4.00x" in v["why"] and "tree growth" in v["why"]


def test_golden_stall_rate_collapse_vs_own_trailing():
    summary = podwatch.pod_summary(os.path.join(GOLDEN, "stall"), now=NOW)
    assert [v["verdict"] for v in summary["verdicts"]] == ["stall"]
    v = summary["verdicts"][0]
    assert v["rank"] == 0
    ev = v["evidence"]
    # 9 boundaries at 40 it/s then 3 at 2 it/s, same chunk size throughout
    assert ev["recent_it_per_s"] == pytest.approx(2.0)
    assert ev["trailing_it_per_s"] == pytest.approx(40.0)
    assert ev["collapse"] == pytest.approx(20.0)
    assert ev["threshold"] == podwatch.STALL_FACTOR


def test_golden_skew_names_laggard_and_leader():
    summary = podwatch.pod_summary(os.path.join(GOLDEN, "skew"), now=NOW)
    assert summary["iteration_spread"] == 100
    assert [v["verdict"] for v in summary["verdicts"]] == ["skew"]
    v = summary["verdicts"][0]
    assert v["rank"] == 1  # the verdict lands on the laggard
    ev = v["evidence"]
    assert ev["spread"] == 100
    assert ev["leader"] == 0 and ev["leader_iteration"] == 152
    assert ev["laggard"] == 1 and ev["laggard_iteration"] == 52


def test_golden_dead_stale_and_missing_heartbeats():
    summary = podwatch.pod_summary(os.path.join(GOLDEN, "dead"), now=NOW)
    dead = _verdicts(summary, "dead")
    assert [v["rank"] for v in dead] == [1, 2]
    stale, missing = dead
    assert stale["evidence"]["age_s"] == pytest.approx(130.0)
    # the verdict cites the blob's last known position without re-reading
    assert stale["evidence"]["heartbeat"]["iteration"] == 36
    assert "iteration 36" in stale["why"]
    assert missing["evidence"]["age_s"] is None
    assert "no readable heartbeat" in missing["why"]
    # world inferred from the shard that outlived its heartbeat
    assert summary["world"] == 3


def test_golden_warmup_boundaries_excluded():
    """The two compile-paying boundaries (10s serial + 8s chunk) sit in
    every golden shard; a mean that included them would be ~0.8s, not the
    0.1s steady state the healthy fixture asserts — this pins WARMUP_SKIP
    as the contract, not an accident of fixture shape."""
    timelines = podwatch.load_timelines(os.path.join(GOLDEN, "healthy"))
    raw = [s["dt_s"] for s in timelines[0]]
    assert raw[0] == 10.0 and raw[1] == 8.0  # the fixture really has them
    w = podwatch._window(timelines[0])
    assert len(w) == len(raw) - podwatch.WARMUP_SKIP
    assert all(s["dt_s"] == pytest.approx(0.1) for s in w)


# ---------------------------------------------------------------------------
# CLI: the operator's entry point over the same fixtures
# ---------------------------------------------------------------------------

def test_cli_json_and_strict_exit_codes(capsys):
    rc = podwatch.main([os.path.join(GOLDEN, "straggler"), "--json",
                        "--now", str(NOW)])
    assert rc == 0  # without --strict verdicts are informational
    out = json.loads(capsys.readouterr().out)
    assert [v["verdict"] for v in out["verdicts"]] == ["straggler"]

    rc = podwatch.main([os.path.join(GOLDEN, "straggler"), "--strict",
                        "--now", str(NOW)])
    assert rc == 3
    assert "VERDICT straggler rank 1" in capsys.readouterr().out

    # skew alone stays informational even under --strict
    rc = podwatch.main([os.path.join(GOLDEN, "skew"), "--strict",
                        "--now", str(NOW)])
    assert rc == 0

    rc = podwatch.main([os.path.join(GOLDEN, "healthy"), "--strict",
                        "--now", str(NOW)])
    assert rc == 0
    assert "pod looks healthy" in capsys.readouterr().out


def test_cli_max_age_overrides_dead_threshold(capsys):
    # at --max-age-s 200 the 130s-old heartbeat is still alive; only the
    # missing-file rank stays dead
    rc = podwatch.main([os.path.join(GOLDEN, "dead"), "--json",
                        "--max-age-s", "200", "--now", str(NOW)])
    assert rc == 0
    out = json.loads(capsys.readouterr().out)
    assert [v["rank"] for v in out["verdicts"]
            if v["verdict"] == "dead"] == [2]


# ---------------------------------------------------------------------------
# publication: podwatch_* gauges + the fleet_telemetry report section
# ---------------------------------------------------------------------------

def test_publish_gauges_and_report_section():
    reg = registry_mod.MetricsRegistry()
    summary = podwatch.pod_summary(os.path.join(GOLDEN, "straggler"), now=NOW)
    podwatch.publish(summary, registry=reg)
    g = reg.gauge("podwatch_verdicts").values()
    assert g[(("verdict", "straggler"),)] == 1
    # every kind publishes, so a cleared verdict re-publishes as 0
    for kind in ("stall", "skew", "dead"):
        assert g[(("verdict", kind),)] == 0
    ranks = reg.gauge("podwatch_rank_chunk_seconds").values()
    assert ranks[(("rank", "1"),)] == pytest.approx(0.4)
    expo = reg.prometheus_text()
    assert 'lgbtpu_podwatch_verdicts{verdict="straggler"} 1' in expo
    report = reg.run_report()
    assert report["fleet_telemetry"]["verdicts"][0]["rank"] == 1
    # ...and the HTML report grows its §Fleet telemetry section from it
    from lightgbm_tpu.obs import report as report_mod

    html = report_mod.render(metrics=report)
    assert "Fleet telemetry" in html
    assert "straggler" in html and "rank 1" in html


# ---------------------------------------------------------------------------
# recorder → aggregator, programmatically seeded 2-rank layout
# ---------------------------------------------------------------------------

def test_seeded_two_rank_recorders_roundtrip(tmp_path):
    d = str(tmp_path)
    for rank, dt in ((0, 0.05), (1, 0.25)):
        rec = podwatch.TelemetryRecorder(d, rank=rank, world=2)
        for i in range(podwatch.WARMUP_SKIP + podwatch.MIN_SAMPLES + 5):
            rec.sample(iteration=4 * i + 3, chunk=4, dt_s=dt)
    # shards + enriched heartbeats landed side by side
    assert os.path.exists(podwatch.timeline_path(d, 0))
    assert os.path.exists(coord.heartbeat_path(
        podwatch.heartbeat_base(d), 1))
    summary = podwatch.pod_summary(d)  # real clock: heartbeats are fresh
    assert summary["world"] == 2
    stragglers = _verdicts(summary, "straggler")
    assert [v["rank"] for v in stragglers] == [1]
    assert stragglers[0]["evidence"]["factor"] == pytest.approx(5.0)
    assert not _verdicts(summary, "dead")
    hb = summary["ranks"]["1"]["heartbeat"]
    assert hb["last_chunk_s"] == pytest.approx(0.25)
    assert hb["it_per_s"] > 0 and "mono" in hb


def test_recorder_ring_is_bounded_and_shard_tracks_it(tmp_path):
    rec = podwatch.TelemetryRecorder(str(tmp_path), rank=0)
    for i in range(podwatch.RING_SIZE + 40):
        rec.sample(iteration=i, chunk=1, dt_s=0.01)
    assert len(rec.window()) == podwatch.RING_SIZE
    with open(rec.path) as fh:
        lines = [l for l in fh.read().splitlines() if l.strip()]
    assert len(lines) == podwatch.RING_SIZE
    # the shard is the ring: oldest surviving record is sample 40
    assert json.loads(lines[0])["iteration"] == 40
    assert json.loads(lines[-1])["iteration"] == podwatch.RING_SIZE + 39


def test_load_timelines_tolerates_torn_lines(tmp_path):
    p = podwatch.timeline_path(str(tmp_path), 0)
    with open(p, "w") as fh:
        fh.write(json.dumps({"iteration": 1, "dt_s": 0.1}) + "\n")
        fh.write('{"iteration": 2, "dt_'  # torn mid-key
                 "\n")
        fh.write(json.dumps({"iteration": 3, "dt_s": 0.1}) + "\n")
    tl = podwatch.load_timelines(str(tmp_path))
    assert [s["iteration"] for s in tl[0]] == [1, 3]


# ---------------------------------------------------------------------------
# off-path: provably free when unset
# ---------------------------------------------------------------------------

def test_off_path_no_instance_no_threads_no_files(tmp_path):
    threads_before = threading.active_count()
    assert podwatch.maybe_start() is None
    assert podwatch.active() is None
    assert threading.active_count() == threads_before
    podwatch.note_boundary(0, 1, 0.1)  # must be a no-op, not an error
    assert os.listdir(str(tmp_path)) == []


def test_port_only_arms_server_but_not_recorder(monkeypatch):
    monkeypatch.setenv(podwatch.ENV_TELEMETRY_PORT, "0")
    assert podwatch.maybe_start() is None  # no recorder without the dir
    assert podwatch.active() is None
    srv = podwatch._SERVER
    assert srv is not None and srv.port > 0
    code, body = _get(srv.port, "/health")
    assert code == 200
    assert json.loads(body)["telemetry_armed"] is False


def test_bad_port_env_is_warned_not_fatal(monkeypatch):
    monkeypatch.setenv(podwatch.ENV_TELEMETRY_PORT, "not-a-port")
    assert podwatch.env_port() is None
    assert podwatch.maybe_start() is None


def test_nested_start_keeps_outer_recorder(tmp_path):
    outer = podwatch.start(str(tmp_path), rank=0)
    assert outer is not None and podwatch.active() is outer
    assert podwatch.start(str(tmp_path / "inner"), rank=0) is None
    assert podwatch.active() is outer


def test_telemetry_off_models_byte_identical(tmp_path, monkeypatch, rng):
    X = rng.randn(300, 6)
    y = (X[:, 0] + 0.3 * X[:, 1] > 0).astype(np.float64)
    params = {"objective": "binary", "num_leaves": 7, "verbosity": -1,
              "device_chunk_size": 4}

    def _train():
        return lgb.train(params, lgb.Dataset(X, label=y),
                         num_boost_round=12, verbose_eval=False)

    plain = _train().model_to_string()
    monkeypatch.setenv(podwatch.ENV_TELEMETRY, str(tmp_path))
    armed = _train().model_to_string()
    podwatch.stop()
    assert armed == plain, "telemetry recording changed the model bytes"
    # ...and the armed run really recorded
    assert os.path.exists(podwatch.timeline_path(str(tmp_path), 0))


# ---------------------------------------------------------------------------
# scrape endpoint: live round-trip against a real training run
# ---------------------------------------------------------------------------

def _get(port, path):
    with urllib.request.urlopen(
        "http://127.0.0.1:%d%s" % (port, path), timeout=5
    ) as resp:
        return resp.status, resp.read().decode("utf-8")


def test_scrape_roundtrip_during_training(tmp_path, monkeypatch, rng):
    monkeypatch.setenv(podwatch.ENV_TELEMETRY, str(tmp_path))
    monkeypatch.setenv(podwatch.ENV_TELEMETRY_PORT, "0")  # pick a free port
    X = rng.randn(400, 6)
    y = (X[:, 0] > 0).astype(np.float64)
    seen = {}

    def scrape_mid_train(env):
        if env.iteration < 8 or seen:
            return  # past compile warm-up, once only
        port = podwatch._SERVER.port
        seen["health"] = json.loads(_get(port, "/health")[1])
        seen["metrics"] = _get(port, "/metrics")[1]
        seen["timeline"] = json.loads(_get(port, "/timeline")[1])
    scrape_mid_train.order = 99

    lgb.train({"objective": "binary", "num_leaves": 7, "verbosity": -1,
               "device_chunk_size": 4},
              lgb.Dataset(X, label=y), num_boost_round=24,
              callbacks=[scrape_mid_train], verbose_eval=False)

    h = seen["health"]
    assert h["telemetry_armed"] is True
    assert h["rank"] == 0 and h["world"] == 1
    assert h["last_iteration"] is not None
    assert h["last_boundary_age_s"] >= 0
    assert "lgbtpu_train_iterations_total" in seen["metrics"]
    tl = seen["timeline"]
    assert tl["telemetry_armed"] and tl["rank"] == 0
    assert tl["samples"], "no boundary samples mid-run"
    s = tl["samples"][-1]
    assert {"iteration", "chunk", "dt_s", "it_per_s",
            "counters"} <= set(s)
    # training over: the recorder closed, the listener survives by design
    assert podwatch.active() is None
    assert podwatch._SERVER is not None
    assert json.loads(
        _get(podwatch._SERVER.port, "/health")[1]
    )["telemetry_armed"] is False
    # the shard feeds the aggregator directly
    summary = podwatch.pod_summary(str(tmp_path))
    assert summary["ranks"]["0"]["samples"] >= 3
    assert not _verdicts(summary, "dead")


def test_scrape_404_status(monkeypatch):
    monkeypatch.setenv(podwatch.ENV_TELEMETRY_PORT, "0")
    podwatch.maybe_start()
    port = podwatch._SERVER.port
    with pytest.raises(urllib.error.HTTPError) as ei:
        urllib.request.urlopen(
            "http://127.0.0.1:%d/nope" % port, timeout=5)
    assert ei.value.code == 404


# ---------------------------------------------------------------------------
# heartbeat enrichment (satellite: resil/coord)
# ---------------------------------------------------------------------------

def test_heartbeat_extra_merges_under_core_keys(tmp_path):
    base = str(tmp_path / "ck")
    coord.heartbeat(base, 7, rank=0,
                    extra={"last_chunk_s": 0.5, "it_per_s": 8.0,
                           "rank": 999})  # core keys must win
    blob = coord.read_heartbeats(base, 1)[0]
    assert blob["rank"] == 0 and blob["iteration"] == 7
    assert blob["last_chunk_s"] == 0.5 and blob["it_per_s"] == 8.0
    assert blob["mono"] > 0 and blob["pid"] == os.getpid()


def test_stale_ranks_tuple_compat_and_evidence(tmp_path):
    base = str(tmp_path / "ck")
    # an OLD-shape blob (pre-enrichment: no mono, no extras) still reads
    with open(coord.heartbeat_path(base, 0), "w") as fh:
        json.dump({"rank": 0, "iteration": 3, "pid": 1, "time": 1000.0}, fh)
    stale = coord.stale_ranks(base, 2, max_age_s=60.0, now=1130.0)
    # PR 14 callers' tuple shape holds exactly
    assert stale == [(0, 130.0), (1, None)]
    assert [s.rank for s in stale] == [0, 1]
    assert stale[0].age == pytest.approx(130.0)
    assert stale[0].evidence["iteration"] == 3
    assert stale[1].evidence == {}
    # fresh heartbeat: empty list, still `== []` as PR 14 asserts
    coord.heartbeat(base, 4, rank=0)
    assert coord.stale_ranks(base, 1, max_age_s=60.0) == []


def test_read_heartbeats_skips_torn_files(tmp_path):
    base = str(tmp_path / "ck")
    coord.heartbeat(base, 1, rank=0)
    with open(coord.heartbeat_path(base, 1), "w") as fh:
        fh.write('{"rank": 1, "iter')  # torn
    blobs = coord.read_heartbeats(base, 3)
    assert sorted(blobs) == [0]


# ---------------------------------------------------------------------------
# the verdict→action plane flexctl consumes (ISSUE 20): heartbeat ages must
# be judged by a cross-host-comparable clock, and dead verdicts map to
# drain_survivors only when the age evidence is real
# ---------------------------------------------------------------------------

def test_heartbeat_age_mtime_fallback_and_age_source(tmp_path):
    """A blob without a wall ``time`` stamp (foreign/legacy writer) is aged
    by the heartbeat FILE's mtime — never by the per-process mono clock,
    whose epoch is the writer's start and means nothing cross-rank."""
    base = str(tmp_path / "ck")
    now = 1030.0
    # rank 0: no wall stamp, mono ancient (would read as ~1030s "old" if a
    # broken implementation compared it to now); mtime says 130s
    p0 = coord.heartbeat_path(base, 0)
    with open(p0, "w", encoding="utf-8") as fh:
        json.dump({"rank": 0, "iteration": 36, "mono": 1.5}, fh)
    os.utime(p0, (now - 130.0, now - 130.0))
    # rank 1: fresh wall stamp wins even though mono is equally ancient
    p1 = coord.heartbeat_path(base, 1)
    with open(p1, "w", encoding="utf-8") as fh:
        json.dump({"rank": 1, "iteration": 40, "time": now - 5.0,
                   "mono": 1.5}, fh)
    os.utime(p1, (now - 500.0, now - 500.0))  # stale mtime must NOT matter

    stale = coord.stale_ranks(base, 2, 60.0, now=now)
    assert [s[0] for s in stale] == [0]
    assert stale[0][1] == pytest.approx(130.0, abs=1.0)
    assert stale[0].evidence["age_source"] == "mtime"

    # the direct unit contract, including the missing-file terminal case
    with open(p1, encoding="utf-8") as fh:
        blob = json.load(fh)
    assert coord.heartbeat_age(p1, blob, now) == (pytest.approx(5.0), "wall")
    gone = str(tmp_path / "ck.hb.rank9.json")
    assert coord.heartbeat_age(gone, {}, now) == (None, "missing")


def test_actions_for_verdict_decision_table():
    """flexctl's side of the contract: only a dead verdict WITH age
    evidence reshards; a missing heartbeat file (age None) is
    startup-ambiguous and is demoted to watch, like every advisory
    verdict."""
    summary = {"verdicts": [
        {"rank": 1, "verdict": "dead", "why": "stale",
         "evidence": {"age_s": 130.0}},
        {"rank": 2, "verdict": "dead", "why": "no file",
         "evidence": {"age_s": None}},
        {"rank": 0, "verdict": "straggler", "why": "slow", "evidence": {}},
        {"rank": 0, "verdict": "stall", "why": "collapsed", "evidence": {}},
        {"rank": 3, "verdict": "skew", "why": "behind", "evidence": {}},
    ]}
    acts = {(a["rank"], a["verdict"]): a["action"]
            for a in podwatch.actions_for(summary)}
    assert acts == {
        (1, "dead"): "drain_survivors",
        (2, "dead"): "watch",
        (0, "straggler"): "watch",
        (0, "stall"): "watch",
        (3, "skew"): "watch",
    }
    assert podwatch.actions_for({}) == []

    # against the golden dead fixture: the stale rank reshards, the
    # missing-heartbeat rank stays advisory, and evidence carries the
    # clock that judged it
    summary = podwatch.pod_summary(os.path.join(GOLDEN, "dead"), now=NOW)
    acts = {a["rank"]: a["action"] for a in podwatch.actions_for(summary)
            if a["verdict"] == "dead"}
    assert acts == {1: "drain_survivors", 2: "watch"}
    stale = [v for v in summary["verdicts"]
             if v["verdict"] == "dead" and v["rank"] == 1][0]
    assert stale["evidence"]["age_source"] == "wall"
