"""Distributed observability (obs/dist.py + trace merge).

Runs on the conftest 8-virtual-CPU-device mesh:

 * pod-wide aggregation: registry snapshot merge (counters == per-process
   sums, gauges keep ``process=`` provenance), the file-based fallback,
   and the Chrome-trace merge (disjoint pids, dropped-events marker
   preserved);
 * shard skew: the N=1003-over-8 padding shape's known 7x126+121 row
   split in ``train_shard_rows{device=}``;
 * the flight manifest's mesh and process fields.
"""
import json
import os
import subprocess
import sys

import numpy as np

import jax

import lightgbm_tpu as lgb
from lightgbm_tpu.obs import dist, registry as registry_mod, trace as trace_mod
from lightgbm_tpu.obs.registry import MetricsRegistry

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _data(n=600, f=5, seed=3):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, f)
    y = (X[:, 0] + 0.5 * X[:, 1] > 0).astype(float)
    return X, y


def _train(params, X, y, rounds):
    p = {"objective": "binary", "num_leaves": 8, "verbosity": -1,
         "tree_learner": "data", "num_machines": 2, "min_data_in_leaf": 5}
    p.update(params)
    return lgb.train(p, lgb.Dataset(X, label=y), rounds)


# ---------------------------------------------------------------------------
# registry snapshot + merge
# ---------------------------------------------------------------------------

def _two_snaps():
    a, b = MetricsRegistry(), MetricsRegistry()
    a.counter("reqs").inc(3)
    a.counter("reqs").inc(2, model="m1")
    a.gauge("depth").set(4.0)
    a.histogram("lat").record(1.0)
    b.counter("reqs").inc(7)
    b.counter("reqs").inc(1, model="m1")
    b.gauge("depth").set(9.0)
    sa = dist.snapshot(a)
    sa["process"] = 0
    sb = dist.snapshot(b)
    sb["process"] = 1
    return sa, sb


def test_merge_counters_sum_and_gauge_provenance():
    sa, sb = _two_snaps()
    merged = dist.merge_snapshots([sa, sb])
    # counters: summed over identical (name, labels) across processes
    assert merged.counter("reqs").value() == 10
    assert merged.counter("reqs").value(model="m1") == 3
    # gauges: one entry per process, tagged with the provenance label
    vals = merged.gauge("depth").values()
    assert vals[(("process", "0"),)] == 4.0
    assert vals[(("process", "1"),)] == 9.0
    expo = merged.prometheus_text()
    assert 'process="0"' in expo and 'process="1"' in expo
    assert "lgbtpu_reqs_total 10" in expo
    # histogram summaries surface as stat-labeled gauges + summed count
    assert merged.counter("lat_count").value() == 1
    rep = dist.merged_run_report([sa, sb])
    assert rep["process_count"] == 2
    assert rep["counters"]["reqs"] == 10


def test_merge_snapshot_files_roundtrip(tmp_path):
    sa, sb = _two_snaps()
    for s in (sa, sb):
        with open(tmp_path / ("reg.rank%d.json" % s["process"]), "w") as fh:
            json.dump(s, fh)
    snaps = dist.merge_snapshot_files(str(tmp_path / "reg.rank*.json"))
    assert [s["process"] for s in snaps] == [0, 1]
    merged = dist.merge_snapshots(snaps)
    assert merged.counter("reqs").value() == 10


def test_gather_snapshots_single_process_fallback():
    # one process (the test world): the gather is the local snapshot alone
    out = dist.gather_snapshots({"process": 0, "counters": {}})
    assert out == [{"process": 0, "counters": {}}]


# ---------------------------------------------------------------------------
# trace merge + rank suffix
# ---------------------------------------------------------------------------

def _mini_trace(path, pid, dropped=0):
    doc = {
        "traceEvents": [
            {"ph": "M", "name": "thread_name", "pid": pid, "tid": 0,
             "args": {"name": "main"}},
            {"ph": "X", "name": "step", "cat": "t", "pid": pid, "tid": 0,
             "ts": 1.0, "dur": 5.0},
        ],
        "otherData": ({"dropped_events": dropped} if dropped else {}),
    }
    with open(path, "w") as fh:
        json.dump(doc, fh)


def test_trace_merge_disjoint_pids_and_dropped_marker(tmp_path):
    a = tmp_path / "t.rank0.json"
    b = tmp_path / "t.rank1.json"
    _mini_trace(a, pid=42)
    _mini_trace(b, pid=42, dropped=7)  # SAME pid in both source files
    out = tmp_path / "merged.json"
    stats = trace_mod.merge_traces(str(out), [str(a), str(b)])
    assert stats["files"] == 2 and stats["pids"] == 2
    assert stats["dropped"] == 7
    doc = json.load(open(out))
    pids = {ev["pid"] for ev in doc["traceEvents"]}
    assert len(pids) == 2, "same-pid events from two files must not collide"
    assert doc["otherData"]["dropped_events"] == 7
    names = [ev for ev in doc["traceEvents"]
             if ev.get("name") == "process_name"]
    assert len(names) == 2  # one provenance row per source process


def test_trace_merge_cli(tmp_path, capsys):
    a = tmp_path / "x1.json"
    _mini_trace(a, pid=1)
    out = tmp_path / "m.json"
    rc = trace_mod.main(["merge", "-o", str(out), str(tmp_path / "x*.json")])
    assert rc == 0 and out.exists()
    assert "1 file(s)" in capsys.readouterr().out


def test_trace_rank_suffix_under_distributed(tmp_path, monkeypatch):
    monkeypatch.setenv(trace_mod.ENV_TRACE, str(tmp_path / "t.json"))
    monkeypatch.setattr(jax, "process_count", lambda: 2)
    monkeypatch.setattr(jax, "process_index", lambda: 1)
    tr = trace_mod.start()
    try:
        assert tr.path.endswith("t.json.rank1")
    finally:
        trace_mod.stop()
    # explicit caller paths are never rewritten
    tr = trace_mod.start(str(tmp_path / "explicit.json"))
    try:
        assert tr.path.endswith("explicit.json")
    finally:
        trace_mod.stop()


# ---------------------------------------------------------------------------
# shard skew
# ---------------------------------------------------------------------------

def test_shard_rows_gauge_reports_1003_over_8_split():
    X, y = _data(n=1003, seed=5)
    _train({"num_machines": 8, "device_chunk_size": 2}, X, y, 3)
    vals = registry_mod.REGISTRY.gauge("train_shard_rows").values()
    by_dev = {k: v for k, v in vals.items()
              if any(lk == "device" for lk, _ in k)}
    assert len(by_dev) >= 8
    counts = sorted(int(v) for v in by_dev.values())[-8:]
    assert counts == [121] + [126] * 7
    assert dist.shard_valid_counts(1003, 8) == [126] * 7 + [121]


# ---------------------------------------------------------------------------
# flight manifest
# ---------------------------------------------------------------------------

def test_flight_manifest_carries_mesh_and_process(tmp_path):
    from lightgbm_tpu.obs import flight

    X, y = _data(n=300)
    log_path = tmp_path / "run.jsonl"
    p = {"objective": "binary", "num_leaves": 6, "verbosity": -1,
         "tree_learner": "data", "num_machines": 2,
         "flight_record": str(log_path)}
    lgb.train(p, lgb.Dataset(X, label=y), 3)
    rec = flight.load(str(log_path))
    man = rec["manifest"]
    assert man["process_index"] == 0 and man["process_count"] == 1
    assert man["mesh"] == {"learner": "data", "axes": {"data": 2}}


# ---------------------------------------------------------------------------
# subprocess: the real two-rank file-based merge path (cheap worker)
# ---------------------------------------------------------------------------

WORKER = """
import json, sys
sys.path.insert(0, %r)
from lightgbm_tpu.obs import dist, registry
rank = int(sys.argv[1])
registry.REGISTRY.counter("mp_file_total").inc(5 * (rank + 1))
registry.REGISTRY.gauge("mp_file_rank").set(float(rank))
snap = dist.snapshot()
snap["process"] = rank
json.dump(snap, open(sys.argv[2], "w"))
print("DONE")
""" % REPO


def test_two_rank_file_merge_subprocess(tmp_path):
    for rank in range(2):
        out = subprocess.run(
            [sys.executable, "-c", WORKER, str(rank),
             str(tmp_path / ("s.rank%d.json" % rank))],
            capture_output=True, text=True,
            env=dict(os.environ, JAX_PLATFORMS="cpu"),
        )
        assert out.returncode == 0, out.stderr[-800:]
    merged = dist.merge_snapshots(
        dist.merge_snapshot_files(str(tmp_path / "s.rank*.json"))
    )
    assert merged.counter("mp_file_total").value() == 15
    expo = merged.prometheus_text()
    assert 'process="0"' in expo and 'process="1"' in expo
