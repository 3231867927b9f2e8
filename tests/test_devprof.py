"""Device-timeline auditor tests (obs/devprof.py, ISSUE 14).

The parser tests run against CHECKED-IN golden captures
(tests/golden/devprof/, regenerated by make_fixtures.py — every expected
number below derives from that file's literals) and synthetic in-memory
documents: no jax, no chip required. One real-capture smoke at the end
drives ``devprof.capture`` around live jitted dispatch on the CPU backend
and asserts a parseable, non-empty, attributed timeline.
"""
import json
import os

import pytest

import helpers.bench_diff as bench_diff
from lightgbm_tpu.obs import devprof
from lightgbm_tpu.obs import registry as registry_mod
from lightgbm_tpu.obs import report as report_mod
from lightgbm_tpu.obs import trace as trace_mod

GOLD = os.path.join(os.path.dirname(__file__), "golden", "devprof")
TPU_CAP = os.path.join(GOLD, "tpu_capture")
RANK_CAP = os.path.join(GOLD, "rank_capture")


def _golden(**kw):
    return devprof.analyze_dir(
        TPU_CAP, device_kind="TPU v5e", platform="tpu", **kw
    )


# ---------------------------------------------------------------------------
# golden capture: attribution / busy-idle / transfers / roofline / verdict
# ---------------------------------------------------------------------------

def test_golden_segment_attribution_exact():
    rec = _golden()
    assert rec["lanes_source"] == "device"
    # self-times: fusion.123 (2000-500 nested) + nested.child (500) +
    # scatter-add.7 (1200) land in hist_build; cumsum.2 in split_scan;
    # loop_unrolled.9 overlaps NO annotation -> unattributed, kept loudly
    assert rec["segments"] == {
        "hist_build": 0.0032, "split_scan": 0.0009, "unattributed": 0.0007,
    }
    assert rec["attributed_fraction"] == pytest.approx(4100 / 4800, abs=1e-4)
    # the acceptance bar: >= 80% of device self-time named
    assert rec["attributed_fraction"] >= 0.8


def test_analyze_is_idempotent_on_one_timeline():
    """Re-analyzing a loaded Timeline (e.g. to add iters/device_kind after
    a first look) must not double-subtract nested self-time or keep stale
    segments."""
    tl = devprof.Timeline.from_dir(TPU_CAP)
    r1 = devprof.analyze(tl, device_kind="TPU v5e", platform="tpu")
    r2 = devprof.analyze(tl, device_kind="TPU v5e", platform="tpu", iters=8)
    assert r2["segments"] == r1["segments"]
    assert r2["attributed_fraction"] == r1["attributed_fraction"]
    assert r2["top_ops"][0]["self_s"] == r1["top_ops"][0]["self_s"]


def test_golden_innermost_annotation_wins():
    """Every device op also sits inside the outer 'tree growth' and
    'train.iteration' spans — attribution must pick the innermost
    (prof.hist_build / prof.split_scan), not the containers."""
    rec = _golden()
    segs = set(rec["segments"])
    assert "tree growth" not in segs and "train.iteration" not in segs


def test_golden_busy_idle_and_dispatch_gaps():
    rec = _golden()
    # window: first transfer (ts=300) .. last transfer end (10450); the
    # 0.5s profiler-internal start_trace frame must NOT stretch it
    assert rec["window_s"] == pytest.approx(10150 / 1e6, abs=1e-9)
    assert rec["device_busy_fraction"] == pytest.approx(
        4800 / 10150, abs=1e-4)
    gaps = rec["dispatch_gaps"]
    assert gaps["count"] == 3
    assert gaps["max_ms"] == pytest.approx(3.4, abs=1e-6)
    assert gaps["histogram"] == {
        "<0.1ms": 0, "0.1-1ms": 2, "1-10ms": 1, ">=10ms": 0,
    }


def test_golden_transfers_time_and_bytes():
    rec = _golden(iters=2)
    tr = rec["transfers"]
    assert tr["h2d"] == {"count": 1, "seconds": 0.00015, "bytes": 1048576}
    assert tr["d2h"] == {"count": 1, "seconds": 0.0001, "bytes": 2048}
    assert tr["total_seconds"] == pytest.approx(0.00025)
    assert tr["per_iteration"]["seconds"] == pytest.approx(0.000125)
    assert tr["per_iteration"]["bytes"] == (1048576 + 2048) // 2
    assert rec["iters"] == 2


def test_golden_top_ops_and_roofline_placement():
    rec = _golden()
    tops = rec["top_ops"]
    assert [t["op"] for t in tops] == [
        "fusion.123", "scatter-add.7", "cumsum.2", "loop_unrolled.9",
        "nested.child",
    ]
    pin = tops[0]
    assert pin["segment"] == "hist_build"
    assert pin["self_s"] == pytest.approx(0.0015)
    assert pin["share"] == pytest.approx(1500 / 4800, abs=1e-4)
    # 4e9 flops over 1.5ms against the v5e published peak (197 TF bf16)
    assert pin["peak_flops_fraction"] == pytest.approx(
        (4e9 / 0.0015) / 197e12, rel=1e-3)
    assert pin["achieved_bytes_per_s"] == pytest.approx(1e8 / 0.0015,
                                                        rel=1e-3)
    assert "v5e" in rec["roofline_chip"]
    # the MFU-pinning op is named
    assert rec["mfu_pin"]["op"] == "fusion.123"
    # an op without cost args carries no fabricated roofline row
    unk = next(t for t in tops if t["op"] == "loop_unrolled.9")
    assert "peak_flops_fraction" not in unk


def test_golden_verdict_device_bound_with_evidence():
    rec = _golden()
    v = rec["verdict"]
    assert v["bound"] == "device-bound"
    ev = v["evidence"]
    assert ev["device_busy_fraction"] == rec["device_busy_fraction"]
    assert ev["transfer_seconds"] == pytest.approx(0.00025)
    assert ev["lanes_source"] == "device"
    assert "busy" in v["why"]


# ---------------------------------------------------------------------------
# synthetic documents: the other verdicts + fallbacks
# ---------------------------------------------------------------------------

def _doc(events):
    meta = [
        {"ph": "M", "name": "process_name", "pid": 1,
         "args": {"name": "/host:CPU"}},
        {"ph": "M", "name": "process_name", "pid": 9,
         "args": {"name": "/device:TPU:0"}},
    ]
    return {"traceEvents": meta + events}


def test_verdict_host_bound_on_sparse_device_lane():
    tl = devprof.Timeline.from_docs([_doc([
        {"ph": "X", "name": "op.a", "pid": 9, "tid": 1, "ts": 0,
         "dur": 100},
        {"ph": "X", "name": "op.b", "pid": 9, "tid": 1, "ts": 9900,
         "dur": 100},
    ])])
    rec = devprof.analyze(tl)
    assert rec["device_busy_fraction"] == pytest.approx(0.02)
    assert rec["verdict"]["bound"] == "host-bound"
    assert "dispatch gaps" in rec["verdict"]["why"]


def test_verdict_transfer_bound():
    tl = devprof.Timeline.from_docs([_doc([
        {"ph": "X", "name": "op.a", "pid": 9, "tid": 1, "ts": 0,
         "dur": 4000},
        {"ph": "X", "name": "TransferToDevice", "pid": 1, "tid": 2,
         "ts": 4000, "dur": 4000, "args": {"bytes": 123}},
    ])])
    rec = devprof.analyze(tl)
    assert rec["transfer_fraction"] == pytest.approx(0.5)
    assert rec["verdict"]["bound"] == "transfer-bound"


def test_no_annotations_means_unattributed_not_dropped():
    tl = devprof.Timeline.from_docs([_doc([
        {"ph": "X", "name": "op.a", "pid": 9, "tid": 1, "ts": 0,
         "dur": 500},
    ])])
    rec = devprof.analyze(tl)
    assert rec["segments"] == {"unattributed": 0.0005}
    assert rec["attributed_fraction"] == 0.0
    assert rec["top_ops"][0]["segment"] == "unattributed"


def test_host_executor_proxy_when_no_device_lanes():
    tl = devprof.Timeline.from_docs([{"traceEvents": [
        {"ph": "M", "name": "process_name", "pid": 1,
         "args": {"name": "/host:CPU"}},
        {"ph": "X", "name": "tree growth", "pid": 1, "tid": 1, "ts": 0,
         "dur": 5000},
        {"ph": "X", "name": "TfrtCpuExecutable::Execute", "pid": 1,
         "tid": 1, "ts": 100, "dur": 2000},
    ]}])
    rec = devprof.analyze(tl)
    assert rec["lanes_source"] == "host_executor"
    assert rec["segments"] == {"tree growth": 0.002}
    assert "proxy" in rec["verdict"]["why"]


def test_empty_capture_reads_empty_verdict():
    rec = devprof.analyze(devprof.Timeline.from_docs([{"traceEvents": []}]))
    assert rec["verdict"]["bound"] == "empty"


def test_segment_vocabulary_mapping():
    f = devprof.segment_for_span
    assert f("prof.hist_build") == "hist_build"
    assert f("dist.hist_combine") == "hist_combine"
    assert f("tree growth") == "tree growth"
    assert f("train.iteration") == "train.iteration"
    assert f("serve.batch_dispatch") == "serve.batch_dispatch"
    assert f("PjitFunction(f)") is None
    assert f("my_random_thing") is None
    assert f("$profiler.py:91 start_trace") is None


# ---------------------------------------------------------------------------
# per-rank folding + trace merge interop
# ---------------------------------------------------------------------------

def test_rank_suffixed_dirs_fold_into_one_parse():
    """maybe_profile's .rank<N> suffix leaves NO base dir behind — the
    parser must still find and fold every rank's capture."""
    files = devprof.find_trace_files(RANK_CAP)
    assert len(files) == 2
    rec = devprof.analyze(devprof.Timeline.load(files))
    assert len(rec["lanes"]) == 2  # one lane per rank's device pid
    assert rec["transfers"]["h2d"]["bytes"] == 4096 + 8192
    assert rec["segments"] == {"hist_build": 0.002}


def test_trace_merge_reads_gz_and_expands_capture_dirs(tmp_path):
    out = str(tmp_path / "merged.json")
    rc = trace_mod.main(["merge", "-o", out, TPU_CAP])
    assert rc == 0
    with open(out) as fh:
        doc = json.load(fh)
    names = {e.get("name") for e in doc["traceEvents"]}
    assert "fusion.123" in names and "prof.hist_build" in names
    # disjoint-pid remap still applies to profiler files
    pids = {e["pid"] for e in doc["traceEvents"] if e.get("ph") == "X"}
    assert pids


def test_maybe_profile_rank_suffixes_env_dir(monkeypatch, tmp_path):
    """Under an initialized multi-process world, two ranks sharing one
    LIGHTGBM_TPU_PROFILE dir must diverge to .rank<N> captures (the PR 9
    LIGHTGBM_TPU_TRACE fix, now applied to the profiler dir too)."""
    import jax

    from lightgbm_tpu.utils import timer

    seen = {}
    monkeypatch.setattr(jax, "process_count", lambda: 2)
    monkeypatch.setattr(jax, "process_index", lambda: 1)
    monkeypatch.setattr(
        jax.profiler, "start_trace", lambda d: seen.setdefault("dir", d))
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
    target = str(tmp_path / "prof_dir")
    monkeypatch.setenv(timer.ENV_PROFILE, target)
    with timer.maybe_profile():
        pass
    assert seen["dir"] == target + ".rank1"


# ---------------------------------------------------------------------------
# publication: gauges, run-report section, HTML page
# ---------------------------------------------------------------------------

def test_publish_gauges_and_report_section():
    rec = _golden()
    reg = registry_mod.MetricsRegistry()
    devprof.publish(rec, registry=reg)
    rr = reg.run_report()
    g = rr["gauges"]
    assert g["devprof_device_busy_fraction"] == pytest.approx(
        rec["device_busy_fraction"])
    assert g["devprof_transfer_bytes{direction=h2d}"] == 1048576
    assert g["devprof_segment_self_seconds{segment=hist_build}"] == \
        pytest.approx(0.0032)
    assert g["devprof_bound{verdict=device-bound}"] == 1.0
    assert rr["device_timeline"]["verdict"]["bound"] == "device-bound"


def test_republish_with_changed_verdict_zeroes_old_label():
    """Two publishes with different verdicts must leave exactly one
    devprof_bound{verdict=}=1 row — a scraper reads the CURRENT verdict."""
    reg = registry_mod.MetricsRegistry()
    rec = _golden()
    assert rec["verdict"]["bound"] == "device-bound"
    host = dict(rec)
    host["verdict"] = {"bound": "host-bound", "why": "x", "evidence": {}}
    devprof.publish(host, registry=reg)
    devprof.publish(rec, registry=reg)
    g = reg.run_report()["gauges"]
    assert g["devprof_bound{verdict=device-bound}"] == 1.0
    assert g["devprof_bound{verdict=host-bound}"] == 0.0


def test_report_renders_device_timeline_page():
    rec = _golden()
    html = report_mod.render(metrics={"device_timeline": rec},
                             title="devprof test")
    assert "Device timeline" in html
    assert "device-bound" in html
    assert "fusion.123" in html
    assert "unattributed" in html
    assert "dispatch-gap" in html


# ---------------------------------------------------------------------------
# bench_diff: WARN-never-FAIL drift rows
# ---------------------------------------------------------------------------

def _bench_rec(**kw):
    rec = {"metric": "m", "platform": "cpu"}
    rec.update(kw)
    return rec


def test_bench_diff_busy_fraction_drop_warns_never_fails():
    rows, failed = bench_diff.compare(
        _bench_rec(device_busy_fraction=0.30),
        _bench_rec(device_busy_fraction=0.80),
    )
    row = next(r for r in rows if r["metric"] == "device_busy_fraction")
    assert row["status"] == bench_diff.WARN
    assert not failed


def test_bench_diff_busy_fraction_small_move_passes():
    rows, failed = bench_diff.compare(
        _bench_rec(device_busy_fraction=0.72),
        _bench_rec(device_busy_fraction=0.80),
    )
    row = next(r for r in rows if r["metric"] == "device_busy_fraction")
    assert row["status"] == bench_diff.PASS and not failed


def test_bench_diff_busy_fraction_platform_mismatch_skips():
    rows, _ = bench_diff.compare(
        _bench_rec(device_busy_fraction=0.30, platform="tpu"),
        _bench_rec(device_busy_fraction=0.80),
    )
    row = next(r for r in rows if r["metric"] == "device_busy_fraction")
    assert row["status"] == bench_diff.SKIP


def test_bench_diff_busy_fraction_absent_in_one_skips():
    rows, _ = bench_diff.compare(
        _bench_rec(device_busy_fraction=0.30), _bench_rec(),
    )
    row = next(r for r in rows if r["metric"] == "device_busy_fraction")
    assert row["status"] == bench_diff.SKIP


def test_bench_diff_transfer_seconds_doubling_warns():
    rows, failed = bench_diff.compare(
        _bench_rec(device_busy_fraction=0.8, transfer_seconds=0.05),
        _bench_rec(device_busy_fraction=0.8, transfer_seconds=0.01),
    )
    row = next(r for r in rows if r["metric"] == "transfer_seconds")
    assert row["status"] == bench_diff.WARN and not failed


def test_bench_diff_transfers_appearing_from_zero_baseline_warns():
    """A 0.0s baseline (clean device-resident run) growing real transfer
    time is the exact regression the row exists for — a falsy-zero guard
    must not swallow it."""
    rows, failed = bench_diff.compare(
        _bench_rec(device_busy_fraction=0.8, transfer_seconds=5.0),
        _bench_rec(device_busy_fraction=0.8, transfer_seconds=0.0),
    )
    row = next(r for r in rows if r["metric"] == "transfer_seconds")
    assert row["status"] == bench_diff.WARN and not failed


# ---------------------------------------------------------------------------
# real capture on the live backend (CPU in CI): parseable, non-empty
# ---------------------------------------------------------------------------

def test_real_capture_smoke(tmp_path):
    import jax
    import jax.numpy as jnp
    import numpy as np

    @jax.jit
    def step(x):
        return (x @ x.T).sum(axis=0)

    x = jnp.asarray(np.random.RandomState(0).rand(256, 256),
                    dtype=jnp.float32)
    step(x).block_until_ready()  # compile outside the window
    cap = str(tmp_path / "cap")
    with devprof.capture(cap) as target:
        with trace_mod.span("tree growth", cat="train.phase"):
            for _ in range(3):
                step(x).block_until_ready()
    files = devprof.find_trace_files(target)
    assert files, "capture emitted no trace files"
    rec = devprof.analyze_dir(target, platform=jax.default_backend())
    assert rec["events"] > 0
    assert rec["window_s"] > 0
    assert rec["lanes_source"] in ("device", "host_executor")
    assert rec["verdict"]["bound"] in (
        "host-bound", "device-bound", "transfer-bound")
    # the spanned annotation must have anchored at least part of the work
    assert "tree growth" in rec["segments"]
