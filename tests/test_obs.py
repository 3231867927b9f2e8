"""Unified observability layer (lightgbm_tpu/obs, docs/Observability.md):

  * structured tracing: a tiny traced train+serve run emits Chrome-trace
    JSON with pid/tid/ph/ts on every event, >= 3 training-phase spans
    nested inside an iteration span, and >= 1 serve request span;
  * retrace watchdog: counts REAL jax.jit trace events, passes on the
    warmed serve path, and trips (LIGHTGBM_TPU_RETRACE=fail) on a
    deliberately shape-unstable call;
  * metrics registry: Prometheus text exposition round-trips through a
    parser and carries latency quantiles, QPS, retrace count and peak
    device bytes;
  * memwatch: shape-math attribution equals the actual donated buffer
    sizes (hist carry + spec_rhist) on CPU;
  * profiler capture dirs: ``trace merge`` reads ``.gz`` traces and folds
    rank-suffixed capture dirs (tests/golden/devprof/);
  * the cost-analysis book and the chip peak table (obs/costs.py);
  * satellites: perf_counter-based phase timers, log.warn_once with ISO
    timestamps, spec_rhist donation reuse.
"""
import collections
import json
import os
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import lightgbm_tpu as lgb
import lightgbm_tpu.ops.grow as grow_mod
from lightgbm_tpu.obs import costs as costs_mod
from lightgbm_tpu.obs import memwatch, registry as registry_mod, retrace, trace
from lightgbm_tpu.obs.registry import MetricsRegistry
from lightgbm_tpu.ops.histogram import leaf_histogram
from lightgbm_tpu.utils import log
from lightgbm_tpu.utils.log import LightGBMError
from lightgbm_tpu.utils.timer import PhaseTimers


@pytest.fixture
def clean_obs(monkeypatch):
    """Isolate the global tracer/watchdog state per test."""
    trace.stop()
    retrace.disarm()
    monkeypatch.delenv("LIGHTGBM_TPU_TRACE", raising=False)
    monkeypatch.delenv("LIGHTGBM_TPU_RETRACE", raising=False)
    yield
    trace.stop()
    retrace.disarm()
    log.reset_warn_once()


def _train_small(rounds=3, n=500, leaves=7, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, 4)
    y = (X[:, 0] > 0).astype(float)
    bst = lgb.train(
        {"objective": "binary", "num_leaves": leaves, "verbosity": -1},
        lgb.Dataset(X, label=y), num_boost_round=rounds,
    )
    return bst, X


# ---------------------------------------------------------------------------
# structured tracing
# ---------------------------------------------------------------------------

PHASES = {"boosting(grad)", "bagging", "tree growth", "renew+score update"}


def test_trace_golden_train_and_serve(clean_obs, monkeypatch, tmp_path):
    """The acceptance-criteria trace: train + one serve request under
    LIGHTGBM_TPU_TRACE, then validate the Chrome-trace JSON structurally."""
    path = str(tmp_path / "trace.json")
    monkeypatch.setenv("LIGHTGBM_TPU_TRACE", path)
    bst, X = _train_small()
    model = str(tmp_path / "m.txt")
    bst.save_model(model)

    from lightgbm_tpu.serve.server import ServeApp

    app = ServeApp(max_delay_ms=1.0, min_bucket_rows=8)
    try:
        app.registry.load("m", model)
        out, _ = app.predict(X[:5])
        assert out.shape[0] == 5
    finally:
        app.close()
    written = trace.stop()
    assert written == path

    doc = json.load(open(path))
    events = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
    assert events
    for e in events:  # structural contract: chrome-trace complete events
        for field in ("pid", "tid", "ph", "ts", "dur", "name", "cat"):
            assert field in e, (field, e)
        assert e["dur"] >= 0.0
    names = {e["name"] for e in events}
    assert len(names & PHASES) >= 3, sorted(names)
    assert "train.iteration" in names
    # serve request lifecycle: root span + worker-side batch events
    assert "serve.request" in names
    assert "serve.batch_dispatch" in names
    assert "serve.queue_wait" in names


def test_trace_spans_nest_inside_iteration(clean_obs, monkeypatch, tmp_path):
    path = str(tmp_path / "trace.json")
    monkeypatch.setenv("LIGHTGBM_TPU_TRACE", path)
    _train_small(rounds=2)
    trace.stop()
    events = [
        e for e in json.load(open(path))["traceEvents"] if e.get("ph") == "X"
    ]
    iters = [e for e in events if e["name"] == "train.iteration"]
    phases = [e for e in events if e["name"] in PHASES]
    assert len(iters) == 2
    # every phase span lies inside SOME iteration span on the same thread
    for ph in phases:
        assert any(
            it["tid"] == ph["tid"]
            and it["ts"] <= ph["ts"]
            and ph["ts"] + ph["dur"] <= it["ts"] + it["dur"] + 1.0
            for it in iters
        ), ph


def test_trace_disabled_is_silent(clean_obs, monkeypatch):
    """``LIGHTGBM_TPU_TRACE=0`` records nothing, ring included; without it a
    span of no ring category stays out of the ring."""
    trace.reset()
    assert trace.active() is None
    with trace.span("nothing"):  # no category of the ring, no file mode
        pass
    assert trace.events() == [] and trace.stop() is None
    monkeypatch.setenv("LIGHTGBM_TPU_TRACE", "0")
    assert trace.active() is None and not trace.enabled()
    assert not trace.recording("train")
    _train_small(rounds=1)
    with trace.span("nothing", cat="train", iteration=0):
        trace.counters("grow.counters", cat="grow", tree=0)
    assert trace.events() == [] and trace.stop() is None


def test_ring_is_on_by_default_and_bounded(clean_obs):
    trace.reset()
    assert trace.active() is None and not trace.enabled()
    assert trace.recording("train") and not trace.recording("serve")
    with trace.span("first", cat="train"):
        pass
    assert [e["name"] for e in trace.events()] == ["first"]
    extra = 10
    for k in range(trace.RING_EVENTS + extra - 1):
        trace.counters("fill", cat="grow", tree=k)
    events = trace.events()
    assert len(events) == trace.RING_EVENTS
    assert trace.dropped() == extra            # the oldest went, and are counted
    assert events[0]["args"]["tree"] == extra - 1
    assert events[-1]["args"]["tree"] == trace.RING_EVENTS + extra - 2
    trace.reset()
    assert trace.events() == [] and trace.dropped() == 0


def test_events_is_a_copy(clean_obs):
    trace.reset()
    with trace.span("kept", cat="train", iteration=4):
        pass
    first = trace.events()
    first[0]["name"] = "changed"
    first[0]["args"]["iteration"] = 99
    first.clear()
    again = trace.events()
    assert [e["name"] for e in again] == ["kept"]
    assert again[0]["args"] == {"iteration": 4}


SETUP_CHILDREN = {"dataset.to_float", "dataset.sample", "dataset.find_bins",
                  "dataset.bin_matrix"}


def test_ids_and_parents_form_a_tree_over_a_training_run(clean_obs):
    jax.clear_caches()  # so that this run compiles, and jit.* has parents
    trace.reset()
    _train_small(rounds=3)
    events = trace.events()
    by_id = {e["id"]: e for e in events}
    assert len(by_id) == len(events)  # ids are unique
    for e in events:
        for key in ("name", "cat", "ts", "id", "parent", "pid", "tid"):
            assert key in e, (key, e)
        seen = set()
        while e["parent"] is not None:  # every chain ends at a root
            assert e["id"] not in seen
            seen.add(e["id"])
            e = by_id[e["parent"]]

    def parent_name(e):
        return None if e["parent"] is None else by_id[e["parent"]]["name"]

    names = collections.Counter(e["name"] for e in events)
    assert names["train.iteration"] == names["train.boundary"] == 3
    assert names["train.init"] == names["dataset.construct"] == 1
    for e in events:
        if e["name"] in PHASES | {"valid scores"}:
            assert parent_name(e) == "train.iteration", e
            assert e["args"]["iteration"] == by_id[e["parent"]]["args"]["iteration"]
        elif e["name"] == "train.callbacks":
            assert parent_name(e) == "train.boundary", e
        elif e["name"] in SETUP_CHILDREN:
            assert parent_name(e) == "dataset.construct", e
        elif e["name"] in ("train.iteration", "train.boundary", "train.init"):
            assert e["parent"] is None, e
        elif e["name"].startswith("jit."):
            # a program is traced, lowered and built where it is first
            # called: under a phase, set-up, or the boundary's eval
            assert e["parent"] is not None and e["cat"] == "compile", e
            assert "dur" in e and "fun" in e["args"], e
    # the deferred stop check waits inside the NEXT iteration, and once more
    # after the loop (engine._finish_train), outside any iteration
    waits = [e for e in events if e["name"] == "train.wait_prev_tree"]
    assert [parent_name(w) for w in waits] == ["train.iteration"] * 2 + [None]
    assert [w["args"].get("iteration") for w in waits] == [1, 2, None]
    assert all(names[child] == 1 for child in SETUP_CHILDREN)
    init = next(e for e in events if e["name"] == "train.init")
    assert init["args"]["bytes"] == 500 * 4  # the binned matrix, one byte a cell
    # a fixed handful per iteration and per Dataset, never per row or split
    per_iteration = collections.Counter(
        e["args"]["iteration"] for e in events
        if e["cat"] != "compile" and "iteration" in e["args"])
    assert max(per_iteration.values()) < 20


def test_compile_spans_on_the_first_train_only(clean_obs):
    jax.clear_caches()
    trace.reset()
    _train_small(rounds=2)
    first = [e for e in trace.events() if e["cat"] == "compile"]
    built = [e["args"]["fun"] for e in first if e["name"] == "jit.compile"]
    assert "jit(grow_tree)" in built
    # one top-level jit.trace a program, not one per jitted call inside it
    per_name = collections.Counter(e["name"] for e in first)
    assert per_name["jit.trace"] <= per_name["jit.lower"] == per_name["jit.compile"]
    trace.reset()
    _train_small(rounds=2)
    again = [e["args"]["fun"] for e in trace.events() if e["name"] == "jit.compile"]
    # the same shapes: the grower is not built again (the per-Booster
    # closure of the score update is: GBDT._finish_fns)
    assert "jit(grow_tree)" not in again and len(again) < len(built) / 4


def test_a_programs_own_trace_is_told_from_the_enclosed_ones(
        clean_obs, monkeypatch):
    """jax reports every jitted function traced on the way, enclosed ones
    first, and kernels traced while another program is lowered: jit.trace is
    the one that ended last before the lowering began."""
    trace.reset()
    clock = [0.0]
    monkeypatch.setattr(trace, "now_us", lambda: clock[0])

    def fire(kind, end_s, seconds, fun):
        clock[0] = end_s * 1e6
        trace._on_jax_duration(
            "/jax/core/compile/%s_duration" % kind, seconds, fun_name=fun)

    fire("jaxpr_trace", 1.2, 0.1, "where")             # inside grow_tree's
    fire("jaxpr_trace", 2.0, 1.5, "grow_tree")         # the program's own
    fire("jaxpr_trace", 2.6, 0.2, "kernel")            # while lowering
    fire("jaxpr_to_mlir_module", 3.0, 0.99, "jit(grow_tree)")
    fire("backend_compile", 9.0, 6.0, "jit(grow_tree)")
    fire("some/other", 9.5, 0.1, "ignored")
    fire("jaxpr_to_mlir_module", 10.0, 0.5, "jit(aot)")  # lowered, not traced
    got = [(e["name"], e["args"]["fun"], e["ts"], e["dur"]) for e in trace.events()]
    assert got == [
        ("jit.trace", "grow_tree", 0.5e6, 1.5e6),
        ("jit.lower", "jit(grow_tree)", 2.01e6, 0.99e6),
        ("jit.compile", "jit(grow_tree)", 3.0e6, 6.0e6),
        ("jit.lower", "jit(aot)", 9.5e6, 0.5e6),
    ]


def test_serve_spans_stay_out_of_the_ring(clean_obs, tmp_path):
    trace.reset()
    bst, X = _train_small()
    model = str(tmp_path / "m.txt")
    bst.save_model(model)
    from lightgbm_tpu.serve.server import ServeApp

    app = ServeApp(max_delay_ms=1.0, min_bucket_rows=8)
    try:
        app.registry.load("m", model)
        out, _ = app.predict(X[:5])
        assert out.shape[0] == 5
    finally:
        app.close()
    names = {e["name"] for e in trace.events()}
    assert "train.iteration" in names
    assert not [n for n in names if n.startswith(("serve.", "loop.", "cli."))]


def test_file_mode_still_writes_a_loadable_file_beside_the_ring(
        clean_obs, monkeypatch, tmp_path):
    path = str(tmp_path / "trace.json")
    monkeypatch.setenv("LIGHTGBM_TPU_TRACE", path)
    trace.reset()
    assert trace.enabled() and trace.recording("serve")
    _train_small(rounds=2)
    with trace.span("serve.request", cat="serve"):
        pass
    assert trace.stop() == path
    doc = json.load(open(path))
    spans = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
    in_file = {e["name"] for e in spans}
    assert {"train.iteration", "train.boundary", "serve.request"} <= in_file
    assert all("id" in e and "parent" in e for e in spans)
    assert any(e.get("ph") == "M" for e in doc["traceEvents"])  # thread names
    in_ring = {e["name"] for e in trace.events()}
    assert "train.iteration" in in_ring and "serve.request" not in in_ring


def test_an_abandoned_span_is_closed_by_its_frame(clean_obs):
    """``__enter__()`` without ``with`` (engine.train's train.init, the
    loop's train.boundary): ``close_to`` in the caller's ``finally`` ends what
    an exception left open, so later spans do not hang under it."""
    trace.reset()
    depth = trace.open_depth()
    try:
        outer = trace.span("train.init", cat="setup").__enter__()
        trace.span("train.boundary", cat="train", iteration=7).__enter__()
        raise RuntimeError("a callback failed")
    except RuntimeError:
        trace.close_to(depth)
    outer.close()  # closing twice records once
    with trace.span("after", cat="train"):
        pass
    events = trace.events()
    assert [e["name"] for e in events] == ["train.boundary", "train.init", "after"]
    assert events[2]["parent"] is None and events[2]["args"] == {}
    assert trace.open_depth() == depth
    with pytest.raises(LightGBMError):  # engine.train itself, left by an error
        lgb.train({"objective": "no-such-objective", "verbosity": -1},
                  lgb.Dataset(np.zeros((10, 2)), label=np.zeros(10)))
    assert trace.open_depth() == depth


# ---------------------------------------------------------------------------
# the grower's work counters and scopes
# ---------------------------------------------------------------------------


@pytest.fixture
def grow_mode(monkeypatch):
    def set_mode(mode):
        monkeypatch.setattr(grow_mod, "_ENV_GROW", mode)
        jax.clear_caches()

    yield set_mode
    monkeypatch.setattr(grow_mod, "_ENV_GROW", "")
    jax.clear_caches()


def _recount(tree, rows):
    """What a tree's splits needed, from the materialised tree alone."""
    def count(child):
        return (tree.leaf_count[-(child + 1)] if child < 0
                else tree.internal_count[child])

    splits = tree.num_leaves - 1
    smaller = sum(min(count(int(l)), count(int(r)))
                  for l, r in zip(tree.left_child[:splits], tree.right_child[:splits]))
    return {"splits": splits, "hist_rows": rows + smaller,
            "part_rows": int(sum(tree.internal_count[:splits]))}


@pytest.mark.parametrize("mode", ["seq", "spec"])
def test_grower_counters_against_a_host_recount(clean_obs, grow_mode, mode):
    grow_mode("seq")
    reference = _train_small(rounds=3, n=1500, leaves=31)[0].model_to_string()
    grow_mode(mode)
    trace.reset()
    bst, X = _train_small(rounds=3, n=1500, leaves=31)
    assert grow_mod._LAST_GROW_MODE == mode
    assert not [e for e in trace.events() if e["name"] == "grow.counters"]
    text = bst.model_to_string()  # materialises the trees: one event each
    assert text == reference  # counting changes no tree
    counted = [e for e in trace.events() if e["name"] == "grow.counters"]
    assert [e["args"]["tree"] for e in counted] == [0, 1, 2]
    assert [e["args"]["iteration"] for e in counted] == [0, 1, 2]
    assert all(e["ph"] == "C" and e["cat"] == "grow" for e in counted)
    bst.model_to_string()  # nothing is emitted twice
    assert len([e for e in trace.events() if e["name"] == "grow.counters"]) == 3
    for e, tree in zip(counted, bst._gbdt.trees()):
        c, want = e["args"], _recount(tree, len(X))
        assert set(grow_mod.COUNTER_NAMES) <= set(c)
        assert c["splits"] == want["splits"] > 0
        assert c["slots_computed"] >= c["splits"]
        assert c["hist_rows_streamed"] >= c["hist_rows_needed"]
        assert c["part_rows_streamed"] >= c["part_rows_needed"]
        if mode == "seq":
            assert c["steps"] == c["slots_computed"] == c["splits"]
            assert c["hist_rows_needed"] == want["hist_rows"]
            assert c["part_rows_needed"] == want["part_rows"]
        else:
            # a pass applies a batch; a slot computed and never applied read
            # its rows for nothing
            assert c["steps"] < c["splits"]
            assert c["hist_rows_needed"] >= want["hist_rows"]
            assert c["part_rows_needed"] >= want["part_rows"]
            wasted = c["slots_computed"] - c["splits"]
            assert (wasted == 0) == (c["hist_rows_needed"] == want["hist_rows"])


def test_grow_tree_lowers_with_its_four_scopes(clean_obs):
    bst, _ = _train_small(rounds=1)
    g = bst._gbdt
    cfg = g.config
    n = g.num_data
    lowered = grow_mod.grow_tree.lower(
        g.bins_dev, jnp.zeros((n,), jnp.float32), jnp.ones((n,), jnp.float32),
        g._bag_mask, g._fmask_all, g.feature_meta,
        num_leaves=cfg.num_leaves, max_depth=cfg.max_depth,
        num_bins=g.num_bins, params=g.split_params,
    )
    text = lowered.as_text(debug_info=True)
    for scope in ("hist_build", "partition", "split_find", "apply_split"):
        assert "/%s/" % scope in text or "/%s\"" % scope in text, scope


def test_phase_spans_without_timetag(clean_obs, monkeypatch, tmp_path):
    """Tracing is independent of the TIMETAG accumulators: phases emit
    spans even with timers disabled (and the timers stay off)."""
    path = str(tmp_path / "trace.json")
    monkeypatch.setenv("LIGHTGBM_TPU_TRACE", path)
    monkeypatch.delenv("LIGHTGBM_TPU_TIMETAG", raising=False)
    bst, _ = _train_small(rounds=1)
    assert not bst._gbdt.timers.enabled
    assert not bst._gbdt.timers.seconds
    trace.stop()
    names = {
        e["name"]
        for e in json.load(open(path))["traceEvents"]
        if e.get("ph") == "X"
    }
    assert len(names & PHASES) >= 3


# ---------------------------------------------------------------------------
# profiler capture dirs: per-rank folding + trace merge
# ---------------------------------------------------------------------------

GOLD_CAPTURES = os.path.join(os.path.dirname(__file__), "golden", "devprof")
TPU_CAP = os.path.join(GOLD_CAPTURES, "tpu_capture")
RANK_CAP = os.path.join(GOLD_CAPTURES, "rank_capture")


def test_rank_suffixed_dirs_fold_into_one_parse():
    """maybe_profile's .rank<N> suffix leaves NO base dir behind —
    find_trace_files must still find every rank's capture."""
    files = trace.find_trace_files(RANK_CAP)
    assert [os.path.basename(f) for f in files] == [
        "rank0.trace.json.gz", "rank1.trace.json.gz"
    ]
    for rank, f in enumerate(files):
        assert os.sep + "rank_capture.rank%d" % rank + os.sep in f
        names = {e["name"] for e in trace.load_chrome_trace(f)["traceEvents"]}
        assert "fusion.%d" % rank in names


def test_trace_merge_reads_gz_and_expands_capture_dirs(tmp_path):
    out = str(tmp_path / "merged.json")
    rc = trace.main(["merge", "-o", out, TPU_CAP])
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
    LIGHTGBM_TPU_PROFILE dir must diverge to .rank<N> captures (the
    LIGHTGBM_TPU_TRACE fix, applied to the profiler dir too)."""
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
# retrace watchdog
# ---------------------------------------------------------------------------


def test_watchdog_counts_real_jit_traces(clean_obs, monkeypatch):
    wd = retrace.RetraceWatchdog()

    @jax.jit
    def f(x):
        wd.note_trace("f")
        return x * 2

    f(jnp.ones(4))
    f(jnp.ones(4))  # cache hit: no new trace
    assert wd.counts() == {"f": 1}
    f(jnp.ones(8))  # new shape: one real compile
    assert wd.counts() == {"f": 2}

    wd.arm()
    f(jnp.ones(8))  # warmed shape
    assert wd.retraces_after_warmup() == {}
    monkeypatch.setenv("LIGHTGBM_TPU_RETRACE", "fail")
    with pytest.raises(LightGBMError, match="retrace after warmup"):
        f(jnp.ones(16))  # shape-unstable: trips the armed watchdog
    assert wd.retraces_after_warmup() == {"f": 1}


def test_watchdog_warn_mode_warns_once(clean_obs, monkeypatch):
    wd = retrace.RetraceWatchdog()
    lines = []
    log.set_verbosity(1)  # earlier verbosity=-1 training left level=fatal
    log.register_callback(lines.append)
    try:

        @jax.jit
        def g(x):
            wd.note_trace("g")
            return x + 1

        g(jnp.ones(4))
        wd.arm()
        monkeypatch.setenv("LIGHTGBM_TPU_RETRACE", "warn")
        g(jnp.ones(8))
        g(jnp.ones(16))
        retraced = [ln for ln in lines if "retrace after warmup" in ln]
        assert len(retraced) == 1  # warn_once: one line for the pattern
        assert wd.total_retraces() == 2
    finally:
        log.register_callback(None)
        log.reset_warn_once()


def test_retrace_fail_passes_on_warmed_serve_path(
    clean_obs, monkeypatch, tmp_path
):
    """The acceptance criterion: with every bucket warmed and the watchdog
    armed, LIGHTGBM_TPU_RETRACE=fail serves mixed-size traffic without a
    single compile — and a deliberately shape-unstable call trips it."""
    bst, X = _train_small()
    model = str(tmp_path / "m.txt")
    bst.save_model(model)

    from lightgbm_tpu.serve.server import ServeApp

    app = ServeApp(max_delay_ms=1.0, min_bucket_rows=8)
    try:
        served = app.registry.load("m", model)
        served.warmup(max_rows=64)  # compiles every bucket 8..64, both paths
        app.arm_retrace_watchdog()
        monkeypatch.setenv("LIGHTGBM_TPU_RETRACE", "fail")
        for n in (3, 9, 17, 33, 64):  # all land in warmed buckets
            out, _ = app.predict(X[:n])
            assert out.shape[0] == n
        assert retrace.retraces_after_warmup() == {}
        # now bypass the bucket cache with a raw 100-row dispatch: a fresh
        # shape, a fresh XLA trace, a hard failure
        with pytest.raises(LightGBMError, match="retrace after warmup"):
            served.ensemble.predict_leaves(X[:100])
    finally:
        monkeypatch.delenv("LIGHTGBM_TPU_RETRACE", raising=False)
        retrace.reset()
        app.close()


def test_hot_swap_warms_and_rearms_armed_watchdog(clean_obs, tmp_path):
    """A hot swap on a hardened server must not fail its first requests:
    ModelRegistry.load suspends the armed watchdog around the incoming
    model's warmup (those compiles are legitimate), then re-arms with the
    fresh counts, so LIGHTGBM_TPU_RETRACE=fail survives the swap.

    Runs in a SUBPROCESS: the in-process jit cache may already hold the
    second model's shapes from earlier tests, which would make the swap
    compile nothing and the assertion vacuous — a fresh process guarantees
    the swap really traces."""
    import subprocess
    import sys

    src = """
import os
import numpy as np
import lightgbm_tpu as lgb
from lightgbm_tpu.serve.server import ServeApp
from lightgbm_tpu.obs import retrace

rng = np.random.RandomState(0)
X = rng.randn(400, 4); y = (X[:, 0] > 0).astype(float)
a = lgb.train({"objective": "binary", "num_leaves": 7, "verbose": -1},
              lgb.Dataset(X, label=y), 2)
b = lgb.train({"objective": "binary", "num_leaves": 15, "verbose": -1},
              lgb.Dataset(X, label=y), 4)  # different packed shapes
td = os.environ["SWAP_DIR"]
pa, pb = os.path.join(td, "a.txt"), os.path.join(td, "b.txt")
a.save_model(pa); b.save_model(pb)

app = ServeApp(max_delay_ms=1.0, min_bucket_rows=8, warmup_rows=16)
app.registry.load("m", pa)
app.arm_retrace_watchdog()
os.environ["LIGHTGBM_TPU_RETRACE"] = "fail"
before = sum(retrace.counts().values())
app.registry.load("m", pb)  # must warm + re-arm, not trip on its compiles
assert sum(retrace.counts().values()) > before, "swap compiled nothing: vacuous"
out, served = app.predict(X[:5])
assert served.version == 2 and out.shape[0] == 5
assert retrace.retraces_after_warmup() == {}
app.close()
print("SWAP_OK")
"""
    env = dict(
        os.environ, JAX_PLATFORMS="cpu", SWAP_DIR=str(tmp_path),
    )
    env.pop("LIGHTGBM_TPU_RETRACE", None)
    proc = subprocess.run(
        [sys.executable, "-c", src], env=env, capture_output=True,
        text=True, timeout=300, cwd="/root/repo",
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "SWAP_OK" in proc.stdout


# ---------------------------------------------------------------------------
# metrics registry + Prometheus exposition
# ---------------------------------------------------------------------------

_PROM_SAMPLE = re.compile(
    r"^(?P<name>[a-zA-Z_:][a-zA-Z0-9_:]*)"
    r"(?:\{(?P<labels>[^}]*)\})? (?P<value>[0-9eE\+\-\.]+)$"
)


def _parse_prom(text):
    """Prometheus text exposition -> {(name, labels): float}; raises on any
    malformed line (the round-trip contract)."""
    out = {}
    types = {}
    for line in text.strip().splitlines():
        if not line:
            continue
        if line.startswith("#"):
            parts = line.split()
            if parts[1] == "TYPE":
                types[parts[2]] = parts[3]
            continue
        m = _PROM_SAMPLE.match(line)
        assert m, "malformed exposition line: %r" % line
        out[(m.group("name"), m.group("labels") or "")] = float(
            m.group("value")
        )
    return out, types


def test_registry_prometheus_roundtrip():
    reg = MetricsRegistry()
    reg.counter("requests").inc(5)
    reg.counter("by_model").inc(2, model="prod")
    reg.counter("by_model").inc(3, model="canary")
    reg.gauge("queue_depth").set(7)
    reg.gauge("phase_s").set(1.5, phase="tree growth")
    h = reg.histogram("latency_seconds")
    for v in (0.001, 0.002, 0.003, 0.004):
        h.record(v)
    reg.rate("qps").record(10)

    samples, types = _parse_prom(reg.prometheus_text())
    assert types["lgbtpu_requests_total"] == "counter"
    assert types["lgbtpu_latency_seconds"] == "summary"
    assert types["lgbtpu_qps"] == "gauge"
    assert samples[("lgbtpu_requests_total", "")] == 5
    assert samples[("lgbtpu_by_model_total", 'model="canary"')] == 3
    assert samples[("lgbtpu_queue_depth", "")] == 7
    assert samples[("lgbtpu_phase_s", 'phase="tree growth"')] == 1.5
    assert samples[("lgbtpu_latency_seconds", 'quantile="0.5"')] == 0.003
    assert samples[("lgbtpu_latency_seconds_count", "")] == 4
    assert samples[("lgbtpu_latency_seconds_sum", "")] == pytest.approx(0.01)

    report = reg.run_report()
    assert report["counters"]["requests"] == 5
    assert report["summaries"]["latency_seconds"]["count"] == 4


def test_prometheus_label_value_escaping_roundtrip():
    """Label values carrying the three characters the text exposition
    escapes (backslash, double-quote, newline) must render per the 0.0.4
    format — backslash FIRST, then quote, then newline — and decode back
    to the original value (the podwatch aggregator and any real scraper
    both rely on this)."""
    reg = MetricsRegistry()
    nasty = 'C:\\tmp\\x "quoted"\nline2'
    reg.gauge("paths").set(1.0, path=nasty)
    expo = reg.prometheus_text()
    line = next(l for l in expo.splitlines() if l.startswith("lgbtpu_paths{"))
    assert line == (
        'lgbtpu_paths{path="C:\\\\tmp\\\\x \\"quoted\\"\\nline2"} 1'
    )
    # decode exactly as a scraper would: the escaped body is one line
    body = line[len('lgbtpu_paths{path="'):-len('"} 1')]
    assert "\n" not in body
    decoded = (
        body.replace("\\n", "\n").replace('\\"', '"').replace("\\\\", "\\")
    )
    assert decoded == nasty


def test_prometheus_nonfinite_values_render_as_tokens():
    """NaN/Inf must render as the format's tokens — int(nan) raises
    ValueError and int(inf) OverflowError, and before the podwatch PR
    either took the WHOLE /metrics scrape down with it."""
    reg = MetricsRegistry()
    reg.gauge("weird").set(float("nan"), kind="nan")
    reg.gauge("weird").set(float("inf"), kind="pinf")
    reg.gauge("weird").set(float("-inf"), kind="ninf")
    reg.gauge("fine").set(3.5)
    expo = reg.prometheus_text()
    assert 'lgbtpu_weird{kind="nan"} NaN' in expo
    assert 'lgbtpu_weird{kind="pinf"} +Inf' in expo
    assert 'lgbtpu_weird{kind="ninf"} -Inf' in expo
    # the finite neighbours still scrape
    assert "lgbtpu_fine 3.5" in expo


def test_prometheus_help_lines_escaped_and_parseable():
    """# HELP rides each instrument's help string, with backslash/newline
    escaped (HELP values are unquoted, so a raw `\"` stays raw) — and the
    standard parser helpers above must keep skipping them."""
    reg = MetricsRegistry()
    reg.counter("jobs", 'help with \\ and\nnewline and "quote"').inc(2)
    reg.gauge("depth", "queue depth").set(4)
    expo = reg.prometheus_text()
    assert ('# HELP lgbtpu_jobs_total help with \\\\ and\\nnewline '
            'and "quote"') in expo
    assert "# HELP lgbtpu_depth queue depth" in expo
    # HELP precedes TYPE for the same family (textfile-collector ordering)
    lines = expo.splitlines()
    assert lines.index("# HELP lgbtpu_depth queue depth") < lines.index(
        "# TYPE lgbtpu_depth gauge"
    )
    samples, types = _parse_prom(expo)
    assert samples[("lgbtpu_jobs_total", "")] == 2
    assert types["lgbtpu_depth"] == "gauge"


def test_serve_metrics_exposition_has_required_families(clean_obs, tmp_path):
    """/metrics acceptance: latency quantiles, QPS, retrace count and peak
    device bytes all present and parseable."""
    bst, X = _train_small()
    model = str(tmp_path / "m.txt")
    bst.save_model(model)

    from lightgbm_tpu.serve.server import ServeApp

    app = ServeApp(max_delay_ms=1.0, min_bucket_rows=8)
    try:
        app.registry.load("m", model)
        app.predict(X[:5])
        samples, types = _parse_prom(app.prometheus_metrics())
    finally:
        app.close()
    assert types["lgbtpu_request_latency_seconds"] == "summary"
    assert ("lgbtpu_request_latency_seconds", 'quantile="0.5"') in samples
    assert ("lgbtpu_qps", "") in samples
    assert samples[("lgbtpu_requests_total", "")] >= 1
    assert ("lgbtpu_jit_retraces_after_warmup", "") in samples
    assert ("lgbtpu_jit_traces_total", "") in samples
    assert samples[("lgbtpu_device_peak_bytes", "")] > 0
    assert ("lgbtpu_bucket_retraces_total", "") in samples


def test_training_publishes_phase_gauges(clean_obs, monkeypatch):
    monkeypatch.setenv("LIGHTGBM_TPU_TIMETAG", "1")
    before = registry_mod.REGISTRY.counters().get("train_iterations", 0)
    _train_small(rounds=2)
    report = registry_mod.REGISTRY.run_report()
    assert report["counters"]["train_iterations"] == before + 2
    assert any(
        k.startswith("train_phase_seconds_total") and "tree growth" in k
        for k in report["gauges"]
    )


def test_record_metrics_callback():
    from lightgbm_tpu.callback import record_metrics

    reg = MetricsRegistry()
    rng = np.random.RandomState(0)
    X = rng.randn(400, 4)
    y = (X[:, 0] > 0).astype(float)
    ds = lgb.Dataset(X, label=y)
    lgb.train(
        {"objective": "binary", "num_leaves": 4, "verbosity": -1},
        ds, num_boost_round=3, valid_sets=[ds], valid_names=["train"],
        callbacks=[record_metrics(reg)], verbose_eval=False,
    )
    report = reg.run_report()
    assert report["gauges"]["train_last_iteration"] == 3
    assert report["counters"]["train_eval_boundaries"] == 3
    assert any(k.startswith("eval_metric") for k in report["gauges"])


# ---------------------------------------------------------------------------
# memwatch
# ---------------------------------------------------------------------------


def test_memwatch_shape_math_matches_hist_buffer(clean_obs):
    bst, _ = _train_small(leaves=15)
    g = bst._gbdt
    attr = memwatch.attribute_training(g)
    assert g._hist_buf is not None
    assert attr["hist_carry"]["bytes"] == g._hist_buf.nbytes
    assert attr["hist_carry"]["donated"]
    assert attr["scores"]["bytes"] == g.scores.nbytes
    assert attr["bins"]["bytes"] == g.bins_dev.nbytes
    assert attr["total_bytes"] >= attr["hist_carry"]["bytes"]


def test_memwatch_packed_attribution(clean_obs):
    bst, _ = _train_small()
    pk = bst.to_packed()
    attr = memwatch.attribute_packed(pk)
    actual = sum(int(a.nbytes) for a in pk.packed)
    assert attr["total_bytes"] == actual
    assert attr["fields_bytes"]["leaf_value"] == int(pk.packed.leaf_value.nbytes)


def test_memwatch_snapshot_cpu(clean_obs):
    reg = MetricsRegistry()
    rec = memwatch.snapshot("test_point", registry=reg)
    assert rec["tag"] == "test_point"
    # CPU backend reports no allocator stats; the live census stands in
    assert rec["live_buffer_bytes"] >= 0
    gauges = reg.run_report()["gauges"]
    assert "device_peak_bytes" in gauges
    assert memwatch.snapshots()[-1]["tag"] == "test_point"


# ---------------------------------------------------------------------------
# cost-analysis book + peak table (obs/costs.py)
# ---------------------------------------------------------------------------

@pytest.fixture
def clean_cost_book():
    costs_mod.COSTS.reset()
    yield
    costs_mod.COSTS.reset()


def test_cost_bytes_match_memwatch_shape_math(clean_cost_book):
    """The compiled executable's argument/output byte counts must equal the
    shape math memwatch uses for the same tensors — the cross-check that
    keeps the two attribution layers honest with each other."""
    F, N, B = 4, 512, 16
    bins = jnp.zeros((F, N), jnp.uint8)
    vals = jnp.zeros((N, 3), jnp.float32)
    rec = costs_mod.COSTS.harvest(
        "test.leaf_histogram", leaf_histogram, (bins, vals, B)
    )
    assert rec is not None and rec["flops"] > 0
    assert rec["argument_bytes"] == bins.nbytes + vals.nbytes
    # [F, B, 3] f32 output == a 1-row histogram carry in memwatch's math
    assert rec["output_bytes"] == memwatch.hist_carry_bytes(1, F, B)
    # dedupe: the same signature returns the cached record, no re-compile
    again = costs_mod.COSTS.harvest(
        "test.leaf_histogram", leaf_histogram, (bins, vals, B)
    )
    assert again == rec


def test_cost_harvest_during_training(clean_cost_book, monkeypatch):
    monkeypatch.setenv(costs_mod.ENV_COSTS, "1")
    _train_small(rounds=1, seed=11)
    book = costs_mod.COSTS.report()
    assert "ops.grow_tree" in book, sorted(book)
    assert book["ops.grow_tree"].get("flops", 0) > 0
    report = registry_mod.REGISTRY.run_report()
    assert "cost_analysis" in report
    prom = registry_mod.REGISTRY.prometheus_text()
    assert 'lgbtpu_xla_cost_flops{executable="ops.grow_tree"}' in prom
    # the satellite wiring: per-name compile counts ride next to the costs
    assert 'lgbtpu_jit_traces{name="ops.grow_tree"}' in prom


def test_costs_disabled_by_default(clean_cost_book, monkeypatch):
    monkeypatch.delenv(costs_mod.ENV_COSTS, raising=False)
    assert not costs_mod.enabled()
    _train_small(rounds=1, seed=13)
    assert "ops.grow_tree" not in costs_mod.COSTS.report()


def test_chip_peak_table():
    assert costs_mod.normalize_device_kind("TPU v4") == "v4"
    assert costs_mod.normalize_device_kind("TPU v5e") == "v5e"
    # "TPU v5 lite" is what a v5e reports (jax 0.9 / libtpu 0.0.34, PR 21)
    assert costs_mod.normalize_device_kind("TPU v5 lite") == "v5e"
    assert costs_mod.normalize_device_kind("TPU v5p") == "v5p"
    assert costs_mod.normalize_device_kind("TPU v6e") == "v6e"
    assert costs_mod.normalize_device_kind("TPU v6 lite") == "v6e"
    assert costs_mod.normalize_device_kind("cpu") == "cpu"
    assert costs_mod.normalize_device_kind("warp9") is None
    for fam, rec in costs_mod.CHIP_PEAKS.items():
        assert rec["peak_flops"] > 0 and rec["peak_bw"] > 0, fam
    v5e = costs_mod.chip_peaks("TPU v5e", platform="tpu")
    assert v5e["peak_flops"] == 197e12 and "v5e" in v5e["chip"]
    # no default chip and no cpu row (tests/test_chip_smoke.py pins the rest)
    for kind, plat in (("warp9", "tpu"), ("cpu", "cpu")):
        with pytest.raises(LightGBMError):
            costs_mod.chip_peaks(kind, platform=plat)


# ---------------------------------------------------------------------------
# satellites: timers, warn_once, spec donation reuse
# ---------------------------------------------------------------------------


def test_phase_timers_use_monotonic_clock(clean_obs, monkeypatch):
    """A wall-clock step (NTP) must not corrupt phase totals: freeze
    time.time and confirm the timers still measure real elapsed time."""
    import lightgbm_tpu.utils.timer as timer_mod

    monkeypatch.setattr(timer_mod.time, "time", lambda: 0.0)
    t = PhaseTimers(enabled=True, sync=False)
    with t.phase("p") as ph:
        time.perf_counter()  # any work
        ph.mark()
        # busy-wait ~2ms of real monotonic time
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < 0.002:
            pass
    assert t.seconds["p"] >= 0.002  # wall-clock says 0; perf_counter doesn't
    assert 0.0 <= t.dispatch_seconds["p"] <= t.seconds["p"] + 1e-9


def test_warn_once_rate_limits_and_stamps(clean_obs):
    lines = []
    log.set_verbosity(1)  # earlier verbosity=-1 training left level=fatal
    log.register_callback(lines.append)
    try:
        assert log.warn_once("k1", "thing happened: %d", 7)
        assert not log.warn_once("k1", "thing happened: %d", 8)
        assert log.warn_once("k2", "other thing")
        assert len(lines) == 2
        # ISO-8601 timestamp on every emitted line
        for ln in lines:
            assert re.search(r"\[\d{4}-\d{2}-\d{2}T\d{2}:\d{2}:\d{2}\]", ln)
        assert "thing happened: 7" in lines[0]
    finally:
        log.register_callback(None)
        log.reset_warn_once()


def test_spec_batch_slots_gate():
    """The helper gbdt/memwatch rely on must agree with grow_tree's spec
    gate (single source of truth): every decline condition zeroes it."""
    import lightgbm_tpu.ops.grow as g

    orig = g._ENV_GROW
    g._ENV_GROW = "spec"
    try:
        assert g.spec_batch_slots(31) > 0
        assert g.spec_batch_slots(31, pooled=True) == 0
        assert g.spec_batch_slots(31, cegb_on=True) == 0
        assert g.spec_batch_slots(31, hist_mode="masked") == 0
        assert g.spec_batch_slots(31, custom_split=True) == 0
        assert g.spec_batch_slots(2) == 0  # kb < 2 degenerates to seq
        g._ENV_GROW = "seq"
        assert g.spec_batch_slots(31) == 0
    finally:
        g._ENV_GROW = orig


# NOTE: this test (and only it in this module) clears the jit caches, so it
# runs LAST — earlier tests reuse one another's compiled programs.
def test_spec_buf_donation_is_bitwise_invariant(clean_obs, monkeypatch):
    """The spec_rhist carry survives across trees as a donated scratch (no
    per-tree re-zeroing) and changes NOTHING semantically: spec training
    with the donated buffer is bit-identical to spec training without it.
    (Spec-vs-SEQ exactness is test_spec_grow's contract and has its own
    documented flat-path near-tie caveat, ADVICE r5 #1 — this test pins the
    delta this PR introduced: the donation itself.)"""
    import lightgbm_tpu.models.gbdt as gbdt_mod
    import lightgbm_tpu.ops.histogram as hist_mod

    monkeypatch.setattr(hist_mod, "_ENV_IMPL", "xla")
    monkeypatch.setattr(grow_mod, "_ENV_SPEC_HIST", "flat")
    monkeypatch.setattr(grow_mod, "_ENV_GROW", "spec")

    rng = np.random.RandomState(3)
    X = rng.randn(900, 6)
    y = (X[:, 0] + 0.4 * X[:, 1] > 0).astype(float)
    params = {"objective": "binary", "num_leaves": 15, "verbosity": -1}

    jax.clear_caches()
    try:
        with_don = lgb.train(params, lgb.Dataset(X, label=y), 4)
        assert grow_mod._LAST_GROW_MODE == "spec"
        g = with_don._gbdt
        assert g._spec_buf is not None
        assert g._spec_buf.shape == (15, 6, g.num_bins, 3)
        # memwatch shape math equals the real donated buffer (ADVICE r5 #2)
        attr = memwatch.attribute_training(g)
        assert attr["spec_rhist"]["bytes"] == g._spec_buf.nbytes
        assert attr["spec_rhist"]["donated"]
        # gbdt-side gate forced to 0 -> grow_tree gets spec_buf=None and
        # allocates + zeros its own spec_rhist every tree (the pre-PR path)
        monkeypatch.setattr(gbdt_mod, "spec_batch_slots", lambda *a, **k: 0)
        jax.clear_caches()
        no_don = lgb.train(params, lgb.Dataset(X, label=y), 4)
        assert getattr(no_don._gbdt, "_spec_buf", None) is None
        assert with_don.model_to_string() == no_don.model_to_string()
    finally:
        monkeypatch.setattr(grow_mod, "_ENV_GROW", "")
        monkeypatch.setattr(grow_mod, "_ENV_SPEC_HIST", "")
        jax.clear_caches()
