"""The readers of the program's own trace: ``spans.py``'s selections and self
time on a recorded event list, each span and counter metric on it, and that a
reader reports nothing, not 0, where its events are missing."""
import numpy as np
import pytest

from benchmarks import spans
from benchmarks.manifest import Manifest

MAN = Manifest()
WARMUP, ITERATIONS = 2, 3
ROWS = 1000
SPAN_METRICS = [m["name"] for m in MAN.data["per_layer"]
                if m["source"] == "program_span"
                or (m["source"] == "program_counter" and m["name"].startswith("grow."))]


def _recorded():
    """What a run of 6 iterations leaves in the ring, times in microseconds:
    set-up ends at 10 s, an iteration lasts 1 s and waits 0.6 s of it, a
    boundary 2000 us of which 1500 in the callbacks."""
    evs, ids = [], iter(range(1, 10000))

    def add(name, ts, dur, parent=None, cat="train", ph="X", **args):
        ev = {"ph": ph, "name": name, "cat": cat, "pid": 1, "tid": 0, "ts": float(ts),
              "id": next(ids), "parent": parent, "args": args}
        if dur is not None:
            ev["dur"] = float(dur)
        evs.append(ev)
        return ev["id"]

    ds = add("dataset.construct", 0, 4_000_000, cat="setup")
    add("dataset.find_bins", 100, 1_000_000, ds, cat="setup", columns=7)
    # a reference's construction nested in another's: counted once
    add("dataset.construct", 2_000_000, 500_000, ds, cat="setup")
    add("dataset.construct", 4_500_000, 250_000, cat="setup")  # a valid set
    init = add("train.init", 5_000_000, 1_000_000, cat="setup", bytes=7 * ROWS)
    add("jit.trace", 5_100_000, 300_000, init, cat="compile", fun="f")
    add("jit.lower", 5_400_000, 200_000, init, cat="compile", fun="jit(f)")
    add("jit.compile", 5_600_000, 400_000, init, cat="compile", fun="jit(f)")
    t = 6_000_000
    for k in range(6):
        if k == WARMUP:
            assert t == 10_000_000
        it = add("train.iteration", t, 1_000_000, iteration=k)
        if k:
            add("train.wait_prev_tree", t + 10, 600_000, it, iteration=k)
        grow = add("tree growth", t + 700_000, 250_000, it, cat="train.phase", iteration=k)
        if k < WARMUP:  # compiled where first called, inside warm-up
            add("jit.trace", t + 700_100, 100_000, grow, cat="compile", fun="grow_tree", iteration=k)
            add("jit.lower", t + 800_100, 50_000, grow, cat="compile", fun="jit(grow_tree)", iteration=k)
            add("jit.compile", t + 850_100, 90_000, grow, cat="compile", fun="jit(grow_tree)", iteration=k)
        b = add("train.boundary", t + 1_000_000, 2000 if k >= WARMUP else 1_000_000, iteration=k)
        add("train.callbacks", t + 1_000_100, 1500, b, iteration=k)
        t += 1_002_000 if k >= WARMUP else 2_000_000
    # a compile after the window began: not set-up's
    add("jit.compile", 10_500_000, 7_000_000, cat="compile", fun="jit(late)")
    for k in range(6):
        add("grow.counters", t, None, cat="grow", ph="C", tree=k, iteration=k,
            steps=4.0 + k, slots_computed=10.0, splits=8.0,
            hist_rows_streamed=6000.0, hist_rows_needed=2100.0,
            part_rows_streamed=4000.0, part_rows_needed=3000.0)
    return evs


def _tree():
    """Two splits of 1000 rows: 1000 -> 400 + 600, the 600 -> 250 + 350."""
    return {"num_leaves": 3, "left_child": np.array([-1, -2]), "right_child": np.array([1, -3]),
            "leaf_count": np.array([400, 250, 350]), "internal_count": np.array([1000, 600])}


@pytest.fixture
def ctx():
    return {"traffic": {"warmup_iterations": WARMUP}, "iterations": ITERATIONS,
            "config": {"rows": ROWS, "features": 7, "params": {"max_bin": 15}},
            "window_trees": [_tree()] * ITERATIONS}


@pytest.fixture
def recorded(monkeypatch):
    evs = _recorded()
    monkeypatch.setattr(spans, "events", lambda: evs)
    return evs


def test_the_nine_new_metrics_are_the_ones_under_test():
    assert len(SPAN_METRICS) == 9


def test_selection_by_iteration_and_by_set_up(recorded, ctx):
    assert list(spans.window_iterations(ctx)) == [2, 3, 4]
    assert spans.setup_end_us(recorded, ctx) == 10_000_000
    assert len(spans.of_iteration(recorded, "train.iteration", 3)) == 1
    assert spans.of_iteration(recorded, "train.wait_prev_tree", 0) == []
    assert [c["tree"] for c in spans.window_counters(ctx)] == [2, 3, 4]
    assert spans.mean([]) is None and spans.mean([1.0, 2.0]) == 1.5


def test_self_time_is_the_duration_less_what_children_cover():
    def ev(i, ts, dur, parent=None):
        return {"name": "n%d" % i, "id": i, "parent": parent, "ts": ts, "dur": dur, "args": {}}

    parent = ev(1, 100.0, 1000.0)
    evs = [parent,
           ev(2, 200.0, 100.0, 1),            # 100 covered
           ev(3, 250.0, 150.0, 1),            # overlaps the last: 100 more
           ev(4, 1050.0, 500.0, 1),           # runs past the parent's end: 50
           ev(5, 300.0, 50.0, 3),             # a grandchild: the child's matter
           ev(6, 500.0, 100.0, None),         # inside in time, not a child
           {"name": "c", "id": 7, "parent": 1, "ts": 600.0, "args": {}}]  # a counter
    assert spans.self_us(evs, parent) == 1000.0 - 100.0 - 100.0 - 50.0
    assert spans.self_us(evs, evs[2]) == 150.0 - 50.0
    assert spans.self_us(evs, evs[5]) == 100.0


EXPECTED = {
    "dataset.construct_s": 4.25,             # 4 s + the valid set's 0.25 s, the nested one once
    "compile.trace_lower_s": 0.5 + 2 * 0.15,
    "compile.backend_s": 0.4 + 2 * 0.09,     # the late 7 s lie in the window
    "engine.wait_ms_per_iter": 600.0,
    "engine.dispatch_ms_per_iter": 400.0,    # the phase stays in
    "engine.boundary_ms_per_iter": 0.5,
    "grow.steps_per_tree": (6.0 + 7.0 + 8.0) / 3,
    "grow.hist_rows_ratio": 6000.0 / (1000 + 400 + 250),
    "grow.spec_hit_pct": 80.0,
}


@pytest.mark.parametrize("metric", sorted(EXPECTED))
def test_reader_on_the_recorded_events(recorded, ctx, metric, capsys):
    assert metric in SPAN_METRICS
    assert MAN.reader(metric)(ctx) == pytest.approx(EXPECTED[metric], rel=1e-12)
    if metric == "grow.hist_rows_ratio":  # the program's own count, beside
        assert "needed 4950 by the trees and 6300 by the program" in capsys.readouterr().err


@pytest.mark.parametrize("metric", sorted(EXPECTED))
@pytest.mark.parametrize("ring", ["empty", "none", "other_run"])
def test_reader_reports_nothing_where_its_events_are_missing(monkeypatch, ctx, metric, ring):
    """An empty ring, a program with no such read-out (this PR's parent), and
    a ring that holds another run's iterations: None, never 0."""
    held = {"empty": [], "none": None,
            "other_run": [e for e in _recorded() if e["args"].get("iteration", 9) < WARMUP]}[ring]
    monkeypatch.setattr(spans, "events", lambda: held)
    assert MAN.reader(metric)(ctx) is None


def test_a_program_without_the_read_out_gives_none(monkeypatch):
    from lightgbm_tpu.obs import trace

    assert isinstance(spans.events(), list)
    monkeypatch.delattr(trace, "events")
    assert spans.events() is None


def test_the_readers_agree_with_a_real_run_of_the_program(ctx):
    """The names are a contract between the program and the readers: a tiny
    training run has to leave what each of them looks for."""
    import lightgbm_tpu as lgb
    from lightgbm_tpu.obs import trace

    trace.reset()
    rng = np.random.RandomState(0)
    X = rng.randn(ROWS, 7)
    y = (X[:, 0] > 0).astype(float)
    bst = lgb.train({"objective": "binary", "num_leaves": 7, "verbosity": -1},
                    lgb.Dataset(X, label=y), num_boost_round=WARMUP + ITERATIONS)
    from benchmarks import model_text

    trees = model_text.parse_trees(bst.model_to_string())
    ctx["window_trees"] = trees[WARMUP: WARMUP + ITERATIONS]
    got = {m: MAN.reader(m)(ctx) for m in SPAN_METRICS}
    assert all(v is not None for v in got.values()), got
    assert got["grow.hist_rows_ratio"] >= 1.0
    assert got["grow.spec_hit_pct"] <= 100.0 and got["grow.steps_per_tree"] <= 6
    assert got["engine.dispatch_ms_per_iter"] > 0 and got["dataset.construct_s"] > 0
