"""The reduction from trace rows to busy, idle and per-program time: on rows
written by hand, and on the cut-down trace recorded on the v5e
(``benchmarks/fixtures/trace_v5e.json``)."""
import json
import os

import pytest

from benchmarks import trace_reduce
from benchmarks.manifest import HERE, Manifest

DEV, HOST = "/device:TPU:0", "/host:CPU"
OPS, MODS = trace_reduce.OPS_LINE, trace_reduce.MODULES_LINE
SPANS = {"bench.callback": "in the benchmark's callback",
         "bench.loop": "in the boosting loop between callbacks"}

# one chip, times in ns. A program of 1000 ns holding a `while` of 900 ns
# whose body ran two fusions (300 + 200 ns), then 500 ns of nothing, then a
# second program of 400 ns holding one operation of 400 ns.
HAND = [
    [DEV, MODS, "jit_grow_tree(123)", 0, 1000],
    [DEV, OPS, "while.1", 50, 900],
    [DEV, OPS, "fusion.7", 100, 300],
    [DEV, OPS, "fusion.9", 500, 200],
    [DEV, MODS, "jit_step(77)", 1500, 400],
    [DEV, OPS, "fusion.2", 1500, 400],
    [HOST, "python", "bench.callback", 1100, 300],
    [HOST, "python", "bench.loop", 1400, 1100],
    [HOST, "python", "bench.callback", 2500, 100],
    [HOST, "python", "something else", 0, 5000],
    ["/device:TPU:0 SparseCore", OPS, "not a chip's plane", 0, 9000],
]


def test_union_merges_what_overlaps():
    assert trace_reduce.union([(5, 9), (0, 3), (2, 4), (9, 10)]) == [(0, 4), (5, 10)]


def test_self_times_take_nested_time_out_of_the_holder():
    own = trace_reduce.self_times(
        [("while.1", 50, 900), ("fusion.7", 100, 300), ("fusion.9", 500, 200)])
    assert own == {"while.1": 400, "fusion.7": 300, "fusion.9": 200}


def test_module_name_drops_the_fingerprint():
    assert trace_reduce.module_name("jit_grow_tree(1234567)") == "jit_grow_tree"
    assert trace_reduce.module_name("jit__lambda_") == "jit__lambda_"


def test_reduce_on_rows_written_by_hand():
    out = trace_reduce.reduce(HAND, host_spans=SPANS)
    assert out["chips"] == 1
    # an operation ran in [50,950) and [1500,1900)
    assert out["busy_s"] == pytest.approx(1300e-9)
    assert out["window_s"] == pytest.approx(1850e-9)
    assert out["modules"] == {
        "jit_grow_tree": {"count": 1, "seconds": pytest.approx(1000e-9)},
        "jit_step": {"count": 1, "seconds": pytest.approx(400e-9)}}
    assert out["device_ops"][:2] == [["fusion.2", pytest.approx(400e-9)],
                                     ["while.1", pytest.approx(400e-9)]] \
        or out["device_ops"][:2] == [["while.1", pytest.approx(400e-9)],
                                     ["fusion.2", pytest.approx(400e-9)]]
    # the one gap, 950..1500, has its middle inside the first callback
    assert out["idle_gaps"] == [["in the benchmark's callback", pytest.approx(550e-9)]]


@pytest.mark.parametrize("gap, what", [
    ((100, 200), "in the benchmark's callback"),
    ((300, 400), "in the boosting loop between callbacks"),
    ((500, 600), "elsewhere")])
def test_an_idle_gap_is_named_by_the_host_span_it_lies_in(gap, what):
    rows = [[DEV, OPS, "fusion.1", gap[0] - 100, 100], [DEV, OPS, "fusion.2", gap[1], 100],
            [HOST, "python", "bench.callback", 90, 120],
            [HOST, "python", "bench.loop", 310, 80]]
    out = trace_reduce.reduce(rows, host_spans=SPANS)
    assert out["idle_gaps"] == [[what, pytest.approx(100e-9)]]
    assert trace_reduce.reduce(rows)["idle_gaps"] == [["elsewhere", pytest.approx(100e-9)]]


def test_two_chips_are_averaged():
    second = [[DEV.replace("0", "1"), r[1], r[2], r[3], r[4]] for r in HAND if r[0] == DEV]
    out = trace_reduce.reduce(HAND + second)
    assert out["chips"] == 2 and out["busy_s"] == pytest.approx(1300e-9)
    assert out["modules"]["jit_grow_tree"]["count"] == 2
    assert out["modules"]["jit_grow_tree"]["seconds"] == pytest.approx(1000e-9)


def test_a_trace_without_device_operations_gives_nothing():
    assert trace_reduce.reduce([r for r in HAND if r[0] != DEV]) is None
    assert trace_reduce.reduce([]) is None


def test_dump_keeps_programs_and_cuts_operations(tmp_path):
    rows = HAND + [[DEV, OPS, "fusion.%d" % i, 3000 + i, 1] for i in range(50)]
    path = os.path.join(str(tmp_path), "sub", "trace.json")
    trace_reduce.dump(rows, path, ops_kept=10)
    with open(path) as fh:
        kept = json.load(fh)
    assert kept["lines"]["%s | %s" % (DEV, OPS)] == 54
    assert sum(r[1] == OPS and r[0] == DEV for r in kept["rows"]) == 10
    assert sum(r[1] == MODS for r in kept["rows"]) == 2


# -- the recorded trace ------------------------------------------------------

FIXTURE = os.path.join(HERE, "fixtures", "trace_v5e.json")


@pytest.fixture(scope="module")
def recorded():
    with open(FIXTURE) as fh:
        return json.load(fh)


def test_recorded_trace_reduces_to_its_known_numbers(recorded):
    out = trace_reduce.reduce(recorded["rows"], host_spans=SPANS)
    want = recorded["expect"]
    assert out["chips"] == want["chips"]
    assert out["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    assert out["window_s"] == pytest.approx(want["window_s"], rel=1e-9)
    assert 0 < out["busy_s"] <= out["window_s"]
    for name, m in want["modules"].items():
        assert out["modules"][name]["count"] == m["count"]
        assert out["modules"][name]["seconds"] == pytest.approx(m["seconds"], rel=1e-9)
    assert any("grow_tree" in name for name in out["modules"])
    assert len(out["device_ops"]) <= 10 and len(out["idle_gaps"]) <= 10


@pytest.mark.parametrize("metric", [m["name"] for m in Manifest().data["per_layer"]
                                    if m["source"] == "device_trace"])
def test_trace_metric_readers_on_the_recorded_trace(recorded, metric):
    out = trace_reduce.reduce(recorded["rows"], host_spans=SPANS)
    out["traced_iterations"] = recorded["expect"]["traced_iterations"]
    read = Manifest().reader(metric)
    assert read({"trace": out}) == pytest.approx(recorded["expect"]["metrics"][metric], rel=1e-9)
    # a reader that finds nothing to read returns nothing, never 0
    assert read({"trace": None}) is None
