"""The three readers of the grower's padded work (``grower.hist_gather_ratio``,
``grower.part_take_ratio``, ``grower.part_lanes_ratio``) on recorded events:
each its ratio of two counters over the window's trees, nothing (never 0)
where its counter is missing, as in a parent that does not count it; and
each something after a tiny training on the CPU."""
import numpy as np
import pytest

from benchmarks import spans
from benchmarks.manifest import Manifest

MAN = Manifest()
READERS = {
    "grower.hist_gather_ratio": ("hist_rows_gathered", "hist_rows_needed"),
    "grower.part_take_ratio": ("part_rows_taken", "part_rows_needed"),
    "grower.part_lanes_ratio": ("part_rows_streamed", "part_rows_needed"),
}
WARMUP, ITERATIONS = 2, 3
CTX = {"traffic": {"warmup_iterations": WARMUP}, "iterations": ITERATIONS}


def _counters(**more):
    """Six trees' grow.counters, one an iteration, as a parent that counts
    neither new work leaves them, with ``more`` on each."""
    return [{"name": "grow.counters", "args": dict({
        "tree": k, "iteration": k, "steps": 5.0, "slots_computed": 9.0,
        "splits": 8.0, "hist_rows_streamed": 6000.0, "hist_rows_needed": 2000.0,
        "part_rows_needed": 3000.0}, **more)} for k in range(6)]


@pytest.mark.parametrize("reader", sorted(READERS))
def test_each_reader_is_its_ratio_over_the_window(monkeypatch, reader):
    top, bottom = READERS[reader]
    evs = _counters(hist_rows_gathered=7000.0, part_rows_taken=48000.0,
                    part_rows_streamed=4000.0)
    # the window's trees alone: the warm-up's and the traced ones' differ
    for e in evs:
        if e["args"]["iteration"] not in range(WARMUP, WARMUP + ITERATIONS):
            e["args"][top] = 10 ** 9
    monkeypatch.setattr(spans, "events", lambda: evs)
    want = {"hist_rows_gathered": 7000.0, "part_rows_taken": 48000.0,
            "part_rows_streamed": 4000.0}[top] / {"hist_rows_needed": 2000.0,
                                                 "part_rows_needed": 3000.0}[bottom]
    assert MAN.reader(reader)(CTX) == pytest.approx(want)


@pytest.mark.parametrize("reader", sorted(READERS))
@pytest.mark.parametrize("ring", ["empty", "none", "parent", "nothing_needed"])
def test_a_reader_reports_nothing_where_its_counter_lacks(monkeypatch, reader, ring):
    """An empty ring, no ring, the parent's counters (without the new two,
    and here without ``part_rows_streamed`` too), and trees that needed no
    row: None, never 0."""
    top, bottom = READERS[reader]
    held = {"empty": [], "none": None, "parent": _counters(),
            "nothing_needed": _counters(**{top: 100.0, bottom: 0.0})}[ring]
    monkeypatch.setattr(spans, "events", lambda: held)
    assert MAN.reader(reader)(CTX) is None


def test_the_readers_after_a_tiny_training_on_the_cpu(monkeypatch):
    """The names are a contract between the program and the readers: a
    training leaves what each looks for, and each reads at least 1."""
    import lightgbm_tpu as lgb
    from lightgbm_tpu.obs import trace

    rng = np.random.RandomState(3)
    X = rng.randn(3000, 8)
    y = (X[:, 0] + X[:, 1] * X[:, 2] > 0).astype(np.float32)
    trace.reset()
    lgb.train({"objective": "binary", "num_leaves": 15, "verbosity": -1},
              lgb.Dataset(X, label=y), num_boost_round=WARMUP + ITERATIONS
              ).model_to_string()
    got = {r: MAN.reader(r)(CTX) for r in READERS}
    assert all(v is not None and v >= 1.0 for v in got.values()), got
    # the sequential grower on the CPU takes one column of N rows a split
    assert got["grower.part_take_ratio"] > got["grower.part_lanes_ratio"]
