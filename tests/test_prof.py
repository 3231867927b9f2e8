"""Kernel-level performance attribution (ISSUE 6): the segment profiler
(obs/prof.py), the measured cost-analysis book + roofline peak table
(obs/costs.py), and the bench regression gate (helpers/bench_diff.py).

The load-bearing assertions:
  * the segmented (fenced sub-step) grower's final model is BITWISE
    identical to the fused grower's — the proof that the breakdown measures
    the real computation;
  * cost-analysis byte counts agree with memwatch's shape math for the
    same tensors;
  * the bench_diff golden fixtures behave: the synthetic ~10% regression
    FAILS the gate, the improvement PASSES.
"""
import os

import numpy as np
import pytest

import jax.numpy as jnp

import helpers.bench_diff as bench_diff
import lightgbm_tpu as lgb
from lightgbm_tpu.obs import REGISTRY, memwatch
from lightgbm_tpu.obs import costs as costs_mod
from lightgbm_tpu.obs import prof as prof_mod
from lightgbm_tpu.ops.histogram import leaf_histogram
from lightgbm_tpu.utils.log import LightGBMError

GOLD = os.path.join(os.path.dirname(__file__), "golden", "bench_diff")


@pytest.fixture(autouse=True)
def _clean_cost_book():
    costs_mod.COSTS.reset()
    yield
    costs_mod.COSTS.reset()


def _make_booster(seed=7, n=1024, f=5, leaves=15, **extra):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, f).astype(np.float32)
    y = (X[:, 0] + 0.5 * X[:, 1] * X[:, 2] + rng.randn(n) * 0.3 > 0).astype(
        np.float32
    )
    params = dict(objective="binary", num_leaves=leaves, verbosity=-1,
                  **extra)
    bst = lgb.Booster(params=params, train_set=lgb.Dataset(X, label=y))
    bst.update()
    return bst


@pytest.fixture(scope="module")
def prof_record():
    """One shared profiling run (compiles the fused grower + every segment
    kernel once for the whole module)."""
    bst = _make_booster()
    return prof_mod.profile_growth(bst, iters=2)


# --------------------------------------------------------------------------
# segment profiler
# --------------------------------------------------------------------------

def test_segmented_model_bitwise_identical(prof_record):
    assert prof_record["bitwise_identical"] is True


def test_breakdown_structure(prof_record):
    segs = prof_record["segments_per_tree_s"]
    for name in prof_mod.CORE_SEGMENTS:
        assert name in segs, (name, sorted(segs))
        assert segs[name] >= 0.0
    assert prof_record["trees"] == 2
    assert prof_record["splits_per_tree"] > 1
    # every per-split segment fired once per split (counts include the
    # warmup-excluded timed passes only)
    counts = prof_record["segment_counts"]
    per_split = int(prof_record["splits_per_tree"] * prof_record["trees"])
    for name in prof_mod.CORE_SEGMENTS:
        assert counts[name] == per_split, (name, counts[name], per_split)


def test_segment_sum_tracks_fused_time(prof_record):
    """The fenced segments re-run the same computation; their sum must land
    in the same ballpark as the fused phase (the tight 15% bound is asserted
    at the bench shape by bench.py's prof block — at this tiny test shape
    per-dispatch overhead dominates, so the bound here is loose)."""
    ratio = prof_record["segment_sum_ratio"]
    assert 0.2 < ratio < 8.0, ratio
    assert prof_record["fused_growth_s_per_tree"] > 0


def test_run_report_carries_growth_segments(prof_record):
    report = REGISTRY.run_report()
    assert "growth_segments_s" in report
    assert set(prof_mod.CORE_SEGMENTS) <= set(report["growth_segments_s"])
    prom = REGISTRY.prometheus_text()
    assert "lgbtpu_growth_segment_seconds_total" in prom


def test_profile_growth_never_mutates_trainer_rng():
    """The never-mutates guarantee includes the feature-sampling RNG
    position (checkpoint/resume byte-identity depends on it): profiling a
    feature_fraction<1 booster must leave the stream where it found it."""
    bst = _make_booster(n=512, leaves=7, feature_fraction=0.6)
    rng_state = bst._gbdt._feat_rng.get_state()
    scores_before = np.asarray(bst._gbdt.scores)
    prof_mod.profile_growth(bst, iters=1)
    after = bst._gbdt._feat_rng.get_state()
    assert rng_state[0] == after[0] and np.array_equal(rng_state[1], after[1])
    assert rng_state[2:] == after[2:]
    assert np.array_equal(scores_before, np.asarray(bst._gbdt.scores))


def test_unsupported_reasons():
    masked = _make_booster(n=512, leaves=7, tpu_hist_mode="masked")
    reason = prof_mod.unsupported_reason(masked._gbdt)
    assert reason is not None and "masked" in reason
    with pytest.raises(LightGBMError):
        prof_mod.profile_growth(masked, iters=1)
    pooled = _make_booster(n=512, leaves=7, histogram_pool_size=0.001)
    assert prof_mod.unsupported_reason(pooled._gbdt) is not None


# --------------------------------------------------------------------------
# cost-analysis book + peak table
# --------------------------------------------------------------------------

def test_cost_bytes_match_memwatch_shape_math():
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


def test_cost_harvest_during_training(monkeypatch):
    monkeypatch.setenv(costs_mod.ENV_COSTS, "1")
    _make_booster(seed=11, n=512, f=4, leaves=7)
    book = costs_mod.COSTS.report()
    assert "ops.grow_tree" in book, sorted(book)
    assert book["ops.grow_tree"].get("flops", 0) > 0
    report = REGISTRY.run_report()
    assert "cost_analysis" in report
    prom = REGISTRY.prometheus_text()
    assert 'lgbtpu_xla_cost_flops{executable="ops.grow_tree"}' in prom
    # the satellite wiring: per-name compile counts ride next to the costs
    assert 'lgbtpu_jit_traces{name="ops.grow_tree"}' in prom


def test_costs_disabled_by_default(monkeypatch):
    monkeypatch.delenv(costs_mod.ENV_COSTS, raising=False)
    assert not costs_mod.enabled()
    _make_booster(seed=13, n=512, f=4, leaves=7)
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


# --------------------------------------------------------------------------
# bench_diff regression gate
# --------------------------------------------------------------------------

def _gold(name):
    return bench_diff.load_bench_json(os.path.join(GOLD, name + ".json"))


def test_bench_diff_regression_fixture_fails():
    rows, failed = bench_diff.compare(_gold("regression"), _gold("baseline"))
    assert failed
    fails = {r["metric"] for r in rows if r["status"] == bench_diff.FAIL}
    assert "value(iters/s)" in fails  # the synthetic ~10% throughput drop
    assert "predict.retraces_after_warmup" in fails
    warns = {r["metric"] for r in rows if r["status"] == bench_diff.WARN}
    assert "roofline_source" in warns  # measured -> analytic flip


def test_bench_diff_improvement_fixture_passes():
    rows, failed = bench_diff.compare(_gold("improvement"), _gold("baseline"))
    assert not failed
    assert any(
        r["metric"] == "value(iters/s)" and r["status"] == bench_diff.PASS
        for r in rows
    )


def test_bench_diff_platform_mismatch_skips_throughput():
    base = _gold("baseline")
    cur = dict(_gold("regression"), platform="tpu")
    rows, _ = bench_diff.compare(cur, base)
    row = next(r for r in rows if r["metric"] == "value(iters/s)")
    assert row["status"] == bench_diff.SKIP


def test_bench_diff_self_test_green():
    assert bench_diff.self_test() == 0


def test_bench_diff_small_drop_passes():
    base = _gold("baseline")
    cur = dict(_gold("improvement"))
    cur["value"] = base["value"] * 0.97  # -3% < the 5% threshold
    rows, failed = bench_diff.compare(cur, base)
    assert not failed
