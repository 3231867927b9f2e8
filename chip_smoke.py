#!/usr/bin/env python3
"""First contact with the chip: train, check the kernels, serve — one process.

    python chip_smoke.py                   the default run (needs a TPU)
    python chip_smoke.py --chunk 4         + lax.scan loop == per-iteration loop
    python chip_smoke.py --grower-equality + spec grower == sequential grower
    python chip_smoke.py --devices 4       + data-parallel over four chips
    python chip_smoke.py --rehearse [...]  tiny shapes, any backend; every
                                           line says REHEARSAL and can never
                                           be read as a pass

The default run drives the public entry points at the full width of the one
configuration the repo measures (1M x 28, 255 leaves, 255 bins, binary):
``lgb.Dataset`` -> ``lgb.train`` under whatever routing a TPU process picks,
then every histogram implementation the router may offer on a TPU plus the
Pallas split kernel compiled for real (``interpret=False``) against a float64
oracle, then ``Booster.to_packed()`` and a ``ServeApp`` in this same process.
A leg flag replaces the kernel and serve phases with that leg's comparison
against the default training.

No child process touches JAX, no ``LIGHTGBM_TPU_*`` variable is set, no
platform is selected in code. A failed phase is a non-zero exit; without
``--rehearse`` so is any backend other than a TPU whose ``device_kind``
``obs.costs`` knows. The last line of a run that reached the chip is exactly
``{"ok": true|false, "device": {"platform", "kind", "count"}}``, the device
as JAX reports it; what the phases observed is one ``report:`` object on the
line before. The timings it prints are smoke observations, not a benchmark.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time
from typing import Dict, List, Optional

import numpy as np

# the one measured configuration (BASELINE.md's Higgs row)
FULL = dict(rows=1_000_000, features=28, leaves=255, max_bin=255,
            kernel_rows=(4096, 1_000_000), serve_batches=(1, 256, 4096),
            equality_rounds=5)
TINY = dict(rows=6000, features=28, leaves=15, max_bin=255,
            kernel_rows=(4096,), serve_batches=(1, 37, 512),
            equality_rounds=3)
WARMUP_ROUNDS, TIMED_ROUNDS = 3, 10
MIN_TRAIN_AUC = 0.70
# one-hot operands are exact in bf16, but a default-precision MXU pass cuts
# grad/hess to bf16 too: |err| <= 2^-8 * sum|v| per bin (measured 3.4e-3 on
# the v5e for the XLA contractions); gate with a factor of two to spare
HIST_TOL = 2.0 ** -7


class Smoke:
    """What the run prints and what its final JSON line carries."""

    def __init__(self, rehearse: bool) -> None:
        self.rehearse = rehearse
        self.platform = "?"
        self.report: Dict[str, object] = {}
        self.failures: List[str] = []

    def say(self, msg: str) -> None:
        tag = "REHEARSAL [%s] " % self.platform if self.rehearse else ""
        print("%schip_smoke: %s" % (tag, msg), flush=True)

    def fail(self, what: str) -> None:
        self.failures.append(what)
        self.say("FAILED: " + what)

    def check(self, cond: bool, what: str) -> None:
        if not cond:
            self.fail(what)


# ---------------------------------------------------------------------------
# Device
# ---------------------------------------------------------------------------

def phase_device(s: Smoke, n_devices: int) -> None:
    import jax
    import jaxlib

    from lightgbm_tpu.obs.costs import normalize_device_kind

    try:
        import libtpu

        libtpu_version = getattr(libtpu, "__version__", "present")
    except ImportError:
        libtpu_version = None
    backend = jax.default_backend()
    devs = jax.devices()
    s.platform = backend
    kind = devs[0].device_kind
    family = normalize_device_kind(kind)
    s.say("jax %s jaxlib %s libtpu %s backend=%s device_kind=%r (%s) count=%d"
          % (jax.__version__, jaxlib.__version__, libtpu_version, backend,
             kind, family, len(devs)))
    if not s.rehearse:
        if backend != "tpu":
            sys.exit("chip_smoke: no TPU — jax.default_backend() is %r. "
                     "Run it on the chip; --rehearse is the only way to run "
                     "it anywhere else." % backend)
        if family in (None, "cpu"):
            sys.exit("chip_smoke: TPU device_kind %r is not a family "
                     "obs/costs.CHIP_PEAKS knows" % kind)
    if len(devs) < n_devices:
        sys.exit("chip_smoke: --devices %d needs %d devices, JAX reports %d"
                 % (n_devices, n_devices, len(devs)))
    s.report["versions"] = {"jax": jax.__version__,
                            "jaxlib": jaxlib.__version__,
                            "libtpu": libtpu_version}
    s.report["device"] = {"platform": devs[0].platform, "kind": kind,
                          "count": len(devs)}
    s.report["device_family"] = family


def bytes_in_use(key: str = "bytes_in_use") -> List[Optional[int]]:
    """One allocator figure per device (None where the backend keeps none)."""
    import jax

    return [(d.memory_stats() or {}).get(key) for d in jax.devices()]


# ---------------------------------------------------------------------------
# Train
# ---------------------------------------------------------------------------

class Stamps:
    """After-iteration callback: closes the warm-up and the timed window
    inside the one ``lgb.train`` call, the timed one twice."""

    order = 100
    before_iteration = False

    def __init__(self, warm: int) -> None:
        self.warm = warm
        self.t_warm = self.t_block = self.t_fetch = None
        self.mem_warm = None

    def __call__(self, env) -> None:
        import jax

        done = env.iteration - env.begin_iteration + 1
        total = env.end_iteration - env.begin_iteration
        scores = env.model._gbdt.scores
        if self.t_warm is None and done >= self.warm:
            float(np.asarray(scores[0, 0]))  # compiles the fetch used below
            self.mem_warm = bytes_in_use()
            self.t_warm = time.perf_counter()
        elif done == total:
            jax.block_until_ready(scores)
            self.t_block = time.perf_counter()
            float(np.asarray(scores[0, 0]))
            self.t_fetch = time.perf_counter()


def base_params(cfg: dict) -> dict:
    return {"objective": "binary", "num_leaves": cfg["leaves"],
            "max_bin": cfg["max_bin"], "learning_rate": 0.1, "metric": "auc",
            "verbosity": -1}


def train(cfg: dict, ds, rounds: int, stamps: Optional[Stamps] = None,
          **extra):
    import lightgbm_tpu as lgb

    return lgb.train(dict(base_params(cfg), **extra), ds,
                     num_boost_round=rounds, verbose_eval=False,
                     keep_training_booster=True,
                     callbacks=[stamps] if stamps is not None else None)


def trees_only(bst) -> str:
    """The model string without its trailing parameter echo."""
    return bst.model_to_string().split("parameters:")[0]


def first_difference(a: str, b: str) -> str:
    ta, tb = a.split("Tree=")[1:], b.split("Tree=")[1:]
    if len(ta) != len(tb):
        return "%d trees against %d" % (len(ta), len(tb))
    for i, (x, y) in enumerate(zip(ta, tb)):
        if x != y:
            lx, ly = x.splitlines(), y.splitlines()
            line = next((j for j, (p, q) in enumerate(zip(lx, ly)) if p != q),
                        min(len(lx), len(ly)))
            return "tree %d, line %d: %r against %r" % (
                i, line, lx[line][:120] if line < len(lx) else None,
                ly[line][:120] if line < len(ly) else None)
    return "headers differ"


def phase_train(s: Smoke, cfg: dict):
    """The default training; returns (booster, dataset, X)."""
    import jax
    import jax.numpy as jnp

    import lightgbm_tpu as lgb
    from helpers.bench_data import make_higgs_like
    from lightgbm_tpu import native
    from lightgbm_tpu.ops import grow as grow_mod
    from lightgbm_tpu.ops import histogram as hist_mod

    t0 = time.perf_counter()
    native_ok = native.get_lib() is not None
    s.say("native library loaded: %s (%.1fs, g++ on first use)"
          % (native_ok, time.perf_counter() - t0))
    X, y = make_higgs_like(cfg["rows"], cfg["features"])
    params = base_params(cfg)
    t0 = time.perf_counter()
    ds = lgb.Dataset(X, label=y, params=params).construct()
    bin_s = time.perf_counter() - t0
    s.say("binned %d x %d in %.2fs" % (X.shape[0], X.shape[1], bin_s))

    rounds = WARMUP_ROUNDS + TIMED_ROUNDS
    stamps = Stamps(WARMUP_ROUNDS)
    t_start = time.perf_counter()
    bst = train(cfg, ds, rounds, stamps)
    gbdt = bst._gbdt
    setup_s = stamps.t_warm - t_start
    block_s = stamps.t_block - stamps.t_warm
    fetch_s = stamps.t_fetch - stamps.t_warm
    mem_end = bytes_in_use()
    peak = bytes_in_use("peak_bytes_in_use")

    # one scalar device-to-host round trip, ready value and fresh dispatch
    x = jnp.ones((), jnp.float32)
    ready, fresh = [], []
    for i in range(5):
        v = jax.block_until_ready(x + i)
        t0 = time.perf_counter()
        float(v)
        ready.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        float(x * (i + 2))
        fresh.append(time.perf_counter() - t0)

    grow_mode = grow_mod._LAST_GROW_MODE
    routing = {"grower": grow_mode, "spec_hist": grow_mod._LAST_SPEC_HIST,
               "hist_impl": hist_mod.default_impl(),
               "device_chunk": gbdt.device_chunk(),
               "learner": gbdt._learner_kind()}
    s.say("routing: %s" % json.dumps(routing))
    s.say("smoke observation: compile/set-up (%d iterations) %.1fs; "
          "%d iterations %.3fs by block_until_ready, %.3fs by value fetch "
          "-> %.4f / %.4f s/iteration"
          % (WARMUP_ROUNDS, setup_s, TIMED_ROUNDS, block_s, fetch_s,
             block_s / TIMED_ROUNDS, fetch_s / TIMED_ROUNDS))
    s.say("smoke observation: scalar device-to-host %.3f ms ready, %.3f ms "
          "with its dispatch (medians of 5)"
          % (np.median(ready) * 1e3, np.median(fresh) * 1e3))
    s.say("bytes_in_use after warm-up %s, at the end %s, peak %s"
          % (stamps.mem_warm, mem_end, peak))

    trees = gbdt.trees()
    leaves = [int(t.num_leaves) for t in trees]
    score = gbdt._train_score_np()
    auc = train_auc(bst)
    s.say("trees %d, leaves first/min %d/%d, train AUC %.5f"
          % (len(trees), leaves[0] if leaves else 0,
             min(leaves) if leaves else 0, auc))
    s.check(bst.num_trees() == rounds and len(trees) == rounds,
            "expected %d trees, have %d" % (rounds, bst.num_trees()))
    s.check(bool(leaves) and min(leaves) > 1, "a tree did not split")
    s.check(bool(leaves) and leaves[0] == cfg["leaves"],
            "first tree has %s leaves, expected %d"
            % (leaves[:1], cfg["leaves"]))
    s.check(score.shape[-1] == cfg["rows"] and bool(np.isfinite(score).all()),
            "scores not finite or wrong shape %s" % (score.shape,))
    s.check(auc >= MIN_TRAIN_AUC,
            "train AUC %.5f below %.2f" % (auc, MIN_TRAIN_AUC))
    s.report["train"] = {
        "label": "smoke observation, not a benchmark",
        "rows": cfg["rows"], "features": cfg["features"],
        "num_leaves": cfg["leaves"], "max_bin": cfg["max_bin"],
        "native": native_ok, "binning_s": round(bin_s, 3),
        "setup_s": round(setup_s, 2), "warmup_iters": WARMUP_ROUNDS,
        "timed_iters": TIMED_ROUNDS,
        "timed_s_block_until_ready": round(block_s, 4),
        "timed_s_value_fetch": round(fetch_s, 4),
        "s_per_iter": round(fetch_s / TIMED_ROUNDS, 5),
        "d2h_scalar_ms": round(float(np.median(ready)) * 1e3, 3),
        "dispatch_fetch_scalar_ms": round(float(np.median(fresh)) * 1e3, 3),
        "bytes_in_use_warm": stamps.mem_warm, "bytes_in_use_end": mem_end,
        "peak_bytes_in_use": peak, "routing": routing,
        "trees": len(trees), "first_tree_leaves": leaves[0] if leaves else 0,
        "min_leaves": min(leaves) if leaves else 0,
        "train_auc": round(auc, 6),
    }
    return bst, ds, X


# ---------------------------------------------------------------------------
# Kernels
# ---------------------------------------------------------------------------

def _short(e: BaseException) -> str:
    text = "%s: %s" % (type(e).__name__, e)
    return text if len(text) <= 900 else text[:450] + " ... " + text[-450:]


def oracle(bins: np.ndarray, vals: np.ndarray, num_bins: int) -> np.ndarray:
    """[F, B, K] float64 histogram of [F, N] bins over [N, K] values."""
    return np.stack([
        np.stack([np.bincount(b, weights=vals[:, k], minlength=num_bins)
                  for k in range(vals.shape[1])], axis=-1)
        for b in bins
    ])


def phase_kernels(s: Smoke, cfg: dict, interpret: bool, chunk: int) -> None:
    """Every histogram implementation the router may offer on a TPU, and the
    Pallas split kernel, compiled and compared — at the row chunk the
    trainer passes (``tpu_hist_chunk``), in float32, and the Pallas kernels
    at full N in bfloat16 too (it costs them more VMEM). One failure does
    not hide the next: each is recorded, and any of them fails the run."""
    import jax
    import jax.numpy as jnp

    from lightgbm_tpu.ops import histogram as H
    from lightgbm_tpu.ops.split import SplitParams, find_best_split
    from lightgbm_tpu.ops import split_pallas

    F, K = cfg["features"], 3
    n_max = max(cfg["kernel_rows"])
    rng = np.random.RandomState(0)
    mask = (rng.rand(n_max) > 0.3).astype(np.float32)
    vals_np = rng.randn(n_max, K).astype(np.float32)
    vals_np[:, 1] = np.abs(vals_np[:, 1])  # a hessian is positive
    vals_np[:, 2] = 1.0
    vals_np *= mask[:, None]
    errors: List[str] = []
    table = []
    children = {}  # B -> [2, F, B, K] oracle histograms of two row halves
    for B in (15, 63, 255):
        impls = [i for i in H.IMPLS if H.impl_supported(i, B, "tpu")]
        if B == 15:
            impls = [i for i in impls if i in ("xla", "pallas_packed4")]
        bins_np = rng.randint(0, B, size=(F, n_max)).astype(np.uint8)
        for n in cfg["kernel_rows"]:
            b_np, v_np = bins_np[:, :n], vals_np[:n]
            ref = oracle(b_np, v_np, B)
            scale = np.maximum(oracle(b_np, np.abs(v_np), B), 1.0)
            if n == min(cfg["kernel_rows"]) and B != 15:
                children[B] = np.stack([
                    oracle(b_np[:, :n // 2], v_np[:n // 2], B),
                    oracle(b_np[:, n // 2:], v_np[n // 2:], B),
                ]).astype(np.float32)
            b_dev, v_dev = jnp.asarray(b_np), jnp.asarray(v_np)
            h_xla = None
            cases = [(i, "float32") for i in impls]
            if n == n_max:
                cases += [(i, "bfloat16") for i in impls
                          if i in H.hist_pallas.KERNEL_CAPS]
            for impl, dtype in cases:
                row = {"impl": impl, "dtype": dtype, "B": B, "rows": n}
                t0 = time.perf_counter()
                try:
                    h = np.asarray(jax.block_until_ready(H.leaf_histogram(
                        b_dev, v_dev, B, chunk=chunk, impl=impl,
                        hist_dtype=dtype, interpret=interpret,
                    )), np.float64)
                except Exception as e:  # recorded, and fails the run below
                    row["error"] = _short(e)
                    errors.append("%s %s B=%d rows=%d: %s"
                                  % (impl, dtype, B, n, e))
                    s.fail("histogram %s %s B=%d rows=%d does not "
                           "compile/run: %s"
                           % (impl, dtype, B, n, row["error"]))
                    table.append(row)
                    continue
                row["first_call_s"] = round(time.perf_counter() - t0, 2)
                if impl == "xla" and dtype == "float32":
                    h_xla = h
                err = float((np.abs(h - ref) / scale).max())
                row["max_err"] = float("%.3g" % err)
                row["count_exact"] = bool(
                    np.array_equal(h[:, :, 2], ref[:, :, 2]))
                if h_xla is not None:
                    row["max_vs_xla"] = float(
                        "%.3g" % (np.abs(h - h_xla) / scale).max())
                ok = (h.shape == (F, B, K) and np.isfinite(h).all()
                      and err < HIST_TOL and row["count_exact"])
                if not ok:
                    s.fail("histogram %s %s B=%d rows=%d disagrees with "
                           "the oracle: %s" % (impl, dtype, B, n, row))
                table.append(row)
                s.say("histogram %-15s %-8s B=%-3d rows=%-7d err %.2e vs_xla "
                      "%s count_exact=%s first call %.2fs"
                      % (impl, dtype, B, n, err, row.get("max_vs_xla"),
                         row["count_exact"], row["first_call_s"]))

    # the Pallas split kernel against the XLA scan, on two children's
    # histograms as a tree would hand them over — where it is offered on a TPU
    split_rows = []
    params = SplitParams(0.0, 0.0, 0.0, 5, 1e-3, 0.0)
    for B, half in sorted(children.items()):
        hist2 = jnp.asarray(half)
        meta = {"num_bin": jnp.full((F,), B, jnp.int32),
                "missing_type": jnp.zeros((F,), jnp.int32),
                "default_bin": jnp.zeros((F,), jnp.int32),
                "monotone": jnp.zeros((F,), jnp.int32)}
        if not split_pallas.supported(meta, "tpu"):
            s.say("split_pallas B=%d: not offered on a TPU, nothing to "
                  "compile" % B)
            split_rows.append({"kernel": "split_pallas", "B": B,
                               "offered_on_tpu": False})
            continue
        sg, sh, nd = (hist2[:, 0, :, c].sum(axis=1) for c in range(3))
        lo = jnp.full((2,), -jnp.inf, jnp.float32)
        hi = jnp.full((2,), jnp.inf, jnp.float32)
        fmask = jnp.ones((F,), bool)
        want = jax.vmap(lambda a, g, q, m, x, z: find_best_split(
            a, g, q, m, x, z, meta, fmask, params))(hist2, sg, sh, nd, lo, hi)
        row = {"kernel": "split_pallas", "B": B}
        try:
            got = jax.block_until_ready(split_pallas.find_best_split_pair_pallas(
                hist2, sg, sh, nd, lo, hi, meta, fmask, params,
                interpret=interpret))
        except Exception as e:  # recorded, and fails the run below
            row["error"] = _short(e)
            errors.append("split_pallas B=%d: %s" % (B, e))
            s.fail("split_pallas B=%d does not compile/run: %s"
                   % (B, row["error"]))
            split_rows.append(row)
            continue
        gw, gg = np.asarray(want.gain), np.asarray(got.gain)
        same = (np.array_equal(got.feature, want.feature)
                and np.array_equal(got.threshold, want.threshold))
        close = bool(np.allclose(gg, gw, rtol=1e-4, atol=1e-4))
        tie = bool(np.allclose(gg, gw, rtol=1e-6, atol=1e-6))
        row.update(gain_xla=[float(v) for v in gw],
                   gain_pallas=[float(v) for v in gg],
                   same_split=bool(same))
        s.say("split_pallas B=%d same_split=%s gains %s against %s"
              % (B, same, row["gain_pallas"], row["gain_xla"]))
        if not (close and (same or tie)):
            s.fail("split_pallas B=%d disagrees with find_best_split: %s"
                   % (B, row))
        split_rows.append(row)
    s.report["kernels"] = {"histogram": table, "split": split_rows,
                           "chunk": chunk, "interpret": interpret}
    if errors:
        os.makedirs("chiprun_out", exist_ok=True)
        with open(os.path.join("chiprun_out", "chip_smoke_kernel_errors.txt"),
                  "w") as fh:
            fh.write("\n\n=====\n\n".join(errors))


# ---------------------------------------------------------------------------
# Serve
# ---------------------------------------------------------------------------

def phase_serve(s: Smoke, cfg: dict, bst, X) -> None:
    from lightgbm_tpu.serve.server import ServeApp

    packed = bst.to_packed()
    app = ServeApp(mode="exact")
    rows_served = 0
    try:
        with tempfile.TemporaryDirectory(prefix="chip_smoke_") as td:
            path = os.path.join(td, "model.txt")
            bst.save_model(path)
            served = app.registry.load("smoke", path)
            s.check(served.ensemble.fingerprint == packed.fingerprint,
                    "served model is not the packed model just trained")
            for i, b in enumerate(cfg["serve_batches"]):
                for rep in range(2):
                    lo = (i * 2 + rep) * 7
                    rows = np.asarray(X[lo:lo + b], np.float64)
                    want = bst.predict(rows)
                    got, _ = app.predict(rows, model="smoke")
                    direct = packed.predict(rows)
                    rows_served += b
                    s.check(
                        got.shape == want.shape
                        and bool(np.isfinite(got).all())
                        and np.array_equal(got, want)
                        and np.array_equal(direct, want),
                        "serve batch %d: ServeApp/to_packed differ from "
                        "Booster.predict (max |d| %.3g)"
                        % (b, float(np.abs(got - want).max())))
        counters = app.metrics.counters()
    finally:
        app.close()
    fallbacks = int(counters.get("serve_cpu_fallback", 0))
    s.check(fallbacks == 0, "serve_cpu_fallback = %d" % fallbacks)
    s.say("serve: backend %s, %d requests, %d rows at batches %s bit-equal "
          "to Booster.predict, serve_cpu_fallback=%d"
          % (app.backend, int(counters.get("requests", 0)), rows_served,
             list(cfg["serve_batches"]), fallbacks))
    s.report["serve"] = {"backend": app.backend,
                         "batches": list(cfg["serve_batches"]),
                         "requests": int(counters.get("requests", 0)),
                         "rows": rows_served, "bit_equal": True,
                         "serve_cpu_fallback": fallbacks}


# ---------------------------------------------------------------------------
# Legs
# ---------------------------------------------------------------------------

def compare_trees(s: Smoke, name: str, trees, ref_trees) -> Dict[str, object]:
    """Tree for tree against a reference model: splits must be the same;
    leaf values may differ by what a regrouped f32 sum explains."""
    s.check(len(trees) == len(ref_trees),
            "%s: %d trees against %d" % (name, len(trees), len(ref_trees)))
    first_bad, worst = None, 0.0
    for i, (a, b) in enumerate(zip(trees, ref_trees)):
        if not (a.num_leaves == b.num_leaves
                and np.array_equal(a.split_feature, b.split_feature)
                and np.array_equal(a.threshold_bin, b.threshold_bin)):
            first_bad = i
            break
        worst = max(worst, float(np.abs(
            np.asarray(a.leaf_value) - np.asarray(b.leaf_value)).max()))
    return {"splits_equal": first_bad is None,
            "first_tree_with_other_splits": first_bad,
            "max_leaf_value_diff": worst}


def train_auc(bst) -> float:
    return float(next(v for (_, m, v, _) in bst.eval_train() if m == "auc"))


def leg_chunk(s: Smoke, cfg: dict, ds, ref_bst, chunk: int) -> None:
    rounds = WARMUP_ROUNDS + TIMED_ROUNDS
    t0 = time.perf_counter()
    bst = train(cfg, ds, rounds, device_chunk_size=chunk)
    dt = time.perf_counter() - t0
    reason = bst._gbdt.device_chunk_fallback_reason()
    ref_model, model = trees_only(ref_bst), trees_only(bst)
    same = model == ref_model
    cmp = compare_trees(s, "--chunk", bst._gbdt.trees(), ref_bst._gbdt.trees())
    auc = train_auc(bst)
    s.say("--chunk %d: lax.scan loop engaged=%s, %.1fs for %d iterations "
          "with its compile, train AUC %.5f; against the per-iteration model: "
          "strings equal %s, %s"
          % (chunk, reason is None, dt, rounds, auc, same, json.dumps(cmp)))
    s.check(reason is None, "chunked loop fell back: %s" % reason)
    if not same:
        s.fail("--chunk %d model differs from the per-iteration model: %s"
               % (chunk, first_difference(ref_model, model)))
    s.report["chunk"] = dict(cmp, chunk=chunk, engaged=reason is None,
                             model_equal=same, wall_s=round(dt, 1),
                             train_auc=round(auc, 6))


def leg_grower_equality(s: Smoke, cfg: dict, ds) -> None:
    """Spec grower against the sequential one, the way the tests force them:
    the import-time switch patched, the jit caches dropped."""
    import jax

    from lightgbm_tpu.ops import grow as grow_mod

    rounds = cfg["equality_rounds"]
    models, took = {}, {}
    saved = grow_mod._ENV_GROW
    try:
        for mode in ("spec", "seq"):
            grow_mod._ENV_GROW = mode
            jax.clear_caches()
            t0 = time.perf_counter()
            bst = train(cfg, ds, rounds)
            jax.block_until_ready(bst._gbdt.scores)
            took[mode] = time.perf_counter() - t0
            s.check(grow_mod._LAST_GROW_MODE == mode,
                    "asked for the %s grower, traced %s"
                    % (mode, grow_mod._LAST_GROW_MODE))
            models[mode] = trees_only(bst)
    finally:
        grow_mod._ENV_GROW = saved
        jax.clear_caches()
    same = models["spec"] == models["seq"]
    diff = None if same else first_difference(models["spec"], models["seq"])
    s.say("--grower-equality: %d iterations each, spec %.1fs seq %.1fs with "
          "their compiles, model strings equal: %s%s"
          % (rounds, took["spec"], took["seq"], same,
             "" if same else " — first difference: " + diff))
    if not same:
        s.fail("spec and sequential growers disagree: " + diff)
    s.report["grower_equality"] = {
        "rounds": rounds, "equal": same, "first_difference": diff,
        "wall_s": {k: round(v, 1) for k, v in took.items()}}


def leg_devices(s: Smoke, cfg: dict, ds, ref_bst, n_devices: int) -> None:
    """tree_learner=data over ``n_devices`` chips, per-iteration and as a
    chunked scan, against the one-device model."""
    import jax

    rounds = WARMUP_ROUNDS + TIMED_ROUNDS
    ref_trees = ref_bst._gbdt.trees()
    rec: Dict[str, object] = {"devices": n_devices}
    models = {}
    for chunk in (1, 4):
        name = "data_chunk%d" % chunk
        stamps = Stamps(WARMUP_ROUNDS)
        t0 = time.perf_counter()
        bst = train(cfg, ds, rounds, stamps, tree_learner="data",
                    num_machines=n_devices, device_chunk_size=chunk)
        gbdt = bst._gbdt
        jax.block_until_ready(gbdt.scores)
        wall = time.perf_counter() - t0
        kind = gbdt._learner_kind()
        shards = len(gbdt.bins_dev.sharding.device_set)
        mem = bytes_in_use()
        timed = stamps.t_fetch - stamps.t_warm
        done_timed = rounds - WARMUP_ROUNDS if chunk == 1 else None
        s.say("--devices %d %s: learner=%s bins_dev over %d devices, "
              "scores over %d, %.1fs with compile, bytes_in_use per device "
              "%s%s"
              % (n_devices, name, kind, shards,
                 len(gbdt.scores.sharding.device_set), wall, mem,
                 "" if done_timed is None else
                 ", smoke observation %.4f s/iteration"
                 % (timed / done_timed)))
        s.check(kind == "data", "learner is %r, not data" % kind)
        s.check(shards == n_devices,
                "bins_dev sharded over %d devices, expected %d"
                % (shards, n_devices))
        if chunk > 1:
            reason = gbdt.device_chunk_fallback_reason()
            s.check(reason is None, "sharded chunk fell back: %s" % reason)
        models[chunk] = trees_only(bst)
        cmp = compare_trees(s, name, gbdt.trees(), ref_trees)
        if not cmp["splits_equal"]:
            s.fail("%s: tree %d splits differ from the one-device model's"
                   % (name, cmp["first_tree_with_other_splits"]))
        else:
            s.check(cmp["max_leaf_value_diff"] < 2e-4,
                    "%s: leaf values off by %.3g"
                    % (name, cmp["max_leaf_value_diff"]))
        rec[name] = dict(cmp, learner=kind, bins_shards=shards,
                         bytes_in_use=mem, wall_s=round(wall, 1),
                         s_per_iter=(None if done_timed is None
                                     else round(timed / done_timed, 5)),
                         train_auc=round(train_auc(bst), 6))
        s.say("%s against the one-device model: %s, train AUC %.5f"
              % (name, json.dumps(cmp), rec[name]["train_auc"]))
    same = models[1] == models[4]
    s.say("--devices %d: per-iteration and chunked data-parallel model "
          "strings equal: %s" % (n_devices, same))
    if not same:
        s.fail("data-parallel chunk=4 differs from per-iteration: "
               + first_difference(models[1], models[4]))
    rec["chunk_equals_per_iteration"] = same
    s.report["devices"] = rec


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny shapes, any backend; never a pass")
    ap.add_argument("--chunk", type=int, default=0, metavar="K",
                    help="leg: device_chunk_size=K against per-iteration")
    ap.add_argument("--grower-equality", action="store_true",
                    help="leg: spec grower against the sequential grower")
    ap.add_argument("--devices", type=int, default=1, metavar="N",
                    help="leg: tree_learner=data over N chips")
    args = ap.parse_args(argv)
    cfg = TINY if args.rehearse else FULL
    s = Smoke(args.rehearse)

    from lightgbm_tpu.utils import platform as platform_mod

    if args.rehearse and args.devices > 1:
        # virtual devices, and only when JAX_PLATFORMS=cpu says so
        platform_mod.ensure_virtual_devices(args.devices)
    cache_dir = platform_mod.place_compile_cache()
    phase_device(s, args.devices)
    s.say("compile cache: %s" % cache_dir)
    s.report["compile_cache"] = cache_dir

    bst, ds, X = phase_train(s, cfg)
    legs = bool(args.chunk or args.grower_equality or args.devices > 1)
    if not legs:
        interpret = s.platform != "tpu"  # only reachable under --rehearse
        phase_kernels(s, cfg, interpret, bst.config.tpu_hist_chunk)
        phase_serve(s, cfg, bst, X)
    if args.chunk:
        leg_chunk(s, cfg, ds, bst, args.chunk)
    if args.devices > 1:
        leg_devices(s, cfg, ds, bst, args.devices)
    if args.grower_equality:  # last: it drops every compiled program
        leg_grower_equality(s, cfg, ds)

    if s.failures:
        s.say("%d failure(s):" % len(s.failures))
        for f in s.failures:
            s.say("  - " + f)
    ok = not s.failures
    # everything printed above, as one object, on a line of its own; the
    # result line after it holds "ok" and "device" and nothing else
    s.say("report: " + json.dumps(dict(s.report, ok=ok)))
    result = json.dumps({"ok": ok, "device": s.report["device"]})
    if args.rehearse:
        s.say("would have printed: " + result)
        s.say("rehearsal %s; this is not a chip run"
              % ("passed" if ok else "failed"))
    else:
        print(result, flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
