"""bench_diff: the bench-regression gate (compare bench JSON vs a baseline).

The BENCH_r01→r05 trajectory was eyeballed by hand; this makes it a gate.
Given two bench.py output records (the one-line JSON the driver captures),
apply per-metric thresholds and emit a markdown verdict table:

  * ``higgs1m_boost_iters_per_sec`` drop > 5%          -> FAIL
  * ``train_auc`` drop > 0.002 absolute                -> FAIL
  * ``predict.rows_per_sec`` drop > 10%                -> FAIL
  * ``predict.retraces_after_warmup`` > 0 (current)    -> FAIL
  * ``jit_retraces_after_warmup`` gauge > 0 (current)  -> FAIL
  * ``error`` field present in current                 -> FAIL
  * ``predict.p99_ms`` rise > 25%                      -> WARN
  * ``growth_segments_s`` share shift > 10 points      -> WARN
  * ``roofline_source`` measured -> analytic           -> WARN
  * ``hist_routing`` changed (env/default impl or
    tune-table digest; obs/tune.py)                    -> WARN
    (a routing flip changes which kernels were measured — the throughput
    rows then reflect routing, never gated as a code regression)
  * serve drift alert counted / PSI gauge > 0.2        -> WARN
    (serve/drift.py: drifted input invalidates comparisons but is a data
    condition, not a code regression)
  * ``device_busy_fraction`` drop > 0.15 /
    ``transfer_seconds`` > 2x (obs/devprof.py)          -> WARN
    (the bound-ness of the run moved — a pointer into the record's
    device_timeline section, never gated as a code regression)
  * ``podwatch`` verdicts present (straggler/stall/dead)
    or iteration spread grew (obs/podwatch.py)          -> WARN
    (fleet-telemetry signals name sick RANKS, not code — a straggling
    host invalidates the throughput comparison but must never FAIL it)

Throughput comparisons apply only between records from the SAME platform —
a CPU-fallback capture vs an on-chip record is apples-to-oranges and every
such row reads SKIP (the ``roofline_source`` stamp exists for the same
reason).

Usage (also wired as ``helpers/check.sh --bench-diff``):

    python helpers/bench_diff.py CURRENT.json BASELINE.json   # hard gate
    python helpers/bench_diff.py --series 'BENCH_r*.json'     # informational
    python helpers/bench_diff.py --self-test                  # golden fixtures

``--self-test`` runs the golden fixtures under tests/golden/bench_diff/:
the synthetic ~10% regression must FAIL and the improvement must PASS —
the gate gating itself.

Stdlib only (no jax, no numpy): runs in driver processes that must never
touch a backend.
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import sys
from typing import Dict, List, Optional, Tuple

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
GOLDEN_DIR = os.path.join(REPO, "tests", "golden", "bench_diff")

THRESHOLDS = {
    "iters_drop_pct": 5.0,
    "auc_drop_abs": 0.002,
    "predict_rows_drop_pct": 10.0,
    "predict_p99_rise_pct": 25.0,
    "segment_share_shift_pts": 10.0,
    "scaling_eff_drop": 0.10,
    "busy_fraction_drop": 0.15,
    "podwatch_spread_growth": 2.0,  # iteration-spread growth factor
}

PASS, WARN, FAIL, SKIP = "PASS", "WARN", "FAIL", "SKIP"


def load_bench_json(path: str) -> Dict:
    """A bench record from any of the shapes it is captured in: bench.py's
    raw one-line JSON, the driver's BENCH_r*.json wrapper (record under
    ``"parsed"``), or a log with stderr lines above the record."""
    with open(path) as fh:
        text = fh.read()
    try:
        doc = json.loads(text)
    except ValueError:
        doc = None
    if isinstance(doc, dict):
        if "metric" in doc:
            return doc
        parsed = doc.get("parsed")
        if isinstance(parsed, dict) and "metric" in parsed:
            return parsed
    for line in text.splitlines():
        line = line.strip()
        if line.startswith("{"):
            try:
                rec = json.loads(line)
            except ValueError:
                continue
            if isinstance(rec, dict) and "metric" in rec:
                return rec
    raise ValueError("no bench record in %s" % path)


def _row(metric, baseline, current, threshold, status, note="") -> Dict:
    return {
        "metric": metric, "baseline": baseline, "current": current,
        "threshold": threshold, "status": status, "note": note,
    }


def _pct(cur: float, base: float) -> float:
    return (cur - base) / base * 100.0 if base else 0.0


def compare(
    current: Dict, baseline: Dict, thresholds: Optional[Dict] = None
) -> Tuple[List[Dict], bool]:
    """(verdict rows, failed). ``failed`` is True iff any row is FAIL."""
    th = dict(THRESHOLDS, **(thresholds or {}))
    rows: List[Dict] = []
    same_platform = current.get("platform") == baseline.get("platform")
    plat_note = (
        ""
        if same_platform
        else "platform %s vs %s — not comparable"
        % (current.get("platform"), baseline.get("platform"))
    )

    if current.get("error"):
        rows.append(_row("error", None, str(current["error"])[:120],
                         "absent", FAIL, "current capture errored"))

    # headline throughput
    base_v, cur_v = baseline.get("value"), current.get("value")
    if base_v and cur_v is not None:
        if not same_platform:
            rows.append(_row("value(iters/s)", base_v, cur_v, "-", SKIP,
                             plat_note))
        else:
            d = _pct(cur_v, base_v)
            status = FAIL if d < -th["iters_drop_pct"] else PASS
            rows.append(_row(
                "value(iters/s)", base_v, cur_v,
                ">-%.1f%%" % th["iters_drop_pct"], status,
                "%+.1f%%" % d,
            ))

    # model quality
    base_a, cur_a = baseline.get("train_auc"), current.get("train_auc")
    if base_a is not None and cur_a is not None:
        drop = base_a - cur_a
        status = FAIL if drop > th["auc_drop_abs"] else PASS
        rows.append(_row("train_auc", base_a, cur_a,
                         "drop<=%.3g" % th["auc_drop_abs"], status,
                         "%+.4f" % (cur_a - base_a)))

    # serving numbers
    bp = baseline.get("predict") or {}
    cp = current.get("predict") or {}
    if bp.get("rows_per_sec") and cp.get("rows_per_sec") is not None:
        if not same_platform:
            rows.append(_row("predict.rows_per_sec", bp["rows_per_sec"],
                             cp["rows_per_sec"], "-", SKIP, plat_note))
        else:
            d = _pct(cp["rows_per_sec"], bp["rows_per_sec"])
            status = FAIL if d < -th["predict_rows_drop_pct"] else PASS
            rows.append(_row(
                "predict.rows_per_sec", bp["rows_per_sec"],
                cp["rows_per_sec"],
                ">-%.1f%%" % th["predict_rows_drop_pct"], status,
                "%+.1f%%" % d,
            ))
    if bp.get("p99_ms") and cp.get("p99_ms") is not None and same_platform:
        d = _pct(cp["p99_ms"], bp["p99_ms"])
        status = WARN if d > th["predict_p99_rise_pct"] else PASS
        rows.append(_row("predict.p99_ms", bp["p99_ms"], cp["p99_ms"],
                         "<+%.1f%%" % th["predict_p99_rise_pct"], status,
                         "%+.1f%%" % d))

    # retraces: absolute gates on the CURRENT capture (baseline-independent)
    cr = cp.get("retraces_after_warmup")
    if cr is not None:
        rows.append(_row("predict.retraces_after_warmup",
                         bp.get("retraces_after_warmup"), cr, "== 0",
                         FAIL if cr > 0 else PASS,
                         "bucket cache must hold after warmup"))
    gauges = (current.get("obs_report") or {}).get("gauges") or {}
    jr = gauges.get("jit_retraces_after_warmup")
    if jr is not None:
        rows.append(_row("jit_retraces_after_warmup", None, jr, "== 0",
                         FAIL if jr > 0 else PASS, "retrace watchdog"))

    # roofline provenance: a measured->analytic flip means the next
    # comparison would be apples-to-oranges — surface it
    brs, crs = baseline.get("roofline_source"), current.get("roofline_source")
    if brs or crs:
        status = WARN if (brs == "measured" and crs != "measured") else PASS
        rows.append(_row("roofline_source", brs, crs, "no measured->analytic",
                         status, ""))

    # histogram routing provenance (obs/tune.py, ISSUE 13): records measured
    # under different kernel routing (env impl, backend default, or a
    # different tune-table digest) are comparing different kernels — the
    # throughput rows then reflect a routing change, not a code regression,
    # so this WARNs and never FAILs (docs/HistogramRouting.md)
    bhr, chr_ = baseline.get("hist_routing"), current.get("hist_routing")
    if bhr is not None or chr_ is not None:
        def _fmt_routing(h):
            if not h:
                return None
            impl = h.get("env_impl") or h.get("impl_default")
            dig = h.get("tune_digest")
            return "%s%s" % (impl, " tune=%s" % dig if dig else "")

        if bhr is None or chr_ is None:
            # one record predates the routing stamp: nothing to verify —
            # informational, never noise on every first new-format diff
            rows.append(_row(
                "hist_routing", _fmt_routing(bhr), _fmt_routing(chr_),
                "unchanged", SKIP,
                "routing provenance absent in one record",
            ))
        else:
            same = _fmt_routing(bhr) == _fmt_routing(chr_)
            rows.append(_row(
                "hist_routing", _fmt_routing(bhr), _fmt_routing(chr_),
                "unchanged", PASS if same else WARN,
                "" if same else "histogram kernel routing changed — "
                "throughput deltas reflect routing, not a code regression",
            ))

    # serve feature drift (serve/drift.py): any PSI alert in the current
    # capture, or a tracked PSI gauge above 0.2, is a WARN — drifted input
    # makes every other row's comparison suspect (the model was measured
    # against traffic it wasn't trained on), but it is a data condition,
    # not a code regression, so it never FAILs the gate
    obs = current.get("obs_report") or {}
    drift_alerts = sum(
        v for k, v in (obs.get("counters") or {}).items()
        if k.startswith("serve_drift_alerts")
    )
    drift_psis = {
        k: float(v) for k, v in (obs.get("gauges") or {}).items()
        if k.startswith("serve_drift_psi")
    }
    if drift_alerts or drift_psis:
        worst_k = max(drift_psis, key=drift_psis.get) if drift_psis else None
        worst_v = drift_psis.get(worst_k, 0.0) if worst_k else 0.0
        status = WARN if (drift_alerts > 0 or worst_v > 0.2) else PASS
        rows.append(_row(
            "serve_drift", None,
            "%d alert(s)" % int(drift_alerts), "0 alerts, psi<=0.2", status,
            "max psi %.3f (%s)" % (worst_v, worst_k) if worst_k else "",
        ))

    # multichip scaling efficiency (helpers/multichip_bench.py): a drop
    # between MULTICHIP rounds means the pod curve bent — same-platform
    # only, and a WARN rather than a FAIL (device counts, chip generations
    # and comms fabric vary between capture environments; the
    # comms_fraction attribution in the record says why)
    bse = baseline.get("scaling_efficiency")
    cse = current.get("scaling_efficiency")
    if bse is not None and cse is not None:
        if not same_platform:
            rows.append(_row("scaling_efficiency", bse, cse, "-", SKIP,
                             plat_note))
        else:
            d = float(cse) - float(bse)
            status = WARN if d < -th["scaling_eff_drop"] else PASS
            rows.append(_row(
                "scaling_efficiency", bse, cse,
                ">-%.2f" % th["scaling_eff_drop"], status,
                "%+.3f (never a hard FAIL; see comms_fraction)" % d,
            ))

    # device-timeline audit (obs/devprof.py, ISSUE 14): a busy-fraction
    # drop (or a transfer-time blow-up) between same-platform records
    # means the bound-ness of the run moved — a diagnosis pointer into the
    # device_timeline section, NOT a throughput gate, so it WARNs and
    # never FAILs
    bdb = baseline.get("device_busy_fraction")
    cdb = current.get("device_busy_fraction")
    if bdb is not None or cdb is not None:
        if bdb is None or cdb is None:
            rows.append(_row(
                "device_busy_fraction", bdb, cdb, "-", SKIP,
                "devprof stamp absent in one record",
            ))
        elif not same_platform:
            rows.append(_row("device_busy_fraction", bdb, cdb, "-", SKIP,
                             plat_note))
        else:
            d = float(cdb) - float(bdb)
            status = WARN if d < -th["busy_fraction_drop"] else PASS
            rows.append(_row(
                "device_busy_fraction", bdb, cdb,
                ">-%.2f" % th["busy_fraction_drop"], status,
                "%+.3f (never a hard FAIL; see device_timeline)" % d,
            ))
        bts = baseline.get("transfer_seconds")
        cts = current.get("transfer_seconds")
        # max(2x, 0.01s floor): a 0.0s baseline (clean device-resident run)
        # must still WARN when transfers appear, not fall through a falsy
        # guard — that 0 -> seconds jump is the exact regression this row
        # exists to surface
        if (same_platform and bts is not None and cts is not None
                and float(cts) > max(2.0 * float(bts), 0.01)):
            rows.append(_row(
                "transfer_seconds", bts, cts, "<=2x", WARN,
                "H2D/D2H time doubled — check the devprof transfer table",
            ))

    # growth-segment share drift (profiler breakdown, obs/prof.py)
    bs = baseline.get("growth_segments_s") or {}
    cs = current.get("growth_segments_s") or {}
    if bs and cs:
        bt, ct = sum(bs.values()), sum(cs.values())
        worst, worst_shift = None, 0.0
        for seg in sorted(set(bs) | set(cs)):
            b_share = bs.get(seg, 0.0) / bt * 100.0 if bt else 0.0
            c_share = cs.get(seg, 0.0) / ct * 100.0 if ct else 0.0
            if abs(c_share - b_share) > abs(worst_shift):
                worst, worst_shift = seg, c_share - b_share
        status = (
            WARN if abs(worst_shift) > th["segment_share_shift_pts"] else PASS
        )
        rows.append(_row(
            "growth_segments share", None, worst,
            "shift<=%g pts" % th["segment_share_shift_pts"], status,
            "max shift %+.1f pts (%s)" % (worst_shift, worst),
        ))

    # fleet-telemetry drift (obs/podwatch.py): sick-rank verdicts and an
    # iteration spread that grew name HOST conditions — they invalidate a
    # throughput comparison but are never a code regression, so WARN only
    cpw = current.get("podwatch") or {}
    if cpw:
        bpw = baseline.get("podwatch") or {}
        bad = [v for v in (cpw.get("verdicts") or [])
               if v.get("verdict") in ("straggler", "stall", "dead")]
        if bad:
            first = bad[0]
            rows.append(_row(
                "podwatch.verdicts",
                len([v for v in (bpw.get("verdicts") or [])
                     if v.get("verdict") in ("straggler", "stall", "dead")]),
                len(bad), "0", WARN,
                "%s rank %s — %s" % (first.get("verdict"),
                                     first.get("rank"),
                                     str(first.get("why", ""))[:120]),
            ))
        bsp = bpw.get("iteration_spread")
        csp = cpw.get("iteration_spread")
        if bsp is not None and csp is not None:
            grew = (float(csp)
                    > max(float(bsp) * th["podwatch_spread_growth"], 1.0))
            rows.append(_row(
                "podwatch.iteration_spread", bsp, csp,
                "<=%gx" % th["podwatch_spread_growth"],
                WARN if grew else PASS,
                "pod ranks drifting apart — see the record's podwatch "
                "block" if grew else "",
            ))

    failed = any(r["status"] == FAIL for r in rows)
    return rows, failed


def to_markdown(rows: List[Dict], failed: bool, title: str = "") -> str:
    lines = []
    if title:
        lines.append("### bench-diff: %s" % title)
    lines.append("| metric | baseline | current | threshold | status | note |")
    lines.append("|---|---|---|---|---|---|")
    for r in rows:
        lines.append("| %s | %s | %s | %s | %s | %s |" % (
            r["metric"],
            "-" if r["baseline"] is None else r["baseline"],
            "-" if r["current"] is None else r["current"],
            r["threshold"], r["status"], r["note"],
        ))
    lines.append("")
    lines.append("**verdict: %s**" % ("FAIL" if failed else "PASS"))
    return "\n".join(lines)


def self_test() -> int:
    """The gate gating itself: the golden ~10% regression fixture must
    FAIL, the improvement fixture must PASS. Returns 0 on success."""
    base = load_bench_json(os.path.join(GOLDEN_DIR, "baseline.json"))
    reg = load_bench_json(os.path.join(GOLDEN_DIR, "regression.json"))
    imp = load_bench_json(os.path.join(GOLDEN_DIR, "improvement.json"))
    rows_r, failed_r = compare(reg, base)
    rows_i, failed_i = compare(imp, base)
    ok = True
    if not failed_r:
        print("bench_diff self-test: regression fixture did NOT fail!")
        print(to_markdown(rows_r, failed_r, "regression fixture"))
        ok = False
    fail_metrics = {r["metric"] for r in rows_r if r["status"] == FAIL}
    if "value(iters/s)" not in fail_metrics:
        print("bench_diff self-test: regression fixture missed the "
              "throughput drop (failed: %s)" % sorted(fail_metrics))
        ok = False
    if failed_i:
        print("bench_diff self-test: improvement fixture FAILED wrongly:")
        print(to_markdown(rows_i, failed_i, "improvement fixture"))
        ok = False
    if ok:
        print("bench_diff self-test OK: regression fixture FAILS "
              "(%s), improvement fixture PASSES" % sorted(fail_metrics))
    return 0 if ok else 1


def series(pattern: str) -> int:
    """Informational pairwise comparison of a BENCH_r*.json series:
    consecutive same-platform records only; never exits nonzero (historic
    records are evidence, not a gate)."""
    paths = sorted(glob.glob(pattern))
    if len(paths) < 2:
        print("bench_diff: series %r has %d record(s); nothing to compare"
              % (pattern, len(paths)))
        return 0
    records = []
    for p in paths:
        try:
            records.append((p, load_bench_json(p)))
        except (OSError, ValueError) as e:
            print("bench_diff: skipping %s (%s)" % (p, e))
    for (pa, a), (pb, b) in zip(records, records[1:]):
        title = "%s -> %s" % (os.path.basename(pa), os.path.basename(pb))
        if a.get("platform") != b.get("platform"):
            print("### bench-diff: %s\nplatform %s -> %s: skipped "
                  "(not comparable)\n" % (title, a.get("platform"),
                                          b.get("platform")))
            continue
        rows, failed = compare(b, a)
        print(to_markdown(rows, failed, title + " (informational)"))
        print()
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("current", nargs="?", help="current bench JSON")
    ap.add_argument("baseline", nargs="?", help="baseline bench JSON")
    ap.add_argument("--baseline", dest="baseline_opt", help="baseline path")
    ap.add_argument("--series", help="glob of a BENCH_r*.json series "
                                     "(informational pairwise diffs)")
    ap.add_argument("--self-test", action="store_true",
                    help="run the golden-fixture self test")
    ap.add_argument("--json", action="store_true",
                    help="emit the verdict rows as JSON instead of markdown")
    args = ap.parse_args(argv)

    if args.self_test:
        return self_test()
    if args.series:
        return series(args.series)
    if not args.current:
        ap.error("need CURRENT (and BASELINE), --series, or --self-test")
    baseline_path = args.baseline or args.baseline_opt
    if not baseline_path:
        ap.error("need a BASELINE to diff against")
    current = load_bench_json(args.current)
    baseline = load_bench_json(baseline_path)
    rows, failed = compare(current, baseline)
    if args.json:
        print(json.dumps({"rows": rows, "failed": failed}, indent=1))
    else:
        print(to_markdown(rows, failed, "%s vs %s"
                          % (os.path.basename(args.current),
                             os.path.basename(baseline_path))))
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
