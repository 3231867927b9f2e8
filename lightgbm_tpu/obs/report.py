"""Self-contained HTML run report: one file, zero dependencies, inline SVG.

``python -m lightgbm_tpu.obs.report`` renders a training flight log
(obs/flight.py), a metrics/run-report snapshot (obs/registry.py) and a drift
snapshot into a single HTML file a browser opens offline — what an
investigation passes around instead of three JSON files and a plotting
environment.

Sections (each rendered only when its input is present):

  * run manifest (config digest, dataset shape, backend, resume provenance)
  * learning curves — eval-history series per dataset/metric
  * per-tree gain + leaf count along the boosting sequence
  * cumulative gain-importance evolution of the top features
  * serve drift table (serve/drift.py PSI per feature)
  * counters/gauges digest

Usage::

    python -m lightgbm_tpu.obs.report --flight run.jsonl \
        --metrics metrics.json -o report.html

``--metrics`` accepts either a bare ``run_report()`` block or a record that
holds one under ``obs_report``. Stdlib-only: importing this
module never touches a jax backend.
"""
from __future__ import annotations

import argparse
import html
import json
import math
import sys
from typing import Dict, List, Optional, Sequence, Tuple

Point = Tuple[float, float]
Series = Tuple[str, List[Point]]

#: categorical palette for chart series (hex, print-safe)
PALETTE = (
    "#2563eb", "#dc2626", "#059669", "#d97706", "#7c3aed",
    "#0891b2", "#be185d", "#4d7c0f", "#b45309", "#1e40af",
)

_CSS = """
body { font: 14px/1.45 system-ui, sans-serif; margin: 24px auto; max-width: 960px;
       color: #1f2430; }
h1 { font-size: 22px; } h2 { font-size: 17px; margin-top: 28px;
     border-bottom: 1px solid #d8dce4; padding-bottom: 4px; }
table { border-collapse: collapse; margin: 8px 0; }
td, th { border: 1px solid #d8dce4; padding: 3px 9px; text-align: left;
         font-size: 13px; }
th { background: #f1f3f7; }
.small { color: #6a7283; font-size: 12px; }
.alert { color: #b91c1c; font-weight: 600; }
.ok { color: #15803d; }
svg { background: #fbfcfe; border: 1px solid #e3e6ee; margin: 6px 0; }
"""


def _esc(v) -> str:
    return html.escape(str(v))


def _fmt(v: float) -> str:
    if not math.isfinite(v):
        return str(v)  # a diverged run's NaN/inf must render, not crash
    a = abs(v)
    if v == int(v) and a < 1e7:
        return str(int(v))
    if a != 0 and (a < 1e-3 or a >= 1e6):
        return "%.3g" % v
    return "%.4g" % v


# ---------------------------------------------------------------------------
# inline-SVG primitives
# ---------------------------------------------------------------------------

def svg_line_chart(
    series: Sequence[Series], title: str = "", width: int = 860,
    height: int = 230, y_zero: bool = False,
) -> str:
    """Multi-series polyline chart with min/max axis labels and a legend."""
    series = [
        (name, [(x, y) for x, y in pts
                if math.isfinite(x) and math.isfinite(y)])
        for name, pts in series
    ]
    series = [(name, pts) for name, pts in series if pts]
    if not series:
        return ""
    xs = [x for _, pts in series for x, _ in pts]
    ys = [y for _, pts in series for _, y in pts]
    x0, x1 = min(xs), max(xs)
    y0, y1 = (0.0 if y_zero else min(ys)), max(ys)
    if x1 == x0:
        x1 = x0 + 1
    if y1 == y0:
        y1 = y0 + (abs(y0) if y0 else 1.0)
    ml, mr, mt, mb = 58, 12, 24, 30
    iw, ih = width - ml - mr, height - mt - mb

    def sx(x: float) -> float:
        return ml + (x - x0) / (x1 - x0) * iw

    def sy(y: float) -> float:
        return mt + (1 - (y - y0) / (y1 - y0)) * ih

    out = ['<svg width="%d" height="%d" role="img">' % (width, height)]
    if title:
        out.append(
            '<text x="%d" y="15" font-size="13" font-weight="600">%s</text>'
            % (ml, _esc(title))
        )
    # frame + y min/max + x min/max
    out.append(
        '<rect x="%d" y="%d" width="%d" height="%d" fill="none" '
        'stroke="#c4cad6"/>' % (ml, mt, iw, ih)
    )
    for y, anchor_y in ((y1, mt + 10), (y0, mt + ih)):
        out.append(
            '<text x="%d" y="%d" font-size="11" text-anchor="end" '
            'fill="#6a7283">%s</text>' % (ml - 5, anchor_y, _fmt(y))
        )
    for x, anchor in ((x0, "start"), (x1, "end")):
        out.append(
            '<text x="%d" y="%d" font-size="11" text-anchor="%s" '
            'fill="#6a7283">%s</text>'
            % (sx(x), height - 8, anchor, _fmt(x))
        )
    for i, (name, pts) in enumerate(series):
        color = PALETTE[i % len(PALETTE)]
        coord = " ".join(
            "%.1f,%.1f" % (sx(x), sy(y)) for x, y in sorted(pts)
        )
        out.append(
            '<polyline points="%s" fill="none" stroke="%s" '
            'stroke-width="1.6"/>' % (coord, color)
        )
        # legend row (right-aligned stack)
        out.append(
            '<rect x="%d" y="%d" width="10" height="10" fill="%s"/>'
            '<text x="%d" y="%d" font-size="11">%s</text>'
            % (width - 190, mt + 4 + i * 15, color,
               width - 176, mt + 13 + i * 15, _esc(name[:26]))
        )
    out.append("</svg>")
    return "".join(out)


def _table(headers: Sequence[str], rows: Sequence[Sequence[object]]) -> str:
    out = ["<table><tr>"]
    out.extend("<th>%s</th>" % _esc(h) for h in headers)
    out.append("</tr>")
    for row in rows:
        out.append(
            "<tr>" + "".join("<td>%s</td>" % (c if str(c).startswith("<span")
                                              else _esc(c)) for c in row)
            + "</tr>"
        )
    out.append("</table>")
    return "".join(out)


# ---------------------------------------------------------------------------
# sections
# ---------------------------------------------------------------------------

def _section_manifest(flight: Dict) -> str:
    man = flight.get("manifest") or {}
    if not man:
        return ""
    keys = (
        "objective", "num_data", "num_features", "num_class",
        "num_boost_round", "init_iteration", "backend", "config_digest",
        "label_digest", "started_at", "resume_from", "checkpoint_path",
    )
    rows = [(k, man[k]) for k in keys if man.get(k) not in (None, "", {})]
    end = flight.get("end") or {}
    for k in ("num_trees", "iterations", "best_iteration", "stopped"):
        if k in end:
            rows.append((k, end[k]))
    return "<h2>Run manifest</h2>" + _table(("field", "value"), rows)


def _section_learning_curves(flight: Dict) -> str:
    by_key: Dict[str, List[Point]] = {}
    for it in flight.get("iterations", []):
        for (dname, mname, val) in it.get("evals", []):
            by_key.setdefault("%s/%s" % (dname, mname), []).append(
                (float(it["iteration"]) + 1, float(val))
            )
    if not by_key:
        return ""
    chart = svg_line_chart(
        sorted(by_key.items()), title="eval metrics vs iteration"
    )
    return "<h2>Learning curves</h2>" + chart


def _section_trees(flight: Dict) -> str:
    trees = flight.get("trees", [])
    if not trees:
        return ""
    gain = [(float(t["tree"]), float(t.get("total_gain", 0))) for t in trees]
    leaves = [(float(t["tree"]), float(t.get("num_leaves", 0))) for t in trees]
    depth = [(float(t["tree"]), float(t.get("max_depth", 0))) for t in trees]
    out = ["<h2>Per-tree shape</h2>"]
    out.append(svg_line_chart(
        [("total_gain", gain)], title="split gain per tree", y_zero=True,
    ))
    out.append(svg_line_chart(
        [("num_leaves", leaves), ("max_depth", depth)],
        title="leaf count / depth per tree", y_zero=True,
    ))
    return "".join(out)


def _section_importance_evolution(flight: Dict, top: int = 6) -> str:
    trees = flight.get("trees", [])
    if not trees:
        return ""
    totals: Dict[str, float] = {}
    cum: Dict[str, List[Point]] = {}
    running: Dict[str, float] = {}
    for t in trees:
        for f, g in t.get("top_gain_features", []) or []:
            key = "f%s" % f
            running[key] = running.get(key, 0.0) + float(g)
            totals[key] = running[key]
        x = float(t["tree"])
        for key, v in running.items():
            cum.setdefault(key, []).append((x, v))
    if not totals:
        return ""
    top_keys = [k for k, _ in sorted(totals.items(), key=lambda kv: -kv[1])][:top]
    series = [(k, cum[k]) for k in top_keys]
    return (
        "<h2>Importance evolution</h2>"
        '<div class="small">cumulative split gain of the top features '
        "(per-tree top-%d records; features outside a tree's top-k "
        "accumulate at their next appearance)</div>" % 5
        + svg_line_chart(series, title="cumulative gain vs tree", y_zero=True)
    )


def _metrics_block(metrics: Optional[Dict]) -> Dict:
    """Accept a run_report() block, bare or under an ``obs_report`` key."""
    if not metrics:
        return {}
    if "obs_report" in metrics and isinstance(metrics["obs_report"], dict):
        return metrics["obs_report"]
    return metrics


def _section_drift(metrics: Dict, drift: Optional[Dict]) -> str:
    # (sort key, model, feature, psi text, state) — psi sorts NUMERICALLY
    # (string sort would rank "9.0" above "12.3"); None psi sinks to the end
    rows: List[Tuple[float, str, str, str, str]] = []
    threshold = None
    if drift:
        for model, snap in (drift.get("models") or {}).items():
            threshold = snap.get("threshold")
            for name, st in (snap.get("features") or {}).items():
                if not st.get("tracked"):
                    continue
                v = st.get("psi")
                mark = (
                    '<span class="alert">ALERT</span>'
                    if st.get("alert") else '<span class="ok">ok</span>'
                )
                rows.append((
                    float("-inf") if v is None else float(v),
                    model, name, "-" if v is None else "%.4f" % v, mark,
                ))
    else:
        for key, v in (metrics.get("gauges") or {}).items():
            if not key.startswith("serve_drift_psi{"):
                continue
            body = key[len("serve_drift_psi{"):-1]
            labels = dict(
                kv.split("=", 1) for kv in body.split(",") if "=" in kv
            )
            rows.append((
                float(v), labels.get("model", ""),
                labels.get("feature", key), "%.4f" % float(v), "",
            ))
    if not rows:
        return ""
    head = "<h2>Serve drift (PSI vs training reference)</h2>"
    if threshold is not None:
        head += '<div class="small">alert threshold %s</div>' % _esc(threshold)
    rows.sort(key=lambda r: r[0], reverse=True)
    return head + _table(
        ("model", "feature", "PSI", "state"), [r[1:] for r in rows]
    )


def _section_fleet(metrics: Dict) -> str:
    """§Fleet telemetry (obs/podwatch.py): the pod view — per-rank
    progress/rate table plus the evidence-backed straggler/stall/skew/dead
    verdict list, each sentence citing the threshold it tripped."""
    rec = metrics.get("fleet_telemetry")
    if not isinstance(rec, dict) or not rec.get("ranks"):
        return ""
    out = ["<h2>Fleet telemetry</h2>"]
    out.append(
        '<div class="small">world %s · iteration spread %s · '
        "podwatch over %s</div>"
        % (_esc(rec.get("world", "?")), _esc(rec.get("iteration_spread", 0)),
           _esc(rec.get("dir", "?")))
    )
    rows = []
    for r, info in sorted(rec["ranks"].items(), key=lambda kv: int(kv[0])):
        rows.append((
            r,
            _esc(info.get("iteration", "-")),
            _esc(info.get("it_per_s", "-")),
            _esc(info.get("chunk_s", "-")),
            _esc(info.get("samples", 0)),
        ))
    out.append(_table(
        ("rank", "iteration", "it/s", "chunk s", "samples"), rows
    ))
    verdicts = rec.get("verdicts") or []
    if not verdicts:
        out.append('<div><span class="ok">no verdicts</span> — '
                   '<span class="small">pod looks healthy</span></div>')
    for v in verdicts:
        out.append(
            '<div><span class="alert">%s rank %s</span> — '
            '<span class="small">%s</span></div>'
            % (_esc(v.get("verdict")), _esc(v.get("rank")),
               _esc(v.get("why", "")))
        )
    return "".join(out)


def _section_registry_digest(metrics: Dict, limit: int = 40) -> str:
    rows: List[Tuple[str, str]] = []
    for kind in ("counters", "gauges", "rates"):
        for k, v in sorted((metrics.get(kind) or {}).items())[:limit]:
            rows.append(("%s %s" % (kind[:-1], k), _fmt(float(v))))
    if not rows:
        return ""
    return "<h2>Registry digest</h2>" + _table(("metric", "value"), rows)


# ---------------------------------------------------------------------------
# assembly + CLI
# ---------------------------------------------------------------------------

def render(
    flight: Optional[Dict] = None,
    metrics: Optional[Dict] = None,
    drift: Optional[Dict] = None,
    title: str = "lightgbm_tpu run report",
) -> str:
    """Assemble the report HTML from whatever inputs exist (each may be
    None); always returns a complete document."""
    flight = flight or {}
    mblock = _metrics_block(metrics)
    parts = [
        "<!doctype html><html><head><meta charset='utf-8'>",
        "<title>%s</title><style>%s</style></head><body>" % (_esc(title), _CSS),
        "<h1>%s</h1>" % _esc(title),
        _section_manifest(flight),
        _section_learning_curves(flight),
        _section_trees(flight),
        _section_importance_evolution(flight),
        _section_fleet(mblock),
        _section_drift(mblock, drift),
        _section_registry_digest(mblock),
        "<div class='small'>generated by python -m lightgbm_tpu.obs.report"
        "</div></body></html>",
    ]
    return "".join(p for p in parts if p)


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m lightgbm_tpu.obs.report",
        description=__doc__.splitlines()[0],
    )
    ap.add_argument("--flight", help="flight JSONL log (obs/flight.py)")
    ap.add_argument("--metrics",
                    help="run_report JSON (bare, or under an obs_report "
                         "key)")
    ap.add_argument("--drift", help="a /drift endpoint snapshot JSON")
    ap.add_argument("--title", default="lightgbm_tpu run report")
    ap.add_argument("-o", "--out", default="run_report.html")
    args = ap.parse_args(argv)
    if not (args.flight or args.metrics or args.drift):
        ap.error("nothing to report: pass --flight, --metrics and/or "
                 "--drift")

    flight = None
    if args.flight:
        from . import flight as flight_mod

        flight = flight_mod.load(args.flight)
    metrics = None
    if args.metrics:
        with open(args.metrics, encoding="utf-8") as fh:
            metrics = json.load(fh)
    drift = None
    if args.drift:
        with open(args.drift, encoding="utf-8") as fh:
            drift = json.load(fh)
    doc = render(flight=flight, metrics=metrics, drift=drift,
                 title=args.title)
    with open(args.out, "w", encoding="utf-8") as fh:
        fh.write(doc)
    print("report: wrote %s (%d bytes)" % (args.out, len(doc)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
