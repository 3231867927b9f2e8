"""Device-timeline auditor: parse the XLA profile, attribute device time.

``LIGHTGBM_TPU_PROFILE=<dir>`` has always captured a full ``jax.profiler``
trace (utils/timer.py ``maybe_profile``), and PR 4's span tracer enters
``jax.profiler.TraceAnnotation`` so device timelines carry our segment
names — but nothing in the repo ever READ the emitted artifacts. This
module closes that loop: it parses the Chrome-trace JSON(.gz) the profiler
writes under ``<dir>/plugins/profile/<session>/`` (stdlib only — no jax,
no tensorboard) and answers the question the bench numbers cannot:
is the chip idle (host-bound dispatch), busy on the wrong ops
(device-bound), or stalled on transfers (transfer-bound)?

Outputs, from one capture:

 * **op-level attribution** — top-K ops by device SELF time, each grouped
   into the existing segment vocabulary via the ``TraceAnnotation`` names
   PR 4/PR 6 already emit (``prof.hist_build``, the PhaseTimers phase
   names, ``train.iteration`` …). Ops covered by no annotation are
   bucketed loudly as ``unattributed`` — never dropped.
 * **bound-ness verdict** — ``device_busy_fraction``, a dispatch-gap
   (device-idle) histogram, H2D/D2H transfer seconds + bytes, and a
   host-bound / device-bound / transfer-bound classification with the
   evidence inline (:data:`HOST_BOUND_BUSY`, :data:`TRANSFER_BOUND_FRAC`).
 * **per-op roofline placement** — achieved FLOP/s and bytes/s per
   attributed op (from the per-op cost args the TPU profiler embeds)
   against ``costs.CHIP_PEAKS``, naming the op that pins MFU.

Results publish as ``devprof_*`` gauges on the one MetricsRegistry and as
the ``device_timeline`` run-report section (rendered by obs/report.py);
bench.py stamps ``device_busy_fraction``/``transfer_seconds`` into every
bench record and helpers/bench_diff.py WARNs (never FAILs) on their drift.

Capture contract (``capture()`` below, and the CLI ``capture`` command):

 * the profile dir comes from ``LIGHTGBM_TPU_PROFILE`` (or an explicit
   path) and is rank-suffixed (``.rank<N>``) under an initialized
   ``jax.distributed`` world — the same clobber fix PR 9 gave
   ``LIGHTGBM_TPU_TRACE``; :func:`find_trace_files` folds the per-rank
   dirs back together at parse time;
 * segment names reach the device timeline only while an obs tracer is
   live (``trace.span`` is what enters ``TraceAnnotation``), so
   ``capture()`` arms a throwaway tracer when none is active;
 * host-only captures (the CPU backend emits no ``/device:`` lanes)
   degrade to the executor-event proxy (``lanes_source:
   "host_executor"``): ``TfrtCpuExecutable::Execute`` &co stand in for
   device busy time, which on the synchronous CPU runtime they are.

CLI::

    python -m lightgbm_tpu.obs.devprof parse <profile-dir-or-trace.json[.gz]>
        [--top 15] [--device-kind v5e] [--iters N] [--json out.json]
        [--report out.html]
    python -m lightgbm_tpu.obs.devprof capture [--rows 20000] [--iters 8]
        [--dir DIR] [--mode train|predict] ...   # capture, then parse

docs/Observability.md §Device timeline documents the full contract.
"""
from __future__ import annotations

import bisect
import contextlib
import glob as glob_mod
import gzip
import json
import os
import re
import sys
from typing import Dict, List, Optional, Sequence, Tuple

from ..utils import log
from . import registry as registry_mod

ENV_PROFILE = "LIGHTGBM_TPU_PROFILE"  # shared with utils/timer.maybe_profile

# ---------------------------------------------------------------------------
# verdict thresholds (module constants so the evidence can cite them)
# ---------------------------------------------------------------------------

#: busy fraction below which a run reads host-bound: the device spent most
#: of the window waiting for the host to dispatch
HOST_BOUND_BUSY = 0.40
#: transfer time share of the window above which a run reads
#: transfer-bound (checked before the busy-fraction split: a device kept
#: busy shuffling bytes is still transfer-bound)
TRANSFER_BOUND_FRAC = 0.25

#: dispatch-gap histogram bucket upper bounds, milliseconds (last = +inf)
GAP_BUCKETS_MS = (0.1, 1.0, 10.0)

# ---------------------------------------------------------------------------
# segment vocabulary: TraceAnnotation name -> segment label
# ---------------------------------------------------------------------------

#: PhaseTimers phase names (utils/timer.py call sites in models/gbdt.py) —
#: they enter TraceAnnotation verbatim whenever an obs tracer is live
_PHASE_SPANS = frozenset({
    "boosting(grad)", "bagging", "tree growth", "renew+score update",
    "valid scores", "chunked boosting",
})

#: span namespaces that name a segment directly; prof./dist. are the
#: segment profilers' namespaces and are STRIPPED so the attribution lands
#: in the same vocabulary as growth_segment_seconds_total (hist_build,
#: partition, split_scan, hist_combine, ...)
_STRIP_PREFIXES = ("prof.", "dist.")
_KEEP_PREFIXES = (
    "train.", "serve.", "loop.", "cli.", "resil.", "bringup.", "devprof.",
)


def segment_for_span(name: str) -> Optional[str]:
    """The segment label a host annotation span maps to (None = not one of
    ours — an arbitrary profiler-internal host event, never an anchor)."""
    if name in _PHASE_SPANS:
        return name
    for p in _STRIP_PREFIXES:
        if name.startswith(p) and len(name) > len(p):
            return name[len(p):]
    for p in _KEEP_PREFIXES:
        if name.startswith(p):
            return name
    return None


# ---------------------------------------------------------------------------
# event classification
# ---------------------------------------------------------------------------

#: a process lane holding real device op events ("/device:TPU:0", and the
#: "TPU:0"-style spellings some exporter versions use)
_DEVICE_PID_RE = re.compile(r"/device:|^TPU(?: core)?[ :]?\d", re.IGNORECASE)

#: host events that ARE the device work on synchronous backends (CPU):
#: the per-dispatch executable execution — the busy-time proxy when the
#: capture has no /device: lanes at all
_EXEC_RE = re.compile(
    r"::Execute\b|ExecuteSharded|ExecuteOnLocal|ExecuteComputation"
    r"|XlaLocalLaunch|EagerExecute"
)

#: transfer-event vocabulary, host-to-device vs device-to-host. Covers the
#: TPU exporter spellings (TransferToDevice / TransferFromDevice, infeed /
#: outfeed) and the stream-executor ones (MemcpyH2D / MemcpyD2H)
_H2D_RE = re.compile(
    r"TransferToDevice|MemcpyH2D|Memcpy.*HToD|InfeedEnqueue|"
    r"BufferFromHost|CopyToDevice|host_to_device|h2d", re.IGNORECASE)
_D2H_RE = re.compile(
    r"TransferFromDevice|MemcpyD2H|Memcpy.*DToH|OutfeedDequeue|"
    r"BufferToHost|CopyFromDevice|device_to_host|d2h|TransferLiteral",
    re.IGNORECASE)

#: args keys that carry a byte count on transfer/op events
_BYTES_KEYS = (
    "bytes", "num_bytes", "size", "bytes_transferred", "buffer_size",
    "bytes accessed", "bytes_accessed", "requested_bytes",
)
#: args keys that carry a FLOP count on op events (TPU op lanes embed
#: these; absent elsewhere — roofline rows exist only where they do)
_FLOPS_KEYS = ("flops", "model_flops")


def _arg_num(args: Optional[Dict], keys: Sequence[str]) -> Optional[float]:
    if not args:
        return None
    for k in keys:
        v = args.get(k)
        if v is None:
            continue
        try:
            return float(str(v).replace(",", ""))
        except (TypeError, ValueError):
            continue
    return None


class _Ev:
    """One complete ('X') event on the shared profiler clock."""

    __slots__ = ("name", "pkey", "tid", "ts", "dur", "args", "self_us",
                 "segment")

    def __init__(self, name, pkey, tid, ts, dur, args):
        self.name = name
        self.pkey = pkey
        self.tid = tid
        self.ts = ts
        self.dur = dur
        self.args = args
        self.self_us = dur
        self.segment: Optional[str] = None

    @property
    def end(self) -> float:
        return self.ts + self.dur


# ---------------------------------------------------------------------------
# loading
# ---------------------------------------------------------------------------

def load_chrome_trace(path: str) -> Dict:
    """One Chrome-trace document, transparently gunzipping ``*.gz``."""
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rt", encoding="utf-8") as fh:
        return json.load(fh)


def find_trace_files(profile_dir: str, include_ranks: bool = True,
                     latest_only: bool = True) -> List[str]:
    """The Chrome-trace files of a profiler capture dir.

    Looks under ``<dir>/plugins/profile/<session>/*.trace.json(.gz)``
    (newest session per dir when ``latest_only``) and — the multi-process
    story — folds sibling ``<dir>.rank<N>`` dirs in, so one parse sees the
    whole pod. A direct file path passes through untouched.
    """
    if os.path.isfile(profile_dir):
        return [profile_dir]
    dirs = [profile_dir]
    if include_ranks:
        dirs += sorted(glob_mod.glob(glob_mod.escape(profile_dir) + ".rank*"))
    out: List[str] = []
    for d in dirs:
        sessions = sorted(glob_mod.glob(
            os.path.join(glob_mod.escape(d), "plugins", "profile", "*")))
        sessions = [s for s in sessions if os.path.isdir(s)]
        if latest_only and sessions:
            sessions = sessions[-1:]
        for s in sessions:
            hits = sorted(
                glob_mod.glob(os.path.join(glob_mod.escape(s),
                                           "*.trace.json.gz"))
                + glob_mod.glob(os.path.join(glob_mod.escape(s),
                                             "*.trace.json"))
            )
            out.extend(hits)
    return out


class Timeline:
    """Events + process/thread metadata from one or more trace files.

    pids are keyed ``(file_index, pid)`` internally so per-rank files with
    colliding pids can never interleave (same rule as obs/trace.py merge).
    """

    def __init__(self) -> None:
        self.files: List[str] = []
        self.processes: Dict[Tuple[int, object], str] = {}
        self.threads: Dict[Tuple[Tuple[int, object], object], str] = {}
        self.events: List[_Ev] = []

    @classmethod
    def load(cls, paths: Sequence[str]) -> "Timeline":
        tl = cls()
        for i, p in enumerate(paths):
            try:
                doc = load_chrome_trace(p)
            except (OSError, ValueError) as e:
                # a torn/absent per-rank file must not kill the whole parse
                log.warn_once("devprof:load:%s" % p,
                              "devprof: skipping unreadable trace %s (%r)"
                              % (p, e))
                continue
            tl.files.append(p)
            tl._ingest(doc, i)
        return tl

    @classmethod
    def from_docs(cls, docs: Sequence[Dict]) -> "Timeline":
        """Already-parsed Chrome-trace documents (tests, in-process use)."""
        tl = cls()
        for i, doc in enumerate(docs):
            tl.files.append("<doc %d>" % i)
            tl._ingest(doc, i)
        return tl

    def _ingest(self, doc: Dict, i: int) -> None:
        for ev in doc.get("traceEvents") or []:
            ph = ev.get("ph")
            pkey = (i, ev.get("pid", 0))
            if ph == "M":
                if ev.get("name") == "process_name":
                    self.processes[pkey] = str(
                        (ev.get("args") or {}).get("name", ""))
                elif ev.get("name") == "thread_name":
                    self.threads[(pkey, ev.get("tid"))] = str(
                        (ev.get("args") or {}).get("name", ""))
            elif ph == "X":
                try:
                    ts = float(ev["ts"])
                    dur = float(ev.get("dur", 0.0))
                except (KeyError, TypeError, ValueError):
                    continue
                self.events.append(_Ev(
                    str(ev.get("name", "")), pkey, ev.get("tid"),
                    ts, max(dur, 0.0), ev.get("args"),
                ))

    @classmethod
    def from_dir(cls, profile_dir: str, **kw) -> "Timeline":
        return cls.load(find_trace_files(profile_dir, **kw))

    # -- classification ----------------------------------------------------

    def device_pkeys(self) -> List[Tuple[int, object]]:
        return sorted(
            (k for k, name in self.processes.items()
             if _DEVICE_PID_RE.search(name)),
            key=lambda k: (k[0], str(k[1])),
        )

    def device_ops(self) -> Tuple[List[_Ev], str]:
        """(op events, lanes_source). Real ``/device:`` lanes when present;
        else the host executor-event proxy; else an empty list."""
        dev = set(self.device_pkeys())
        if dev:
            ops = [e for e in self.events if e.pkey in dev
                   and not _H2D_RE.search(e.name)
                   and not _D2H_RE.search(e.name)]
            if ops:
                return ops, "device"
        ops = [e for e in self.events if _EXEC_RE.search(e.name)]
        return ops, ("host_executor" if ops else "none")

    def annotations(self) -> List[_Ev]:
        """Host spans that name a segment (TraceAnnotation entries of the
        obs tracer's spans), innermost attribution anchors."""
        dev = set(self.device_pkeys())
        anns = []
        for e in self.events:
            if e.pkey in dev:
                continue
            seg = segment_for_span(e.name)
            if seg is not None:
                e.segment = seg
                anns.append(e)
        return anns

    def transfers(self) -> Dict[str, List[_Ev]]:
        out: Dict[str, List[_Ev]] = {"h2d": [], "d2h": []}
        for e in self.events:
            if _H2D_RE.search(e.name):
                out["h2d"].append(e)
            elif _D2H_RE.search(e.name):
                out["d2h"].append(e)
        return out

    def window_us(self) -> float:
        if not self.events:
            return 0.0
        t0 = min(e.ts for e in self.events)
        t1 = max(e.end for e in self.events)
        return max(t1 - t0, 0.0)


# ---------------------------------------------------------------------------
# interval math
# ---------------------------------------------------------------------------

def _compute_self_times(events: List[_Ev]) -> None:
    """Self time per lane: an event's duration minus the time covered by
    events nested inside it on the SAME (pkey, tid) lane. Sorting by
    (ts, -dur) makes any container precede its contents; partial overlaps
    (ill-nested exporter artifacts) subtract only the overlapping part.
    Resets self_us first so re-analyzing one Timeline never
    double-subtracts."""
    for e in events:
        e.self_us = e.dur
    lanes: Dict[Tuple, List[_Ev]] = {}
    for e in events:
        lanes.setdefault((e.pkey, e.tid), []).append(e)
    for lane in lanes.values():
        lane.sort(key=lambda e: (e.ts, -e.dur))
        stack: List[_Ev] = []
        for e in lane:
            while stack and e.ts >= stack[-1].end - 1e-9:
                stack.pop()
            if stack:
                top = stack[-1]
                top.self_us -= max(
                    0.0, min(e.end, top.end) - e.ts)
            stack.append(e)
    for e in events:
        e.self_us = max(e.self_us, 0.0)


def _merge_intervals(
    iv: List[Tuple[float, float]]
) -> List[Tuple[float, float]]:
    if not iv:
        return []
    iv = sorted(iv)
    out = [list(iv[0])]
    for a, b in iv[1:]:
        if a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _attribute(ops: List[_Ev], anns: List[_Ev]) -> None:
    """Assign each op the segment of the annotation span it overlaps most;
    ties break to the SHORTEST (innermost) span. No overlap -> None
    (bucketed as ``unattributed`` downstream, never dropped). Resets op
    segments first so re-analyzing one Timeline starts clean."""
    for op in ops:
        op.segment = None
    if not anns:
        return
    anns = sorted(anns, key=lambda a: a.ts)
    starts = [a.ts for a in anns]
    max_dur = max(a.dur for a in anns)
    for op in ops:
        # candidates: anns with ts < op.end and end > op.ts; anything
        # starting before op.ts - max_dur has necessarily ended
        lo = bisect.bisect_left(starts, op.ts - max_dur)
        hi = bisect.bisect_right(starts, op.end)
        best, best_ov, best_dur = None, 0.0, 0.0
        for a in anns[lo:hi]:
            ov = min(op.end, a.end) - max(op.ts, a.ts)
            if ov <= 0:
                continue
            if ov > best_ov + 1e-9 or (
                abs(ov - best_ov) <= 1e-9 and a.dur < best_dur
            ):
                best, best_ov, best_dur = a, ov, a.dur
        if best is not None:
            op.segment = best.segment


# ---------------------------------------------------------------------------
# analysis
# ---------------------------------------------------------------------------

def analyze(
    timeline: Timeline,
    device_kind: Optional[str] = None,
    platform: Optional[str] = None,
    iters: Optional[int] = None,
    top_k: int = 15,
) -> Dict[str, object]:
    """The full device-timeline record (the ``device_timeline`` section).

    ``device_kind`` feeds the roofline peak lookup (costs.chip_peaks) when
    the capture came from a TPU (``platform`` "tpu", or unstated when
    parsing a saved capture); a capture from any other platform gets no
    peak fractions. ``iters`` — the number of boosting iterations the
    profiled window covered — adds per-iteration transfer rates.
    """
    from . import costs as costs_mod

    rec: Dict[str, object] = {
        "files": [os.path.basename(p) for p in timeline.files],
        "events": len(timeline.events),
    }
    ops, source = timeline.device_ops()
    rec["lanes_source"] = source
    anns = timeline.annotations()
    tr_all = [e for evs in timeline.transfers().values() for e in evs]
    # the analysis window spans the events the verdict reasons about —
    # NOT every host event: the profiler exports long-lived bookkeeping
    # spans (e.g. its own start_trace frame) that would dilute busy/idle
    # fractions to meaninglessness
    considered = ops + anns + tr_all
    if considered:
        window_us = (max(e.end for e in considered)
                     - min(e.ts for e in considered))
    else:
        window_us = timeline.window_us()
    rec["window_s"] = round(window_us / 1e6, 6)
    if source == "none" or window_us <= 0:
        rec["verdict"] = {
            "bound": "empty",
            "why": "no device lanes and no executor events in the capture",
        }
        return rec

    _compute_self_times(ops)
    _attribute(ops, anns)

    # -- per-device busy/idle ---------------------------------------------
    by_dev: Dict[str, List[_Ev]] = {}
    for op in ops:
        label = timeline.processes.get(op.pkey, "") or "pid %s" % (op.pkey,)
        if source == "host_executor":
            label = "host executor (%s)" % label.strip("/ ") if label else \
                "host executor"
        by_dev.setdefault(label, []).append(op)
    lanes = []
    gaps_ms: List[float] = []
    busy_us_total = 0.0
    for label in sorted(by_dev):
        devops = by_dev[label]
        merged = _merge_intervals([(e.ts, e.end) for e in devops])
        busy = sum(b - a for a, b in merged)
        busy_us_total += busy
        for (a0, b0), (a1, _b1) in zip(merged, merged[1:]):
            gaps_ms.append((a1 - b0) / 1e3)
        lanes.append({
            "device": label,
            "ops": len(devops),
            "busy_s": round(busy / 1e6, 6),
            "busy_fraction": round(busy / window_us, 4),
        })
    n_lanes = max(len(lanes), 1)
    busy_fraction = busy_us_total / (window_us * n_lanes)
    rec["lanes"] = lanes
    rec["device_busy_fraction"] = round(busy_fraction, 4)
    rec["busy_seconds"] = round(busy_us_total / 1e6, 6)
    rec["idle_seconds"] = round(
        max(window_us * n_lanes - busy_us_total, 0.0) / 1e6, 6)

    hist: Dict[str, int] = {}
    edges = ["<%gms" % GAP_BUCKETS_MS[0]] + [
        "%g-%gms" % (a, b)
        for a, b in zip(GAP_BUCKETS_MS, GAP_BUCKETS_MS[1:])
    ] + [">=%gms" % GAP_BUCKETS_MS[-1]]
    for label in edges:
        hist[label] = 0
    for g in gaps_ms:
        idx = bisect.bisect_right(GAP_BUCKETS_MS, g)
        hist[edges[idx]] += 1
    rec["dispatch_gaps"] = {
        "count": len(gaps_ms),
        "total_ms": round(sum(gaps_ms), 3),
        "max_ms": round(max(gaps_ms), 3) if gaps_ms else 0.0,
        "histogram": hist,
    }

    # -- transfers ---------------------------------------------------------
    tr = timeline.transfers()
    transfers: Dict[str, object] = {}
    transfer_us = 0.0
    for direction, evs in tr.items():
        merged = _merge_intervals([(e.ts, e.end) for e in evs])
        secs = sum(b - a for a, b in merged)
        transfer_us += secs
        nbytes = sum(
            v for v in (_arg_num(e.args, _BYTES_KEYS) for e in evs)
            if v is not None
        )
        transfers[direction] = {
            "count": len(evs),
            "seconds": round(secs / 1e6, 6),
            "bytes": int(nbytes),
        }
    transfers["total_seconds"] = round(transfer_us / 1e6, 6)
    if iters:
        transfers["per_iteration"] = {
            "seconds": round(transfer_us / 1e6 / iters, 6),
            "bytes": int(sum(
                transfers[d]["bytes"] for d in ("h2d", "d2h")) / iters),
        }
        rec["iters"] = int(iters)
    rec["transfers"] = transfers
    transfer_fraction = transfer_us / window_us
    rec["transfer_fraction"] = round(transfer_fraction, 4)

    # -- op attribution ----------------------------------------------------
    seg_self: Dict[str, float] = {}
    op_groups: Dict[Tuple[str, str], Dict[str, float]] = {}
    total_self = 0.0
    for op in ops:
        seg = op.segment or "unattributed"
        total_self += op.self_us
        seg_self[seg] = seg_self.get(seg, 0.0) + op.self_us
        g = op_groups.setdefault((op.name, seg), {
            "self_us": 0.0, "count": 0.0, "flops": 0.0, "bytes": 0.0,
        })
        g["self_us"] += op.self_us
        g["count"] += 1
        g["flops"] += _arg_num(op.args, _FLOPS_KEYS) or 0.0
        g["bytes"] += _arg_num(op.args, _BYTES_KEYS) or 0.0

    rec["segments"] = {
        k: round(v / 1e6, 6)
        for k, v in sorted(seg_self.items(), key=lambda kv: -kv[1])
    }
    attributed = total_self - seg_self.get("unattributed", 0.0)
    rec["attributed_fraction"] = (
        round(attributed / total_self, 4) if total_self else 0.0
    )

    peaks = (
        costs_mod.chip_peaks(device_kind, platform=platform)
        if device_kind and platform in (None, "tpu") else None
    )
    top = sorted(op_groups.items(), key=lambda kv: -kv[1]["self_us"])
    top_ops = []
    for (name, seg), g in top[:top_k]:
        row: Dict[str, object] = {
            "op": name,
            "segment": seg,
            "self_s": round(g["self_us"] / 1e6, 6),
            "count": int(g["count"]),
            "share": round(g["self_us"] / total_self, 4) if total_self else 0.0,
        }
        if g["flops"] and g["self_us"]:
            achieved = g["flops"] / (g["self_us"] / 1e6)
            row["flops"] = g["flops"]
            row["achieved_flops_per_s"] = round(achieved, 1)
            if peaks is not None:
                row["peak_flops_fraction"] = round(
                    achieved / float(peaks["peak_flops"]), 6)
        if g["bytes"] and g["self_us"]:
            bw = g["bytes"] / (g["self_us"] / 1e6)
            row["bytes"] = int(g["bytes"])
            row["achieved_bytes_per_s"] = round(bw, 1)
            if peaks is not None:
                row["peak_bw_fraction"] = round(
                    bw / float(peaks["peak_bw"]), 6)
        top_ops.append(row)
    rec["top_ops"] = top_ops

    # the op pinning MFU: the largest device self-time sink, with its
    # roofline placement when the capture carried per-op cost args
    if top_ops:
        pin = dict(top_ops[0])
        pin_extra = {
            "why": "largest device self-time share (%.1f%% of %s)"
            % (100.0 * pin["share"], "device time"),
        }
        pin.update(pin_extra)
        rec["mfu_pin"] = pin
    if peaks is not None:
        rec["roofline_chip"] = peaks["chip"]

    # -- verdict -----------------------------------------------------------
    gaps = rec["dispatch_gaps"]
    evidence = {
        "device_busy_fraction": rec["device_busy_fraction"],
        "transfer_fraction": rec["transfer_fraction"],
        "transfer_seconds": transfers["total_seconds"],
        "idle_gap_total_ms": gaps["total_ms"],
        "idle_gap_max_ms": gaps["max_ms"],
        "lanes_source": source,
        "window_s": rec["window_s"],
    }
    if transfer_fraction >= TRANSFER_BOUND_FRAC:
        bound = "transfer-bound"
        why = (
            "transfers cover %.0f%% of the %.3fs window "
            "(threshold %.0f%%); the chip waits on bytes, not dispatch"
            % (100 * transfer_fraction, rec["window_s"],
               100 * TRANSFER_BOUND_FRAC)
        )
    elif busy_fraction < HOST_BOUND_BUSY:
        bound = "host-bound"
        why = (
            "device busy only %.0f%% of the window (threshold %.0f%%): "
            "%.1fms of dispatch gaps (max %.1fms) — the host is the "
            "bottleneck, the chip is idle between dispatches"
            % (100 * busy_fraction, 100 * HOST_BOUND_BUSY,
               gaps["total_ms"], gaps["max_ms"])
        )
    else:
        bound = "device-bound"
        top_name = top_ops[0]["op"] if top_ops else "?"
        why = (
            "device busy %.0f%% of the window with transfers at %.0f%%; "
            "time goes to on-device ops (top: %s)"
            % (100 * busy_fraction, 100 * transfer_fraction, top_name)
        )
    if source == "host_executor":
        why += " [host-executor proxy: no /device: lanes in this capture]"
    rec["verdict"] = {"bound": bound, "why": why, "evidence": evidence}
    return rec


def analyze_dir(profile_dir: str, **kw) -> Dict[str, object]:
    """find_trace_files + Timeline.load + analyze, one call."""
    return analyze(Timeline.from_dir(profile_dir),
                   **kw)


# ---------------------------------------------------------------------------
# publication: gauges + run-report section
# ---------------------------------------------------------------------------

_LAST_RECORD: Dict[str, object] = {}
_SECTION_REGISTERED = False


def _report_section() -> Dict[str, object]:
    return dict(_LAST_RECORD)


def publish(record: Dict[str, object], registry=None) -> None:
    """``devprof_*`` gauges on the one registry + the ``device_timeline``
    run-report section (obs/report.py renders it)."""
    global _SECTION_REGISTERED
    reg = registry if registry is not None else registry_mod.REGISTRY
    if record.get("device_busy_fraction") is not None:
        reg.gauge("devprof_device_busy_fraction").set(
            float(record["device_busy_fraction"]))
    if record.get("attributed_fraction") is not None:
        reg.gauge("devprof_attributed_fraction").set(
            float(record["attributed_fraction"]))
    tr = record.get("transfers") or {}
    for direction in ("h2d", "d2h"):
        d = tr.get(direction)
        if d:
            reg.gauge("devprof_transfer_seconds").set(
                float(d["seconds"]), direction=direction)
            reg.gauge("devprof_transfer_bytes").set(
                float(d["bytes"]), direction=direction)
    for seg, secs in (record.get("segments") or {}).items():
        reg.gauge("devprof_segment_self_seconds").set(
            float(secs), segment=seg)
    verdict = (record.get("verdict") or {}).get("bound")
    if verdict:
        # zero the other labels so a re-publish with a changed verdict
        # never leaves two devprof_bound{verdict=}=1 rows on one scrape
        for known in ("host-bound", "device-bound", "transfer-bound",
                      "empty"):
            reg.gauge("devprof_bound").set(
                1.0 if known == str(verdict) else 0.0, verdict=known)
        if str(verdict) not in ("host-bound", "device-bound",
                                "transfer-bound", "empty"):
            reg.gauge("devprof_bound").set(1.0, verdict=str(verdict))
    _LAST_RECORD.clear()
    _LAST_RECORD.update(record)
    if registry is None:
        if not _SECTION_REGISTERED:
            reg.register_report_section("device_timeline", _report_section)
            _SECTION_REGISTERED = True
    else:
        reg.register_report_section("device_timeline", _report_section)


def reset() -> None:
    _LAST_RECORD.clear()


# ---------------------------------------------------------------------------
# capture
# ---------------------------------------------------------------------------

@contextlib.contextmanager
def capture(out_dir: Optional[str] = None, ensure_annotations: bool = True):
    """Scoped ``jax.profiler`` trace around a profiled window.

    ``out_dir`` defaults to ``LIGHTGBM_TPU_PROFILE`` (the maybe_profile env
    contract) and is rank-suffixed under a multi-process world. Segment
    names reach the device timeline only through a live obs tracer
    (trace.span enters TraceAnnotation), so when none is active a
    throwaway one is armed for the window and stopped after. Yields the
    resolved capture dir.
    """
    from . import trace as trace_mod

    target = out_dir or os.environ.get(ENV_PROFILE, "")
    if not target:
        raise ValueError(
            "devprof.capture() needs a dir (or set %s)" % ENV_PROFILE)
    target = trace_mod.rank_suffixed(target)
    started = False
    if ensure_annotations and trace_mod.active() is None:
        os.makedirs(target, exist_ok=True)
        try:
            trace_mod.start(os.path.join(target, "host_spans.trace.json"))
            started = True
        except (ValueError, OSError) as e:
            log.debug("devprof: could not arm host tracer: %r" % (e,))
    import jax

    jax.profiler.start_trace(target)
    try:
        yield target
    finally:
        jax.profiler.stop_trace()
        if started:
            trace_mod.stop()


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def _print_summary(rec: Dict[str, object], stream=None) -> None:
    out = stream or sys.stdout
    v = rec.get("verdict") or {}
    print("devprof: %d event(s) from %d file(s), window %.3fs, lanes=%s"
          % (rec.get("events", 0), len(rec.get("files") or []),
             rec.get("window_s", 0.0), rec.get("lanes_source")), file=out)
    if rec.get("device_busy_fraction") is not None:
        print("  device_busy_fraction = %.3f   transfer_fraction = %.3f   "
              "attributed = %.0f%%"
              % (rec["device_busy_fraction"], rec.get("transfer_fraction", 0),
                 100 * rec.get("attributed_fraction", 0.0)), file=out)
    for seg, secs in list((rec.get("segments") or {}).items())[:10]:
        print("  segment %-24s %10.6fs" % (seg, secs), file=out)
    for row in (rec.get("top_ops") or [])[:10]:
        extraf = ""
        if row.get("peak_flops_fraction") is not None:
            extraf = "  peak=%.2f%%" % (100 * row["peak_flops_fraction"])
        print("  op %-40s %-18s %9.6fs x%d%s"
              % (row["op"][:40], row["segment"][:18], row["self_s"],
                 row["count"], extraf), file=out)
    print("VERDICT: %s — %s" % (v.get("bound"), v.get("why")), file=out)


def _cmd_parse(args) -> int:
    tl = Timeline.from_dir(args.target)
    if not tl.files:
        print("devprof: no trace files under %r" % args.target,
              file=sys.stderr)
        return 1
    rec = analyze(tl, device_kind=args.device_kind, platform=args.platform,
                  iters=args.iters, top_k=args.top)
    publish(rec)
    if args.json:
        with open(args.json, "w", encoding="utf-8") as fh:
            json.dump(rec, fh, indent=1)
        print("devprof: wrote %s" % args.json)
    if args.report:
        from . import report as report_mod

        doc = report_mod.render(
            metrics={"device_timeline": rec},
            title="lightgbm_tpu device timeline",
        )
        with open(args.report, "w", encoding="utf-8") as fh:
            fh.write(doc)
        print("devprof: wrote %s" % args.report)
    _print_summary(rec)
    return 0


def _cmd_capture(args) -> int:
    """Capture a profiled window of real training (or packed predict)
    dispatch, then parse it — the zero-to-verdict path."""
    import numpy as np

    out_dir = args.dir or os.environ.get(ENV_PROFILE, "") or "devprof_capture"
    import lightgbm_tpu as lgb

    rng = np.random.RandomState(7)
    X = rng.rand(args.rows, args.features).astype(np.float32)
    y = (X[:, 0] + 0.25 * rng.rand(args.rows) > 0.6).astype(np.float32)
    params = {
        "objective": "binary", "num_leaves": args.leaves,
        "max_bin": args.bins, "learning_rate": 0.1, "verbosity": -1,
    }
    if args.device_type:
        params["device_type"] = args.device_type
    booster = lgb.Booster(params=params, train_set=lgb.Dataset(X, label=y))
    import jax

    # warmup outside the capture: the multi-minute XLA compile would
    # otherwise dominate the window and every verdict would read host-bound
    for _ in range(2):
        booster.update()
    jax.block_until_ready(booster._gbdt.scores)
    mode = args.mode
    with capture(out_dir) as target:
        if mode == "predict":
            pk = booster.to_packed()
            xd = jax.device_put(X[: min(args.rows, 1 << 14)])
            for _ in range(args.iters):
                out = pk.fused_scores(xd)
            jax.block_until_ready(out)
        else:
            for _ in range(args.iters):
                booster.update()
            jax.block_until_ready(booster._gbdt.scores)
    rec = analyze_dir(target, device_kind=jax.devices()[0].device_kind,
                      platform=jax.default_backend(), iters=args.iters,
                      top_k=args.top)
    publish(rec)
    if args.json:
        with open(args.json, "w", encoding="utf-8") as fh:
            json.dump(rec, fh, indent=1)
        print("devprof: wrote %s" % args.json)
    _print_summary(rec)
    print("devprof: capture dir %s" % target)
    return 0


def main(argv: Optional[List[str]] = None) -> int:
    import argparse

    ap = argparse.ArgumentParser(
        prog="python -m lightgbm_tpu.obs.devprof",
        description="Device-timeline auditor (obs/devprof.py)",
    )
    sub = ap.add_subparsers(dest="cmd", required=True)
    pp = sub.add_parser("parse", help="parse an existing profile capture")
    pp.add_argument("target", help="profile dir (LIGHTGBM_TPU_PROFILE "
                                   "target) or a trace.json(.gz) file")
    pp.add_argument("--top", type=int, default=15)
    pp.add_argument("--device-kind", default=None,
                    help="the TPU the capture came from (e.g. 'TPU v5e'); "
                         "without it no peak fractions are computed")
    pp.add_argument("--platform", default=None)
    pp.add_argument("--iters", type=int, default=None,
                    help="iterations the window covered (per-iter rates)")
    pp.add_argument("--json", help="write the full record as JSON")
    pp.add_argument("--report", help="write a device_timeline HTML page")
    pp.set_defaults(fn=_cmd_parse)
    cp = sub.add_parser("capture", help="profile a training window, then "
                                        "parse it")
    cp.add_argument("--dir", default=None)
    cp.add_argument("--rows", type=int, default=20000)
    cp.add_argument("--features", type=int, default=16)
    cp.add_argument("--leaves", type=int, default=31)
    cp.add_argument("--bins", type=int, default=63)
    cp.add_argument("--iters", type=int, default=8)
    cp.add_argument("--mode", choices=("train", "predict"), default="train")
    cp.add_argument("--device-type", default=None,
                    help="forwarded as the device_type param (e.g. 'cpu' "
                         "for the native host learner — the profiled-window "
                         "escape hatch on the CPU backend, where per-thunk "
                         "host events scale with rows x leaves and a "
                         "1M-row XLA-grower window exhausts memory; the "
                         "same reason bench.py trains native off-chip)")
    cp.add_argument("--top", type=int, default=15)
    cp.add_argument("--json", help="write the full record as JSON")
    cp.set_defaults(fn=_cmd_capture)
    args = ap.parse_args(argv)
    return args.fn(args)


if __name__ == "__main__":
    sys.exit(main())
