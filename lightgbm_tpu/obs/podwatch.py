"""podwatch: the live fleet telemetry plane (docs/Observability.md §Fleet
telemetry).

Everything else in obs/ answers questions about a run after it happened
(flight stamps provenance, report renders a finished run); podwatch answers them WHILE the pod is training:

 * **Per-rank time-series recorder** — env-gated by
   ``LIGHTGBM_TPU_TELEMETRY=<dir>``: at every chunk boundary the boost loop
   samples the one metrics registry (cumulative train/resil/hist counters),
   the TIMETAG phase accumulators (per-boundary deltas, when armed), the
   memwatch device-bytes gauge, and the boundary's own wall time into a
   bounded ring buffer persisted as ``<dir>/timeline.rank<N>.jsonl`` through
   resil/atomic. Each sample also refreshes this rank's heartbeat
   (``<dir>/pod.hb.rank<N>.json``, resil/coord) enriched with the chunk
   seconds and cumulative iteration rate — so liveness and rate evidence
   live together for the aggregator. Off (env unset) the whole plane costs
   one env read per gate at train() start: no threads, no ring, no files.

 * **Training-side scrape endpoint** — opt-in
   ``LIGHTGBM_TPU_TELEMETRY_PORT=<port>``: a daemon-thread HTTP listener
   (serve/httpbase plumbing) exposing ``/metrics`` (the registry's
   Prometheus text exposition), ``/health`` (rank, iteration,
   last-boundary age, preempt/watchdog state) and ``/timeline`` (the recent
   ring-buffer window as JSON). The listener outlives individual train()
   calls by design — a pod is watched across warm-start retrains — and a
   failure to bind is a warning, never a training failure.

 * **Cross-rank aggregator + verdicts** — ``python -m
   lightgbm_tpu.obs.podwatch <dir>`` (and :func:`pod_summary` as a library)
   folds every rank's timeline shard and heartbeat into one pod view and
   issues evidence-backed verdicts, each citing the
   module-constant threshold it tripped: *straggler* (a named rank whose
   mean chunk seconds exceed the pod median by ``STRAGGLER_FACTOR``, with
   the segment that diverges — the synthetic ``host_other`` bucket catches
   time no TIMETAG phase claims), *stall* (a rank's recent iteration rate
   collapsed vs its own trailing window by ``STALL_FACTOR``), *skew*
   (iteration spread across ranks beyond ``SKEW_ITERATIONS``) and *dead*
   (via resil/coord.stale_ranks, heartbeat evidence attached). Verdicts
   surface as ``podwatch_*`` gauges, a run_report() ``fleet_telemetry``
   section (report.py renders it as §Fleet telemetry).

The aggregator half is stdlib-only and never imports jax — it must run on
an operator's laptop against an NFS dir while the pod is still training.
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys
import threading
import time
from collections import deque
from typing import Callable, Dict, List, Optional, Tuple

from . import registry as registry_mod
from . import sanitize as sanitize_mod
from ..utils import log

ENV_TELEMETRY = "LIGHTGBM_TPU_TELEMETRY"
ENV_TELEMETRY_PORT = "LIGHTGBM_TPU_TELEMETRY_PORT"

#: ring capacity per rank — at one sample per chunk boundary this spans the
#: recent past (a 512-boundary window) while bounding both memory and the
#: per-boundary shard rewrite (the whole ring is re-published atomically,
#: so a scraper never reads a torn line)
RING_SIZE = 512

#: cumulative-counter families sampled into each boundary record
COUNTER_PREFIXES = ("train_", "resil_", "hist_")

# ---------------------------------------------------------------------------
# verdict thresholds — module constants so the evidence can cite them
# ---------------------------------------------------------------------------

#: boundaries dropped from the front of every rank's window before any
#: verdict math: the first boundary pays the serial-path jit compile and the
#: second pays the train_chunk compile (the boost loop bootstraps one
#: per-iteration step before chunking) — either would dominate every mean
WARMUP_SKIP = 2
#: recent-past window (samples) the per-rank statistics are computed over
WINDOW = 32
#: straggler: a rank's mean chunk seconds vs the pod median
STRAGGLER_FACTOR = 1.5
#: minimum post-warmup samples before a rank can be judged at all
MIN_SAMPLES = 3
#: stall: recent-rate samples compared against the rank's own trailing rate
STALL_RECENT = 3
STALL_FACTOR = 3.0
#: minimum post-warmup samples before the stall comparison is meaningful
STALL_MIN_SAMPLES = 8
#: skew: iteration spread across ranks (leader minus laggard)
SKEW_ITERATIONS = 32
#: dead: heartbeat age beyond this is a dead-rank verdict
DEAD_MAX_AGE_S = 60.0

#: synthetic segment: boundary seconds no TIMETAG phase accounts for
#: (callbacks, eval host math, GC, a seeded sleep) — named honestly instead
#: of silently vanishing from the attribution
HOST_OTHER = "host_other"

_TIMELINE_RE = re.compile(r"timeline\.rank(\d+)\.jsonl$")


def env_dir() -> Optional[str]:
    """The telemetry output dir, or None when recording is off."""
    return os.environ.get(ENV_TELEMETRY) or None


def env_port() -> Optional[int]:
    raw = os.environ.get(ENV_TELEMETRY_PORT) or None
    if raw is None:
        return None
    try:
        return int(raw)
    except ValueError:
        log.warn_once(
            "podwatch-bad-port",
            "podwatch: %s=%r is not an integer port; scrape endpoint off"
            % (ENV_TELEMETRY_PORT, raw),
        )
        return None


def timeline_path(out_dir: str, rank: int) -> str:
    return os.path.join(out_dir, "timeline.rank%d.jsonl" % rank)


def heartbeat_base(out_dir: str) -> str:
    """The coord.heartbeat base path: rank files land as
    ``<dir>/pod.hb.rank<N>.json``."""
    return os.path.join(out_dir, "pod")


# ---------------------------------------------------------------------------
# per-rank recorder (training side)
# ---------------------------------------------------------------------------

class TelemetryRecorder:
    """Bounded per-rank boundary ring, persisted as a rank-suffixed JSONL
    shard through resil/atomic at every sample. Built by :func:`maybe_start`
    inside train(); tests construct it directly (jax-free — ``rank`` is
    explicit and nothing here touches a backend)."""

    def __init__(self, out_dir: str, rank: int, world: int = 1) -> None:
        self.out_dir = out_dir
        self.rank = int(rank)
        self.world = int(world)
        self.path = timeline_path(out_dir, self.rank)
        self._ring: deque = deque(maxlen=RING_SIZE)
        self._lock = sanitize_mod.make_lock("obs.podwatch.ring")
        self._start_mono = time.monotonic()
        self._iters_done = 0
        self._prev_counters: Dict[str, int] = {}
        self._prev_phases: Dict[str, float] = {}
        self.last_mono: Optional[float] = None
        self.last_iteration: Optional[int] = None
        os.makedirs(out_dir, exist_ok=True)

    # -- sampling ----------------------------------------------------------

    def _counters(self) -> Dict[str, int]:
        out: Dict[str, int] = {}
        for name, v in registry_mod.REGISTRY.counters().items():
            if name.startswith(COUNTER_PREFIXES):
                out[name] = int(v)
        return out

    def _phase_deltas(self, gbdt) -> Dict[str, float]:
        """Per-boundary deltas of the TIMETAG phase accumulators — empty
        when LIGHTGBM_TPU_TIMETAG is off (the dict never grows then)."""
        seconds = dict(getattr(getattr(gbdt, "timers", None), "seconds",
                               None) or {})
        if not seconds:
            return {}
        out = {}
        for name, total in seconds.items():
            d = float(total) - self._prev_phases.get(name, 0.0)
            if d > 0:
                out[name] = round(d, 6)
        self._prev_phases = {k: float(v) for k, v in seconds.items()}  # unlocked: written only by the training thread (sample()); scrape threads never read it
        return out

    @staticmethod
    def _mem_bytes() -> Optional[float]:
        try:
            vals = registry_mod.REGISTRY.gauge("device_peak_bytes").values()
            v = vals.get(())
            return float(v) if v else None
        except Exception:
            return None

    def sample(self, iteration: int, chunk: int, dt_s: float,
               gbdt=None) -> Dict:
        """One boundary record: append to the ring, republish the shard,
        refresh this rank's enriched heartbeat. Returns the record (tests
        assert on it); any persistence failure is the caller's to swallow
        (note_boundary does — observability must never fail the run)."""
        now_mono = time.monotonic()
        self._iters_done += int(chunk)  # unlocked: single writer (the training thread); the lock below guards the RING the scrape threads read
        cum_rate = self._iters_done / max(now_mono - self._start_mono, 1e-9)
        rec = {
            "v": 1,
            "rank": self.rank,
            "t": round(time.time(), 6),
            "mono": round(now_mono, 6),
            "iteration": int(iteration),
            "chunk": int(chunk),
            "dt_s": round(float(dt_s), 6),
            "it_per_s": round(int(chunk) / max(float(dt_s), 1e-9), 6),
            "cum_it_per_s": round(cum_rate, 6),
            "counters": self._counters(),
            "segments": self._phase_deltas(gbdt) if gbdt is not None else {},
        }
        mem = self._mem_bytes()
        if mem is not None:
            rec["mem_bytes"] = mem
        with self._lock:
            self._ring.append(rec)
            lines = [json.dumps(r) for r in self._ring]
        from ..resil.atomic import atomic_write_text

        atomic_write_text(self.path, "\n".join(lines) + "\n", fsync=False)
        from ..resil import coord

        coord.heartbeat(
            heartbeat_base(self.out_dir), int(iteration), rank=self.rank,
            extra={"last_chunk_s": round(float(dt_s), 6),
                   "it_per_s": round(cum_rate, 6)},
        )
        self.last_mono = now_mono
        self.last_iteration = int(iteration)
        return rec

    def window(self, n: int = RING_SIZE) -> List[Dict]:
        with self._lock:
            items = list(self._ring)
        return items[-n:]


# ---------------------------------------------------------------------------
# module lifecycle (mirrors obs/flight.py: one active recorder, start/stop,
# a no-op fast path when off)
# ---------------------------------------------------------------------------

_LOCK = sanitize_mod.make_lock("obs.podwatch")
_ACTIVE: Optional[TelemetryRecorder] = None
_SERVER: Optional["TelemetryServer"] = None
_PREEMPT_FN: Optional[Callable[[], bool]] = None


def active() -> Optional[TelemetryRecorder]:
    return _ACTIVE


def maybe_start(preempt_watcher=None) -> Optional[TelemetryRecorder]:
    """The train() entry point: one env read per gate; both unset means
    nothing happens — no threads, no ring, no instance (the off-path pins
    in tests/test_podwatch.py hold this to account). Returns the recorder
    (None when only the scrape endpoint is armed, or on any failure —
    observability must never fail the training run)."""
    out_dir = env_dir()
    port = env_port()
    if out_dir is None and port is None:
        return None
    global _PREEMPT_FN
    if preempt_watcher is not None:
        _PREEMPT_FN = preempt_watcher.requested
    if port is not None:
        ensure_server(port)
    if out_dir is None:
        return None
    try:
        from . import dist as dist_mod

        rank, world = dist_mod.process_info()
        return start(out_dir, rank=rank, world=world)
    except Exception as e:
        log.warning("podwatch: recorder start failed (%s: %s); telemetry "
                    "off for this run" % (type(e).__name__, str(e)[:160]))
        return None


def start(out_dir: str, rank: int = 0,
          world: int = 1) -> Optional[TelemetryRecorder]:
    """Arm the per-rank recorder; None (recording stays off) when another
    recorder is already active — nested train() calls (the loop
    controller's warm-start retrain inside a recorded run) keep the outer
    run's telemetry."""
    global _ACTIVE
    with _LOCK:
        if _ACTIVE is not None:
            log.warn_once(
                "podwatch-nested",
                "podwatch: a telemetry recorder is already active (%s); "
                "nested run not recorded" % _ACTIVE.path,
            )
            return None
        try:
            rec = TelemetryRecorder(out_dir, rank, world)
        except OSError as e:
            log.warning("podwatch: cannot record to %s (%s)" % (out_dir, e))
            return None
        _ACTIVE = rec
        return rec


def note_boundary(iteration: int, chunk: int, dt_s: float, gbdt=None) -> None:
    """Per-chunk-boundary hook (engine._boost_loop): no-op when off."""
    rec = _ACTIVE
    if rec is None:
        return
    try:
        rec.sample(iteration, chunk, dt_s, gbdt=gbdt)
    except Exception as e:
        log.debug("podwatch: boundary sample failed: %r" % (e,))


def stop() -> None:
    """Close the active recorder (the shard on disk is already current —
    every boundary republished it). The scrape listener, if any, stays up:
    a pod is watched across train() calls."""
    global _ACTIVE
    with _LOCK:
        _ACTIVE = None


# ---------------------------------------------------------------------------
# scrape endpoint (training side)
# ---------------------------------------------------------------------------

def health_payload() -> Dict:
    """Liveness + progress for /health: cheap enough to poll every second."""
    rec = _ACTIVE
    fn = _PREEMPT_FN
    reg = registry_mod.REGISTRY
    payload: Dict[str, object] = {
        "status": "ok",
        "pid": os.getpid(),
        "telemetry_armed": rec is not None,
        "iteration": int(reg.counter("train_iterations").value()),
        "preempt_requested": bool(fn()) if fn is not None else False,
        "watchdog_deadline_total": int(
            reg.counter("resil_collective_deadline").value()
        ),
    }
    if rec is not None:
        payload["rank"] = rec.rank
        payload["world"] = rec.world
        payload["last_iteration"] = rec.last_iteration
        payload["last_boundary_age_s"] = (
            round(time.monotonic() - rec.last_mono, 3)
            if rec.last_mono is not None else None
        )
    return payload


def timeline_payload(n: int = RING_SIZE) -> Dict:
    rec = _ACTIVE
    if rec is None:
        return {"telemetry_armed": False, "samples": []}
    return {
        "telemetry_armed": True,
        "rank": rec.rank,
        "world": rec.world,
        "samples": rec.window(n),
    }


def _make_handler():
    """Build the handler class lazily: serve/httpbase is a sibling package
    import, and podwatch's aggregator half must import cleanly even if the
    serve package ever grows heavier."""
    from ..serve import httpbase

    class PodwatchHandler(httpbase.JsonHandler):
        server_version = "lightgbm-tpu-podwatch/1.0"
        log_prefix = "podwatch"

        def do_GET(self):  # noqa: N802 (http.server API)
            path = self.path.split("?", 1)[0]
            try:
                if path == "/metrics":
                    self._text(
                        200, registry_mod.REGISTRY.prometheus_text(),
                        httpbase.PROM_CONTENT_TYPE,
                    )
                elif path == "/health":
                    self._json(200, health_payload())
                elif path == "/timeline":
                    self._json(200, timeline_payload())
                else:
                    self._json(404, {"error": "unknown path %s" % path})
            except Exception as e:  # a scrape must never kill the listener
                self._json(500, {"error": "%s: %s" % (type(e).__name__, e)})

    return PodwatchHandler


class TelemetryServer:
    """The opt-in scrape listener: one daemon serve_forever thread, handler
    threads daemonized by serve/httpbase.DaemonHTTPServer. ``port`` is the
    BOUND port (pass 0 to pick a free one — tests do)."""

    def __init__(self, port: int, host: str = "127.0.0.1") -> None:
        from ..serve import httpbase

        self._httpd = httpbase.DaemonHTTPServer((host, port), _make_handler())
        self.port = int(self._httpd.server_address[1])
        self._thread = threading.Thread(
            target=self._httpd.serve_forever, name="podwatch-http",
            daemon=True,
        )
        self._thread.start()

    def close(self) -> None:
        self._httpd.shutdown()
        self._httpd.server_close()
        self._thread.join(timeout=5)


def ensure_server(port: int) -> Optional[TelemetryServer]:
    """Start (or return) the process-wide scrape listener. A bind failure
    is a warning — the port may be held by this very process's previous
    listener after a port-env change, or by an unrelated tenant — and
    training proceeds unscrapable rather than dead."""
    global _SERVER
    with _LOCK:
        if _SERVER is not None:
            return _SERVER
    try:
        srv = TelemetryServer(port)
    except OSError as e:
        log.warning(
            "podwatch: cannot bind scrape endpoint on port %d (%s); "
            "training continues without it" % (port, e)
        )
        return None
    with _LOCK:
        if _SERVER is None:
            _SERVER = srv
            log.info("podwatch: scrape endpoint on 127.0.0.1:%d "
                     "(/metrics /health /timeline)" % srv.port)
            return srv
    srv.close()  # lost the race to a concurrent ensure_server
    with _LOCK:
        return _SERVER


def shutdown_server() -> None:
    """Tear the listener down (tests; training never calls this)."""
    global _SERVER
    with _LOCK:
        srv = _SERVER
        _SERVER = None
    if srv is not None:
        srv.close()


# ---------------------------------------------------------------------------
# aggregator + verdicts (stdlib-only; runs anywhere the shared dir mounts)
# ---------------------------------------------------------------------------

def load_timelines(out_dir: str) -> Dict[int, List[Dict]]:
    """{rank: samples} from every ``timeline.rank*.jsonl`` shard, torn
    tails tolerated line-by-line (the writer republishes atomically, but an
    operator may point podwatch at a half-copied dir)."""
    out: Dict[int, List[Dict]] = {}
    for path in sorted(glob.glob(os.path.join(out_dir, "timeline.rank*.jsonl"))):
        m = _TIMELINE_RE.search(os.path.basename(path))
        if not m:
            continue
        samples: List[Dict] = []
        try:
            with open(path, encoding="utf-8") as fh:
                for line in fh:
                    line = line.strip()
                    if not line:
                        continue
                    try:
                        rec = json.loads(line)
                    except ValueError:
                        continue
                    if isinstance(rec, dict):
                        samples.append(rec)
        except OSError:
            continue
        out[int(m.group(1))] = samples
    return out


def _window(samples: List[Dict]) -> List[Dict]:
    return samples[WARMUP_SKIP:][-WINDOW:]


def _mean(xs: List[float]) -> float:
    return sum(xs) / len(xs) if xs else 0.0


def _median(xs: List[float]) -> float:
    """Lower median: for even counts take the lower of the two middle
    elements instead of averaging. In a 2-rank pod the averaged median sits
    halfway between the healthy rank and the straggler — diluted by the very
    rank under judgment — while the lower median stays anchored to the
    healthy one."""
    if not xs:
        return 0.0
    s = sorted(xs)
    return s[(len(s) - 1) // 2]


def _segment_means(window: List[Dict]) -> Dict[str, float]:
    """Mean seconds per boundary per segment, including the synthetic
    ``host_other`` bucket (boundary time no TIMETAG phase claims)."""
    totals: Dict[str, float] = {}
    other = 0.0
    for s in window:
        segs = s.get("segments") or {}
        for k, v in segs.items():
            totals[k] = totals.get(k, 0.0) + float(v)
        other += max(float(s.get("dt_s", 0.0)) - sum(
            float(v) for v in segs.values()), 0.0)
    n = max(len(window), 1)
    out = {k: v / n for k, v in totals.items()}
    out[HOST_OTHER] = other / n
    return out


def _diverging_segment(
    rank: int, seg_means: Dict[int, Dict[str, float]]
) -> Tuple[str, float, float]:
    """(segment, rank_s, pod_median_s): the segment where ``rank``'s mean
    boundary seconds exceed the pod median by the most absolute time."""
    mine = seg_means.get(rank, {})
    best, best_excess = HOST_OTHER, float("-inf")
    best_mine, best_pod = 0.0, 0.0
    for seg in sorted(set(k for sm in seg_means.values() for k in sm)):
        pod = _median([sm.get(seg, 0.0) for r, sm in seg_means.items()
                       if r != rank]) if len(seg_means) > 1 else 0.0
        excess = mine.get(seg, 0.0) - pod
        if excess > best_excess:
            best, best_excess = seg, excess
            best_mine, best_pod = mine.get(seg, 0.0), pod
    return best, best_mine, best_pod


def compute_verdicts(
    timelines: Dict[int, List[Dict]],
    stale: Optional[List] = None,
) -> List[Dict]:
    """Evidence-backed verdict list (``verdict``/``why``/``evidence``,
    thresholds cited by value so the sentence stands alone).
    Deterministic order: stragglers, stalls, skew, dead — each by rank."""
    verdicts: List[Dict] = []
    windows = {r: _window(s) for r, s in timelines.items()}

    # -- straggler: mean chunk seconds vs the pod median -------------------
    chunk_means = {
        r: _mean([float(s.get("dt_s", 0.0)) for s in w])
        for r, w in windows.items() if len(w) >= MIN_SAMPLES
    }
    if len(chunk_means) >= 2:
        med = _median(list(chunk_means.values()))
        seg_means = {r: _segment_means(w) for r, w in windows.items()
                     if r in chunk_means}
        for r in sorted(chunk_means):
            mine = chunk_means[r]
            if med > 0 and mine > STRAGGLER_FACTOR * med:
                seg, seg_mine, seg_pod = _diverging_segment(r, seg_means)
                verdicts.append({
                    "verdict": "straggler",
                    "rank": r,
                    "why": "rank %d chunk %.3fs = %.2fx pod median %.3fs "
                           "(threshold %.2fx); diverging segment %s "
                           "(%.3fs vs pod %.3fs per boundary)"
                           % (r, mine, mine / med, med, STRAGGLER_FACTOR,
                              seg, seg_mine, seg_pod),
                    "evidence": {
                        "rank_chunk_s": round(mine, 6),
                        "pod_median_chunk_s": round(med, 6),
                        "factor": round(mine / med, 3),
                        "threshold": STRAGGLER_FACTOR,
                        "segment": seg,
                        "segment_rank_s": round(seg_mine, 6),
                        "segment_pod_s": round(seg_pod, 6),
                        "samples": len(windows[r]),
                    },
                })

    # -- stall: recent rate collapse vs the rank's OWN trailing window -----
    for r in sorted(windows):
        if not windows[r]:
            continue
        # compare like with like: a chunked run's per-iteration tail
        # legitimately divides it/s by the chunk size (per-boundary overhead
        # amortizes over fewer iterations) — that is a schedule change, not
        # a stall, so only boundaries sharing the newest sample's chunk size
        # enter the comparison
        tail_chunk = int(windows[r][-1].get("chunk", 1))
        rates = [float(s.get("it_per_s", 0.0)) for s in windows[r]
                 if int(s.get("chunk", 1)) == tail_chunk]
        if len(rates) < STALL_MIN_SAMPLES:
            continue
        recent = _mean(rates[-STALL_RECENT:])
        trailing = _median(rates[:-STALL_RECENT])
        if trailing > 0 and recent < trailing / STALL_FACTOR:
            verdicts.append({
                "verdict": "stall",
                "rank": r,
                "why": "rank %d recent rate %.3f it/s is %.1fx below its "
                       "own trailing median %.3f it/s (threshold %.1fx "
                       "over the last %d boundaries)"
                       % (r, recent, trailing / max(recent, 1e-9), trailing,
                          STALL_FACTOR, STALL_RECENT),
                "evidence": {
                    "recent_it_per_s": round(recent, 6),
                    "trailing_it_per_s": round(trailing, 6),
                    "collapse": round(trailing / max(recent, 1e-9), 3),
                    "threshold": STALL_FACTOR,
                    "recent_boundaries": STALL_RECENT,
                    "samples": len(rates),
                },
            })

    # -- skew: iteration spread across ranks -------------------------------
    last_iter = {
        r: int(s[-1].get("iteration", 0))
        for r, s in timelines.items() if s
    }
    if len(last_iter) >= 2:
        leader = max(last_iter, key=lambda r: (last_iter[r], -r))
        laggard = min(last_iter, key=lambda r: (last_iter[r], r))
        spread = last_iter[leader] - last_iter[laggard]
        if spread > SKEW_ITERATIONS:
            verdicts.append({
                "verdict": "skew",
                "rank": laggard,
                "why": "iteration spread %d across the pod exceeds %d: "
                       "rank %d is at %d while rank %d leads at %d"
                       % (spread, SKEW_ITERATIONS, laggard,
                          last_iter[laggard], leader, last_iter[leader]),
                "evidence": {
                    "spread": spread,
                    "threshold": SKEW_ITERATIONS,
                    "laggard": laggard,
                    "laggard_iteration": last_iter[laggard],
                    "leader": leader,
                    "leader_iteration": last_iter[leader],
                },
            })

    # -- dead: stale/missing heartbeats (resil/coord.stale_ranks) ----------
    for entry in sorted(stale or []):
        r, age = entry[0], entry[1]
        evidence = dict(getattr(entry, "evidence", None) or {})
        why = (
            "rank %d heartbeat is %.1fs old (stale past %.0fs); last seen "
            "at iteration %s" % (r, age, DEAD_MAX_AGE_S,
                                 evidence.get("iteration", "?"))
            if age is not None
            else "rank %d has no readable heartbeat file" % r
        )
        verdicts.append({
            "verdict": "dead",
            "rank": r,
            "why": why,
            # age_source: which clock judged the age (coord.heartbeat_age —
            # "wall" from the blob's time stamp, "mtime" when a foreign/
            # legacy writer omitted it; never the per-process mono clock)
            "evidence": {"age_s": None if age is None else round(age, 3),
                         "threshold_s": DEAD_MAX_AGE_S,
                         "age_source": evidence.get("age_source"),
                         "heartbeat": evidence},
        })
    return verdicts


#: flexctl's decision table (docs/FaultTolerance.md §Fleet orchestrator):
#: what an orchestrator should DO about each verdict kind. Only *dead*
#: triggers an automatic reshard — and only when its evidence shows a rank
#: that heartbeat and then went silent (``age_s`` present); a missing
#: heartbeat file is startup-ambiguous and stays advisory. straggler/stall
#: are performance findings (the run is correct, just slow) and *skew* on
#: a healthy pod means the collectives are already keeping ranks honest.
VERDICT_ACTIONS = {
    "dead": "drain_survivors",
    "stall": "watch",
    "straggler": "watch",
    "skew": "watch",
}


def actions_for(summary: Dict) -> List[Dict]:
    """The verdict→action plumbing flexctl consumes: one record per
    verdict with the action from :data:`VERDICT_ACTIONS` (*dead* without
    age evidence is demoted to ``watch``, see the table's doc)."""
    out: List[Dict] = []
    for v in summary.get("verdicts") or []:
        action = VERDICT_ACTIONS.get(v.get("verdict"), "watch")
        if (v.get("verdict") == "dead"
                and (v.get("evidence") or {}).get("age_s") is None):
            action = "watch"
        out.append({
            "rank": v.get("rank"),
            "verdict": v.get("verdict"),
            "action": action,
            "why": v.get("why", ""),
        })
    return out


def pod_summary(out_dir: str, now: Optional[float] = None,
                max_age_s: float = DEAD_MAX_AGE_S) -> Dict:
    """Fold every rank's shards + heartbeats into one pod view. ``now`` is
    the wall clock the dead-rank ages are judged against (tests pin it)."""
    from ..resil import coord

    timelines = load_timelines(out_dir)
    hb_base = heartbeat_base(out_dir)
    hb_world = 0
    for path in glob.glob(hb_base + ".hb.rank*.json"):
        m = re.search(r"\.hb\.rank(\d+)\.json$", path)
        if m:
            hb_world = max(hb_world, int(m.group(1)) + 1)
    world = max(hb_world, (max(timelines) + 1) if timelines else 0)
    heartbeats = coord.read_heartbeats(hb_base, world)
    stale = (coord.stale_ranks(hb_base, world, max_age_s, now=now)
             if world else [])
    ranks: Dict[str, Dict] = {}
    for r in sorted(set(timelines) | set(heartbeats)):
        samples = timelines.get(r) or []
        w = _window(samples)
        hb = heartbeats.get(r) or {}
        ranks[str(r)] = {
            "samples": len(samples),
            "iteration": (int(samples[-1]["iteration"]) if samples
                          else hb.get("iteration")),
            "chunk_s": round(_mean([float(s.get("dt_s", 0.0)) for s in w]), 6),
            "it_per_s": round(
                float(samples[-1].get("cum_it_per_s", 0.0)), 6
            ) if samples else hb.get("it_per_s"),
            "heartbeat": {k: hb[k] for k in
                          ("iteration", "time", "mono", "last_chunk_s",
                           "it_per_s", "pid") if k in hb},
        }
    last_iters = [int(s[-1]["iteration"]) for s in timelines.values() if s]
    return {
        "dir": out_dir,
        "world": world,
        "ranks": ranks,
        "iteration_spread": (max(last_iters) - min(last_iters)
                             if len(last_iters) >= 2 else 0),
        "verdicts": compute_verdicts(timelines, stale=stale),
    }


# ---------------------------------------------------------------------------
# publication: podwatch_* gauges + the run_report section
# ---------------------------------------------------------------------------

VERDICT_KINDS = ("straggler", "stall", "skew", "dead")

_SECTION_REGISTERED = False
_LAST_SUMMARY: Dict = {}


def _report_section() -> Dict:
    return dict(_LAST_SUMMARY)


def publish(summary: Dict, registry=None) -> None:
    """Land the pod view on the registry: ``podwatch_verdicts{verdict=}``
    (every kind set, so a cleared verdict re-publishes as 0),
    ``podwatch_iteration_spread``, per-rank iteration/chunk gauges, and the
    ``fleet_telemetry`` run_report section (report.py §Fleet telemetry)."""
    global _SECTION_REGISTERED
    reg = registry if registry is not None else registry_mod.REGISTRY
    counts = {k: 0 for k in VERDICT_KINDS}
    for v in summary.get("verdicts") or []:
        k = v.get("verdict")
        if k in counts:
            counts[k] += 1
    g = reg.gauge("podwatch_verdicts",
                  "fleet-telemetry verdicts by kind (obs/podwatch.py)")
    for k, n in counts.items():
        g.set(n, verdict=k)
    reg.gauge("podwatch_iteration_spread",
              "pod iteration spread: leader minus laggard").set(
        float(summary.get("iteration_spread") or 0))
    g_it = reg.gauge("podwatch_rank_iteration",
                     "last recorded iteration per rank")
    g_ch = reg.gauge("podwatch_rank_chunk_seconds",
                     "mean chunk-boundary seconds per rank (recent window)")
    for r, rec in (summary.get("ranks") or {}).items():
        if rec.get("iteration") is not None:
            g_it.set(float(rec["iteration"]), rank=str(r))
        if rec.get("chunk_s") is not None:
            g_ch.set(float(rec["chunk_s"]), rank=str(r))
    _LAST_SUMMARY.clear()
    _LAST_SUMMARY.update(summary)
    if reg is not registry_mod.REGISTRY:
        reg.register_report_section("fleet_telemetry", _report_section)
    elif not _SECTION_REGISTERED:
        _SECTION_REGISTERED = True
        reg.register_report_section("fleet_telemetry", _report_section)


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def _print_human(summary: Dict) -> None:
    print("podwatch: %s — world %d, iteration spread %d"
          % (summary["dir"], summary["world"], summary["iteration_spread"]))
    for r, rec in sorted(summary["ranks"].items(), key=lambda kv: int(kv[0])):
        print("  rank %s: iter %s, %s it/s, chunk %ss (%d samples)"
              % (r, rec.get("iteration"), rec.get("it_per_s"),
                 rec.get("chunk_s"), rec.get("samples", 0)))
    if not summary["verdicts"]:
        print("  verdicts: none — pod looks healthy")
    for v in summary["verdicts"]:
        print("  VERDICT %s rank %s: %s" % (v["verdict"], v["rank"], v["why"]))


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m lightgbm_tpu.obs.podwatch",
        description="Fold per-rank telemetry shards + heartbeats into one "
                    "pod view with straggler/stall/skew/dead verdicts",
    )
    ap.add_argument("dir", help="the LIGHTGBM_TPU_TELEMETRY directory")
    ap.add_argument("--json", action="store_true",
                    help="emit the pod summary as JSON")
    ap.add_argument("--strict", action="store_true",
                    help="exit 3 when any straggler/stall/dead verdict "
                         "fires (skew alone stays informational)")
    ap.add_argument("--max-age-s", type=float, default=DEAD_MAX_AGE_S,
                    help="heartbeat age beyond which a rank is dead "
                         "(default %(default)s)")
    ap.add_argument("--now", type=float, default=None,
                    help="wall-clock override for the dead-rank judgement "
                         "(tests/replays)")
    args = ap.parse_args(argv)
    summary = pod_summary(args.dir, now=args.now, max_age_s=args.max_age_s)
    publish(summary)
    if args.json:
        print(json.dumps(summary, indent=2, sort_keys=True))
    else:
        _print_human(summary)
    if args.strict and any(
        v["verdict"] in ("straggler", "stall", "dead")
        for v in summary["verdicts"]
    ):
        return 3
    return 0


if __name__ == "__main__":
    sys.exit(main())
