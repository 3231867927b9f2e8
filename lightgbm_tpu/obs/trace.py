"""Structured span tracer: Chrome-trace-format JSON, Perfetto-viewable.

Env-gated with ``LIGHTGBM_TPU_TRACE=<path>``: when set, every ``span()``
context in the process records a Chrome "complete" event (``ph: "X"`` with
pid/tid/ts/dur, microseconds) and the buffer is written to ``<path>`` at
``stop()``/``flush()`` or process exit. Load the file in Perfetto
(https://ui.perfetto.dev) or chrome://tracing; events on one thread nest by
time containment, so a ``train.iteration`` span visually contains its
``tree growth`` / ``renew+score update`` phase spans.

Span sites (cat → where):
  * ``train.phase``   — every PhaseTimers phase (utils/timer.py)
  * ``train``         — per-iteration / per-chunk spans (engine._boost_loop)
  * ``serve``         — request lifecycle: queue wait → batch gather →
                        dispatch → reply (serve/server.py, serve/batcher.py)
  * ``cli``           — task-level spans (cli.py)

Device correlation: when jax is already imported and a tracer is active,
``span()`` additionally enters ``jax.profiler.TraceAnnotation(name)`` so the
host span shows up inside the XLA/TPU profile that ``LIGHTGBM_TPU_PROFILE``
captures — the host and device timelines line up by annotation name.

One trace file per PROCESS: a subprocess inheriting the env var would clobber
the parent's file at exit, so drivers that fan out children rewrite the path
per child (helpers/multichip_bench.py appends ``.dev<N>``).

Disabled cost: one dict lookup per ``span()`` call. Thread-safe throughout.
"""
from __future__ import annotations

import atexit
import contextlib
import json
import os
import sys
import threading

from . import sanitize as sanitize_mod
import time
from typing import Dict, List, Optional

ENV_TRACE = "LIGHTGBM_TPU_TRACE"

_EPOCH = time.perf_counter()


def now_us() -> float:
    """Microseconds on the tracer's (monotonic) clock."""
    return (time.perf_counter() - _EPOCH) * 1e6


#: buffer cap: ~160 bytes/event dict puts 1M events around 160MB — enough
#: for hours of phase spans or minutes of per-request serve spans, small
#: enough that a traced long-lived server cannot OOM from the tracer
MAX_EVENTS = 1_000_000


class Tracer:
    """In-memory Chrome-trace event buffer bound to one output path.

    The buffer is CAPPED at ``max_events``: once full, further events are
    counted (``dropped``) but not stored, and the flushed file carries a
    ``dropped_events`` marker — tracing a long-lived serve process degrades
    to a truncated-but-loadable trace instead of unbounded memory growth.
    """

    def __init__(self, path: str, max_events: int = MAX_EVENTS) -> None:
        self.path = path
        self.pid = os.getpid()
        self.max_events = max_events
        self.dropped = 0
        self._events: List[Dict] = []
        self._lock = sanitize_mod.make_lock("obs.trace.buffer")
        self._tids: Dict[int, int] = {}  # thread ident -> small stable tid

    def _append(self, ev: Dict) -> None:
        with self._lock:
            if len(self._events) >= self.max_events:
                self.dropped += 1
                return
            self._events.append(ev)

    def _tid(self) -> int:
        ident = threading.get_ident()
        with self._lock:
            tid = self._tids.get(ident)
            if tid is None:
                tid = len(self._tids)
                self._tids[ident] = tid
                name = threading.current_thread().name
                # metadata rides outside the cap: a handful of threads
                self._events.insert(tid, {
                    "ph": "M", "name": "thread_name", "pid": self.pid,
                    "tid": tid, "args": {"name": name},
                })
            return tid

    def complete(
        self, name: str, cat: str, ts_us: float, dur_us: float,
        args: Optional[Dict] = None, tid: Optional[int] = None,
    ) -> None:
        ev = {
            "ph": "X", "name": name, "cat": cat or "lgbtpu",
            "pid": self.pid, "tid": self._tid() if tid is None else tid,
            "ts": round(ts_us, 3), "dur": round(max(dur_us, 0.0), 3),
        }
        if args:
            ev["args"] = args
        self._append(ev)

    def instant(self, name: str, cat: str = "", args: Optional[Dict] = None) -> None:
        ev = {
            "ph": "i", "s": "t", "name": name, "cat": cat or "lgbtpu",
            "pid": self.pid, "tid": self._tid(), "ts": round(now_us(), 3),
        }
        if args:
            ev["args"] = args
        self._append(ev)

    def counter(self, name: str, value: float) -> None:
        self._append({
            "ph": "C", "name": name, "cat": "lgbtpu", "pid": self.pid,
            "tid": 0, "ts": round(now_us(), 3),
            "args": {"value": float(value)},
        })

    def flush(self) -> str:
        """Write the full buffer (Chrome trace object form) to ``path``."""
        with self._lock:
            events = list(self._events)
            dropped = self.dropped
        payload = {
            "traceEvents": events,
            "displayTimeUnit": "ms",
            "otherData": {"producer": "lightgbm_tpu.obs.trace"},
        }
        if dropped:
            payload["otherData"]["dropped_events"] = dropped
        with open(self.path, "w", encoding="utf-8") as fh:
            json.dump(payload, fh)
            fh.write("\n")
        return self.path

    def event_count(self) -> int:
        with self._lock:
            return len(self._events)


_TRACER: Optional[Tracer] = None
_LOCK = sanitize_mod.make_lock("obs.trace")
_ATEXIT_ARMED = False


def start(path: Optional[str] = None) -> Tracer:
    """Start (or return) the process tracer; ``path`` defaults to the
    LIGHTGBM_TPU_TRACE env var. Idempotent while a tracer is live."""
    global _TRACER, _ATEXIT_ARMED
    with _LOCK:
        if _TRACER is not None:
            return _TRACER
        target = path or os.environ.get(ENV_TRACE, "")
        if not target:
            raise ValueError(
                "trace.start() needs a path (or set %s)" % ENV_TRACE
            )
        if path is None:
            # jax.distributed runs: every rank inherits the SAME env var, so
            # an env-derived default path gets a .rank<N> suffix — two ranks
            # must never clobber one trace file. Explicit paths are the
            # caller's responsibility.
            target = rank_suffixed(target)
        _TRACER = Tracer(target)
        if not _ATEXIT_ARMED:
            _ATEXIT_ARMED = True
            atexit.register(_atexit_flush)
        return _TRACER


def rank_suffixed(target: str) -> str:
    """``<target>.rank<N>`` when a multi-process jax.distributed world is
    initialized (consults only an already-imported jax; never imports it).
    Shared clobber fix for every env-derived per-process artifact path:
    the tracer's LIGHTGBM_TPU_TRACE file here, utils/timer.maybe_profile's
    LIGHTGBM_TPU_PROFILE dir, and obs/devprof.capture's profile window —
    devprof.find_trace_files folds the ``.rank<N>`` siblings back together
    at parse time."""
    if ".rank" in target:
        return target
    jx = sys.modules.get("jax")
    if jx is None:
        return target
    try:
        if int(jx.process_count()) > 1:
            return "%s.rank%d" % (target, int(jx.process_index()))
    except Exception as e:
        # a half-initialized runtime must not break tracing; the
        # single-file default stands
        from ..utils import log

        log.debug("trace: rank probe failed: %r" % (e,))
    return target


def stop() -> Optional[str]:
    """Flush and detach the tracer; returns the written path (None when no
    tracer was live). A later ``span()`` re-arms from the env var, so tests
    can start/stop repeatedly."""
    global _TRACER
    with _LOCK:
        tr, _TRACER = _TRACER, None
    if tr is None:
        return None
    return tr.flush()


def _atexit_flush() -> None:
    with _LOCK:
        tr = _TRACER
    if tr is not None:
        try:
            tr.flush()
        except OSError:
            pass  # a dead target dir must not break interpreter shutdown


def active() -> Optional[Tracer]:
    """The live tracer, auto-starting from the env var on first use."""
    tr = _TRACER
    if tr is not None:
        return tr
    if os.environ.get(ENV_TRACE, ""):
        try:
            return start()
        except (ValueError, OSError):
            return None
    return None


def enabled() -> bool:
    return active() is not None


@contextlib.contextmanager
def span(name: str, cat: str = "", **args):
    """Record a complete event around the body; no-op without a tracer.

    Keyword args land in the event's ``args`` dict (JSON-able values only).
    When jax is already imported, the span also enters
    ``jax.profiler.TraceAnnotation`` so device profiles carry the same name.
    """
    tr = active()
    if tr is None:
        yield
        return
    ann = None
    jx = sys.modules.get("jax")
    if jx is not None:
        try:
            ann = jx.profiler.TraceAnnotation(name)
            ann.__enter__()
        except Exception:
            ann = None  # profiler unavailable on this backend/version
    t0 = now_us()
    try:
        yield
    finally:
        t1 = now_us()
        if ann is not None:
            try:
                ann.__exit__(None, None, None)
            except Exception as e:
                # annotation teardown must never mask the body's result
                from ..utils import log

                log.debug("trace: TraceAnnotation teardown failed: %r", e)
        tr.complete(name, cat, t0, t1 - t0, args or None)


def complete_at(name: str, cat: str, t0_us: float, t1_us: float,
                **args) -> None:
    """Record a complete event with explicit start/end (``now_us`` clock) —
    for spans measured across threads, e.g. a request's queue wait."""
    tr = active()
    if tr is not None:
        tr.complete(name, cat, t0_us, t1_us - t0_us, args or None)


def instant(name: str, cat: str = "", **args) -> None:
    tr = active()
    if tr is not None:
        tr.instant(name, cat, args or None)


# ---------------------------------------------------------------------------
# multi-file merge: fold per-process/per-rank traces into ONE timeline
# ---------------------------------------------------------------------------

def merge_traces(out_path: str, in_paths) -> Dict:
    """Fold several Chrome-trace files (a pod's per-rank ``.rank<N>`` files, a sweep's ``.dev<D>``
    workers) into ONE Perfetto-loadable timeline. Every source (file, pid)
    pair is remapped to a fresh DISJOINT pid with a ``process_name``
    metadata row naming its origin, so same-pid events from different
    processes can never interleave; ``dropped_events`` markers are summed
    and preserved. Gzipped inputs (``*.json.gz`` — the XLA profiler's own
    export format) load transparently, so per-rank LIGHTGBM_TPU_PROFILE
    captures merge next to the host-span files.
    Returns {files, events, pids, dropped, path}."""
    from . import devprof as devprof_mod  # one gz-transparent loader

    events: List[Dict] = []
    pid_map: Dict = {}
    dropped = 0
    n_events = 0
    files = 0
    for i, p in enumerate(in_paths):
        try:
            doc = devprof_mod.load_chrome_trace(str(p))
        except (OSError, ValueError):
            continue  # a torn/absent child trace must not kill the merge
        files += 1
        dropped += int((doc.get("otherData") or {}).get("dropped_events", 0)
                       or 0)
        label = os.path.basename(str(p))
        for ev in doc.get("traceEvents") or []:
            old = ev.get("pid", 0)
            key = (i, old)
            new = pid_map.get(key)
            if new is None:
                new = pid_map[key] = len(pid_map) + 1
                events.append({
                    "ph": "M", "name": "process_name", "pid": new, "tid": 0,
                    "args": {"name": "%s (pid %s)" % (label, old)},
                })
            ev2 = dict(ev)
            ev2["pid"] = new
            events.append(ev2)
            n_events += 1
    payload: Dict = {
        "traceEvents": events,
        "displayTimeUnit": "ms",
        "otherData": {"producer": "lightgbm_tpu.obs.trace merge"},
    }
    if dropped:
        payload["otherData"]["dropped_events"] = dropped
    with open(out_path, "w", encoding="utf-8") as fh:
        json.dump(payload, fh)
        fh.write("\n")
    return {
        "files": files, "events": n_events, "pids": len(pid_map),
        "dropped": dropped, "path": out_path,
    }


def main(argv: Optional[List[str]] = None) -> int:
    """``python -m lightgbm_tpu.obs.trace merge -o out.json in1 in2 ...``
    (globs welcome) — the pod-wide timeline merge. Stdlib only."""
    import argparse
    import glob as glob_mod

    ap = argparse.ArgumentParser(
        prog="python -m lightgbm_tpu.obs.trace",
        description="Chrome-trace utilities (obs/trace.py)",
    )
    sub = ap.add_subparsers(dest="cmd", required=True)
    mg = sub.add_parser(
        "merge", help="fold per-process trace files into one timeline "
                      "with disjoint pids",
    )
    mg.add_argument("inputs", nargs="+",
                    help="trace files, shell-unexpanded globs, or "
                         "LIGHTGBM_TPU_PROFILE capture dirs (expanded to "
                         "their per-rank trace.json.gz files)")
    mg.add_argument("-o", "--out", default="trace_merged.json")
    args = ap.parse_args(argv)
    paths: List[str] = []
    for item in args.inputs:
        hits = sorted(glob_mod.glob(item))
        for hit in hits if hits else [item]:
            if os.path.isdir(hit):
                # a profiler capture dir: fold its (and its .rank<N>
                # siblings') Chrome traces in — obs/devprof.py owns the
                # directory-layout knowledge, stdlib only like this module
                from . import devprof as devprof_mod

                paths.extend(devprof_mod.find_trace_files(hit))
            else:
                paths.append(hit)
    # a dir and its .rank<N> sibling both matching the glob would fold the
    # same files twice — order-preserving dedupe
    paths = list(dict.fromkeys(paths))
    stats = merge_traces(args.out, paths)
    print(
        "trace merge: %(files)d file(s) -> %(path)s "
        "(%(events)d events, %(pids)d pids, %(dropped)d dropped)" % stats
    )
    return 0 if stats["files"] else 1


if __name__ == "__main__":
    sys.exit(main())
