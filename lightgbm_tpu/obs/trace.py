"""The one in-program trace: spans and counters of the training path in a
bounded in-memory ring, on by default, and Chrome-trace JSON on request.

``LIGHTGBM_TPU_TRACE`` (read at every ``span()`` call, so it can change in a
running process):

 * unset or empty: the **ring** is on. The coarse spans of the training path
   (the categories in ``RING_CATS``: a fixed handful per ``Dataset`` and per
   boosting iteration, one per compiled program, one counter event per
   materialised tree; never one per row, column, split or request) go to a
   ring of the last ``RING_EVENTS`` events, oldest dropped and counted.
   ``events()`` copies it out, ``reset()`` empties it. Spans of every other
   category (``serve``, ``loop``, ``cli``, ``resil``) are not recorded
   and cost what they did before the ring: one lookup.
 * ``<path>``: **file mode**, as before: every ``span()`` of every category
   also records into a buffer capped at ``MAX_EVENTS`` that is written to
   ``<path>`` at ``stop()``/``flush()`` or process exit, Perfetto-viewable
   (https://ui.perfetto.dev). ``enabled()``/``active()`` mean this mode.
 * ``0``: nothing is recorded, ring included.

Every event is a Chrome-trace dict: ``ph`` (``X`` complete span, ``C``
counter, ``i`` instant), ``name``, ``cat``, ``pid``, ``tid``, ``ts`` and
``dur`` in microseconds on ``now_us()``'s monotonic clock, plus ``id`` (unique
in the process) and ``parent`` (the id of the span that was open on the same
thread when this one began, None at the root: a thread-local stack, not time
containment). ``args`` holds the keyword arguments and the identifier the
event's work shares: ``iteration`` is inherited by everything inside one
boosting iteration, ``tree`` rides on a tree's counters.

Span and counter sites (name [cat], where):
  * ``dataset.construct`` [setup] with children ``dataset.to_float``,
    ``dataset.sample``, ``dataset.find_bins``, ``dataset.bin_matrix``
    (basic.py, dataset.py)
  * ``train.init`` [setup]: ``engine.train`` from entry to the loop's first
    pass, ``bytes=`` of the binned matrix moved to the device
  * ``jit.trace``, ``jit.lower``, ``jit.compile`` [compile]: one per program
    jax traces, lowers and builds or loads from its cache, ``fun=`` its name
    (``watch_compiles()``, a ``jax.monitoring`` listener)
  * ``train.iteration`` / ``train.chunk`` [train] with the ``PhaseTimers``
    phases [train.phase] (utils/timer.py) and ``train.wait_prev_tree``
    [train], the host's blocking read of the previous tree's ``num_leaves``
    (models/gbdt.py), as children
  * ``train.sample`` [train] and ``sample.counters`` [train], ``ph: "C"``: a
    booster that draws rows (GOSS, bagging, rf): the span around its
    ``_bagging``, and what it sampled this iteration (``rows``, ``in_bag``,
    ``top_k``, ``other_k``, ``multiplier``; models/gbdt.py ``_note_sample``)
  * ``train.feature_sample`` [train] and ``feature.counters`` [train], ``ph:
    "C"``: a training under ``feature_fraction`` < 1: the span around a tree's
    column draw and its hand-over to the grower, and what was drawn
    (``columns``, ``drawn``; models/gbdt.py ``_draw_columns``)
  * ``train.boundary`` [train]: from ``update``'s return to the next
    ``train.iteration``, child ``train.callbacks`` (engine._boost_loop)
  * ``grow.counters`` [grow], ``ph: "C"``: the grower's work counters of one
    tree (``ops/grow.COUNTER_NAMES``), emitted when the tree is materialised
    on the host (``GBDT._materialize``)
  * file mode only: ``serve.*`` [serve], ``loop.*`` [loop], ``cli.*`` [cli],
    ``resil.*`` [resil]

Device correlation: when jax is already imported, a recorded ``span()`` also
enters ``jax.profiler.TraceAnnotation(name)``, so the program's spans lie in
the host plane of every profile (``LIGHTGBM_TPU_PROFILE``, the benchmark's
traced run) under their names, on the profiler's clock.

One trace file per PROCESS: a subprocess inheriting the env var would clobber
the parent's file at exit, so a driver that fans out children rewrites the
path per child.

Thread-safe throughout.
"""
from __future__ import annotations

import atexit
import collections
import glob as glob_mod
import gzip
import itertools
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

#: the default-on ring: ~40 events an iteration keeps the last ~400
#: iterations, a few MB at most
RING_EVENTS = 16_384
#: what the ring records without file mode: the training path's coarse spans
RING_CATS = frozenset({"setup", "compile", "train", "train.phase", "grow"})

_JIT_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "jit.trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jit.lower",
    "/jax/core/compile/backend_compile_duration": "jit.compile",
}

_IDS = itertools.count(1)
_LOCAL = threading.local()


def _stack() -> list:
    """This thread's open spans (``_Span``), outermost first."""
    st = getattr(_LOCAL, "stack", None)
    if st is None:
        st = _LOCAL.stack = []
    return st


class _Ring:
    """The last ``RING_EVENTS`` events and how many were pushed out."""

    def __init__(self, size: int = RING_EVENTS) -> None:
        self.events = collections.deque(maxlen=size)
        self.dropped = 0
        self.lock = sanitize_mod.make_lock("obs.trace.ring")
        self.tids: Dict[int, int] = {}  # thread ident -> small stable tid

    def append(self, ev: Dict) -> None:
        with self.lock:
            if len(self.events) == self.events.maxlen:
                self.dropped += 1
            self.events.append(ev)

    def tid(self) -> int:
        ident = threading.get_ident()
        tid = self.tids.get(ident)
        if tid is None:
            with self.lock:
                tid = self.tids.setdefault(ident, len(self.tids))
        return tid


_RING = _Ring()


def events() -> List[Dict]:
    """A copy of the ring, oldest first: what the run was doing. Each event
    is a dict of its own (``args`` too); changing it changes nothing here."""
    with _RING.lock:
        snap = list(_RING.events)
    return [dict(ev, args=dict(ev["args"])) for ev in snap]


def dropped() -> int:
    """Events the ring has pushed out since the last ``reset()``."""
    return _RING.dropped


def reset() -> None:
    """Empty the ring and its drop count (file mode's buffer is untouched)."""
    with _RING.lock:
        _RING.events.clear()
        _RING.dropped = 0


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
        self._named: Dict[int, str] = {}  # tid -> thread name, for the file

    def _append(self, ev: Dict) -> None:
        with self._lock:
            if ev["tid"] not in self._named:
                self._named[ev["tid"]] = threading.current_thread().name
            if len(self._events) >= self.max_events:
                self.dropped += 1
                return
            self._events.append(ev)

    def flush(self) -> str:
        """Write the full buffer (Chrome trace object form) to ``path``."""
        with self._lock:
            # metadata rides outside the cap: a handful of threads
            events = [
                {"ph": "M", "name": "thread_name", "pid": self.pid,
                 "tid": tid, "args": {"name": name}}
                for tid, name in sorted(self._named.items())
            ] + self._events
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
    """Start (or return) the process's file tracer; ``path`` defaults to the
    LIGHTGBM_TPU_TRACE env var. Idempotent while a tracer is live."""
    global _TRACER, _ATEXIT_ARMED
    with _LOCK:
        if _TRACER is not None:
            return _TRACER
        target = path or _env_path()
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


def _env_path() -> str:
    """The file LIGHTGBM_TPU_TRACE asks for ("" when unset or ``0``)."""
    value = os.environ.get(ENV_TRACE, "")
    return "" if value == "0" else value


def rank_suffixed(target: str) -> str:
    """``<target>.rank<N>`` when a multi-process jax.distributed world is
    initialized (consults only an already-imported jax; never imports it).
    Shared clobber fix for every env-derived per-process artifact path:
    the tracer's LIGHTGBM_TPU_TRACE file here and utils/timer.maybe_profile's
    LIGHTGBM_TPU_PROFILE dir — :func:`find_trace_files` folds the
    ``.rank<N>`` siblings back together for ``merge``."""
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
    """The live file tracer, auto-starting from the env var on first use."""
    tr = _TRACER
    if tr is not None:
        return tr
    if _env_path():
        try:
            return start()
        except (ValueError, OSError):
            return None
    return None


def enabled() -> bool:
    """File mode is on: spans of every category are recorded."""
    return active() is not None


def _sinks(cat: str):
    """Where an event of this category goes now: (the file tracer or None,
    whether the ring takes it)."""
    return active(), (cat in RING_CATS
                      and os.environ.get(ENV_TRACE, "") != "0")


def recording(cat: str) -> bool:
    """Whether a span of this category would be recorded now, by the ring or
    by file mode."""
    tr, ring = _sinks(cat)
    return ring or tr is not None


def _emit(ph: str, name: str, cat: str, ts_us: float, args: Dict,
          parent: Optional[int], tr: Optional[Tracer], ring: bool,
          eid: Optional[int] = None, **more) -> None:
    ev = {
        "ph": ph, "name": name, "cat": cat or "lgbtpu", "pid": os.getpid(),
        "tid": _RING.tid(), "ts": round(ts_us, 3),
        "id": next(_IDS) if eid is None else eid, "parent": parent,
    }
    ev.update(more)
    ev["args"] = args
    if tr is not None:
        tr._append(ev)
    if ring:
        _RING.append(ev)


def _under_open_span(args: Dict) -> Optional[int]:
    """The id of this thread's innermost open span, whose ``iteration``
    ``args`` inherits unless it brings its own."""
    st = _stack()
    if not st:
        return None
    iteration = st[-1].args.get("iteration")
    if iteration is not None and "iteration" not in args:
        args["iteration"] = iteration
    return st[-1].id


class _NullSpan:
    """What ``span()`` hands out when nothing records it."""

    __slots__ = ()
    t0_us = None

    def __enter__(self):
        return self

    def __exit__(self, *exc) -> bool:
        return False

    def note(self, **args) -> None:
        pass

    def close(self) -> None:
        pass


_NULL = _NullSpan()


class _Span:
    """One open span: a context manager, or ``__enter__()`` now and
    ``close()`` later where the span outlives a block (the loop's boundary)."""

    __slots__ = ("name", "cat", "args", "tr", "ring", "id", "parent",
                 "t0_us", "_depth", "_ann")

    def __init__(self, name: str, cat: str, args: Dict,
                 tr: Optional[Tracer], ring: bool) -> None:
        self.name, self.cat, self.args = name, cat, args
        self.tr, self.ring = tr, ring
        self.t0_us = None

    def __enter__(self) -> "_Span":
        st = _stack()
        self._depth = len(st)
        self.parent = _under_open_span(self.args)
        self.id = next(_IDS)
        st.append(self)
        self._ann = None
        jx = sys.modules.get("jax")
        if jx is not None:
            try:
                self._ann = jx.profiler.TraceAnnotation(self.name)
                self._ann.__enter__()
            except Exception:
                self._ann = None  # profiler unavailable on this backend/version
        self.t0_us = now_us()
        return self

    def __exit__(self, *exc) -> bool:
        if self.t0_us is None:
            return False  # closed already
        t1 = now_us()
        if self._ann is not None:
            try:
                self._ann.__exit__(None, None, None)
            except Exception as e:
                # annotation teardown must never mask the body's result
                from ..utils import log

                log.debug("trace: TraceAnnotation teardown failed: %r", e)
        # also drops what a span abandoned by an exception left above this one
        del _stack()[self._depth:]
        _emit("X", self.name, self.cat, self.t0_us, self.args, self.parent,
              self.tr, self.ring, eid=self.id,
              dur=round(max(t1 - self.t0_us, 0.0), 3))
        self.t0_us = None
        return False

    def note(self, **args) -> None:
        """More ``args`` for the event, known only once the span is open."""
        self.args.update(args)

    def close(self) -> None:
        """End a span begun with ``__enter__()``; once closed, a no-op."""
        self.__exit__(None, None, None)


def span(name: str, cat: str = "", **args):
    """A context manager that records a complete event around its body; a
    shared no-op where nothing records this category.

    Keyword args land in the event's ``args`` dict (JSON-able values only).
    When jax is already imported, the span also enters
    ``jax.profiler.TraceAnnotation`` so device profiles carry the same name.
    """
    tr, ring = _sinks(cat)
    if tr is None and not ring:
        return _NULL
    return _Span(name, cat, args, tr, ring)


def open_depth() -> int:
    """How many spans this thread has open: what ``close_to`` takes."""
    return len(_stack())


def close_to(depth: int) -> None:
    """Close, innermost first, the spans this thread still has open above
    ``depth``: the ``finally`` of a function that begins spans with
    ``__enter__()`` and may be left by an exception."""
    st = _stack()
    while len(st) > depth:
        st[-1].close()


def complete_at(name: str, cat: str, t0_us: float, t1_us: float,
                **args) -> None:
    """Record a complete event with explicit start/end (``now_us`` clock) —
    for spans measured across threads, e.g. a request's queue wait."""
    tr, ring = _sinks(cat)
    if ring or tr is not None:
        _emit("X", name, cat, t0_us, args, _under_open_span(args), tr, ring,
              dur=round(max(t1_us - t0_us, 0.0), 3))


def instant(name: str, cat: str = "", **args) -> None:
    tr, ring = _sinks(cat)
    if ring or tr is not None:
        _emit("i", name, cat, now_us(), args, _under_open_span(args), tr,
              ring, s="t")


def counters(name: str, cat: str = "", **args) -> None:
    """Record one counter event (``ph: "C"``): ``args`` holds the counts and
    the identifier they belong to (``tree=``, ``iteration=``)."""
    tr, ring = _sinks(cat)
    if ring or tr is not None:
        _emit("C", name, cat, now_us(), args, _under_open_span(args), tr,
              ring)


def _on_jax_duration(event: str, duration: float, **kwargs) -> None:
    name = _JIT_EVENTS.get(event)
    if name is None or not recording("compile"):
        return
    t1 = now_us()
    t0, fun = t1 - duration * 1e6, kwargs.get("fun_name")
    if name == "jit.trace":
        # jax reports every jitted function it traces on the way (a jnp call
        # inside the grower is one, a kernel traced while lowering another):
        # which of them was a program's own trace shows at its lowering
        traced = getattr(_LOCAL, "traced", None)
        if traced is None:
            # bounded: traces that no lowering follows (make_jaxpr) pile up
            traced = _LOCAL.traced = collections.deque(maxlen=4096)
        traced.append((t0, t1, fun))
        return
    if name == "jit.lower":
        # the enclosed traces end before the program's own does, and that one
        # before the lowering starts (1 ms of slack between the two clocks)
        traced = getattr(_LOCAL, "traced", None) or ()
        own = [t for t in traced if t[1] <= t0 + 1e3]
        if traced:
            traced.clear()
        if own:
            complete_at("jit.trace", "compile", own[-1][0], own[-1][1],
                        fun=own[-1][2])
    complete_at(name, "compile", t0, t1, fun=fun)


_WATCHING = False


def watch_compiles() -> None:
    """Register, once in the process, the ``jax.monitoring`` listener that
    records ``jit.trace`` / ``jit.lower`` / ``jit.compile`` (a build and a
    load from the persistent cache alike). Called where the training path
    begins (``Dataset.construct``, ``engine.train``), so that importing this
    package still touches no jax."""
    global _WATCHING
    with _LOCK:
        if _WATCHING:
            return
        _WATCHING = True
    import jax.monitoring

    jax.monitoring.register_event_duration_secs_listener(_on_jax_duration)


# ---------------------------------------------------------------------------
# multi-file merge: fold per-process/per-rank traces into ONE timeline
# ---------------------------------------------------------------------------

def load_chrome_trace(path: str) -> Dict:
    """One Chrome-trace document, transparently gunzipping ``*.gz``."""
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rt", encoding="utf-8") as fh:
        return json.load(fh)


def find_trace_files(profile_dir: str) -> List[str]:
    """The Chrome-trace files of a profiler capture dir.

    Looks under ``<dir>/plugins/profile/<session>/*.trace.json(.gz)``, the
    newest session per dir, and — the multi-process story — folds sibling
    ``<dir>.rank<N>`` dirs in, so one merge sees the whole pod. A direct
    file path passes through untouched.
    """
    if os.path.isfile(profile_dir):
        return [profile_dir]
    dirs = [profile_dir] + sorted(
        glob_mod.glob(glob_mod.escape(profile_dir) + ".rank*"))
    out: List[str] = []
    for d in dirs:
        sessions = sorted(
            s for s in glob_mod.glob(
                os.path.join(glob_mod.escape(d), "plugins", "profile", "*"))
            if os.path.isdir(s))
        for s in sessions[-1:]:
            out.extend(sorted(
                glob_mod.glob(os.path.join(glob_mod.escape(s),
                                           "*.trace.json.gz"))
                + glob_mod.glob(os.path.join(glob_mod.escape(s),
                                             "*.trace.json"))
            ))
    return out


def merge_traces(out_path: str, in_paths) -> Dict:
    """Fold several Chrome-trace files (a pod's per-rank ``.rank<N>`` files)
    into ONE Perfetto-loadable timeline. Every source (file, pid)
    pair is remapped to a fresh DISJOINT pid with a ``process_name``
    metadata row naming its origin, so same-pid events from different
    processes can never interleave; ``dropped_events`` markers are summed
    and preserved. Gzipped inputs (``*.json.gz`` — the XLA profiler's own
    export format) load transparently, so per-rank LIGHTGBM_TPU_PROFILE
    captures merge next to the host-span files.
    Returns {files, events, pids, dropped, path}."""
    events: List[Dict] = []
    pid_map: Dict = {}
    dropped = 0
    n_events = 0
    files = 0
    for i, p in enumerate(in_paths):
        try:
            doc = load_chrome_trace(str(p))
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
                # siblings') Chrome traces in
                paths.extend(find_trace_files(hit))
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
