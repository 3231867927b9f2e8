"""Per-phase wall-clock timers (TIMETAG analogue).

The reference accumulates per-phase timings (init/hist/find-split/split) behind
the compile-time TIMETAG flag and prints them at teardown
(/root/reference/src/treelearner/serial_tree_learner.cpp:19-47,
src/boosting/gbdt.cpp:29-42). Here whole-tree growth is one fused XLA program,
so the observable phases are the training-loop stages around it; enable with
the LIGHTGBM_TPU_TIMETAG=1 environment variable (the runtime analogue of the
reference's compile-time switch).

Two numbers are recorded per phase:

 * ``dispatch_seconds`` — host wall time up to the phase's ``mark()`` call,
   i.e. the time the host spent ISSUING the work (async launch cost). This is
   always cheap to record and never perturbs pipelining.
 * ``seconds`` — total phase wall time. With ``LIGHTGBM_TPU_TIMERS=sync`` the
   ``mark()`` call additionally ``block_until_ready``s the phase's result, so
   ``seconds`` becomes host-attributed DEVICE time and ``seconds -
   dispatch_seconds`` is the per-phase device-compute gap. Without the sync
   opt-in no blocking happens: timing a pipelined run no longer serializes
   every phase (the pre-r6 behavior, which destroyed the very dispatch
   overlap being measured).

For kernel-level breakdowns use LIGHTGBM_TPU_PROFILE=<dir> instead, which
wraps training in a ``jax.profiler`` trace readable in TensorBoard/Perfetto —
the TPU-native counterpart of poking timers into the C++ learner. Every phase
below also records a span in the in-program trace (obs/trace.py: its ring by
default, a Chrome-trace file under LIGHTGBM_TPU_TRACE=<path>), independent of
whether the TIMETAG accumulators are on.

Clock: ``time.perf_counter`` throughout — monotonic. The pre-obs
``time.time()`` was wall-clock, so an NTP step mid-run silently corrupted
phase totals (and could even go negative).
"""
from __future__ import annotations

import contextlib
import os
import time
from typing import Dict, Optional

from ..obs import trace as trace_mod
from . import log

ENV_FLAG = "LIGHTGBM_TPU_TIMETAG"
ENV_SYNC = "LIGHTGBM_TPU_TIMERS"
ENV_PROFILE = "LIGHTGBM_TPU_PROFILE"


def timetag_enabled() -> bool:
    return os.environ.get(ENV_FLAG, "") not in ("", "0")


def sync_enabled() -> bool:
    """LIGHTGBM_TPU_TIMERS=sync opts into blocking per-phase device syncs
    (implies timing on). Any other value leaves phases async."""
    return os.environ.get(ENV_SYNC, "") == "sync"


class _PhaseHandle:
    """Yielded by ``PhaseTimers.phase``; ``mark(result)`` records the host
    dispatch time and — under the sync opt-in — blocks on ``result`` so the
    enclosing phase's total attributes device work to it."""

    __slots__ = ("_sync", "_t0", "dispatch")

    def __init__(self, sync: bool, t0: float) -> None:
        self._sync = sync
        self._t0 = t0
        self.dispatch: Optional[float] = None

    def mark(self, result=None) -> None:
        self.dispatch = time.perf_counter() - self._t0
        if self._sync and result is not None:
            import jax

            jax.block_until_ready(result)


class _NoopHandle:
    __slots__ = ()

    def mark(self, result=None) -> None:
        pass


_NOOP = _NoopHandle()


class PhaseTimers:
    """Accumulates wall seconds per named phase; no-op unless enabled."""

    def __init__(
        self, enabled: bool | None = None, sync: bool | None = None
    ) -> None:
        self.sync = sync_enabled() if sync is None else sync
        self.enabled = (
            (timetag_enabled() or self.sync) if enabled is None else enabled
        )
        self.seconds: Dict[str, float] = {}
        self.counts: Dict[str, int] = {}
        self.dispatch_seconds: Dict[str, float] = {}

    @contextlib.contextmanager
    def phase(self, name: str):
        # the obs tracer records a span for every phase even when the
        # TIMETAG accumulators are off (its ring is on by default) — routed
        # through trace_mod.span so the phase ALSO enters
        # jax.profiler.TraceAnnotation and lines up with device timelines;
        # under LIGHTGBM_TPU_TRACE=0 the cost is one lookup
        if not self.enabled and not trace_mod.recording("train.phase"):
            yield _NOOP
            return
        with trace_mod.span(name, cat="train.phase"):
            if not self.enabled:
                yield _NOOP
                return
            t0 = time.perf_counter()
            handle = _PhaseHandle(self.sync, t0)
            try:
                yield handle
            finally:
                dt = time.perf_counter() - t0
                self.seconds[name] = self.seconds.get(name, 0.0) + dt
                self.counts[name] = self.counts.get(name, 0) + 1
                # a phase that never mark()ed is all host work:
                # dispatch == total
                host = handle.dispatch if handle.dispatch is not None else dt
                self.dispatch_seconds[name] = (
                    self.dispatch_seconds.get(name, 0.0) + host
                )

    def report(self) -> None:
        if not self.enabled or not self.seconds:
            return
        total = sum(self.seconds.values())
        log.info(
            "phase timing (TIMETAG%s):" % (", synced" if self.sync else "")
        )
        for name, secs in sorted(self.seconds.items(), key=lambda kv: -kv[1]):
            disp = self.dispatch_seconds.get(name, secs)
            log.info(
                "  %-18s %8.3fs  (%5.1f%%, %d calls, dispatch %.3fs)"
                % (
                    name, secs, 100.0 * secs / max(total, 1e-12),
                    self.counts[name], disp,
                )
            )
        log.info("  %-18s %8.3fs" % ("total", total))

    def publish(self, registry=None) -> None:
        """Export the accumulated phase totals into the metrics registry
        (labels carry the phase name): ``train_phase_seconds_total``,
        ``train_phase_dispatch_seconds_total``, ``train_phase_calls_total``.
        No-op when nothing was recorded; engine.train calls this once at
        the end so /metrics and run reports read the same numbers
        (docs/Observability.md)."""
        if not self.seconds:
            return
        from ..obs import registry as registry_mod

        reg = registry if registry is not None else registry_mod.REGISTRY
        g_total = reg.gauge("train_phase_seconds_total")
        g_disp = reg.gauge("train_phase_dispatch_seconds_total")
        g_calls = reg.gauge("train_phase_calls_total")
        for name, secs in self.seconds.items():
            g_total.set(secs, phase=name)
            g_disp.set(self.dispatch_seconds.get(name, secs), phase=name)
            g_calls.set(self.counts.get(name, 0), phase=name)


@contextlib.contextmanager
def maybe_profile():
    """jax.profiler trace around training when LIGHTGBM_TPU_PROFILE is set.

    Under an initialized multi-process ``jax.distributed`` world every
    rank inherits the SAME env var, and two profiler sessions writing one
    dir clobber each other's ``plugins/profile/<ts>`` session — so the
    env-derived dir gets the shared ``.rank<N>`` suffix (obs/trace.py
    ``rank_suffixed``, the same fix PR 9 gave LIGHTGBM_TPU_TRACE);
    ``python -m lightgbm_tpu.obs.trace merge <dir>`` folds the per-rank
    dirs' Chrome traces back together. The capture's ``.xplane.pb`` is read
    by ``benchmarks/trace_reduce.py`` (device busy and idle, the operations)
    and ``helpers/xplane_scopes.py`` (time by ``jax.named_scope``).
    """
    out_dir = os.environ.get(ENV_PROFILE, "")
    if not out_dir:
        yield
        return
    import jax

    out_dir = trace_mod.rank_suffixed(out_dir)
    jax.profiler.start_trace(out_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()
        log.info("Wrote jax profiler trace to %s" % out_dir)
