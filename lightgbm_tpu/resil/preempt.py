"""Boundary-latched exits: SIGTERM -> emergency checkpoint -> exit 75,
and flexctl's planned drain -> coordinated checkpoint -> exit 76.

TPU pods are preemptible: the scheduler sends SIGTERM, waits a grace
window, then SIGKILLs. The serve stack already honors that contract with a
graceful drain (serve/__main__.py); this module gives the TRAINING stack
the matching behavior. When armed (``preempt_exit=true`` param or
``LIGHTGBM_TPU_PREEMPT=1``), ``engine.train`` installs a SIGTERM handler
that only sets a flag; the boost loop checks it at each chunk boundary,
writes an EMERGENCY checkpoint through the ordinary resil/checkpoint
machinery (atomic publish, fault site ``ckpt.emergency``), and raises
:class:`TrainingPreempted`. Process entry points (``lightgbm_tpu`` CLI
task=train, ``python -m lightgbm_tpu.loop``) translate that into exit code
:data:`PREEMPT_EXIT_CODE`, which orchestrators — ``loop``'s restart
contract — recognize as "resume me", NOT "I failed": the re-run resumes
from the emergency checkpoint instead of restarting from scratch
(docs/FaultTolerance.md §Elastic training).

The fleet orchestrator (``lightgbm_tpu/flex/``) shares the same
chunk-boundary mechanism through the :class:`BoundaryLatch` base: a
planned capacity change latches ``reason="drain"`` instead of a signal,
the boost loop takes the same checkpoint at the same boundary, and the
process exits :data:`RESHARD_EXIT_CODE` — "relaunch me at the current
capacity", distinct from 75's "resume me as I was"
(docs/FaultTolerance.md §Fleet orchestrator).

This module is deliberately jax-free, like resil/backoff.py.
"""
from __future__ import annotations

import os
import signal
import threading
from typing import Optional

#: The documented preemption exit code: EX_TEMPFAIL from sysexits.h —
#: "temporary failure, retry later", which is precisely the contract (the
#: emergency checkpoint makes the retry a resume). Distinct from 0
#: (success), 1 (real failure) and -signal codes (crash).
PREEMPT_EXIT_CODE = 75

#: The documented drain-for-reshard exit code: the trainer checkpointed at
#: a chunk boundary because the WORLD is about to change (planned capacity
#: event or dead-rank degradation) and must be RELAUNCHED at the current
#: capacity — unlike 75, a plain same-world resume is the wrong response.
#: 76 is EX_PROTOCOL in sysexits.h, the nearest free neighbor of 75;
#: nothing else in the stack claims it.
RESHARD_EXIT_CODE = 76

ENV_PREEMPT = "LIGHTGBM_TPU_PREEMPT"

#: the reasons a boundary latch carries; "preempt" keeps the exact exit-75
#: semantics, "drain" is flexctl's planned/forced world change (exit 76)
REASONS = ("preempt", "drain")


def env_enabled() -> bool:
    """Ambient opt-in: ``LIGHTGBM_TPU_PREEMPT=1`` arms preemption handling
    for every train() in the process (the param form wins when given)."""
    return os.environ.get(ENV_PREEMPT, "") in ("1", "true")


class TrainingPreempted(Exception):
    """Raised out of engine.train when a boundary latch was honored.

    Deliberately NOT a LightGBMError: config-error handlers (e.g. the loop
    controller's bad-checkpoint fallback) must never swallow a preemption
    and retrain from scratch — the whole point is that the emergency
    checkpoint carries the run.
    """

    #: which latch reason produced this exit; subclasses override
    reason = "preempt"

    def __init__(self, message: str, checkpoint_path: Optional[str] = None,
                 iteration: int = -1, signum: int = 0) -> None:
        super().__init__(message)
        self.checkpoint_path = checkpoint_path
        self.iteration = int(iteration)
        self.signum = int(signum)

    @property
    def exit_code(self) -> int:
        """The process exit code this latch reason maps to (75 / 76)."""
        return RESHARD_EXIT_CODE if self.reason == "drain" \
            else PREEMPT_EXIT_CODE


class TrainingDrained(TrainingPreempted):
    """The drain flavor: the run checkpointed and exited because the world
    is about to change; the orchestrator relaunches at current capacity
    (exit :data:`RESHARD_EXIT_CODE`). Subclassing TrainingPreempted keeps
    every existing "preemption is not a failure" handler correct — a drain
    is never a failure either — while ``reason``/``exit_code`` let entry
    points tell the two relaunch contracts apart."""

    reason = "drain"

    def __init__(self, message: str, checkpoint_path: Optional[str] = None,
                 iteration: int = -1, signum: int = 0,
                 detail: str = "") -> None:
        super().__init__(message, checkpoint_path=checkpoint_path,
                         iteration=iteration, signum=signum)
        self.detail = str(detail)


class BoundaryLatch:
    """A reason-carrying flag the boost loop honors at the next chunk
    boundary — the one mechanism behind both preemption (SIGTERM sets it
    from a signal frame) and flexctl's drain (the capacity watcher sets it
    from the boundary itself).

    ``request`` is async-signal-safe by construction (attribute stores and
    ``Event.set`` only; no I/O, no locks, no device calls) so the signal
    subclass can route through it. First request wins, with one exception:
    a later *preempt* upgrades a pending *drain* — the scheduler's kill
    grace window is real and the drain's coordinated save may not fit in
    it, so the exit must carry the preempt contract (75, no barrier).
    """

    def __init__(self) -> None:
        self._event = threading.Event()
        self.signum = 0
        self.reason = "preempt"
        self.detail = ""
        #: set for dead-rank drains: the coordinated save barrier cannot
        #: complete (a participant is gone), so the boundary skips it and
        #: exits on the last periodic checkpoint
        self.no_barrier = False

    def request(self, reason: str = "drain", detail: str = "",
                signum: int = 0, no_barrier: bool = False) -> bool:
        """Latch; returns True when this call took effect (first request
        wins; a preempt may upgrade a pending drain, see class doc)."""
        if self._event.is_set() and not (
                reason == "preempt" and self.reason != "preempt"):
            return False
        self.reason = reason if reason in REASONS else "drain"
        self.detail = detail
        self.signum = int(signum)
        self.no_barrier = bool(no_barrier)
        self._event.set()
        return True

    def requested(self) -> bool:
        return self._event.is_set()


class PreemptionWatcher(BoundaryLatch):
    """Latches a SIGTERM until the boost loop reaches a safe boundary.

    The handler itself does nothing but record the signal (async-signal
    safety: no I/O, no locks, no device calls from the signal frame — the
    same rule serve's drain handler follows). ``install`` only succeeds on
    the main thread (CPython restricts ``signal.signal`` to it); elsewhere
    — e.g. a train() driven from a worker thread — it degrades to a warned
    no-op and training proceeds un-armed. The previous handler is restored
    on ``uninstall`` so nesting (a train inside a serve/loop process that
    has its own SIGTERM contract) never leaks a stale handler.
    """

    def __init__(self, signals=(signal.SIGTERM,)) -> None:
        super().__init__()
        self.signals = tuple(signals)
        self._previous = {}
        self.installed = False

    def _handler(self, signum, frame) -> None:
        self.request("preempt", signum=int(signum))

    def install(self) -> bool:
        if threading.current_thread() is not threading.main_thread():
            from ..utils import log

            log.warn_once(
                "preempt-not-main-thread",
                "preempt: train() is not on the main thread; SIGTERM "
                "handling stays un-armed (signal handlers are main-thread "
                "only)",
            )
            return False
        for s in self.signals:
            self._previous[s] = signal.signal(s, self._handler)
        self.installed = True
        return True

    def uninstall(self) -> None:
        if not self.installed:
            return
        for s, prev in self._previous.items():
            try:
                signal.signal(s, prev)
            except (ValueError, OSError):  # interpreter teardown
                pass
        self._previous.clear()
        self.installed = False

    def __enter__(self) -> "PreemptionWatcher":
        self.install()
        return self

    def __exit__(self, *exc) -> None:
        self.uninstall()
