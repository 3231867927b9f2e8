"""Fault-tolerance layer: crash-safe checkpoints, fault injection, backoff.

The ROADMAP north-star is a production system; production systems get
preempted, SIGKILLed, and wedged. This package is the layer that lets the
rest of lightgbm_tpu *survive* the failures the obs layer reports:

 * ``resil.atomic``     — temp-file + fsync + rename publication for every
                          model/checkpoint artifact (the same pattern
                          native/__init__.py uses for its built .so), so a
                          crash mid-write can never truncate a published file.
 * ``resil.checkpoint`` — periodic training checkpoints capturing model text
                          + device score carries + host RNG position +
                          deferred-stop and early-stopping state;
                          ``engine.train(checkpoint_path=...,
                          resume_from=...)`` resumes BIT-identically
                          (docs/FaultTolerance.md).
 * ``resil.faults``     — deterministic, env-gated fault injection
                          (``LIGHTGBM_TPU_FAULTS=site:occurrence[:action]``)
                          with named sites in the boost loop, checkpoint
                          writer, serve dispatch and batcher worker, so every
                          recovery path is exercised by REAL induced failures
                          in tests rather than mocks.
 * ``resil.backoff``    — the one exponential-backoff helper shared by the
                          serve dispatch retry and the loop controller.
 * ``resil.preempt``    — preemption-aware training: SIGTERM → emergency
                          boundary checkpoint → ``TrainingPreempted`` →
                          documented exit code 75, which the loop
                          auto-resumes from (jax-free by design).
 * ``resil.coord``      — coordinated multi-process checkpointing: digest
                          barrier + rank-0-writes + per-rank heartbeats.
 * ``resil.watchdog``   — host-side deadline around sharded collective
                          dispatch (hang detection, warn-then-raise).

Import discipline: this ``__init__`` pulls in only the jax-free modules
(``backoff``, ``faults``) so host-side drivers can
use them without paying a jax import; ``checkpoint``/``coord`` are imported
lazily by their callers (engine.py), and ``watchdog`` rides models/gbdt.py.
"""
from __future__ import annotations

from . import backoff, faults  # noqa: F401  (jax-free; see docstring)
from .atomic import atomic_write_text  # noqa: F401
from .faults import InjectedFault, maybe_fire  # noqa: F401
