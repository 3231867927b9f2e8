"""Exponential backoff: the one retry-delay schedule for the whole package.

Every consumer of retries — the serve dispatch retry (serve/server.py) and
the continuous-training controller's observe/retry loops
(lightgbm_tpu/loop/) — draws its sleeps
from ``delays`` so "how long do we wait after a transient failure" is
decided in exactly one place; the retry LOOPS themselves stay with their
callers (serve needs its asymmetric CPU-fallback arm, the loop controller
journals between waits). Stdlib only.

Two opt-in extensions (defaults preserve the historical schedule exactly):

  * ``jitter``/``seed`` — each delay is scaled by a factor drawn uniformly
    from ``[1 - jitter, 1 + jitter]``. With ``seed`` given the stream is
    ``random.Random(seed)`` and therefore REPRODUCIBLE — the controller's
    kill-anywhere tests replay identical schedules across restarts; without
    a seed the jitter is process-random (fleet de-synchronization).
  * ``max_elapsed_s`` — a TOTAL sleep budget: the final delay is truncated
    to what remains of the budget and the schedule then stops, so a retry
    loop's worst-case wall time is bounded regardless of ``attempts``.
"""
from __future__ import annotations

import random
from typing import Iterator, Optional


def delays(
    attempts: int,
    base_s: float = 1.0,
    factor: float = 2.0,
    max_s: float = 60.0,
    jitter: float = 0.0,
    seed: Optional[int] = None,
    max_elapsed_s: Optional[float] = None,
) -> Iterator[float]:
    """The sleep (seconds) before each RETRY of an ``attempts``-attempt loop:
    up to ``attempts - 1`` values, ``base_s * factor**i`` capped at ``max_s``,
    optionally jittered (deterministically when ``seed`` is given) and
    bounded by the ``max_elapsed_s`` total budget. With the default
    ``jitter=0`` the schedule is deterministic by design — the
    fault-injection tests (resil/faults.py) must not be timing-dependent."""
    rng = random.Random(seed) if jitter > 0 else None
    elapsed = 0.0
    for i in range(max(attempts - 1, 0)):
        d = min(base_s * (factor ** i), max_s)
        if rng is not None:
            # scale, then re-cap: a jittered delay must still honor max_s
            d = min(d * (1.0 + jitter * (2.0 * rng.random() - 1.0)), max_s)
        if max_elapsed_s is not None and elapsed + d >= max_elapsed_s:
            d = max_elapsed_s - elapsed
            if d > 0:
                yield d
            return
        elapsed += d
        yield d


def decorrelated(
    base_s: float = 1.0,
    max_s: float = 60.0,
    seed: Optional[int] = None,
) -> Iterator[float]:
    """Decorrelated-jitter schedule (the AWS architecture-blog variant):
    ``sleep_n = min(max_s, uniform(base_s, 3 * sleep_{n-1}))``.

    Unlike ``delays``, this generator is UNBOUNDED — it is the restart
    pacer for supervisors that run indefinitely (flexctl's relaunch loop),
    which impose their own hard caps on *consecutive rapid* restarts
    rather than on total attempts. Decorrelation matters there more than
    in a finite retry loop: a whole fleet of controllers restarted by the
    same capacity event must not re-converge onto synchronized retry
    waves, and plain jittered exponential backoff re-correlates at the
    ``max_s`` ceiling. Every value is in ``[base_s, max_s]``; ``seed``
    makes the stream reproducible for the flap-guard tests."""
    if base_s <= 0:
        raise ValueError("decorrelated: base_s must be > 0 (got %r)"
                         % (base_s,))
    rng = random.Random(seed)
    prev = base_s
    while True:
        prev = min(max_s, rng.uniform(base_s, 3.0 * prev))
        yield prev
