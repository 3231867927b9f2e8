"""jit-trace watchdog: count XLA compilations per entry point, police them.

``jax.jit`` re-traces (and re-compiles — seconds of XLA work) whenever an
argument's shape/dtype or a static value changes. On the serving path one
stray unpadded batch, or on the training path one drifting trace-time
constant, silently turns a millisecond dispatch into a multi-second compile.
The serve bucket cache asserted this privately (serve/cache.py counts
first-seen buckets); this module generalizes the discipline to every hot
entry point.

Mechanics: each watched jit function calls :func:`note_trace(name)` at the
TOP of its traced body. Under jit the python body runs only when XLA traces,
so the count of ``note_trace`` calls IS the real compile count — no reliance
on jax-internal cache introspection. Instrumented entry points:

  * ``ops.grow_tree``              — the tree grower (ops/grow.py)
  * ``gbdt.train_chunk``           — the fused K-iteration scan (models/gbdt.py)
  * ``ops.packed_predict_leaves``  — packed serving traversal (ops/predict.py)
  * ``ops.packed_predict_values``  — fused scores (ops/predict.py)
  * ``ops.packed_bin_rows``        — fused raw->rank binning (ops/predict.py)

After warmup, call :func:`arm` to snapshot the counts. Any later trace of an
armed name is a RETRACE: it always warns once per name (utils/log.warn_once)
and, with ``LIGHTGBM_TPU_RETRACE=fail``, raises ``LightGBMError`` — turning a
silent performance cliff into a loud failure. ``LIGHTGBM_TPU_RETRACE=warn``
is the explicit spelling of the default. Counts feed the metrics registry as
``jit_traces_total`` / ``jit_retraces_after_warmup`` (obs/__init__.py wires
the gauges), so /metrics and run reports carry them per run.
"""
from __future__ import annotations

import os
import threading
from typing import Dict, Iterable, Optional

from ..utils import log
from ..utils.log import LightGBMError
from . import registry as registry_mod
from . import sanitize as sanitize_mod

ENV_RETRACE = "LIGHTGBM_TPU_RETRACE"


def _mode() -> str:
    """Read per event, not at import: tests and long-lived servers flip it."""
    return os.environ.get(ENV_RETRACE, "").lower()


class RetraceWatchdog:
    """Per-name compile counts + an armed warm baseline."""

    def __init__(self) -> None:
        self._counts: Dict[str, int] = {}
        self._warm: Dict[str, int] = {}
        self._armed = False
        self._lock = sanitize_mod.make_lock("obs.retrace")

    def note_trace(self, name: str) -> None:
        """Called from inside a traced body — once per real XLA trace."""
        with self._lock:
            count = self._counts[name] = self._counts.get(name, 0) + 1
            retrace = self._armed and name in self._warm
        # labeled per-name compile count, published next to the xla_cost_*
        # gauges (obs/costs.py) so ONE /metrics scrape answers "what
        # compiled, how big, how hot" — the aggregate jit_traces_total pull
        # gauge (obs/__init__.py) stays for dashboards that sum anyway
        try:
            registry_mod.REGISTRY.gauge("jit_traces").set(count, name=name)
        except TypeError as e:
            # the ONE error this call can actually raise: a metric-kind
            # collision in MetricsRegistry._get_or_create ("jit_traces"
            # already registered as a counter/histogram). Gauge.set itself
            # is float()+dict-store and cannot fail on an int count. Metrics
            # must never break a trace, so log and continue — but anything
            # ELSE propagates rather than being silently swallowed (JX008's
            # own standard, applied to obs code)
            log.debug("retrace: jit_traces gauge update failed: %r" % e)
        if retrace:
            msg = (
                "jit retrace after warmup: %r compiled again (%d traces "
                "total) — a shape/dtype/static-arg drifted on the hot path; "
                "set %s=fail to hard-fail here" % (name, count, ENV_RETRACE)
            )
            if _mode() == "fail":
                raise LightGBMError(msg)
            log.warn_once("retrace:%s" % name, msg)

    def arm(self, names: Optional[Iterable[str]] = None) -> None:
        """Snapshot current counts as the warm baseline. With ``names``,
        only those entry points are policed (unknown names are armed at 0
        so their very first compile counts as a retrace)."""
        with self._lock:
            if names is None:
                self._warm = dict(self._counts)
            else:
                for n in names:
                    self._warm[n] = self._counts.get(n, 0)
            self._armed = True

    def disarm(self) -> None:
        with self._lock:
            self._armed = False
            self._warm = {}

    def reset(self) -> None:
        with self._lock:
            self._counts = {}
            self._warm = {}
            self._armed = False

    @property
    def armed(self) -> bool:
        return self._armed

    def counts(self) -> Dict[str, int]:
        with self._lock:
            return dict(self._counts)

    def retraces_after_warmup(self) -> Dict[str, int]:
        """name -> traces since arm(), for armed names only (empty unarmed)."""
        with self._lock:
            if not self._armed:
                return {}
            return {
                n: self._counts.get(n, 0) - base
                for n, base in self._warm.items()
                if self._counts.get(n, 0) > base
            }

    def total_retraces(self) -> int:
        return sum(self.retraces_after_warmup().values())


#: process-wide watchdog; ops/grow.py, ops/predict.py and models/gbdt.py
#: note into it, serve warmup arms it
WATCHDOG = RetraceWatchdog()


def note_trace(name: str) -> None:
    WATCHDOG.note_trace(name)


def arm(names: Optional[Iterable[str]] = None) -> None:
    WATCHDOG.arm(names)


def disarm() -> None:
    WATCHDOG.disarm()


def reset() -> None:
    WATCHDOG.reset()


def counts() -> Dict[str, int]:
    return WATCHDOG.counts()


def retraces_after_warmup() -> Dict[str, int]:
    return WATCHDOG.retraces_after_warmup()
