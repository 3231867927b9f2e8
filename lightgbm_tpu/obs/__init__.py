"""lightgbm_tpu.obs: the unified observability layer (docs/Observability.md).

System tier (trace/retrace/memwatch/costs/registry) plus the model/data
tier — :mod:`~lightgbm_tpu.obs.flight` (training flight recorder),
:mod:`~lightgbm_tpu.obs.modelstats` (importance evolution, bin occupancy,
leaf shape) and :mod:`~lightgbm_tpu.obs.report` (the self-contained HTML run
report); the serve-side drift monitor lives in serve/drift.py. One spine:

 * :mod:`~lightgbm_tpu.obs.trace`    — the one in-program trace. On by
   default: the training path's coarse spans (``dataset.*``, ``train.init``,
   ``jit.*``, ``train.iteration`` with its phases and ``train.wait_prev_tree``,
   ``train.boundary``) and the grower's ``grow.counters`` in a bounded ring,
   each event with an id, its parent and its ``iteration``/``tree``;
   ``trace.events()`` reads it. ``LIGHTGBM_TPU_TRACE=<path>`` also writes
   Perfetto-viewable Chrome-trace JSON of every span, ``=0`` records nothing.
   Device-aligned through ``jax.profiler.TraceAnnotation``.
 * :mod:`~lightgbm_tpu.obs.retrace`  — jit-compile watchdog; counts real XLA
   traces per entry point, ``LIGHTGBM_TPU_RETRACE=fail`` hard-fails on
   retraces after warmup.
 * :mod:`~lightgbm_tpu.obs.memwatch` — device-memory snapshots at named
   points + shape-math attribution of the known large carries.
 * :mod:`~lightgbm_tpu.obs.costs`    — measured XLA cost analysis per core
   executable (flops / bytes via ``lower().compile().cost_analysis()``,
   env-gated ``LIGHTGBM_TPU_COSTS=1``) + the per-``device_kind`` peak
   table (``ops/hist_pallas.py`` sizes its blocks by its VMEM figures).
 * :mod:`~lightgbm_tpu.obs.registry` — the one metrics registry (counters /
   gauges / histograms / rates) behind the serve ``/metrics`` Prometheus
   endpoint, the training callback, and the run reports.
 * :mod:`~lightgbm_tpu.obs.sanitize` — the graftsan runtime sanitizer
   (``LIGHTGBM_TPU_SAN=transfer,nan,locks``): transfer guards at the jitted
   dispatch seams, NaN tripwires on the score carries, lock-order inversion
   detection (docs/StaticAnalysis.md §Runtime sanitizer).
 * :mod:`~lightgbm_tpu.obs.tune`     — the shape-aware histogram autotuner
   (``python -m lightgbm_tpu.obs.tune``): measured per-shape kernel
   routing tables, atomically persisted, frozen per training run
   (docs/HistogramRouting.md). Imported lazily (it pulls ops/ on use).
 * :mod:`~lightgbm_tpu.obs.dist`     — what joins the per-process pieces
   of a multi-process run: registry snapshots gathered and merged, the
   byte allgather the checkpoint barrier rides, per-shard row counts.
   jax-lazy; imported by its callers.
 * :mod:`~lightgbm_tpu.obs.podwatch` — the live fleet telemetry plane
   (``python -m lightgbm_tpu.obs.podwatch``): per-rank chunk-boundary
   time-series ring (``LIGHTGBM_TPU_TELEMETRY=<dir>``), the opt-in
   training-side scrape endpoint (``LIGHTGBM_TPU_TELEMETRY_PORT``:
   /metrics /health /timeline), and the cross-rank aggregator issuing
   straggler/stall/skew/dead verdicts from the shards + heartbeats
   (docs/Observability.md §Fleet telemetry). Not imported by this
   package's init; the aggregator half never imports jax.

Importing this package never touches a jax backend.
"""
from __future__ import annotations

from . import costs, flight, memwatch, modelstats, registry, retrace, trace  # noqa: F401
from .registry import REGISTRY, MetricsRegistry  # noqa: F401

# NOTE: obs.report is the run-report CLI (`python -m lightgbm_tpu.obs.report`)
# and is imported on use; `python -m lightgbm_tpu.obs.trace merge` folds
# per-process trace files into one timeline.

# cross-wiring: the default registry's watchdog/memory gauges pull live
# values at read time, so any exposition (serve /metrics, run_report) is
# current without a push site having to remember them
REGISTRY.gauge(
    "jit_traces_total"
).set_fn(lambda: float(sum(retrace.WATCHDOG.counts().values())))
REGISTRY.gauge(
    "jit_retraces_after_warmup"
).set_fn(lambda: float(retrace.WATCHDOG.total_retraces()))
REGISTRY.gauge("device_peak_bytes").set_fn(memwatch.peak_device_bytes)
# the measured-cost book rides in every run report (empty dict -> omitted)
REGISTRY.register_report_section("cost_analysis", costs.COSTS.report)

__all__ = [
    "REGISTRY",
    "MetricsRegistry",
    "costs",
    "flight",
    "memwatch",
    "modelstats",
    "registry",
    "retrace",
    "trace",
]
