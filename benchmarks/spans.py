"""Selections and sums over the program's own trace, for the metrics that read
its spans and counters.

The program keeps the coarse spans of its training path and its grower's work
counters in memory (``lightgbm_tpu.obs.trace.events()``: Chrome-trace dicts
with ``name``, ``ts`` and ``dur`` in microseconds, an ``id``, the ``parent``
span's id, and ``args``, which holds the ``iteration`` or ``tree`` the work
belongs to).
A program without that read-out gives ``None`` here, and every reader then
reports nothing.

Window iterations are those with ``warmup <= iteration < warmup +
ctx["iterations"]``; set-up is what ends before the ``train.iteration`` span
of ``iteration == warmup`` starts.
"""
from __future__ import annotations

from typing import Dict, Iterable, List, Optional


def events() -> Optional[List[dict]]:
    """The program's trace, oldest first; None where it keeps none."""
    try:
        from lightgbm_tpu.obs import trace
    except ImportError:
        return None
    read = getattr(trace, "events", None)
    return read() if read is not None else None


def window_iterations(ctx: dict) -> range:
    warmup = ctx["traffic"]["warmup_iterations"]
    return range(warmup, warmup + ctx["iterations"])


def named(evs: Iterable[dict], *names: str) -> List[dict]:
    return [e for e in evs if e["name"] in names]


def of_iteration(evs: Iterable[dict], name: str, iteration: int) -> List[dict]:
    return [e for e in evs if e["name"] == name
            and e["args"].get("iteration") == iteration]


def children(evs: Iterable[dict], span: dict) -> List[dict]:
    return [e for e in evs if e.get("parent") == span["id"] and "dur" in e]


def self_us(evs: Iterable[dict], span: dict) -> float:
    """The span's duration less what its children cover of it (children
    that overlap count once)."""
    lo, hi = span["ts"], span["ts"] + span["dur"]
    covered, reach = 0.0, lo
    for a, b in sorted((max(c["ts"], lo), min(c["ts"] + c["dur"], hi))
                       for c in children(evs, span)):
        if b > reach:
            covered += b - max(a, reach)
            reach = b
    return span["dur"] - covered


def setup_end_us(evs: Iterable[dict], ctx: dict) -> Optional[float]:
    """Where the window's first ``train.iteration`` starts."""
    first = of_iteration(evs, "train.iteration", window_iterations(ctx).start)
    return first[0]["ts"] if first else None


def setup_seconds(ctx: dict, *names: str, roots_only: bool = False
                  ) -> Optional[float]:
    """Summed duration, in seconds, of the spans of these names that end
    before the window; with ``roots_only``, not of those that lie inside
    another of the same names. None where there is none."""
    evs = events()
    end = evs and setup_end_us(evs, ctx)
    if not end:
        return None
    spans = [e for e in named(evs, *names) if e["ts"] + e["dur"] <= end]
    if roots_only:
        ids = {e["id"] for e in spans}
        spans = [e for e in spans if e.get("parent") not in ids]
    return sum(e["dur"] for e in spans) / 1e6 if spans else None


def mean(values: Iterable[float]) -> Optional[float]:
    values = list(values)
    return sum(values) / len(values) if values else None


def per_window_iteration_ms(ctx: dict, of_one) -> Optional[float]:
    """Mean over the window's iterations of ``of_one(events, iteration)``, a
    number of microseconds or None, in milliseconds; None where no iteration
    of the window gives one."""
    evs = events()
    if not evs:
        return None
    each = [of_one(evs, k) for k in window_iterations(ctx)]
    got = mean(v for v in each if v is not None)
    return None if got is None else got / 1e3


def window_counters(ctx: dict) -> List[Dict[str, float]]:
    """The ``grow.counters`` of the window's trees, one dict a tree."""
    evs = events() or []
    window = window_iterations(ctx)
    return [e["args"] for e in named(evs, "grow.counters")
            if e["args"].get("iteration") in window]
