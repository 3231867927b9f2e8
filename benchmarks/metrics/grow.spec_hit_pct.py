"""Splits applied over candidate slots whose partition and histogram were
computed (program counters ``splits`` / ``slots_computed`` of
``grow.counters``, ops/grow.py), over the window's trees: 100 where every
computed slot became a split, less where speculation was thrown away."""
from benchmarks import spans


def read(ctx):
    counters = [c for c in spans.window_counters(ctx) if c.get("slots_computed")]
    if not counters:
        return None
    return (100.0 * sum(c["splits"] for c in counters)
            / sum(c["slots_computed"] for c in counters))
