"""Passes of the grower's ``while_loop`` body per tree (program counter
``steps`` of ``grow.counters``, ops/grow.py), mean over the window's trees:
254 splits a tree cost 254 passes one by one, fewer where a pass applies a
batch."""
from benchmarks import spans


def read(ctx):
    return spans.mean(c["steps"] for c in spans.window_counters(ctx)
                      if "steps" in c)
