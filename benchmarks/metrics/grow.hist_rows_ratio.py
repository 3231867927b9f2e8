"""Rows the grower's histogram calls passed over (program counter
``hist_rows_streamed`` of ``grow.counters``, ops/grow.py: bucket padding and
speculative lanes included) over the rows the same trees needed
(``work.tree_work``'s ``hist_rows``, the yardstick ``step.mfu_pct`` uses: the
root's rows and each split's smaller child's). The program's own count of
needed rows is printed beside it, so that the two can be compared."""
import sys

from benchmarks import spans, work


def read(ctx):
    counters = [c for c in spans.window_counters(ctx) if "hist_rows_streamed" in c]
    trees, config = ctx["window_trees"], ctx["config"]
    if not counters or len(counters) != len(trees):
        return None
    needed = sum(work.tree_work(t, config["rows"], config["features"],
                                int(config["params"]["max_bin"]) + 1)["hist_rows"]
                 for t in trees)
    if not needed:
        return None
    streamed = sum(c["hist_rows_streamed"] for c in counters)
    print("bench: histogram rows over %d trees: streamed %.0f, needed %.0f by the "
          "trees and %.0f by the program's count"
          % (len(trees), streamed, needed,
             sum(c.get("hist_rows_needed", 0.0) for c in counters)),
          file=sys.stderr, flush=True)
    return streamed / needed
