"""Share of the rows the grower's partitions passed that went by their
split's default direction (program counters ``part_rows_missing`` /
``part_rows_needed`` of ``grow.counters``, ops/grow.py), over the window's
trees: the rows whose bin is the split feature's missing bin. Nothing where
the program does not count them."""
from benchmarks import spans


def read(ctx):
    counters = [c for c in spans.window_counters(ctx)
                if "part_rows_missing" in c and c.get("part_rows_needed")]
    if not counters:
        return None
    return (100.0 * sum(c["part_rows_missing"] for c in counters)
            / sum(c["part_rows_needed"] for c in counters))
