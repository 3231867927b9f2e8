"""Lanes the partition's flat pass ran over (program counter
``part_rows_streamed`` of ``grow.counters``, ops/grow.py: the switch branch
its padded slots fell in) over the rows its partitions needed
(``part_rows_needed``), across the window's trees. Nothing where the program
does not count them."""
from benchmarks import spans


def read(ctx):
    counters = [c for c in spans.window_counters(ctx)
                if "part_rows_streamed" in c and c.get("part_rows_needed")]
    if not counters:
        return None
    return (sum(c["part_rows_streamed"] for c in counters)
            / sum(c["part_rows_needed"] for c in counters))
