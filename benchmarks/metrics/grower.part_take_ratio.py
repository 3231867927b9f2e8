"""Rows the partition's take of its split columns asked for (program counter
``part_rows_taken`` of ``grow.counters``, ops/grow.py: W x N a call, whatever
the rows the call splits) over the rows its partitions needed
(``part_rows_needed``), across the window's trees. Nothing where the program
does not count them."""
from benchmarks import spans


def read(ctx):
    counters = [c for c in spans.window_counters(ctx)
                if "part_rows_taken" in c and c.get("part_rows_needed")]
    if not counters:
        return None
    return (sum(c["part_rows_taken"] for c in counters)
            / sum(c["part_rows_needed"] for c in counters))
