"""Rows the histogram's input gather read (program counter
``hist_rows_gathered`` of ``grow.counters``, ops/grow.py: the switch branch's
static rows in the flat form, the lattice's round-up the kernel skips
included) over the rows the same trees needed (the program's own
``hist_rows_needed``), across the window's trees. Nothing where the program
does not count them."""
from benchmarks import spans


def read(ctx):
    counters = [c for c in spans.window_counters(ctx)
                if "hist_rows_gathered" in c and c.get("hist_rows_needed")]
    if not counters:
        return None
    return (sum(c["hist_rows_gathered"] for c in counters)
            / sum(c["hist_rows_needed"] for c in counters))
