"""Share of the table's rows in the grower's root segment, over the window's
trees (program counter ``root_rows`` of ``grow.counters``, counted in the
grower's carry, ops/grow.py): 100 x root rows over the configuration's rows.
30 where the grower is rooted at GOSS's sample, 100 where the sample is a mask
over every row. Nothing where the program does not count the root's rows."""
from benchmarks import spans


def read(ctx):
    counters = [c for c in spans.window_counters(ctx) if "root_rows" in c]
    if not counters:
        return None
    return (100.0 * sum(c["root_rows"] for c in counters)
            / (len(counters) * ctx["config"]["rows"]))
