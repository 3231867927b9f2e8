"""Share of the table's columns that the grower's histograms were built over,
across the window's trees (program counter ``hist_columns`` of
``grow.counters``: the static width of the matrix the grower was handed,
ops/grow.py): 100 x histogram columns over the configuration's columns. 80
where the grower is handed the columns of a feature_fraction draw of 0.8, 100
where the draw is a mask over every column. Nothing where the program does not
count its histograms' columns."""
from benchmarks import spans


def read(ctx):
    counters = [c for c in spans.window_counters(ctx) if "hist_columns" in c]
    if not counters:
        return None
    return (100.0 * sum(c["hist_columns"] for c in counters)
            / (len(counters) * ctx["config"]["features"]))
