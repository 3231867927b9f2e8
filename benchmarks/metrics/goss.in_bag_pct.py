"""Share of the table's rows in the bag, over the window's iterations (program
counter ``sample.counters``, which the booster emits each iteration with
``rows``, ``in_bag``, ``top_k``, ``other_k`` and ``multiplier``;
models/gbdt.py ``_note_sample``): 100 x in-bag rows over rows. 30 where every
iteration of the window is one of GOSS's sampled ones at top_rate 0.2 and
other_rate 0.1; 100 would say the window sat in the unsampled lead-in.
Nothing where the program emits no such counter."""
from benchmarks import spans


def read(ctx):
    window = spans.window_iterations(ctx)
    drawn = [e["args"] for e in spans.named(spans.events() or [], "sample.counters")
             if e["args"].get("iteration") in window and e["args"].get("rows")]
    if not drawn:
        return None
    return 100.0 * sum(c["in_bag"] for c in drawn) / sum(c["rows"] for c in drawn)
