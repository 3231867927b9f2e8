"""Share of the table's columns in a tree's draw, over the window's trees
(program counter ``feature.counters``, which the booster emits for each tree
it draws columns for, with ``columns`` and ``drawn``; models/gbdt.py
``_draw_columns``): 100 x drawn over columns. 80 where every tree of the
window is drawn at feature_fraction 0.8. Nothing where the program emits no
such counter: no tree draws, or the program is an older one."""
from benchmarks import spans


def read(ctx):
    window = spans.window_iterations(ctx)
    drawn = [e["args"] for e in spans.named(spans.events() or [], "feature.counters")
             if e["args"].get("iteration") in window and e["args"].get("columns")]
    if not drawn:
        return None
    return 100.0 * sum(c["drawn"] for c in drawn) / sum(c["columns"] for c in drawn)
