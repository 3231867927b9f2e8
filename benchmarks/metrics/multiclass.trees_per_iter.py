"""Trees grown an iteration, over the window's iterations (program counter
``class.counters``, which the booster emits once an iteration where it grows
more than one tree an iteration, with ``classes``, ``trees`` and
``grower_calls``; models/gbdt.py ``train_one_iter``): 5.0 where every window
iteration grows each of five classes' trees. Nothing where the program emits
no such counter: one tree an iteration, or an older program."""
from benchmarks import spans


def read(ctx):
    window = spans.window_iterations(ctx)
    loops = [e["args"] for e in spans.named(spans.events() or [], "class.counters")
             if e["args"].get("iteration") in window]
    if not loops:
        return None
    return sum(c["trees"] for c in loops) / len(loops)
