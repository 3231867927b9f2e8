"""The grower's dispatches an iteration, over the window's iterations (program
counter ``grower_calls`` of ``class.counters``, models/gbdt.py
``train_one_iter``): one a class's tree today, so 5.0 at five classes; a grower
that grew an iteration's trees in one program would read 1. Nothing where the
program emits no such counter: one tree an iteration, or an older program."""
from benchmarks import spans


def read(ctx):
    window = spans.window_iterations(ctx)
    loops = [e["args"] for e in spans.named(spans.events() or [], "class.counters")
             if e["args"].get("iteration") in window]
    if not loops:
        return None
    return sum(c["grower_calls"] for c in loops) / len(loops)
