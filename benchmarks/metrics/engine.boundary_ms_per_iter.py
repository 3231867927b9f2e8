"""Host time per iteration in the loop's boundary work (self time of program
span ``train.boundary``, engine.py: from ``update``'s return to the next
``train.iteration``; eval, registry, flight and podwatch notes, checkpoint,
flex and preempt reads, the next pass's before-callbacks), the
``train.callbacks`` child taken out: a callback may block, the benchmark's
does, and that is not the loop's time. Mean over the window's iterations."""
from benchmarks import spans


def read(ctx):
    def boundary_us(evs, k):
        bounds = spans.of_iteration(evs, "train.boundary", k)
        return sum(spans.self_us(evs, b) for b in bounds) if bounds else None

    return spans.per_window_iteration_ms(ctx, boundary_us)
