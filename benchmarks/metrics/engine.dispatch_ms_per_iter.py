"""Host time to issue one iteration (program span ``train.iteration``,
engine.py, less its ``train.wait_prev_tree`` child): gradients, bagging, the
grower's and the score update's dispatch. The phase spans stay in: they are
the dispatch. Mean over the window's iterations."""
from benchmarks import spans


def read(ctx):
    def dispatch_us(evs, k):
        its = spans.of_iteration(evs, "train.iteration", k)
        if not its:
            return None
        return sum(it["dur"] - sum(c["dur"] for c in spans.children(evs, it)
                                   if c["name"] == "train.wait_prev_tree")
                   for it in its)

    return spans.per_window_iteration_ms(ctx, dispatch_us)
