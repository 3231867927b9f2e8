"""Host time per iteration in the program's own wait on the device (program
span ``train.wait_prev_tree``, models/gbdt.py): the blocking read of the
previous tree's ``num_leaves`` at the start of ``train_one_iter``. Mean over
the window's iterations."""
from benchmarks import spans


def read(ctx):
    def wait_us(evs, k):
        waits = spans.of_iteration(evs, "train.wait_prev_tree", k)
        return sum(w["dur"] for w in waits) if waits else None

    return spans.per_window_iteration_ms(ctx, wait_us)
