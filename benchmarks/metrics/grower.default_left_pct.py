"""Share of the splits applied that send the missing to the left on a
feature that has a missing type (program counters ``splits_default_left`` /
``splits`` of ``grow.counters``, ops/grow.py), over the window's trees: how
often the second scan direction won. Nothing where the program does not
count them."""
from benchmarks import spans


def read(ctx):
    counters = [c for c in spans.window_counters(ctx)
                if "splits_default_left" in c and c.get("splits")]
    if not counters:
        return None
    return (100.0 * sum(c["splits_default_left"] for c in counters)
            / sum(c["splits"] for c in counters))
