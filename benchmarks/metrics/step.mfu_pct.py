"""The whole step's share of the chip's peak: the least time the chip could
take for the work the window's trees needed (``work.py``: bytes over the
chip's bandwidth or operations over its peak rate, whichever is larger; it is
the bytes here) over the window's time. Counted from the trees, so it still
bounds a gain after a later change replaces a kernel."""
from benchmarks import work


def read(ctx):
    trees, peak = ctx["window_trees"], ctx["peak"]
    if not trees or peak is None or not ctx["window_s"]:
        return None
    config = ctx["config"]
    least = work.least_seconds(trees, config["rows"], config["features"],
                               int(config["params"]["max_bin"]) + 1, peak)
    return 100.0 * least["seconds"] / ctx["window_s"]
