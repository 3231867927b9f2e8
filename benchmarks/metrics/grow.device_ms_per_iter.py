"""Device time of the ``grow_tree`` program per traced iteration (device
trace): the summed durations of the executions of the XLA module whose name
holds ``grow_tree``, over the iterations traced."""


def read(ctx):
    trace = ctx["trace"]
    if not trace or not trace["traced_iterations"]:
        return None
    grow = [m["seconds"] for name, m in trace["modules"].items() if "grow_tree" in name]
    if not grow:
        return None
    return 1e3 * sum(grow) / trace["traced_iterations"]
