"""Device time per traced iteration in every program but ``grow_tree``
(device trace): gradients, the score update, the leaf-value passes."""


def read(ctx):
    trace = ctx["trace"]
    if not trace or not trace["traced_iterations"]:
        return None
    if not any("grow_tree" in name for name in trace["modules"]):
        return None
    other = [m["seconds"] for name, m in trace["modules"].items()
             if "grow_tree" not in name]
    return 1e3 * sum(other) / trace["traced_iterations"]
