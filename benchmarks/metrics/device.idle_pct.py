"""The share of the traced stretch in which no operation ran on the device
(device trace): 1 - union of the operations' intervals over the stretch."""


def read(ctx):
    trace = ctx["trace"]
    if not trace or not trace["window_s"]:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
