"""The whole timed window, from the end of warm-up to the closing fetch, over
every iteration completed in it (host clock). Not a median of per-iteration
times: a stall inside the window has to show."""


def read(ctx):
    if not ctx["iterations"]:
        return None
    return ctx["window_s"] / ctx["iterations"]
