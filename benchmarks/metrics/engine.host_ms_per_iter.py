"""Host time the boosting loop takes per iteration (host clock): from each
return of the benchmark's after-iteration callback to the callback's next
entry, that is dispatching one iteration and the loop's boundary work, the
callback's own blocking left out. Mean over the window's iterations."""


def read(ctx):
    gaps = ctx["host_gaps_s"]
    if not gaps:
        return None
    return 1e3 * sum(gaps) / len(gaps)
