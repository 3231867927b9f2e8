"""Process start to the end of the warm-up iterations (host clock): data from
the seed, binning, transfer, tracing and compiling or loading the programs."""


def read(ctx):
    return ctx["setup_s"]
