"""Seconds jax spent before the window tracing the programs' Python and
lowering them (program spans ``jit.trace`` + ``jit.lower``, obs/trace.py's
``jax.monitoring`` listener): paid on every run, warm cache or cold."""
from benchmarks import spans


def read(ctx):
    return spans.setup_seconds(ctx, "jit.trace", "jit.lower")
