"""Seconds before the window in the backend's compile step (program span
``jit.compile``): a build where the persistent cache has no entry, a load
from it where it has, as ``compiles_in_window`` counts both."""
from benchmarks import spans


def read(ctx):
    return spans.setup_seconds(ctx, "jit.compile")
