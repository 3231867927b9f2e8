"""Seconds in ``lgb.Dataset`` construction before the window (program span
``dataset.construct``, basic.py): the float copy, the sample, the bin finding
and the binning of every column. One that a reference's or a lazy
construction nests inside another counts once."""
from benchmarks import spans


def read(ctx):
    return spans.setup_seconds(ctx, "dataset.construct", roots_only=True)
