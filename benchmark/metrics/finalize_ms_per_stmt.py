"""Finalize + encode: self time of `finalize` (the host blocked on the
device readback at the output boundary) per statement completed."""

from benchlib import spans


def read(ctx):
    return spans.ms_per_stmt(ctx, "finalize")
