"""Kernels, host side: self time of `dispatch` (pad / transfer / async
enqueue) per statement completed."""

from benchlib import spans


def read(ctx):
    return spans.ms_per_stmt(ctx, "dispatch")
