"""Coprocessor scan + decode, third step: self time of `copr.exec` (the
pushed filter / projection / partial aggregate over a decoded chunk)
over the base-table rows read."""

from benchlib import spans


def read(ctx):
    return spans.us_per_row(ctx, "copr.exec")
