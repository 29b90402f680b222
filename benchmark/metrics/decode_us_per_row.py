"""Coprocessor scan + decode, second step: self time of `copr.decode`
(raw KV rows -> decoded chunk) over the base-table rows read."""

from benchlib import spans


def read(ctx):
    return spans.us_per_row(ctx, "copr.decode")
