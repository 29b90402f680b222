"""Coprocessor scan + decode, first step: self time of `copr.kv_scan`
(each `storage.engine.scan` call, MVCC iteration) over the base-table
rows the window's statements read."""

from benchlib import spans


def read(ctx):
    return spans.us_per_row(ctx, "copr.kv_scan")
