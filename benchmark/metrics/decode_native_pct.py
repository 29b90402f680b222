"""Coprocessor scan + decode: share of the rows the window's scans
decoded whose chunk `native/codec.cc` built and not the Python decoder
(`tidb_tpu_decode_rows_total{path="native"|"python"}`, incremented by a
batch's rows where span `copr.decode` is). Nothing on a program without
the counter, or in a window that decoded no row."""

from benchlib import spans

COUNTER = 'tidb_tpu_decode_rows_total{path="%s"}'


def read(ctx):
    native, python = (spans.counter_delta(ctx, COUNTER % path)
                      for path in ("native", "python"))
    total = (native or 0) + (python or 0)
    if not total:
        return None
    return 100.0 * (native or 0) / total
