"""Finalize + encode, the wire: result-set encoding + socket write
(`tidb_tpu_wire_write_seconds_total`, outside the statement's root span)
per statement completed."""

from benchlib import spans


def read(ctx):
    return spans.per_stmt(
        ctx, spans.counter_delta(ctx, "tidb_tpu_wire_write_seconds_total"),
        1000.0)
