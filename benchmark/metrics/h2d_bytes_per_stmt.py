"""Bytes handed to host->device transfers (`tidb_tpu_h2d_bytes_total`:
the one-chip transfer seam and the join matcher's operands) per
statement completed."""

from benchlib import spans


def read(ctx):
    return spans.per_stmt(
        ctx, spans.counter_delta(ctx, "tidb_tpu_h2d_bytes_total"))
