"""Coprocessor scan + decode: TableReader operator seconds over the
base-table rows the window's statements read (window deltas)."""

from benchlib import rates


def read(ctx):
    rows = sum(rates.op_rows(ctx, o) for o in ctx.window.ops
               if o.ok and o.loop == "closed")
    if not rows:
        return None
    secs = rates.delta(
        ctx, "metrics", 'tidb_tpu_op_duration_seconds_sum{op="TableReader"}')
    return 1e6 * secs / rows
