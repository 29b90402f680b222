"""Python's garbage collector holding the server: the window's delta of
`tidb_tpu_gc_pause_seconds_total`, every generation summed, per
statement completed. A pause stops every thread of the process, so this
is wall time, not thread time. None on a program without the counter
(its gc hook)."""

from benchlib import rates, spans

FAMILY = "tidb_tpu_gc_pause_seconds_total{"


def read(ctx):
    if not any(k.startswith(FAMILY) for k in ctx.after["metrics"]):
        return None
    return spans.per_stmt(ctx, rates.delta_prefix(ctx, FAMILY), 1000.0)
