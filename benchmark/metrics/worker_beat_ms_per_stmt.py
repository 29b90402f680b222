"""The supervised background workers' beats (metrics history, schema,
stats, heartbeat) inside the window: the delta of
`tidb_tpu_worker_beat_seconds_total`, every worker summed, per statement
completed. They run beside the statements under the same GIL and belong
to none. None on a program without the counter."""

from benchlib import rates, spans

FAMILY = "tidb_tpu_worker_beat_seconds_total{"


def read(ctx):
    if not any(k.startswith(FAMILY) for k in ctx.after["metrics"]):
        return None
    return spans.per_stmt(ctx, rates.delta_prefix(ctx, FAMILY), 1000.0)
