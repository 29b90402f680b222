"""Device trace: busy time per analytic statement traced (one chip)."""

from benchlib import rates


def read(ctx):
    return rates.device_busy_ms_per_stmt(ctx)
