"""Device trace: 1 - busy / traced window on the fullest device, in
the cache-resident analytic cells (a name per end-to-end metric it moves)."""

from benchlib import rates


def read(ctx):
    return rates.device_idle_pct(ctx)
