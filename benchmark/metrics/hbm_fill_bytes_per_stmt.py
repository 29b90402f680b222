"""Bytes the HBM cache took in during the window, per statement: growth
of `resident_bytes()`, and where blocks were refilled in place (misses
with no growth) the misses times the mean resident block. 0 when warm."""

from benchlib import rates


def read(ctx):
    n = rates.completed(ctx, "closed")
    if not n:
        return None
    grown = max(rates.delta(ctx, "hbm_resident_bytes"), 0)
    misses = rates.delta(ctx, "metrics", "tidb_tpu_hbm_cache_misses_total")
    blocks = max(int(ctx.config["regions_per_big_table"]), 1)
    refilled = misses * ctx.after["hbm_resident_bytes"] / blocks
    return max(grown, refilled) / n
