"""Chunk-cache hits over lookups in the window (one-chip analytic cells)."""

from benchlib import rates


def read(ctx):
    return rates.chunk_cache_hit_pct(ctx)
