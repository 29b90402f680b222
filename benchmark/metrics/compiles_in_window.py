"""Kernel compiles and persistent-cache misses inside the window: a
shape that set-up failed to warm. Expected 0."""

from benchlib import rates


def read(ctx):
    return float(
        rates.delta_prefix(ctx, "tidb_tpu_kernel_compile_seconds_count")
        + rates.delta(ctx, "compile_cache", "misses"))
