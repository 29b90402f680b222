"""Seconds the program's kernels spent compiling during set-up
(`tidb_tpu_kernel_compile_seconds_sum`, every family)."""

from benchlib import rates


def read(ctx):
    return rates.delta_prefix(ctx, "tidb_tpu_kernel_compile_seconds_sum",
                              (ctx.at_start, ctx.after_setup))
