"""Kernels: device time of the `jit_hashagg` programs per analytic
statement traced (device trace, by XLA module name)."""

from benchlib import spans


def read(ctx):
    return spans.kernel_ms_per_stmt(ctx, "hashagg")
