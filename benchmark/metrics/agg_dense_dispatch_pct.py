"""Kernels: share of the window's group-by dispatches whose block took
the dense masked reductions and not the scatters
(`tidb_tpu_agg_dispatch_total{path="dense"|"scatter"}`, one increment
per dispatch read back). Nothing on a program without the counter, or
in a window without a group-by dispatch."""

from benchlib import spans

COUNTER = 'tidb_tpu_agg_dispatch_total{path="%s"}'


def read(ctx):
    dense, scatter = (spans.counter_delta(ctx, COUNTER % path)
                      for path in ("dense", "scatter"))
    total = (dense or 0) + (scatter or 0)
    if not total:
        return None
    return 100.0 * (dense or 0) / total
