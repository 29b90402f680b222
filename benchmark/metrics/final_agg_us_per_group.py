"""Root executors: self time of `exec.agg` (FinalAggExec's and
HashAggExec's host merge of partial groups) over the groups those
aggregates emitted in the window
(`tidb_tpu_agg_final_groups_total`). Nothing on a program without the
counter, or in a window that emitted no group."""

from benchlib import spans

COUNTER = "tidb_tpu_agg_final_groups_total"


def read(ctx):
    groups = spans.counter_delta(ctx, COUNTER)
    secs = spans.self_seconds(ctx, "exec.agg")
    if not groups or secs is None:
        return None
    return 1e6 * secs / groups
