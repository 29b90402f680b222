"""Root executors: share of the groups the root's aggregates emitted in
the window that the columnar merge built and not the dict merge
(`tidb_tpu_agg_final_groups_total{path="columnar"|"dict"}`, counted
where span `exec.agg` is). Nothing on a program without the labeled
counter, or in a window that emitted no group."""

from benchlib import spans

COUNTER = 'tidb_tpu_agg_final_groups_total{path="%s"}'


def read(ctx):
    columnar, dict_ = (spans.counter_delta(ctx, COUNTER % path)
                       for path in ("columnar", "dict"))
    total = (columnar or 0) + (dict_ or 0)
    if not total:
        return None
    return 100.0 * (columnar or 0) / total
