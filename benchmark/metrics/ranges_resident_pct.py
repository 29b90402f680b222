"""The share of the window's coprocessor region ranges served from the
columnar caches: `tidb_tpu_cop_ranges_total{served="resident"}` over
every `served` (`streamed`: framed from the raw scan, with `why`
`cold`, `over_frame` or `uncacheable`; `scanned`: the materialized
handler's whole-range read). None on a program without the counter,
or where the window served no range."""

from benchlib import rates

FAMILY = "tidb_tpu_cop_ranges_total{"


def read(ctx):
    before, after = ctx.before["metrics"], ctx.after["metrics"]
    if not any(k.startswith(FAMILY) for k in after):
        return None
    total = rates.delta_prefix(ctx, FAMILY)
    if not total:
        return None
    resident = sum(v - before.get(k, 0) for k, v in after.items()
                   if k.startswith(FAMILY) and 'served="resident"' in k)
    return 100.0 * resident / total
