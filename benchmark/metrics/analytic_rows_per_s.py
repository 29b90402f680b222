"""Base-table rows read by every analytic statement completed in the
window, over the window's whole wall; cells whose working set is
cache-resident once warm."""

from benchlib import rates


def read(ctx):
    return rates.closed_loop_rows_per_s(ctx)
