"""The same arithmetic as analytic_rows_per_s, under a name of its own
for the cell whose raw scans are re-read and re-decoded on the host in
every execution: a host-bound rate gets a bound from its own spread."""

from benchlib import rates


def read(ctx):
    return rates.closed_loop_rows_per_s(ctx)
