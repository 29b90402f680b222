"""Planner: how far column pruning narrows the window's scans: the
columns its table readers asked for as a share of the columns their
tables have (`tidb_tpu_reader_columns_total{kind="scanned"|"table"}`,
one increment per table-reader execution in a statement: the CopPlan's
columns, and the table's public ones). 100 = every reader scans whole
rows. Nothing on a program without the counter."""

from benchlib import spans

COUNTER = 'tidb_tpu_reader_columns_total{kind="%s"}'


def read(ctx):
    scanned, table = (spans.counter_delta(ctx, COUNTER % kind)
                      for kind in ("scanned", "table"))
    if not table or scanned is None:
        return None
    return 100.0 * scanned / table
