"""Host time no span below explains: self time of the `statement` root
and of `execute` (the executor tree's own drive) per statement completed."""

from benchlib import spans


def read(ctx):
    return spans.ms_per_stmt(ctx, "statement", "execute")
