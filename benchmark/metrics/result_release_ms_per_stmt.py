"""A region task's device agg dropping its result: self time of span
`copr.release` (the last references to the result pytree's device
arrays dying, inside `copr.exec`) per statement completed,
thread-seconds over the statement's region tasks."""

from benchlib import spans


def read(ctx):
    return spans.ms_per_stmt(ctx, "copr.release")
