"""Root executors: what a statement's subqueries cost under their own
name: self time of `exec.apply` (the predicate over the outer chunks)
plus `exec.apply.inner` (the inner plan's runs, less what the inner
executors' own spans name) per statement completed."""

from benchlib import spans


def read(ctx):
    return spans.ms_per_stmt(ctx, "exec.apply", "exec.apply.inner")
