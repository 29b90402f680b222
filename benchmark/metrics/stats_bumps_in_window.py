"""Bumps of the statistics handle's version inside the window: an
ANALYZE saved (the stats worker's tick) or a histogram feedback. Each
one re-plans the next execution of every cached statement. Expected 0:
set-up runs the worker's first pass and warms again after it."""


def read(ctx):
    if "stats_version" not in ctx.after:
        return None
    return float(ctx.after["stats_version"] - ctx.before["stats_version"])
