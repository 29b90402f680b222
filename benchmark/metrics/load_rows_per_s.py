"""Bulk load: rows loaded over the load's wall (DDL, import, split)."""


def read(ctx):
    if not ctx.setup.get("load"):
        return None
    return ctx.setup["rows_loaded"] / ctx.setup["load"]
