"""Coprocessor stream frames per statement of the window. 0 means the
raw scan stopped streaming: the cell no longer measures what it is for."""

from benchlib import rates


def read(ctx):
    n = rates.completed(ctx, "closed")
    if not n:
        return None
    return rates.delta(ctx, "metrics", "tidb_tpu_cop_stream_frames_total") / n
