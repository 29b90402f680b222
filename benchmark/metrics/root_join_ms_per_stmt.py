"""Root executors: self time of `exec.join` (HashJoinExec's own host
work on the session thread: build concat and key encode, superchunk
assembly, gather and emit of the matched pairs; the waits for its
children's chunks and its `dispatch` / `finalize` / `join.partition`
children are not in it) per statement completed."""

from benchlib import spans


def read(ctx):
    return spans.ms_per_stmt(ctx, "exec.join")
