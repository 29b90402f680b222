"""The draining thread's wait for the next frame of a fanned-out stream:
self time of span `copr.wait` (one a frame or sentinel, around
`BoundedFrameQueue.drain`'s blocking get) per statement completed. The
session thread's under a table reader, the inner plan's under
`exec.apply.inner`: time that `unspanned_ms_per_stmt` (the self time of
`statement` + `execute`) and `subquery_ms_per_stmt` do not hold."""

from benchlib import spans


def read(ctx):
    return spans.ms_per_stmt(ctx, "copr.wait")
