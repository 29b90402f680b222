"""Arithmetic the metric readers share: rates over all the work and all
the time of the window, nearest-rank percentiles, counter deltas."""

from __future__ import annotations

import math

from benchlib import needed


def percentile(values, q: float) -> float | None:
    """Nearest-rank percentile of all `values`; None when there is none."""
    if not values:
        return None
    s = sorted(values)
    return s[min(len(s) - 1, max(0, math.ceil(q * len(s)) - 1))]


def op_rows(ctx, op) -> int:
    return needed.statement_rows(ctx.statements[op.statement],
                                 ctx.counts[op.database])


def closed_loop_rows_per_s(ctx) -> float | None:
    """Base-table rows read by every closed-loop statement completed,
    over the window's whole wall (start to last completion)."""
    done = [o for o in ctx.window.ops if o.loop == "closed" and o.ok]
    if not done or ctx.window.wall_s <= 0:
        return None
    return sum(op_rows(ctx, o) for o in done) / ctx.window.wall_s


def completed(ctx, loop: str | None = None) -> int:
    return sum(1 for o in ctx.window.ops
               if o.ok and (loop is None or o.loop == loop))


def delta(ctx, *path) -> float:
    """`after - before` of one counter, by its path in a snapshot; a
    counter that never fired is absent and reads 0."""
    def get(snap):
        v = snap
        for p in path:
            v = v.get(p, 0) if isinstance(v, dict) else 0
        return v or 0
    return get(ctx.after) - get(ctx.before)


def delta_prefix(ctx, prefix: str, snap_pair=None) -> float:
    """Sum of the deltas of every /status metric whose name starts with
    `prefix` (all label values of one family)."""
    before, after = snap_pair or (ctx.before, ctx.after)
    b, a = before["metrics"], after["metrics"]
    return sum(v - b.get(k, 0) for k, v in a.items()
               if k.startswith(prefix))


def traced_statements(ctx, kind: str = "analytic") -> float:
    """How many statements of `kind` the traced part holds, counting one
    that its edge cuts by the share inside."""
    tr = ctx.trace
    if not tr:
        return 0.0
    lo, hi = tr["window_ns"]
    n = 0.0
    for name, s, e in tr["spans"]:
        stmt = ctx.statements.get(name[len("inside_"):])
        if stmt and stmt["kind"] == kind and e > s:
            n += max(0.0, min(e, hi) - max(s, lo)) / (e - s)
    return n


def traced_needed_bytes(ctx) -> float:
    """Bytes the traced statements need (benchlib.needed), a cut one by
    its share."""
    tr = ctx.trace
    if not tr:
        return 0.0
    lo, hi = tr["window_ns"]
    per_stmt = {}
    total = 0.0
    for name, s, e in tr["spans"]:
        stmt = name[len("inside_"):]
        if stmt not in ctx.statements or e <= s:
            continue
        if stmt not in per_stmt:
            per_stmt[stmt] = ctx.needed_bytes(stmt)
        total += per_stmt[stmt] * max(0.0, min(e, hi) - max(s, lo)) / (e - s)
    return total


def device_idle_pct(ctx) -> float | None:
    """Device trace: 1 - busy / traced window on the fullest device."""
    if not ctx.trace or ctx.trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - ctx.trace["busy_s_fullest"] / ctx.trace["window_s"])


def chunk_cache_hit_pct(ctx) -> float | None:
    """Chunk-cache hits over lookups in the window; nothing when the
    window made no lookup."""
    hits = delta(ctx, "chunk_cache", "hits")
    lookups = hits + delta(ctx, "chunk_cache", "misses")
    return 100.0 * hits / lookups if lookups else None


def device_busy_ms_per_stmt(ctx) -> float | None:
    """Device trace: union of the device-op intervals, averaged over the
    chips, per analytic statement the traced part holds."""
    n = traced_statements(ctx, "analytic")
    if not ctx.trace or not n:
        return None
    return 1000.0 * ctx.trace["busy_s"] / n


def hbm_roofline_pct(ctx) -> float | None:
    """Bytes the traced statements need (referenced columns x declared
    widths x rows, benchlib/needed.py) per chip, over the chip's peak HBM
    bandwidth, over the device's busy time. Bound: bytes. Nothing when
    the trace holds no device time."""
    if not ctx.trace or not ctx.peaks or ctx.trace["busy_s"] <= 0:
        return None
    need = traced_needed_bytes(ctx)
    if not need:
        return None
    least_s = need / ctx.trace["devices"] / ctx.peaks["bytes_per_s"]
    return 100.0 * least_s / ctx.trace["busy_s"]
