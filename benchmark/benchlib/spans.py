"""Arithmetic the span readers share. The program folds every ended
statement's span tree into two counter families on GET /status
(`tidb_tpu_span_self_seconds_total{span=...}`: a span's duration less
what its same-thread children cover, so thread-seconds that count no
interval twice; `tidb_tpu_span_count_total{span=...}`), and counts
host->device bytes and the wire write beside them. A reader diffs them
over the window's two snapshots. A program without the counter (the
parent of the PR that brought them) gives None, and the line leaves the
metric out."""

from __future__ import annotations

from benchlib import rates

SELF = 'tidb_tpu_span_self_seconds_total{span="%s"}'


def counter_delta(ctx, name: str) -> float | None:
    """Window delta of one /status counter; None where it never fired."""
    if name not in ctx.after["metrics"]:
        return None
    return rates.delta(ctx, "metrics", name)


def self_seconds(ctx, *spans: str) -> float | None:
    """Self seconds the window's statements spent in `spans`, summed;
    None where none of them has ended since the process began."""
    got = [counter_delta(ctx, SELF % s) for s in spans]
    if all(v is None for v in got):
        return None
    return sum(v for v in got if v is not None)


def us_per_row(ctx, *spans: str) -> float | None:
    """Self time of `spans` over the base-table rows the window's
    statements read (scan_us_per_row's divisor)."""
    rows = sum(rates.op_rows(ctx, o) for o in ctx.window.ops
               if o.ok and o.loop == "closed")
    secs = self_seconds(ctx, *spans)
    if not rows or secs is None:
        return None
    return 1e6 * secs / rows


def per_stmt(ctx, value: float | None, scale: float = 1.0) -> float | None:
    """`value` per closed-loop statement completed in the window."""
    n = rates.completed(ctx, "closed")
    if not n or value is None:
        return None
    return scale * value / n


def ms_per_stmt(ctx, *spans: str) -> float | None:
    return per_stmt(ctx, self_seconds(ctx, *spans), 1000.0)


def kernel_ms_per_stmt(ctx, family: str) -> float | None:
    """Device trace: seconds of the device operations whose XLA module is
    `jit_<family>` (the program names each jitted program by its kernel
    family), per analytic statement the traced part holds. None where
    the trace shows no such module."""
    n = rates.traced_statements(ctx, "analytic")
    if not ctx.trace or not n:
        return None
    prefix = f"jit_{family}:"
    secs = [s for op, s in ctx.trace["device_ops"] if op.startswith(prefix)]
    if not secs:
        return None
    return 1000.0 * sum(secs) / n
