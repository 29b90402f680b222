"""The wire's text forms of DECIMAL, AVG and DATE values, computed from
exact integers (copied from chip_smoke.py)."""

from __future__ import annotations

import datetime

from generators.tpch_dbgen import EPOCH


def days(y: int, m: int, d: int) -> int:
    return (datetime.date(y, m, d) - EPOCH).days


def date(day_offset) -> str:
    return (EPOCH + datetime.timedelta(days=int(day_offset))).isoformat()


def dec(v: int, scale: int) -> str:
    """Scaled integer -> the wire's DECIMAL text."""
    sign, v = ("-", -v) if v < 0 else ("", v)
    q, r = divmod(v, 10 ** scale)
    return f"{sign}{q}.{r:0{scale}d}" if scale else f"{sign}{q}"


def avg(total: int, n: int) -> str:
    """AVG over DECIMAL(15,2): scale 6, rounded half up."""
    return dec((total * 10 ** 4 * 2 + n) // (2 * n), 6)
