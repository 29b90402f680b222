"""TPC-H Q1 (clause 2.4.1) over the generator's arrays, exact."""

from __future__ import annotations

import numpy as np

from generators import tpch_dbgen as g
from statements.fmt import avg, days, dec


def truth(d, key=None) -> list[tuple]:
    live = d.l_shipdate <= days(1998, 12, 1) - 90
    px = d.l_extendedprice.astype(np.int64)
    disc_px = px * (100 - d.l_discount)
    charge = disc_px * (100 + d.l_tax)
    rows = []
    for rf, flag in enumerate(g.FLAGS):
        for ls, status in enumerate(g.STATUSES):
            m = live & (d.l_returnflag == rf) & (d.l_linestatus == ls)
            n = int(m.sum())
            if not n:
                continue
            qty = int(d.l_quantity[m].sum()) * 100
            base = int(px[m].sum())
            rows.append((
                flag, status, dec(qty, 2), dec(base, 2),
                dec(int(disc_px[m].sum()), 4),
                dec(int(charge[m].sum()), 6),
                avg(qty, n), avg(base, n),
                avg(int(d.l_discount[m].sum()), n), str(n)))
    return rows


def control(d, key=None, dtype=np.float64) -> list[tuple]:
    """The same answer with floating accumulators of `dtype` in place of
    exact integers: the approximate arithmetic a later PR might be
    tempted by. Must not pass for the truth."""
    live = d.l_shipdate <= days(1998, 12, 1) - 90
    px = d.l_extendedprice.astype(dtype)
    disc_px = px * (100 - d.l_discount).astype(dtype)
    charge = disc_px * (100 + d.l_tax).astype(dtype)
    qty100 = d.l_quantity.astype(dtype) * dtype(100)
    disc = d.l_discount.astype(dtype)
    rows = []
    for rf, flag in enumerate(g.FLAGS):
        for ls, status in enumerate(g.STATUSES):
            m = live & (d.l_returnflag == rf) & (d.l_linestatus == ls)
            n = int(m.sum())
            if not n:
                continue
            qty = int(qty100[m].sum(dtype=dtype))
            base = int(px[m].sum(dtype=dtype))
            rows.append((
                flag, status, dec(qty, 2), dec(base, 2),
                dec(int(disc_px[m].sum(dtype=dtype)), 4),
                dec(int(charge[m].sum(dtype=dtype)), 6),
                avg(qty, n), avg(base, n),
                avg(int(disc[m].sum(dtype=dtype)), n), str(n)))
    return rows
