"""TPC-H Q5 (clause 2.4.5) over the generator's arrays, exact."""

from __future__ import annotations

import numpy as np

from generators import tpch_dbgen as g
from statements.fmt import days, dec


def _answer(d, dtype) -> list[tuple]:
    lo, hi = days(1994, 1, 1), days(1995, 1, 1)
    odate = d.o_orderdate[d.l_order]
    snat = d.s_nationkey[d.l_suppkey - 1]
    cnat = d.c_nationkey[d.o_custkey[d.l_order] - 1]
    m = (odate >= lo) & (odate < hi) & (cnat == snat)
    disc_px = (d.l_extendedprice.astype(dtype)
               * (100 - d.l_discount).astype(dtype))
    rows = []
    for nk, (name, region) in enumerate(g.NATIONS):
        if g.REGIONS[region] != "ASIA":
            continue
        sel = m & (snat == nk)
        if sel.any():
            rows.append((int(disc_px[sel].sum(dtype=dtype)), name))
    rows.sort(reverse=True)
    return [(name, dec(rev, 4)) for rev, name in rows]


def truth(d, key=None) -> list[tuple]:
    return _answer(d, np.int64)


def control(d, key=None, dtype=np.float64) -> list[tuple]:
    """Revenue accumulated in floating `dtype`: must not pass for the
    truth (see q1.control)."""
    return _answer(d, dtype)
