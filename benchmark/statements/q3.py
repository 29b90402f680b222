"""TPC-H Q3 (clause 2.4.3) over the generator's arrays, exact. Order
keys are sparse, so line items carry their order's position (`l_order`)
and customers are found by key - 1."""

from __future__ import annotations

import numpy as np

from generators import tpch_dbgen as g
from statements.fmt import date, days, dec


def _answer(d, dtype) -> list[tuple]:
    cut = days(1995, 3, 15)
    bldg = d.c_mktsegment == g.SEGMENTS.index("BUILDING")
    order_ok = (d.o_orderdate < cut) & bldg[d.o_custkey - 1]
    m = (d.l_shipdate > cut) & order_ok[d.l_order]
    rev = np.zeros(d.counts["orders"], dtype=dtype)
    np.add.at(rev, d.l_order[m],
              d.l_extendedprice[m].astype(dtype)
              * (100 - d.l_discount[m]).astype(dtype))
    hit = np.zeros(d.counts["orders"], dtype=bool)
    hit[d.l_order[m]] = True
    top = sorted(np.flatnonzero(hit),
                 key=lambda k: (-rev[k], d.o_orderdate[k]))[:10]
    return [(str(d.o_orderkey[k]), dec(int(rev[k]), 4),
             date(d.o_orderdate[k]), str(d.o_shippriority[k])) for k in top]


def truth(d, key=None) -> list[tuple]:
    return _answer(d, np.int64)


def control(d, key=None, dtype=np.float64) -> list[tuple]:
    """Revenue accumulated in floating `dtype`: must not pass for the
    truth (see q1.control)."""
    return _answer(d, dtype)
