"""TPC-H Q18 (clause 2.4.18) over the generator's arrays, exact. Line
items carry their order's position (`l_order`); every order has a
customer and at least one line, so the three-way join loses no order,
and `c_name` is the generator's `Customer#%09d` of the key."""

from __future__ import annotations

import numpy as np

from statements.fmt import date, dec

QUANTITY = 300


def _answer(d, dtype) -> list[tuple]:
    qty = np.zeros(d.counts["orders"], dtype=dtype)
    np.add.at(qty, d.l_order, d.l_quantity.astype(dtype))
    price = d.o_totalprice.astype(dtype)            # cents
    large = np.flatnonzero(qty > QUANTITY)
    top = sorted(large, key=lambda k: (-price[k], d.o_orderdate[k]))[:100]
    return [(f"Customer#{d.o_custkey[k]:09d}", str(d.o_custkey[k]),
             str(d.o_orderkey[k]), date(d.o_orderdate[k]),
             dec(int(price[k]), 2), dec(int(qty[k]) * 100, 2)) for k in top]


def truth(d, key=None) -> list[tuple]:
    return _answer(d, np.int64)


def control(d, key=None, dtype=np.float64) -> list[tuple]:
    """Every DECIMAL the answer prints carried in floating `dtype`: the
    quantity's accumulator and `o_totalprice`. A sum of at most seven
    quantities is exact even in float32, so the accumulator alone passes
    for the truth; float32 cannot hold the cents of an order over
    167,772.16 (2^24 cents), which every large order is. Must not pass
    for the truth in float32 (float64 holds both exactly and passes)."""
    return _answer(d, dtype)
