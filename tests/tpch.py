"""Tiny deterministic TPC-H data generator + loader.

Shapes follow the TPC-H spec's tables/columns (the reference exposes them
through plain SQL; BASELINE.md configs 2-4 name Q1/Q3/Q5 as the perf
targets). Row counts are scaled way down for hermetic tests; value
distributions keep the queries' selectivity non-trivial.
"""

from __future__ import annotations

import datetime

import numpy as np

REGIONS = ["AFRICA", "AMERICA", "ASIA", "EUROPE", "MIDDLE EAST"]
NATIONS = [  # (name, region_idx)
    ("ALGERIA", 0), ("ARGENTINA", 1), ("BRAZIL", 1), ("CANADA", 1),
    ("EGYPT", 4), ("ETHIOPIA", 0), ("FRANCE", 3), ("GERMANY", 3),
    ("INDIA", 2), ("INDONESIA", 2), ("IRAN", 4), ("IRAQ", 4),
    ("JAPAN", 2), ("JORDAN", 4), ("KENYA", 0), ("MOROCCO", 0),
    ("MOZAMBIQUE", 0), ("PERU", 1), ("CHINA", 2), ("ROMANIA", 3),
    ("SAUDI ARABIA", 4), ("VIETNAM", 2), ("RUSSIA", 3),
    ("UNITED KINGDOM", 3), ("UNITED STATES", 1),
]
SEGMENTS = ["AUTOMOBILE", "BUILDING", "FURNITURE", "MACHINERY", "HOUSEHOLD"]
PRIORITIES = ["1-URGENT", "2-HIGH", "3-MEDIUM", "4-NOT SPECIFIED", "5-LOW"]
FLAGS = ["A", "N", "R"]
STATUSES = ["F", "O"]

_EPOCH = datetime.date(1992, 1, 1)


def _d(days: int) -> str:
    return (_EPOCH + datetime.timedelta(days=int(days))).isoformat()


class TpchData:
    """Numpy-array tables, deterministic for a given (scale, seed)."""

    def __init__(self, customers=120, orders=600, lineitems=2400,
                 suppliers=40, seed=42):
        rng = np.random.default_rng(seed)
        self.n_nation = len(NATIONS)
        # customer
        self.c_custkey = np.arange(customers)
        self.c_nationkey = rng.integers(0, self.n_nation, customers)
        self.c_mktsegment = rng.integers(0, len(SEGMENTS), customers)
        # supplier
        self.s_suppkey = np.arange(suppliers)
        self.s_nationkey = rng.integers(0, self.n_nation, suppliers)
        # orders (orderdate in days since epoch, 1992-01-01 .. 1998-08-02)
        self.o_orderkey = np.arange(orders)
        self.o_custkey = rng.integers(0, customers, orders)
        self.o_orderdate = rng.integers(0, 2405, orders)
        self.o_shippriority = np.zeros(orders, dtype=np.int64)
        self.o_orderpriority = rng.integers(0, len(PRIORITIES), orders)
        # lineitem
        self.l_orderkey = rng.integers(0, orders, lineitems)
        self.l_suppkey = rng.integers(0, suppliers, lineitems)
        self.l_quantity = rng.integers(1, 51, lineitems)
        self.l_extendedprice = rng.integers(90000, 10500000, lineitems)  # cents
        self.l_discount = rng.integers(0, 11, lineitems)   # percent
        self.l_tax = rng.integers(0, 9, lineitems)         # percent
        self.l_returnflag = rng.integers(0, 3, lineitems)
        self.l_linestatus = rng.integers(0, 2, lineitems)
        base = self.o_orderdate[self.l_orderkey]
        self.l_shipdate = base + rng.integers(1, 122, lineitems)
        self.l_commitdate = base + rng.integers(30, 92, lineitems)
        self.l_receiptdate = self.l_shipdate + rng.integers(1, 31, lineitems)


DDL = """
CREATE TABLE region (r_regionkey BIGINT PRIMARY KEY, r_name VARCHAR(25));
CREATE TABLE nation (n_nationkey BIGINT PRIMARY KEY, n_name VARCHAR(25),
                     n_regionkey BIGINT);
CREATE TABLE customer (c_custkey BIGINT PRIMARY KEY,
                       c_nationkey BIGINT, c_mktsegment VARCHAR(10));
CREATE TABLE supplier (s_suppkey BIGINT PRIMARY KEY, s_nationkey BIGINT);
CREATE TABLE orders (o_orderkey BIGINT PRIMARY KEY, o_custkey BIGINT,
                     o_orderdate DATE, o_shippriority BIGINT,
                     o_orderpriority VARCHAR(15));
CREATE TABLE lineitem (l_id BIGINT PRIMARY KEY, l_orderkey BIGINT,
                       l_suppkey BIGINT,
                       l_quantity DECIMAL(15,2),
                       l_extendedprice DECIMAL(15,2),
                       l_discount DECIMAL(15,2), l_tax DECIMAL(15,2),
                       l_returnflag CHAR(1), l_linestatus CHAR(1),
                       l_shipdate DATE, l_commitdate DATE,
                       l_receiptdate DATE);
"""


def load(session, data: TpchData, batch=500):
    for stmt in DDL.strip().split(";"):
        if stmt.strip():
            session.execute(stmt)

    def ins(table, rows_iter):
        buf = []
        for r in rows_iter:
            buf.append("(" + ",".join(r) + ")")
            if len(buf) >= batch:
                session.execute(f"INSERT INTO {table} VALUES {','.join(buf)}")
                buf = []
        if buf:
            session.execute(f"INSERT INTO {table} VALUES {','.join(buf)}")

    ins("region", ((str(i), f"'{n}'") for i, n in enumerate(REGIONS)))
    ins("nation", ((str(i), f"'{n}'", str(r))
                   for i, (n, r) in enumerate(NATIONS)))
    ins("customer", ((str(k), str(data.c_nationkey[k]),
                      f"'{SEGMENTS[data.c_mktsegment[k]]}'")
                     for k in data.c_custkey))
    ins("supplier", ((str(k), str(data.s_nationkey[k]))
                     for k in data.s_suppkey))
    ins("orders", ((str(k), str(data.o_custkey[k]),
                    f"'{_d(data.o_orderdate[k])}'",
                    str(data.o_shippriority[k]),
                    f"'{PRIORITIES[data.o_orderpriority[k]]}'")
                   for k in data.o_orderkey))
    n = len(data.l_orderkey)
    ins("lineitem", ((str(i), str(data.l_orderkey[i]),
                      str(data.l_suppkey[i]),
                      f"{data.l_quantity[i]}.00",
                      f"{data.l_extendedprice[i] // 100}."
                      f"{data.l_extendedprice[i] % 100:02d}",
                      f"0.{data.l_discount[i]:02d}",
                      f"0.{data.l_tax[i]:02d}",
                      f"'{FLAGS[data.l_returnflag[i]]}'",
                      f"'{STATUSES[data.l_linestatus[i]]}'",
                      f"'{_d(data.l_shipdate[i])}'",
                      f"'{_d(data.l_commitdate[i])}'",
                      f"'{_d(data.l_receiptdate[i])}'")
                     for i in range(n)))


Q1 = """
SELECT l_returnflag, l_linestatus,
       SUM(l_quantity) AS sum_qty,
       SUM(l_extendedprice) AS sum_base_price,
       SUM(l_extendedprice * (1 - l_discount)) AS sum_disc_price,
       SUM(l_extendedprice * (1 - l_discount) * (1 + l_tax)) AS sum_charge,
       AVG(l_quantity) AS avg_qty,
       AVG(l_extendedprice) AS avg_price,
       AVG(l_discount) AS avg_disc,
       COUNT(*) AS count_order
FROM lineitem
WHERE l_shipdate <= DATE '1998-12-01' - INTERVAL '90' DAY
GROUP BY l_returnflag, l_linestatus
ORDER BY l_returnflag, l_linestatus
"""

Q3 = """
SELECT l_orderkey,
       SUM(l_extendedprice * (1 - l_discount)) AS revenue,
       o_orderdate, o_shippriority
FROM customer, orders, lineitem
WHERE c_mktsegment = 'BUILDING'
  AND c_custkey = o_custkey
  AND l_orderkey = o_orderkey
  AND o_orderdate < DATE '1995-03-15'
  AND l_shipdate > DATE '1995-03-15'
GROUP BY l_orderkey, o_orderdate, o_shippriority
ORDER BY revenue DESC, o_orderdate
LIMIT 10
"""

Q5 = """
SELECT n_name,
       SUM(l_extendedprice * (1 - l_discount)) AS revenue
FROM customer, orders, lineitem, supplier, nation, region
WHERE c_custkey = o_custkey
  AND l_orderkey = o_orderkey
  AND l_suppkey = s_suppkey
  AND c_nationkey = s_nationkey
  AND s_nationkey = n_nationkey
  AND n_regionkey = r_regionkey
  AND r_name = 'ASIA'
  AND o_orderdate >= DATE '1994-01-01'
  AND o_orderdate < DATE '1994-01-01' + INTERVAL '1' YEAR
GROUP BY n_name
ORDER BY revenue DESC
"""


# -- independent ground truth (pure python/numpy over the arrays) -----------

def truth_q1(d: TpchData):
    cutoff = (datetime.date(1998, 12, 1) - datetime.timedelta(days=90)
              - _EPOCH).days
    out = {}
    for i in range(len(d.l_orderkey)):
        if d.l_shipdate[i] > cutoff:
            continue
        key = (FLAGS[d.l_returnflag[i]], STATUSES[d.l_linestatus[i]])
        e = out.setdefault(key, [0, 0, 0.0, 0.0, 0, 0])
        px = d.l_extendedprice[i] / 100
        disc = d.l_discount[i] / 100
        tax = d.l_tax[i] / 100
        e[0] += int(d.l_quantity[i])
        e[1] += d.l_extendedprice[i]
        e[2] += px * (1 - disc)
        e[3] += px * (1 - disc) * (1 + tax)
        e[4] += d.l_discount[i]
        e[5] += 1
    rows = []
    for key in sorted(out):
        q, b, dp, ch, disc, n = out[key]
        rows.append((key[0], key[1], float(q), b / 100, dp, ch,
                     q / n, b / 100 / n, disc / 100 / n, n))
    return rows


def truth_q3(d: TpchData):
    cut = (datetime.date(1995, 3, 15) - _EPOCH).days
    seg = SEGMENTS.index("BUILDING")
    bldg = set(np.flatnonzero(d.c_mktsegment == seg))
    orders_ok = {}
    for k in d.o_orderkey:
        if d.o_custkey[k] in bldg and d.o_orderdate[k] < cut:
            orders_ok[k] = d.o_orderdate[k]
    rev = {}
    for i in range(len(d.l_orderkey)):
        ok = d.l_orderkey[i]
        if ok in orders_ok and d.l_shipdate[i] > cut:
            px = d.l_extendedprice[i] / 100 * (1 - d.l_discount[i] / 100)
            rev[ok] = rev.get(ok, 0.0) + px
    rows = sorted(((k, v, orders_ok[k]) for k, v in rev.items()),
                  key=lambda t: (-t[1], t[2]))[:10]
    return [(int(k), v, _d(od), 0) for k, v, od in rows]


def truth_q5(d: TpchData):
    lo = (datetime.date(1994, 1, 1) - _EPOCH).days
    hi = (datetime.date(1995, 1, 1) - _EPOCH).days
    asia = {i for i, (_n, r) in enumerate(NATIONS)
            if REGIONS[r] == "ASIA"}
    rev = {}
    for i in range(len(d.l_orderkey)):
        ok = d.l_orderkey[i]
        if not (lo <= d.o_orderdate[ok] < hi):
            continue
        sk = d.l_suppkey[i]
        snat = d.s_nationkey[sk]
        if snat not in asia:
            continue
        ck = d.o_custkey[ok]
        if d.c_nationkey[ck] != snat:
            continue
        px = d.l_extendedprice[i] / 100 * (1 - d.l_discount[i] / 100)
        nname = NATIONS[snat][0]
        rev[nname] = rev.get(nname, 0.0) + px
    return sorted(rev.items(), key=lambda t: -t[1])


Q4 = """
SELECT o_orderpriority, COUNT(*) AS order_count
FROM orders
WHERE o_orderdate >= DATE '1993-07-01'
  AND o_orderdate < DATE '1993-10-01'
  AND EXISTS (
    SELECT 1 FROM lineitem
    WHERE l_orderkey = o_orderkey AND l_commitdate < l_receiptdate)
GROUP BY o_orderpriority
ORDER BY o_orderpriority
"""


Q6 = """
SELECT SUM(l_extendedprice * l_discount) AS revenue
FROM lineitem
WHERE l_shipdate >= DATE '1994-01-01'
  AND l_shipdate < DATE '1995-01-01'
  AND l_discount BETWEEN 0.05 AND 0.07
  AND l_quantity < 24
"""


def truth_q4(d: TpchData):
    lo = (datetime.date(1993, 7, 1) - _EPOCH).days
    hi = (datetime.date(1993, 10, 1) - _EPOCH).days
    late = set()
    for i in range(len(d.l_orderkey)):
        if d.l_commitdate[i] < d.l_receiptdate[i]:
            late.add(int(d.l_orderkey[i]))
    out = {}
    for k in d.o_orderkey:
        if lo <= d.o_orderdate[k] < hi and int(k) in late:
            p = PRIORITIES[d.o_orderpriority[k]]
            out[p] = out.get(p, 0) + 1
    return sorted(out.items())


def truth_q6(d: TpchData):
    lo = (datetime.date(1994, 1, 1) - _EPOCH).days
    hi = (datetime.date(1995, 1, 1) - _EPOCH).days
    rev = 0.0
    for i in range(len(d.l_orderkey)):
        if not (lo <= d.l_shipdate[i] < hi):
            continue
        if not (5 <= d.l_discount[i] <= 7):
            continue
        if d.l_quantity[i] >= 24:
            continue
        rev += (d.l_extendedprice[i] / 100) * (d.l_discount[i] / 100)
    return rev


Q12 = """
SELECT l_linestatus, COUNT(*) AS n
FROM orders, lineitem
WHERE o_orderkey = l_orderkey
  AND l_receiptdate >= DATE '1994-01-01'
  AND l_receiptdate < DATE '1994-01-01' + INTERVAL '1' YEAR
  AND l_commitdate < l_receiptdate
  AND l_shipdate < l_commitdate
GROUP BY l_linestatus
ORDER BY l_linestatus
"""


def truth_q12(d: TpchData):
    lo = (datetime.date(1994, 1, 1) - _EPOCH).days
    hi = (datetime.date(1995, 1, 1) - _EPOCH).days
    out = {}
    for i in range(len(d.l_orderkey)):
        if not (lo <= d.l_receiptdate[i] < hi):
            continue
        if not (d.l_commitdate[i] < d.l_receiptdate[i]):
            continue
        if not (d.l_shipdate[i] < d.l_commitdate[i]):
            continue
        key = STATUSES[d.l_linestatus[i]]
        out[key] = out.get(key, 0) + 1
    return sorted((k, v) for k, v in out.items())


# -- Q18 (large volume customer): tables of its own -------------------------
# The tables above carry neither c_name nor o_totalprice, so Q18 gets a
# database of its own: the columns it reads, with more orders than the
# group-by's dense branch holds slots (ops/hashagg._DENSE_SLOTS).

class Q18Data:
    """Deterministic for (sizes, seed). An order's line count is Poisson
    around 4, so a few orders pass 300 units; o_totalprice ties in pairs
    (the second sort key, o_orderdate, decides them) and a pair that
    would tie on the date too is one cent apart."""

    def __init__(self, customers=150, orders=3000, lineitems=12000, seed=42):
        rng = np.random.default_rng(seed)
        self.c_custkey = np.arange(1, customers + 1)
        self.o_orderkey = np.arange(1, orders + 1) * 4      # sparse keys
        self.o_custkey = rng.integers(1, customers + 1, orders)
        self.o_orderdate = rng.integers(0, 2405, orders)
        rank = rng.permutation(orders)
        self.o_totalprice = 1_000_000 + (rank // 2) * 137   # cents
        by_rank = np.argsort(rank)
        for a, b in zip(by_rank[0::2], by_rank[1::2]):
            if self.o_orderdate[a] == self.o_orderdate[b]:
                self.o_totalprice[b] += 1
        self.l_order = rng.integers(0, orders, lineitems)   # position
        self.l_orderkey = self.o_orderkey[self.l_order]
        self.l_quantity = rng.integers(1, 51, lineitems)


Q18_DDL = """
CREATE TABLE customer (c_custkey BIGINT PRIMARY KEY, c_name VARCHAR(25));
CREATE TABLE orders (o_orderkey BIGINT PRIMARY KEY, o_custkey BIGINT,
                     o_totalprice DECIMAL(15,2), o_orderdate DATE);
CREATE TABLE lineitem (l_id BIGINT PRIMARY KEY, l_orderkey BIGINT,
                       l_quantity DECIMAL(15,2));
"""


def _cents(v) -> str:
    return f"{int(v) // 100}.{int(v) % 100:02d}"


def load_q18(session, data: Q18Data, batch=1000):
    for stmt in Q18_DDL.strip().split(";"):
        if stmt.strip():
            session.execute(stmt)

    def ins(table, rows):
        rows = ["(" + ",".join(r) + ")" for r in rows]
        for lo in range(0, len(rows), batch):
            session.execute(f"INSERT INTO {table} VALUES "
                            f"{','.join(rows[lo:lo + batch])}")

    ins("customer", [(str(k), f"'Customer#{k:09d}'")
                     for k in data.c_custkey])
    ins("orders", [(str(k), str(c), _cents(p), f"'{_d(d)}'")
                   for k, c, p, d in zip(data.o_orderkey, data.o_custkey,
                                         data.o_totalprice,
                                         data.o_orderdate)])
    ins("lineitem", [(str(i), str(k), f"{q}.00")
                     for i, (k, q) in enumerate(zip(data.l_orderkey,
                                                    data.l_quantity))])


# clause 2.4.18's text with LIMIT 100 for "the first 100 rows";
# {quantity} is the substitution parameter (validation value 300)
Q18 = """
SELECT c_name, c_custkey, o_orderkey, o_orderdate, o_totalprice,
       SUM(l_quantity)
FROM customer, orders, lineitem
WHERE o_orderkey IN (
        SELECT l_orderkey FROM lineitem
        GROUP BY l_orderkey HAVING SUM(l_quantity) > {quantity})
  AND c_custkey = o_custkey
  AND o_orderkey = l_orderkey
GROUP BY c_name, c_custkey, o_orderkey, o_orderdate, o_totalprice
ORDER BY o_totalprice DESC, o_orderdate
LIMIT 100
"""


def truth_q18(d: Q18Data, quantity: int):
    """-> (rows in order, how many orders qualified before the LIMIT);
    rows are (c_name, c_custkey, o_orderkey, o_orderdate, o_totalprice
    in cents, sum of l_quantity in whole units), python ints."""
    qty = {}
    for pos, q in zip(d.l_order, d.l_quantity):
        qty[int(pos)] = qty.get(int(pos), 0) + int(q)
    large = [pos for pos, q in qty.items() if q > quantity]
    large.sort(key=lambda pos: (-int(d.o_totalprice[pos]),
                                int(d.o_orderdate[pos])))
    rows = [(f"Customer#{int(d.o_custkey[pos]):09d}", int(d.o_custkey[pos]),
             int(d.o_orderkey[pos]), _d(d.o_orderdate[pos]),
             int(d.o_totalprice[pos]), qty[pos]) for pos in large[:100]]
    return rows, len(large)
