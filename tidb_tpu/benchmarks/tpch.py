"""Scaled TPC-H generator + offline loader for chip_smoke.py and the tests.

Reference: BASELINE.md configs 2-4 (TPC-H Q1/Q3/Q5 through the server) and
/root/reference/cmd/benchdb (the SQL workload driver role). Row counts
scale with `sf` following the TPC-H spec's cardinalities; value
distributions match tests/tpch.py so the tiny SQL-loaded corpus and the
bulk-loaded benchmark corpus exercise identical query selectivities.

Everything is generated as numpy columns and ingested through
table.bulkload (the offline-import path) — the SQL INSERT path is
exercised separately by the test suite.
"""

from __future__ import annotations

import datetime

import numpy as np

from tidb_tpu.table import Table, bulkload

REGIONS = ["AFRICA", "AMERICA", "ASIA", "EUROPE", "MIDDLE EAST"]
NATIONS = [  # (name, region_idx) — the 25 spec nations
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

_EPOCH_DATE = datetime.date(1992, 1, 1)
_DAY_US = 86_400_000_000


def _epoch_us() -> int:
    # match sqltypes.parse_datetime's epoch convention exactly
    from tidb_tpu.sqltypes import parse_datetime
    return parse_datetime("1992-01-01")

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


def _days_us(days: np.ndarray) -> np.ndarray:
    """TPC-H day offsets -> epoch-microsecond DATE datums."""
    return _epoch_us() + days.astype(np.int64) * _DAY_US


class ScaledTpch:
    """Numpy TPC-H tables at scale factor `sf` (sf=1 ~ 6M lineitem)."""

    def __init__(self, sf: float = 1.0, seed: int = 42):
        rng = np.random.default_rng(seed)
        self.sf = sf
        customers = max(int(150_000 * sf), 50)
        orders = max(int(1_500_000 * sf), 200)
        lineitems = max(int(6_001_215 * sf), 800)
        suppliers = max(int(10_000 * sf), 20)
        self.counts = {"region": len(REGIONS), "nation": len(NATIONS),
                       "customer": customers, "supplier": suppliers,
                       "orders": orders, "lineitem": lineitems}
        n_nation = len(NATIONS)
        self.c_custkey = np.arange(customers, dtype=np.int64)
        self.c_nationkey = rng.integers(0, n_nation, customers)
        self.c_mktsegment = rng.integers(0, len(SEGMENTS), customers)
        self.s_suppkey = np.arange(suppliers, dtype=np.int64)
        self.s_nationkey = rng.integers(0, n_nation, suppliers)
        self.o_orderkey = np.arange(orders, dtype=np.int64)
        self.o_custkey = rng.integers(0, customers, orders)
        self.o_orderdate = rng.integers(0, 2405, orders)  # days since epoch
        self.o_shippriority = np.zeros(orders, dtype=np.int64)
        self.o_orderpriority = rng.integers(0, len(PRIORITIES), orders)
        self.l_orderkey = rng.integers(0, orders, lineitems)
        self.l_suppkey = rng.integers(0, suppliers, lineitems)
        self.l_quantity = rng.integers(1, 51, lineitems)       # whole units
        self.l_extendedprice = rng.integers(90000, 10500000, lineitems)
        self.l_discount = rng.integers(0, 11, lineitems)       # percent
        self.l_tax = rng.integers(0, 9, lineitems)             # percent
        self.l_returnflag = rng.integers(0, 3, lineitems)
        self.l_linestatus = rng.integers(0, 2, lineitems)
        base = self.o_orderdate[self.l_orderkey]
        self.l_shipdate = base + rng.integers(1, 122, lineitems)
        self.l_commitdate = base + rng.integers(30, 92, lineitems)
        self.l_receiptdate = self.l_shipdate + rng.integers(1, 31, lineitems)


def load(session, storage, d: ScaledTpch, regions_per_table: int = 4) -> int:
    """DDL + bulk ingest + region pre-split. -> total rows loaded."""
    for stmt in DDL.strip().split(";"):
        if stmt.strip():
            session.execute(stmt)
    ischema = session.domain.info_schema()
    db = session.current_db

    def tbl(name):
        return Table(ischema.table(db, name), storage)

    def strs(values, idx):
        return np.array(values, dtype=object)[idx]

    total = 0
    total += bulkload.bulk_load(storage, tbl("region"), {
        "r_regionkey": np.arange(len(REGIONS), dtype=np.int64),
        "r_name": np.array(REGIONS, dtype=object)})
    total += bulkload.bulk_load(storage, tbl("nation"), {
        "n_nationkey": np.arange(len(NATIONS), dtype=np.int64),
        "n_name": np.array([n for n, _r in NATIONS], dtype=object),
        "n_regionkey": np.array([r for _n, r in NATIONS], dtype=np.int64)})
    total += bulkload.bulk_load(storage, tbl("customer"), {
        "c_custkey": d.c_custkey,
        "c_nationkey": d.c_nationkey,
        "c_mktsegment": strs(SEGMENTS, d.c_mktsegment)})
    total += bulkload.bulk_load(storage, tbl("supplier"), {
        "s_suppkey": d.s_suppkey, "s_nationkey": d.s_nationkey})
    total += bulkload.bulk_load(storage, tbl("orders"), {
        "o_orderkey": d.o_orderkey, "o_custkey": d.o_custkey,
        "o_orderdate": _days_us(d.o_orderdate),
        "o_shippriority": d.o_shippriority,
        "o_orderpriority": strs(PRIORITIES, d.o_orderpriority)})
    nl = d.counts["lineitem"]
    total += bulkload.bulk_load(storage, tbl("lineitem"), {
        "l_id": np.arange(nl, dtype=np.int64),
        "l_orderkey": d.l_orderkey, "l_suppkey": d.l_suppkey,
        "l_quantity": d.l_quantity * 100,          # DECIMAL(15,2) scaled
        "l_extendedprice": d.l_extendedprice,      # cents == scaled frac 2
        "l_discount": d.l_discount,                # 0.0p -> p at frac 2
        "l_tax": d.l_tax,
        "l_returnflag": strs(FLAGS, d.l_returnflag),
        "l_linestatus": strs(STATUSES, d.l_linestatus),
        "l_shipdate": _days_us(d.l_shipdate),
        "l_commitdate": _days_us(d.l_commitdate),
        "l_receiptdate": _days_us(d.l_receiptdate)})
    # pre-split the big tables so reads exercise the region fan-out
    # (ref: cluster.go SplitTable; BASELINE config 5's multi-region scan)
    cluster = storage.cluster
    for name, count in (("lineitem", nl), ("orders", d.counts["orders"])):
        cluster.split_table(ischema.table(db, name).id, regions_per_table,
                            max_handle=count)
    return total


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

QUERIES = {"q1": Q1, "q3": Q3, "q5": Q5}
