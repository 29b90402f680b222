"""TPC-H tables as numpy columns, to the specification's shapes: the
eight tables of clause 1.4 with every column, populated by the rules of
clause 4.2.3 (what dbgen implements): sparse order keys, one to seven
lines an order, `l_suppkey` and `l_extendedprice` derived from the part,
return flags and line statuses from the dates, order status and total
from the order's lines, names, addresses, phones and comments at their
declared lengths. Row counts are the specification's cardinalities times
`sf`.

Not dbgen's bytes: the random stream is numpy's, seeded from --seed, and
comment text is cut from one pool of the grammar's words instead of being
parsed sentence by sentence (dbgen 2.x cuts from a pool as well). Two
departures are listed under `assumed` in the configuration's file: each
order's line count is the multiset {1..7 equally often} in a seeded order,
so that every seed loads the same number of rows (4 a order on average, as
dbgen's uniform draw), and `lineitem` / `partsupp` declare no primary key
(clause 1.4.2: constraints are optional).

The numeric columns are drawn when the data is made, since the plain
references read them; strings are drawn in `columns()`, per table, from
generators of their own, so a table's text does not depend on which other
tables a cell loads.
"""

from __future__ import annotations

import datetime

import numpy as np

REGIONS = ["AFRICA", "AMERICA", "ASIA", "EUROPE", "MIDDLE EAST"]
NATIONS = [  # (name, region index): clause 4.2.3's 25 nations
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
INSTRUCTIONS = ["DELIVER IN PERSON", "COLLECT COD", "NONE",
                "TAKE BACK RETURN"]
MODES = ["REG AIR", "AIR", "RAIL", "SHIP", "TRUCK", "MAIL", "FOB"]
FLAGS = ["A", "N", "R"]
STATUSES = ["F", "O"]
ORDER_STATUSES = ["F", "O", "P"]
TYPES = [f"{a} {b} {c}"
         for a in ("STANDARD", "SMALL", "MEDIUM", "LARGE", "ECONOMY", "PROMO")
         for b in ("ANODIZED", "BURNISHED", "PLATED", "POLISHED", "BRUSHED")
         for c in ("TIN", "NICKEL", "BRASS", "STEEL", "COPPER")]
CONTAINERS = [f"{a} {b}" for a in ("SM", "LG", "MED", "JUMBO", "WRAP")
              for b in ("CASE", "BOX", "BAG", "JAR", "PKG", "PACK", "CAN",
                        "DRUM")]
COLOURS = (
    "almond antique aquamarine azure beige bisque black blanched blue blush "
    "brown burlywood burnished chartreuse chiffon chocolate coral cornflower "
    "cornsilk cream cyan dark deep dim dodger drab firebrick floral forest "
    "frosted gainsboro ghost goldenrod green grey honeydew hot indian ivory "
    "khaki lace lavender lawn lemon light lime linen magenta maroon medium "
    "metallic midnight mint misty moccasin navajo navy olive orange orchid "
    "pale papaya peach peru pink plum powder puff purple red rose rosy royal "
    "saddle salmon sandy seashell sienna sky slate smoke snow spring steel "
    "tan thistle tomato turquoise violet wheat white yellow").split()
_WORDS = (  # clause 4.2.2.13's grammar: nouns, verbs, adjectives, adverbs,
    # prepositions, auxiliaries, terminators
    "foxes ideas theodolites pinto beans instructions dependencies excuses "
    "platelets asymptotes courts dolphins multipliers sauternes warthogs "
    "frets dinos attainments somas Tiresias' patterns forges braids hockey "
    "players frays warhorses dugouts notornis epitaphs pearls tithes "
    "waters orbits gifts sheaves depths sentiments decoys realms pains "
    "grouches escapades packages requests accounts deposits "
    "sleep wake are cajole haggle nag use boost affix detect integrate "
    "maintain nod was lose sublate solve thrash promise engage hinder "
    "print x-ray breach eat grow impress mold poach serve run dazzle "
    "snooze doze unwind kindle play hang believe doubt "
    "furious sly careful blithe quick fluffy slow quiet ruthless thin "
    "close dogged daring brave stealthy permanent enticing idle busy "
    "regular final ironic even bold silent special pending unusual express "
    "sometimes always never furiously slyly carefully blithely quickly "
    "fluffily slowly quietly ruthlessly thinly closely doggedly daringly "
    "bravely stealthily permanently enticingly idly busily regularly "
    "finally ironically evenly boldly silently "
    "about above according to across after against along alongside of "
    "among around at atop before behind beneath beside besides between "
    "beyond by despite during except for from in place of inside instead "
    "of into near of on outside over past since through throughout to "
    "toward under until up upon without with within "
    "do may might shall will would can could should ought to must "
    "will have to shall have to could have to should have to must have to "
    "need to try to . ; : ? ! --").split()
_ALNUM = np.frombuffer(
    b"0123456789abcdefghijklmnopqrstuvwxyzABCDEFGHIJKLMNOPQRSTUVWXYZ,. ",
    dtype=np.uint8)

EPOCH = datetime.date(1992, 1, 1)             # STARTDATE
_EPOCH_US = int(datetime.datetime(
    1992, 1, 1, tzinfo=datetime.timezone.utc).timestamp()) * 1_000_000
_DAY_US = 86_400_000_000
_LAST_ORDER_DAY = (datetime.date(1998, 12, 31) - EPOCH).days - 151
CURRENT_DAY = (datetime.date(1995, 6, 17) - EPOCH).days   # CURRENTDATE

TABLES = ("region", "nation", "part", "supplier", "partsupp", "customer",
          "orders", "lineitem")

_DDL = {
    "region": "CREATE TABLE region (r_regionkey BIGINT NOT NULL PRIMARY KEY, "
              "r_name CHAR(25) NOT NULL, r_comment VARCHAR(152))",
    "nation": "CREATE TABLE nation (n_nationkey BIGINT NOT NULL PRIMARY KEY, "
              "n_name CHAR(25) NOT NULL, n_regionkey BIGINT NOT NULL, "
              "n_comment VARCHAR(152))",
    "part": "CREATE TABLE part (p_partkey BIGINT NOT NULL PRIMARY KEY, "
            "p_name VARCHAR(55) NOT NULL, p_mfgr CHAR(25) NOT NULL, "
            "p_brand CHAR(10) NOT NULL, p_type VARCHAR(25) NOT NULL, "
            "p_size INT NOT NULL, p_container CHAR(10) NOT NULL, "
            "p_retailprice DECIMAL(15,2) NOT NULL, "
            "p_comment VARCHAR(23) NOT NULL)",
    "supplier": "CREATE TABLE supplier (s_suppkey BIGINT NOT NULL PRIMARY "
                "KEY, s_name CHAR(25) NOT NULL, s_address VARCHAR(40) NOT "
                "NULL, s_nationkey BIGINT NOT NULL, s_phone CHAR(15) NOT "
                "NULL, s_acctbal DECIMAL(15,2) NOT NULL, "
                "s_comment VARCHAR(101) NOT NULL)",
    "partsupp": "CREATE TABLE partsupp (ps_partkey BIGINT NOT NULL, "
                "ps_suppkey BIGINT NOT NULL, ps_availqty INT NOT NULL, "
                "ps_supplycost DECIMAL(15,2) NOT NULL, "
                "ps_comment VARCHAR(199) NOT NULL)",
    "customer": "CREATE TABLE customer (c_custkey BIGINT NOT NULL PRIMARY "
                "KEY, c_name VARCHAR(25) NOT NULL, c_address VARCHAR(40) NOT "
                "NULL, c_nationkey BIGINT NOT NULL, c_phone CHAR(15) NOT "
                "NULL, c_acctbal DECIMAL(15,2) NOT NULL, c_mktsegment "
                "CHAR(10) NOT NULL, c_comment VARCHAR(117) NOT NULL)",
    "orders": "CREATE TABLE orders (o_orderkey BIGINT NOT NULL PRIMARY KEY, "
              "o_custkey BIGINT NOT NULL, o_orderstatus CHAR(1) NOT NULL, "
              "o_totalprice DECIMAL(15,2) NOT NULL, o_orderdate DATE NOT "
              "NULL, o_orderpriority CHAR(15) NOT NULL, o_clerk CHAR(15) NOT "
              "NULL, o_shippriority INT NOT NULL, "
              "o_comment VARCHAR(79) NOT NULL)",
    "lineitem": "CREATE TABLE lineitem (l_orderkey BIGINT NOT NULL, "
                "l_partkey BIGINT NOT NULL, l_suppkey BIGINT NOT NULL, "
                "l_linenumber INT NOT NULL, l_quantity DECIMAL(15,2) NOT "
                "NULL, l_extendedprice DECIMAL(15,2) NOT NULL, l_discount "
                "DECIMAL(15,2) NOT NULL, l_tax DECIMAL(15,2) NOT NULL, "
                "l_returnflag CHAR(1) NOT NULL, l_linestatus CHAR(1) NOT "
                "NULL, l_shipdate DATE NOT NULL, l_commitdate DATE NOT NULL, "
                "l_receiptdate DATE NOT NULL, l_shipinstruct CHAR(25) NOT "
                "NULL, l_shipmode CHAR(10) NOT NULL, "
                "l_comment VARCHAR(44) NOT NULL)",
}
# tables split into the configuration's regions; the rest stay in one
_SPLIT = ("lineitem", "orders")
_IDX = {t: i for i, t in enumerate(TABLES)}


def retail_price(partkey: np.ndarray) -> np.ndarray:
    """P_RETAILPRICE in cents, clause 4.2.3's formula of the part key."""
    pk = partkey.astype(np.int64)
    return 90000 + (pk // 10) % 20001 + 100 * (pk % 1000)


def part_supplier(partkey, i, suppliers: int) -> np.ndarray:
    """The i-th (0..3) supplier of a part, clause 4.2.3 (PS_SUPPKEY)."""
    pk = partkey.astype(np.int64)
    s = int(suppliers)
    return (pk + i * (s // 4 + (pk - 1) // s)) % s + 1


class Tpch:
    """The numeric columns at scale factor `sf` (sf 1: 1,500,000 orders,
    6,000,000 line items, dbgen's draw gives 6,001,215)."""

    def __init__(self, sf: float, seed: int):
        self.sf, self.seed = sf, int(seed)
        suppliers = max(int(round(10_000 * sf)), 20)
        parts = max(int(round(200_000 * sf)), 200)
        customers = max(int(round(150_000 * sf)), 60)
        orders = max(int(round(1_500_000 * sf)), 210)
        n_nation = len(NATIONS)

        rng = self._rng("supplier")
        self.s_suppkey = np.arange(1, suppliers + 1, dtype=np.int64)
        self.s_nationkey = rng.integers(0, n_nation, suppliers)
        self.s_acctbal = rng.integers(-99_999, 1_000_000, suppliers)

        rng = self._rng("customer")
        self.c_custkey = np.arange(1, customers + 1, dtype=np.int64)
        self.c_nationkey = rng.integers(0, n_nation, customers)
        self.c_acctbal = rng.integers(-99_999, 1_000_000, customers)
        self.c_mktsegment = rng.integers(0, len(SEGMENTS), customers)

        rng = self._rng("orders")
        i = np.arange(1, orders + 1, dtype=np.int64)
        self.o_orderkey = ((i >> 3) << 5) | (i & 7)     # 8 of every 32 keys
        with_orders = self.c_custkey[self.c_custkey % 3 != 0]
        self.o_custkey = with_orders[rng.integers(0, len(with_orders),
                                                  orders)]
        self.o_orderdate = rng.integers(0, _LAST_ORDER_DAY + 1, orders)
        self.o_orderpriority = rng.integers(0, len(PRIORITIES), orders)
        self.o_clerk = rng.integers(1, max(int(round(1000 * sf)), 1) + 1,
                                    orders)
        self.o_shippriority = np.zeros(orders, dtype=np.int64)
        lines = rng.permutation(np.arange(orders, dtype=np.int64) % 7 + 1)

        rng = self._rng("lineitem")
        n = int(lines.sum())
        first = np.cumsum(lines) - lines
        self.l_order = np.repeat(np.arange(orders, dtype=np.int64), lines)
        self.l_orderkey = self.o_orderkey[self.l_order]
        self.l_linenumber = np.arange(n, dtype=np.int64) \
            - first[self.l_order] + 1
        self.l_partkey = rng.integers(1, parts + 1, n)
        self.l_suppkey = part_supplier(self.l_partkey, rng.integers(0, 4, n),
                                       suppliers)
        self.l_quantity = rng.integers(1, 51, n)               # whole units
        self.l_extendedprice = self.l_quantity * retail_price(self.l_partkey)
        self.l_discount = rng.integers(0, 11, n)               # percent
        self.l_tax = rng.integers(0, 9, n)                     # percent
        base = self.o_orderdate[self.l_order]
        self.l_shipdate = base + rng.integers(1, 122, n)
        self.l_commitdate = base + rng.integers(30, 91, n)
        self.l_receiptdate = self.l_shipdate + rng.integers(1, 31, n)
        returned = self.l_receiptdate <= CURRENT_DAY
        self.l_returnflag = np.where(
            returned, np.where(rng.integers(0, 2, n) == 0, 2, 0), 1)
        self.l_linestatus = (self.l_shipdate > CURRENT_DAY).astype(np.int64)
        self.l_shipinstruct = rng.integers(0, len(INSTRUCTIONS), n)
        self.l_shipmode = rng.integers(0, len(MODES), n)

        open_lines = np.bincount(self.l_order, self.l_linestatus,
                                 orders).astype(np.int64)
        self.o_orderstatus = np.where(open_lines == 0, 0,
                                      np.where(open_lines == lines, 1, 2))
        # dbgen's integer arithmetic, line by line, in cents
        charged = (self.l_extendedprice * (100 - self.l_discount) // 100
                   * (100 + self.l_tax) // 100)
        self.o_totalprice = np.bincount(
            self.l_order, charged.astype(np.float64), orders
        ).round().astype(np.int64)

        self.counts = {"region": len(REGIONS), "nation": n_nation,
                       "part": parts, "supplier": suppliers,
                       "partsupp": 4 * parts, "customer": customers,
                       "orders": orders, "lineitem": n}

    def _rng(self, table: str, stream: int = 0):
        return np.random.default_rng([self.seed, _IDX[table], stream])


def generate(params: dict, seed: int) -> Tpch:
    return Tpch(sf=float(params["sf"]), seed=seed)


def ddl(table: str) -> str:
    return _DDL[table]


def split(table: str, data: Tpch):
    """-> one past the largest row handle of `table` when it is pre-split
    over the configuration's regions, or None for a table that stays in
    one region."""
    if table not in _SPLIT:
        return None
    if table == "orders":
        return int(data.o_orderkey[-1]) + 1
    return data.counts[table] + 1


def handles(table: str, data: Tpch):
    """Row handles for a table that declares no integer primary key
    (1..n in generation order), or None where the key is the handle."""
    if table in ("lineitem", "partsupp"):
        return np.arange(1, data.counts[table] + 1, dtype=np.int64)
    return None


def _days_us(days: np.ndarray) -> np.ndarray:
    """Day offsets from EPOCH -> epoch-microsecond DATE datums."""
    return _EPOCH_US + days.astype(np.int64) * _DAY_US


def _strs(values, idx) -> np.ndarray:
    return np.array(values, dtype=object)[idx]


def _objects(items: list) -> np.ndarray:
    out = np.empty(len(items), dtype=object)
    out[:] = items
    return out


_POOL = None


def _pool() -> str:
    """One megabyte of the grammar's words, the same for every seed."""
    global _POOL
    if _POOL is None:
        rng = np.random.default_rng(0)
        words = np.array(_WORDS, dtype=object)
        _POOL = " ".join(words[rng.integers(0, len(words), 180_000)])
    return _POOL


def _text(rng, n: int, lo: int, hi: int) -> np.ndarray:
    """TEXT strings of a length uniform in [lo, hi], cut from the pool."""
    pool = _pool()
    length = rng.integers(lo, hi + 1, n).tolist()
    at = rng.integers(0, len(pool) - hi, n).tolist()
    return _objects([pool[a:a + k].strip() or "x"
                     for a, k in zip(at, length)])


def _vstring(rng, n: int, lo: int, hi: int) -> np.ndarray:
    """Random v-strings (addresses): length uniform in [lo, hi]."""
    chars = _ALNUM[rng.integers(0, len(_ALNUM), n * hi)].tobytes().decode()
    length = rng.integers(lo, hi + 1, n).tolist()
    return _objects([chars[i * hi:i * hi + k].strip() or "x"
                     for i, k in enumerate(length)])


def _phones(rng, nationkey: np.ndarray) -> np.ndarray:
    n = len(nationkey)
    a = rng.integers(100, 1000, n).tolist()
    b = rng.integers(100, 1000, n).tolist()
    c = rng.integers(1000, 10000, n).tolist()
    return _objects([f"{cc + 10}-{x}-{y}-{z}" for cc, x, y, z
                     in zip(nationkey.tolist(), a, b, c)])


def _numbered(prefix: str, keys: np.ndarray) -> np.ndarray:
    return _objects([f"{prefix}#{k:09d}" for k in keys.tolist()])


def columns(d: Tpch, table: str) -> dict:
    """The columns of `table` in the form the program's bulk import
    takes: int64 for integers, scaled int64 for DECIMAL, epoch
    microseconds for DATE, object str for strings."""
    rng = d._rng(table, 1)
    n = d.counts[table]
    if table == "region":
        return {"r_regionkey": np.arange(n, dtype=np.int64),
                "r_name": _objects(REGIONS),
                "r_comment": _text(rng, n, 31, 115)}
    if table == "nation":
        return {"n_nationkey": np.arange(n, dtype=np.int64),
                "n_name": _objects([name for name, _r in NATIONS]),
                "n_regionkey": np.array([r for _n, r in NATIONS],
                                        dtype=np.int64),
                "n_comment": _text(rng, n, 31, 114)}
    if table == "part":
        key = np.arange(1, n + 1, dtype=np.int64)
        colour = np.array(COLOURS, dtype=object)
        words = colour[rng.integers(0, len(COLOURS), (n, 5))]
        mfgr = rng.integers(1, 6, n)
        brand = mfgr * 10 + rng.integers(1, 6, n)
        return {"p_partkey": key,
                "p_name": _objects([" ".join(w) for w in words.tolist()]),
                "p_mfgr": _objects([f"Manufacturer#{m}"
                                    for m in mfgr.tolist()]),
                "p_brand": _objects([f"Brand#{b}" for b in brand.tolist()]),
                "p_type": _strs(TYPES, rng.integers(0, len(TYPES), n)),
                "p_size": rng.integers(1, 51, n),
                "p_container": _strs(CONTAINERS,
                                     rng.integers(0, len(CONTAINERS), n)),
                "p_retailprice": retail_price(key),
                "p_comment": _text(rng, n, 5, 22)}
    if table == "supplier":
        return {"s_suppkey": d.s_suppkey,
                "s_name": _numbered("Supplier", d.s_suppkey),
                "s_address": _vstring(rng, n, 10, 40),
                "s_nationkey": d.s_nationkey,
                "s_phone": _phones(rng, d.s_nationkey),
                "s_acctbal": d.s_acctbal,
                "s_comment": _text(rng, n, 25, 100)}
    if table == "partsupp":
        part = np.repeat(np.arange(1, d.counts["part"] + 1, dtype=np.int64),
                         4)
        return {"ps_partkey": part,
                "ps_suppkey": part_supplier(part, np.arange(n) % 4,
                                            d.counts["supplier"]),
                "ps_availqty": rng.integers(1, 10_000, n),
                "ps_supplycost": rng.integers(100, 100_001, n),
                "ps_comment": _text(rng, n, 49, 198)}
    if table == "customer":
        return {"c_custkey": d.c_custkey,
                "c_name": _numbered("Customer", d.c_custkey),
                "c_address": _vstring(rng, n, 10, 40),
                "c_nationkey": d.c_nationkey,
                "c_phone": _phones(rng, d.c_nationkey),
                "c_acctbal": d.c_acctbal,
                "c_mktsegment": _strs(SEGMENTS, d.c_mktsegment),
                "c_comment": _text(rng, n, 29, 116)}
    if table == "orders":
        return {"o_orderkey": d.o_orderkey, "o_custkey": d.o_custkey,
                "o_orderstatus": _strs(ORDER_STATUSES, d.o_orderstatus),
                "o_totalprice": d.o_totalprice,
                "o_orderdate": _days_us(d.o_orderdate),
                "o_orderpriority": _strs(PRIORITIES, d.o_orderpriority),
                "o_clerk": _numbered("Clerk", d.o_clerk),
                "o_shippriority": d.o_shippriority,
                "o_comment": _text(rng, n, 19, 78)}
    if table == "lineitem":
        return {"l_orderkey": d.l_orderkey, "l_partkey": d.l_partkey,
                "l_suppkey": d.l_suppkey, "l_linenumber": d.l_linenumber,
                "l_quantity": d.l_quantity * 100,       # DECIMAL(15,2)
                "l_extendedprice": d.l_extendedprice,   # cents
                "l_discount": d.l_discount, "l_tax": d.l_tax,
                "l_returnflag": _strs(FLAGS, d.l_returnflag),
                "l_linestatus": _strs(STATUSES, d.l_linestatus),
                "l_shipdate": _days_us(d.l_shipdate),
                "l_commitdate": _days_us(d.l_commitdate),
                "l_receiptdate": _days_us(d.l_receiptdate),
                "l_shipinstruct": _strs(INSTRUCTIONS, d.l_shipinstruct),
                "l_shipmode": _strs(MODES, d.l_shipmode),
                "l_comment": _text(rng, n, 10, 43)}
    raise KeyError(f"tpch_dbgen has no table {table!r}")
