"""The needed-bytes and rows-read functions against hand counts."""

import json
import os

from benchlib import needed

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


SCHEMA = _load("generators", "tpch_dbgen.schema.json")
# the generator's counts at sf 0.2
COUNTS = {"region": 5, "nation": 25, "customer": 30_000, "supplier": 2_000,
          "orders": 300_000, "lineitem": 1_200_000}


def test_q1_by_hand():
    q1 = _load("statements", "q1.json")
    # four DECIMALs at 8, two CHAR(1), one DATE at 4
    assert needed.bytes_per_row(q1, SCHEMA) == {"lineitem": 4 * 8 + 2 + 4}
    assert needed.statement_bytes(q1, SCHEMA, COUNTS) == 38 * 1_200_000
    assert needed.statement_rows(q1, COUNTS) == 1_200_000


def test_q3_by_hand():
    q3 = _load("statements", "q3.json")
    # o_shippriority is the specification's integer: 4
    assert needed.bytes_per_row(q3, SCHEMA) == {
        "lineitem": 8 + 8 + 8 + 4, "orders": 8 + 8 + 4 + 4,
        "customer": 8 + 10}
    assert needed.statement_bytes(q3, SCHEMA, COUNTS) == (
        28 * 1_200_000 + 24 * 300_000 + 18 * 30_000)
    assert needed.statement_rows(q3, COUNTS) == 1_200_000 + 300_000 + 30_000


def test_q5_by_hand():
    q5 = _load("statements", "q5.json")
    assert needed.bytes_per_row(q5, SCHEMA) == {
        "lineitem": 32, "orders": 20, "customer": 16, "supplier": 16,
        "nation": 41, "region": 33}
    assert needed.statement_bytes(q5, SCHEMA, COUNTS) == (
        32 * 1_200_000 + 20 * 300_000 + 16 * 30_000 + 16 * 2_000
        + 41 * 25 + 33 * 5)
    assert needed.statement_rows(q5, COUNTS) == 1_532_030


def test_generated_counts_follow_the_scale():
    """Every seed loads the same number of rows, and a keyed statement
    (`rows_read`) needs that many rows and not the table."""
    from generators import tpch_dbgen
    a = tpch_dbgen.generate({"sf": 0.01}, 1).counts
    b = tpch_dbgen.generate({"sf": 0.01}, 3000000019).counts
    assert a == b == {"region": 5, "nation": 25, "part": 2000,
                      "supplier": 100, "partsupp": 8000, "customer": 1500,
                      "orders": 15000, "lineitem": 59997}
    keyed = {"tables": ["lineitem"], "rows_read": 1,
             "needed_columns": {"lineitem": ["l_orderkey", "l_comment"]}}
    assert needed.statement_rows(keyed, a) == 1
    assert needed.statement_bytes(keyed, SCHEMA, a) == 8 + 44


def test_peaks_table():
    import pytest

    from benchlib import peaks
    v5e = peaks.for_kind("TPU v5 lite")
    assert v5e["bytes_per_s"] == 819e9 and v5e["flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        peaks.for_kind("TPU v9 imaginary")
