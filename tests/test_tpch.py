"""TPC-H Q1/Q3/Q5 end-to-end through the session, vs independent truth."""

from decimal import Decimal

import pytest

import tpch
from tidb_tpu.session import Session
from tidb_tpu.store.storage import new_mock_storage


@pytest.fixture(scope="module")
def sess():
    s = Session(new_mock_storage())
    s.execute("CREATE DATABASE tpch")
    s.execute("USE tpch")
    data = tpch.TpchData()
    tpch.load(s, data)
    s._data = data
    return s


def _approx(a, b, tol=1e-6):
    a = float(a) if isinstance(a, Decimal) else a
    b = float(b) if isinstance(b, Decimal) else b
    assert a == pytest.approx(b, rel=tol, abs=1e-6), (a, b)


def test_q1(sess):
    rows = sess.query(tpch.Q1).rows
    want = tpch.truth_q1(sess._data)
    assert len(rows) == len(want) == 6
    for got, exp in zip(rows, want):
        assert got[0] == exp[0] and got[1] == exp[1]
        for g, w in zip(got[2:], exp[2:]):
            _approx(g, w)


def test_q3(sess):
    rows = sess.query(tpch.Q3).rows
    want = tpch.truth_q3(sess._data)
    assert len(rows) == len(want)
    for got, exp in zip(rows, want):
        assert got[0] == exp[0], (got, exp)
        _approx(got[1], exp[1])
        assert got[2] == exp[2]
        assert got[3] == exp[3]


def test_q5(sess):
    rows = sess.query(tpch.Q5).rows
    want = tpch.truth_q5(sess._data)
    assert len(rows) == len(want)
    for got, exp in zip(rows, want):
        assert got[0] == exp[0]
        _approx(got[1], exp[1])


def test_q4(sess):
    """EXISTS-correlated subquery through the apply executor."""
    rows = sess.query(tpch.Q4).rows
    want = tpch.truth_q4(sess._data)
    assert rows == want


def test_q6(sess):
    rows = sess.query(tpch.Q6).rows
    want = tpch.truth_q6(sess._data)
    assert len(rows) == 1
    _approx(rows[0][0], want)


def test_q12(sess):
    """Shipping-mode-style two-table join with date predicates between
    columns (l_shipdate < l_commitdate < l_receiptdate)."""
    rows = sess.query(tpch.Q12).rows
    want = tpch.truth_q12(sess._data)
    assert [(r[0], r[1]) for r in rows] == want


# -- Q18: a grouped IN-subquery above a three-way join ----------------------

SCATTER = 'tidb_tpu_agg_dispatch_total{path="scatter"}'


@pytest.fixture(scope="module")
def sess18():
    s = Session(new_mock_storage())
    s.execute("CREATE DATABASE tpch18")
    s.execute("USE tpch18")
    s._data = tpch.Q18Data()
    tpch.load_q18(s, s._data)
    return s


def _q18_rows(sess, quantity):
    return [(r[0], r[1], r[2], r[3], Decimal(r[4]), Decimal(r[5]))
            for r in sess.query(tpch.Q18.format(quantity=quantity)).rows]


@pytest.mark.parametrize("quantity,limited", [
    (300, False),       # the specification's validation value
    (150, True),        # > 100 orders qualify: LIMIT and both sort keys
])
def test_q18(sess18, quantity, limited):
    want, qualified = tpch.truth_q18(sess18._data, quantity)
    assert (qualified > 100) is limited and 0 < len(want) <= 100
    if limited:
        # some of the first hundred tie on o_totalprice, so the order of
        # the answer rests on o_orderdate too
        assert len({w[4] for w in want}) < len(want)
    got = _q18_rows(sess18, quantity)
    assert got == [(n, ck, ok, od, Decimal(p) / 100, Decimal(q))
                   for n, ck, ok, od, p, q in want]


def test_q18_inner_group_by_takes_the_scatter_arm(sess18):
    """3,000 order keys in one block are past the dense branch's
    ops/hashagg._DENSE_SLOTS, so the subquery's partial aggregate on the
    device reduces by segment scatters; the answer is the host's."""
    from tidb_tpu import metrics
    from tidb_tpu.ops import hashagg
    assert len(set(sess18._data.l_orderkey)) > hashagg._DENSE_SLOTS
    before = metrics.snapshot().get(SCATTER, 0)
    got = _q18_rows(sess18, 300)
    assert metrics.snapshot().get(SCATTER, 0) > before
    sess18.execute("SET tidb_tpu_device = 0")
    try:
        assert _q18_rows(sess18, 300) == got
    finally:
        sess18.execute("SET tidb_tpu_device = 1")
