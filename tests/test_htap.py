"""HTAP through the real wire protocol (ISSUE 11): a TPC-C-style
new-order/payment write mix on live connections while analytic readers
hammer the same table — the workload the MVCC delta store
(store/delta.py) exists for. The fast test pins the wire-level
consistency contract; the write mix under analytic load rides behind
the `slow` marker."""

import threading
import time

import numpy as np
import pytest

from tests.mysql_client import MiniClient, MySQLError
from tidb_tpu import metrics
from tidb_tpu.server import Server
from tidb_tpu.session import Session
from tidb_tpu.store import new_mock_storage
from tidb_tpu.table import Table, bulkload


@pytest.fixture
def env():
    storage = new_mock_storage()
    storage.async_commit_secondaries = False
    server = Server(storage, port=0)
    server.start()
    admin = MiniClient("127.0.0.1", server.port)
    admin.query("CREATE DATABASE IF NOT EXISTS htap")
    admin.use("htap")
    yield storage, server, admin
    admin.close()
    server.close()
    storage.close()


def _ints(cli, sql):
    """One wire resultset row, decoded to ints (the text protocol
    ships strings)."""
    _cols, rs = cli.query(sql)
    return tuple(None if x is None else int(x) for x in rs[0])


def _load_stock(storage, n=5000):
    s = Session(storage, db="htap")
    s.execute("CREATE TABLE stock (s_id BIGINT PRIMARY KEY, "
              "s_seg BIGINT, s_qty BIGINT, s_cnt BIGINT)")
    s.execute("CREATE TABLE orders (o_id BIGINT PRIMARY KEY, "
              "o_item BIGINT)")
    bulkload.bulk_load(storage, Table(
        s.domain.info_schema().table("htap", "stock"), storage), {
        "s_id": np.arange(n, dtype=np.int64),
        "s_seg": np.arange(n, dtype=np.int64) % 7,
        "s_qty": np.full(n, 50, dtype=np.int64),
        "s_cnt": np.zeros(n, dtype=np.int64)})
    s.close()
    return n


class TestHtapWire:
    def test_write_becomes_visible_through_cached_analytics(self, env):
        """A committed wire write is visible to the NEXT analytic read
        (freshness through the base⋈delta serve path, not a cache
        staleness window)."""
        storage, server, admin = env
        n = _load_stock(storage, n=3000)
        q = "SELECT COUNT(*), SUM(s_qty), MAX(s_cnt) FROM stock"
        assert _ints(admin, q) == (n, 50 * n, 0)
        assert _ints(admin, q) == (n, 50 * n, 0)   # warm
        wcli = MiniClient("127.0.0.1", server.port, db="htap")
        served0 = metrics.snapshot().get(metrics.CACHE_DELTA_SERVES, 0)
        for i in range(1, 6):
            wcli.query(f"UPDATE stock SET s_qty = s_qty - 1, "
                       f"s_cnt = {i} WHERE s_id = {i}")
            assert _ints(admin, q) == (n, 50 * n - i, i), \
                f"write {i} not visible to the next analytic read"
        wcli.close()
        assert metrics.snapshot().get(
            metrics.CACHE_DELTA_SERVES, 0) > served0

    @pytest.mark.slow
    def test_wire_write_mix_under_analytic_load(self, env):
        """2 writers x 2 analytic readers on live connections for a
        few hundred ops: every read is a consistent snapshot (COUNT
        never moves, SUM(s_qty) only falls as new-orders decrement),
        the final state matches the applied writes exactly, and the
        delta plane (not re-scans) served the reads."""
        storage, server, admin = env
        n = _load_stock(storage)
        q = "SELECT COUNT(*), SUM(s_qty) FROM stock"
        admin.query(q)
        admin.query(q)      # warm both cache tiers
        stop = threading.Event()
        applied = [0, 0]
        bad: list = []
        wire_errs: list = []

        def writer(w):
            cli = MiniClient("127.0.0.1", server.port, db="htap")
            k = 0
            while not stop.is_set() and k < 120:
                k += 1
                rid = (w * 2477 + k * 31) % 5000
                try:
                    cli.query(f"UPDATE stock SET s_qty = s_qty - 1 "
                              f"WHERE s_id = {rid}")
                    cli.query(f"INSERT INTO orders VALUES "
                              f"({w * 100000 + k}, {rid})")
                    applied[w] += 1
                except MySQLError as e:
                    wire_errs.append(str(e))
            cli.close()

        def reader(_r):
            cli = MiniClient("127.0.0.1", server.port, db="htap")
            prev_sum = 50 * 5000 + 1
            while not stop.is_set():
                cnt, sq = _ints(cli, q)
                if cnt != n or sq >= prev_sum + 1:
                    bad.append((cnt, sq, prev_sum))
                prev_sum = sq
                time.sleep(0.005)
            cli.close()

        rts = [threading.Thread(target=reader, args=(r,))
               for r in range(2)]
        wts = [threading.Thread(target=writer, args=(w,))
               for w in range(2)]
        for t in rts + wts:
            t.start()
        for t in wts:
            t.join(120)
        stop.set()
        for t in rts:
            t.join(30)
        assert wire_errs == []
        assert bad == [], f"inconsistent snapshots: {bad[:3]}"
        total = applied[0] + applied[1]
        assert total > 0
        assert _ints(admin, q) == (n, 50 * n - total)
        assert _ints(admin, "SELECT COUNT(*) FROM orders")[0] == total
        # a forced merge (the /shed path's fold) changes nothing
        storage.delta_store.merge(trigger="shed")
        assert _ints(admin, q) == (n, 50 * n - total)
