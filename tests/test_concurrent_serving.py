"""Concurrent serving through the real wire protocol (ISSUE 10).

Eight client threads drive one server with a mixed analytic +
point-lookup replay and the suite asserts the serving contracts:
result isolation per connection, PROCESSLIST / memory_usage visibility
for every session, no cross-session digest bleed, background-worker
heartbeats NOT invalidating the columnar caches, the connection gauges,
the status-port /shed hook returning the hbm-cache ledger to zero, and
— under a pinched `tidb_tpu_server_mem_quota` — statements queueing or
shedding with the RETRYABLE 9008, never a mid-query
ER_MEM_EXCEED_QUOTA."""

import threading
import time

import pytest

from tests.mysql_client import MiniClient, MySQLError
from tidb_tpu import config, errcode, memtrack, metrics, sched
from tidb_tpu.util import statusclient
from tidb_tpu.server import Server
from tidb_tpu.server.status import StatusServer
from tidb_tpu.store import new_mock_storage

N_CLIENTS = 8


@pytest.fixture
def env():
    saved = {v: config.get_var(v) for v in
             ("tidb_tpu_server_mem_quota", "tidb_tpu_admission_timeout_ms",
              "tidb_tpu_sched_inflight")}
    sched.reset_for_tests()
    storage = new_mock_storage()
    storage.async_commit_secondaries = False
    server = Server(storage, port=0)
    server.start()
    admin = MiniClient("127.0.0.1", server.port)
    admin.query("CREATE DATABASE IF NOT EXISTS test")
    admin.use("test")
    yield server, admin
    admin.close()
    server.close()
    storage.close()
    for k, v in saved.items():
        config.set_var(k, v)
    sched.reset_for_tests()


def _fanout(n, fn):
    """Run fn(i) on n threads; re-raise the first worker error."""
    errors: list = []
    barrier = threading.Barrier(n)

    def run(i):
        try:
            barrier.wait(timeout=30)
            fn(i)
        except Exception as e:  # noqa: BLE001 - surfaced below
            errors.append(e)

    threads = [threading.Thread(target=run, args=(i,)) for i in range(n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(120)
    if errors:
        raise errors[0]


class TestMultiClientIsolation:
    def test_eight_clients_mixed_replay(self, env):
        """Mixed analytic + point-lookup replay on 8 connections: every
        client sees exactly its own data (result isolation), and every
        session shows up in PROCESSLIST and memory_usage."""
        server, admin = env
        admin.query("CREATE TABLE conc (id BIGINT PRIMARY KEY, "
                    "cli BIGINT, v BIGINT)")
        rows = ", ".join(f"({c * 1000 + i}, {c}, {i})"
                         for c in range(N_CLIENTS) for i in range(200))
        admin.query(f"INSERT INTO conc VALUES {rows}")
        seen_sessions: list = []

        def client(i):
            c = MiniClient("127.0.0.1", server.port, db="test")
            try:
                for _round in range(3):
                    # analytic: my partition's aggregate
                    _cols, rs = c.query(
                        "SELECT COUNT(*), SUM(v) FROM conc "
                        f"WHERE cli = {i}")
                    assert rs == [("200", str(sum(range(200))))], (i, rs)
                    # point lookups: my own rows only
                    for j in (0, 7, 199):
                        _cols, rs = c.query(
                            "SELECT v FROM conc WHERE id = "
                            f"{i * 1000 + j}")
                        assert rs == [(str(j),)], (i, j, rs)
                # PROCESSLIST sees my session while the conn is open
                _cols, pl = c.query("SHOW PROCESSLIST")
                assert len(pl) >= 2     # me + the admin at minimum
                seen_sessions.append(len(pl))
            finally:
                c.close()

        _fanout(N_CLIENTS, client)
        assert seen_sessions

    def test_sessions_visible_in_memory_usage(self, env):
        server, admin = env
        admin.query("CREATE TABLE mu (id BIGINT PRIMARY KEY, v BIGINT)")
        admin.query("INSERT INTO mu VALUES " + ", ".join(
            f"({i}, {i % 7})" for i in range(3000)))
        clients = [MiniClient("127.0.0.1", server.port, db="test")
                   for _ in range(4)]
        try:
            for c in clients:
                c.query("SELECT v, COUNT(*) FROM mu GROUP BY v")
            _cols, rs = admin.query(
                "SELECT scope, session_id, peak_host_bytes FROM "
                "information_schema.memory_usage")
            session_rows = [r for r in rs if r[0] == "session"]
            # every open connection's session is attributed (4 clients
            # + admin). At least the cache-cold client carries a real
            # peak; cache-warm ones legitimately track less (the scan
            # served from the columnar cache stages nothing)
            assert len(session_rows) >= 5
            busy = [r for r in session_rows if int(r[2]) > 10_000]
            assert len(busy) >= 1
        finally:
            for c in clients:
                c.close()

    def test_no_cross_session_digest_bleed(self, env):
        """Each client hammers a structurally distinct statement; the
        digest summary must attribute exactly its executions to each —
        concurrent sessions must not merge or miscount digests."""
        server, admin = env
        admin.query("CREATE TABLE dig (id BIGINT PRIMARY KEY, "
                    "a BIGINT, b BIGINT, c BIGINT)")
        admin.query("INSERT INTO dig VALUES " + ", ".join(
            f"({i}, {i}, {i * 2}, {i * 3})" for i in range(100)))
        col_of = {0: "a", 1: "b", 2: "c"}
        execs = {0: 4, 1: 5, 2: 6}

        def client(i):
            col, n = col_of[i % 3], execs[i % 3]
            c = MiniClient("127.0.0.1", server.port, db="test")
            try:
                for _ in range(n):
                    c.query(f"SELECT SUM({col}) FROM dig "
                            f"WHERE {col} > {i}")
            finally:
                c.close()

        _fanout(3, client)
        _cols, rs = admin.query(
            "SELECT digest_text, exec_count FROM "
            "performance_schema.events_statements_summary_by_digest")
        counts = {}
        for text, n in rs:
            low = text.lower()
            if "from dig" not in low:
                continue    # the summary is process-global: other
                #             suites' SUM(...) digests are not ours
            for i, col in col_of.items():
                if f"sum ( {col} )" in low:
                    counts[col] = int(n)
        assert counts == {"a": 4, "b": 5, "c": 6}, rs

    def test_connection_gauges(self, env):
        server, admin = env
        snap = metrics.snapshot()
        base = snap.get(metrics.CONNECTIONS_CURRENT, 0)
        assert base >= 1                # the admin connection
        extra = [MiniClient("127.0.0.1", server.port) for _ in range(3)]
        try:
            # gauge updates on the accept path
            assert metrics.snapshot()[metrics.CONNECTIONS_CURRENT] \
                == base + 3
        finally:
            for c in extra:
                c.close()
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline:
            if metrics.snapshot()[metrics.CONNECTIONS_CURRENT] == base:
                break
            time.sleep(0.02)
        assert metrics.snapshot()[metrics.CONNECTIONS_CURRENT] == base


class TestHeartbeatCacheStability:
    def test_workers_do_not_bump_data_version(self, env):
        """The schema worker publishes its version ~every half-lease;
        those bookkeeping commits must NOT invalidate the columnar
        caches — before this PR every cache entry died within a second
        of a wire server starting, so serving traffic never saw a warm
        cache."""
        server, admin = env
        storage = server.storage
        v0 = storage.engine.data_version
        time.sleep(1.6)                 # > one worker tick
        assert storage.engine.data_version == v0
        # a REAL write still invalidates
        admin.query("CREATE TABLE hb (id BIGINT PRIMARY KEY)")
        admin.query("INSERT INTO hb VALUES (1)")
        assert storage.engine.data_version > v0


class TestPinchedServerQuota:
    def test_statements_queue_or_shed_never_oom_cancel(self, env):
        """The acceptance bar: under a deliberately pinched server
        quota the 8-client replay completes; admission queues/sheds/
        rejects with the retryable 9008; NO statement dies mid-query
        with ER_MEM_EXCEED_QUOTA."""
        server, admin = env
        admin.query("CREATE TABLE pin (id BIGINT PRIMARY KEY, "
                    "g BIGINT, v BIGINT)")
        admin.query("INSERT INTO pin VALUES " + ", ".join(
            f"({i}, {i % 97}, {i % 13})" for i in range(6000)))
        agg = "SELECT g, COUNT(*), SUM(v) FROM pin GROUP BY g"
        admin.query(agg)                # record the digest's peak
        from tidb_tpu import perfschema
        peak = perfschema.digest_max_mem(agg)
        assert peak > 0
        quota = max(peak, 1 << 20)
        oom_key = 'tidb_tpu_mem_quota_exceeded_total{action="cancel"}'
        oom0 = metrics.snapshot().get(oom_key, 0)
        adm0 = sched.stats()["admission"]
        config.set_var("tidb_tpu_server_mem_quota", quota)
        config.set_var("tidb_tpu_admission_timeout_ms", 150)
        completed = []
        try:
            def client(i):
                c = MiniClient("127.0.0.1", server.port, db="test")
                try:
                    for _ in range(2):
                        tries = 0
                        while True:
                            try:
                                c.query(agg)
                                break
                            except MySQLError as e:
                                # ONLY the retryable admission code may
                                # surface; a mid-query OOM cancel
                                # (8175) fails the test right here
                                assert e.code == \
                                    errcode.ER_SERVER_BUSY_ADMISSION, e
                                tries += 1
                                assert tries < 200, "never admitted"
                                time.sleep(0.02)
                        completed.append(i)
                finally:
                    c.close()

            _fanout(N_CLIENTS, client)
        finally:
            config.set_var("tidb_tpu_server_mem_quota", 0)
        assert len(completed) == N_CLIENTS * 2      # workload completed
        adm1 = sched.stats()["admission"]
        contended = (adm1["queued"] - adm0["queued"]) + \
            (adm1["shed"] - adm0["shed"]) + \
            (adm1["rejected"] - adm0["rejected"])
        assert contended >= 1, adm1                 # the quota really bit
        assert metrics.snapshot().get(oom_key, 0) == oom0   # zero cancels
        assert memtrack.SERVER.total() >= 0


class TestStatusPort:
    def test_status_serving_block_and_shed_endpoint(self, env):
        server, admin = env
        status = StatusServer(server.storage, server)
        status.start()
        try:
            # warm an agg so the hbm-cache can hold residency: repeat
            # the SAME query (fills on the second, cache-resident scan)
            admin.query("CREATE TABLE sh (id BIGINT PRIMARY KEY, "
                        "v BIGINT)")
            admin.query("INSERT INTO sh VALUES " + ", ".join(
                f"({i}, {i % 5})" for i in range(4096)))
            for _ in range(3):
                admin.query("SELECT v, COUNT(*) FROM sh GROUP BY v")

            def get(path):
                return statusclient.get_json("127.0.0.1", status.port,
                                             path, timeout=10)

            st = get("/status")
            assert "serving" in st
            assert {"scheduler", "admission"} <= set(st["serving"])
            from tidb_tpu.store.device_cache import tracker
            resident = tracker().device
            shed = get("/shed")
            assert shed["freed_bytes"] >= resident
            # the satellite pin: one shed call returns the hbm-cache
            # ledger to zero
            assert tracker().device == 0
        finally:
            status.close()


class TestResourceMetering:
    def test_resource_usage_top_and_history(self, env):
        """The ISSUE 15 acceptance bar: under the concurrent serve
        workload, per-session device-time (resource_usage + GET /top)
        sums to the SERVER device busy-time within 10%, and the
        device-utilization series appears in GET /metrics/history."""
        from tidb_tpu import meter, metrics_history
        server, admin = env
        status = StatusServer(server.storage, server)
        status.start()
        try:
            admin.query("CREATE TABLE ru (id BIGINT PRIMARY KEY, "
                        "g BIGINT, v BIGINT)")
            admin.query("INSERT INTO ru VALUES " + ", ".join(
                f"({i}, {i % 53}, {i % 11})" for i in range(6000)))
            admin.query("SELECT g, COUNT(*), SUM(v) FROM ru GROUP BY g")

            # baseline: the meter is process-cumulative, so the 10%
            # reconciliation is over THIS leg's deltas
            srv0 = meter.SERVER.totals()
            sess0 = {s["session_id"]: s["device_ns"]
                     for s in meter.sessions_snapshot()}

            def client(i):
                c = MiniClient("127.0.0.1", server.port, db="test")
                try:
                    for _ in range(3):
                        c.query("SELECT g, COUNT(*), SUM(v) FROM ru "
                                f"WHERE id > {i} GROUP BY g")
                finally:
                    c.close()

            _fanout(4, client)
            srv1 = meter.SERVER.totals()
            busy = srv1["device_ns"] - srv0["device_ns"]
            attributed = sum(
                s["device_ns"] - sess0.get(s["session_id"], 0)
                for s in meter.sessions_snapshot())
            assert busy > 0, srv1
            assert 0.9 <= attributed / busy <= 1.1, (attributed, busy)

            # the memtable serves the same ledger
            _cols, rs = admin.query(
                "SELECT scope, session_id, device_time_ns, rows_sent "
                "FROM information_schema.resource_usage")
            scopes = {r[0] for r in rs}
            assert {"server", "user", "session"} <= scopes
            srv_row = [r for r in rs if r[0] == "server"][0]
            sess_sum = sum(int(r[2]) for r in rs if r[0] == "session")
            assert int(srv_row[2]) > 0
            assert sess_sum <= int(srv_row[2])

            def get(path):
                return statusclient.get_json("127.0.0.1", status.port,
                                             path, timeout=10)

            top = get("/top")
            assert top["server"]["device_ns"] > 0
            assert top["sessions"], top
            assert top["digests"], top
            assert 0 < top["attributed_device_ns"] \
                <= top["server"]["device_ns"] * 1.1
            # the busiest digest carries real device time
            assert top["digests"][0]["device_ns"] > 0

            # utilization history: force one sample, then the series
            # must serve on the status port
            metrics_history.sample_now()
            hist = get("/metrics/history")
            assert hist["history"]["points"] >= 1
            assert "tidb_tpu_device_utilization_ratio" in \
                hist["series"]
            for t, v in hist["series"][
                    "tidb_tpu_device_utilization_ratio"]:
                assert t > 0 and v >= 0
        finally:
            status.close()

