"""Hierarchical memory tracking (memtrack.py): per-query host+HBM
accounting, tidb_tpu_mem_quota_query enforcement with the spill/cancel
OOM-action chain, cross-query isolation (no watermark bleed), and the
observability surfaces (EXPLAIN ANALYZE mem, SHOW PROCESSLIST,
information_schema.memory_usage, digest max_mem, metrics)."""

import re
import threading

import pytest

from tidb_tpu import memtrack, metrics
from tidb_tpu.session import Session, SQLError
from tidb_tpu.store.storage import new_mock_storage


# -- unit: the tracker tree -------------------------------------------------


class TestTracker:
    def test_rollup_peaks_and_ledgers(self):
        root = memtrack.MemTracker("root")
        sess = memtrack.statement_root(root, label="s")
        op = sess.node(object())
        op.consume(host=100, device=40)
        assert (op.host, op.device) == (100, 40)
        assert (sess.host, sess.device) == (100, 40)
        assert (root.host, root.device) == (100, 40)
        op.release(host=60)
        assert root.host == 40 and root.host_peak == 100
        assert root.device == 40 and root.device_peak == 40

    def test_detach_zeroes_the_parent(self):
        root = memtrack.MemTracker("root")
        sess = memtrack.statement_root(root, label="s")
        sess.node(object()).consume(host=512, device=64)
        sess.detach()
        assert root.total() == 0
        # peaks survive for post-mortem readers
        assert root.host_peak == 512 and sess.host_peak == 512

    def test_quota_fires_spill_then_cancel(self):
        root = memtrack.statement_root(None, label="q")
        root.quota = 1000
        shed = []

        def spill():
            shed.append(True)
            root.release(host=900)

        root.add_spill_action(spill)
        root.consume(host=950)
        root.consume(host=200)          # crosses: spill sheds 900
        assert shed and root.total() == 250
        root.remove_spill_action(spill)
        with pytest.raises(memtrack.QuotaExceededError,
                           match="Out Of Memory Quota"):
            root.consume(host=2000)

    def test_spill_action_is_rearmed(self):
        root = memtrack.statement_root(None, label="q")
        root.quota = 100
        fired = []
        root.add_spill_action(lambda: (fired.append(1),
                                       root.release(host=root.host)))
        root.consume(host=150)
        root.consume(host=150)
        assert len(fired) == 2

    def test_track_to_moves_absolute(self):
        root = memtrack.statement_root(None, label="t")
        plan = object()
        with memtrack.tracking(root):
            prev = memtrack.track_to(plan, 500)
            prev = memtrack.track_to(plan, 200, prev)
            assert root.total() == 200 and root.host_peak == 500
            memtrack.release(plan, host=prev)
        assert root.total() == 0

    def test_suspended_hides_the_tracker(self):
        root = memtrack.statement_root(None, label="t")
        with memtrack.tracking(root):
            with memtrack.suspended():
                memtrack.consume(object(), host=999)
        assert root.total() == 0


# -- session fixtures -------------------------------------------------------


@pytest.fixture(scope="module")
def store():
    st = new_mock_storage()
    s = Session(st)
    s.execute("CREATE DATABASE d; USE d")
    s.execute("CREATE TABLE t (id BIGINT PRIMARY KEY, a BIGINT, "
              "b BIGINT, v BIGINT)")
    vals = ",".join(f"({i},{i * 3 % 997},{i * 7 % 997},{i % 7})"
                    for i in range(3000))
    s.execute("INSERT INTO t VALUES " + vals)
    s.query("SELECT * FROM t ORDER BY a")          # warm compile/caches
    s.query("SELECT id, COUNT(*) FROM t GROUP BY id LIMIT 1")
    yield st
    s.close()


@pytest.fixture
def sess(store):
    s = Session(store, db="d")
    yield s
    s.execute("SET tidb_tpu_mem_quota_query = 0")
    s.close()


def _quota_count(action: str) -> float:
    return metrics.snapshot().get(
        'tidb_tpu_mem_quota_exceeded_total{action="%s"}' % action, 0)


# -- quota enforcement ------------------------------------------------------


class TestQuota:
    def test_sort_spills_instead_of_cancel(self, sess):
        """The plan contains a SpillSorter: crossing the quota sheds the
        buffered rows to disk, the query COMPLETES, and the tracker
        drops back (session root zero afterwards)."""
        before = _quota_count("spill")
        # 3000 rows x 4 bigint cols ~ 100KB buffered; keys ~27KB stay
        sess.execute("SET tidb_tpu_mem_quota_query = 60000")
        rows = sess.query("SELECT * FROM t ORDER BY a").rows
        assert len(rows) == 3000
        assert _quota_count("spill") > before
        assert sess.mem_tracker.total() == 0

    def test_hash_agg_over_quota_cancels(self, sess):
        before = _quota_count("cancel")
        sess.execute("SET tidb_tpu_mem_quota_query = 20000")
        with pytest.raises(SQLError, match="Out Of Memory Quota"):
            sess.query("SELECT id, COUNT(*) FROM t GROUP BY id")
        assert _quota_count("cancel") > before
        # session survives; the next (unquota'd) statement runs clean
        sess.execute("SET tidb_tpu_mem_quota_query = 0")
        assert sess.query("SELECT COUNT(*) FROM t").rows == [(3000,)]
        assert sess.mem_tracker.total() == 0

    def test_join_over_quota_cancels(self, sess):
        sess.execute("SET tidb_tpu_mem_quota_query = 20000")
        with pytest.raises(SQLError, match="Out Of Memory Quota"):
            sess.query("SELECT COUNT(*) FROM t x JOIN t y ON x.a = y.b")
        sess.execute("SET tidb_tpu_mem_quota_query = 0")
        assert sess.mem_tracker.total() == 0

    def test_worker_thread_cancel_surfaces_quota_error(self, store):
        """With a multi-region fan-out the quota usually trips inside a
        cop pool worker; the session thread's cooperative-kill check
        races the worker's exception — the client must still see the
        quota message (ER_MEM_EXCEED_QUOTA), never a generic
        'interrupted', and the root must come back to zero."""
        s = Session(store, db="d")
        try:
            s.query("SPLIT TABLE t REGIONS 8")
            s.execute("SET tidb_tpu_mem_quota_query = 20000")
            with pytest.raises(SQLError, match="Out Of Memory Quota"):
                s.query("SELECT id, COUNT(*) FROM t GROUP BY id")
            s.execute("SET tidb_tpu_mem_quota_query = 0")
            assert s.mem_tracker.total() == 0
        finally:
            s.close()

    def test_cancel_rolls_back_the_txn(self, sess):
        sess.execute("BEGIN")
        sess.execute("INSERT INTO t VALUES (99999, 1, 1, 1)")
        sess.execute("SET tidb_tpu_mem_quota_query = 20000")
        with pytest.raises(SQLError, match="Out Of Memory Quota"):
            sess.query("SELECT id, COUNT(*) FROM t GROUP BY id")
        assert sess.txn is None
        sess.execute("SET tidb_tpu_mem_quota_query = 0")
        assert sess.query(
            "SELECT COUNT(*) FROM t WHERE id = 99999").rows == [(0,)]

    def test_quota_error_classifies_as_mem_exceed(self):
        from tidb_tpu import errcode
        errno, state, _msg = errcode.classify(
            SQLError("Out Of Memory Quota! query tracked 9 bytes > "
                     "tidb_tpu_mem_quota_query 1"))
        assert errno == errcode.ER_MEM_EXCEED_QUOTA
        assert state == "HY000"


# -- release-on-close / leak check (util/testleak.py pattern) ---------------


class TestLeak:
    @pytest.mark.parametrize("sql", [
        "SELECT * FROM t ORDER BY a LIMIT 7",
        "SELECT v, SUM(a) FROM t GROUP BY v",
        "SELECT COUNT(*) FROM t x JOIN t y ON x.a = y.b",
        "EXPLAIN ANALYZE SELECT v, COUNT(*) FROM t GROUP BY v",
    ])
    def test_session_root_zero_after_each_statement(self, sess, sql):
        sess.query(sql)
        assert sess.mem_tracker.total() == 0, sql
        # and the statement root credited everything it ever held
        assert sess._last_mem.peak_total() > 0, sql


# -- isolation + surfaces ---------------------------------------------------


_UNITS = {"B": 1, "KB": 1 << 10, "MB": 1 << 20, "GB": 1 << 30}


def _parse_mem(cell: str) -> int:
    m = re.fullmatch(r"([0-9.]+)(B|KB|MB|GB)", cell)
    assert m, cell
    return int(float(m.group(1)) * _UNITS[m.group(2)])


# what `SELECT id, COUNT(*) FROM t GROUP BY id` holds at its root merge:
# 3,000 groups as columns (HashAggregator.approx_bytes), each an int64
# key, its validity byte, the COUNT lane and the group's row count
_GROUPS_BYTES = 3000 * (8 + 1 + 8 + 8)


class TestIsolation:
    def test_explain_analyze_mem_is_tracked_and_ungated(self, sess):
        """mem renders real tracked bytes with host collection alone —
        no tidb_tpu_runtime_stats_device needed any more."""
        rs = sess.query(
            "EXPLAIN ANALYZE SELECT v, SUM(a) FROM t GROUP BY v")
        mem_i = rs.columns.index("mem")
        cells = [r[mem_i] for r in rs.rows]
        assert all(c != "-" for c in cells), cells
        assert any(_parse_mem(c) > 0 for c in cells), cells

    def test_idle_session_mem_stays_near_zero(self, store):
        """The busy session's hash build must NOT inflate the idle
        session's mem column (the process-global watermark did exactly
        that). Sequential here; the threaded variant below races them."""
        busy = Session(store, db="d")
        idle = Session(store, db="d")
        try:
            busy.query("SELECT id, COUNT(*) FROM t GROUP BY id")
            assert busy._last_mem.host_peak >= _GROUPS_BYTES > 64 << 10
            rs = idle.query(
                "EXPLAIN ANALYZE SELECT COUNT(*) FROM t WHERE id = 1")
            mem_i = rs.columns.index("mem")
            for r in rs.rows:
                assert _parse_mem(r[mem_i]) < 64 << 10, r
        finally:
            busy.close()
            idle.close()

    def test_concurrent_no_bleed(self, store):
        busy = Session(store, db="d")
        idle = Session(store, db="d")
        done = threading.Event()

        def run_busy():
            try:
                busy.query("SELECT id, COUNT(*) FROM t GROUP BY id")
            finally:
                done.set()

        t = threading.Thread(target=run_busy, name="memtrack-busy")
        t.start()
        try:
            rs = idle.query(
                "EXPLAIN ANALYZE SELECT COUNT(*) FROM t WHERE id = 1")
            mem_i = rs.columns.index("mem")
            for r in rs.rows:
                assert _parse_mem(r[mem_i]) < 64 << 10, r
        finally:
            done.wait(30)
            t.join(30)
            busy.close()
            idle.close()

    def test_memory_usage_memtable_attributes_sessions(self, store):
        busy = Session(store, db="d")
        probe = Session(store, db="d")
        try:
            busy.query("SELECT id, COUNT(*) FROM t GROUP BY id")
            rs = probe.query(
                "SELECT scope, session_id, peak_host_bytes, "
                "peak_device_bytes FROM information_schema.memory_usage")
            assert ("server", 0) in [(r[0], r[1]) for r in rs.rows]
            by_sid = {r[1]: r for r in rs.rows if r[0] == "session"}
            assert by_sid[busy.session_id][2] >= _GROUPS_BYTES
            # the probe session only ever ran tiny statements
            assert by_sid[probe.session_id][2] < \
                by_sid[busy.session_id][2]
        finally:
            busy.close()
            probe.close()

    def test_mesh_path_is_tracked(self, store):
        """The mesh-routed aggregation path must bill the trackers too —
        quota and the mem column cannot have a blind spot on the mesh."""
        from tidb_tpu import devplane
        s = Session(store, db="d")
        devplane.enable_mesh(8)
        try:
            rs = s.query(
                "EXPLAIN ANALYZE SELECT a, SUM(v) FROM t GROUP BY a")
            mesh_rows = [r for r in rs.rows if "MeshAgg" in r[0]]
            if mesh_rows:   # planner routed to the mesh
                mem_i = rs.columns.index("mem")
                assert _parse_mem(mesh_rows[0][mem_i]) > 0, mesh_rows
            assert s.mem_tracker.total() == 0
        finally:
            devplane.disable_mesh()
            s.close()

    def test_processlist_mem_column(self, sess):
        rs = sess.query("SHOW PROCESSLIST")
        mem_idx = rs.columns.index("Mem")
        me = [r for r in rs.rows if r[0] == sess.session_id]
        assert me and isinstance(me[0][mem_idx], int)

    def test_digest_summary_max_mem(self, sess):
        sess.query("SELECT v, SUM(b) FROM t GROUP BY v")
        rows = sess.query(
            "SELECT digest_text, max_mem_bytes FROM "
            "performance_schema.events_statements_summary_by_digest").rows
        mine = [r for r in rows if "SUM" in r[0].upper()
                and "summary" not in r[0]]
        assert mine and mine[0][1] > 0

    def test_query_mem_gauges_emitted(self, sess):
        sess.query("SELECT v, SUM(a) FROM t GROUP BY v")
        snap = metrics.snapshot()
        assert snap.get('tidb_tpu_query_mem_bytes{kind="host"}', 0) > 0
        assert 'tidb_tpu_device_peak_bytes' in snap

    def test_slow_log_mem_line(self, sess, caplog):
        import logging
        from tidb_tpu import config
        old = config.get_var("tidb_tpu_slow_query_ms")
        config.set_var("tidb_tpu_slow_query_ms", 0)
        try:
            with caplog.at_level(logging.WARNING,
                                 logger="tidb_tpu.slow_query"):
                sess.query("SELECT v, COUNT(*) FROM t GROUP BY v")
        finally:
            config.set_var("tidb_tpu_slow_query_ms", old)
        recs = [r.getMessage() for r in caplog.records
                if "slow query" in r.getMessage()]
        assert recs and "# Mem: " in recs[-1]
        assert "host=" in recs[-1] and "device=" in recs[-1]
