"""Closing the statistics loop: auto-analyze on DML deltas + range-scan
query feedback (ref: statistics/update.go:53-135, handle.go:106)."""

import numpy as np
import pytest

from tidb_tpu.session import Domain, Session
from tidb_tpu.store.storage import new_mock_storage
from tidb_tpu.table import Table, bulkload


@pytest.fixture
def sess():
    st = new_mock_storage()
    s = Session(st)
    s.execute("CREATE DATABASE d")
    s.execute("USE d")
    yield s
    s.close()


class TestAutoAnalyze:
    def test_tick_analyzes_after_heavy_dml(self, sess):
        sess.execute("CREATE TABLE t (id BIGINT PRIMARY KEY, v BIGINT)")
        sess.execute("INSERT INTO t VALUES " + ",".join(
            f"({i},{i % 7})" for i in range(200)))
        sess.execute("ANALYZE TABLE t")
        handle = sess.domain.stats_handle()
        tid = sess.domain.info_schema().table("d", "t").id
        assert handle.get(tid).count == 200
        # +150 rows = 75% of analyzed count >= ratio 0.5
        sess.execute("INSERT INTO t VALUES " + ",".join(
            f"({i},{i % 7})" for i in range(200, 350)))
        assert handle.need_auto_analyze(tid)
        analyzed = sess.domain.auto_analyze_tick()
        assert tid in analyzed
        assert handle.get(tid).count == 350
        assert not handle.need_auto_analyze(tid)
        # second tick: nothing to do
        assert sess.domain.auto_analyze_tick() == []

    def test_never_analyzed_table_with_dml_gets_stats(self, sess):
        sess.execute("CREATE TABLE u (id BIGINT PRIMARY KEY)")
        sess.execute("INSERT INTO u VALUES (1), (2), (3)")
        tid = sess.domain.info_schema().table("d", "u").id
        assert tid in sess.domain.auto_analyze_tick()
        assert sess.domain.stats_handle().get(tid).count == 3

    def test_dropped_table_delta_cleared(self, sess):
        sess.execute("CREATE TABLE w (id BIGINT PRIMARY KEY)")
        sess.execute("INSERT INTO w VALUES (1)")
        tid = sess.domain.info_schema().table("d", "w").id
        sess.execute("DROP TABLE w")
        assert tid not in sess.domain.auto_analyze_tick()
        assert tid not in sess.domain.stats_handle()._deltas

    def test_worker_start_stop_idempotent(self, sess):
        d = sess.domain
        d.start_stats_worker(interval=3600)
        d.start_stats_worker(interval=3600)
        d.stop_stats_worker()
        d.stop_stats_worker()

    def test_interrupted_analyze_raises_and_tick_keeps_table_pending(
            self, sess):
        from tidb_tpu.executor import ExecError
        from tidb_tpu.statistics import analyze_table
        sess.execute("CREATE TABLE x (id BIGINT PRIMARY KEY)")
        sess.execute("INSERT INTO x VALUES (1), (2), (3)")
        info = sess.domain.info_schema().table("d", "x")
        with pytest.raises(ExecError, match="interrupted"):
            analyze_table(sess.storage, sess.storage.current_ts(), info,
                          interrupted=lambda: True)
        # the scan passes; the per-column build sees the probe
        probes = iter([False, False, True])
        with pytest.raises(ExecError, match="interrupted"):
            analyze_table(sess.storage, sess.storage.current_ts(), info,
                          interrupted=lambda: next(probes, True))
        assert sess.domain.auto_analyze_tick(lambda: True) == []
        assert info.id in sess.domain.stats_handle().pending_tables()

    def test_stop_cancels_analyze_in_flight_and_joins(self, sess,
                                                      monkeypatch):
        """Shutdown must not leave an ANALYZE running: the worker's
        stop event reaches the analyze as its interrupt probe and
        stop_stats_worker() returns only once the thread is gone."""
        import threading

        from tidb_tpu import statistics
        from tidb_tpu.executor import ExecError
        sess.execute("CREATE TABLE y (id BIGINT PRIMARY KEY)")
        sess.execute("INSERT INTO y VALUES (1)")
        entered = threading.Event()

        def slow_analyze(_storage, _ts, _info, interrupted=None):
            entered.set()
            while not interrupted():
                threading.Event().wait(0.01)
            raise ExecError("Query execution was interrupted")

        monkeypatch.setattr(statistics, "analyze_table", slow_analyze)
        d = sess.domain
        d.start_stats_worker(interval=0.01)
        thread = d._stats_thread
        assert entered.wait(timeout=30)
        d.stop_stats_worker()
        assert not thread.is_alive()


class TestQueryFeedback:
    def _setup(self, sess, n=10000):
        sess.execute("CREATE TABLE t (id BIGINT PRIMARY KEY, v BIGINT)")
        tbl = Table(sess.domain.info_schema().table("d", "t"),
                    sess.storage)
        bulkload.bulk_load(sess.storage, tbl, {
            "id": np.arange(n, dtype=np.int64),
            "v": np.arange(n, dtype=np.int64)})
        sess.execute("ANALYZE TABLE t")
        return sess.domain.info_schema().table("d", "t")

    def test_range_scan_corrects_stale_histogram(self, sess):
        info = self._setup(sess)
        handle = sess.domain.stats_handle()
        ts = handle.get(info.id)
        pk_id = info.col_by_name("id").id
        from tidb_tpu import ranger as rg
        dr = [rg.DatumRange(low=[0], high=[2000], high_incl=False)]
        good = ts.col_ranges_row_count(pk_id, dr)
        assert good == pytest.approx(2000, rel=0.2)
        # simulate staleness: crush the histogram to 10% of reality
        hist = ts.columns[pk_id].hist
        hist.counts = [c // 10 for c in hist.counts]
        hist.total = hist.counts[-1]
        stale = ts.col_ranges_row_count(pk_id, dr)
        assert stale < 400
        # a pure range scan observes the true cardinality
        r = sess.query("SELECT id FROM t WHERE id >= 0 AND id < 2000")
        assert len(r.rows) == 2000
        corrected = ts.col_ranges_row_count(pk_id, dr)
        assert corrected > stale * 2, (stale, corrected)

    def test_feedback_plan_flag_only_on_pure_range(self, sess):
        self._setup(sess)
        p = sess.plan("SELECT id FROM t WHERE id < 100")
        assert p.children[0].cop.feedback is not None
        # residual filter: actual counts no longer equal the range count
        p2 = sess.plan("SELECT id FROM t WHERE id < 100 AND v > 5")
        assert p2.children[0].cop.feedback is None
