"""MVCC delta store (store/delta.py): committed writes keep the
columnar/HBM cache planes hot — served as base ⋈ delta — without ever
violating snapshot isolation. Pins the consistency contract (a reader
at ts T never sees a delta committed after T, repeatable reads across a
background merge, delete-then-scan), the regression that a single-row
UPDATE no longer evicts unrelated tables' cache entries, and the
staged-bytes spill action on the SERVER root."""

import numpy as np
import pytest

from tidb_tpu import config, memtrack, metrics, sched
from tidb_tpu.session import Session
from tidb_tpu.store import delta as deltamod
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
    st.close()


def _load(sess, name, n=4000, mod=7):
    sess.execute(f"CREATE TABLE {name} (id BIGINT PRIMARY KEY, "
                 f"v BIGINT, s VARCHAR(8))")
    ti = sess.domain.info_schema().table("d", name)
    bulkload.bulk_load(sess.storage, Table(ti, sess.storage), {
        "id": np.arange(n), "v": np.arange(n) % mod,
        "s": np.array(["x", "yy", "zzz"], dtype=object)[
            np.arange(n) % 3]})
    return sum(i % mod for i in range(n))


def _served_with_delta():
    return metrics.snapshot().get(metrics.CACHE_DELTA_SERVES, 0)


class TestDeltaServe:
    def test_row_commit_does_not_bump_version(self, sess):
        total = _load(sess, "t")
        assert sess.query("SELECT SUM(v) FROM t").rows[0][0] == total
        dv0 = sess.storage.engine.data_version
        sess.execute("UPDATE t SET v = v + 10 WHERE id = 5")
        sess.execute("DELETE FROM t WHERE id = 6")
        sess.execute("INSERT INTO t VALUES (99999, 3, 'ins')")
        assert sess.storage.engine.data_version == dv0
        want = total + 10 - (6 % 7) + 3
        assert sess.query("SELECT SUM(v) FROM t").rows[0][0] == want
        assert sess.storage.delta_store.rows_current() >= 3

    def test_served_as_base_plus_delta_not_rescan(self, sess):
        total = _load(sess, "t")
        sess.query("SELECT SUM(v) FROM t")      # cache fill
        c0 = _served_with_delta()
        sess.execute("UPDATE t SET v = 0 WHERE id = 0")
        assert sess.query("SELECT SUM(v) FROM t").rows[0][0] == total
        assert _served_with_delta() > c0
        # repeated hot reads at the same delta state reuse the memo
        assert sess.query("SELECT SUM(v) FROM t").rows[0][0] == total

    def test_update_does_not_evict_unrelated_tables(self, sess):
        """Regression pin: before the delta store, ANY committed write
        bumped data_version and invalidated EVERY table's entries."""
        _load(sess, "a")
        b_total = _load(sess, "b", n=1000)
        sess.query("SELECT SUM(v) FROM a")
        sess.query("SELECT SUM(v) FROM b")
        cc = sess.storage.chunk_cache
        keys_b = {k for k in cc._entries if k[2] ==
                  sess.domain.info_schema().table("d", "b").id}
        assert keys_b
        sess.execute("UPDATE a SET v = 1 WHERE id = 1")
        assert keys_b <= set(cc._entries), \
            "table b's entries were evicted by a write to table a"
        cc.hits = cc.misses = 0
        assert sess.query("SELECT SUM(v) FROM b").rows[0][0] == b_total
        assert cc.hits >= 1 and cc.misses == 0

    def test_dict_columns_extend_incrementally(self, sess):
        _load(sess, "t")
        sess.query("SELECT s, COUNT(*) FROM t GROUP BY s")
        sess.execute("UPDATE t SET s = 'fresh' WHERE id = 0")
        rows = dict(sess.query(
            "SELECT s, COUNT(*) FROM t GROUP BY s").rows)
        assert rows["fresh"] == 1

    def test_delete_then_scan(self, sess):
        total = _load(sess, "t", n=500)
        assert sess.query("SELECT COUNT(*) FROM t").rows[0][0] == 500
        sess.execute("DELETE FROM t WHERE id < 10")
        gone = sum(i % 7 for i in range(10))
        r = sess.query("SELECT COUNT(*), SUM(v) FROM t").rows[0]
        assert r == (490, total - gone)
        sess.execute("DELETE FROM t")
        assert sess.query("SELECT COUNT(*) FROM t").rows[0][0] == 0
        assert sess.query("SELECT SUM(v) FROM t").rows[0][0] is None


class TestDeltaMVCC:
    def test_reader_at_t_never_sees_later_delta(self, sess):
        total = _load(sess, "t")
        s2 = Session(sess.storage, db="d")
        s2.execute("BEGIN")
        assert s2.query("SELECT SUM(v) FROM t").rows[0][0] == total
        sess.execute("UPDATE t SET v = v + 100 WHERE id = 1")
        sess.execute("DELETE FROM t WHERE id = 2")
        # the old snapshot re-reads its own view, repeatedly
        for _ in range(3):
            assert s2.query("SELECT SUM(v) FROM t").rows[0][0] == total
        s2.execute("COMMIT")
        want = total + 100 - (2 % 7)
        assert s2.query("SELECT SUM(v) FROM t").rows[0][0] == want
        s2.close()

    def test_repeatable_reads_across_background_merge(self, sess):
        total = _load(sess, "t")
        sess.query("SELECT SUM(v) FROM t")
        sess.execute("UPDATE t SET v = v + 1 WHERE id < 50")
        s2 = Session(sess.storage, db="d")
        s2.execute("BEGIN")
        assert s2.query("SELECT SUM(v) FROM t").rows[0][0] == total + 50
        sess.execute("UPDATE t SET v = v + 1 WHERE id < 20")
        folded = sess.storage.delta_store.merge(trigger="rows")
        assert folded > 0
        # the merge promoted newer bases; the old reader must either
        # keep serving its snapshot or transparently re-scan — never
        # see the post-snapshot writes
        assert s2.query("SELECT SUM(v) FROM t").rows[0][0] == total + 50
        s2.execute("COMMIT")
        assert s2.query("SELECT SUM(v) FROM t").rows[0][0] == total + 70
        s2.close()

    def test_merge_truncates_journal_and_metric(self, sess):
        _load(sess, "t")
        sess.query("SELECT SUM(v) FROM t")
        sess.execute("UPDATE t SET v = 0 WHERE id = 3")
        sess.query("SELECT SUM(v) FROM t")    # memoize base⋈delta
        st = sess.storage
        assert st.delta_store.rows_current() >= 1
        snap0 = metrics.snapshot().get(
            metrics.DELTA_MERGES + '{trigger="rows"}', 0)
        assert st.delta_store.merge(trigger="rows") >= 1
        assert st.delta_store.rows_current() == 0
        assert metrics.snapshot().get(
            metrics.DELTA_MERGES + '{trigger="rows"}', 0) == snap0 + 1

    def test_locked_range_veto(self, sess):
        """A pending lock a reader must observe routes the range to the
        real scan path; the cached entries survive the write."""
        from tidb_tpu import tablecodec
        _load(sess, "t", n=100)
        sess.query("SELECT SUM(v) FROM t")
        engine = sess.storage.engine
        tid = sess.domain.info_schema().table("d", "t").id
        s, e = tablecodec.table_prefix_range(tid)
        ts = sess.storage.current_ts()
        assert not engine.locked_in_range(s, e, ts)
        from tidb_tpu.kv import Mutation, MutationOp
        key = tablecodec.record_key(tid, 1)
        engine.prewrite([Mutation(MutationOp.PUT, key, b"x")],
                        key, ts, ttl_ms=30000)
        assert engine.locked_in_range(s, e, sess.storage.current_ts())
        # an OLDER reader (snapshot before the lock's txn) is not blocked
        assert not engine.locked_in_range(s, e, ts - 1)
        engine.rollback([key], ts)
        assert not engine.locked_in_range(s, e,
                                          sess.storage.current_ts())

    def test_lease_commits_do_not_veto_cache_fills(self, sess):
        """A live server's lease workers commit ephemeral meta keys
        about once a second. They lie in no table range, so they move
        neither max_commit_ts nor the cache-veto lock set: a snapshot
        taken BEFORE such a commit still fills the chunk cache (it
        used to lose that race on every scan longer than one tick, so
        a serving process never warmed a real-size region)."""
        from tidb_tpu.kv import Mutation, MutationOp
        total = _load(sess, "t")
        st = sess.storage
        engine, cc = st.engine, st.chunk_cache
        sess.execute("BEGIN")
        sess.query("SELECT 1")          # pins the snapshot ts
        mc0 = engine.max_commit_ts
        lease = st.begin()
        lease.set(b"m_owner_ddl", b"lease")
        lease.commit()
        assert engine.max_commit_ts == mc0
        ts = st.current_ts()
        engine.prewrite([Mutation(MutationOp.PUT, b"m_member_x", b"hb")],
                        b"m_member_x", ts, ttl_ms=30000)
        assert not engine._locked_keys      # pending, but vetoes nothing
        assert sess.query("SELECT SUM(v) FROM t").rows[0][0] == total
        engine.rollback([b"m_member_x"], ts)
        sess.execute("COMMIT")
        assert len(cc._entries) >= 1        # the older snapshot filled
        hits0 = cc.hits
        assert sess.query("SELECT SUM(v) FROM t").rows[0][0] == total
        assert cc.hits > hits0
        # an auto-analyze save is the same class: statistics, no row
        dv0 = engine.data_version
        sess.execute("ANALYZE TABLE t")
        assert engine.data_version == dv0
        assert sess.query("SELECT SUM(v) FROM t").rows[0][0] == total
        assert cc.hits > hits0 + 1
        # a ROW commit still moves the fill contract's watermark
        sess.execute("UPDATE t SET v = v WHERE id = 1")
        assert engine.max_commit_ts > mc0

    def test_index_commit_invalidates_index_entries_only(self, sess):
        _load(sess, "a")
        sess.execute("CREATE TABLE ix (id BIGINT PRIMARY KEY, "
                     "v BIGINT)")
        sess.execute("CREATE INDEX iv ON ix (v)")
        for i in range(40):
            sess.execute(f"INSERT INTO ix VALUES ({i}, {i % 5})")
        sess.query("SELECT SUM(v) FROM a")
        assert sess.query(
            "SELECT COUNT(*) FROM ix WHERE v = 2").rows[0][0] == 8
        cc = sess.storage.chunk_cache
        a_id = sess.domain.info_schema().table("d", "a").id
        keys_a = {k for k in cc._entries if k[2] == a_id}
        sess.execute("UPDATE ix SET v = 0 WHERE id = 2")
        # index reads stay correct after the index-key commit
        assert sess.query(
            "SELECT COUNT(*) FROM ix WHERE v = 2").rows[0][0] == 7
        # ...and table a's entries were untouched by ix's write
        assert keys_a <= set(cc._entries)

    def test_disabled_reverts_to_legacy_invalidation(self, sess):
        _load(sess, "t", n=200)
        prev = config.get_var("tidb_tpu_delta_store")
        config.set_var("tidb_tpu_delta_store", 0)
        try:
            dv0 = sess.storage.engine.data_version
            sess.execute("UPDATE t SET v = 9 WHERE id = 0")
            assert sess.storage.engine.data_version > dv0
            assert sess.query(
                "SELECT SUM(v) FROM t").rows[0][0] is not None
        finally:
            config.set_var("tidb_tpu_delta_store", prev)

    def test_disable_flip_flushes_staged_journal(self, sess):
        """Flipping the store OFF with staged (journaled, never
        version-bumped) deltas must not leave cached entries serving
        pre-update data: the first consult after the flip flushes the
        journal and bumps the structural version once."""
        total = _load(sess, "t", n=300)
        sess.query("SELECT SUM(v) FROM t")      # cache fill
        sess.execute("UPDATE t SET v = v + 7 WHERE id = 0")
        assert sess.storage.delta_store.rows_current() >= 1
        prev = config.get_var("tidb_tpu_delta_store")
        config.set_var("tidb_tpu_delta_store", 0)
        try:
            assert sess.query(
                "SELECT SUM(v) FROM t").rows[0][0] == total + 7
            assert sess.storage.delta_store.rows_current() == 0
        finally:
            config.set_var("tidb_tpu_delta_store", prev)


class TestStagingAndShed:
    def test_staged_bytes_on_server_root_and_shed(self, sess):
        _load(sess, "t")
        sess.query("SELECT SUM(v) FROM t")
        sess.execute("UPDATE t SET v = 0 WHERE id < 30")
        sess.query("SELECT SUM(v) FROM t")    # memoize for the fold
        st = sess.storage
        staged = st.delta_store.staged_bytes()
        assert staged > 0
        assert deltamod.tracker().host >= staged
        shed0 = metrics.snapshot().get(
            metrics.DELTA_MERGES + '{trigger="shed"}', 0)
        # the SERVER shed chain (GET /shed, admission overflow) forces
        # an early merge that frees the staged journal bytes. The chain
        # sheds EVERY live store (other suites' storages linger until
        # GC), so the counter moves by at least one, not exactly one.
        sched.shed_server(0)
        assert st.delta_store.staged_bytes() == 0
        assert metrics.snapshot().get(
            metrics.DELTA_MERGES + '{trigger="shed"}', 0) >= shed0 + 1
        # reads stay correct after the forced merge
        want = sum(i % 7 for i in range(30, 4000))
        assert sess.query("SELECT SUM(v) FROM t").rows[0][0] == want

    def test_row_threshold_triggers_background_merge(self, sess):
        _load(sess, "t", n=600)
        sess.query("SELECT SUM(v) FROM t")
        prev = config.get_var("tidb_tpu_delta_merge_rows")
        config.set_var("tidb_tpu_delta_merge_rows", 8)
        try:
            for i in range(12):
                sess.execute(f"UPDATE t SET v = {i} WHERE id = {i}")
                sess.query("SELECT SUM(v) FROM t")   # keep memo fresh
            import time
            for _ in range(100):
                if sess.storage.delta_store.rows_current() < 12:
                    break
                time.sleep(0.05)
            assert sess.storage.delta_store.rows_current() < 12, \
                "background merge never fired past the row threshold"
        finally:
            config.set_var("tidb_tpu_delta_merge_rows", prev)

    def test_close_releases_ledger(self):
        st = new_mock_storage()
        s = Session(st)
        s.execute("CREATE DATABASE d2; USE d2")
        s.execute("CREATE TABLE t (id BIGINT PRIMARY KEY, v BIGINT)")
        s.execute("INSERT INTO t VALUES (1, 1)")
        s.execute("UPDATE t SET v = 2 WHERE id = 1")
        before = deltamod.tracker().host
        staged = st.delta_store.staged_bytes()
        assert staged > 0
        s.close()
        st.close()
        assert deltamod.tracker().host == before - staged


class TestDeviceDeltaPatch:
    def test_hbm_block_patched_in_place(self, sess):
        """An UPDATE folds into the resident device block (fill_ts
        advances, same entry) instead of dropping it."""
        _load(sess, "t")
        # twice: a cold streamed read fills the host cache at stream
        # end; the device block fills on the first cache-resident serve
        sess.query("SELECT SUM(v) FROM t")
        sess.query("SELECT SUM(v) FROM t")
        dc = sess.storage.device_cache
        if len(dc) == 0:
            pytest.skip("device path off in this environment")
        tid = sess.domain.info_schema().table("d", "t").id
        snap0 = {k: ts for k, _dv, ts in dc.snapshot_table(tid)}
        sess.execute("UPDATE t SET v = v + 5 WHERE id = 7")
        total = sess.query("SELECT SUM(v) FROM t").rows[0][0]
        assert total == sum(i % 7 for i in range(4000)) + 5
        snap1 = {k: ts for k, _dv, ts in dc.snapshot_table(tid)}
        advanced = [k for k, ts in snap1.items()
                    if k in snap0 and ts > snap0[k]]
        assert advanced, "no resident block advanced its fill_ts"

    def test_insert_lands_in_padding_tail(self, sess):
        _load(sess, "t", n=100)
        sess.query("SELECT COUNT(*), SUM(v) FROM t")
        for i in range(5):
            sess.execute(f"INSERT INTO t VALUES ({1000 + i}, 1, 'n')")
        r = sess.query("SELECT COUNT(*), SUM(v) FROM t").rows[0]
        assert r == (105, sum(i % 7 for i in range(100)) + 5)
