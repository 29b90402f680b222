"""SQL -> mesh execution: TPC-H Q1/Q3/Q5 routed onto the 8-device virtual
mesh through plain Session.execute, cross-checked against the host path.

This is the repo's copTask-pushdown-equivalent test tier (ref:
/root/reference/plan/dag_plan_test.go asserts pushdown plan shapes;
executor tests assert results) — here we assert BOTH the routed plan
shape (EXPLAIN) and result equality with the mesh disabled.
"""

import pytest

import tpch
from tidb_tpu import devplane
from tidb_tpu.executor import mesh as mesh_exec
from tidb_tpu.session import Session
from tidb_tpu.store.storage import new_mock_storage


@pytest.fixture(scope="module")
def sess():
    s = Session(new_mock_storage())
    s.execute("CREATE DATABASE tpch")
    s.execute("USE tpch")
    # seed=7: every one of Q1/Q3/Q5 has a NON-empty result (Q5 is empty
    # on the default seed, which would make result comparison vacuous)
    data = tpch.TpchData(seed=7)
    tpch.load(s, data)
    yield s
    s.close()


@pytest.fixture
def mesh():
    devplane.enable_mesh(8)
    yield devplane.active_mesh()
    devplane.disable_mesh()


def _explain(sess, sql):
    return "\n".join(r[0] for r in sess.query("EXPLAIN " + sql).rows)


class TestRouting:
    def test_q1_routes_to_mesh_agg(self, sess, mesh):
        assert "MeshAgg" in _explain(sess, tpch.Q1)

    def test_q3_q5_route_to_mesh_lookup(self, sess, mesh):
        e3 = _explain(sess, tpch.Q3)
        assert "MeshLookupAgg" in e3
        # probe must be the fact table, dims the unique-keyed ones
        assert "table:lineitem" in e3
        assert "dims:[orders,customer]" in e3
        e5 = _explain(sess, tpch.Q5)
        assert "MeshLookupAgg" in e5
        assert "dims:[" in e5

    def test_no_mesh_no_routing(self, sess):
        assert devplane.active_mesh() is None
        assert "MeshAgg" not in _explain(sess, tpch.Q1)
        assert "MeshLookupAgg" not in _explain(sess, tpch.Q3)

    def test_single_device_mesh_keeps_cop_path(self, sess):
        """A 1-device mesh must NOT reroute: sharding over one chip only
        adds gather overhead and routes scans around the storage-side
        columnar caches — the copTask path serves them fused from the
        HBM device cache (store/device_cache.py), measured 1.2-2.6x
        faster warm on Q1/Q3/Q5 (plan/mesh_route.route_mesh)."""
        devplane.enable_mesh(1)
        try:
            assert "MeshAgg" not in _explain(sess, tpch.Q1)
            assert "MeshLookupAgg" not in _explain(sess, tpch.Q3)
        finally:
            devplane.disable_mesh()


class TestResults:
    @pytest.mark.parametrize("q", ["Q1", "Q3", "Q5"])
    def test_matches_host(self, sess, mesh, q):
        sql = getattr(tpch, q)
        got = sess.query(sql).rows
        devplane.disable_mesh()
        try:
            want = sess.query(sql).rows
        finally:
            devplane.enable_mesh(8)
        assert want, "vacuous comparison: host result is empty"
        assert len(got) == len(want)
        for g, w in zip(got, want):
            assert len(g) == len(w)
            for a, b in zip(g, w):
                if isinstance(a, float) or isinstance(b, float):
                    assert float(a) == pytest.approx(float(b), rel=1e-9)
                else:
                    assert a == b

    def test_mesh_respects_txn_dirty_reads(self, sess, mesh):
        sess.execute("BEGIN")
        try:
            sess.execute("DELETE FROM region WHERE r_name = 'ASIA'")
            rows = sess.query(tpch.Q5).rows
            assert rows == []
        finally:
            sess.execute("ROLLBACK")
        assert len(sess.query(tpch.Q5).rows) > 0

    def test_capacity_escalation(self, sess, mesh, monkeypatch):
        # force the initial capacity below Q1's 6 groups: the executor
        # must re-plan with a larger table, not fall back
        monkeypatch.setattr(mesh_exec, "DEFAULT_CAPACITY", 4)
        calls = []
        orig = mesh_exec.MeshAggExec._run_with_escalation

        def spy(self, make, run):
            calls.append(1)
            return orig(self, make, run)

        monkeypatch.setattr(mesh_exec.MeshAggExec,
                            "_run_with_escalation", spy)
        rows = sess.query(tpch.Q1).rows
        assert len(rows) == 6 and calls


class TestShuffleJoinSQL:
    def test_duplicate_key_join_uses_shuffle(self, sess, mesh, monkeypatch):
        """A join with duplicate keys on both sides cannot be a lookup
        chain; with a mesh active HashJoinExec repartitions both sides
        via the all_to_all shuffle kernel instead."""
        from tidb_tpu import executor as ex
        from tidb_tpu.ops import meshshuffle as sj

        sql = ("SELECT o_custkey, COUNT(*) FROM orders, lineitem "
               "WHERE o_custkey = l_suppkey GROUP BY o_custkey "
               "ORDER BY o_custkey")
        e = _explain(sess, sql)
        assert "MeshLookupAgg" not in e and "HashJoin" in e

        devplane.disable_mesh()
        try:
            want = sess.query(sql).rows
        finally:
            devplane.enable_mesh(8)
        assert want

        monkeypatch.setattr(ex.HashJoinExec, "_DEVICE_MIN_BUILD", 64)
        monkeypatch.setattr(ex.HashJoinExec, "_DEVICE_MIN_PROBE", 64)
        used = []
        orig = sj.MeshShuffleJoinKernel.__call__

        def spy(self, *a, **kw):
            out = orig(self, *a, **kw)
            used.append(1)   # count only a SUCCESSFUL mesh join
            return out

        monkeypatch.setattr(sj.MeshShuffleJoinKernel, "__call__", spy)
        assert sess.query(sql).rows == want
        assert used, "mesh shuffle kernel was not exercised"

    def test_small_probe_skips_shuffle(self, sess, mesh, monkeypatch):
        """A tiny probe must NOT pay an all_to_all repartition even when
        the build side qualifies (advisor r2): the join falls through to
        the per-chunk single-chip paths."""
        from tidb_tpu import executor as ex
        from tidb_tpu.ops import meshshuffle as sj

        # n_regionkey is NOT unique-keyed, so this cannot become a
        # MeshLookupAgg chain — it must stay a HashJoin
        sql = ("SELECT n_name, COUNT(*) FROM nation, lineitem "
               "WHERE n_regionkey = l_suppkey GROUP BY n_name "
               "ORDER BY n_name")
        e = _explain(sess, sql)
        assert "MeshLookupAgg" not in e and "HashJoin" in e
        # probe (left) = nation: far below _DEVICE_MIN_PROBE
        monkeypatch.setattr(ex.HashJoinExec, "_DEVICE_MIN_BUILD", 64)
        used = []
        orig = sj.MeshShuffleJoinKernel.__call__

        def spy(self, *a, **kw):
            used.append(1)
            return orig(self, *a, **kw)

        monkeypatch.setattr(sj.MeshShuffleJoinKernel, "__call__", spy)
        devplane.disable_mesh()
        try:
            want = sess.query(sql).rows
        finally:
            devplane.enable_mesh(8)
        got = sess.query(sql).rows
        assert got == want and want
        assert not used, "small probe still paid the mesh shuffle"


class TestMeshAggRawReaderSchema:
    def test_stripped_reader_schema_matches_scan(self, sess, mesh):
        """PhysMeshAgg.children[0] (the agg-stripped raw scan) must carry
        the raw scan schema, not the agg output schema (advisor r2)."""
        from tidb_tpu.plan.mesh_route import PhysMeshAgg

        plan = sess.plan(tpch.Q1)

        def find(p):
            if isinstance(p, PhysMeshAgg):
                return p
            for c in p.children:
                r = find(c)
                if r is not None:
                    return r
            return None

        node = find(plan)
        assert node is not None, "Q1 did not route to MeshAgg"
        raw = node.children[0]
        assert len(raw.schema) == len(raw.cop.cols) + \
            (1 if raw.cop.handle_col is not None else 0)
        names = [c.name for c in raw.schema.cols]
        assert names[:len(raw.cop.cols)] == \
            [c.name.lower() for c in raw.cop.cols]
