"""Skew-aware, spill-capable hybrid hash join & partitioned agg
(ops/hybrid.py): partition-exact pair matching against the host
matcher, heavy-hitter routing (CMSketch-seeded and stream-promoted),
per-partition capacity/collision retry for aggregation, quota-pressure
partition spill (completes, never ER_MEM_EXCEED_QUOTA), and the
fallback observability surfaces."""

import numpy as np
import pytest

from tidb_tpu import config, memtrack, metrics
from tidb_tpu.chunk import Chunk, Column
from tidb_tpu.expression import AggDesc, AggFunc
from tidb_tpu.expression.core import ColumnRef
from tidb_tpu.ops import hybrid
from tidb_tpu.ops.hashagg import CapacityError, CollisionError, kernel_for
from tidb_tpu.ops.hostagg import host_hash_agg
from tidb_tpu.ops.join import JoinKernel, host_match_pairs
from tidb_tpu.session import Session
from tidb_tpu.sqltypes import FieldType, TypeCode
from tidb_tpu.store.storage import new_mock_storage

FT_I = FieldType(tp=TypeCode.LONGLONG)
FT_D = FieldType(tp=TypeCode.DOUBLE)


def _metric(prefix: str) -> float:
    return sum(v for k, v in metrics.snapshot().items()
               if k.startswith(prefix))


def _pairs_via_hybrid(hyb: hybrid.HybridJoinBuild, kernel, pk, n):
    """Drive route/ensure/dispatch/finalize by hand; -> set of global
    (probe, build) pairs."""
    hp, tasks = hyb.route(pk, n)
    out = set()
    for p, idx in tasks:
        dev = hyb.ensure(p)
        rows = hyb.build_rows(p)
        sub = [(d[idx], v[idx]) for d, v in pk]
        cap = hyb.hot_out_cap(hp[idx]) if p == hyb.parts else None
        tok = kernel.dispatch(None, sub, len(rows), len(idx),
                              out_cap=cap, build_dev=dev)
        li_l, ri_l = kernel.finalize(tok)
        out.update(zip(idx[li_l].tolist(), rows[ri_l].tolist()))
    return out


def _host_pairs(bk, pk, nb, n):
    li, ri = host_match_pairs(bk, pk, nb, n)
    return set(zip(li.tolist(), ri.tolist()))


class TestPartitionedPairs:
    """Device==host pair sets through the partitioned matcher on the
    capacity-sensitive shapes the ISSUE names."""

    @pytest.mark.parametrize("n", [1024, 2048, 4096])  # pow2 boundaries
    def test_pow2_boundary(self, n):
        rng = np.random.default_rng(7)
        nb = 4096
        bk = [(np.arange(nb, dtype=np.int64), np.ones(nb, bool))]
        pk = [(rng.integers(0, nb + 64, n).astype(np.int64),
               np.ones(n, bool))]
        kernel = JoinKernel(1)
        hyb = hybrid.HybridJoinBuild(kernel, bk, nb, parts=4,
                                     plan=object(), threshold=0)
        try:
            assert _pairs_via_hybrid(hyb, kernel, pk, n) == \
                _host_pairs(bk, pk, nb, n)
        finally:
            hyb.close()

    def test_all_one_key(self):
        """Every probe row carries THE one key: the worst skew there
        is — the single partition holding it must still match exactly
        (and with a threshold, the hot lane takes it wholesale)."""
        nb, n = 4096, 3000
        bk = [(np.arange(nb, dtype=np.int64), np.ones(nb, bool))]
        pk = [(np.full(n, 17, dtype=np.int64), np.ones(n, bool))]
        kernel = JoinKernel(1)
        want = _host_pairs(bk, pk, nb, n)
        for threshold in (0, 100):       # plain partition vs hot lane
            hyb = hybrid.HybridJoinBuild(kernel, bk, nb, parts=4,
                                         plan=object(),
                                         threshold=threshold)
            try:
                if threshold:
                    promo = hyb.observe(hybrid.probe_hashes(pk, n))
                    if promo is not None:
                        assert hyb.promote(promo)
                assert _pairs_via_hybrid(hyb, kernel, pk, n) == want
                if threshold:
                    assert hyb.hot_rows == n
            finally:
                hyb.close()

    def test_null_keys_match_nothing(self):
        rng = np.random.default_rng(8)
        nb, n = 4096, 5000
        bv = rng.random(nb) > 0.1        # some NULL build rows
        pv = rng.random(n) > 0.3         # many NULL probe rows
        bk = [(np.arange(nb, dtype=np.int64), bv)]
        pk = [(rng.integers(0, nb, n).astype(np.int64), pv)]
        kernel = JoinKernel(1)
        hyb = hybrid.HybridJoinBuild(kernel, bk, nb, parts=4,
                                     plan=object(), threshold=0)
        try:
            got = _pairs_via_hybrid(hyb, kernel, pk, n)
        finally:
            hyb.close()
        assert got == _host_pairs(bk, pk, nb, n)
        assert all(pv[li] and bv[ri] for li, ri in got)

    def test_cms_seeded_hot_routing(self):
        """A probe-side CMSketch with one heavy value seeds the hot set
        at detection time (the statistics.CMSketch leg), and probe rows
        of that key route through the broadcast lane."""
        from tidb_tpu.statistics import CMSketch, cm_key
        rng = np.random.default_rng(9)
        nb, n = 4096, 6000
        bk = [(np.arange(nb, dtype=np.int64), np.ones(nb, bool))]
        cid = rng.integers(0, nb, n)
        cid[rng.random(n) < 0.5] = 99
        pk = [(cid.astype(np.int64), np.ones(n, bool))]
        cms = CMSketch()
        for v, c in zip(*np.unique(cid, return_counts=True)):
            cms.insert(cm_key(int(v)), int(c))
        h = hybrid.build_hashes(bk, nb)
        hot = hybrid.detect_hot_hashes(h, threshold=1000,
                                       raw_key=bk[0], probe_cms=cms)
        assert hot.size >= 1
        kernel = JoinKernel(1)
        hyb = hybrid.HybridJoinBuild(kernel, bk, nb, parts=4,
                                     plan=object(), hot_hashes=hot,
                                     threshold=1000, h=h)
        try:
            got = _pairs_via_hybrid(hyb, kernel, pk, n)
            assert hyb.hot_rows >= int((cid == 99).sum())
        finally:
            hyb.close()
        assert got == _host_pairs(bk, pk, nb, n)

    def test_build_side_duplication_goes_hot(self):
        """Exact build-side dup counts alone (no sketch) classify a
        many-to-many hot key."""
        nb = 4096
        key = np.arange(nb, dtype=np.int64)
        key[:2000] = 5                     # 2000 duplicate build rows
        h = hybrid.build_hashes([(key, np.ones(nb, bool))], nb)
        hot = hybrid.detect_hot_hashes(h, threshold=1000)
        assert hot.size == 1


class TestPartitionedAgg:
    def _chunk(self, k, amt=None, valid=None):
        n = len(k)
        amt = amt if amt is not None else np.arange(n, dtype=np.float64)
        valid = valid if valid is not None else np.ones(n, bool)
        return Chunk([Column(FT_I, np.asarray(k, np.int64), valid),
                      Column(FT_D, amt, np.ones(n, bool))])

    def _exprs(self):
        g = ColumnRef(0, FT_I, name="k")
        aggs = [AggDesc(fn=AggFunc.COUNT, arg=None),
                AggDesc(fn=AggFunc.SUM, arg=ColumnRef(1, FT_D,
                                                      name="amt"))]
        return g, aggs

    @staticmethod
    def _norm(gr):
        return {key: (int(gr.partials[0][0][i]),
                      round(float(gr.partials[1][0][i]), 6))
                for i, key in enumerate(gr.keys)}

    @pytest.mark.parametrize("case", ["highcard", "onekey", "nulls",
                                      "pow2"])
    def test_matches_host(self, case):
        rng = np.random.default_rng(11)
        if case == "highcard":
            chunk = self._chunk(rng.integers(0, 9000, 50000))
        elif case == "onekey":
            chunk = self._chunk(np.full(4096, 3))
        elif case == "nulls":
            chunk = self._chunk(rng.integers(0, 500, 8192),
                                valid=rng.random(8192) > 0.25)
        else:
            chunk = self._chunk(rng.integers(0, 6000, 16384))
        g, aggs = self._exprs()
        gr = hybrid.partitioned_agg(chunk, None, [g], aggs, object(),
                                    parts=4)
        assert self._norm(gr) == \
            self._norm(host_hash_agg(chunk, None, [g], aggs))

    def test_agg_retry_from_real_capacity_error(self):
        rng = np.random.default_rng(12)
        chunk = self._chunk(rng.integers(0, 9000, 40000))
        g, aggs = self._exprs()
        k = kernel_for(None, [g], aggs, capacity=64)
        with pytest.raises(CapacityError) as ei:
            k(chunk)
        gr = hybrid.agg_retry(chunk, None, [g], aggs, object(),
                              ei.value)
        assert self._norm(gr) == \
            self._norm(host_hash_agg(chunk, None, [g], aggs))

    def test_collision_retries_per_partition(self, monkeypatch):
        """A CollisionError strands ONE partition on the host; the rest
        stay on device, the merged result is exact, and the fallback is
        counted with reason=collision."""
        rng = np.random.default_rng(13)
        chunk = self._chunk(rng.integers(0, 2000, 20000))
        g, aggs = self._exprs()
        real = hybrid.kernel_for
        state = {"failed": 0}

        def flaky(filter_expr, group_exprs, aggs_, capacity=4096):
            k = real(filter_expr, group_exprs, aggs_, capacity=capacity)
            if state["failed"] == 0:
                state["failed"] = 1

                class Once:
                    def dispatch_nbytes(self, c):
                        return k.dispatch_nbytes(c)

                    def __call__(self, c, dev_cols=None):
                        raise CollisionError("forced")
                return Once()
            return k

        monkeypatch.setattr(hybrid, "kernel_for", flaky)
        before = _metric(metrics.DEVICE_FALLBACKS)
        gr = hybrid.partitioned_agg(chunk, None, [g], aggs, object(),
                                    parts=4, reason="collision")
        assert self._norm(gr) == \
            self._norm(host_hash_agg(chunk, None, [g], aggs))
        assert state["failed"] == 1
        assert _metric(metrics.DEVICE_FALLBACKS) == before + 1
        snap = metrics.snapshot()
        assert any("reason=\"collision\"" in key.replace("'", "\"")
                   for key in snap if key.startswith(
                       metrics.DEVICE_FALLBACKS))


class TestQuotaSpill:
    def test_spill_action_sheds_cold_partitions(self):
        """Deterministic re-entrancy pin: an ensure() that crosses the
        statement quota fires the registered spill action, which evicts
        the OTHER resident partitions (never the active one), and the
        ensure completes instead of raising ER_MEM_EXCEED_QUOTA."""
        nb = 32768
        bk = [(np.arange(nb, dtype=np.int64), np.ones(nb, bool))]
        kernel = JoinKernel(1)
        root = memtrack.statement_root(None, quota=0)
        with memtrack.tracking(root):
            hyb = hybrid.HybridJoinBuild(kernel, bk, nb, parts=4,
                                         plan=object(), threshold=0)
            try:
                hyb.ensure(0)
                hyb.ensure(1)
                per_part = kernel.build_nbytes(hyb.part_rows(2))
                # quota admits the gathered copy + ~2.5 resident
                # partitions: the NEXT ensure must spill, not cancel
                root.quota = root.total() + per_part // 2
                before = _metric(metrics.JOIN_SPILL_PARTITIONS)
                spill_events = _metric(metrics.MEM_QUOTA_EXCEEDED +
                                       '{action="spill"}')
                hyb.ensure(2)          # crosses: spill action fires
                assert hyb.spilled >= 1
                assert _metric(metrics.JOIN_SPILL_PARTITIONS) > before
                assert _metric(metrics.MEM_QUOTA_EXCEEDED +
                               '{action="spill"}') > spill_events
                # spilled partitions now stage instead of re-uploading
                assert hyb.under_pressure()
                assert not hyb.want_immediate(0)
                assert hyb.want_immediate(2)   # the active one survived
            finally:
                hyb.close()
                root.detach()
        assert root.host == 0 and root.device == 0

    def test_sql_join_completes_with_spill_under_quota(self, skew_sess):
        """End-to-end acceptance: under a constrained
        tidb_tpu_mem_quota_query the hybrid join COMPLETES via
        partition spill — spill metric > 0, correct rows, no quota
        cancel."""
        s, host_rows, q = skew_sess
        s.execute("SET tidb_tpu_device = 1")
        s.execute("SET tidb_tpu_join_partitions = 8")
        s.execute("SET tidb_tpu_skew_threshold = 1500")
        s.execute("SET tidb_tpu_superchunk_rows = 4096")
        s.query(q)                       # unquota'd run: records peak
        mem = s._last_mem
        peak = mem.host_peak + mem.device_peak
        before = _metric(metrics.JOIN_SPILL_PARTITIONS)
        try:
            s.execute(f"SET tidb_tpu_mem_quota_query = {peak - 4096}")
            rows = s.query(q).rows
        finally:
            s.execute("SET tidb_tpu_mem_quota_query = 0")
        assert _metric(metrics.JOIN_SPILL_PARTITIONS) > before
        assert _approx(rows, host_rows)


def _approx(a, b):
    if len(a) != len(b):
        return False
    for ra, rb in zip(a, b):
        for x, y in zip(ra, rb):
            if isinstance(x, float) or isinstance(y, float):
                if abs(float(x) - float(y)) > max(1e-6,
                                                  abs(float(y)) * 1e-9):
                    return False
            elif x != y:
                return False
    return True


@pytest.fixture(scope="module")
def skew_sess():
    """Zipf-ish skewed join workload: dim table c (6000 rows), fact o
    (18000 rows, 35% on one hot cid), ANALYZE'd so the planner attaches
    the probe-side CMSketch. -> (session, host-truth rows, query)."""
    s = Session(new_mock_storage())
    s.execute("CREATE DATABASE hj")
    s.execute("USE hj")
    s.execute("CREATE TABLE c (id BIGINT PRIMARY KEY, seg BIGINT)")
    s.execute("CREATE TABLE o (id BIGINT PRIMARY KEY, cid BIGINT, "
              "amt DOUBLE)")
    rng = np.random.default_rng(21)
    nb, n = 6000, 18000
    s.execute("INSERT INTO c VALUES " +
              ",".join(f"({i}, {i % 7})" for i in range(nb)))
    cid = rng.integers(0, nb + 900, n)      # some dangle (outer joins)
    cid[rng.random(n) < 0.35] = 42          # the heavy hitter
    amt = rng.uniform(1, 100, n).round(2)
    for lo in range(0, n, 9000):
        s.execute("INSERT INTO o VALUES " + ",".join(
            f"({i}, {cid[i]}, {amt[i]})"
            for i in range(lo, min(lo + 9000, n))))
    s.execute("ANALYZE TABLE o")
    s.execute("ANALYZE TABLE c")
    q = ("SELECT c.seg, COUNT(*), SUM(o.amt) FROM o JOIN c "
         "ON o.cid = c.id GROUP BY c.seg ORDER BY c.seg")
    s.execute("SET tidb_tpu_device = 0")
    host_rows = s.query(q).rows
    s.execute("SET tidb_tpu_device = 1")
    s._truth = (cid, amt, nb, n)
    return s, host_rows, q


class TestSqlHybrid:
    def test_skew_join_on_device_no_fallback(self, skew_sess):
        """The ISSUE's acceptance shape: the skewed join runs entirely
        on device (fallback count 0), with the heavy hitter routed
        through the broadcast lane seeded from ANALYZE's CMSketch."""
        s, host_rows, q = skew_sess
        s.execute("SET tidb_tpu_join_partitions = 4")
        s.execute("SET tidb_tpu_skew_threshold = 1500")
        s.execute("SET tidb_tpu_superchunk_rows = 4096")
        hot0 = _metric(metrics.JOIN_HOT_ROWS)
        fb0 = _metric(metrics.DEVICE_FALLBACKS)
        rows = s.query(q).rows
        assert _approx(rows, host_rows)
        assert _metric(metrics.JOIN_HOT_ROWS) > hot0
        assert _metric(metrics.DEVICE_FALLBACKS) == fb0

    def test_left_join_null_extension_via_hybrid(self, skew_sess):
        s, _host_rows, _q = skew_sess
        cid, _amt, nb, _n = s._truth
        s.execute("SET tidb_tpu_join_partitions = 4")
        s.execute("SET tidb_tpu_skew_threshold = 1500")
        s.execute("SET tidb_tpu_superchunk_rows = 4096")
        rows = s.query(
            "SELECT COUNT(*) FROM o LEFT JOIN c ON o.cid = c.id "
            "WHERE c.id IS NULL").rows
        assert rows[0][0] == int(np.sum(cid >= nb))

    def test_high_card_cop_agg_stays_on_device(self, skew_sess):
        """Storage-side partial agg over > capacity distinct groups:
        before the hybrid retry this host-fell-back invisibly at
        store/copr.py's except net; now it escalates/partitions and the
        fallback counter stays flat."""
        s, _host_rows, _q = skew_sess
        q = "SELECT cid, COUNT(*) FROM o GROUP BY cid ORDER BY cid LIMIT 7"
        s.execute("SET tidb_tpu_device = 0")
        want = s.query(q).rows
        s.execute("SET tidb_tpu_device = 1")
        fb0 = _metric(metrics.DEVICE_FALLBACKS)
        got = s.query(q).rows
        assert got == want
        assert _metric(metrics.DEVICE_FALLBACKS) == fb0

    def test_cop_agg_capacity_miss_is_paid_once_a_plan(self, skew_sess,
                                                       monkeypatch):
        """The kernel a capacity miss escalated to stays on the cached
        plan (store/copr._keep_escalated): the statement's next
        execution, from any session, starts at the table that fitted and
        does not run the 4,096-slot program to the same miss again."""
        s, _host_rows, _q = skew_sess
        q = "SELECT cid, SUM(amt) FROM o GROUP BY cid ORDER BY cid LIMIT 5"
        retries = []
        real = hybrid.agg_retry

        def counted(*a, **kw):
            retries.append(1)
            return real(*a, **kw)

        monkeypatch.setattr(hybrid, "agg_retry", counted)
        first = s.query(q).rows
        assert len(retries) == 1
        other = Session(s.storage)
        other.execute("USE hj")
        assert s.query(q).rows == first and other.query(q).rows == first
        assert len(retries) == 1

    def test_explain_analyze_fallback_note(self, skew_sess):
        """A designed device rejection (string-computed group key) is
        counted and surfaces as a fallback note in the EXPLAIN ANALYZE
        pipeline column."""
        s, _host_rows, _q = skew_sess
        s.execute("CREATE TABLE sfb (id BIGINT PRIMARY KEY, "
                  "name VARCHAR(32), v BIGINT)")
        s.execute("INSERT INTO sfb VALUES " + ",".join(
            f"({i}, 'n{i % 50}', {i})" for i in range(4096)))
        fb0 = _metric(metrics.DEVICE_FALLBACKS)
        r = s.query("EXPLAIN ANALYZE SELECT CONCAT(name, 'x'), "
                    "COUNT(*) FROM sfb GROUP BY CONCAT(name, 'x')")
        assert _metric(metrics.DEVICE_FALLBACKS) > fb0
        pipeline_col = r.columns.index("pipeline")
        assert any("fallback=" in str(row[pipeline_col])
                   for row in r.rows)
        snap = metrics.snapshot()
        assert any(key.startswith(metrics.DEVICE_FALLBACKS) and
                   "unsupported" in key for key in snap)
