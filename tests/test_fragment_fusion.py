"""Fragment fusion (ops/fragment.py, ISSUE 12): one XLA program per
probe superchunk executes match -> gather -> group -> partial agg under
an agg-over-inner-join. Fused == unfused byte-for-byte, pair-capacity
overflow self-heals, group-capacity misses escalate then degrade per
batch, ineligible shapes (outer joins, other_cond, skewed/hybrid
builds) keep the per-operator path, and EXPLAIN ANALYZE shows
`enc=fused:probe-agg`."""

import numpy as np
import pytest

from tidb_tpu import metrics
from tidb_tpu.expression.core import ColumnRef
from tidb_tpu.ops import fragment as op_fragment
from tidb_tpu.ops.hashagg import DeviceRejectError
from tidb_tpu.session import Session
from tidb_tpu.sqltypes import FieldType, TypeCode, new_string_field
from tidb_tpu.store.storage import new_mock_storage

FT_I = FieldType(tp=TypeCode.LONGLONG)
FT_S = new_string_field()


def _metric(prefix: str) -> float:
    return sum(v for k, v in metrics.snapshot().items()
               if k.startswith(prefix))


@pytest.fixture(scope="module")
def frag_sess():
    s = Session(new_mock_storage())
    s.execute("CREATE DATABASE frag")
    s.execute("USE frag")
    s.execute("CREATE TABLE fact (id BIGINT PRIMARY KEY, k BIGINT, "
              "amt DECIMAL(12,2), q BIGINT)")
    s.execute("CREATE TABLE dim (id BIGINT PRIMARY KEY, grp VARCHAR(8), "
              "w BIGINT)")
    rng = np.random.default_rng(12)
    n, nd = 8000, 300
    rows = []
    for i in range(n):
        # dangling keys past the dim table + a few NULL keys
        k = "NULL" if i % 97 == 0 else str(int(rng.integers(0, nd + 40)))
        rows.append(f"({i}, {k}, {rng.integers(0, 99999) / 100}, "
                    f"{i % 19})")
    for i in range(0, n, 500):
        s.execute("INSERT INTO fact VALUES " + ",".join(rows[i:i + 500]))
    s.execute("INSERT INTO dim VALUES " + ",".join(
        f"({i}, 'g{i % 7}', {i % 13})" for i in range(nd)))
    s.execute("SET tidb_tpu_device_min_rows = 1")
    yield s
    s.close()


def _fused_vs_not(s, q):
    s.execute("SET tidb_tpu_fuse_fragments = 1")
    fused = s.query(q).rows
    s.execute("SET tidb_tpu_fuse_fragments = 0")
    try:
        plain = s.query(q).rows
    finally:
        s.execute("SET tidb_tpu_fuse_fragments = 1")
    return fused, plain


class TestFusedEqualsUnfused:
    def test_group_by_build_string(self, frag_sess):
        q = ("SELECT dim.grp, COUNT(*), SUM(fact.amt), MIN(fact.q), "
             "MAX(dim.w) FROM fact JOIN dim ON fact.k = dim.id "
             "GROUP BY dim.grp ORDER BY dim.grp")
        fused, plain = _fused_vs_not(frag_sess, q)
        assert fused == plain

    def test_group_by_probe_key_highcard(self, frag_sess):
        """> capacity distinct groups: the fragment kernel escalates
        once and stays fused (or falls back per batch) — results must
        not change either way."""
        q = ("SELECT fact.id, SUM(fact.amt) FROM fact "
             "JOIN dim ON fact.k = dim.id "
             "GROUP BY fact.id ORDER BY fact.id LIMIT 17")
        fused, plain = _fused_vs_not(frag_sess, q)
        assert fused == plain

    def test_avg_and_mixed_side_columns(self, frag_sess):
        q = ("SELECT dim.grp, AVG(fact.amt), SUM(dim.w), COUNT(*) "
             "FROM fact JOIN dim ON fact.k = dim.id "
             "GROUP BY dim.grp ORDER BY dim.grp")
        fused, plain = _fused_vs_not(frag_sess, q)
        assert fused == plain

    def test_scalar_agg_over_join(self, frag_sess):
        q = ("SELECT COUNT(*), SUM(fact.amt) FROM fact "
             "JOIN dim ON fact.k = dim.id")
        fused, plain = _fused_vs_not(frag_sess, q)
        assert fused == plain

    def test_explain_shows_fused_mode(self, frag_sess):
        r = frag_sess.query(
            "EXPLAIN ANALYZE SELECT dim.grp, COUNT(*) FROM fact "
            "JOIN dim ON fact.k = dim.id GROUP BY dim.grp")
        pc = r.columns.index("pipeline")
        cell = next(row[pc] for row in r.rows if "HashAgg" in row[0])
        assert "enc=fused:probe-agg" in cell


class TestPairOverflow:
    def test_many_to_many_regrow(self):
        """All-one-key many-to-many: total pairs far exceed the initial
        pair capacity — finalize must regrow and stay exact."""
        s = Session(new_mock_storage())
        s.execute("CREATE DATABASE ovf")
        s.execute("USE ovf")
        s.execute("CREATE TABLE p (id BIGINT PRIMARY KEY, k BIGINT, "
                  "v BIGINT)")
        s.execute("CREATE TABLE b (id BIGINT PRIMARY KEY, k BIGINT)")
        rows = ",".join(f"({i}, 1, {i % 7})" for i in range(5000))
        s.execute("INSERT INTO p VALUES " + rows)
        s.execute("INSERT INTO b VALUES " + ",".join(
            f"({i}, 1)" for i in range(100)))
        s.execute("SET tidb_tpu_device_min_rows = 1")
        try:
            q = ("SELECT COUNT(*), SUM(p.v) FROM p JOIN b "
                 "ON p.k = b.k")
            fused, plain = _fused_vs_not(s, q)
            assert fused == plain == [(500000, 1499500)]
        finally:
            s.close()


class TestIneligibleShapes:
    def test_outer_join_not_fused_still_correct(self, frag_sess):
        q = ("SELECT dim.grp, COUNT(*) FROM fact LEFT JOIN dim "
             "ON fact.k = dim.id GROUP BY dim.grp ORDER BY dim.grp")
        fused, plain = _fused_vs_not(frag_sess, q)
        assert fused == plain

    def test_other_cond_not_fused_still_correct(self, frag_sess):
        q = ("SELECT dim.grp, COUNT(*) FROM fact JOIN dim "
             "ON fact.k = dim.id AND fact.q < dim.w "
             "GROUP BY dim.grp ORDER BY dim.grp")
        fused, plain = _fused_vs_not(frag_sess, q)
        assert fused == plain

    def test_first_row_agg_rejects(self):
        from tidb_tpu.expression import AggDesc, AggFunc
        with pytest.raises(DeviceRejectError):
            op_fragment.ProbeAggKernel(
                1, 2, 4, [ColumnRef(0, FT_I, "k")],
                [AggDesc(fn=AggFunc.FIRST_ROW,
                         arg=ColumnRef(3, FT_S, "s"))])

    def test_hybrid_build_stands_aside(self, frag_sess):
        """An over-superchunk build (> _DEVICE_MIN_BUILD rows, bigger
        than tidb_tpu_superchunk_rows) hands the probe to the hybrid
        join's machinery; results match the per-operator path."""
        s = frag_sess
        s.execute("SET tidb_tpu_superchunk_rows = 128")
        try:
            # self-join: BOTH sides exceed the hybrid's build floor, so
            # whichever side the planner builds engages partitioning
            q = ("SELECT f2.q, COUNT(*), SUM(f1.amt) FROM fact f1 "
                 "JOIN fact f2 ON f1.k = f2.id GROUP BY f2.q "
                 "ORDER BY f2.q")
            fused, plain = _fused_vs_not(s, q)
            assert fused == plain
        finally:
            s.execute("SET tidb_tpu_superchunk_rows = 262144")


class TestDenseBranch:
    """The fused program shares group_partial with the group-by kernel
    (PR 25): a Q5-shaped fragment (an inner join grouped by the build
    side's name, SUM of a decimal product) takes the dense masked
    reductions, a group-by as wide as the probe takes the scatters,
    both give what the program with the dense branch shut gives and
    what plain Python gives, and each dispatch read back moves
    tidb_tpu_agg_dispatch_total by exactly one."""

    N_PROBE, N_BUILD, NATIONS = 3000, 120, 25

    def _sides(self):
        from tidb_tpu.chunk import Chunk
        from tidb_tpu.sqltypes import new_decimal_field
        dec = new_decimal_field(frac=2)
        rng = np.random.default_rng(25)
        probe = [(i, None if i % 53 == 0 else int(rng.integers(0, 150)),
                  int(rng.integers(0, 10**6)), int(rng.integers(0, 11)))
                 for i in range(self.N_PROBE)]
        build = [(i, f"NATION{i % self.NATIONS:02d}")
                 for i in range(self.N_BUILD)]
        import decimal
        pch = Chunk.from_rows(
            [FT_I, FT_I, dec, FT_I],
            [(i, k, decimal.Decimal(a) / 100, q) for i, k, a, q in probe])
        bch = Chunk.from_rows([FT_I, FT_S], build)
        return probe, build, pch, bch, dec

    def _run(self, k, pch, bch):
        pk = [(pch.columns[1].data, pch.columns[1].valid)]
        bk = [(bch.columns[0].data, bch.columns[0].valid)]
        nb, n = bch.num_rows, pch.num_rows
        dev = k.prepare_build(bch, bk, nb)
        before = {p: _metric('tidb_tpu_agg_dispatch_total{path="%s"}' % p)
                  for p in ("dense", "scatter")}
        gr = k.finalize(pch, bch, nb, k.dispatch(dev, nb, pk, pch, n))
        moved = {p: _metric('tidb_tpu_agg_dispatch_total{path="%s"}' % p)
                 - before[p] for p in before}
        return gr, moved

    @pytest.mark.parametrize("shape,path", [("q5", "dense"),
                                            ("wide", "scatter")])
    def test_dense_scatter_and_plain_python_agree(self, monkeypatch,
                                                  shape, path):
        from tidb_tpu.expression import AggDesc, AggFunc, Op, func
        from tidb_tpu.ops import hashagg
        probe, build, pch, bch, dec = self._sides()
        # joined schema: probe (id, k, amt, q) then build (id, name)
        group = [ColumnRef(5, FT_S, "name")] if shape == "q5" else \
            [ColumnRef(0, FT_I, "id")]
        revenue = func(Op.MUL, ColumnRef(2, dec, "amt"),
                       ColumnRef(3, FT_I, "q"))
        aggs = [AggDesc(fn=AggFunc.SUM, arg=revenue),
                AggDesc(fn=AggFunc.COUNT, arg=None)]
        limit = hashagg._DENSE_SLOTS
        assert self.NATIONS + 2 <= limit < self.N_PROBE // 2
        got = []
        for slots in (limit, 0):       # as it is; the dense branch shut
            monkeypatch.setattr(hashagg, "_DENSE_SLOTS", slots)
            k = op_fragment.ProbeAggKernel(1, 4, 6, group, aggs)
            gr, moved = self._run(k, pch, bch)
            want = path if slots else "scatter"
            assert moved == {"dense": int(want == "dense"),
                             "scatter": int(want == "scatter")}
            got.append({key: (int(gr.partials[0][0][i]),
                              int(gr.partials[1][0][i]))
                        for i, key in enumerate(gr.keys)})
        names = dict(build)
        want = {}
        for i, k_, amt, q in probe:
            if k_ is None or k_ not in names:
                continue
            key = (names[k_],) if shape == "q5" else (i,)
            s, c = want.get(key, (0, 0))
            want[key] = (s + amt * q, c + 1)
        assert got[0] == got[1] == want
        assert (len(want) > limit) == (path == "scatter")
        if shape == "q5":
            assert len(want) == self.NATIONS
