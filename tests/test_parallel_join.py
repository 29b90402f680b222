"""Mesh lookup-join + aggregation tests (the Q3/Q5 distributed shape).

Ref model: executor/join.go HashJoinExec chains + aggregate.go, here as
one fused mesh program cross-checked against the pure-host reference.
Runs on the 8-virtual-device CPU mesh from conftest.
"""

import numpy as np
import pytest

from tidb_tpu.chunk import Chunk, Column
from tidb_tpu.devplane import build_mesh
from tidb_tpu.expression import AggDesc, AggFunc
from tidb_tpu.expression.core import Op, col, const, func
from tidb_tpu.ops.hashagg import HashAggregator
from tidb_tpu.ops.meshjoin import (BuildError, LookupSpec,
                                   MeshLookupAggKernel, host_lookup_agg)
from tidb_tpu.sqltypes import (new_double_field, new_int_field,
                               new_string_field)


def _finalize(aggs, gr):
    agg = HashAggregator(aggs)
    agg.update(gr)
    return agg.results()


def _mesh():
    return build_mesh(8)


def _assert_same(aggs, got_gr, want_gr):
    got = _finalize(aggs, got_gr)
    want = _finalize(aggs, want_gr)
    assert len(got) == len(want)
    for (gk, gv), (wk, wv) in zip(got, want):
        assert gk == wk
        for a, b in zip(gv, wv):
            if isinstance(b, float):
                assert abs(a - b) <= 1e-9 * max(1.0, abs(b)), (gk, a, b)
            else:
                assert a == b, (gk, a, b)


class TestSingleLookup:
    def _data(self, n=5000, dims=40):
        rng = np.random.default_rng(5)
        probe = Chunk([
            Column(new_int_field(), rng.integers(0, dims, n).astype(np.int64)),
            Column(new_double_field(), rng.uniform(0, 100, n)),
            Column(new_int_field(), rng.integers(0, 3650, n).astype(np.int64)),
        ])
        build = Chunk([
            Column(new_int_field(), np.arange(dims, dtype=np.int64)),
            Column(new_int_field(),
                   (np.arange(dims, dtype=np.int64) % 5)),
            Column(new_string_field(),
                   np.array([f"region{i % 5}" for i in range(dims)],
                            dtype=object)),
        ])
        return probe, build

    def test_q3_shape(self):
        """filter(probe) join dim group by dim.attr agg sums."""
        probe, build = self._data()
        flt = func(Op.LT, col(2, new_int_field()), const(1800))
        lookups = [LookupSpec(
            key_exprs=[col(0, new_int_field())],
            build_chunk=build, build_key_offsets=[0],
            payload_offsets=[1, 2])]
        # virtual schema: probe 0..2, then build cols at 3 (int), 4 (str)
        groups = [col(3, new_int_field())]
        aggs = [AggDesc(AggFunc.SUM, col(1, new_double_field())),
                AggDesc(AggFunc.COUNT, None)]
        k = MeshLookupAggKernel(_mesh(), flt, lookups, groups, aggs,
                                capacity=64)
        got = k(probe)
        want = host_lookup_agg(probe, flt, lookups, groups, aggs)
        _assert_same(aggs, got, want)

    def test_string_payload_group_key(self):
        probe, build = self._data()
        lookups = [LookupSpec(
            key_exprs=[col(0, new_int_field())],
            build_chunk=build, build_key_offsets=[0],
            payload_offsets=[2])]
        groups = [col(3, new_string_field())]   # the string payload
        aggs = [AggDesc(AggFunc.SUM, col(1, new_double_field())),
                AggDesc(AggFunc.MAX, col(2, new_int_field()))]
        k = MeshLookupAggKernel(_mesh(), None, lookups, groups, aggs,
                                capacity=64)
        got_gr = k(probe)
        _assert_same(aggs, got_gr,
                     host_lookup_agg(probe, None, lookups, groups, aggs))
        got = _finalize(aggs, got_gr)
        assert all(isinstance(k0[0], str) for k0, _ in got)

    def test_probe_misses_are_dropped(self):
        probe, build = self._data()
        # restrict the dimension table: keys >= 20 have no match
        small = build.filter(np.asarray(build.columns[0].data) < 20)
        lookups = [LookupSpec(key_exprs=[col(0, new_int_field())],
                              build_chunk=small, build_key_offsets=[0],
                              payload_offsets=[1])]
        groups = [col(3, new_int_field())]
        aggs = [AggDesc(AggFunc.COUNT, None)]
        k = MeshLookupAggKernel(_mesh(), None, lookups, groups, aggs,
                                capacity=64)
        got = _finalize(aggs, k(probe))
        want = _finalize(aggs, host_lookup_agg(probe, None, lookups,
                                               groups, aggs))
        assert got == want
        total = sum(v[0] for _k, v in got)
        expect = int((np.asarray(probe.columns[0].data) < 20).sum())
        assert total == expect

    def test_null_probe_keys_never_match(self):
        n = 64
        key = np.arange(n, dtype=np.int64) % 8
        valid = np.ones(n, dtype=bool)
        valid[::4] = False
        probe = Chunk([Column(new_int_field(), key, valid),
                       Column(new_double_field(), np.ones(n))])
        build = Chunk([Column(new_int_field(),
                              np.arange(8, dtype=np.int64)),
                       Column(new_int_field(),
                              np.arange(8, dtype=np.int64) * 10)])
        lookups = [LookupSpec(key_exprs=[col(0, new_int_field())],
                              build_chunk=build, build_key_offsets=[0],
                              payload_offsets=[1])]
        aggs = [AggDesc(AggFunc.COUNT, None)]
        k = MeshLookupAggKernel(_mesh(), None, lookups, [], aggs,
                                capacity=16)
        got = _finalize(aggs, k(probe))
        assert got[0][1][0] == int(valid.sum())


class TestChain:
    def test_q5_shape_two_hops(self):
        """probe -> dim1 (via fk) -> dim2 (via dim1 payload): the star
        chain; group on dim2's name, sum probe measure."""
        rng = np.random.default_rng(9)
        n = 4000
        probe = Chunk([
            Column(new_int_field(), rng.integers(0, 100, n).astype(np.int64)),
            Column(new_double_field(), rng.uniform(1, 10, n)),
        ])
        # dim1: 100 rows, fk -> dim2 (10 rows)
        dim1 = Chunk([
            Column(new_int_field(), np.arange(100, dtype=np.int64)),
            Column(new_int_field(),
                   (np.arange(100, dtype=np.int64) * 7 % 10)),
        ])
        dim2 = Chunk([
            Column(new_int_field(), np.arange(10, dtype=np.int64)),
            Column(new_string_field(),
                   np.array([f"nation{i}" for i in range(10)],
                            dtype=object)),
        ])
        lookups = [
            LookupSpec(key_exprs=[col(0, new_int_field())],
                       build_chunk=dim1, build_key_offsets=[0],
                       payload_offsets=[1]),           # virt[2] = dim1.fk
            LookupSpec(key_exprs=[col(2, new_int_field())],
                       build_chunk=dim2, build_key_offsets=[0],
                       payload_offsets=[1]),           # virt[3] = name
        ]
        groups = [col(3, new_string_field())]
        aggs = [AggDesc(AggFunc.SUM, col(1, new_double_field())),
                AggDesc(AggFunc.COUNT, None),
                AggDesc(AggFunc.AVG, col(1, new_double_field()))]
        k = MeshLookupAggKernel(_mesh(), None, lookups, groups, aggs,
                                capacity=32)
        got = k(probe)
        want = host_lookup_agg(probe, None, lookups, groups, aggs)
        _assert_same(aggs, got, want)

    def test_composite_key(self):
        rng = np.random.default_rng(2)
        n = 2000
        probe = Chunk([
            Column(new_int_field(), rng.integers(0, 6, n).astype(np.int64)),
            Column(new_int_field(), rng.integers(0, 5, n).astype(np.int64)),
            Column(new_double_field(), rng.uniform(0, 1, n)),
        ])
        a, b = np.meshgrid(np.arange(6), np.arange(5), indexing="ij")
        build = Chunk([
            Column(new_int_field(), a.ravel().astype(np.int64)),
            Column(new_int_field(), b.ravel().astype(np.int64)),
            Column(new_int_field(),
                   (a.ravel() * 10 + b.ravel()).astype(np.int64)),
        ])
        lookups = [LookupSpec(
            key_exprs=[col(0, new_int_field()), col(1, new_int_field())],
            build_chunk=build, build_key_offsets=[0, 1],
            payload_offsets=[2])]
        groups = [col(3, new_int_field())]
        aggs = [AggDesc(AggFunc.SUM, col(2, new_double_field()))]
        k = MeshLookupAggKernel(_mesh(), None, lookups, groups, aggs,
                                capacity=64)
        got = k(probe)
        want = host_lookup_agg(probe, None, lookups, groups, aggs)
        _assert_same(aggs, got, want)


class TestBuildValidation:
    def test_duplicate_build_keys_rejected(self):
        build = Chunk([Column(new_int_field(),
                              np.array([1, 1, 2], dtype=np.int64))])
        spec = LookupSpec(key_exprs=[col(0, new_int_field())],
                          build_chunk=build, build_key_offsets=[0])
        with pytest.raises(BuildError):
            MeshLookupAggKernel(_mesh(), None, [spec], [],
                                [AggDesc(AggFunc.COUNT, None)])

    def test_null_build_keys_dropped(self):
        data = np.array([1, 2, 3], dtype=np.int64)
        valid = np.array([True, False, True])
        build = Chunk([Column(new_int_field(), data, valid),
                       Column(new_int_field(), data * 10)])
        probe = Chunk([Column(new_int_field(),
                              np.array([1, 2, 3, 2], dtype=np.int64))])
        lookups = [LookupSpec(key_exprs=[col(0, new_int_field())],
                              build_chunk=build, build_key_offsets=[0],
                              payload_offsets=[1])]
        aggs = [AggDesc(AggFunc.COUNT, None)]
        k = MeshLookupAggKernel(_mesh(), None, lookups, [], aggs,
                                capacity=8)
        got = _finalize(aggs, k(probe))
        assert got[0][1][0] == 2     # rows 1 and 3 match; NULL-key row 2 not

    def test_string_build_key_rejected(self):
        build = Chunk([Column(new_string_field(),
                              np.array(["a", "b"], dtype=object))])
        spec = LookupSpec(key_exprs=[col(0, new_string_field())],
                          build_chunk=build, build_key_offsets=[0])
        with pytest.raises(BuildError):
            MeshLookupAggKernel(_mesh(), None, [spec], [],
                                [AggDesc(AggFunc.COUNT, None)])
