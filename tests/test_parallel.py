"""Mesh-distributed aggregation vs the host ground truth.

Runs on the 8-virtual-CPU-device mesh from conftest.py — the hermetic
multi-"node" strategy of the reference's mocktikv (SURVEY.md §4), at the
chip level.
"""

import numpy as np
import pytest

import jax

from tidb_tpu.chunk import Chunk, Column
from tidb_tpu.devplane import build_mesh
from tidb_tpu.expression import AggDesc, AggFunc
from tidb_tpu.expression.core import Op, col, const, func
from tidb_tpu.ops.hashagg import HashAggregator
from tidb_tpu.ops.hostagg import host_hash_agg
from tidb_tpu.ops.meshagg import MeshAggKernel
from tidb_tpu.sqltypes import new_double_field, new_int_field, new_string_field


def _mk_chunk(n, num_groups=37, with_strings=False, seed=0):
    rng = np.random.default_rng(seed)
    g = rng.integers(0, num_groups, n).astype(np.int64)
    gv = rng.random(n) > 0.05
    x = rng.integers(-1000, 1000, n).astype(np.int64)
    xv = rng.random(n) > 0.1
    y = rng.normal(size=n)
    cols = [Column(new_int_field(), g, gv),
            Column(new_int_field(), x, xv),
            Column(new_double_field(), y)]
    if with_strings:
        names = np.array([f"name-{v}" for v in g % 7], dtype=object)
        cols.append(Column(new_string_field(32), names,
                           rng.random(n) > 0.03))
    return Chunk(cols)


def _results(group_exprs, aggs, gr):
    agg = HashAggregator(aggs)
    agg.update(gr)
    return agg.results()


def _assert_same(res_a, res_b):
    assert len(res_a) == len(res_b)
    for (ka, va), (kb, vb) in zip(res_a, res_b):
        assert ka == kb
        for a, b in zip(va, vb):
            if isinstance(a, float) or isinstance(b, float):
                assert a == pytest.approx(b, rel=1e-9)
            else:
                assert a == b


@pytest.fixture(scope="module")
def mesh():
    assert len(jax.devices()) == 8, "conftest must force 8 virtual devices"
    return build_mesh(8)


def test_mesh_shape(mesh):
    assert mesh.shape == {"batch": 8}


def test_dist_agg_matches_host(mesh):
    ch = _mk_chunk(10_000)
    gcol = col(0, new_int_field(), "g")
    xcol = col(1, new_int_field(), "x")
    ycol = col(2, new_double_field(), "y")
    flt = func(Op.GT, xcol, const(-500))
    aggs = [AggDesc(AggFunc.COUNT, None),
            AggDesc(AggFunc.SUM, xcol),
            AggDesc(AggFunc.AVG, ycol),
            AggDesc(AggFunc.MIN, xcol),
            AggDesc(AggFunc.MAX, ycol),
            AggDesc(AggFunc.FIRST_ROW, gcol)]
    k = MeshAggKernel(mesh, flt, [gcol], aggs, capacity=256)
    got = _results([gcol], aggs, k(ch))
    # host ground truth: filter first, then group
    mask = np.asarray((ch.columns[1].data > -500) & ch.columns[1].valid)
    want = _results([gcol], aggs,
                    host_hash_agg(ch.filter(mask), None, [gcol], aggs))
    _assert_same(got, want)


def test_dist_agg_string_group_keys(mesh):
    ch = _mk_chunk(5_000, with_strings=True, seed=3)
    scol = col(3, new_string_field(32), "name")
    gcol = col(0, new_int_field(), "g")
    aggs = [AggDesc(AggFunc.COUNT, None),
            AggDesc(AggFunc.FIRST_ROW, scol)]
    k = MeshAggKernel(mesh, None, [scol, gcol], aggs, capacity=512)
    got = _results([scol, gcol], aggs, k(ch))
    want = _results([scol, gcol], aggs,
                    host_hash_agg(ch, None, [scol, gcol], aggs))
    _assert_same(got, want)


def test_dist_agg_scalar_no_groups(mesh):
    ch = _mk_chunk(4_000, seed=7)
    xcol = col(1, new_int_field(), "x")
    aggs = [AggDesc(AggFunc.COUNT, None), AggDesc(AggFunc.SUM, xcol)]
    k = MeshAggKernel(mesh, None, [], aggs, capacity=8)
    got = _results([], aggs, k(ch))
    want = _results([], aggs, host_hash_agg(ch, None, [], aggs))
    _assert_same(got, want)


def test_dist_agg_capacity_overflow(mesh):
    from tidb_tpu.ops.hashagg import CapacityError
    n = 4096
    ch = Chunk([Column(new_int_field(), np.arange(n, dtype=np.int64))])
    gcol = col(0, new_int_field(), "g")
    k = MeshAggKernel(mesh, None, [gcol], [AggDesc(AggFunc.COUNT, None)],
                      capacity=64)
    with pytest.raises(CapacityError):
        k(ch)


def test_dist_agg_empty_chunk(mesh):
    ch = Chunk([Column(new_int_field(), np.empty(0, dtype=np.int64))])
    gcol = col(0, new_int_field(), "g")
    aggs = [AggDesc(AggFunc.COUNT, None)]
    k = MeshAggKernel(mesh, None, [gcol], aggs, capacity=8)
    gr = k(ch)
    assert gr.num_groups == 0


def test_dist_agg_float_group_keys(mesh):
    # regression: value-cast hashing truncated 2.3 and 2.7 to the same
    # group under both seeds; bitcast hashing must keep them distinct
    n = 4096
    vals = np.tile(np.array([2.3, 2.7, -0.0, 0.0]), n // 4)
    ch = Chunk([Column(new_double_field(), vals)])
    gcol = col(0, new_double_field(), "g")
    aggs = [AggDesc(AggFunc.COUNT, None)]
    k = MeshAggKernel(mesh, None, [gcol], aggs, capacity=16)
    got = dict((key[0], v[0]) for key, v in _results([gcol], aggs, k(ch)))
    assert got == {2.3: n // 4, 2.7: n // 4, 0.0: n // 2}
