"""Mesh shuffle hash join vs a naive host oracle.

Covers what the replicated lookup join (dist_join.py) rejects: duplicate
keys on BOTH sides, large build sides, NULL keys, multi-column keys,
skewed hash distributions (bucket overflow retry), and string keys via
the shared-dictionary encoder. Ref model: executor/join_test.go cases
over mocktikv, here against the 8-device virtual mesh.
"""

import numpy as np
import pytest

from tidb_tpu.devplane import build_mesh
from tidb_tpu.ops.join import JoinKeyEncoder
from tidb_tpu.ops.meshshuffle import MeshShuffleJoinKernel


@pytest.fixture(scope="module")
def mesh():
    return build_mesh(8)


def oracle_pairs(pk, bk):
    """All (probe_i, build_i) with equal, fully-non-NULL keys."""
    out = set()
    index = {}
    for i in range(len(bk[0][0])):
        if all(v[i] for _d, v in bk):
            index.setdefault(tuple(d[i] for d, _v in bk), []).append(i)
    for i in range(len(pk[0][0])):
        if not all(v[i] for _d, v in pk):
            continue
        for b in index.get(tuple(d[i] for d, _v in pk), ()):
            out.add((i, b))
    return out


def lanes(*cols):
    return [(np.asarray(d), np.asarray(v, dtype=bool)) for d, v in cols]


def check(mesh, pk, bk):
    k = MeshShuffleJoinKernel(mesh, len(pk))
    li, ri = k(pk, bk, len(bk[0][0]), len(pk[0][0]))
    got = set(zip(li.tolist(), ri.tolist()))
    assert got == oracle_pairs(pk, bk)


def test_duplicate_keys_both_sides(mesh):
    rng = np.random.default_rng(0)
    n, m = 5000, 3000
    pk = lanes((rng.integers(0, 50, n), np.ones(n)))
    bk = lanes((rng.integers(0, 50, m), np.ones(m)))
    check(mesh, pk, bk)


def test_multi_key_with_nulls(mesh):
    rng = np.random.default_rng(1)
    n, m = 2000, 2500
    pk = lanes((rng.integers(0, 30, n), rng.random(n) > 0.1),
               (rng.integers(0, 4, n), rng.random(n) > 0.1))
    bk = lanes((rng.integers(0, 30, m), rng.random(m) > 0.1),
               (rng.integers(0, 4, m), rng.random(m) > 0.1))
    check(mesh, pk, bk)


def test_float_keys(mesh):
    rng = np.random.default_rng(2)
    n, m = 1500, 1500
    vals = np.array([0.5, 1.25, -3.75, 2.0, 1e9])
    pk = lanes((vals[rng.integers(0, 5, n)], np.ones(n)))
    bk = lanes((vals[rng.integers(0, 5, m)], np.ones(m)))
    check(mesh, pk, bk)


def test_skewed_single_key_forces_bucket_retry(mesh):
    # 90% of rows share one key: one destination chip receives almost
    # everything, far past the 4x slack buckets
    rng = np.random.default_rng(3)
    n, m = 4000, 4000
    p = np.where(rng.random(n) < 0.9, 7, rng.integers(0, 1000, n))
    b = np.where(rng.random(m) < 0.9, 7, rng.integers(0, 1000, m))
    pk, bk = lanes((p, np.ones(n))), lanes((b, np.ones(m)))
    k = MeshShuffleJoinKernel(mesh, 1)
    li, ri = k(pk, bk, m, n)
    assert set(zip(li.tolist(), ri.tolist())) == oracle_pairs(pk, bk)


def test_string_keys_via_encoder(mesh):
    rng = np.random.default_rng(4)
    n, m = 1200, 900
    words = np.array(["asia", "europe", "africa", "america", None],
                     dtype=object)
    pv = words[rng.integers(0, 5, n)]
    bv = words[rng.integers(0, 5, m)]
    enc = JoinKeyEncoder(1)
    bk = enc.fit_build([(bv, np.array([x is not None for x in bv]))])
    pk = enc.transform_probe([(pv, np.array([x is not None for x in pv]))])
    check(mesh, pk, bk)


def test_empty_sides(mesh):
    k = MeshShuffleJoinKernel(mesh, 1)
    e = lanes((np.empty(0, np.int64), np.empty(0, bool)))
    p = lanes((np.arange(10), np.ones(10)))
    assert k(p, e, 0, 10) == (pytest.approx([]), pytest.approx([]))
    li, ri = k(e, p, 10, 0)
    assert len(li) == 0 and len(ri) == 0


def test_single_device_mesh_delegates(mesh):
    m1 = build_mesh(1)
    rng = np.random.default_rng(5)
    pk = lanes((rng.integers(0, 20, 500), np.ones(500)))
    bk = lanes((rng.integers(0, 20, 400), np.ones(400)))
    k = MeshShuffleJoinKernel(m1, 1)
    li, ri = k(pk, bk, 400, 500)
    assert set(zip(li.tolist(), ri.tolist())) == oracle_pairs(pk, bk)
