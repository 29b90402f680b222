"""Streaming mesh execution (BASELINE config 5): probes larger than
tidb_tpu_stream_rows are never materialized whole on the host — they feed
the mesh kernels in bounded, double-buffered super-batches.

Asserted here, through plain Session.execute on the 8-device virtual mesh:
  * results match the host path exactly (Q1 and Q3 shapes);
  * buffering is bounded: no batch ever exceeds stream_rows + one chunk;
  * the overlap happened: batch i+1's launch preceded batch i's readback.

Ref: the reference streams bounded chunk channels between distsql fetch
and executor consume (/root/reference/distsql/distsql.go:92-98); here the
bound is host-side super-batches sized for a TPU dispatch.
"""

import pytest

import tpch
from tidb_tpu import config, devplane
from tidb_tpu.executor import mesh as mesh_exec
from tidb_tpu.session import Session
from tidb_tpu.store.storage import new_mock_storage

STREAM_ROWS = 512          # tiny threshold so small test tables stream


@pytest.fixture(scope="module")
def sess():
    s = Session(new_mock_storage())
    s.execute("CREATE DATABASE tpch")
    s.execute("USE tpch")
    data = tpch.TpchData(seed=7)
    tpch.load(s, data)
    yield s
    s.close()


@pytest.fixture
def mesh():
    devplane.enable_mesh(8)
    yield devplane.active_mesh()
    devplane.disable_mesh()


@pytest.fixture
def small_stream():
    old = config.get_var("tidb_tpu_stream_rows")
    config.set_var("tidb_tpu_stream_rows", STREAM_ROWS)
    mesh_exec.reset_stream_stats()
    yield
    config.set_var("tidb_tpu_stream_rows", old)


def _host_rows(sess, sql):
    devplane.disable_mesh()
    try:
        return sess.query(sql).rows
    finally:
        devplane.enable_mesh(8)


def _check(got, want):
    assert want, "vacuous comparison: host result is empty"
    assert len(got) == len(want)
    for g, w in zip(got, want):
        for a, b in zip(g, w):
            if isinstance(a, float) or isinstance(b, float):
                assert float(a) == pytest.approx(float(b), rel=1e-9)
            else:
                assert a == b


@pytest.mark.parametrize("q", ["Q1", "Q3"])
def test_streamed_results_match_host(sess, mesh, small_stream, q):
    sql = getattr(tpch, q)
    got = sess.query(sql).rows
    stats = mesh_exec.stream_stats()
    assert stats["streams"] >= 1, "streaming path did not activate"
    assert stats["batches"] >= 2, "input did not split into batches"
    _check(got, _host_rows(sess, sql))


def test_buffering_is_bounded(sess, mesh, small_stream):
    sess.query(tpch.Q1)
    stats = mesh_exec.stream_stats()
    # one in-flight super-batch is the whole host footprint; a batch may
    # overshoot the threshold by at most one storage chunk
    max_chunk = 1024
    assert 0 < stats["max_batch_rows"] <= STREAM_ROWS + max_chunk


def test_double_buffer_overlap(sess, mesh, small_stream):
    sess.query(tpch.Q1)
    stats = mesh_exec.stream_stats()
    # every batch after the first must have been launched while the
    # previous batch was still in flight
    assert stats["overlapped_launches"] >= stats["batches"] - \
        stats["streams"] - stats["host_batches"]
    assert stats["overlapped_launches"] >= 1


def test_small_probe_keeps_memoized_path(sess, mesh):
    """Below the threshold nothing streams (the memoized whole-table path
    serves hot cached plans with zero re-transfer)."""
    mesh_exec.reset_stream_stats()
    sess.query(tpch.Q1)
    assert mesh_exec.stream_stats()["streams"] == 0
