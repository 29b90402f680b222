"""Streaming coprocessor subsystem (store/stream.py + wire + copr).

Ref: the CmdCopStream mode of store/tikv/coprocessor.go:547-555 —
incremental per-range responses, stream re-created from the last
returned range on region errors. Asserted here:

  * bounded memory: a region strictly larger than the response cap
    streams in frames of <= cap raw bytes, and the client never buffers
    more than the credit window of frames;
  * KeepOrder parity: streamed results are IDENTICAL to the
    materialized path, ordered scans included;
  * resume: a failpoint kills the stream mid-region and the client
    re-issues from the last acked range boundary — no duplicate, no
    missing row;
  * the same path serves in-process (mockstore/rpc.py) and
    out-of-process (store/remote.py) storage.
"""

import os

import pytest

from tidb_tpu import config, metrics
from tidb_tpu.kv import EpochNotMatchError
from tidb_tpu.session import Session
from tidb_tpu.store import stream as costream
from tidb_tpu.store.storage import new_mock_storage
from tidb_tpu.util import failpoint

N_ROWS = 2000
FRAME_BYTES = 1024       # each row is ~45 raw bytes: dozens of frames
CREDIT = 3
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def q(s, sql):
    return s.query(sql).rows


@pytest.fixture
def sess():
    st = new_mock_storage()
    s = Session(st)
    s.execute("CREATE DATABASE d")
    s.execute("USE d")
    s.execute("CREATE TABLE t (id BIGINT PRIMARY KEY, v BIGINT, "
              "s VARCHAR(10))")
    s.execute("INSERT INTO t VALUES " + ",".join(
        f"({i},{i * 7 % 1000},'s{i % 13}')" for i in range(N_ROWS)))
    info = s.domain.info_schema().table("d", "t")
    st.cluster.split_table(info.id, 4, max_handle=N_ROWS)
    yield s
    s.close()


@pytest.fixture
def streaming():
    old = {k: config.get_var(k) for k in
           ("tidb_tpu_copr_stream", "tidb_tpu_copr_stream_frame_bytes",
            "tidb_tpu_copr_stream_credit", "tidb_tpu_chunk_cache")}
    config.set_var("tidb_tpu_copr_stream", 1)
    config.set_var("tidb_tpu_copr_stream_frame_bytes", FRAME_BYTES)
    config.set_var("tidb_tpu_copr_stream_credit", CREDIT)
    # the frame contracts pinned in this file (byte cap, exact range
    # tiling, resume boundaries) are the COLD-path guarantees: with the
    # chunk cache on, a re-read of a resident range legitimately serves
    # as ONE final frame straight from the cached block instead
    # (TestStreamCacheIntegration pins that shape) — so these tests run
    # cache-off to exercise the real framed scan every time
    config.set_var("tidb_tpu_chunk_cache", 0)
    costream.reset_stream_stats()
    yield
    for k, v in old.items():
        config.set_var(k, v)


def _materialized(s, sql):
    config.set_var("tidb_tpu_copr_stream", 0)
    try:
        return q(s, sql)
    finally:
        config.set_var("tidb_tpu_copr_stream", 1)


class TestBoundedMemory:
    def test_region_larger_than_cap_streams_in_frames(self, sess,
                                                      streaming):
        """The acceptance shape: each region's data is strictly larger
        than the frame cap, so every region MUST multi-frame; no frame
        exceeds the cap and client buffering never exceeds the credit
        window."""
        want = _materialized(sess, "SELECT COUNT(*), SUM(v) FROM t")
        got = q(sess, "SELECT COUNT(*), SUM(v) FROM t")
        assert got == want == [(N_ROWS, sum(i * 7 % 1000
                                            for i in range(N_ROWS)))]
        st = costream.stream_stats()
        assert st["streams"] >= 4                  # one per region
        assert st["frames"] > st["streams"]        # regions multi-framed
        assert st["bytes"] > 4 * FRAME_BYTES       # data outgrew the cap
        assert 0 < st["frame_bytes_max"] <= FRAME_BYTES
        assert st["peak_buffered"] <= CREDIT

    def test_metrics_counters_advance(self, sess, streaming):
        before = metrics.snapshot().get(metrics.COP_STREAM_FRAMES, 0)
        q(sess, "SELECT SUM(v) FROM t")
        snap = metrics.snapshot()
        assert snap.get(metrics.COP_STREAM_FRAMES, 0) > before
        assert snap.get(metrics.COP_STREAM_BYTES, 0) > 0


class TestKeepOrderParity:
    def test_ordered_scan_identical(self, sess, streaming):
        sql = "SELECT id, v FROM t WHERE v >= 500 ORDER BY id"
        got = q(sess, sql)
        assert got == _materialized(sess, sql)
        assert [r[0] for r in got] == sorted(r[0] for r in got)

    def test_group_by_partials_merge(self, sess, streaming):
        sql = ("SELECT s, COUNT(*), SUM(v), MIN(id), MAX(id) FROM t "
               "GROUP BY s ORDER BY s")
        assert q(sess, sql) == _materialized(sess, sql)

    def test_limit_early_stop(self, sess, streaming):
        sql = "SELECT id FROM t ORDER BY id LIMIT 7"
        assert q(sess, sql) == [(i,) for i in range(7)]


class TestFrameContiguity:
    def test_frames_cover_contiguous_ranges(self, sess, streaming):
        """Unit-level: the producer's frames tile the region exactly —
        frame i+1 starts where frame i ended, the final frame is marked
        last and ends at the region-clamped scan end."""
        from tidb_tpu.kv import CopRequest, KVRange, ReqType
        from tidb_tpu.plan.physical import CopPlan  # noqa: F401 (shape)

        st = sess.storage
        # record per-stream through the client with a wrapping recorder
        streams = []
        orig = st.shim.coprocessor_stream

        def recording(ctx, req, **kw):
            mine = {"req_start": req.ranges[0].start, "frames": []}
            streams.append(mine)
            for f in orig(ctx, req, **kw):
                mine["frames"].append(f)
                yield f

        st.shim.coprocessor_stream = recording
        try:
            q(sess, "SELECT id FROM t")
        finally:
            st.shim.coprocessor_stream = orig
        # an attempt aborted before its first frame (e.g. KeyLockedError
        # while the fixture INSERT's async lock resolution is pending)
        # records as an empty stream; the client resumes it — only the
        # attempts that delivered frames carry tiling obligations
        streams = [s for s in streams if s["frames"]]
        assert len(streams) >= 4               # one per region
        multi = 0
        for s in streams:
            frames = s["frames"]
            assert frames[0].range.start >= s["req_start"]
            for a, b in zip(frames, frames[1:]):
                assert not a.last
                assert b.range.start == a.range.end   # exact tiling
            assert frames[-1].last
            multi += len(frames) > 1
        assert multi >= 4           # regions outgrew the cap: multi-framed


class TestFailpointResume:
    def test_mid_stream_kill_resumes_no_dup_no_loss(self, sess,
                                                    streaming):
        """Kill the stream after a few delivered frames via the shim
        failpoint; the client must resume from the last acked range
        boundary: the full ordered id list comes back exactly once."""
        shim = sess.storage.shim
        calls = {"n": 0, "fired": 0}

        def inject(cmd, ctx):
            if cmd != "CopStream":
                return
            calls["n"] += 1
            # fire twice, mid-region (every 5th frame check), to prove
            # repeated interruption still converges
            if calls["n"] in (5, 11):
                calls["fired"] += 1
                raise EpochNotMatchError(ctx.region_id)

        failpoint.enable("rpc/request", inject)
        try:
            got = q(sess, "SELECT id FROM t ORDER BY id")
        finally:
            failpoint.disable("rpc/request")
        assert calls["fired"] == 2
        assert [r[0] for r in got] == list(range(N_ROWS))
        assert costream.stream_stats()["resumes"] >= 2

    def test_kill_during_agg_partials(self, sess, streaming):
        """Resume must also hold for partial aggregates: an un-acked
        frame's partial is never merged, so re-scanning its range cannot
        double-count."""
        shim = sess.storage.shim
        state = {"n": 0}

        def inject(cmd, ctx):
            if cmd != "CopStream":
                return
            state["n"] += 1
            if state["n"] == 7:
                raise EpochNotMatchError(ctx.region_id)

        want = _materialized(sess, "SELECT COUNT(*), SUM(v) FROM t")
        failpoint.enable("rpc/request", inject)
        try:
            got = q(sess, "SELECT COUNT(*), SUM(v) FROM t")
        finally:
            failpoint.disable("rpc/request")
        assert got == want

    def test_real_region_split_mid_stream(self, sess, streaming):
        """An actual epoch change (region split) mid-stream: the
        per-frame epoch re-check surfaces it, the client re-splits and
        finishes both halves."""
        from tidb_tpu import tablecodec
        st = sess.storage
        info = sess.domain.info_schema().table("d", "t")
        state = {"n": 0, "split": 0}

        def inject(cmd, ctx):
            if cmd != "CopStream":
                return
            state["n"] += 1
            if state["n"] == 4 and not state["split"]:
                state["split"] = 1
                st.cluster.split(
                    tablecodec.record_key(info.id, N_ROWS // 8))

        failpoint.enable("rpc/request", inject)
        try:
            got = q(sess, "SELECT id FROM t ORDER BY id")
        finally:
            failpoint.disable("rpc/request")
        assert state["split"] == 1
        assert [r[0] for r in got] == list(range(N_ROWS))


class TestClosurePhaseInterruption:
    def test_drop_after_final_frame_does_not_rescan(self, sess,
                                                    streaming):
        """An interruption AFTER the final frame was delivered (e.g. a
        connection drop before STREAM_END) must not resume: for an
        open-ended final frame the resume cursor is b'' — re-issuing
        from it would replay the whole table as duplicates."""
        st = sess.storage
        orig = st.shim.coprocessor_stream
        fired = {"n": 0}

        def dying(ctx, req, **kw):
            for f in orig(ctx, req, **kw):
                yield f
                if f.last:
                    fired["n"] += 1
                    from tidb_tpu.kv import StreamInterruptedError
                    raise StreamInterruptedError("drop before END")

        st.shim.coprocessor_stream = dying
        try:
            got = q(sess, "SELECT id FROM t ORDER BY id")
        finally:
            st.shim.coprocessor_stream = orig
        assert fired["n"] >= 4          # every region's stream died late
        assert [r[0] for r in got] == list(range(N_ROWS))   # no dups


class TestMeshFeed:
    def test_streamed_frames_feed_mesh_superbatches(self, sess,
                                                    streaming):
        """Streamed coprocessor frames flow straight into the mesh
        executor's double-buffered host->HBM super-batches
        (executor/mesh.py _stream_groups) with NO intermediate full
        materialization: both streaming layers engage and the result
        matches the host path."""
        from tidb_tpu import devplane
        from tidb_tpu.executor import mesh as mesh_exec

        sql = "SELECT s, COUNT(*), SUM(v) FROM t GROUP BY s ORDER BY s"
        want = _materialized(sess, sql)
        devplane.enable_mesh(8)
        old = config.get_var("tidb_tpu_stream_rows")
        config.set_var("tidb_tpu_stream_rows", 256)
        mesh_exec.reset_stream_stats()
        try:
            got = q(sess, sql)
        finally:
            config.set_var("tidb_tpu_stream_rows", old)
            devplane.disable_mesh()
        mstats = mesh_exec.stream_stats()
        assert mstats["streams"] >= 1 and mstats["batches"] >= 2, mstats
        cstats = costream.stream_stats()
        assert cstats["frames"] > cstats["streams"]
        assert len(got) == len(want)
        for g, w in zip(got, want):
            assert g[0] == w[0] and g[1] == w[1]
            assert abs(float(g[2]) - float(w[2])) <= \
                1e-9 * max(1.0, abs(float(w[2])))


class TestRemoteStream:
    def test_wire_path_parity_and_backpressure(self, streaming):
        from tidb_tpu.store.remote import StorageServer, connect
        srv = StorageServer()
        srv.start()
        st = connect("127.0.0.1", srv.port)
        s = Session(st)
        try:
            s.execute("CREATE DATABASE d; USE d")
            s.execute("CREATE TABLE t (id BIGINT PRIMARY KEY, v BIGINT)")
            s.execute("INSERT INTO t VALUES " + ",".join(
                f"({i},{i * 3})" for i in range(1200)))
            want = _materialized(s, "SELECT COUNT(*), SUM(v) FROM t")
            costream.reset_stream_stats()
            got = q(s, "SELECT COUNT(*), SUM(v) FROM t")
            assert got == want
            stats = costream.stream_stats()
            assert stats["frames"] > 1
            assert stats["frame_bytes_max"] <= FRAME_BYTES
            # server-side blocking on the credit window happened: the
            # producer outran the consumer and was backpressured
            assert stats["credit_stalls"] >= 1
            # ordered scan over the wire, then plain requests still work
            # on the pooled connections (stream left them clean)
            rows = q(s, "SELECT id FROM t WHERE v > 30 ORDER BY id")
            assert [r[0] for r in rows] == list(range(11, 1200))
            assert q(s, "SELECT COUNT(*) FROM t") == [(1200,)]
        finally:
            s.close()
            st.close()
            srv.close()

    def test_frame_cap_is_the_clients_not_the_servers(self, streaming):
        """The frame cap ships WITH the request: against a storage node
        in another PROCESS (whose own sysvar default is 4 MiB), the
        client's SET must still bound every frame."""
        import subprocess
        import sys as _sys
        import time as _time
        proc = subprocess.Popen(
            [_sys.executable, "-m", "tidb_tpu.store.remote", "--port",
             "0"], stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True, cwd=REPO)
        try:
            line = proc.stdout.readline()
            assert "listening" in line, line
            port = int(line.rsplit(":", 1)[1])
            from tidb_tpu.store.remote import connect
            st = connect("127.0.0.1", port)
            s = Session(st)
            try:
                s.execute("CREATE DATABASE d; USE d")
                s.execute("CREATE TABLE t (id BIGINT PRIMARY KEY, "
                          "v BIGINT)")
                s.execute("INSERT INTO t VALUES " + ",".join(
                    f"({i},{i})" for i in range(1000)))
                # count frames client-side: raw rows are ~30B, so a
                # 512B cap over 1000 rows MUST multi-frame per region —
                # if the server used its own 4MiB default, one frame
                # per region would suffice
                config.set_var("tidb_tpu_copr_stream_frame_bytes", 512)
                frames = [0]
                orig = st.shim.coprocessor_stream

                def counting(ctx, req, **kw):
                    for f in orig(ctx, req, **kw):
                        frames[0] += 1
                        yield f

                st.shim.coprocessor_stream = counting
                assert q(s, "SELECT COUNT(*) FROM t") == [(1000,)]
                assert frames[0] > 20, frames
            finally:
                s.close()
                st.close()
        finally:
            proc.terminate()
            for _ in range(50):
                if proc.poll() is not None:
                    break
                _time.sleep(0.1)
            proc.kill()

    def test_wire_limit_abandons_stream_cleanly(self, streaming):
        """LIMIT abandons the stream mid-flight: the dropped connection
        must not poison the pool for later calls."""
        from tidb_tpu.store.remote import StorageServer, connect
        srv = StorageServer()
        srv.start()
        st = connect("127.0.0.1", srv.port)
        s = Session(st)
        try:
            s.execute("CREATE DATABASE d; USE d")
            s.execute("CREATE TABLE t (id BIGINT PRIMARY KEY, v BIGINT)")
            s.execute("INSERT INTO t VALUES " + ",".join(
                f"({i},{i})" for i in range(1500)))
            for _ in range(3):
                assert q(s, "SELECT id FROM t ORDER BY id LIMIT 2") == \
                    [(0,), (1,)]
                assert q(s, "SELECT COUNT(*) FROM t") == [(1500,)]
        finally:
            s.close()
            st.close()
            srv.close()


class TestStreamCacheIntegration:
    """COP_STREAM consults and fills the columnar cache hierarchy
    (store/stream.py module docstring) — the fix that let
    tidb_tpu_copr_stream default ON. Cold streams keep the bounded
    framed contract and fill the host chunk cache at stream end; warm
    streams serve one final frame per region straight from residency,
    and fused agg plans hit the HBM device cache."""

    @pytest.fixture
    def cached_streaming(self):
        old = {k: config.get_var(k) for k in
               ("tidb_tpu_copr_stream", "tidb_tpu_copr_stream_frame_bytes",
                "tidb_tpu_copr_stream_credit", "tidb_tpu_chunk_cache",
                "tidb_tpu_device_min_rows")}
        config.set_var("tidb_tpu_copr_stream", 1)
        config.set_var("tidb_tpu_copr_stream_frame_bytes", FRAME_BYTES)
        config.set_var("tidb_tpu_copr_stream_credit", CREDIT)
        config.set_var("tidb_tpu_chunk_cache", 1)
        config.set_var("tidb_tpu_device_min_rows", 1)
        costream.reset_stream_stats()
        yield
        for k, v in old.items():
            config.set_var(k, v)

    def test_streaming_defaults_on(self):
        """The documented default (docs/PERF.md): streaming no longer
        trades away cache residency, so it is on out of the box."""
        import tidb_tpu.config as cfg
        assert cfg._DEFS["tidb_tpu_copr_stream"][1] == 1

    def test_cold_fills_then_warm_single_frames(self, sess,
                                                cached_streaming):
        sql = "SELECT COUNT(*), SUM(v) FROM t"
        cold = q(sess, sql)
        st1 = costream.stream_stats()
        assert st1["streams"] >= 4
        assert st1["frames"] > st1["streams"]   # cold: real framed scan
        costream.reset_stream_stats()
        warm = q(sess, sql)
        st2 = costream.stream_stats()
        assert warm == cold
        # warm: every region serves as ONE final frame from the cache
        assert st2["streams"] >= 4
        assert st2["frames"] == st2["streams"]

    def test_warm_stream_hits_device_cache(self, sess, cached_streaming):
        sql = "SELECT COUNT(*), SUM(v) FROM t"
        q(sess, sql)            # cold: host-cache fill
        q(sess, sql)            # warm: device-cache fill (fused path)
        before = metrics.snapshot()
        got = q(sess, sql)      # warm: fused dispatch from HBM
        snap = metrics.snapshot()
        assert got == [(N_ROWS, sum(i * 7 % 1000 for i in range(N_ROWS)))]
        assert snap.get(metrics.HBM_CACHE_HITS, 0) - \
            before.get(metrics.HBM_CACHE_HITS, 0) >= 4
        assert snap.get(metrics.HBM_CACHE_MISSES, 0) == \
            before.get(metrics.HBM_CACHE_MISSES, 0)

    def test_write_between_streams_is_fresh(self, sess,
                                            cached_streaming):
        sql = "SELECT COUNT(*) FROM t"
        assert q(sess, sql) == [(N_ROWS,)]
        assert q(sess, sql) == [(N_ROWS,)]      # warm, from residency
        sess.execute(f"INSERT INTO t VALUES ({N_ROWS + 5}, 1, 'zz')")
        assert q(sess, sql) == [(N_ROWS + 1,)]  # version bump: fresh
        assert q(sess, sql) == [(N_ROWS + 1,)]  # and warm again

    def test_filter_scan_parity_warm_and_cold(self, sess,
                                              cached_streaming):
        sql = "SELECT id, v FROM t WHERE v >= 500 ORDER BY id"
        cold = q(sess, sql)
        warm = q(sess, sql)
        assert cold == warm == _materialized(sess, sql)

    def test_oversized_warm_agg_partial_streams_framed(self, sess,
                                                       cached_streaming):
        """A warm high-cardinality GROUP BY partial approaches the raw
        block size; shipping it as ONE cached frame would bust the
        streamed constant-client-memory contract. _cached_frame refuses
        (returns None) and the region streams framed from the raw scan
        instead — still correct, and the block stays resident for
        materialized readers."""
        sql = "SELECT v, COUNT(*) FROM t GROUP BY v ORDER BY v"
        cold = q(sess, sql)
        costream.reset_stream_stats()
        warm = q(sess, sql)
        st = costream.stream_stats()
        assert warm == cold == _materialized(sess, sql)
        # the ~1000-group partial busts the 1KB cap: every region must
        # fall back to the framed raw scan, never one unbounded frame
        assert st["streams"] >= 4
        assert st["frames"] > st["streams"]
        assert st["frame_bytes_max"] <= FRAME_BYTES
        # the refusal memoized the over-cap size: the next warm stream
        # skips the wasted fused dispatch and goes straight to the raw
        # framed scan — _cached_frame must not run at all
        calls = []
        orig = costream._cached_frame
        costream._cached_frame = lambda *a, **k: calls.append(1) or \
            orig(*a, **k)
        try:
            assert q(sess, sql) == cold
        finally:
            costream._cached_frame = orig
        assert not calls
