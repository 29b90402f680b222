"""The program's spans as counters and on the profiler's clock (PR 24):

* the self-time walk (`trace.self_times`) against hand-built trees;
* `tidb_tpu_span_self_seconds_total` / `tidb_tpu_span_count_total` move
  by exactly one statement's tree when its root ends;
* the streamed and the materialized scan of one table both produce
  `copr.kv_scan` / `copr.decode` / `copr.exec`, `rows` summing to the
  table, and count its rows in `tidb_tpu_decode_rows_total` under the
  decoder that built the chunks (an index layout under `python`);
* under a live `jax.profiler` session the `.xplane.pb` host plane holds
  the statement's span names with one shared trace id, workers' spans on
  their own threads; outside a session no annotation is constructed;
* every jitted program lowers as `jit_<family>` (profiler.FAMILIES);
* `tidb_tpu_h2d_bytes_total` counts the padded bytes of a chunk put once
  and stays put on a memo or HBM-cache hit;
* the wire-write counters move by the bytes a resultset put on the socket.
"""

import glob
import threading

import numpy as np
import pytest

from tidb_tpu import config, metrics, profiler, trace
from tidb_tpu.session import Session
from tidb_tpu.store.storage import new_mock_storage

SELF = 'tidb_tpu_span_self_seconds_total{span="%s"}'
COUNT = 'tidb_tpu_span_count_total{span="%s"}'
DECODED = 'tidb_tpu_decode_rows_total{path="%s"}'


# -- the self-time walk against hand-built trees ----------------------------

def _span(name, start, end, tid=1, children=()):
    s = trace.Span(name)
    s.start_ns, s.end_ns, s.tid = start, end, tid
    s.children = list(children)
    return s


def _nested():
    # root 0-100: A 10-40 holds G 20-30; B 50-90
    return _span("statement", 0, 100, children=[
        _span("execute", 10, 40, children=[_span("dispatch", 20, 30)]),
        _span("commit", 50, 90)]), {
        "statement": [30, 1], "execute": [20, 1], "dispatch": [10, 1],
        "commit": [40, 1]}


def _cross_thread():
    # the worker's span runs beside the root and keeps its own time
    return _span("statement", 0, 100, children=[
        _span("copr.task", 10, 90, tid=2)]), {
        "statement": [100, 1], "copr.task": [80, 1]}


def _mixed():
    # execute (same thread) fans out to two workers; each worker's
    # children are on the worker's thread
    w1 = _span("copr.stream", 12, 60, tid=2, children=[
        _span("copr.kv_scan", 12, 20, tid=2),
        _span("copr.decode", 20, 50, tid=2),
        _span("copr.exec", 50, 58, tid=2)])
    w2 = _span("copr.stream", 12, 70, tid=3, children=[
        _span("copr.kv_scan", 14, 18, tid=3),
        _span("copr.decode", 18, 66, tid=3)])
    ex = _span("execute", 10, 95, children=[
        w1, w2, _span("finalize", 80, 90)])
    return _span("statement", 0, 100, children=[
        _span("parse", 0, 5), ex]), {
        "statement": [10, 1], "parse": [5, 1], "execute": [75, 1],
        "finalize": [10, 1], "copr.stream": [2 + 6, 2],
        "copr.kv_scan": [8 + 4, 2], "copr.decode": [30 + 48, 2],
        "copr.exec": [8, 1]}


def _repeated_names():
    kids = [_span("dispatch", 10 * i, 10 * i + 4) for i in range(1, 9)]
    return _span("execute", 0, 100, children=kids), {
        "execute": [100 - 8 * 4, 1], "dispatch": [8 * 4, 8]}


def _root_only():
    return _span("statement", 7, 19), {"statement": [12, 1]}


def _grafted_overlap():
    # attach_remote pins grafted trees at "now": two of them overlap,
    # and one starts before its parent; each covers only what the one
    # before did not, clipped to the parent
    return _span("execute", 100, 200, children=[
        _span("storage:coprocessor_stream", 90, 150),
        _span("storage:coprocessor_stream", 120, 170)]), {
        "execute": [100 - 50 - 20, 1],
        "storage:coprocessor_stream": [60 + 50, 2]}


@pytest.mark.parametrize("build", [
    _nested, _cross_thread, _mixed, _repeated_names, _root_only,
    _grafted_overlap], ids=lambda f: f.__name__.lstrip("_"))
def test_self_times_against_hand_counts(build):
    root, want = build()
    assert trace.self_times(root) == want


def test_self_times_reads_an_open_span_as_closed_now():
    import time
    now = time.perf_counter_ns()
    child = _span("execute", now - 3_000_000, 0)         # still open
    root = _span("statement", now - 5_000_000, 0, children=[child])
    got = trace.self_times(root)
    # the child covers all of the root but the 2 ms before it began
    assert got["statement"] == [2_000_000, 1]
    assert got["execute"][1] == 1
    assert 3_000_000 <= got["execute"][0] < 3_000_000 + 5_000_000_000


def test_self_times_partition_a_single_threaded_statement():
    root = trace.begin("statement")
    with trace.span("plan"):
        pass
    with trace.span("execute"):
        with trace.span("dispatch"):
            pass
        with trace.span("finalize"):
            pass
    trace.end(root)
    st = trace.self_times(root)
    assert sum(ns for ns, _n in st.values()) == root.duration_ns
    assert trace.validate(root) == []


# -- the counters move by exactly one statement's tree ----------------------

def _span_counters():
    snap = metrics.snapshot()
    return {k: v for k, v in snap.items()
            if k.startswith(("tidb_tpu_span_self_seconds_total",
                             "tidb_tpu_span_count_total"))}


def test_counters_move_by_exactly_one_tree():
    root, want = _mixed()
    root.sampled = root.forced = False
    before = _span_counters()
    assert trace.finish_statement(root, "SELECT 1", slow_ms=0) is None
    after = _span_counters()
    moved = {k for k in after if after[k] != before.get(k, 0)}
    assert moved == {SELF % n for n in want} | {COUNT % n for n in want}
    for name, (ns, n) in want.items():
        assert after[SELF % name] - before.get(SELF % name, 0) == \
            pytest.approx(ns / 1e9, abs=1e-12)
        assert after[COUNT % name] - before.get(COUNT % name, 0) == n
    # the Prometheus exposition carries the same series
    text = metrics.expose()
    assert "# TYPE tidb_tpu_span_self_seconds_total counter" in text
    assert 'tidb_tpu_span_count_total{span="copr.decode"}' in text


def test_an_executed_statement_folds_its_own_tree():
    storage = new_mock_storage()
    s = Session(storage)
    try:
        s.execute("CREATE DATABASE sc1")
        s.execute("USE sc1")
        s.execute("CREATE TABLE t (a INT PRIMARY KEY, b INT)")
        s.execute("INSERT INTO t VALUES (1, 2), (3, 4)")
        before = _span_counters()
        s.query("SELECT b FROM t WHERE a = 3")
        after = _span_counters()
    finally:
        s.close()

    def d(key):
        return after.get(key, 0) - before.get(key, 0)

    assert d(COUNT % "statement") == 1
    assert d(COUNT % "execute") == 1
    assert d(COUNT % "parse") == 1
    assert d(SELF % "statement") > 0 and d(SELF % "execute") > 0


# -- the scan's three steps, streamed and materialized ----------------------

N_ROWS = 5000


@pytest.fixture(scope="module")
def scan_session():
    storage = new_mock_storage()
    s = Session(storage)
    s.execute("CREATE DATABASE sc2")
    s.execute("USE sc2")
    s.execute("CREATE TABLE t (a INT PRIMARY KEY, b INT, c VARCHAR(16))")
    for lo in range(0, N_ROWS, 1000):
        s.execute("INSERT INTO t VALUES " + ", ".join(
            f"({i}, {i % 7}, 'v{i % 13}')" for i in range(lo, lo + 1000)))
    info = s.domain.info_schema().table("sc2", "t")
    storage.cluster.split_table(info.id, 4, max_handle=N_ROWS)
    yield s
    s.close()


def _walk(span, out):
    out.append(span)
    for c in span.children:
        _walk(c, out)
    return out


def _native_built() -> bool:
    from tidb_tpu import native
    return native.decoder_kind() == "native"


def _decoded_rows() -> dict:
    snap = metrics.snapshot()
    return {path: snap.get(DECODED % path, 0)
            for path in ("native", "python")}


@pytest.mark.parametrize("stream", [1, 0], ids=["streamed", "materialized"])
def test_scan_steps_on_both_paths(scan_session, stream):
    s = scan_session
    trace.reset_for_tests()
    # a cold scan every time: the chunk cache would serve the second one
    with config.session_overlay({"tidb_tpu_copr_stream": stream,
                                 "tidb_tpu_chunk_cache": 0,
                                 "tidb_tpu_trace_sample": 1}):
        rows = s.query("SELECT a, b, c FROM t WHERE b < 7").rows
    assert len(rows) == N_ROWS
    rec = [r for r in trace.ring_records() if "FROM t" in r["sql"]][-1]
    spans = _walk(rec["root"], [])
    by_name = {}
    for sp in spans:
        by_name.setdefault(sp.name, []).append(sp)
    assert ("copr.stream" in by_name) == bool(stream)
    for name in ("copr.kv_scan", "copr.decode", "copr.exec"):
        assert name in by_name, sorted(by_name)
        assert sum(sp.tags["rows"] for sp in by_name[name]) == N_ROWS, name
    # a VARCHAR column is a layout native/codec.cc takes
    assert {sp.tags["native"] for sp in by_name["copr.decode"]} == \
        {int(_native_built())}
    assert trace.validate(rec["root"]) == []


@pytest.mark.parametrize("stream", [1, 0], ids=["streamed", "materialized"])
def test_decode_rows_counted_under_the_decoder_that_ran(scan_session,
                                                        stream):
    s = scan_session
    ran, other = ("native", "python") if _native_built() \
        else ("python", "native")
    before = _decoded_rows()
    # untraced: the counter does not hang on the span being kept
    with config.session_overlay({"tidb_tpu_copr_stream": stream,
                                 "tidb_tpu_chunk_cache": 0,
                                 "tidb_tpu_trace_sample": 0}):
        assert len(s.query("SELECT a, b, c FROM t WHERE b < 7").rows) == \
            N_ROWS
    after = _decoded_rows()
    assert after[ran] - before[ran] == N_ROWS
    assert after[other] == before[other]


def test_an_index_scan_counts_as_python(scan_session):
    s = scan_session
    s.execute("CREATE TABLE IF NOT EXISTS ix (a INT PRIMARY KEY, b INT, "
              "KEY ib (b))")
    s.execute("INSERT IGNORE INTO ix VALUES " + ", ".join(
        f"({i}, {i % 5})" for i in range(200)))
    trace.reset_for_tests()
    before = _decoded_rows()
    with config.session_overlay({"tidb_tpu_chunk_cache": 0,
                                 "tidb_tpu_trace_sample": 1}):
        rows = s.query("SELECT b FROM ix USE INDEX (ib) WHERE b = 3").rows
    after = _decoded_rows()
    assert len(rows) == 40
    rec = [r for r in trace.ring_records() if "FROM ix" in r["sql"]][-1]
    dec = [sp for sp in _walk(rec["root"], []) if sp.name == "copr.decode"]
    assert dec and {sp.tags["native"] for sp in dec} == {0}
    assert after["python"] - before["python"] == 40
    assert after["native"] == before["native"]


def test_decode_span_says_when_the_native_codec_took_the_layout(
        scan_session):
    s = scan_session
    trace.reset_for_tests()
    with config.session_overlay({"tidb_tpu_chunk_cache": 0,
                                 "tidb_tpu_trace_sample": 1}):
        s.execute("CREATE TABLE IF NOT EXISTS f (a INT PRIMARY KEY, b INT)")
        s.execute("INSERT IGNORE INTO f VALUES (1, 1), (2, 2), (3, 3)")
        trace.reset_for_tests()
        assert len(s.query("SELECT a, b FROM f WHERE b < 9").rows) == 3
    rec = [r for r in trace.ring_records() if "FROM f" in r["sql"]][-1]
    dec = [sp for sp in _walk(rec["root"], []) if sp.name == "copr.decode"]
    assert dec and {sp.tags["native"] for sp in dec} == \
        {int(_native_built())}


# -- the same spans on the profiler's clock ---------------------------------

def _host_events(trace_dir):
    """[(line index, name, trace_id)] of the program's spans on the host
    planes of the one .xplane.pb under `trace_dir`."""
    from jax.profiler import ProfileData
    [path] = glob.glob(str(trace_dir) + "/**/*.xplane.pb", recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            for e in line.events:
                if e.name in trace.SPAN_NAMES:
                    out.append((i, e.name, dict(e.stats).get("trace_id")))
    return out


def test_spans_are_annotations_under_a_live_profiler_session(
        scan_session, tmp_path):
    import jax
    s = scan_session
    made = []
    real = trace._annotation

    def counting(name, root):
        made.append(name)
        return real(name, root)

    trace._annotation = counting
    try:
        # outside a session: the statement constructs no annotation
        with config.session_overlay({"tidb_tpu_chunk_cache": 0}):
            s.query("SELECT a, b, c FROM t WHERE b < 7")
        assert made == []
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            with config.session_overlay({"tidb_tpu_chunk_cache": 0,
                                         "tidb_tpu_copr_stream": 1}):
                s.query("SELECT a, b, c FROM t WHERE b < 7")
        finally:
            jax.profiler.stop_trace()
        assert "statement" in made and "copr.decode" in made
        n_made = len(made)
        s.query("SELECT a FROM t WHERE a = 1")       # the session is over
        assert len(made) == n_made
    finally:
        trace._annotation = real
    events = _host_events(tmp_path)
    names = {n for _l, n, _t in events}
    assert {"statement", "execute", "copr.stream", "copr.kv_scan",
            "copr.decode", "copr.exec"} <= names, names
    # one statement ran inside the session: one shared trace id
    ids = {t for _l, _n, t in events}
    assert len(ids) == 1 and None not in ids
    # the workers' spans sit on their own threads' lines
    root_lines = {ln for ln, n, _t in events if n == "statement"}
    worker_lines = {ln for ln, n, _t in events if n == "copr.stream"}
    assert len(root_lines) == 1 and worker_lines
    assert not (root_lines & worker_lines)


@pytest.mark.parametrize("boundary", sorted(trace._RECHECK_SPANS))
def test_a_long_statement_joins_a_session_at_a_coarse_boundary(boundary):
    """A root that began before the profiler session stays dark only
    until its next copr.task / copr.stream / sched.slot span or its
    next decoded frame."""
    flips = iter([False, True])
    made = []
    trace._profiling()                  # binds trace._TraceMe
    real_tm, real_ann = trace._TraceMe, trace._annotation

    class _Ann:
        def __exit__(self, *a):
            made.append("exit")

    class _FakeTraceMe:
        is_enabled = staticmethod(lambda: next(flips))

    trace._TraceMe = _FakeTraceMe
    trace._annotation = lambda name, root: made.append(name) or _Ann()
    try:
        root = trace.begin("statement")           # no session yet
        with trace.span("execute"):
            with trace.span("copr.kv_scan"):
                pass                              # still dark
            assert made == []
            with trace.span(boundary):            # the session opened
                pass
            with trace.span("copr.exec"):
                pass
        trace.end(root)
    finally:
        trace._TraceMe, trace._annotation = real_tm, real_ann
        trace._session = False
    assert made == [boundary, "exit", "copr.exec", "exit"]
    assert root.live is True


# -- every jitted program is named by its family ----------------------------

def _module_of(prog, *args, **kwargs) -> str:
    return prog.lower(*args, **kwargs).as_text().split(" ", 2)[1]


def _agg_chunk(n=300):
    from tidb_tpu import sqltypes as st
    from tidb_tpu.chunk import Chunk, Column
    rng = np.random.default_rng(5)
    return Chunk([Column(st.new_int_field(), rng.integers(0, 6, n),
                         np.ones(n, bool)),
                  Column(st.new_int_field(), rng.integers(0, 50, n),
                         np.ones(n, bool))])


def _agg_parts():
    from tidb_tpu import sqltypes as st
    from tidb_tpu.expression import AggDesc, AggFunc, col
    INT = st.new_int_field()
    return [col(0, INT)], [AggDesc(AggFunc.SUM, col(1, INT)),
                           AggDesc(AggFunc.COUNT, None)]


def _lower_hashagg():
    from tidb_tpu.ops import runtime
    from tidb_tpu.ops.hashagg import HashAggKernel
    groups, aggs = _agg_parts()
    ch = _agg_chunk()
    k = HashAggKernel(None, groups, aggs, capacity=64)
    cols, _d = runtime.device_put_chunk(ch, memo=False)
    return [_module_of(k._jit, cols, ch.num_rows)]


def _lower_scalaragg():
    from tidb_tpu.ops import runtime
    from tidb_tpu.ops.hashagg import ScalarAggKernel
    _groups, aggs = _agg_parts()
    ch = _agg_chunk()
    k = ScalarAggKernel(None, aggs)
    cols, _d = runtime.device_put_chunk(ch, memo=False)
    return [_module_of(k._jit, cols, ch.num_rows)]


def _lower_streamagg():
    from tidb_tpu.ops import runtime
    from tidb_tpu.ops.streamagg import SegmentAggKernel
    groups, aggs = _agg_parts()
    ch = _agg_chunk()
    k = SegmentAggKernel(groups, aggs)
    cols, _d = runtime.device_put_chunk(ch, memo=False)
    return [_module_of(k._jit, cols, ch.num_rows)]


def _key_lanes(n, size):
    import jax.numpy as jnp
    from tidb_tpu.ops import runtime
    d, v = runtime.pad_column(np.arange(n, dtype=np.int64),
                              np.ones(n, bool), size)
    return [(jnp.asarray(d), jnp.asarray(v))]


def _lower_fragment():
    from tidb_tpu.ops import runtime
    from tidb_tpu.ops.fragment import ProbeAggKernel
    groups, aggs = _agg_parts()
    ch = _agg_chunk(200)
    k = ProbeAggKernel(1, 2, 3, groups, aggs, capacity=64)
    pcols, _d = runtime.device_put_chunk(k._probe_sub(ch), 1024,
                                         memo=False)
    return [_module_of(k._jit, _key_lanes(50, 1024), _key_lanes(200, 1024),
                       pcols, [], 50, 200, out_cap=1024)]


def _lower_join():
    from tidb_tpu.ops.join import _matcher_program
    return [_module_of(_matcher_program(1024), _key_lanes(50, 1024),
                       _key_lanes(200, 1024), 50, 200)]


def _lower_sort():
    from tidb_tpu.ops.stats import _jit_sort
    return [_module_of(_jit_sort, np.arange(1024))]


def _plane_mesh():
    from tidb_tpu import devplane
    return devplane.build_mesh(4)


def _lower_mesh():
    import jax.numpy as jnp
    from tidb_tpu.ops.meshagg import MeshAggKernel
    groups, aggs = _agg_parts()
    ch = _agg_chunk(400)
    k = MeshAggKernel(_plane_mesh(), None, groups, aggs, capacity=64)
    cols, _ln = k._shard_probe(ch)
    return [_module_of(k._jit, cols, jnp.int64(ch.num_rows))]


def _lower_plane():
    """The `plane` family is whatever devplane.plane_jit stages: the
    shuffle join's program and the lookup join's three stages."""
    import inspect

    from tidb_tpu.ops import meshjoin
    from tidb_tpu.ops.meshshuffle import MeshShuffleJoinKernel
    mesh = _plane_mesh()
    k = MeshShuffleJoinKernel(mesh, 1)
    prog = k._program(256, 256, 256, 256, 1024)
    lanes = tuple((np.zeros(1024, np.int64), np.ones(1024, bool))
                  for _ in range(1))
    got = [_module_of(prog, lanes, lanes, np.int64(900), np.int64(900))]
    # the lookup join's stages are bucketed program memos built deep in
    # its host driver: pin the name each construction site hands over
    src = inspect.getsource(meshjoin.MeshLookupAggKernel)
    assert src.count('name="meshjoin"') == 3
    return got


_EXPECTED_MODULES = {
    "hashagg": {"@jit_hashagg"}, "scalaragg": {"@jit_scalaragg"},
    "streamagg": {"@jit_streamagg"}, "fragment": {"@jit_fragment"},
    "join": {"@jit_join"}, "sort": {"@jit_sort"},
    "mesh": {"@jit_meshagg"}, "plane": {"@jit_meshshuffle"},
}


@pytest.mark.parametrize("family", profiler.FAMILIES)
def test_lowered_module_is_named_by_its_family(family):
    got = globals()["_lower_" + family]()
    assert set(got) == _EXPECTED_MODULES[family]


def test_join_and_sort_have_kernel_profile_rows():
    from tidb_tpu.ops.join import JoinKernel
    from tidb_tpu.ops.stats import device_sort
    profiler.reset_for_tests()
    keys = [(np.arange(300, dtype=np.int64), np.ones(300, bool))]
    li, ri = JoinKernel(1)(keys, keys, 300, 300)
    assert len(li) == len(ri) == 300
    data = np.random.default_rng(3).integers(0, 1000, 700)
    assert (device_sort(data) == np.sort(data)).all()
    rows = {r["family"]: r for r in profiler.snapshot()}
    assert {"join", "sort"} <= set(rows)
    assert rows["join"]["dispatches"] == 1 and rows["sort"]["dispatches"] == 1
    assert rows["join"]["bytes_in"] > 0


def test_a_compile_inside_a_statement_is_a_point_event():
    from tidb_tpu.ops.stats import _SEEN, device_sort
    profiler.reset_for_tests()
    _SEEN.clear()
    root = trace.begin("statement")
    with trace.span("execute") as sp:
        device_sort(np.arange(2000, dtype=np.int32)[::-1].copy())
        device_sort(np.arange(2000, dtype=np.int32))      # warm: no event
    trace.end(root)
    assert [(n, tg) for n, _t, tg in sp.events] == \
        [("kernel.compile", {"family": "sort"})]


# -- host->device bytes ------------------------------------------------------

def _h2d():
    return metrics.snapshot().get("tidb_tpu_h2d_bytes_total", 0)


def test_h2d_bytes_are_the_padded_bytes_of_a_chunk_put_once():
    from tidb_tpu.ops import runtime
    ch = _agg_chunk(300)                 # pads to the 1024-row bucket
    want = sum(1024 * (c.data.dtype.itemsize + 1) for c in ch.columns)
    b0 = _h2d()
    runtime.device_put_chunk(ch)
    assert _h2d() - b0 == want
    runtime.device_put_chunk(ch)         # the chunk's own memo: no transfer
    assert _h2d() - b0 == want
    runtime.device_put_chunk(ch, to_device=False, memo=False)
    assert _h2d() - b0 == want           # nothing handed to the device


def test_h2d_bytes_do_not_move_on_an_hbm_cache_hit(scan_session):
    s = scan_session
    sql = "SELECT b, COUNT(*), SUM(a) FROM t GROUP BY b"
    hits0 = metrics.snapshot().get("tidb_tpu_hbm_cache_hits_total", 0)
    with config.session_overlay({"tidb_tpu_device": 1,
                                 "tidb_tpu_device_min_rows": 1}):
        first = s.query(sql).rows
        s.query(sql)                     # second serve fills the HBM tier
        b0 = _h2d()
        assert sorted(s.query(sql).rows) == sorted(first)
        moved = _h2d() - b0
    assert metrics.snapshot().get("tidb_tpu_hbm_cache_hits_total", 0) > hits0
    assert moved == 0


# -- the wire write ----------------------------------------------------------

def test_wire_write_counters_move_by_the_resultset_bytes():
    from tidb_tpu.server import ClientConn
    from tidb_tpu.session import ResultSet

    class _Sock:
        def __init__(self):
            self.got = b""

        def sendall(self, b):
            self.got += b

    sock = _Sock()
    conn = ClientConn(server=None, sock=sock, conn_id=1)
    rs = ResultSet(["a", "b"], [(1, "x"), (None, "yy"), (3, "zzz")])
    before = metrics.snapshot()
    conn._write_resultset(rs)
    after = metrics.snapshot()
    assert len(sock.got) > 0
    assert after["tidb_tpu_wire_write_bytes_total"] - \
        before.get("tidb_tpu_wire_write_bytes_total", 0) == len(sock.got)
    assert after["tidb_tpu_wire_write_seconds_total"] > \
        before.get("tidb_tpu_wire_write_seconds_total", 0)


def test_span_threads_are_recorded_for_worker_spans(scan_session):
    """A worker's span carries the worker's thread id, which is what the
    self-time walk keys "same thread" on."""
    s = scan_session
    trace.reset_for_tests()
    with config.session_overlay({"tidb_tpu_chunk_cache": 0,
                                 "tidb_tpu_copr_stream": 1,
                                 "tidb_tpu_trace_sample": 1}):
        s.query("SELECT a, b, c FROM t WHERE b < 7")
    rec = [r for r in trace.ring_records() if "FROM t" in r["sql"]][-1]
    spans = _walk(rec["root"], [])
    me = threading.get_ident()
    assert rec["root"].tid == me
    workers = [sp for sp in spans if sp.name == "copr.stream"]
    assert workers and all(sp.tid != me for sp in workers)
    for w in workers:
        assert all(c.tid == w.tid for c in _walk(w, []))


# -- the root executors under spans (PR 33) ---------------------------------
# exec.join / exec.apply (+ exec.apply.inner) / exec.agg / exec.topn are
# open while the operator's own generator body runs and closed while it
# waits for a child's chunk or its consumer holds one (executor._OwnSpan):
# Q18 runs all five.

EXEC_SPANS = ("exec.join", "exec.apply", "exec.apply.inner", "exec.agg",
              "exec.topn")
FINAL_GROUPS = "tidb_tpu_agg_final_groups_total"


@pytest.fixture(scope="module")
def q18_session():
    import tpch
    s = Session(new_mock_storage())
    s.execute("CREATE DATABASE sc18")
    s.execute("USE sc18")
    s._data = tpch.Q18Data(customers=80, orders=1500, lineitems=6000)
    tpch.load_q18(s, s._data)
    s._sql = tpch.Q18.format(quantity=250)
    s._want, s._qualified = tpch.truth_q18(s._data, 250)
    yield s
    s.close()


def _traced_q18(s):
    """One Q18, retained: -> (its root, the counters' moves)."""
    trace.reset_for_tests()
    before = metrics.snapshot()
    with config.session_overlay({"tidb_tpu_trace_sample": 1}):
        rows = s.query(s._sql).rows
    after = metrics.snapshot()
    assert len(rows) == len(s._want) > 0
    rec = [r for r in trace.ring_records() if "l_quantity" in r["sql"]][-1]
    return rec["root"], {k: v - before.get(k, 0) for k, v in after.items()
                         if v != before.get(k, 0)}


def test_root_executor_spans_fold_into_the_counters(q18_session):
    root, moved = _traced_q18(q18_session)
    assert trace.validate(root) == []
    st = trace.self_times(root)
    for name in EXEC_SPANS:
        assert name in st, sorted(st)
        ns, n = st[name]
        assert moved[COUNT % name] == n >= 1
        assert moved[SELF % name] == pytest.approx(ns / 1e9, abs=1e-9)
        assert ns > 0
    # an uncorrelated inner runs once
    assert st["exec.apply.inner"][1] == 1


def test_final_groups_counter_equals_the_groups_emitted(q18_session):
    s = q18_session
    root, moved = _traced_q18(s)
    orders_with_lines = len(set(s._data.l_orderkey.tolist()))
    # the subquery's FinalAgg emits every order, the HashAgg above the
    # join the orders that qualified (LIMIT cuts later, in TopN)
    assert s._qualified > 0
    assert moved[FINAL_GROUPS] == orders_with_lines + s._qualified
    tags = sorted(sp.tags["groups"] for sp in _walk(root, [])
                  if sp.name == "exec.agg" and "groups" in sp.tags)
    assert tags == sorted([orders_with_lines, s._qualified])


def test_a_parents_self_time_excludes_its_children(q18_session):
    root, _moved = _traced_q18(q18_session)
    spans = _walk(root, [])
    st = trace.self_times(root)
    inner = [sp for sp in spans if sp.name == "exec.apply.inner"]
    applies = [sp for sp in spans if sp.name == "exec.apply"]
    assert len(inner) == 1 and inner[0] in [
        c for a in applies for c in a.children]
    # exec.apply's seconds do not contain the inner plan's run ...
    assert st["exec.apply"][0] == \
        sum(a.duration_ns for a in applies) - inner[0].duration_ns
    # ... and the inner's own do not contain its aggregate's merge
    nested = [c for c in inner[0].children if c.tid == inner[0].tid]
    assert {c.name for c in nested} >= {"exec.agg"}
    assert st["exec.apply.inner"][0] == \
        inner[0].duration_ns - sum(c.duration_ns for c in nested)
    assert st["exec.apply.inner"][0] < inner[0].duration_ns


def test_no_root_executor_span_is_open_across_a_pull(q18_session):
    """A child's chunk is pulled with the operator's span closed, so a
    reader's fan-out (copr.*) and the operators below hang beside an
    exec.* span, never under it; what an exec.* span holds is the device
    work it started itself, and exec.apply its inner."""
    root, _moved = _traced_q18(q18_session)
    own_children = {"sched.slot", "dispatch", "finalize", "join.partition",
                    "host.fallback"}
    for sp in _walk(root, []):
        if sp.name in ("exec.join", "exec.agg", "exec.topn"):
            assert {c.name for c in sp.children} <= own_children, sp.name
        elif sp.name == "exec.apply":
            assert {c.name for c in sp.children} <= {"exec.apply.inner"}


def test_abandoned_operators_leave_no_span_open(q18_session):
    """LIMIT stops pulling with joins mid-stream: every exec.* span was
    already closed at the yield, and the next statement's tree is whole."""
    s = q18_session
    trace.reset_for_tests()
    with config.session_overlay({"tidb_tpu_trace_sample": 1}):
        rows = s.query("SELECT o_orderkey, l_quantity FROM orders, lineitem "
                       "WHERE o_orderkey = l_orderkey LIMIT 3").rows
        assert len(rows) == 3
        s.query("SELECT COUNT(*) FROM customer")
    recs = trace.ring_records()
    assert len(recs) == 2
    for rec in recs:
        assert trace.validate(rec["root"]) == []
    assert "exec.join" in {sp.name for sp in _walk(recs[0]["root"], [])}
    assert trace.current_root() is None


def test_drive_closes_the_body_inside_the_span():
    """A consumer that stops early closes `drive` at its yield; the
    body's own clean-up (its `finally`) must run then, not whenever the
    collector gets to it, and under the operator's span."""
    from tidb_tpu.executor import _OwnSpan
    events = []

    class Cm:
        def __exit__(self, *exc):
            events.append("close")

    def open_span():
        events.append("open")
        return Cm()

    def body():
        try:
            yield 1
            yield 2
        finally:
            events.append("cleanup")

    own = _OwnSpan(open_span)
    gen = own.drive(body())
    assert next(gen) == 1
    assert events == ["open", "close"]      # closed across the yield
    gen.close()
    assert events == ["open", "close", "open", "cleanup", "close"]
