"""Host work that holds the chip idle, as counters:

* the gc hook: a collection moves `tidb_tpu_gc_pause_seconds_total` and
  `tidb_tpu_gc_collections_total` of its generation, the hook goes in
  once however often it is installed;
* a supervised beat moves `tidb_tpu_worker_beat_seconds_total` /
  `…_count_total`, a beat that raises included, and a worker's series
  exist at 0 before its first beat;
* under a live profiler session a collection and a beat are `gc.pause`
  / `worker.beat` annotations on the host planes, and outside one no
  annotation is made;
* `tidb_tpu_cop_ranges_total{served,why}`: a cold range streams `cold`,
  then serves `resident` while its entry fits a frame and streams
  `over_frame` under a smaller frame cap; `uncacheable` on both paths
  with the chunk cache off, and the materialized handler's miss and hit.
"""

import gc
import glob
import re
import threading
import time

import pytest

from tidb_tpu import config, metrics, trace
from tidb_tpu.session import Session
from tidb_tpu.store.storage import new_mock_storage
from tidb_tpu.util import supervisor

PAUSE = 'tidb_tpu_gc_pause_seconds_total{generation="%d"}'
COLLECTIONS = 'tidb_tpu_gc_collections_total{generation="%d"}'
BEAT_S = 'tidb_tpu_worker_beat_seconds_total{worker="%s"}'
BEATS = 'tidb_tpu_worker_beat_count_total{worker="%s"}'
RESTARTS = 'tidb_tpu_worker_restarts_total{worker="%s"}'


def _moved(before: dict, after: dict, prefix: str) -> dict:
    return {k: after[k] - before.get(k, 0) for k in after
            if k.startswith(prefix) and after[k] != before.get(k, 0)}


# -- the collector's pauses --------------------------------------------------

def test_a_collection_moves_both_gc_counters():
    trace.install_gc_hook()
    trace.install_gc_hook()                     # once, however often
    assert gc.callbacks.count(trace._on_gc) == 1
    before = metrics.snapshot()
    gc.collect()
    after = metrics.snapshot()
    assert after[COLLECTIONS % 2] - before[COLLECTIONS % 2] >= 1
    assert after[PAUSE % 2] > before[PAUSE % 2]
    text = metrics.expose()
    assert "# TYPE tidb_tpu_gc_pause_seconds_total counter" in text
    assert COLLECTIONS % 0 in text


# -- the supervised beats ----------------------------------------------------

def test_a_beat_counts_its_time_and_a_raising_one_too():
    name = "test-beat-counted"
    stop = threading.Event()
    calls = []

    def beat():
        calls.append(1)
        time.sleep(0.02)
        if len(calls) == 2:
            raise RuntimeError("the second beat fails")
        if len(calls) == 3:
            stop.set()

    t = supervisor.supervise(name, beat, stop, 0.001)
    t.join(10)
    assert not t.is_alive() and len(calls) == 3
    snap = metrics.snapshot()
    assert snap[BEATS % name] == 3
    assert snap[BEAT_S % name] >= 3 * 0.02
    assert snap[RESTARTS % name] == 1


def test_a_worker_has_its_beat_series_before_its_first_beat():
    name = "test-beat-idle"
    stop = threading.Event()
    stop.set()
    t = supervisor.supervise(name, lambda: None, stop, 0.001)
    t.join(10)
    assert not t.is_alive()
    snap = metrics.snapshot()
    assert snap[BEATS % name] == 0 and snap[BEAT_S % name] == 0


# -- on the device trace's clock ---------------------------------------------

def test_pauses_and_beats_are_annotations_under_a_live_session(tmp_path):
    import jax
    from jax.profiler import ProfileData
    trace.install_gc_hook()
    made = []
    real = trace.host_annotation

    def counting(name, **meta):
        ann = real(name, **meta)
        made.append((name, ann is not None))
        return ann

    trace.host_annotation = counting
    try:
        gc.collect()                            # no session: nothing made
        assert made and not any(live for _n, live in made)
        made.clear()
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            gc.collect()
            stop = threading.Event()
            t = supervisor.supervise("test-beat-traced", stop.set, stop,
                                     0.001)
            t.join(10)
            assert not t.is_alive()
        finally:
            jax.profiler.stop_trace()
    finally:
        trace.host_annotation = real
    assert ("gc.pause", True) in made and ("worker.beat", True) in made
    [path] = glob.glob(str(tmp_path) + "/**/*.xplane.pb", recursive=True)
    got = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in trace.HOST_MARKS:
                        got.setdefault(e.name, []).append(dict(e.stats))
    assert {2} <= {st.get("generation") for st in got["gc.pause"]}
    assert "test-beat-traced" in {st.get("worker")
                                  for st in got["worker.beat"]}


# -- why a range was served as it was ----------------------------------------

N_ROWS = 4000


@pytest.fixture(scope="module")
def ranges_session():
    s = Session(new_mock_storage())
    s.execute("CREATE DATABASE hr")
    s.execute("USE hr")
    s.execute("CREATE TABLE t (a INT PRIMARY KEY, b INT, c INT)")
    for lo in range(0, N_ROWS, 1000):
        s.execute("INSERT INTO t VALUES " + ", ".join(
            f"({i}, {i % 5}, {i % 3})" for i in range(lo, lo + 1000)))
    info = s.domain.info_schema().table("hr", "t")
    s.storage.cluster.split_table(info.id, 4, max_handle=N_ROWS)
    yield s
    s.close()


def _ranges(s, sql: str, **overlay) -> dict:
    """{(served, why): ranges} one statement counted."""
    before = metrics.snapshot()
    with config.session_overlay(overlay):
        rows = s.query(sql).rows
    assert rows
    moved = _moved(before, metrics.snapshot(), "tidb_tpu_cop_ranges_total")
    # labels render sorted: served, then why
    return {tuple(re.findall(r'"([^"]*)"', k)): v for k, v in moved.items()}


def test_a_streamed_range_says_why_it_streamed(ranges_session):
    s = ranges_session
    sql = "SELECT a, b FROM t WHERE b < 3"
    cold = _ranges(s, sql, tidb_tpu_copr_stream=1)
    [(key, n)] = cold.items()
    assert key == ("streamed", "cold") and n >= 4
    # the cold stream filled the cache: a frame cap over the entry serves
    # every range as one resident frame ...
    assert _ranges(s, sql, tidb_tpu_copr_stream=1) == {("resident", "hit"): n}
    # ... and one under it streams them again, over the frame
    assert _ranges(s, sql, tidb_tpu_copr_stream=1,
                   tidb_tpu_copr_stream_frame_bytes=256) == \
        {("streamed", "over_frame"): n}
    assert _ranges(s, sql, tidb_tpu_copr_stream=1,
                   tidb_tpu_chunk_cache=0) == {("streamed", "uncacheable"): n}


def test_the_materialized_handler_counts_its_ranges(ranges_session):
    s = ranges_session
    # pruned to column c: a cache entry of its own
    sql = "SELECT c FROM t WHERE c = 2"
    got = _ranges(s, sql, tidb_tpu_copr_stream=0)
    [(key, n)] = got.items()
    assert key == ("scanned", "cold") and n >= 4
    assert _ranges(s, sql, tidb_tpu_copr_stream=0) == {("resident", "hit"): n}
    assert _ranges(s, sql, tidb_tpu_copr_stream=0,
                   tidb_tpu_chunk_cache=0) == {("scanned", "uncacheable"): n}
