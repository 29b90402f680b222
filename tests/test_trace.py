"""End-to-end statement tracing (tidb_tpu/trace.py): lifecycle span
trees, cross-thread propagation into the coprocessor fan-out,
deterministic sampling + slow-trace capture into the bounded
memtrack-billed ring, the TRACE statement (row and json forms), the
statement_traces memtable / digest / slow-log linkage, the /trace
status endpoints, the Chrome trace-event export, and the disarmed
overhead pin."""

import json
import logging
import sys
import time
import urllib.error

from tidb_tpu.util import statusclient

import pytest

from tidb_tpu import config, memtrack, perfschema, sched, trace
from tidb_tpu.session import Session
from tidb_tpu.store.storage import new_mock_storage


@pytest.fixture(autouse=True)
def _trace_isolation():
    """Fresh sampling counters + empty ring per test; sampling and
    slow-capture OFF unless the test arms them (retention is what's
    under test, not an accident of counter position)."""
    saved = {k: config.get_var(k) for k in
             ("tidb_tpu_trace_sample", "tidb_tpu_slow_trace_ms")}
    config.set_var("tidb_tpu_trace_sample", 0)
    config.set_var("tidb_tpu_slow_trace_ms", 0)
    trace.reset_for_tests()
    yield
    for k, v in saved.items():
        config.set_var(k, v)
    trace.reset_for_tests()


@pytest.fixture
def sess():
    s = Session(new_mock_storage())
    s.execute("CREATE DATABASE td")
    s.execute("USE td")
    s.execute("CREATE TABLE t (id BIGINT PRIMARY KEY, v BIGINT)")
    s.execute("INSERT INTO t VALUES " +
              ",".join(f"({i},{i % 7})" for i in range(4000)))
    yield s
    s.close()


def _names(d: dict, acc: set) -> set:
    acc.add(d["name"])
    for c in d.get("children", ()):
        _names(c, acc)
    return acc


def _span_tids(root, acc):
    acc.append(root.tid)
    for c in root.children:
        _span_tids(c, acc)
    return acc


# -- sampling / retention ----------------------------------------------------


class TestSampling:
    def test_deterministic_one_in_n(self, sess):
        config.set_var("tidb_tpu_trace_sample", 3)
        for _ in range(7):
            sess.query("SELECT COUNT(*) FROM t")
        recs = trace.ring_snapshot()
        # statements 3 and 6 of the window retain, deterministically
        assert len(recs) == 2, recs
        assert all(r["reason"] == "sampled" for r in recs)

    def test_every_retained_tree_of_a_mix_is_balanced(self, sess):
        """Aggregates and point reads, every statement retained: no
        tree in the ring holds a begin without an end."""
        config.set_var("tidb_tpu_trace_sample", 1)
        for i in range(3):
            sess.query("SELECT v, COUNT(*) FROM t GROUP BY v")
            for j in range(4):
                sess.query(f"SELECT v FROM t WHERE id = {i * 7 + j}")
        records = trace.ring_records()
        assert len(records) == 15
        assert [(r["trace_id"], p) for r in records
                for p in trace.validate(r["root"])] == []

    def test_sampling_off_retains_nothing(self, sess):
        for _ in range(5):
            sess.query("SELECT COUNT(*) FROM t")
        assert trace.ring_snapshot() == []

    def test_slow_trace_capture_links_digest_and_slow_log(
            self, sess, caplog):
        config.set_var("tidb_tpu_slow_trace_ms", 1)   # everything slow
        slow_prev = config.get_var("tidb_tpu_slow_query_ms")
        config.set_var("tidb_tpu_slow_query_ms", 0)
        try:
            with caplog.at_level(logging.WARNING,
                                 logger="tidb_tpu.slow_query"):
                sess.query("SELECT v, COUNT(*) FROM t GROUP BY v")
        finally:
            config.set_var("tidb_tpu_slow_query_ms", slow_prev)
        recs = trace.ring_snapshot()
        assert recs and recs[-1]["reason"] == "slow"
        tid = recs[-1]["trace_id"]
        # slow log carries the captured trace id
        msgs = [r.getMessage() for r in caplog.records
                if "slow query" in r.getMessage()]
        assert any(f"# Trace_id: {tid}" in m for m in msgs), msgs
        # ... and the digest summary row points at the same trace (the
        # summary is process-global, so match by the EXACT digest — a
        # prior test's GROUP BY statement may rank higher)
        dg, _ = perfschema.sql_digest(
            "SELECT v, COUNT(*) FROM t GROUP BY v")
        row = next(
            (r for r in sess.query(
                "SELECT digest, last_trace_id FROM "
                "performance_schema.events_statements_summary_by_digest"
            ).rows if r[0] == dg), None)
        assert row is not None and row[1] == tid

    def test_session_scope_set_is_honored(self, sess):
        """SET (session scope) of the trace knobs must shadow the
        globals like every other sysvar: sampling is decided under the
        overlay at begin, and the slow threshold is captured while the
        overlay is still installed (regression: both used to read only
        the global registry)."""
        sess.execute("SET tidb_tpu_trace_sample = 1")
        sess.query("SELECT COUNT(*) FROM t")
        recs = trace.ring_snapshot()
        assert recs and recs[-1]["reason"] == "sampled"
        sess.execute("SET tidb_tpu_trace_sample = 0")
        sess.execute("SET tidb_tpu_slow_trace_ms = 1")
        sess.query("SELECT v, COUNT(*) FROM t GROUP BY v")
        recs = trace.ring_snapshot()
        assert recs and recs[-1]["reason"] == "slow"
        # another session (global values: both off) retains nothing
        other = Session(sess.storage, db="td")
        try:
            n0 = len(trace.ring_snapshot())
            other.query("SELECT COUNT(*) FROM t")
            assert len(trace.ring_snapshot()) == n0
        finally:
            other.close()

    def test_internal_sessions_never_retained(self, sess):
        config.set_var("tidb_tpu_trace_sample", 1)
        internal = Session(sess.storage, db="td", internal=True)
        try:
            internal.query("SELECT COUNT(*) FROM t")
        finally:
            internal.close()
        assert trace.ring_snapshot() == []


class TestRing:
    def _retain(self, n: int) -> None:
        for _ in range(n):
            root = trace.begin("statement")
            root.forced = True
            trace.end(root)
            trace.finish_statement(root, "SELECT 1")

    def test_record_cap_bounds_the_ring(self):
        self._retain(trace._RING_CAP + 50)
        snap = trace.ring_stats()
        assert snap["records"] == trace._RING_CAP
        # ids keep counting; the ring keeps the NEWEST records
        recs = trace.ring_snapshot()
        assert recs[-1]["trace_id"] > trace._RING_CAP

    def test_ring_bytes_billed_to_server_node_and_shed_action(self):
        self._retain(10)
        snap = trace.ring_stats()
        assert snap["records"] == 10 and snap["bytes"] > 0
        node = trace._RING._node
        assert node is not None and node.host == snap["bytes"]
        # the registered shed action (driven via the SERVER chain, the
        # same door admission shedding and GET /shed use) clears it
        freed = sched.shed_server(0)
        assert freed >= snap["bytes"]
        assert trace.ring_snapshot() == []
        assert trace.ring_stats()["bytes"] == 0
        assert node.host == 0

    def test_eviction_releases_ledger_bytes(self):
        self._retain(trace._RING_CAP + 20)
        node = trace._RING._node
        assert node.host == trace.ring_stats()["bytes"]


# -- span coverage / cross-thread propagation --------------------------------


class TestSpanCoverage:
    def test_copr_fanout_spans_attach_cross_thread(self, sess):
        # multiple regions force the pool fan-out; the workers re-install
        # the dispatching span like the stats collector / memtracker
        sess.execute("SPLIT TABLE t REGIONS 4")
        captured = []
        orig_end = trace.end

        def capture(root):
            captured.append(root)
            return orig_end(root)

        trace.end = capture
        try:
            sess.query("SELECT v, COUNT(*) FROM t GROUP BY v")
        finally:
            trace.end = orig_end
        root = captured[-1]
        names = set()

        def walk(s):
            names.add(s.name)
            for c in s.children:
                walk(c)

        walk(root)
        assert {"copr.task", "copr.stream"} & names, names
        # worker spans carry worker-thread ids: the tree spans threads
        tids = set(_span_tids(root, []))
        assert len(tids) > 1, "no cross-thread spans attached"

    def test_device_spans_present_for_agg(self, sess):
        min_prev = config.get_var("tidb_tpu_device_min_rows")
        config.set_var("tidb_tpu_device_min_rows", 1)
        try:
            doc = json.loads(sess.query(
                "TRACE FORMAT='json' SELECT v, COUNT(*) FROM t "
                "GROUP BY v").rows[0][0])
        finally:
            config.set_var("tidb_tpu_device_min_rows", min_prev)
        names = _names(doc["spans"], set())
        assert {"dispatch", "finalize", "sched.slot"} <= names, names

    def test_fault_events_land_on_spans(self):
        root = trace.begin("statement")
        try:
            with trace.span("dispatch") as s:
                trace.event("device.fault", attempt=1)
        finally:
            trace.end(root)
        assert s.events and s.events[0][0] == "device.fault"
        d = trace.tree(root)
        ev = d["children"][0]["events"][0]
        assert ev["name"] == "device.fault"
        assert ev["tags"] == {"attempt": 1}


# -- TRACE statement ---------------------------------------------------------


class TestTraceStatement:
    def test_row_form(self, sess):
        rs = sess.query("TRACE SELECT COUNT(*) FROM t")
        assert rs.columns == ["operation", "start", "duration"]
        ops = [r[0] for r in rs.rows]
        assert ops[0].startswith("statement")
        assert any(o.strip().startswith("plan") for o in ops)
        assert any(o.strip().startswith("execute") for o in ops)
        # depth-indented, start/duration rendered in ms
        assert all(r[1].endswith("ms") and (r[2].endswith("ms") or
                                            r[2] == "-")
                   for r in rs.rows)

    def test_json_form_balanced_and_retained(self, sess):
        doc = json.loads(sess.query(
            "TRACE FORMAT='json' SELECT COUNT(*) FROM t").rows[0][0])
        assert doc["trace_id"] > 0

        def check(d):
            assert d["duration_us"] >= 0
            assert d["start_us"] >= 0 or d["name"] == "statement"
            for c in d.get("children", ()):
                check(c)

        check(doc["spans"])
        names = _names(doc["spans"], set())
        assert {"statement", "parse", "plan", "execute"} <= names
        # forced retention: the ring serves the same tree by id
        rec = trace.ring_get(doc["trace_id"])
        assert rec is not None and rec["reason"] == "forced"
        assert trace.validate(rec["root"]) == []

    def test_admission_span_when_admission_armed(self, sess):
        prev = config.get_var("tidb_tpu_server_mem_quota")
        config.set_var("tidb_tpu_server_mem_quota", 8 << 30)
        try:
            doc = json.loads(sess.query(
                "TRACE FORMAT='json' SELECT COUNT(*) FROM t"
            ).rows[0][0])
        finally:
            config.set_var("tidb_tpu_server_mem_quota", prev)
        assert "admission" in _names(doc["spans"], set())

    def test_trace_of_dml_executes_it(self, sess):
        sess.query("TRACE INSERT INTO t VALUES (99999, 1)")
        assert sess.query("SELECT COUNT(*) FROM t WHERE id = 99999"
                          ).rows == [(1,)]
        rec = trace.ring_snapshot()[0]
        assert rec["reason"] == "forced"

    def test_nested_trace_rejected(self, sess):
        from tidb_tpu.session import SQLError
        with pytest.raises(SQLError, match="nest"):
            sess.query("TRACE TRACE SELECT 1")

    def test_bad_format_rejected(self, sess):
        from tidb_tpu.parser import ParseError
        with pytest.raises(ParseError, match="FORMAT"):
            sess.query("TRACE FORMAT='xml' SELECT 1")

    def test_memtable_row_joinable_to_digest(self, sess):
        doc = json.loads(sess.query(
            "TRACE FORMAT='json' SELECT COUNT(*) FROM t").rows[0][0])
        rows = sess.query(
            "SELECT trace_id, digest, reason, span_count FROM "
            "information_schema.statement_traces").rows
        mine = [r for r in rows if r[0] == doc["trace_id"]]
        assert mine and mine[0][2] == "forced" and mine[0][3] >= 4
        # the digest column matches the perfschema digest of the SQL
        dg, _ = perfschema.sql_digest(
            "TRACE FORMAT='json' SELECT COUNT(*) FROM t")
        assert mine[0][1] == dg


# -- status endpoints / Chrome export ----------------------------------------


def _get_json(port: int, path: str):
    return statusclient.get_json("127.0.0.1", port, path, timeout=10)


class TestTraceEndpoints:
    def test_list_fetch_and_chrome(self, sess):
        from tidb_tpu.server.status import StatusServer
        doc = json.loads(sess.query(
            "TRACE FORMAT='json' SELECT COUNT(*) FROM t").rows[0][0])
        status = StatusServer(sess.storage, None)
        status.start()
        try:
            listing = _get_json(status.port, "/trace")
            ids = [r["trace_id"] for r in listing["traces"]]
            assert doc["trace_id"] in ids
            assert listing["ring"]["records"] == len(ids)
            one = _get_json(status.port, f"/trace/{doc['trace_id']}")
            assert one["spans"]["name"] == "statement"
            chrome = _get_json(status.port,
                               f"/trace/{doc['trace_id']}/chrome")
            _validate_chrome_doc(chrome)
            with pytest.raises(urllib.error.HTTPError) as ei:
                _get_json(status.port, "/trace/999999")
            assert ei.value.code == 404
        finally:
            status.close()


def _validate_chrome_doc(doc: dict) -> None:
    evs = doc["traceEvents"]
    assert isinstance(evs, list) and evs
    assert any(e["ph"] == "X" for e in evs)
    for e in evs:
        assert e["ph"] in ("X", "i", "M"), e
        assert isinstance(e["name"], str)
        assert isinstance(e["pid"], int) and isinstance(e["tid"], int)
        if e["ph"] in ("X", "i"):
            assert isinstance(e["ts"], (int, float)) and e["ts"] >= 0
        if e["ph"] == "X":
            assert isinstance(e["dur"], (int, float)) and e["dur"] >= 0


class TestChromeExport:
    def test_schema_and_event_instants(self):
        root = trace.begin("statement")
        root.forced = True
        try:
            with trace.span("dispatch", superchunk=0):
                trace.event("device.fault")
            with trace.span("finalize"):
                pass
        finally:
            trace.end(root)
        tid = trace.finish_statement(root, "SELECT 1")
        doc = trace.to_chrome(trace.ring_get(tid))
        _validate_chrome_doc(doc)
        xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
        assert {e["name"] for e in xs} == {"statement", "dispatch",
                                           "finalize"}
        instants = [e for e in doc["traceEvents"] if e["ph"] == "i"]
        assert instants and instants[0]["name"] == "device.fault"

    def test_phases_of_sums_to_total(self):
        # ported from trace.phases_of (inclusive sums by name) to the
        # self-time walk: on one thread the self times PARTITION the
        # statement, so they sum to its total exactly
        root = trace.begin("statement")
        with trace.span("plan"):
            time.sleep(0.002)
        with trace.span("execute"):
            with trace.span("dispatch"):
                time.sleep(0.002)
        trace.end(root)
        st = trace.self_times(root)
        assert st["plan"][0] > 0 and st["dispatch"][0] > 0
        assert set(st) == {"statement", "plan", "execute", "dispatch"}
        assert all(n == 1 for _ns, n in st.values())
        assert sum(ns for ns, _n in st.values()) == root.duration_ns
        # execute's self time excludes the dispatch under it
        assert st["execute"][0] < st["dispatch"][0]


# -- overhead ----------------------------------------------------------------


def _calls_per_iteration(body, n=300):
    """Python and C calls `body()` makes per iteration on this thread,
    under `sys.setprofile` (per-thread: other workers and the machine's
    load cannot move the count; `body` itself is not counted)."""
    calls = [0]

    def profiler(_frame, event, _arg):
        if event in ("call", "c_call"):
            calls[0] += 1

    for _ in range(20):             # past first-use imports and caches
        body()
    sys.setprofile(profiler)
    try:
        for _ in range(n):
            body()
    finally:
        sys.setprofile(None)
    # less the profiler's own removal and `body`'s frame each round
    return (calls[0] - 1 - n) / n


class TestOverhead:
    def test_disarmed_per_statement_overhead_is_tiny(self):
        """Sampling disarmed (the N-1 of N statements): what the
        tracing subsystem adds per statement beyond the phase-skeleton
        spans perfschema always needed is the root lifecycle — begin
        (sampling decision) + end + finish_statement (retention
        check). Counted in calls, not seconds: the tree makes 18 per
        untraced statement, so one more retention check or sysvar read
        fails the budget of 19."""
        def statement():
            root = trace.begin("statement")
            trace.end(root)
            trace.finish_statement(root, "SELECT 1")

        per_stmt = _calls_per_iteration(statement)
        assert trace.ring_snapshot() == []     # truly disarmed
        assert per_stmt <= 19, f"{per_stmt} calls per statement"

    def test_span_skeleton_stays_cheap(self):
        """Regression guard on span() itself (it runs per dispatch and
        per phase): the full 2-phase-span statement skeleton makes 44
        calls, 13 a span — the slotted context manager must never
        regress back to a generator-based @contextmanager (six or more
        calls a span on top)."""
        def statement():
            root = trace.begin("statement")
            with trace.span("plan"):
                pass
            with trace.span("execute"):
                pass
            trace.end(root)
            trace.finish_statement(root, "SELECT 1")

        per_stmt = _calls_per_iteration(statement)
        assert per_stmt <= 46, f"{per_stmt} calls per statement"
