"""One device plane: the accounting and observability seams fire
IDENTICALLY at mesh size 1 (the copTask path) and mesh size 8 (the
NamedSharding plane). The tentpole contract is that a statement's
externally visible machinery — memtrack ledgers, trace-span
vocabulary, meter attribution, scheduler slot grants, failpoint
recovery — must not depend on how many chips executed it; only the
numbers (per-chip spread, wall time) may differ.

Each check runs under both plane sizes via the parametrized `plane`
fixture; cross-size equality (span sets, query results) is asserted
once both sizes have recorded their observation.
"""

import pytest

import tpch
from tidb_tpu import config, devplane, memtrack, meter, metrics, sched, trace
from tidb_tpu.session import Session
from tidb_tpu.store.storage import new_mock_storage
from tidb_tpu.util import failpoint

pytestmark = pytest.mark.usefixtures("ledger_hygiene")

# every statement that reached the device must retain these spans,
# whatever the plane size (the trace-names lint vocabulary)
DEVICE_SPANS = {"sched.slot", "dispatch", "finalize"}

# storage-transport envelope spans: which ONE fires depends on the read
# path (framed streaming vs cached whole-region tasks), a per-scan
# choice that is orthogonal to the plane size contract below
TRANSPORT_SPANS = {"copr.task", "copr.stream"}

# the scan's three steps (PR 24): the pushed subplan runs over every
# scanned chunk at every plane size, cached or cold; the KV scan and the
# decode fire only on a COLD scan — cache temperature, like the
# transport, is a per-scan matter orthogonal to the plane size
SCAN_EXEC_SPANS = {"copr.exec"}
COLD_SCAN_SPANS = {"copr.kv_scan", "copr.decode"}

# the root executors' own spans (PR 33): exec.agg and exec.topn run at
# every size (FinalAggExec / HashAggExec on one chip, MeshAggExec /
# MeshLookupAggExec above it). exec.join names an OPERATOR the plane does
# not run: on one chip Q3's customer-orders join is a HashJoinExec under
# the fused aggregate, above one chip route_mesh folds every join into
# MeshLookupAggExec's lookup chain, whose host work is that operator's
# exec.agg
ONE_CHIP_OPERATOR_SPANS = {"exec.join"}

SIZES = (1, 8)


@pytest.fixture(scope="module")
def sess():
    s = Session(new_mock_storage())
    s.execute("CREATE DATABASE tpch")
    s.execute("USE tpch")
    # seed=7: Q1/Q3 both return non-empty results (tests/tpch.py)
    tpch.load(s, tpch.TpchData(seed=7))
    yield s
    s.close()


@pytest.fixture(params=SIZES, ids=["plane1", "plane8"])
def plane(request):
    n = request.param
    if n > 1:
        devplane.enable_mesh(n)
    sched.reset_for_tests()
    trace.reset_for_tests()
    old = config.get_var("tidb_tpu_trace_sample")
    config.set_var("tidb_tpu_trace_sample", 1)   # retain every trace
    yield n
    config.set_var("tidb_tpu_trace_sample", old)
    failpoint.disable_all()
    sched.device_health().note_ok()      # leave no quarantine behind
    if n > 1:
        devplane.disable_mesh()


def _span_names(rec) -> set:
    out = set()

    def walk(s):
        out.add(s.name)
        for c in s.children:
            walk(c)

    walk(rec["root"])
    return out


def _fallbacks(reason: str) -> int:
    snap = metrics.snapshot()
    return int(sum(v for k, v in snap.items()
                   if k.startswith(metrics.DEVICE_FALLBACKS)
                   and f'reason="{reason}"' in k))


def _assert_same_across_sizes(store: dict, size: int, value):
    """Record `value` under `size`; once every plane size has reported,
    the observations must be equal — the one-plane contract."""
    store[size] = value
    if all(s in store for s in SIZES):
        first = store[SIZES[0]]
        for s in SIZES[1:]:
            assert store[s] == first, (
                f"plane-size-dependent behavior: {SIZES[0]} chip(s) -> "
                f"{first!r}, {s} chip(s) -> {store[s]!r}")


class TestTraceSpans:
    _spans: dict = {}
    _rows: dict = {}

    def test_span_vocabulary_identical(self, sess, plane):
        r1 = sess.query(tpch.Q1).rows
        r3 = sess.query(tpch.Q3).rows
        assert r1 and r3
        names = set()
        for rec in trace.ring_records():
            names |= _span_names(rec)
        assert DEVICE_SPANS | SCAN_EXEC_SPANS <= names, (
            f"plane size {plane}: missing device/scan spans "
            f"{(DEVICE_SPANS | SCAN_EXEC_SPANS) - names}")
        assert names <= set(trace.SPAN_NAMES)
        # one plane: no fallback class of the mesh's own at any size
        assert _fallbacks("mesh") == 0
        _assert_same_across_sizes(
            self._spans, plane,
            tuple(sorted(names - TRANSPORT_SPANS - COLD_SCAN_SPANS
                         - ONE_CHIP_OPERATOR_SPANS)))
        _assert_same_across_sizes(self._rows, plane,
                                  (sorted(map(tuple, r1)),
                                   sorted(map(tuple, r3))))


class TestSchedulerSlots:
    def test_grants_drain_and_spread(self, sess, plane):
        sess.query(tpch.Q1)
        sess.query(tpch.Q3)
        snap = sched.device_scheduler().snapshot()
        assert snap["grants"] >= 2
        assert snap["inflight"] == 0                 # every slot released
        chips = snap["chips"]
        assert set(chips) == set(range(plane))       # one stream per chip
        assert sum(v["grants"] for v in chips.values()) == snap["grants"]
        used = [c for c, v in chips.items() if v["grants"]]
        assert all(0 <= c < plane for c in used)
        if plane == 1:
            assert used == [0]
        else:
            # least-loaded placement rotates sequential statements off
            # the chip whose busy-time the previous grant accrued
            assert len(used) >= 2
        for c in used:
            assert chips[c]["busy_seconds"] > 0


class TestMemtrackLedgers:
    def test_device_ledger_drains(self, sess, plane):
        sess.query(tpch.Q1)
        sess.query(tpch.Q3)
        # dispatch-scoped device charges (padded uploads, scratch) are
        # all credited back at finalize on EVERY plane size; the ONLY
        # device bytes allowed to remain are the long-lived HBM
        # region-block cache's resident blocks (server-scope residency,
        # reclaimed by its LRU / the shed chain, not by statements)
        from tidb_tpu.store import device_cache
        assert memtrack.SERVER.device == device_cache.tracker().device


class TestMeterAttribution:
    def test_device_time_attributed(self, sess, plane):
        d0 = meter.SERVER.totals()["device_ns"]
        a0 = meter.attributed_device_ns()
        sess.query(tpch.Q1)
        assert meter.SERVER.totals()["device_ns"] > d0
        # the session meter (not just the server roll-up) carries it:
        # per-tenant attribution works on every plane size
        assert meter.attributed_device_ns() > a0


class TestFailpointRecovery:
    def test_dispatch_fault_recovers(self, sess, plane):
        want = sorted(map(tuple, sess.query(tpch.Q1).rows))
        fb = _fallbacks("fault")
        failpoint.enable("device/dispatch", "raise(DeviceFaultError)")
        try:
            got = sorted(map(tuple, sess.query(tpch.Q1).rows))
        finally:
            failpoint.disable("device/dispatch")
        sched.device_health().note_ok()
        assert got == want              # correct answer via host path
        assert _fallbacks("fault") > fb  # and the fault was counted
        snap = sched.device_scheduler().snapshot()
        assert snap["inflight"] == 0     # fault path released its slots
