"""Fleet process-level behavior (tidb_tpu/fleet.py): one store-plane
subprocess + N SQL-server subprocesses — the real multi-process
topology, not in-process lookalikes. Pins cross-process schema
coordination (DDL on A visible on B within the schema lease; a write
from B under the old schema version rejected, not silently applied;
DDL availability restored within a lease interval after a member
dies) and the chaos contract: SIGKILL one member mid-statement under
seeded faults and only retryable errors reach that member's clients
while survivors keep serving with drained gauges."""

import json
import threading
import time

import pytest

from tidb_tpu import errcode
from tidb_tpu.fleet import Fleet
from tidb_tpu.util import statusclient

from tests.mysql_client import MiniClient, MySQLError

pytestmark = pytest.mark.usefixtures("ledger_hygiene")

LEASE_MS = 2000          # Domain.SCHEMA_LEASE_MS default in the servers
CONVERGE_S = 30.0        # lease + worker tick + slow-CI slack


@pytest.fixture(scope="module")
def fleet():
    with Fleet(n_sql=2) as f:
        f.wait_healthy(timeout=120)
        yield f


def _client(fleet, index, db=""):
    c = MiniClient(fleet.host, fleet.members[index].port, db=db)
    c.sock.settimeout(120)
    return c


def _query_until(fleet, index, sql, db="", timeout=CONVERGE_S):
    """Poll one member until the statement succeeds (schema-lease
    convergence, owner failover); returns (rows, elapsed_seconds)."""
    t0 = time.monotonic()
    last = None
    while time.monotonic() - t0 < timeout:
        try:
            c = _client(fleet, index, db=db)
            try:
                res = c.query(sql)
                # SELECTs return (cols, rows); DML/DDL an OK rowcount
                rows = res[1] if isinstance(res, tuple) else res
                return rows, time.monotonic() - t0
            finally:
                c.close()
        except (MySQLError, OSError) as e:
            last = e
            time.sleep(0.25)
    raise AssertionError(
        f"member {index} never served {sql!r} within {timeout}s "
        f"(last: {last})")


def _arm_failpoint(fleet, index, name, spec):
    m = fleet.members[index]
    doc = statusclient.post_json(fleet.host, m.status_port,
                                 "/failpoint",
                                 {"name": name, "spec": spec},
                                 timeout=10)
    assert doc.get("ok"), doc


class TestCrossProcessSchema:
    def test_ddl_on_a_visible_on_b_within_lease(self, fleet):
        a = _client(fleet, 0)
        a.query("CREATE DATABASE fd")
        a.query("CREATE TABLE fd.t (id BIGINT PRIMARY KEY, v BIGINT)")
        a.query("INSERT INTO fd.t VALUES (1, 5)")
        a.close()
        rows, elapsed = _query_until(fleet, 1, "SELECT v FROM fd.t",
                                     db="fd")
        assert rows == [("5",)]
        assert elapsed < CONVERGE_S

    def test_write_under_old_schema_rejected_not_applied(self, fleet):
        """B opens a txn touching a column A then drops: commit-time
        schema validation must reject the write (replay cannot apply),
        never silently commit it under the old layout."""
        a = _client(fleet, 0)
        a.query("CREATE DATABASE sv")
        a.query("CREATE TABLE sv.t (id BIGINT PRIMARY KEY, v BIGINT, "
                "w BIGINT)")
        a.query("INSERT INTO sv.t VALUES (1, 1, 1)")
        a.close()
        _query_until(fleet, 1, "SELECT v FROM sv.t", db="sv")
        b = _client(fleet, 1, db="sv")
        b.query("BEGIN")
        b.query("UPDATE t SET w = 99 WHERE id = 1")
        a = _client(fleet, 0)
        a.query("ALTER TABLE sv.t DROP COLUMN w")
        with pytest.raises((MySQLError, OSError)):
            b.query("COMMIT")
        b.close()
        # the stale write is gone WITH the column; v untouched
        assert a.query("SELECT v FROM sv.t")[1] == [("1",)]
        with pytest.raises(MySQLError):
            a.query("SELECT w FROM sv.t")
        a.close()

    def test_ddl_available_within_lease_after_member_dies(self, fleet):
        """Owner failover: SIGKILL one member (it may hold the DDL
        owner lease); the survivor must run DDL as soon as the lease
        expires — bounded by the lease interval plus worker cadence,
        not a hang."""
        fleet.kill(0)
        try:
            rows, elapsed = _query_until(fleet, 1,
                                         "CREATE DATABASE failover_db")
            assert elapsed < CONVERGE_S
            names, _ = _query_until(fleet, 1, "SHOW DATABASES")
            assert ("failover_db",) in names
        finally:
            fleet.restart(0)
            fleet.wait_healthy(timeout=120)


class TestClusterObservability:
    def test_cluster_members_lists_every_process(self, fleet):
        """The membership registry seen from ANY member: both SQL
        servers and the store plane itself, each with its status port
        and lease."""
        rows, _ = _query_until(
            fleet, 1, "SELECT member_id, role, status_port FROM "
                      "information_schema.cluster_members")
        roles = [r[1] for r in rows]
        assert roles.count("sql") >= 2, rows
        assert "store" in roles, rows
        ports = {int(r[2]) for r in rows}
        assert {m.status_port for m in fleet.members} <= ports
        assert fleet.store_status_port in ports

    def test_cluster_resource_usage_attributes_every_member(self, fleet):
        """Per-member utilization through the cluster fan-out: every
        live member has a server-scope row, and the member that served
        statements shows them attributed."""
        _query_until(fleet, 0, "SELECT 1")
        mrows, _ = _query_until(
            fleet, 1, "SELECT member_id FROM "
                      "information_schema.cluster_members")
        deadline = time.monotonic() + CONVERGE_S
        while True:
            urows, _ = _query_until(
                fleet, 1,
                "SELECT member, statements FROM "
                "information_schema.cluster_resource_usage "
                "WHERE scope = 'server'")
            util = {r[0]: int(r[1]) for r in urows}
            if set(util) >= {r[0] for r in mrows} and \
                    any(n > 0 for n in util.values()):
                break
            if time.monotonic() > deadline:
                raise AssertionError(
                    f"live={sorted(r[0] for r in mrows)} "
                    f"attributed={util}")
            time.sleep(0.25)

    def test_cross_member_trace_correlation(self, fleet):
        """The ISSUE 17 acceptance bar: a statement TRACEd on member 0
        mints a fleet-unique trace id; one SELECT over
        cluster_statement_traces on a DIFFERENT member locates the
        store-plane-retained record whose origin_trace_id equals it
        (the origin stamp shipped inside the traced store RPCs)."""
        a = _client(fleet, 0)
        try:
            a.query("CREATE DATABASE obs_corr")
            a.query("CREATE TABLE obs_corr.t (id BIGINT PRIMARY KEY, "
                    "v BIGINT)")
            a.query("INSERT INTO obs_corr.t VALUES (1, 7)")
            res = a.query("TRACE FORMAT='json' SELECT v FROM "
                          "obs_corr.t WHERE id = 1")
            tid = json.loads(res[1][0][0])["trace_id"]
        finally:
            a.close()
        assert tid > 0xFFFFFF   # fleet-unique: member nonce folded in
        mrows, _ = _query_until(
            fleet, 1, "SELECT member_id, role FROM "
                      "information_schema.cluster_members")
        store_ids = {r[0] for r in mrows if r[1] == "store"}
        assert store_ids, mrows
        deadline = time.monotonic() + 20
        while True:
            srows, _ = _query_until(
                fleet, 1,
                "SELECT member, origin_member, origin_trace_id FROM "
                "information_schema.cluster_statement_traces "
                f"WHERE origin_trace_id = {tid}")
            hit = [r for r in srows if r[0] in store_ids]
            if hit:
                break
            if time.monotonic() > deadline:
                raise AssertionError(
                    f"no store-plane record for trace {tid}: {srows}")
            time.sleep(0.25)
        # the store-plane record names the ISSUING member (member 0),
        # not the store member that served the RPC
        issuer = f"{fleet.host}:{fleet.members[0].status_port}:"
        assert hit[0][1].startswith(issuer), hit


class TestFleetChaos:
    def test_sigkill_mid_statement_retryable_only(self, fleet):
        """The ISSUE 16 chaos leg: seeded faults armed on the victim,
        SIGKILL mid-statement. The victim's clients may see socket
        drops (reconnect-retryable by definition) or RETRYABLE SQL
        codes — never a non-retryable error, never a wrong row.
        Survivors keep serving and their level gauges drain."""
        setup = _client(fleet, 1)
        setup.query("CREATE DATABASE chaos")
        setup.query("CREATE TABLE chaos.t (id BIGINT PRIMARY KEY, "
                    "v BIGINT)")
        setup.query("INSERT INTO chaos.t VALUES " +
                    ", ".join(f"({i}, {i})" for i in range(32)))
        setup.close()
        _query_until(fleet, 0, "SELECT v FROM chaos.t WHERE id = 3",
                     db="chaos")
        # the seeded fault schedule on the victim: retryable-classed
        # device and RPC faults with small budgets (tests/chaos_harness.py
        # vocabulary), so statements are mid-flight through fault
        # handling when the SIGKILL lands
        _arm_failpoint(fleet, 0, "device/dispatch",
                       "3*raise(DeviceFaultError)")
        _arm_failpoint(fleet, 0, "rpc/request",
                       "3*raise(ServerBusyError)")

        bad: list = []
        wrong: list = []
        stop = threading.Event()

        def victim_client() -> None:
            while not stop.is_set():
                try:
                    c = MiniClient(fleet.host, fleet.members[0].port,
                                   db="chaos")
                    c.sock.settimeout(60)
                    while not stop.is_set():
                        _cols, rows = c.query(
                            "SELECT v FROM chaos.t WHERE id = 3")
                        if rows != [("3",)]:
                            wrong.append(rows)
                except MySQLError as e:
                    if e.code not in errcode.RETRYABLE:
                        bad.append(f"({e.code}) {e}")
                    time.sleep(0.05)
                except OSError:
                    time.sleep(0.05)   # connection drop: reconnect

        threads = [threading.Thread(target=victim_client)
                   for _ in range(2)]
        for t in threads:
            t.start()
        time.sleep(1.0)                # statements in flight
        try:
            fleet.kill(0)              # SIGKILL, mid-statement
            time.sleep(1.0)            # clients churn on the dead port
        finally:
            stop.set()
            for t in threads:
                t.join(timeout=30)
        assert not bad, f"non-retryable errors surfaced: {bad[:3]}"
        assert not wrong, f"wrong results under chaos: {wrong[:3]}"

        # survivors keep serving the same data
        rows, _ = _query_until(fleet, 1,
                               "SELECT v FROM chaos.t WHERE id = 3",
                               db="chaos")
        assert rows == [("3",)]
        assert fleet.health(1)["version"]

        # membership churn: while the dead member's lease is still
        # live, a cluster fan-out from the survivor returns partial
        # rows within the bounded timeout plus a warning — never a
        # stall, never a statement error; then the member ages out of
        # cluster_members within one TTL (it stopped heartbeating; no
        # deregistration path exists to miss)
        dead_pfx = f"{fleet.host}:{fleet.members[0].status_port}:"
        c = _client(fleet, 1)
        try:
            _cols, mrows = c.query(
                "SELECT member_id FROM "
                "information_schema.cluster_members")
            dead_listed = any(r[0].startswith(dead_pfx) for r in mrows)
            t0 = time.monotonic()
            _cols, prows = c.query(
                "SELECT member, id FROM "
                "information_schema.cluster_processlist")
            assert time.monotonic() - t0 < 10   # bounded degradation
            # the survivor itself answered (partial rows, not empty)
            assert any(not r[0].startswith(dead_pfx) for r in prows), \
                prows
            if dead_listed:
                _cols, wrows = c.query("SHOW WARNINGS")
                assert any("unreachable" in r[2] for r in wrows), wrows
        finally:
            c.close()
        deadline = time.monotonic() + 20        # TTL (3s) + CI slack
        while True:
            mrows, _ = _query_until(
                fleet, 1, "SELECT member_id FROM "
                          "information_schema.cluster_members")
            if not any(r[0].startswith(dead_pfx) for r in mrows):
                break
            if time.monotonic() > deadline:
                raise AssertionError(
                    f"dead member never aged out: {mrows}")
            time.sleep(0.25)

        # survivor gauge hygiene: every *_current/_depth level family
        # returns to zero once its clients are gone (no ledger leaks
        # from the dead peer or the chaos churn)
        deadline = time.monotonic() + 20
        while True:
            snap = fleet.health(1)["metrics"]
            leaked = {k: v for k, v in snap.items()
                      if (k.split("{")[0].endswith("_current") or
                          k.split("{")[0].endswith("_depth")) and v}
            if not leaked:
                break
            if time.monotonic() > deadline:
                raise AssertionError(f"survivor gauges leaked: {leaked}")
            time.sleep(0.25)
        fleet.restart(0)
        fleet.wait_healthy(timeout=120)
