"""Chaos soak harness over the wire (docs/ROBUSTNESS.md): the serve mix
(TPC-H analytics + point lookups over N wire clients) runs beside
HTAP-style writes while a SEEDED driver thread arms and disarms
budgeted failpoints across the device plane (dispatch/finalize faults
and delays, HBM fill/patch faults, RPC server-busy bursts, delta-merge
crashes, slot-grant delays). It times nothing; its verdict is the
robustness contract:

  * zero wrong results (analytics match the fault-free reference; the
    written table's write-invariant columns match);
  * zero non-retryable errors surfaced to clients, zero mid-query OOM
    cancels;
  * zero stuck statements (per-op deadline; the dispatch watchdog is
    armed, so nothing can hang past its timeout);
  * scheduler slots and the SERVER memtrack ledgers drain to zero at
    the end.

`run_chaos(secs, clients, sf)` returns the verdict as a dict.
`python -m tests.chaos_harness [secs clients sf]` prints it as JSON and
exits 1 unless `passed`: tests/test_chaos.py::TestChaosBenchLeg runs it
that way, in a process of its own, because the ledgers it reads at the
end are the process's."""

from __future__ import annotations

import gc
import json
import random
import sys
import threading
import time

SEED = 20260804
WRITES_PER_SEC = 25.0
TIMEOUT_MS = 3000       # dispatch watchdog
STUCK_SECS = 90.0       # per-op ceiling


def _parse_cell(x):
    if isinstance(x, (bytes, bytearray)):
        x = x.decode()
    if isinstance(x, str):
        try:
            return int(x)
        except ValueError:
            pass
        try:
            return float(x)
        except ValueError:
            return x
    return x


def _rows_match(got, want, cols=None) -> bool:
    """Approximate row-set equality across the wire (string cells) and
    execution paths (device vs host float-sum ordering): numeric cells
    compare with relative tolerance, everything else exactly. With
    `cols`, only those column indexes are compared (write-invariant
    columns of a mutating table)."""
    if len(got) != len(want):
        return False
    for rg, rw in zip(got, want):
        if len(rg) != len(rw):
            return False
        idxs = range(len(rg)) if cols is None else cols
        for i in idxs:
            x, y = _parse_cell(rg[i]), _parse_cell(rw[i])
            if isinstance(x, float) or isinstance(y, float):
                try:
                    fx, fy = float(x), float(y)
                except (TypeError, ValueError):
                    return False
                if abs(fx - fy) > max(1e-5, abs(fy) * 1e-6):
                    return False
            elif x != y:
                return False
    return True


def run_chaos(secs: float = 15.0, clients: int = 4, sf: float = 0.01,
              progress=lambda msg: None) -> dict:
    import numpy as np

    from tests.mysql_client import MiniClient, MySQLError
    from tidb_tpu import config, errcode, memtrack, metrics, sched
    from tidb_tpu.benchmarks import tpch
    from tidb_tpu.server import Server
    from tidb_tpu.session import Session, SQLError
    from tidb_tpu.store.storage import new_mock_storage
    from tidb_tpu.table import Table, bulkload
    from tidb_tpu.util import failpoint

    rng = random.Random(SEED)
    saved = {k: config.get_var(k) for k in
             ("tidb_tpu_dispatch_timeout_ms", "tidb_tpu_delta_merge_rows",
              "tidb_tpu_failpoints", "tidb_tpu_trace_sample")}
    sched.reset_for_tests()
    storage = new_mock_storage()
    session = Session(storage)
    session.execute("CREATE DATABASE chaos")
    session.execute("USE chaos")
    progress(f"loading tpch sf={sf} + stock (seed {SEED})")
    tpch.load(session, storage, tpch.ScaledTpch(sf=sf),
              regions_per_table=2)
    n_stock = 12000
    session.execute("CREATE TABLE stock (s_id BIGINT PRIMARY KEY, "
                    "s_seg BIGINT, s_qty BIGINT)")
    srng = np.random.default_rng(SEED)
    bulkload.bulk_load(storage, Table(
        session.domain.info_schema().table("chaos", "stock"), storage), {
        "s_id": np.arange(n_stock, dtype=np.int64),
        "s_seg": np.arange(n_stock, dtype=np.int64) % 11,
        "s_qty": srng.integers(10, 100, n_stock)})
    stock_sql = ("SELECT s_seg, COUNT(*), SUM(s_qty) FROM stock "
                 "GROUP BY s_seg ORDER BY s_seg")
    n_orders = tpch.ScaledTpch(sf=sf).counts["orders"]

    analytics = dict(tpch.QUERIES)
    analytics["stock"] = stock_sql
    progress("warmup + fault-free references")
    for sql in analytics.values():
        session.query(sql)

    server = Server(storage)
    server.start()

    def new_client() -> MiniClient:
        c = MiniClient("127.0.0.1", server.port, db="chaos")
        c.sock.settimeout(STUCK_SECS)
        return c

    # references through the SAME surface the clients use (text rows)
    ref_cli = new_client()
    refs = {cls: ref_cli.query(sql)[1] for cls, sql in analytics.items()}
    point_keys = [(ci * 7919 + j * 131) % n_orders
                  for ci in range(clients) for j in range(8)]
    point_sql = ("SELECT o_custkey, o_orderpriority FROM orders "
                 "WHERE o_orderkey = {k}")
    point_refs = {k: ref_cli.query(point_sql.format(k=k))[1]
                  for k in set(point_keys)}
    ref_cli.close()

    # seeded chaos schedule: every spec carries a budget or rides a
    # short arm window, so no fault outlives its slice of the run
    # (point, spec factory, hold): hold=None arms for a short random
    # window; a float holds the arm until the budget fires (or the
    # hold expires) — the watchdog-tripping long delay would otherwise
    # almost never coincide with a device dispatch in a short run
    schedule = [
        ("device/dispatch", lambda: f"{rng.randint(2, 6)}*"
                                    f"raise(DeviceFaultError)", None),
        ("device/finalize", lambda: f"1-in-{rng.randint(3, 6)}:"
                                    f"delay({rng.randint(10, 60)})",
         None),
        ("device/finalize", lambda: f"1*delay({int(TIMEOUT_MS * 1.4)})",
         6.0),
        ("hbm/fill", lambda: f"{rng.randint(1, 4)}*"
                             f"raise(DeviceFaultError)", 2.0),
        ("hbm/patch", lambda: f"{rng.randint(1, 4)}*return(1)", None),
        ("rpc/request", lambda: f"{rng.randint(2, 6)}*"
                                f"raise(ServerBusyError)", None),
        ("delta/merge", lambda: "1*raise(RuntimeError:chaos-merge)",
         4.0),
        ("sched/slot", lambda: f"1-in-{rng.randint(4, 8)}:"
                               f"delay({rng.randint(5, 20)})", None),
    ]
    stop = threading.Event()
    armed_log: list = []

    def chaos_driver() -> None:
        # every epoch arms EVERY schedule entry once, in seeded-shuffled
        # order — pure random picks can starve the rare-but-load-bearing
        # entries (the watchdog-tripping long delay, the merge crash)
        # out of a short window
        while not stop.is_set():
            order = list(range(len(schedule)))
            rng.shuffle(order)
            for i in order:
                if stop.is_set():
                    return
                name, mk, hold = schedule[i]
                spec = mk()
                failpoint.enable(name, spec)
                armed_log.append(f"{name}={spec}")
                if hold is None:
                    stop.wait(rng.uniform(0.1, 0.4))
                else:
                    end = time.monotonic() + hold
                    while time.monotonic() < end and \
                            name in failpoint.armed() and \
                            not stop.is_set():
                        stop.wait(0.1)
                failpoint.disable(name)
                if stop.wait(rng.uniform(0.0, 0.05)):
                    return

    wrong: list = []
    non_retryable: list = []
    stuck: list = []
    ops_done = [0]
    retried = [0]

    def run_op(cli, cls, sql, check) -> None:
        deadline = time.monotonic() + STUCK_SECS
        while True:
            try:
                out = cli.query(sql)
                rows = out[1] if isinstance(out, tuple) else []
                if not check(rows):
                    wrong.append(f"{cls}: {rows[:2]!r}")
                ops_done[0] += 1
                return
            except MySQLError as e:
                if not errcode.is_retryable(e.code):
                    non_retryable.append(f"{cls}: ({e.code}) {e}")
                    return
                retried[0] += 1
                if time.monotonic() >= deadline:
                    stuck.append(f"{cls}: retries past {STUCK_SECS}s")
                    return
                time.sleep(0.03)
            except OSError as e:
                stuck.append(f"{cls}: socket {e}")
                return

    def client_worker(ci: int) -> None:
        cli = new_client()
        classes = list(analytics)
        j = 0
        try:
            while not stop.is_set():
                cls = classes[(ci + j) % len(classes)]
                if cls == "stock":
                    # the written table: only the write-invariant
                    # columns (seg, count) are comparable
                    run_op(cli, cls, analytics[cls],
                           lambda rows: _rows_match(
                               rows, refs["stock"], cols=(0, 1)))
                else:
                    run_op(cli, cls, analytics[cls],
                           lambda rows, c=cls: _rows_match(
                               rows, refs[c]))
                for pk in point_keys[ci * 8:(ci + 1) * 8]:
                    if stop.is_set():
                        break
                    run_op(cli, "point", point_sql.format(k=pk),
                           lambda rows, k=pk: _rows_match(
                               rows, point_refs[k]))
                j += 1
        finally:
            try:
                cli.close()
            except Exception:  # noqa: BLE001 - teardown best effort
                pass

    writes_done = [0]

    def writer() -> None:
        ws = Session(storage, db="chaos")
        period = 1.0 / WRITES_PER_SEC
        seq = 0
        nxt = time.perf_counter()
        while not stop.is_set():
            seq += 1
            k = (seq * 7919) % n_stock
            try:
                ws.execute(f"UPDATE stock SET s_qty = s_qty + 1 "
                           f"WHERE s_id = {k}")
                writes_done[0] += 1
            except SQLError as exc:
                code = errcode.classify(exc)[0]
                if not errcode.is_retryable(code):
                    non_retryable.append(f"write: ({code}) {exc}")
            nxt += period
            d = nxt - time.perf_counter()
            if d > 0:
                time.sleep(min(d, 0.25))
            else:
                nxt = time.perf_counter()
        ws.close()

    snap0 = metrics.snapshot()
    oom_key = 'tidb_tpu_mem_quota_exceeded_total{action="cancel"}'
    config.set_var("tidb_tpu_dispatch_timeout_ms", TIMEOUT_MS)
    config.set_var("tidb_tpu_delta_merge_rows", 64)
    # retain 1-in-2 statement traces through the window: the trace
    # ring is billed to the SERVER ledger that must drain at the end
    config.set_var("tidb_tpu_trace_sample", 2)
    progress(f"{clients} clients + writer + driver for {secs}s "
             f"(watchdog {TIMEOUT_MS}ms)")
    threads = [threading.Thread(target=client_worker, args=(ci,),
                                name=f"chaos-client-{ci}")
               for ci in range(clients)]
    threads.append(threading.Thread(target=writer, name="chaos-writer"))
    driver = threading.Thread(target=chaos_driver, name="chaos-driver")
    t0 = time.monotonic()
    for t in threads:
        t.start()
    driver.start()
    try:
        while time.monotonic() < t0 + secs:
            time.sleep(0.1)
    finally:
        stop.set()
        driver.join(timeout=10)
        failpoint.disable_all()
        for t in threads:
            t.join(timeout=STUCK_SECS + 30)
            if t.is_alive():
                stuck.append(f"thread {t.name} did not drain")
    config.set_var("tidb_tpu_dispatch_timeout_ms", 0)

    # post-chaos serving health: faults disarmed, every analytic must
    # answer correctly again through a fresh connection
    post_ok = True
    try:
        c = new_client()
        for cls, sql in analytics.items():
            rows = c.query(sql)[1]
            cols = (0, 1) if cls == "stock" else None
            if not _rows_match(rows, refs[cls], cols=cols):
                post_ok = False
                wrong.append(f"post-chaos {cls}")
        c.close()
    except Exception as e:  # noqa: BLE001 - recorded in the verdict
        post_ok = False
        wrong.append(f"post-chaos: {e}")

    server.close()
    session.close()
    sched_snap = sched.device_scheduler().snapshot()
    # drain: dead sessions collect, forced merges + HBM sheds return
    # every server-scope residency; the ledgers must reach ZERO
    deadline = time.monotonic() + 10.0
    while (memtrack.SERVER.host or memtrack.SERVER.device) and \
            time.monotonic() < deadline:
        gc.collect()
        sched.shed_server(0)
        time.sleep(0.05)
    ledger_host, ledger_device = memtrack.SERVER.host, \
        memtrack.SERVER.device
    storage.close()
    for k, v in saved.items():
        config.set_var(k, v)

    snap1 = metrics.snapshot()

    def delta_of(prefix: str) -> int:
        return int(sum(v for kk, v in snap1.items()
                       if kk.startswith(prefix)) -
                   sum(v for kk, v in snap0.items()
                       if kk.startswith(prefix)))

    fires = {kk.split('name="')[1].rstrip('"}'): int(
        v - snap0.get(kk, 0))
        for kk, v in snap1.items()
        if kk.startswith(metrics.FAILPOINT_FIRES) and
        v - snap0.get(kk, 0) > 0}
    out = {
        "seed": SEED,
        "clients": clients,
        "ops_completed": ops_done[0],
        "writes_completed": writes_done[0],
        "retries": retried[0],
        "failpoints_armed": len(armed_log),
        "failpoint_fires": fires,
        "wrong_results": wrong[:10],
        "non_retryable_errors": non_retryable[:10],
        "stuck_statements": stuck[:10],
        "oom_cancels": int(snap1.get(oom_key, 0) -
                           snap0.get(oom_key, 0)),
        "watchdog_fires": delta_of(metrics.DISPATCH_TIMEOUTS),
        "quarantines": delta_of(metrics.DEVICE_QUARANTINES),
        "worker_restarts": delta_of(metrics.WORKER_RESTARTS),
        "post_chaos_healthy": post_ok,
        "sched_inflight_end": sched_snap["inflight"],
        "sched_waiting_end": sched_snap["waiting"],
        "server_ledger_host_end": ledger_host,
        "server_ledger_device_end": ledger_device,
    }
    out["passed"] = (not wrong and not non_retryable and not stuck and
                     out["oom_cancels"] == 0 and post_ok and
                     sched_snap["inflight"] == 0 and
                     sched_snap["waiting"] == 0 and
                     ledger_host == 0 and ledger_device == 0 and
                     ops_done[0] > 0 and writes_done[0] > 0)
    progress(f"{ops_done[0]} ops, {writes_done[0]} writes, "
             f"{len(armed_log)} arms, fires={sum(fires.values())}, "
             f"passed={out['passed']}")
    return out


def main(argv: list) -> int:
    sizes = (float(argv[0]), int(argv[1]), float(argv[2])) if argv else ()
    t_start = time.monotonic()

    def progress(msg: str) -> None:
        print(f"[chaos +{time.monotonic() - t_start:7.1f}s] {msg}",
              file=sys.stderr, flush=True)

    verdict = run_chaos(*sizes, progress=progress)
    print(json.dumps(verdict))
    return 0 if verdict["passed"] else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
