"""Chaos under concurrency (docs/ROBUSTNESS.md): seeded fault
schedules over concurrent readers + writers, with the ledger/slot
hygiene fixture asserting SERVER memtrack ledgers and scheduler slots
drain to zero after every test. The light leg runs in-process on
direct sessions inside the tier-1 budget; the full wire-protocol
harness (tests/chaos_harness.py) rides behind the `slow` marker."""

import json
import random
import subprocess
import sys
import threading
import time

import pytest

from tidb_tpu import config, errcode, metrics, sched
from tidb_tpu.session import Session, SQLError
from tidb_tpu.store.storage import new_mock_storage
from tidb_tpu.util import failpoint

pytestmark = pytest.mark.usefixtures("ledger_hygiene")

N_ROWS = 3000
SEED = 20260804


@pytest.fixture
def env():
    saved = {k: config.get_var(k) for k in
             ("tidb_tpu_device", "tidb_tpu_device_min_rows",
              "tidb_tpu_dispatch_timeout_ms",
              "tidb_tpu_delta_merge_rows")}
    config.set_var("tidb_tpu_device_min_rows", 1)
    st = new_mock_storage()
    s = Session(st)
    s.execute("CREATE DATABASE c")
    s.execute("USE c")
    s.execute("CREATE TABLE t (id BIGINT PRIMARY KEY, seg BIGINT, "
              "v BIGINT)")
    rows = [f"({i},{i % 7},{(i * 37) % 500})" for i in range(N_ROWS)]
    s.execute("INSERT INTO t VALUES " + ",".join(rows))
    info = s.domain.info_schema().table("c", "t")
    st.cluster.split_table(info.id, 4, max_handle=N_ROWS)
    yield s, st
    failpoint.disable_all()
    sched.device_health().note_ok()
    s.close()
    st.close()
    for k, v in saved.items():
        config.set_var(k, v)


AGG = "SELECT seg, COUNT(*), SUM(v) FROM t GROUP BY seg ORDER BY seg"


class TestInProcessChaos:
    def test_concurrent_readers_writers_under_seeded_faults(self, env):
        """3 reader threads + 1 writer run ~3s under a seeded schedule
        of device faults, HBM faults and RPC bursts: every analytic
        answer matches the write-invariant reference columns, every
        error that surfaces is retryable-classified, and (fixture) the
        ledgers/slots drain afterwards."""
        s, st = env
        rng = random.Random(SEED)
        ref = s.query(AGG).rows
        ref_counts = [(r[0], r[1]) for r in ref]

        stop = threading.Event()
        wrong: list = []
        non_retryable: list = []
        done = [0]

        def reader(ri: int) -> None:
            rs = Session(st, db="c")
            while not stop.is_set():
                try:
                    rows = rs.query(AGG).rows
                    # seg/count columns are write-invariant (the
                    # writer only touches v): they must match exactly
                    if [(r[0], r[1]) for r in rows] != ref_counts:
                        wrong.append(rows[:2])
                    done[0] += 1
                except SQLError as e:
                    code = errcode.classify(e)[0]
                    if not errcode.is_retryable(code):
                        non_retryable.append(f"({code}) {e}")
                except failpoint.DeviceFaultError as e:
                    # a raw device fault (no SQL wrapping on the
                    # library path) is retryable by contract
                    assert errcode.classify(e)[0] == \
                        errcode.ER_DEVICE_FAULT
            rs.close()

        def writer() -> None:
            ws = Session(st, db="c")
            seq = 0
            while not stop.is_set():
                seq += 1
                k = (seq * 7919) % N_ROWS
                try:
                    ws.execute(f"UPDATE t SET v = v + 1 "
                               f"WHERE id = {k}")
                except SQLError as e:
                    code = errcode.classify(e)[0]
                    if not errcode.is_retryable(code):
                        non_retryable.append(f"write ({code}) {e}")
                time.sleep(0.01)
            ws.close()

        def driver() -> None:
            schedule = [
                ("device/dispatch",
                 lambda: f"{rng.randint(1, 3)}*raise(DeviceFaultError)"),
                ("hbm/fill",
                 lambda: f"{rng.randint(1, 2)}*raise(DeviceFaultError)"),
                ("hbm/patch", lambda: "2*return(1)"),
                ("rpc/request",
                 lambda: f"{rng.randint(2, 4)}*raise(ServerBusyError)"),
                ("device/finalize",
                 lambda: f"1-in-4:delay({rng.randint(5, 20)})"),
            ]
            while not stop.is_set():
                name, mk = schedule[rng.randrange(len(schedule))]
                failpoint.enable(name, mk())
                stop.wait(rng.uniform(0.05, 0.15))
                failpoint.disable(name)

        threads = [threading.Thread(target=reader, args=(i,),
                                    name=f"chaos-reader-{i}")
                   for i in range(3)]
        threads.append(threading.Thread(target=writer,
                                        name="chaos-writer"))
        dt = threading.Thread(target=driver, name="chaos-driver")
        for t in threads:
            t.start()
        dt.start()
        time.sleep(3.0)
        stop.set()
        dt.join(timeout=10)
        failpoint.disable_all()
        for t in threads:
            t.join(timeout=60)
            assert not t.is_alive(), f"{t.name} stuck"
        assert wrong == []
        assert non_retryable == []
        assert done[0] > 0
        # post-chaos: disarmed serving answers correctly again
        sched.device_health().note_ok()
        rows = s.query(AGG).rows
        assert [(r[0], r[1]) for r in rows] == ref_counts

    def test_watchdog_under_concurrency_never_wedges(self, env):
        """A watchdog-tripping delay under concurrent statements: the
        affected statements surface the retryable 9009 (or succeed on
        a retried path), nothing hangs, slots drain (fixture)."""
        s, st = env
        want = [(r[0], r[1]) for r in s.query(AGG).rows]
        config.set_var("tidb_tpu_dispatch_timeout_ms", 150)
        failpoint.enable("device/finalize", "2*delay(600)")
        errs: list = []
        oks = [0]

        def runner() -> None:
            rs = Session(st, db="c")
            for _ in range(3):
                try:
                    rows = rs.query(AGG).rows
                    assert [(r[0], r[1]) for r in rows] == want
                    oks[0] += 1
                except Exception as e:  # noqa: BLE001 - classified below
                    errs.append(errcode.classify(e)[0])
            rs.close()

        threads = [threading.Thread(target=runner, name=f"wd-{i}")
                   for i in range(2)]
        t0 = time.time()
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
            assert not t.is_alive(), "statement wedged past watchdog"
        failpoint.disable("device/finalize")
        config.set_var("tidb_tpu_dispatch_timeout_ms", 0)
        # bounded wall time: 6 statements, two 600ms delays, no hang
        assert time.time() - t0 < 110
        assert oks[0] + len(errs) == 6
        for code in errs:
            assert code == errcode.ER_DEVICE_FAULT, errs


@pytest.mark.slow
class TestChaosBenchLeg:
    def test_bench_chaos_small_leg(self):
        """The full wire-protocol chaos harness, small: fixed seed,
        short window, in a process of its own (it reads the process's
        ledgers at the end); the verdict must be passed=True with every
        invariant field clean."""
        import os
        r = subprocess.run(
            [sys.executable, "-m", "tests.chaos_harness",
             "8", "3", "0.005"],
            capture_output=True, text=True,
            env=dict(os.environ, JAX_PLATFORMS="cpu"), timeout=600,
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(
                __file__))))
        assert r.returncode == 0, r.stderr[-2000:]
        d = json.loads(r.stdout.strip().splitlines()[-1])
        assert d["passed"], d
        assert d["wrong_results"] == []
        assert d["non_retryable_errors"] == []
        assert d["stuck_statements"] == []
        assert d["oom_cancels"] == 0
        assert d["sched_inflight_end"] == 0
        assert d["server_ledger_host_end"] == 0
        assert d["server_ledger_device_end"] == 0
