"""Test harness config: force an 8-device virtual CPU mesh BEFORE jax import.

Mirrors the reference's mocktikv strategy (SURVEY.md §4): all distributed
behavior is exercised hermetically on one host — here, multi-chip sharding
runs on 8 virtual CPU devices via XLA's host-platform device count.
"""

import os
import sys

# Tests are hermetic: they run on the CPU backend whatever accelerator
# the machine has (a chip belongs to one process, and the suite spawns
# many). The environment variable is enough, and children inherit it.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: heavy legs excluded from the tier-1 budget")


import pytest  # noqa: E402


@pytest.fixture
def ledger_hygiene():
    """Ledger/slot/gauge hygiene under faults (docs/ROBUSTNESS.md):
    after the test, every armed failpoint is disarmed, the device
    scheduler holds zero in-flight slots and zero waiters, the SERVER
    memtrack host+device ledgers drain to zero once dead storages are
    collected and the shed chain (forced delta merges, HBM sheds) has
    run, and every *_current/*_depth gauge series returns to zero — a
    leaked decrement on an abnormal disconnect/error path shows up as a
    gauge stuck above zero forever. Applied module-wide by the
    failpoint/chaos suites via
    `pytestmark = pytest.mark.usefixtures("ledger_hygiene")`."""
    yield
    import gc
    import time as _time

    from tidb_tpu import memtrack, metrics, sched
    from tidb_tpu.util import failpoint

    failpoint.disable_all()
    snap = sched.device_scheduler().snapshot()
    assert snap["inflight"] == 0, f"scheduler slots leaked: {snap}"
    assert snap["waiting"] == 0, f"scheduler waiters leaked: {snap}"
    # drain loop: a background delta merge may hold staged bytes for a
    # moment (merge() is single-flight, so one shed can miss it)
    deadline = _time.time() + 5.0
    while True:
        gc.collect()
        sched.shed_server(0)
        if memtrack.SERVER.host == 0 and memtrack.SERVER.device == 0:
            break
        if _time.time() >= deadline:
            raise AssertionError(
                f"SERVER ledgers not drained: host={memtrack.SERVER.host}"
                f" device={memtrack.SERVER.device} "
                f"children={[c.snapshot() for c in memtrack.SERVER.children.values()]}")
        _time.sleep(0.05)

    def _leaked_gauges() -> dict:
        """Instantaneous-count gauge series still above zero. The
        series name precedes any {label} suffix; only the unit-less
        level families (_current/_depth) must return to zero — ratio
        and last-statement-peak gauges legitimately hold values."""
        out = {}
        for key, v in metrics.gauges_snapshot().items():
            name = key.split("{", 1)[0]
            if name.endswith(("_current", "_depth")) and v != 0:
                out[key] = v
        return out

    # gauges drain asynchronously (a disconnecting client's server
    # thread decrements the connection gauge after the socket drops)
    deadline = _time.time() + 5.0
    while True:
        leaked = _leaked_gauges()
        if not leaked:
            break
        if _time.time() >= deadline:
            raise AssertionError(
                f"level gauges not drained to zero: {leaked}")
        _time.sleep(0.05)
