#!/usr/bin/env bash
# CI wrapper for the chaos serve harness (`python bench.py chaos`,
# docs/ROBUSTNESS.md): the PR-9 serve mix + PR-11 HTAP writes under a
# FIXED-SEED randomized fault schedule across the device plane, with
# hard assertions on the robustness contract — zero wrong results,
# zero non-retryable errors, zero stuck statements, zero mid-query OOM
# cancels, and every scheduler slot / memtrack ledger drained to zero.
# Env overrides (BENCH_CHAOS_SEED / _CLIENTS / _SECS / _SF /
# _WRITES_PER_SEC / _TIMEOUT_MS) pass straight through to bench.py.
set -euo pipefail
cd "$(dirname "$0")/.."

export BENCH_CHAOS_SEED="${BENCH_CHAOS_SEED:-20260804}"
export BENCH_CHAOS_CLIENTS="${BENCH_CHAOS_CLIENTS:-4}"
export BENCH_CHAOS_SECS="${BENCH_CHAOS_SECS:-12}"
export BENCH_CHAOS_SF="${BENCH_CHAOS_SF:-0.01}"

out="$(python bench.py chaos)"
echo "$out"

CHAOS_JSON="$out" python - <<'PY'
import json, os

rep = json.loads(os.environ["CHAOS_JSON"])
d = rep["detail"]
assert d["ops_completed"] > 0, "no client ops completed under chaos"
assert d["writes_completed"] > 0, "no HTAP writes completed under chaos"
assert d["failpoints_armed"] > 0 and d["failpoint_fires"], \
    "the fault schedule never fired — the run proved nothing"
assert d["wrong_results"] == [], \
    f"WRONG RESULTS under faults: {d['wrong_results']}"
assert d["non_retryable_errors"] == [], \
    f"non-retryable errors surfaced: {d['non_retryable_errors']}"
assert d["stuck_statements"] == [], \
    f"stuck statements: {d['stuck_statements']}"
assert d["oom_cancels"] == 0, \
    f"chaos paid {d['oom_cancels']} mid-query OOM cancels"
assert d["post_chaos_healthy"], "serving did not recover after disarm"
assert d["sched_inflight_end"] == 0 and d["sched_waiting_end"] == 0, \
    "scheduler slots leaked"
assert d["server_ledger_host_end"] == 0 and \
    d["server_ledger_device_end"] == 0, "SERVER memtrack ledgers leaked"
assert d["passed"], "chaos harness reported failure"
print(f"chaos bench OK: {d['ops_completed']} ops + "
      f"{d['writes_completed']} writes under "
      f"{d['failpoints_armed']} armed faults "
      f"(fires={sum(d['failpoint_fires'].values())}, "
      f"retries={d['retries']}, watchdog={d['watchdog_fires']}, "
      f"quarantines={d['quarantines']}, "
      f"worker_restarts={d['worker_restarts']}); "
      f"zero wrong results, zero non-retryable errors, ledgers drained")
PY
