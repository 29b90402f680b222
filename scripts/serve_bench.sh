#!/usr/bin/env bash
# CI wrapper for the multi-client serving harness (`python bench.py
# serve`): a small fixed workload that fits the tier-1 time budget,
# with sanity floors on the output — the heavy leg (more clients,
# bigger scale) lives in tests/test_concurrent_serving.py behind the
# `slow` marker. Env overrides (BENCH_SERVE_CLIENTS / _ROUNDS /
# _LOOKUPS / _SF) pass straight through to bench.py.
set -euo pipefail
cd "$(dirname "$0")/.."

export BENCH_SERVE_CLIENTS="${BENCH_SERVE_CLIENTS:-4}"
export BENCH_SERVE_ROUNDS="${BENCH_SERVE_ROUNDS:-1}"
export BENCH_SERVE_LOOKUPS="${BENCH_SERVE_LOOKUPS:-4}"
export BENCH_SERVE_SF="${BENCH_SERVE_SF:-0.01}"
# p99 sanity ceiling per class, milliseconds (generous: CPU-XLA CI)
SERVE_P99_FLOOR_MS="${SERVE_P99_FLOOR_MS:-60000}"

out="$(python bench.py serve)"
echo "$out"

SERVE_JSON="$out" SERVE_P99_FLOOR_MS="$SERVE_P99_FLOOR_MS" python - <<'PY'
import json, os

floor_ms = float(os.environ["SERVE_P99_FLOOR_MS"])
rep = json.loads(os.environ["SERVE_JSON"])
d = rep["detail"]
conc = d["concurrent"]
assert rep["value"] > 0, "aggregate rows/sec must be positive"
for cls, lat in conc["latency"].items():
    assert lat["p99_ms"] <= floor_ms, \
        f"{cls}: p99 {lat['p99_ms']}ms over the {floor_ms}ms sanity floor"
pinched = d["pinched"]
assert pinched["completed"], f"pinched leg failed: {pinched['errors']}"
assert pinched["oom_cancels"] == 0, \
    f"pinched leg paid {pinched['oom_cancels']} mid-query OOM cancels"
util = d.get("utilization")
assert util, "utilization block missing from the serve detail"
for key in ("device_busy_fraction", "device_busy_secs",
            "attributed_device_secs", "attribution_coverage",
            "per_class_device_secs"):
    assert key in util, f"utilization block unpopulated: missing {key}"
assert util["device_busy_secs"] > 0, \
    f"utilization block unpopulated: zero device busy time ({util})"
assert 0.9 <= util["attribution_coverage"] <= 1.1, \
    f"attribution coverage {util['attribution_coverage']} outside " \
    f"[0.9, 1.1]: per-session metering is leaking ({util})"
print(f"serve bench OK: {rep['value']} rows/s concurrent "
      f"({conc['speedup_vs_serialized']}x vs serialized), "
      f"admission_shed={pinched['admission_shed']}, oom_cancels=0, "
      f"busy={util['device_busy_fraction']}, "
      f"coverage={util['attribution_coverage']}")
PY
