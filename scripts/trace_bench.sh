#!/usr/bin/env bash
# CI wrapper for the statement-tracing leg (`python bench.py trace`):
# a traced warm Q1 + point-lookup mix that FAILS if the
# latency_attribution block is unpopulated, any retained span tree is
# unbalanced (begin without end), the TRACE statement's tree is
# missing lifecycle/device-plane spans, or the Chrome trace-event
# export fails schema validation — bench.py asserts all of that
# itself and exits non-zero. Env overrides (BENCH_TRACE_SF / _ITERS /
# _LOOKUPS) pass straight through.
set -euo pipefail
cd "$(dirname "$0")/.."

export BENCH_TRACE_SF="${BENCH_TRACE_SF:-0.02}"
export BENCH_TRACE_ITERS="${BENCH_TRACE_ITERS:-3}"
export BENCH_TRACE_LOOKUPS="${BENCH_TRACE_LOOKUPS:-16}"

out="$(python bench.py trace)"
echo "$out"

TRACE_JSON="$out" python - <<'PY'
import json, os

rep = json.loads(os.environ["TRACE_JSON"])
d = rep["detail"]
assert d.get("passed"), f"trace bench did not pass: {d}"
assert rep["value"] > 0, "no traces retained"
attr = d["latency_attribution"]
assert attr.get("q1", {}).get("traces", 0) > 0, \
    f"attribution unpopulated: {attr}"
print(f"trace bench OK: {rep['value']} traces retained, "
      f"{d['chrome_events']} chrome events, "
      f"q1 p99={attr['q1']['statement']['p99_ms']}ms "
      f"(coverage {attr['q1'].get('p99_coverage')})")
PY
