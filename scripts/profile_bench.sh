#!/usr/bin/env bash
# CI wrapper for the kernel-profiling leg (`python bench.py profile`):
# warm Q1/Q3/Q5 under the continuous profiler that FAILS if
# information_schema.kernel_profile is unpopulated, any row that moved
# bytes is missing its roofline_fraction, compile counts grow across
# the warm iterations (a warm run that recompiles), or a
# statement_profile memo row is missing the mode that ran — bench.py
# asserts all of that itself and exits non-zero. Env overrides
# (BENCH_PROFILE_SF / _ITERS) pass straight through.
set -euo pipefail
cd "$(dirname "$0")/.."

export BENCH_PROFILE_SF="${BENCH_PROFILE_SF:-0.02}"
export BENCH_PROFILE_ITERS="${BENCH_PROFILE_ITERS:-3}"

out="$(python bench.py profile)"
echo "$out"

PROFILE_JSON="$out" python - <<'PY'
import json, os

rep = json.loads(os.environ["PROFILE_JSON"])
d = rep["detail"]
assert d.get("passed"), f"profile bench did not pass: {d['failures']}"
assert rep["value"] > 0, "no kernel profiles recorded"
assert d["statement_profile_rows"] > 0, "mode-history memo empty"
print(f"profile bench OK: {rep['value']} kernel profiles "
      f"({', '.join(d['kernel_profile_families'])}), "
      f"{d['statement_profile_rows']} memo rows "
      f"(modes {', '.join(d['statement_profile_modes'])}), "
      f"roofline peak {d['roofline']['peak_gbps']}GB/s "
      f"[{d['roofline']['source']}]")
PY
