#!/usr/bin/env bash
# CI wrapper for the static-vs-runtime cross-check
# (`python bench.py lintcheck`): the device dataflow pass
# (tidb_tpu/lint/flow/device.py) predicts per-family compile behavior
# from source alone; the leg runs warm Q1/Q3 under kernel profiling
# and fails on drift in EITHER direction — a family the static model
# does not predict, a fingerprinted kernel_profile row compiling past
# the predicted per-row bound, any compile during warm iterations, or
# a non-clean `python -m tidb_tpu.lint --json` run — bench.py asserts
# all of that itself and exits non-zero. Env overrides
# (BENCH_LINTCHECK_SF / _ITERS) pass straight through.
set -euo pipefail
cd "$(dirname "$0")/.."

export BENCH_LINTCHECK_SF="${BENCH_LINTCHECK_SF:-0.02}"
export BENCH_LINTCHECK_ITERS="${BENCH_LINTCHECK_ITERS:-2}"

out="$(python bench.py lintcheck)"
echo "$out"

LINTCHECK_JSON="$out" python - <<'PY'
import json, os

rep = json.loads(os.environ["LINTCHECK_JSON"])
d = rep["detail"]
assert d.get("passed"), f"lintcheck did not pass: {d['failures']}"
assert rep["value"] > 0, "cross-check verified no kernel family"
assert d["lint_clean"], "lint --json reported findings"
assert not d["rows_over_bound"], d["rows_over_bound"]
slow = sorted(d["lint_rule_ms"].items(), key=lambda kv: -kv[1])[:3]
print(f"lintcheck OK: {rep['value']} families verified against the "
      f"static model ({', '.join(sorted(d['predictions']))}), "
      f"{d['traced_sites']} traced sites, {d['lint_rules']} lint rules "
      f"clean (slowest " +
      ", ".join(f"{n} {ms:.0f}ms" for n, ms in slow) + ")")
PY
