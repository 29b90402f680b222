#!/usr/bin/env bash
# CI wrapper for the multichip scaling series (`python bench.py
# multichip`): per-chip rows/sec and serving aggregate at 1/2/4/8
# virtual devices, one subprocess per device count (the XLA
# host-platform device count is fixed at backend init). The bench
# itself exits non-zero on per-chip collapse (>25% drop 1→8), a
# serving aggregate that does not grow with the mesh, or any
# reason="mesh" fallback; this wrapper re-asserts those gates on the
# JSON so a silently-truncated report also fails. Env overrides
# (BENCH_MULTICHIP_SF / _ITERS / _SERVE_ROUNDS / _DEVS) pass straight
# through to bench.py.
set -euo pipefail
cd "$(dirname "$0")/.."

# a VIRTUAL-device series: --xla_force_host_platform_device_count only
# exists on the CPU backend, so this leg is a CPU run by definition (its
# JSON says "platform": "cpu") and checks scaling behaviour, not speed
export JAX_PLATFORMS=cpu

out="$(python bench.py multichip)"
echo "$out"

MULTICHIP_JSON="$out" python - <<'PY'
import json, os

rep = json.loads(os.environ["MULTICHIP_JSON"])
d = rep["detail"]
assert d["ok"], f"multichip checks failed: {d['checks']}"
assert d["checks"]["no_mesh_fallbacks"], \
    "reason=\"mesh\" fallback observed: the unified plane must not " \
    "have a mesh-specific fallback class"
ratios = d["per_chip_ratio_1_to_n"]
assert ratios and min(ratios.values()) >= 0.75, \
    f"per-chip rows/sec collapsed 1->N: {ratios}"
serve = {int(k): v for k, v in d["serve_aggregate_by_n"].items()}
ns = sorted(serve)
assert serve[ns[-1]] > serve[ns[0]] > 0, \
    f"serving aggregate did not grow with the mesh: {serve}"
print(f"multichip bench OK: per-chip ratio 1->{ns[-1]} = "
      f"{min(ratios.values())}, serve {serve[ns[0]]} -> "
      f"{serve[ns[-1]]} rows/s")
PY
