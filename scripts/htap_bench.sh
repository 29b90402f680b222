#!/usr/bin/env bash
# CI wrapper for the HTAP write-pressure sweep (`python bench.py
# htap`): a TPC-C-style new-order/payment write mix under a warm
# analytic loop, swept across write rates, with sanity floors on the
# output — the heavy leg (wire connections, bigger scale) lives in
# tests/test_htap.py behind the `slow` marker. Env overrides
# (BENCH_HTAP_ROWS / _SECS / _RATES) pass straight through to bench.py.
set -euo pipefail
cd "$(dirname "$0")/.."

export BENCH_HTAP_ROWS="${BENCH_HTAP_ROWS:-40000}"
export BENCH_HTAP_SECS="${BENCH_HTAP_SECS:-4}"
export BENCH_HTAP_RATES="${BENCH_HTAP_RATES:-0,20,100}"
# acceptance: analytic rows/sec at the BEST nonzero write rate must be
# within 2x of the read-only warm number (the pre-delta-store behavior
# was cold-scan throughput at ANY nonzero rate)
HTAP_VS_FLOOR="${HTAP_VS_FLOOR:-0.5}"
# write-to-visible freshness must stay bounded (generous: CPU-XLA CI)
HTAP_FRESHNESS_CEIL_MS="${HTAP_FRESHNESS_CEIL_MS:-30000}"

out="$(python bench.py htap)"
echo "$out"

HTAP_JSON="$out" HTAP_VS_FLOOR="$HTAP_VS_FLOOR" \
HTAP_FRESHNESS_CEIL_MS="$HTAP_FRESHNESS_CEIL_MS" python - <<'PY'
import json, os

floor = float(os.environ["HTAP_VS_FLOOR"])
fresh_ceil = float(os.environ["HTAP_FRESHNESS_CEIL_MS"])
rep = json.loads(os.environ["HTAP_JSON"])
d = rep["detail"]
assert rep["value"] > 0, "analytic rows/sec must be positive"
nonzero = {int(k): v for k, v in d["rates"].items() if int(k) > 0}
assert nonzero, "sweep must include a nonzero write rate"
for rate, leg in sorted(d["rates"].items(), key=lambda kv: int(kv[0])):
    assert not leg["errors"], f"rate {rate}: errors {leg['errors']}"
    # the load-bearing pin: the HBM plane never re-colds under writes
    assert leg["delta"]["hbm_misses"] == 0, \
        f"rate {rate}: HBM cache re-colded ({leg['delta']})"
    if int(rate) > 0:
        assert leg["delta"]["served_with_delta"] > 0, \
            f"rate {rate}: no reads served as base+delta"
        assert leg["freshness_ms_max"] is None or \
            leg["freshness_ms_max"] <= fresh_ceil, \
            f"rate {rate}: freshness lag {leg['freshness_ms_max']}ms " \
            f"over the {fresh_ceil}ms ceiling"
ratios = [v["vs_read_only"] for v in nonzero.values()
          if v["vs_read_only"] is not None]
assert ratios, \
    "no read-only baseline ran — include rate 0 in BENCH_HTAP_RATES"
best = max(ratios)
assert best >= floor, \
    f"best nonzero-rate analytic throughput {best} of read-only " \
    f"(< {floor}: the write cliff is back)"
print(f"htap bench OK: {rep['value']} analytic rows/s at the top "
      f"write rate, best nonzero-rate ratio {best} vs read-only, "
      f"zero HBM re-colds")
PY
