#!/usr/bin/env bash
# CI wrapper for the fleet scale-out harness (`python bench.py fleet`):
# one store-plane process + N stateless SQL-server processes with
# journal-coherent caches (ISSUE 16). A small fixed mixed workload
# replays against 1 -> 2 -> 4 SQL servers; the gate fails on an
# unpopulated block or sub-linear collapse (4-server aggregate below
# FLEET_SCALING_FLOOR x the single-server aggregate). Env overrides
# (BENCH_FLEET_SERVERS / _CLIENTS / _ROUNDS / _LOOKUPS / _SF) pass
# straight through to bench.py.
set -euo pipefail
cd "$(dirname "$0")/.."

export BENCH_FLEET_SERVERS="${BENCH_FLEET_SERVERS:-4}"
export BENCH_FLEET_CLIENTS="${BENCH_FLEET_CLIENTS:-4}"
export BENCH_FLEET_ROUNDS="${BENCH_FLEET_ROUNDS:-1}"
export BENCH_FLEET_LOOKUPS="${BENCH_FLEET_LOOKUPS:-4}"
export BENCH_FLEET_SF="${BENCH_FLEET_SF:-0.01}"
# the sub-linear-collapse gate: 4-server aggregate must reach this
# multiple of the single-server aggregate (ISSUE 16 satellite bar)
FLEET_SCALING_FLOOR="${FLEET_SCALING_FLOOR:-2.0}"
# p99 sanity ceiling per class, milliseconds (generous: CPU-XLA CI)
FLEET_P99_FLOOR_MS="${FLEET_P99_FLOOR_MS:-60000}"

out="$(python bench.py fleet)"
echo "$out"

FLEET_JSON="$out" FLEET_SCALING_FLOOR="$FLEET_SCALING_FLOOR" \
    FLEET_P99_FLOOR_MS="$FLEET_P99_FLOOR_MS" python - <<'PY'
import json, os

floor = float(os.environ["FLEET_SCALING_FLOOR"])
p99_floor = float(os.environ["FLEET_P99_FLOOR_MS"])
rep = json.loads(os.environ["FLEET_JSON"])
d = rep["detail"]
legs = d.get("legs")
assert legs, "fleet detail has no legs block"
assert rep["value"] > 0, "aggregate statements/sec must be positive"
for leg in legs:
    assert leg["stmts_per_sec"] > 0, f"leg x{leg['servers']} unpopulated"
    assert leg["latency"], f"leg x{leg['servers']} has no latency block"
    for cls, lat in leg["latency"].items():
        assert lat["p99_ms"] <= p99_floor, \
            f"x{leg['servers']} {cls}: p99 {lat['p99_ms']}ms over " \
            f"the {p99_floor}ms sanity floor"
    per = leg.get("per_server")
    assert per and len(per) == leg["servers"], \
        f"leg x{leg['servers']} per-server utilization unpopulated"
    served = sum(s["stmts"] for s in per.values())
    assert served > 0, f"leg x{leg['servers']}: no statements attributed"
cores = os.cpu_count() or 1
if legs[-1]["servers"] >= 4 and cores >= 4:
    scale = d["scaling_max_vs_1"]
    assert scale >= floor, \
        f"sub-linear collapse: x{legs[-1]['servers']} aggregate is " \
        f"only {scale}x the single-server aggregate (floor {floor}x)"
elif legs[-1]["servers"] >= 4:
    # N processes cannot scale past the physical core count; on a
    # starved CI box the gate keeps the populated/latency floors but
    # skips the scale-out multiple
    print(f"fleet bench: {cores} core(s) < 4 — scaling floor skipped "
          f"(observed {d['scaling_max_vs_1']}x)")
coh = d.get("coherence")
assert coh, "coherence counter block missing from the fleet detail"
assert sum(c["journal_pulls"] for c in coh.values()) > 0, \
    f"no journal-window pulls recorded: caches are not coherent ({coh})"
# cluster observability plane (ISSUE 17): the fleet_attribution block
# must be populated for every live member, and the traced statement's
# store-plane ring record must carry its origin_trace_id (bench.py
# raises — never a degraded-but-silent pass — if the cluster-table
# query errors instead of degrading, this block is simply absent)
fa = d.get("fleet_attribution")
assert fa, "fleet_attribution block missing from the fleet detail"
live = fa.get("live_members") or {}
util = fa.get("members") or {}
assert live and set(util) >= set(live), \
    f"per-member utilization unpopulated: live={sorted(live)} " \
    f"attributed={sorted(util)}"
assert any(m["statements"] > 0 for m in util.values()), \
    f"no member shows attributed statements: {util}"
assert fa.get("trace_id", 0) > 0xFFFFFF, \
    f"trace id {fa.get('trace_id')} is not fleet-unique (no nonce)"
assert fa.get("stitched_store"), \
    "store-plane ring record missing origin_trace_id for the traced " \
    "statement"
print(f"fleet bench OK: {rep['value']} stmts/s at "
      f"x{legs[-1]['servers']} ({d['scaling_max_vs_1']}x vs x1), "
      f"journal_pulls="
      f"{sum(c['journal_pulls'] for c in coh.values())}, "
      f"fleet trace {fa['trace_id']} stitched across "
      f"{len(fa['stitched_records'])} member(s)")
PY
