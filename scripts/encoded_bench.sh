#!/usr/bin/env bash
# CI wrapper for the encoded-execution comparison (`python bench.py
# encoded`): warm Q1 (dict group keys, direct-indexed agg) and Q3
# (string-filtered join chain — encoded join key lanes + fragment
# fusion) with `tidb_tpu_encoded_exec` on vs off. Contract:
# identical results, ZERO device fallbacks with reason="encoding" on
# the stock TPC-H schema, and a populated bytes_touched block whose
# encoded bytes undercut the decoded equivalent. Env overrides
# (BENCH_ENCODED_SF / _ITERS) pass straight through to bench.py.
set -euo pipefail
cd "$(dirname "$0")/.."

export BENCH_ENCODED_SF="${BENCH_ENCODED_SF:-0.05}"
export BENCH_ENCODED_ITERS="${BENCH_ENCODED_ITERS:-3}"

out="$(python bench.py encoded)"
echo "$out"

ENCODED_JSON="$out" python - <<'PY'
import json, os

rep = json.loads(os.environ["ENCODED_JSON"])
qs = rep["detail"]["queries"]
assert qs, "no queries ran"
for name, q in qs.items():
    # the load-bearing pin: the encoded path never falls back on the
    # stock TPC-H schema — a fallback here means the vocabulary
    # regressed and warm scans silently re-decode
    assert q["encoding_fallbacks"] == 0, \
        f"{name}: {q['encoding_fallbacks']} encoding fallback(s)"
    bt = q["bytes_touched"]
    assert bt["decoded_equivalent_bytes"] > 0, \
        f"{name}: bytes_touched not populated ({bt})"
    assert bt["encoded_bytes"] > 0, \
        f"{name}: encoded bytes not counted ({bt})"
print("encoded bench OK: " +
      ", ".join(f"{n} speedup {q['speedup']}x ratio "
                f"{q['bytes_touched']['ratio']}"
                for n, q in sorted(qs.items())))
PY
