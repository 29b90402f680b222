#!/usr/bin/env python3
"""Where ops/hashagg._SegBatch's dense branch stops paying: one
524,288-row block with TPC-H Q1's lanes (12 sum, 6 max, 2 min, int64)
reduced into a 4,096-slot table by each branch at 16 .. 2,048 slots in
use. _DENSE_SLOTS is the largest power of two at which dense costs at
most half the scatters (PERF.md section 6, PR 25). Run on the chip:

    python3 scripts/agg_crossover.py [chiprun_out/agg_crossover.json [rows]]

(a smaller `rows` rehearses the script on the CPU; its times mean nothing)
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

import tidb_tpu  # noqa: E402,F401  (enables x64 before jax makes an array)
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from tidb_tpu.ops import hashagg  # noqa: E402

ROWS, CAPACITY = 1 << 19, 4096
LANES = (("sum", 12), ("max", 6), ("min", 2))
SLOTS = (16, 32, 64, 128, 256, 512, 1024, 2048)


def reduce_block(inv, lanes, nuniq):
    b = hashagg._SegBatch(inv, CAPACITY, nuniq)
    ids = [b.add(x, op) for (op, _k), xs in zip(LANES, lanes) for x in xs]
    b.run()
    return [b.get(i) for i in ids], b.dense


def best_ms(fn, *args, runs=5):
    jax.block_until_ready(fn(*args))
    out = []
    for _ in range(runs):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        out.append(1e3 * (time.perf_counter() - t0))
    return min(out)


def main(argv):
    rows = int(argv[2]) if len(argv) > 2 else ROWS
    rng = np.random.default_rng(25)
    lanes = [[jnp.asarray(rng.integers(-10**12, 10**12, rows))
              for _ in range(k)] for _op, k in LANES]
    table = []
    for g in SLOTS:
        inv = jnp.asarray(rng.integers(0, g, rows), jnp.int32)
        row = {"slots": g}
        got = {}
        for path, limit in (("dense", CAPACITY), ("scatter", 0)):
            hashagg._DENSE_SLOTS = limit     # read when the branch traces
            # a jit of its own: the cache is keyed on the function
            fn = jax.jit(lambda *a: reduce_block(*a))
            row[path + "_ms"] = best_ms(fn, inv, lanes, g)
            out, dense = jax.device_get(fn(inv, lanes, g))
            assert bool(dense) == (path == "dense")
            got[path] = out
        row["equal"] = all(np.array_equal(a, b) for a, b in
                           zip(got["dense"], got["scatter"]))
        row["scatter_over_dense"] = row["scatter_ms"] / row["dense_ms"]
        table.append(row)
        print(json.dumps(row), flush=True)
    doc = {"device": jax.devices()[0].device_kind, "rows": rows,
           "capacity": CAPACITY, "lanes": dict(LANES), "table": table}
    if len(argv) > 1:
        os.makedirs(os.path.dirname(argv[1]) or ".", exist_ok=True)
        with open(argv[1], "w") as f:
            json.dump(doc, f, indent=1)


if __name__ == "__main__":
    main(sys.argv)
