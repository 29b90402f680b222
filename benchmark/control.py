#!/usr/bin/env python3
"""The control of `correct`: the plain reference, put in the program's
place and computed with less than the configuration's guarantee (exact
answers): floating accumulators in float64, the nearest precision below
exact 64-bit integers, and float32 below that; for a keyed statement a
lossy 20-bit key cache. It has to come out as NOT correct.

    python3 benchmark/control.py --workload <name> --seed <n> [--scale f]

No server and no window: the cell's data at the cell's own size, the
cell's statements, and for an open loop the keys the window would send.
The control's answers are put where a run puts the window's and go
through the run's own comparison (benchlib/compare.py). Prints, per
statement and control, the numbers compared; exits 0 when every
statement of the cell is caught by a control, 1 when some control passes
for the truth everywhere.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def _load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, default=None)
    p.add_argument("--scale", type=float, default=1.0,
                   help="multiply every database's sf (tests)")
    args = p.parse_args(argv)
    from benchlib import loadgen
    from benchlib.compare import compare
    manifest = _load(ROOT, "BENCHMARK.json")
    cell = next(w for w in manifest["workloads"]
                if w["name"] == args.workload)
    cfg = _load(ROOT, next(c["file"] for c in manifest["configs"]
                           if c["name"] == cell["config"]))
    traffic = _load(HERE, "traffic", cell["traffic"] + ".json")
    seconds = args.seconds or manifest["run_seconds"]
    data, report = {}, []
    for si, s in enumerate(traffic["streams"]):
        db = s["database"]
        if db not in data:
            spec = dict(cfg["databases"][db])
            spec["sf"] *= args.scale
            gen = importlib.import_module("generators." + spec["generator"])
            data[db] = gen.generate(spec, args.seed)
        for name in loadgen.stream_statements(s):
            mod = importlib.import_module("statements." + name)
            stmt = _load(HERE, "statements", name + ".json")
            if s["loop"] == "open":
                n_keys = data[db].counts[stmt["key_table"]]
                _due, keys = loadgen.open_schedule(s, seconds, n_keys,
                                                   args.seed + si)
                keys = [int(k) for k in keys]
            else:
                keys = [None]
            for label, dtype in (("float64", np.float64),
                                 ("float32", np.float32)):
                if keys != [None] and label == "float32":
                    continue
                answers = [loadgen.Op("control", s["loop"], db, name, k, 0.0,
                                      ok=True,
                                      rows=mod.control(data[db], k, dtype))
                           for k in keys]
                correct, checks = compare(answers, data)
                report.append({
                    "statement": f"{db}.{name}",
                    "control": label if keys == [None] else "key_cache_20bit",
                    "correct": correct,
                    "compared": {k: v["value"] for k, v in checks.items()}})
    for r in report:
        print("control " + json.dumps(r), flush=True)
    by_stmt = {}
    for r in report:
        by_stmt.setdefault(r["statement"], []).append(not r["correct"])
    caught = all(any(v) for v in by_stmt.values())
    print(json.dumps({"workload": args.workload, "seed": args.seed,
                      "control_correct": not caught}), flush=True)
    return 0 if caught else 1


if __name__ == "__main__":
    sys.path.insert(0, HERE)
    sys.exit(main())
