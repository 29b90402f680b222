#!/usr/bin/env python3
"""The program's spans in a kept profiler trace, on the trace's clock.

    BENCH_KEEP_TRACE=1 python3 benchmark/run.py --workload <cell> ... --trace 1
    python3 scripts/trace_spans.py .benchmark_work/trace-<cell> [out.json]

While a jax.profiler session is live every `trace.span` of a statement
is also a TraceAnnotation carrying the statement's trace id
(tidb_tpu/trace.py). This reads them back from the `.xplane.pb`'s host
planes and prints, by hand, what `benchmark/benchlib/tracered.py` cannot
yet: which of the program's spans cover the device's longest idle gap.

  spans      per span name: count, seconds (inclusive), threads, trace ids
  gaps       the two longest intervals of the traced part (between the
             benchmark's `trace_begin` / `trace_end` markers) in which no
             device op ran
  innermost  per gap and host thread, the gap's seconds by the innermost
             open program span (a thread's annotations nest, so the
             innermost one is what that thread was doing), and
             `(no span)`. A span that straddles an edge of the profiler
             session is not in the trace at all (TraceMe records complete
             events only), so a thread inside such a span reads `(no span)`
"""

from __future__ import annotations

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "benchmark"))


def find_xplane(path: str) -> str:
    if os.path.isfile(path):
        return path
    for base, _dirs, files in os.walk(path):
        for f in files:
            if f.endswith(".xplane.pb"):
                return os.path.join(base, f)
    raise SystemExit(f"no .xplane.pb under {path}")


def read(path: str):
    """-> (device op intervals, host lines [(plane, line, events)]) with
    events (name, start_ns, end_ns, trace_id|None), program spans and
    the load generator's `inside_*` only."""
    from jax.profiler import ProfileData

    from tidb_tpu import trace
    dev, host, marks = [], [], {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    dev += [(float(e.start_ns),
                             float(e.start_ns) + float(e.duration_ns))
                            for e in line.events]
        elif plane.name.startswith("/host:"):
            for i, line in enumerate(plane.lines):
                evs = []
                for e in line.events:
                    if e.name in ("trace_begin", "trace_end"):
                        marks[e.name] = float(e.start_ns)
                    if e.name in trace.SPAN_NAMES or \
                            e.name.startswith("inside_"):
                        tid = dict(e.stats).get("trace_id")
                        s = float(e.start_ns)
                        evs.append((e.name, s, s + float(e.duration_ns),
                                    tid))
                if evs:
                    host.append((plane.name, f"{line.name}#{i}", evs))
    return dev, host, marks


def innermost(evs, lo: float, hi: float) -> dict:
    """Seconds of [lo, hi] by the innermost open span of one thread's
    properly nested events."""
    out: dict = {}

    def add(name, a, b):
        a, b = max(a, lo), min(b, hi)
        if b > a:
            out[name] = out.get(name, 0.0) + (b - a) / 1e9

    stack: list = []      # (name, end)
    at = lo
    for name, s, e, _tid in sorted(evs, key=lambda v: (v[1], -v[2])):
        while stack and stack[-1][1] <= s:
            top, top_end = stack.pop()
            add(top, at, top_end)
            at = max(at, top_end)
        add(stack[-1][0] if stack else "(no span)", at, s)
        at = max(at, s)
        stack.append((name, e))
    while stack:
        top, top_end = stack.pop()
        add(top, at, top_end)
        at = max(at, top_end)
    add("(no span)", at, hi)
    return out


def main(argv) -> int:
    from benchlib import tracered
    path = find_xplane(argv[1])
    dev, host, marks = read(path)
    spans: dict = {}
    for _plane, line, evs in host:
        for name, s, e, tid in evs:
            d = spans.setdefault(name, {"count": 0, "seconds": 0.0,
                                        "threads": set(), "ids": set()})
            d["count"] += 1
            d["seconds"] += (e - s) / 1e9
            d["threads"].add(line)
            if tid is not None:
                d["ids"].add(tid)
    every = [(s, e) for _p, _l, evs in host
             for _n, s, e, _t in evs] + dev
    report = {"xplane": os.path.relpath(path, ROOT),
              "spans": {n: {"count": d["count"],
                            "seconds": round(d["seconds"], 6),
                            "threads": len(d["threads"]),
                            "trace_ids": len(d["ids"])}
                        for n, d in sorted(spans.items())}}
    if dev and every:
        # the benchmark's own markers bound the traced part where the
        # trace has them (benchmark/run.py:_trace_part)
        lo = marks.get("trace_begin", min(s for s, _e in every))
        hi = marks.get("trace_end", max(e for _s, e in every))
        busy = tracered.union(tracered.clip(dev, lo, hi))
        gaps = sorted(tracered.complement(busy, lo, hi),
                      key=lambda g: g[0] - g[1])[:2]
        report["device_busy_s"] = round(tracered.total(busy) / 1e9, 6)
        report["traced_s"] = round((hi - lo) / 1e9, 6)
        report["longest_gaps"] = []
        for g_lo, g_hi in gaps:
            ids = {t for _p, _l, evs in host for _n, s, e, t in evs
                   if t is not None and e > g_lo and s < g_hi}
            report["longest_gaps"].append({
                "start_s": round((g_lo - lo) / 1e9, 6),
                "seconds": round((g_hi - g_lo) / 1e9, 6),
                "trace_ids": sorted(ids),
                "innermost": {
                    line: {n: round(v, 6) for n, v in sorted(
                        innermost(evs, g_lo, g_hi).items(),
                        key=lambda kv: -kv[1])}
                    for _plane, line, evs in host}})
    text = json.dumps(report, indent=1)
    print(text)
    if len(argv) > 2:
        os.makedirs(os.path.dirname(os.path.abspath(argv[2])), exist_ok=True)
        with open(argv[2], "w") as f:
            f.write(text + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
