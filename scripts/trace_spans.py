#!/usr/bin/env python3
"""The program's spans in a kept profiler trace, on the trace's clock.

    BENCH_KEEP_TRACE=1 python3 benchmark/run.py --workload <cell> ... --trace 1
    python3 scripts/trace_spans.py .benchmark_work/trace-<cell> [out.json]

While a jax.profiler session is live every `trace.span` of a statement
is also a TraceAnnotation carrying the statement's trace id, and a
garbage collection or a supervised background beat is a `gc.pause` /
`worker.beat` annotation (trace.HOST_MARKS) on the thread that ran it
(tidb_tpu/trace.py). This reads them back from the `.xplane.pb`'s host
planes and prints, by hand, what `benchmark/benchlib/tracered.py` cannot
yet: what the host was doing while the device idled.

  spans         per annotation name: count, seconds (inclusive),
                threads, trace ids
  idle_by_kind  every idle interval of the fullest device in the traced
                part (between the benchmark's `trace_begin` /
                `trace_end` markers), summed by the innermost open
                annotation of each kind of host thread: `session` (a
                line that holds a statement's own spans: its root or
                phases, an `exec.*` span, `copr.wait`), `cop-stream`
                (one that holds a `copr.stream` worker span), `other`
                (every other line with an annotation of the program).
                Where several threads of one kind have one open, the interval
                is shared among them in equal parts, so no interval
                counts twice within a kind, and each kind sums to the
                idle seconds with `(no span)` for the rest
  longest_gaps  the two longest idle intervals, with the same innermost
                attribution per host thread

A span that straddles an edge of the profiler session is not in the
trace at all (TraceMe records complete events only), so a thread inside
such a span reads `(no span)`. The load generator's `inside_*` spans
bound statements on the client's threads and are not the program's:
they count in `spans` and nowhere else.
"""

from __future__ import annotations

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "benchmark"))

NO_SPAN = "(no span)"
KINDS = ("session", "cop-stream", "other")


def find_xplane(path: str) -> str:
    if os.path.isfile(path):
        return path
    for base, _dirs, files in os.walk(path):
        for f in files:
            if f.endswith(".xplane.pb"):
                return os.path.join(base, f)
    raise SystemExit(f"no .xplane.pb under {path}")


def read(path: str):
    """-> (device op intervals by device plane, host lines [(plane,
    line, events)], markers) with events (name, start_ns, end_ns,
    trace_id|None): the program's annotations and the load generator's
    `inside_*` only."""
    from jax.profiler import ProfileData

    from tidb_tpu import trace
    names = set(trace.SPAN_NAMES) | set(trace.HOST_MARKS)
    dev, host, marks = {}, [], {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    dev.setdefault(plane.name, []).extend(
                        (float(e.start_ns),
                         float(e.start_ns) + float(e.duration_ns))
                        for e in line.events)
        elif plane.name.startswith("/host:"):
            for i, line in enumerate(plane.lines):
                evs = []
                for e in line.events:
                    if e.name in ("trace_begin", "trace_end"):
                        marks[e.name] = float(e.start_ns)
                    if e.name in names or e.name.startswith("inside_"):
                        tid = dict(e.stats).get("trace_id")
                        s = float(e.start_ns)
                        evs.append((e.name, s, s + float(e.duration_ns),
                                    tid))
                if evs:
                    host.append((plane.name, f"{line.name}#{i}", evs))
    return dev, host, marks


def segments(evs, lo: float, hi: float) -> list:
    """[(start, end, name)]: [lo, hi] cut where the innermost open event
    of one thread's properly nested events changes, in order, with
    NO_SPAN where none is open."""
    out: list = []

    def add(name, a, b):
        a, b = max(a, lo), min(b, hi)
        if b > a:
            out.append((a, b, name))

    stack: list = []      # (name, end)
    at = lo
    for name, s, e, _tid in sorted(evs, key=lambda v: (v[1], -v[2])):
        while stack and stack[-1][1] <= s:
            top, top_end = stack.pop()
            add(top, at, top_end)
            at = max(at, top_end)
        add(stack[-1][0] if stack else NO_SPAN, at, s)
        at = max(at, s)
        stack.append((name, e))
    while stack:
        top, top_end = stack.pop()
        add(top, at, top_end)
        at = max(at, top_end)
    add(NO_SPAN, at, hi)
    return out


def innermost(evs, lo: float, hi: float) -> dict:
    """Seconds of [lo, hi] by the innermost open event of one thread."""
    out: dict = {}
    for a, b, name in segments(evs, lo, hi):
        out[name] = out.get(name, 0.0) + (b - a) / 1e9
    return out


# what only a statement's own thread opens: its root and phases, the
# root executors' spans, the wait for a fanned-out stream's frames. A
# statement longer than the traced part has no `statement` event in it
# (its root straddles the session's edge), so its thread is known by
# the rest
SESSION_SPANS = frozenset({"statement", "parse", "plan", "execute",
                           "admission", "commit", "copr.wait"})


def kind_of(evs) -> str | None:
    """The kind of host thread a line's events say it is; None for a
    line with none of the program's annotations (the load generator's)."""
    names = {n for n, _s, _e, _t in evs}
    if names & SESSION_SPANS or any(n.startswith("exec.") for n in names):
        return "session"
    if "copr.stream" in names:
        return "cop-stream"
    if any(not n.startswith("inside_") for n in names):
        return "other"
    return None


def share(threads, gaps) -> dict:
    """Seconds of the sorted, disjoint `gaps` by the innermost open
    annotation of a kind's `threads` (each a list of its events): at
    every instant the gap is shared in equal parts among the threads
    that have one open, and NO_SPAN takes the instants where none has.
    The values sum to the gaps' seconds."""
    if not gaps:
        return {}
    lo, hi = gaps[0][0], gaps[-1][1]
    pts = []
    for i, evs in enumerate(threads):
        program = [v for v in evs if not v[0].startswith("inside_")]
        segs = [sg for sg in segments(program, lo, hi) if sg[2] != NO_SPAN]
        for a, b, name in _clip(segs, gaps):
            pts.append((a, 1, i, name))
            pts.append((b, 0, i, name))
    pts.sort()
    out: dict = {}
    active: dict = {}
    prev = None
    for x, opens, i, name in pts:
        if active and x > prev:
            w = (x - prev) / len(active) / 1e9
            for n in active.values():
                out[n] = out.get(n, 0.0) + w
        prev = x
        if opens:
            active[i] = name
        else:
            active.pop(i, None)
    idle = sum(b - a for a, b in gaps) / 1e9
    out[NO_SPAN] = max(0.0, idle - sum(out.values()))
    return out


def _clip(segs, gaps) -> list:
    """The named segments (sorted, disjoint) cut to the gaps (sorted,
    disjoint)."""
    out, i, j = [], 0, 0
    while i < len(segs) and j < len(gaps):
        a, b = max(segs[i][0], gaps[j][0]), min(segs[i][1], gaps[j][1])
        if b > a:
            out.append((a, b, segs[i][2]))
        if segs[i][1] < gaps[j][1]:
            i += 1
        else:
            j += 1
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
             for _n, s, e, _t in evs] + [iv for v in dev.values()
                                         for iv in v]
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
        busy_by = {p: tracered.union(tracered.clip(v, lo, hi))
                   for p, v in dev.items()}
        fullest = max(busy_by, key=lambda p: tracered.total(busy_by[p]))
        busy = busy_by[fullest]
        idle = tracered.complement(busy, lo, hi)
        report["device"] = fullest
        report["device_busy_s"] = round(tracered.total(busy) / 1e9, 6)
        report["traced_s"] = round((hi - lo) / 1e9, 6)
        report["idle_s"] = round(tracered.total(idle) / 1e9, 6)
        by_kind: dict = {k: [] for k in KINDS}
        for _plane, _line, evs in host:
            kind = kind_of(evs)
            if kind is not None:
                by_kind[kind].append(evs)
        report["idle_by_kind"] = {
            k: {"threads": len(threads),
                "seconds": {n: round(v, 6) for n, v in sorted(
                    share(threads, idle).items(), key=lambda kv: -kv[1])}}
            for k, threads in by_kind.items()}
        report["longest_gaps"] = []
        for g_lo, g_hi in sorted(idle, key=lambda g: g[0] - g[1])[:2]:
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
