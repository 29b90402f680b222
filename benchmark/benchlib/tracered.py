"""Reduction of a profiler trace (.xplane.pb) to the device numbers.

Reads the file through `jax.profiler.ProfileData` and nothing else. A
device plane is one whose name starts with "/device:" and has a line of
executed operations ("XLA Ops"); host spans are the benchmark's own
`TraceAnnotation`s, found on any line of a "/host:" plane by name.

  busy        union of the device-op intervals inside the window
  idle share  1 - busy / window
  op ranking  self time by op name: an op's interval less the ops
              nested inside it on its device's line, so a `conditional`
              or `while` counts what it does itself and the ranking
              sums to busy
  collectives union of the collective ops' intervals (sync and async)
  gaps        the complement of busy, attributed to what the host was
              doing: each `inside_<statement>` span by name, and
              `between_statements` where no such span is open

Intervals are (start_ns, end_ns) pairs throughout.
"""

from __future__ import annotations

import re

OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"     # async collectives and copies live here
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "inside_"
BETWEEN = "between_statements"
MARK_BEGIN = "trace_begin"
MARK_END = "trace_end"
_COLLECTIVE = re.compile(
    r"all-reduce|all-gather|all-to-all|reduce-scatter|collective-permute"
    r"|collective-broadcast|\bsend\b|\brecv\b", re.IGNORECASE)


def read_planes(path: str) -> list[dict]:
    """-> [{"name", "lines": [{"name", "events": [(name, start, end)]}]}]"""
    from jax.profiler import ProfileData
    planes = []
    for plane in ProfileData.from_file(path).planes:
        lines = []
        for line in plane.lines:
            events = [(e.name, float(e.start_ns),
                       float(e.start_ns) + float(e.duration_ns))
                      for e in line.events]
            lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return planes


def union(intervals) -> list[tuple[float, float]]:
    """Sorted, disjoint union of intervals; empty ones dropped."""
    out: list[list[float]] = []
    for s, e in sorted(i for i in intervals if i[1] > i[0]):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def total(intervals) -> float:
    return sum(e - s for s, e in intervals)


def complement(disjoint, lo: float, hi: float) -> list[tuple[float, float]]:
    """What [lo, hi] holds outside a sorted disjoint list."""
    out, at = [], lo
    for s, e in disjoint:
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if hi > at:
        out.append((at, hi))
    return out


def intersect(a, b) -> list[tuple[float, float]]:
    """Intersection of two sorted disjoint lists."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            out.append((lo, hi))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def device_planes(planes) -> list[dict]:
    out = []
    for p in planes:
        if not p["name"].startswith("/device:"):
            continue
        ops = [ln for ln in p["lines"] if ln["name"] == OPS_LINE]
        if ops and ops[0]["events"]:
            mods = [ln for ln in p["lines"] if ln["name"] == MODULES_LINE]
            asyn = [ln for ln in p["lines"] if ln["name"] == ASYNC_LINE]
            out.append({"name": p["name"], "ops": ops[0]["events"],
                        "modules": mods[0]["events"] if mods else [],
                        "async": asyn[0]["events"] if asyn else []})
    return out


def host_spans(planes) -> tuple[list, float | None, float | None]:
    """-> (spans [(name, start, end)], marked begin, marked end)."""
    spans, begin, end = [], None, None
    for p in planes:
        if not p["name"].startswith("/host:"):
            continue
        for ln in p["lines"]:
            for name, s, e in ln["events"]:
                if name.startswith(SPAN_PREFIX):
                    spans.append((name, s, e))
                elif name == MARK_BEGIN:
                    begin = s if begin is None else min(begin, s)
                elif name == MARK_END:
                    end = e if end is None else max(end, e)
    return spans, begin, end


def self_times(ops) -> list[tuple[str, float, float]]:
    """[(name, start, self time)] of one device line's operations: each
    one's duration less those of the operations nested directly inside
    it. A device line runs one thing at a time, so two of its operations
    are disjoint or one holds the other; an overlap that is not nesting
    leaves both whole."""
    own, holding = [], []        # holding: (end, index into own), outermost first
    for name, s, e in sorted(ops, key=lambda o: (o[1], -o[2])):
        while holding and holding[-1][0] < e:
            holding.pop()
        if holding:
            own[holding[-1][1]][2] -= e - s
        holding.append((e, len(own)))
        own.append([name, s, e - s])
    return [tuple(o) for o in own]


def _module_of(modules, at: float) -> str:
    for name, s, e in modules:
        if s <= at < e:
            return name.split("(")[0]
    return ""


def short_name(op: str) -> str:
    """An HLO instruction's text -> "<name> <first shape> <kind>", short
    enough for a ledger line and stable while the program is."""
    lhs, sep, rhs = op.partition(" = ")
    if not sep:
        return op[:96]
    shape = re.search(r"[a-z]+[0-9]*\[[0-9,]*\]", rhs)
    kind = re.search(r"(?:kind|custom_call_target)=\"?(\w+)", rhs)
    verb = re.search(r"\)?\s*([a-z][a-z0-9-]*)\(", rhs)
    parts = [lhs.lstrip("%"), shape.group(0) if shape else "",
             kind.group(1) if kind else (verb.group(1) if verb else "")]
    return " ".join(p for p in parts if p)[:96]


def reduce(planes, spans=None, begin_host_s=None) -> dict | None:
    """The device numbers of one trace, or None where no operation ran
    on any device (there is then nothing to read).

    `spans` are host spans [(name, start_s, end_s)] on the host's clock,
    and `begin_host_s` that clock's reading at the `trace_begin` marker:
    the load generator's own records, which unlike the trace's
    annotations include the statements open at either edge. Without
    them the trace's `inside_*` annotations are used."""
    devs = device_planes(planes)
    if not devs:
        return None
    traced, lo, hi = host_spans(planes)
    if spans is not None and begin_host_s is not None and lo is not None:
        spans = [(n, lo + (s - begin_host_s) * 1e9,
                  lo + (e - begin_host_s) * 1e9) for n, s, e in spans]
    else:
        spans = traced
    every = [iv for d in devs for iv in d["ops"]] + traced
    if lo is None:
        lo = min(s for _n, s, _e in every)
    if hi is None:
        hi = max(e for _n, _s, e in every)
    window = hi - lo
    busy, op_s, coll_s = [], {}, []
    for d in devs:
        inside = [(n, max(s, lo), min(e, hi)) for n, s, e in d["ops"]
                  if min(e, hi) > max(s, lo)]
        busy.append(union((s, e) for _n, s, e in inside))
        coll = [(max(s, lo), min(e, hi)) for n, s, e in d["async"]
                if _COLLECTIVE.search(n) and min(e, hi) > max(s, lo)]
        coll += [(s, e) for n, s, e in inside if _COLLECTIVE.search(n)]
        for n, s, own in self_times(inside):
            mod = _module_of(d["modules"], s)
            key = f"{mod}:{short_name(n)}" if mod else short_name(n)
            op_s[key] = op_s.get(key, 0.0) + own
        coll_s.append(total(union(coll)))
    busy_s = [total(b) for b in busy]
    fullest = max(range(len(devs)), key=lambda i: busy_s[i])
    gaps = complement(busy[fullest], lo, hi)
    by_name: dict[str, list] = {}
    for n, s, e in spans:
        by_name.setdefault(n, []).append((s, e))
    open_any = union(clip([(s, e) for _n, s, e in spans], lo, hi))
    by_name[BETWEEN] = complement(open_any, lo, hi)
    idle = []
    for n, ivs in by_name.items():
        parts = intersect(union(clip(ivs, lo, hi)), gaps)
        if parts:
            idle.append((f"{n}:_total", total(parts) / 1e9))
            idle.append((f"{n}:_longest",
                         max(e - s for s, e in parts) / 1e9))
    ndev = len(devs)
    return {
        "window_s": window / 1e9,
        "window_ns": (lo, hi),
        "devices": ndev,
        "busy_s_per_device": [b / 1e9 for b in busy_s],
        "busy_s": sum(busy_s) / ndev / 1e9,
        "busy_s_fullest": busy_s[fullest] / 1e9,
        "collective_s": sum(coll_s) / ndev / 1e9,
        "device_ops": sorted(((k, v / ndev / 1e9) for k, v in op_s.items()),
                             key=lambda kv: -kv[1]),
        "idle_gaps": sorted(idle, key=lambda kv: -kv[1]),
        "spans": spans,
    }


def reduce_file(path: str) -> dict | None:
    return reduce(read_planes(path))
