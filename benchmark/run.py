#!/usr/bin/env python3
"""One run of one cell of the benchmark:

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A new process each time. It finds the cell in BENCHMARK.json, its
configuration, traffic mix, statements and metric readers by name in
the files beside this one, checks that JAX sees the cell's chips on a
TPU, starts the server through the program's entry point, generates the
data from --seed, loads and warms only what the cell's traffic names,
runs one beat of the program's stats worker and warms again while the
statistics moved after the last warm-up began (so no window holds the
worker's first pass, and every window opens on the plans it leaves and
on what they left in the caches), drives the server's MySQL wire port
for --seconds, reads the counters (and with --trace 1 a profiler trace
of a short steady part), frees the server, compares every answer of the
window with the plain reference, and prints one JSON line. README.md
has the file layout.

--rehearse accepts whatever platform JAX has (the CPU here) and prints
its readings under `rehearsal_metrics`, never under `metrics`: a CPU run
proves control flow and counts, not a device number.
"""

from __future__ import annotations

import time

_T_PROC = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402
import traceback  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
WORK = os.path.join(ROOT, ".benchmark_work")
# re-warms at most, should the statistics keep moving during set-up
_REWARMS = 3


class Ctx:
    """What one run hands to a metric reader."""

    def __init__(self):
        self.cell = self.config = self.traffic = None
        self.statements = {}      # name -> the statement's file
        self.schema = {}          # generator name -> its schema file
        self.counts = {}          # database -> table -> rows generated
        self.stmt_db = {}         # statement -> a database it runs on
        self.setup = {}           # phase -> seconds, plus load counts
        self.setup_s = None
        self.before = self.after = self.after_setup = self.at_start = None
        self.window = None
        self.trace = None         # benchlib.tracered.reduce(), traced runs
        self.device = {}
        self.peaks = None

    def needed_bytes(self, stmt: str) -> int:
        from benchlib import needed
        db = self.stmt_db[stmt]
        gen = self.config["databases"][db]["generator"]
        return needed.statement_bytes(self.statements[stmt],
                                      self.schema[gen], self.counts[db])


def _load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def _reader(name: str):
    """metrics/<name>.py, loaded by path; a suffixed name (`x.stream`:
    one quantity, a name per end-to-end metric it moves) is served by
    metrics/x.py where it has no file of its own."""
    path = os.path.join(HERE, "metrics", name + ".py")
    if not os.path.exists(path) and "." in name:
        path = os.path.join(HERE, "metrics", name.rsplit(".", 1)[0] + ".py")
    spec = importlib.util.spec_from_file_location(
        "metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _metrics_of(manifest: dict, section: str, cell: str) -> list[dict]:
    return [m for m in manifest[section]
            if "workloads" not in m or cell in m["workloads"]]


def _statement_names(traffic: dict) -> list[str]:
    from benchlib.loadgen import stream_statements
    names = [n for s in traffic["streams"] for n in stream_statements(s)]
    names += [w["statement"] for w in traffic.get("warm", [])]
    return sorted(set(names))


def _log(msg: str) -> None:
    print(f"[bench +{time.perf_counter() - _T_PROC:7.1f}s] {msg}",
          file=sys.stderr, flush=True)


def _warm(ctx: Ctx, port: int, phase: str) -> None:
    """Run the traffic's `warm` list once, each statement on a connection
    of its own."""
    from benchlib.wire import Client
    for w in ctx.traffic.get("warm", []):
        c = Client("127.0.0.1", port, db=w["database"],
                   timeout_s=float(w.get("timeout_s", 1000.0)))
        sql = ctx.statements[w["statement"]]["sql"]
        took = []
        for i in range(int(w["times"])):
            t1 = time.perf_counter()
            c.query(sql.format(key=i) if "{key}" in sql else sql)
            took.append(round(time.perf_counter() - t1, 3))
        _log(f"{phase} {w['database']}.{w['statement']}: {took[:5]} s")
        c.close()


def _settle(ctx: Ctx, sut, warm) -> None:
    """Warm the cell, run one beat of the program's stats worker, and
    warm again while the statistics' version is higher than it was when
    the last warm-up began: whichever analyzed the tables, the beat or
    the worker's own tick (during a long warm-up, in a run that
    compiles, or racing the beat), a version that moved re-plans every
    cached statement, and the window opens on the plans the statistics
    now give, with what those plans leave in the caches. `warm` runs
    the traffic's warm list once, under the phase name it gets."""
    version = sut.stats_version()
    t = time.perf_counter()
    warm("warm")
    ctx.setup["warm"] = time.perf_counter() - t
    t = time.perf_counter()
    got = sut.stats_pass()
    ctx.setup["stats"] = time.perf_counter() - t
    ctx.setup["stats_analyzed"] = len(got["analyzed"])
    _log(f"stats pass analyzed {got['analyzed']}; version {version} "
         f"before the warm-up, {got['version_before']} -> "
         f"{got['version_after']} over the pass")
    rewarms = 0
    while rewarms < _REWARMS and sut.stats_version() > version:
        version = sut.stats_version()
        t = time.perf_counter()
        warm("rewarm")
        ctx.setup["rewarm"] = (ctx.setup.get("rewarm", 0.0)
                               + time.perf_counter() - t)
        rewarms += 1
    ctx.setup["rewarms"] = rewarms
    if not rewarms:
        _log("no re-warm: the statistics did not move after the warm-up "
             "began")


def _trace_part(spec: dict, window_s: float, trace_dir: str, marks: dict):
    """Trace `spec["seconds"]` of the window (or what the window has
    left) from `spec["start_s"]` on, from a thread of its own. `marks`
    gets the host clock at the `trace_begin` marker, which ties the load
    generator's records to the trace's clock (a span open when the trace
    starts or stops is not in the trace)."""
    import jax
    start_s = min(float(spec.get("start_s", 2.0)), window_s / 4)
    traced_s = min(float(spec["seconds"]), window_s - start_s)

    def body():
        time.sleep(start_s)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        try:
            marks["begin"] = time.perf_counter()
            with jax.profiler.TraceAnnotation("trace_begin"):
                pass
            time.sleep(traced_s)
            with jax.profiler.TraceAnnotation("trace_end"):
                pass
        finally:
            jax.profiler.stop_trace()

    t = threading.Thread(target=body, daemon=True)
    t.start()
    return t


def _find_xplane(trace_dir: str) -> str | None:
    for base, _dirs, files in os.walk(trace_dir):
        for f in files:
            if f.endswith(".xplane.pb"):
                return os.path.join(base, f)
    return None


def _top_ops(ranked: list, n: int = 10) -> list:
    """The first `n` places of the device-op ranking for the result's
    line; where it is longer, the last place holds the rest together, so
    the entries still sum to the device's busy time."""
    if len(ranked) <= n:
        return [[k, v] for k, v in ranked]
    rest = ranked[n - 1:]
    return [[k, v] for k, v in ranked[:n - 1]] + [
        [f"{len(rest)} other ops", sum(v for _k, v in rest)]]


def _sweep(args, ctx: Ctx, sut, loadgen) -> None:
    """Several fixed rates in one process on one set-up: for each, one
    window of --seconds with every open loop at that rate. Prints a row
    per rate; the knee is read off by hand and written into the traffic
    file (PERF.md has the table)."""
    from benchlib import rates
    for rate in (float(r) for r in args.sweep.split(",")):
        traffic = json.loads(json.dumps(ctx.traffic))
        for s in traffic["streams"]:
            if s["loop"] == "open":
                s["rate_per_s"] = rate
        ctx.window = loadgen.run_window(
            sut.port, traffic, ctx.statements, ctx.counts, args.seed,
            args.seconds)
        opens = [o for o in ctx.window.ops if o.loop == "open"]
        lat = [1000.0 * (o.done - o.due) for o in opens if o.ok]
        tail = [1000.0 * (o.done - o.due) for o in opens[-len(opens) // 5:]
                if o.ok]
        row = {"rate_per_s": rate, "sent": len(opens),
               "failed": sum(1 for o in opens if not o.ok),
               "p50_ms": rates.percentile(lat, 0.5),
               "p95_ms": rates.percentile(lat, 0.95),
               "p99_ms": rates.percentile(lat, 0.99),
               "last_fifth_p50_ms": rates.percentile(tail, 0.5),
               "late_p95_ms": rates.percentile(
                   [1000.0 * (o.sent - o.due) for o in opens], 0.95),
               "closed_rows_per_s": rates.closed_loop_rows_per_s(ctx),
               "wall_s": ctx.window.wall_s}
        print("sweep " + json.dumps(row), flush=True)


def run(args) -> dict:
    sys.path.insert(0, ROOT)
    manifest = _load_json(ROOT, "BENCHMARK.json")
    cells = {w["name"]: w for w in manifest["workloads"]}
    if args.workload not in cells:
        raise SystemExit(f"no workload {args.workload!r} in BENCHMARK.json: "
                         f"{sorted(cells)}")
    ctx = Ctx()
    ctx.cell = cells[args.workload]
    cfg_entry = next(c for c in manifest["configs"]
                     if c["name"] == ctx.cell["config"])
    ctx.config = _load_json(ROOT, cfg_entry["file"])
    ctx.traffic = _load_json(HERE, "traffic", ctx.cell["traffic"] + ".json")
    chips = int(ctx.cell["chips"])
    if chips != int(ctx.config["chips"]):
        raise SystemExit("the cell and its configuration disagree on chips")
    for name in _statement_names(ctx.traffic):
        ctx.statements[name] = _load_json(HERE, "statements", name + ".json")
    section = "per_layer" if args.trace else "end_to_end"
    wanted = _metrics_of(manifest, section, args.workload)
    readers = {m["name"]: _reader(m["name"]) for m in wanted}

    # -- the device, before anything else touches it -------------------------
    try:
        import tidb_tpu  # noqa: F401  (turns x64 on before jax makes arrays)
    except ImportError as e:
        raise SystemExit(f"the system under test is not in this checkout: "
                         f"{e}") from e
    import jax
    devs = jax.devices()
    ctx.device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
                  "count": len(devs)}
    _log(f"device {ctx.device}")
    if not args.rehearse:
        if devs[0].platform != "tpu":
            raise SystemExit(f"no accelerator: JAX reports platform "
                             f"{devs[0].platform!r}")
        from benchlib import peaks
        ctx.peaks = peaks.for_kind(devs[0].device_kind)
    if len(devs) < chips:
        raise SystemExit(f"{chips} chip(s) asked for, {len(devs)} visible")

    from benchlib import loadgen, tracered
    from benchlib.sut import Sut
    from benchlib.wire import Client

    sut = Sut(chips)
    data = {}
    try:
        ctx.setup["init"] = time.perf_counter() - _T_PROC
        _log(f"server up on port {sut.port}")
        probe = Client("127.0.0.1", sut.port)

        # -- generate and load only what the traffic names -------------------
        ctx.setup["generate"] = ctx.setup["load"] = 0.0
        ctx.setup["rows_loaded"] = 0
        for db, tables in ctx.traffic["databases"].items():
            spec = ctx.config["databases"][db]
            gen = importlib.import_module("generators." + spec["generator"])
            ctx.schema[spec["generator"]] = _load_json(
                HERE, "generators", spec["generator"] + ".schema.json")
            if args.rehearse:
                spec = dict(spec, sf=spec["sf"] * args.rehearse_scale)
            t = time.perf_counter()
            data[db] = gen.generate(spec, args.seed)
            ctx.counts[db] = dict(data[db].counts)
            ctx.setup["generate"] += time.perf_counter() - t
            got = sut.load(db, gen, data[db], tables,
                           int(ctx.config["regions_per_big_table"]))
            ctx.setup["load"] += got["seconds"]
            ctx.setup["rows_loaded"] += got["rows"]
            _log(f"loaded {db}: {got['rows']} rows in "
                 f"{got['seconds']:.1f} s")
        for s in ctx.traffic["streams"]:
            for name in loadgen.stream_statements(s):
                ctx.stmt_db.setdefault(name, s["database"])
        ctx.at_start = sut.snapshot(probe)

        # -- warm this cell's statements and no others, the stats pass -------
        _settle(ctx, sut, lambda phase: _warm(ctx, sut.port, phase))
        ctx.before = ctx.after_setup = sut.snapshot(probe)

        # -- the window ------------------------------------------------------
        tracer, marks = None, {}
        trace_dir = os.path.join(WORK, "trace-" + args.workload)
        if args.trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
            os.makedirs(trace_dir, exist_ok=True)
            tracer = _trace_part(ctx.traffic["trace"], args.seconds,
                                 trace_dir, marks)

        def annotate(name):
            return jax.profiler.TraceAnnotation("inside_" + name)

        ctx.setup_s = time.perf_counter() - _T_PROC
        _log(f"window opens: set-up took {ctx.setup_s:.1f} s")
        if args.sweep:
            _sweep(args, ctx, sut, loadgen)
            raise SystemExit("sweep done; a sweep prints no result")
        ctx.window = loadgen.run_window(
            sut.port, ctx.traffic, ctx.statements, ctx.counts, args.seed,
            args.seconds, annotate)
        if tracer is not None:
            tracer.join()
        ctx.after = sut.snapshot(probe)
        probe.close()
        _log(f"window closed: {len(ctx.window.ops)} statements in "
             f"{ctx.window.wall_s:.1f} s; the first: " + ", ".join(
                 f"{o.statement} {o.sent:.1f}-{o.done:.1f}"
                 for o in ctx.window.ops[:6]))
        peak = max((m["peak_bytes_in_use"] for m in ctx.after["memory"]),
                   default=0)
    finally:
        sut.close()
    ctx.device["memory_peak_bytes"] = int(peak)

    if args.trace:
        xplane = _find_xplane(trace_dir)
        ctx.trace = None
        if xplane and "begin" in marks:
            t0 = ctx.window.t0
            spans = [("inside_" + o.statement, o.sent + t0, o.done + t0)
                     for o in ctx.window.ops]
            ctx.trace = tracered.reduce(tracered.read_planes(xplane),
                                        spans, marks["begin"])
        if os.environ.get("BENCH_KEEP_TRACE") != "1":
            shutil.rmtree(trace_dir, ignore_errors=True)
        if ctx.trace:
            ctx.device["busy_s"] = ctx.trace["busy_s"]
            ctx.device["window_s"] = ctx.trace["window_s"]

    values = {}
    for m in wanted:
        v = readers[m["name"]](ctx)
        if v is not None:
            values[m["name"]] = {"value": v, "unit": m["unit"]}

    # -- every answer of the window against the plain reference --------------
    from benchlib.compare import compare
    correct, checks = compare(ctx.window.ops, data, _log)
    never = checks["answers_never_came"]["value"]

    result = {"correct": correct, "attempted": len(ctx.window.ops),
              "failed": never,
              "metrics": {} if args.rehearse else values,
              "device": ctx.device}
    if args.rehearse:
        result["rehearsal_metrics"] = values
    if args.trace and ctx.trace:
        result["breakdown"] = {
            "device_ops": _top_ops(ctx.trace["device_ops"]),
            "idle_gaps": [[k, v] for k, v in ctx.trace["idle_gaps"][:10]]}
    result["setup_phases_s"] = dict(ctx.setup)
    result["window_wall_s"] = ctx.window.wall_s
    result["compared"] = checks
    return result


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--rehearse", action="store_true",
                   help="accept a platform that is not a TPU; readings go "
                        "under rehearsal_metrics")
    p.add_argument("--sweep", default="",
                   help="comma-separated open-loop rates: after set-up, "
                        "one window of --seconds at each; prints a row "
                        "per rate and no result")
    p.add_argument("--rehearse-scale", type=float, default=1.0,
                   help="with --rehearse: multiply every database's sf")
    args = p.parse_args(argv)
    try:
        result = run(args)
    except SystemExit as e:
        print(f"benchmark: {e}", file=sys.stderr, flush=True)
        return 2
    except Exception:
        traceback.print_exc()
        print("benchmark: the run failed; no result", file=sys.stderr,
              flush=True)
        return 1
    sys.stdout.flush()
    for k, v in result["compared"].items():
        print(f"compared {k} = {v['value']} (limit {v['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    # the server's worker pools are joined by close(); nothing is left to
    # wait for, and a lingering non-daemon thread must not hold the exit
    os._exit(code)
