#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that tpu-db still starts on the chip.

One process (the only one that touches JAX) drives the main path once:
MySQL wire -> session -> planner -> coprocessor -> device plane, at
TPC-H SF1 (`benchmarks/tpch.ScaledTpch`, the smallest scale the TPC-H
specification defines), at default sysvars, through the entry points a
user would call: the server is brought up by `tidb_tpu.__main__.start`
(what `python -m tidb_tpu` runs) and every statement goes over the wire.

Phases, in order; any exception or failed check ends the run non-zero,
naming the phase, and no result line is printed:

  device   jax.devices()[0].platform == --expect-platform, >= --chips
  server   storage + mesh + Server + StatusServer via __main__.start
  load     ScaledTpch(sf, seed) bulk-loaded; rows and rows/s printed
  q1 x3    cold, warm, warm — each equal to a numpy truth, exact
  resident HBM bytes in use >= Q1's resident columns, HBM-cache hits
           rose, the third Q1 filled no HBM block and compiled nothing
  q1 host  SET tidb_tpu_device=0 reference == device rows
  q3, q5   once each, equal to the numpy truth, exact — on one chip,
           on a second, smaller database where --sf exceeds the join
           cap (the one cut of scale; printed under `reduced` with the
           measured us/row)
  point    primary-key select == the generator's row
  write    INSERT acknowledged, read back by key, seen by COUNT(*) on
           the device path
  proof    kernel_profile rows keyed batch-<chips>-<platform> with
           dispatches, statement_profile attributes Q1 to the device,
           zero fault/quarantine/unsupported fallbacks, healthy
           device, datasheet peak

The repo has no host->device byte counter; region columns reach HBM
only through `DeviceCache.fill` (the one audited upload site), so "the
third Q1 moved no column bytes" is read as: no HBM-cache miss, no fill,
resident bytes unchanged. Operator times are inclusive of children
(runtime_stats.OpStats), summed by operator name: the line to read for
the streamed raw scan is TableReader against the statement's wall.

Times printed here are observations of one run, not metrics. The last
stdout line is `{"ok": true, "device": {...}}` as JAX reports the
device; the details go to `chiprun_out/<--out>`.
"""

from __future__ import annotations

import argparse
import datetime
import json
import os
import sys
import time
import traceback
import urllib.request

HERE = os.path.dirname(os.path.abspath(__file__))
_EPOCH = datetime.date(1992, 1, 1)
_PHASE = "start"

# the one row the write phase inserts, after its l_id: four numeric
# columns, then the quoted flag, status and three dates
_NEW_ROW = ("5", "3", "7.00", "1234.56", "0.05", "0.02", "N", "O",
            "1996-01-02", "1996-02-01", "1996-01-20")
_NEW_ROW_SQL = ", ".join(_NEW_ROW[:6]) + ", " + ", ".join(
    f"'{v}'" for v in _NEW_ROW[6:])
# The one cut of scale, forced by the 1200 s limit on the run, on one
# chip only: there a raw (non-aggregating) scan is streamed, re-read from
# KV and re-decoded in Python on every execution — 42 us per lineitem row
# on the chip's host, 25 s of Q3's 31 s at sf 0.1 with compiles cached,
# and the join programs' first compiles add 116 s (Q3) + 38 s (Q5) (chip
# run, PR 21). At SF1 that is ~400 s + ~290 s for the two join queries on
# top of ~100 s of load and a ~270 s cold Q1: it does not fit. Load, Q1,
# the point select and the write stay at --sf. Above one chip the mesh
# route scans once into the chunk cache, so nothing is cut.
_JOIN_SF_CAP = 0.2
# wire client socket timeout: under the run's limit, over the ~270 s cold Q1
_STATEMENT_TIMEOUT_S = 1000.0
# faults the smoke refuses; capacity/collision/encoding are designed
# retries and are printed only
_HARD_FALLBACKS = ("fault", "quarantine", "unsupported")
_HOST_MODES = ("", "host")


def phase(name: str) -> None:
    global _PHASE
    _PHASE = name
    print(f"[smoke +{time.perf_counter() - _T0:7.1f}s] {name}", flush=True)


def check(cond, msg: str) -> None:
    if not cond:
        raise AssertionError(msg)


# -- numpy truths over the generator's arrays -------------------------------
# Every key in ScaledTpch is a dense arange, so the joins are gathers.

def _days(y: int, m: int, d: int) -> int:
    return (datetime.date(y, m, d) - _EPOCH).days


def _date(days) -> str:
    return (_EPOCH + datetime.timedelta(days=int(days))).isoformat()


def _dec(v: int, scale: int) -> str:
    """Scaled integer -> the wire's DECIMAL text."""
    sign, v = ("-", -v) if v < 0 else ("", v)
    q, r = divmod(v, 10 ** scale)
    return f"{sign}{q}.{r:0{scale}d}" if scale else f"{sign}{q}"


def _avg(total: int, n: int) -> str:
    """AVG over DECIMAL(15,2): scale 6, rounded half up."""
    return _dec((total * 10 ** 4 * 2 + n) // (2 * n), 6)


def truth_q1(d, tpch) -> list[tuple]:
    import numpy as np
    cutoff = (datetime.date(1998, 12, 1) - datetime.timedelta(days=90)
              - _EPOCH).days
    live = d.l_shipdate <= cutoff
    px = d.l_extendedprice.astype(np.int64)
    disc_px = px * (100 - d.l_discount)
    charge = disc_px * (100 + d.l_tax)
    rows = []
    for rf, flag in enumerate(tpch.FLAGS):
        for ls, status in enumerate(tpch.STATUSES):
            m = live & (d.l_returnflag == rf) & (d.l_linestatus == ls)
            n = int(m.sum())
            if not n:
                continue
            qty = int(d.l_quantity[m].sum()) * 100
            base = int(px[m].sum())
            rows.append((
                flag, status, _dec(qty, 2), _dec(base, 2),
                _dec(int(disc_px[m].sum()), 4),
                _dec(int(charge[m].sum()), 6),
                _avg(qty, n), _avg(base, n),
                _avg(int(d.l_discount[m].sum()), n), str(n)))
    return rows


def truth_q3(d, tpch) -> list[tuple]:
    import numpy as np
    cut = _days(1995, 3, 15)
    bldg = d.c_mktsegment == tpch.SEGMENTS.index("BUILDING")
    order_ok = (d.o_orderdate < cut) & bldg[d.o_custkey]
    m = (d.l_shipdate > cut) & order_ok[d.l_orderkey]
    rev = np.zeros(d.counts["orders"], dtype=np.int64)
    np.add.at(rev, d.l_orderkey[m],
              d.l_extendedprice[m].astype(np.int64)
              * (100 - d.l_discount[m]))
    keys = np.flatnonzero(rev)
    top = sorted(keys, key=lambda k: (-rev[k], d.o_orderdate[k]))[:10]
    return [(str(k), _dec(int(rev[k]), 4), _date(d.o_orderdate[k]),
             str(d.o_shippriority[k])) for k in top]


def truth_q5(d, tpch) -> list[tuple]:
    import numpy as np
    lo, hi = _days(1994, 1, 1), _days(1995, 1, 1)
    odate = d.o_orderdate[d.l_orderkey]
    snat = d.s_nationkey[d.l_suppkey]
    cnat = d.c_nationkey[d.o_custkey[d.l_orderkey]]
    m = (odate >= lo) & (odate < hi) & (cnat == snat)
    disc_px = d.l_extendedprice.astype(np.int64) * (100 - d.l_discount)
    rows = []
    for nk, (name, region) in enumerate(tpch.NATIONS):
        if tpch.REGIONS[region] != "ASIA":
            continue
        rev = int(disc_px[m & (snat == nk)].sum())
        if rev:
            rows.append((rev, name))
    rows.sort(reverse=True)
    return [(name, _dec(rev, 4)) for rev, name in rows]


def lineitem_row(d, tpch, i: int) -> tuple:
    return (str(i), str(d.l_orderkey[i]), str(d.l_suppkey[i]),
            _dec(int(d.l_quantity[i]) * 100, 2),
            _dec(int(d.l_extendedprice[i]), 2),
            _dec(int(d.l_discount[i]), 2), _dec(int(d.l_tax[i]), 2),
            tpch.FLAGS[d.l_returnflag[i]],
            tpch.STATUSES[d.l_linestatus[i]],
            _date(d.l_shipdate[i]), _date(d.l_commitdate[i]),
            _date(d.l_receiptdate[i]))


# -- observation: counters around one statement -----------------------------

class Probe:
    """Reads the server's counters the way an operator would (GET
    /status on the StatusServer, information_schema over the wire) and
    diffs them around a statement."""

    def __init__(self, running, client):
        self.running = running
        self.client = client

    def status(self) -> dict:
        url = f"http://127.0.0.1:{self.running.status.port}/status"
        with urllib.request.urlopen(url, timeout=60) as r:
            return json.load(r)

    def kernels(self) -> dict:
        _cols, rows = self.client.query(
            "SELECT family, fingerprint, mesh, compiles, dispatches, "
            "pcache_hits, pcache_misses, fallbacks "
            "FROM information_schema.kernel_profile")
        return {r[:3]: tuple(int(x) for x in r[3:]) for r in rows}

    def snap(self) -> dict:
        st = self.status()
        cc = self.running.storage.chunk_cache
        return {"metrics": st["metrics"],
                "pcache": st["compile_cache"],
                "kernels": self.kernels(),
                "chunk_cache": {"hits": cc.hits, "misses": cc.misses}}

    def timed(self, label: str, sql: str):
        """Run `sql` over the wire -> (rows, observation dict)."""
        s0 = self.snap()
        t0 = time.perf_counter()
        res = self.client.query(sql)
        wall = time.perf_counter() - t0
        s1 = self.snap()
        m0, m1 = s0["metrics"], s1["metrics"]

        def delta(name: str) -> int:
            return int(m1.get(name, 0) - m0.get(name, 0))

        def by_op(prefix: str) -> dict:
            out = {}
            for k, v in m1.items():
                if k.startswith(prefix) and v > m0.get(k, 0):
                    out[k[len(prefix):-2]] = round(v - m0.get(k, 0), 4)
            return dict(sorted(out.items(), key=lambda kv: -kv[1]))

        families: dict = {}
        compiles = 0
        for key, (comp, disp, *_rest) in s1["kernels"].items():
            comp0, disp0 = s0["kernels"].get(key, (0, 0))[:2]
            compiles += comp - comp0
            if disp > disp0:
                families[key[0]] = families.get(key[0], 0) + disp - disp0
        obs = {
            "wall_s": round(wall, 4),
            # by kernel_profile family (`join` rows since PR 24; the
            # executors' superchunk counter shows a device HashJoin too)
            "device_dispatches": families,
            "device_superchunks": by_op(
                'tidb_tpu_superchunks_total{op="'),
            "kernel_compiles": compiles,
            "ops_inclusive_s": by_op(
                'tidb_tpu_op_duration_seconds_sum{op="'),
            "hbm_cache": {"hits": delta("tidb_tpu_hbm_cache_hits_total"),
                          "misses": delta(
                              "tidb_tpu_hbm_cache_misses_total"),
                          "evictions": delta(
                              "tidb_tpu_hbm_cache_evictions_total")},
            "chunk_cache": {k: s1["chunk_cache"][k] - s0["chunk_cache"][k]
                            for k in ("hits", "misses")},
            "persistent_cache": {k: s1["pcache"][k] - s0["pcache"][k]
                                 for k in ("hits", "misses")},
        }
        print(f"[smoke] {label}: " + json.dumps(obs), flush=True)
        rows = res[1] if isinstance(res, tuple) else res
        return rows, obs


def fallbacks_by_reason(metrics_snap: dict) -> dict:
    out: dict = {}
    prefix = "tidb_tpu_device_fallback_total{"
    for k, v in metrics_snap.items():
        if k.startswith(prefix) and 'reason="' in k:
            reason = k.split('reason="', 1)[1].split('"', 1)[0]
            out[reason] = out.get(reason, 0) + int(v)
    return out


# -- phases -----------------------------------------------------------------

def device_phase(args) -> dict:
    import importlib.metadata as md

    import jax
    import jaxlib

    # importing the package turns x64 on and enables the compile cache
    from tidb_tpu import native
    from tidb_tpu.util import compile_cache

    devs = jax.devices()
    cache = compile_cache.stats()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    try:
        libtpu = md.version("libtpu")
    except md.PackageNotFoundError:
        libtpu = "not installed"
    print("[smoke] " + json.dumps({
        "device": dev, "jax": jax.__version__,
        "jaxlib": jaxlib.__version__, "libtpu": libtpu,
        "compile_cache_dir": cache["dir"],
        "compile_cache_entries": cache["entries"],
        "row_decoder": native.decoder_kind()}), flush=True)
    check(dev["platform"] == args.expect_platform,
          f"no accelerator: jax.devices()[0].platform is "
          f"{dev['platform']!r}, expected {args.expect_platform!r}")
    check(dev["count"] >= args.chips,
          f"{args.chips} chip(s) asked for, {dev['count']} visible")
    return dev


def run(args) -> tuple[dict, dict]:
    sys.path.insert(0, HERE)
    phase("device")
    dev = device_phase(args)
    on_chip = dev["platform"] != "cpu"

    import jax

    from tests.mysql_client import MiniClient
    from tidb_tpu import perfschema, profiler
    from tidb_tpu.__main__ import start
    from tidb_tpu.benchmarks import tpch
    from tidb_tpu.session import Session
    from tidb_tpu.util import compile_cache

    join_sf = min(args.sf, _JOIN_SF_CAP) if args.chips == 1 else args.sf
    summary: dict = {"device": dev, "sf": args.sf, "join_sf": join_sf,
                     "seed": args.seed, "chips": args.chips,
                     "reduced": [], "statements": {}}
    if args.sf < 1:
        summary["reduced"].append({
            "what": "scale", "from_sf": 1.0, "to_sf": args.sf,
            "why": "--sf under the default: SF1 is the smallest scale "
                   "TPC-H defines"})

    phase("server")
    running = start(port=0, status_port=0, mesh=args.chips)
    try:
        phase("load")
        t0 = time.perf_counter()
        data = tpch.ScaledTpch(sf=args.sf, seed=args.seed)
        gen_s = time.perf_counter() - t0
        session = Session(running.storage)
        session.execute("CREATE DATABASE tpch")
        session.execute("USE tpch")
        t0 = time.perf_counter()
        rows_loaded = tpch.load(session, running.storage, data)
        load_s = time.perf_counter() - t0
        summary["load"] = {"rows": rows_loaded, "generate_s": round(gen_s, 2),
                           "load_s": round(load_s, 2),
                           "rows_per_s": round(rows_loaded / load_s, 1)}
        jdata, jdb = data, "tpch"
        if join_sf != args.sf:
            # the one cut the time limit forces: Q3/Q5 run on a
            # second, smaller database; load and Q1 stay at --sf
            jdata, jdb = tpch.ScaledTpch(sf=join_sf,
                                         seed=args.seed), "tpch_join"
            session.execute(f"CREATE DATABASE {jdb}")
            session.execute(f"USE {jdb}")
            t0 = time.perf_counter()
            jrows = tpch.load(session, running.storage, jdata)
            summary["load"]["join_db"] = {
                "rows": jrows,
                "load_s": round(time.perf_counter() - t0, 2)}
        print("[smoke] load: " + json.dumps(summary["load"]), flush=True)
        session.close()

        client = MiniClient("127.0.0.1", running.server.port, db="tpch")
        client.sock.settimeout(_STATEMENT_TIMEOUT_S)
        probe = Probe(running, client)
        stm = summary["statements"]

        want_q1 = truth_q1(data, tpch)
        for label in ("q1_cold", "q1_warm", "q1_third"):
            phase(label)
            # read before each run; the last reading precedes the third
            resident0 = running.storage.device_cache.resident_bytes()
            rows, stm[label] = probe.timed(label, tpch.Q1)
            check(rows == want_q1,
                  f"{label} != numpy truth:\n{rows}\n{want_q1}")

        phase("resident")
        third = stm["q1_third"]
        resident = running.storage.device_cache.resident_bytes()
        q1_bytes = 7 * 8 * data.counts["lineitem"]
        mem = [d.memory_stats() for d in jax.devices()[:args.chips]]
        in_use = [m["bytes_in_use"] if m else None for m in mem]
        summary["resident"] = {
            "hbm_cache_resident_bytes": resident,
            "q1_columns_min_bytes": q1_bytes,
            "device_bytes_in_use": in_use,
            "block_replicas": args.chips,
            "third_q1_hbm_fill_bytes": resident - resident0}
        print("[smoke] resident: " + json.dumps(summary["resident"]),
              flush=True)
        check(third["kernel_compiles"] == 0
              and third["persistent_cache"] == {"hits": 0, "misses": 0},
              f"third Q1 compiled: {third}")
        check(sum(third["device_dispatches"].values()) > 0,
              f"third Q1 made no device dispatch: {third}")
        if args.chips == 1:
            # the copTask route serves warm scans from DeviceCache
            # blocks; the mesh route shards superchunks instead and
            # keeps no block there, so these are one-chip checks
            hits = sum(stm[k]["hbm_cache"]["hits"]
                       for k in ("q1_warm", "q1_third"))
            check(hits > 0,
                  "HBM-cache hit counter did not rise on warm Q1")
            check(third["hbm_cache"]["misses"] == 0
                  and third["hbm_cache"]["evictions"] == 0
                  and resident == resident0,
                  f"third Q1 moved column bytes host->device: {third}")
            if on_chip:
                check(in_use[0] >= q1_bytes,
                      f"HBM bytes in use {in_use} < Q1's resident "
                      f"columns ({q1_bytes})")
        elif on_chip:
            # code that never saw a second device may put everything
            # on the first
            check(min(in_use) > 0 and max(in_use) <= 2 * min(in_use),
                  f"HBM use uneven across chips: {in_use}")

        phase("q1_host")
        client.query("SET tidb_tpu_device = 0")
        rows, stm["q1_host"] = probe.timed("q1_host", tpch.Q1)
        client.query("SET tidb_tpu_device = 1")
        check(rows == want_q1, f"host Q1 != device Q1:\n{rows}\n{want_q1}")
        if args.chips == 1:
            check(not stm["q1_host"]["device_dispatches"],
                  f"host-mode Q1 dispatched: {stm['q1_host']}")
        else:
            # route_mesh decides from the mesh alone: above one chip
            # the sysvar does not leave the mesh, so this run is not a
            # host reference — the numpy truth is the only one
            summary["host_reference"] = (
                "tidb_tpu_device=0 ignored by the mesh route"
                if stm["q1_host"]["device_dispatches"] else "host")

        if args.chips > 1:
            phase("explain_mesh")
            client.use(jdb)
            for q in ("q1", "q3", "q5"):
                _c, plan = client.query("EXPLAIN " + tpch.QUERIES[q])
                text = "\n".join(r[0] for r in plan)
                check("Mesh" in text, f"{q} not mesh-routed:\n{text}")

        client.use(jdb)
        for q, truth in (("q3", truth_q3), ("q5", truth_q5)):
            phase(q)
            want = truth(jdata, tpch)
            rows, stm[q] = probe.timed(q, tpch.QUERIES[q])
            check(rows == want, f"{q} != numpy truth:\n{rows}\n{want}")
            scan_s = stm[q]["ops_inclusive_s"].get("TableReader", 0.0)
            stm[q]["table_reader_us_per_lineitem_row"] = round(
                scan_s * 1e6 / jdata.counts["lineitem"], 2)
        client.use("tpch")
        summary["host_only_statements"] = [
            q for q in ("q3", "q5")
            if not stm[q]["device_dispatches"]
            and not stm[q]["device_superchunks"]]
        if join_sf != args.sf:
            summary["reduced"].append({
                "what": "Q3/Q5 scale", "from_sf": args.sf,
                "to_sf": join_sf,
                "why": "the one-chip route streams raw scans: every "
                       "lineitem row is re-read and re-decoded on the "
                       "host per execution, and the join programs' "
                       "first compiles take minutes; at --sf the two "
                       "join queries do not fit the run's 1200 s",
                "measured_table_reader_us_per_lineitem_row": {
                    q: stm[q]["table_reader_us_per_lineitem_row"]
                    for q in ("q3", "q5")}})

        phase("point")
        nl = data.counts["lineitem"]
        k = (nl * 2) // 3
        rows, stm["point"] = probe.timed(
            "point", f"SELECT * FROM lineitem WHERE l_id = {k}")
        check(rows == [lineitem_row(data, tpch, k)],
              f"point select: {rows}")

        phase("write")
        rows, stm["count_before"] = probe.timed(
            "count_before", "SELECT COUNT(*) FROM lineitem")
        check(rows == [(str(nl),)], f"COUNT(*) before write: {rows}")
        new = (str(nl),) + _NEW_ROW
        acked = client.query(
            f"INSERT INTO lineitem VALUES ({nl}, {_NEW_ROW_SQL})")
        check(acked == 1, f"INSERT not acknowledged: {acked!r}")
        rows, stm["read_back"] = probe.timed(
            "read_back", f"SELECT * FROM lineitem WHERE l_id = {nl}")
        check(rows == [new], f"write not read back: {rows}")
        rows, stm["count_after"] = probe.timed(
            "count_after", "SELECT COUNT(*) FROM lineitem")
        check(rows == [(str(nl + 1),)],
              f"COUNT(*) does not see the acknowledged write: {rows}")
        check(sum(stm["count_after"]["device_dispatches"].values()) > 0,
              f"COUNT(*) after the write ran off the device: "
              f"{stm['count_after']}")

        phase("proof")
        mesh_key = f"batch-{args.chips}-{dev['platform']}"
        kernels = probe.kernels()
        on_mesh = {k: v for k, v in kernels.items()
                   if k[2] == mesh_key and v[1] > 0}
        check(on_mesh, f"no kernel_profile row keyed {mesh_key} with "
                       f"dispatches: {kernels}")
        summary["kernel_profile"] = [
            {"family": k[0], "fingerprint": k[1], "mesh": k[2],
             "compiles": v[0], "dispatches": v[1], "pcache_hits": v[2],
             "pcache_misses": v[3], "fallbacks": v[4]}
            for k, v in sorted(kernels.items())]
        q1_digest = perfschema.sql_digest(tpch.Q1)[0]
        _c, sp = client.query(
            "SELECT op, mode, runs FROM information_schema."
            f"statement_profile WHERE digest = '{q1_digest}'")
        summary["q1_statement_profile"] = sp
        # the mesh executors note no mode, so above one chip the memo
        # holds no row for Q1; kernel_profile attributed it above
        check(args.chips > 1
              or any(mode not in _HOST_MODES and int(runs) >= 3
                     for _op, mode, runs in sp),
              f"statement_profile does not attribute Q1 to the device: "
              f"{sp}")
        st = probe.status()
        fb = fallbacks_by_reason(st["metrics"])
        health = st["serving"]["device_health"]
        summary["fallbacks_by_reason"] = fb
        summary["device_health"] = health
        check(not any(fb.get(r, 0) for r in _HARD_FALLBACKS),
              f"device work fell back to the host: {fb}")
        check(health["faults"] == 0 and health["quarantines"] == 0
              and not health["quarantined"],
              f"device health: {health}")
        peak = profiler.platform_peak_gbps()
        summary["platform_peak_gbps"] = peak
        if on_chip:
            check(peak[1] == f"datasheet({dev['kind']})",
                  f"roofline peak is not the datasheet's: {peak}")
        summary["persistent_cache"] = compile_cache.stats()
        print("[smoke] proof: " + json.dumps({
            k: summary[k] for k in (
                "fallbacks_by_reason", "device_health",
                "platform_peak_gbps", "persistent_cache",
                "host_only_statements", "reduced")}), flush=True)
        client.close()
    finally:
        running.close()
    return dev, summary


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--sf", type=float, default=1.0,
                   help="TPC-H scale factor (Q3/Q5: up to the one-chip "
                        "join cap)")
    p.add_argument("--seed", type=int, default=42)
    p.add_argument("--chips", type=int, default=1)
    p.add_argument("--expect-platform", default="tpu")
    p.add_argument("--out", default="chip_smoke.json",
                   help="file name under chiprun_out/ for the details")
    args = p.parse_args(argv)
    try:
        dev, summary = run(args)
    except Exception:
        traceback.print_exc()
        print(f"chip_smoke: FAILED in phase {_PHASE!r}", file=sys.stderr,
              flush=True)
        return 1
    summary["wall_s"] = round(time.perf_counter() - _T0, 1)
    out_dir = os.path.join(HERE, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, args.out), "w") as f:
        json.dump(summary, f, indent=1, sort_keys=True)
    print("[smoke] summary: " + json.dumps(summary), flush=True)
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


_T0 = time.perf_counter()

if __name__ == "__main__":
    sys.exit(main())
