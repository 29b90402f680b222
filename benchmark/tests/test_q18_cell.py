"""`tpch1.q18_warm` (PR 33): its control comes out not correct, a
rehearsal run is `correct` and shows the cell's readers, a fault planted
under the timed path turns `correct` false, Q18's needed bytes and rows
against hand counts, the three new readers on written counter snapshots,
and the cell's entries in the manifest.

The rehearsal that shows the readers runs at six tenths of the cell's
scale: a pruned `lineitem` region (two lanes, 18 bytes a row) is then
270,000 rows, 4.86 MB, over the 4 MiB frame cap, so it streams as the
chip's 450,000-row regions do. At a tenth (45,000 rows) the program
serves it from the chunk cache and the window decodes no row."""

import json
import os
import subprocess
import sys

import pytest

import run
from benchlib import needed, spans
from test_span_readers import MANIFEST, _ctx

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
CELL = "tpch1.q18_warm"
SELF = spans.SELF
FRAME_BYTES = 4 << 20
LINEITEM_ROWS = 1_799_995       # the cell's sf 0.3 (test_q18_needs_by_hand)
PRUNED_ROW_BYTES = 18           # l_orderkey, l_quantity and a null byte each
READERS_SCALE = "0.6"
GROUPS = "tidb_tpu_agg_final_groups_total"

# per-layer entries of the cell's own (`run._reader` serves a suffixed
# name from the unsuffixed reader), and the accepted lists it joined
NEW = ["root_join_ms_per_stmt", "subquery_ms_per_stmt",
       "final_agg_us_per_group"]
SUFFIXED = {
    "scan_us_per_row.analytic", "unspanned_ms_per_stmt.analytic",
    "kv_scan_us_per_row.analytic", "decode_us_per_row.analytic",
    "cop_exec_us_per_row.analytic", "stream_frames_per_stmt.analytic",
    "decode_native_pct.analytic",
    "slot_wait_ms_per_stmt.q18", "dispatch_ms_per_stmt.q18",
    "finalize_ms_per_stmt.q18", "agg_dense_dispatch_pct.q18",
    "wire_write_ms_per_stmt.q18", "wire_sends_per_stmt.q18"}
JOINED = ["chunk_cache_hit_pct", "hbm_fill_bytes_per_stmt",
          "device_busy_ms_per_stmt", "hbm_roofline_pct",
          "device_idle_pct.analytic", "peak_hbm_bytes", "compile_s",
          "load_rows_per_s", "compiles_in_window"]


# -- the control ------------------------------------------------------------

@pytest.mark.parametrize("seed", [1, 2147483659, 3000000019])
def test_control_is_not_correct(seed):
    """At a fifth of the cell's scale every seed still has orders over
    300 units (3 to 5), each with a price float32 cannot hold."""
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "control.py"), "--workload",
         CELL, "--seed", str(seed), "--scale", "0.2"],
        capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stdout + out.stderr[-2000:]
    lines = out.stdout.strip().splitlines()
    assert json.loads(lines[-1])["control_correct"] is False
    by = {r["control"]: r["correct"] for r in
          (json.loads(ln[len("control "):]) for ln in lines[:-1])}
    # float64 holds the cents and the sums exactly: float32 is this
    # cell's control
    assert by == {"float64": True, "float32": False}


# -- a rehearsal run, whole and broken underneath ---------------------------

def _rehearse(fault, trace, scale="0.1", seed="3000000019", timeout=900):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(HERE, "fault_driver.py"), fault,
         "--workload", CELL, "--seed", seed, "--seconds", "3",
         "--trace", str(trace), "--rehearse", "--rehearse-scale", scale],
        capture_output=True, text=True, timeout=timeout, env=env, cwd=ROOT)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_a_pruned_region_is_over_one_frame_at_the_readers_scale():
    region = LINEITEM_ROWS * float(READERS_SCALE) / 4 * PRUNED_ROW_BYTES
    assert region > FRAME_BYTES > LINEITEM_ROWS * 0.1 / 4 * PRUNED_ROW_BYTES


def test_rehearsal_is_correct_and_shows_the_cells_readers():
    result = _rehearse("none", 1, READERS_SCALE, "2147489011", 1500)
    assert result["correct"] is True, result["compared"]
    assert result["compared"]["answers_compared"]["value"] >= 2
    assert result["metrics"] == {}            # a rehearsal names no metric
    got = result["rehearsal_metrics"]
    # the CPU's trace has no device plane and its memory_stats() no
    # peak: the device_trace readers and peak_hbm_bytes have nothing to
    # read here, the counter readers do
    counters = [m["name"] for m in MANIFEST["per_layer"]
                if CELL in m.get("workloads", ())
                and m["source"] != "device_trace"
                and m["name"] != "peak_hbm_bytes"]
    assert set(NEW) | SUFFIXED <= set(counters) <= set(got), sorted(got)
    for n in NEW:
        assert got[n]["value"] > 0, n
    # every group-by block took the scatters
    assert got["agg_dense_dispatch_pct.q18"]["value"] == 0.0
    # lineitem streams through native/codec.cc, at two columns of
    # sixteen; orders and customer come from residency, a frame a region
    assert got["decode_native_pct.analytic"]["value"] == 100.0
    assert got["decode_us_per_row.analytic"]["value"] > 0
    assert got["stream_frames_per_stmt.analytic"]["value"] > 12
    assert got["scan_cols_pct.analytic"]["value"] == 100.0 * 10 / 49
    assert got["compiles_in_window"]["value"] == 0


@pytest.mark.parametrize("fault", ["answer_altered", "half_left_out"])
def test_not_correct_under_fault(fault):
    result = _rehearse(fault, 0)
    assert result["correct"] is False, result["compared"]
    bad = result["compared"]
    assert bad["answers_wrong"]["value"] + \
        bad["answers_never_came"]["value"] > 0


# -- needed bytes and rows against hand counts ------------------------------

def test_q18_needs_by_hand():
    with open(os.path.join(BENCH, "statements", "q18.json")) as f:
        q18 = json.load(f)
    with open(os.path.join(BENCH, "generators",
                           "tpch_dbgen.schema.json")) as f:
        schema = json.load(f)
    # two BIGINT/DECIMAL at 8; BIGINT, BIGINT, DATE 4, DECIMAL 8; BIGINT
    # and VARCHAR(25)
    assert needed.bytes_per_row(q18, schema) == {
        "lineitem": 16, "orders": 28, "customer": 33}
    # the generator's counts at the cell's sf 0.3
    counts = {"lineitem": 1_799_995, "orders": 450_000, "customer": 45_000,
              "part": 60_000, "supplier": 3_000}
    assert needed.statement_bytes(q18, schema, counts) == (
        16 * 1_799_995 + 28 * 450_000 + 33 * 45_000) == 42_884_920
    # each table once, whatever plan reads lineitem twice
    assert needed.statement_rows(q18, counts) == 2_294_995


def test_q18_truth_on_a_hand_made_database():
    """Three orders, one over 300 units, against the reference."""
    import types

    import numpy as np

    from statements import q18
    d = types.SimpleNamespace(
        counts={"orders": 3},
        o_orderkey=np.array([1, 2, 33]), o_custkey=np.array([7, 5, 7]),
        o_orderdate=np.array([10, 20, 30]),
        o_totalprice=np.array([100_00, 45_678_913, 20_000_001]),
        l_order=np.array([0, 1, 1, 1, 1, 1, 1, 1, 2, 2]),
        l_quantity=np.array([50, 50, 50, 50, 50, 50, 50, 1, 50, 50]))
    assert q18.truth(d) == [("Customer#000000005", "5", "2", "1992-01-21",
                             "456789.13", "301.00")]
    assert q18.control(d, None, np.float64) == q18.truth(d)
    assert q18.control(d, None, np.float32) != q18.truth(d)


# -- the three new readers on written snapshots -----------------------------

def _q18_ctx(before, after):
    return _ctx(before, after, statements=("q18", "q18", "q18"))


def test_root_join_is_self_time_of_exec_join():
    ctx = _q18_ctx({SELF % "exec.join": 1.0, SELF % "exec.agg": 5.0},
                   {SELF % "exec.join": 7.0, SELF % "exec.agg": 9.0})
    assert run._reader("root_join_ms_per_stmt")(ctx) == \
        pytest.approx(2000.0)
    assert run._reader("root_join_ms_per_stmt")(
        _q18_ctx({}, {SELF % "exec.agg": 9.0})) is None


def test_subquery_is_apply_plus_its_inner():
    before = {SELF % "exec.apply": 0.5, SELF % "exec.apply.inner": 2.0}
    after = {SELF % "exec.apply": 0.8, SELF % "exec.apply.inner": 8.0}
    assert run._reader("subquery_ms_per_stmt")(_q18_ctx(before, after)) == \
        pytest.approx(1000.0 * (0.3 + 6.0) / 3)
    # a statement with an inner that never ended a span of its own
    assert run._reader("subquery_ms_per_stmt")(
        _q18_ctx({}, {SELF % "exec.apply": 0.9})) == pytest.approx(300.0)
    assert run._reader("subquery_ms_per_stmt")(_q18_ctx({}, {})) is None


def test_final_agg_is_self_time_over_groups():
    before = {SELF % "exec.agg": 1.0, GROUPS: 1_000}
    after = {SELF % "exec.agg": 10.0, GROUPS: 901_000}
    assert run._reader("final_agg_us_per_group")(
        _q18_ctx(before, after)) == pytest.approx(10.0)


@pytest.mark.parametrize("before,after", [
    ({}, {}),                                   # the parent: neither
    ({}, {SELF % "exec.agg": 3.0}),             # spans, no counter
    ({}, {GROUPS: 5}),                          # counter, no span
    ({SELF % "exec.agg": 1.0, GROUPS: 7},       # none in the window
     {SELF % "exec.agg": 1.5, GROUPS: 7}),
])
def test_final_agg_nothing_to_read_is_none(before, after):
    assert run._reader("final_agg_us_per_group")(
        _q18_ctx(before, after)) is None


def test_suffixed_names_are_served_by_the_readers_there():
    before = {SELF % "statement": 1.0, SELF % "execute": 10.0,
              'tidb_tpu_op_duration_seconds_sum{op="TableReader"}': 1.0}
    after = {SELF % "statement": 1.3, SELF % "execute": 40.0,
             'tidb_tpu_op_duration_seconds_sum{op="TableReader"}': 1.6}
    ctx = _q18_ctx(before, after)
    assert run._reader("unspanned_ms_per_stmt.analytic")(ctx) == \
        run._reader("unspanned_ms_per_stmt")(ctx) == \
        pytest.approx(1000.0 * 30.3 / 3)
    assert run._reader("scan_us_per_row.analytic")(ctx) == \
        run._reader("scan_us_per_row")(ctx) == \
        pytest.approx(1e6 * 0.6 / 3000)
    assert run._reader("unspanned_ms_per_stmt.analytic")(
        _q18_ctx({}, {})) is None


# -- the manifest -----------------------------------------------------------

def test_manifest_entries():
    """Membership, never equality or position: the next cell appends to
    the same lists."""
    cell = next(w for w in MANIFEST["workloads"] if w["name"] == CELL)
    assert cell == {"name": CELL, "config": "tpch_orders_1chip",
                    "traffic": "q18_warm", "chips": 1, "why": cell["why"]}
    cfg = next(c for c in MANIFEST["configs"]
               if c["name"] == "tpch_orders_1chip")
    assert cfg["reduced"] == ["tpch.sf"]
    by_name = {m["name"]: m
               for m in MANIFEST["end_to_end"] + MANIFEST["per_layer"]}
    assert CELL in by_name["analytic_rows_per_s"]["workloads"]
    assert "workloads" not in by_name["setup_s"]
    for name in JOINED:
        assert CELL in by_name[name]["workloads"], name
    for name in NEW + sorted(SUFFIXED):
        m = by_name[name]
        assert m["workloads"] == [CELL] and \
            m["moves"] == "analytic_rows_per_s" and \
            m["source"] == "program_counter", name
    assert {by_name[n]["layer"] for n in NEW} == {"root executors"}
    # decode_native_pct has no unsuffixed entry (the streamed
    # cell's window decodes no row): its reader's unit and direction
    lone = {"decode_native_pct": {
        "unit": "%", "better": "higher",
        "layer": "coprocessor scan + decode", "workloads": []}}
    for name in SUFFIXED:
        # one quantity, one reader: unit, layer and direction are the
        # unsuffixed entry's
        stem = name.rsplit(".", 1)[0]
        base = by_name[stem] if stem in by_name else lone[stem]
        assert {k: by_name[name][k] for k in ("unit", "better", "layer")} \
            == {k: base[k] for k in ("unit", "better", "layer")}, name
        assert CELL not in base["workloads"], name
    # every entry that lists the cell has a reader run.py can load
    for m in MANIFEST["end_to_end"] + MANIFEST["per_layer"]:
        if CELL in m.get("workloads", [CELL]):
            assert callable(run._reader(m["name"])), m["name"]


def test_traffic_is_the_issues():
    with open(os.path.join(BENCH, "traffic", "q18_warm.json")) as f:
        t = json.load(f)
    assert t["databases"] == {"tpch": ["lineitem", "orders", "customer"]}
    assert t["streams"] == [{"loop": "closed", "count": 2, "rounds": True,
                             "database": "tpch", "statements": ["q18"]}]
    assert t["warm"] == [{"database": "tpch", "statement": "q18",
                          "times": 2}]
    assert t["trace"] == {"start_s": 1, "seconds": 25}


def test_configuration_says_what_tpch_1chip_says():
    def load(name):
        with open(os.path.join(BENCH, "configs", name + ".json")) as f:
            return json.load(f)
    mine, theirs = load("tpch_orders_1chip"), load("tpch_1chip")
    for key in ("deployment", "chips", "regions_per_big_table", "sysvars",
                "guarantees"):
        assert mine[key] == theirs[key], key
    assert mine["databases"] == {"tpch": theirs["databases"]["tpch"]}
    assert list(mine["reduced"]) == ["tpch.sf"]
    assert mine["source"] == next(
        c for c in MANIFEST["configs"]
        if c["name"] == "tpch_orders_1chip")["source"]
    assert len(mine["source"]) <= 200 and "2.4.18" in mine["source"]
