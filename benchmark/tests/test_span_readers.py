"""The span readers (PR 24) on a written ctx: counter snapshots and a
`device_ops` list against hand counts, None where the counter never
fired (the parent of the PR that brought the counters has none), and a
rehearsal run of each cell showing the new names."""

import json
import os
import subprocess
import sys

import pytest

import run
from benchlib import spans
from benchlib.loadgen import Op, Window

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    MANIFEST = json.load(f)

SELF = spans.SELF

STREAM = ["kv_scan_us_per_row", "decode_us_per_row", "cop_exec_us_per_row",
          "unspanned_ms_per_stmt", "h2d_bytes_per_stmt"]
WARM = ["slot_wait_ms_per_stmt", "dispatch_ms_per_stmt",
        "finalize_ms_per_stmt", "wire_write_ms_per_stmt",
        "kernel_ms_per_stmt.hashagg"]


def _ctx(before: dict, after: dict, statements=("q3", "q5", "q3"),
         rows=1000):
    """Three completed closed-loop statements (one more failed), each
    reading `rows` base-table rows, between two /status snapshots."""
    ctx = run.Ctx()
    ctx.statements = {n: {"kind": "analytic", "tables": ["t"]}
                      for n in set(statements)}
    ctx.counts = {"db": {"t": rows}}
    ctx.before = {"metrics": before}
    ctx.after = {"metrics": after}
    ctx.window = Window(seconds=10.0)
    for i, name in enumerate(statements):
        ctx.window.ops.append(Op("s0", "closed", "db", name, None, float(i),
                                 sent=float(i), done=i + 1.0, ok=True))
    ctx.window.ops.append(Op("s0", "closed", "db", statements[0], None, 9.0,
                             sent=9.0, done=9.5, ok=False))
    return ctx


def test_manifest_names_the_ten_with_their_cells():
    by_name = {m["name"]: m for m in MANIFEST["per_layer"]}
    for name in STREAM:
        assert by_name[name]["workloads"] == ["tpch1.q3q5_stream"]
        assert by_name[name]["moves"] == "stream_rows_per_s"
    for name in WARM:
        assert by_name[name]["workloads"] == ["tpch1.q1_warm"]
        assert by_name[name]["moves"] == "analytic_rows_per_s"
    assert by_name["kernel_ms_per_stmt.hashagg"]["source"] == "device_trace"
    assert {by_name[n]["source"] for n in STREAM + WARM[:4]} == \
        {"program_counter"}


@pytest.mark.parametrize("name,span", [
    ("kv_scan_us_per_row", "copr.kv_scan"),
    ("decode_us_per_row", "copr.decode"),
    ("cop_exec_us_per_row", "copr.exec")])
def test_us_per_row_is_self_seconds_over_rows_read(name, span):
    # 0.5 s before, 0.8 s after: 0.3 s over 3 statements x 1000 rows
    ctx = _ctx({SELF % span: 0.5, SELF % "other": 7.0},
               {SELF % span: 0.8, SELF % "other": 9.0})
    assert run._reader(name)(ctx) == pytest.approx(1e6 * 0.3 / 3000)
    # the counter never fired: nothing to read, not zero
    assert run._reader(name)(_ctx({}, {SELF % "other": 9.0})) is None
    # it fired only before the window opened: a true zero
    ctx = _ctx({SELF % span: 0.5}, {SELF % span: 0.5})
    assert run._reader(name)(ctx) == 0.0


def test_unspanned_is_statement_plus_execute_self_time():
    before = {SELF % "statement": 1.0, SELF % "execute": 10.0}
    after = {SELF % "statement": 1.3, SELF % "execute": 40.0,
             SELF % "copr.decode": 99.0}
    got = run._reader("unspanned_ms_per_stmt")(_ctx(before, after))
    assert got == pytest.approx(1000.0 * (0.3 + 30.0) / 3)
    # one of the two alone is still a reading
    got = run._reader("unspanned_ms_per_stmt")(
        _ctx({}, {SELF % "execute": 6.0}))
    assert got == pytest.approx(2000.0)
    assert run._reader("unspanned_ms_per_stmt")(_ctx({}, {})) is None


@pytest.mark.parametrize("name,span", [
    ("slot_wait_ms_per_stmt", "sched.slot"),
    ("dispatch_ms_per_stmt", "dispatch"),
    ("finalize_ms_per_stmt", "finalize")])
def test_ms_per_stmt_of_one_span(name, span):
    ctx = _ctx({SELF % span: 2.0}, {SELF % span: 3.5},
               statements=("q1", "q1", "q1"))
    assert run._reader(name)(ctx) == pytest.approx(500.0)
    assert run._reader(name)(_ctx({}, {})) is None


def test_counter_readers_per_statement():
    before = {"tidb_tpu_h2d_bytes_total": 1000,
              "tidb_tpu_wire_write_seconds_total": 0.010}
    after = {"tidb_tpu_h2d_bytes_total": 7000,
             "tidb_tpu_wire_write_seconds_total": 0.013}
    ctx = _ctx(before, after)
    assert run._reader("h2d_bytes_per_stmt")(ctx) == pytest.approx(2000.0)
    assert run._reader("wire_write_ms_per_stmt")(ctx) == pytest.approx(1.0)
    empty = _ctx({}, {})
    assert run._reader("h2d_bytes_per_stmt")(empty) is None
    assert run._reader("wire_write_ms_per_stmt")(empty) is None
    # no statement completed: no per-statement reading
    none_done = _ctx(before, after)
    for o in none_done.window.ops:
        o.ok = False
    assert run._reader("h2d_bytes_per_stmt")(none_done) is None


def test_kernel_ms_reads_its_family_by_module_name():
    ctx = _ctx({}, {}, statements=("q1", "q1", "q1"))
    lo, hi = 0.0, 10e9
    ctx.trace = {
        "window_ns": (lo, hi),
        # two whole statements and one cut in half by the trace's edge
        "spans": [("inside_q1", 1e9, 3e9), ("inside_q1", 4e9, 6e9),
                  ("inside_q1", 9e9, 11e9)],
        "device_ops": [("jit_hashagg:fusion.1 u32[4096,6] kCustom", 1.2),
                       ("jit_hashagg:fusion.2 u32[4096] kCustom", 0.3),
                       ("jit_join:while.17", 0.4),
                       ("copy u32[524288,2]", 0.1)],
    }
    got = run._reader("kernel_ms_per_stmt.hashagg")(ctx)
    assert got == pytest.approx(1000.0 * 1.5 / 2.5)
    assert spans.kernel_ms_per_stmt(ctx, "join") == \
        pytest.approx(1000.0 * 0.4 / 2.5)
    # the parent's trace names every program jit__kernel: nothing to read
    ctx.trace["device_ops"] = [("jit__kernel:fusion.1", 1.2)]
    assert run._reader("kernel_ms_per_stmt.hashagg")(ctx) is None
    ctx.trace = None
    assert run._reader("kernel_ms_per_stmt.hashagg")(ctx) is None


@pytest.mark.parametrize("cell,names", [
    ("tpch1.q1_warm", WARM), ("tpch1.q3q5_stream", STREAM)])
def test_rehearsal_shows_the_new_names(cell, names):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", cell,
         "--seed", "2147483659", "--seconds", "3", "--trace", "1",
         "--rehearse", "--rehearse-scale", "0.05"],
        capture_output=True, text=True, timeout=600, env=env, cwd=ROOT)
    assert out.returncode == 0, out.stderr[-3000:]
    result = json.loads(out.stdout.strip().splitlines()[-1])
    assert result["correct"] is True, result["compared"]
    assert result["metrics"] == {}
    got = result["rehearsal_metrics"]
    # the CPU's trace has no device plane, so the one device_trace
    # reader has nothing to read here; the nine counter readers do
    want = [n for n in names if n != "kernel_ms_per_stmt.hashagg"]
    assert set(want) <= set(got), sorted(got)
    for n in want:
        assert got[n]["value"] >= 0
    assert ("kernel_ms_per_stmt.hashagg" in got) == ("breakdown" in result)
