"""The readers of what the host does while the chip idles (the frames'
wait, a device agg's result release, GC pauses, background beats, how
ranges were served) on written snapshots: hand counts where the counter
moved, None on a snapshot without it (the parent of the program that
brought the counters has none), and the manifest's nine entries."""

import json
import os

import pytest

import run
from benchlib import spans
from benchlib.loadgen import Op, Window

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    MANIFEST = json.load(f)

SELF = spans.SELF
PAUSE = 'tidb_tpu_gc_pause_seconds_total{generation="%d"}'
BEAT = 'tidb_tpu_worker_beat_seconds_total{worker="%s"}'
RANGES = 'tidb_tpu_cop_ranges_total{served="%s",why="%s"}'

STREAM, Q1, Q18 = "tpch1.q3q5_stream", "tpch1.q1_warm", "tpch1.q18_warm"
ENTRIES = {
    "frame_wait_ms_per_stmt": ([STREAM], "coprocessor scan + decode"),
    "frame_wait_ms_per_stmt.analytic": ([Q18], "coprocessor scan + decode"),
    "result_release_ms_per_stmt": ([Q1, Q18], "kernels"),
    "gc_pause_ms_per_stmt.stream": ([STREAM], "host runtime"),
    "gc_pause_ms_per_stmt.analytic": ([Q1, Q18], "host runtime"),
    "worker_beat_ms_per_stmt.stream": ([STREAM], "host runtime"),
    "worker_beat_ms_per_stmt.analytic": ([Q1, Q18], "host runtime"),
    "ranges_resident_pct": ([STREAM], "chunk cache"),
    "ranges_resident_pct.analytic": ([Q18], "chunk cache"),
}


def _ctx(before: dict, after: dict, done=4):
    """`done` completed closed-loop statements (and one failed) between
    two /status snapshots."""
    ctx = run.Ctx()
    ctx.before = {"metrics": before}
    ctx.after = {"metrics": after}
    ctx.window = Window(seconds=10.0)
    for i in range(done + 1):
        ctx.window.ops.append(Op("s0", "closed", "db", "q", None, float(i),
                                 sent=float(i), done=i + 0.5,
                                 ok=i < done))
    return ctx


def test_manifest_has_the_nine_entries():
    by_name = {m["name"]: m for m in MANIFEST["per_layer"]}
    for name, (cells, layer) in ENTRIES.items():
        m = by_name[name]
        assert (m["workloads"], m["layer"], m["source"]) == (
            cells, layer, "program_counter"), name
        assert m["moves"] == ("stream_rows_per_s" if cells == [STREAM]
                              else "analytic_rows_per_s"), name


@pytest.mark.parametrize("name,span", [
    ("frame_wait_ms_per_stmt", "copr.wait"),
    ("frame_wait_ms_per_stmt.analytic", "copr.wait"),
    ("result_release_ms_per_stmt", "copr.release")])
def test_span_readers_are_self_ms_per_statement(name, span):
    ctx = _ctx({SELF % span: 1.0, SELF % "execute": 5.0},
               {SELF % span: 3.0, SELF % "execute": 9.0})
    assert run._reader(name)(ctx) == pytest.approx(500.0)
    assert run._reader(name)(_ctx({}, {SELF % "execute": 9.0})) is None


@pytest.mark.parametrize("name,family,key", [
    ("gc_pause_ms_per_stmt.stream", PAUSE, (0, 1, 2)),
    ("gc_pause_ms_per_stmt.analytic", PAUSE, (0, 1, 2)),
    ("worker_beat_ms_per_stmt.stream", BEAT,
     ("metrics-history", "schema-worker", "stats-auto-analyze")),
    ("worker_beat_ms_per_stmt.analytic", BEAT,
     ("metrics-history", "schema-worker", "stats-auto-analyze"))])
def test_family_readers_sum_every_label(name, family, key):
    a, b, c = (family % k for k in key)
    # 0.1 + 0.3 s in the window over 4 statements; `c` first fired in it
    ctx = _ctx({a: 1.0, b: 2.0}, {a: 1.1, b: 2.1, c: 0.2})
    assert run._reader(name)(ctx) == pytest.approx(100.0)
    # present and still: a true zero
    assert run._reader(name)(_ctx({a: 1.0}, {a: 1.0})) == 0.0
    assert run._reader(name)(_ctx({}, {SELF % "execute": 1.0})) is None
    # no statement completed: nothing per statement
    assert run._reader(name)(_ctx({a: 1.0}, {a: 2.0}, done=0)) is None


@pytest.mark.parametrize("name", ["ranges_resident_pct",
                                  "ranges_resident_pct.analytic"])
def test_resident_share_of_the_windows_ranges(name):
    res, over = RANGES % ("resident", "hit"), RANGES % ("streamed",
                                                        "over_frame")
    cold = RANGES % ("streamed", "cold")
    ctx = _ctx({res: 10, over: 4}, {res: 19, over: 6, cold: 1})
    assert run._reader(name)(ctx) == pytest.approx(100.0 * 9 / 12)
    assert run._reader(name)(_ctx({res: 3}, {res: 3})) is None
    assert run._reader(name)(_ctx({}, {SELF % "execute": 1.0})) is None
