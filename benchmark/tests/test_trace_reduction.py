"""The trace reduction against hand counts on a written trace, and
against a brute-force count on a trace recorded on the chip."""

import os

import pytest
from xplane_writer import encode

import run
from benchlib import tracered

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")

# one device: busy [100,400] u [600,700] of a window [0,1000]
_PLANES = [
    {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": [("jit_kernel(123)", 100, 900)]},
        {"name": "XLA Ops", "events": [
            ("%fusion.1 = u32[8]{0} fusion(u32[8] %p), kind=kLoop", 100, 300),
            ("%fusion.2 = u32[8]{0} fusion(u32[8] %p), kind=kCustom", 250, 400),
            ("%all-reduce.1 = u32[8]{0} all-reduce(u32[8] %p)", 600, 700)]},
        {"name": "Async XLA Ops", "events": [
            ("%all-gather-start.1 = (u32[8], u32[32]) all-gather-start(u32[8] %p)",
             650, 800),
            ("%slice-start.4 = (u32[8]) async-start(u32[8] %p)", 0, 1000)]},
        {"name": "Steps", "events": [("step", 0, 1000)]}]},
    {"name": "/host:CPU", "lines": [{"name": "python3", "events": [
        ("trace_begin", 0, 0), ("inside_q1", 50, 500),
        ("inside_q1", 550, 800), ("trace_end", 1000, 1000)]}]},
]


@pytest.fixture()
def written(tmp_path):
    path = tmp_path / "written.xplane.pb"
    path.write_bytes(encode(_PLANES))
    return str(path)


def test_busy_union_and_idle_share(written):
    r = tracered.reduce_file(written)
    assert r["devices"] == 1
    assert r["window_s"] == pytest.approx(1000e-9)
    assert r["busy_s"] == pytest.approx(400e-9)        # overlap counted once
    assert 1 - r["busy_s_fullest"] / r["window_s"] == pytest.approx(0.6)
    # sync all-reduce [600,700] u async all-gather [650,800]; busy is
    # the executed-ops line alone
    assert r["collective_s"] == pytest.approx(200e-9)


def test_op_ranking_names_and_order(written):
    ops = tracered.reduce_file(written)["device_ops"]
    assert [n for n, _s in ops] == ["jit_kernel:fusion.1 u32[8] kLoop",
                                    "jit_kernel:fusion.2 u32[8] kCustom",
                                    "jit_kernel:all-reduce.1 u32[8] all-reduce"]
    assert [s for _n, s in ops] == pytest.approx([200e-9, 150e-9, 100e-9])


# the shape of warm Q1's program since PR 25: a `conditional` around a
# `while` around two fusions, a third fusion outside; busy [100,900]
_NESTED = [
    {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": [("jit_hashagg(7)", 100, 900)]},
        {"name": "XLA Ops", "events": [
            ("%conditional = (u32[4096]{0}) conditional(s32[] %p)", 200, 800),
            ("%while.14 = (s32[]) while((s32[]) %t)", 220, 780),
            ("%fusion.9 = u32[16,12]{1,0} fusion(u32[8] %p), kind=kLoop",
             230, 500),
            ("%fusion.8 = u32[16,6]{1,0} fusion(u32[8] %p), kind=kLoop",
             500, 770),
            ("%fusion.3 = u32[8]{0} fusion(u32[8] %p), kind=kLoop",
             100, 200),
            ("%fusion.4 = u32[8]{0} fusion(u32[8] %p), kind=kLoop",
             800, 900)]}]},
    {"name": "/host:CPU", "lines": [{"name": "python3", "events": [
        ("trace_begin", 0, 0), ("trace_end", 1000, 1000)]}]},
]


def test_op_times_are_self_times(tmp_path):
    path = tmp_path / "nested.xplane.pb"
    path.write_bytes(encode(_NESTED))
    r = tracered.reduce_file(str(path))
    ops = dict(r["device_ops"])
    assert r["busy_s"] == pytest.approx(800e-9)
    assert sum(ops.values()) == pytest.approx(r["busy_s"])
    assert r["device_ops"][0][0].startswith("jit_hashagg:fusion.")
    assert ops["jit_hashagg:fusion.9 u32[16,12] kLoop"] == pytest.approx(270e-9)
    assert ops["jit_hashagg:fusion.8 u32[16,6] kLoop"] == pytest.approx(270e-9)
    # the wrappers keep what no operation inside them covers
    assert ops["jit_hashagg:conditional u32[4096] conditional"] == \
        pytest.approx(40e-9)
    assert ops["jit_hashagg:while.14 s32[] while"] == pytest.approx(20e-9)


def test_self_times_cut_at_the_window(tmp_path):
    # the traced part opens inside the `while`'s first fusion and closes
    # inside its second: each level is clipped before it is nested
    planes = [_NESTED[0], {"name": "/host:CPU", "lines": [
        {"name": "python3", "events": [("trace_begin", 400, 400),
                                       ("trace_end", 600, 600)]}]}]
    path = tmp_path / "cut.xplane.pb"
    path.write_bytes(encode(planes))
    r = tracered.reduce_file(str(path))
    ops = dict(r["device_ops"])
    assert r["busy_s"] == pytest.approx(200e-9)
    assert sum(ops.values()) == pytest.approx(200e-9)
    assert ops["jit_hashagg:fusion.9 u32[16,12] kLoop"] == pytest.approx(100e-9)
    assert ops["jit_hashagg:conditional u32[4096] conditional"] == \
        pytest.approx(0.0, abs=1e-12)


def test_the_result_lines_ranking_sums_to_busy():
    ranked = [(f"op{i}", float(20 - i)) for i in range(14)]
    top = run._top_ops(ranked)
    assert len(top) == 10 and top[:9] == [[k, v] for k, v in ranked[:9]]
    assert top[9] == ["5 other ops", sum(v for _k, v in ranked[9:])]
    assert sum(v for _k, v in top) == sum(v for _k, v in ranked)
    assert run._top_ops(ranked[:10]) == [[k, v] for k, v in ranked[:10]]


def test_gap_attribution(written):
    gaps = dict(tracered.reduce_file(written)["idle_gaps"])
    # gaps [0,100] [400,600] [700,1000]; spans [50,500] [550,800]
    assert gaps["inside_q1:_total"] == pytest.approx(300e-9)
    assert gaps["inside_q1:_longest"] == pytest.approx(100e-9)
    assert gaps["between_statements:_total"] == pytest.approx(300e-9)
    assert gaps["between_statements:_longest"] == pytest.approx(200e-9)


def test_host_records_replace_cut_annotations(written):
    # the load generator's records on the host clock (seconds), anchored
    # at trace_begin: a statement open at both edges covers every gap
    planes = tracered.read_planes(written)
    r = tracered.reduce(planes, [("inside_q9", 9.0, 11.0)], 10.0)
    gaps = dict(r["idle_gaps"])
    assert gaps["inside_q9:_total"] == pytest.approx(600e-9)
    assert "between_statements:_total" not in gaps


def test_no_device_plane_reads_nothing(tmp_path):
    path = tmp_path / "host_only.xplane.pb"
    path.write_bytes(encode(_PLANES[1:]))
    assert tracered.reduce_file(str(path)) is None


def test_recorded_trace_against_brute_force():
    """1.5 s of tpch1.q1_warm on a TPU v5 lite (chip run, PR 23), cut to
    the device's op and module lines and the benchmark's spans."""
    planes = tracered.read_planes(
        os.path.join(DATA, "recorded_q1_warm.xplane.pb"))
    r = tracered.reduce(planes)
    lo, hi = r["window_ns"]
    ops = tracered.device_planes(planes)[0]["ops"]
    assert len(ops) > 1000
    # brute force on a 1 us grid: a cell is busy if any op covers its middle
    import numpy as np
    grid = np.zeros(int((hi - lo) // 1000) + 1, dtype=bool)
    for _n, s, e in ops:
        a, b = int((max(s, lo) - lo) // 1000), int((min(e, hi) - lo) // 1000)
        grid[a:b + 1] = True
    assert r["busy_s"] == pytest.approx(grid.sum() * 1e-6, rel=0.01)
    assert 0.9 < r["busy_s"] / r["window_s"] < 1.0     # device-bound Q1
    top = [n for n, _s in r["device_ops"][:4]]
    assert all("kCustom" in n and "u32[4096" in n for n in top)
    # a flat program (no operation holds another for longer than 1 ns):
    # self times are the durations, as this trace read before PR 30
    whole, own = {}, {}
    for n, s, e in ops:
        if min(e, hi) > max(s, lo):
            key = tracered.short_name(n)
            whole[key] = whole.get(key, 0.0) + (min(e, hi) - max(s, lo)) / 1e9
    for n, v in r["device_ops"]:
        key = n.removeprefix("jit__kernel:")
        own[key] = own.get(key, 0.0) + v
    assert own == pytest.approx(whole, abs=1e-12)
    assert sum(own.values()) == pytest.approx(r["busy_s"], rel=1e-6)
    gaps = dict(r["idle_gaps"])
    assert sum(v for k, v in gaps.items() if k.endswith(":_total")) == \
        pytest.approx(r["window_s"] - r["busy_s"], rel=1e-6)
