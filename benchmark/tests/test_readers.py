"""Every metric BENCHMARK.json names has a reader file, every cell has its
configuration, traffic and statements, and the device-trace readers give
the chip's numbers on the recorded trace."""

import json
import os

import pytest

import run
from benchlib import compare, peaks, tracered

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    MANIFEST = json.load(f)
METRICS = MANIFEST["end_to_end"] + MANIFEST["per_layer"]


@pytest.mark.parametrize("name", [m["name"] for m in METRICS])
def test_metric_has_a_reader(name):
    assert callable(run._reader(name))


@pytest.mark.parametrize("cell", MANIFEST["workloads"], ids=lambda w: w["name"])
def test_cell_files_exist(cell):
    cfg = next(c for c in MANIFEST["configs"] if c["name"] == cell["config"])
    config = run._load_json(ROOT, cfg["file"])
    traffic = run._load_json(BENCH, "traffic", cell["traffic"] + ".json")
    assert config["chips"] == cell["chips"]
    assert set(traffic["databases"]) <= set(config["databases"])
    for name in run._statement_names(traffic):
        stmt = run._load_json(BENCH, "statements", name + ".json")
        assert callable(compare.truth_of(name)) and "{key}" in stmt["sql"] or \
            stmt["kind"] == "analytic"
    reported = [m for m in METRICS
                if "workloads" not in m or cell["name"] in m["workloads"]]
    ends = {m["name"] for m in MANIFEST["end_to_end"]}
    assert "setup_s" in {m["name"] for m in reported}
    assert len([m for m in reported if m["name"] in ends]) >= 2
    # a layer metric moves an end-to-end metric its cell reports
    for m in reported:
        if "moves" in m:
            assert m["moves"] in {r["name"] for r in reported}, m["name"]


def test_device_readers_on_the_recorded_trace():
    ctx = run.Ctx()
    ctx.statements = {"q1": run._load_json(BENCH, "statements", "q1.json")}
    ctx.schema = {"tpch_dbgen": run._load_json(
        BENCH, "generators", "tpch_dbgen.schema.json")}
    ctx.config = {"databases": {"tpch": {"generator": "tpch_dbgen"}}}
    ctx.counts = {"tpch": {"lineitem": 1_200_243}}
    ctx.stmt_db = {"q1": "tpch"}
    ctx.peaks = peaks.for_kind("TPU v5 lite")
    ctx.trace = tracered.reduce_file(
        os.path.join(HERE, "data", "recorded_q1_warm.xplane.pb"))
    busy = run._reader("device_busy_ms_per_stmt")(ctx)
    roof = run._reader("hbm_roofline_pct")(ctx)
    # 1.43 s busy over the three Q1 spans the cut trace holds (cut at its
    # edges, so each counts whole: 478 ms; uncut it was 633 ms at the
    # scale the trace was recorded at, 1,200,243 rows of the narrower
    # lineitem this benchmark started with); 45.6 MB needed a statement
    # at 819 GB/s
    assert busy == pytest.approx(1000 * ctx.trace["busy_s"] / 3)
    assert roof == pytest.approx(
        100 * 38 * 1_200_243 / 819e9 / (busy / 1000), rel=1e-6)
    # one reader file serves every suffixed name
    for suffix in ("analytic", "stream"):
        idle = run._reader("device_idle_pct." + suffix)(ctx)
        assert 0 < idle < 10
