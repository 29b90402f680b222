"""Set-up's stats pass: every window opens after the program's first
auto-analyze pass, and while the statistics moved after the last
warm-up began the cell's warm list runs once more.

Unit cases drive `run._settle` with a stub system under test; a
rehearsal of `tpch1.q3q5_stream` at the cell's own scale (a pruned
`lineitem` region fits one frame there, as on the chip) shows the pass,
the re-warm, no bump of the statistics inside the window, and every Q3
of the window streaming its pruned `lineitem`: the stats pass re-plans
Q3 after the warm-up's cache hit, the re-warm's hit under the new plan
adds a second filter memo to each `lineitem` entry, and an entry with
both is over the frame that serving from residency allows."""

import json
import os
import subprocess
import sys

import pytest

import run
from test_span_readers import MANIFEST

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CELLS = ["tpch1.q1_warm", "tpch1.q3q5_stream", "tpch1.q18_warm"]


class StubSut:
    """The version the statistics handle reads, moved by the n-th run of
    the warm list (`bumps[n]`: the worker's own tick during a long
    warm-up, or racing the pass) and by the pass."""

    def __init__(self, version, bumps, pass_analyzes):
        self.version = version
        self.bumps = list(bumps)
        self.pass_analyzes = pass_analyzes
        self.phases = []

    def stats_version(self):
        return self.version

    def warm(self, phase):
        n = len(self.phases)
        self.phases.append(phase)
        self.version += self.bumps[n] if n < len(self.bumps) else 0

    def stats_pass(self):
        before = self.version
        self.version += len(self.pass_analyzes)
        return {"analyzed": list(self.pass_analyzes),
                "version_before": before, "version_after": self.version}


@pytest.mark.parametrize("version,bumps,analyzes,rewarms", [
    # the pass analyzes bootstrap's two system tables
    (0, [], ["mysql.user", "mysql.tidb"], 1),
    # the worker's tick came during the warm-up; the pass finds nothing
    (0, [2], [], 1),
    # the tick came during the load: nothing moves after the warm-up began
    (2, [], [], 0),
    # the worker's tick raced the pass and saved during the re-warm
    (0, [0, 1], ["mysql.user"], 2),
    # the statistics never settle: the window opens after three re-warms
    (0, [0, 1, 1, 1, 1], ["mysql.user"], 3),
], ids=["pass_analyzed", "tick_in_warm_up", "tick_before_warm_up",
        "tick_races_the_pass", "never_settles"])
def test_rewarm_follows_the_version(version, bumps, analyzes, rewarms):
    sut = StubSut(version, bumps, analyzes)
    ctx = run.Ctx()
    run._settle(ctx, sut, sut.warm)
    assert sut.phases == ["warm"] + ["rewarm"] * rewarms
    assert ctx.setup["rewarms"] == rewarms
    assert ("rewarm" in ctx.setup) is bool(rewarms)
    assert ctx.setup["stats_analyzed"] == len(analyzes)
    assert ctx.setup["warm"] >= 0 and ctx.setup["stats"] >= 0


def _bumps(before, after, name="stats_bumps_in_window"):
    ctx = run.Ctx()
    ctx.before, ctx.after = before, after
    return run._reader(name)(ctx)


def test_bumps_reader():
    assert _bumps({"stats_version": 2}, {"stats_version": 2}) == 0.0
    assert _bumps({"stats_version": 2}, {"stats_version": 5}) == 3.0
    assert _bumps({"stats_version": 0}, {"stats_version": 1},
                  "stats_bumps_in_window.analytic") == 1.0
    # a snapshot without the version has nothing to read
    assert _bumps({}, {}) is None


def test_manifest_lists_the_bumps_for_every_cell():
    by_name = {m["name"]: m for m in MANIFEST["per_layer"]}
    entries = [by_name["stats_bumps_in_window"],
               by_name["stats_bumps_in_window.analytic"]]
    assert sorted(c for m in entries for c in m["workloads"]) == \
        sorted(CELLS)
    for m in entries:
        assert (m["unit"], m["better"], m["source"], m["layer"]) == (
            "bumps", "lower", "program_counter", "planner (statistics)")
    assert entries[0]["moves"] == "stream_rows_per_s"
    assert entries[1]["moves"] == "analytic_rows_per_s"


# -- the streamed cell at its own scale ----------------------------------------

# frames a statement at the cell's scale: a Q3 takes lineitem's pruned
# region streamed (24 frames over four regions), orders' four resident
# ranges and customer's one; a Q5, served from residency, one a range of
# lineitem, orders, customer, supplier, nation and region.
Q3, Q5 = 24 + 4 + 1, 4 + 4 + 1 + 1 + 1 + 1


def test_rehearsal_every_q3_streams_after_the_pass():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", "tpch1.q3q5_stream", "--seed", "2147489123",
         "--seconds", "6", "--trace", "1", "--rehearse",
         "--rehearse-scale", "1.0"],
        capture_output=True, text=True, timeout=900, env=env, cwd=ROOT)
    assert out.returncode == 0, out.stderr[-3000:]
    result = json.loads(out.stdout.strip().splitlines()[-1])
    assert result["correct"] is True, result["compared"]
    assert result["failed"] == 0
    setup = result["setup_phases_s"]
    # the pass analyzed bootstrap's system tables, and the cell warmed
    # again after it, once
    assert setup["stats_analyzed"] >= 1
    assert setup["rewarms"] == 1 and setup["rewarm"] > 0
    got = {k: v["value"] for k, v in result["rehearsal_metrics"].items()}
    assert got["stats_bumps_in_window"] == 0
    assert got["compiles_in_window"] == 0
    # one stream alternating Q3 and Q5, from Q3: every Q3 streamed its
    # lineitem, so the scan readers have rows to read
    n = result["attempted"]
    assert n >= 4
    assert got["stream_frames_per_stmt"] * n == pytest.approx(
        Q3 * ((n + 1) // 2) + Q5 * (n // 2))
    assert got["kv_scan_us_per_row"] > 0
    assert got["decode_native_pct"] == 100
