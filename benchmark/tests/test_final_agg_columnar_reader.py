"""`final_agg_columnar_pct` on a written ctx: a percentage from
two /status snapshots, None where the program has no such counter (a
program from before the labeled counter) or the window emitted no
group, and its entry in the manifest."""

import pytest

import run
from test_span_readers import MANIFEST, _ctx

TOTAL = "tidb_tpu_agg_final_groups_total"
COLUMNAR = 'tidb_tpu_agg_final_groups_total{path="columnar"}'
DICT = 'tidb_tpu_agg_final_groups_total{path="dict"}'


def _read(before, after):
    return run._reader("final_agg_columnar_pct")(
        _ctx(before, after, statements=("q18", "q18")))


def test_percentage_from_two_snapshots():
    # set-up emitted 900,040 columnar and 40 dict groups; the window
    # 1,800,000 and 60 more
    before = {TOTAL: 900_080, COLUMNAR: 900_040, DICT: 40}
    after = {TOTAL: 2_700_140, COLUMNAR: 2_700_040, DICT: 100}
    assert _read(before, after) == pytest.approx(
        100.0 * 1_800_000 / 1_800_060)


@pytest.mark.parametrize("before,after,want", [
    ({COLUMNAR: 8}, {COLUMNAR: 288}, 100.0),      # no dict merge ran
    ({}, {DICT: 16}, 0.0),                        # no columnar merge ran
    ({COLUMNAR: 8, DICT: 2}, {COLUMNAR: 8, DICT: 6}, 0.0),
])
def test_one_path_alone_is_a_reading(before, after, want):
    assert _read(before, after) == pytest.approx(want)


@pytest.mark.parametrize("before,after", [
    ({}, {}),
    # a program from before the label: the total alone
    ({TOTAL: 10}, {TOTAL: 450_030}),
    ({COLUMNAR: 8, DICT: 2}, {COLUMNAR: 8, DICT: 2}),   # none in the window
])
def test_nothing_to_read_is_none(before, after):
    assert _read(before, after) is None


def test_total_reader_reads_on_beside_the_labels():
    """final_agg_us_per_group keeps dividing by the unlabeled total,
    which is the sum of both paths."""
    self_key = 'tidb_tpu_span_self_seconds_total{span="exec.agg"}'
    before = {TOTAL: 0, COLUMNAR: 0, DICT: 0, self_key: 0.0}
    after = {TOTAL: 1_000_000, COLUMNAR: 999_980, DICT: 20, self_key: 0.2}
    got = run._reader("final_agg_us_per_group")(
        _ctx(before, after, statements=("q18", "q18")))
    assert got == pytest.approx(0.2)


def test_manifest_entry():
    by_name = {m["name"]: m for m in MANIFEST["per_layer"]}
    entry = dict(by_name["final_agg_columnar_pct"])
    assert "tpch1.q18_warm" in entry.pop("workloads")
    assert entry == {
        "name": "final_agg_columnar_pct", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "root executors",
        "moves": "analytic_rows_per_s"}
