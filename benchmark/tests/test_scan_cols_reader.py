"""`scan_cols_pct` (PR 34) on a written ctx: a percentage from two
/status snapshots, None where the program has no such counter (the
parent of the PR that brought it) or no reader ran in the window, the
suffixed name served by the same file, and its entries in the manifest."""

import pytest

import run
from test_span_readers import MANIFEST, _ctx

SCANNED = 'tidb_tpu_reader_columns_total{kind="scanned"}'
TABLE = 'tidb_tpu_reader_columns_total{kind="table"}'


def _read(before, after, name="scan_cols_pct"):
    return run._reader(name)(_ctx(before, after))


def test_percentage_from_two_snapshots():
    # the warm-up ran one Q3 and one Q5 (10 of 33 columns, 16 of 47);
    # the window two Q3s and one Q5
    assert _read({SCANNED: 26, TABLE: 80}, {SCANNED: 62, TABLE: 193}) == \
        pytest.approx(100.0 * 36 / 113)


def test_suffixed_name_is_served_by_the_same_file():
    # Q1: 7 of lineitem's 16
    assert _read({SCANNED: 7, TABLE: 16}, {SCANNED: 707, TABLE: 1616},
                 "scan_cols_pct.analytic") == pytest.approx(43.75)


@pytest.mark.parametrize("before,after", [
    ({}, {}),                                     # the parent: no counter
    ({"tidb_tpu_h2d_bytes_total": 1}, {"tidb_tpu_h2d_bytes_total": 9}),
    ({SCANNED: 26, TABLE: 80}, {SCANNED: 26, TABLE: 80}),   # no reader ran
])
def test_nothing_to_read_is_none(before, after):
    assert _read(before, after) is None


@pytest.mark.parametrize("name,moves,cells", [
    ("scan_cols_pct", "stream_rows_per_s", ["tpch1.q3q5_stream"]),
    ("scan_cols_pct.analytic", "analytic_rows_per_s",
     ["tpch1.q1_warm", "tpch1.q18_warm"])])
def test_manifest_entries(name, moves, cells):
    by_name = {m["name"]: m for m in MANIFEST["per_layer"]}
    assert by_name[name] == {
        "name": name, "unit": "%", "better": "lower",
        "source": "program_counter",
        "layer": "wire + session + planner; admission + slots",
        "moves": moves, "workloads": cells}
