"""`agg_dense_dispatch_pct` (PR 25) on a written ctx: a percentage from
two /status snapshots, None where the program has no such counter (the
parent of the PR that brought it) or the window held no group-by
dispatch, and its entry in the manifest."""

import pytest

import run
from test_span_readers import MANIFEST, _ctx

DENSE = 'tidb_tpu_agg_dispatch_total{path="dense"}'
SCATTER = 'tidb_tpu_agg_dispatch_total{path="scatter"}'


def _read(before, after):
    return run._reader("agg_dense_dispatch_pct")(
        _ctx(before, after, statements=("q1", "q1", "q1")))


def test_percentage_from_two_snapshots():
    # set-up dispatched 12 dense and 3 scatter; the window 30 and 10
    assert _read({DENSE: 12, SCATTER: 3}, {DENSE: 42, SCATTER: 13}) == \
        pytest.approx(75.0)


@pytest.mark.parametrize("before,after,want", [
    ({DENSE: 8}, {DENSE: 288}, 100.0),            # scatter never fired
    ({}, {SCATTER: 5}, 0.0),                      # dense never fired
    ({DENSE: 8, SCATTER: 2}, {DENSE: 8, SCATTER: 6}, 0.0),
])
def test_one_path_alone_is_a_reading(before, after, want):
    assert _read(before, after) == pytest.approx(want)


@pytest.mark.parametrize("before,after", [
    ({}, {}),                                     # the parent: no counter
    ({"tidb_tpu_h2d_bytes_total": 1}, {"tidb_tpu_h2d_bytes_total": 9}),
    ({DENSE: 8, SCATTER: 2}, {DENSE: 8, SCATTER: 2}),   # none in the window
])
def test_nothing_to_read_is_none(before, after):
    assert _read(before, after) is None


def test_manifest_entry():
    by_name = {m["name"]: m for m in MANIFEST["per_layer"]}
    assert by_name["agg_dense_dispatch_pct"] == {
        "name": "agg_dense_dispatch_pct", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "kernels",
        "moves": "analytic_rows_per_s", "workloads": ["tpch1.q1_warm"]}
