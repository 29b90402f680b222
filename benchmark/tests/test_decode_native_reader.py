"""`decode_native_pct` (PR 32) on a written ctx: a percentage from two
/status snapshots, None where the program has no such counter (the
parent of the PR that brought it) or the window decoded no row, and its
entry in the manifest."""

import pytest

import run
from test_span_readers import MANIFEST, _ctx

NATIVE = 'tidb_tpu_decode_rows_total{path="native"}'
PYTHON = 'tidb_tpu_decode_rows_total{path="python"}'


def _read(before, after):
    return run._reader("decode_native_pct")(
        _ctx(before, after, statements=("q3", "q5", "q3")))


def test_percentage_from_two_snapshots():
    # set-up decoded 612,418 rows natively and 25 of an index in Python;
    # the window 918,000 and 306,000
    assert _read({NATIVE: 612418, PYTHON: 25},
                 {NATIVE: 1530418, PYTHON: 306025}) == pytest.approx(75.0)


@pytest.mark.parametrize("before,after,want", [
    ({NATIVE: 612418}, {NATIVE: 1530836}, 100.0),   # python never ran
    ({}, {PYTHON: 918418}, 0.0),                    # no compiler
    ({NATIVE: 8, PYTHON: 2}, {NATIVE: 8, PYTHON: 6}, 0.0),
])
def test_one_path_alone_is_a_reading(before, after, want):
    assert _read(before, after) == pytest.approx(want)


@pytest.mark.parametrize("before,after", [
    ({}, {}),                                     # the parent: no counter
    ({"tidb_tpu_h2d_bytes_total": 1}, {"tidb_tpu_h2d_bytes_total": 9}),
    ({NATIVE: 8, PYTHON: 2}, {NATIVE: 8, PYTHON: 2}),   # none in the window
])
def test_nothing_to_read_is_none(before, after):
    assert _read(before, after) is None


def test_manifest_entry():
    by_name = {m["name"]: m for m in MANIFEST["per_layer"]}
    assert by_name["decode_native_pct"] == {
        "name": "decode_native_pct", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "coprocessor scan + decode",
        "moves": "stream_rows_per_s", "workloads": ["tpch1.q3q5_stream"]}
