"""`wire_sends_per_stmt` (PR 29) on a written ctx: socket writes of the
server's packet writer over statements completed, from two /status
snapshots; None where the program has no such counter (the parent of
the PR that brought it); and its entry in the manifest."""

import pytest

import run
from test_span_readers import MANIFEST, _ctx

CALLS = "tidb_tpu_wire_write_calls_total"


def _read(before, after, statements=("q1", "q1", "q1")):
    return run._reader("wire_sends_per_stmt")(
        _ctx(before, after, statements=statements))


@pytest.mark.parametrize("before,after,statements,want", [
    # the per-packet writer: 17 sendalls for one Q1 answer
    ({CALLS: 40}, {CALLS: 57}, ("q1",), 17.0),
    # one write a response: three statements and the probe's own reply
    ({CALLS: 40}, {CALLS: 44}, ("q1", "q1", "q1"), 4 / 3),
    # it fired only before the window opened: a true zero
    ({CALLS: 40}, {CALLS: 40}, ("q1",), 0.0),
])
def test_calls_over_statements_completed(before, after, statements, want):
    assert _read(before, after, statements) == pytest.approx(want)


@pytest.mark.parametrize("before,after", [
    ({}, {}),                                     # the parent: no counter
    ({"tidb_tpu_wire_write_bytes_total": 1},
     {"tidb_tpu_wire_write_bytes_total": 9}),
])
def test_a_program_without_the_counter_reads_nothing(before, after):
    assert _read(before, after) is None


def test_no_statement_completed_reads_nothing():
    ctx = _ctx({CALLS: 40}, {CALLS: 57})
    for o in ctx.window.ops:
        o.ok = False
    assert run._reader("wire_sends_per_stmt")(ctx) is None


def test_manifest_entry():
    by_name = {m["name"]: m for m in MANIFEST["per_layer"]}
    assert by_name["wire_sends_per_stmt"] == {
        "name": "wire_sends_per_stmt", "unit": "sends", "better": "lower",
        "source": "program_counter", "layer": "finalize + encode",
        "moves": "analytic_rows_per_s", "workloads": ["tpch1.q1_warm"]}
