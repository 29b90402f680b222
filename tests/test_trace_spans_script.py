"""scripts/trace_spans.py's attribution of the device's idle time, on
hand-built host lines: the innermost open annotation of one thread,
which kind of thread a line is, and the share within a kind that never
counts one interval twice and sums to the idle time."""

import importlib.util
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_spec = importlib.util.spec_from_file_location(
    "trace_spans", os.path.join(ROOT, "scripts", "trace_spans.py"))
ts = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(ts)

S = 1e9     # a second in the trace's nanoseconds


def ev(name, a, b):
    return (name, a * S, b * S, None)


SESSION = [ev("statement", 0, 10), ev("execute", 1, 9),
           ev("copr.wait", 2, 4), ev("copr.wait", 5, 8)]


def test_segments_follow_the_innermost_annotation():
    got = [(a / S, b / S, n) for a, b, n in ts.segments(SESSION, 0, 12 * S)]
    assert got == [(0, 1, "statement"), (1, 2, "execute"),
                   (2, 4, "copr.wait"), (4, 5, "execute"),
                   (5, 8, "copr.wait"), (8, 9, "execute"),
                   (9, 10, "statement"), (10, 12, ts.NO_SPAN)]
    assert ts.innermost(SESSION, 3 * S, 6 * S) == {
        "copr.wait": 2.0, "execute": 1.0}


@pytest.mark.parametrize("evs,kind", [
    (SESSION, "session"),
    # a statement longer than the trace has no root in it: its other
    # spans name its thread
    ([ev("copr.wait", 0, 5), ev("exec.agg", 6, 9)], "session"),
    ([ev("copr.stream", 0, 5), ev("copr.kv_scan", 1, 2)], "cop-stream"),
    ([ev("copr.task", 0, 5), ev("copr.kv_scan", 1, 2)], "other"),
    ([ev("gc.pause", 3, 3.1)], "other"),
    ([ev("worker.beat", 0, 1), ev("inside_q3", 0, 9)], "other"),
    ([ev("inside_q3", 0, 9)], None)])
def test_kind_of_a_line(evs, kind):
    assert ts.kind_of(evs) == kind


def test_share_counts_no_interval_twice_within_a_kind():
    other = [ev("statement", 0, 10), ev("execute", 0, 10),
             ev("gc.pause", 3, 4), ev("inside_q5", 0, 10)]
    gaps = [(1 * S, 4 * S), (6 * S, 11 * S)]
    got = ts.share([SESSION, other], gaps)
    # 8 idle seconds: 1-2 execute | execute; 2-3 wait | execute;
    # 3-4 wait | gc.pause; 6-8 wait | execute; 8-9 execute | execute;
    # 9-10 statement | execute; 10-11 nothing open
    assert got == pytest.approx({
        "execute": 1 + 0.5 + 1 + 1 + 0.5, "copr.wait": 0.5 + 0.5 + 1,
        "gc.pause": 0.5, "statement": 0.5, ts.NO_SPAN: 1.0})
    assert sum(got.values()) == pytest.approx(8.0)
    assert ts.share([], gaps) == {ts.NO_SPAN: 8.0}
    assert ts.share([SESSION], []) == {}
