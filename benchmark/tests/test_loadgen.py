"""An open loop's schedule: every seed gets the same set of gaps and
keys' ranks, in another order; the same seed gets the same schedule.
Closed loops alone and in rounds."""

import time

import numpy as np
import pytest

from benchlib import loadgen

SPEC = {"loop": "open", "rate_per_s": 120, "arrivals": "poisson",
        "keys": {"dist": "zipf", "theta": 0.99}}


def test_same_work_for_every_seed_in_another_order():
    due_a, keys_a = loadgen.open_schedule(SPEC, 10.0, 50_000, 7)
    due_b, keys_b = loadgen.open_schedule(SPEC, 10.0, 50_000, 3000000019)
    again, keys_again = loadgen.open_schedule(SPEC, 10.0, 50_000, 7)
    assert len(due_a) == len(due_b) == 1200
    assert np.array_equal(due_a, again) and np.array_equal(keys_a, keys_again)
    gaps_a, gaps_b = np.diff(due_a), np.diff(due_b)
    assert not np.array_equal(gaps_a, gaps_b)
    # the same multiset of gaps but for the first, which every schedule
    # starts on: all but one or two values pair up
    assert np.allclose(np.sort(gaps_a)[5:-5], np.sort(gaps_b)[5:-5],
                       rtol=0.02)
    assert 0.0 == due_a[0] and due_a[-1] <= 10.0
    # hot keys: the same number of requests go to the hottest key
    assert (np.bincount(np.unique(keys_a, return_counts=True)[1]).tolist()
            == np.bincount(np.unique(keys_b, return_counts=True)[1]).tolist())


class _SleepConn:
    """Stands in for a wire connection: stream k's statement takes
    TAKES[k] seconds."""
    TAKES = {"s0.0": 0.02, "s0.1": 0.07}

    def __init__(self, *_args):
        pass

    def run(self, op, _sql, t0, _annotate):
        op.sent = time.perf_counter() - t0
        time.sleep(self.TAKES[op.stream])
        op.ok, op.rows = True, []
        op.done = time.perf_counter() - t0

    def close(self):
        pass


@pytest.mark.parametrize("rounds", [False, True])
def test_closed_streams_alone_or_in_rounds(monkeypatch, rounds):
    monkeypatch.setattr(loadgen, "_Conn", _SleepConn)
    spec = {"loop": "closed", "count": 2, "database": "d",
            "statements": ["q"]}
    if rounds:
        spec["rounds"] = True
    win = loadgen.run_window(0, {"streams": [spec]}, {"q": {"sql": ""}},
                             {}, 7, 0.3)
    fast, slow = ([o for o in win.ops if o.stream == s]
                  for s in ("s0.0", "s0.1"))
    assert all(o.sent < 0.3 for o in fast + slow)
    if not rounds:
        assert len(fast) > 2 * len(slow)
        return
    # the same count a stream, and each round's statements sent only
    # once the round before has all its answers
    assert len(fast) == len(slow) >= 3
    for k in range(1, len(fast)):
        assert min(fast[k].sent, slow[k].sent) >= slow[k - 1].done
    assert win.wall_s >= 0.3
