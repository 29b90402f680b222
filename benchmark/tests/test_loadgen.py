"""An open loop's schedule: every seed gets the same set of gaps and
keys' ranks, in another order; the same seed gets the same schedule."""

import numpy as np

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
