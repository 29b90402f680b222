"""`correct` has to come out false when the timed path is broken
underneath, once for each fault a cell can have; and true on the same
run with nothing broken. Each case is a rehearsal run (the look for a
chip skipped, tiny scale) in a process of its own."""

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))

CASES = [
    ("none", "tpch1.q1_warm", True),
    ("answer_altered", "tpch1.q1_warm", False),
    ("half_left_out", "tpch1.q1_warm", False),
    ("none", "tpch1.q3q5_stream", True),
    ("answer_altered", "tpch1.q3q5_stream", False),
    ("half_left_out", "tpch1.q3q5_stream", False),
]


@pytest.mark.parametrize("fault,cell,want", CASES)
def test_correct_under_fault(fault, cell, want):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(HERE, "fault_driver.py"), fault,
         "--workload", cell, "--seed", "2147483659", "--seconds", "2",
         "--trace", "0", "--rehearse", "--rehearse-scale", "0.05"],
        capture_output=True, text=True, timeout=600, env=env)
    assert out.returncode == 0, out.stderr[-3000:]
    result = json.loads(out.stdout.strip().splitlines()[-1])
    assert result["correct"] is want, result["compared"]
    assert result["metrics"] == {}            # a rehearsal names no metric
    if not want:
        bad = result["compared"]
        assert bad["answers_wrong"]["value"] + \
            bad["answers_never_came"]["value"] > 0
