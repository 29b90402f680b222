"""The control (the reference computed with less than the stated
guarantee) must come out as not correct, at a size a test run holds; and
the harness refuses to run without a chip unless it is rehearsing."""

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)


@pytest.mark.parametrize("cell,scale", [
    ("tpch1.q1_warm", 0.2), ("tpch1.q3q5_stream", 1.0)])
@pytest.mark.parametrize("seed", [1, 2147483659, 3000000019])
def test_control_is_not_correct(cell, scale, seed):
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "control.py"), "--workload",
         cell, "--seed", str(seed), "--scale", str(scale), "--seconds", "10"],
        capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stdout + out.stderr[-2000:]
    last = json.loads(out.stdout.strip().splitlines()[-1])
    assert last["control_correct"] is False


def test_no_chip_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "tpch1.q1_warm", "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=300, env=env)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "no accelerator" in out.stderr
