"""chip_smoke.py on the CPU: the control flow, the numpy truths and the
write read-back at a tiny scale — and the refusal to pass where there
is no accelerator. The chip run itself is the driver's (`python
chip_smoke.py` on the machine with the chip).
"""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _smoke(*argv, out: str):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    # one virtual device: the default route, as on a one-chip machine
    env.pop("XLA_FLAGS", None)
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"),
         "--sf", "0.01", "--out", out, *argv],
        capture_output=True, text=True, timeout=600, env=env, cwd=REPO)


def test_smoke_passes_on_cpu_at_tiny_scale():
    out = "test_chip_smoke.json"
    proc = _smoke("--expect-platform", "cpu", out=out)
    assert proc.returncode == 0, proc.stdout[-4000:] + proc.stderr[-4000:]
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert last == {"ok": True, "device": {"platform": "cpu",
                                           "kind": "cpu", "count": 1}}
    with open(os.path.join(REPO, "chiprun_out", out)) as f:
        summary = json.load(f)
    stm = summary["statements"]
    assert set(stm) >= {"q1_cold", "q1_warm", "q1_third", "q1_host",
                        "q3", "q5", "point", "read_back", "count_after"}
    # the third Q1 is served from HBM blocks: no fill, no compile
    assert stm["q1_third"]["hbm_cache"] == {"hits": 4, "misses": 0,
                                            "evictions": 0}
    assert summary["resident"]["third_q1_hbm_fill_bytes"] == 0
    assert stm["q1_third"]["kernel_compiles"] == 0
    assert stm["count_after"]["device_dispatches"]
    assert not any(summary["fallbacks_by_reason"].get(r)
                   for r in ("fault", "quarantine", "unsupported"))
    # any scale under SF1 is a recorded cut; the join cap does not bite
    assert [(r["what"], r["to_sf"]) for r in summary["reduced"]] == [
        ("scale", 0.01)]


def test_smoke_fails_without_a_chip():
    proc = _smoke(out="test_chip_smoke_nochip.json")
    assert proc.returncode != 0
    assert "no accelerator" in proc.stderr
    assert "FAILED in phase 'device'" in proc.stderr
    assert '"ok"' not in proc.stdout          # no result line
