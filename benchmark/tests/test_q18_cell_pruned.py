"""`tpch1.q18_warm` rehearsed at a scale where its `lineitem` still
streams once the readers are pruned (PR 34).

`test_q18_cell.py` rehearses the cell at a tenth of its scale. A pruned
`lineitem` region is 18 bytes a row (two lanes), so at 45,000 rows it is
under the 4 MiB frame cap, the program serves it from the chunk cache
and that rehearsal's window decodes no row: its case
`test_rehearsal_is_correct_and_shows_the_cells_readers` has no
`decode_native_pct.analytic` to read (`benchmark/conftest.py` marks it
until the next `benchmark` PR rescales it). On the chip a region is
450,000 rows, 8.1 MB, and streams. This file holds the same guard at six
tenths of the cell's scale: 270,000 rows a region, 4.86 MB, over the cap
as on the chip."""

import json
import os
import subprocess
import sys

from test_q18_cell import CELL, HERE, NEW, ROOT, SUFFIXED
from test_span_readers import MANIFEST

SCALE = "0.6"
FRAME_BYTES = 4 << 20
LINEITEM_ROWS = 1_799_995       # the cell's sf 0.3 (test_q18_needs_by_hand)
PRUNED_ROW_BYTES = 18           # l_orderkey, l_quantity and a null byte each


def test_a_pruned_region_is_over_one_frame_at_this_scale():
    region = LINEITEM_ROWS * float(SCALE) / 4 * PRUNED_ROW_BYTES
    assert region > FRAME_BYTES > LINEITEM_ROWS * 0.1 / 4 * PRUNED_ROW_BYTES


def test_rehearsal_streams_pruned_and_shows_the_cells_readers():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(HERE, "fault_driver.py"), "none",
         "--workload", CELL, "--seed", "2147489011", "--seconds", "3",
         "--trace", "1", "--rehearse", "--rehearse-scale", SCALE],
        capture_output=True, text=True, timeout=1500, env=env, cwd=ROOT)
    assert out.returncode == 0, out.stderr[-3000:]
    result = json.loads(out.stdout.strip().splitlines()[-1])
    assert result["correct"] is True, result["compared"]
    assert result["compared"]["answers_compared"]["value"] >= 2
    got = result["rehearsal_metrics"]
    counters = [m["name"] for m in MANIFEST["per_layer"]
                if CELL in m.get("workloads", ())
                and m["source"] != "device_trace"
                and m["name"] != "peak_hbm_bytes"]
    assert set(NEW) | SUFFIXED <= set(counters) <= set(got), sorted(got)
    for n in NEW:
        assert got[n]["value"] > 0, n
    assert got["agg_dense_dispatch_pct.q18"]["value"] == 0.0
    # lineitem streams through native/codec.cc, at two columns of sixteen;
    # orders and customer come from residency, a frame a region
    assert got["decode_native_pct.analytic"]["value"] == 100.0
    assert got["decode_us_per_row.analytic"]["value"] > 0
    assert got["stream_frames_per_stmt.analytic"]["value"] > 12
    assert got["scan_cols_pct.analytic"]["value"] == 100.0 * 10 / 49
    assert got["compiles_in_window"]["value"] == 0
