"""Drive one rehearsal run of the benchmark with the timed path broken
underneath: `python fault_driver.py <fault> <run.py arguments...>`.

The faults are planted in the program, not in the harness, before the
harness starts the server:

  answer_altered   the wire layer alters the last digit of every 7th row it
                   encodes (a token or an answer altered where it is
                   produced)
  half_left_out    the coprocessor client drops every other region task
                   of a scan (half of the batch left out, the mean taken
                   over the rest)
"""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, os.path.dirname(BENCH))
sys.path.insert(0, BENCH)


def answer_altered():
    from tidb_tpu import server
    encode = server.ClientConn._encode_row
    count = [0]

    def broken(row):
        out = encode(row)
        count[0] += 1
        if count[0] % 7 == 0 and out and 0x30 <= out[-1] <= 0x39:
            out = out[:-1] + bytes([0x30 + (out[-1] - 0x30 + 1) % 10])
        return out

    server.ClientConn._encode_row = staticmethod(broken)


def half_left_out():
    from tidb_tpu.store import region_cache
    split = region_cache.RegionCache.split_ranges_by_region

    def broken(self, ranges):
        tasks = split(self, ranges)
        return tasks[::2] if len(tasks) > 1 else tasks

    region_cache.RegionCache.split_ranges_by_region = broken


if __name__ == "__main__":
    {"answer_altered": answer_altered, "half_left_out": half_left_out,
     "none": lambda: None}[sys.argv[1]]()
    import run
    sys.stdout.flush()
    code = run.main(sys.argv[2:])
    sys.stdout.flush()
    os._exit(code)
