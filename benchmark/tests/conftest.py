"""The benchmark's own tests run on the CPU: `python -m pytest
benchmark/tests -q` from the repo's root (README.md)."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
for p in (BENCH, HERE):
    if p not in sys.path:
        sys.path.insert(0, p)
