"""Device kernels for ANALYZE: whole-column sort on the accelerator.

The reference's ANALYZE builds samples row-at-a-time inside each storage
node (mocktikv/analyze.go). Here the histogram build is one XLA sort over
the full column — the MXU doesn't help, but the vector units + HBM
bandwidth make multi-million-row sorts far faster than numpy, and the
sorted array round-trips through the same host buffers the chunk layer
already uses.

Inputs ride the pow2 superchunk buckets (runtime.bucket_size) before
dispatch: jit caches one executable per dtype/shape, so a raw-length
sort would recompile per distinct column length. Padding values are
chosen to sort AFTER every real element (NaN for inexact dtypes, the
dtype max for integers), so the first n lanes of the sorted bucket are
exactly the sorted input.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from tidb_tpu import profiler
from tidb_tpu.ops import runtime

_jit_sort = jax.jit(jnp.sort)     # the XLA module is `jit_sort` as is

# (dtype, bucket) pairs already dispatched: jit holds one executable
# per pair, so a pair's first sight is one `sort` compile unit
_SEEN: set = set()


def device_sort(data: np.ndarray) -> np.ndarray:
    """Sort a numeric column on the default device; returns numpy."""
    n = data.shape[0]
    cap = runtime.bucket_size(n)
    if cap != n:
        if np.issubdtype(data.dtype, np.inexact):
            fill = np.array(np.nan, dtype=data.dtype)
        else:
            fill = np.array(np.iinfo(data.dtype).max, dtype=data.dtype)
        # lint: exempt[memtrack-alloc] pow2 pad of the ANALYZE column the statement already bills; at most 2x the tracked input
        padded = np.empty(cap, dtype=data.dtype)
        padded[:n] = data
        padded[n:] = fill
        data = padded
    key = (data.dtype.str, cap)
    prof = profiler.profile("sort", f"{key[0]}|{cap}")
    if key not in _SEEN:
        _SEEN.add(key)
        profiler.note_construct(prof, reuse=False)
    with profiler.dispatch_section(prof, nbytes=2 * data.nbytes):
        return np.asarray(_jit_sort(data))[:n]
