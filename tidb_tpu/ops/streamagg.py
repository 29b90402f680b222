"""Stream (sorted-input) aggregation on device: segment-reduce.

Replaces /root/reference/executor/aggregate.go:150-170 (StreamAggExec:
pipelined aggregation over input sorted by the group keys). On TPU this is
the *most* natural aggregation shape — no hash table, no capacity/overflow
protocol, no collision risk:

    1. the input chunk arrives sorted by the group-key expressions
       (planner guarantee: a Sort below, or an order-preserving reader)
    2. adjacent-row key comparison marks segment starts; a cumulative sum
       turns the boundary mask into dense segment ids
    3. jax.ops.segment_* reduce every aggregate into per-segment lanes
       with num_segments = chunk rows (static shape, never overflows)

Unlike HashAggKernel the result is EXACT by construction (keys compare by
value, not by hash), so there is no CollisionError path. Chunk partials
merge across chunk boundaries on the host exactly like the hash path
(a group spanning two chunks meets itself in HashAggregator).
"""

from __future__ import annotations

from typing import Sequence

import numpy as np

import jax
import jax.numpy as jnp

from tidb_tpu import devplane
from tidb_tpu.chunk import Chunk
from tidb_tpu.expression import AggDesc, Expression
from tidb_tpu.ops import runtime
from tidb_tpu.ops.hashagg import (GroupResult, _agg_lanes, _key_bits,
                                  _validate_device_exprs,
                                  finalize_group_result)

__all__ = ["SegmentAggKernel", "segment_kernel_for"]


class SegmentAggKernel:
    """Compiled segment-reduce over one sorted-chunk schema.

    The caller owns the sorted-input contract: rows with equal group keys
    must be adjacent (full sorted order is not required, contiguity is
    enough). group_exprs must be device-safe or bare string ColumnRefs
    (dict codes compare equal iff the values are equal, which is all
    boundary detection needs)."""

    def __init__(self, group_exprs: Sequence[Expression],
                 aggs: Sequence[AggDesc]):
        self.group_exprs = list(group_exprs)
        self.aggs = list(aggs)
        _validate_device_exprs(None, self.group_exprs, self.aggs)
        self._jit = jax.jit(devplane.named(self._kernel, "streamagg"))
        self._jitd = None   # donating variant, built on first dispatch

    # lint: exempt[dtype-discipline] int64 segment counts/ids: exact lane semantics shared with hashagg's agg-state stacking
    def _kernel(self, cols, nrows):
        xp = jnp
        n = cols[0][0].shape[0]
        alive = xp.arange(n) < nrows
        key_cols = [g.eval_xp(xp, cols, n) for g in self.group_exprs]
        # segment starts: row 0, plus any row whose key differs from the
        # previous row's (exact bit compare; NULLs equal NULLs)
        new = xp.zeros(n, dtype=bool).at[0].set(True)
        for d, v in key_cols:
            bits = _key_bits(xp, d)
            diff = (bits[1:] != bits[:-1]) | (v[1:] != v[:-1])
            new = new.at[1:].set(new[1:] | diff)
        new = new & alive                      # padding opens no segment
        seg = xp.cumsum(new.astype(jnp.int32)) - 1
        seg = xp.clip(seg, 0, n - 1)           # all-padding chunk guard
        nseg = xp.sum(new.astype(jnp.int64))
        counts = jax.ops.segment_sum(alive.astype(jnp.int64), seg,
                                     num_segments=n)
        rep = jax.ops.segment_min(xp.where(alive, xp.arange(n), n), seg,
                                  num_segments=n)
        lanes = [[l for l, _op in
                  _agg_lanes(xp, a, cols, n, alive, seg, n)]
                 for a in self.aggs]
        return nseg, counts, rep, lanes

    def scratch_nbytes(self, chunk: Chunk) -> int:
        """Device bytes beyond the input columns: segment-id/count/lane
        scratch (num_segments = padded rows, the no-capacity-limit
        trade) — the fused-dispatch share when the input is an
        HBM-cache-resident block."""
        n = runtime.bucket_size(max(chunk.num_rows, 1))
        return n * 8 * (3 + 2 * len(self.aggs))

    def dispatch_nbytes(self, chunk: Chunk) -> int:
        """HBM bytes one dispatch stages, from shapes at dispatch time:
        padded input columns plus the kernel scratch."""
        from tidb_tpu import memtrack
        n = runtime.bucket_size(max(chunk.num_rows, 1))
        return memtrack.device_put_bytes(chunk, n) + \
            self.scratch_nbytes(chunk)

    def dispatch(self, chunk: Chunk, donate: bool = False, dev_cols=None):
        """Async half: pad + transfer + enqueue, no sync (see
        HashAggKernel.dispatch for the donation and dev_cols
        contracts)."""
        if dev_cols is not None:
            return self._jit(dev_cols, chunk.num_rows)
        donate = donate and runtime.donation_supported()
        cols, _dicts = runtime.device_put_chunk(chunk, memo=not donate)
        if donate:
            if self._jitd is None:
                self._jitd = jax.jit(
                    devplane.named(self._kernel, "streamagg"),
                    donate_argnums=(0,))
            return self._jitd(cols, chunk.num_rows)
        return self._jit(cols, chunk.num_rows)

    def finalize(self, chunk: Chunk, pending) -> GroupResult:
        # one batched device->host transfer (per-array reads pay full
        # round-trip latency each; see HashAggKernel.finalize)
        nseg, counts, rep, lanes = jax.device_get(pending)
        nseg = int(nseg)
        gidx = np.arange(nseg)
        lanes_at = [[l[gidx] for l in ls] for ls in lanes]
        return finalize_group_result(chunk, self.group_exprs, self.aggs,
                                     rep[gidx], lanes_at, counts[gidx])

    def __call__(self, chunk: Chunk, dev_cols=None) -> GroupResult:
        return self.finalize(chunk, self.dispatch(chunk,
                                                  dev_cols=dev_cols))


# process-wide cache like ops/hashagg.kernel_for, keyed on the group/agg
# fingerprint (segment kernels have no capacity axis); shares the same
# thread-safe true-LRU implementation
_SEG_KERNELS = runtime.FingerprintCache(64)


def segment_kernel_for(group_exprs, aggs) -> SegmentAggKernel:
    from tidb_tpu import devplane, profiler
    made = []

    def make():
        made.append(1)
        return SegmentAggKernel(group_exprs, aggs)

    fp = runtime.plan_fingerprint(None, group_exprs, aggs)
    if fp is None:
        k = make()
        prof = profiler.profile("streamagg", None)
        profiler.note_construct(prof, reuse=False)
        k._profile = prof
        return k
    key = (fp, devplane.mesh_fingerprint(process=True))
    k = _SEG_KERNELS.get_or_create(key, make)
    prof = profiler.profile("streamagg", fp)
    profiler.note_construct(prof, reuse=not made)
    k._profile = prof
    return k
