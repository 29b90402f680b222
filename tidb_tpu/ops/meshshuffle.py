"""Plane shuffle hash join: all_to_all repartition + per-chip sort join.

The reference's general hash join (/root/reference/executor/join.go:37)
builds an mvmap from the whole build side and probes it with worker
goroutines; scaled out, both sides would be repartitioned by key hash
across nodes. On the device plane that repartition is ONE collective:
each chip buckets its row shard by destination chip (hash mod n), an
``all_to_all`` over the ``"batch"`` axis exchanges the buckets over ICI,
and every chip then joins only its hash partition with the same
sort/searchsorted matcher as the single-chip kernel (ops/join.py).
Per-chip memory is O(N/ndev) for both sides — unlike the replicated-
dimension lookup join (ops/meshjoin.py), duplicate keys on either side
and build sides too large to replicate are fine. On a 1-device plane
the kernel delegates to the single-chip JoinKernel: no shuffle at all.

Static-shape handling (XLA cannot see data-dependent counts):
* send buckets have a fixed per-destination capacity; a pmax over the
  true bucket sizes detects overflow, and the host retries with larger
  buckets — heavy-hitter skew is absorbed by capacity escalation, the
  per-destination growth bounded by the shard size itself.
* the matcher emits into a fixed per-chip pair capacity with the same
  total-count overflow/retry protocol as ops/join.py.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from tidb_tpu import devplane
from tidb_tpu.devplane import AXIS
from tidb_tpu.ops import runtime
from tidb_tpu.ops.hashagg import _FILL, _SENTINEL_MASKED, _hash_keys
from tidb_tpu.ops.join import JoinKernel, match_pairs

__all__ = ["MeshShuffleJoinKernel", "ShuffleOverflowError"]

_DEAD_BUILD = _SENTINEL_MASKED
_DEAD_PROBE = _FILL
_HASH_SEED = 0x9E3779B97F4A7C15


class ShuffleOverflowError(Exception):
    """A shuffle bucket or the pair output exceeded its static capacity
    beyond the retry budget (extreme hash skew)."""


def _bucketize(xp, ndev, cap, dst, keep, lanes, fills):
    """Scatter each row's lanes into its destination bucket.
    -> ([ndev*cap] buffers per lane, local max bucket fill)."""
    n = dst.shape[0]
    order = xp.argsort(dst)
    sdst = dst[order]
    first = xp.searchsorted(sdst, sdst, side="left")
    rank = xp.arange(n) - first
    # dropped rows (dead/padding) and overflowing ranks park on a dump
    # slot past the buffer end
    ok = keep[order] & (rank < cap)
    slot = xp.where(ok, sdst * cap + rank, ndev * cap)
    out = []
    for lane, fill in zip(lanes, fills):
        buf = xp.full(ndev * cap + 1, fill, dtype=lane.dtype)
        out.append(buf.at[slot].set(lane[order])[:-1])
    maxfill = xp.max(xp.where(keep[order], rank + 1, 0), initial=0)
    return out, maxfill


class MeshShuffleJoinKernel:
    """Distributed equi-join pair matcher. Call signature mirrors
    ops/join.py JoinKernel: fixed-width key lanes in, (probe_idx,
    build_idx) numpy pair arrays out, so the executor's host-side payload
    gather is unchanged."""

    def __init__(self, mesh, num_keys: int):
        self.mesh = mesh
        self.ndev = devplane.ndev(mesh)
        self.num_keys = num_keys
        self._jits: dict = {}
        self._single = JoinKernel(num_keys) if self.ndev == 1 else None
        # one-slot build-side transfer memo: a streamed probe calls the
        # kernel once per super-batch against the SAME build keys object;
        # pinning it (identity compare) makes every batch after the first
        # re-send only the probe. One slot bounds pinned device memory.
        self._build_memo = None       # (build_keys_obj, shard_len, arrays)

    # -- traced program ------------------------------------------------------

    def _program(self, ls, rs, cap_l, cap_r, out_cap):
        ndev = self.ndev

        def shard_side(keys, n, shard_len, dead, is_probe):
            # lint: exempt[dtype-discipline] global row offsets are exact int64 (shard base can exceed int32)
            ci = lax.axis_index(AXIS).astype(jnp.int64)
            offs = ci * shard_len
            alive = (offs + jnp.arange(shard_len)) < n
            valid = alive
            for _d, v in keys:
                valid = valid & v
            h = _hash_keys(jnp, [(d, v & valid) for d, v in keys],
                           shard_len, seed=_HASH_SEED)
            h = jnp.where(valid, h, dead)
            # dead rows (NULL keys, shard padding) route past every real
            # bucket so they never inflate a live bucket's ranks
            dst = jnp.where(
                valid,
                # lint: exempt[dtype-discipline] route lanes ride the int64 hash dtype (dead-row sentinel = ndev)
                (h.astype(jnp.uint64) % np.uint64(ndev)).astype(jnp.int64),
                ndev)
            gidx = offs + jnp.arange(shard_len)
            cap = cap_l if is_probe else cap_r
            lanes = [h, gidx] + [d for d, _v in keys]
            fills = [dead, -1] + [np.array(0, d.dtype) for d, _v in keys]
            bufs, maxfill = _bucketize(jnp, ndev, cap, dst, valid,
                                       lanes, fills)
            exch = [lax.all_to_all(b.reshape(ndev, cap), AXIS, 0, 0)
                    .reshape(ndev * cap) for b in bufs]
            return exch[0], exch[1], exch[2:], maxfill

        def kernel(lkeys, rkeys, nl, nr):
            hp, pli, pd, ofl_l = shard_side(lkeys, nl, ls, _DEAD_PROBE,
                                            True)
            hb, bli, bd, ofl_r = shard_side(rkeys, nr, rs, _DEAD_BUILD,
                                            False)
            # per-partition sort join: the shared matcher of ops/join.py
            li_c, ri, ok, total = match_pairs(jnp, hb, hp, bd, pd, out_cap)
            gl = jnp.where(ok, pli[li_c], -1)
            gr = jnp.where(ok, bli[ri], -1)
            return (gl, gr, ok, total.reshape(1), ofl_l.reshape(1),
                    ofl_r.reshape(1))

        spec_row = devplane.batch_spec()
        nk = self.num_keys
        in_specs = (tuple((spec_row, spec_row) for _ in range(nk)),
                    tuple((spec_row, spec_row) for _ in range(nk)),
                    P(), P())
        out_specs = (spec_row, spec_row, spec_row,
                     spec_row, spec_row, spec_row)
        sm = devplane.shard_map(kernel, self.mesh, in_specs=in_specs,
                                out_specs=out_specs)
        return devplane.plane_jit(sm, name="meshshuffle")

    # -- host driver ---------------------------------------------------------

    def _put_side(self, keys, shard_len):
        sh = devplane.batch_sharding(self.mesh)
        out = []
        for d, v in keys:
            pd_, pv = runtime.pad_column(np.asarray(d), np.asarray(v),
                                         shard_len * self.ndev)
            # numpy straight into the sharded device_put: one transfer,
            # no commit-then-reshard hop
            out.append((jax.device_put(pd_, sh), jax.device_put(pv, sh)))
        return tuple(out)

    def finalize(self, pending):
        """One batched device->host readback — the kernel's output
        boundary, shared by the retry loop's control read (the small
        overflow counters land first so a retry discards the cap-sized
        pair buffers without transferring them) and the success path's
        pair read (per-array reads each pay a full device round
        trip)."""
        return jax.device_get(pending)

    def __call__(self, probe_keys, build_keys, nb: int, np_: int):
        """probe/build key lanes [(data, valid)] -> (li, ri) pair arrays.
        Argument order mirrors JoinKernel.__call__(bk, pk, nb, np_) users:
        here probe first for readability, sizes last."""
        if self._single is not None:
            return self._single(build_keys, probe_keys, nb, np_)
        if nb == 0 or np_ == 0:
            return (np.empty(0, np.int64), np.empty(0, np.int64))
        ndev = self.ndev
        ls = runtime.bucket_size(-(-max(np_, 1) // ndev))
        rs = runtime.bucket_size(-(-max(nb, 1) // ndev))
        # expected per-destination fill is shard/ndev; 4x slack absorbs
        # ordinary skew, the retry loop the rest
        cap_l = min(ls, runtime.bucket_size(max(-(-ls // ndev) * 4, 16)))
        cap_r = min(rs, runtime.bucket_size(max(-(-rs // ndev) * 4, 16)))
        out_cap = runtime.bucket_size(max(2 * ls, 1024))
        lk = self._put_side(probe_keys, ls)
        memo = self._build_memo
        if memo is not None and memo[0] is build_keys and memo[1] == rs:
            rk = memo[2]
        else:
            rk = self._put_side(build_keys, rs)
            self._build_memo = (build_keys, rs, rk)
        for _ in range(8):
            key = (ls, rs, cap_l, cap_r, out_cap)
            prog = self._jits.get(key)
            if prog is None:
                prog = self._program(*key)
                self._jits[key] = prog
            gl, gr, ok, totals, fl, fr = prog(lk, rk, np_, nb)
            # small control arrays first: an overflow retry then discards
            # the cap-sized pair buffers without transferring them
            totals, fl, fr = self.finalize((totals, fl, fr))
            need_l = int(np.max(fl))
            need_r = int(np.max(fr))
            max_total = int(np.max(totals))
            if need_l > cap_l:
                cap_l = min(ls, runtime.bucket_size(need_l))
                continue
            if need_r > cap_r:
                cap_r = min(rs, runtime.bucket_size(need_r))
                continue
            if max_total > out_cap:
                out_cap = runtime.bucket_size(max_total)
                continue
            gl, gr, ok = self.finalize((gl, gr, ok))
            sel = np.flatnonzero(ok)
            return (gl[sel].astype(np.int64),
                    gr[sel].astype(np.int64))
        raise ShuffleOverflowError("shuffle join retry budget exhausted")
