"""Distributed group-by aggregation on the one device plane.

The reference merges per-region partial aggregates on one Go root
(/root/reference/executor/aggregate.go + distsql fan-in, distsql.go:92).
Here the merge itself is distributed: every chip aggregates its row shard
locally (sort-based groups, exactly like ops/hashagg.py), the per-chip
group tables ride an ``all_gather`` over ICI, and each chip re-reduces
the gathered tables — the aggregation-state analogue of ring attention
(SURVEY.md §5.7). Rows arrive as ``NamedSharding(mesh, P("batch"))``
(devplane.batch_sharding); the merged bucket table is replicated, so the
host reads one copy and downstream operators are free to re-shard it.
On a 1-device mesh the collectives are elided at trace time and the
program lowers to the plain single-chip kernel — one code path, 1..N
chips (the "global table strikes back" replicate-the-merge placement:
arxiv 2505.04153 measures gather+re-reduce beating partitioned group
exchange until group counts far exceed ours).

Collision/overflow semantics match the single-chip kernel: a dual 64-bit
hash detects key collisions, a true-distinct count detects capacity
overflow; both raise so the caller can fall back or re-plan.
"""

from __future__ import annotations

from typing import Sequence

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from tidb_tpu import devplane
from tidb_tpu.chunk import Chunk
from tidb_tpu.devplane import AXIS
from tidb_tpu.expression import AggDesc, AggFunc, Expression
from tidb_tpu.ops import runtime
from tidb_tpu.ops.hashagg import (CapacityError, CollisionError, GroupResult,
                                  _FILL, _SENTINEL_MASKED, _I64_MAX, _I64_MIN,
                                  _agg_requests, _direct_group_mode,
                                  _group_slots, _group_table,
                                  _validate_device_exprs,
                                  finalize_group_result)

__all__ = ["MeshAggKernel", "MeshKernelBase", "group_merge_program"]

_BIG = _I64_MAX


_MERGE = {"sum": jax.ops.segment_sum,
          "min": jax.ops.segment_min,
          "max": jax.ops.segment_max}


def group_merge_program(xp, cols, mask, ln, offs, group_exprs, aggs,
                        C, ndev, row_ids=None):
    """The shared traced body: local sort-based group tables, all_gather
    merge over the ``"batch"`` axis, replicated output. `cols` is any
    virtual column list (probe columns, or probe + gathered join
    payloads — ops/meshjoin.py); expressions index into it. row_ids
    (global original probe row index per row) replaces offs+arange for
    the representative/FIRST_ROW lanes when rows were compacted."""
    direct = _direct_group_mode(group_exprs)
    # local slots by the keys' shape, exactly as on one chip; the slot
    # space of the direct modes is agreed over the batch axis. The
    # header lanes + every aggregate share the one _SegBatch, which
    # picks dense or scatter from this shard's own count
    b, h2, uniq_of = _group_slots(xp, group_exprs, cols, ln, mask, C,
                                  pmax_axes=(AXIS,) if ndev > 1 else None)
    # lint: exempt[dtype-discipline] int64 COUNT lane: exact past 2^53 rows, matches the agg-state stacking dtype
    mask_i = mask.astype(jnp.int64)
    i_cnt = b.add(mask_i, "sum")
    i_h2min = b.add(xp.where(mask, h2, _I64_MAX), "min")
    i_h2max = b.add(xp.where(mask, h2, _I64_MIN), "max")
    if row_ids is not None:
        i_grep = b.add(xp.where(mask, row_ids, _BIG), "min")
    else:
        i_grep = b.add(xp.where(mask, xp.arange(ln), ln), "min")
    i_ghas = b.add(mask_i, "max")
    assembles = [_agg_requests(xp, a, cols, ln, mask, b, offs=offs,
                               row_ids=row_ids)
                 for a in aggs]
    b.run()
    uniq, local_tot = uniq_of(b.get(i_cnt)), b.nuniq

    lanes: list[tuple] = []  # (array[C], merge_op)
    lanes.append((b.get(i_cnt), "sum"))                            # cnt
    lanes.append((b.get(i_h2min), "min"))
    lanes.append((b.get(i_h2max), "max"))
    if row_ids is not None:
        lanes.append((b.get(i_grep), "min"))                       # rep
    else:
        lanes.append((xp.where(b.get(i_ghas) > 0,
                               offs + b.get(i_grep), _BIG), "min"))
    agg_lane_slices = []
    for assemble in assembles:
        ls = assemble(b.get)
        agg_lane_slices.append((len(lanes) - 4, len(ls)))
        lanes.extend(ls)

    # -- cross-chip merge: gather every shard's table, re-reduce -----------
    # (single-device meshes skip the collectives entirely: some
    # single-chip runtimes can't lower pmax/all_gather, and the local
    # table already is the global table)
    if ndev == 1:
        return (uniq, *(l for l, _op in lanes[:4]),
                tuple(tuple(lanes[4 + s + i][0] for i in range(w))
                      for s, w in agg_lane_slices),
                local_tot)
    ax = (AXIS,)
    if direct:
        # every shard shares one slot space: merge is an elementwise
        # reduce over the gathered [ndev, C] tables — no re-unique
        gu = lax.all_gather(uniq, ax)                        # [ndev, C]
        muniq = xp.min(gu, axis=0)     # FILL > real code > SENTINEL;
        # a slot live anywhere must not surface as masked-sentinel
        any_real = xp.max(xp.where(gu == _SENTINEL_MASKED,
                                   _I64_MIN, gu), axis=0)
        muniq = xp.where((muniq == _SENTINEL_MASKED) &
                         (any_real != _I64_MIN) & (any_real != _FILL),
                         any_real, muniq)
        tot = devplane.pmax(local_tot, ax)
        merged = []
        _RED = {"sum": xp.sum, "min": xp.min, "max": xp.max}
        for lane, op in lanes:
            g = lax.all_gather(lane, ax)                     # [ndev, C]
            merged.append(_RED[op](g, axis=0))
        cnt, h2min, h2max, rep = merged[:4]
        agg_out = tuple(
            tuple(merged[4 + start + i] for i in range(width))
            for start, width in agg_lane_slices)
        return (muniq, cnt, h2min, h2max, rep, agg_out, tot)
    all_uniq = lax.all_gather(uniq, ax, tiled=True)          # [ndev*C]
    muniq, minv, gtot = _group_table(xp, all_uniq, ndev * C, C)
    # gathered fill/sentinel slots can add up to 2 phantom values to
    # gtot relative to a single table; they are excluded on the host
    # via the live mask, and capacity is checked with slack for them
    tot = xp.maximum(gtot, devplane.pmax(local_tot, ax))
    # batched re-reduce: stack same-(op,dtype) lanes, one all_gather +
    # one segment op per kind instead of one per lane
    groups: dict = {}
    for i, (lane, op) in enumerate(lanes):
        groups.setdefault((op, lane.dtype), []).append(i)
    merged: list = [None] * len(lanes)
    for (op, _dt), idxs in groups.items():
        if len(idxs) == 1:
            g = lax.all_gather(lanes[idxs[0]][0], ax, tiled=True)
            merged[idxs[0]] = _MERGE[op](g, minv, num_segments=C)
        else:
            stk = jnp.stack([lanes[i][0] for i in idxs], axis=1)
            g = lax.all_gather(stk, ax, tiled=True)
            r = _MERGE[op](g, minv, num_segments=C)
            for j, i in enumerate(idxs):
                merged[i] = r[:, j]

    # -- replicated outputs: every chip holds the full merged table --------
    cnt, h2min, h2max, rep = merged[:4]
    agg_out = tuple(
        tuple(merged[4 + start + i] for i in range(width))
        for start, width in agg_lane_slices)
    return (muniq, cnt, h2min, h2max, rep, agg_out, tot)


class MeshKernelBase:
    """Shared plane plumbing: capacity sizing, shard_map wrapper, probe
    sharding, and the merged-table finalize (capacity / collision
    checks + live-group extraction)."""

    def _setup_sizes(self, mesh: Mesh, capacity: int):
        self.mesh = mesh
        self.ndev = devplane.ndev(mesh)
        # internal table size = requested capacity + 2 headroom slots for
        # the masked-sentinel and fill phantoms (which count as "distinct"
        # but are never live groups)
        self.capacity = max(capacity, 1)
        self._C = self.capacity + 2
        self._row_spec = devplane.batch_spec()

    def _setup_mesh(self, mesh: Mesh, capacity: int, n_extra_args: int = 0):
        self._setup_sizes(mesh, capacity)
        in_specs = (self._row_spec, P()) + (P(),) * n_extra_args
        shard = devplane.shard_map(
            self._kernel, mesh, in_specs=in_specs,
            out_specs=(P(), P(), P(), P(), P(), P(), P()))
        self._jit = devplane.plane_jit(shard, name="meshagg")

    def _shard_probe(self, chunk: Chunk, bucket: bool = False):
        """-> (sharded device cols, padded shard length). The sharded
        transfer is memoized on the chunk (keyed by mesh + padded size):
        cached storage chunks stay resident across re-executions.
        bucket=True pads the shard length to a power-of-two bucket so a
        stream of similar-sized super-batches reuses one compiled shape."""
        n = chunk.num_rows
        ln = -(-max(n, 1) // self.ndev)
        ln += (-ln) % 8
        if bucket:
            ln = runtime.bucket_size(ln)
        # generation (not id(mesh)) keys the memo: a torn-down mesh's id
        # can be recycled by a new Mesh object at the same address
        key = ("shard", devplane.mesh_generation(), ln * self.ndev)
        hit = runtime.dev_cache_get(chunk, key)
        if hit is not None:
            return hit, ln
        cols, _dicts = runtime.device_put_chunk(chunk, size=ln * self.ndev,
                                                to_device=False)
        sh = devplane.batch_sharding(self.mesh)
        cols = jax.device_put(cols, sh)   # one batched sharded transfer
        runtime.dev_cache_put(chunk, key, cols)
        return cols, ln

    def finalize(self, outs):
        """-> (gidx, rep_rows, lanes_at, counts) from the kernel outputs,
        raising on capacity overflow or group-key hash collision. The
        kernel's one output boundary: ONE batched device->host transfer
        for the whole output pytree (per-array reads each pay full
        round-trip latency; see ops/hashagg.py HashAggKernel.__call__)."""
        uniq, cnt, h2min, h2max, rep, agg_out, tot = jax.device_get(outs)
        # tot counts the masked sentinel / fill phantoms; _C holds >= 2
        # headroom slots for them, so tot > _C means possible truncation
        if int(tot) > self._C:
            err = CapacityError(
                f"distinct groups {int(tot)} > capacity {self.capacity}")
            err.needed = int(tot)   # executors re-plan with 2x this
            raise err
        live = (cnt > 0) & (uniq != _SENTINEL_MASKED) & (uniq != _FILL)
        if bool(np.any(live & (h2min != h2max))):
            raise CollisionError("group key hash collision")
        gidx = np.flatnonzero(live)
        rep_rows = rep[gidx]
        lanes_at = [[l[gidx] for l in ls] for ls in agg_out]
        return gidx, rep_rows, lanes_at, cnt[gidx]


class MeshAggKernel(MeshKernelBase):
    """Filter + group-by + aggregation, distributed over the ("batch",)
    device plane.

    One compiled XLA program: per-shard local aggregation, all_gather of
    the group tables across the batch axis, re-reduction to a replicated
    merged table. Rows are sharded as NamedSharding(mesh, P("batch"));
    columns stay separate arrays so int64 keys keep exact bits.
    """

    def __init__(self, mesh: Mesh, filter_expr: Expression | None,
                 group_exprs: Sequence[Expression],
                 aggs: Sequence[AggDesc], capacity: int = 4096):
        self.filter_expr = filter_expr
        self.group_exprs = list(group_exprs)
        self.aggs = list(aggs)
        _validate_device_exprs(filter_expr, self.group_exprs, self.aggs)
        self._setup_mesh(mesh, capacity)

    # -- traced program ------------------------------------------------------

    def _kernel(self, cols, nrows):
        ln = cols[0][0].shape[0]
        xp = jnp
        bi = lax.axis_index(AXIS)
        # lint: exempt[dtype-discipline] global row offsets are exact int64 (shard base can exceed int32 on big superchunks)
        offs = bi.astype(jnp.int64) * ln
        alive = (offs + xp.arange(ln)) < nrows
        mask = runtime.filter_mask_xp(xp, self.filter_expr, cols, ln) & alive
        return group_merge_program(xp, cols, mask, ln, offs,
                                   self.group_exprs, self.aggs, self._C,
                                   self.ndev)

    # -- host driver ---------------------------------------------------------

    def launch(self, chunk: Chunk, bucket: bool = False):
        """Asynchronous half: host→HBM transfer + kernel dispatch. Returns
        an opaque in-flight handle; nothing blocks, so the caller can
        overlap the next batch's transfer with this batch's readback
        (the pipeline_map streaming of executor/mesh.py)."""
        cols, _ln = self._shard_probe(chunk, bucket=bucket)
        return self._jit(cols, jnp.int64(chunk.num_rows))

    def finish(self, outs, chunk: Chunk) -> GroupResult:
        """Blocking half: one batched device→host readback + host tail."""
        gidx, rep_rows, lanes_at, counts = self.finalize(outs)
        return finalize_group_result(chunk, self.group_exprs, self.aggs,
                                     gidx, rep_rows, lanes_at, counts)

    def __call__(self, chunk: Chunk) -> GroupResult:
        return self.finish(self.launch(chunk), chunk)
