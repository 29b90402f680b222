"""Fused pipeline fragments: probe + partial-agg in ONE XLA program.

The per-operator execution of a `scan -> filter -> join-probe ->
partial-agg` pipeline pays two HBM round trips the query never needed:
the matcher writes a static-capacity pair list back to the host, the
host gathers a materialized joined chunk, and the agg re-uploads that
chunk to group it. ProbeAggKernel executes the whole fragment per probe
superchunk in one compiled call (ROADMAP item 4 / arxiv 2603.26698's
partial-aggregate placement):

    1. hash both sides' key lanes and expand the sort-join candidate
       runs into a static-capacity (li, ri) pair list with exact-key
       verification — ops/join.match_pairs, unchanged semantics;
    2. gather ONLY the columns the group/agg expressions read, straight
       from the device-resident padded columns (probe superchunk cols +
       the once-uploaded build cols) at the pair indices — the joined
       intermediate never exists in HBM at full width, and varlen lanes
       stay dictionary codes end-to-end;
    3. run the shared group+partial-agg phase (ops/hashagg.group_partial:
       direct-indexed / runtime-selected / packed-sort group table, one
       batched scatter pass, dual-hash collision check) over the pairs.

Only the group tables return to the host; representative (li, ri) pairs
late-materialize exact group-key values from the two source chunks at
the finalize boundary. Pair-capacity overflow self-heals inside
finalize (regrown program over the SAME device-resident lanes, billed
to the statement's device ledger); capacity/collision misses raise to
the executor, which escalates the fragment kernel once and then falls
back to the decoded per-batch path (match on device, aggregate on
host), counted in tidb_tpu_device_fallback_total.

Gated by `tidb_tpu_fuse_fragments`; engaged by HashAggExec when its
child is a plain inner hash join (executor/__init__.py).
"""

from __future__ import annotations

from typing import Sequence

import numpy as np

import jax
import jax.numpy as jnp

from tidb_tpu import devplane
from tidb_tpu.chunk import Chunk
from tidb_tpu.expression import AggDesc, AggFunc, Expression
from tidb_tpu.ops import runtime
from tidb_tpu.ops.hashagg import (DeviceRejectError, GroupResult,
                                  _validate_device_exprs,
                                  finalize_group_result, group_partial,
                                  _hash_keys)
from tidb_tpu.ops.join import _DEAD_BUILD, _DEAD_PROBE, match_pairs

__all__ = ["ProbeAggKernel", "fragment_kernel_for"]


class _PendingFragment:
    """One in-flight fused dispatch: the padded device-resident lanes
    (probe AND the shared build reference) ride along so a
    pair-capacity overflow retry re-runs WITHOUT re-padding or
    re-transferring anything. The kernel object itself stays stateless
    — it is cached process-wide across plans and sessions."""

    __slots__ = ("build_dev", "nb", "pk", "pcols", "np_", "cap", "res")

    def __init__(self, build_dev, nb, pk, pcols, np_, cap, res):
        self.build_dev = build_dev
        self.nb = nb
        self.pk, self.pcols = pk, pcols
        self.np_ = np_
        self.cap = cap
        self.res = res


class ProbeAggKernel:
    """Compiled probe->partial-agg over one (join keys, joined-schema
    group/agg) fragment signature.

    `group_exprs`/`aggs` reference the JOINED schema: probe columns at
    [0, probe_width), build columns at [probe_width, width). FIRST_ROW
    and GROUP_CONCAT reject (their late-materialize protocol needs
    row-identity lanes the pair space does not preserve) — the executor
    then runs the unfused per-operator path."""

    def __init__(self, num_keys: int, probe_width: int, width: int,
                 group_exprs: Sequence[Expression],
                 aggs: Sequence[AggDesc], capacity: int = 4096,
                 force_hash: bool = False, direct_limit=None):
        self.num_keys = num_keys
        self.probe_width = probe_width
        self.width = width
        self.group_exprs = list(group_exprs)
        self.aggs = list(aggs)
        self.capacity = capacity
        self.force_hash = force_hash
        self.direct_limit = direct_limit
        for a in self.aggs:
            if a.fn in (AggFunc.FIRST_ROW, AggFunc.GROUP_CONCAT):
                raise DeviceRejectError(
                    f"{a.fn} needs row identity at finalize; the fused "
                    f"fragment carries only pair indices")
        _validate_device_exprs(None, self.group_exprs, self.aggs)
        used = set()
        for g in self.group_exprs:
            used |= g.columns_used()
        for a in self.aggs:
            if a.arg is not None:
                used |= a.arg.columns_used()
        if any(j >= width for j in used):
            raise DeviceRejectError("agg reads past the joined schema")
        self.probe_used = sorted(j for j in used if j < probe_width)
        self.build_used = sorted(j for j in used if j >= probe_width)
        self._jit = jax.jit(devplane.named(self._kernel, "fragment"),
                            static_argnames=("out_cap",))

    # -- traced program ------------------------------------------------------

    def _kernel(self, bkeys, pkeys, pcols, bcols, nb, np_, out_cap):
        xp = jnp
        b_n = bkeys[0][0].shape[0]
        p_n = pkeys[0][0].shape[0]
        b_valid = xp.arange(b_n) < nb
        for _d, v in bkeys:
            b_valid = b_valid & v
        p_valid = xp.arange(p_n) < np_
        for _d, v in pkeys:
            p_valid = p_valid & v
        hb = _hash_keys(xp, [(d, v & b_valid) for d, v in bkeys],
                        b_n, seed=0x9E3779B97F4A7C15)
        hp = _hash_keys(xp, [(d, v & p_valid) for d, v in pkeys],
                        p_n, seed=0x9E3779B97F4A7C15)
        hb = xp.where(b_valid, hb, _DEAD_BUILD)
        hp = xp.where(p_valid, hp, _DEAD_PROBE)
        li, ri, ok, total = match_pairs(
            xp, hb, hp, [d for d, _v in bkeys],
            [d for d, _v in pkeys], out_cap)
        # the joined row never materializes at full width: only the
        # lanes the group/agg expressions read are gathered, straight
        # from the device-resident padded columns
        joined = [None] * self.width
        for lane, j in enumerate(self.probe_used):
            d, v = pcols[lane]
            joined[j] = (d[li], v[li] & ok)
        for lane, j in enumerate(self.build_used):
            d, v = bcols[lane]
            joined[j] = (d[ri], v[ri] & ok)
        uniq, nuniq, collided, counts, rep, lanes, dense = group_partial(
            xp, self.group_exprs, self.aggs, joined, out_cap, ok,
            self.capacity, force_hash=self.force_hash,
            direct_limit=self.direct_limit)
        # representative PAIRS (not pair indices) return to the host:
        # finalize gathers exact group-key values from the two source
        # chunks without ever reading the full li/ri buffers back
        repc = xp.clip(rep, 0, out_cap - 1)
        return (uniq, nuniq, collided, counts, li[repc], ri[repc],
                lanes, total, dense)

    # -- sizing (device-ledger billing, from shapes alone) -------------------

    def _build_sub(self, build: Chunk) -> Chunk:
        return Chunk([build.columns[j - self.probe_width]
                      for j in self.build_used])

    def build_nbytes(self, build: Chunk, nb: int) -> int:
        """HBM bytes the once-per-probe build residency stages: the USED
        build columns (varlen as int64 codes + validity) plus the padded
        key lanes."""
        from tidb_tpu import memtrack
        bb = runtime.bucket_size(max(nb, 1))
        return memtrack.device_put_bytes(self._build_sub(build), bb) + \
            self.num_keys * 9 * bb

    def _probe_sub(self, chunk: Chunk) -> Chunk:
        return Chunk([chunk.columns[j] for j in self.probe_used])

    def input_nbytes(self, chunk: Chunk) -> int:
        """HBM bytes of one dispatch's INPUT lanes: only the probe
        columns the group/agg expressions read (the rest never ship),
        plus the padded key lanes — the bytes_touched figure."""
        from tidb_tpu import memtrack
        pb = runtime.bucket_size(max(chunk.num_rows, 1))
        return memtrack.device_put_bytes(self._probe_sub(chunk), pb) + \
            self.num_keys * 9 * pb

    def dispatch_nbytes(self, chunk: Chunk, out_cap: int) -> int:
        """HBM bytes one fused dispatch stages: used probe columns +
        key lanes, the pair buffers, and the group-table scratch."""
        return self.input_nbytes(chunk) + out_cap * 17 + \
            self.capacity * 8 * (5 + 2 * len(self.aggs))

    # -- async dispatch / blocking finalize ----------------------------------

    def prepare_build(self, build: Chunk, build_keys, nb: int):
        """Upload the build side once for the whole probe: padded key
        lanes + the USED build columns (dict-encoded, padded). ->
        (bkeys_dev, bcols_dev), reused by every dispatch."""
        bb = runtime.bucket_size(max(nb, 1))
        bkeys = runtime.put_lanes(build_keys, bb)
        bcols, _dicts = runtime.device_put_chunk(
            self._build_sub(build), bb, memo=False) \
            if self.build_used else ([], {})
        return bkeys, bcols

    def dispatch(self, build_dev, nb: int, probe_keys, chunk: Chunk,
                 np_: int, out_cap: int | None = None) -> _PendingFragment:
        """Async half: pad + transfer the probe superchunk (used columns
        only reach the program) and enqueue the fused program — no sync,
        the pipeline's overlap point. `build_dev` is prepare_build's
        once-uploaded result, shared across every probe batch."""
        bkeys, bcols = build_dev
        pb = runtime.bucket_size(max(np_, 1))
        cap = out_cap or runtime.bucket_size(max(np_ * 2, 1024))
        pk = runtime.put_lanes(probe_keys, pb)
        # only the USED probe columns ship — the kernel reads nothing
        # else, and the key lanes already ride pk
        pcols, _dicts = runtime.device_put_chunk(
            self._probe_sub(chunk), pb, memo=False) \
            if self.probe_used else ([], {})
        res = self._jit(bkeys, pk, pcols, bcols, nb, np_, out_cap=cap)
        return _PendingFragment(build_dev, nb, pk, pcols, np_, cap, res)

    def finalize(self, probe_chunk: Chunk, build: Chunk, nb: int,
                 p: _PendingFragment) -> GroupResult:
        """Blocking half: read the pair total first (a scalar — an
        overflow retry then regrows the program over the SAME resident
        lanes without transferring the dead buffers), then one batched
        device->host read of the group tables, then the host
        late-materialize tail."""
        from tidb_tpu import memtrack
        from tidb_tpu.ops.hashagg import (CapacityError, CollisionError,
                                          count_dispatch)
        root = memtrack.current()
        extra = 0
        try:
            while True:
                total = int(jax.device_get(p.res[7]))
                if total <= p.cap:
                    break
                new_cap = runtime.bucket_size(total)
                if root is not None:
                    grow = (new_cap - p.cap) * 17
                    extra += grow       # before consume: it may raise
                    root.consume(device=grow)
                p.cap = new_cap
                bkeys, bcols = p.build_dev
                p.res = self._jit(bkeys, p.pk, p.pcols, bcols, p.nb,
                                  p.np_, out_cap=p.cap)
            (uniq, nuniq, collided, counts, rep_li, rep_ri, lanes,
             _total, dense) = jax.device_get(p.res)
        finally:
            if root is not None and extra:
                root.release(device=extra)
        count_dispatch(dense)
        if int(nuniq) > self.capacity:
            err = CapacityError(f"distinct groups {int(nuniq)} > "
                                f"capacity {self.capacity}")
            err.needed = int(nuniq)
            raise err
        if bool(collided):
            raise CollisionError("fused group key hash collision")
        from tidb_tpu.ops.hashagg import _FILL, _SENTINEL_MASKED
        live = (counts > 0) & (uniq != _SENTINEL_MASKED) & (uniq != _FILL)
        gidx = np.flatnonzero(live)
        lanes_at = [[lane[gidx] for lane in ls] for ls in lanes]
        # late materialization: gather ONLY the representative joined
        # rows from the two source chunks (strings decode here, at the
        # operator-output boundary, never inside the fragment)
        pli = np.clip(rep_li[gidx], 0, max(probe_chunk.num_rows - 1, 0))
        pri = np.clip(rep_ri[gidx], 0, max(nb - 1, 0))
        rep_chunk = Chunk(probe_chunk.take(pli).columns +
                          build.take(pri).columns)
        order = np.arange(len(gidx), dtype=np.int64)
        return finalize_group_result(rep_chunk, self.group_exprs,
                                     self.aggs, order, order, lanes_at,
                                     counts[gidx])

# process-wide fragment-kernel cache, keyed on the structural identity
# of the whole fragment (join-key arity, schema split, group/agg
# fingerprint, table capacity and the degrade bounds) — a re-created
# plan reuses the traced program instead of re-tracing it
_FRAGMENTS = runtime.FingerprintCache(32)


def fragment_kernel_for(num_keys: int, probe_width: int, width: int,
                        group_exprs, aggs, capacity: int = 4096):
    """ProbeAggKernel with process-wide reuse; raises DeviceRejectError
    (or ValueError) when the fragment is not device-safe — the caller
    then keeps the per-operator path."""
    from tidb_tpu import config
    from tidb_tpu.ops.hashagg import _direct_group_mode
    direct_limit = config.direct_agg_slots()
    force_hash = capacity > direct_limit and \
        _direct_group_mode(group_exprs)

    from tidb_tpu import profiler
    made = []

    def make():
        made.append(1)
        return ProbeAggKernel(num_keys, probe_width, width, group_exprs,
                              aggs, capacity=capacity,
                              force_hash=force_hash,
                              direct_limit=direct_limit)

    fp = runtime.plan_fingerprint(None, group_exprs, aggs)
    if fp is None:
        k = make()
        prof = profiler.profile("fragment", None)
        profiler.note_construct(prof, reuse=False)
        k._profile = prof
        return k
    from tidb_tpu import devplane
    key = (fp, num_keys, probe_width, width, capacity, force_hash,
           direct_limit, devplane.mesh_fingerprint(process=True))
    k = _FRAGMENTS.get_or_create(key, make)
    prof = profiler.profile(
        "fragment", f"{fp}|{num_keys}|{probe_width}|{width}|{capacity}"
                    f"|{force_hash}|{direct_limit}")
    profiler.note_construct(prof, reuse=not made)
    k._profile = prof
    return k
