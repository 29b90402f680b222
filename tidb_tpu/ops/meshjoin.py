"""Distributed star-join + aggregation pipeline on the one device plane.

The reference executes Q3/Q5-shaped plans as a chain of HashJoinExecs
(executor/join.go:37: build a hash table per join, probe row-at-a-time in
goroutines) feeding a HashAggExec. On the device plane the idiomatic
program is one fused XLA computation per probe shard:

    probe rows sharded over ("batch",)    [the fact table: lineitem]
    build tables replicated on every chip [the dimension tables]
    filter -> lookup chain -> group-by aggregate -> all_gather merge

Each lookup is a bounded open-addressing probe against the dimension
table's packed hash slots plus an exact-bits verify — the join never
materializes: matched rows flow straight into the aggregation, so HBM
traffic is one pass over the probe shard. Build keys must be unique
(dimension tables: customer, orders, nation, ...); the executor layer
falls back to the host hash join otherwise. Replicating the small build
side and sharding the large probe side is the skew-free co-location
placement (JSPIM, arxiv 2508.08503): no probe row ever leaves its chip.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Sequence

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from tidb_tpu import devplane
from tidb_tpu.chunk import Chunk, Column
from tidb_tpu.devplane import AXIS
from tidb_tpu.expression import AggDesc, AggFunc, Expression
from tidb_tpu.ops import runtime
from tidb_tpu.ops.hashagg import (_hash_keys, _key_bits, _splitmix,
                                  _validate_device_exprs,
                                  finalize_group_result)
from tidb_tpu.ops.meshagg import MeshKernelBase, group_merge_program

__all__ = ["LookupSpec", "MeshLookupAggKernel", "BuildError",
           "host_lookup_agg"]

_KEY_SEED = 0x9E6D55A3C1B70F27


def _lookup_hash(xp, key_cols, n):
    """Join-key hash WITHOUT the NULL-validity lane of _hash_keys: build
    keys are NULL-free by construction and NULL probe rows are masked
    out by `hit & v`, so mixing validity would only double the hash
    cost. Half the splitmix rounds of the group-key hash."""
    import jax.numpy as jnp
    ut = jnp.uint64 if xp is not np else np.uint64
    h = xp.full(n, np.uint64(_KEY_SEED), dtype=ut)
    for d, _v in key_cols:
        h = _splitmix(xp, h ^ _key_bits(xp, d))
    # lint: exempt[dtype-discipline] row hashes are int64 by contract (splitmix64 bit patterns, sentinel headroom)
    return h.astype(jnp.int64 if xp is not np else np.int64)


class BuildError(Exception):
    """Build side unusable for the lookup kernel (dup/NULL keys, strings
    in key columns, hash collision) — caller falls back to the host join."""


@dataclass
class LookupSpec:
    """One dimension-table lookup in the chain.

    key_exprs index the CURRENT virtual schema (probe columns, then the
    payloads of earlier lookups, in order). build_key_offsets/payload
    offsets index build_chunk's columns; payload columns are appended to
    the virtual schema for later key_exprs / group_exprs / aggs."""

    key_exprs: list
    build_chunk: Chunk
    build_key_offsets: list[int]
    payload_offsets: list[int] = field(default_factory=list)


_EMPTY_SLOT = np.int64((1 << 63) - 1)   # _hash_keys never emits it


class _BuildTable:
    """Host-prepared replicated lookup table: an open-addressing hash
    table over the key hashes (load factor <= 0.25, linear probing with
    a KNOWN max displacement so the device probe is a statically
    unrolled gather chain — no sort, no searchsorted), exact key bit
    lanes, payload lanes (strings dict-encoded for the device; original
    values kept for host finalize)."""

    def __init__(self, spec: LookupSpec):
        ch = spec.build_chunk
        keys = [ch.columns[o] for o in spec.build_key_offsets]
        n = ch.num_rows
        valid = np.ones(n, dtype=bool)
        for k in keys:
            valid &= np.asarray(k.valid)
        if not valid.all():
            # NULL join keys never match anything: drop them here
            ch = ch.filter(valid)
            keys = [ch.columns[o] for o in spec.build_key_offsets]
            n = ch.num_rows
        key_lanes = []
        for k in keys:
            if k.data.dtype == np.dtype(object):
                raise BuildError("string build keys need the host join")
            key_lanes.append((np.asarray(k.data),
                              np.ones(n, dtype=bool)))
        h = _lookup_hash(np, key_lanes, n)
        if n > 1:
            hs = np.sort(h)
            if (hs[1:] == hs[:-1]).any():
                # duplicate hash: either duplicate keys (not a dimension
                # table) or a 2^-64 collision — both go to the host join
                raise BuildError("duplicate build keys / hash collision")
        self.chunk = ch                         # NULL-free build rows
        self.n = n
        self._insert(h)
        self.key_bits = [np.asarray(_key_bits(np, d))
                         for d, _v in key_lanes]
        self.pay_data = []
        self.pay_valid = []
        for o in spec.payload_offsets:
            c = ch.columns[o]
            d = np.asarray(c.data)
            if d.dtype == np.dtype(object):
                # lint: exempt[memtrack-alloc] build-side encode scratch bounded by the build rows the executor bills via device_scope at launch
                codes = np.empty(n, dtype=np.int64)
                seen: dict = {}
                for i, v in enumerate(d):
                    codes[i] = seen.setdefault(v, len(seen))
                d = codes
            self.pay_data.append(d)
            self.pay_valid.append(np.asarray(c.valid))
        self._key_lanes = key_lanes
        self._row_by_key = None
        self._dev = None

    def _insert(self, h: np.ndarray) -> None:
        """Vectorized round-based insertion: round d places every pending
        key whose slot (base+d) is free, first writer per slot wins. The
        final round count bounds every key's displacement, so lookups
        probe exactly `probe_depth` slots.

        Slots PACK (quantized hash | row index) into one int64 — one
        gather per probe step instead of two (random gathers dominate the
        probe cost). Probe hits compare the quantized top bits; the
        existing exact key-bits verify makes quantization merges
        harmless (they can only produce false candidates, which the
        verify rejects)."""
        n = len(h)
        M = 1 << max(int(2 * max(n, 1) - 1).bit_length(), 4)
        bits = max(1, int(max(n, 1) - 1).bit_length()) if n > 1 else 1
        B = np.int64(bits)
        hq = (h >> B) << B
        slot_pack = np.full(M, _EMPTY_SLOT, dtype=np.int64)
        # reserve the empty-marker's quantum so no packed value can
        # alias it (quantized _EMPTY_SLOT has the row bits free)
        eq = (_EMPTY_SLOT >> B) << B
        hq = np.where(hq == eq, eq - (np.int64(1) << B), hq)
        if n > 1:
            sq = np.sort(hq)
            if (sq[1:] == sq[:-1]).any():
                # two build keys share a quantized hash: the probe's
                # first-match-wins walk could stop at the wrong slot
                raise BuildError("quantized hash collision")
        base = h & np.int64(M - 1)
        pending = np.arange(n)
        d = 0
        while pending.size:
            if d > 64:
                raise BuildError("pathological hash clustering")
            cand = (base[pending] + d) & (M - 1)
            empty = slot_pack[cand] == _EMPTY_SLOT
            marked = np.where(empty, cand, -1)
            uniq, first = np.unique(marked, return_index=True)
            win = np.zeros(len(pending), dtype=bool)
            win[first[uniq >= 0]] = True
            win &= empty
            wi = pending[win]
            slot_pack[cand[win]] = hq[wi] | wi
            pending = pending[~win]
            d += 1
        self.slot_pack = slot_pack
        self.hash_quantum_bits = bits
        self.table_size = M
        self.probe_depth = max(d, 1)

    @property
    def row_by_key(self) -> dict:
        """Host-side exact map for finalize / reference impl, keyed in the
        chunk-layer value domain (raw int64/float64; decimals scaled) to
        match host expression eval output. Built lazily — the device path
        only touches it for a handful of representative rows, and a large
        dimension table (orders at SF>=1) costs seconds to enumerate."""
        if self._row_by_key is None:
            m = {}
            for i in range(self.n):
                m[tuple(d[i].item() for d, _v in self._key_lanes)] = i
            self._row_by_key = m
        return self._row_by_key

    def device_arrays(self, sharding=None):
        """Build lanes on device (replicated under `sharding`), memoized:
        one batched device_put on first use, zero transfer when a cached
        kernel re-executes against unchanged dimension data. Keyed by the
        mesh GENERATION (id(mesh) could be recycled after a reconfigure)."""
        key = devplane.mesh_generation() if sharding is not None else None
        if self._dev is None or self._dev[0] != key:
            tree = (self.slot_pack, tuple(self.key_bits),
                    tuple(self.pay_data), tuple(self.pay_valid))
            self._dev = (key, jax.device_put(tree, sharding))
        return self._dev[1]


def _probe_build(xp, bt, b, key_cols, ph, mask, ln):
    """Shared traced probe of one build table -> (hit, row)."""
    slot_pack, key_bits, _pay_data, _pay_valid = b
    hit = mask
    for d, v in key_cols:
        hit = hit & v                   # NULL keys match nothing
    if bt.n == 0:
        return hit & False, xp.zeros(ln, dtype=jnp.int32)
    # open-addressing probe, ONE packed gather per step, with a GLOBAL
    # early exit: the while_loop stops as soon as every row found its
    # slot (or proved absence), so the typical batch pays ~2 steps
    # instead of the worst-case displacement. Random gathers are the
    # dominant cost on both backends.
    M1 = np.int64(bt.table_size - 1)
    B = np.int64(bt.hash_quantum_bits)
    Q = np.int64(1) << B
    eq = (_EMPTY_SLOT >> B) << B
    phq = (ph >> B) << B
    phq = xp.where(phq == eq, eq - Q, phq)
    base = ph & M1
    empty = np.int64(int(_EMPTY_SLOT))

    def probe_step(st):
        j, row, found, done = st
        cand = (base + j) & M1
        pk = slot_pack[cand]
        newhit = (~done) & (((pk >> B) << B) == phq)
        row = xp.where(newhit, (pk & (Q - 1)).astype(jnp.int32), row)
        found = found | newhit
        # an empty slot on the probe path proves absence
        done = done | newhit | (pk == empty)
        return j + np.int64(1), row, found, done

    def probe_cond(st):
        j, _row, _found, done = st
        return (j < bt.probe_depth) & ~done.all()

    _j, row, found, _done = lax.while_loop(
        probe_cond, probe_step,
        (jnp.int64(0), xp.zeros(ln, dtype=jnp.int32),
         xp.zeros(ln, dtype=bool), xp.zeros(ln, dtype=bool)))
    hit = hit & found
    # exact verify: quantized-hash equality is not key equality
    for (d, _v), bb in zip(key_cols, key_bits):
        hit = hit & (_key_bits(xp, d) == bb[row])
    return hit, row


def _lookup_step(xp, lk, bt, b, virt, mask, ln):
    """One lookup of the chain: probe + payload appends -> new mask."""
    _slot, _kb, pay_data, pay_valid = b
    key_cols = [e.eval_xp(xp, virt, ln) for e in lk.key_exprs]
    ph = _lookup_hash(xp, key_cols, ln)
    hit, row = _probe_build(xp, bt, b, key_cols, ph, mask, ln)
    safe = xp.where(hit, row, 0)
    appended = [(d[safe], v[safe] & hit)
                for d, v in zip(pay_data, pay_valid)]
    if not appended:
        return hit
    # materialize between lookups: without the barrier XLA's producer-
    # consumer fusion re-evaluates the whole gather chain once per
    # downstream use (measured 3-4x on Q5's lookup chain, CPU backend)
    barred = lax.optimization_barrier(
        (hit, tuple(x for pair in appended for x in pair)))
    flat = barred[1]
    for i in range(0, len(flat), 2):
        virt.append((flat[i], flat[i + 1]))
    return barred[0]


class MeshLookupAggKernel(MeshKernelBase):
    """filter -> unique-key lookup chain -> group-by agg over the device
    plane, in TWO compiled stages with a compaction between them:

      stage 1: filter + FIRST lookup, then prefix-sum compaction of the
               surviving rows (the first lookup is usually the selective
               one — a filtered dimension like orders-by-date kills most
               fact rows, exactly like the reference's first HashJoin).
      stage 2: remaining lookups + group-by agg over the compacted rows,
               padded to a power-of-two bucket so a handful of compiled
               shapes serve any selectivity.

    Static XLA shapes cannot shrink mid-program, so without the split
    every lookup and the aggregation pay full-width work regardless of
    selectivity; the split costs one scalar device->host sync (the
    survivor count) and wins the whole compaction factor on everything
    after the first probe. Original probe row indices ride along as a
    column so representative-row finalize is unchanged."""

    def __init__(self, mesh: Mesh, filter_expr: Expression | None,
                 lookups: Sequence[LookupSpec],
                 group_exprs: Sequence[Expression],
                 aggs: Sequence[AggDesc], capacity: int = 4096,
                 builds: list | None = None):
        self.mesh = mesh
        self.filter_expr = filter_expr
        self.lookups = list(lookups)
        self.group_exprs = list(group_exprs)
        self.aggs = list(aggs)
        _validate_device_exprs(filter_expr, self.group_exprs, self.aggs)
        for lk in self.lookups:
            _validate_device_exprs(None, lk.key_exprs, [])
        self.builds = builds if builds is not None \
            else [_BuildTable(lk) for lk in self.lookups]
        self._setup_sizes(mesh, capacity)
        self._stage1_jit = None
        self._stage2_jits: dict = {}
        self._stage3_jits: dict = {}

    # -- traced programs -----------------------------------------------------

    def _compact(self, xp, virt, mask, row_ids, ln):
        """Prefix-sum compaction of the surviving rows ->
        (compacted (data, valid) pairs, live flag, row ids, global max
        survivor count)."""
        s_local = mask.sum()
        pos = xp.cumsum(mask.astype(jnp.int32)) - 1
        idx = xp.where(mask, pos, ln)      # OOB -> dropped by scatter
        compacted = []
        for d, v in virt:
            cd = xp.zeros(ln, dtype=d.dtype).at[idx].set(d, mode="drop")
            cv = xp.zeros(ln, dtype=bool).at[idx].set(v, mode="drop")
            compacted.append((cd, cv))
        live = xp.zeros(ln, dtype=bool).at[idx].set(mask, mode="drop")
        # lint: exempt[dtype-discipline] compacted row ids stay exact int64 (global offsets exceed int32)
        rid = xp.zeros(ln, dtype=jnp.int64).at[idx].set(row_ids,
                                                        mode="drop")
        smax = s_local if self.ndev == 1 else \
            devplane.pmax(s_local, (AXIS,))
        return tuple(compacted), live, rid, smax

    def _stage1(self, cols, nrows, build0):
        """filter + first lookup + compaction."""
        ln = cols[0][0].shape[0]
        xp = jnp
        bi = lax.axis_index(AXIS)
        # lint: exempt[dtype-discipline] global row offsets are exact int64 (shard base can exceed int32 on big superchunks)
        offs = bi.astype(jnp.int64) * ln
        alive = (offs + xp.arange(ln)) < nrows
        mask = runtime.filter_mask_xp(xp, self.filter_expr, cols, ln) & alive
        virt = list(cols)
        mask = _lookup_step(xp, self.lookups[0], self.builds[0], build0,
                            virt, mask, ln)
        row_ids = offs + xp.arange(ln)
        return self._compact(xp, virt, mask, row_ids, ln)

    def _stage2_fn(self, bucket: int):
        """Remaining lookups, then compact AGAIN: the chain's total
        selectivity (a 20% dimension filter deep in a star join) shrinks
        the aggregation's input — the group-table sort is the next cost
        center after the probes."""
        def stage2(ccols, live, rid, builds_rest):
            xp = jnp
            b = bucket
            virt = [(d[:b], v[:b]) for d, v in ccols]
            mask = live[:b]
            rids = rid[:b]
            for lk, bt, bd in zip(self.lookups[1:], self.builds[1:],
                                  builds_rest):
                mask = _lookup_step(xp, lk, bt, bd, virt, mask, b)
            return self._compact(xp, virt, mask, rids, b)
        return stage2

    def _stage3_fn(self, bucket: int):
        def stage3(ccols, live, rid):
            xp = jnp
            b = bucket
            virt = [(d[:b], v[:b]) for d, v in ccols]
            return group_merge_program(
                xp, virt, live[:b], b, jnp.int64(0),
                self.group_exprs, self.aggs, self._C, self.ndev,
                row_ids=rid[:b])
        return stage3

    # -- host driver ---------------------------------------------------------

    def _get_stage1(self):
        if self._stage1_jit is None:
            sm = devplane.shard_map(
                self._stage1, self.mesh,
                in_specs=(self._row_spec, P(), P()),
                out_specs=(self._row_spec, self._row_spec,
                           self._row_spec, P()))
            self._stage1_jit = devplane.plane_jit(sm, name="meshjoin")
        return self._stage1_jit

    def _get_stage2(self, bucket: int):
        j = self._stage2_jits.get(bucket)
        if j is None:
            sm = devplane.shard_map(
                self._stage2_fn(bucket), self.mesh,
                in_specs=(self._row_spec, self._row_spec,
                          self._row_spec, P()),
                out_specs=(self._row_spec, self._row_spec,
                           self._row_spec, P()))
            j = self._stage2_jits[bucket] = devplane.plane_jit(
                sm, name="meshjoin")
        return j

    def _get_stage3(self, bucket: int):
        j = self._stage3_jits.get(bucket)
        if j is None:
            sm = devplane.shard_map(
                self._stage3_fn(bucket), self.mesh,
                in_specs=(self._row_spec, self._row_spec,
                          self._row_spec),
                out_specs=(P(), P(), P(), P(), P(), P(), P()))
            j = self._stage3_jits[bucket] = devplane.plane_jit(
                sm, name="meshjoin")
        return j

    @staticmethod
    def _bucket(s: int, ln: int) -> int:
        b = 8
        while b < s:
            b <<= 1
        return min(b, ln)

    def launch(self, probe: Chunk, bucket: bool = False):
        """Dispatches stage 1 (filter + first lookup + compact), reads
        back one survivor-count scalar, dispatches stage 2 (remaining
        lookups + compact), reads one more, then stage 3 (aggregation)
        on the chain-selectivity-sized bucket. Build tables are
        device-memoized by _BuildTable.device_arrays, so per-batch
        launches re-send nothing."""
        cols, ln = self._shard_probe(probe, bucket=bucket)
        rep_sh = devplane.replicated(self.mesh)
        builds = tuple(b.device_arrays(rep_sh) for b in self.builds)
        ccols, live, rid, smax = self._get_stage1()(
            cols, jnp.int64(probe.num_rows), builds[0])
        bkt = self._bucket(int(smax), ln)
        if len(self.lookups) > 1:
            ccols, live, rid, smax2 = self._get_stage2(bkt)(
                ccols, live, rid, builds[1:])
            bkt = self._bucket(int(smax2), bkt)
        return self._get_stage3(bkt)(ccols, live, rid)

    def finish(self, outs, probe: Chunk):
        gidx, rep_rows, lanes_at, counts = self.finalize(outs)
        return self._finalize(probe, gidx, rep_rows, lanes_at, counts)

    def __call__(self, probe: Chunk):
        return self.finish(self.launch(probe), probe)

    def _finalize(self, probe: Chunk, gidx, rep_rows, lanes_at, counts):
        """Re-run the lookup chain on the handful of representative rows
        (and FIRST_ROW rows) host-side so group keys / first values come
        back as exact original values, strings included."""
        needed = set(int(r) for r in rep_rows)
        for a, ls in zip(self.aggs, lanes_at):
            if a.fn == AggFunc.FIRST_ROW:
                for i, has in zip(ls[0], ls[1]):
                    if has > 0:
                        needed.add(int(i))
        order = sorted(needed)
        pos = {g: i for i, g in enumerate(order)}
        mini = self._host_chain(probe.take(np.array(order, dtype=np.int64)))
        rep_local = np.array([pos[int(r)] for r in rep_rows],
                             dtype=np.int64)
        fixed_lanes = []
        for a, ls in zip(self.aggs, lanes_at):
            if a.fn == AggFunc.FIRST_ROW:
                idx = np.array([pos.get(int(i), 0) for i in ls[0]],
                               dtype=np.int64)
                fixed_lanes.append([idx, ls[1]])
            else:
                fixed_lanes.append(ls)
        return finalize_group_result(mini, self.group_exprs, self.aggs,
                                     rep_local, fixed_lanes, counts)

    def _host_chain(self, mini: Chunk) -> Chunk:
        """Append payload columns for the (matched) mini rows on the host,
        with original (undecoded) build values."""
        out_cols = list(mini.columns)
        for lk, b in zip(self.lookups, self.builds):
            virt = Chunk(out_cols)
            n = virt.num_rows
            keyvals = []
            for e in lk.key_exprs:
                d, v = e.eval(virt)
                keyvals.append([None if not v[i] else
                                (d[i].item() if hasattr(d[i], "item")
                                 else d[i]) for i in range(n)])
            rows = []
            for i in range(n):
                rows.append(b.row_by_key.get(
                    tuple(kv[i] for kv in keyvals)))
            for o in lk.payload_offsets:
                src = b.chunk.columns[o]
                vals = [None if r is None else src.get(r) for r in rows]
                out_cols.append(Column.from_values(src.ft, vals))
        return Chunk(out_cols)


def host_lookup_agg(probe: Chunk, filter_expr, lookups: Sequence[LookupSpec],
                    group_exprs, aggs, builds=None):
    """Pure-host reference implementation (ground truth for tests, the
    dryrun cross-check, and the per-batch fallback of the streaming mesh
    path — which passes its prebuilt `builds` so dimension hash tables
    are not rebuilt per batch)."""
    from tidb_tpu.ops.hostagg import host_hash_agg
    mask = runtime.eval_filter_host(filter_expr, probe)
    ch = probe.filter(mask)
    if builds is None:
        builds = [_BuildTable(lk) for lk in lookups]
    cols = list(ch.columns)
    for lk, b in zip(lookups, builds):
        virt = Chunk(cols)
        n = virt.num_rows
        keyvals = []
        for e in lk.key_exprs:
            d, v = e.eval(virt)
            keyvals.append([None if not v[i] else
                            (d[i].item() if hasattr(d[i], "item") else d[i])
                            for i in range(n)])
        # lint: exempt[memtrack-alloc] host-fallback row gather bounded by the probe chunk the statement already tracks
        rows = np.empty(n, dtype=object)
        keep = np.zeros(n, dtype=bool)
        for i in range(n):
            r = b.row_by_key.get(tuple(kv[i] for kv in keyvals))
            rows[i] = r
            keep[i] = r is not None
        cols = [c.take(np.flatnonzero(keep)) for c in cols]
        matched = [int(r) for r in rows[keep]]
        for o in lk.payload_offsets:
            src = b.chunk.columns[o]
            cols.append(Column.from_values(
                src.ft, [src.get(r) for r in matched]))
    combined = Chunk(cols)
    return host_hash_agg(combined, None, group_exprs, aggs)
