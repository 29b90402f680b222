"""Hash aggregation on device: filter + group-by + partial agg in one XLA
program.

Replaces /root/reference/executor/aggregate.go:32-57 (HashAggExec over an
mvmap hash table, row-at-a-time aggCtx updates) and the storage-side agg of
mocktikv/aggregate.go. The dynamic hash table becomes a TPU-friendly
sort-based group-by (SURVEY.md §7 "Device hash tables", Plan A):

    1. give every row a slot: dictionary / small-range keys index slots
       directly by their codes; anything else mixes the key lanes into a
       64-bit hash and ONE packed sort (_group_table) yields the group
       table, the rows' slots and the true distinct count (static
       shapes; capacity overflow detected and surfaced). Masked rows
       land in a sentinel slot
    2. reduce every partial-state lane per slot (_SegBatch): a masked
       reduction over the row axis per slot while the block's slots in
       use are few, jax.ops.segment_* scatters otherwise, chosen at run
       time from the count step 1 already has
    3. a second independent hash verifies per-group key agreement, so a
       64-bit collision is *detected* (collision -> caller falls back to
       the host path) rather than silently merging groups

Partial states follow expression/agg.py's protocol, so chunk partials merge
on the host (or across a mesh with psum) exactly like the reference's
partial/final agg split.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Sequence

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from tidb_tpu import devplane, metrics
from tidb_tpu.chunk import Chunk
from tidb_tpu.expression import AggDesc, AggFunc, Expression
from tidb_tpu.ops import runtime
from tidb_tpu.sqltypes import EvalType

__all__ = ["AggSpec", "HashAggKernel", "ScalarAggKernel", "HashAggregator",
           "CapacityError", "CollisionError", "DeviceRejectError",
           "GroupResult", "finalize_group_result", "kernel_for",
           "group_partial", "count_dispatch"]

AggSpec = AggDesc  # the planner's descriptor doubles as the kernel spec

_SENTINEL_MASKED = np.int64(-(1 << 63))        # all filtered-out rows
_FILL = np.int64((1 << 63) - 1)                # unique() padding
_I64_MAX = np.int64((1 << 63) - 1)
_I64_MIN = np.int64(-(1 << 63))

# golden-ratio mixing constants (splitmix64, public domain)
_MIX1 = np.uint64(0xBF58476D1CE4E5B9)
_MIX2 = np.uint64(0x94D049BB133111EB)
_GOLD = np.uint64(0x9E3779B97F4A7C15)


class CapacityError(Exception):
    """More groups than the kernel's static capacity: re-plan with a larger
    capacity or fall back to the host path."""


class CollisionError(Exception):
    """Two distinct key tuples collided in 64-bit hash space (detected by
    the check hash); fall back to the host path."""


class DeviceRejectError(ValueError):
    """The plan is not device-safe BY DESIGN (string computation, host-
    only aggregate): the designed device->host fallback signal. A
    ValueError subclass so legacy `except ValueError` handlers keep
    working — but fallback nets should catch THIS, so a genuine kernel
    bug raising a bare ValueError surfaces instead of masquerading as a
    capacity miss."""


def _splitmix(xp, h):
    h = xp.asarray(h).astype(jnp.uint64) if xp is jnp else h.astype(np.uint64)
    h = (h + _GOLD)
    h = (h ^ (h >> np.uint64(30))) * _MIX1
    h = (h ^ (h >> np.uint64(27))) * _MIX2
    h = h ^ (h >> np.uint64(31))
    return h


def _key_bits(xp, d):
    """Exact uint64 bit pattern of a key lane: floats are bitcast (value
    cast would truncate 2.3 and 2.7 to the same hash under BOTH seeds,
    silently merging groups), with -0.0 normalized to +0.0 first since
    SQL treats them as equal."""
    ut = jnp.uint64 if xp is jnp else np.uint64
    d = xp.asarray(d)
    if d.dtype == (jnp.float64 if xp is jnp else np.float64):
        d = xp.where(d == 0.0, 0.0, d)
        if xp is jnp:
            return jax.lax.bitcast_convert_type(d, jnp.uint64)
        return d.view(np.uint64)
    return d.astype(ut)


# lint: exempt[dtype-discipline] row hashes are int64 by contract: splitmix64 bit patterns, sentinel headroom at both int64 extremes
def _hash_keys(xp, key_cols, n, seed: int):
    """Combine (data, valid) key lanes into one int64 hash per row.
    NULL contributes a distinct tag so NULL groups separately from 0."""
    h = xp.full(n, np.uint64(seed), dtype=jnp.uint64 if xp is jnp else np.uint64)
    for d, v in key_cols:
        u = _key_bits(xp, d)
        # validity mixes as its OWN lane: zeroing the data under NULL and
        # hashing v separately means no data value can alias the NULL key
        # (a fixed null-tag constant would collide with that literal value
        # under BOTH seeds, defeating the dual-hash collision check)
        h = _splitmix(xp, h ^ xp.where(v, u, np.uint64(0)))
        h = _splitmix(xp, h ^ v.astype(h.dtype))
    out = h.astype(jnp.int64 if xp is jnp else np.int64)
    # reserve the sentinel values for masked/fill
    out = xp.where(out == _SENTINEL_MASKED, np.int64(-(1 << 63) + 1), out)
    out = xp.where(out == _FILL, np.int64((1 << 63) - 2), out)
    return out


def _distinct_count(xp, h):
    """True number of distinct values in h (any size), static shape."""
    s = xp.sort(h)
    return 1 + xp.sum(s[1:] != s[:-1])


def _direct_group_mode(group_exprs) -> bool:
    """True when every group key is a dict-encoded string ColumnRef: the
    device sees small dense int64 codes, so group slots can be indexed
    DIRECTLY (code0 * m1 + code1 ...) — no sort, no hash, no collision
    possibility, and cross-shard tables merge elementwise because every
    shard shares one slot space. This is the TPC-H Q1/Q5 shape (group by
    returnflag+linestatus / n_name)."""
    from tidb_tpu.expression.core import ColumnRef
    from tidb_tpu.sqltypes import EvalType, TypeCode
    if not group_exprs:
        return False
    return all(isinstance(g, ColumnRef) and
               g.ft.eval_type == EvalType.STRING and
               g.ft.tp != TypeCode.JSON
               for g in group_exprs)


# lint: exempt[dtype-discipline] group codes carry exact int64 key values (scaled decimals / epoch-micros exceed float range)
def _direct_group_table(xp, group_exprs, cols, n, mask, C, pmax_axes=None):
    """Direct-indexed group slots -> (inv[n] i32, tot).
    Strides come from data maxima (pmax over the mesh axes so every
    shard agrees on the slot space). Slot C-1 is the masked-rows slot;
    combined codes clamp to C-2 and `tot` overshoots _C when clamping
    occurred, so the capacity-escalation path re-plans exactly as in
    the hash mode. A live slot's identity is its own index, so the
    table follows from the per-slot row counts (_slot_uniq)."""
    combined = None
    for g in group_exprs:
        d, v = g.eval_xp(xp, cols, n)
        code = xp.where(v, xp.asarray(d, dtype=jnp.int64) + 1, 0)
        code = xp.where(mask, code, 0)
        if combined is None:
            combined = code
        else:
            m = xp.max(code) if n else jnp.int64(0)
            if pmax_axes is not None:
                m = devplane.pmax(m, pmax_axes)
            combined = combined * (m + 1) + code
    tot = xp.max(xp.where(mask, combined, -1)) + 2
    slot = xp.minimum(combined, C - 2).astype(jnp.int32)
    inv = xp.where(mask, slot, C - 1).astype(jnp.int32)
    return inv, tot


def _slot_uniq(xp, ids, mask, C):
    """uniq[C] of a direct-indexed table from `ids` (each live slot's
    identity, _FILL in an empty one) with no pass over the rows: slot
    C-1 holds the masked sentinel as soon as one row is masked."""
    return ids.at[C - 1].set(
        xp.where(xp.all(mask), _FILL, _SENTINEL_MASKED))


def _cond_direct_mode(group_exprs) -> bool:
    """True when every group key is a bare ColumnRef of INT or
    dict-string kind — the shape where a RUNTIME range check can pick
    direct code-indexed slots (no sort, no hash, exact) over the packed
    sort, via lax.cond. Covers low-cardinality int keys (status codes,
    dates-as-days, small dimension ids) that the static string-only
    check misses; wide-range keys take the hash branch at runtime."""
    from tidb_tpu.expression.core import ColumnRef
    from tidb_tpu.sqltypes import EvalType, TypeCode
    if not group_exprs:
        return False
    for g in group_exprs:
        if not isinstance(g, ColumnRef) or g.ft.tp == TypeCode.JSON:
            return False
        if g.ft.eval_type not in (EvalType.INT, EvalType.STRING,
                                  EvalType.DATETIME,
                                  EvalType.DURATION):
            return False
    return True


# lint: exempt[dtype-discipline] exact int64 key codes + float64 span product (span overflow check must not round at 2^53)
def _cond_group_table(xp, group_exprs, cols, n, mask, h, C,
                      pmax_axes=None, direct_limit=None):
    """Runtime-selected group table: if the keys' (min..max) span
    product fits the capacity, index slots DIRECTLY by normalized
    codes; otherwise fall back to the packed-sort table over the
    precomputed hash `h`. Mins/spans are global over the mesh axes so
    every shard agrees on the code space (the value-based re-unique
    merge then stays correct). `direct_limit` caps the direct branch
    below the table capacity (tidb_tpu_direct_agg_slots): a
    capacity-escalated retry keeps a bounded direct domain and degrades
    wide spans to the hash branch instead of ballooning the
    direct-indexed table. -> (uniq, inv, tot, small): on the direct
    branch (`small`) a slot's rows share one hash, so its table is one
    min lane of the caller's batch (_group_slots) and `uniq` here is
    all _FILL."""
    codes = []
    spans = []
    span_fs = []
    for g in group_exprs:
        d, v = g.eval_xp(xp, cols, n)
        d = xp.asarray(d, jnp.int64)
        live = mask & v
        lo = xp.min(xp.where(live, d, _I64_MAX))
        hi_raw = xp.max(xp.where(live, d, _I64_MIN))
        if pmax_axes is not None:
            lo = -devplane.pmax(-lo, pmax_axes)
            hi_raw = devplane.pmax(hi_raw, pmax_axes)
        # NULL -> 0; live values -> 1.. (saturate when no live rows)
        code = xp.where(live, xp.maximum(d - lo, 0) + 1, 0)
        hi = xp.max(code)
        if pmax_axes is not None:
            hi = devplane.pmax(hi, pmax_axes)
        codes.append(code)
        spans.append(hi + 1)
        # the SMALLNESS decision uses raw min/max in float64: the int64
        # code math (d - lo) wraps when the raw span exceeds 2^63 and
        # would make a huge span look tiny, forcing the direct branch
        # onto colliding codes
        span_fs.append(jnp.maximum(
            hi_raw.astype(jnp.float64) - lo.astype(jnp.float64) + 2.0,
            1.0))      # no live rows: empty span counts as 1

    span_prod = jnp.prod(jnp.stack(span_fs))
    bound = C - 2 if direct_limit is None else min(C - 2, direct_limit)
    small = span_prod <= jnp.float64(bound)

    def direct(_):
        combined = codes[0]
        for c, s in zip(codes[1:], spans[1:]):
            combined = combined * s + c
        tot = xp.max(xp.where(mask, combined, -1)) + 2
        slot = xp.minimum(combined, C - 2).astype(jnp.int32)
        inv = xp.where(mask, slot, C - 1).astype(jnp.int32)
        return (xp.full(C, _FILL, dtype=jnp.int64), inv,
                tot.astype(jnp.int64))

    def hashed(_):
        uniq, inv, tot = _group_table(xp, h, n, C, mask=mask)
        return uniq, inv, jnp.asarray(tot, jnp.int64)

    return (*lax.cond(small, direct, hashed, None), small)


# lint: exempt[dtype-discipline] packed sort rides the int64 hash lanes (row index bit-packed into the low hash bits)
def _group_table(xp, x, m, C, mask=None):
    """Dense group-id table from one PACKED sort — the jnp.unique
    replacement. jnp.unique(size=C, return_inverse) costs a sort plus an
    argsort-shaped pair sort (~5x a plain sort on CPU XLA, measured), and
    the separate _distinct_count costs another; here the hash is
    quantized to (64 - ceil_log2(m)) bits, the element index rides the
    freed low bits, and ONE sort yields uniq, inverse, and the true
    distinct count via boundary flags + cumsum + two cheap scatters.

    Quantization can merge two distinct hashes into one group; like a
    full 64-bit collision that is caught by the caller's dual-hash
    (h2 min != max) check, which triggers the host fallback. The
    bottom and top quanta are reserved so real hashes never alias
    _SENTINEL_MASKED (masked rows, with `mask`) or _FILL (padding in
    gathered tables).

    -> (uniq[C] ascending with _FILL padding, inv[m] int32, tot)."""
    bits = max(1, int(m - 1).bit_length()) if m > 1 else 1  # lint: exempt[retrace-hazard] m is the padded length (shape-derived, static at trace time), not a traced value
    B = np.int64(bits)
    Q = np.int64(1) << B
    low = Q - np.int64(1)
    qfill = (_FILL >> B) << B
    hq = (x >> B) << B
    hq = xp.where(hq == _SENTINEL_MASKED, _SENTINEL_MASKED + Q, hq)
    hq = xp.where(hq == qfill, qfill - Q, hq)
    hq = xp.where(x == _FILL, qfill, hq)
    hq = xp.where(x == _SENTINEL_MASKED, _SENTINEL_MASKED, hq)
    if mask is not None:
        hq = xp.where(mask, hq, _SENTINEL_MASKED)
    packed = hq | xp.arange(m, dtype=jnp.int64)
    s = xp.sort(packed)
    sh = (s >> B) << B
    row = (s & low).astype(jnp.int32)
    newg = xp.concatenate([xp.ones((1,), dtype=bool), sh[1:] != sh[:-1]])
    sid = xp.cumsum(newg.astype(jnp.int32)) - 1
    tot = sid[-1] + 1
    sidc = xp.minimum(sid, C - 1)
    inv = xp.zeros(m, dtype=jnp.int32).at[row].set(sidc)
    uniq = xp.full(C, _FILL, dtype=jnp.int64).at[sidc].set(sh)
    uniq = xp.where(uniq == qfill, _FILL, uniq)
    return uniq, inv, tot


_SEG_FNS = {"sum": jax.ops.segment_sum,
            "min": jax.ops.segment_min,
            "max": jax.ops.segment_max}
_RED_FNS = {"sum": jnp.sum, "min": jnp.min, "max": jnp.max}

# A block whose slots in use number at most _DENSE_SLOTS reduces each
# slot by a masked pass over the rows instead of scattering the rows
# into the table: the largest power of two at which that still costs
# half the scatters on a 524,288-row block with TPC-H Q1's lanes (TPU
# v5e; the table is in PERF.md section 6, PR 25). One pass over the rows
# reduces _DENSE_PASS slots, and only the passes below the count run.
_DENSE_SLOTS = 1024
_DENSE_PASS = 16


def _empty_segment(op: str, dtype):
    """What jax.ops.segment_<op> leaves in a segment no row landed in:
    the identity the masked reduction starts from."""
    if op == "sum":
        return np.zeros((), dtype)
    floating = jnp.issubdtype(dtype, jnp.floating)
    if op == "min":
        return np.array(np.inf if floating else np.iinfo(dtype).max, dtype)
    return np.array(-np.inf if floating else np.iinfo(dtype).min, dtype)


class _SegBatch:
    """Batches the per-slot reductions: every requested lane with the
    same (merge-op, dtype) reduces together, so Q1's ~20 lanes are three
    reductions and not twenty. dtype-separated stacking keeps int64
    lanes exact (decimal sums can exceed 2^53 - promoting through
    float64 would corrupt them).

    Two implementations, chosen at run time from `nuniq` (the table's
    count of slots in use, which every slot a row can land in is below,
    bar the masked slot capacity-1 whose rows carry only identities):

    * scatter: one jax.ops.segment_* over stacked [n, k] lanes. On the
      TPU that is a row-at-a-time update, ~78 ns a row whether 1 lane
      or 12 and whatever the table's size (emulated int64; 122 ms a
      524,288-row block for Q1's three), and on the CPU a serial loop.
    * dense (nuniq <= _DENSE_SLOTS): slot s is
      reduce_rows(where(inv == s, lanes, identity)), _DENSE_PASS slots a
      pass, on the vector unit in the lanes' own dtype; slots at or
      past the count hold what a scatter leaves in an empty segment.

    With nuniq None (a table as long as the rows: ops/streamagg, the
    one-slot scalar kernel) the scatter alone is traced. `dense` is the
    traced predicate (None then)."""

    def __init__(self, inv, capacity: int, nuniq=None):
        self.inv = inv
        self.capacity = capacity
        self.nuniq = nuniq
        self.dense = None
        self._reqs: list = []     # (op, array[n])
        self._out: list | None = None

    def add(self, x, op: str) -> int:
        self._reqs.append((op, x))
        return len(self._reqs) - 1

    def _groups(self) -> dict:
        groups: dict = {}
        for i, (op, x) in enumerate(self._reqs):
            groups.setdefault((op, x.dtype), []).append((i, x))
        return groups

    def _scatter(self) -> list:
        out: list = [None] * len(self._reqs)
        for (op, _dt), reqs in self._groups().items():
            fn = _SEG_FNS[op]
            if len(reqs) == 1:
                i, x = reqs[0]
                out[i] = fn(x, self.inv, num_segments=self.capacity)
            else:
                stk = jnp.stack([x for _i, x in reqs], axis=1)
                r = fn(stk, self.inv, num_segments=self.capacity)
                for j, (i, _x) in enumerate(reqs):
                    out[i] = r[:, j]
        return out

    def _dense(self) -> list:
        C, P = self.capacity, _DENSE_PASS
        rows = -(-C // P) * P           # the table, in whole passes
        todo = (jnp.minimum(self.nuniq, C).astype(jnp.int32) + (P - 1)) // P
        inv = self.inv[None, None, :]
        slot_ids = jnp.arange(P, dtype=jnp.int32)[:, None, None]
        out: list = [None] * len(self._reqs)
        for (op, dt), reqs in self._groups().items():
            ident = _empty_segment(op, dt)
            red = _RED_FNS[op]
            # rows minor: [k, n] tiles the row axis over the vector
            # lanes, and the [P, k, n] select fuses into the reduce
            stk = jnp.stack([x for _i, x in reqs])[None]

            def one_pass(p, acc):
                hit = inv == slot_ids + p * P
                part = red(jnp.where(hit, stk, ident), axis=2)
                return lax.dynamic_update_slice(acc, part,
                                                (p * P, jnp.int32(0)))

            acc = lax.fori_loop(0, todo, one_pass,
                                jnp.full((rows, len(reqs)), ident, dt))
            for j, (i, _x) in enumerate(reqs):
                out[i] = acc[:C, j]
        return out

    def run(self) -> None:
        if self.nuniq is None:
            self._out = self._scatter()
            return
        self.dense = self.nuniq <= _DENSE_SLOTS
        self._out = lax.cond(self.dense, self._dense, self._scatter)

    def get(self, i: int):
        return self._out[i]


# lint: exempt[dtype-discipline] int64 sum lanes: decimal sums exceed 2^53, float64 promotion would corrupt them
def _agg_requests(xp, agg: AggDesc, cols, n, mask, batch: _SegBatch,
                  offs=None, row_ids=None):
    """Phase 1 of an aggregate's partial-state lanes: enqueue the per-row
    inputs on `batch`, return assemble(get) -> [(array[capacity],
    merge_op)] for after batch.run(). merge_op in {'sum','min','max'} is
    how lanes of the same group combine across chunks/shards. With offs
    (a shard's global row offset) FIRST_ROW indices are globalized for
    cross-shard merging; without it they stay chunk-local. row_ids
    overrides the per-row identity entirely (compacted stages carry the
    ORIGINAL probe row index as a column — ops/meshjoin two-phase path)."""
    fn = agg.fn
    if agg.arg is not None:
        d, v = agg.arg.eval_xp(xp, cols, n)
        live = mask & v
    else:
        d, live = None, mask
    live_i = live.astype(jnp.int64)

    if fn == AggFunc.COUNT:
        i0 = batch.add(live_i, "sum")
        return lambda g: [(g(i0), "sum")]
    if fn == AggFunc.SUM:
        i0 = batch.add(xp.where(live, d, jnp.zeros((), d.dtype)), "sum")
        i1 = batch.add(live_i, "max")
        return lambda g: [(g(i0), "sum"), (g(i1), "max")]
    if fn == AggFunc.AVG:
        i0 = batch.add(xp.where(live, d, jnp.zeros((), d.dtype)), "sum")
        i1 = batch.add(live_i, "sum")
        return lambda g: [(g(i0), "sum"), (g(i1), "sum")]
    if fn == AggFunc.MIN:
        ident = jnp.inf if d.dtype == jnp.float64 else _I64_MAX
        i0 = batch.add(xp.where(live, d, ident), "min")
        i1 = batch.add(live_i, "max")
        return lambda g: [(g(i0), "min"), (g(i1), "max")]
    if fn == AggFunc.MAX:
        ident = -jnp.inf if d.dtype == jnp.float64 else _I64_MIN
        i0 = batch.add(xp.where(live, d, ident), "max")
        i1 = batch.add(live_i, "max")
        return lambda g: [(g(i0), "max"), (g(i1), "max")]
    if fn == AggFunc.FIRST_ROW:
        if row_ids is not None:
            i0 = batch.add(xp.where(live, row_ids, _I64_MAX), "min")
            i1 = batch.add(live_i, "max")
            return lambda g: [(g(i0), "min"), (g(i1), "max")]
        i0 = batch.add(xp.where(live, xp.arange(n), n), "min")
        i1 = batch.add(live_i, "max")

        def assemble(g):
            first = g(i0)
            if offs is not None:
                first = xp.where(g(i1) > 0, offs + first, _I64_MAX)
            return [(first, "min"), (g(i1), "max")]
        return assemble
    raise NotImplementedError(f"device agg {fn}")


def _agg_lanes(xp, agg: AggDesc, cols, n, mask, inv, capacity: int,
               offs=None):
    """Single-aggregate convenience wrapper over _agg_requests."""
    b = _SegBatch(inv, capacity)
    assemble = _agg_requests(xp, agg, cols, n, mask, b, offs=offs)
    b.run()
    return assemble(b.get)


def _validate_device_exprs(filter_expr, group_exprs, aggs) -> None:
    """Device kernels see dict-encoded int64 codes for varlen columns, so a
    string column may appear ONLY as a bare group-key ColumnRef (codes group
    identically to values within a chunk; exact values are recovered from
    representative rows). Any computation over strings must be pre-applied
    on the host by the planner."""
    from tidb_tpu.expression import ColumnRef
    if filter_expr is not None and not filter_expr.is_device_safe():
        raise DeviceRejectError("filter expression is not device-safe; planner "
                         "must split string predicates to the host path")
    for g in group_exprs:
        if not g.is_device_safe() and not isinstance(g, ColumnRef):
            raise DeviceRejectError(f"group expr {g!r} computes over a varlen "
                             "column; pre-project it on the host")
    for a in aggs:
        if a.fn == AggFunc.GROUP_CONCAT:
            raise DeviceRejectError("GROUP_CONCAT aggregates on the host")
        if a.arg is not None and not a.arg.is_device_safe():
            # FIRST_ROW only needs a row index on device, so a bare string
            # ColumnRef is fine (value gathered host-side); computed string
            # exprs would still trace eval_xp and explode mid-jit
            if not (a.fn == AggFunc.FIRST_ROW and
                    isinstance(a.arg, ColumnRef)):
                raise DeviceRejectError(f"agg arg {a.arg!r} is not device-safe")


@dataclass
class GroupResult:
    """Partial aggregation result of one chunk."""

    keys: list[tuple]            # group key tuples (host python values)
    partials: list[np.ndarray]   # per agg: [lanes][num_groups] arrays
    counts: np.ndarray           # rows per group


def finalize_group_result(chunk: Chunk, group_exprs, aggs, gidx: np.ndarray,
                          rep_rows: np.ndarray, lanes_per_agg,
                          counts: np.ndarray) -> GroupResult:
    """Shared host tail of the device kernels: recover exact group-key
    values from representative rows (strings included — host path),
    materialize FIRST_ROW values, and package a GroupResult.

    lanes_per_agg: per agg, the [num_live_groups]-length lane arrays
    (already gathered at gidx)."""
    sub = chunk.take(rep_rows)
    key_cols = []
    for g in group_exprs:
        d, v = g.eval(sub)
        key_cols.append([None if not v[i] else
                         (d[i].item() if hasattr(d[i], "item") else d[i])
                         for i in range(len(gidx))])
    keys = list(zip(*key_cols)) if key_cols else [()] * len(gidx)
    partials = []
    for a, ls in zip(aggs, lanes_per_agg):
        if a.fn == AggFunc.FIRST_ROW:
            # gather only the first-row rows, then evaluate the arg on
            # that tiny sub-chunk (host path handles strings)
            idx = ls[0]
            hasv = ls[1] > 0
            safe_idx = np.where(hasv, idx, 0).astype(np.int64)
            d, _v = a.arg.eval(chunk.take(safe_idx))
            vals = np.where(hasv, d, 0) if d.dtype != object else d
            ls = [vals, hasv.astype(np.int64)]
        partials.append(ls)
    return GroupResult(keys=keys, partials=partials, counts=counts)


# lint: exempt[dtype-discipline] int64 slot identities: exact key codes and key hashes (splitmix64 bit patterns)
def _group_slots(xp, group_exprs, cols, n, mask, C, force_hash=False,
                 direct_limit=None, pmax_axes=None):
    """Give every row its slot, by the group keys' shape: direct-indexed
    (dictionary strings), runtime-selected (bare int / dict keys) or the
    packed sort over the key hash. -> (batch, h2, uniq_of): a _SegBatch
    over the rows' slots that knows the table's count (batch.nuniq, the
    caller's overflow check) with the table's own lane enqueued where
    it has one; the check hash per row; and uniq_of(counts) -> uniq[C]
    for after batch.run(). pmax_axes: the mesh axes a sharded caller's
    slot space must agree over (ops/meshagg.py)."""
    if not force_hash and _direct_group_mode(group_exprs):
        inv, nuniq = _direct_group_table(
            xp, group_exprs, cols, n, mask, C, pmax_axes=pmax_axes)
        # no hash, no collisions: the check trivially passes
        h2 = xp.zeros(n, dtype=jnp.int64)
        return _SegBatch(inv, C, nuniq), h2, lambda counts: _slot_uniq(
            xp, xp.where(counts > 0, xp.arange(C), _FILL), mask, C)
    key_cols = [g.eval_xp(xp, cols, n) for g in group_exprs]
    h = _hash_keys(xp, key_cols, n, seed=0x517CC1B727220A95)
    h2 = _hash_keys(xp, key_cols, n, seed=0x2545F4914F6CDD1D)
    if not force_hash and _cond_direct_mode(group_exprs):
        uniq, inv, nuniq, small = _cond_group_table(
            xp, group_exprs, cols, n, mask, h, C, pmax_axes=pmax_axes,
            direct_limit=direct_limit)
        b = _SegBatch(inv, C, nuniq)
        # slot IDENTITY on the direct branch is the key-tuple hash, not
        # the dense code: the cross-shard re-unique merge quantizes top
        # bits, which would collapse small codes into one group (hash
        # values keep the hash mode's merge contract exactly). _FILL
        # is no hash (_hash_keys), so an empty slot reads _FILL
        i_id = b.add(xp.where(mask, h, _FILL), "min")
        return b, h2, lambda _counts: xp.where(
            small, _slot_uniq(xp, b.get(i_id), mask, C), uniq)
    # one packed sort -> group table + inverse + true distinct count
    # (incl. masked sentinel) for overflow detection
    uniq, inv, nuniq = _group_table(xp, h, n, C, mask=mask)
    return _SegBatch(inv, C, nuniq), h2, lambda _counts: uniq


# lint: exempt[dtype-discipline] int64 header lanes: exact row counts and the int64 check hash
def group_partial(xp, group_exprs, aggs, cols, n, mask, capacity,
                  force_hash: bool = False, direct_limit=None):
    """The traced group+partial-agg phase shared by HashAggKernel and
    the fused pipeline-fragment kernel (ops/fragment.py): the rows'
    slots (_group_slots), one batched reduction per (merge-op, dtype)
    for the header lanes + every aggregate (_SegBatch), dual-hash
    collision check. `cols` entries may be None for columns no
    group/agg expression reads (the fragment kernel gathers only used
    lanes). -> (uniq, nuniq, collided, counts, rep, lanes, dense);
    `dense` says which of _SegBatch's two implementations ran."""
    b, h2, uniq_of = _group_slots(xp, group_exprs, cols, n, mask, capacity,
                                  force_hash=force_hash,
                                  direct_limit=direct_limit)
    mask_i = mask.astype(jnp.int64)
    i_cmin = b.add(xp.where(mask, h2, _I64_MAX), "min")
    i_cmax = b.add(xp.where(mask, h2, _I64_MIN), "max")
    i_live = b.add(mask_i, "max")
    i_cnt = b.add(mask_i, "sum")
    i_rep = b.add(xp.where(mask, xp.arange(n), n), "min")
    assembles = [_agg_requests(xp, a, cols, n, mask, b) for a in aggs]
    b.run()
    # collision check: within each group, the check hash must agree
    collided = jnp.any((b.get(i_live) > 0) &
                       (b.get(i_cmin) != b.get(i_cmax)))
    counts = b.get(i_cnt)
    rep = b.get(i_rep)
    lanes = [[l for l, _op in assemble(b.get)] for assemble in assembles]
    return uniq_of(counts), b.nuniq, collided, counts, rep, lanes, b.dense


def count_dispatch(dense) -> None:
    """One group-by dispatch read back: which of _SegBatch's two
    implementations its block took."""
    metrics.counter(metrics.AGG_DISPATCHES,
                    {"path": "dense" if bool(dense) else "scatter"})


class HashAggKernel:
    """Compiled filter+group+partial-agg over one chunk schema.

    group_exprs must be device-safe (strings dict-encoded upstream by
    runtime.device_put_chunk; their ColumnRefs then see int64 codes).
    """

    def __init__(self, filter_expr: Expression | None,
                 group_exprs: Sequence[Expression],
                 aggs: Sequence[AggDesc], capacity: int = 4096,
                 force_hash: bool = False, direct_limit: int | None = None):
        """`force_hash` degrades the direct-indexed (code-indexed) group
        table to the packed-sort hash path — set by kernel_for when a
        capacity escalation crosses `tidb_tpu_direct_agg_slots`, so the
        fixed-size direct table never balloons past its bound.
        `direct_limit` caps the runtime-selected direct branch the same
        way (both are construction-time values; kernel_for keys its
        cache on them)."""
        self.filter_expr = filter_expr
        self.group_exprs = list(group_exprs)
        self.aggs = list(aggs)
        self.capacity = capacity
        self.force_hash = force_hash
        self.direct_limit = direct_limit
        _validate_device_exprs(filter_expr, self.group_exprs, self.aggs)
        self._jit = jax.jit(devplane.named(self._kernel, "hashagg"))
        self._jitd = None   # donating variant, built on first dispatch

    def _kernel(self, cols, nrows):
        n = cols[0][0].shape[0]
        xp = jnp
        mask = runtime.filter_mask_xp(xp, self.filter_expr, cols, n)
        mask = mask & (xp.arange(n) < nrows)   # padding rows are dead
        return group_partial(xp, self.group_exprs, self.aggs, cols, n,
                             mask, self.capacity,
                             force_hash=self.force_hash,
                             direct_limit=self.direct_limit)

    def scratch_nbytes(self, chunk: Chunk) -> int:
        """Device bytes a dispatch stages BEYOND the input columns: the
        group-table and lane scratch at the kernel's static capacity —
        the share a fused dispatch over an HBM-cache-resident block
        still pays (the input bytes stay on the cache's own ledger)."""
        return self.capacity * 8 * (5 + 2 * len(self.aggs))

    def dispatch_nbytes(self, chunk: Chunk) -> int:
        """HBM bytes one dispatch stages, sized purely from shapes at
        dispatch time: the padded input columns (varlen ships as int64
        dict codes, every lane carries bool validity) plus the
        group-table and lane scratch at the kernel's static capacity.
        Executors charge this to the plan node's device ledger before
        dispatch and credit it back at finalize."""
        from tidb_tpu import memtrack
        n = runtime.bucket_size(max(chunk.num_rows, 1))
        return memtrack.device_put_bytes(chunk, n) + \
            self.scratch_nbytes(chunk)

    def dispatch(self, chunk: Chunk, donate: bool = False, dev_cols=None):
        """Pad + transfer + enqueue the program WITHOUT forcing a sync
        (jax dispatch is async): the pipeline's overlap point. With
        donate=True (and a backend that honors it) the padded input
        buffers are donated to the program, so a transient superchunk's
        HBM is reused for the group tables instead of living alongside
        them; donated transfers skip the chunk memo (a memoized donated
        buffer would be read after free). With dev_cols (device-resident
        padded columns, e.g. an HBM cache block — store/device_cache.py)
        the upload is skipped entirely and the fused program runs
        straight from HBM; cached blocks are shared, so donation never
        applies to them. -> opaque pending token."""
        if dev_cols is not None:
            return self._jit(dev_cols, chunk.num_rows)
        donate = donate and runtime.donation_supported()
        cols, _dicts = runtime.device_put_chunk(chunk, memo=not donate)
        if donate:
            if self._jitd is None:
                self._jitd = jax.jit(
                    devplane.named(self._kernel, "hashagg"),
                    donate_argnums=(0,))
            return self._jitd(cols, chunk.num_rows)
        return self._jit(cols, chunk.num_rows)

    def finalize(self, chunk: Chunk, pending) -> GroupResult:
        """Blocking half: one batched device->host transfer for the whole
        result pytree (per-array reads each pay a full device round
        trip), then the host tail."""
        uniq, nuniq, collided, counts, rep, lanes, dense = \
            jax.device_get(pending)
        count_dispatch(dense)
        # capacity before collision: overflow groups clamp into the last
        # slot, which then trips the collision check spuriously
        if int(nuniq) > self.capacity:
            err = CapacityError(f"distinct groups {int(nuniq)} > capacity "
                                f"{self.capacity}")
            err.needed = int(nuniq)   # executors re-plan with 2x this
            raise err
        if bool(collided):
            raise CollisionError("group key hash collision")
        live = (counts > 0) & (uniq != _SENTINEL_MASKED) & (uniq != _FILL)
        gidx = np.flatnonzero(live)
        lanes_at = [[l[gidx] for l in ls] for ls in lanes]
        return finalize_group_result(chunk, self.group_exprs, self.aggs,
                                     gidx, rep[gidx], lanes_at, counts[gidx])

    def __call__(self, chunk: Chunk, dev_cols=None) -> GroupResult:
        return self.finalize(chunk, self.dispatch(chunk,
                                                  dev_cols=dev_cols))


class ScalarAggKernel:
    """No-group aggregation: one partial state row per chunk."""

    def __init__(self, filter_expr: Expression | None,
                 aggs: Sequence[AggDesc]):
        self.filter_expr = filter_expr
        self.aggs = list(aggs)
        _validate_device_exprs(filter_expr, [], self.aggs)
        self._jit = jax.jit(devplane.named(self._kernel, "scalaragg"))
        self._jitd = None

    # lint: exempt[dtype-discipline] int64 COUNT lane: exact even past 2^53 rows, matches the agg-state stacking dtype
    def _kernel(self, cols, nrows):
        n = cols[0][0].shape[0]
        xp = jnp
        mask = runtime.filter_mask_xp(xp, self.filter_expr, cols, n)
        mask = mask & (xp.arange(n) < nrows)   # padding rows are dead
        inv = xp.zeros(n, dtype=jnp.int32)
        count = jax.ops.segment_sum(mask.astype(jnp.int64), inv,
                                    num_segments=1)
        lanes = [[l for l, _op in _agg_lanes(xp, a, cols, n, mask, inv, 1)]
                 for a in self.aggs]
        return count, lanes

    def scratch_nbytes(self, chunk: Chunk) -> int:
        """See HashAggKernel.scratch_nbytes (one state row, no table)."""
        return 16 * len(self.aggs)

    def dispatch_nbytes(self, chunk: Chunk) -> int:
        """See HashAggKernel.dispatch_nbytes (one state row, no table)."""
        from tidb_tpu import memtrack
        n = runtime.bucket_size(max(chunk.num_rows, 1))
        return memtrack.device_put_bytes(chunk, n) + \
            self.scratch_nbytes(chunk)

    def dispatch(self, chunk: Chunk, donate: bool = False, dev_cols=None):
        """Async half; see HashAggKernel.dispatch."""
        if dev_cols is not None:
            return self._jit(dev_cols, chunk.num_rows)
        donate = donate and runtime.donation_supported()
        cols, _ = runtime.device_put_chunk(chunk, memo=not donate)
        if donate:
            if self._jitd is None:
                self._jitd = jax.jit(
                    devplane.named(self._kernel, "scalaragg"),
                    donate_argnums=(0,))
            return self._jitd(cols, chunk.num_rows)
        return self._jit(cols, chunk.num_rows)

    def finalize(self, chunk: Chunk, pending) -> GroupResult:
        count, lanes = jax.device_get(pending)
        partials = []
        for a, ls in zip(self.aggs, lanes):
            if a.fn == AggFunc.FIRST_ROW:
                idx = ls[0]
                hasv = ls[1] > 0
                if hasv[0] and chunk.num_rows > 0:
                    d, _v = a.arg.eval(chunk.take(np.array([int(idx[0])])))
                    val = d[0]
                else:
                    val = 0
                ls = [np.array([val]), hasv.astype(np.int64)]
            partials.append(ls)
        return GroupResult(keys=[()], partials=partials, counts=count)

    def __call__(self, chunk: Chunk, dev_cols=None) -> GroupResult:
        return self.finalize(chunk, self.dispatch(chunk,
                                                  dev_cols=dev_cols))


# -- process-wide kernel cache (executable reuse across plan objects) --------

# keyed on (plan fingerprint, capacity): a plan-cache miss, a new session,
# or a re-parsed statement re-creates plan OBJECTS, but the device program
# is identical — re-tracing and re-compiling it per plan instance is pure
# waste. jit's own executable
# cache inside each kernel then handles the bucket-shape axis: one traced
# kernel serves every padded superchunk size. Sized for encoded filters
# too (ops/encoded.py): a translated constant is a dictionary-specific
# CODE baked into the fingerprint, so a query over R regions can occupy
# R keys for one plan shape — the capacity keeps that from thrashing
# genuinely-hot kernels, and the dictionaries themselves are stable
# (memoized per cached column), so warm serving converges on a fixed
# key set whose compiles the persistent XLA cache absorbs.
_KERNELS = runtime.FingerprintCache(256)


def kernel_for(filter_expr, group_exprs, aggs, capacity: int = 4096):
    """HashAggKernel/ScalarAggKernel with process-wide reuse keyed on the
    structural plan fingerprint + capacity. Falls back to a fresh
    (uncached) kernel when the plan cannot be fingerprinted. Raises
    ValueError exactly like the constructors when the exprs are not
    device-safe.

    Degrade-to-hash boundary (tidb_tpu_direct_agg_slots): a direct-mode
    group-by whose capacity escalation crosses the bound is rebuilt on
    the packed-sort hash path — the direct-indexed partial table stays
    a FIXED-SIZE array (arxiv 2603.26698) instead of doubling with the
    group domain; wide-span int keys clamp the runtime-selected direct
    branch the same way."""
    from tidb_tpu import config
    direct_limit = config.direct_agg_slots()
    force_hash = bool(group_exprs) and capacity > direct_limit and \
        _direct_group_mode(group_exprs)

    from tidb_tpu import profiler
    family = "hashagg" if group_exprs else "scalaragg"
    made = []

    def make():
        made.append(1)
        if group_exprs:
            return HashAggKernel(filter_expr, group_exprs, aggs,
                                 capacity=capacity,
                                 force_hash=force_hash,
                                 direct_limit=direct_limit)
        return ScalarAggKernel(filter_expr, aggs)

    fp = runtime.plan_fingerprint(filter_expr, group_exprs, aggs)
    if fp is None:
        k = make()
        prof = profiler.profile(family, None)
        profiler.note_construct(prof, reuse=False)
        k._profile = prof
        return k
    from tidb_tpu import devplane
    key = (fp, capacity if group_exprs else 0, force_hash,
           direct_limit if group_exprs else 0,
           # plane identity: a 1-chip and an 8-chip mesh executable for
           # the same plan shape must never alias one cache slot
           devplane.mesh_fingerprint(process=True))
    k = _KERNELS.get_or_create(key, make)
    # profile rows key on the same (family, fingerprint, mesh) identity
    # as the cache slot; an LRU miss (`made` fired) is one compile unit
    prof = profiler.profile(family, f"{fp}|{key[1]}|{key[2]}|{key[3]}")
    profiler.note_construct(prof, reuse=not made)
    k._profile = prof
    return k


class HashAggregator:
    """Stateful final aggregator: merges chunk partials on the host and
    finalizes per-group values. Mirrors Aggregation.GetPartialResult
    merging (expression/aggregation/aggregation.go:32-47)."""

    def __init__(self, aggs: Sequence[AggDesc], group_meta=None):
        """group_meta: the group-key expressions OR FieldTypes, in key
        order (anything with an .ft, or an ft itself)."""
        self.aggs = list(aggs)
        self._state: dict[tuple, list] = {}
        self._orig: dict[tuple, tuple] = {}
        # _ci group keys must merge across CHUNK partials too (per-chunk
        # grouping already folds): fold the dict identity, surface the
        # first-seen variant
        self._ci = [getattr(g, "ft", g).is_ci for g in group_meta] \
            if group_meta else None

    def _group_key(self, key: tuple) -> tuple:
        if not self._ci or not any(self._ci):
            return key
        from tidb_tpu.sqltypes import collation_key
        return tuple(collation_key(x) if c and x is not None else x
                     for x, c in zip(key, self._ci))

    def approx_bytes(self) -> int:
        """Rough host footprint of the merged state — dict slots, key
        tuples and per-agg lane scalars at CPython object costs. This is
        the number memtrack bounds under tidb_tpu_mem_quota_query: it
        scales with the live GROUP COUNT (the quantity that actually
        grows without bound on a runaway aggregation), not the input."""
        n = len(self._state)
        if n == 0:
            return 0
        st = next(iter(self._state.values()))
        lanes = sum(len(ls) for ls in st)
        key = next(iter(self._orig.values()))
        return n * (96 + 56 * len(key) + 48 * lanes)

    def update(self, res: GroupResult) -> None:
        for gi, key in enumerate(res.keys):
            gkey = self._group_key(key)
            st = self._state.get(gkey)
            if st is None:
                self._state[gkey] = [
                    [lane[gi] for lane in res.partials[ai]]
                    for ai in range(len(self.aggs))]
                self._orig[gkey] = key
                continue
            for ai, agg in enumerate(self.aggs):
                lanes = res.partials[ai]
                cur = st[ai]
                fn = agg.fn
                if fn == AggFunc.COUNT:
                    cur[0] += lanes[0][gi]
                elif fn in (AggFunc.SUM, AggFunc.AVG):
                    cur[0] += lanes[0][gi]
                    cur[1] = max(cur[1], lanes[1][gi]) if fn == AggFunc.SUM \
                        else cur[1] + lanes[1][gi]
                elif fn == AggFunc.MIN:
                    if lanes[1][gi] > 0:
                        cur[0] = min(cur[0], lanes[0][gi]) if cur[1] > 0 \
                            else lanes[0][gi]
                        cur[1] = 1
                elif fn == AggFunc.MAX:
                    if lanes[1][gi] > 0:
                        cur[0] = max(cur[0], lanes[0][gi]) if cur[1] > 0 \
                            else lanes[0][gi]
                        cur[1] = 1
                elif fn == AggFunc.FIRST_ROW:
                    if cur[1] == 0 and lanes[1][gi] > 0:
                        cur[0], cur[1] = lanes[0][gi], 1
                elif fn == AggFunc.GROUP_CONCAT:
                    if lanes[1][gi] > 0:
                        if cur[1] > 0:
                            cur[0] = cur[0] + agg.sep + lanes[0][gi]
                        else:
                            cur[0], cur[1] = lanes[0][gi], 1

    def results(self) -> list[tuple[tuple, list]]:
        """-> [(key, [final agg values])] with AVG finalized; SUM/AVG of
        decimals stay scaled ints (callers format via the agg result_ft)."""
        out = []
        for key, st in sorted(self._state.items(),
                              key=lambda kv: tuple(
                                  (x is None, x) for x in kv[0])):
            key = self._orig.get(key, key)
            vals = []
            for agg, cur in zip(self.aggs, st):
                fn = agg.fn
                if fn == AggFunc.COUNT:
                    vals.append(int(cur[0]))
                elif fn == AggFunc.SUM:
                    vals.append(None if cur[1] == 0 else cur[0])
                elif fn == AggFunc.AVG:
                    if cur[1] == 0:
                        vals.append(None)
                    elif agg.result_ft.eval_type == EvalType.DECIMAL:
                        # scaled-int avg: rescale sum by extra frac then
                        # divide in EXACT integer arithmetic (half-up;
                        # float division corrupts wide decimals)
                        extra = agg.result_ft.frac - agg.arg.ft.frac
                        num = int(cur[0]) * (10 ** extra)
                        den = int(cur[1])
                        q, r = divmod(abs(num), den)
                        if 2 * r >= den:
                            q += 1
                        vals.append(q if num >= 0 else -q)
                    else:
                        vals.append(float(cur[0]) / float(cur[1]))
                elif fn in (AggFunc.MIN, AggFunc.MAX, AggFunc.FIRST_ROW,
                            AggFunc.GROUP_CONCAT):
                    vals.append(None if cur[1] == 0 else cur[0])
                else:
                    raise NotImplementedError(fn)
            out.append((key, vals))
        return out
