"""Equi-join pair matching on device.

Replaces the matching loop of /root/reference/executor/join.go:37
(HashJoinExec: mvmap build + per-row probe goroutines). A dynamic hash
table fights XLA's static shapes, so the device program is sort-based
(SURVEY.md §7 "Device hash tables", Plan A):

    1. hash both sides' key tuples to int64 (NULL keys -> per-side
       sentinels so they never match anything, SQL semantics)
    2. sort the build hashes once; searchsorted gives every probe row its
       contiguous candidate run [left,right)
    3. a prefix sum over run lengths + one searchsorted turns the dynamic
       fan-out into a static-capacity (li, ri) pair list with an overflow
       flag (caller doubles capacity and retries)
    4. candidate pairs are verified by EXACT key equality on device, so
       hash collisions only cost a discarded candidate — never a wrong row

Keys are evaluated to fixed-width arrays on the host first (strings get a
dictionary shared across both sides), so the kernel only ever sees int64 /
float64 lanes; payload gather happens on the host from the returned pair
indices.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from tidb_tpu import devplane, profiler
from tidb_tpu.ops import runtime
from tidb_tpu.ops.hashagg import _FILL, _SENTINEL_MASKED, _hash_keys

__all__ = ["JoinKernel", "JoinOverflowError", "JoinKeyEncoder",
           "match_pairs"]

# build-side dead rows hash to _SENTINEL_MASKED, probe-side to _FILL:
# distinct values, and _hash_keys never produces either for live rows
_DEAD_BUILD = _SENTINEL_MASKED
_DEAD_PROBE = _FILL


class JoinOverflowError(Exception):
    """More output pairs than the kernel's static capacity."""

    def __init__(self, needed: int):
        super().__init__(f"join output needs {needed} pairs")
        self.needed = needed


class JoinKeyEncoder:
    """Aligns varlen key columns across both sides of a join.

    Fitted once on the (materialized) build side; probe chunks stream
    through transform(). String values get int64 codes from one shared
    dictionary; probe values absent from it get unique negative codes so
    they match nothing yet remain live rows (outer-join semantics).

    Encoded fast path (ops/encoded.py, `tidb_tpu_encoded_exec`): when a
    side arrives PRE-ENCODED — the memoized dict_encode of a bare varlen
    ColumnRef — the per-row Python dict loop disappears. A probe side
    sharing the build's dictionary OBJECT passes its codes straight
    through; a mismatched dictionary re-keys with one vectorized gather
    through a code-translation array (O(|dict|) to build, O(rows) to
    apply)."""

    def __init__(self, num_keys: int):
        self._dicts: list[dict | None] = [None] * num_keys
        self._bvalues: list[list | None] = [None] * num_keys
        self._ci = [False] * num_keys

    # lint: exempt[memtrack-alloc] build-side key lanes: covered by the tracked build (prepare_build device billing)
    def fit_build(self, cols, encoded=None, ci=None):
        out = []
        for j, (d, v) in enumerate(cols):
            enc = encoded[j] if encoded is not None else None
            if enc is not None:
                # pre-encoded lane: the column's memoized dictionary IS
                # the join dictionary (value map built lazily only if a
                # raw probe side ever needs it)
                codes, values = enc
                self._bvalues[j] = values
                if ci is not None:
                    self._ci[j] = bool(ci[j])
                out.append((codes, v))
                continue
            if d.dtype != object:
                out.append((d, v))
                continue
            mapping: dict = {}
            codes = np.empty(len(d), dtype=np.int64)
            for i, val in enumerate(d):
                codes[i] = mapping.setdefault(val, len(mapping)) if v[i] \
                    else -1
            self._dicts[j] = mapping
            out.append((codes, v))
        return out

    def _mapping(self, j: int) -> dict | None:
        """The build-side value->code map, built lazily from an encoded
        build dictionary when a raw probe side needs per-value lookup."""
        mapping = self._dicts[j]
        if mapping is None and self._bvalues[j] is not None:
            from tidb_tpu.ops import encoded as op_encoded
            mapping = op_encoded._dict_map(self._bvalues[j], self._ci[j])
            self._dicts[j] = mapping
        return mapping

    # lint: exempt[memtrack-alloc] probe key lanes bounded by the probe chunk already billed upstream
    def transform_probe(self, cols, encoded=None):
        out = []
        for j, (d, v) in enumerate(cols):
            enc = encoded[j] if encoded is not None else None
            bvals = self._bvalues[j]
            if enc is not None and bvals is not None:
                codes, values = enc
                if values is bvals:
                    # shared dictionary: codes are directly comparable
                    out.append((codes, v))
                else:
                    from tidb_tpu.ops import encoded as op_encoded
                    # the cached build map amortizes across probe
                    # batches; only the O(|probe dict|) walk is per batch
                    t = op_encoded.code_translation(
                        values, bvals, self._ci[j],
                        dst_map=self._mapping(j))
                    out.append((t[codes], v))
                continue
            mapping = self._mapping(j)
            if mapping is None:
                if d.dtype == object:
                    # build side had no string values at all: nothing can
                    # match, but rows stay live for outer joins
                    codes = np.arange(-2, -2 - len(d), -1, dtype=np.int64)
                    out.append((codes, v))
                else:
                    out.append((d, v))
                continue
            codes = np.empty(len(d), dtype=np.int64)
            for i, val in enumerate(d):
                codes[i] = mapping.get(val, -2 - i) if v[i] else -1
            out.append((codes, v))
        return out


def match_pairs(xp, hb, hp, bd_lanes, pd_lanes, out_cap):
    """Sort-join matcher steps 2-4 (module docstring): build hashes `hb`
    (dead rows = _DEAD_BUILD) vs probe hashes `hp` (dead = _DEAD_PROBE),
    expanded into a static-capacity pair list with exact-key verification
    over the raw data lanes. Shared by the single-chip kernel and the
    per-partition stage of the mesh shuffle join
    (ops/meshshuffle.py). -> (li, ri, ok, total)."""
    b_n = hb.shape[0]
    p_n = hp.shape[0]
    perm = xp.argsort(hb)
    sb = hb[perm]
    left = xp.searchsorted(sb, hp, side="left")
    right = xp.searchsorted(sb, hp, side="right")
    counts = xp.where(hp != _DEAD_PROBE, right - left, 0)
    cum = xp.cumsum(counts)
    total = cum[p_n - 1] if p_n else 0

    k = xp.arange(out_cap)
    li = xp.searchsorted(cum, k, side="right")
    li_c = xp.clip(li, 0, p_n - 1)
    start = cum[li_c] - counts[li_c]
    pos = left[li_c] + (k - start)
    ri = perm[xp.clip(pos, 0, b_n - 1)]
    ok = k < xp.minimum(total, out_cap)
    # exact key verification: candidates from colliding hashes are
    # discarded here, making the join exact
    for bd, pd in zip(bd_lanes, pd_lanes):
        ok = ok & (bd[ri] == pd[li_c])
    return li_c, ri, ok, total


def host_match_pairs(build_keys, probe_keys, nb: int, np_: int):
    """Vectorized numpy pair matcher — the same sort-join algorithm as the
    device kernel, with dynamic shapes (free on the host). This is the
    measured-baseline equivalent of the reference's compiled Go hash join
    (executor/join.go:37): columnar and vectorized, no accelerator.
    -> (li, ri) numpy index arrays of matching (probe, build) pairs."""
    if nb == 0 or np_ == 0:
        return np.empty(0, np.int64), np.empty(0, np.int64)
    b_valid = np.ones(nb, dtype=bool)
    for _d, v in build_keys:
        b_valid &= v[:nb]
    p_valid = np.ones(np_, dtype=bool)
    for _d, v in probe_keys:
        p_valid &= v[:np_]
    hb = _hash_keys(np, [(d[:nb], v[:nb] & b_valid)
                         for d, v in build_keys], nb,
                    seed=0x9E3779B97F4A7C15)
    hp = _hash_keys(np, [(d[:np_], v[:np_] & p_valid)
                         for d, v in probe_keys], np_,
                    seed=0x9E3779B97F4A7C15)
    hb = np.where(b_valid, hb, _DEAD_BUILD)
    hp = np.where(p_valid, hp, _DEAD_PROBE)
    perm = np.argsort(hb, kind="stable")
    sb = hb[perm]
    left = np.searchsorted(sb, hp, side="left")
    right = np.searchsorted(sb, hp, side="right")
    counts = np.where(hp != _DEAD_PROBE, right - left, 0)
    total = int(counts.sum())
    if total == 0:
        return np.empty(0, np.int64), np.empty(0, np.int64)
    li = np.repeat(np.arange(np_, dtype=np.int64), counts)
    # position within each probe row's candidate run
    run_start = np.cumsum(counts) - counts
    pos = left[li] + (np.arange(total, dtype=np.int64) - run_start[li])
    ri = perm[pos]
    # exact key verification discards hash-collision candidates
    ok = np.ones(total, dtype=bool)
    for (bd, _bv), (pd_, _pv) in zip(build_keys, probe_keys):
        ok &= bd[:nb][ri] == pd_[:np_][li]
    return li[ok], ri[ok]


# Module-level program memo: the traced matcher depends only on out_cap
# (shapes, dtypes and key arity are jit's own cache key). Executors build
# a fresh JoinKernel per query execution — a per-instance cache would
# re-trace and re-compile the identical program on EVERY query (~300ms
# per join). Capacities are power-of-two buckets, so this stays small.
_PROGRAMS: dict[int, object] = {}


def _matcher_program(out_cap: int):
    prog = _PROGRAMS.get(out_cap)
    if prog is not None:
        return prog

    def kernel(bkeys, pkeys, nb, np_):
        xp = jnp
        b_n = bkeys[0][0].shape[0]
        p_n = pkeys[0][0].shape[0]
        b_alive = (xp.arange(b_n) < nb)
        p_alive = (xp.arange(p_n) < np_)
        b_valid = b_alive
        for _d, v in bkeys:
            b_valid = b_valid & v
        p_valid = p_alive
        for _d, v in pkeys:
            p_valid = p_valid & v
        hb = _hash_keys(xp, [(d, v & b_valid) for d, v in bkeys],
                        b_n, seed=0x9E3779B97F4A7C15)
        hp = _hash_keys(xp, [(d, v & p_valid) for d, v in pkeys],
                        p_n, seed=0x9E3779B97F4A7C15)
        hb = xp.where(b_valid, hb, _DEAD_BUILD)
        hp = xp.where(p_valid, hp, _DEAD_PROBE)

        return match_pairs(xp, hb, hp, [d for d, _v in bkeys],
                           [d for d, _v in pkeys], out_cap)

    prog = jax.jit(devplane.named(kernel, "join"))
    _PROGRAMS[out_cap] = prog
    profiler.note_construct(_profile(out_cap), reuse=False)
    return prog


def _profile(out_cap: int):
    """The `join` kernel_profile row of one capacity bucket's program
    (the program memo's own key)."""
    return profiler.profile("join", f"cap{out_cap}")


class _PendingJoin:
    """In-flight matcher dispatch: the padded device-resident key lanes
    ride along so an overflow retry re-runs WITHOUT re-padding or
    re-transferring either side."""

    __slots__ = ("bk", "pk", "nb", "np_", "cap", "res")

    def __init__(self, bk, pk, nb, np_, cap, res):
        self.bk, self.pk = bk, pk
        self.nb, self.np_ = nb, np_
        self.cap = cap
        self.res = res


class JoinKernel:
    """Pair matcher for one key-lane signature; compiled programs are
    shared process-wide (see _matcher_program)."""

    def __init__(self, num_keys: int):
        self.num_keys = num_keys

    def build_nbytes(self, nb: int) -> int:
        """HBM bytes prepare_build stages: one padded int64/float64 data
        lane + bool validity per key — the device-resident build side a
        pipelined probe keeps for its whole lifetime."""
        return self.num_keys * 9 * runtime.bucket_size(max(nb, 1))

    def dispatch_nbytes(self, np_: int, out_cap: int | None = None) -> int:
        """HBM bytes one probe dispatch stages, from shapes alone: the
        padded probe key lanes plus the static-capacity pair buffers
        (li/ri int64 + ok bool). Charged to the plan node's device
        ledger at dispatch, credited back at finalize."""
        cap = out_cap or runtime.bucket_size(max(np_ * 2, 1024))
        return self.num_keys * 9 * runtime.bucket_size(max(np_, 1)) \
            + cap * 17

    def prepare_build(self, build_keys, nb: int):
        """Pad + transfer the build-side key lanes once; the returned
        device lanes feed every probe superchunk's dispatch (per-probe
        build re-uploads were pure waste)."""
        return runtime.put_lanes(build_keys,
                                 runtime.bucket_size(max(nb, 1)))

    def dispatch(self, build_keys, probe_keys, nb: int, np_: int,
                 out_cap: int | None = None, build_dev=None) -> _PendingJoin:
        """Async half: enqueue the matcher program for one probe batch
        (no sync — the pipeline's overlap point). build_dev, when given,
        is the prepare_build() result reused across batches."""
        bk = build_dev if build_dev is not None \
            else self.prepare_build(build_keys, nb)
        pb = runtime.bucket_size(max(np_, 1))
        cap = out_cap or runtime.bucket_size(max(np_ * 2, 1024))
        pk = runtime.put_lanes(probe_keys, pb)
        prog = _matcher_program(cap)
        # the enqueue interval lands on the bucket's `join` profile
        # row: a fresh program's first call is where jax traces +
        # compiles, so that interval is its compile
        with profiler.dispatch_section(
                _profile(cap), nbytes=self.dispatch_nbytes(np_, cap)):
            res = prog(bk, pk, nb, np_)
        return _PendingJoin(bk, pk, nb, np_, cap, res)

    def finalize(self, p: _PendingJoin):
        """Blocking half: read back the pair list, growing the output
        capacity (device lanes reused) until it fits. Capacity growth is
        billed to the ACTIVE statement's memory root (device ledger):
        the regrown li/ri/ok buffers on a many-to-many join are the
        join's largest HBM allocation, and the quota must see them even
        though no plan handle reaches this layer."""
        from tidb_tpu import memtrack
        root = memtrack.current()
        extra = 0
        try:
            while True:
                li, ri, ok, total = p.res
                # scalar first: an overflow retry then discards the
                # cap-sized index buffers without ever transferring them;
                # the success path batches the three arrays into one
                # device_get (per-array reads each pay a full device
                # round trip)
                total = int(jax.device_get(total))
                if total <= p.cap:
                    break
                new_cap = runtime.bucket_size(total)
                if root is not None:
                    grow = (new_cap - p.cap) * 17    # li+ri int64, ok bool
                    extra += grow    # before consume: it may raise
                    root.consume(device=grow)
                p.cap = new_cap
                with profiler.dispatch_section(
                        _profile(p.cap),
                        nbytes=self.dispatch_nbytes(p.np_, p.cap)):
                    p.res = _matcher_program(p.cap)(p.bk, p.pk, p.nb,
                                                    p.np_)
            li, ri, ok = jax.device_get((li, ri, ok))
        finally:
            if root is not None and extra:
                root.release(device=extra)
        sel = np.flatnonzero(ok)
        return li[sel], ri[sel]

    def __call__(self, build_keys, probe_keys, nb: int, np_: int,
                 out_cap: int | None = None):
        """build_keys/probe_keys: [(np data, np valid)] aligned fixed-width
        lanes (see encode_join_keys). Returns (li, ri) numpy index arrays
        of matching (probe, build) row pairs."""
        return self.finalize(self.dispatch(build_keys, probe_keys, nb, np_,
                                           out_cap=out_cap))
