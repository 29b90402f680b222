"""Device runtime helpers: padding, transfer, kernel caching.

The reference streams 1024-row chunks through goroutine pipelines
(util/chunk, distsql); a TPU wants large static-shape batches. Chunks are
padded to bucketed sizes (powers of two) so each physical plan compiles a
small, reusable set of XLA programs; padding rows carry valid=False so every
kernel treats them as NULLs that match no filter and join no group.
"""

from __future__ import annotations

import threading
from collections import OrderedDict, deque

import numpy as np

import jax
import jax.numpy as jnp

from tidb_tpu import metrics
from tidb_tpu.chunk import Chunk, dict_encode
from tidb_tpu.expression import Expression

__all__ = ["bucket_size", "pad_column", "put_lanes", "device_put_chunk",
           "eval_filter_host", "super_batches", "MIN_BUCKET",
           "Superchunk", "superchunk_batches", "pipeline_map",
           "donation_supported", "plan_fingerprint"]

MIN_BUCKET = 1024


class Superchunk:
    """One coalesced batch: a chunk re-assembled from `sources` storage
    chunks, destined for a single padded-bucket device dispatch.
    `sources` counts the chunks that CONTRIBUTED rows to this batch — a
    chunk spanning a coalesce boundary feeds (and counts in) each
    superchunk it touches, so per-superchunk attribution stays honest
    even though the per-query sum can exceed the distinct chunk count.
    The fill ratio (rows over the padded bucket) is the fraction of
    device work spent on live rows — the number EXPLAIN ANALYZE
    surfaces."""

    __slots__ = ("chunk", "sources")

    def __init__(self, chunk: Chunk, sources: int):
        self.chunk = chunk
        self.sources = sources

    @property
    def num_rows(self) -> int:
        return self.chunk.num_rows

    @property
    def bucket(self) -> int:
        return bucket_size(self.chunk.num_rows)

    @property
    def fill(self) -> float:
        return self.chunk.num_rows / self.bucket


def superchunk_batches(chunks, limit: int, tracker=None):
    """Coalesce a chunk stream into ~limit-row Superchunks: device
    dispatches stay large while host memory stays O(limit) — the
    TPU-sized form of the reference's bounded chunk channels
    (distsql/distsql.go:92). Oversize chunks are sliced so one storage
    chunk cannot break the memory bound; 0-row chunks fold away.
    A `limit` that is a power of two keeps every full superchunk on ONE
    bucket shape; only the tail pays a smaller power-of-two bucket.

    `tracker` (a memtrack.MemTracker) accounts the staging buffer: bytes
    are held while chunks sit in the assembly buffer and credited back
    when the superchunk is yielded — ownership passes to the consumer
    (pipeline_map's in-flight slots pick it up from there)."""
    from tidb_tpu import memtrack
    limit = max(int(limit), 1)    # a 0/negative sysvar must not hang
    buf, total, srcs, staged = [], 0, 0, 0

    def emit():
        nonlocal staged
        big = Chunk.concat_all(buf)
        if tracker is not None and staged:
            tracker.release(host=staged)
            staged = 0
        return Superchunk(big, srcs) if big is not None else None

    try:
        for c in chunks:
            if c.num_rows == 0:
                continue
            srcs += 1
            start = 0
            while start < c.num_rows:
                take = min(c.num_rows - start, limit - total)
                piece = c if (start == 0 and take == c.num_rows) \
                    else c.slice(start, start + take)
                buf.append(piece)
                if tracker is not None:
                    b = memtrack.chunk_bytes(piece)
                    tracker.consume(host=b)
                    staged += b
                total += take
                start += take
                if total >= limit:
                    sc = emit()
                    if sc is not None:
                        yield sc
                    buf, total, srcs = [], 0, \
                        1 if start < c.num_rows else 0
        if buf:
            sc = emit()
            if sc is not None:
                yield sc
    finally:
        # abandoned/raised mid-assembly: whatever still sits in the
        # buffer was never handed to a consumer — credit it back now
        # instead of waiting for the statement root's detach
        if tracker is not None and staged:
            tracker.release(host=staged)
            staged = 0


def super_batches(first_parts, rest, limit: int):
    """Chunk-only view of superchunk_batches (legacy callers)."""
    import itertools
    for sc in superchunk_batches(itertools.chain(first_parts, rest),
                                 limit):
        yield sc.chunk


def pipeline_map(items, dispatch, finalize, depth: int,
                 tracker=None, cost=None, profile=None):
    """Depth-N dispatch-ahead map over an item stream: up to `depth`
    dispatched items are in flight before the oldest is finalized, so
    item k+1's host-side prep (padding, dict-encode, device_put) and its
    async XLA dispatch overlap item k's device execution — the double
    buffer at depth 2. Results come back in item order.

    dispatch(item) -> token must only ENQUEUE work (jax dispatch is
    async; nothing here may force a sync). finalize(item, token) is the
    one blocking point (device_get at the operator output boundary);
    callers that want stall attribution time their device readback
    inside finalize (runtime_stats.note_finalize_wait), where they can
    tell device tokens from host-fallback ones.

    With `tracker`/`cost` set, each in-flight slot holds cost(item) host
    bytes from dispatch until its finalize returns — the depth-N window
    is exactly the memory the pipeline pins beyond one batch.

    `depth` is this STATEMENT's window; the server-wide window belongs
    to the device scheduler (tidb_tpu/sched.py): every dispatch takes a
    global slot first, granted round-robin across concurrent
    statements. Under contention the pipeline drains its own oldest
    in-flight token before asking again — shrinking its local window to
    its fair share — and past the scheduler's bypass valve the dispatch
    proceeds unscheduled, so the global window can throttle but never
    hang a statement.

    With `profile` set (a profiler.KernelProfile), each device token's
    enqueue interval records as one dispatch and its blocking readback
    as busy-ns on that profile row — the pipelined seam of the kernel
    profiling plane (the sync seams use profiler.dispatch_section);
    bytes are billed by the dispatch closures, which know them."""
    import time as _time

    from tidb_tpu import meter, profiler, sched, trace
    from tidb_tpu.util import failpoint
    scheduler = sched.device_scheduler()
    depth = max(int(depth), 1)
    pending: deque = deque()
    track = tracker is not None and cost is not None

    def _token_kind(tok) -> str:
        # host-path items: None (the common convention) or the fused
        # probe-agg's explicit ("host", ...) token — everything else
        # really enqueued device work
        if tok is None or (isinstance(tok, tuple) and tok
                           and tok[0] == "host"):
            return "host"
        return "device"

    def pop_finalize():
        prev, seq, tok, held, slot = pending.popleft()
        kind = _token_kind(tok)
        try:
            # the watchdog bounds the blocking readback: past
            # tidb_tpu_dispatch_timeout_ms the statement cancels with
            # the retryable device-fault error, and the finally below
            # (plus each kernel's own finalize-path credit) drains the
            # slot and the staged bytes exactly as on any error
            with sched.finalize_watch("pipeline-finalize"):
                failpoint.eval("device/finalize")
                # the blocking readback at the output boundary — the
                # per-superchunk finalize serialization the Chrome
                # export makes visible next to the dispatch-ahead
                # lanes. The interval bills to the tenant's work
                # ledger (meter.py) as a SECTION: escalation retries
                # and degraded partitions inside the finalize meter
                # themselves, and the section charges the remainder
                with meter.busy_section(kind), \
                        trace.span("finalize", superchunk=seq,
                                   host=int(kind == "host")):
                    t0p = _time.perf_counter_ns()
                    out = finalize(prev, tok)
                    if profile is not None and kind == "device":
                        profiler.note_busy(
                            profile, _time.perf_counter_ns() - t0p)
                    return out
        finally:
            scheduler.release(slot)
            if held:
                tracker.release(host=held)

    def acquire_slot(bypass: bool):
        # the global round-robin slot wait, traced per attempt so slot
        # stalls attribute to THIS statement's timeline (and to the
        # tenant's slot-wait ledger)
        t0 = _time.perf_counter_ns()
        try:
            with trace.span("sched.slot"):
                return scheduler.acquire_or_bypass() if bypass \
                    else scheduler.acquire()
        finally:
            meter.note_slot_wait(_time.perf_counter_ns() - t0)

    seq = -1
    try:
        for it in items:
            seq += 1
            while len(pending) >= depth:
                yield pop_finalize()
            slot = acquire_slot(False)
            while slot is None and pending:
                yield pop_finalize()
                slot = acquire_slot(False)
            if slot is None:
                slot = acquire_slot(True)
            held = cost(it) if track else 0
            if held:
                tracker.consume(host=held)
            try:
                failpoint.eval("device/dispatch")
                # the enqueue interval (pad/transfer/launch) meters as
                # device time for device tokens, host-fallback time for
                # host-path items — the kind is only known once
                # dispatch() returns, so it is assigned on the section
                busy = meter.busy_section()
                cc = profiler.cc_probe(profile)
                t0p = _time.perf_counter_ns()
                with busy, trace.span("dispatch", superchunk=seq):
                    tok = dispatch(it)
                    busy.kind = _token_kind(tok)
                if profile is not None and busy.kind == "device":
                    profiler.note_dispatch(
                        profile, _time.perf_counter_ns() - t0p,
                        cc_before=cc)
            except BaseException as e:
                # executor-plane device faults feed the same health
                # tracker as the copr sites, so repeated pipeline
                # faults still quarantine the device — the fault
                # itself propagates (retryable 9009 at the client;
                # the per-dispatch retry/degrade chain lives on the
                # copr path)
                if isinstance(e, failpoint.DeviceFaultError) and not \
                        isinstance(e, failpoint.DispatchTimeoutError):
                    sched.device_health().note_fault()
                scheduler.release(slot)
                if held:
                    tracker.release(host=held)
                raise
            if tok is None:
                # host-path item: nothing went to the device — hand the
                # slot back now instead of across its (host) finalize
                scheduler.release(slot)
                slot = None
            pending.append((it, seq, tok, held, slot))
        while pending:
            yield pop_finalize()
    finally:
        # a consumer that stops early (limit hit, error upstream)
        # abandons the generator with dispatched slots still in flight:
        # neither their held host bytes nor the device bytes their
        # dispatch charged may linger until statement detach. Every
        # kernel credits dispatch_nbytes back on its finalize path, so
        # each abandoned token is finalized (result discarded); a slot
        # whose finalize fails still releases its host bytes
        while pending:
            prev, _seq, tok, held, slot = pending.popleft()
            try:
                # abandoned tokens still occupied the device until this
                # drain — their finalize interval meters like any other
                with meter.busy_section(_token_kind(tok)):
                    finalize(prev, tok)
            except Exception:
                pass    # the slot is dead either way; ledger cleanup
                #         continues with the remaining slots
            finally:
                scheduler.release(slot)
                if held:
                    tracker.release(host=held)


_donation_supported: bool | None = None


def donation_supported() -> bool:
    """True when the active backend honors input-buffer donation (TPU /
    GPU). XLA:CPU ignores donations with a per-call warning, so the
    donating jit variants only engage off-CPU."""
    global _donation_supported
    if _donation_supported is None:
        try:
            _donation_supported = jax.default_backend() not in ("cpu",)
        except Exception:  # noqa: BLE001 - no backend: treat as host-only
            _donation_supported = False
    return _donation_supported


def bucket_size(n: int) -> int:
    """Next power of two >= n (min MIN_BUCKET): the static shape bucket."""
    b = MIN_BUCKET
    while b < n:
        b <<= 1
    return b


# lint: exempt[memtrack-alloc] callers bill padded superchunk staging at dispatch (superchunk_batches tracker)
def pad_column(data: np.ndarray, valid: np.ndarray, size: int):
    n = len(data)
    if n == size:
        return data, valid
    pd = np.zeros(size, dtype=data.dtype)
    pd[:n] = data
    pv = np.zeros(size, dtype=bool)
    pv[:n] = valid
    return pd, pv


def _count_h2d(cols) -> None:
    """Bytes of [(data, valid)] host arrays about to be handed to the
    device, padding included (metrics.H2D_BYTES)."""
    metrics.counter(metrics.H2D_BYTES,
                    inc=sum(d.nbytes + v.nbytes for d, v in cols))


def put_lanes(keys, size: int):
    """Pad [(data, valid)] key lanes to `size` and hand them to the
    device as the operands of a join-shaped program (ops/join.py,
    ops/fragment.py): those programs take host arrays directly, so
    their host->device bytes are counted here and not at
    device_put_chunk."""
    lanes = [pad_column(d, v, size) for d, v in keys]
    _count_h2d(lanes)
    return [tuple(map(jnp.asarray, lane)) for lane in lanes]


def device_put_chunk(chunk: Chunk, size: int | None = None,
                     to_device: bool = True, memo: bool = True):
    """-> (cols, dicts): cols is a list of (data, valid) per column, padded
    to a bucketed static size; varlen columns are dict-encoded and their
    dictionaries returned in `dicts[col_idx]` for host-side decode.
    With to_device=False the arrays stay numpy so the caller can issue one
    jax.device_put with an explicit sharding (no double transfer).

    Device transfers are memoized on the chunk (keyed by padded size):
    chunks served repeatedly from the storage-side columnar cache keep
    their columns resident in HBM, so a hot analytical query pays zero
    host->device bytes. Callers must treat chunks as immutable.
    memo=False skips the memo entirely — REQUIRED when the caller will
    donate the transferred buffers to a kernel (a memoized donated
    buffer would be read after free) or when the chunk is a transient
    superchunk that no one will ever present again."""
    size = size or bucket_size(chunk.num_rows)
    if to_device and memo:
        hit = dev_cache_get(chunk, size)
        if hit is not None:
            return hit
    cols = []
    dicts: dict[int, list] = {}
    for j, c in enumerate(chunk.columns):
        if c.fixed_width:
            data, valid = c.data, c.valid
        else:
            codes, values = dict_encode(c)
            dicts[j] = values
            data, valid = codes, c.valid & (codes >= 0)
        data, valid = pad_column(np.ascontiguousarray(data), valid, size)
        cols.append((data, valid))
    if to_device:
        # the one-chip transfer seam (a memo hit above, or an
        # HBM-cache hit upstream, never reaches here)
        _count_h2d(cols)
        cols = jax.device_put(cols)   # one batched transfer
        if memo:
            dev_cache_put(chunk, size, (cols, dicts))
    return cols, dicts


# a chunk may be consumed by both the single-chip path (int size key) and
# a mesh path (('shard', mesh, size) key); a tiny per-chunk dict lets the
# two memos coexist instead of evicting each other
_DEV_CACHE_SLOTS = 2


def dev_cache_get(chunk, key):
    cache = getattr(chunk, "_dev_cache", None)
    if isinstance(cache, OrderedDict):
        hit = cache.get(key)
        if hit is not None:
            # true LRU: a hit refreshes the entry's position, so the
            # entry that actually gets evicted is the LEAST recently
            # used one, not merely the oldest inserted
            cache.move_to_end(key)
        return hit
    return None


def dev_cache_put(chunk, key, value) -> None:
    cache = getattr(chunk, "_dev_cache", None)
    if not isinstance(cache, OrderedDict):
        cache = OrderedDict()
        chunk._dev_cache = cache
    while len(cache) >= _DEV_CACHE_SLOTS:
        cache.popitem(last=False)
    cache[key] = value


def eval_filter_host(expr: Expression | None, chunk: Chunk) -> np.ndarray:
    """Host-path filter: bool mask over rows (NULL -> False).
    Mirror of the device mask used inside kernels."""
    if expr is None:
        return np.ones(chunk.num_rows, dtype=bool)
    d, v = expr.eval(chunk)
    return v & (d != 0)


def filter_mask_xp(xp, expr: Expression | None, cols, n):
    """Device-path filter mask inside a traced kernel."""
    if expr is None:
        return xp.ones(n, dtype=bool)
    d, v = expr.eval_xp(xp, cols, n)
    return v & (d != 0)


# -- plan fingerprints (executable-cache keys) -------------------------------


class FingerprintCache:
    """Thread-safe LRU keyed by plan fingerprint: ONE implementation for
    every process-wide kernel cache (hashagg, streamagg), so the true-LRU
    contract (a hit refreshes the entry) holds everywhere. Initialized
    at module level by its owners — no lazy check-then-create races."""

    def __init__(self, capacity: int = 64):
        self._cap = capacity
        self._d: OrderedDict = OrderedDict()
        self._mu = threading.Lock()

    def get_or_create(self, key, factory):
        """Cached value for `key`, else factory() (called OUTSIDE the
        lock — kernel construction may validate expressions; a racing
        duplicate is discarded in favor of the first insert). factory
        exceptions propagate without touching the cache."""
        with self._mu:
            hit = self._d.get(key)
            if hit is not None:
                self._d.move_to_end(key)
                return hit
        obj = factory()
        with self._mu:
            cur = self._d.setdefault(key, obj)
            self._d.move_to_end(key)
            while len(self._d) > self._cap:
                old = next(iter(self._d))
                if old == key:      # never evict the entry just touched
                    break
                self._d.pop(old)
            return cur


class _Unfingerprintable(Exception):
    """Expression tree contains a node whose device behavior cannot be
    captured structurally (correlated cells, unknown extensions)."""


def _ft_fp(ft) -> str:
    if ft is None:
        return "?"
    return (f"{ft.tp}:{getattr(ft, 'flen', 0)}:{getattr(ft, 'frac', 0)}:"
            f"{int(bool(getattr(ft, 'is_ci', False)))}:"
            f"{int(bool(getattr(ft, 'is_wide_decimal', False)))}")


def _extra_fp(extra) -> str:
    """ScalarFunc.extra carries eval-relevant payload (IN value lists,
    LIKE patterns, cast target types) that MUST distinguish kernels."""
    if extra is None:
        return ""
    if hasattr(extra, "tp"):          # a FieldType (cast target)
        return _ft_fp(extra)
    if isinstance(extra, (list, tuple)):
        return repr([repr(x) for x in extra])
    if isinstance(extra, (str, bytes, int, float, bool)):
        return repr(extra)
    # arbitrary payload (GENERIC handlers): no structural identity
    raise _Unfingerprintable(type(extra).__name__)


def _expr_fp(e) -> str:
    from tidb_tpu.expression.core import ColumnRef, Constant, ScalarFunc
    if e is None:
        return "~"
    ft = _ft_fp(getattr(e, "ft", None))
    if isinstance(e, ColumnRef):
        return f"c{e.idx}|{ft}"
    if isinstance(e, Constant):
        return f"k{e.value!r}|{ft}"
    if isinstance(e, ScalarFunc):
        args = ",".join(_expr_fp(a) for a in e.args)
        return f"f{e.op.value}({args})|x{_extra_fp(e.extra)}|{ft}"
    raise _Unfingerprintable(type(e).__name__)


def plan_fingerprint(filter_expr, group_exprs, aggs) -> str | None:
    """Structural identity of a pushed (filter, group-by, agg) subplan —
    the process-wide executable-cache key. Two plans with the same
    fingerprint trace to IDENTICAL device programs: the walk encodes
    everything a kernel's eval_xp depends on (column indices, field
    types incl. frac/collation, operator tree shape, literal values).
    Returns None when any node falls outside the structural vocabulary
    (then the caller builds an uncached kernel — correct, just slower on
    a plan-cache miss)."""
    try:
        parts = [_expr_fp(filter_expr),
                 ";".join(_expr_fp(g) for g in group_exprs)]
        for a in aggs:
            parts.append(f"{a.fn.value}|{int(bool(a.distinct))}|"
                         f"{_expr_fp(a.arg)}|{a.sep!r}")
        return "#".join(parts)
    except _Unfingerprintable:
        return None
