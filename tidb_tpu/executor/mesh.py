"""Executors for mesh-routed plans (plan/mesh_route.py).

The reference's distributed aggregation pulls per-region partials onto one
root goroutine (/root/reference/distsql/distsql.go:92 fan-in feeding
executor/aggregate.go); here the heavy reduction happens ON the device
plane (ops/meshagg.py, ops/meshjoin.py) and the host only merges the
already tiny per-statement group tables and formats rows.

One pipeline: the streaming path is the SAME superchunk_batches +
pipeline_map machinery as the single-chip executors (executor/__init__.py
_superchunk_partials) — pipeline_map owns the dispatch slots, meter
sections, trace spans, failpoint seams and the abandoned-token drain;
this module only supplies the dispatch/finalize closures and their
device-ledger charges. Per-batch recovery: capacity overflow re-plans
the kernel and re-runs only that batch (group merging is associative —
already-merged batches stay valid); collisions or non-device
expressions aggregate that batch on the host.

Fallback contract: every mesh plan carries the original subtree; we
delegate to it when no process mesh is active, when expressions fail
device validation, on group-capacity overflow past the escalation cap,
on hash collisions, or on non-unique dimension build keys."""

from __future__ import annotations

import itertools
import time
from collections import OrderedDict

from tidb_tpu import config as sysconf
from tidb_tpu import devplane, memtrack, profiler, runtime_stats, sched, trace
from tidb_tpu.chunk import Chunk, Column
from tidb_tpu.ops import runtime as op_runtime
from tidb_tpu.ops.hashagg import (CapacityError, CollisionError,
                                  DeviceRejectError, HashAggregator)
from tidb_tpu.ops.hostagg import host_hash_agg
from tidb_tpu.ops.meshagg import MeshAggKernel
from tidb_tpu.ops.meshjoin import (BuildError, LookupSpec,
                                   MeshLookupAggKernel, _BuildTable,
                                   host_lookup_agg)
from tidb_tpu.ops.runtime import bucket_size, superchunk_batches
from tidb_tpu.util import failpoint

__all__ = ["MeshAggExec", "MeshLookupAggExec", "stream_stats",
           "reset_stream_stats"]

# Streaming telemetry (tests + metrics assert bounded buffering and that
# the dispatch-ahead overlap actually happened).
_STREAM_STATS = {"streams": 0, "batches": 0, "host_batches": 0,
                 "max_batch_rows": 0, "overlapped_launches": 0}


def stream_stats() -> dict:
    return dict(_STREAM_STATS)


def reset_stream_stats() -> None:
    for k in _STREAM_STATS:
        _STREAM_STATS[k] = 0

# Initial per-chip group-table capacity; on overflow the executor re-plans
# the kernel once with 2x the observed distinct count (the re-plan the
# single-chip kernel docstring promises), then falls back to the host.
DEFAULT_CAPACITY = 4096
MAX_CAPACITY = 1 << 20

# kernel reuse across executions of cached plans: jit programs are per
# (structure, capacity); keyed by plan object identity (the entry pins
# the plan so its id cannot be recycled) PLUS the plane identity — the
# mesh generation and its structural fingerprint (axis, device count,
# platform), so a 1-chip and an 8-chip executable for the same plan can
# never collide (plan_fingerprint-keyed caches fold the same identity in
# via ops/hashagg.kernel_for).
_KERNELS: OrderedDict = OrderedDict()
_KERNELS_CAP = 64


def _kernel_cache_get(plan, capacity):
    key = (devplane.mesh_generation(),
           devplane.mesh_fingerprint(process=True), id(plan), capacity)
    hit = _KERNELS.get(key)
    if hit is not None and hit[0] is plan:
        _KERNELS.move_to_end(key)
        profiler.note_construct(profiler.profile_of(hit[1]), reuse=True)
        return hit[1]
    return None


def _kernel_cache_put(plan, capacity, kernel) -> None:
    gen = devplane.mesh_generation()
    # kernels from older mesh generations can never be hit again; drop
    # them now rather than pinning their replicated build tables
    for k in [k for k in _KERNELS if k[0] != gen]:
        del _KERNELS[k]
    key = (gen, devplane.mesh_fingerprint(process=True), id(plan), capacity)
    # one mesh-family compile unit per cache fill; the profile row keys
    # on the same plan identity + capacity the executable slot does
    prof = profiler.profile("mesh", f"plan{id(plan)}|{capacity}")
    profiler.note_construct(prof, reuse=False)
    kernel._profile = prof
    _KERNELS[key] = (plan, kernel)
    _KERNELS.move_to_end(key)
    while len(_KERNELS) > _KERNELS_CAP:
        _KERNELS.popitem(last=False)


def _concat_chunks(parts, schema) -> Chunk:
    big = Chunk.concat_all([p for p in parts if p.num_rows])
    if big is None:
        return Chunk([Column.from_values(c.ft, []) for c in schema.cols])
    return big


# Bounded registry of concat memos: each entry pins a table-sized host
# chunk (and transitively its device copy), so unlike the row-bounded
# ChunkCache these must be counted — a long-lived server executing many
# distinct cached plans would otherwise pin one table copy per plan.
_CONCATS: OrderedDict = OrderedDict()
_CONCATS_CAP = 16


def _concat_chunks_cached(holder, slot: str, parts, schema) -> Chunk:
    """Concat memoized on `holder` (a plan node): when the storage chunk
    cache serves the same per-region chunk objects again, the concatenated
    table — and therefore its memoized device copy — is reused, so a hot
    multi-region scan transfers zero bytes. Keyed by part identities; the
    parts are pinned in the cache entry so ids cannot be recycled. The
    global _CONCATS LRU bounds how many such table copies stay pinned."""
    key = tuple(id(p) for p in parts)
    cached = getattr(holder, slot, None)
    if cached is not None and cached[0] == key:
        reg_key = (id(holder), slot)
        if reg_key in _CONCATS:
            _CONCATS.move_to_end(reg_key)
        return cached[2]
    big = _concat_chunks(parts, schema)
    if len(parts) > 1:     # single-part concat returns the cached chunk
        setattr(holder, slot, (key, parts, big))
        reg_key = (id(holder), slot)
        _CONCATS[reg_key] = holder
        _CONCATS.move_to_end(reg_key)
        while len(_CONCATS) > _CONCATS_CAP:
            _rk, h = _CONCATS.popitem(last=False)
            if hasattr(h, _rk[1]):
                delattr(h, _rk[1])
    return big


def _emit_agg(plan, agg, executor_mod):
    results = agg.results()
    if not plan.group_exprs and not results:
        results = [((), [executor_mod._empty_agg_value(a)
                         for a in plan.aggs])]
    executor_mod._note_final_groups(len(results))
    return executor_mod._agg_results_to_chunk(
        plan.schema, plan.num_group_cols, plan.aggs, results)


def _emit_results(plan, gr_or_none, executor_mod):
    agg = HashAggregator(plan.aggs, plan.group_exprs)
    if gr_or_none is not None:
        agg.update(gr_or_none)
    return _emit_agg(plan, agg, executor_mod)


def _fallback_reason(e) -> str:
    """Metric label for a per-batch host fallback — the REAL cause, not
    a blanket reason="mesh" (that label is gone: the plane shares the
    single-chip pipeline, so its fallbacks are the same taxonomy)."""
    if isinstance(e, CollisionError):
        return "collision"
    if isinstance(e, CapacityError):
        return "capacity"
    return "unsupported"


class _MeshExecBase:
    def __init__(self, plan):
        self.plan = plan
        self.schema = plan.schema

    def chunks(self, ctx):
        # the plane's aggregates are root executors like the one-chip
        # ones (executor._OwnSpan): the host's part of the statement
        # under exec.agg, every pull of an operand scan or of the
        # fallback subtree outside it
        from tidb_tpu.executor import _OwnSpan
        own = _OwnSpan(lambda: trace.span("exec.agg"))
        yield from own.drive(self._chunks(ctx, own))

    def _fallback(self, ctx, own):
        from tidb_tpu.executor import build_executor
        return own.pull(build_executor(self.plan.fallback).chunks(ctx))

    @staticmethod
    def _cached_scan(reader, ctx):
        """Pull a mesh operand scan through the NON-streaming copr path.

        Framed copr streaming re-encodes and re-decodes the table on
        every execution — resumable framing buys nothing for a
        plane-local scan feeding a sharded kernel, and it bypasses the
        columnar chunk cache entirely (measured: a warm TPC-H Q1 on the
        8-device plane spent ~14s of a ~14.5s statement re-draining
        stream frames). The whole-region decoded chunks served here are
        cache-hits on re-execution, which also keeps their object
        identities stable — the concat and device-transfer memos key on
        them. With the chunk cache OFF there is nothing to serve from,
        so framed streaming keeps its memory-bounded cold-scan role
        unchanged. The overlay is thread-local, so it shadows the
        session's tidb_tpu_copr_stream only while this generator is
        being pulled."""
        if not sysconf.chunk_cache_enabled():
            yield from reader.chunks(ctx)
            return
        it = reader.chunks(ctx)
        while True:
            with sysconf.session_overlay({"tidb_tpu_copr_stream": 0}):
                try:
                    c = next(it)
                except StopIteration:
                    return
            yield c

    def _whole_table_run(self, kernel, chunk, chip):
        """One whole-table kernel execution under the SAME trace-span
        pair and failpoint seams as the copr sync sites and the
        pipelined dispatch wrapper — a statement's span vocabulary must
        not depend on the mesh size that executed it."""
        prof = profiler.profile_of(kernel)
        nb = memtrack.device_put_bytes(chunk) if prof is not None else 0
        with profiler.dispatch_section(prof, nbytes=nb, plan=self.plan):
            with trace.span("dispatch", rows=chunk.num_rows, chip=chip):
                outs = kernel.launch(chunk, bucket=True)
            failpoint.eval("device/finalize")
            with trace.span("finalize"):
                return kernel.finish(outs, chunk)

    def _run_with_escalation(self, make_kernel, run):
        """Kernel-build + run with one capacity re-plan on overflow.
        The successful capacity sticks to the plan so re-executions of a
        cached plan start there instead of re-failing at the default.
        -> GroupResult or None (caller falls back)."""
        capacity = getattr(self.plan, "_mesh_capacity", DEFAULT_CAPACITY)
        for _attempt in (0, 1):
            try:
                kernel = _kernel_cache_get(self.plan, capacity)
                if kernel is None:
                    kernel = make_kernel(capacity)
                    _kernel_cache_put(self.plan, capacity, kernel)
                out = run(kernel)
                self.plan._mesh_capacity = capacity
                return out
            except CapacityError as e:
                profiler.note_escalation(profiler.profile_of(kernel))
                needed = getattr(e, "needed", None)
                if needed is None:
                    return None
                capacity = 1 << max(needed * 2 - 1, 1).bit_length()
                if capacity > MAX_CAPACITY:
                    return None
            except (CollisionError, BuildError, DeviceRejectError) as e:
                profiler.note_kernel_fallback(profiler.profile_of(kernel),
                                              _fallback_reason(e))
                return None
        return None

    def _stream_groups(self, superchunks, get_kernel, host_batch,
                       agg: HashAggregator) -> int:
        """Streaming aggregation on the shared pipeline: pipeline_map
        keeps tidb_tpu_pipeline_depth launches in flight (host→HBM
        transfer + async dispatch of superchunk k+1 overlap k's blocking
        readback) and owns the dispatch slots, meter sections, trace
        spans, failpoint seams, and the abandoned-token drain — exactly
        the machinery the single-chip executors ride. This method only
        supplies the dispatch/finalize closures: each in-flight launch
        holds its padded upload on the plan node's DEVICE ledger until
        its readback, and the merged agg state is tracked to the host
        ledger as it grows — so the mesh path answers to
        tidb_tpu_mem_quota_query and EXPLAIN ANALYZE `mem` like the
        single-chip pipeline. Returns the tracked state bytes for the
        caller to release once the results are emitted."""
        _STREAM_STATS["streams"] += 1
        plan = self.plan
        mt_node = memtrack.op_node(plan)
        state = {"kernel": None, "inflight": 0}
        try:
            state["kernel"] = get_kernel(
                getattr(plan, "_mesh_capacity", DEFAULT_CAPACITY))
        except (DeviceRejectError, BuildError):
            state["kernel"] = None      # every batch goes host

        def dispatch(sc):
            batch = sc.chunk
            _STREAM_STATS["batches"] += 1
            _STREAM_STATS["max_batch_rows"] = max(
                _STREAM_STATS["max_batch_rows"], batch.num_rows)
            k = state["kernel"]
            if k is None:
                # no device kernel for this plan (failed validation /
                # build): every batch aggregates on the host
                runtime_stats.note_fallback(plan, "unsupported")
                return None              # host path at finalize
            # device ledger: the sharded padded upload, sized from
            # shapes at dispatch; credited back at finalize
            db = memtrack.device_put_bytes(batch)
            memtrack.consume(plan, device=db)
            try:
                outs = k.launch(batch, bucket=True)
            except (DeviceRejectError, CollisionError, BuildError) as e:
                memtrack.release(plan, device=db)
                runtime_stats.note_fallback(plan, _fallback_reason(e))
                return None
            except BaseException:        # quota cancel / device fault
                memtrack.release(plan, device=db)
                raise
            if state["inflight"]:
                _STREAM_STATS["overlapped_launches"] += 1
            state["inflight"] += 1
            profiler.note_bytes(profiler.profile_of(k), nbytes=db)
            runtime_stats.note_superchunk(
                plan, batch.num_rows, bucket_size(max(batch.num_rows, 1)),
                sc.sources)
            return (k, outs, db)

        def finalize(sc, tok):
            batch = sc.chunk
            if tok is None:
                _STREAM_STATS["host_batches"] += 1
                return host_batch(batch)
            k, outs, db = tok
            state["inflight"] -= 1
            t0 = time.perf_counter_ns()
            reason = "capacity"
            try:
                return k.finish(outs, batch)
            except CapacityError as e:
                # per-batch capacity re-plan: re-run only THIS batch at
                # 2x the observed distinct count; later batches dispatch
                # with the escalated kernel
                profiler.note_escalation(profiler.profile_of(k))
                needed = getattr(e, "needed", None)
                while needed is not None:
                    cap2 = 1 << max(needed * 2 - 1, 1).bit_length()
                    if cap2 > MAX_CAPACITY:
                        break
                    try:
                        k2 = get_kernel(cap2)
                        gr = k2.finish(k2.launch(batch, bucket=True),
                                       batch)
                        state["kernel"] = k2
                        plan._mesh_capacity = cap2
                        return gr
                    except CapacityError as e2:
                        needed = getattr(e2, "needed", None)
                    except (CollisionError, BuildError,
                            DeviceRejectError) as e2:
                        reason = _fallback_reason(e2)
                        break
            except (CollisionError, BuildError, DeviceRejectError) as e:
                reason = _fallback_reason(e)
            finally:
                memtrack.release(plan, device=db)
                runtime_stats.note_finalize_wait(
                    plan, time.perf_counter_ns() - t0)
            _STREAM_STATS["host_batches"] += 1
            runtime_stats.note_fallback(plan, reason)
            return host_batch(batch)

        tracked = 0
        try:
            for gr in op_runtime.pipeline_map(
                    superchunks, dispatch, finalize,
                    sysconf.pipeline_depth(), tracker=mt_node,
                    cost=lambda sc: memtrack.chunk_bytes(sc.chunk),
                    profile=profiler.profile_of(state["kernel"])):
                agg.update(gr)
                tracked = memtrack.track_to(plan, agg.approx_bytes(),
                                            tracked)
        except BaseException:
            # the caller's finally releases only what we report; on an
            # unwinding cancel nothing is reported, so credit here
            memtrack.release(plan, host=tracked)
            raise
        return tracked

    def _buffer_probe(self, it, limit):
        """Pull chunks until the probe proves larger than `limit`.
        -> (buffered parts, total rows, exhausted?)."""
        parts, total = [], 0
        for c in it:
            if c.num_rows:
                parts.append(c)
                total += c.num_rows
            if total > limit:
                return parts, total, False
        return parts, total, True


class MeshAggExec(_MeshExecBase):
    """Group-by aggregation on the device plane (Q1 shape)."""

    def _chunks(self, ctx, own):
        import tidb_tpu.executor as ex

        mesh = devplane.active_mesh()
        if mesh is None:
            yield from self._fallback(ctx, own)
            return
        plan = self.plan
        schema = plan.children[0].schema
        reader = ex.build_executor(plan.children[0])
        it = own.pull(self._cached_scan(reader, ctx))
        limit = sysconf.stream_rows()
        parts, total, exhausted = self._buffer_probe(it, limit)

        def make(capacity):
            return MeshAggKernel(mesh, plan.filter_expr, plan.group_exprs,
                                 plan.aggs, capacity=capacity)

        if not exhausted:
            # probe larger than the streaming threshold: never materialize
            # it — feed the kernel ≤limit-row super-batches, dispatch-ahead
            def get_kernel(capacity):
                k = _kernel_cache_get(plan, capacity)
                if k is None:
                    k = make(capacity)
                    _kernel_cache_put(plan, capacity, k)
                return k

            agg = HashAggregator(plan.aggs, plan.group_exprs)
            tracked = 0
            try:
                # plane pipelines overlap async launches, so the device
                # time is the whole streaming region's wall (readback)
                with runtime_stats.device_section(plan):
                    tracked = self._stream_groups(
                        superchunk_batches(itertools.chain(parts, it),
                                           limit,
                                           tracker=memtrack.op_node(plan)),
                        get_kernel,
                        lambda b: host_hash_agg(b, plan.filter_expr,
                                                plan.group_exprs,
                                                plan.aggs),
                        agg)
                yield _emit_agg(plan, agg, ex)
            finally:
                memtrack.release(plan, host=tracked)
            return

        # small probe: whole-table path, memoized so hot re-executions of
        # a cached plan transfer zero bytes (the resident copy belongs to
        # the memo; the transfer watermark below is this query's charge)
        big = _concat_chunks_cached(plan, "_probe_cache", parts, schema)
        gr = None
        if big.num_rows:
            try:
                failpoint.eval("device/dispatch")
                with sched.device_slot() as slot, \
                        runtime_stats.device_section(plan,
                                                     errors=False), \
                        memtrack.device_scope(
                            plan, memtrack.device_put_bytes(big)):
                    gr = self._run_with_escalation(
                        make,
                        lambda k: self._whole_table_run(k, big, slot.chip))
            except failpoint.DispatchTimeoutError:
                raise   # statement already cancel-latched by the watchdog
            except failpoint.DeviceFaultError:
                sched.device_health().note_fault()
                runtime_stats.note_fallback(plan, "fault")
                yield from self._fallback(ctx, own)
                return
            if gr is None:
                yield from self._fallback(ctx, own)
                return
            # the whole table went down as ONE maximally-coalesced batch
            runtime_stats.note_superchunk(
                plan, big.num_rows, bucket_size(max(big.num_rows, 1)),
                max(len(parts), 1))
        yield _emit_results(plan, gr, ex)


class MeshLookupAggExec(_MeshExecBase):
    """Star join + aggregation on the device plane (Q3/Q5 shape)."""

    def _chunks(self, ctx, own):
        import tidb_tpu.executor as ex

        mesh = devplane.active_mesh()
        if mesh is None:
            yield from self._fallback(ctx, own)
            return
        plan = self.plan
        try:
            specs = []
            for lk in plan.lookups:
                bexec = ex.build_executor(lk.build_plan)
                bchunk = _concat_chunks_cached(lk, "_chunk_cache",
                                               list(own.pull(
                                                   self._cached_scan(
                                                       bexec, ctx))),
                                               lk.build_plan.schema)
                specs.append(LookupSpec(
                    key_exprs=lk.key_exprs, build_chunk=bchunk,
                    build_key_offsets=lk.build_key_offsets,
                    payload_offsets=lk.payload_offsets))
            builds = [self._build_table(d, sp)
                      for d, sp in zip(plan.lookups, specs)]
        except BuildError:
            # non-unique / NULL-heavy dimension keys: host join fallback
            yield from self._fallback(ctx, own)
            return

        def make(capacity):
            k = MeshLookupAggKernel(mesh, plan.filter_expr, specs,
                                    plan.group_exprs, plan.aggs,
                                    capacity=capacity, builds=builds)
            k.lookups = specs    # freshly built: skip the refresh rebuild
            return k

        def refresh(kernel):
            if kernel.lookups is not specs:
                # cached kernel: the traced program depends only on the
                # lookup STRUCTURE; swap in the current tables
                kernel.lookups = specs
                kernel.builds = builds
            return kernel

        reader = ex.build_executor(plan.children[0])
        it = own.pull(self._cached_scan(reader, ctx))
        limit = sysconf.stream_rows()
        parts, total, exhausted = self._buffer_probe(it, limit)

        if not exhausted:
            # fact side larger than the streaming threshold: feed the
            # lookup-chain kernel in super-batches; dimension tables stay
            # resident on device across batches (device-memoized builds)
            def get_kernel(capacity):
                k = _kernel_cache_get(plan, capacity)
                if k is None:
                    k = make(capacity)
                    _kernel_cache_put(plan, capacity, k)
                return refresh(k)

            agg = HashAggregator(plan.aggs, plan.group_exprs)
            tracked = 0
            try:
                with runtime_stats.device_section(plan):
                    tracked = self._stream_groups(
                        superchunk_batches(itertools.chain(parts, it),
                                           limit,
                                           tracker=memtrack.op_node(plan)),
                        get_kernel,
                        lambda b: host_lookup_agg(b, plan.filter_expr,
                                                  specs, plan.group_exprs,
                                                  plan.aggs,
                                                  builds=builds),
                        agg)
                yield _emit_agg(plan, agg, ex)
            finally:
                memtrack.release(plan, host=tracked)
            return

        probe = _concat_chunks_cached(plan, "_probe_cache", parts,
                                      plan.children[0].schema)
        gr = None
        if probe.num_rows:
            try:
                failpoint.eval("device/dispatch")
                with sched.device_slot() as slot, \
                        runtime_stats.device_section(plan,
                                                     errors=False), \
                        memtrack.device_scope(
                            plan, memtrack.device_put_bytes(probe)):
                    gr = self._run_with_escalation(
                        make,
                        lambda kernel: self._whole_table_run(
                            refresh(kernel), probe, slot.chip))
            except failpoint.DispatchTimeoutError:
                raise   # statement already cancel-latched by the watchdog
            except failpoint.DeviceFaultError:
                sched.device_health().note_fault()
                runtime_stats.note_fallback(plan, "fault")
                yield from self._fallback(ctx, own)
                return
            if gr is None:
                yield from self._fallback(ctx, own)
                return
            runtime_stats.note_superchunk(
                plan, probe.num_rows, bucket_size(max(probe.num_rows, 1)),
                max(len(parts), 1))
        yield _emit_results(plan, gr, ex)

    @staticmethod
    def _build_table(desc, spec):
        """Host build-table prep (sort, exact-bit lanes, device upload)
        memoized on the plan's lookup descriptor: when the storage chunk
        cache serves the same dimension chunk object again, the prepared
        table (and its device copy) is reused as-is."""
        cached = getattr(desc, "_build_cache", None)
        if cached is not None and cached[0] is spec.build_chunk:
            return cached[1]
        bt = _BuildTable(spec)
        desc._build_cache = (spec.build_chunk, bt)
        return bt
