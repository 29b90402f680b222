"""Volcano executors over chunks.

Reference: /root/reference/executor/ — Executor iface (executor.go:172-180,
Open/NextChunk/Close), builder dispatch (builder.go:62-146). Pull model kept
(chunked iterators), but per-chunk compute is columnar numpy / XLA instead
of row loops; the distsql leaves stream partial results from the
coprocessor fan-out (distsql.go:92).
"""

from __future__ import annotations

import threading
import time

import numpy as np

from tidb_tpu import (config, kv, memtrack, metrics, profiler,
                      runtime_stats, sched, tablecodec, trace)
from tidb_tpu.chunk import Chunk, Column
from tidb_tpu.expression import AggDesc, AggFunc, Expression
from tidb_tpu.kv import CopRequest, KVRange, ReqType
from tidb_tpu.ops import hybrid as op_hybrid
from tidb_tpu.ops import runtime as op_runtime
from tidb_tpu.ops.hashagg import (CapacityError, CollisionError,
                                  DeviceRejectError, GroupResult,
                                  HashAggregator, kernel_for)
from tidb_tpu.ops.hostagg import host_hash_agg
from tidb_tpu.ops.join import (JoinKernel, JoinKeyEncoder,
                               host_match_pairs)
from tidb_tpu.ops.streamagg import segment_kernel_for
from tidb_tpu.ops.runtime import eval_filter_host, super_batches
from tidb_tpu.plan import physical as ph
from tidb_tpu.sqltypes import EvalType, FieldType, np_dtype_for
from tidb_tpu.store.copr import exec_cop_plan
from tidb_tpu.table import Table, encode_datum_for_col, kvrows_to_chunk

__all__ = ["build_executor", "ExecError", "ExecContext"]


class ExecError(kv.KVError):
    pass


# shared shuffle-join kernels, keyed (mesh_generation, num_keys): the
# shard_map program is shape-polymorphic, so one kernel serves every
# query with the same key arity on the same mesh
_SHUFFLE_KERNELS: dict = {}
_SHUFFLE_KERNELS_LOCK = threading.Lock()


def _evict_stale_shuffle_kernels() -> None:
    from tidb_tpu import devplane as mesh_config
    gen = mesh_config.mesh_generation()
    with _SHUFFLE_KERNELS_LOCK:
        for k in [k for k in _SHUFFLE_KERNELS if k[0] != gen]:
            _SHUFFLE_KERNELS.pop(k, None)


def _register_mesh_listener() -> None:
    # release compiled shard_map executables when the topology changes
    # (incl. disable_mesh — no later join would otherwise evict them)
    from tidb_tpu import devplane as mesh_config
    mesh_config.on_topology_change(_evict_stale_shuffle_kernels)


_register_mesh_listener()


class ExecContext:
    """What executors need from the session: storage, the read ts, the
    active transaction (for writes and dirty reads), and an interrupt
    probe (KILL QUERY; ref: the Go ctx cancellation threaded through
    executors)."""

    def __init__(self, storage, read_ts: int, txn=None,
                 interrupted=None):
        self.storage = storage
        self.read_ts = read_ts
        self.txn = txn   # kv transaction or None (autocommit read)
        self.interrupted = interrupted

    def check_interrupt(self) -> None:
        if self.interrupted is not None and self.interrupted():
            raise ExecError("Query execution was interrupted")


class Executor:
    schema = None

    def open(self, ctx: ExecContext):
        pass

    def chunks(self, ctx: ExecContext):
        """Yields Chunks."""
        raise NotImplementedError

    def close(self):
        pass


class _OwnSpan:
    """One root executor's own host work under spans of one name: open
    while the operator's generator body runs, closed while it waits for
    a child's chunk (`pull`) and while its consumer holds a chunk it
    yielded (`drive`). The executors are generators: a span held open
    across `for chunk in child.chunks(ctx)` would take the child's time
    (a TableReader's wait for frames) into this operator's name, and one
    open across a `yield` would interleave with the consumer's spans. So
    no span is open wherever the generator is suspended, and what the
    body dispatches to the device keeps its own child spans (sched.slot
    / dispatch / finalize / join.partition): the self time left under
    the name is the host's. A span a pull or a yield, never a row.
    `open_span` is the call site's `lambda: trace.span("<literal>")`
    (lint rule trace-names)."""

    __slots__ = ("_open", "_cm")

    def __init__(self, open_span):
        self._open = open_span
        self._cm = None

    def resume(self) -> None:
        self._cm = self._open()

    def suspend(self) -> None:
        cm, self._cm = self._cm, None
        if cm is not None:
            cm.__exit__(None, None, None)

    def pull(self, it):
        """A child's stream, each pull of it outside the span."""
        it = iter(it)
        while True:
            self.suspend()
            try:
                item = next(it)
            except StopIteration:
                return
            finally:
                self.resume()
            yield item

    def drive(self, body):
        """The operator's generator `body`, run inside the span and
        handing each chunk on outside it."""
        self.resume()
        try:
            for out in body:
                self.suspend()
                try:
                    yield out
                finally:
                    self.resume()
        finally:
            # a consumer that stops early closes this generator at the
            # yield: the body's own clean-up runs now, inside the span
            try:
                body.close()
            finally:
                self.suspend()


def build_executor(plan: ph.PhysPlan) -> Executor:
    """Ref: executorBuilder.build (builder.go:62-146)."""
    t = type(plan)
    b = _BUILDERS.get(t)
    if b is None:
        raise ExecError(f"no executor for {t.__name__}")
    exe = b(plan)
    # per-statement runtime stats: children are built (and wrapped)
    # inside the constructor above, so every node in the tree passes
    # through here exactly once per execution
    runtime_stats.instrument(exe, plan)
    return exe


# ---------------------------------------------------------------------------
# Readers

def _txn_is_dirty(ctx: ExecContext, table_id: int) -> bool:
    if ctx.txn is None:
        return False
    lo, hi = tablecodec.table_prefix_range(table_id)
    for _k, _v in ctx.txn.us.membuf.iter_range(lo, hi):
        return True
    return False


class TableReaderExec(Executor):
    """distsql leaf (ref: executor/distsql.go:297 TableReaderExecutor).
    Streams region partial results; in a dirty transaction, falls back to
    scanning through the union store so own writes are visible
    (ref: UnionScanExec, executor/union_scan.go:90)."""

    def __init__(self, plan: ph.PhysTableReader):
        self.plan = plan
        self.schema = plan.schema

    def _ranges(self):
        cop = self.plan.cop
        if cop.ranges is not None:
            return cop.ranges
        lo = tablecodec.record_prefix(cop.table.id)
        from tidb_tpu import codec
        return [KVRange(lo, codec.prefix_next(lo))]

    def _count_columns(self):
        cop = self.plan.cop
        metrics.counter(metrics.READER_COLUMNS, {"kind": "scanned"},
                        len(cop.cols))
        metrics.counter(metrics.READER_COLUMNS, {"kind": "table"},
                        len(cop.table.public_columns()))

    def partials(self, ctx: ExecContext):
        """Agg mode: yields GroupResults."""
        cop = self.plan.cop
        self._count_columns()
        if _txn_is_dirty(ctx, cop.table.id):
            for chunk in self._dirty_chunks(ctx):
                yield exec_cop_plan(cop, chunk).chunk
            return
        req = CopRequest(tp=ReqType.DAG, ranges=self._ranges(), plan=cop,
                         start_ts=ctx.read_ts)
        for resp in ctx.storage.client().send(req):
            ctx.check_interrupt()
            yield resp.chunk

    def chunks(self, ctx: ExecContext):
        cop = self.plan.cop
        assert not cop.is_agg
        self._count_columns()
        if _txn_is_dirty(ctx, cop.table.id):
            for chunk in self._dirty_chunks(ctx):
                yield exec_cop_plan(cop, chunk).chunk
            return
        req = CopRequest(tp=ReqType.DAG, ranges=self._ranges(), plan=cop,
                         start_ts=ctx.read_ts,
                         keep_order=getattr(self.plan, "keep_order", False))
        if cop.feedback is not None and cop.limit is None:
            yield from self._chunks_with_feedback(ctx, req)
            return
        remaining = cop.limit
        for resp in ctx.storage.client().send(req):
            ctx.check_interrupt()
            ch = resp.chunk
            if remaining is not None:
                if remaining <= 0:
                    return
                if ch.num_rows > remaining:
                    ch = ch.slice(0, remaining)
                remaining -= ch.num_rows
            yield ch

    def _chunks_with_feedback(self, ctx, req):
        """Stream the scan while counting actual rows; report the range's
        true cardinality to the stats handle afterwards (ref:
        statistics/update.go:88 QueryFeedback collection at the reader)."""
        cop = self.plan.cop
        actual = 0
        for resp in ctx.storage.client().send(req):
            ctx.check_interrupt()
            actual += resp.chunk.num_rows
            yield resp.chunk
        col_id, dranges = cop.feedback
        try:
            from tidb_tpu.session import Domain
            Domain.get(ctx.storage).stats_handle().feedback_range(
                cop.table.id, col_id, dranges, actual)
        except Exception:   # noqa: BLE001 - feedback must never fail reads
            pass

    def _decode_rows(self, rows):
        cop = self.plan.cop
        return kvrows_to_chunk(cop.table, cop.cols, rows, cop.handle_col)

    def _dirty_chunks(self, ctx: ExecContext):
        """Union-store scan: buffered writes shadow the snapshot. The cop
        plan then runs at the root over these chunks (host compute)."""
        rows = []
        for rng in self._ranges():
            for k, v in ctx.txn.iter_range(rng.start, rng.end):
                rows.append((k, v))
                if len(rows) >= 65536:
                    yield self._decode_rows(rows)
                    rows = []
        yield self._decode_rows(rows)


class IndexReaderExec(TableReaderExec):
    """Covering-index distsql leaf (ref: executor/distsql.go:412
    IndexReaderExecutor): identical client machinery; the storage side
    decodes index entries instead of rows."""

    def __init__(self, plan: ph.PhysIndexReader):
        self.plan = plan
        self.schema = plan.schema

    def _decode_rows(self, rows):
        from tidb_tpu.table import index_kvrows_to_chunk
        cop = self.plan.cop
        return index_kvrows_to_chunk(cop.table, cop.index, cop.cols, rows,
                                     cop.handle_col)


class IndexLookUpExec(Executor):
    """Index scan -> handle batches -> parallel batched row fetch.
    Ref: executor/distsql.go:524-737 — index worker streaming handles into
    lookupTableTasks consumed by a table-worker pool; order preserved by
    yielding futures in submission order."""

    BATCH = 1024              # handles per lookup task
    LOOKUP_CONCURRENCY = 4    # ref: IndexLookupConcurrency default

    def __init__(self, plan: ph.PhysIndexLookUp):
        self.plan = plan
        self.schema = plan.schema

    def _handle_batches(self, ctx: ExecContext):
        icop = self.plan.index_cop
        req = CopRequest(tp=ReqType.DAG, ranges=icop.ranges, plan=icop,
                         start_ts=ctx.read_ts,
                         keep_order=self.plan.keep_order)
        batch: list[int] = []
        hcol = icop.handle_col
        for resp in ctx.storage.client().send(req):
            ch = resp.chunk
            handles = ch.columns[hcol].data
            for h in handles.tolist():
                batch.append(h)
                if len(batch) >= self.BATCH:
                    yield batch
                    batch = []
        if batch:
            yield batch

    def _fetch_rows(self, ctx: ExecContext, handles: list[int]):
        tcop = self.plan.table_cop
        snap = ctx.storage.snapshot(ctx.read_ts)
        keys = [tablecodec.record_key(tcop.table.id, h) for h in handles]
        got = snap.batch_get(keys)
        kvrows = [(k, got[k]) for k in keys if k in got]
        chunk = kvrows_to_chunk(tcop.table, tcop.cols, kvrows,
                                tcop.handle_col)
        return exec_cop_plan(tcop, chunk).chunk

    def chunks(self, ctx: ExecContext):
        tcop = self.plan.table_cop
        if _txn_is_dirty(ctx, tcop.table.id):
            # own writes visible: all conjuncts are retained in the
            # residual filters, so a full union-store scan is equivalent
            yield from TableReaderExec(
                ph.PhysTableReader(schema=self.schema, cop=tcop)).chunks(ctx)
            return
        from collections import deque
        from concurrent.futures import ThreadPoolExecutor
        pool = ThreadPoolExecutor(max_workers=self.LOOKUP_CONCURRENCY,
                                  thread_name_prefix="idxlookup")
        pending = deque()
        try:
            for batch in self._handle_batches(ctx):
                pending.append(pool.submit(self._fetch_rows, ctx, batch))
                while len(pending) >= self.LOOKUP_CONCURRENCY:
                    yield pending.popleft().result()
            while pending:
                yield pending.popleft().result()
        finally:
            pool.shutdown(wait=False, cancel_futures=True)


class PointGetExec(Executor):
    """Single-row read bypassing the coprocessor (ref: the point-get fast
    path detector, executor/adapter.go:381). Reads through the active
    transaction's union store so own writes are visible."""

    def __init__(self, plan: ph.PhysPointGet):
        self.plan = plan
        self.schema = plan.schema

    def chunks(self, ctx: ExecContext):
        p = self.plan
        retr = ctx.txn if ctx.txn is not None \
            else ctx.storage.snapshot(ctx.read_ts)
        handle = p.handle
        if p.index is not None:
            ik = tablecodec.index_key(p.table.id, p.index.id,
                                      list(p.index_values))
            from tidb_tpu import codec as _codec
            v = retr.get(ik)
            if v is None:
                yield kvrows_to_chunk(p.table, p.cols, [], p.handle_col)
                return
            handle, _ = _codec.decode_int(v, 0)
        rk = tablecodec.record_key(p.table.id, handle)
        raw = retr.get(rk)
        kvrows = [] if raw is None else [(rk, raw)]
        chunk = kvrows_to_chunk(p.table, p.cols, kvrows, p.handle_col)
        if p.filter is not None and chunk.num_rows:
            chunk = chunk.filter(eval_filter_host(p.filter, chunk))
        yield chunk


class ValuesExec(Executor):
    def __init__(self, plan: ph.PhysValues):
        self.plan = plan
        self.schema = plan.schema

    def chunks(self, ctx):
        fts = [c.ft for c in self.plan.schema.cols] if self.plan.schema.cols \
            else []
        rows = []
        for rexprs in self.plan.rows:
            row = []
            for e in rexprs:
                d, v = e.eval_xp(np, [], 1)
                row.append(None if not v[0] else
                           (d[0].item() if hasattr(d[0], "item") else d[0]))
            rows.append(row)
        if not fts and rows:
            fts = [e.ft for e in self.plan.rows[0]]
        cols = []
        for j, ft in enumerate(fts):
            dtype = np_dtype_for(ft.tp, ft.flen)
            valid = np.array([r[j] is not None for r in rows], dtype=bool)
            if dtype == np.dtype(object):
                from tidb_tpu.sqltypes import object_fill
                _fill = object_fill(ft)
                data = np.array([r[j] if r[j] is not None else _fill
                                 for r in rows], dtype=object)
            else:
                data = np.array([r[j] if r[j] is not None else 0
                                 for r in rows], dtype=dtype)
            cols.append(Column(ft, data, valid))
        yield Chunk(cols)


# ---------------------------------------------------------------------------
# Aggregation

# lint: exempt[memtrack-alloc] group-count-sized outputs, bounded by the tracked agg state (HashAggregator.approx_bytes)
def _agg_results_to_chunk(schema, num_group: int, aggs: list[AggDesc],
                          results) -> Chunk:
    fts = [c.ft for c in schema.cols]
    n = len(results)
    arrays = []
    for j, ft in enumerate(fts):
        dtype = np_dtype_for(ft.tp, ft.flen)
        valid = np.ones(n, dtype=bool)
        data = np.empty(n, dtype=object) if dtype == np.dtype(object) \
            else np.zeros(n, dtype=dtype)
        arrays.append((data, valid))
    for i, (key, vals) in enumerate(results):
        for j in range(num_group):
            v = key[j]
            data, valid = arrays[j]
            if v is None:
                valid[i] = False
                if data.dtype == np.dtype(object):
                    data[i] = ""
            else:
                data[i] = v
        for a_i, v in enumerate(vals):
            data, valid = arrays[num_group + a_i]
            if v is None:
                valid[i] = False
                if data.dtype == np.dtype(object):
                    data[i] = ""
            else:
                data[i] = v
    return Chunk([Column(ft, d, v) for ft, (d, v) in zip(fts, arrays)])


# lint: exempt[memtrack-alloc] group-count-sized outputs, bounded by the tracked agg state (HashAggregator.approx_bytes)
def _agg_columns_to_chunk(schema, cols) -> Chunk:
    """The columnar merge's (data, valid) columns as the output chunk,
    each cast to its column's storage dtype (object columns hold host
    python values, "" under NULL, as _agg_results_to_chunk writes)."""
    out = []
    for ft, (d, v) in zip((c.ft for c in schema.cols), cols):
        dtype = np_dtype_for(ft.tp, ft.flen)
        if dtype == np.dtype(object):
            data = np.empty(len(d), dtype=object)
            data[:] = [x if ok else "" for x, ok in
                       zip(d.tolist(), v.tolist())]
        else:
            data = d.astype(dtype)
        out.append(Column(ft, data, v))
    return Chunk(out)


def _agg_chunk(schema, num_group: int, aggs: list[AggDesc],
               agg: HashAggregator, note: bool = True) -> Chunk:
    """A root aggregate's one output chunk: straight from the columns on
    the columnar path, row by row from results() on the dict path; a
    scalar aggregate over no rows still answers one row. `note`: count
    the groups emitted (_note_final_groups)."""
    got = agg.result_columns()
    if got is None:
        results = agg.results()
        n, cols = len(results), None
    else:
        (n, cols), results = got, []
    if not n and not num_group:
        results, n, cols = [((), [_empty_agg_value(a) for a in aggs])], 1, None
    if note:
        _note_final_groups(n, "columnar" if cols else "dict")
    if cols:
        return _agg_columns_to_chunk(schema, cols)
    return _agg_results_to_chunk(schema, num_group, aggs, results)


class FinalAggExec(Executor):
    """Merges storage-side partials (ref: final HashAgg over partial agg,
    executor/aggregate.go + aggregation.GetPartialResult protocol)."""

    def __init__(self, plan: ph.PhysFinalAgg):
        self.plan = plan
        self.schema = plan.schema
        self.reader = build_executor(plan.children[0])

    def chunks(self, ctx):
        own = _OwnSpan(lambda: trace.span("exec.agg"))
        yield from own.drive(self._merged(ctx, own))

    def _merged(self, ctx, own):
        # partials arrive pre-grouped: key fts are the schema's leading
        # num_group_cols columns
        agg = HashAggregator(
            self.plan.aggs,
            [c.ft for c in
             self.plan.schema.cols[:self.plan.num_group_cols]])
        tracked = 0
        try:
            for gr in own.pull(self.reader.partials(ctx)):
                agg.update(gr)
                tracked = memtrack.track_to(self.plan,
                                            agg.approx_bytes(), tracked)
            yield _agg_chunk(self.schema, self.plan.num_group_cols,
                             self.plan.aggs, agg)
        finally:
            memtrack.release(self.plan, host=tracked)


def _empty_agg_value(a: AggDesc):
    return 0 if a.fn == AggFunc.COUNT else None


def _note_final_groups(n: int, path: str) -> None:
    """The groups a root aggregate is about to emit: the `groups` tag
    of the exec.agg span open around the caller, and the counter that
    divides that span's self time — its total, and by the merge that
    built them (`path`: HashAggregator.path)."""
    trace.annotate(groups=n)
    metrics.counter(metrics.AGG_FINAL_GROUPS, inc=n)
    metrics.counter(metrics.AGG_FINAL_GROUPS, {"path": path}, inc=n)


class HashAggExec(Executor):
    """Root-side complete aggregation over child chunks."""

    def __init__(self, plan: ph.PhysHashAgg):
        self.plan = plan
        self.schema = plan.schema
        self.child = build_executor(plan.children[0])
        # kernels live on the plan object: the plan cache shares plans
        # across executions, so the jit program (and its XLA compile)
        # outlives any one query run
        self._kernel = getattr(plan, "_root_kernel", None)

    def chunks(self, ctx):
        own = _OwnSpan(lambda: trace.span("exec.agg"))
        yield from own.drive(self._aggregated(ctx, own))

    def _aggregated(self, ctx, own):
        agg = HashAggregator(self.plan.aggs, self.plan.group_exprs)
        distinct_ok = all(not a.distinct for a in self.plan.aggs)
        sc_rows = config.superchunk_rows()
        tracked = 0
        try:
            if distinct_ok and config.device_enabled() and sc_rows:
                # fused pipeline fragment (ops/fragment.py): when the
                # child is a plain inner hash join, ONE XLA program per
                # probe superchunk executes match + gather + group +
                # partial agg — the joined intermediate never
                # materializes in HBM or on the host
                frag = self._fragment_kernel()
                source = self._fused_partials(ctx, frag, own) \
                    if frag is not None else \
                    self._superchunk_partials(
                        own.pull(self.child.chunks(ctx)))
                # superchunk pipeline: child chunks coalesce into big
                # padded batches and flow through the dispatch-ahead
                # device queue; one partial-agg dispatch per superchunk
                for gr in source:
                    agg.update(gr)
                    tracked = memtrack.track_to(
                        self.plan, agg.approx_bytes(), tracked)
            else:
                for chunk in own.pull(self.child.chunks(ctx)):
                    if chunk.num_rows == 0:
                        continue
                    gr = None
                    if distinct_ok and config.device_enabled() and \
                            chunk.num_rows >= config.device_min_rows():
                        gr = self._device_partial(chunk)
                    if gr is None:
                        gr = host_hash_agg(chunk, None,
                                           self.plan.group_exprs,
                                           self.plan.aggs)
                    agg.update(gr)
                    tracked = memtrack.track_to(
                        self.plan, agg.approx_bytes(), tracked)
            yield _agg_chunk(self.schema, len(self.plan.group_exprs),
                             self.plan.aggs, agg)
        finally:
            memtrack.release(self.plan, host=tracked)

    def _set_kernel(self, kernel) -> None:
        self._kernel = kernel
        # kernels live on the plan object: the plan cache shares plans
        # across executions, so the jit program outlives any one run
        self.plan._root_kernel = kernel

    def _fragment_kernel(self):
        """A ProbeAggKernel when this agg can fuse with its child join
        into one program per probe superchunk (ops/fragment.py), else
        None. Fusion requires a plain single-chip inner hash join (no
        other_cond — pair filtering would need the joined width) and a
        device-safe group/agg set over the joined schema; everything
        else keeps the per-operator path."""
        if not config.fuse_fragments_enabled():
            return None
        join = self.child
        if type(join) is not HashJoinExec:      # not Merge/Index subclasses
            return None
        jplan = join.plan
        if jplan.join_type != "inner" or jplan.other_cond is not None \
                or not jplan.left_keys:
            return None
        from tidb_tpu import devplane as mesh_config
        mesh = mesh_config.active_mesh()
        if mesh is not None and mesh.devices.size > 1:
            return None     # the mesh shuffle plane owns multi-chip joins
        from tidb_tpu.ops import fragment as op_fragment
        nl = len(jplan.children[0].schema)
        width = nl + len(jplan.children[1].schema)
        try:
            return op_fragment.fragment_kernel_for(
                len(jplan.left_keys), nl, width, self.plan.group_exprs,
                self.plan.aggs)
        except (DeviceRejectError, NotImplementedError, ValueError):
            return None

    def _fused_partials(self, ctx, fk, own):
        """Partial GroupResults from the fused probe->agg fragment: the
        build side uploads once (used columns + key lanes), probe
        superchunks stream through the dispatch-ahead pipeline, and
        each in-flight token is one whole-fragment program. A capacity
        miss escalates the fragment kernel once (later batches inherit
        it); a miss that survives — or a collision — falls back to the
        decoded per-batch path (host pair match + gather + host agg),
        counted on tidb_tpu_device_fallback_total. The fragment is this
        operator's work, join included (`own`, its exec.agg span: the
        join's two inputs are pulled outside it)."""
        plan = self.plan
        join = self.child
        jplan = join.plan
        nl = len(jplan.children[0].schema)
        width = nl + len(jplan.children[1].schema)
        build = Chunk.concat_all(list(own.pull(join.right.chunks(ctx))))
        nb = build.num_rows if build is not None else 0
        if nb == 0:
            return      # inner join over an empty build: no input rows
        tracked = memtrack.track_to(plan, memtrack.chunk_bytes(build))
        enc = JoinKeyEncoder(len(jplan.right_keys))
        raw_bk = join._eval_keys(jplan.right_keys, build)
        bk = enc.fit_build(
            raw_bk, encoded=join._encoded_keys(jplan.right_keys, build),
            ci=[e.ft.is_ci for e in jplan.right_keys])
        engage, hot, h = join._hybrid_engage(bk, nb, raw_bk)
        if engage:
            # skew / quota pressure / over-superchunk build: the hybrid
            # join's heavy-hitter lanes and partition-spill machinery
            # own this probe — run the per-operator path (the fragment
            # would funnel a 30%-hot key through ONE ballooning pair
            # buffer with nothing sheddable under quota). The encoded
            # keys, hashes and hot set just computed ride along.
            try:
                yield from self._superchunk_partials(join._probe_join(
                    ctx, build, nb, own,
                    prepared=(enc, bk, raw_bk, hot, h)))
            finally:
                memtrack.release(plan, host=tracked)
            return
        state = {"fk": fk, "build_dev": None, "build_db": 0}
        min_rows = config.device_min_rows()
        mt_node = memtrack.op_node(plan)

        def decoded_batch(pk, chunk):
            li, ri = host_match_pairs(bk, pk, nb, chunk.num_rows)
            pair = join._gather(chunk, build, li, ri)
            return host_hash_agg(pair, None, plan.group_exprs,
                                 plan.aggs)

        def dispatch(sc):
            n = sc.num_rows
            pk = join._probe_keys(enc, sc.chunk)
            if n < min_rows and nb < join._DEVICE_MIN_BUILD:
                return ("host", pk, 0)
            k = state["fk"]
            if state["build_dev"] is None:
                # build lanes stay device-resident for the whole probe
                state["build_db"] = k.build_nbytes(build, nb)
                memtrack.consume(plan, device=state["build_db"])
                state["build_dev"] = k.prepare_build(build, bk, nb)
            cap = op_runtime.bucket_size(max(n * 2, 1024))
            db = k.dispatch_nbytes(sc.chunk, cap)
            memtrack.consume(plan, device=db)
            try:
                tok = k.dispatch(state["build_dev"], nb, pk, sc.chunk, n)
            except BaseException:
                memtrack.release(plan, device=db)
                raise
            profiler.note_bytes(profiler.profile_of(k), nbytes=db)
            runtime_stats.note_superchunk(plan, n, sc.bucket, sc.sources)
            runtime_stats.note_bytes_touched(
                memtrack.chunk_bytes(sc.chunk), k.input_nbytes(sc.chunk))
            return ("dev", (k, tok, pk), db)

        def finalize(sc, tok):
            kind, payload, db = tok
            if kind == "host":
                return decoded_batch(payload, sc.chunk)
            k, pend, pk = payload
            t0 = time.perf_counter_ns()
            try:
                gr = k.finalize(sc.chunk, build, nb, pend)
                runtime_stats.note_encoding(plan, "fused:probe-agg")
                runtime_stats.note_mode(plan, "fused")
                return gr
            except CapacityError as e:
                profiler.note_escalation(profiler.profile_of(k))
                k2 = self._escalated_fragment(e, nl, width)
                if k2 is not None:
                    state["fk"] = k2    # later batches dispatch with it
                    n = sc.num_rows
                    cap = op_runtime.bucket_size(max(n * 2, 1024))
                    with sched.device_slot(), memtrack.device_scope(
                            plan, k2.dispatch_nbytes(sc.chunk, cap)):
                        try:
                            gr = k2.finalize(
                                sc.chunk, build, nb,
                                k2.dispatch(state["build_dev"], nb, pk,
                                            sc.chunk, n))
                            runtime_stats.note_encoding(
                                plan, "fused:probe-agg")
                            runtime_stats.note_mode(plan, "fused")
                            return gr
                        except (CapacityError, CollisionError):
                            pass
                runtime_stats.note_fallback(plan, "capacity")
                profiler.note_kernel_fallback(profiler.profile_of(k),
                                              "capacity")
                return decoded_batch(pk, sc.chunk)
            except CollisionError:
                runtime_stats.note_fallback(plan, "collision")
                profiler.note_kernel_fallback(profiler.profile_of(k),
                                              "collision")
                return decoded_batch(pk, sc.chunk)
            finally:
                memtrack.release(plan, device=db)
                runtime_stats.note_finalize_wait(
                    plan, time.perf_counter_ns() - t0)

        sc_iter = op_runtime.superchunk_batches(
            own.pull(join.left.chunks(ctx)), config.superchunk_rows(),
            tracker=mt_node)
        try:
            yield from op_runtime.pipeline_map(
                sc_iter, dispatch, finalize, config.pipeline_depth(),
                tracker=mt_node,
                cost=lambda sc: memtrack.chunk_bytes(sc.chunk),
                profile=profiler.profile_of(fk))
        finally:
            if state["build_db"]:
                memtrack.release(plan, device=state["build_db"])
            memtrack.release(plan, host=tracked)

    def _escalated_fragment(self, e: CapacityError, nl: int, width: int):
        """Fragment-kernel re-plan after a group-capacity miss; None
        when the overflow is hopeless (the per-batch decoded fallback
        then owns the batch)."""
        from tidb_tpu.ops import fragment as op_fragment
        cap = op_hybrid.escalated_capacity(getattr(e, "needed", 0))
        if cap is None:
            return None
        jplan = self.child.plan
        try:
            return op_fragment.fragment_kernel_for(
                len(jplan.left_keys), nl, width, self.plan.group_exprs,
                self.plan.aggs, capacity=cap)
        except (DeviceRejectError, NotImplementedError, ValueError):
            return None

    def _escalated_kernel(self, e: CapacityError):
        """Re-plan once with a larger device table (the re-plan the
        kernel docstring promises); None when the overflow is hopeless.
        The growth rule/ceiling live in hybrid.escalated_capacity so the
        whole-chunk retry and the per-partition chains cannot drift."""
        cap = op_hybrid.escalated_capacity(getattr(e, "needed", 0))
        if cap is None:
            return None
        try:
            k = kernel_for(None, self.plan.group_exprs, self.plan.aggs,
                           capacity=cap)
        except ValueError:
            return None
        self._set_kernel(k)
        return k

    def _device_partial(self, chunk):
        """Per-chunk device partial agg (superchunk coalescing off).
        A capacity miss re-plans once with a bigger table; a miss that
        survives (or a collision) radix-partitions the chunk and retries
        per partition (ops/hybrid.py) instead of abandoning the device.
        Returns None only for designed rejections (not device-safe) —
        the caller's host path, counted as a fallback."""
        try:
            if self._kernel is None:
                self._set_kernel(kernel_for(
                    None, self.plan.group_exprs, self.plan.aggs))
            nb = self._kernel.dispatch_nbytes(chunk)
            with sched.device_slot(), memtrack.device_scope(
                    self.plan, nb), \
                    profiler.dispatch_section(
                        profiler.profile_of(self._kernel), nbytes=nb,
                        plan=self.plan):
                gr = runtime_stats.device_call(
                    self.plan, self._kernel, chunk)
            runtime_stats.note_mode(self.plan, "hash")
            return gr
        except CapacityError as e:
            reason = "capacity"
            profiler.note_escalation(profiler.profile_of(self._kernel))
            k = self._escalated_kernel(e)
            if k is not None:
                # the retry kernel's (>=2x) scratch is the statement's
                # LARGEST device allocation — it must not dodge the quota
                nb = k.dispatch_nbytes(chunk)
                try:
                    with sched.device_slot(), \
                            memtrack.device_scope(self.plan, nb), \
                            profiler.dispatch_section(
                                profiler.profile_of(k), nbytes=nb,
                                plan=self.plan):
                        gr = runtime_stats.device_call(
                            self.plan, k, chunk)
                    runtime_stats.note_mode(self.plan, "hash")
                    return gr
                except CapacityError:
                    pass
                except CollisionError:
                    reason = "collision"
                except (DeviceRejectError, NotImplementedError):
                    runtime_stats.note_fallback(self.plan,
                                                "unsupported")
                    return None
            runtime_stats.note_mode(self.plan, "hybrid")
            return op_hybrid.partitioned_agg(
                chunk, None, self.plan.group_exprs, self.plan.aggs,
                self.plan, reason=reason)
        except CollisionError:
            runtime_stats.note_mode(self.plan, "hybrid")
            return op_hybrid.partitioned_agg(
                chunk, None, self.plan.group_exprs, self.plan.aggs,
                self.plan, reason="collision")
        except (DeviceRejectError, NotImplementedError):
            runtime_stats.note_fallback(self.plan, "unsupported")
        return None

    def _superchunk_partials(self, chunks):
        """Coalesced device partial aggregation: superchunk_batches folds
        the child's chunk stream into ~tidb_tpu_superchunk_rows batches,
        pipeline_map keeps tidb_tpu_pipeline_depth of them in flight
        (padding + H2D transfer of batch k+1 overlaps batch k's compute;
        the only sync is the finalize device_get at the output boundary),
        and the padded input buffers are donated to the kernel. Capacity
        overflow re-plans and re-runs the offending superchunk; collision
        or non-device-safe plans fall back to the host per superchunk."""
        plan = self.plan
        min_rows = config.device_min_rows()
        if self._kernel is None:
            try:
                self._set_kernel(kernel_for(None, plan.group_exprs,
                                            plan.aggs))
            except DeviceRejectError:
                # not device-safe BY DESIGN: every superchunk goes host
                runtime_stats.note_fallback(plan, "unsupported")

        mt_node = memtrack.op_node(plan)

        def dispatch(sc):
            k = self._kernel
            if k is None or sc.num_rows < min_rows:
                return None      # host path at finalize
            # device ledger: padded upload + group-table scratch, sized
            # from shapes at dispatch; credited back at finalize
            db = k.dispatch_nbytes(sc.chunk)
            memtrack.consume(plan, device=db)
            try:
                tok = (k, k.dispatch(sc.chunk, donate=True), db)
            except (DeviceRejectError, NotImplementedError):
                # trace-time rejection: this plan will never run on device
                self._kernel = None
                memtrack.release(plan, device=db)
                runtime_stats.note_fallback(plan, "unsupported")
                return None
            except BaseException:
                memtrack.release(plan, device=db)
                raise
            profiler.note_bytes(profiler.profile_of(k), nbytes=db)
            runtime_stats.note_superchunk(plan, sc.num_rows, sc.bucket,
                                          sc.sources)
            runtime_stats.note_bytes_touched(
                memtrack.chunk_bytes(sc.chunk),
                memtrack.device_put_bytes(sc.chunk))
            return tok

        def finalize(sc, tok):
            if tok is not None:
                k, fut, db = tok
                t0 = time.perf_counter_ns()
                try:
                    gr = k.finalize(sc.chunk, fut)
                    runtime_stats.note_mode(plan, "hash")
                    return gr
                except CapacityError as e:
                    reason = "capacity"
                    profiler.note_escalation(profiler.profile_of(k))
                    k2 = self._escalated_kernel(e)
                    if k2 is not None:
                        with sched.device_slot(), memtrack.device_scope(
                                plan, k2.dispatch_nbytes(sc.chunk)):
                            try:
                                gr = k2(sc.chunk)
                                runtime_stats.note_mode(plan, "hash")
                                return gr
                            except CapacityError:
                                pass
                            except CollisionError:
                                reason = "collision"
                            except (DeviceRejectError,
                                    NotImplementedError):
                                runtime_stats.note_fallback(
                                    plan, "unsupported")
                                return host_hash_agg(
                                    sc.chunk, None, plan.group_exprs,
                                    plan.aggs)
                    # a miss that survived escalation retries per
                    # radix partition instead of abandoning the device
                    runtime_stats.note_mode(plan, "hybrid")
                    return op_hybrid.partitioned_agg(
                        sc.chunk, None, plan.group_exprs, plan.aggs,
                        plan, reason=reason)
                except CollisionError:
                    runtime_stats.note_mode(plan, "hybrid")
                    return op_hybrid.partitioned_agg(
                        sc.chunk, None, plan.group_exprs, plan.aggs,
                        plan, reason="collision")
                except (DeviceRejectError, NotImplementedError):
                    runtime_stats.note_fallback(plan, "unsupported")
                finally:
                    memtrack.release(plan, device=db)
                    runtime_stats.note_finalize_wait(
                        plan, time.perf_counter_ns() - t0)
            return host_hash_agg(sc.chunk, None, plan.group_exprs,
                                 plan.aggs)

        yield from op_runtime.pipeline_map(
            op_runtime.superchunk_batches(chunks, config.superchunk_rows(),
                                          tracker=mt_node),
            dispatch, finalize, config.pipeline_depth(),
            tracker=mt_node, cost=lambda sc: memtrack.chunk_bytes(sc.chunk),
            profile=profiler.profile_of(self._kernel))


class StreamAggExec(Executor):
    """Sort-based aggregation: order rows by the group keys, then
    segment-reduce on device (ops/streamagg.py). Ref:
    executor/aggregate.go:150-170 StreamAggExec — there the sorted input
    comes from a child sort/index; here the sort itself is one vectorized
    lexsort, and the reduce has NO capacity limit (num_segments = slice
    rows), so arbitrarily many groups never overflow a device table."""

    _SLICE = 1 << 17     # rows per device dispatch

    def __init__(self, plan: ph.PhysStreamAgg):
        self.plan = plan
        self.schema = plan.schema
        self.child = build_executor(plan.children[0])
        self._kernel = getattr(plan, "_root_kernel", None)

    def chunks(self, ctx):
        agg = HashAggregator(self.plan.aggs, self.plan.group_exprs)
        use_device = (config.device_enabled() and
                      all(not a.distinct for a in self.plan.aggs))
        slice_rows = config.superchunk_rows() or self._SLICE
        mt_node = memtrack.op_node(self.plan)

        def parts():
            """Ordered ~slice_rows Superchunks: key-adjacency (all the
            segment kernel needs) survives coalescing because both
            sources below yield key-ordered chunks and superchunk
            assembly preserves order. Oversize blocks are re-sliced so
            device dispatches stay bounded."""
            if self.plan.sorted_input:
                # already key-ordered (pk scan / keep_order index): pure
                # streaming, the whole input is never materialized
                yield from op_runtime.superchunk_batches(
                    self.child.chunks(ctx), slice_rows, tracker=mt_node)
                return
            # needs its own ordering pass: the spill sorter keeps row
            # memory O(run + block) however large the input
            # (executor/extsort.py), then yields globally ordered blocks.
            # The sorter bills this node and registers a quota spill
            # action — over tidb_tpu_mem_quota_query it sheds its buffer
            # to disk instead of cancelling the statement.
            from tidb_tpu.executor.extsort import SpillSorter
            by = [(g, False) for g in self.plan.group_exprs]
            sorter = SpillSorter(by, run_rows=config.sort_spill_rows(),
                                 block_rows=slice_rows, tracker=mt_node)
            try:
                for chunk in self.child.chunks(ctx):
                    sorter.add(chunk)
                yield from op_runtime.superchunk_batches(
                    sorter.sorted_chunks(), slice_rows, tracker=mt_node)
            finally:
                sorter.close()

        # batches keep host+device memory bounded; a group spanning two
        # batches merges itself in the HashAggregator
        def feed(part: Chunk) -> None:
            nonlocal use_device
            gr = None
            if use_device and part.num_rows >= config.device_min_rows():
                try:
                    if self._kernel is None:
                        self._kernel = segment_kernel_for(
                            self.plan.group_exprs, self.plan.aggs)
                        self.plan._root_kernel = self._kernel
                    nb = self._kernel.dispatch_nbytes(part)
                    with sched.device_slot(), memtrack.device_scope(
                            self.plan, nb), \
                            profiler.dispatch_section(
                                profiler.profile_of(self._kernel),
                                nbytes=nb, plan=self.plan):
                        gr = runtime_stats.device_call(
                            self.plan, self._kernel, part)
                    runtime_stats.note_mode(self.plan, "sort")
                except (DeviceRejectError, NotImplementedError):
                    runtime_stats.note_fallback(self.plan, "unsupported")
                    use_device = False
            if gr is None:
                gr = host_hash_agg(part, None, self.plan.group_exprs,
                                   self.plan.aggs)
            agg.update(gr)

        tracked = 0
        try:
            if use_device and config.superchunk_rows():
                for gr in self._pipelined_segments(parts()):
                    agg.update(gr)
                    tracked = memtrack.track_to(
                        self.plan, agg.approx_bytes(), tracked)
            else:
                for sc in parts():
                    feed(sc.chunk)
                    tracked = memtrack.track_to(
                        self.plan, agg.approx_bytes(), tracked)
            yield _agg_chunk(self.schema, len(self.plan.group_exprs),
                             self.plan.aggs, agg, note=False)
        finally:
            memtrack.release(self.plan, host=tracked)

    def _pipelined_segments(self, parts):
        """Segment-reduce each superchunk through the dispatch-ahead
        queue (see HashAggExec._superchunk_partials): one whole-
        superchunk segment op per coalesced batch, inputs donated, the
        next batch padded/transferred while this one executes. Segment
        kernels have no capacity protocol; a trace failure permanently
        reverts to the host path (matching the old per-batch behavior)."""
        plan = self.plan
        min_rows = config.device_min_rows()
        if self._kernel is None:
            try:
                self._kernel = segment_kernel_for(plan.group_exprs,
                                                  plan.aggs)
                plan._root_kernel = self._kernel
            except (DeviceRejectError, NotImplementedError):
                runtime_stats.note_fallback(plan, "unsupported")
                self._kernel = None

        mt_node = memtrack.op_node(plan)

        def dispatch(sc):
            k = self._kernel
            if k is None or sc.num_rows < min_rows:
                return None
            db = k.dispatch_nbytes(sc.chunk)
            memtrack.consume(plan, device=db)
            try:
                tok = (k, k.dispatch(sc.chunk, donate=True), db)
            except (DeviceRejectError, NotImplementedError):
                self._kernel = None
                memtrack.release(plan, device=db)
                runtime_stats.note_fallback(plan, "unsupported")
                return None
            except BaseException:
                memtrack.release(plan, device=db)
                raise
            profiler.note_bytes(profiler.profile_of(k), nbytes=db)
            runtime_stats.note_superchunk(plan, sc.num_rows, sc.bucket,
                                          sc.sources)
            runtime_stats.note_bytes_touched(
                memtrack.chunk_bytes(sc.chunk),
                memtrack.device_put_bytes(sc.chunk))
            return tok

        def finalize(sc, tok):
            if tok is not None:
                k, fut, db = tok
                t0 = time.perf_counter_ns()
                try:
                    gr = k.finalize(sc.chunk, fut)
                    runtime_stats.note_mode(plan, "sort")
                    return gr
                except (DeviceRejectError, NotImplementedError):
                    self._kernel = None
                    runtime_stats.note_fallback(plan, "unsupported")
                finally:
                    memtrack.release(plan, device=db)
                    runtime_stats.note_finalize_wait(
                        plan, time.perf_counter_ns() - t0)
            return host_hash_agg(sc.chunk, None, plan.group_exprs,
                                 plan.aggs)

        yield from op_runtime.pipeline_map(
            parts, dispatch, finalize, config.pipeline_depth(),
            tracker=mt_node, cost=lambda sc: memtrack.chunk_bytes(sc.chunk),
            profile=profiler.profile_of(self._kernel))


# ---------------------------------------------------------------------------
# Row ops

class SelectionExec(Executor):
    def __init__(self, plan: ph.PhysSelection):
        self.plan = plan
        self.schema = plan.schema
        self.child = build_executor(plan.children[0])

    def chunks(self, ctx):
        for chunk in self.child.chunks(ctx):
            mask = eval_filter_host(self.plan.cond, chunk)
            yield chunk.filter(mask)


class ProjectionExec(Executor):
    def __init__(self, plan: ph.PhysProjection):
        self.plan = plan
        self.schema = plan.schema
        self.child = build_executor(plan.children[0])

    def chunks(self, ctx):
        fts = [c.ft for c in self.schema.cols]
        for chunk in self.child.chunks(ctx):
            cols = []
            for e, ft in zip(self.plan.exprs, fts):
                d, v = e.eval(chunk)
                if d.dtype != np.dtype(object):
                    want = np_dtype_for(ft.tp, ft.flen)
                    if d.dtype != want:
                        d = d.astype(want)
                cols.append(Column(ft, d, v.copy()))
            yield Chunk(cols)


class LimitExec(Executor):
    def __init__(self, plan: ph.PhysLimit):
        self.plan = plan
        self.schema = plan.schema
        self.child = build_executor(plan.children[0])

    def chunks(self, ctx):
        skip = self.plan.offset
        left = self.plan.count
        for chunk in self.child.chunks(ctx):
            if skip >= chunk.num_rows:
                skip -= chunk.num_rows
                continue
            if skip:
                chunk = chunk.slice(skip, chunk.num_rows)
                skip = 0
            if chunk.num_rows > left:
                chunk = chunk.slice(0, left)
            left -= chunk.num_rows
            yield chunk
            if left <= 0:
                return


def _ofill(ft):
    from tidb_tpu.sqltypes import object_fill
    return object_fill(ft)


def _sort_order(by, chunk) -> np.ndarray:
    """-> int64 permutation ordering chunk rows by the sort items, fully
    vectorized (no per-row Python objects — ref SURVEY §3.2's per-row
    dispatch sin). NULLs first ascending / last descending (MySQL)."""
    from tidb_tpu.executor.extsort import order_from_keys
    keys = []
    for e, desc in by:
        d, v = e.eval(chunk)
        if e.ft.is_ci and np.asarray(d).dtype == np.dtype(object):
            from tidb_tpu.sqltypes import fold_column
            d = fold_column(np.asarray(d))   # _ci ordering
        keys.append((d, v, desc))
    return order_from_keys(keys, chunk.num_rows)


class SortExec(Executor):
    """Sort with spill-to-disk (ref: executor/sort.go:35 in-memory path +
    util/filesort/filesort.go:319 external path, unified): below the
    tidb_tpu_sort_spill_rows sysvar everything is one in-memory lexsort;
    above it, full rows spill to memory-mapped runs while the keys stay
    resident (executor/extsort.py)."""

    def __init__(self, plan: ph.PhysSort):
        self.plan = plan
        self.schema = plan.schema
        self.child = build_executor(plan.children[0])

    def chunks(self, ctx):
        from tidb_tpu.executor.extsort import SpillSorter
        # the sorter bills this plan node and registers a quota spill
        # action: crossing tidb_tpu_mem_quota_query sheds the buffered
        # rows to disk (tracker drops) instead of cancelling
        sorter = SpillSorter(self.plan.by,
                             run_rows=config.sort_spill_rows(),
                             tracker=memtrack.op_node(self.plan))
        try:
            empty = None
            for chunk in self.child.chunks(ctx):
                if chunk.num_rows == 0:
                    empty = chunk
                    continue
                sorter.add(chunk)
            n = 0
            for out in sorter.sorted_chunks():
                n += out.num_rows
                yield out
            if n == 0 and empty is not None:
                yield empty
        finally:
            sorter.close()


class TopNExec(Executor):
    """Heap-free TopN: keep best (count+offset) rows per chunk
    (ref: pushDownTopNOptimizer + executor TopN)."""

    def __init__(self, plan: ph.PhysTopN):
        self.plan = plan
        self.schema = plan.schema
        self.child = build_executor(plan.children[0])

    def chunks(self, ctx):
        n = self.plan.count + self.plan.offset
        best = None
        tracked = 0
        try:
            for chunk in self.child.chunks(ctx):
                cand = chunk if best is None else best.concat(chunk)
                if cand.num_rows > 0:
                    best = cand.take(_sort_order(self.plan.by, cand)[:n])
                else:
                    best = cand
                tracked = memtrack.track_to(
                    self.plan, memtrack.chunk_bytes(best), tracked)
            if best is None:
                return
            yield best.slice(min(self.plan.offset, best.num_rows),
                             best.num_rows)
        finally:
            memtrack.release(self.plan, host=tracked)


class HashJoinExec(Executor):
    """Equi-join: device sort-based pair matching (ops/join.py) for large
    inputs, python hash probe for small ones (ref: executor/join.go:37
    HashJoinExec). Build side = right child, probe streams left chunks."""

    # below these sizes the jit dispatch beats the device win
    _DEVICE_MIN_PROBE = 1024
    _DEVICE_MIN_BUILD = 4096

    def __init__(self, plan: ph.PhysHashJoin):
        self.plan = plan
        self.schema = plan.schema
        self.left = build_executor(plan.children[0])
        self.right = build_executor(plan.children[1])
        # shared via the plan object so the jit shape cache survives
        # across executions of a cached plan
        self._kernel = getattr(plan, "_join_kernel", None)
        if self._kernel is None and plan.left_keys:
            self._kernel = JoinKernel(len(plan.left_keys))
            plan._join_kernel = self._kernel

    def _eval_keys(self, exprs, chunk):
        """-> [(data, valid)] with both sides brought to one comparable
        representation: decimal-vs-decimal/int rescale to the common frac
        as exact scaled ints (falling back to double when the scaled value
        could overflow int64); anything involving a REAL side compares as
        double, matching MySQL's mixed-numeric comparison."""
        out = []
        for e, oe in zip(exprs, self._other_keys(exprs)):
            d, v = e.eval(chunk)
            d, v = np.asarray(d), np.asarray(v)
            if d.dtype == np.dtype(object) and \
                    (e.ft.is_ci or oe.ft.is_ci):
                from tidb_tpu.sqltypes import fold_column
                d = fold_column(d)           # _ci join keys
            et, ot = e.ft.eval_type, oe.ft.eval_type
            my = e.ft.frac if et == EvalType.DECIMAL else 0
            their = oe.ft.frac if ot == EvalType.DECIMAL else 0
            if EvalType.REAL in (et, ot):
                if et == EvalType.DECIMAL:
                    d = d.astype(np.float64) / (10 ** my)
                elif d.dtype != np.float64 and d.dtype != np.dtype(object):
                    d = d.astype(np.float64)
            elif EvalType.DECIMAL in (et, ot):
                common = max(my, their)
                dig = (e.ft.flen if et == EvalType.DECIMAL else 19) \
                    + common - my
                odig = (oe.ft.flen if ot == EvalType.DECIMAL else 19) \
                    + common - their
                if max(dig, odig) > 18:   # scaled int64 could overflow
                    d = d.astype(np.float64) / (10 ** my)
                elif common > my:
                    d = d * np.int64(10 ** (common - my))
            out.append((d, v))
        return out

    def _other_keys(self, exprs):
        return self.plan.right_keys if exprs is self.plan.left_keys \
            else self.plan.left_keys

    def _encoded_keys(self, exprs, chunk):
        """Pre-encoded (codes, values) key lanes for bare varlen
        ColumnRefs (ops/encoded.py, `tidb_tpu_encoded_exec`): the join
        then hashes dictionary codes directly — a probe side sharing
        the build's dictionary passes through, a mismatched one re-keys
        through a code-translation array — instead of re-building a
        per-join Python dict over every value. Engages per key only
        when BOTH sides are plain string columns with matching
        collation (mixed-type and mixed-collation keys keep the raw
        path, whose rescale/fold rules own those semantics)."""
        if not config.encoded_exec_enabled():
            return None
        from tidb_tpu.ops import encoded as op_encoded
        out = []
        any_lane = False
        for e, oe in zip(exprs, self._other_keys(exprs)):
            lane = None
            if (e.ft.eval_type == EvalType.STRING and
                    oe.ft.eval_type == EvalType.STRING and
                    bool(e.ft.is_ci) == bool(oe.ft.is_ci)):
                lane = op_encoded.encoded_lane(e, chunk)
            out.append(lane)
            any_lane = any_lane or lane is not None
        return out if any_lane else None

    def _probe_keys(self, enc, chunk):
        """One probe batch's aligned key lanes, through the encoded
        fast path when the lanes are pre-encodable."""
        return enc.transform_probe(
            self._eval_keys(self.plan.left_keys, chunk),
            encoded=self._encoded_keys(self.plan.left_keys, chunk))

    def _mesh_kernel(self, nb: int):
        """A shuffle-join kernel when a multi-chip mesh is active and the
        build side is big enough to be worth a repartition (ref: the
        scaled-out form of executor/join.go's partitioned build). Cached
        per (mesh generation, key arity) — the shard_map program costs
        seconds of XLA compile and is shape-polymorphic across queries."""
        from tidb_tpu import devplane as mesh_config
        mesh = mesh_config.active_mesh()
        if mesh is None or mesh.devices.size <= 1 or \
                nb < self._DEVICE_MIN_BUILD or not config.device_enabled():
            return None
        from tidb_tpu.ops.meshshuffle import MeshShuffleJoinKernel
        key = (mesh_config.mesh_generation(), len(self.plan.left_keys))
        with _SHUFFLE_KERNELS_LOCK:
            kernel = _SHUFFLE_KERNELS.get(key)
            if kernel is None:
                for k in [k for k in _SHUFFLE_KERNELS if k[0] != key[0]]:
                    _SHUFFLE_KERNELS.pop(k, None)
                kernel = MeshShuffleJoinKernel(mesh, len(self.plan.left_keys))
                _SHUFFLE_KERNELS[key] = kernel
        return kernel

    def chunks(self, ctx):
        if not self.plan.left_keys:
            yield from self._cross_join(ctx)
            return
        own = _OwnSpan(lambda: trace.span("exec.join"))
        yield from own.drive(self._joined(ctx, own))

    def _joined(self, ctx, own):
        build = Chunk.concat_all(list(own.pull(self.right.chunks(ctx))))
        nb = build.num_rows if build is not None else 0
        # the materialized build side is the join's dominant host buffer:
        # hold it on this node's ledger for the whole probe phase
        tracked = memtrack.track_to(
            self.plan, memtrack.chunk_bytes(build) if nb else 0)
        try:
            yield from self._probe_join(ctx, build, nb, own)
        finally:
            memtrack.release(self.plan, host=tracked)

    def _probe_join(self, ctx, build, nb: int, own, prepared=None):
        """`own` is the span of the operator driving this probe (this
        join's exec.join, or the exec.agg of a fused aggregate standing
        aside): the probe side is pulled outside it.
        `prepared` = (enc, bk, raw_bk, hot, h) from a caller that
        already encoded the build keys and ran the hybrid-engage scan
        (the fused fragment's stand-aside path) — O(nb) key evaluation
        and heavy-hitter hashing must not run twice on exactly the
        large-build cases."""
        plan = self.plan
        if prepared is not None and nb:
            enc, bk, raw_bk, pre_hot, pre_h = prepared
        else:
            enc = JoinKeyEncoder(len(plan.right_keys))
            raw_bk = self._eval_keys(plan.right_keys, build) if nb \
                else None
            bk = enc.fit_build(
                raw_bk,
                encoded=self._encoded_keys(plan.right_keys, build),
                ci=[e.ft.is_ci for e in plan.right_keys]) if nb else None
            pre_hot = pre_h = None
        matched_build = np.zeros(nb, dtype=bool)
        probe_iter = own.pull(self.left.chunks(ctx))
        mesh_kernel = self._mesh_kernel(nb)
        if mesh_kernel is not None:
            # each shuffle-join call is one all_to_all repartition of both
            # sides over the mesh, so probe chunks are re-batched into
            # large super-batches — but never the whole table: past
            # tidb_tpu_stream_rows per batch the collective is amortized
            # and host memory stays bounded (the build side's device
            # transfer is memoized across batches). A small probe doesn't
            # pay for the collective at all: fall through to the
            # per-chunk device/host paths
            buffered, total = [], 0
            for c in probe_iter:
                buffered.append(c)
                total += c.num_rows
                if total >= self._DEVICE_MIN_PROBE:
                    break
            if total >= self._DEVICE_MIN_PROBE:
                probe_iter = super_batches(
                    buffered, probe_iter,
                    max(config.stream_rows(), self._DEVICE_MIN_PROBE))
            else:
                mesh_kernel = None
                probe_iter = iter(buffered)
        device_ok = (mesh_kernel is None and nb > 0 and
                     self._kernel is not None and
                     config.device_enabled() and
                     config.superchunk_rows())
        if not device_ok:
            hyb = None
        elif pre_h is not None:
            # the caller's engage scan already said yes: construct
            # directly over its hashes/hot set
            hyb = op_hybrid.HybridJoinBuild(
                self._kernel, bk, nb, config.join_partitions(), plan,
                hot_hashes=pre_hot, h=pre_h)
        else:
            hyb = self._maybe_hybrid(bk, nb, raw_bk)
        if hyb is not None:
            # partitioned hybrid path (ops/hybrid.py): skew routed
            # through the heavy-hitter lane, cold build partitions
            # spillable to host staging under quota pressure
            try:
                yield from self._hybrid_probe(probe_iter, build, hyb,
                                              enc, matched_build)
            finally:
                hyb.close()
        elif device_ok:
            # single-chip device path: probe chunks coalesce into
            # superchunks and flow through the dispatch-ahead matcher
            # queue (build-side lanes transfer once for the whole probe)
            yield from self._pipelined_probe(probe_iter, build, bk, enc,
                                             matched_build, nb)
        else:
            for chunk in probe_iter:
                n = chunk.num_rows
                if n == 0:
                    continue
                if nb == 0:
                    if plan.join_type == "left":
                        out = self._emit(chunk, build,
                                         np.empty(0, np.int64),
                                         np.empty(0, np.int64),
                                         np.arange(n))
                        if out is not None:
                            yield out
                    elif plan.join_type == "anti":
                        yield chunk        # nothing can match: all survive
                    continue
                pk = self._probe_keys(enc, chunk)
                if mesh_kernel is not None:
                    from tidb_tpu.ops.meshshuffle import \
                        ShuffleOverflowError
                    try:
                        li, ri = runtime_stats.device_call(
                            self.plan, mesh_kernel, pk, bk, nb, n)
                    except ShuffleOverflowError:
                        # designed fallback: extreme hash skew exhausted
                        # the repartition retry budget
                        li, ri = runtime_stats.device_call(
                            self.plan, self._kernel, bk, pk, nb, n)
                elif config.device_enabled() and \
                        (n >= self._DEVICE_MIN_PROBE or
                         nb >= self._DEVICE_MIN_BUILD):
                    with sched.device_slot(), memtrack.device_scope(
                            self.plan,
                            self._kernel.build_nbytes(nb) +
                            self._kernel.dispatch_nbytes(n)):
                        li, ri = runtime_stats.device_call(
                            self.plan, self._kernel, bk, pk, nb, n)
                else:
                    # small inputs / device disabled: the same sort-join,
                    # vectorized in numpy (no jit dispatch, dynamic shapes)
                    li, ri = host_match_pairs(bk, pk, nb, n)
                yield from self._post_match(chunk, build, li, ri,
                                            matched_build)
        if plan.join_type == "right" and build is not None:
            un = np.flatnonzero(~matched_build)
            if len(un):
                yield self._emit_right_unmatched(build, un)

    def _post_match(self, chunk, build, li, ri, matched_build):
        """Shared tail after pair matching for one probe batch:
        other_cond filtering, semi/anti emission, left-unmatched fill;
        marks matched build rows for the right-join pass."""
        plan = self.plan
        n = chunk.num_rows
        # other_cond filters pairs BEFORE unmatched detection, so a
        # probe row whose every match fails the condition re-enters
        # as unmatched (outer-join ON-clause semantics)
        pair = None
        if plan.other_cond is not None and len(li):
            pair = self._gather(chunk, build, li, ri)
            keep = eval_filter_host(plan.other_cond, pair)
            li, ri = li[keep], ri[keep]
            pair = pair.filter(keep)
        if plan.join_type in ("semi", "anti"):
            # (anti-)semi join: emit probe rows by match existence,
            # never the joined width (ref: the semi-join family of
            # plan/gen_physical_plans.go; decorrelated EXISTS/IN)
            m = np.zeros(n, dtype=bool)
            m[li] = True
            yield chunk.filter(m if plan.join_type == "semi" else ~m)
            return
        matched_build[ri] = True
        unmatched = np.empty(0, np.int64)
        if plan.join_type == "left":
            m = np.zeros(n, dtype=bool)
            m[li] = True
            unmatched = np.flatnonzero(~m)
        out = self._emit(chunk, build, li, ri, unmatched, pair=pair)
        if out is not None:
            yield out

    def _hybrid_engage(self, bk, nb: int, raw_bk):
        """(engage, hot, h): should the partitioned hybrid path carry
        this build? Decision only — no HybridJoinBuild is constructed,
        so the fused-fragment eligibility check (HashAggExec) can
        consult it cheaply and stand aside when the skew/quota/spill
        machinery owns the probe."""
        parts = config.join_partitions()
        plan = self.plan
        if parts <= 1 or nb < self._DEVICE_MIN_BUILD:
            return False, None, None
        h = op_hybrid.build_hashes(bk, nb)
        raw_key = None
        if len(plan.right_keys) == 1 and raw_bk:
            rk, lk = plan.right_keys[0], plan.left_keys[0]
            ok_types = (EvalType.INT, EvalType.STRING, EvalType.DATETIME,
                        EvalType.DURATION)
            # decimal/real keys rescale in _eval_keys, so their raw
            # values no longer match the ANALYZE-time sketch encoding;
            # _ci strings fold the same way — skip sketch seeding there
            if rk.ft.eval_type in ok_types and \
                    lk.ft.eval_type in ok_types and \
                    not rk.ft.is_ci and not lk.ft.is_ci:
                raw_key = raw_bk[0]
        threshold = config.skew_threshold()
        cms = getattr(plan, "probe_cms", None)
        # the per-distinct-key sketch scan is ~1us/key: cache its result
        # on the (plan-cache-shared) plan object keyed by sketch
        # identity + threshold, so repeated executions pay it once.
        # Staleness is bounded by re-ANALYZE (new sketch object -> new
        # scan); build keys that appeared since simply miss the seed and
        # are caught by streaming promotion instead
        cached = getattr(plan, "_hot_seed", None)
        if cached is not None and cached[0] is cms and \
                cached[1] == threshold:
            sketch_hot = cached[2]
        else:
            sketch_hot = op_hybrid.sketch_hot_hashes(h, threshold,
                                                     raw_key, cms)
            plan._hot_seed = (cms, threshold, sketch_hot)
        hot = np.union1d(op_hybrid.dup_hot_hashes(h, threshold),
                         sketch_hot)
        root = memtrack.current()
        quota = root is not None and root.quota > 0
        if not hot.size and not quota and nb <= config.superchunk_rows():
            return False, hot, h
        return True, hot, h

    def _maybe_hybrid(self, bk, nb: int, raw_bk):
        """A HybridJoinBuild when the partitioned path should carry this
        probe (ops/hybrid.py). Partitioning is pure win under skew,
        memory pressure, or an over-superchunk build — and pure overhead
        otherwise, so the unskewed in-HBM case stays on the classic
        pipelined probe. Heavy hitters are seeded from exact build-side
        duplication plus the probe table's ANALYZE-time CMSketch when
        the planner traced the probe key to a base column."""
        engage, hot, h = self._hybrid_engage(bk, nb, raw_bk)
        if not engage:
            return None
        return op_hybrid.HybridJoinBuild(self._kernel, bk, nb,
                                         config.join_partitions(),
                                         self.plan, hot_hashes=hot, h=h)

    # lint: exempt[memtrack-alloc] pair-index buffers are billed at dispatch (cap*17 inside dispatch_nbytes); staged sub-chunks consume on mt_node below
    def _hybrid_probe(self, probe_iter, build, hyb, enc, matched_build):
        """Partitioned probe over a HybridJoinBuild.

        Phase 1 streams probe superchunks through the dispatch-ahead
        pipeline: rows route per partition (the heavy-hitter lane at
        index `parts`), device-resident partitions match immediately,
        and — once the memtrack quota action has spilled cold build
        partitions — rows bound for spilled partitions stage to host
        buffers instead of thrashing re-uploads. Phase 2 drains the
        staging one partition at a time, re-uploading each spilled
        build partition once and evicting it when drained.

        Every probe row reaches exactly one _post_match call (its
        matching, if any, is complete there), so outer-join unmatched
        detection and semi/anti emission stay exact per subset."""
        plan = self.plan
        kernel = self._kernel
        mt_node = memtrack.op_node(plan)
        staged: list = []      # (pid, sub_chunk, pk_lanes, host_bytes)

        def dispatch_one(p, pk_sub, hp_sub, n_sub):
            bdev = hyb.ensure(p)
            # SNAPSHOT the partition->global row map at dispatch time: a
            # later heavy-hitter promotion re-layouts the build while
            # this token is still in flight, and the pair indices must
            # resolve against the layout the matcher actually saw. The
            # pin keeps the partition's device bytes on the ledger (and
            # off the spill action's menu) while the token is pending.
            rows = hyb.build_rows(p)
            cap = hyb.hot_out_cap(hp_sub) if p == hyb.parts else None
            db = kernel.dispatch_nbytes(n_sub, cap)
            memtrack.consume(plan, device=db)
            hyb.pin(p)
            try:
                tok = kernel.dispatch(None, pk_sub, len(rows),
                                      n_sub, out_cap=cap, build_dev=bdev)
            except BaseException:
                hyb.unpin(p)
                memtrack.release(plan, device=db)
                raise
            return (p, rows, tok, db)

        def finalize_one(t):
            p, rows, tok, db = t
            t0 = time.perf_counter_ns()
            try:
                li_l, ri_l = kernel.finalize(tok)
            finally:
                hyb.unpin(p)
                memtrack.release(plan, device=db)
                runtime_stats.note_finalize_wait(
                    plan, time.perf_counter_ns() - t0)
            return li_l, rows[ri_l]

        # one superchunk fans out into one task per touched partition;
        # tasks (not whole superchunks) ride the dispatch-ahead pipeline
        # so only ~depth partitions are pinned by in-flight tokens at
        # any moment — everything else stays evictable by the quota
        # spill action. A superchunk's emission fires when its LAST
        # task finalizes (tasks of one superchunk are contiguous in the
        # stream, so that is also emission order).
        pending_promo: list = [None]
        open_states: dict = {}      # id -> state; bytes held to emission

        def task_iter(sc_iter):
            for sc in sc_iter:
                # apply the promotion observed on the PREVIOUS batch:
                # all of its tasks have dispatched by the time the
                # pipeline pulls this batch's first task, so no routed-
                # but-undispatched task can straddle the re-layout
                if pending_promo[0] is not None:
                    hyb.promote(pending_promo[0])
                    pending_promo[0] = None
                n = sc.num_rows
                pk = self._probe_keys(enc, sc.chunk)
                hp, tasks = hyb.route(pk, n)
                pending_promo[0] = hyb.observe(hp)
                staged_mask = np.zeros(n, dtype=bool)
                imm = []
                for p, idx in tasks:
                    if hyb.want_immediate(p):
                        imm.append((p, idx))
                    else:
                        sub = [(d[idx], v[idx]) for d, v in pk]
                        sub_chunk = sc.chunk.take(idx)
                        sb = memtrack.chunk_bytes(sub_chunk) + \
                            sum(d.nbytes + v.nbytes for d, v in sub)
                        if mt_node is not None:
                            # ownership transfer: staged probe bytes
                            # release in the drain loop / outer finally
                            mt_node.consume(host=sb)
                        staged.append((p, sub_chunk, sub, sb))
                        staged_mask[idx] = True
                sb = memtrack.chunk_bytes(sc.chunk)
                if mt_node is not None:
                    # held until the superchunk's emission (outer
                    # finally sweeps abandoned states)
                    mt_node.consume(host=sb)
                state = {"chunk": sc.chunk, "pk": pk, "hp": hp,
                         "mask": staged_mask, "li": [], "ri": [],
                         "left": max(len(imm), 1), "bytes": sb}
                open_states[id(state)] = state
                runtime_stats.note_superchunk(plan, n, sc.bucket,
                                              sc.sources)
                if not imm:
                    # every row staged or unmatched: one sentinel task
                    # still flows through so the emission fires
                    yield (state, None, None)
                else:
                    for p, idx in imm:
                        yield (state, p, idx)

        def dispatch(task):
            state, p, idx = task
            if p is None:
                return None
            pk = state["pk"]
            sub = [(d[idx], v[idx]) for d, v in pk]
            return dispatch_one(p, sub, state["hp"][idx], len(idx))

        def finalize(task, tok):
            state, _p, idx = task
            if tok is not None:
                li_l, ri = finalize_one(tok)
                state["li"].append(idx[li_l])
                state["ri"].append(ri)
            state["left"] -= 1
            if state["left"] > 0:
                return None
            open_states.pop(id(state), None)
            if mt_node is not None and state["bytes"]:
                mt_node.release(host=state["bytes"])
            li = np.concatenate(state["li"]) if state["li"] \
                else np.empty(0, dtype=np.int64)
            ri = np.concatenate(state["ri"]) if state["ri"] \
                else np.empty(0, dtype=np.int64)
            mask = state["mask"]
            if mask.any():
                # staged rows' matching is NOT complete: hand only the
                # immediately-matched subset to _post_match
                keep = np.flatnonzero(~mask)
                li = np.searchsorted(keep, li)
                return state["chunk"].take(keep), li, ri
            return state["chunk"], li, ri

        sc_iter = op_runtime.superchunk_batches(probe_iter,
                                                config.superchunk_rows(),
                                                tracker=mt_node)
        try:
            for out in op_runtime.pipeline_map(
                    task_iter(sc_iter), dispatch, finalize,
                    config.pipeline_depth()):
                if out is None:
                    continue
                chunk_out, li, ri = out
                yield from self._post_match(chunk_out, build, li, ri,
                                            matched_build)
            # phase 2: drain staged cold-partition rows, grouped by
            # partition so each spilled build uploads exactly once.
            # Promotions only ever MOVE keys to the always-resident hot
            # lane, so a staged batch re-routes within {its partition,
            # hot} and the grouping stays partition-local.
            staged.sort(key=lambda t: t[0])
            while staged:
                p_hint, sub_chunk, pk_sub, sb = staged[0]
                try:
                    hp, tasks = hyb.route(pk_sub, sub_chunk.num_rows)
                    li_parts, ri_parts = [], []
                    for p, idx in tasks:
                        lanes = [(d[idx], v[idx]) for d, v in pk_sub]
                        li_l, ri = finalize_one(
                            dispatch_one(p, lanes, hp[idx], len(idx)))
                        li_parts.append(idx[li_l])
                        ri_parts.append(ri)
                    li = np.concatenate(li_parts) if li_parts \
                        else np.empty(0, dtype=np.int64)
                    ri = np.concatenate(ri_parts) if ri_parts \
                        else np.empty(0, dtype=np.int64)
                finally:
                    staged.pop(0)
                    if mt_node is not None and sb:
                        mt_node.release(host=sb)
                yield from self._post_match(sub_chunk, build, li, ri,
                                            matched_build)
                if hyb.under_pressure() and \
                        (not staged or staged[0][0] != p_hint):
                    hyb.evict(p_hint)
        finally:
            if mt_node is not None:
                for _p, _c, _k, sb in staged:
                    if sb:
                        mt_node.release(host=sb)
                # superchunks abandoned before their last task finalized
                for state in open_states.values():
                    if state["bytes"]:
                        mt_node.release(host=state["bytes"])
            staged.clear()
            open_states.clear()

    def _pipelined_probe(self, probe_iter, build, bk, enc, matched_build,
                         nb: int):
        """Coalesced probe matching with dispatch-ahead: while superchunk
        k's matcher program executes, k+1's keys are encoded, padded and
        transferred (the host-side emit of k's output overlaps too). A
        probe too small to pay a dispatch matches on the host inline —
        same decision the per-chunk loop made, now per superchunk."""
        plan = self.plan
        kernel = self._kernel
        build_dev = None
        build_db = 0
        mt_node = memtrack.op_node(plan)

        def dispatch(sc):
            nonlocal build_dev, build_db
            n = sc.num_rows
            pk = self._probe_keys(enc, sc.chunk)
            if n < self._DEVICE_MIN_PROBE and nb < self._DEVICE_MIN_BUILD:
                return ("host", host_match_pairs(bk, pk, nb, n), 0)
            if build_dev is None:
                # build lanes stay device-resident for the whole probe:
                # held on the device ledger until the generator winds down
                build_db = kernel.build_nbytes(nb)
                memtrack.consume(plan, device=build_db)
                build_dev = kernel.prepare_build(bk, nb)
            db = kernel.dispatch_nbytes(n)
            memtrack.consume(plan, device=db)
            try:
                tok = kernel.dispatch(bk, pk, nb, n, build_dev=build_dev)
            except BaseException:
                memtrack.release(plan, device=db)
                raise
            runtime_stats.note_superchunk(plan, n, sc.bucket, sc.sources)
            return ("dev", tok, db)

        def finalize(sc, tok):
            kind, payload, db = tok
            if kind == "host":
                li, ri = payload
            else:
                t0 = time.perf_counter_ns()
                try:
                    li, ri = kernel.finalize(payload)
                finally:
                    memtrack.release(plan, device=db)
                    runtime_stats.note_finalize_wait(
                        plan, time.perf_counter_ns() - t0)
            return sc, li, ri

        sc_iter = op_runtime.superchunk_batches(probe_iter,
                                                config.superchunk_rows(),
                                                tracker=mt_node)
        try:
            for sc, li, ri in op_runtime.pipeline_map(
                    sc_iter, dispatch, finalize, config.pipeline_depth(),
                    tracker=mt_node,
                    cost=lambda sc: memtrack.chunk_bytes(sc.chunk)):
                yield from self._post_match(sc.chunk, build, li, ri,
                                            matched_build)
        finally:
            if build_db:
                memtrack.release(plan, device=build_db)

    def _gather(self, left_chunk, build, li, ri):
        cols = [Column(c.ft, c.data[li], c.valid[li])
                for c in left_chunk.columns]
        cols += [Column(c.ft, c.data[ri], c.valid[ri])
                 for c in build.columns]
        return Chunk(cols)

    # lint: exempt[memtrack-alloc] join-emit padding over the tracked build; pair buffers billed at dispatch
    def _emit(self, left_chunk, build, li, ri, left_unmatched, pair=None):
        plan = self.plan
        out = pair
        if out is None:
            out = self._gather(left_chunk, build, li, ri) \
                if len(li) or not len(left_unmatched) else None
        if plan.join_type == "left" and len(left_unmatched):
            ui = np.asarray(left_unmatched, dtype=np.int64)
            ucols = [Column(c.ft, c.data[ui], c.valid[ui])
                     for c in left_chunk.columns]
            for sc in self.plan.children[1].schema.cols:
                dtype = np_dtype_for(sc.ft.tp, sc.ft.flen)
                data = np.zeros(len(ui), dtype=dtype) \
                    if dtype != np.dtype(object) \
                    else np.full(len(ui), _ofill(sc.ft), dtype=object)
                ucols.append(Column(sc.ft, data,
                                    np.zeros(len(ui), dtype=bool)))
            uchunk = Chunk(ucols)
            out = uchunk if out is None else out.concat(uchunk)
        return out

    # lint: exempt[memtrack-alloc] emits over the tracked build side (right-unmatched pass)
    def _emit_right_unmatched(self, build, un):
        cols = []
        for sc in self.left.schema.cols:
            dtype = np_dtype_for(sc.ft.tp, sc.ft.flen)
            data = np.zeros(len(un), dtype=dtype) \
                if dtype != np.dtype(object) \
                else np.full(len(un), _ofill(sc.ft), dtype=object)
            cols.append(Column(sc.ft, data, np.zeros(len(un), dtype=bool)))
        for c in build.columns:
            cols.append(Column(c.ft, c.data[un], c.valid[un]))
        return Chunk(cols)

    def _cross_join(self, ctx):
        build = None
        tracked = 0
        for chunk in self.right.chunks(ctx):
            build = chunk if build is None else build.concat(chunk)
            tracked = memtrack.track_to(
                self.plan, memtrack.chunk_bytes(build), tracked)
        if build is None or build.num_rows == 0:
            memtrack.release(self.plan, host=tracked)
            return
        try:
            yield from self._cross_probe(ctx, build)
        finally:
            memtrack.release(self.plan, host=tracked)

    def _cross_probe(self, ctx, build):
        nb = build.num_rows
        for chunk in self.left.chunks(ctx):
            nl = chunk.num_rows
            if nl == 0:
                continue
            li = np.repeat(np.arange(nl), nb)
            ri = np.tile(np.arange(nb), nl)
            cols = [Column(c.ft, c.data[li], c.valid[li])
                    for c in chunk.columns]
            cols += [Column(c.ft, c.data[ri], c.valid[ri])
                     for c in build.columns]
            out = Chunk(cols)
            if self.plan.other_cond is not None:
                out = out.filter(eval_filter_host(self.plan.other_cond, out))
            yield out


class MergeJoinExec(HashJoinExec):
    """Streaming sorted-merge equi-join (ref: executor/merge_join.go:34).

    Contract (planner-enforced): both children deliver rows ascending by
    their single join key — pk-handle table scans arrive in handle order,
    keep_order index readers in index order. The executor keeps only a
    sliding window of the right side (rows whose key may still match a
    future left chunk), so neither side is fully materialized: memory is
    O(chunk + widest equal-key run). Matching is one vectorized
    searchsorted per left chunk — the same sort-join shape as the device
    kernel, minus the sort the inputs already paid."""

    def __init__(self, plan: ph.PhysMergeJoin):
        self.plan = plan
        self.schema = plan.schema
        self.left = build_executor(plan.children[0])
        self.right = build_executor(plan.children[1])
        self._kernel = None   # no device kernel: inputs are pre-sorted

    # lint: exempt[memtrack-alloc] merge window concatenation billed via track_to on the window buffer
    def chunks(self, ctx):
        plan = self.plan
        right_iter = self.right.chunks(ctx)
        window: Chunk | None = None    # right rows that may still match
        right_done = False
        # the sliding right window is this operator's only buffer; an
        # abandoned generator's residue is credited back at statement
        # detach (memtrack release-on-close)
        tracked_w = 0

        def right_key(ch):
            d, v = self._eval_keys(plan.right_keys, ch)[0]
            return d, v

        for chunk in self.left.chunks(ctx):
            n = chunk.num_rows
            if n == 0:
                continue
            lk, lv = self._eval_keys(plan.left_keys, chunk)[0]
            has_valid = bool(np.any(lv))
            lmax = lk[lv].max() if has_valid else None
            # grow the window until its tail key exceeds this chunk's max
            while not right_done and has_valid:
                wd, wv = (right_key(window) if window is not None
                          and window.num_rows else (None, None))
                if wd is not None and len(wd) and wv[-1] and wd[-1] > lmax:
                    break
                nxt = next(right_iter, None)
                if nxt is None:
                    right_done = True
                    break
                window = nxt if window is None else window.concat(nxt)
            tracked_w = memtrack.track_to(
                plan, memtrack.chunk_bytes(window) if window is not None
                else 0, tracked_w)
            if window is None or window.num_rows == 0:
                li = ri = np.empty(0, np.int64)
                unmatched = np.arange(n) if plan.join_type == "left" \
                    else np.empty(0, np.int64)
                out = self._emit(chunk, _empty_like_schema(
                    self.plan.children[1].schema), li, ri, unmatched)
                if out is not None and out.num_rows:
                    yield out
                continue
            wd, wv = right_key(window)
            val_idx = np.flatnonzero(wv)
            wdv = wd[val_idx]
            lo = np.searchsorted(wdv, lk, side="left")
            hi = np.searchsorted(wdv, lk, side="right")
            counts = np.where(lv, hi - lo, 0)
            total = int(counts.sum())
            li = np.repeat(np.arange(n), counts)
            cs = np.concatenate(([0], np.cumsum(counts)[:-1]))
            w = np.arange(total) - np.repeat(cs, counts)
            ri = val_idx[np.repeat(lo, counts) + w] if total else \
                np.empty(0, np.int64)
            pair = None
            if plan.other_cond is not None and len(li):
                pair = self._gather(chunk, window, li, ri)
                keep = eval_filter_host(plan.other_cond, pair)
                li, ri = li[keep], ri[keep]
                pair = pair.filter(keep)
            unmatched = np.empty(0, np.int64)
            if plan.join_type == "left":
                m = np.zeros(n, dtype=bool)
                m[li] = True
                unmatched = np.flatnonzero(~m)
            out = self._emit(chunk, window, li, ri, unmatched, pair=pair)
            if out is not None and out.num_rows:
                yield out
            # slide: right rows strictly below this chunk's max key can
            # never match again (left keys are non-decreasing)
            if has_valid and window.num_rows:
                keep = ~wv | (wd >= lmax)
                if not keep.all():
                    window = window.filter(keep)
                    tracked_w = memtrack.track_to(
                        plan, memtrack.chunk_bytes(window), tracked_w)
        memtrack.release(plan, host=tracked_w)


def _empty_like_schema(schema) -> Chunk:
    cols = []
    for sc in schema.cols:
        dtype = np_dtype_for(sc.ft.tp, sc.ft.flen)
        data = np.empty(0, dtype=dtype if dtype != np.dtype(object)
                        else object)
        cols.append(Column(sc.ft, data, np.empty(0, dtype=bool)))
    return Chunk(cols)


class IndexJoinExec(HashJoinExec):
    """Index nested-loop join (ref: executor/index_lookup_join.go:87).

    Streams the outer side; per outer chunk, collects the distinct valid
    join-key values and fetches ONLY the matching inner rows — via pk
    point reads (batch_get) when the key is the handle, else via
    synthesized point index ranges through the coprocessor. The fetched
    inner batch then joins against the chunk with the standard pair
    matcher. Never scans the inner table."""

    def __init__(self, plan: ph.PhysIndexJoin):
        self.plan = plan
        self.schema = plan.schema
        self.left = build_executor(plan.children[0])
        self._kernel = JoinKernel(len(plan.left_keys))

    def _fetch_inner(self, ctx, key_vals: np.ndarray) -> Chunk:
        """Inner rows whose key is in key_vals (distinct, non-null).
        Under a dirty txn, the SAME point lookups run through the union
        store (membuffer overlay) instead of the snapshot, so own writes
        are visible without ever scanning the whole inner table."""
        from tidb_tpu import ranger as rg
        icop = self.plan.children[1].cop
        dirty = _txn_is_dirty(ctx, icop.table.id)
        if self.plan.inner_index is None:
            handles = [int(v) for v in key_vals]
            if dirty:
                return self._dirty_rows_by_handles(ctx, icop, handles)
            return self._fetch_rows_by_handles(ctx, icop, handles)
        # secondary index: scan index entries for the key points to get
        # handles, then batch-fetch the rows (the per-batch form of
        # IndexLookUpExecutor, executor/distsql.go:524)
        ft = self.plan.right_keys[0].ft
        ranges = [rg.DatumRange(low=[_index_datum(v, ft)],
                                high=[_index_datum(v, ft)])
                  for v in key_vals]
        kv_ranges = rg.index_ranges_to_kv(icop.table.id,
                                          self.plan.inner_index.id, ranges)
        index_cols = [icop.table.col_by_name(c)
                      for c in self.plan.inner_index.columns]
        if dirty:
            # point index ranges through the union store: dirty index
            # entries (and tombstones) shadow the snapshot's. One range
            # scan per distinct key (bounded by the outer chunk's
            # distinct count); batching the snapshot side through the
            # coprocessor would need tombstone matching by raw index key
            # (unique-index tombstones carry no handle), so the simple
            # union scan wins until dirty index joins prove hot
            from tidb_tpu.table import index_kvrows_to_chunk
            rows = []
            for rng in kv_ranges:
                rows.extend(ctx.txn.iter_range(rng.start, rng.end))
            ich = index_kvrows_to_chunk(icop.table, self.plan.inner_index,
                                        index_cols, rows, len(index_cols))
            hc = ich.columns[len(index_cols)]
            handles = [int(h) for h in hc.data[:ich.num_rows]]
            return self._dirty_rows_by_handles(ctx, icop, handles)
        index_cop = ph.CopPlan(table=icop.table, cols=index_cols,
                               handle_col=len(index_cols),
                               index=self.plan.inner_index,
                               ranges=kv_ranges)
        req = CopRequest(tp=ReqType.DAG, ranges=kv_ranges,
                         plan=index_cop, start_ts=ctx.read_ts)
        handles: list[int] = []
        for resp in ctx.storage.client().send(req):
            hc = resp.chunk.columns[len(index_cols)]
            handles.extend(int(h) for h in hc.data[:resp.chunk.num_rows])
        return self._fetch_rows_by_handles(ctx, icop, handles)

    def _fetch_rows_by_handles(self, ctx, icop, handles) -> Chunk:
        snap = ctx.storage.snapshot(ctx.read_ts)
        keys = [tablecodec.record_key(icop.table.id, h) for h in handles]
        got = snap.batch_get(keys)
        kvrows = [(k, got[k]) for k in keys if k in got]
        chunk = kvrows_to_chunk(icop.table, icop.cols, kvrows,
                                icop.handle_col)
        return exec_cop_plan(icop, chunk).chunk

    def _dirty_rows_by_handles(self, ctx, icop, handles) -> Chunk:
        """Point reads with the membuffer overlaid on ONE batched
        snapshot read: own inserts appear, own deletes vanish, and the
        clean majority of keys costs a single batch_get instead of
        per-key round trips."""
        keys = [tablecodec.record_key(icop.table.id, h)
                for h in dict.fromkeys(int(h) for h in handles)]
        membuf = ctx.txn.us.membuf
        dirty_vals = {}
        clean = []
        for k in keys:
            v = membuf.get(k)
            if v is None:
                clean.append(k)
            else:
                dirty_vals[k] = v
        got = ctx.txn.snapshot.batch_get(clean) if clean else {}
        kvrows = []
        for k in keys:
            v = dirty_vals.get(k)
            if v is None:
                v = got.get(k)
            elif v is kv._TOMBSTONE:     # own delete shadows the snapshot
                continue
            if v is not None:
                kvrows.append((k, v))
        chunk = kvrows_to_chunk(icop.table, icop.cols, kvrows,
                                icop.handle_col)
        return exec_cop_plan(icop, chunk).chunk

    def chunks(self, ctx):
        plan = self.plan
        tracked = 0
        for chunk in self.left.chunks(ctx):
            n = chunk.num_rows
            if n == 0:
                continue
            kd, kv = plan.left_keys[0].eval(chunk)
            kd, kv = np.asarray(kd), np.asarray(kv, dtype=bool)
            vals = np.unique(kd[kv]) if kv.any() else kd[:0]
            build = self._fetch_inner(ctx, vals) if len(vals) else \
                _empty_like_schema(plan.children[1].schema)
            # per-outer-batch inner build: tracked to its replacement
            tracked = memtrack.track_to(
                plan, memtrack.chunk_bytes(build), tracked)
            nb = build.num_rows
            if nb == 0:
                if plan.join_type == "left":
                    out = self._emit(chunk, build, np.empty(0, np.int64),
                                     np.empty(0, np.int64), np.arange(n))
                    if out is not None and out.num_rows:
                        yield out
                continue
            enc = JoinKeyEncoder(len(plan.right_keys))  # fresh per batch
            bk = enc.fit_build(self._eval_keys(plan.right_keys, build))
            pk = enc.transform_probe(self._eval_keys(plan.left_keys, chunk))
            with sched.device_slot(), memtrack.device_scope(
                    self.plan, self._kernel.build_nbytes(nb) +
                    self._kernel.dispatch_nbytes(n)):
                li, ri = runtime_stats.device_call(
                    self.plan, self._kernel, bk, pk, nb, n)
            pair = None
            if plan.other_cond is not None and len(li):
                pair = self._gather(chunk, build, li, ri)
                keep = eval_filter_host(plan.other_cond, pair)
                li, ri = li[keep], ri[keep]
                pair = pair.filter(keep)
            unmatched = np.empty(0, np.int64)
            if plan.join_type == "left":
                m = np.zeros(n, dtype=bool)
                m[li] = True
                unmatched = np.flatnonzero(~m)
            out = self._emit(chunk, build, li, ri, unmatched, pair=pair)
            if out is not None and out.num_rows:
                yield out
        memtrack.release(plan, host=tracked)


def _index_datum(v, ft):
    """numpy scalar -> the datum representation codec.encode_key expects
    for an index column of FieldType ft."""
    if ft.eval_type == EvalType.DECIMAL:
        return (ft.frac, int(v))
    if isinstance(v, (np.integer, int)):
        return int(v)
    if isinstance(v, (np.floating, float)):
        return float(v)
    return v


# ---------------------------------------------------------------------------
# Writes

def _chunk_row_to_kvdatums(chunk: Chunk, cols, row: int) -> dict[int, object]:
    """Row of a reader chunk -> {col_id: KV datum} for index maintenance."""
    out = {}
    for j, ci in enumerate(cols):
        c = chunk.columns[j]
        if not c.valid[row]:
            out[ci.id] = None
            continue
        v = c.data[row]
        if ci.ft.eval_type == EvalType.DECIMAL:
            out[ci.id] = (ci.ft.frac, int(v))
        elif c.data.dtype == np.dtype(object):
            out[ci.id] = v
        else:
            out[ci.id] = v.item()
    return out


class InsertExec(Executor):
    """Ref: executor/write.go:896 InsertExec (dup handling :1343)."""

    def __init__(self, plan: ph.PhysInsert):
        self.plan = plan
        self.schema = plan.schema
        self.source = build_executor(plan.source)

    def execute(self, ctx: ExecContext) -> int:
        from tidb_tpu.table import DupKeyError, Table
        plan = self.plan
        tbl = Table(plan.table, ctx.storage)
        txn = ctx.txn
        affected = 0
        for values in self._source_rows(ctx):
            try:
                tbl.add_record(txn, values)
                affected += 1
            except DupKeyError:
                if plan.ignore:
                    continue
                if plan.is_replace or plan.on_duplicate:
                    affected += self._handle_dup(ctx, tbl, txn, values)
                    continue
                raise
        if tbl.first_alloc_id is not None:
            # LAST_INSERT_ID(): first auto value of this statement
            ctx.last_insert_id = tbl.first_alloc_id
        return affected

    def _source_rows(self, ctx):
        """Yields {col_name: value} dicts; a key present with None is an
        explicit NULL, an absent key means 'use the default' (DEFAULT
        keyword or omitted column)."""
        plan = self.plan
        if isinstance(plan.source, ph.PhysValues) and not plan.source.schema.cols:
            # literal VALUES rows: evaluate per cell; None expr == DEFAULT
            for rexprs in plan.source.rows:
                values = {}
                for cname, e in zip(plan.columns, rexprs):
                    if e is None:      # DEFAULT keyword
                        continue
                    d, v = e.eval_xp(np, [], 1)
                    if not v[0]:
                        values[cname] = None
                    elif e.ft.eval_type == EvalType.DECIMAL:
                        values[cname] = (e.ft.frac, int(d[0]))
                    else:
                        values[cname] = d[0].item() \
                            if hasattr(d[0], "item") else d[0]
                yield values
            return
        for chunk in self.source.chunks(ctx):
            src_cols = chunk.columns
            for i in range(chunk.num_rows):
                values = {}
                for cname, col in zip(plan.columns, src_cols):
                    if not col.valid[i]:
                        values[cname] = None   # explicit NULL
                        continue
                    v = col.data[i]
                    if col.ft.eval_type == EvalType.DECIMAL:
                        # scaled at the SOURCE column's frac; target frac
                        # conversion happens in encode_datum_for_col
                        values[cname] = (col.ft.frac, int(v))
                    else:
                        values[cname] = v.item() if hasattr(v, "item") else v
                yield values

    def _handle_dup(self, ctx, tbl: "Table", txn, values) -> int:
        """REPLACE / ON DUPLICATE KEY UPDATE: find the conflicting row."""
        info = self.plan.table
        handle = self._find_conflict(tbl, txn, values)
        if handle is None:
            raise ExecError("duplicate row vanished")
        old = tbl.row_by_handle(txn, handle)
        if self.plan.is_replace:
            tbl.remove_record(txn, handle, old)
            tbl.add_record(txn, values)
            return 2
        # ON DUPLICATE KEY UPDATE over [old | candidate]: the second
        # half feeds VALUES(col) refs (planner's __values__ columns)
        cols = info.public_columns()
        from tidb_tpu.table import encode_datum_for_col, rows_to_chunk
        cand = []
        for c in cols:
            cn = c.name.lower()
            if cn in values:
                cand.append(encode_datum_for_col(values[cn], c.ft))
            elif c.has_default:
                cand.append(encode_datum_for_col(c.default, c.ft))
            else:
                cand.append(None)
        row_chunk = rows_to_chunk(
            [c.ft for c in cols] * 2,
            [[old.get(c.id) for c in cols] + cand])
        new_vals = {}
        for cname, expr in self.plan.on_duplicate:
            d, v = expr.eval(row_chunk)
            ci = info.col_by_name(cname)
            if not v[0]:
                new_vals[cname] = None
            elif ci.ft.eval_type == EvalType.DECIMAL:
                new_vals[cname] = (expr.ft.frac if
                                   expr.ft.eval_type == EvalType.DECIMAL
                                   else ci.ft.frac, int(d[0]))
            else:
                new_vals[cname] = d[0].item() if hasattr(d[0], "item") \
                    else d[0]
        tbl.update_record(txn, handle, old, new_vals)
        return 2

    def _find_conflict(self, tbl, txn, values):
        info = self.plan.table
        if info.pk_is_handle:
            pk = info.col_by_name(info.pk_col_name)
            v = values.get(info.pk_col_name.lower())
            if v is not None and tbl.row_by_handle(txn, int(v)) is not None:
                return int(v)
        for idx in info.indexes:
            if not idx.unique:
                continue
            vals = []
            for cn in idx.columns:
                ci = info.col_by_name(cn)
                v = encode_datum_for_col(values.get(cn.lower()), ci.ft)
                if ci.ft.is_ci and isinstance(v, str):
                    from tidb_tpu.sqltypes import collation_key
                    v = collation_key(v)
                vals.append(v)
            if any(v is None for v in vals):
                continue
            raw = txn.get(tablecodec.index_key(info.id, idx.id, vals))
            if raw is not None:
                from tidb_tpu import codec
                return codec.decode_int(raw)[0]
        return None


class UpdateExec(Executor):
    def __init__(self, plan: ph.PhysUpdate):
        self.plan = plan
        self.reader = build_executor(plan.reader)

    def execute(self, ctx: ExecContext) -> int:
        plan = self.plan
        tbl = Table(plan.table, ctx.storage)
        cols = plan.table.public_columns()
        affected = 0
        for chunk in self.reader.chunks(ctx):
            if chunk.num_rows == 0:
                continue
            handle_col = chunk.columns[-1]
            new_cols = {}
            for cname, expr in plan.assignments:
                new_cols[cname] = (expr, *expr.eval(chunk))
            pk_name = plan.table.pk_col_name.lower() \
                if plan.table.pk_is_handle else None
            for i in range(chunk.num_rows):
                handle = int(handle_col.data[i])
                old = _chunk_row_to_kvdatums(chunk, cols, i)
                new_vals = {}
                for cname, (expr, d, v) in new_cols.items():
                    ci = plan.table.col_by_name(cname)
                    if not v[i]:
                        new_vals[cname] = None
                    elif ci.ft.eval_type == EvalType.DECIMAL:
                        frac = expr.ft.frac if \
                            expr.ft.eval_type == EvalType.DECIMAL else ci.ft.frac
                        new_vals[cname] = (frac, int(d[i]))
                    else:
                        new_vals[cname] = d[i].item() \
                            if hasattr(d[i], "item") else d[i]
                if pk_name is not None and pk_name in new_vals and \
                        new_vals[pk_name] is not None and \
                        int(new_vals[pk_name]) != handle:
                    # handle change: move the row (delete + insert w/ dup
                    # check) instead of rewriting under the old handle
                    merged = {}
                    for c in cols:
                        merged[c.name.lower()] = old.get(c.id)
                    merged.update(new_vals)
                    tbl.remove_record(ctx.txn, handle, old)
                    tbl.add_record(ctx.txn, merged)
                else:
                    tbl.update_record(ctx.txn, handle, old, new_vals)
                affected += 1
        return affected


class DeleteExec(Executor):
    def __init__(self, plan: ph.PhysDelete):
        self.plan = plan
        self.reader = build_executor(plan.reader)

    def execute(self, ctx: ExecContext) -> int:
        tbl = Table(self.plan.table, ctx.storage)
        cols = self.plan.table.public_columns()
        affected = 0
        for chunk in self.reader.chunks(ctx):
            handle_col = chunk.columns[-1]
            for i in range(chunk.num_rows):
                handle = int(handle_col.data[i])
                old = _chunk_row_to_kvdatums(chunk, cols, i)
                tbl.remove_record(ctx.txn, handle, old)
                affected += 1
        return affected


class MultiUpdateExec(Executor):
    """UPDATE t1, t2 SET ... (ref: executor/write.go:479 multi-table
    UpdateExec): one pass over the join result; each target updates its
    matched rows, deduped per handle; assignment expressions evaluate
    over the full join row, so t1's new value may read t2's columns."""

    def __init__(self, plan: ph.PhysMultiUpdate):
        self.plan = plan
        self.reader = build_executor(plan.reader)

    def execute(self, ctx: ExecContext) -> int:
        per_target = []
        for info, col_start, handle_idx, assigns in self.plan.targets:
            per_target.append((Table(info, ctx.storage), info,
                               col_start, handle_idx, assigns, set()))
        affected = 0
        for chunk in self.reader.chunks(ctx):
            if chunk.num_rows == 0:
                continue
            for tbl, info, col_start, handle_idx, assigns, seen \
                    in per_target:
                hcol = chunk.columns[handle_idx]
                cols = info.public_columns()
                block = Chunk(chunk.columns[col_start:
                                            col_start + len(cols)])
                new_cols = {}
                for cname, expr in assigns:
                    new_cols[cname] = (expr, *expr.eval(chunk))
                pk_name = info.pk_col_name.lower() \
                    if info.pk_is_handle else None
                for i in range(chunk.num_rows):
                    if not hcol.valid[i]:
                        continue    # outer-join padding: no row there
                    handle = int(hcol.data[i])
                    if handle in seen:
                        continue
                    seen.add(handle)
                    old = _chunk_row_to_kvdatums(block, cols, i)
                    new_vals = {}
                    for cname, (expr, d, v) in new_cols.items():
                        ci = info.col_by_name(cname)
                        if not v[i]:
                            new_vals[cname] = None
                        elif ci.ft.eval_type == EvalType.DECIMAL:
                            frac = expr.ft.frac if \
                                expr.ft.eval_type == EvalType.DECIMAL \
                                else ci.ft.frac
                            new_vals[cname] = (frac, int(d[i]))
                        else:
                            new_vals[cname] = d[i].item() \
                                if hasattr(d[i], "item") else d[i]
                    if pk_name is not None and pk_name in new_vals and \
                            new_vals[pk_name] is not None and \
                            int(new_vals[pk_name]) != handle:
                        merged = {}
                        for c in cols:
                            merged[c.name.lower()] = old.get(c.id)
                        merged.update(new_vals)
                        tbl.remove_record(ctx.txn, handle, old)
                        tbl.add_record(ctx.txn, merged)
                    else:
                        tbl.update_record(ctx.txn, handle, old, new_vals)
                    affected += 1
        return affected


class MultiDeleteExec(Executor):
    """DELETE t1, t2 FROM <join> (ref: executor/write.go:194
    deleteMultiTables): one pass over the join result; each target
    deletes its matched rows, deduped per handle (a handle can match
    several join rows)."""

    def __init__(self, plan: ph.PhysMultiDelete):
        self.plan = plan
        self.reader = build_executor(plan.reader)

    def execute(self, ctx: ExecContext) -> int:
        per_target = []
        for info, col_start, handle_idx in self.plan.targets:
            per_target.append((Table(info, ctx.storage), info,
                               col_start, handle_idx, set()))
        affected = 0
        for chunk in self.reader.chunks(ctx):
            for tbl, info, col_start, handle_idx, seen in per_target:
                hcol = chunk.columns[handle_idx]
                cols = info.public_columns()
                block = Chunk(chunk.columns[col_start:
                                            col_start + len(cols)])
                for i in range(chunk.num_rows):
                    if not hcol.valid[i]:
                        continue    # outer-join padding: no row there
                    handle = int(hcol.data[i])
                    if handle in seen:
                        continue
                    seen.add(handle)
                    old = _chunk_row_to_kvdatums(block, cols, i)
                    tbl.remove_record(ctx.txn, handle, old)
                    affected += 1
        return affected


class ApplyExec(Executor):
    """Correlated-subquery apply (ref: executor/join.go:447
    NestedLoopApplyExec): per outer row, bind the correlated cells, run
    the inner plan, and evaluate the EXISTS / IN / comparison predicate
    as a filter over the outer rows. Uncorrelated inners run exactly once
    and the predicate vectorizes over the whole chunk."""

    def __init__(self, plan: ph.PhysApply):
        self.plan = plan
        self.schema = plan.schema
        self.child = build_executor(plan.children[0])

    def chunks(self, ctx: ExecContext):
        # the predicate over the outer chunks is exec.apply; the inner
        # plan's runs are its child exec.apply.inner (one span around an
        # uncorrelated inner's single run, or around one outer chunk's
        # row-by-row runs), the inner executors' own spans nesting there
        own = _OwnSpan(lambda: trace.span("exec.apply"))
        body = self._scalar_chunks if self.plan.mode == "scalar" \
            else self._filtered
        yield from own.drive(body(ctx, own))

    def _filtered(self, ctx, own):
        plan = self.plan
        cache = None            # uncorrelated: (vals, valid, has_rows)
        for chunk in own.pull(self.child.chunks(ctx)):
            n = chunk.num_rows
            if n == 0:
                continue
            left = None
            if plan.left is not None:
                ld, lv = plan.left.eval(chunk)
                left = (np.asarray(ld), np.asarray(lv))
            if not plan.corr:
                if cache is None:
                    with trace.span("exec.apply.inner"):
                        cache = self._run_inner(
                            ctx, first_only=plan.mode == "exists")
                keep = self._vector_predicate(left, n, *cache)
            else:
                keep = np.zeros(n, dtype=bool)
                with trace.span("exec.apply.inner", rows=n):
                    for i in range(n):
                        self._bind_corr(chunk, i)
                        vals, valid, has = self._run_inner(
                            ctx, first_only=plan.mode == "exists")
                        row_left = None if left is None else \
                            (left[0][i:i + 1], left[1][i:i + 1])
                        keep[i] = bool(self._vector_predicate(
                            row_left, 1, vals, valid, has)[0])
            yield chunk.filter(keep)

    def _scalar_chunks(self, ctx, own):
        """mode="scalar": append the inner's single value as a new
        column (the planner's lifted scalar subquery)."""
        plan = self.plan
        ft = plan.schema.cols[-1].ft
        dtype = np_dtype_for(ft.tp, ft.flen)
        cache = None
        for chunk in own.pull(self.child.chunks(ctx)):
            n = chunk.num_rows
            if n == 0:
                continue
            if not plan.corr:
                if cache is None:
                    with trace.span("exec.apply.inner"):
                        cache = self._scalar_value(ctx)
                val, ok = cache
                data = np.full(n, val if ok else
                               ("" if dtype == np.dtype(object) else 0),
                               dtype=dtype)
                valid = np.full(n, ok, dtype=bool)
            else:
                # lint: exempt[memtrack-alloc] one scalar column per probe chunk
                data = np.zeros(n, dtype=dtype) \
                    if dtype != np.dtype(object) else \
                    np.full(n, "", dtype=object)
                valid = np.zeros(n, dtype=bool)
                with trace.span("exec.apply.inner", rows=n):
                    for i in range(n):
                        self._bind_corr(chunk, i)
                        val, ok = self._scalar_value(ctx)
                        if ok:
                            data[i] = val
                            valid[i] = True
            yield Chunk(chunk.columns + [Column(ft, data, valid)])

    def _scalar_value(self, ctx):
        """Run the inner plan expecting at most one row -> (value, ok);
        an empty result is SQL NULL."""
        vals, valid, has = self._run_inner(ctx, first_only=False)
        if not has or len(vals) == 0:
            return None, False
        if len(vals) > 1:
            raise ExecError("Subquery returns more than 1 row")
        return vals[0], bool(valid[0])

    def _bind_corr(self, chunk, i: int):
        """Bind outer row i into the inner plan's correlated cells."""
        for oi, cell in self.plan.corr:
            c = chunk.columns[oi]
            cell.cell[0] = c.data[i]
            cell.cell[1] = bool(c.valid[i])

    def _run_inner(self, ctx, first_only: bool):
        """-> (first-column values, valid, has_rows)."""
        exe = build_executor(self.plan.inner)
        vals = []
        valid = []
        has = False
        for ch in exe.chunks(ctx):
            if ch.num_rows == 0:
                continue
            has = True
            if first_only:
                return None, None, True
            c = ch.columns[0]
            vals.append(np.asarray(c.data))
            valid.append(np.asarray(c.valid))
        if not vals:
            return (np.empty(0), np.empty(0, dtype=bool), has)
        # lint: exempt[memtrack-alloc] subquery first-column buffer, inner-bounded
        return np.concatenate(vals), np.concatenate(valid), has

    def _vector_predicate(self, left, n: int, vals, valid, has):
        plan = self.plan
        if plan.mode == "exists":
            r = np.full(n, has, dtype=bool)
            return ~r if plan.negated else r
        if plan.mode == "cmp":
            if plan.quant:
                return self._quant_mask(left, n, vals, valid)
            if not has or len(vals) == 0:
                return np.zeros(n, dtype=bool)   # NULL -> filtered
            if len(vals) > 1:
                raise ExecError("Subquery returns more than 1 row")
            return self._cmp_mask(left, n, vals, valid)
        # IN / NOT IN with SQL three-valued logic
        ld, lv = left
        inner = vals[valid] if len(vals) else vals
        has_null = bool((~valid).any()) if len(valid) else False
        match = self._set_match(ld, inner)
        if plan.negated:
            # NOT IN: TRUE only for valid left, no match, and no NULLs
            # in the subquery result (else NULL) — except the empty set,
            # where x NOT IN () is TRUE even for NULL x
            if has_null:
                return np.zeros(n, dtype=bool)
            if len(inner) == 0:
                return np.ones(n, dtype=bool)
            return lv & ~match
        return lv & match

    def _norm_in_sides(self, ld, inner):
        """Bring both IN sides to one comparable representation (mirrors
        HashJoinExec key normalization): decimals compare at a common
        scale, mixed numeric compares as double."""
        lft = self.plan.left.ft
        ift = self.plan.inner.schema.cols[0].ft
        let, iet = lft.eval_type, ift.eval_type
        if np.dtype(object) in (getattr(ld, "dtype", None),
                                getattr(inner, "dtype", None)):
            return ld, inner
        lfrac = lft.frac if let == EvalType.DECIMAL else 0
        ifrac = ift.frac if iet == EvalType.DECIMAL else 0
        if let == iet and lfrac == ifrac:
            return ld, inner
        def to_f(d, frac):
            return np.asarray(d).astype(np.float64) / (10.0 ** frac)
        return to_f(ld, lfrac), to_f(inner, ifrac)

    def _quant_mask(self, left, n: int, vals, valid):
        """expr <cmp> ANY/ALL (subquery) with SQL three-valued logic
        (ref: expression/builtin_compare.go + plan rewrite of
        quantified comparisons): only the set's extrema decide ordering
        comparisons, so no per-element loop is needed.

        ANY:  TRUE if some valid element satisfies; else NULL if the
              set has NULLs or the left is NULL; else FALSE (empty ->
              FALSE).
        ALL:  FALSE if some valid element violates; else NULL if the
              set has NULLs or the left is NULL; else TRUE (empty ->
              TRUE)."""
        from tidb_tpu.expression.core import Op as _Op
        plan = self.plan
        ld, lv = left
        vv = vals[valid] if len(vals) else vals
        has_null_inner = bool((~valid).any()) if len(valid) else False
        is_all = plan.quant == "all"
        if len(vv) == 0:
            if has_null_inner:          # all-NULL set: always NULL
                return np.zeros(n, dtype=bool)
            base = np.full(n, is_all, dtype=bool)   # truly empty set
            return ~base if plan.negated else base
        op = plan.cmp_op

        def cmp_vs(v, o):
            return self._one_cmp(ld, lv, n, v, o)

        lo, hi = vv.min(), vv.max()
        if op in (_Op.EQ, _Op.NE):
            # = ANY is IN; = ALL: every element equal (min==v==max);
            # <> ALL is NOT IN; <> ANY: some element differs
            def all_eq():
                return cmp_vs(lo, _Op.EQ) & cmp_vs(hi, _Op.EQ)
            def in_set():
                return lv & self._set_match(ld, vv)
            if op == _Op.EQ:
                true_m = all_eq() if is_all else in_set()
            else:
                true_m = (lv & ~in_set()) if is_all else (lv & ~all_eq())
        else:
            # ordering: ANY against the friendliest element, ALL
            # against the harshest
            pick_min = (op in (_Op.GT, _Op.GE)) != is_all
            true_m = cmp_vs(lo if pick_min else hi, op)
        if is_all:
            # violation is definite FALSE even with NULLs around
            false_m = lv & ~true_m
            if has_null_inner:
                true_m = np.zeros(n, dtype=bool)
            return false_m if plan.negated else true_m
        if has_null_inner:
            false_m = np.zeros(n, dtype=bool)
        else:
            false_m = lv & ~true_m
        return false_m if plan.negated else true_m

    def _set_match(self, ld, inner):
        """Membership of each left value in the inner set, after the
        shared type normalization. Used by IN and the EQ quantifiers."""
        ld2, inner2 = self._norm_in_sides(ld, inner)
        if len(inner2) and inner2.dtype != np.dtype(object) and \
                ld2.dtype != np.dtype(object):
            return np.isin(ld2, inner2)
        pool = set(inner2.tolist())
        return np.array([v in pool for v in ld2], dtype=bool)

    def _one_cmp(self, ld, lv, n: int, v, op):
        """Vector compare of the left side against one inner value,
        through the expression layer for type-correct semantics."""
        plan = self.plan
        ift = plan.inner.schema.cols[0].ft
        dt = np.dtype(object) if isinstance(v, (str, bytes)) else None
        rhs_d = np.full(n, v, dtype=dt)
        lexpr = _ArrayExpr(plan.left.ft, ld, lv)
        rexpr = _ArrayExpr(ift, rhs_d, np.ones(n, dtype=bool))
        from tidb_tpu.expression.core import func as _f
        d, vmask = _f(op, lexpr, rexpr).eval_xp(np, [], n)
        return np.asarray(d).astype(bool) & np.asarray(vmask) & lv

    def _cmp_mask(self, left, n: int, vals, valid):
        plan = self.plan
        if not bool(valid[0]):
            return np.zeros(n, dtype=bool)       # NULL scalar
        ld, lv = left
        ift = plan.inner.schema.cols[0].ft
        v = vals[0]
        rhs_d = np.full(n, v, dtype=vals.dtype) if \
            vals.dtype != np.dtype(object) else np.full(n, v, dtype=object)
        # compare through the expression layer for type-correct semantics
        lexpr = _ArrayExpr(plan.left.ft, ld, lv)
        rexpr = _ArrayExpr(ift, rhs_d, np.ones(n, dtype=bool))
        from tidb_tpu.expression.core import func as _f
        d, vmask = _f(plan.cmp_op, lexpr, rexpr).eval_xp(np, [], n)
        out = np.asarray(d).astype(bool) & np.asarray(vmask)
        return ~out & np.asarray(vmask) if plan.negated else out


class _ArrayExpr(Expression):
    """Adapter: a precomputed (data, valid) pair as an Expression leaf."""

    def __init__(self, ft, data, valid):
        self.ft = ft
        self._d = data
        self._v = valid

    def eval_xp(self, xp, cols, n):
        return self._d, self._v

    def columns_used(self):
        return set()

    def is_device_safe(self):
        return False


def _mesh_agg_builder(plan):
    from tidb_tpu.executor.mesh import MeshAggExec
    return MeshAggExec(plan)


def _mesh_lookup_agg_builder(plan):
    from tidb_tpu.executor.mesh import MeshLookupAggExec
    return MeshLookupAggExec(plan)


from tidb_tpu.plan import mesh_route as _mr  # noqa: E402

class UnionExec(Executor):
    """UNION ALL over chunk streams: children run in order, their chunks
    pass through with columns coerced to the union's output types
    (numeric widening; names from the first branch). DISTINCT is a
    HashAgg the planner layers on top — no row-level Python dedup."""

    def __init__(self, plan: ph.PhysUnion):
        self.plan = plan
        self.schema = plan.schema
        self.children = [build_executor(c) for c in plan.children]

    @staticmethod
    def _coerce(c: Column, ft) -> Column:
        d, src = c.data, c.ft
        if ft.eval_type == EvalType.STRING and \
                src.eval_type != EvalType.STRING:
            # mixed string/numeric union: MySQL coerces to string
            from tidb_tpu.sqltypes import (format_datetime,
                                           scaled_to_decimal)
            if src.eval_type == EvalType.DECIMAL:
                vals = [str(scaled_to_decimal(int(x), src.frac))
                        for x in d]
            elif src.eval_type == EvalType.DATETIME:
                vals = [format_datetime(int(x), src.tp) for x in d]
            elif d.dtype == np.float64:
                vals = [repr(float(x)) for x in d]
            else:
                vals = [str(int(x)) for x in d]
            return Column(ft, np.array(vals, dtype=object),
                          c.valid.copy())
        if ft.eval_type == EvalType.DECIMAL:
            if src.eval_type == EvalType.DECIMAL:
                if ft.frac > src.frac:
                    d = d.astype(np.int64) * np.int64(
                        10 ** (ft.frac - src.frac))
            elif src.eval_type == EvalType.INT:
                d = d.astype(np.int64) * np.int64(10 ** ft.frac)
        elif ft.eval_type == EvalType.REAL:
            if src.eval_type == EvalType.DECIMAL:
                d = d.astype(np.float64) / (10.0 ** src.frac)
            elif d.dtype != np.float64 and d.dtype != np.dtype(object):
                d = d.astype(np.float64)
        else:
            want = np_dtype_for(ft.tp, ft.flen)
            if d.dtype != want:
                d = d.astype(want)
        return Column(ft, d, c.valid.copy())

    def chunks(self, ctx):
        fts = [c.ft for c in self.schema.cols]
        for child in self.children:
            for chunk in child.chunks(ctx):
                yield Chunk([self._coerce(c, ft)
                             for c, ft in zip(chunk.columns, fts)])


_BUILDERS = {
    _mr.PhysMeshAgg: _mesh_agg_builder,
    _mr.PhysMeshLookupAgg: _mesh_lookup_agg_builder,
    ph.PhysApply: ApplyExec,
    ph.PhysTableReader: TableReaderExec,
    ph.PhysIndexReader: IndexReaderExec,
    ph.PhysIndexLookUp: IndexLookUpExec,
    ph.PhysPointGet: PointGetExec,
    ph.PhysUnion: UnionExec,
    ph.PhysValues: ValuesExec,
    ph.PhysFinalAgg: FinalAggExec,
    ph.PhysHashAgg: HashAggExec,
    ph.PhysStreamAgg: StreamAggExec,
    ph.PhysMergeJoin: MergeJoinExec,
    ph.PhysIndexJoin: IndexJoinExec,
    ph.PhysSelection: SelectionExec,
    ph.PhysProjection: ProjectionExec,
    ph.PhysLimit: LimitExec,
    ph.PhysSort: SortExec,
    ph.PhysTopN: TopNExec,
    ph.PhysHashJoin: HashJoinExec,
    ph.PhysInsert: InsertExec,
    ph.PhysUpdate: UpdateExec,
    ph.PhysDelete: DeleteExec,
    ph.PhysMultiDelete: MultiDeleteExec,
    ph.PhysMultiUpdate: MultiUpdateExec,
}
