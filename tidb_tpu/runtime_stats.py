"""Per-operator runtime statistics: the RuntimeStatsColl analogue.

Reference: the reference's execdetails.RuntimeStatsColl — every executor
registers basic stats (actual rows, loop count, wall time) keyed by plan
node, EXPLAIN ANALYZE renders them next to the plan tree, and the slow
log / statement summary embed them per statement.

Here a `StatsCollector` lives for one statement execution. The session
installs it in a thread-local around build_executor + execution;
`instrument()` (called from build_executor) wraps each executor's
`chunks`/`partials`/`execute` so every batch yielded records
rows/loops/host-time into the node's `OpStats`. The coprocessor fan-out
re-installs the collector inside its pool workers (like the sysvar
overlay) so storage-side device kernels can attribute device time to the
reader node that issued them.

Device time is EXPENSIVE to observe — `jax.block_until_ready` serializes
dispatch — so it is gated behind the `tidb_tpu_runtime_stats_device`
sysvar and collected only at explicit kernel call sites via
`device_call()` / `device_section()`. Host-side counts stay on by
default (`tidb_tpu_runtime_stats`): the per-chunk cost is one
perf_counter read and three integer adds, amortized over 64k-row chunks.
"""

from __future__ import annotations

import contextlib
import threading
import time

__all__ = ["OpStats", "StatsCollector", "collecting", "current",
           "instrument", "device_call", "device_section", "fmt_ns",
           "fmt_bytes", "note_superchunk", "note_pipeline_stall",
           "note_finalize_wait", "note_fallback", "note_encoding",
           "note_bytes_touched", "note_kernel", "note_mode",
           "device_watermark"]

_tl = threading.local()


_mem_stats_available: bool | None = None   # None = not yet probed


def device_watermark() -> int:
    """Backend peak-memory watermark, 0 when the platform doesn't report
    one (CPU jax has no allocator stats). PROCESS-WIDE: concurrent
    statements' allocations inflate it for each other, so it feeds only
    the server-root gauge (tidb_tpu_device_peak_bytes) — per-operator
    `mem` comes from memtrack's per-statement trackers. The availability
    probe is cached so CPU backends never pay a raised-and-swallowed
    exception per call."""
    global _mem_stats_available
    if _mem_stats_available is False:
        return 0
    try:
        import jax
        ms = jax.local_devices()[0].memory_stats()
        if ms:
            _mem_stats_available = True
            return int(ms.get("peak_bytes_in_use", 0) or 0)
        _mem_stats_available = False
    except Exception:  # noqa: BLE001 - stats must never break execution
        _mem_stats_available = False
    return 0


class OpStats:
    """One physical operator's actuals for one statement execution."""

    __slots__ = ("name", "act_rows", "loops", "time_ns",
                 "device_time_ns", "cop_tasks",
                 "superchunks", "coalesced_chunks", "superchunk_fill_rows",
                 "superchunk_bucket_rows", "pipeline_stall_ns",
                 "fallbacks", "encoding", "kernel_family",
                 "kernel_compile", "kernel_bytes", "kernel_busy_ns",
                 "kernel_dispatches", "mode")

    def __init__(self, name: str):
        self.name = name
        self.act_rows = 0
        self.loops = 0
        self.time_ns = 0           # host wall, inclusive of children
        self.device_time_ns = 0    # sum around block_until_ready
        self.cop_tasks = 0
        # superchunk pipeline (ops/runtime.py): how the operator's device
        # work was batched and how long the host sat blocked on readback
        self.superchunks = 0            # coalesced device dispatches
        self.coalesced_chunks = 0       # source chunks folded into them
        self.superchunk_fill_rows = 0   # live rows across superchunks
        self.superchunk_bucket_rows = 0  # padded bucket rows (>= fill)
        self.pipeline_stall_ns = 0      # host blocked in finalize
        # device->host fallbacks: batches this operator planned for the
        # device but executed on the host (capacity/collision miss that
        # survived the partition retry, or a non-device-safe plan)
        self.fallbacks = 0
        # encoded-execution mode this operator last ran in (EXPLAIN
        # ANALYZE pipeline column): "" = nothing noted, else one of
        # encoded | decoded | direct-agg | fused:<fragment>
        self.encoding = ""
        # kernel-profile feed (tidb_tpu/profiler.py, EXPLAIN ANALYZE
        # `kernel` column): which kernel family served this operator's
        # dispatches this statement, how its compile was satisfied
        # (hit|miss|cached, the persistent-cache attribution) and the
        # bytes/busy-ns this statement's dispatches contributed — the
        # per-statement slice of the process-wide profile row, from
        # which the online roofline_fraction is rendered
        self.kernel_family = ""
        self.kernel_compile = ""
        self.kernel_bytes = 0
        self.kernel_busy_ns = 0
        self.kernel_dispatches = 0
        # execution mode that actually ran (the perfschema mode-history
        # memo's vocabulary): "" = nothing noted, else one of
        # direct | hash | sort | fused | hybrid | host
        self.mode = ""

    def fill_ratio(self) -> float:
        """Live rows over padded bucket rows (0.0 when no superchunks)."""
        if not self.superchunk_bucket_rows:
            return 0.0
        return self.superchunk_fill_rows / self.superchunk_bucket_rows

    def to_dict(self) -> dict:
        return {"name": self.name, "act_rows": self.act_rows,
                "loops": self.loops, "time_ns": self.time_ns,
                "device_time_ns": self.device_time_ns,
                "cop_tasks": self.cop_tasks,
                "superchunks": self.superchunks,
                "coalesced_chunks": self.coalesced_chunks,
                "superchunk_fill_rows": self.superchunk_fill_rows,
                "superchunk_bucket_rows": self.superchunk_bucket_rows,
                "pipeline_stall_ns": self.pipeline_stall_ns,
                "fallbacks": self.fallbacks,
                "encoding": self.encoding,
                "kernel_family": self.kernel_family,
                "kernel_compile": self.kernel_compile,
                "kernel_bytes": self.kernel_bytes,
                "kernel_busy_ns": self.kernel_busy_ns,
                "kernel_dispatches": self.kernel_dispatches,
                "mode": self.mode}


class StatsCollector:
    """Stats for one statement: OpStats keyed by plan-node identity.

    The entry pins the plan node, so ids cannot be recycled while the
    collector lives. `link()` routes records made against a secondary
    key (a reader's CopPlan, executed storage-side) onto the owning
    node's OpStats. Device notes may arrive from cop pool workers, so
    those go through a lock; the host counters are only touched by the
    session thread that drives the executor tree."""

    def __init__(self, device: bool = False):
        self.device = device
        # guarded-by: _lock
        self._nodes: dict[int, tuple[object, OpStats]] = {}
        self._lock = threading.Lock()

    def node(self, plan, name: str | None = None) -> OpStats:
        ent = self._nodes.get(id(plan))
        if ent is not None:
            return ent[1]
        if name is None:
            name = type(plan).__name__.removeprefix("Phys")
        st = OpStats(name)
        with self._lock:
            self._nodes.setdefault(id(plan), (plan, st))
        return self._nodes[id(plan)][1]

    def link(self, alias_plan, stats: OpStats) -> None:
        """Route records against `alias_plan` onto `stats`."""
        with self._lock:
            self._nodes[id(alias_plan)] = (alias_plan, stats)

    def get(self, plan) -> OpStats | None:
        ent = self._nodes.get(id(plan))
        return ent[1] if ent is not None else None

    def note_device(self, plan, elapsed_ns: int) -> None:
        # NO watermark read here: the backend's peak-bytes gauge is
        # process-wide, so a concurrent statement's build would bleed
        # into this operator's mem — tracked bytes (memtrack) carry the
        # per-op attribution instead
        st = self.node(plan)
        with self._lock:
            st.device_time_ns += elapsed_ns

    def note_cop_tasks(self, plan, n: int) -> None:
        st = self.node(plan)
        with self._lock:
            st.cop_tasks += n

    def note_superchunk(self, plan, rows: int, bucket: int,
                        sources: int) -> None:
        """One coalesced device dispatch: `sources` chunks folded into
        `rows` live rows padded to a `bucket`-row shape. May arrive from
        cop pool workers, hence the lock."""
        st = self.node(plan)
        with self._lock:
            st.superchunks += 1
            st.coalesced_chunks += sources
            st.superchunk_fill_rows += rows
            st.superchunk_bucket_rows += bucket

    def note_pipeline_stall(self, plan, ns: int) -> None:
        st = self.node(plan)
        with self._lock:
            st.pipeline_stall_ns += ns

    def note_fallback(self, plan) -> "OpStats":
        """One device->host fallback on this operator (may arrive from
        cop pool workers, hence the lock). Returns the OpStats so the
        caller can label the metric with the operator name."""
        st = self.node(plan)
        with self._lock:
            st.fallbacks += 1
        return st

    def note_encoding(self, plan, mode: str) -> None:
        """Record the operator's encoded-execution mode (encoded /
        decoded / direct-agg / fused:<fragment>) for the EXPLAIN
        ANALYZE pipeline column. May arrive from cop pool workers."""
        st = self.node(plan)
        with self._lock:
            st.encoding = mode

    def note_kernel(self, plan, family: str, compile_src: str,
                    nbytes: int, busy_ns: int) -> None:
        """Fold one kernel dispatch's profile slice onto the operator
        (EXPLAIN ANALYZE `kernel` column + the slow log's roofline
        line). May arrive from cop pool workers, hence the lock."""
        st = self.node(plan)
        with self._lock:
            st.kernel_family = family
            if compile_src:
                st.kernel_compile = compile_src
            st.kernel_bytes += nbytes
            st.kernel_busy_ns += busy_ns
            st.kernel_dispatches += 1

    def note_mode(self, plan, mode: str) -> None:
        """Record the execution mode that actually ran (direct / hash /
        sort / fused / hybrid / host) — the perfschema mode-history
        memo's per-operator feed."""
        st = self.node(plan)
        with self._lock:
            st.mode = mode

    def ops(self) -> list[OpStats]:
        """Distinct OpStats (aliases deduped), insertion order."""
        sealed = getattr(self, "_sealed_ops", None)
        if sealed is not None:
            return list(sealed)
        seen: list[OpStats] = []
        for _plan, st in self._nodes.values():
            if all(st is not s for s in seen):
                seen.append(st)
        return seen

    def seal(self) -> None:
        """Drop the plan-object references once the statement is done:
        the collector outlives the statement on the session (the slow
        log and tests/test_runtime_stats.py read it), and it must not
        pin the executed plan tree. ops() keeps
        answering from the sealed snapshot."""
        ops = self.ops()
        with self._lock:
            self._sealed_ops = ops
            self._nodes = {}


@contextlib.contextmanager
def collecting(coll: StatsCollector | None):
    """Install `coll` as this thread's active collector. Passing the
    already-active collector (or None) nests transparently."""
    prev = getattr(_tl, "coll", None)
    _tl.coll = coll if coll is not None else prev
    try:
        yield _tl.coll
    finally:
        _tl.coll = prev


def current() -> StatsCollector | None:
    return getattr(_tl, "coll", None)


def note_superchunk(plan, rows: int, bucket: int, sources: int) -> None:
    """Record a coalesced dispatch against the active collector (no-op
    without one) — the call-site form for executors and the cop handler."""
    coll = getattr(_tl, "coll", None)
    if coll is not None:
        coll.note_superchunk(plan, rows, bucket, sources)


def note_pipeline_stall(plan, ns: int) -> None:
    coll = getattr(_tl, "coll", None)
    if coll is not None:
        coll.note_pipeline_stall(plan, ns)


def note_encoding(plan, mode: str) -> None:
    """Record the operator's encoded-execution mode against the active
    collector (no-op without one): EXPLAIN ANALYZE's enc= note."""
    coll = getattr(_tl, "coll", None)
    if coll is not None and plan is not None:
        coll.note_encoding(plan, mode)


def note_kernel(plan, family: str, compile_src: str, nbytes: int,
                busy_ns: int) -> None:
    """Record a kernel dispatch's profile slice against the active
    collector (no-op without one) — called from profiler.note_dispatch
    so every instrumented seam feeds both the process-wide registry row
    and the statement's per-operator view with one call."""
    coll = getattr(_tl, "coll", None)
    if coll is not None and plan is not None:
        coll.note_kernel(plan, family, compile_src, nbytes, busy_ns)


def note_mode(plan, mode: str) -> None:
    """Record the operator's actually-run execution mode against the
    active collector (no-op without one): the memo's vocabulary
    (direct | hash | sort | fused | hybrid | host)."""
    coll = getattr(_tl, "coll", None)
    if coll is not None and plan is not None:
        coll.note_mode(plan, mode)


def note_bytes_touched(decoded_equiv: int, encoded: int) -> None:
    """Account one device dispatch's input bytes on the two
    bytes-touched counter families: `encoded` is what the dispatch
    actually staged/read (dict codes + validity at the padded bucket),
    `decoded_equiv` is what the same input would occupy decoded into
    wide host vectors — their ratio is the compression win. Also the
    per-tenant bytes
    ledger's single chokepoint (meter.py)."""
    from tidb_tpu import meter, metrics
    metrics.counter(metrics.BYTES_DECODED_EQUIV, inc=decoded_equiv)
    metrics.counter(metrics.BYTES_ENCODED, inc=encoded)
    meter.note_bytes(encoded, decoded_equiv)


def note_fallback(plan, reason: str) -> None:
    """Record one device->host fallback: counted on the operator's
    OpStats (EXPLAIN ANALYZE `pipeline` column) and on the
    tidb_tpu_device_fallback_total{op,reason} metric family. `reason`
    is one of capacity|collision|unsupported|encoding (single-chip),
    mesh (a mesh stream batch served by the host), or the device-fault
    recovery pair fault|quarantine (tidb_tpu/sched.py DeviceHealth) —
    the designed fallback causes; anything else should RAISE, not
    fall back."""
    from tidb_tpu import metrics
    coll = getattr(_tl, "coll", None)
    name = None
    if coll is not None and plan is not None:
        name = coll.note_fallback(plan).name
    if name is None:
        name = type(plan).__name__.removeprefix("Phys") \
            if plan is not None else "?"
    metrics.counter(metrics.DEVICE_FALLBACKS,
                    {"op": name, "reason": reason})


def note_finalize_wait(plan, ns: int) -> None:
    """Blocked-readback time at a pipeline's output boundary: always
    recorded as pipeline stall; with the device-profiling sysvar on it
    doubles as the operator's device time (under dispatch overlap,
    per-launch timing is meaningless — the honest number is the wait at
    the boundary where the host actually needed the result)."""
    coll = getattr(_tl, "coll", None)
    if coll is None:
        return
    coll.note_pipeline_stall(plan, ns)
    if coll.device:
        coll.note_device(plan, ns)


@contextlib.contextmanager
def suspended():
    """Hide the active collector (internal bookkeeping sessions run
    inside a client statement but must not pollute its operator stats —
    the stats twin of trace.detach())."""
    prev = getattr(_tl, "coll", None)
    _tl.coll = None
    try:
        yield
    finally:
        _tl.coll = prev


# -- executor instrumentation (wired from build_executor) -------------------


def instrument(exe, plan) -> None:
    """Wrap the executor's production methods so each yielded batch
    records rows/loops/time into the active collector's node for `plan`.
    Also pre-registers the plan node (and its pushed CopPlans) with the
    active memory tracker, so storage-side allocations credit the
    issuing reader. No-op when neither is active (internal sessions,
    stats off)."""
    from tidb_tpu import memtrack
    mt = memtrack.current()
    if mt is not None:
        mnode = mt.node(plan)
        for attr in ("cop", "index_cop", "table_cop"):
            cop = getattr(plan, attr, None)
            if cop is not None:
                mt.link(cop, mnode)
    coll = current()
    if coll is None:
        return
    st = coll.node(plan)
    # storage-side execution of a reader's pushed subplan records against
    # the CopPlan object; route those onto the reader's stats
    for attr in ("cop", "index_cop", "table_cop"):
        cop = getattr(plan, attr, None)
        if cop is not None:
            coll.link(cop, st)

    if hasattr(exe, "chunks"):
        exe.chunks = _wrap_iter(exe.chunks, st)
    if hasattr(exe, "partials"):
        exe.partials = _wrap_iter(exe.partials, st)
    if hasattr(exe, "execute"):
        inner_exec = exe.execute

        def execute(ctx):
            t0 = time.perf_counter_ns()
            try:
                n = inner_exec(ctx)
            finally:
                st.time_ns += time.perf_counter_ns() - t0
            st.loops += 1
            if isinstance(n, int):
                st.act_rows += n
            return n

        exe.execute = execute


def _wrap_iter(fn, st: OpStats):
    def produce(ctx):
        it = fn(ctx)
        while True:
            t0 = time.perf_counter_ns()
            try:
                out = next(it)
            except StopIteration:
                st.time_ns += time.perf_counter_ns() - t0
                return
            st.time_ns += time.perf_counter_ns() - t0
            st.loops += 1
            n = getattr(out, "num_rows", None)
            if n is None:
                # agg-pushdown readers yield GroupResult partials: count
                # the groups they carry, not zero
                n = len(getattr(out, "keys", ()) or ())
            st.act_rows += n
            yield out

    return produce


# -- device timing (gated: block_until_ready serializes dispatch) -----------


def device_call(plan, fn, *args):
    """Run a device kernel call, attributing its completion time to
    `plan`'s stats when device timing is on. With the sysvar off (or no
    collector) this is one attribute read + one call — cheap enough for
    the hot loop."""
    coll = getattr(_tl, "coll", None)
    if coll is None or not coll.device:
        return fn(*args)
    t0 = time.perf_counter_ns()
    out = fn(*args)
    try:
        import jax
        jax.block_until_ready(out)
    except Exception:  # noqa: BLE001 - host results pass through
        pass
    coll.note_device(plan, time.perf_counter_ns() - t0)
    return out


@contextlib.contextmanager
def device_section(plan, errors: bool = True):
    """Time a whole device region (mesh pipelines overlap async launches,
    so per-launch timing is meaningless — the region's wall time, which
    ends on the blocking readback, is the honest number). With
    errors=False the section records only on SUCCESS — device_call's
    contract, for call sites whose failures retry through an escalated
    kernel (the failed attempt's time would double against the
    retry's)."""
    coll = getattr(_tl, "coll", None)
    if coll is None or not coll.device:
        yield
        return
    t0 = time.perf_counter_ns()
    try:
        yield
    except BaseException:
        if errors:
            coll.note_device(plan, time.perf_counter_ns() - t0)
        raise
    coll.note_device(plan, time.perf_counter_ns() - t0)


# -- rendering helpers ------------------------------------------------------


def fmt_ns(ns: int) -> str:
    if ns >= 1_000_000_000:
        return f"{ns / 1e9:.2f}s"
    if ns >= 1_000_000:
        return f"{ns / 1e6:.2f}ms"
    if ns >= 1_000:
        return f"{ns / 1e3:.1f}us"
    return f"{ns}ns"


def fmt_bytes(n: int) -> str:
    if n >= 1 << 30:
        return f"{n / (1 << 30):.2f}GB"
    if n >= 1 << 20:
        return f"{n / (1 << 20):.2f}MB"
    if n >= 1 << 10:
        return f"{n / (1 << 10):.1f}KB"
    return f"{n}B" if n else "0B"
