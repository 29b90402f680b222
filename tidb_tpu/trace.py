"""Statement tracing: lifecycle span trees, sampling, slow-trace
capture, and the device-timeline export.

Reference: the OpenTracing spans threaded through the reference stack —
dispatch (server/conn.go:559), session.Execute (session.go:692), Compile
(executor/compiler.go:34), runStmt (tidb.go:156), TSO wait
(session.go:1198-1206) — and the F1/Spanner practice of making the
per-request trace tree the primary tool for debugging a distributed SQL
path. Here spans are in-process structures: each non-internal statement
runs under a root span, every subsystem annotates itself via the
`span()` context manager (session phases, admission wait, scheduler
slot waits, per-superchunk dispatch/finalize, coprocessor pool and
stream workers, HBM fill/patch, delta fold/merge, hybrid-join partition
chains), and device-plane recovery transitions (fault retry, degrade,
quarantine, watchdog) land as point EVENTS on the span they interrupted.

Retention: every statement gets a tree (perfschema's phase breakdown
reads it), but only some trees are RETAINED into the bounded server
ring (`tidb_tpu/trace.py:_Ring`) that the `TRACE` statement,
`information_schema.statement_traces`, `GET /trace` and the Chrome
trace-event export serve:

  * 1-in-N deterministic sampling (`tidb_tpu_trace_sample`, always on);
  * threshold capture (`tidb_tpu_slow_trace_ms`: any statement over the
    threshold keeps its full tree — the slow log and the digest summary
    carry the trace id, so a digest hot spot links to a timeline);
  * the `TRACE <stmt>` statement forces retention.

The ring is billed to a `trace-ring` memtrack SERVER node with a
registered shed action, so admission shedding and `GET /shed` reclaim
retained trees like any other server-scope residency.

Two more readers of the same trees, neither needing retention: when a
root ends, `finish_statement` folds the tree's self time per span name
into counters (`self_times` -> metrics.span_totals:
tidb_tpu_span_self_seconds_total{span} / tidb_tpu_span_count_total
{span}), so a window of statements can be diffed whether sampled or
not; and while a `jax.profiler` session is live every span is also a
`jax.profiler.TraceAnnotation` carrying the root's trace id, so the
program's spans sit on the device trace's clock (decided once per root,
`_profiling`; nothing per span outside a session).

Cross-thread propagation follows the house pattern (the runtime_stats
collector and the memtrack tracker): the coprocessor fan-out captures
the dispatching span with `propagate()` and re-installs it inside every
pool/stream worker with `attached()`, so storage-side spans hang off
the reader that issued them. Span names at `trace.begin`/`trace.span`
call sites are literals declared in SPAN_NAMES (lint rule
`trace-names`), the same registry discipline metric names and
failpoints already follow."""

from __future__ import annotations

import contextlib
import itertools
import logging
import threading
import time

__all__ = ["Span", "SPAN_NAMES", "begin", "end", "span", "event",
           "annotate", "current_root", "active", "detach", "restore",
           "attached", "propagate", "attach_remote", "origin",
           "phase_ns", "log_tree", "ensure_id", "finish_statement",
           "tree", "validate", "self_times", "ring_snapshot",
           "ring_records", "ring_get", "to_chrome", "reset_for_tests"]

log = logging.getLogger("tidb_tpu.trace")

_tl = threading.local()

# declared span vocabulary: every trace.begin / trace.span call site in
# the package names one of these, as a string literal (lint rule
# trace-names — tidb_tpu/lint/rules/tracenames.py). One table so the
# docs (docs/OBSERVABILITY.md), the Chrome export and the span counters
# (tidb_tpu_span_*_total{span}, read by benchmark/metrics/) all read
# the same names.
SPAN_NAMES = {
    # statement lifecycle (session/__init__.py)
    "statement": "root of one non-internal statement execution",
    "parse": "this statement's share of the batch parse",
    "plan": "logical+physical planning (plan-cache miss)",
    "execute": "executor tree drive, operator output boundary to rows",
    "commit": "2PC commit incl. optimistic replay retries",
    "admission": "wait in the server admission controller",
    # device plane (sched.py, ops/runtime.py, store/copr.py)
    "sched.slot": "wait for a global device dispatch slot",
    "dispatch": "kernel dispatch: pad/transfer/async enqueue",
    "finalize": "blocking device readback at the output boundary",
    "host.fallback": "host-path aggregation of device-planned work",
    # coprocessor fan-out (store/copr.py)
    "copr.task": "one region task on a coprocessor pool worker",
    "copr.stream": "one streaming fan-out worker's frame production",
    # the scan's three steps, the same names on the streamed
    # (store/stream.py) and the materialized (store/copr.py) path; they
    # wrap calls (one KV batch, one frame or batch), never a row
    "copr.kv_scan": "one storage.engine.scan call (MVCC iteration)",
    "copr.decode": "raw KV rows -> decoded chunk (decode_cop_batch)",
    "copr.exec": "the pushed filter/projection/partial agg over a chunk",
    # storage-side caches and deltas (store/device_cache.py, delta.py)
    "hbm.fill": "HBM region-block cache upload",
    "hbm.patch": "in-place delta patch of a resident HBM block",
    "delta.fold": "base-chunk ⋈ delta-journal merge on the read path",
    "delta.merge": "delta-store merge into new base blocks",
    # hybrid join/agg partition phases (ops/hybrid.py)
    "join.partition": "one radix partition's device chain",
    # the root executors' own host work on the session thread
    # (executor/__init__.py:_OwnSpan): open while the operator's
    # generator body runs, closed while it waits for a child's chunk and
    # while its consumer holds one it yielded, so a reader's wait for
    # frames stays out of them; the device work they start keeps its own
    # child spans (sched.slot / dispatch / finalize / join.partition)
    "exec.join": "HashJoinExec: build concat + key encode, probe emit",
    "exec.apply": "ApplyExec: the subquery predicate over outer chunks",
    "exec.apply.inner": "ApplyExec: one run of the inner plan",
    "exec.agg": "FinalAggExec / HashAggExec: the host's merge of partials",
    "exec.topn": "TopNExec: sort + cut of each chunk against the best",
    # cross-process storage roots (store/remote.py)
    "storage:coprocessor_stream": "storage-side root of one COP stream",
    # cluster observability fan-out (util/statusclient.fetch_all): one
    # bounded-timeout sweep over live members' status ports serving a
    # cluster_* memtable or a /fleet/* endpoint
    "cluster.fetch": "fan-out fetch over live members' status ports",
}

# retention bounds of the server-scope trace ring: records and an
# estimated-bytes budget, billed to the trace-ring memtrack node
_RING_CAP = 256
_RING_BYTES_CAP = 16 << 20
_SPAN_EST_BYTES = 256          # rough per-span record cost estimate


class Span:
    # the last five slots are ROOT-ONLY state: retention (sampling
    # decided at begin(), TRACE forces, ids assigned on first need) and
    # the profiler-session decision (`live`, with the root's own
    # annotation in `ann`). begin() writes them; child spans leave them
    # unset — the hot constructor must not pay dead writes per span
    __slots__ = ("name", "tags", "start_ns", "end_ns", "children",
                 "events", "tid", "sampled", "forced", "trace_id",
                 "live", "ann")

    def __init__(self, name: str, tags: dict | None = None):
        self.name = name
        self.tags = tags or {}
        self.start_ns = time.perf_counter_ns()
        self.end_ns = 0
        self.children: list[Span] = []
        self.events: list | None = None   # (name, t_ns, tags), lazy
        self.tid = threading.get_ident()

    @property
    def duration_ns(self) -> int:
        return (self.end_ns or time.perf_counter_ns()) - self.start_ns

    def event(self, name: str, **tags) -> None:
        """Point event on THIS span (fault retries, degrade/quarantine
        transitions, watchdog fires — the PR-13 state machine on the
        statement timeline)."""
        ev = (name, time.perf_counter_ns(), tags or None)
        if self.events is None:
            self.events = [ev]
        else:
            self.events.append(ev)

    def to_dict(self) -> dict:
        d = {"name": self.name, "duration_ns": self.duration_ns}
        if self.tags:
            d["tags"] = dict(self.tags)
        if self.events:
            d["events"] = [{"name": n, "tags": t} if t else {"name": n}
                           for n, _t_ns, t in self.events]
        if self.children:
            d["children"] = [c.to_dict() for c in self.children]
        return d


def begin(name: str, **tags) -> Span:
    """Open a root span for the current thread's statement. Statement
    roots (`name == "statement"`) take the deterministic 1-in-N
    sampling decision here — `tidb_tpu_trace_sample` — so the whole
    tree below either records for retention or is a pure phase-
    breakdown skeleton."""
    root = Span(name, tags)
    root.sampled = _sample_next() if name == "statement" else False
    root.forced = False
    root.trace_id = None
    # one profiler-session check per root: the whole tree below either
    # mirrors itself onto the device trace's clock or pays nothing
    global _session
    live = root.live = _session = \
        _TraceMe.is_enabled() if _TraceMe is not None else _profiling()
    root.ann = _annotation(name, root) if live else None
    _tl.cur = root
    # the ROOT is tracked separately from the current span: origin()
    # must name the enclosing statement from arbitrarily deep inside
    # its tree (spans carry no parent pointers), and the store-RPC
    # client fires from exactly there
    _tl.root = root
    return root


def end(root: Span) -> Span:
    root.end_ns = time.perf_counter_ns()
    try:
        if root.ann is not None:
            root.ann.__exit__(None, None, None)
            root.ann = None
    except AttributeError:      # a hand-built Span, never begun
        pass
    if getattr(_tl, "cur", None) is root:
        _tl.cur = None
    if getattr(_tl, "root", None) is root:
        _tl.root = None
    return root


def current_root():
    return getattr(_tl, "cur", None)


def detach():
    """Suspend the thread's trace (internal bookkeeping sessions run
    inside a client statement but must not pollute its phase breakdown).
    -> opaque token for restore()."""
    token = (getattr(_tl, "cur", None), getattr(_tl, "root", None))
    _tl.cur = None
    _tl.root = None
    return token


def restore(token) -> None:
    _tl.cur, _tl.root = token


def propagate():
    """Opaque token naming the current span AND its statement root, for
    re-installation inside worker threads with `attached()` — the trace
    twin of runtime_stats.current() / memtrack.current() riding into
    the coprocessor fan-out. The root rides along so store RPCs issued
    from pool/stream workers still know which statement they originate
    from (origin())."""
    return (getattr(_tl, "cur", None), getattr(_tl, "root", None))


@contextlib.contextmanager
def attached(token):
    """Install a propagate() token (possibly None) as this thread's
    current span + root: spans the worker opens hang off the
    dispatching statement's tree. Child appends are GIL-atomic list
    ops, so concurrent workers may attach under one parent."""
    prev_cur = getattr(_tl, "cur", None)
    prev_root = getattr(_tl, "root", None)
    cur, root = token if token is not None else (None, None)
    _tl.cur = cur if cur is not None else prev_cur
    _tl.root = root if root is not None else prev_root
    try:
        yield
    finally:
        _tl.cur = prev_cur
        _tl.root = prev_root


class span:
    """Child span under the thread's current span; a no-op (still timed,
    but unattached) when no trace is active — internal sessions and
    worker threads pay one thread-local read. A plain slotted context
    manager, not @contextmanager: this sits on the per-statement and
    per-dispatch hot paths, and the generator machinery would double
    the disarmed cost (pinned <5us/statement by TestOverhead). The
    span opens in __init__ — legal because a `with` statement calls
    __enter__ immediately after evaluating the expression, with no
    user code in between; use only as `with trace.span(...)`."""

    __slots__ = ("_span", "_parent", "_ann")

    def __init__(self, name: str, **tags):
        parent = getattr(_tl, "cur", None)
        s = Span(name, tags)
        self._span = s
        self._parent = parent
        self._ann = None
        if parent is not None:
            parent.children.append(s)
            _tl.cur = s
            if _session or name in _RECHECK_SPANS:
                self._ann = _mirror(name)

    def __enter__(self) -> Span:
        return self._span

    def __exit__(self, exc_type, exc, tb):
        self._span.end_ns = time.perf_counter_ns()
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
        if self._parent is not None:
            _tl.cur = self._parent
        return False


# -- the same spans on the device trace's clock ------------------------------

# where a long statement re-takes the profiler-session decision: a
# streamed join runs for seconds and a session may open (or close) under
# it, so these coarse spans (a region task, a stream worker, a device
# slot wait, one decoded frame or scan batch — never a per-row span)
# refresh `root.live`. The frame is in the set because a join starts all
# its stream workers at once: on the chip a 15 s statement crossed no
# other boundary for its first 4 s (PERF.md §6, PR 24)
_RECHECK_SPANS = frozenset({"copr.task", "copr.stream", "sched.slot",
                            "copr.decode"})

_TraceMe = None
# what the last _profiling() call saw, process-wide: the one word a
# span reads outside a session (the decision itself rides on each root)
_session = False


def _profiling() -> bool:
    """True while a jax.profiler session is recording host events: the
    TraceMe recorder's own flag, one C call (~30ns)."""
    global _TraceMe, _session
    if _TraceMe is None:
        from jax.profiler import TraceAnnotation
        _TraceMe = TraceAnnotation
    _session = _TraceMe.is_enabled()
    return _session


def _mirror(name: str):
    """span()'s slow path: the annotation for a span of the thread's
    statement when its root is live, re-taking the root's decision at a
    coarse boundary. None where there is nothing to mirror."""
    root = getattr(_tl, "root", None)
    if root is None:
        return None
    if name in _RECHECK_SPANS:
        root.live = _profiling()
    return _annotation(name, root) if root.live else None


def _annotation(name: str, root: Span):
    """An entered jax.profiler.TraceAnnotation named like the span, on
    the calling thread, carrying the statement's trace id: the span as
    the device trace sees it. The caller exits it where the span ends
    (same thread: a span never migrates)."""
    ann = _TraceMe(name, trace_id=ensure_id(root))
    ann.__enter__()
    return ann


def active() -> bool:
    """True when the calling thread is inside a traced statement."""
    return getattr(_tl, "cur", None) is not None


def annotate(**tags) -> None:
    """Merge tags into the thread's CURRENT span without opening a child
    — safe from inside generators (a `with span(...)` wrapped around a
    `yield` would interleave restores with the consumer's own spans).
    Used by the streaming coprocessor to stamp per-stream frame/byte/
    stall counts onto the dispatching span. No-op untraced."""
    cur = getattr(_tl, "cur", None)
    if cur is not None:
        cur.tags.update(tags)


def event(name: str, **tags) -> None:
    """Point event on the thread's current span (no-op untraced): the
    call-site form for the device-plane recovery transitions."""
    cur = getattr(_tl, "cur", None)
    if cur is not None:
        cur.event(name, **tags)


def origin() -> dict | None:
    """Forward propagation context of the statement enclosing this
    thread: the fleet-unique trace id of its ROOT plus the retention
    flags, shipped inside traced store RPCs (store/remote.py request
    flags) so anything the store plane retains on its own — slow
    handler roots, forced traces — carries the originating statement's
    id and member instead of being unjoinable. None when untraced."""
    root = getattr(_tl, "root", None)
    if root is None:
        return None
    return {"trace_id": ensure_id(root),
            "sampled": bool(root.sampled),
            "forced": bool(root.forced),
            "member": _member().member_id()}


def attach_remote(d: dict) -> None:
    """Graft a span tree returned by another PROCESS (the storage node's
    side of an RPC — store/remote.py) under the current span. Remote
    clocks don't align, so only names/tags/durations carry over; the
    child is pinned at the current moment with its reported duration.
    Ref: the reference's cross-process span propagation
    (session.go:692 opentracing context over gRPC)."""
    parent = getattr(_tl, "cur", None)
    if parent is None:
        return

    def build(node: dict) -> Span:
        s = Span(node.get("name", "remote"), node.get("tags"))
        dur = int(node.get("duration_ns", 0))
        # end at "now" (the Span's birth instant), duration preserved
        s.end_ns, s.start_ns = s.start_ns, s.start_ns - dur
        for c in node.get("children", ()):
            s.children.append(build(c))
        return s

    parent.children.append(build(d))


def phase_ns(root: Span | None, name: str) -> int:
    """Sum of top-level child spans with `name` (a statement's parse /
    plan / execute / commit phase totals)."""
    if root is None:
        return 0
    return sum(c.duration_ns for c in root.children if c.name == name)


def log_tree(root: Span, sql: str) -> None:
    parts: list[str] = []

    def walk(s: Span, depth: int) -> None:
        parts.append("%s%s %.3fms %s" % (
            "  " * depth, s.name, s.duration_ns / 1e6,
            s.tags if s.tags else ""))
        for c in s.children:
            walk(c, depth + 1)

    walk(root, 0)
    log.info("trace for %r:\n%s", sql[:256], "\n".join(parts))


# -- sampling ----------------------------------------------------------------

_seq_lock = threading.Lock()
# statements since process start (or reset): itertools.count's next()
# is one atomic C call, so the per-statement sampling decision takes no
# lock
_stmt_seq = itertools.count(1)
_id_seq = 0

# lazy config binding: trace.py keeps zero package imports at module
# level (it loads before most of the package), and a per-statement
# `from tidb_tpu import config` would dominate the disarmed cost. The
# two sysvars a statement reads (sampling at begin, the slow threshold
# at finish) go through config's own overlay-aware reader directly:
# the named accessors' two extra calls were a fifth of the disarmed
# cost, which the statement-end fold now needs (TestOverhead)
_sysvar = None


def _bind_sysvar():
    global _sysvar
    if _sysvar is None:
        from tidb_tpu.config import _read
        _sysvar = _read
    return _sysvar


_member_mod = None


def _member():
    global _member_mod
    if _member_mod is None:
        from tidb_tpu import member
        _member_mod = member
    return _member_mod


_metrics_mod = None


def _metrics():
    global _metrics_mod
    if _metrics_mod is None:
        from tidb_tpu import metrics
        _metrics_mod = metrics
    return _metrics_mod


def _sample_next() -> bool:
    """Deterministic 1-in-N: the N-th, 2N-th, ... statement since
    process start (or reset) is sampled. One atomic counter step per
    statement — with the profiler-session check and the statement-end
    fold of the span tree into the self-time counters, the whole
    disarmed cost besides the skeleton spans the phase breakdown needs
    anyway."""
    n = (_sysvar or _bind_sysvar())("tidb_tpu_trace_sample")
    if n <= 0:
        return False
    return next(_stmt_seq) % n == 0


def ensure_id(root: Span) -> int:
    """The root's FLEET-UNIQUE trace id, assigned on first need (the
    TRACE statement reads it before retention runs). The process's
    32-bit member start nonce (member.py) occupies the high bits over
    a 24-bit per-process sequence: two members minting concurrently
    never collide, a restarted member never reuses its predecessor's
    id space, and ids stay monotonic within one process — min_id
    filtering (ring_records) keeps working."""
    if root.trace_id is None:
        global _id_seq
        with _seq_lock:
            _id_seq += 1
            seq = _id_seq
        root.trace_id = (_member().nonce() << 24) | (seq & 0xFFFFFF)
    return root.trace_id


# -- the bounded, memtrack-billed trace ring ---------------------------------


class _Ring:
    """Finished trace records, newest last, bounded by count AND an
    estimated-bytes budget billed to a `trace-ring` memtrack SERVER
    node. The registered shed action clears the ring, so admission
    shedding / GET /shed reclaim retained trees."""

    def __init__(self):
        self._mu = threading.Lock()
        self._records: list[dict] = []    # guarded-by: _mu
        self._bytes = 0                   # guarded-by: _mu
        self._node = None                 # guarded-by: _mu (memtrack)

    def _tracker(self):
        """Lazy node creation (imports memtrack on first retention)."""
        from tidb_tpu import memtrack
        with self._mu:
            if self._node is None:
                self._node = memtrack.server_node("trace-ring")
                self._node.add_spill_action(self.shed)
            return self._node

    def append(self, rec: dict) -> None:
        node = self._tracker()
        # lint: exempt[paired-resource] ownership transfer: ring bytes release on evict (below) / shed / reset
        node.consume(host=rec["cost"])
        evicted = 0
        with self._mu:
            self._records.append(rec)
            self._bytes += rec["cost"]
            while len(self._records) > _RING_CAP or \
                    self._bytes > _RING_BYTES_CAP:
                old = self._records.pop(0)
                self._bytes -= old["cost"]
                evicted += old["cost"]
        if evicted:
            node.release(host=evicted)

    def shed(self) -> int:
        """Drop every retained record (the memtrack shed action).
        -> bytes freed."""
        with self._mu:
            freed = self._bytes
            self._records.clear()
            self._bytes = 0
            node = self._node
        if node is not None and freed:
            node.release(host=freed)
        return freed

    def get(self, trace_id: int) -> dict | None:
        with self._mu:
            for rec in self._records:
                if rec["trace_id"] == trace_id:
                    return rec
        return None

    def records(self, min_id: int = 0) -> list[dict]:
        with self._mu:
            return [r for r in self._records if r["trace_id"] > min_id]

    def snapshot(self) -> dict:
        with self._mu:
            return {"records": len(self._records), "bytes": self._bytes}


_RING = _Ring()


def _span_count(root: Span) -> int:
    n = 1
    for c in root.children:
        n += _span_count(c)
    return n


def finish_statement(root: Span, sql: str, error: str | None = None,
                     slow_ms: int | None = None,
                     origin: dict | None = None) -> int | None:
    """Retention decision for one ENDED statement root: keep the full
    tree in the ring when the statement was sampled, forced (TRACE), or
    ran past `tidb_tpu_slow_trace_ms`. -> trace id when retained, else
    None. The untraced path is one flag test + one sysvar read.
    `slow_ms` overrides the registry read — the session passes its
    shadowed (session-SET) value, captured while its overlay was still
    installed. `origin` is the forward-propagated context of a
    CROSS-PROCESS caller (trace.origin() shipped in store-RPC flags):
    the record's origin_trace_id/origin_member then name the SQL
    statement that caused this store-plane root, instead of defaulting
    to the local identity — the join key cluster_statement_traces and
    /fleet/trace search on."""
    # self time per span name, once a statement (never in
    # span.__exit__): what the window's counters are diffed over
    dur_ns = root.duration_ns
    if root.children:
        (_metrics_mod or _metrics()).span_totals(self_times(root))
    else:
        (_metrics_mod or _metrics()).span_one(root.name, dur_ns)
    if root.forced:
        reason = "forced"
    elif root.sampled:
        reason = "sampled"
    else:
        if slow_ms is None:
            slow_ms = (_sysvar or _bind_sysvar())(
                "tidb_tpu_slow_trace_ms")
        if slow_ms <= 0 or dur_ns < slow_ms * 1_000_000:
            return None
        reason = "slow"
    from tidb_tpu import metrics, perfschema
    tid = ensure_id(root)
    rec = {
        "trace_id": tid,
        "sql": sql[:512],
        "digest": perfschema.sql_digest(sql)[0],
        "start_unix": time.time() - dur_ns / 1e9,
        "duration_ns": dur_ns,
        "reason": reason,
        "error": error and error[:256],
        "span_count": _span_count(root),
        "origin_trace_id": int(origin["trace_id"]) if origin else tid,
        "origin_member": (origin.get("member") or "") if origin
        else _member().member_id(),
        "root": root,
    }
    rec["cost"] = rec["span_count"] * _SPAN_EST_BYTES + len(rec["sql"])
    _RING.append(rec)
    metrics.counter(metrics.TRACES, {"reason": reason})
    return tid


def ring_snapshot() -> list[dict]:
    """Summaries of retained traces, oldest first (the
    information_schema.statement_traces rows and GET /trace list)."""
    out = []
    for rec in _RING.records():
        out.append({k: rec[k] for k in
                    ("trace_id", "digest", "sql", "start_unix",
                     "duration_ns", "span_count", "reason", "error",
                     "origin_trace_id", "origin_member")})
    return out


def ring_records(min_id: int = 0) -> list[dict]:
    """Full retained records, trees included (the tests walk them)."""
    return _RING.records(min_id)


def ring_get(trace_id: int) -> dict | None:
    return _RING.get(trace_id)


def ring_stats() -> dict:
    return _RING.snapshot()


def reset_for_tests() -> None:
    """Clear the ring and the sampling counters (test isolation)."""
    global _stmt_seq, _id_seq
    _RING.shed()
    with _seq_lock:
        _stmt_seq = itertools.count(1)
        _id_seq = 0


# -- exports -----------------------------------------------------------------


def tree(root: Span, base_ns: int | None = None) -> dict:
    """Nested export of one span tree with start offsets: start_us is
    relative to the ROOT's start, so the JSON is self-contained and a
    still-open span (the TRACE statement snapshots its own live root)
    reads as closed at "now"."""
    base = root.start_ns if base_ns is None else base_ns

    def walk(s: Span) -> dict:
        d = {"name": s.name,
             "start_us": round((s.start_ns - base) / 1e3, 3),
             "duration_us": round(s.duration_ns / 1e3, 3)}
        if s.tags:
            d["tags"] = {k: v for k, v in s.tags.items()}
        if s.events:
            d["events"] = [
                {"name": n, "at_us": round((t - base) / 1e3, 3),
                 **({"tags": tg} if tg else {})}
                for n, t, tg in s.events]
        if s.children:
            d["children"] = [walk(c) for c in s.children]
        return d

    return walk(root)


def validate(root: Span) -> list[str]:
    """Structural problems of a FINISHED tree: begin-without-end spans
    and negative durations (the balance check tests/test_trace.py and
    tests/test_span_counters.py assert empty)."""
    problems: list[str] = []

    def walk(s: Span) -> None:
        if not s.end_ns:
            problems.append(f"span {s.name!r} has no end (begin "
                            f"without end)")
        elif s.end_ns < s.start_ns:
            problems.append(f"span {s.name!r} ends before it starts")
        for c in s.children:
            walk(c)

    walk(root)
    return problems


def self_times(root: Span) -> dict:
    """name -> [self nanoseconds, spans] over one span tree, the root
    included. A span's self time is its duration minus what its
    children ON THE SAME THREAD cover: a child on a pool or stream
    worker runs beside its parent and keeps its own time, so the sums
    are thread-seconds and no interval is counted twice on one thread.
    A span still open reads as closed now. Same-thread children are
    sequential by construction; grafted remote trees (attach_remote)
    may overlap, so each child only covers what the one before did not,
    clipped to the parent. Runs once a statement (finish_statement), so
    leaves are folded where their parent meets them, without a visit of
    their own."""
    now = 0
    end = root.end_ns
    if not end:
        end = now = time.perf_counter_ns()
    elif not root.children:
        return {root.name: [end - root.start_ns, 1]}    # no walk to make
    out: dict = {}
    stack = [(root, end)]
    while stack:
        s, end = stack.pop()
        at = s.start_ns
        self_ns = end - at
        tid = s.tid
        for c in s.children:
            c_end = c.end_ns
            if not c_end:
                c_end = now = now or time.perf_counter_ns()
            if c.tid == tid:
                lo = c.start_ns if c.start_ns > at else at
                hi = c_end if c_end < end else end
                if hi > lo:
                    self_ns -= hi - lo
                    at = hi
            if c.children:
                stack.append((c, c_end))
                continue
            c_ns = c_end - c.start_ns
            acc = out.get(c.name)
            if acc is None:
                out[c.name] = [c_ns if c_ns > 0 else 0, 1]
            else:
                if c_ns > 0:
                    acc[0] += c_ns
                acc[1] += 1
        acc = out.get(s.name)
        if acc is None:
            out[s.name] = [self_ns if self_ns > 0 else 0, 1]
        else:
            if self_ns > 0:
                acc[0] += self_ns
            acc[1] += 1
    return out


def to_chrome(rec: dict) -> dict:
    """Chrome trace-event JSON for one retained record: complete ("X")
    events per span in µs relative to the root, instant ("i") events
    for the recovery transitions, one lane per OS thread — load it in
    Perfetto / chrome://tracing to SEE dispatch-ahead depth, slot waits
    and finalize serialization across the statement's threads."""
    root: Span = rec["root"]
    base = root.start_ns
    events = [{"ph": "M", "pid": 1, "tid": 0, "name": "process_name",
               "args": {"name": f"tidb-tpu trace {rec['trace_id']}"}}]

    def walk(s: Span) -> None:
        ev = {"ph": "X", "pid": 1, "tid": s.tid, "name": s.name,
              "cat": "statement",
              "ts": round((s.start_ns - base) / 1e3, 3),
              "dur": round(s.duration_ns / 1e3, 3)}
        if s.tags:
            ev["args"] = {k: str(v) for k, v in s.tags.items()}
        events.append(ev)
        for n, t, tg in s.events or ():
            ie = {"ph": "i", "pid": 1, "tid": s.tid, "name": n,
                  "cat": "fault", "s": "t",
                  "ts": round((t - base) / 1e3, 3)}
            if tg:
                ie["args"] = {k: str(v) for k, v in tg.items()}
            events.append(ie)
        for c in s.children:
            walk(c)

    walk(root)
    return {"traceEvents": events, "displayTimeUnit": "ms",
            "otherData": {"trace_id": rec["trace_id"],
                          "sql": rec["sql"],
                          "digest": rec["digest"],
                          "reason": rec["reason"]}}
