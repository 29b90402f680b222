"""Hierarchical per-query memory tracking: the util/memory.Tracker analogue.

Reference: the reference's util/memory — every byte a statement holds is
attributed to a tree of Trackers rooted at the session, `mem-quota-query`
bounds the per-statement total, and OOM actions (spill, then cancel) fire
when the root crosses it.

Here every tracker keeps TWO ledgers — host bytes (chunk buffers, hash
builds, agg state, sort runs, superchunk staging) and device bytes
(padded superchunk uploads, donated kernel buffers, device-resident join
builds) — because on a TPU serving stack HBM is the scarcer resource and
the two must not launder into one number. Consumption rolls up the
parent chain:

    operator node  ->  statement root  ->  session root  ->  SERVER

The statement root carries the `tidb_tpu_mem_quota_query` quota and the
ordered OOM-action chain: spill actions registered by operators that can
shed memory (executor/extsort.SpillSorter) fire first; when none remain
(or none helped) the query cancels — `on_cancel` flips the session's
cooperative-kill flag so concurrent coprocessor workers stop too, and
QuotaExceededError surfaces as ER_MEM_EXCEED_QUOTA.

Lock discipline: consume/release take one per-node lock at a time while
walking up (never nested), and OOM actions fire AFTER every lock is
dropped, so a spill action may itself consume/release re-entrantly.
Cost is a few lock/unlock pairs per *batch* (not per row) — noise next
to the 64k-row chunk work it accounts.

The thread-local `tracking()` context installs a statement root exactly
like runtime_stats.collecting installs the stats collector; the
coprocessor fan-out re-installs it inside pool workers so storage-side
allocations credit the issuing reader.
"""

from __future__ import annotations

import contextlib
import threading

from tidb_tpu import metrics

__all__ = ["MemTracker", "QuotaExceededError", "SERVER", "tracking",
           "suspended", "current", "session_root", "statement_root",
           "server_node", "op_node", "consume", "release", "device_scope",
           "track_to", "register_spill",
           "chunk_bytes", "result_bytes", "device_put_bytes",
           "sessions_snapshot"]


class QuotaExceededError(Exception):
    """Statement memory over tidb_tpu_mem_quota_query with no spill
    action left — surfaced to clients as ER_MEM_EXCEED_QUOTA."""


class MemTracker:
    """One node of the tracking tree. host/device are the two ledgers;
    peaks are monotone high-water marks. quota (statement roots only,
    0 = unlimited) bounds host+device."""

    __slots__ = ("label", "parent", "quota", "on_cancel", "_mu",
                 "host", "device", "host_peak", "device_peak",
                 "_actions", "_firing", "_cancel_msg", "_nodes",
                 "children", "fault_degraded")

    def __init__(self, label: str, parent: "MemTracker | None" = None,
                 quota: int = 0, on_cancel=None):
        self.label = label
        self.parent = parent            # guarded-by: _mu
        self.quota = quota
        self.on_cancel = on_cancel
        self._mu = threading.Lock()
        self.host = 0                   # guarded-by: _mu
        self.device = 0                 # guarded-by: _mu
        self.host_peak = 0              # guarded-by: _mu
        self.device_peak = 0            # guarded-by: _mu
        self._actions: list = []        # guarded-by: _mu  (OOM spills)
        self._firing = False            # guarded-by: _mu
        self._cancel_msg: str | None = None   # guarded-by: _mu
        # statement roots only: sched.degrade_statement latched this
        # statement onto the host path after a retried device fault
        self.fault_degraded = False
        # id(plan) -> (plan, tracker)
        self._nodes: dict[int, tuple] = {}    # guarded-by: _mu
        self.children: dict[int, "MemTracker"] = {}   # guarded-by: _mu

    # -- the two ledgers -----------------------------------------------------

    def consume(self, host: int = 0, device: int = 0) -> None:
        """Charge bytes to this node and every ancestor; fires the
        OOM-action chain of the nearest quota-carrying ancestor AFTER all
        locks are released (actions may consume/release re-entrantly).

        The next-parent pointer is read UNDER the node's lock: detach()
        snapshots the counters and severs the parent link in one locked
        region, so a walker that charged a node before the detach also
        reaches the old parent (whose release then cancels out), and one
        that charged after stops at the severed link — either way the
        ancestor ledgers stay exact under races with straggling
        coprocessor workers."""
        node = self
        fire = None
        while node is not None:
            with node._mu:
                node.host += host
                node.device += device
                if node.host > node.host_peak:
                    node.host_peak = node.host
                if node.device > node.device_peak:
                    node.device_peak = node.device
                if fire is None and node.quota and \
                        node.host + node.device > node.quota:
                    fire = node
                nxt = node.parent
            node = nxt
        if fire is not None:
            fire._over_quota()

    def release(self, host: int = 0, device: int = 0) -> None:
        node = self
        while node is not None:
            with node._mu:
                node.host -= host
                node.device -= device
                nxt = node.parent
            node = nxt

    def total(self) -> int:
        return self.host + self.device

    def peak_total(self) -> int:
        return self.host_peak + self.device_peak

    # -- OOM action chain ----------------------------------------------------

    def add_spill_action(self, fn) -> None:
        """Register a memory-shedding callback (fires in quota order,
        re-armed: a spiller that frees bytes may fire again on a later
        episode). The callback must be safe to invoke from ANY thread
        that consumes into this tree."""
        with self._mu:
            self._actions.append(fn)

    def remove_spill_action(self, fn) -> None:
        with self._mu:
            try:
                self._actions.remove(fn)
            except ValueError:
                pass

    def _over_quota(self) -> None:
        with self._mu:
            if self._cancel_msg is not None:
                # cancel already latched: stragglers (cop workers still
                # draining) re-raise WITHOUT re-counting the event or
                # re-running the spill chain — one cancelled statement is
                # one cancel, however many threads hit the wall
                msg = self._cancel_msg
            elif self._firing:     # an action on another frame is already
                return             # shedding; let it finish
            else:
                msg = None
                self._firing = True
                actions = list(self._actions)
        if msg is not None:
            raise QuotaExceededError(msg)
        try:
            for act in actions:
                with self._mu:
                    before = self.host + self.device
                    if before <= self.quota:
                        return
                try:
                    act()
                except Exception:  # noqa: BLE001 - a broken spiller must
                    pass           # not mask the cancel below
                with self._mu:
                    freed = before - (self.host + self.device)
                if freed > 0:
                    # count only spills that actually shed bytes: an
                    # already-drained sorter invoked in vain is not an
                    # OOM-action event
                    metrics.counter(metrics.MEM_QUOTA_EXCEEDED,
                                    {"action": "spill"})
            with self._mu:
                total = self.host + self.device
                if total <= self.quota:
                    return
                msg = (f"Out Of Memory Quota! query tracked {total} "
                       f"bytes > tidb_tpu_mem_quota_query {self.quota}")
                self._cancel_msg = msg
            metrics.counter(metrics.MEM_QUOTA_EXCEEDED,
                            {"action": "cancel"})
            if self.on_cancel is not None:
                # on_cancel(msg) runs BEFORE the raise so the session can
                # remember why it was killed: when this fires on a pool
                # worker, the session thread usually trips the
                # cooperative-kill check before the worker's exception
                # drains, and must still surface the quota error
                try:
                    self.on_cancel(msg)
                except Exception:  # noqa: BLE001
                    pass
            raise QuotaExceededError(msg)
        finally:
            with self._mu:
                self._firing = False

    def cancel(self, msg: str) -> bool:
        """Latch a statement cancel from OUTSIDE the quota chain — the
        dispatch watchdog's door (tidb_tpu/sched.py): the message
        latches exactly like a quota cancel (stragglers that later trip
        the quota re-raise it, never re-count), and the on_cancel hook
        fires so the session's cooperative-kill flag flips. Unlike
        _over_quota this never raises — the caller is a monitor thread,
        not the consuming thread. -> False when a cancel was already
        latched."""
        with self._mu:
            if self._cancel_msg is not None:
                return False
            self._cancel_msg = msg
        if self.on_cancel is not None:
            try:
                self.on_cancel(msg)
            except Exception:  # noqa: BLE001 - monitor must survive
                pass
        return True

    def run_spill_actions(self, target: int = 0,
                          recurse: bool = False) -> int:
        """Administratively drive registered spill actions until this
        node's total() is at/below `target` bytes; -> bytes freed.
        Unlike the quota chain (_over_quota) this NEVER cancels and
        needs no quota armed — it is the door the admission controller
        and the status port's /shed hook use to fire the shed chain the
        HBM cache (and, with recurse=True, running statements' spill
        actions: hybrid-join cold partitions, sort buffers) registered.
        Actions fire with every tracker lock dropped, exactly like the
        quota chain, so they may consume/release re-entrantly."""
        with self._mu:
            before = self.host + self.device
        if before <= target:
            return 0
        actions: list = []
        nodes = [self]
        seen: set[int] = set()
        while nodes:
            node = nodes.pop()
            if id(node) in seen:
                continue
            seen.add(id(node))
            with node._mu:
                actions.extend(node._actions)
                if recurse:
                    nodes.extend(node.children.values())
        for act in actions:
            with self._mu:
                cur = self.host + self.device
            if cur <= target:
                break
            try:
                act()
            except Exception:  # noqa: BLE001 - one broken spiller must
                pass           # not stop the rest of the chain
        with self._mu:
            after = self.host + self.device
        return max(before - after, 0)

    # -- per-plan-node children (statement roots) ----------------------------

    def node(self, plan, name: str | None = None) -> "MemTracker":
        """Child tracker for one plan node; the entry pins the plan so
        ids cannot recycle while this root lives (cleared on detach)."""
        with self._mu:
            ent = self._nodes.get(id(plan))
        if ent is not None:
            return ent[1]
        if name is None:
            name = type(plan).__name__.removeprefix("Phys")
        child = MemTracker(name, parent=self)
        with self._mu:
            ent = self._nodes.setdefault(id(plan), (plan, child))
        return ent[1]

    def link(self, alias_plan, node: "MemTracker") -> None:
        """Route charges made against `alias_plan` (a reader's CopPlan,
        executed storage-side) onto the owning node's tracker."""
        with self._mu:
            self._nodes[id(alias_plan)] = (alias_plan, node)

    def get(self, plan) -> "MemTracker | None":
        with self._mu:
            ent = self._nodes.get(id(plan))
        return ent[1] if ent is not None else None

    # -- lifecycle -----------------------------------------------------------

    def detach(self) -> None:
        """Unhook from the parent, crediting back everything still held:
        release-on-close is what leaves the session root at zero after
        each statement even when an abandoned generator never ran its
        finally. Peaks (and residual current counters) survive for
        post-mortem readers (EXPLAIN ANALYZE, slow log)."""
        with self._mu:
            p = self.parent
            if p is None:
                return
            # counters snapshot + parent sever in ONE locked region:
            # see consume() for why this keeps ancestor ledgers exact
            # under racing walkers
            h, d = self.host, self.device
            self.parent = None
            self._nodes = {}       # drop plan pins
            self._actions = []
        with p._mu:
            p.children.pop(id(self), None)
        if h or d:
            p.release(host=h, device=d)

    def snapshot(self) -> dict:
        with self._mu:
            return {"label": self.label, "host": self.host,
                    "device": self.device, "host_peak": self.host_peak,
                    "device_peak": self.device_peak}


# process root: every session tracker hangs off it, so its ledgers are
# the server totals information_schema.memory_usage reports
SERVER = MemTracker("server")


def session_root(session_id: int) -> MemTracker:
    t = MemTracker(f"session-{session_id}", parent=SERVER)
    with SERVER._mu:
        SERVER.children[id(t)] = t
    return t


def server_node(label: str) -> MemTracker:
    """A long-lived server-scope tracker (shared caches, pools): a child
    of SERVER whose ledgers roll up into the server totals that
    information_schema.memory_usage reports, without belonging to any
    session or statement. The HBM region-block cache charges its
    resident bytes here (store/device_cache.py) — budget enforcement is
    the cache's LRU, visibility is this ledger. The MVCC delta store
    bills its staged commit journal to a sibling `delta-store` node
    (store/delta.py), with a registered spill action that forces an
    early merge — so /shed and admission-driven shedding reclaim
    staged delta bytes like any other server-scope residency."""
    t = MemTracker(label, parent=SERVER)
    with SERVER._mu:
        SERVER.children[id(t)] = t
    return t


def statement_root(parent: MemTracker | None, quota: int = 0,
                   on_cancel=None, label: str = "stmt") -> MemTracker:
    t = MemTracker(label, parent=parent, quota=quota, on_cancel=on_cancel)
    if parent is not None:
        with parent._mu:
            parent.children[id(t)] = t
    return t


def sessions_snapshot() -> list[dict]:
    """Per-session tracker snapshots, session creation order."""
    with SERVER._mu:
        kids = list(SERVER.children.values())
    return [t.snapshot() for t in kids]


# -- thread-local installation (mirrors runtime_stats.collecting) -----------

_tl = threading.local()


@contextlib.contextmanager
def tracking(root: MemTracker | None):
    """Install `root` as this thread's active statement tracker. Passing
    None nests transparently (keeps the outer tracker)."""
    prev = getattr(_tl, "root", None)
    _tl.root = root if root is not None else prev
    try:
        yield _tl.root
    finally:
        _tl.root = prev


@contextlib.contextmanager
def suspended():
    """Hide the active tracker (internal bookkeeping sessions run inside
    a client statement but must not bill it — the memory twin of
    runtime_stats.suspended)."""
    prev = getattr(_tl, "root", None)
    _tl.root = None
    try:
        yield
    finally:
        _tl.root = prev


def current() -> MemTracker | None:
    return getattr(_tl, "root", None)


def op_node(plan) -> MemTracker | None:
    """The active statement's tracker node for `plan` (None when no
    tracker is installed — internal sessions, library use)."""
    root = getattr(_tl, "root", None)
    if root is None:
        return None
    return root.node(plan)


def consume(plan, host: int = 0, device: int = 0) -> None:
    """Charge bytes against the active statement's node for `plan`
    (no-op without a tracker) — the call-site form for executors and the
    coprocessor handler."""
    root = getattr(_tl, "root", None)
    if root is not None and (host or device):
        root.node(plan).consume(host=host, device=device)


def release(plan, host: int = 0, device: int = 0) -> None:
    root = getattr(_tl, "root", None)
    if root is not None and (host or device):
        root.node(plan).release(host=host, device=device)


@contextlib.contextmanager
def device_scope(plan, nbytes: int):
    """Hold `nbytes` on `plan`'s device ledger for the duration of a
    synchronous kernel call — the leak-proof form of the
    consume/try/finally-release pattern at dispatch sites. Split
    dispatch/finalize pairs (pipelines) still pair the calls manually
    because the release happens in a different closure."""
    consume(plan, device=nbytes)
    try:
        yield
    finally:
        release(plan, device=nbytes)


def track_to(plan, nbytes: int, prev: int = 0, kind: str = "host") -> int:
    """Move `plan`'s tracked bytes (one ledger) to an absolute value:
    the pattern for accumulators that grow or shrink (hash builds, TopN
    windows, agg state). Returns nbytes for the caller to carry."""
    delta = nbytes - prev
    if delta > 0:
        consume(plan, **{kind: delta})
    elif delta < 0:
        release(plan, **{kind: -delta})
    return nbytes


def register_spill(fn):
    """Hook a spill action onto the active statement root; returns an
    unregister callable (a no-op pair when no tracker is active)."""
    root = getattr(_tl, "root", None)
    if root is None:
        return lambda: None
    root.add_spill_action(fn)
    return lambda: root.remove_spill_action(fn)


# -- size estimators --------------------------------------------------------


def chunk_bytes(chunk) -> int:
    """Host footprint of a chunk: numpy buffers at their real size,
    object (string) columns at pointer + payload length. Memoized on
    the (immutable) chunk — string columns make this an O(rows) scan,
    and hot cached chunks are re-sized on every dispatch."""
    hit = getattr(chunk, "_bytes_memo", None)
    if hit is not None:
        return hit
    total = 0
    for c in chunk.columns:
        data = c.data
        if getattr(data, "dtype", None) is not None and \
                data.dtype != object:
            total += data.nbytes
        else:
            total += 8 * len(data)
            total += sum(len(x) for x in data
                         if isinstance(x, (str, bytes)))
        total += len(c.valid)          # bool mask
    try:
        chunk._bytes_memo = total
    except AttributeError:
        pass        # duck-typed chunk without the memo slot
    return total


def result_bytes(res) -> int:
    """Host footprint of a coprocessor response payload: a decoded
    Chunk (chunk_bytes), or an agg partial shaped like
    ops.hashagg.GroupResult (keys / per-agg lane arrays / counts).
    Anything else — scalar partials are a handful of lanes — rounds to
    its lane arrays alone."""
    if getattr(res, "columns", None) is not None:
        return chunk_bytes(res)
    total = 0
    for lanes in getattr(res, "partials", None) or []:
        for arr in lanes:
            nb = getattr(arr, "nbytes", None)
            total += nb if nb is not None else 8 * len(arr)
    counts = getattr(res, "counts", None)
    if counts is not None:
        total += counts.nbytes
    for key in getattr(res, "keys", None) or []:
        total += 8 * max(len(key), 1)
        total += sum(len(x) for x in key if isinstance(x, (str, bytes)))
    return total


_MIN_BUCKET = 1024     # mirrors ops/runtime.MIN_BUCKET (no jax import here)


def _bucket(n: int) -> int:
    b = _MIN_BUCKET
    while b < n:
        b <<= 1
    return b


def device_put_bytes(chunk, size: int | None = None) -> int:
    """HBM bytes one device_put_chunk transfer stages, from shapes alone:
    each column pads to the bucket size; varlen columns ship as int64
    dict codes; every column carries a bool validity lane."""
    n = size or _bucket(max(chunk.num_rows, 1))
    total = 0
    for c in chunk.columns:
        itemsize = 8 if c.data.dtype == object else c.data.dtype.itemsize
        total += n * (itemsize + 1)
    return total


# The allocation lint that used to consult an AUDITED_HELPERS function
# registry here now lives in tidb_tpu/lint (rule `memtrack-alloc`):
# helpers whose data-sized numpy allocations are covered by tracker
# accounting carry a lint-exempt tag (rule memtrack-alloc, with reason)
# on their def, and the engine's unused-suppression check reports any
# tag that stops matching (the old registry-staleness guard).
