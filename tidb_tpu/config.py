"""Global runtime configuration: the sysvar registry.

Reference: /root/reference/sessionctx/variable/sysvar.go (typed sysvar
registry), config/config.go:29-52 (TOML config tree) and the concurrency
knobs of sessionctx/variable/session.go:209-245. One flat registry serves
all three roles here: every performance knob that used to be a hard-coded
constant reads through it, `SET @@tidb_tpu_x = v` writes through it, and
the server CLI seeds it from flags.

Scope: the registry is GLOBAL (process-wide); sessions shadow it with a
thread-local overlay installed for the duration of each statement
(`session_overlay`, ref: sessionctx/variable SessionVars layering over
globals). Reads on the session's thread see the session values; the
coprocessor fan-out re-installs the overlay inside its pool workers
(store/copr.py) so per-session knobs apply uniformly there too.
"""

from __future__ import annotations

import os
import threading

__all__ = ["get_var", "set_var", "all_vars", "coerce", "session_overlay",
           "current_overlay", "device_enabled", "chunk_cache_enabled",
           "cop_concurrency", "sort_spill_rows", "device_min_rows",
           "stream_rows", "superchunk_rows", "pipeline_depth",
           "copr_stream_enabled", "copr_stream_frame_bytes",
           "copr_stream_credit", "join_partitions", "skew_threshold",
           "runtime_stats_enabled",
           "runtime_stats_device", "mem_quota_query",
           "device_cache_bytes", "fused_scan_enabled",
           "encoded_exec_enabled", "fuse_fragments_enabled",
           "direct_agg_slots",
           "server_mem_quota", "admission_timeout_ms",
           "sched_inflight", "sched_inflight_bytes",
           "delta_store_enabled", "delta_merge_rows",
           "delta_merge_ratio_pct", "delta_retain_ms",
           "fleet_local_cache",
           "dispatch_timeout_ms", "failpoints_spec", "on_change",
           "trace_sample", "slow_trace_ms",
           "kernel_profile", "kernel_profile_cap", "stmt_profile_cap",
           "metrics_history_interval_ms", "metrics_history_points",
           "member_heartbeat_ms", "member_ttl_ms",
           "cluster_fetch_timeout_ms",
           "UnknownVariableError"]


class UnknownVariableError(Exception):
    pass


_BOOL, _INT, _STR = "bool", "int", "str"

# name -> (type, default). Bool vars store 0/1 like MySQL switches;
# the rare _STR vars (failpoint arming) store their string verbatim.
_DEFS: dict[str, tuple[str, int]] = {
    # master switch for single-chip device kernels; 0 = pure host numpy
    # execution everywhere (the plain reference every device result is
    # compared with)
    "tidb_tpu_device": (_BOOL, 1),
    # columnar region-chunk cache on the storage side (store/chunk_cache)
    "tidb_tpu_chunk_cache": (_BOOL, 1),
    # coprocessor fan-out worker count
    # (ref: DistSQLScanConcurrency, sessionctx/variable/tidb_vars.go:115)
    "tidb_tpu_cop_concurrency": (_INT, 10),
    # SortExec spill threshold in rows (executor/extsort.py run size)
    "tidb_tpu_sort_spill_rows": (_INT, 1 << 20),
    # min chunk rows before an executor pays a device dispatch
    "tidb_tpu_device_min_rows": (_INT, 2048),
    # streaming threshold for mesh/device operators: probe sides larger
    # than this never materialize whole on the host — they feed the
    # kernels in ≤stream_rows super-batches, double-buffered so the
    # host→HBM transfer of batch i+1 overlaps batch i's readback
    # (BASELINE config 5; ref: the bounded producer/consumer channels of
    # distsql/distsql.go:92-98). The default is deliberately high:
    # below it, whole tables stay memoized/resident in HBM and hot
    # re-executions transfer ZERO bytes (the analytics fast path);
    # streaming trades that residency for bounded host memory, so it
    # should engage only when tables genuinely outgrow memory. Lower it
    # per deployment (SET tidb_tpu_stream_rows = ...) to cap footprint.
    "tidb_tpu_stream_rows": (_INT, 1 << 23),
    # streaming coprocessor (store/stream.py; ref: CmdCopStream,
    # store/tikv/coprocessor.go:547-555): storage yields framed partial
    # responses per contiguous key range instead of materializing one
    # response list per region. On by default since streams consult and
    # populate the columnar chunk cache (and the HBM device cache when
    # eligible) exactly like the materialized path — the old
    # cache-bypass penalty that forced the default off is gone. 0 =
    # materialized per-region response lists.
    "tidb_tpu_copr_stream": (_BOOL, 1),
    # response-size cap: a streamed frame never carries more than this
    # many raw scanned bytes (the bound that makes SF>=1 scans run in
    # constant client memory). Cache-resident ranges ship as ONE final
    # frame only when the response respects this cap too: agg partials
    # (tiny by construction) and raw blocks that fit a single frame —
    # bigger resident blocks stream framed like a cold scan
    "tidb_tpu_copr_stream_frame_bytes": (_INT, 4 << 20),
    # credit window: max frames in flight past the consumer (client
    # grants N outstanding frames; the producer blocks past the window —
    # a slow consumer backpressures the server instead of buffering)
    "tidb_tpu_copr_stream_credit": (_INT, 4),
    # superchunk coalescing (ops/runtime.py): chunks arriving from the
    # coprocessor fan-out are re-batched into ~this-many-row fixed-shape
    # batches before a device kernel sees them, so each query compiles a
    # handful of XLA programs over big buckets instead of dispatching per
    # storage chunk (the per-batch amortization of arxiv 2505.04153 /
    # 2603.26698). Power of two keeps full superchunks on one bucket
    # shape; the tail pads to the next power of two with valid=False
    # rows. 0 disables coalescing (per-chunk dispatch, the pre-superchunk
    # behavior). Order-sensitive paths (KeepOrder streaming readers,
    # limit short-circuit scans, merge join) stay chunk-at-a-time.
    "tidb_tpu_superchunk_rows": (_INT, 1 << 18),
    # dispatch-ahead window of the device pipeline: up to this many
    # superchunks in flight, so superchunk k+1 is padded and transferred
    # while k executes (2 = classic double buffering). 1 serializes
    # dispatch against readback.
    "tidb_tpu_pipeline_depth": (_INT, 2),
    # HBM-resident columnar region-block cache (store/device_cache.py):
    # device-side budget in bytes for dict-encoded, padded region
    # columns kept resident in HBM across queries, accounted on the
    # memtrack SERVER device ledger and LRU-evicted past the budget.
    # 0 disables (every dispatch re-uploads, the pre-cache behavior).
    "tidb_tpu_device_cache_bytes": (_INT, 2 << 30),
    # fused scan->filter->partial-agg dispatch (store/copr.py): an
    # HBM-cached region block flows through predicate + partial
    # aggregation in ONE compiled call — no per-op device_put/device_get
    # round trips. 0 reverts the scan path to per-dispatch upload AND
    # stops consulting/filling the device cache entirely: a cached
    # block is only consumable by a kernel that accepts device-resident
    # columns, i.e. the fused dispatch.
    "tidb_tpu_fused_scan": (_BOOL, 1),
    # encoded execution (ops/encoded.py): operate on dictionary codes
    # end-to-end instead of decoding varlen columns at the device-cache
    # boundary — string filters compare against pre-encoded constant
    # codes on device, join build/probe sides hash codes directly
    # (re-keyed through a code-translation array when the dictionaries
    # differ), and only result columns late-materialize at the
    # operator-output finalize boundary. Any unsupported expression
    # falls back to the decoded path, counted in
    # tidb_tpu_device_fallback_total{reason="encoding"}. 0 = always
    # decode (the pre-encoded behavior).
    "tidb_tpu_encoded_exec": (_BOOL, 1),
    # fragment fusion (ops/fragment.py): one XLA program executes a
    # whole pipeline fragment (scan->filter->probe->partial-agg) per
    # probe superchunk instead of one program per operator, eliminating
    # the inter-operator HBM round trips (the joined intermediate never
    # materializes). 0 = per-operator programs.
    "tidb_tpu_fuse_fragments": (_BOOL, 1),
    # cardinality bound of the direct-indexed (code-indexed) partial-agg
    # table: group domains whose code-span product fits this many slots
    # aggregate through a fixed-size direct-indexed array (no sort, no
    # hash, no collision possibility); past it the group-by degrades to
    # the packed-sort hash table instead of ballooning the direct table
    # (arxiv 2603.26698 "Partial Partial Aggregates").
    "tidb_tpu_direct_agg_slots": (_INT, 4096),
    # radix fan-out of the partitioned hybrid hash join/agg
    # (ops/hybrid.py; arxiv 2112.02480's dynamic hybrid hash join): build
    # and probe keys split into this many hash partitions so a capacity
    # or collision miss retries ONE partition (and a memtrack quota spill
    # sheds cold build partitions to host staging) instead of dropping
    # the whole operator to the host. 0/1 disables partitioning (the
    # pre-hybrid all-or-nothing behavior). The unskewed fast path is
    # unchanged: partitioning engages only on detected skew, an
    # over-superchunk build, an active memory quota, or an agg miss.
    "tidb_tpu_join_partitions": (_INT, 8),
    # heavy-hitter threshold in rows (ops/hybrid.py; arxiv 2505.04153):
    # a join key whose build-side duplication or (CMSketch-estimated)
    # probe-side frequency reaches this many rows routes to the
    # dedicated broadcast lane, so one hot key cannot overflow its hash
    # partition. 0 disables skew routing.
    "tidb_tpu_skew_threshold": (_INT, 1 << 15),
    # statements at/above this wall time land in the slow-query log
    # (ref: config.Log.SlowThreshold, default 300ms)
    "tidb_tpu_slow_query_ms": (_INT, 300),
    # per-operator runtime statistics (runtime_stats.py; ref: the
    # RuntimeStatsColl threaded through the reference's executors). On by
    # default: the host-side cost is a clock read per chunk. Feeds
    # EXPLAIN ANALYZE, the digest summary's hot spots, the slow log and
    # the tidb_tpu_op_* metric families.
    "tidb_tpu_runtime_stats": (_BOOL, 1),
    # device-time attribution: times kernel calls around
    # block_until_ready, which SERIALIZES dispatch — off by default,
    # flip per session when profiling (EXPLAIN ANALYZE device_time)
    "tidb_tpu_runtime_stats_device": (_BOOL, 0),
    # emit every statement's span tree to the tidb_tpu.trace logger
    # (ref: the OpenTracing spans of session.go:692 / compiler.go:34)
    "tidb_tpu_trace_log": (_BOOL, 0),
    # always-on statement-trace sampling (trace.py): every N-th
    # non-internal statement retains its full span tree in the bounded
    # server trace ring (TRACE statement / statement_traces memtable /
    # GET /trace / Chrome export). Deterministic counter, not random —
    # 1 retains everything, 0 disables sampling (slow-trace capture and
    # the TRACE statement still retain).
    "tidb_tpu_trace_sample": (_INT, 64),
    # slow-trace capture threshold in milliseconds: any statement at or
    # over it retains its full span tree regardless of sampling, and
    # its trace id rides the slow log + digest summary so a digest hot
    # spot links to a concrete timeline. 0 = off.
    "tidb_tpu_slow_trace_ms": (_INT, 300),
    # per-statement memory quota in bytes over BOTH tracker ledgers
    # (host + device, memtrack.py; ref: the reference's mem-quota-query).
    # 0 = unlimited. Crossing it fires the OOM-action chain: registered
    # sort/agg spills first, then cancel with ER_MEM_EXCEED_QUOTA.
    "tidb_tpu_mem_quota_query": (_INT, 0),
    # SERVER-wide memory budget in bytes over the memtrack root's two
    # ledgers combined (tidb_tpu/sched.py AdmissionController; ref: the
    # reference's server-memory-quota). 0 = admission control off. On
    # projected overflow at statement admission the controller first
    # drives the registered shed chain (HBM cache blocks, running
    # statements' spill actions), then queues the statement up to
    # tidb_tpu_admission_timeout_ms, then rejects with the RETRYABLE
    # ER_SERVER_BUSY_ADMISSION (9008) — never a mid-query OOM cancel.
    "tidb_tpu_server_mem_quota": (_INT, 0),
    # bounded admission-queue wait before a statement is rejected with
    # the retryable 9008 (milliseconds)
    "tidb_tpu_admission_timeout_ms": (_INT, 1000),
    # global device dispatch window (tidb_tpu/sched.py DeviceScheduler):
    # at most this many kernel dispatches in flight across ALL
    # concurrent statements, granted round-robin per statement so one
    # long analytic query cannot monopolize the device while point
    # lookups starve. 0 = scheduler off (the pre-scheduler free-for-all
    # where each statement owned a private pipeline-depth window).
    "tidb_tpu_sched_inflight": (_INT, 4),
    # in-flight-bytes gate: a dispatch slot is granted only while the
    # memtrack SERVER root's DEVICE ledger sits below this many bytes
    # (0 = no bytes gate). Size it to HBM minus the device-cache budget;
    # one dispatch is always allowed through when none are in flight.
    "tidb_tpu_sched_inflight_bytes": (_INT, 0),
    # MVCC delta store (store/delta.py): committed row mutations are
    # journaled per table and cached columnar blocks serve as
    # base + delta instead of being wholesale-invalidated — the HTAP
    # write path. 0 = legacy behavior: every committed write bumps
    # data_version and re-colds both the chunk cache and the HBM cache.
    "tidb_tpu_delta_store": (_BOOL, 1),
    # staged delta rows per table that trigger a background merge
    # (fold deltas into new base blocks + truncate the journal)
    "tidb_tpu_delta_merge_rows": (_INT, 8192),
    # store-plane journal retention window in wall-clock ms: merges keep
    # at least this much journal behind now so remote fleet caches
    # (pulling (fill_ts, read_ts] windows over the journal-window RPC)
    # can patch in place instead of going STALE. 0 = truncate to the
    # local floor only (single-process behavior)
    "tidb_tpu_delta_retain_ms": (_INT, 0),
    # fleet SQL servers serve coprocessor reads from their own chunk +
    # HBM caches, kept coherent by journal-window pulls from the store
    # plane; 0 = every remote read executes on the store plane
    "tidb_tpu_fleet_local_cache": (_BOOL, 1),
    # merge when staged delta rows exceed this percent of the table's
    # observed cached base rows (0 = ratio trigger off)
    "tidb_tpu_delta_merge_ratio_pct": (_INT, 25),
    # dispatch watchdog (tidb_tpu/sched.py DispatchWatchdog): a kernel
    # finalize (or device_slot-guarded sync dispatch) that exceeds this
    # many milliseconds cancels its statement with the RETRYABLE
    # ER_DEVICE_FAULT (9009), releasing its scheduler slots and
    # device-ledger bytes on the existing finally paths — a wedged
    # device degrades to a retryable error, never a stuck server.
    # 0 = watchdog off (the default: CPU-XLA first compiles can
    # legitimately take tens of seconds).
    "tidb_tpu_dispatch_timeout_ms": (_INT, 0),
    # kernel profiling plane (tidb_tpu/profiler.py): continuous per-
    # kernel compile/dispatch/roofline accounting keyed (family, plan
    # fingerprint, mesh fingerprint), surfaced in EXPLAIN ANALYZE's
    # `kernel` column, information_schema.kernel_profile and
    # GET /profile. On by default: the armed per-dispatch cost is one
    # perf_counter pair + a dict fold under one lock, amortized over
    # superchunk-sized dispatches; disarmed cost is pinned <5us per
    # statement (tests/test_profiler.py, same discipline as trace).
    "tidb_tpu_kernel_profile": (_BOOL, 1),
    # bounded size of the kernel-profile registry (distinct
    # family/fingerprint/mesh keys; true LRU beyond). Entries bill a
    # fixed per-entry cost to the `kernel-profile` memtrack SERVER
    # node, with a registered shed action — GET /shed (and admission
    # shedding) drops the profile history before it cancels work.
    "tidb_tpu_kernel_profile_cap": (_INT, 512),
    # bounded size of the per-digest per-operator mode-history memo
    # (perfschema.py): which agg/join mode actually ran per statement
    # digest, observed group cardinality and per-mode device-ns — the
    # read side the future adaptive mode chooser (ROADMAP item 3)
    # consults. Served as information_schema.statement_profile.
    "tidb_tpu_stmt_profile_cap": (_INT, 1024),
    # metrics-history sampler cadence (tidb_tpu/metrics_history.py): a
    # supervised background sampler snapshots registered gauges plus
    # derived device-utilization / HBM occupancy / hit-rate series into
    # a bounded in-process ring (billed to a memtrack SERVER node with
    # a registered shed action) every this-many milliseconds, and rolls
    # the resource meter's per-tenant interval baselines. Served on
    # GET /metrics/history. 0 = sampler idle (manual sample_now() — the
    # tests' door — still records).
    "tidb_tpu_metrics_history_interval_ms": (_INT, 1000),
    # metrics-history ring capacity in points (one point per sampler
    # tick); the oldest points evict past it
    "tidb_tpu_metrics_history_points": (_INT, 512),
    # fleet membership registry (tidb_tpu/member.py): every server
    # process republishes its ephemeral heartbeat record this often...
    "tidb_tpu_member_heartbeat_ms": (_INT, 1000),
    # ...and a record not rebeaten within this window is dead — peers
    # stop fanning cluster_* queries out to it and it drops from
    # information_schema.cluster_members. TTL should be >= 2-3x the
    # heartbeat so one delayed beat doesn't flap membership.
    "tidb_tpu_member_ttl_ms": (_INT, 3000),
    # per-member HTTP budget of the cluster_* / /fleet/* fan-out
    # (util/statusclient.fetch_all): an unreachable member costs at
    # most this long and degrades that member's rows to a warning,
    # never a hang or a statement error
    "tidb_tpu_cluster_fetch_timeout_ms": (_INT, 2000),
    # failpoint arming (util/failpoint.py): "name=spec;name=spec" over
    # the declared registry, e.g. 'hbm/fill=2*raise(DeviceFaultError)'.
    # The value is DECLARATIVE for the SET surface: writing it arms the
    # listed points and disarms whatever a previous SET armed (env and
    # POST /failpoint arming is unaffected). Empty = none armed via
    # SET. GLOBAL scope only — arming is a process-wide side effect.
    "tidb_tpu_failpoints": (_STR, ""),
}

_lock = threading.Lock()
_vals: dict[str, int] = {}
# name -> [fn]: set_var notifies AFTER the write, with _lock dropped
# (hooks may read the registry); util/failpoint.py uses this to make
# `SET GLOBAL tidb_tpu_failpoints = ...` arm the registry
_hooks: dict[str, list] = {}        # guarded-by: _lock


def on_change(name: str, fn) -> None:
    """Register fn(new_value) to run after every set_var(name)."""
    key = name.lower()
    if key not in _DEFS:
        raise UnknownVariableError(name)
    with _lock:
        _hooks.setdefault(key, []).append(fn)


def _coerce(name: str, tp: str, value) -> int:
    if tp == _STR:
        return "" if value is None else str(value)
    if isinstance(value, str):
        v = value.strip().lower()
        if tp == _BOOL and v in ("on", "true"):
            return 1
        if tp == _BOOL and v in ("off", "false"):
            return 0
        value = int(v)
    iv = int(value)
    if tp == _BOOL:
        iv = 1 if iv else 0
    return iv


def _init() -> None:
    """Defaults, overridable by environment (TIDB_TPU_DEVICE=0 etc.) so
    benchmarks and CI can flip modes without code. Malformed values fail
    fast with the offending variable named (not a bare int() traceback)."""
    for name, (tp, dflt) in _DEFS.items():
        env = os.environ.get(name.upper())
        if env is None:
            _vals[name] = dflt
            continue
        try:
            _vals[name] = _coerce(name, tp, env)
        except ValueError:
            raise ValueError(
                f"invalid value for environment variable "
                f"{name.upper()}={env!r} (expected "
                f"{'on/off/true/false/0/1' if tp == _BOOL else 'an integer'})"
            ) from None


_init()


_tls = threading.local()


def _read(key: str) -> int:
    ov = getattr(_tls, "overlay", None)
    if ov is not None and key in ov:
        return ov[key]
    return _vals[key]


def current_overlay() -> dict:
    """This thread's effective session overlay (for propagating into
    worker threads: wrap their work in session_overlay(...))."""
    return dict(getattr(_tls, "overlay", None) or {})


class session_overlay:
    """Shadow registry values on THIS thread for a statement's duration
    (per-session SET). Nests: inner overlays win, outers restore."""

    def __init__(self, vars: dict):
        self.vars = {k.lower(): v for k, v in vars.items()
                     if k.lower() in _DEFS}
        self._prev = None

    def __enter__(self):
        self._prev = getattr(_tls, "overlay", None)
        merged = dict(self._prev) if self._prev else {}
        merged.update(self.vars)
        _tls.overlay = merged
        return self

    def __exit__(self, *exc):
        _tls.overlay = self._prev
        return False


# vars whose write is a process-wide side effect routed through
# on_change hooks: session-scope SET would shadow the value on one
# thread while arming nothing — reject it (ER_GLOBAL_VARIABLE)
_GLOBAL_ONLY = frozenset({"tidb_tpu_failpoints"})


def is_global_only(name: str) -> bool:
    return name.lower() in _GLOBAL_ONLY


def is_known(name: str) -> bool:
    return name.lower() in _DEFS


def coerce(name: str, value) -> int:
    """Validate + normalize a value for a known variable (raises
    UnknownVariableError / ValueError)."""
    key = name.lower()
    tp_dflt = _DEFS.get(key)
    if tp_dflt is None:
        raise UnknownVariableError(name)
    return _coerce(key, tp_dflt[0], value)


def get_var(name: str) -> int:
    key = name.lower()
    if key not in _DEFS:
        raise UnknownVariableError(name)
    return _read(key)


def set_var(name: str, value) -> None:
    key = name.lower()
    tp_dflt = _DEFS.get(key)
    if tp_dflt is None:
        raise UnknownVariableError(name)
    new = _coerce(key, tp_dflt[0], value)
    with _lock:
        prev = _vals.get(key)
        _vals[key] = new
        hooks = list(_hooks.get(key, ()))
    try:
        for fn in hooks:
            fn(new)
    except Exception:
        # a hook that rejects the value (bad failpoint spec) must not
        # leave the registry claiming a value that never took effect;
        # compare-and-restore so a CONCURRENT successful set_var that
        # interleaved before this rollback is not clobbered
        with _lock:
            if _vals.get(key) == new:
                _vals[key] = prev
        raise


def all_vars() -> dict[str, int]:
    """Effective values on this thread (session overlay applied)."""
    out = dict(_vals)
    ov = getattr(_tls, "overlay", None)
    if ov:
        out.update(ov)
    return out


# -- hot-path accessors (dict reads; no lock needed for int loads) ----------

def device_enabled() -> bool:
    return bool(_read("tidb_tpu_device"))


def chunk_cache_enabled() -> bool:
    return bool(_read("tidb_tpu_chunk_cache"))


def cop_concurrency() -> int:
    return _read("tidb_tpu_cop_concurrency")


def sort_spill_rows() -> int:
    return _read("tidb_tpu_sort_spill_rows")


def device_min_rows() -> int:
    return _read("tidb_tpu_device_min_rows")


def stream_rows() -> int:
    return _read("tidb_tpu_stream_rows")


def superchunk_rows() -> int:
    return max(0, _read("tidb_tpu_superchunk_rows"))


def pipeline_depth() -> int:
    return max(1, _read("tidb_tpu_pipeline_depth"))


def copr_stream_enabled() -> bool:
    return bool(_read("tidb_tpu_copr_stream"))


def copr_stream_frame_bytes() -> int:
    # clamp both ends: the sysvar is unbounded, the wire/shim contract
    # (mockstore/rpc.py validation) is not
    return min(max(1, _read("tidb_tpu_copr_stream_frame_bytes")), 1 << 30)


def copr_stream_credit() -> int:
    return max(1, _read("tidb_tpu_copr_stream_credit"))


def join_partitions() -> int:
    return max(0, _read("tidb_tpu_join_partitions"))


def skew_threshold() -> int:
    return max(0, _read("tidb_tpu_skew_threshold"))


def runtime_stats_enabled() -> bool:
    return bool(_read("tidb_tpu_runtime_stats"))


def runtime_stats_device() -> bool:
    return bool(_read("tidb_tpu_runtime_stats_device"))


def mem_quota_query() -> int:
    return max(0, _read("tidb_tpu_mem_quota_query"))


def device_cache_bytes() -> int:
    return max(0, _read("tidb_tpu_device_cache_bytes"))


def server_mem_quota() -> int:
    return max(0, _read("tidb_tpu_server_mem_quota"))


def admission_timeout_ms() -> int:
    return max(0, _read("tidb_tpu_admission_timeout_ms"))


def sched_inflight() -> int:
    return max(0, _read("tidb_tpu_sched_inflight"))


def sched_inflight_bytes() -> int:
    return max(0, _read("tidb_tpu_sched_inflight_bytes"))


def fused_scan_enabled() -> bool:
    return bool(_read("tidb_tpu_fused_scan"))


def encoded_exec_enabled() -> bool:
    return bool(_read("tidb_tpu_encoded_exec"))


def fuse_fragments_enabled() -> bool:
    return bool(_read("tidb_tpu_fuse_fragments"))


def direct_agg_slots() -> int:
    return max(16, _read("tidb_tpu_direct_agg_slots"))


def delta_store_enabled() -> bool:
    return bool(_read("tidb_tpu_delta_store"))


def delta_merge_rows() -> int:
    return max(1, _read("tidb_tpu_delta_merge_rows"))


def delta_merge_ratio_pct() -> int:
    return max(0, _read("tidb_tpu_delta_merge_ratio_pct"))


def delta_retain_ms() -> int:
    return max(0, _read("tidb_tpu_delta_retain_ms"))


def fleet_local_cache() -> bool:
    return bool(_read("tidb_tpu_fleet_local_cache"))


def dispatch_timeout_ms() -> int:
    return max(0, _read("tidb_tpu_dispatch_timeout_ms"))


def failpoints_spec() -> str:
    return str(_read("tidb_tpu_failpoints") or "")


def metrics_history_interval_ms() -> int:
    return max(0, _read("tidb_tpu_metrics_history_interval_ms"))


def metrics_history_points() -> int:
    return min(max(16, _read("tidb_tpu_metrics_history_points")), 1 << 16)


def member_heartbeat_ms() -> int:
    return max(100, _read("tidb_tpu_member_heartbeat_ms"))


def member_ttl_ms() -> int:
    return max(200, _read("tidb_tpu_member_ttl_ms"))


def cluster_fetch_timeout_ms() -> int:
    return max(100, _read("tidb_tpu_cluster_fetch_timeout_ms"))


def trace_sample() -> int:
    return max(0, _read("tidb_tpu_trace_sample"))


def slow_trace_ms() -> int:
    return max(0, _read("tidb_tpu_slow_trace_ms"))


def kernel_profile() -> bool:
    return bool(_read("tidb_tpu_kernel_profile"))


def kernel_profile_cap() -> int:
    return min(max(16, _read("tidb_tpu_kernel_profile_cap")), 1 << 16)


def stmt_profile_cap() -> int:
    return min(max(16, _read("tidb_tpu_stmt_profile_cap")), 1 << 16)
