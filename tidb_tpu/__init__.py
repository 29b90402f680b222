"""tidb_tpu — a TPU-native distributed HTAP SQL framework.

A ground-up rebuild of the capabilities of TiDB (reference: /root/reference,
Go, ~192k LoC) designed TPU-first:

* Control plane (SQL -> plan -> schema -> txn protocol) is host Python/C++,
  structurally mirroring the reference's session/planner/kv layers.
* Data plane (scan/filter/project/join/aggregate/sort over columns) is
  JAX/XLA: jit kernels per operator, shard_map over a `jax.sharding.Mesh`
  for multi-chip group-by/join with psum/all_gather merges.
* Storage is a Percolator-style MVCC transactional KV store partitioned
  into regions, with an in-process mock cluster (the reference's mocktikv
  move) providing hermetic multi-"node" testing on one host.

Layer map (cf. SURVEY.md §1):

    session/    Session API: Execute, txn lifecycle          (ref: session.go)
    parser/     SQL -> AST                                   (ref: parser/, ast/)
    plan/       logical/physical planner, copTask model      (ref: plan/)
    executor/   volcano-over-chunks executors                (ref: executor/)
    expression/ expr trees, numpy + jax evaluation           (ref: expression/)
    ops/        TPU kernels: filter/agg/join/sort            (ref: executor/ hot ops)
    devplane    device mesh + layout; ops/mesh* sharded kernels (new, TPU-native)
    kv/         engine-neutral txn KV contract               (ref: kv/)
    store/      distributed client: regions, 2PC, cop fanout (ref: store/tikv/)
    mockstore/  in-process MVCC cluster + coprocessor        (ref: store/tikv/mocktikv/)
    table/      row <-> KV mapping                           (ref: table/, tablecodec/)
    meta/       schema metadata on KV                        (ref: meta/, structure/)
    schema/     model + infoschema                           (ref: model/, infoschema/)
    codec/      memcomparable datum codec                    (ref: util/codec/)
    chunk/      Arrow-layout columnar batches                (ref: util/chunk/)
    sqltypes/   field types, eval types, decimal             (ref: types/)
"""

__version__ = "0.1.0"

# The device data plane is built on int64 lanes (scaled decimals, epoch-micros
# datetimes, memcomparable-ordered keys). JAX defaults to 32-bit; without x64
# the compute silently truncates — so the framework requires it globally.
import jax as _jax

_jax.config.update("jax_enable_x64", True)

# Persistent XLA compilation cache: operator kernels are compiled per
# (program, shape-bucket) and identical HLO must never recompile — not
# across kernel instances, not across processes. Large-batch programs
# cost tens of seconds of XLA compile; this turns them into disk hits.
# util/compile_cache owns the wiring (JAX_COMPILATION_CACHE_DIR where
# set, else <checkout>/.jax_cache) and counts hits/misses for
# chip_smoke.py / GET /profile / the server log.
from tidb_tpu.util import compile_cache as _compile_cache

_compile_cache.enable()

# Debug lock-order sanitizer (default off, zero overhead): with
# TIDB_TPU_LOCK_SANITIZER=1 the threading lock factories are patched
# here — before any runtime module constructs its locks — so every
# registered lock created from now on is order-checked against the
# statically-derived DAG (docs/CONCURRENCY.md, util/lockorder.py).
from tidb_tpu.util import lockorder as _lockorder

_lockorder.enable_from_env()
