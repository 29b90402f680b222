"""Rule catalog: importing this package registers every rule, in the
order CI reports them. Four ported from the original standalone test
walkers, nine project-specific additions, three whole-program flow
rules built on tidb_tpu/lint/flow (call graph + lock registry over
the same shared parse), and three device-plane dataflow rules built
on tidb_tpu/lint/flow/device (traced-program discovery over that
same parse)."""

from tidb_tpu.lint.rules import (  # noqa: F401  (import == register)
    wire,        # wire-discipline   (ported: tests/test_lint_wire.py)
    sync,        # hot-path-sync     (ported: tests/test_lint_sync.py)
    metrics,     # metric-names      (ported: tests/test_lint_metrics.py)
    memtrack,    # memtrack-alloc    (ported: tests/test_lint_memtrack.py)
    locks,       # lock-discipline
    sysvars,     # sysvar-registry
    errcodes,    # errcode-discipline
    dtypes,      # dtype-discipline
    excepts,     # bare-except
    devcache,    # device-cache
    decode,      # decode-discipline (encoded execution stays encoded)
    failpoints,  # failpoint-discipline (fault-injection registry)
    tracenames,  # trace-names       (statement-trace span vocabulary)
    lockorder,   # lock-order        (flow: acquisition-order cycles)
    guardedby,   # guarded-by        (flow: annotated shared state)
    pairres,     # paired-resource   (flow: consume/release, dispatch/
    #              finalize balance)
    device,      # donation-safety / cache-key / retrace-hazard
)                #                    (flow: device-plane dataflow)
