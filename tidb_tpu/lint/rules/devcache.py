"""Device-cache upload discipline: region columns reach HBM only through
the audited upload helper."""

from __future__ import annotations

import ast

from tidb_tpu.lint.engine import Finding, Rule, register_rule

_SCOPES = ("tidb_tpu/store/", "tidb_tpu/executor/")
_AUDITED = "tidb_tpu/store/device_cache.py"
_UPLOADS = ("device_put", "device_put_chunk")


@register_rule("device-cache")
class DeviceCacheRule(Rule):
    """In store/ and executor/, jax.device_put / runtime.device_put_chunk
    calls live ONLY in store/device_cache.py (the audited upload helper).

    The HBM region-block cache is the single owner of device residency
    for region columns: its ledger (memtrack `hbm-cache` node) is exact
    only if every upload of storage-side columns flows through
    `upload_block`. A stray device_put in a handler or executor creates
    untracked, unbudgeted HBM residency that the eviction/OOM machinery
    can neither see nor reclaim — the exact failure mode the old
    per-chunk transfer memos had. Kernel-internal transfers (ops/)
    are out of scope: they are transient dispatch staging,
    billed per-dispatch via dispatch_nbytes.
    """

    min_sites = 1       # the audited upload_block site must still exist
    fixture_rel = "tidb_tpu/store/__lint_fixture__.py"
    fixture = (
        "import jax\n"
        "def serve_block(cols):\n"
        "    return jax.device_put(cols)\n"
    )

    def check(self, forest):
        for pf in forest:
            if not pf.rel.startswith(_SCOPES):
                continue
            for node in pf.nodes:
                kind = self._upload_kind(node)
                if kind is None:
                    continue
                self.sites += 1
                if pf.rel == _AUDITED:
                    continue        # sanctioned: the audited helper
                yield Finding(
                    pf.rel, node.lineno, self.name,
                    f"direct {kind} of region columns outside the "
                    f"audited upload helper — route the transfer "
                    f"through store/device_cache.upload_block so HBM "
                    f"residency stays tracked and evictable")

    @staticmethod
    def _upload_kind(node) -> str | None:
        if not isinstance(node, ast.Call):
            return None
        fn = node.func
        if isinstance(fn, ast.Attribute) and fn.attr in _UPLOADS:
            return fn.attr
        if isinstance(fn, ast.Name) and fn.id in _UPLOADS:
            return fn.id
        return None
