"""Encoded execution: operate on dictionary codes end-to-end.

Before this module most device time went to moving bytes the query
never needed: varlen columns decoded into
wide host vectors at the device-cache boundary, string predicates
evaluated over object arrays on the host (which also rewrote the chunk
and disqualified it from the fused HBM-cache dispatch), and every join
side re-building its key dictionary with a per-row Python loop. This
module keeps the data ENCODED across those boundaries:

* `translate_filter` rewrites a host-only string filter (EQ/NE/<=>/IN/
  IS [NOT] NULL over varlen columns, AND/OR combinations, device-safe
  subtrees passed through) into code space: the column rides the device
  as its int64 dict codes (exactly what `runtime.device_put_chunk`
  ships), and each string constant is pre-encoded to its code in the
  SAME dictionary — equality over codes is equality over values by
  construction (collation-folded dictionaries keep _ci semantics). The
  rewritten filter is device-safe, so the fused scan->filter->
  partial-agg dispatch keeps running from HBM-resident columns instead
  of falling back to a host filter pass + re-upload.
* `code_translation` re-keys one dictionary's codes into another's with
  a single vectorized gather — the join build/probe bridge when the two
  sides hold different dictionaries. Sides sharing one dictionary (the
  memoized `dict_encode` of a cached column) skip even that.
* `decode_codes` is THE registered full-column late-materializer: the
  only sanctioned way to decode a whole column from its dictionary
  (lint rule `decode-discipline` — everything else must decode at most
  representative rows at the operator-output finalize boundary).

Anything outside this vocabulary returns None and the caller runs the
decoded path, counted in tidb_tpu_device_fallback_total{reason=
"encoding"}. Gated by the `tidb_tpu_encoded_exec` sysvar.

Known tradeoff: a translated constant is a dictionary-specific CODE
baked into the kernel fingerprint, so distinct dictionaries (one per
region block) trace distinct programs for one plan shape. Dictionaries
are memoized per cached column — stable across executions — so warm
serving converges on one kernel per (plan, region), held by the
widened process-wide kernel cache and the persistent XLA compile
cache. Passing codes as runtime operands (one program per plan) is the
next step if region counts grow past that.
"""

from __future__ import annotations

import numpy as np

from tidb_tpu.chunk import dict_encode
from tidb_tpu.expression.core import ColumnRef, Constant, Op, ScalarFunc, func
from tidb_tpu.sqltypes import EvalType, TypeCode, new_int_field

__all__ = ["CodeColumnRef", "translate_filter", "code_translation",
           "encoded_lane", "decode_codes", "MISSING_CODE",
           "LATE_MATERIALIZE"]

# a code no live row ever carries (live codes >= 0, NULL is -1): an
# encoded constant absent from the dictionary compares equal to nothing
MISSING_CODE = -2

_CODE_FT = new_int_field()

# (repo-relative file, function name) of every sanctioned full-column
# decode site — the decode-discipline lint rule exempts decode-shaped
# gathers inside these functions and flags them everywhere else in
# ops/ + store/copr.py. finalize_group_result decodes representative
# rows only, but owns the one place agg outputs late-materialize.
LATE_MATERIALIZE = frozenset({
    ("tidb_tpu/ops/encoded.py", "decode_codes"),
    ("tidb_tpu/ops/hashagg.py", "finalize_group_result"),
})


class CodeColumnRef(ColumnRef):
    """A varlen column viewed as its int64 dictionary codes — the lane
    `runtime.device_put_chunk` (and the HBM cache block) actually holds
    on device. Device-safe by construction: the inherited eval_xp reads
    cols[idx], which on the device path IS the code lane (validity lane
    carries the column's NULLs). Never evaluated on the host — encoded
    filters exist only on the device dispatch path."""

    def __repr__(self):
        return f"codes({self.name or f'col#{self.idx}'})"

    def __hash__(self):
        return hash(("codecol", self.idx))

    def eval(self, chunk):
        # the host chunk holds VALUES in this lane, not codes: silently
        # comparing strings against an int code would drop every row.
        # Encoded filters must never reach a host evaluator — callers
        # fall back to the ORIGINAL filter on any host path.
        raise RuntimeError("encoded filter evaluated on the host path")


class _Unsupported(Exception):
    """Filter node outside the encodable vocabulary."""


def _dict_key(v, ci: bool):
    if ci:
        from tidb_tpu.sqltypes import collation_key
        return collation_key(v)
    return v


def _dict_map(values: list, ci: bool) -> dict:
    return {_dict_key(v, ci): c for c, v in enumerate(values)}


def _is_varlen_ref(e, chunk) -> bool:
    return (type(e) is ColumnRef and
            e.ft.eval_type == EvalType.STRING and
            e.ft.tp != TypeCode.JSON and
            e.idx < chunk.num_cols and
            not chunk.columns[e.idx].fixed_width)


def _code_const(values: list, ci: bool, const: Constant) -> Constant:
    """Pre-encode one string constant against the dictionary. NULL
    constants stay NULL (comparisons with them are never true, exactly
    as in value space); absent values get MISSING_CODE."""
    v = const.value
    if v is None:
        return Constant(None, _CODE_FT)
    if not isinstance(v, (str, bytes)):
        raise _Unsupported(f"non-string constant {v!r}")
    code = _dict_map_cached(values, ci).get(_dict_key(v, ci))
    return Constant(int(code) if code is not None else MISSING_CODE,
                    _CODE_FT)


# per-translation map cache: one (values -> map) pair, keyed by list
# identity. Dictionaries are memoized per column (chunk.dict_encode),
# so repeated translations over a hot cached chunk rebuild nothing; the
# one-slot shape keeps the cache O(1) without weakrefs (lists don't
# support them).
_map_cache: tuple = (None, False, None)


def _dict_map_cached(values: list, ci: bool) -> dict:
    global _map_cache
    vals, cci, m = _map_cache
    if vals is values and cci is ci and len(m) == len(values):
        return m
    m = _dict_map(values, ci)
    _map_cache = (values, ci, m)
    return m


def translate_filter(expr, chunk, dict_of=None):
    """Rewrite a host-only filter into code space. -> a device-safe
    Expression over dictionary codes, or None when any node falls
    outside the encodable vocabulary (the caller then runs the decoded
    path and counts the fallback as reason="encoding").

    `dict_of(col_idx) -> values list` overrides where dictionaries come
    from — the fused HBM path passes the resident block's (incrementally
    extended) dictionaries so constant codes match the code lanes the
    kernel actually reads; the default is the chunk's own memoized
    dict_encode, which is what `device_put_chunk` ships on the upload
    path."""
    if expr is None:
        return None
    if dict_of is None:
        def dict_of(j):
            return dict_encode(chunk.columns[j])[1]
    try:
        return _translate(expr, chunk, dict_of)
    except _Unsupported:
        return None


def _translate(e, chunk, dict_of):
    if e.is_device_safe():
        return e                    # mixed AND/OR trees pass through
    if not isinstance(e, ScalarFunc):
        raise _Unsupported(type(e).__name__)
    op = e.op
    if op in (Op.AND, Op.OR):
        return func(op, _translate(e.args[0], chunk, dict_of),
                    _translate(e.args[1], chunk, dict_of))
    if op in (Op.IS_NULL, Op.IS_NOT_NULL):
        a = e.args[0]
        if not _is_varlen_ref(a, chunk):
            raise _Unsupported(repr(a))
        return func(op, CodeColumnRef(a.idx, _CODE_FT, a.name))
    if op in (Op.EQ, Op.NE, Op.NULLEQ):
        a, b = e.args
        if _is_varlen_ref(a, chunk) and isinstance(b, Constant):
            ref, const = a, b
        elif _is_varlen_ref(b, chunk) and isinstance(a, Constant):
            ref, const = b, a
        else:
            raise _Unsupported(repr(e))
        values = dict_of(ref.idx)
        if values is None:
            raise _Unsupported(f"no dictionary for col#{ref.idx}")
        code_ref = CodeColumnRef(ref.idx, _CODE_FT, ref.name)
        ci = ref.ft.is_ci
        if ref is a:
            return func(op, code_ref, _code_const(values, ci, const))
        return func(op, _code_const(values, ci, const), code_ref)
    if op == Op.IN:
        a = e.args[0]
        if not _is_varlen_ref(a, chunk) or not isinstance(e.extra, list):
            raise _Unsupported(repr(e))
        values = dict_of(a.idx)
        if values is None:
            raise _Unsupported(f"no dictionary for col#{a.idx}")
        ci = a.ft.is_ci
        codes = []
        for v in e.extra:
            if not isinstance(v, (str, bytes)):
                raise _Unsupported(f"non-string IN item {v!r}")
            c = _dict_map_cached(values, ci).get(_dict_key(v, ci))
            codes.append(int(c) if c is not None else MISSING_CODE)
        return func(Op.IN, CodeColumnRef(a.idx, _CODE_FT, a.name),
                    extra=codes)
    raise _Unsupported(repr(e))


def encoded_lane(expr, chunk):
    """(codes, values) when `expr` is a bare varlen ColumnRef into
    `chunk` — the pre-encoded key lane a join consumes directly — else
    None. Codes/values are the column's memoized dict_encode, so two
    sides reading the same cached column share ONE dictionary object
    (identity comparison detects it)."""
    if not _is_varlen_ref(expr, chunk):
        return None
    return dict_encode(chunk.columns[expr.idx])


def code_translation(src_values: list, dst_values: list, ci: bool,
                     dst_map: dict | None = None) -> np.ndarray:
    """Re-keying bridge between two dictionaries: an int64 array T with
    T[src_code] = the matching code in `dst_values`, or a unique
    negative no-match code (<= MISSING_CODE) when the value is absent —
    rows stay live (outer-join semantics) but match nothing. The last
    slot maps the NULL code: T[codes] with codes == -1 indexes it and
    yields -1, so NULL stays NULL through the translation. `dst_map`
    lets a caller with a cached value->code map (JoinKeyEncoder, one
    map per build side vs one translation per probe batch) skip the
    O(|dst|) rebuild."""
    if dst_map is None:
        dst_map = _dict_map(dst_values, ci)
    # lint: exempt[memtrack-alloc] dictionary-sized (distinct values), not row-sized
    t = np.empty(len(src_values) + 1, dtype=np.int64)
    for c, v in enumerate(src_values):
        hit = dst_map.get(_dict_key(v, ci))
        t[c] = hit if hit is not None else MISSING_CODE - c
    t[-1] = -1
    return t


def decode_codes(values: list, codes: np.ndarray) -> np.ndarray:
    """THE registered full-column late-materializer (decode-discipline):
    gather dictionary values by code into an object array (NULL/-1 and
    no-match codes decode to None). Call this only at operator-output
    finalize boundaries — decoding a whole column anywhere else defeats
    encoded execution and the lint rule will flag it."""
    # lint: exempt[memtrack-alloc] dictionary-sized decode table; the gathered output aliases existing values
    table = np.empty(len(values) + 1, dtype=object)
    for c, v in enumerate(values):
        table[c] = v
    table[-1] = None
    safe = np.where(codes >= 0, codes, len(values))
    return table[safe]
