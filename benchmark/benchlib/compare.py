"""The comparison that decides `correct`, shared by a run and by the
control: every answer against the plain reference, exact equality of the
wire's text. Three numbers, each beside its limit."""

from __future__ import annotations

import importlib


def truth_of(statement: str):
    return importlib.import_module("statements." + statement).truth


def compare(ops, data: dict, log=None) -> tuple[bool, dict]:
    """`ops`: records with `ok`, `database`, `statement`, `key`, `rows`
    (and `error` when not ok). -> (correct, the numbers compared)."""
    truths, wrong, never, compared = {}, [], [], 0
    for op in ops:
        if not op.ok:
            never.append(op)
            continue
        tkey = (op.database, op.statement, op.key)
        if tkey not in truths:
            truths[tkey] = truth_of(op.statement)(data[op.database], op.key)
        compared += 1
        if op.rows != truths[tkey]:
            wrong.append(op)
    if log:
        for op in wrong[:3]:
            want = truths[(op.database, op.statement, op.key)]
            log(f"WRONG {op.database}.{op.statement} key={op.key}: got "
                f"{op.rows!r:.600} want {want!r:.600}")
        for op in never[:3]:
            log(f"FAILED {op.database}.{op.statement} key={op.key}: "
                f"{op.error}")
    checks = {
        "answers_compared": {"value": compared, "limit": ">=1"},
        "answers_wrong": {"value": len(wrong), "limit": 0},
        "answers_never_came": {"value": len(never), "limit": 0},
    }
    return compared >= 1 and not wrong and not never, checks
