"""Bytes a statement needs to read: referenced columns x declared widths
x rows, from the statement's file, the generator's schema file and the
generated row counts. Never from the program's counters, so it reads the
same work whatever implements it."""

from __future__ import annotations


def bytes_per_row(statement: dict, schema: dict) -> dict:
    """-> {table: bytes of the referenced columns in one row}."""
    out = {}
    for table, cols in statement["needed_columns"].items():
        widths = schema["tables"][table]
        out[table] = sum(widths[c] for c in cols)
    return out


def statement_bytes(statement: dict, schema: dict, counts: dict) -> int:
    """Bytes one execution needs. A keyed statement (`rows_read`) needs
    that many rows of each table and not the whole table."""
    per_row = bytes_per_row(statement, schema)
    rows = statement.get("rows_read")
    return sum(b * (rows if rows is not None else counts[t])
               for t, b in per_row.items())


def statement_rows(statement: dict, counts: dict) -> int:
    """Base-table rows one execution reads: fixed per statement by the
    generated counts."""
    rows = statement.get("rows_read")
    if rows is not None:
        return rows
    return sum(counts[t] for t in statement["tables"])
