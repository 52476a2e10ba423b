"""Algorithmic bytes of a statement: what any implementation has to read
from HBM to answer it, whatever the program does — never a number the
program reports.

Rows the statement must read (rows of the segments whose range of a column
the layout narrows — the layout column, or one derived from it alone, as a
date's year — meets the statement's predicates on it; every row where the
layout prunes nothing) x the summed widths of the columns it names, each the
fewest whole bytes that hold the column's domain: a number's value range, a
string's index in its dictionary.
"""

from __future__ import annotations

from . import table


def column_width_bytes(config: dict, column: str) -> int:
    n = table.domain_size(config, column)
    return max(1, -(-(n - 1).bit_length() // 8))


def _meets(lo: int, hi: int, op: str, args: list) -> bool:
    if op == "between":
        return args[0] <= hi and args[1] >= lo
    if op in ("lt", "le"):
        return lo < args[0] or (op == "le" and lo == args[0])
    if op in ("gt", "ge"):
        return hi > args[0] or (op == "ge" and hi == args[0])
    if op == "eq":
        return lo <= args[0] <= hi
    if op == "in":
        return any(lo <= a <= hi for a in args)
    raise ValueError(f"unknown predicate {op!r}")


def segments_read(config: dict, statement: dict) -> int:
    """Segments that min/max pruning on the layout column cannot drop."""
    n = 0
    where = statement["reference"].get("where", ())
    for k in range(config["segments"]):
        ranges = {col: table.segment_range(config, k, col)
                  for col in {w[0] for w in where}}
        if all(ranges[col] is None or _meets(*ranges[col], op, args)
               for col, op, *args in where):
            n += 1
    return n


def statement_bytes(config: dict, statement: dict) -> int:
    rows = segments_read(config, statement) * config["rows_per_segment"]
    return rows * sum(column_width_bytes(config, c)
                      for c in statement["columns"])
