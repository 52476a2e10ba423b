"""The plain reference: a mix's statements answered from the generated
columns by numpy alone, and the comparison that decides ``correct``.

It imports nothing of the program and takes nothing the program made. A
statement's meaning comes from the ``reference`` entry beside its SQL in the
traffic file: a conjunction of predicates, group-by columns of any generator
kind (a kind gives the maps between a value and its index in the domain),
SUM / COUNT aggregates — a SUM's argument a column or a whole-number
expression over columns, ``["sub", "lo_revenue", "lo_supplycost"]``, whose
operators are files found by name (``harness/expr.py``) — an order and a
limit. Strings compare as SQL's binary collation does, code point by code
point. Answers are folded in one segment at a time, and whole-number sums
are exact to the unit (``_exact_sums``); the running totals are int64.

``mode`` plants the controls — the reference put in the program's place with
one stated guarantee broken: ``f32_partials`` carries each segment's partial
sums in float32, as a device plane of that type would (the step that would
tempt a later PR: PR 22 found the star-tree's DOUBLE planes stored so), and
``drop_segment`` leaves out the first segment that holds a row some statement
selects, a partial result that nobody flagged.
"""

from __future__ import annotations

import numpy as np

from . import expr, table

MODES = ("exact", "f32_partials", "drop_segment")

_OPS = {
    "between": lambda v, a, b: (v >= a) & (v <= b),
    "lt": lambda v, a: v < a,
    "le": lambda v, a: v <= a,
    "gt": lambda v, a: v > a,
    "ge": lambda v, a: v >= a,
    "eq": lambda v, a: v == a,
    "in": lambda v, *a: np.isin(v, a),
}


def _exact_sums(cell, weights, n: int):
    """Per-cell sums of int64 ``weights``, exact. ``bincount`` adds in
    float64, which holds every whole number below 2**53: a segment's sums
    are exact while rows x the largest weight stays under that. SSB's
    largest argument, ``lo_extendedprice * lo_discount``, is at most 50 x
    209,900 cents x 10 = 1.05e8 a row: 1.97e15 over 18,750,000 rows, a
    quarter of 2**53 = 9.0e15: that argument's ceiling is 85.8M rows a
    segment, a bare ``lo_revenue``'s (under 1.05e7) 858M. A segment that
    could pass the ceiling, by its own largest weight, has its weights split
    into a high part and 26 low bits, each summed exactly (2**26 x fewer
    than 2**27 rows) and put together in int64."""
    peak = int(np.abs(weights).max(initial=0)) * len(weights)
    if peak < 1 << 53:
        return np.rint(np.bincount(cell, weights=weights.astype(np.float64),
                                   minlength=n)).astype(np.int64)
    if len(weights) >= 1 << 27:
        raise ValueError("a segment of 2**27 rows or more: cut it")
    high, low = np.divmod(weights, 1 << 26)
    return (_exact_sums(cell, high, n) << 26) + _exact_sums(cell, low, n)


class _Statement:
    def __init__(self, config: dict, spec: dict, mode: str):
        self.spec, self.mode = spec, mode
        # a group key: (its kind's file, its generator entry, its domain's size)
        self.groups = []
        for column in spec.get("group_by", ()):
            g = table.column_spec(config, column)
            self.groups.append((table.kind_of(g), g,
                                table.kind_of(g).domain_size(g)))
        for fn, arg in spec["aggregates"]:
            if fn not in ("sum", "count"):
                raise ValueError(f"the reference has no aggregate {fn!r}")
            if fn == "sum":
                expr.check(arg)
        for name in self.columns():
            if table.column_spec(config, name).get("helper"):
                raise ValueError(f"{name} is a helper of the generator, no "
                                 "column of the table")
        if spec.get("limit") and spec.get("group_by") \
                and not spec.get("order_by"):
            raise ValueError("a limit over groups needs an order")
        n = int(np.prod([size for _, _, size in self.groups]))
        acc = np.float32 if mode == "f32_partials" else np.int64
        self.count = np.zeros(n, dtype=np.int64)
        self.aggs = [np.zeros(n, dtype=np.int64 if fn == "count" else acc)
                     for fn, _col in spec["aggregates"]]

    def columns(self) -> set:
        s = self.spec
        return ({w[0] for w in s.get("where", ())} | set(s.get("group_by", ()))
                | set().union(*(expr.columns(arg) for fn, arg
                                in s["aggregates"] if fn != "count")))

    def _mask(self, cols: dict):
        mask = None
        for col, op, *args in self.spec.get("where", ()):
            m = _OPS[op](cols[col], *args)
            mask = m if mask is None else mask & m
        return mask

    def selects_from(self, cols: dict) -> bool:
        mask = self._mask(cols)
        return mask is None or bool(mask.any())

    def add(self, cols: dict) -> None:
        s = self.spec
        mask = self._mask(cols)
        pick = (lambda v: v) if mask is None else (lambda v: v[mask])
        n = len(self.count)
        cell = np.zeros(len(pick(next(iter(cols.values())))), dtype=np.int64)
        for kind, g, size in self.groups:
            cell = cell * size + kind.index_of(g, pick(cols[g["column"]]))
        self.count += np.bincount(cell, minlength=n)
        for (fn, arg), total in zip(s["aggregates"], self.aggs):
            if fn == "count":
                total += np.bincount(cell, minlength=n)
                continue
            part = _exact_sums(cell, expr.evaluate(
                arg, {c: pick(cols[c]) for c in expr.columns(arg)}), n)
            total += part.astype(total.dtype)

    def rows(self) -> list:
        s = self.spec
        live = np.flatnonzero(self.count)
        ids = []
        rest = live
        for _, _, size in reversed(self.groups):
            rest, i = np.divmod(rest, size)
            ids.append(i)
        ids.reverse()
        keys = [kind.value_of(g, i)
                for (kind, g, _), i in zip(self.groups, ids)]
        if not self.groups:
            live = np.array([0])  # an aggregate without groups: one row
        vals = [a[live] for a in self.aggs]
        # ties fall to the group key, ascending, then to the order given; a
        # key orders as its index in the domain does (domains ascend), which
        # a string has and a negation has not
        order = [live]
        for kind, i, direction in reversed(s.get("order_by", ())):
            v = vals[i] if kind == "agg" else ids[i]
            order.append(-v if direction == "desc" else v)
        idx = np.lexsort(order)
        if s.get("limit"):
            idx = idx[:s["limit"]]
        return [[_plain(k[i]) for k in keys] + [_plain(v[i]) for v in vals]
                for i in idx]


def _plain(v):
    if isinstance(v, (np.integer, np.floating)):
        return int(v) if float(v) == int(v) else float(v)
    return str(v)


class Reference:
    def __init__(self, config: dict, statements: list, mode: str = "exact"):
        if mode not in MODES:
            raise ValueError(f"mode is one of {MODES}")
        self.mode = mode
        self.dropped = False
        self.statements = {s["name"]: _Statement(config, s["reference"], mode)
                           for s in statements}
        self.columns = set().union(
            *(st.columns() for st in self.statements.values()))

    def add(self, cols: dict) -> None:
        if self.mode == "drop_segment" and not self.dropped and any(
                st.selects_from(cols) for st in self.statements.values()):
            self.dropped = True
            return
        for st in self.statements.values():
            st.add(cols)

    def rows(self) -> dict:
        return {name: st.rows() for name, st in self.statements.items()}


def _is_number(v) -> bool:
    return isinstance(v, (int, float)) and not isinstance(v, bool)


def answer_error(got, want) -> float:
    """0.0 where ``got`` says exactly what the reference says; else the
    largest absolute difference of a number, and infinity for a difference
    no number measures (row count, shape, a string)."""
    if got is None or len(got) != len(want):
        return float("inf")
    worst = 0.0
    for g, w in zip(got, want):
        if len(g) != len(w):
            return float("inf")
        for a, b in zip(g, w):
            if _is_number(a) and _is_number(b):
                if a != b:
                    worst = max(worst, abs(float(a) - float(b)), 1e-300)
            elif a != b:
                return float("inf")
    return worst


LIMITS = {"answers_wrong": 0, "answers_missing": 0, "max_abs_err": 0.0,
          "off_device": 0}


def compare(records: list, want: dict) -> dict:
    """Every answer of the window against the reference. ``records`` are the
    load generator's, one per request sent. Returns the numbers compared,
    each beside its limit, and ``correct``. ``off_device`` counts the
    answers to a ``device`` statement that say the host executor answered
    for a segment — ``numSegmentsOnHost`` above 0, which every response
    carries, or in a traced one a ``host_fallback`` span: a group-by whose
    key space passes the device's table reruns on the host without an
    error, and would otherwise be timed as a device number."""
    wrong = missing = off_device = compared = 0
    worst = 0.0
    for r in records:
        if not r["ok"]:
            missing += 1
            continue
        compared += 1
        err = answer_error(r["rows"], want[r["statement"]])
        if err:
            wrong += 1
            worst = max(worst, err)
        off_device += bool(r.get("off_device"))
    # JSON has no infinity: a difference that no number measures reads 1e308
    values = {"answers_wrong": wrong, "answers_missing": missing,
              "max_abs_err": min(worst, 1e308), "off_device": off_device}
    numbers = {k: {"value": v, "limit": LIMITS[k]} for k, v in values.items()}
    numbers["answers_compared"] = {"value": compared, "at_least": 1}
    correct = compared >= 1 and all(v <= LIMITS[k] for k, v in values.items())
    return {"correct": correct, "numbers": numbers}
