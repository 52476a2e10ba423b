"""The plain reference: a mix's statements answered from the generated
columns by numpy alone, and the comparison that decides ``correct``.

It imports nothing of the program and takes nothing the program made. A
statement's meaning comes from the ``reference`` entry beside its SQL in the
traffic file: a conjunction of predicates, group-by columns, SUM / COUNT
aggregates, an order and a limit. Answers are folded in one segment at a
time; integer sums are exact (a segment's float64 bincount stays far below
2**53, and the running totals are int64).

``mode`` plants the controls — the reference put in the program's place with
one stated guarantee broken: ``f32_partials`` carries each segment's partial
sums in float32, as a device plane of that type would (the step that would
tempt a later PR: PR 22 found the star-tree's DOUBLE planes stored so), and
``drop_segment`` leaves out the first segment that holds a row some statement
selects, a partial result that nobody flagged.
"""

from __future__ import annotations

import numpy as np

from . import table

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


class _Statement:
    def __init__(self, config: dict, spec: dict, mode: str):
        self.spec, self.mode = spec, mode
        self.group_specs = [table.column_spec(config, c)
                            for c in spec.get("group_by", ())]
        for g in self.group_specs:
            if g["kind"] not in ("integers", "choice"):
                raise ValueError(f"cannot group by a {g['kind']} column")
        for fn, _col in spec["aggregates"]:
            if fn not in ("sum", "count"):
                raise ValueError(f"the reference has no aggregate {fn!r}")
        if spec.get("limit") and spec.get("group_by") \
                and not spec.get("order_by"):
            raise ValueError("a limit over groups needs an order")
        self.sizes = [table.domain_size(g) for g in self.group_specs]
        n = int(np.prod(self.sizes)) if self.sizes else 1
        acc = np.float32 if mode == "f32_partials" else np.int64
        self.count = np.zeros(n, dtype=np.int64)
        self.aggs = [np.zeros(n, dtype=np.int64 if fn == "count" else acc)
                     for fn, _col in spec["aggregates"]]

    def columns(self) -> set:
        s = self.spec
        return ({w[0] for w in s.get("where", ())} | set(s.get("group_by", ()))
                | {c for fn, c in s["aggregates"] if fn != "count"})

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
        for g, size in zip(self.group_specs, self.sizes):
            v = pick(cols[g["column"]])
            ids = v.astype(np.int64) - g["low"] if g["kind"] == "integers" \
                else np.searchsorted(np.sort(np.array(g["values"])), v)
            cell = cell * size + ids
        self.count += np.bincount(cell, minlength=n)
        for (fn, col), total in zip(s["aggregates"], self.aggs):
            if fn == "count":
                total += np.bincount(cell, minlength=n)
                continue
            part = np.rint(np.bincount(
                cell, weights=pick(cols[col]).astype(np.float64),
                minlength=n)).astype(np.int64)
            total += part.astype(total.dtype)

    def rows(self) -> list:
        s = self.spec
        live = np.flatnonzero(self.count)
        keys = []
        rest = live
        for g, size in zip(reversed(self.group_specs), reversed(self.sizes)):
            rest, ids = np.divmod(rest, size)
            keys.append(ids + g["low"] if g["kind"] == "integers"
                        else np.sort(np.array(g["values"]))[ids])
        keys.reverse()
        if not self.group_specs:
            live = np.array([0])  # an aggregate without groups: one row
        vals = [a[live] for a in self.aggs]
        # ties fall to the group key, ascending, then to the order given
        order = [live]
        for kind, i, direction in reversed(s.get("order_by", ())):
            v = vals[i] if kind == "agg" else keys[i]
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
    each beside its limit, and ``correct``. ``off_device`` is compared only
    where the requests were traced (a record then has the key): an untraced
    response carries no span to look for, and a limit on nothing is no
    limit."""
    wrong = missing = off_device = compared = traced = 0
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
        if "off_device" in r:
            traced += 1
            off_device += bool(r["off_device"])
    # JSON has no infinity: a difference that no number measures reads 1e308
    values = {"answers_wrong": wrong, "answers_missing": missing,
              "max_abs_err": min(worst, 1e308)}
    if traced:
        values["off_device"] = off_device
    numbers = {k: {"value": v, "limit": LIMITS[k]} for k, v in values.items()}
    numbers["answers_compared"] = {"value": compared, "at_least": 1}
    correct = compared >= 1 and all(v <= LIMITS[k] for k, v in values.items())
    return {"correct": correct, "numbers": numbers}
