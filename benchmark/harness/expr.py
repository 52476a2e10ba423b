"""Whole-number expressions over columns, as data: a string names a column,
a number is itself, a list is an operator's name and its arguments
(``["sub", "lo_revenue", "lo_supplycost"]``). An operator ``<op>`` is the
file ``benchmark/operators/<op>.py`` and its ``apply``; none is named here.
Everything is evaluated in int64 and is exact while no value passes 2**63:
two int32 columns multiplied stay under 2**62. The reference's aggregate
arguments and the ``arith`` generator kind are such expressions."""

from __future__ import annotations

import numpy as np

from . import spec


def _is_number(e) -> bool:
    return isinstance(e, int) and not isinstance(e, bool)


def check(e) -> None:
    """Refuse what cannot be evaluated, before any row is drawn."""
    if isinstance(e, str) or _is_number(e):
        return
    if not (isinstance(e, list) and len(e) >= 2 and isinstance(e[0], str)):
        raise SystemExit(f"no expression: {e!r} (a column's name, a whole "
                         "number, or [operator, argument, ...])")
    spec.found("operators", e[0])
    for arg in e[1:]:
        check(arg)


def columns(e) -> set:
    if isinstance(e, str):
        return {e}
    if isinstance(e, list):
        return set().union(*(columns(arg) for arg in e[1:]))
    return set()


def evaluate(e, cols):
    if isinstance(e, str):
        return cols[e].astype(np.int64)
    if _is_number(e):
        return np.int64(e)
    return spec.found("operators", e[0]).apply(
        *(evaluate(arg, cols) for arg in e[1:]))
