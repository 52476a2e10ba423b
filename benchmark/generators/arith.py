"""``arith``: whole-number arithmetic on earlier columns and constants,
drawn by nothing — dbgen's derived measures: ``lo_extendedprice`` =
``lo_quantity`` x the part's price, ``lo_revenue`` = ``lo_extendedprice`` x
(100 - ``lo_discount``) / 100, ``lo_supplycost`` = 6 x price / 10, and the
price itself, 90000 + (partkey / 10) mod 20001 + 100 x (partkey mod 1000)
cents (TPC-H's ``p_retailprice``). ``expr`` is a nested list, operator
first; a string names a column, a number is itself; the operators are the
files of ``benchmark/operators/`` (``harness/expr.py``), exact in int64.
The entry states the range ``[low, high)`` the values fall in, which is its
domain and is checked on every segment drawn; ``dtype`` is int32 unless it
says int64."""

import numpy as np

from harness import expr


def check(spec):
    expr.check(spec["expr"])


def needs(spec):
    return sorted(expr.columns(spec["expr"]))


def column(spec, raw, cols, seed):
    v = expr.evaluate(spec["expr"], cols)
    if len(v) and not (spec["low"] <= v.min() and v.max() < spec["high"]):
        raise ValueError(
            f"{spec['column']}: drew {v.min()}..{v.max()}, outside the "
            f"[{spec['low']}, {spec['high']}) its entry states")
    return v.astype(spec.get("dtype", "int32"))


def domain_size(spec):
    return spec["high"] - spec["low"]


def value_of(spec, index):
    return index + spec["low"]


def index_of(spec, values):
    return values.astype(np.int64) - spec["low"]
