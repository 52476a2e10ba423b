"""``div``: whole-number division rounding down, in int64
(``harness/expr.py``). For operands that are not negative — every use so
far — that is SQL's and C's integer division, which dbgen's
``lo_extendedprice * (100 - lo_discount) / 100`` is."""


def apply(a, b):
    return a // b
