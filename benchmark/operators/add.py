"""``add``: the sum of two whole-number columns or constants, in int64
(``harness/expr.py``)."""


def apply(a, b):
    return a + b
