"""``mod``: the remainder of ``div``, in int64 (``harness/expr.py``); for
operands that are not negative, C's ``%`` as dbgen's price formula uses
it."""


def apply(a, b):
    return a % b
