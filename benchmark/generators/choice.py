"""``choice``: an independent uniform draw from the listed ``values`` —
``pinot_tpu/tools/ssb.py``'s ``np.array(values)[rng.integers(0, len, n)]``
as data. dbgen draws ``lo_shipmode`` (7 modes) and ``lo_orderpriority`` (5)
so. The domain is the values in ascending order, as a dictionary holds
them."""

import numpy as np


def draw(spec, rng, n):
    return rng.integers(0, len(spec["values"]), n)


def column(spec, raw, cols, seed):
    return np.array(spec["values"])[raw]


def domain_size(spec):
    return len(spec["values"])


def value_of(spec, index):
    return np.sort(np.array(spec["values"]))[index]


def index_of(spec, values):
    return np.searchsorted(np.sort(np.array(spec["values"])), values)
