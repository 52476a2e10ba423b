"""``date_ymd``: the stand-in table's date, ``base + y*10000 + m*100 + d``
with year, month and day drawn apart — ``pinot_tpu/tools/ssb.py``'s
``lo_orderdate``, whose months all have ``days`` days (28: 2,352 values in 7
years). Not a calendar: ``calendar_date`` is. The domain counts the days in
order: index = (y*months + m)*days + d."""

import numpy as np


def draw(spec, rng, n):
    y = rng.integers(0, spec["years"], n)
    m = rng.integers(0, spec["months"], n)
    d = rng.integers(0, spec["days"], n)
    return y, m, d


def column(spec, raw, cols, seed):
    y, m, d = raw
    return (spec["base"] + y * 10000 + m * 100 + d).astype(np.int32)


def domain_size(spec):
    return spec["years"] * spec["months"] * spec["days"]


def value_of(spec, index):
    y, rest = np.divmod(index, spec["months"] * spec["days"])
    m, d = np.divmod(rest, spec["days"])
    return spec["base"] + y * 10000 + m * 100 + d


def index_of(spec, values):
    # through a table from value - base to index: the domain is small and
    # a gather is cheaper than two divisions a row
    days = np.arange(domain_size(spec), dtype=np.int32)
    past_base = value_of(spec, days) - spec["base"]
    index = np.zeros(past_base.max() + 1, dtype=np.int32)
    index[past_base] = days
    return index[values - spec["base"]]
