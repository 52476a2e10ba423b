"""``calendar_date``: a day drawn uniformly from the real calendar between
``first`` and ``last`` (both yyyymmdd, both included; real month lengths,
leap years), stored as the int32 yyyymmdd — dbgen's ``lo_orderdate`` (its
order dates run from STARTDATE 1992-01-01 to ENDDATE - 151 days, 1998-08-02;
the date dimension to the end of 1998). One draw a row, where ``date_ymd``
makes three. The domain is the days in order, so the column can carry the
``by_date`` layout; ``date_part`` derives the year, the month number and
the week from it."""

import numpy as np

from harness import civil


def _span(spec):
    first = int(civil.days_from_ymd(spec["first"]))
    return first, int(civil.days_from_ymd(spec["last"])) - first + 1


def draw(spec, rng, n):
    return rng.integers(0, _span(spec)[1], n)


def column(spec, raw, cols, seed):
    return value_of(spec, np.arange(_span(spec)[1]))[raw]


def domain_size(spec):
    return _span(spec)[1]


def value_of(spec, index):
    return civil.ymd_from_days(_span(spec)[0] + index).astype(np.int32)


def index_of(spec, values):
    return civil.days_from_ymd(values) - _span(spec)[0]
