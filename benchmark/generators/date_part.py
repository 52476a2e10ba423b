"""``date_part``: a part of the calendar date in column ``of`` (a yyyymmdd),
drawn by nothing — dbgen's date dimension joined onto the row. ``part`` is
one of

  date           the date itself (with ``plus``: a later date)
  year           d_year, 1992
  yearmonthnum   d_yearmonthnum, 199201
  yearmonth      d_yearmonth, 'Jan1992'
  weeknuminyear  d_weeknuminyear, 1..53: (day of the year - 1) // 7 + 1
                 (assumed; dbgen's own rule was not to hand)

``plus`` names a column of whole days added first (dbgen's ``lo_commitdate``
is the order date plus 30 to 90 days). ``first`` and ``last`` are the
calendar span of the dates the part is taken of (after ``plus``); they give
the domain: the part's distinct values over that span, ascending. A part
follows its date, so where the date carries the ``by_date`` layout the part
is cut with it."""

import numpy as np

from harness import civil

_MONTHS = np.array(["Jan", "Feb", "Mar", "Apr", "May", "Jun",
                    "Jul", "Aug", "Sep", "Oct", "Nov", "Dec"])


def _yearmonth(y, m, d):
    # one string a month of the span, then a gather: no string a row is
    # ever formatted
    month_no = y * 12 + m - 1
    lo = int(month_no.min())
    months = np.arange(lo, int(month_no.max()) + 1)
    names = np.char.add(_MONTHS[months % 12], (months // 12).astype("U4"))
    return names[month_no - lo]


_PARTS = {
    "date": lambda y, m, d: (y * 10000 + m * 100 + d).astype(np.int32),
    "year": lambda y, m, d: y.astype(np.int32),
    "yearmonthnum": lambda y, m, d: (y * 100 + m).astype(np.int32),
    "yearmonth": _yearmonth,
    "weeknuminyear": lambda y, m, d: (
        (civil.days_from_civil(y, m, d) - civil.days_from_civil(y, 1, 1))
        // 7 + 1).astype(np.int32),
}


def check(spec):
    if spec["part"] not in _PARTS:
        raise SystemExit(f"date_part has no part {spec['part']!r}: it has "
                         f"{sorted(_PARTS)}")


def needs(spec):
    return [spec["of"]] + ([spec["plus"]] if "plus" in spec else [])


def column(spec, raw, cols, seed):
    date = cols[spec["of"]]
    if "plus" in spec:
        ymd = civil.civil_from_days(
            civil.days_from_ymd(date) + cols[spec["plus"]])
    else:
        ymd = civil.split_ymd(date)
    return _PARTS[spec["part"]](*ymd)


def _domain(spec):
    days = np.arange(civil.days_from_ymd(spec["first"]),
                     civil.days_from_ymd(spec["last"]) + 1)
    return np.unique(_PARTS[spec["part"]](*civil.civil_from_days(days)))


def domain_size(spec):
    return len(_domain(spec))


def value_of(spec, index):
    return _domain(spec)[index]


def index_of(spec, values):
    return np.searchsorted(_domain(spec), values)
