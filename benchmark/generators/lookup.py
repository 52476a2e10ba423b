"""``lookup``: the value of a table at ``(cols[of] - base) // div``, drawn
by nothing — a dimension joined onto the row, or one level of a hierarchy
read off the level below. The table is either

  ``values``  listed: dbgen's fixed hierarchies. A city id // 10 is its
              nation (10 cities a nation: the nation's first 9 letters and
              a digit), a nation's region is listed (5 nations a region), a
              brand id // 40 is its category and // 200 its manufacturer
              (40 brands a category, 5 categories a manufacturer), or

  ``seeded``  ``{"rows": R, "low": L, "high": H}``: R whole numbers drawn
              uniformly from [L, H) once a run, from the run's seed and the
              column's name — a dimension table's attribute by key, the
              same in every segment and in the reference: dbgen gives each
              of 30,000 x SF customers and 2,000 x SF suppliers a nation
              and a city digit so, and each part a brand.

``of`` has to hold whole numbers (mark an id nobody queries ``"helper":
true`` and the table has no such column). The domain is the table's distinct
values, ascending."""

import zlib

import numpy as np


def _table(spec, seed):
    if "values" in spec:
        values = np.array(spec["values"])
        return values.astype(np.int32) if values.dtype.kind == "i" else values
    s = spec["seeded"]
    rng = np.random.default_rng(
        [seed, zlib.crc32(spec["column"].encode()), s["rows"]])
    return rng.integers(s.get("low", 0), s["high"], s["rows"]
                        ).astype(np.int32)


def check(spec):
    if ("values" in spec) == ("seeded" in spec):
        raise SystemExit(f"lookup {spec['column']!r} states one table: "
                         "`values` or `seeded`")


def needs(spec):
    return [spec["of"]]


def column(spec, raw, cols, seed):
    index = cols[spec["of"]].astype(np.int64) - spec.get("base", 0)
    return _table(spec, seed)[index // spec.get("div", 1)]


def _domain(spec):
    if "values" in spec:
        return np.unique(_table(spec, 0))
    s = spec["seeded"]
    return np.arange(s.get("low", 0), s["high"])


def domain_size(spec):
    return len(_domain(spec))


def value_of(spec, index):
    return _domain(spec)[index]


def index_of(spec, values):
    return np.searchsorted(_domain(spec), values)
