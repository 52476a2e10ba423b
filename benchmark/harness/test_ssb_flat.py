"""SSB flat at a tiny size, as data (``testdata/ssb_flat_tiny.json`` and
``traffic/ssb_flat_13q_c4.json``): dbgen's shapes hold row by row, the
reference answers Q1.1-Q4.3 as sqlite does, the controls come out not
correct, and the date's parts follow a table cut by date."""

import copy
import datetime
import json
import os
import sqlite3

import numpy as np
import pytest

from harness import algbytes, civil, reference, spec, table

HERE = os.path.dirname(os.path.abspath(__file__))
# seeds at which no flight is empty: at SF 0.1 Q3.3 and Q3.4 select some tens
# of rows and none at one seed in three
SEEDS = (7, 4_000_000_012)


def _json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


CONFIG = _json(HERE, "testdata", "ssb_flat_tiny.json")
STATEMENTS = _json(spec.BENCH_DIR, "traffic",
                   "ssb_flat_13q_c4.json")["statements"]


def _table(config, seed, only=None):
    parts = list(table.reference_segments(config, seed, only))
    return {name: np.concatenate([p[name] for p in parts])
            for name in parts[0]}


def _answers(config, seed, mode="exact"):
    ref = reference.Reference(config, STATEMENTS, mode)
    for cols in table.reference_segments(config, seed, ref.columns):
        ref.add(cols)
    return ref.rows()


@pytest.fixture(scope="module", params=SEEDS)
def rows(request):
    return request.param, _table(CONFIG, request.param)


def test_the_calendar_is_the_real_one():
    days = np.arange(-800, 12000)
    y, m, d = civil.civil_from_days(days)
    assert (civil.days_from_civil(y, m, d) == days).all()
    for z in (0, 8035, 9555, 10440, 11016):  # 1992-01-01, 1996-02-29, ...
        date = datetime.date.fromordinal(z + 719163)
        assert (y[z + 800], m[z + 800], d[z + 800]) == (
            date.year, date.month, date.day)
    assert civil.days_from_ymd(19981231) - civil.days_from_ymd(19920101) \
        == 2556  # the calendar's 2,557 days


def test_the_table_has_lineorders_17_columns_and_the_13_attributes():
    names = [f["name"] for kind in ("dimensionFieldSpecs", "metricFieldSpecs")
             for f in CONFIG["schema"][kind]]
    drawn = [g["column"] for g in CONFIG["generator"] if not g.get("helper")]
    assert sorted(names) == sorted(drawn) and len(drawn) == 30
    assert sum(n.startswith("lo_") for n in drawn) == 17
    assert CONFIG["segments"] * CONFIG["rows_per_segment"] \
        == 6_000_000 * CONFIG["scale"]["sf"]
    used = set().union(*(s["columns"] for s in STATEMENTS))
    assert {n for n in drawn if not n.startswith("lo_")} <= used


def test_hierarchies_hold_row_by_row(rows):
    _seed, t = rows
    for p in "cs":
        city, nation, region = t[p + "_city"], t[p + "_nation"], \
            t[p + "_region"]
        # a city is its nation's first nine letters, padded, and a digit
        assert (np.char.ljust(np.char.ljust(nation, 9).astype("U9"), 9)
                == city.astype("U9")).all()
        assert set(np.char.ljust(city, 10).astype("U10").view("U1")
                   .reshape(-1, 10)[:, 9]) <= set("0123456789")
        regions = {}
        for n, r in zip(nation.tolist(), region.tolist()):
            assert regions.setdefault(n, r) == r
        assert len(regions) <= 25
        per_region = {}
        for n, r in regions.items():
            per_region.setdefault(r, set()).add(n)
        assert all(len(v) <= 5 for v in per_region.values())
    brand, category, mfgr = t["p_brand1"], t["p_category"], t["p_mfgr"]
    assert (brand.astype("U7") == category).all()
    assert (category.astype("U6") == mfgr).all()
    # a key has one city, a part one brand: the dimension is a function
    for key, attr in (("lo_custkey", "c_city"), ("lo_suppkey", "s_city"),
                      ("lo_partkey", "p_brand1")):
        pairs = {(k, a) for k, a in zip(t[key].tolist(), t[attr].tolist())}
        assert len(pairs) == len({k for k, _ in pairs})
    date = t["lo_orderdate"]
    assert (t["d_year"] == date // 10000).all()
    assert (t["d_yearmonthnum"] == date // 100).all()
    first = datetime.date.fromordinal(
        int(civil.days_from_ymd(int(date[0]))) + 719163)
    assert t["d_yearmonth"][0] == first.strftime("%b%Y")
    assert t["d_weeknuminyear"][0] == (first.timetuple().tm_yday - 1) // 7 + 1
    lag = civil.days_from_ymd(t["lo_commitdate"]) - civil.days_from_ymd(date)
    assert 30 <= lag.min() and lag.max() <= 90
    pk = t["lo_partkey"].astype(np.int64)
    price = 90000 + (pk // 10) % 20001 + 100 * (pk % 1000)
    assert (t["lo_extendedprice"] == t["lo_quantity"] * price).all()
    assert (t["lo_revenue"] == t["lo_extendedprice"].astype(np.int64)
            * (100 - t["lo_discount"]) // 100).all()
    assert (t["lo_supplycost"] == 6 * price // 10).all()


def test_marginals_are_ssbs(rows):
    _seed, t = rows
    sizes = {c: table.domain_size(CONFIG, c) for c in t}
    assert [sizes[c] for c in ("c_region", "c_nation", "c_city")] \
        == [sizes[c] for c in ("s_region", "s_nation", "s_city")] \
        == [5, 25, 250]
    assert [sizes[c] for c in ("p_mfgr", "p_category", "p_brand1")] \
        == [5, 25, 1000]
    assert [sizes[c] for c in ("lo_shipmode", "lo_orderpriority", "d_year",
                               "d_yearmonthnum", "d_yearmonth")] \
        == [7, 5, 7, 80, 80]
    for col, lo, hi in (("lo_quantity", 1, 50), ("lo_discount", 0, 10),
                        ("lo_tax", 0, 8), ("lo_linenumber", 1, 7),
                        ("lo_orderdate", 19920101, 19980802),
                        ("d_weeknuminyear", 1, 53),
                        ("lo_custkey", 1, 3000), ("lo_suppkey", 1, 200),
                        ("lo_partkey", 1, 20000)):
        assert (t[col].min(), t[col].max()) == (lo, hi), col
    # every value is live and none is twice as likely as another
    for col in ("c_city", "p_brand1", "c_nation", "s_region", "lo_discount"):
        counts = np.unique(t[col], return_counts=True)[1]
        assert len(counts) == sizes[col], col
    assert np.unique(t["lo_discount"], return_counts=True)[1].std() < 600


def _sqlite(t):
    db = sqlite3.connect(":memory:")
    names = list(t)
    db.execute(f"CREATE TABLE lineorder ({', '.join(names)})")
    db.executemany(
        f"INSERT INTO lineorder VALUES ({', '.join('?' * len(names))})",
        zip(*(t[n].tolist() for n in names)))
    return db


def test_the_reference_answers_as_sqlite_does(rows):
    seed, t = rows
    got = _answers(CONFIG, seed)
    db = _sqlite(t)
    for s in STATEMENTS:
        sql = s["sql"].split(";")[-1]
        if s["reference"]["group_by"]:
            # the reference's ties fall to the group key, ascending
            sql = sql.replace(" LIMIT", ", " + ", ".join(
                s["reference"]["group_by"]) + " LIMIT")
        want = [list(r) for r in db.execute(sql).fetchall()]
        assert got[s["name"]] == want, s["name"]
        assert want and want[0][-1], s["name"]  # no flight is empty here
    # no two statements have one answer: a reply handed to the wrong caller
    # shows
    assert len({json.dumps(r) for r in got.values()}) == len(STATEMENTS)


@pytest.mark.parametrize("seed", [1, 2, 5_000_000_007])
def test_controls_come_out_not_correct_on_every_flight(seed):
    want = _answers(CONFIG, seed)
    for mode in ("f32_partials", "drop_segment"):
        got = _answers(CONFIG, seed, mode)
        wrong = {name for name in want if reference.answer_error(
            got[name], want[name])}
        for flight in "1234":
            assert any(n.startswith("q" + flight) for n in wrong), (
                mode, flight)
        verdict = reference.compare(
            [{"ok": True, "statement": n, "rows": r} for n, r in got.items()],
            want)
        assert not verdict["correct"]


def test_sums_stay_exact_past_float64s_ceiling():
    rng = np.random.default_rng(5)
    cell = rng.integers(0, 3, 4000)
    weights = rng.integers(-(1 << 45), 1 << 45, 4000)  # x 4000 > 2**53
    want = [sum(int(w) for w, c in zip(weights, cell) if c == k)
            for k in range(3)]
    assert reference._exact_sums(cell, weights, 3).tolist() == want
    small = rng.integers(0, 1000, 4000)
    assert reference._exact_sums(cell, small, 3).tolist() == [
        int(small[cell == k].sum()) for k in range(3)]


def test_a_dates_parts_follow_a_table_cut_by_date():
    config = copy.deepcopy(CONFIG)
    config["layout"] = {"kind": "by_date", "column": "lo_orderdate"}
    years = []
    for k in range(config["segments"]):
        cols = table.lay_out(config, table.draw_segment(
            config, table.segment_rng(3, k), seed=3, k=k))
        date = cols["lo_orderdate"]
        assert (np.diff(date) >= 0).all()
        lo, hi = table.segment_range(config, k, "lo_orderdate")
        assert lo <= date.min() and date.max() <= hi
        assert (cols["d_year"] == date // 10000).all()
        assert (cols["d_yearmonthnum"] == date // 100).all()
        years.append(table.segment_range(config, k, "d_year"))
        assert years[-1] == (lo // 10000, hi // 10000)
        assert table.segment_range(config, k, "lo_discount") is None
        assert table.segment_range(config, k, "lo_commitdate") is None
        # the marginals of what the date does not touch stay
        plain = table.draw_segment(config, table.segment_rng(3, k), seed=3)
        assert (np.sort(plain["lo_revenue"])
                == np.sort(cols["lo_revenue"])).all()
    # 2,406 days over 8 segments: shares of 300 or 301 days, in order
    assert years[0][0] == 1992 and years[-1][1] == 1998
    by_name = {s["name"]: s for s in STATEMENTS}
    assert algbytes.segments_read(config, by_name["q1_1"]) == sum(
        lo <= 1993 <= hi for lo, hi in years) < 8
    assert algbytes.segments_read(config, by_name["q2_1"]) == 8
    # the reference draws what it needs, the layout column with it
    assert _answers(config, 3)["q1_1"] != [[0]]
