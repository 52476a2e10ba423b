"""The plain reference, its controls and the arithmetic around them, at a
size a test run can hold. The controls — the reference put in the program's
place with one stated guarantee broken — must come out as not correct."""

import json
import os

import numpy as np
import pytest

from harness import algbytes, reference, spec, table

HERE = os.path.dirname(os.path.abspath(__file__))


def _json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def _config(name):
    return _json(HERE, "testdata", name + ".json")


def _statements(*mixes):
    return [s for m in mixes
            for s in _json(spec.BENCH_DIR, "traffic", m + ".json")["statements"]]


def _answers(config, statements, seed, mode):
    ref = reference.Reference(config, statements, mode)
    for cols in table.reference_segments(config, seed, ref.columns):
        ref.add(cols)
    return ref.rows()


def _records(rows_by_statement):
    return [{"ok": True, "statement": name, "rows": rows}
            for name, rows in rows_by_statement.items()]


def test_generator_is_the_programs_table():
    ssb = pytest.importorskip("pinot_tpu.tools.ssb")
    config = _config("ssb_tiny")
    mine = table.draw_segment(config, np.random.default_rng(7))
    theirs = ssb.segment_columns(np.random.default_rng(7),
                                 config["rows_per_segment"])
    assert list(mine) == list(theirs)
    for name in mine:
        assert mine[name].dtype == theirs[name].dtype
        assert (mine[name] == theirs[name]).all()


def test_reference_answers_by_hand():
    config = _config("ssb_tiny")
    statements = _statements("groupby_bands_c4", "range_years_c4",
                             "range_bands_c4")
    rows = _answers(config, statements, 11, "exact")
    assert len(rows) == len(statements) == 15
    cols = [table.draw_segment(config, table.segment_rng(11, k))
            for k in range(config["segments"])]
    for a, b in ((1, 3), (4, 6), (5, 7), (8, 10)):
        sums = {}
        for c in cols:
            m = (c["lo_discount"] >= a) & (c["lo_discount"] <= b)
            for k, v in zip(c["lo_suppkey"][m].tolist(),
                            c["lo_revenue"][m].tolist()):
                sums[k] = sums.get(k, 0) + v
        top = sorted(sums.items(), key=lambda kv: (-kv[1], kv[0]))[:10]
        assert rows[f"groupby_disc_{a}_{b}"] == [list(kv) for kv in top]
    for year in range(1992, 1999):
        total = 0
        for c in cols:
            m = ((c["lo_orderdate"] >= year * 10000 + 101)
                 & (c["lo_orderdate"] <= year * 10000 + 1231)
                 & (c["lo_discount"] >= 1) & (c["lo_discount"] <= 3)
                 & (c["lo_quantity"] < 25))
            total += sum(c["lo_revenue"][m].tolist())
        assert rows[f"range_sum_{year}"] == [[total]]
    for a, b, q in ((1, 3, 25), (4, 6, 35), (5, 7, 30), (8, 10, 20)):
        total = 0
        for c in cols:
            m = ((c["lo_orderdate"] >= 19930101)
                 & (c["lo_orderdate"] <= 19931231)
                 & (c["lo_discount"] >= a) & (c["lo_discount"] <= b)
                 & (c["lo_quantity"] < q))
            total += sum(c["lo_revenue"][m].tolist())
        assert rows[f"range_sum_d{a}_{b}_q{q}"] == [[total]]
    # no two statements of a mix have one answer: a reply handed to the
    # wrong caller shows
    for mix in ("groupby_bands_c4", "range_years_c4", "range_bands_c4"):
        answers = [json.dumps(rows[s["name"]]) for s in _statements(mix)]
        assert len(set(answers)) == len(answers)


def test_by_date_cuts_segments_by_date_and_keeps_the_marginals():
    config = _config("ssb_tiny_bydate")
    spans = []
    for k in range(config["segments"]):
        plain = table.draw_segment(config, table.segment_rng(3, k))
        before = {n: np.sort(v) for n, v in plain.items()}
        laid = table.lay_out(config, table.draw_segment(
            config, table.segment_rng(3, k), seed=3, k=k))
        d = laid["lo_orderdate"]
        assert (np.diff(d) >= 0).all()
        lo, hi = table.segment_range(config, k, "lo_orderdate")
        assert lo <= d.min() and d.max() <= hi
        spans.append((lo, hi))
        for n in laid:
            if n != "lo_orderdate":
                assert (np.sort(laid[n]) == before[n]).all()
    assert all(a[1] < b[0] for a, b in zip(spans, spans[1:]))
    # every year of the mix meets two of the eight segments
    for q in _statements("range_years_c4", "range_bands_c4"):
        assert algbytes.segments_read(config, q) == 2


def test_algorithmic_bytes():
    plain, bydate = _config("ssb_tiny"), _config("ssb_tiny_bydate")
    q1 = _statements("groupby_bands_c4")[0]
    q2 = _statements("range_years_c4")[1]
    widths = {c: algbytes.column_width_bytes(plain, c) for c in
              ("lo_suppkey", "lo_revenue", "lo_orderdate", "lo_discount",
               "lo_quantity")}
    assert widths == {"lo_suppkey": 2, "lo_revenue": 3, "lo_orderdate": 2,
                      "lo_discount": 1, "lo_quantity": 1}
    rows = plain["segments"] * plain["rows_per_segment"]
    assert algbytes.statement_bytes(plain, q1) == rows * 6
    assert algbytes.statement_bytes(plain, q2) == rows * 7
    assert algbytes.statement_bytes(bydate, q2) == rows * 7 // 4


@pytest.mark.parametrize("seed", [1, 2, 5_000_000_007])
@pytest.mark.parametrize("mix,config", [("groupby_bands_c4", "ssb_tiny"),
                                        ("range_bands_c4", "ssb_tiny_bydate"),
                                        ("range_years_c4", "ssb_tiny_bydate")])
def test_controls_come_out_not_correct(mix, config, seed):
    config, statements = _config(config), _statements(mix)
    want = _answers(config, statements, seed, "exact")
    sound = reference.compare(_records(want), want)
    assert sound["correct"] and not sound["numbers"]["max_abs_err"]["value"]
    for mode in ("f32_partials", "drop_segment"):
        got = _answers(config, statements, seed, mode)
        verdict = reference.compare(_records(got), want)
        assert not verdict["correct"], mode
        # by date the segment left out holds rows of some years only
        wrong = verdict["numbers"]["answers_wrong"]["value"]
        assert wrong == len(statements) or (
            mode == "drop_segment" and mix == "range_years_c4" and wrong)
        assert verdict["numbers"]["max_abs_err"]["value"] > 0


def test_an_answer_that_never_came_is_not_correct():
    want = {"q": [[1, 2]]}
    recs = [{"ok": True, "statement": "q", "rows": [[1, 2]]},
            {"ok": False, "statement": "q", "error": "timeout"}]
    verdict = reference.compare(recs, want)
    assert not verdict["correct"]
    assert verdict["numbers"]["answers_missing"]["value"] == 1
    assert not reference.compare([], want)["correct"]
    # an answer that the host executor gave for a device statement is
    # counted whether or not the request was traced
    assert verdict["numbers"]["off_device"] == {"value": 0, "limit": 0}
    on_host = reference.compare([dict(recs[0], off_device=True)], want)
    assert not on_host["correct"]
    assert on_host["numbers"]["off_device"] == {"value": 1, "limit": 0}
    assert not on_host["numbers"]["answers_wrong"]["value"]
    assert reference.answer_error([[1, 2], [3, 4]], [[1, 2]]) == float("inf")
    assert reference.answer_error([[1, 2.5]], [[1, 2]]) == 0.5


def test_a_device_kind_without_peaks_is_an_error():
    assert spec.peaks("TPU v5 lite")["hbm_gbytes_per_s"] == 819
    with pytest.raises(SystemExit):
        spec.peaks("cpu")
