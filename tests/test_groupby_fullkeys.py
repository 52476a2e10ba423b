"""The FULL key space (ISSUE 36; engine/device.py KEY_SPACES,
ops/keysorted.py, ops/device_reduce.py select_top): a group-by over a large
key space that no filter slices is summed on the device from the batch's
rows in key order, into a table over the key space, and its top rows are
selected there - against benchmark/harness/reference.py, the host executor
and numpy, bit for bit, with NARROW_* as shipped.

The six statements of benchmark/traffic/ssb_fullkeys_6q_c4.json run on the
tiny table of benchmark/harness/testdata/ssb_fullkeys_tiny.json, through
the served path (HTTP -> broker -> server -> device executor) and through
the engine; the ties are counted out on a table of their own.
"""

import json
import os
import sys

import numpy as np
import pytest

from pinot_tpu.common.datatypes import DataType
from pinot_tpu.common.schema import Schema
from pinot_tpu.common.table_config import TableConfig
from pinot_tpu.engine.device import DeviceExecutor
from pinot_tpu.ops import device_reduce as dr
from pinot_tpu.storage.creator import build_segment
from pinot_tpu.storage.segment import ImmutableSegment

from test_groupby_narrowed import _build, _engine, _rows, _traced

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAMES = ["citypair_1997", "city_brand", "city_day", "supp_year",
         "supp_shipmode_disc", "year_city_brand_profit"]
CELLS = {"citypair_1997": 62_500, "city_brand": 250_000,
         "city_day": 601_500, "supp_year": 35_000,
         "supp_shipmode_disc": 35_000, "year_city_brand_profit": 1_750_000}


def _bench(*parts):
    with open(os.path.join(ROOT, "benchmark", *parts)) as f:
        return json.load(f)


def _harness(*names):
    sys.path.insert(0, os.path.join(ROOT, "benchmark"))
    try:
        import importlib

        return [importlib.import_module("harness." + n) for n in names]
    finally:
        sys.path.remove(os.path.join(ROOT, "benchmark"))


@pytest.fixture(scope="module")
def built(tmp_path_factory):
    """The tiny table's segments, built once, and the reference's rows by
    statement - exact, and with each planted control."""
    reference_mod, table = _harness("reference", "table")
    config = _bench("harness", "testdata", "ssb_fullkeys_tiny.json")
    traffic = _bench("traffic", "ssb_fullkeys_6q_c4.json")
    seed = 4_000_000_036
    work = str(tmp_path_factory.mktemp("served_fullkeys"))
    refs = {mode: reference_mod.Reference(config, traffic["statements"], mode)
            for mode in reference_mod.MODES}
    schema = Schema.from_json(config["schema"])
    table_config = TableConfig.from_json(config["table_config"])
    dirs = []
    for k in range(config["segments"]):
        cols = table.lay_out(config, table.draw_segment(
            config, table.segment_rng(seed, k), seed=seed, k=k))
        for ref in refs.values():
            ref.add({c: cols[c] for c in ref.columns})
        dirs.append(os.path.join(work, "server_0", "built", f"s{k}"))
        build_segment(schema, cols, dirs[-1], table_config, f"s{k}")
    want = {mode: ref.rows() for mode, ref in refs.items()}
    return config, traffic, want, reference_mod, dirs, work


@pytest.fixture(scope="module")
def served(built):
    cluster_mod, = _harness("cluster")
    config, traffic, want, reference_mod, dirs, work = built
    config = dict(config, deployment=dict(
        config["deployment"],
        device_executor=DeviceExecutor(mm_mode="interpret")))
    cluster = cluster_mod.Cluster(config, work)
    try:
        cluster.load(dirs, lambda line: None)
        yield cluster, traffic, want["exact"], reference_mod
    finally:
        cluster.close()


@pytest.fixture(scope="module")
def engines(built):
    _config, traffic, _want, _ref, dirs, _work = built
    tables = {"lineorder": [ImmutableSegment(d) for d in dirs]}
    sqls = {s["name"]: traffic["set_prefix"] + s["sql"]
            for s in traffic["statements"]}
    return {"device": _engine(tables, mm_mode="interpret"),
            "host": _engine(tables)}, sqls, tables


# ---- the mix, served: every row against the plain reference ---------------


@pytest.mark.parametrize("name", NAMES)
def test_statement_served_is_the_reference(served, name):
    """Twice: the first answer counts the template's live blocks and goes
    full; the second goes full at once. Both exact, on the device,
    complete, and not flagged by the raised numGroupsLimit."""
    from pinot_tpu import client

    cluster, traffic, want, reference_mod = served
    s = next(s for s in traffic["statements"] if s["name"] == name)
    assert traffic["set_prefix"].endswith("SET numGroupsLimit=4194304; ")
    conn = client.connect(cluster.url, timeout_s=300)
    before = cluster.executor.hbm_stats()
    for _ in range(2):
        cur = conn.cursor()
        cur.execute(traffic["set_prefix"] + s["sql"])
        rows = [list(r) for r in cur.fetchall()]
        assert reference_mod.answer_error(rows, want[name]) == 0.0, name
        assert len(rows) == s["reference"]["limit"] and rows[0][-1], name
        assert cur.stats.get("numSegmentsOnHost") == 0, name
        assert not cur.stats.get("partialResult"), name
        assert not cur.stats.get("numGroupsLimitReached"), name
    after = cluster.executor.hbm_stats()
    assert after["groupby_full_launches"] \
        == before["groupby_full_launches"] + 2
    assert after["groupby_narrowed_launches"] \
        == before["groupby_narrowed_launches"]
    assert after["groupby_key_space_probes"] \
        == before["groupby_key_space_probes"] + 1
    assert after["groupby_narrow_overflows"] == 0
    assert after["groupby_full_table_bytes"] >= 16 * CELLS[name]
    assert not any(cluster.failure_counters().values())


# ---- the regime, launch by launch -----------------------------------------


@pytest.mark.parametrize("name", NAMES)
def test_full_is_the_host_and_says_so(engines, built, name):
    """device == host == reference, and the launch's spans and flight
    record say what ran: the key space, its cells, the live cells, the
    operands and the selection."""
    (by, sqls, _tables), want, reference_mod = engines, built[2], built[3]
    host, _ = _rows(by["host"], sqls[name])
    for first in (True, False):
        got, resp, spans = _traced(by["device"], sqls[name])
        assert got == host, name
        assert reference_mod.answer_error(got, want["exact"][name]) == 0.0
        assert resp.get("numSegmentsOnHost", 0) == 0
        assert not resp.get("numGroupsLimitReached")
        dispatch, wait = (spans[p] for p in (
            "executor.dispatch", "executor.device_wait"))
        for attrs in (dispatch, wait):
            assert attrs["groupbyKeySpace"] == "full", (first, attrs)
            assert attrs["keySpaceCells"] == CELLS[name]
            # the embedded engine's answer is terminal: it keeps the LIMIT
            limit = len(want["exact"][name])
            assert attrs["trimSelect"] == \
                f"select:{1 << (limit - 1).bit_length()}"
            assert attrs["groupbyOperands"] in ("prepared", "built")
        assert 4096 < wait["keySpaceLive"] <= CELLS[name]
        rec = resp["roofline"][0]
        assert rec["kernel"] == "groupby_full+trim"
        assert (rec["groupbyKeySpace"], rec["keySpaceCells"]) \
            == ("full", CELLS[name])
        assert rec["keySpaceLive"] == wait["keySpaceLive"]
        assert rec["trimSelect"].startswith("select:")


def test_a_template_full_once_skips_pass_1(engines):
    """The choice is what the executor observed, remembered with the
    template and the batch: the first launch counts the live blocks (pass
    1 alone, a program of its own), later ones do not; no narrowed
    program is built and nothing goes to the host."""
    _by, sqls, tables = engines
    engine = _engine(tables, mm_mode="interpret")
    seen = []
    for _ in range(3):
        _got, resp = _rows(engine, sqls["supp_year"])
        s = engine.device.hbm_stats()
        seen.append((s["groupby_key_space_probes"],
                     s["groupby_narrowed_launches"],
                     s["groupby_full_launches"],
                     s["groupby_narrow_overflows"],
                     resp.get("numSegmentsOnHost", 0)))
    assert seen == [(1, 0, 1, 0, 0), (1, 0, 2, 0, 0), (1, 0, 3, 0, 0)], seen
    # a literal apart is the same template: counted once, full both times
    for year in (1997, 1993):
        sql = sqls["citypair_1997"].replace("1997", str(year))
        assert _rows(engine, sql)[0] == _rows(engines[0]["host"], sql)[0]
    s = engine.device.hbm_stats()
    assert (s["groupby_key_space_probes"], s["groupby_narrowed_launches"],
            s["groupby_full_launches"]) == (2, 0, 5)
    # no option, variable or argument chose it
    assert "full" not in " ".join(sqls.values()).lower()


def test_a_narrowed_template_stays_narrowed(engines):
    """SSB Q3.2 on the same batch, after the un-sliced statements went
    full: two nations leave 100 city pairs, and the launch is narrowed."""
    by, sqls, _tables = engines
    _rows(by["device"], sqls["citypair_1997"])
    q3_2 = next(s["sql"] for s in _bench(
        "traffic", "ssb_flat_13q_c4.json")["statements"]
        if s["name"] == "q3_2")
    before = by["device"].device.hbm_stats()
    for _ in range(2):
        got, resp, spans = _traced(by["device"], q3_2)
        assert got == _rows(by["host"], q3_2)[0] and got
        assert spans["executor.dispatch"]["groupbyKeySpace"] == "narrowed"
        assert spans["executor.device_wait"]["groupbyKeySpace"] == "narrowed"
        assert resp.get("numSegmentsOnHost", 0) == 0
    after = by["device"].device.hbm_stats()
    assert after["groupby_narrowed_launches"] \
        == before["groupby_narrowed_launches"] + 2
    assert after["groupby_full_launches"] == before["groupby_full_launches"]
    assert after["groupby_key_space_probes"] \
        == before["groupby_key_space_probes"] + 1


def test_the_default_numgroupslimit_behaves_as_before(engines):
    """Without the SET the engine's default (100,000) stands: city_brand
    holds more live groups than that, the trimmed table cannot say which
    the limit would have dropped, and the host answers with its own
    policy and flag, as before this regime."""
    by, sqls, _tables = engines
    sql = sqls["city_brand"].replace("SET numGroupsLimit=4194304; ", "")
    assert sql != sqls["city_brand"]
    got, resp = _rows(by["device"], sql)
    want, host_resp = _rows(by["host"], sql)
    assert got == want
    assert resp["numSegmentsOnHost"] == 8
    # the flag is the host's own (it applies the limit a segment, and no
    # segment of 75,000 rows passes it)
    assert resp.get("numGroupsLimitReached") \
        == host_resp.get("numGroupsLimitReached")
    # and raised, the device's own answer is not flagged
    _got, resp = _rows(by["device"], sqls["city_brand"])
    assert not resp.get("numGroupsLimitReached")
    assert resp.get("numSegmentsOnHost", 0) == 0


# ---- the controls still fail ----------------------------------------------


@pytest.mark.parametrize("mode", ["f32_partials", "drop_segment"])
def test_a_planted_control_fails_the_comparison(built, mode):
    """The reference with one guarantee broken, put in the program's
    place, is not correct on this mix: float32 partial sums round (a
    cell's revenue passes 2**24 with its second row), a dropped segment
    loses an eighth of every cell's rows."""
    _config, traffic, want, reference_mod, _dirs, _work = built
    records = [{"ok": True, "statement": name, "rows": want[mode][name]}
               for name in NAMES]
    verdict = reference_mod.compare(records, want["exact"])
    assert not verdict["correct"]
    assert verdict["numbers"]["answers_wrong"]["value"] >= 4
    exact = reference_mod.compare(
        [{"ok": True, "statement": name, "rows": want["exact"][name]}
         for name in NAMES], want["exact"])
    assert exact["correct"]


# ---- ties, counted out ----------------------------------------------------

TIE_A, TIE_B = 140, 256          # 35,840 cells, every one alive
TIE_RUN = 50                     # cells that share one sum


def _tie_columns():
    """One row a cell of a 140 x 256 key space; cell i's value falls by
    one every TIE_RUN cells, so each sum is shared by a run of 50 cells
    and any LIMIT that is no multiple of 50 ends inside a tie. ``k`` is
    the cell as ONE key column."""
    cell = np.arange(TIE_A * TIE_B)
    order = np.random.default_rng(36).permutation(len(cell))
    cell = cell[order]
    return {"a": (cell // TIE_B).astype(np.int32),
            "b": (cell % TIE_B).astype(np.int32),
            "k": cell.astype(np.int32),
            "v": (100_000 - cell // TIE_RUN).astype(np.int32)}


@pytest.fixture(scope="module")
def tie_engines(tmp_path_factory):
    base = tmp_path_factory.mktemp("fullkeys_ties")
    cols = _tie_columns()
    tables = {"w": _build(base, "w", cols, [(c, DataType.INT) for c in (
        "a", "b", "k")], ["v"])}
    return {"device": _engine(tables, mm_mode="interpret"),
            "host": _engine(tables)}


@pytest.mark.parametrize("order", ["DESC", "ASC"])
@pytest.mark.parametrize("limit", [
    10,                   # inside the first run of ties
    dr.SELECT_MAX_T,      # T itself: the edge of T inside a run of ties
    dr.SELECT_MAX_T - 1,  # the LIMIT's edge one short of T's
])
def test_a_tie_falls_to_the_cells_index(tie_engines, limit, order):
    """A tie in the aggregate falls to the cell's index, ascending, as the
    host's stable sort and the reference's lexsort have it: at the LIMIT's
    edge and at the edge of T, descending and ascending."""
    sql = ("SET numGroupsLimit=4194304; SELECT a, b, SUM(v) FROM w "
           f"GROUP BY a, b ORDER BY SUM(v) {order} LIMIT {limit}")
    got, resp, spans = _traced(tie_engines["device"], sql)
    assert got == _rows(tie_engines["host"], sql)[0]
    cells = np.arange(TIE_A * TIE_B)
    sums = 100_000 - cells // TIE_RUN
    first = np.lexsort((cells, -sums if order == "DESC" else sums))[:limit]
    assert [(r[0], r[1], int(r[2])) for r in got] == [
        (int(c) // TIE_B, int(c) % TIE_B, int(sums[c])) for c in first]
    assert limit % TIE_RUN  # the edge does lie inside a run
    assert spans["executor.device_wait"]["groupbyKeySpace"] == "full"
    assert spans["executor.device_wait"]["trimSelect"] \
        == f"select:{1 << (limit - 1).bit_length()}"
    assert spans["executor.device_wait"]["keySpaceLive"] == TIE_A * TIE_B
    assert resp.get("numSegmentsOnHost", 0) == 0


def test_one_key_past_the_floor_is_full_from_the_first(tie_engines):
    """One key column has no hierarchy to slice by: no pass 1, no narrowed
    launch; COUNT and AVG ride the same channels."""
    sql = ("SET numGroupsLimit=4194304; SELECT k, SUM(v), COUNT(*), AVG(v) "
           "FROM w WHERE v < 99900 GROUP BY k ORDER BY SUM(v) DESC LIMIT 75")
    before = tie_engines["device"].device.hbm_stats()
    got, resp, spans = _traced(tie_engines["device"], sql)
    assert got == _rows(tie_engines["host"], sql)[0] and len(got) == 75
    assert [r[0] for r in got] == list(range(101 * TIE_RUN,
                                            101 * TIE_RUN + 75))
    after = tie_engines["device"].device.hbm_stats()
    assert after["groupby_narrowed_launches"] \
        == before["groupby_narrowed_launches"]
    assert after["groupby_full_launches"] \
        == before["groupby_full_launches"] + 1
    assert spans["executor.dispatch"]["groupbyKeySpace"] == "full"
    assert spans["executor.dispatch"]["keySpaceCells"] == TIE_A * TIE_B
    assert spans["executor.device_wait"]["keySpaceLive"] \
        == TIE_A * TIE_B - 101 * TIE_RUN
    assert resp.get("numSegmentsOnHost", 0) == 0


def test_an_order_the_selection_declines_keeps_the_sort(tie_engines):
    """A key column before the aggregate, or two aggregates: the sort at
    table length, as before (PERF.md section 7 lists the shapes)."""
    for tail in ("ORDER BY a, SUM(v) DESC", "ORDER BY SUM(v) DESC, COUNT(*)",
                 "ORDER BY AVG(v) DESC"):
        sql = ("SET numGroupsLimit=4194304; SELECT a, b, SUM(v), COUNT(*), "
               f"AVG(v) FROM w GROUP BY a, b {tail} LIMIT 60")
        got, _resp, spans = _traced(tie_engines["device"], sql)
        assert got == _rows(tie_engines["host"], sql)[0], tail
        assert spans["executor.device_wait"]["groupbyKeySpace"] == "full"
        assert spans["executor.device_wait"]["trimSelect"] == "sort:64"


# ---- the selection alone, and the planes' width ---------------------------


@pytest.mark.parametrize("cells,keep,spread", [
    (20_000, 64, 50), (20_000, 8192, 3), (70_000, 1024, 1 << 40),
    (9_000, 8192, 2), (40_000, 1, 1)])
def test_select_top_is_the_sort(cells, keep, spread):
    import jax
    import jax.numpy as jnp

    rng = np.random.default_rng(cells + keep)
    rank = rng.integers(-spread, spread + 1, cells).astype(np.int64)
    rank[rng.random(cells) < 0.3] = -(1 << 62)   # empty cells
    got = np.asarray(jax.jit(lambda r: dr.select_top(r, keep))(
        jnp.asarray(rank)))
    want = np.lexsort((np.arange(cells), -rank))[:keep]
    assert (got == want).all()


def test_plane_bits_keep_a_cells_sum_under_2_32():
    from pinot_tpu.ops import keysorted as ks

    assert [ks.plane_bits_for(n) for n in (
        1, 255, 256, 65_535, 65_536, (1 << 24) - 1, 1 << 24)] \
        == [24, 24, 16, 16, 8, 8, 0]
    for rows in (1, 255, 256, 65_535, 65_536, (1 << 24) - 1):
        bits = ks.plane_bits_for(rows)
        assert rows * ((1 << bits) - 1) < 1 << 32


# ---- the configuration ----------------------------------------------------


def test_ssb_sf100_fullkeys_holds_chipshares_table():
    """The new configuration's table is ssb_sf100_chipshare's, key by key;
    its own are the name, the source, the why and what it assumes."""
    ours = _bench("configs", "ssb_sf100_fullkeys.json")
    theirs = _bench("configs", "ssb_sf100_chipshare.json")
    for key in ("generator", "schema", "table_config", "scale", "segments",
                "rows_per_segment", "layout", "deployment", "guarantees",
                "reduced", "table"):
        assert ours[key] == theirs[key], key
    assert ours["name"] == "ssb_sf100_fullkeys" != theirs["name"]
    assert set(theirs["assumed"]) < set(ours["assumed"])
    assert len(ours["source"]) <= 200 and ours["why"]
    entry = next(c for c in _bench("..", "BENCHMARK.json")["configs"]
                 if c["name"] == "ssb_sf100_fullkeys")
    assert entry["source"] == ours["source"]
    assert entry["reduced"] == ["lineorder_rows"]
    tiny = _bench("harness", "testdata", "ssb_fullkeys_tiny.json")
    for key in ("deployment", "guarantees", "table", "layout", "schema",
                "table_config"):
        assert ours[key] == tiny[key], key


def test_the_mix_is_the_issues():
    """Callers, deck, prefix and the six statements' shapes."""
    mix = _bench("traffic", "ssb_fullkeys_6q_c4.json")
    assert (mix["loop"], mix["clients"], mix["warmup_seconds"]) \
        == ("closed", 4, 3)
    assert mix["think_ms"] == [0, 8, 16, 24, 32, 40, 48, 56]
    assert mix["set_prefix"] == (
        "SET useResultCache=false; SET usePartialsCache=false; "
        "SET timeoutMs=60000; SET numGroupsLimit=4194304; ")
    assert [s["name"] for s in mix["statements"]] == NAMES
    for s in mix["statements"]:
        assert s["weight"] == 1 and s["device"] is True
        assert s["sql"].startswith("SET useStarTree = false; SELECT ")
        assert s["reference"]["order_by"] == [["agg", 0, "desc"]]
        assert 2 <= len(s["reference"]["group_by"]) <= 3
        assert f"LIMIT {s['reference']['limit']}" in s["sql"]
    assert [s["reference"]["limit"] for s in mix["statements"]] \
        == [100, 100, 20, 10, 10, 100]
