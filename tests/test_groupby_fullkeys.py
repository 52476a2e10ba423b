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
# the fullest cell's rows, to the sublane tile, where the projected planes
# are laid out cell by slot. The tiny table's 600,000 rows (655,360 as the
# lanes hold them) fill 62,500 and 35,000 cells 38, 39 and 36 deep: 40
# slots a cell are x3.8 and x2.1 of the rows, within FULL_SLOT_PADDING.
# Its larger key spaces are mostly EMPTY here (at 37.5M rows they pad
# x1.4 to x2.2: PERF.md §6): 24 x 250,000 slots are x9.2. At 250,000
# cells 8 slots a cell and the cells past 8 rows in chunks of 8 (SPILLED)
# fit the bound; at 601,500 and 1.75M cells not even 8 slots a cell do,
# and those two statements keep the ordered planes and the cumulative sums.
SLOT_ROWS = {"citypair_1997": 40, "supp_year": 40, "supp_shipmode_disc": 40}
SPILL_ROWS = {"city_brand": 8}


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
    assert after["groupby_slotted_launches"] \
        == before["groupby_slotted_launches"] + 2 * (name in SLOT_ROWS)
    if name in SLOT_ROWS:  # a value, a segment and maybe a filter plane
        assert after["groupby_slotted_bytes"] \
            >= 5 * SLOT_ROWS[name] * CELLS[name]
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
            assert (attrs["groupbyKeyLayout"], attrs.get("slotRows")) == (
                ("slotted", SLOT_ROWS[name]) if name in SLOT_ROWS
                else ("spilled", SPILL_ROWS[name]) if name in SPILL_ROWS
                else ("ordered", None)), (name, attrs)
        assert 4096 < wait["keySpaceLive"] <= CELLS[name]
        rec = resp["roofline"][0]
        assert rec["kernel"] == "groupby_full+trim"
        assert (rec["groupbyKeySpace"], rec["keySpaceCells"]) \
            == ("full", CELLS[name])
        assert rec["groupbyKeyLayout"] == dispatch["groupbyKeyLayout"]
        assert rec.get("slotRows") == SLOT_ROWS.get(
            name, SPILL_ROWS.get(name))
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


# ---- the two layouts of the projected planes -------------------------------

LAYOUT_CELLS = (6, 50)           # 300 cells: the slotted lane axis pads to 384
LAYOUT_CASES = {
    # rows of each cell
    "uniform": lambda rng: rng.integers(20, 41, 300),
    "skewed": lambda rng: np.where(np.arange(300) == 123, 3_000,
                                   rng.integers(0, 6, 300)),
    "empty_cells": lambda rng: np.where(np.arange(300) % 3 == 0, 0,
                                        rng.integers(1, 30, 300)),
    # the fullest cell fills every one of its K slots; so does cell 0, and
    # the last cell, whose slots the batch's padding rows follow
    "a_cell_of_exactly_k_rows": lambda rng: np.where(
        np.isin(np.arange(300), (0, 77, 299)), 40, rng.integers(0, 40, 300)),
}


@pytest.mark.parametrize("bits", [8, 16, 24])
@pytest.mark.parametrize("case", sorted(LAYOUT_CASES))
def test_slotted_sums_are_the_ordered_sums_are_numpy(case, bits):
    """ops/keysorted.py, both layouts from one key order: a count and a
    24-bit value in planes of ``bits``, under a band filter, a dead
    segment (``ps_alive``) and the batch's padding rows, cell by cell
    against numpy."""
    import jax.numpy as jnp

    from pinot_tpu.ops import keysorted as ks

    rng = np.random.default_rng(len(case) * 100 + bits)
    per_cell = LAYOUT_CASES[case](rng)
    cells = len(per_cell)
    gid = rng.permutation(np.repeat(np.arange(cells), per_cell))
    n = len(gid)
    n_pad = -(-(n + 77) // 128) * 128      # 77 or more padding rows
    ids = []
    for div, card in ((LAYOUT_CELLS[1], LAYOUT_CELLS[0]),
                      (1, LAYOUT_CELLS[1])):
        plane = np.full(n_pad, card, np.int32)  # padding: the cardinality
        plane[:n] = gid // div % card
        ids.append(jnp.asarray(plane.reshape(-1, 128)))
    value = rng.integers(0, 1 << 24, n_pad).astype(np.uint32)
    filt = rng.integers(0, 11, n_pad).astype(np.uint8)
    seg = rng.integers(0, 3, n_pad).astype(np.uint8)
    alive = np.array([True, False, True])

    perm, starts, fullest = ks.key_order(tuple(ids), cards=LAYOUT_CELLS)
    assert fullest == per_cell.max()
    assert (np.diff(np.asarray(starts)) == per_cell).all()
    k = ks.slot_rows(fullest)
    assert k % 8 == 0 and 0 <= k - fullest < 8
    if case == "a_cell_of_exactly_k_rows":
        assert k == fullest
    shape = (k, ks.slot_lanes(cells))
    assert shape[1] == 384
    n_split = -(-24 // bits)

    def channels(v, f, sg, keep):
        keep = keep & (f >= 1) & (f <= 3) & jnp.asarray(alive)[sg]
        v = jnp.where(keep, v, jnp.uint32(0))
        return [keep.astype(jnp.uint32)] + [
            (v >> (bits * j)) & ((1 << bits) - 1) for j in range(n_split)]

    ordered = [jnp.asarray(x).reshape(-1)[perm].reshape(-1, 128)
               for x in (value, filt, seg)]
    at = jnp.arange(n_pad, dtype=jnp.int32).reshape(-1, 128)
    by_order = np.asarray(ks.segment_sums(
        starts, channels(*ordered, at < starts[cells])))
    slotted = [ks.slot_plane(x, starts, k=k) for x in ordered]
    assert all(x.shape == shape and x.dtype == o.dtype
               for x, o in zip(slotted, ordered))
    by_slot = np.asarray(ks.slot_sums(
        channels(*slotted, ks.slot_mask(starts, shape)), cells))

    keep = (filt[:n] >= 1) & (filt[:n] <= 3) & alive[seg[:n]]
    want = [np.bincount(gid[keep], minlength=cells)] + [
        np.bincount(gid[keep], weights=(
            (value[:n][keep] >> (bits * j)) & ((1 << bits) - 1)).astype(
                np.float64), minlength=cells).astype(np.int64)
        for j in range(n_split)]
    want = np.stack(want)
    assert by_slot.shape == by_order.shape == want.shape
    assert (by_slot == by_order).all()
    # a channel is summed modulo 2^32: a plane as wide as the fullest
    # cell allows (plane_bits_for) never wraps, a wider one may
    assert (by_slot == want % (1 << 32)).all()
    if bits <= ks.plane_bits_for(fullest):
        assert (by_slot == want).all()
        total = sum(by_slot[1 + j].astype(np.int64) << (bits * j)
                    for j in range(n_split))
        assert (total == np.bincount(
            gid[keep], weights=value[:n][keep].astype(np.float64),
            minlength=cells).astype(np.int64)).all()
    assert want[0].sum() > 0
    assert (want[0] == 0).any() or case == "uniform"


SLOT_A, SLOT_B = 225, 200        # 45,000 cells, c % 9 rows in cell c
SLOT_SQL = ("SET numGroupsLimit=4194304; SELECT a, b, SUM(v), COUNT(*) "
            "FROM {} WHERE f BETWEEN 1 AND 3 GROUP BY a, b "
            "ORDER BY SUM(v) DESC LIMIT 50")


def _slot_columns(fat=0):
    """Cell c of a 225 x 200 key space holds c % 9 rows - a ninth of the
    cells are empty, a ninth hold exactly the 8 rows that are K - and,
    with ``fat``, one cell that many more; shuffled over two segments."""
    rng = np.random.default_rng(37 + fat)
    cell = np.repeat(np.arange(SLOT_A * SLOT_B), np.arange(SLOT_A * SLOT_B) % 9)
    cell = rng.permutation(np.concatenate([cell, np.full(fat, 4_321)]))
    n = len(cell)
    return {"a": (cell // SLOT_B).astype(np.int32),
            "b": (cell % SLOT_B).astype(np.int32),
            "f": rng.integers(0, 11, n).astype(np.int32),
            "v": rng.integers(0, 1 << 20, n).astype(np.int32)}


@pytest.fixture(scope="module")
def slot_engines(tmp_path_factory):
    base = tmp_path_factory.mktemp("fullkeys_slots")
    dims = [(c, DataType.INT) for c in ("a", "b", "f")]
    cols = {"u": _slot_columns(), "sk": _slot_columns(fat=6_000)}
    tables = {name: _build(base, name, c, dims, ["v"])
              for name, c in cols.items()}
    return {"device": _engine(tables, mm_mode="interpret"),
            "host": _engine(tables)}, tables, cols


def _ctx_of(engine, table_segments):
    """The device batch that holds ``table_segments``."""
    names = {s.name for s in table_segments}
    return next(ctx for ctx in engine.device._batches.values()
                if {s.name for s in ctx.segments} == names)


@pytest.mark.parametrize("table,layout,slot_rows", [
    ("u", "slotted", 8),
    # one cell of 6,000 rows among cells of 0-8: 6,000 slots a cell would
    # be 1,400 times the batch's rows; 8 slots a cell and the fat cell's
    # 5,993 more rows in 750 chunks of 8 (six lane tiles) are x1.9
    ("sk", "spilled", 8)])
def test_the_layout_follows_the_fullest_cell(slot_engines, table, layout,
                                             slot_rows):
    """Uniform cells are laid out cell by slot, a skewed key past the
    padding bound is slotted at a K near the mean with its fat cell's
    excess rows in chunks, each says so, and both are the host's answer:
    no option, variable or argument chose."""
    from pinot_tpu.engine import device as device_mod
    from pinot_tpu.ops import keysorted as ks

    by, tables, _cols = slot_engines
    sql = SLOT_SQL.format(table)
    before = by["device"].device.hbm_stats()
    for _ in range(2):
        got, resp, spans = _traced(by["device"], sql)
        assert got == _rows(by["host"], sql)[0] and len(got) == 50
        for phase in ("executor.dispatch", "executor.device_wait"):
            attrs = spans[phase]
            assert attrs["groupbyKeySpace"] == "full"
            assert attrs["groupbyKeyLayout"] == layout, attrs
            assert attrs.get("slotRows") == slot_rows
        assert resp.get("numSegmentsOnHost", 0) == 0
    after = by["device"].device.hbm_stats()
    assert after["groupby_full_launches"] \
        == before["groupby_full_launches"] + 2
    for form in ("slotted", "spilled"):
        assert after[f"groupby_{form}_launches"] \
            == before[f"groupby_{form}_launches"] + 2 * (layout == form)
    ctx = _ctx_of(by["device"], tables[table])
    fullest = ctx.key_order_rows(("a", "b"))
    assert fullest == (8 if table == "u" else 6_001)
    slots = ks.slot_rows(fullest) * ks.slot_lanes(SLOT_A * SLOT_B)
    assert (slots <= device_mod.FULL_SLOT_PADDING * ctx.lane_rows()) \
        == (layout == "slotted")
    if layout == "spilled":
        assert (attrs["spillCells"], attrs["spillChunks"]) == (1, 750)
    built = [k for k in ctx._gb_operands if k.startswith("gp::")]
    assert built and all(
        ("::slot::" if layout == "slotted" else "::spill8::") in k
        for k in built), built
    assert "slot" not in sql.lower() and "order" not in sql.lower()[:40]


def test_a_dead_segment_has_no_row_in_a_slot(slot_engines):
    """``ps_alive`` against the slotted segment plane: with the second
    segment dead the table is the first segment's rows alone (numpy), the
    launch slotted as before."""
    from test_subrtt import _compiled

    by, tables, cols = slot_engines
    segs, c = tables["u"], cols["u"]
    _rows(by["device"], SLOT_SQL.format("u"))
    dev = by["device"].device
    before = dev.hbm_stats()["groupby_slotted_launches"]
    q = _compiled(by["device"], segs, SLOT_SQL.format("u"))
    half = len(c["a"]) // 2
    for alive, rows in (([True, False], slice(0, half)),
                        ([False, True], slice(half, None)),
                        ([True, True], slice(None))):
        got = dev.launch(q, list(segs), alive=alive).fetch()
        keep = (c["f"][rows] >= 1) & (c["f"][rows] <= 3)
        cell = (c["a"][rows].astype(np.int64) * SLOT_B + c["b"][rows])[keep]
        count = np.bincount(cell, minlength=SLOT_A * SLOT_B)
        total = np.bincount(cell, weights=c["v"][rows][keep].astype(
            np.float64), minlength=SLOT_A * SLOT_B)
        live = np.nonzero(count)[0]
        keys = np.asarray(got.group_keys[0]).astype(np.int64) * SLOT_B \
            + np.asarray(got.group_keys[1])
        at = np.argsort(keys)
        assert (keys[at] == live).all(), alive
        assert (np.asarray(got.agg_partials[1]["count"])[at]
                == count[live]).all(), alive
        assert (np.asarray(got.agg_partials[0]["sum"])[at]
                == total[live]).all(), alive
    assert dev.hbm_stats()["groupby_slotted_launches"] == before + 3


def test_the_byte_budget_reckons_a_slotted_plane_at_its_slots(slot_engines):
    """``groupby_operand_cost`` of a slotted ``gp::`` key is the bytes its
    build puts on the device, K x cells (to a lane tile) at the plane's
    width and not the batch's rows; a spilled key's K x its home and chunk
    columns, and its layout operands' a column or a fat cell each; an
    ordered key's is the rows."""
    from pinot_tpu.ops import keysorted as ks

    by, tables, _cols = slot_engines
    for table, slotted in (("u", True), ("sk", False)):
        _rows(by["device"], SLOT_SQL.format(table))
        ctx = _ctx_of(by["device"], tables[table])
        keys = [k for k in ctx._gb_operands if k.startswith("gp::")]
        assert len(keys) == 3  # the segment, the filter's column, the value
        for key in keys:
            arr = ctx._gb_operands.pop(key)
            try:
                assert ctx.groupby_operand_cost([key]) == arr.nbytes, key
            finally:
                ctx._gb_operands[key] = arr
            assert ctx.groupby_operand_cost([key]) == 0  # built: no more
            # the skewed table spills its one fat cell into 750 chunks
            slots = 8 * ks.slot_lanes(SLOT_A * SLOT_B) if slotted \
                else 8 * (ks.slot_lanes(SLOT_A * SLOT_B) + 768)
            assert arr.size == slots and ("::slot::" in key) == slotted
            assert ("::spill8::" in key) != slotted
        # a twin in the other layout is a key of its own, at its own size
        if slotted:
            twin = keys[0].replace("::slot::", "::")
            assert ctx.groupby_operand_cost([twin]) \
                == ctx.lane_rows() * ctx._gb_operands[keys[0]].dtype.itemsize
            assert ctx.slotted_key(twin) == keys[0]
        else:
            for key in ctx.spill_keys("a,b", 8):
                arr = ctx._gb_operands.pop(key)
                try:
                    assert ctx.groupby_operand_cost([key]) == arr.nbytes
                finally:
                    ctx._gb_operands[key] = arr


def test_slotted_planes_past_the_byte_budget_keep_the_order(slot_engines):
    """Where the slotted planes would pass the batch's byte budget and the
    ordered ones fit, the launch keeps the ordered form - and says so -
    before it gives the preparation up."""
    by, tables, _cols = slot_engines
    sql = SLOT_SQL.format("u")
    _rows(by["device"], sql)
    slotted = [k for k in _ctx_of(by["device"], tables["u"])._gb_operands]
    engine = _engine({"u": tables["u"]}, mm_mode="interpret")
    _rows(engine, "SELECT COUNT(*) FROM u WHERE f < 3 AND v >= 0 "
                  "GROUP BY a LIMIT 1")  # the batch and its columns
    ctx = _ctx_of(engine, tables["u"])
    assert not any(k.startswith("gp::") for k in ctx._gb_operands)
    ordered = ctx.groupby_operand_cost(
        [k.replace("::slot::", "::") for k in slotted])
    planes = ctx.lane_rows() * (1 + 1 + 4)  # segment, filter, value
    padding = 8 * ((SLOT_A * SLOT_B + 127) // 128 * 128) * (1 + 1 + 4) \
        - planes
    assert padding > 0 and ordered > planes
    dev = engine.device
    dev.MAX_CACHED_BYTES = ctx.device_bytes() + ordered + padding // 2
    got, _resp, spans = _traced(engine, sql)
    assert got == _rows(by["host"], sql)[0]
    assert spans["executor.dispatch"]["groupbyKeyLayout"] == "ordered"
    assert spans["executor.dispatch"]["groupbyOperands"] == "built"
    assert dev.hbm_stats()["groupby_slotted_launches"] == 0
    assert ctx.device_bytes() <= dev.MAX_CACHED_BYTES


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
