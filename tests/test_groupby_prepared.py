"""The dense group-by's PREPARED operands (ISSUE 30): value byte planes
and key ids kept in HBM in the kernel's lane layout, built once a batch
(engine/params.py BatchContext.groupby_operand, ops/groupby_mm.py
"prepared operands"), against the per-launch preparation, the XLA
scatter and the host — bit for bit, alone and in cohorts.

The per-launch engine is the same executor held under a byte budget the
operands do not fit (``MAX_CACHED_BYTES``): the path every statement
takes that the prepared form declines. Kernels run in interpret mode.
"""

import json
import threading

import numpy as np
import pytest

import jax.numpy as jnp

from pinot_tpu.common.datatypes import DataType
from pinot_tpu.common.schema import Schema
from pinot_tpu.common.table_config import IndexingConfig, TableConfig
from pinot_tpu.engine.device import DeviceExecutor, plan_prepared_groupby
from pinot_tpu.engine.engine import QueryEngine
from pinot_tpu.ops import groupby_mm as mm
from pinot_tpu.ops import pallas_scatter as ps
from pinot_tpu.storage.creator import build_segment
from pinot_tpu.storage.segment import ImmutableSegment

N_ROWS = 30000          # 3 segments of 10,000 -> (3, 12288): 36,864 rows,
N_KEYS = 2000           # not a multiple of SUPERBLOCK (65,536)
# value columns by what the operands' builder has to do with them
VALUE_COLS = {
    # stored uint8, no frame of reference, off = 3: one plane
    "p1": (3, 200, np.int32),
    # stored uint16 with offset 70,000 == off: the stored bytes ARE the planes
    "p2": (70_000, 130_000, np.int32),
    # stored uint16 without an offset, off = 100 != plan.offset: delta -100
    "rev": (100, 60_000, np.int32),
    # negative minimum, int32 plane: delta +500, three planes
    "neg": (-500, 9_000_000, np.int32),
    # int64 past 2^31: four planes
    "big": (5, 3_000_000_000, np.int64),
}
BANDS = ((1, 3), (4, 6), (5, 7), (8, 10))


def _build(base, name, seed):
    rng = np.random.default_rng(seed)
    cols = {"k": rng.integers(0, N_KEYS, N_ROWS).astype(np.int32),
            "disc": rng.integers(0, 11, N_ROWS).astype(np.int32)}
    for c, (lo, hi, dt) in VALUE_COLS.items():
        v = rng.integers(lo, hi, N_ROWS).astype(dt)
        v[0], v[1] = lo, hi - 1  # the metadata bounds are the stated ones
        cols[c] = v
    schema = Schema.build(
        name=name,
        dimensions=[("k", DataType.INT), ("disc", DataType.INT)],
        metrics=[(c, DataType.LONG if dt is np.int64 else DataType.INT)
                 for c, (_lo, _hi, dt) in VALUE_COLS.items()])
    cfg = TableConfig(table_name=name, indexing=IndexingConfig(
        no_dictionary_columns=list(VALUE_COLS)))
    segs = []
    for i in range(3):
        sl = slice(i * 10000, (i + 1) * 10000)
        d = str(base / f"{name}_s{i}")
        build_segment(schema, {k: v[sl] for k, v in cols.items()}, d, cfg,
                      f"{name}_s{i}")
        segs.append(ImmutableSegment(d))
    return segs


def _engine(tables, **executor):
    e = QueryEngine(device_executor=DeviceExecutor(**executor)
                    if executor else None)
    for name, segs in tables.items():
        for s in segs:
            e.add_segment(name, s)
    return e


@pytest.fixture(scope="module")
def tables(tmp_path_factory):
    base = tmp_path_factory.mktemp("prepared_seg")
    return {"t": _build(base, "t", 3), "u": _build(base, "u", 4)}


@pytest.fixture(scope="module")
def engines(tables):
    prepared = _engine(tables, mm_mode="interpret")
    per_launch = _engine(tables, mm_mode="interpret")
    per_launch.device.MAX_CACHED_BYTES = 1  # the operands never fit
    # the matmul tier's prepared form (SET usePallas=false takes it too)
    mm_tier = _engine(tables, mm_mode="interpret", pallas_mode="off")
    xla = _engine(tables, mm_mode="off", pallas_mode="off")
    host = _engine(tables)
    for e in (prepared, per_launch, mm_tier, xla):
        e.device.partials_cache_enabled = False
    return {"prepared": prepared, "perLaunch": per_launch, "mm": mm_tier,
            "xla": xla, "host": host}


def _sql(select, band, table="t"):
    return (f"SELECT k, {select} FROM {table} WHERE disc BETWEEN {band[0]} "
            f"AND {band[1]} GROUP BY k ORDER BY k LIMIT {N_KEYS}")


def _rows(engine, sql):
    r = engine.execute(sql)
    assert not r.get("exceptions"), (sql, r)
    return r["resultTable"]["rows"], r


def _origins(resp):
    return [rec.get("groupbyOperands") for rec in resp.get("roofline") or ()]


def _cohort(engine, sqls):
    """The statements at once, through one coalesced launch."""
    co = engine.device.coalescer
    was = (co.force, co.window_s, co.max_cohort)
    co.force, co.window_s, co.max_cohort = True, 0.25, len(sqls)
    joined = co.queries_coalesced
    got = [None] * len(sqls)
    barrier = threading.Barrier(len(sqls))

    def worker(i):
        barrier.wait()
        got[i] = _rows(engine, sqls[i])

    try:
        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(len(sqls))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    finally:
        co.force, co.window_s, co.max_cohort = was
    assert co.queries_coalesced > joined, "no statement joined a cohort"
    return got


SELECTS = {"sum": "SUM(rev)", "avg": "AVG(rev), SUM(neg)",
           "count": "COUNT(*)"}


@pytest.mark.parametrize("width", [1, 2, 4])
@pytest.mark.parametrize("agg", sorted(SELECTS))
def test_prepared_is_per_launch_is_xla_is_host(engines, agg, width):
    """SUM, AVG, COUNT under a band filter: the four paths agree bit for
    bit, alone and at cohort widths 2 and 4 with different literals — a
    member never receives another's sums."""
    sqls = [_sql(SELECTS[agg], band) for band in BANDS[:width]]
    want = [_rows(engines["host"], s)[0] for s in sqls]
    assert len({json.dumps(w) for w in want}) == width  # answers differ
    for name in ("xla", "perLaunch", "mm", "prepared"):
        e = engines[name]
        got = [_rows(e, s) for s in sqls] if width == 1 \
            else _cohort(e, sqls)
        for s, (rows, _resp), w in zip(sqls, got, want):
            assert rows == w, (name, s, rows[:3], w[:3])
        # the launch's record rides its leader's answer
        origins = {o for _rows_, resp in got for o in _origins(resp)}
        if name in ("prepared", "mm"):
            assert origins and origins <= {"prepared", "built"}, origins
        elif name == "perLaunch":
            assert origins == {"perLaunch"}, origins


@pytest.mark.parametrize("col", sorted(VALUE_COLS))
def test_operand_planes_by_stored_form(engines, tables, col):
    """One- to four-plane ranges, a frame of reference equal to the
    agg's offset and one that differs, a negative minimum: the planes
    recombine to the host's sums, and the plan says how many there are."""
    sql = _sql(f"SUM({col}), COUNT(*)", (2, 9))
    want, _ = _rows(engines["host"], sql)
    for name in ("prepared", "perLaunch", "xla"):
        rows, _resp = _rows(engines[name], sql)
        assert rows == want, (name, col, rows[:3], want[:3])
    lo, hi, _dt = VALUE_COLS[col]
    nplanes = mm.int_planes_needed(lo, hi - 1)
    assert nplanes == {"p1": 1, "p2": 2, "rev": 2, "neg": 3, "big": 4}[col]
    ctx = engines["prepared"].device.batch_for(tables["t"])
    key = ctx.groupby_planes_key(col, lo, nplanes)
    planes, built = ctx.groupby_operand(key)
    assert not built and planes.dtype == jnp.uint8
    assert planes.shape == (nplanes, mm.SUPERBLOCK // 128, 128)
    delta = (ctx.width_plan(col).offset or 0) - lo
    assert (delta == 0) == (col == "p2")


def test_ids_in_lanes_at_the_stored_width(engines, tables):
    _rows(engines["prepared"], _sql("COUNT(*)", (1, 3)))
    ctx = engines["prepared"].device.batch_for(tables["t"])
    ids, built = ctx.groupby_operand("gk::k")
    assert not built and ids.dtype == jnp.uint16
    assert ids.shape == (mm.SUPERBLOCK // 128, 128)
    flat = np.asarray(ids).reshape(-1)
    # rows past a segment's end and past the batch carry num_groups
    seg = flat[: 3 * ctx.pad_to].reshape(3, ctx.pad_to)
    assert (seg[:, 10000:] == N_KEYS).all() and (seg[:, :10000] < N_KEYS).all()
    assert (flat[3 * ctx.pad_to:] == N_KEYS).all()


# ---- several key columns, expression arguments (ISSUE 33) -------------------

# key columns by stored width: a uint8 (20 values), b uint16 (300), c 4-bit
# (5, the opt-in sub-byte tier); a x b x c = 30,000 cells stays dense
MK_CARDS = {"a": 20, "b": 300, "c": 5}
MK_CASES = {
    "two_keys_u8_u16": (("a", "b"), "SUM(rev), COUNT(*)"),
    "two_keys_u16_4bit": (("b", "c"), "AVG(rev), SUM(cost)"),
    "three_keys": (("a", "b", "c"), "SUM(rev)"),
    # rev - cost runs from -89,899 to 59,499: a negative offset, 3 planes
    "three_keys_expr": (("c", "a", "b"), "SUM(rev - cost), COUNT(*)"),
    "one_key_expr": (("b",), "SUM(rev - cost), SUM(rev)"),
}


def _mk_sql(case, band):
    keys, select = MK_CASES[case]
    ks = ", ".join(keys)
    return (f"SELECT {ks}, {select} FROM m WHERE disc BETWEEN {band[0]} AND "
            f"{band[1]} GROUP BY {ks} ORDER BY {ks} LIMIT 40000")


@pytest.fixture(scope="module")
def mk_engines(tmp_path_factory):
    base = tmp_path_factory.mktemp("multikey_seg")
    rng = np.random.default_rng(33)
    cols = {k: rng.integers(0, c, N_ROWS).astype(np.int32)
            for k, c in MK_CARDS.items()}
    cols["disc"] = rng.integers(0, 11, N_ROWS).astype(np.int32)
    cols["rev"] = rng.integers(100, 60_000, N_ROWS).astype(np.int32)
    cols["cost"] = rng.integers(500, 90_000, N_ROWS).astype(np.int32)
    for c, (lo, hi) in {"rev": (100, 59_999), "cost": (500, 89_999)}.items():
        cols[c][0], cols[c][1] = lo, hi  # the metadata bounds
    schema = Schema.build(
        name="m", dimensions=[(k, DataType.INT) for k in (*MK_CARDS, "disc")],
        metrics=[("rev", DataType.INT), ("cost", DataType.INT)])
    cfg = TableConfig(table_name="m", indexing=IndexingConfig(
        no_dictionary_columns=["rev", "cost"]))
    segs = []
    for i in range(3):
        sl = slice(i * 10000, (i + 1) * 10000)
        d = str(base / f"m_s{i}")
        build_segment(schema, {k: v[sl] for k, v in cols.items()}, d, cfg,
                      f"m_s{i}")
        segs.append(ImmutableSegment(d))
    tables = {"m": segs}
    engines = {
        "prepared": _engine(tables, mm_mode="interpret"),
        "perLaunch": _engine(tables, mm_mode="interpret"),
        "mm": _engine(tables, mm_mode="interpret", pallas_mode="off"),
        "xla": _engine(tables, mm_mode="off", pallas_mode="off"),
        "host": _engine(tables)}
    engines["perLaunch"].device.MAX_CACHED_BYTES = 1
    # a batch reads the sub-byte switch when it is made
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("PINOT_TPU_SUBBYTE", "1")
        for name, e in engines.items():
            if name != "host":
                e.device.partials_cache_enabled = False
                e.device.batch_for(segs)
    return engines, segs


@pytest.mark.parametrize("width", [1, 2, 4])
@pytest.mark.parametrize("case", sorted(MK_CASES))
def test_multikey_and_expression_parity(mk_engines, case, width):
    """Two and three key columns of mixed stored widths and a column-only
    expression argument: prepared == per launch == XLA == host, bit for
    bit, alone and as a cohort of two and of four. The kernel combines
    the columns' ids in VMEM; the expression's planes are the batch's."""
    engines, segs = mk_engines
    keys = MK_CASES[case][0]
    sqls = [_mk_sql(case, band) for band in BANDS[:width]]
    want = [_rows(engines["host"], s)[0] for s in sqls]
    assert len({json.dumps(w) for w in want}) == width and want[0]
    for name in ("xla", "perLaunch", "mm", "prepared"):
        e = engines[name]
        got = [_rows(e, s) for s in sqls] if width == 1 \
            else _cohort(e, sqls)
        for s, (rows, _resp), w in zip(sqls, got, want):
            assert rows == w, (name, s, rows[:3], w[:3])
        origins = {o for _rows_, resp in got for o in _origins(resp)}
        # the matmul tier takes one ids operand: several keys are the
        # launch's there
        took = name == "prepared" or (name == "mm" and len(keys) == 1)
        if took:
            assert origins and origins <= {"prepared", "built"}, origins
        elif name != "xla":
            assert origins == {"perLaunch"}, origins
    ctx = engines["prepared"].device.batch_for(segs)
    assert ctx.width_plan("c").bits == 4
    for k in keys:  # one operand a key COLUMN, at its stored width
        ids, built = ctx.groupby_operand("gk::" + k)
        assert not built and ids.dtype == {
            "a": jnp.uint8, "b": jnp.uint16, "c": jnp.uint8}[k]
    if "expr" in case:
        planes, built = ctx.groupby_operand(
            "gv::minus(rev,cost)::-89899::3")
        assert not built and planes.shape == (3, mm.SUPERBLOCK // 128, 128)


def test_key_sets_share_their_columns_operands(mk_engines):
    """However many key sets name a column, its ids are in HBM once, and
    an expression's planes once for every statement that sums it."""
    engines, segs = mk_engines
    e = _engine({"m": segs}, mm_mode="interpret")
    e.device.partials_cache_enabled = False
    seen = []
    for case in ("two_keys_u8_u16", "three_keys", "three_keys_expr",
                 "one_key_expr"):
        _rows_, resp = _rows(e, _mk_sql(case, (2, 9)))
        seen.append((_origins(resp), e.device.groupby_operand_bytes()))
    sb = mm.SUPERBLOCK  # the batch pads to one superblock
    assert seen == [
        (["built"], sb * (1 + 2 + 2)),      # a (u8), b (u16), rev 2 planes
        (["built"], sb * (1 + 2 + 2 + 1)),  # ... and c
        (["built"], sb * (6 + 3)),          # the same ids; rev - cost
        (["prepared"], sb * (6 + 3)),       # nothing new
    ], seen
    ctx = e.device.batch_for(segs)
    assert sorted(ctx._gb_operands) == [
        "gk::a", "gk::b", "gk::c", "gv::minus(rev,cost)::-89899::3",
        "gv::rev::100::2"]
    stats = e.device.hbm_stats()
    assert stats["groupby_operand_bytes"] == sb * 9
    assert stats["groupby_operand_launches"]["perLaunch"] == 0


def test_second_batch_builds_its_own_and_eviction_frees_them(tables):
    e = _engine(tables, mm_mode="interpret")
    dev = e.device
    dev.partials_cache_enabled = False
    before = dev.resident_bytes()
    assert before == 0
    for table in ("t", "u"):
        rows, resp = _rows(e, _sql("SUM(rev)", (4, 6), table))
        assert _origins(resp) == ["built"]
        rows2, resp2 = _rows(e, _sql("SUM(rev)", (5, 7), table))
        assert _origins(resp2) == ["prepared"]
    stats = dev.hbm_stats()
    assert stats["groupby_operand_launches"] == {
        "prepared": 2, "built": 2, "perLaunch": 0}
    per_batch = [b["groupby_operand_bytes"] for b in stats["batches"]]
    # ids (uint16) + two planes (uint8), padded to one superblock
    assert per_batch == [mm.SUPERBLOCK * 4] * 2
    assert stats["groupby_operand_bytes"] == sum(per_batch)
    assert dev.groupby_operand_bytes() == sum(per_batch)
    assert stats["resident_bytes"] > sum(per_batch)
    host = _engine(tables)
    # a second and a third key set over the same columns: each column's
    # ids are in HBM once (disc, one byte a row, is the only new operand)
    for keys, origin in (("k, disc", "built"), ("disc, k", "prepared"),
                         ("disc", "prepared")):
        sql = (f"SELECT {keys}, SUM(rev) FROM t WHERE disc < 9 "
               f"GROUP BY {keys} ORDER BY {keys} LIMIT 30000")
        rows, resp = _rows(e, sql)
        assert _origins(resp) == [origin], keys
        assert rows == _rows(host, sql)[0]
    stats = dev.hbm_stats()
    assert sorted(b["groupby_operand_bytes"] for b in stats["batches"]) \
        == [mm.SUPERBLOCK * 4, mm.SUPERBLOCK * 5]
    assert stats["groupby_operand_bytes"] == dev.groupby_operand_bytes() \
        == mm.SUPERBLOCK * 9
    # the operands die with their batch
    for segs in tables.values():
        assert dev.evict_segment_dir(segs[0].dir) == 1
    assert dev.resident_bytes() == before
    assert dev.groupby_operand_bytes() == 0


OVER_BUDGET = {
    # another value column's planes
    "planes": "SELECT k, SUM(neg), COUNT(*) FROM t WHERE disc BETWEEN 4 AND 6 "
              "GROUP BY k ORDER BY k LIMIT 2000",
    # a second key column's ids
    "ids": "SELECT k, disc, SUM(rev), COUNT(*) FROM t WHERE disc BETWEEN 4 "
           "AND 6 GROUP BY k, disc ORDER BY k, disc LIMIT 30000",
    # an expression's planes
    "expression": "SELECT k, SUM(rev - p1), COUNT(*) FROM t WHERE disc "
                  "BETWEEN 4 AND 6 GROUP BY k ORDER BY k LIMIT 2000",
}


@pytest.mark.parametrize("missing", sorted(OVER_BUDGET))
def test_over_the_byte_budget_the_per_launch_path_answers(tables, missing):
    e = _engine(tables, mm_mode="interpret")
    e.device.partials_cache_enabled = False
    sql = _sql("SUM(rev), COUNT(*)", (4, 6))
    want, resp = _rows(e, sql)
    assert _origins(resp) == ["built"]
    with_operands = e.device.resident_bytes()
    # a cap the batch already fills: the next plan's operands do not fit
    e.device.MAX_CACHED_BYTES = with_operands
    sql2 = OVER_BUDGET[missing]
    rows2, resp2 = _rows(e, sql2)
    assert _origins(resp2) == ["perLaunch"]
    assert rows2 == _rows(_engine(tables), sql2)[0]
    # what is built stays usable under the cap
    rows, resp = _rows(e, sql)
    assert rows == want and _origins(resp) == ["prepared"]
    assert e.device.hbm_stats()["groupby_operand_launches"] == {
        "prepared": 1, "built": 1, "perLaunch": 1}


def test_explain_analyze_and_spans_say_where_operands_came_from(engines):
    out = engines["prepared"].execute(
        "EXPLAIN ANALYZE " + _sql("SUM(rev)", (1, 3)))
    spans = out["analyzedResponse"]["traceInfo"]["server"]
    for phase in ("executor.dispatch", "executor.device_wait"):
        attrs = next(s for s in spans if s["phase"] == phase)["attrs"]
        assert attrs["groupbyOperands"] in ("prepared", "built"), attrs
    text = json.dumps(out["resultTable"]["rows"])
    assert "+pallas" in text and "groupbyOperands=" in text, text
    # a statement with no GROUP BY says nothing of operands
    out = engines["prepared"].execute(
        "EXPLAIN ANALYZE SELECT SUM(rev) FROM t WHERE disc < 5")
    spans = out["analyzedResponse"]["traceInfo"]["server"]
    attrs = next(s for s in spans
                 if s["phase"] == "executor.dispatch")["attrs"]
    assert "groupbyOperands" not in attrs


DECLINED = {
    "float argument": "SUM(fv)",
    # a literal is a launch parameter: the value is not the batch's alone
    "expression with a literal": "SUM(rev + 1)",
    "an agg the kernel does not take": "SUM(rev), MIN(rev)",
}


@pytest.mark.parametrize("why", sorted(DECLINED))
def test_what_the_prepared_form_declines_or_shares(tmp_path, why):
    """The per-launch path still answers what the prepared form does not
    take; a MIN beside the SUM keeps its own scatter and the SUM still
    reads the batch's operands."""
    rng = np.random.default_rng(9)
    n = 6000
    cols = {"k": rng.integers(0, 50, n).astype(np.int32),
            "j": rng.integers(0, 4, n).astype(np.int32),
            "disc": rng.integers(0, 11, n).astype(np.int32),
            "rev": rng.integers(100, 60_000, n).astype(np.int32),
            "fv": rng.integers(0, 1000, n).astype(np.float64)}
    schema = Schema.build(
        name="t", dimensions=[("k", DataType.INT), ("j", DataType.INT),
                              ("disc", DataType.INT)],
        metrics=[("rev", DataType.INT), ("fv", DataType.DOUBLE)])
    cfg = TableConfig(table_name="t", indexing=IndexingConfig(
        no_dictionary_columns=["rev", "fv"]))
    build_segment(schema, cols, str(tmp_path / "s0"), cfg, "s0")
    segs = {"t": [ImmutableSegment(str(tmp_path / "s0"))]}
    sql = (f"SELECT k, {DECLINED[why]} FROM t WHERE disc < 6 GROUP BY k "
           "ORDER BY k LIMIT 60")
    e = _engine(segs, mm_mode="interpret")
    rows, resp = _rows(e, sql)
    assert rows == _rows(_engine(segs), sql)[0]
    shared = why == "an agg the kernel does not take"
    assert _origins(resp) == ["built" if shared else "perLaunch"], resp


def test_plan_follows_the_template():
    """plan_prepared_groupby on hand-built templates: the route, the cols
    keys, and the conditions it declines on."""
    widths = {"v": ("<u2", 0, True, "<i4"), "k": ("<u2", 0, False, ""),
              "j": ("|u1", 0, False, ""), "h": ("|u1", 4, False, ""),
              "dv::d": ("|u1", 0, False, "<i8"), "f": ("<f4", 0, False, "")}
    sums = (("sum", ("raw", "v"), (2, 256)), ("count", None, None),
            ("avg", ("dictval", "d"), (1, 256)))

    def plan(aggs=sums, group=("k",), cards=(2000,), shape="groupby",
             mm_mode="interpret", pallas="interpret", n=1 << 20):
        template = (shape, ("true",), group, cards, aggs, 0, False)
        return plan_prepared_groupby(template, widths, n, mm_mode, pallas,
                                     {0: 7, 2: -3})

    planes = ((0, "gv::v::7::2", 2), (2, "gv::dv::d::-3::1", 1))
    assert plan() == ("pallas", ("gk::k",), planes)
    assert plan(pallas="off")[0] == "mm"
    assert plan(aggs=(("count", None, None),)) == ("pallas", ("gk::k",), ())
    assert plan(mm_mode="off", pallas="off") is None
    assert plan(shape="groupby_sorted") is None
    # several key columns: one ids operand a COLUMN, in the template's
    # order, whatever their stored widths (u16, u8, 4-bit)
    assert plan(group=("k", "j"), cards=(2000, 3)) == (
        "pallas", ("gk::k", "gk::j"), planes)
    assert plan(group=("j", "k", "h"), cards=(3, 2000, 5)) == (
        "pallas", ("gk::j", "gk::k", "gk::h"), planes)
    # ... on the Pallas route only: the matmul kernel takes one ids operand
    assert plan(group=("k", "j"), cards=(2000, 3), pallas="off") is None
    # the narrowed form: both of its passes read the operands
    narrow = dict(group=("k", "j", "h"), cards=(2000, 200, 5),
                  shape="groupby_narrow")
    assert plan(**narrow) == (
        "pallas", ("gk::k", "gk::j", "gk::h"), planes)
    assert plan(**narrow, pallas="off") is None
    # an expression over integer columns alone is the batch's: its planes
    # are keyed by its canonical form
    expr = ("minus", ("raw", "v"), ("abs", ("dictval", "d")))
    assert plan(aggs=(("sum", expr, (3, 256)),)) == (
        "pallas", ("gk::k",), ((0, "gv::minus(v,abs(dv::d))::7::3", 3),))
    # ... one with a literal (a launch parameter), a float column, a
    # function that may leave the integers, or a cast is the launch's
    for arg in (("plus", ("raw", "v"), ("lit", "pr0")), ("raw", "f"),
                ("plus", ("raw", "v"), ("raw", "f")),
                ("divide", ("raw", "v"), ("dictval", "d")),
                ("cast", ("raw", "v"), "LONG")):
        assert plan(aggs=(("sum", arg, (3, 256)),)) is None, arg
    # below the kernels' minimum rows the XLA scatter runs: nothing to
    # prepare for (interpret mode has no such minimum)
    assert plan(mm_mode="tpu", pallas="tpu", n=1000) is None
    assert plan(mm_mode="tpu", pallas="tpu") is not None
    # an unknown range leaves that agg to the exact scatter, as per launch
    unknown = (("sum", ("raw", "v"), (None, None)), ("count", None, None))
    assert plan(aggs=unknown) == ("pallas", ("gk::k",), ())
    # a group count whose row tile cannot hold whole 8-bit tiles
    big = 3_000_000
    assert ps.sums_blk(big, 3) < mm.BLK
    assert not mm.prepared_tile_ok(2048) and mm.prepared_tile_ok(4096)
    assert plan(cards=(big,)) is None
    assert plan(group=("k", "j"), cards=(big // 3, 3)) is None


@pytest.mark.parametrize("width", [1, 2])
def test_sub_byte_filter_and_key_columns(tmp_path, monkeypatch, width):
    """The opt-in sub-byte tier: a 4-bit filter column is unpacked by the
    mask as ever, a 4-bit KEY column is unpacked once, by the ids'
    builder, and stored as uint8 lanes."""
    monkeypatch.setenv("PINOT_TPU_SUBBYTE", "1")
    rng = np.random.default_rng(12)
    n = 9000
    cols = {"k": rng.integers(0, 12, n).astype(np.int32),
            "disc": rng.integers(0, 11, n).astype(np.int32),
            "rev": rng.integers(100, 60_000, n).astype(np.int32)}
    schema = Schema.build(
        name="t", dimensions=[("k", DataType.INT), ("disc", DataType.INT)],
        metrics=[("rev", DataType.INT)])
    cfg = TableConfig(table_name="t", indexing=IndexingConfig(
        no_dictionary_columns=["rev"]))
    build_segment(schema, cols, str(tmp_path / "s0"), cfg, "s0")
    segs = {"t": [ImmutableSegment(str(tmp_path / "s0"))]}
    e = _engine(segs, mm_mode="interpret")
    e.device.partials_cache_enabled = False
    sqls = [f"SELECT k, SUM(rev), COUNT(*) FROM t WHERE disc BETWEEN {a} "
            f"AND {b} GROUP BY k ORDER BY k LIMIT 20" for a, b in BANDS[:width]]
    got = [_rows(e, s) for s in sqls] if width == 1 else _cohort(e, sqls)
    host = _engine(segs)
    for s, (rows, _resp) in zip(sqls, got):
        assert rows == _rows(host, s)[0], s
    ctx = e.device.batch_for(segs["t"])
    assert ctx.width_plan("disc").bits == 4 and ctx.width_plan("k").bits == 4
    ids, built = ctx.groupby_operand("gk::k")
    assert not built and ids.dtype == jnp.uint8
    assert {k[6] for k in e.device._pipelines} == {
        ("pallas", ("gk::k",), ((0, "gv::rev::100::2", 2),))}
