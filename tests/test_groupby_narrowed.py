"""The NARROWED key space (ISSUE 32; engine/device.py NARROW_MIN_CELLS):
a group-by over two or more columns whose cartesian product is large and
whose filter leaves few keys sums into the live 128-cell blocks of the
key space alone, and hands on a keyed table of the live cells — against
the host executor and a numpy group-by, bit for bit, alone and in a
cohort, on the Pallas tier (interpret mode), the matmul tier and the XLA
scatter. A key space whose live keys do not fit is answered by the host,
exactly, and counted.

Last, SSB's 13 flat statements (benchmark/traffic/ssb_flat_13q_c4.json)
on the tiny table of benchmark/harness/testdata/ssb_flat_tiny.json
through the served path (HTTP -> broker -> server -> device executor),
against benchmark/harness/reference.py.
"""

import json
import os
import sys
import threading

import numpy as np
import pytest

from pinot_tpu.common.datatypes import DataType
from pinot_tpu.common.schema import Schema
from pinot_tpu.common.table_config import IndexingConfig, TableConfig
from pinot_tpu.engine import device as dev
from pinot_tpu.engine.device import DeviceExecutor
from pinot_tpu.engine.engine import QueryEngine
from pinot_tpu.storage.creator import build_segment
from pinot_tpu.storage.segment import ImmutableSegment

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
N_ROWS = 24_000
REGIONS = ["AFRICA", "AMERICA", "ASIA", "EUROPE", "MIDDLE EAST"]
NATIONS = [f"{r[:3]}NATION{k}" for r in REGIONS for k in range(5)]   # 25
CITIES = [f"{n}C{d}" for n in NATIONS for d in range(10)]            # 250


def _hier_columns(seed):
    """SSB's shape at a small size: a city is one of its nation's ten, a
    nation one of its region's five, so a predicate on the nation leaves
    10 of the 250 cities live."""
    rng = np.random.default_rng(seed)
    # half the customers and suppliers sit in one nation (ASINATION2), so
    # that its 10 x 10 x 7 cells hold rows enough
    c, s = (np.where(rng.random(N_ROWS) < 0.5, 120 + rng.integers(
        0, 10, N_ROWS), rng.integers(0, 250, N_ROWS)) for _ in "cs")
    brand = rng.integers(0, 1000, N_ROWS)
    cols = {
        "c_city": np.array(CITIES)[c], "c_nation": np.array(NATIONS)[c // 10],
        "c_region": np.array(REGIONS)[c // 50],
        "s_city": np.array(CITIES)[s], "s_nation": np.array(NATIONS)[s // 10],
        "brand": np.array([f"B{b:04d}" for b in range(1000)])[brand],
        "category": np.array([f"B{b:02d}" for b in range(25)])[brand // 40],
        "year": rng.integers(1992, 1999, N_ROWS).astype(np.int32),
        "rev": rng.integers(81_000, 10_495_000, N_ROWS).astype(np.int32),
        "cost": rng.integers(54_000, 125_941, N_ROWS).astype(np.int32),
    }
    for k in ("rev", "cost"):  # the metadata bounds are the stated ones
        cols[k][0], cols[k][1] = cols[k].min(), cols[k].max()
    return cols


# the table whose live keys are counted out: cells (a, b) of a 168 x 256
# key space (43,008 cells, past NARROW_MIN_CELLS), by the value of ``sel``
def _counted_columns():
    a, b, sel = [], [], []
    for i in range(dev.NARROW_GROUPS):        # sel 1: exactly NARROW_GROUPS
        a.append(i // 256), b.append(i % 256), sel.append(1)  # cells, 32 blocks
    for i in range(1000):                     # ... some of them twice
        a.append(i // 256), b.append(i % 256), sel.append(1)
    a.append(16), b.append(0), sel.append(2)  # sel <= 2: one cell more
    for j in range(300):                      # sel 3: 300 cells, each in a
        a.append(20 + j // 2), b.append(128 * (j % 2)), sel.append(3)  # block
    a.append(200), b.append(7), sel.append(9)  # sel 9: one cell
    n = len(a)
    return {"a": np.array(a, np.int32), "b": np.array(b, np.int32),
            "sel": np.array(sel, np.int32),
            "v": (np.arange(n, dtype=np.int32) * 7919) % 100_003}


def _build(base, name, cols, dims, metrics):
    schema = Schema.build(
        name=name, dimensions=[(c, t) for c, t in dims],
        metrics=[(c, DataType.INT) for c in metrics])
    cfg = TableConfig(table_name=name, indexing=IndexingConfig(
        no_dictionary_columns=list(metrics)))
    n = len(next(iter(cols.values())))
    segs = []
    for i, sl in enumerate((slice(0, n // 2), slice(n // 2, n))):
        d = str(base / f"{name}_s{i}")
        build_segment(schema, {k: v[sl] for k, v in cols.items()}, d, cfg,
                      f"{name}_s{i}")
        segs.append(ImmutableSegment(d))
    return segs


@pytest.fixture(scope="module")
def data(tmp_path_factory):
    base = tmp_path_factory.mktemp("narrowed_seg")
    hier, counted = _hier_columns(11), _counted_columns()
    strs = [(c, DataType.STRING) for c in (
        "c_city", "c_nation", "c_region", "s_city", "s_nation", "brand",
        "category")]
    tables = {
        "t": _build(base, "t", hier, strs + [("year", DataType.INT)],
                    ["rev", "cost"]),
        "u": _build(base, "u", counted, [(c, DataType.INT) for c in (
            "a", "b", "sel")], ["v"]),
    }
    return tables, {"t": hier, "u": counted}


def _engine(tables, **executor):
    e = QueryEngine(device_executor=DeviceExecutor(**executor)
                    if executor else None)
    for name, segs in tables.items():
        for s in segs:
            e.add_segment(name, s)
    if e.device is not None:
        e.device.partials_cache_enabled = False
    return e


@pytest.fixture(scope="module")
def engines(data):
    tables, _cols = data
    return {"pallas": _engine(tables, mm_mode="interpret"),
            "mm": _engine(tables, mm_mode="interpret", pallas_mode="off"),
            "xla": _engine(tables, mm_mode="off", pallas_mode="off"),
            "host": _engine(tables)}


def _rows(engine, sql):
    r = engine.execute(sql)
    assert not r.get("exceptions"), (sql, r)
    return [list(x) for x in r["resultTable"]["rows"]], r


def _key_spaces(resp):
    return [rec.get("groupbyKeySpace") for rec in resp.get("roofline") or ()]


def _traced(engine, sql):
    """(rows, response, {span name: attrs}) of one traced statement."""
    from pinot_tpu.common import trace

    tracer = trace.start_trace("narrowed-test")
    try:
        rows, resp = _rows(engine, sql)
    finally:
        trace.end_trace()
    return rows, resp, {s["phase"]: s.get("attrs") or {}
                        for s in tracer.to_json()}


def _numpy_groupby(cols, keep, keys, value):
    """{key tuple: (count, sum)} of the kept rows, in exact integers."""
    out = {}
    for i in np.nonzero(keep)[0]:
        k = tuple(cols[c][i].item() for c in keys)
        n, s = out.get(k, (0, 0))
        out[k] = (n + 1, s + int(value[i]))
    return out


# ---- which statements narrow, and that they answer as the host does -------

T_STATEMENTS = {
    # three keys, 437,500 cells, 600 live: SSB Q3.2's shape
    "three_keys": (
        "SELECT c_city, s_city, year, SUM(rev), COUNT(*) FROM t "
        "WHERE c_nation = 'ASINATION2' AND s_nation = 'ASINATION2' "
        "AND year BETWEEN 1992 AND 1997 GROUP BY c_city, s_city, year "
        "ORDER BY c_city, s_city, year LIMIT 1000", "narrowed"),
    # ORDER BY the aggregate, LIMIT under the live cells: the trim's sort
    "order_by_sum": (
        "SELECT c_city, s_city, year, SUM(rev) FROM t "
        "WHERE c_nation = 'EURNATION0' AND s_nation = 'AMENATION4' "
        "GROUP BY c_city, s_city, year "
        "ORDER BY year ASC, SUM(rev) DESC, c_city, s_city LIMIT 25",
        "narrowed"),
    # two keys, 250,000 cells; SUM of a - b (SSB Q4.x's argument) and AVG
    "two_keys_expr": (
        "SELECT c_city, brand, SUM(rev - cost), AVG(rev) FROM t "
        "WHERE c_region = 'AMERICA' AND category = 'B03' "
        "GROUP BY c_city, brand ORDER BY c_city, brand LIMIT 5000",
        "narrowed"),
    # 25 x 25 x 7 = 4,375 cells: today's dense form (SSB Q3.1)
    "small_three_keys": (
        "SELECT c_nation, s_nation, year, SUM(rev) FROM t "
        "WHERE c_region = 'ASIA' GROUP BY c_nation, s_nation, year "
        "ORDER BY c_nation, s_nation, year LIMIT 5000", "dense"),
    # 7 x 1,000 = 7,000 cells: dense (SSB Q2.x)
    "small_two_keys": (
        "SELECT year, brand, SUM(rev) FROM t WHERE category = 'B11' "
        "GROUP BY year, brand ORDER BY year, brand LIMIT 5000", "dense"),
    # one key never narrows, whatever its cardinality
    "one_key": (
        "SELECT brand, SUM(rev) FROM t WHERE year = 1995 GROUP BY brand "
        "ORDER BY brand LIMIT 2000", "dense"),
    # MIN is not an aggregate the narrowed table holds: dense
    "min_stays_dense": (
        "SELECT c_city, s_city, MIN(rev) FROM t "
        "WHERE c_nation = 'ASINATION2' AND s_nation = 'ASINATION1' "
        "GROUP BY c_city, s_city ORDER BY c_city, s_city LIMIT 1000",
        "dense"),
}


@pytest.mark.parametrize("tier", ["pallas", "mm", "xla"])
@pytest.mark.parametrize("name", list(T_STATEMENTS))
def test_answers_as_the_host_does(engines, tier, name):
    sql, space = T_STATEMENTS[name]
    want, _ = _rows(engines["host"], sql)
    got, resp = _rows(engines[tier], sql)
    assert got == want and want
    assert resp.get("numSegmentsOnHost", 0) == 0
    assert set(_key_spaces(resp)) == {space}
    if space == "narrowed":
        rec = resp["roofline"][0]
        cells = {"three_keys": 437_500, "order_by_sum": 437_500,
                 "two_keys_expr": 250_000}[name]
        assert rec["keySpaceCells"] == cells
        assert 0 < rec["keySpaceLive"] <= dev.NARROW_BLOCKS * dev.NARROW_BLOCK


def test_against_numpy(engines, data):
    """The keyed table's cells decode to the right strings, and the sums
    are the integers' own."""
    cols = data[1]["t"]
    keep = (cols["c_nation"] == "ASINATION2") \
        & (cols["s_nation"] == "ASINATION2") & (cols["year"] <= 1997)
    want = _numpy_groupby(cols, keep, ("c_city", "s_city", "year"),
                          cols["rev"])
    got, _ = _rows(engines["pallas"], T_STATEMENTS["three_keys"][0])
    assert {tuple(r[:3]): (r[4], r[3]) for r in got} == want
    assert len(got) == len(want) > 100


# ---- live keys counted out: 0, 1, all that fit, one more ------------------

U_SQL = ("SELECT a, b, SUM(v), COUNT(*) FROM u WHERE {where} GROUP BY a, b "
         "ORDER BY a, b LIMIT 10000")


@pytest.mark.parametrize("tier", ["pallas", "xla"])
@pytest.mark.parametrize("where,live,fits", [
    ("sel = 0", 0, True),
    ("sel = 9", 1, True),
    ("sel <= 1", dev.NARROW_GROUPS, True),        # every slot of the table
    ("sel <= 2", dev.NARROW_GROUPS + 1, False),   # one cell too many
    ("sel = 3", 300, False),                      # 300 blocks > NARROW_BLOCKS
])
def test_live_keys_counted_out(engines, data, tier, where, live, fits):
    cols = data[1]["u"]
    keep = eval(where.replace("sel", "cols['sel']").replace(" = ", " == "))
    want = _numpy_groupby(cols, keep, ("a", "b"), cols["v"])
    assert len(want) == live
    executor = engines[tier].device
    before = executor.hbm_stats()
    got, resp, spans = _traced(engines[tier], U_SQL.format(where=where))
    assert {(r[0], r[1]): (r[3], r[2]) for r in got} == want
    after = executor.hbm_stats()
    overflowed = after["groupby_narrow_overflows"] \
        - before["groupby_narrow_overflows"]
    if not live:
        return  # every segment pruned or masked: nothing to say of a launch
    assert after["groupby_narrowed_launches"] \
        > before["groupby_narrowed_launches"]
    if fits:
        assert overflowed == 0 and resp.get("numSegmentsOnHost", 0) == 0
        assert _key_spaces(resp) == ["narrowed"]
    else:
        # exact, and still the device's (ISSUE 36): the launch that
        # overflowed is launched again over the whole key space, and the
        # host answers nothing. The trace holds both launches; the later
        # one's spans are the full regime's
        assert overflowed == 0 and resp.get("numSegmentsOnHost", 0) == 0
        assert after["groupby_full_launches"] \
            == before["groupby_full_launches"] + 1
        assert spans["executor.dispatch"]["groupbyKeySpace"] == "full"
        assert spans["executor.device_wait"]["groupbyKeySpace"] == "full"
        assert spans["executor.device_wait"]["keySpaceLive"] == live
        assert "engine.host_fallback" not in spans
        assert _key_spaces(resp) == ["full"]


# ---- a cohort: one template, two literals, each narrows by its own mask ---


def _cohort(engine, sqls):
    co = engine.device.coalescer
    was = (co.force, co.window_s, co.max_cohort)
    co.force, co.window_s, co.max_cohort = True, 0.5, len(sqls)
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


@pytest.mark.parametrize("tier", ["pallas", "xla"])
def test_cohort_of_two_literals(engines, tier):
    sqls = [T_STATEMENTS["three_keys"][0].replace("ASINATION2", nation)
            for nation in ("ASINATION2", "EURNATION3")]
    want = [_rows(engines["host"], s)[0] for s in sqls]
    assert want[0] != want[1]
    got = _cohort(engines[tier], sqls)
    assert [g[0] for g in got] == want
    # the launch's record rides its leader's answer
    assert {k for _r, resp in got for k in _key_spaces(resp)} == {"narrowed"}
    assert all(resp.get("numSegmentsOnHost", 0) == 0 for _r, resp in got)


def test_a_cohort_member_that_overflows_is_launched_again_full(engines, data):
    """One member's live keys fit and the other's do not: the first keeps
    the cohort's answer, the second is launched again over the whole key
    space (ISSUE 36), both exact and neither the host's. An executor of
    its own: the shared one has seen this template full already."""
    sqls = [U_SQL.format(where=w) for w in ("sel <= 1", "sel <= 2")]
    want = [_rows(engines["host"], s)[0] for s in sqls]
    engine = _engine(data[0], mm_mode="interpret")
    got = _cohort(engine, sqls)
    assert [g[0] for g in got] == want
    assert [g[1].get("numSegmentsOnHost", 0) for g in got] == [0, 0]
    stats = engine.device.hbm_stats()
    assert stats["groupby_full_launches"] == 1
    assert stats["groupby_narrow_overflows"] == 0


# ---- a template's programs are built with its first answer -----------------

_BUILT = []


def _count_builds():
    """Executables built from here on (a listener cannot be taken off
    again, so one is registered a process)."""
    import jax

    if not _BUILT:
        _BUILT.append(0)

        def heard(event, _seconds, **_kw):
            if event == "/jax/core/compile/backend_compile_duration":
                _BUILT[0] += 1

        jax.monitoring.register_event_duration_secs_listener(heard)
    start = _BUILT[0]
    return lambda: _BUILT[0] - start


@pytest.mark.parametrize("name", ["three_keys", "small_two_keys"])
def test_the_dense_twin_is_built_with_the_first_answer(data, name):
    """A template's first answer builds its program and, beside it, the
    dense twin of its block-skip form; after it nothing is built: not by
    callers sending the template together (each launches a program of its
    own: no cohort program exists), nor by the advisor's switch to the
    dense form. A program first met under load would stall its callers
    for the seconds it takes to build."""
    engine = _engine(data[0], mm_mode="interpret")
    device = engine.device
    device.prebuild_dense = True  # as on a TPU
    sql = T_STATEMENTS[name][0]
    others = [sql.replace("ASINATION2", n).replace("B11", c)
              for n, c in (("EURNATION3", "B12"), ("AFRNATION1", "B13"),
                           ("AMENATION0", "B14"))]
    sqls = [sql] + others
    want = [_rows(engines_host(data), s)[0] for s in sqls]
    assert _rows(engine, sql)[0] == want[0]
    skipping = [e for e in device._pipelines.values() if e["dense"]]
    assert len(skipping) == 1 and skipping[0]["prebuilt"]
    assert skipping[0]["dense"] in device._pipelines.values()
    built = _count_builds()
    got = [None] * len(sqls)
    barrier = threading.Barrier(len(sqls))

    def caller(i):
        barrier.wait()
        got[i] = _rows(engine, sqls[i])[0]

    callers = [threading.Thread(target=caller, args=(i,))
               for i in range(len(sqls))]
    for t in callers:
        t.start()
    for t in callers:
        t.join()
    assert got == want
    # ... nor does the advisor's switch to the dense form, once it has seen
    # that block skip prunes nothing of this table (its fourth launch on)
    advice = []
    for turn in range(6):
        rows, resp = _rows(engine, sqls[turn % 4])
        assert rows == want[turn % 4]
        advice += resp.get("advisorDecisions") or []
    assert any("blockSkip=dense" in a for a in advice), advice
    assert built() == 0
    coalescer = device.coalescer
    assert (coalescer.cohorts_launched, coalescer.queries_coalesced) == (0, 0)
    assert all(e["cohort"] is None and not e["cohort_layouts"]
               for e in device._pipelines.values())


def engines_host(data, _memo=[]):
    if not _memo:
        _memo.append(_engine(data[0]))
    return _memo[0]


# ---- what the spans, EXPLAIN ANALYZE and /metrics say ----------------------


def test_spans_explain_and_counters(engines):
    engine = engines["pallas"]
    sql = T_STATEMENTS["three_keys"][0]
    _got, _resp, spans = _traced(engine, sql)
    for name in ("executor.dispatch", "executor.device_wait"):
        assert spans[name]["groupbyKeySpace"] == "narrowed"
        assert spans[name]["keySpaceCells"] == 437_500
    assert spans["executor.device_wait"]["keySpaceLive"] > 0
    _got, resp = _rows(engine, "EXPLAIN ANALYZE " + sql)
    text = json.dumps(resp)
    assert "groupbyKeySpace=narrowed" in text \
        and "keySpaceCells=437500" in text and "keySpaceLive=" in text
    stats = engine.device.hbm_stats()
    assert stats["groupby_narrowed_launches"] > 0
    assert "groupby_narrow_overflows" in stats


def test_the_servers_metrics_name_the_counters(tmp_path):
    from pinot_tpu.cluster.registry import ClusterRegistry
    from pinot_tpu.server.server import ServerInstance

    server = ServerInstance("server_0", ClusterRegistry(), str(tmp_path),
                            device_executor=DeviceExecutor())
    try:
        gauges = server.metrics.snapshot()["gauges"]
        assert gauges["server.deviceGroupbyNarrowed.server_0"] == 0
        assert gauges["server.deviceGroupbyNarrowOverflow.server_0"] == 0
    finally:
        server.stop()


# ---- the accepted cells' templates never meet the regime -------------------

# the compiled-pipeline key of benchmark/traffic/groupby_bands_c4.json's
# statement on a stand-in of its table, as the parent commit (35a68ef)
# builds it: a key that differs compiles another program for cell 1.
# (Since PR 33 the plan names one ids operand a key column, so its second
# part is a tuple; the program is the parent's, which
# test_tpu_compile.py's one-key case holds to.)
BANDS_KEY = (
    ("groupby", ("range_dict", "lo_discount", "pr0", "pr1"), ("lo_suppkey",),
     (2000,), (("sum", ("raw", "lo_revenue"), (3, None)),), 0, False),
    "interpret", True,
    (("lo_discount", ("|u1", 0, False, "")),
     ("lo_revenue", ("<i4", 0, False, "")),
     ("lo_suppkey", ("<u2", 0, False, ""))),
    (16, (("agg", 0, "sum", False),)), "interpret",
    ("pallas", ("gk::lo_suppkey",), ((0, "gv::lo_revenue::81000::3", 3),)))


def test_groupby_bands_pipeline_key_is_the_parents(tmp_path):
    rng = np.random.default_rng(5)
    n = 20_000
    cols = {"lo_suppkey": (np.arange(n) % 2000).astype(np.int32),
            "lo_discount": rng.integers(0, 11, n).astype(np.int32),
            "lo_revenue": rng.integers(81_000, 10_495_000, n)
            .astype(np.int32)}
    cols["lo_revenue"][0], cols["lo_revenue"][1] = 81_000, 10_494_999
    segs = _build(tmp_path, "lineorder", cols,
                  [("lo_suppkey", DataType.INT),
                   ("lo_discount", DataType.INT)], ["lo_revenue"])
    engine = _engine({"lineorder": segs}, mm_mode="interpret")
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           "groupby_bands_c4.json")) as f:
        sql = json.load(f)["statements"][0]["sql"]
    _got, resp = _rows(engine, sql)
    # the launched program's key, and its dense twin's (block skip off):
    # the key the parent gives the template once the advisor has seen
    # that block skip prunes nothing, entered here with the first launch
    twin = BANDS_KEY[:2] + (False,) + BANDS_KEY[3:]
    assert list(engine.device._pipelines) == [twin, BANDS_KEY]
    assert _key_spaces(resp) == ["dense"]


# ---- SSB's 13 flat statements through the served path ----------------------


@pytest.fixture(scope="module")
def ssb_flat_built(tmp_path_factory):
    """The tiny SSB table's segments, built once: (config, traffic, the
    reference's rows by statement, harness.reference, segment dirs, the
    work directory)."""
    sys.path.insert(0, os.path.join(ROOT, "benchmark"))
    try:
        from harness import reference as reference_mod
        from harness import table
    finally:
        sys.path.remove(os.path.join(ROOT, "benchmark"))
    with open(os.path.join(ROOT, "benchmark", "harness", "testdata",
                           "ssb_flat_tiny.json")) as f:
        config = json.load(f)
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           "ssb_flat_13q_c4.json")) as f:
        traffic = json.load(f)
    seed = 4_000_000_013  # no flight is empty at this one
    work = str(tmp_path_factory.mktemp("served_ssb_flat"))
    ref = reference_mod.Reference(config, traffic["statements"])
    schema = Schema.from_json(config["schema"])
    table_config = TableConfig.from_json(config["table_config"])
    dirs = []
    for k in range(config["segments"]):
        cols = table.lay_out(config, table.draw_segment(
            config, table.segment_rng(seed, k), seed=seed, k=k))
        ref.add({c: cols[c] for c in ref.columns})
        dirs.append(os.path.join(work, "server_0", "built", f"s{k}"))
        build_segment(schema, cols, dirs[-1], table_config, f"s{k}")
    return config, traffic, ref.rows(), reference_mod, dirs, work


@pytest.fixture(scope="module")
def served_ssb_flat(ssb_flat_built):
    sys.path.insert(0, os.path.join(ROOT, "benchmark"))
    try:
        from harness import cluster as cluster_mod
    finally:
        sys.path.remove(os.path.join(ROOT, "benchmark"))
    config, traffic, want, reference_mod, dirs, work = ssb_flat_built
    # the deployment's executor, with both kernel tiers in interpret mode
    config = dict(config, deployment=dict(
        config["deployment"],
        device_executor=DeviceExecutor(mm_mode="interpret")))
    cluster = cluster_mod.Cluster(config, work)
    try:
        cluster.load(dirs, lambda line: None)
        yield cluster, traffic, want, reference_mod
    finally:
        cluster.close()


def test_ssb_flat_13_statements_served(served_ssb_flat):
    from pinot_tpu import client

    cluster, traffic, want, reference_mod = served_ssb_flat
    conn = client.connect(cluster.url, timeout_s=120)
    for s in traffic["statements"]:
        cur = conn.cursor()
        cur.execute(traffic["set_prefix"] + s["sql"])
        rows = [list(r) for r in cur.fetchall()]
        assert reference_mod.answer_error(rows, want[s["name"]]) == 0.0, \
            s["name"]
        assert rows and rows[0][-1], s["name"]
        assert cur.stats.get("numSegmentsOnHost") == 0, s["name"]
        assert not cur.stats.get("partialResult"), s["name"]
    stats = cluster.executor.hbm_stats()
    # Q3.2, Q3.3, Q3.4 and Q4.3 narrowed (at this size s_city has 131 of
    # its 250 values: 229,250 and 917,000 cells), none overflowed
    assert stats["groupby_narrowed_launches"] == 4
    assert stats["groupby_narrow_overflows"] == 0
    assert not any(cluster.failure_counters().values())


# ---- both passes over the batch's prepared operands (ISSUE 33) -------------


@pytest.fixture(scope="module")
def flat_engines(ssb_flat_built):
    """The tiny SSB table under three engines: the prepared form, the
    per-launch form (the same executor under a byte budget no operand
    fits) and the host."""
    _config, traffic, _want, _ref, dirs, _work = ssb_flat_built
    tables = {"lineorder": [ImmutableSegment(d) for d in dirs]}
    engines = {"prepared": _engine(tables, mm_mode="interpret"),
               "perLaunch": _engine(tables, mm_mode="interpret"),
               "host": _engine(tables)}
    engines["perLaunch"].device.MAX_CACHED_BYTES = 1
    sqls = {s["name"]: s["sql"] for s in traffic["statements"]}
    return engines, sqls, tables


def _flat_variants(sqls):
    q3_2, q4_3 = sqls["q3_2"], sqls["q4_3"]
    return {
        "q3_2": q3_2, "q4_3": q4_3,
        # a filter that leaves no row and that no segment's statistics
        # can prune: the nation lies in another region
        "q3_2_no_row": q3_2.replace("WHERE ", "WHERE c_region = 'ASIA' AND "),
        "q4_3_no_row": q4_3.replace("c_region = 'AMERICA'",
                                    "c_region = 'AMERICA' AND "
                                    "s_region = 'EUROPE'"),
    }


@pytest.mark.parametrize("name", ["q3_2", "q4_3", "q3_2_no_row",
                                  "q4_3_no_row"])
def test_prepared_narrowed_is_per_launch_is_host(flat_engines, name):
    """Q3.2 (three one-byte keys, SUM of a column) and Q4.3 (a two-byte
    key among them, SUM of a - b) on the tiny SSB table: both passes over
    the batch's operands == both passes over the launch's own == the
    host, and the launch says which it was."""
    engines, sqls, _tables = flat_engines
    sql = _flat_variants(sqls)[name]
    want, _ = _rows(engines["host"], sql)
    assert bool(want) == (not name.endswith("no_row"))
    for form in ("perLaunch", "prepared"):
        got, resp, spans = _traced(engines[form], sql)
        assert got == want, (form, name)
        assert resp.get("numSegmentsOnHost", 0) == 0
        for phase in ("executor.dispatch", "executor.device_wait"):
            attrs = spans[phase]
            assert attrs["groupbyKeySpace"] == "narrowed", (form, attrs)
            assert attrs["groupbyOperands"] in (
                ("perLaunch",) if form == "perLaunch"
                else ("prepared", "built")), (form, attrs)
        rec = resp["roofline"][0]
        assert rec["groupbyKeySpace"] == "narrowed"
        assert (rec["keySpaceLive"] > 0) == bool(want)


def test_a_key_space_counted_full_is_the_devices(flat_engines):
    """Q3.2's key space with no nation named: more than 128 live blocks.
    The template's first launch counts them and takes the full regime
    (ISSUE 36; the host answered until then): the device answers,
    exactly, from the batch's rows in key order or, under a byte budget
    no operand fits, by the XLA scatter."""
    engines, sqls, _tables = flat_engines
    sql = sqls["q3_2"].replace(
        "c_nation = 'UNITED STATES' AND s_nation = 'UNITED STATES' AND ", "")
    assert sql != sqls["q3_2"]
    # over 100,000 groups are alive: under the default numGroupsLimit the
    # trimmed table falls to the host, as it did before the regime
    sql = "SET numGroupsLimit = 4194304; " + sql
    want, _ = _rows(engines["host"], sql)
    assert len(want) == 1000
    for form in ("perLaunch", "prepared"):
        executor = engines[form].device
        before = executor.hbm_stats()
        got, resp, spans = _traced(engines[form], sql)
        assert got == want, form
        assert resp.get("numSegmentsOnHost", 0) == 0
        after = executor.hbm_stats()
        assert after["groupby_narrow_overflows"] == 0
        assert after["groupby_narrowed_launches"] \
            == before["groupby_narrowed_launches"]
        assert after["groupby_key_space_probes"] \
            == before["groupby_key_space_probes"] + 1
        assert after["groupby_full_launches"] \
            == before["groupby_full_launches"] + 1
        assert spans["executor.dispatch"]["groupbyKeySpace"] == "full"
        assert spans["executor.device_wait"]["groupbyKeySpace"] == "full"
        assert spans["executor.dispatch"]["groupbyOperands"] in (
            ("perLaunch",) if form == "perLaunch" else ("prepared", "built"))


def test_narrowed_statements_share_a_batchs_operands(flat_engines):
    """Q3.2 builds the ids of c_city, s_city, d_year and lo_revenue's
    planes; Q3.3, Q3.4 (same keys, same argument) read them; Q4.3 adds
    p_brand1's ids and the planes of lo_revenue - lo_supplycost and shares
    s_city's and d_year's; every launch is counted by its operands."""
    _engines, sqls, tables = flat_engines
    engine = _engine(tables, mm_mode="interpret")
    host = _engines["host"]
    seen = []
    for name in ("q3_2", "q3_3", "q3_4", "q4_3", "q4_3"):
        got, resp = _rows(engine, sqls[name])
        assert got == _rows(host, sqls[name])[0], name
        rec = resp["roofline"][0]
        seen.append((rec["groupbyKeySpace"], rec["groupbyOperands"],
                     engine.device.groupby_operand_bytes()))
    unit = seen[0][2] // 6  # three one-byte id columns, three planes
    assert seen == [("narrowed", "built", 6 * unit),
                    ("narrowed", "prepared", 6 * unit),
                    ("narrowed", "prepared", 6 * unit),
                    ("narrowed", "built", 11 * unit),
                    ("narrowed", "prepared", 11 * unit)], seen
    ctx = engine.device.batch_for(tables["lineorder"])
    assert sorted(k for k in ctx._gb_operands if k.startswith("gk::")) == [
        "gk::c_city", "gk::d_year", "gk::p_brand1", "gk::s_city"]
    stats = engine.device.hbm_stats()
    assert stats["groupby_operand_launches"] == {
        "prepared": 3, "built": 2, "perLaunch": 0}
    assert stats["groupby_narrowed_launches"] == 5


# ---- the configuration the regime was built for ----------------------------


def _bench(*parts):
    with open(os.path.join(ROOT, "benchmark", *parts)) as f:
        return json.load(f)


def test_ssb_sf100_chipshare_is_the_tiny_file_at_sf100():
    """benchmark/configs/ssb_sf100_chipshare.json is the tiny file with
    its scale at SF 100 and its rows one chip's share: no column, width,
    cardinality, hierarchy or index of the source differs."""
    tiny = _bench("harness", "testdata", "ssb_flat_tiny.json")
    big = _bench("configs", "ssb_sf100_chipshare.json")
    for key in ("deployment", "guarantees", "table", "layout", "schema",
                "table_config"):
        assert big[key] == tiny[key], key
    assert set(tiny["assumed"]) < set(big["assumed"])
    assert big["segments"] * big["rows_per_segment"] \
        == big["scale"]["lineorder_rows"] == 37_500_000
    assert big["reduced"] and "lineorder_rows" in big["reduced"][0]
    scaled = {"lo_orderkey": 600_000_001, "lo_custkey": 3_000_001,
              "lo_partkey": 1_400_001, "lo_suppkey": 200_001}
    seeded = {"c_cityid": 3_000_000, "s_cityid": 200_000,
              "p_brandid": 1_400_000}
    for g_big, g_tiny in zip(big["generator"], tiny["generator"]):
        want = json.loads(json.dumps(g_tiny))
        if g_big["column"] in scaled:
            want["high"] = scaled[g_big["column"]]
        if g_big["column"] in seeded:
            want["seeded"]["rows"] = seeded[g_big["column"]]
        assert g_big == want, g_big["column"]
    assert len(big["generator"]) == len(tiny["generator"])


def test_algorithmic_bytes_of_the_flat_statements():
    """What scan_roofline divides by in ssb_sf100_chipshare.flat_13q: it
    reads for all 13 statements, all 3 segments of the 37.5M rows (generated
    order prunes nothing) x the named columns' widths."""
    sys.path.insert(0, os.path.join(ROOT, "benchmark"))
    try:
        from harness import algbytes
    finally:
        sys.path.remove(os.path.join(ROOT, "benchmark"))
    config = _bench("configs", "ssb_sf100_chipshare.json")
    got = {s["name"]: algbytes.statement_bytes(config, s)
           for s in _bench("traffic", "ssb_flat_13q_c4.json")["statements"]}
    assert len(got) == 13 and all(got.values())
    assert (got["q1_1"], got["q2_1"], got["q3_2"]) \
        == (225_000_000, 300_000_000, 300_000_000)
