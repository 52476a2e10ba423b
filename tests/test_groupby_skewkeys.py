"""Skewed entity keys: configuration ``ssb_sf100_skewkeys``, whose three
foreign keys are drawn Zipf with z = 1 (benchmark/generators/zipf.py), and
its mix ``ssb_skewkeys_6q_c4``, the cell on the skewed side of the full key
space's layout choice (engine/device.py FULL_SLOT_PADDING).

The kind alone; the configuration held to ``ssb_sf100_fullkeys`` key by
key; the mix on the tiny skewed table (benchmark/harness/testdata/
ssb_skewkeys_tiny.json) served full, SPILLED and prepared, every row equal
to benchmark/harness/reference.py's, each launch saying its layout
(``slotRows``, ``spillCells``, ``spillChunks``) and why it is not slotted
(``fullestCellRows``, ``slotPadding``, ``planeBits``) on its spans, in its
flight record, in EXPLAIN ANALYZE and in the counters; the byte budget's
fallback to the ORDERED planes; the same table with uniform keys laid out
by the same rule; and ``groupby_ordered_share``'s reader.
"""

import copy
import json
import os

import numpy as np
import pytest

from pinot_tpu.common.schema import Schema
from pinot_tpu.common.table_config import TableConfig
from pinot_tpu.engine import device as device_mod
from pinot_tpu.engine.device import DeviceExecutor
from pinot_tpu.ops import keysorted as ks
from pinot_tpu.storage.creator import build_segment
from pinot_tpu.storage.segment import ImmutableSegment

from test_groupby_fullkeys import _bench, _ctx_of, _harness
from test_groupby_narrowed import _engine, _rows, _traced

NAMES = ["top_suppliers_q15", "top_customers_q10", "top_parts", "supp_year",
         "citypair_1997", "city_brand"]
KEYS = ("lo_custkey", "lo_partkey", "lo_suppkey")
SEED = 4_000_000_040
CELL = "ssb_sf100_skewkeys.rank_keys_6q"


def _kind():
    table, = _harness("table")
    return table.kind_of({"kind": "zipf"})


def _zipf_entry(n_keys, z=1, column="lo_suppkey", low=1):
    return {"column": column, "kind": "zipf", "low": low,
            "high": low + n_keys, "z": z}


def _draw(entry, seed, n, stream=0):
    kind = _kind()
    rng = np.random.default_rng([seed, stream])
    return kind.column(entry, kind.draw(entry, rng, n), {}, seed)


# ---- the kind ---------------------------------------------------------------


def test_zipf_is_the_runs_and_the_columns():
    """The same seed and column give the same keys, whichever segment
    draws them (the rank -> key map is the run's); another seed or another
    column's name gives other hot keys."""
    entry = _zipf_entry(40_000)
    a, b = _draw(entry, 7, 50_000), _draw(entry, 7, 50_000)
    assert (a == b).all()
    kind = _kind()
    hot = {}
    for seed, column in ((7, "lo_suppkey"), (8, "lo_suppkey"),
                         (7, "lo_custkey")):
        e = dict(entry, column=column)
        # two segments' streams of one run agree on which key is hottest
        tops = {int(np.bincount(_draw(e, seed, 200_000, k)).argmax())
                for k in range(2)}
        assert len(tops) == 1
        hot[(seed, column)] = tops.pop()
        assert hot[(seed, column)] == e["low"] + kind._keys(
            column, 40_000, seed)[0]
    assert len(set(hot.values())) == 3, hot


@pytest.mark.parametrize("n_keys,low", [(3, 0), (40_000, 1),
                                        (200_000, 1), (3_000_000, 1)])
def test_zipf_stays_in_its_range_as_integers_does(n_keys, low):
    entry = _zipf_entry(n_keys, low=low)
    got = _draw(entry, 11, 100_000)
    assert got.dtype == np.int32
    assert got.min() >= low and got.max() < low + n_keys
    kind, table = _kind(), _harness("table")[0]
    plain = table.kind_of({"kind": "integers"})
    for fn in ("domain_size",):
        assert getattr(kind, fn)(entry) == getattr(plain, fn)(entry) == n_keys
    idx = np.array([0, n_keys - 1])
    assert (kind.value_of(entry, idx) == plain.value_of(entry, idx)).all()
    assert (kind.index_of(entry, got) == plain.index_of(entry, got)).all()


@pytest.mark.parametrize("n_keys,z", [(40_000, 1), (200_000, 1),
                                      (3_000_000, 1), (1_000, 0.5),
                                      (1_000, 2)])
def test_the_hottest_key_holds_one_over_h_of_the_rows(n_keys, z):
    """1 / H(N, z) of the rows, within 4% of it (at 1,000,000 rows that is
    five standard deviations or more), the next rank 2^z times fewer."""
    n = 1_000_000
    counts = np.sort(np.bincount(_draw(_zipf_entry(n_keys, z), 3, n)))[::-1]
    h = np.sum(np.arange(1, n_keys + 1, dtype=np.float64) ** -float(z))
    assert counts[0] / n == pytest.approx(1 / h, rel=0.04)
    assert counts[0] / counts[1] == pytest.approx(2 ** z, rel=0.1)


def test_the_hot_keys_are_not_the_low_ids():
    entry = _zipf_entry(200_000)
    counts = np.bincount(_draw(entry, 5, 1_000_000) - entry["low"])
    hottest = np.argsort(counts)[::-1][:20]
    assert len(set(hottest.tolist()) & set(range(20))) <= 1
    assert len(set(hottest.tolist()) & set(range(199_980, 200_000))) <= 1


@pytest.mark.parametrize("bad", [{"z": None}, {"z": -1}, {"high": 1}])
def test_zipf_refuses_an_entry_without_z_or_range(bad):
    entry = {k: v for k, v in dict(_zipf_entry(10), **bad).items()
             if v is not None}
    with pytest.raises(SystemExit, match="zipf"):
        _kind().check(entry)


# ---- the configuration, its cell and its mix -------------------------------


def test_ssb_sf100_skewkeys_is_fullkeys_but_the_three_keys_kind():
    ours = _bench("configs", "ssb_sf100_skewkeys.json")
    theirs = _bench("configs", "ssb_sf100_fullkeys.json")
    assert list(ours) == list(theirs)
    for key in ("schema", "table_config", "scale", "segments",
                "rows_per_segment", "layout", "deployment", "guarantees",
                "reduced", "table"):
        assert ours[key] == theirs[key], key
    assert [g["column"] for g in ours["generator"]] \
        == [g["column"] for g in theirs["generator"]]
    for mine, base in zip(ours["generator"], theirs["generator"]):
        if mine["column"] in KEYS:
            assert base["kind"] == "integers"
            assert mine == dict(base, kind="zipf", z=1), mine["column"]
        else:
            assert mine == base, mine["column"]
    assert ours["name"] == "ssb_sf100_skewkeys"
    assert len(ours["source"]) <= 200 and "Zipf z=1" in ours["source"]
    said = " ".join(ours["assumed"])
    for word in ("Chaudhuri", "JCC-H", "z = 1", "permutation",
                 "independently", "l_returnflag"):
        assert word in said, word
    entry = next(c for c in _bench("..", "BENCHMARK.json")["configs"]
                 if c["name"] == "ssb_sf100_skewkeys")
    assert entry == {"name": "ssb_sf100_skewkeys", "source": ours["source"],
                     "file": "benchmark/configs/ssb_sf100_skewkeys.json",
                     "reduced": ["lineorder_rows"], "why": entry["why"]}


def test_the_cell_and_its_metric():
    bench = _bench("..", "BENCHMARK.json")
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert {k: cell[k] for k in ("name", "config", "traffic", "chips")} == {
        "name": CELL, "config": "ssb_sf100_skewkeys",
        "traffic": "ssb_skewkeys_6q_c4", "chips": 1}
    assert len(cell["why"]) <= 200 and "ORDERED" in cell["why"]
    assert next(m for m in bench["per_layer"]
                if m["name"] == "groupby_ordered_share") == {
        "name": "groupby_ordered_share", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "kernels",
        "moves": "queries_per_s", "workloads": [CELL]}
    spec, = _harness("spec")
    readers = [m["name"] for m, _ in spec.Cell(CELL).layer_readers()]
    assert "groupby_ordered_share" in readers and "scan_roofline" in readers
    assert "groupby_slotted_share" not in readers


def test_the_mix_is_rank_6qs_loop_over_the_issues_statements():
    mix = _bench("traffic", "ssb_skewkeys_6q_c4.json")
    base = _bench("traffic", "ssb_fullkeys_6q_c4.json")
    assert list(mix) == list(base)
    for key in ("loop", "clients", "think_ms", "think_why", "set_prefix",
                "trace_prefix", "warmup_seconds"):
        assert mix[key] == base[key], key
    assert [s["name"] for s in mix["statements"]] == NAMES
    theirs = {s["name"]: s for s in base["statements"]}
    for s in mix["statements"]:
        assert s["weight"] == 1 and s["device"] is True
        assert s["sql"].startswith("SET useStarTree = false; SELECT ")
        assert s["reference"]["order_by"] == [["agg", 0, "desc"]]
        assert f"LIMIT {s['reference']['limit']}" in s["sql"]
        if s["name"] in theirs:
            assert s == theirs[s["name"]]
    one_key = {s["name"]: (s["reference"]["group_by"],
                           s["reference"]["where"], s["reference"]["limit"])
               for s in mix["statements"][:3]}
    assert one_key == {
        "top_suppliers_q15": (["lo_suppkey"], [
            ["lo_orderdate", "between", 19960101, 19960331]], 10),
        "top_customers_q10": (["lo_custkey"], [
            ["lo_orderdate", "between", 19931001, 19931231]], 20),
        "top_parts": (["lo_partkey"], [["lo_discount", "between", 1, 3]],
                      10)}


# ---- the tiny skewed table, and its uniform twin ---------------------------


def _tiny(uniform=False):
    config = _bench("harness", "testdata", "ssb_skewkeys_tiny.json")
    if uniform:
        config = copy.deepcopy(config)
        for g in config["generator"]:
            if g["kind"] == "zipf":
                g["kind"] = "integers"
                del g["z"]
    return config


def _build_tiny(work, config, statements, modes):
    reference_mod, table = _harness("reference", "table")
    refs ={mode: reference_mod.Reference(config, statements, mode)
            for mode in modes}
    schema = Schema.from_json(config["schema"])
    table_config = TableConfig.from_json(config["table_config"])
    dirs = []
    for k in range(config["segments"]):
        cols = table.lay_out(config, table.draw_segment(
            config, table.segment_rng(SEED, k), seed=SEED, k=k))
        for ref in refs.values():
            ref.add({c: cols[c] for c in ref.columns})
        dirs.append(os.path.join(work, "server_0", "built", f"s{k}"))
        build_segment(schema, cols, dirs[-1], table_config, f"s{k}")
    return dirs, {mode: ref.rows() for mode, ref in refs.items()}


@pytest.fixture(scope="module")
def built(tmp_path_factory):
    reference_mod, = _harness("reference")
    traffic = _bench("traffic", "ssb_skewkeys_6q_c4.json")
    config = _tiny()
    work = str(tmp_path_factory.mktemp("served_skewkeys"))
    dirs, want = _build_tiny(work, config, traffic["statements"],
                             reference_mod.MODES)
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
    _config, traffic, want, reference_mod, dirs, _work = built
    tables = {"lineorder": [ImmutableSegment(d) for d in dirs]}
    sqls = {s["name"]: traffic["set_prefix"] + s["sql"]
            for s in traffic["statements"]}
    return {"device": _engine(tables, mm_mode="interpret"),
            "host": _engine(tables)}, sqls, tables, want["exact"], \
        reference_mod


@pytest.fixture(scope="module")
def uniform(tmp_path_factory, built):
    _config, traffic, _want, reference_mod, _dirs, _work = built
    work = str(tmp_path_factory.mktemp("uniform_twin"))
    dirs, want = _build_tiny(work, _tiny(uniform=True),
                             traffic["statements"], ("exact",))
    tables = {"lineorder": [ImmutableSegment(d) for d in dirs]}
    return _engine(tables, mm_mode="interpret"), tables, want["exact"]


def _group_by(traffic, name):
    return tuple(next(s for s in traffic["statements"]
                      if s["name"] == name)["reference"]["group_by"])


def _why(ctx, group_cols, cells):
    """What the launch should say of its key order: the fullest cell, the
    padding the slotted planes would cost, the planes' width."""
    fullest = ctx.key_order_rows(group_cols)
    padding = ks.slot_rows(fullest) * ks.slot_lanes(cells) / ctx.lane_rows()
    return {"fullestCellRows": fullest, "slotPadding": round(padding, 2),
            "planeBits": ks.plane_bits_for(fullest)}


def _spilled(ctx, group_cols):
    """What a SPILLED launch should say of its layout, from the key order's
    rows a cell (numpy): K by the rule, the cells past K, their chunks."""
    starts = np.asarray(ctx.groupby_operand("gs::" + ",".join(group_cols))[0])
    rows = np.diff(starts)
    k = ks.layout_rows(rows, device_mod.FULL_SLOT_PADDING * ctx.lane_rows(),
                       device_mod.FULL_SPILL_FAT_SLOTS)
    assert 0 < k < ks.slot_rows(rows.max()) and k & (k - 1) == 0
    over = rows[rows > k]
    return {"slotRows": k, "spillCells": int(over.size),
            "spillChunks": int(np.sum(-(-over // k) - 1))}


@pytest.mark.parametrize("name", NAMES)
def test_statement_served_is_the_reference_and_ordered(served, name):
    """Twice through HTTP -> broker -> server: exact, on the device,
    complete, every launch full and spilled, the slotted and ordered
    counters still."""
    from pinot_tpu import client

    cluster, traffic, want, reference_mod = served
    s = next(s for s in traffic["statements"] if s["name"] == name)
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
    for counter, grew in (("groupby_full_launches", 2),
                          ("groupby_spilled_launches", 2),
                          ("groupby_ordered_launches", 0),
                          ("groupby_slotted_launches", 0),
                          ("groupby_narrow_overflows", 0)):
        assert after[counter] == before[counter] + grew, counter
    assert after["groupby_operand_launches"]["perLaunch"] \
        == before["groupby_operand_launches"]["perLaunch"]
    # a value, a segment and maybe a filter plane, a slot each, and the
    # slots hold at least the batch's rows
    assert after["groupby_spilled_bytes"] >= 5 * 600_000
    assert after["groupby_ordered_bytes"] == after["groupby_slotted_bytes"] \
        == 0
    gauges = cluster.server.metrics.snapshot()["gauges"]
    assert gauges["server.deviceGroupbySpilled.server_0"] \
        == after["groupby_spilled_launches"]
    assert gauges["server.deviceGroupbySlotted.server_0"] \
        == gauges["server.deviceGroupbyOrdered.server_0"] == 0
    assert not any(cluster.failure_counters().values())


@pytest.mark.parametrize("name", NAMES)
def test_the_ordered_launch_says_why(engines, name):
    """device == host == reference; both spans, the flight record and
    EXPLAIN ANALYZE say full, spilled, the layout (K a power of two below
    the fullest cell, the fat cells and their chunks, as numpy reckons them
    from the key order) and what the key order holds: a fullest cell
    thousands of times the mean, a padding past FULL_SLOT_PADDING, the
    planes' width for that cell and not for K."""
    by, sqls, tables, want, reference_mod = engines
    traffic = _bench("traffic", "ssb_skewkeys_6q_c4.json")
    host, _ = _rows(by["host"], sqls[name])
    for operands in ("built", "prepared"):
        got, resp, spans = _traced(by["device"], sqls[name])
        assert got == host, name
        assert reference_mod.answer_error(got, want[name]) == 0.0
        assert resp.get("numSegmentsOnHost", 0) == 0
        ctx = _ctx_of(by["device"], tables["lineorder"])
        cells = spans["executor.dispatch"]["keySpaceCells"]
        why = _why(ctx, _group_by(traffic, name), cells)
        assert why["slotPadding"] > device_mod.FULL_SLOT_PADDING
        assert why["fullestCellRows"] > 100 * 600_000 // cells
        why.update(_spilled(ctx, _group_by(traffic, name)))
        assert why["spillCells"] and why["spillChunks"] >= why["spillCells"]
        assert why["planeBits"] < ks.plane_bits_for(why["slotRows"])
        for phase in ("executor.dispatch", "executor.device_wait"):
            attrs = spans[phase]
            assert (attrs["groupbyKeySpace"], attrs["groupbyKeyLayout"],
                    attrs["groupbyOperands"]) \
                == ("full", "spilled", operands), (phase, attrs)
            assert {k: attrs[k] for k in why} == why, (phase, attrs)
        rec = resp["roofline"][0]
        assert rec["groupbyKeyLayout"] == "spilled"
        assert {k: rec[k] for k in why} == why
    _got, resp = _rows(by["device"], sqls[name].replace(
        "SELECT ", "EXPLAIN ANALYZE SELECT ", 1))
    text = json.dumps(resp)
    for k, v in dict(why, groupbyKeyLayout="spilled").items():
        assert f"{k}={v}" in text, k


@pytest.mark.parametrize("name", NAMES)
def test_the_same_table_with_uniform_keys_goes_slotted(uniform, built, name):
    """The choice from the other side: the twin's one-key statements and
    its city pairs fit within the padding bound and are laid out cell by
    slot; a statement's layout is slotted exactly where its padding says
    so, and where it does not (``supp_year`` and ``city_brand``, whose key
    spaces are mostly empty at this size) spilled at the rule's K; the
    answers are the twin's reference."""
    engine, tables, want = uniform
    traffic, reference_mod = built[1], built[3]
    sql = traffic["set_prefix"] + next(
        s["sql"] for s in traffic["statements"] if s["name"] == name)
    before = engine.device.hbm_stats()
    got, _resp, spans = _traced(engine, sql)
    assert reference_mod.answer_error(got, want[name]) == 0.0
    attrs = spans["executor.dispatch"]
    ctx = _ctx_of(engine, tables["lineorder"])
    why = _why(ctx, _group_by(traffic, name), attrs["keySpaceCells"])
    assert {k: attrs[k] for k in why} == why
    slotted = why["slotPadding"] <= device_mod.FULL_SLOT_PADDING
    assert attrs["groupbyKeyLayout"] == ("slotted" if slotted else "spilled")
    assert slotted or name in ("supp_year", "city_brand")
    if slotted:
        assert attrs["slotRows"] == ks.slot_rows(why["fullestCellRows"])
        assert "spillCells" not in attrs and "spillChunks" not in attrs
    else:
        spilled = _spilled(ctx, _group_by(traffic, name))
        assert {k: attrs[k] for k in spilled} == spilled
    after = engine.device.hbm_stats()
    assert after["groupby_slotted_launches"] \
        == before["groupby_slotted_launches"] + slotted
    assert after["groupby_spilled_launches"] \
        == before["groupby_spilled_launches"] + (not slotted)
    assert after["groupby_ordered_launches"] \
        == before["groupby_ordered_launches"]


def test_spilled_planes_past_the_byte_budget_keep_the_order(built, engines):
    """Where the spilled planes would pass the batch's byte budget and the
    ordered ones fit, the launch keeps the ORDERED planes - the budget's
    fallback - says so, and is the reference's answer."""
    _config, traffic, _want, _ref, _dirs, _work = built
    by, sqls, tables, want, reference_mod = engines
    name = "top_parts"
    names = ",".join(_group_by(traffic, name))
    _rows(by["device"], sqls[name])  # the spilled launch's operands
    k = _spilled(_ctx_of(by["device"], tables["lineorder"]),
                 names.split(","))["slotRows"]
    spill = f"::spill{k}::"
    built_keys = [key for key in _ctx_of(
        by["device"], tables["lineorder"])._gb_operands
        if key.startswith(f"gp::{names}{spill}")]
    assert len(built_keys) == 3  # the segment, the filter's column, the value
    engine = _engine(tables, mm_mode="interpret")
    _rows(engine, "SELECT COUNT(*) FROM lineorder WHERE lo_discount < 3 "
                  "AND lo_revenue >= 0 GROUP BY lo_quantity LIMIT 1")
    ctx = _ctx_of(engine, tables["lineorder"])
    ctx.key_order_rows(names.split(","))
    # what either layout is built from: the value's byte planes
    sources = [key.split("::", 2)[2] for key in built_keys if "gv::" in key]
    ordered = ctx.groupby_operand_cost(
        sources + [key.replace(spill, "::") for key in built_keys])
    spilled = ctx.groupby_operand_cost(
        sources + built_keys + list(ctx.spill_keys(names, k)))
    assert spilled > ordered
    dev = engine.device
    dev.MAX_CACHED_BYTES = ctx.device_bytes() + (ordered + spilled) // 2
    got, _resp, spans = _traced(engine, sqls[name])
    assert reference_mod.answer_error(got, want[name]) == 0.0
    attrs = spans["executor.dispatch"]
    assert (attrs["groupbyKeyLayout"], attrs["groupbyOperands"]) \
        == ("ordered", "built")
    assert "slotRows" not in attrs and "spillCells" not in attrs
    stats = dev.hbm_stats()
    assert stats["groupby_ordered_launches"] == 1
    assert stats["groupby_spilled_launches"] == 0
    assert ctx.device_bytes() <= dev.MAX_CACHED_BYTES


@pytest.mark.parametrize("mode", ["f32_partials", "drop_segment"])
def test_a_planted_control_fails_the_comparison(built, mode):
    """The reference with one guarantee broken is not correct on this mix:
    float32 partial sums round the hot keys' revenue, a dropped segment
    loses an eighth of every cell's rows."""
    _config, _traffic, want, reference_mod, _dirs, _work = built
    verdict = reference_mod.compare(
        [{"ok": True, "statement": name, "rows": want[mode][name]}
         for name in NAMES], want["exact"])
    assert not verdict["correct"]
    assert verdict["numbers"]["answers_wrong"]["value"] >= 3
    assert reference_mod.compare(
        [{"ok": True, "statement": name, "rows": want["exact"][name]}
         for name in NAMES], want["exact"])["correct"]


# ---- groupby_ordered_share's reader ----------------------------------------


def _span(phase, span_id, parent, start, end, **attrs):
    return {"phase": phase, "spanId": span_id, "parentId": parent,
            "start": float(start), "end": float(end),
            "durationMs": float(end - start), "attrs": attrs}


def _request(space, layout=None, operands="prepared", host=False,
             span_id=1):
    """One traced request whose launch said ``space``, ``layout`` (None: no
    layout, as a launch whose operands were declined) and ``operands``;
    ``host``: the merge counted its segments on the host."""
    attrs = {"groupbyKeySpace": space, "keySpaceCells": 1_400_000,
             "groupbyOperands": operands}
    if layout:
        attrs.update(groupbyKeyLayout=layout, fullestCellRows=2_930_000,
                     slotPadding=3900.0, planeBits=8)
    return [_span("http.request", span_id, None, 0, 50),
            _span("executor.dispatch", span_id + 1, span_id, 5, 10,
                  launchId=span_id, **attrs),
            _span("executor.device_wait", span_id + 2, span_id, 10, 40,
                  launchId=span_id, **attrs),
            _span("engine.merge", span_id + 3, span_id, 48, 49,
                  segmentsOnDevice=0 if host else 3,
                  segmentsOnHost=3 if host else 0)]


def _reader():
    spec, = _harness("spec")
    return spec.found("layer_metrics", "groupby_ordered_share")


@pytest.mark.parametrize("launches,want", [
    ([("full", "ordered"), ("full", "ordered")], 100.0),
    # the byte budget declined the operands: the XLA scatter, no layout
    ([("full", "ordered"), ("full", None, "perLaunch")], 50.0),
    ([("full", "ordered")] * 3 + [("full", "slotted")], 75.0),
    ([("full", "ordered"), ("full", "ordered", "built")], 50.0),
    # a request the host answered is not the share's; other regimes neither
    ([("full", "ordered"), ("full", "slotted", "prepared", True),
      ("narrowed", None), ("dense", None)], 100.0),
    ([("full", "slotted")], 0.0),
    ([("dense", None), ("narrowed", None)], None),
    ([], None),
])
def test_groupby_ordered_share(launches, want):
    traces = [_request(*launch, span_id=10 * i + 1)
              for i, launch in enumerate(launches)]
    mod = _reader()
    got = mod.read({"spans_in_slice": traces or None})
    assert got == pytest.approx(want) if want is not None else got is None
    assert (mod.LAYER, mod.UNIT, mod.MOVES) \
        == ("kernels", "%", "queries_per_s")
    assert mod.read({}) is None
