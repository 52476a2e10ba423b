"""ISSUE 11 — performance X-ray: kernel roofline accounting, EXPLAIN
ANALYZE, and segment-temperature telemetry.

Covers the three tentpole pieces and their satellites:

- the per-flight bytes-moved/GB/s accounting the device executor records
  on every fetch (hbm_stats roofline section, per-query response fields);
- ``EXPLAIN ANALYZE`` on single-stage group-bys and multi-stage joins,
  embedded and through a real broker/server cluster — per-node actual
  rows/ms, the per-kernel ``KERNEL(<label>: x GB/s, …)`` line, and the
  bit-identical-results contract (``analyzedResponse``);
- the decayed per-segment heat tracker (server/heat.py), its heartbeat
  piggyback, the controller's ``GET /tables/{t}/heat`` aggregation, and
  the ``tools/clusterstat.py`` CLI;
- the Prometheus name sanitizer (legal exposition under
  ``prometheus_client`` for instance/attempt-keyed metrics), the query
  log summarizer's result-cache rate + scatter waterfall slot.
"""

import json
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from pinot_tpu.cluster.registry import ClusterRegistry
from pinot_tpu.common.datatypes import DataType
from pinot_tpu.common.schema import Schema
from pinot_tpu.common.table_config import TableConfig
from pinot_tpu.controller.controller import Controller, aggregate_heat
from pinot_tpu.engine.engine import QueryEngine
from pinot_tpu.server.server import ServerInstance
from pinot_tpu.storage.creator import build_segment
from pinot_tpu.storage.segment import ImmutableSegment


def wait_until(cond, timeout=15.0, interval=0.05):
    t0 = time.time()
    while time.time() - t0 < timeout:
        if cond():
            return True
        time.sleep(interval)
    return False


# ---------------------------------------------------------------------------
# fixtures
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def xray_engine(tmp_path_factory):
    """Embedded engine: a device-eligible fact table plus a dim table for
    join ANALYZE."""
    base = tmp_path_factory.mktemp("xray")
    fact_schema = Schema.build(
        name="xf",
        dimensions=[("k", DataType.STRING)],
        metrics=[("v", DataType.INT)],
    )
    dim_schema = Schema.build(
        name="xd",
        dimensions=[("k", DataType.STRING), ("grp", DataType.STRING)],
        metrics=[],
    )
    eng = QueryEngine()
    rng = np.random.default_rng(7)
    fcfg = TableConfig(table_name="xf")
    for i in range(2):
        cols = {
            "k": np.array(["a", "b", "c", "d"])[rng.integers(0, 4, 8000)],
            "v": rng.integers(0, 50, 8000).astype(np.int32),
        }
        d = str(base / f"f{i}")
        build_segment(fact_schema, cols, d, fcfg, f"xf_s{i}")
        eng.add_segment("xf", ImmutableSegment(d))
    dcfg = TableConfig(table_name="xd", is_dim_table=True)
    dcols = {"k": np.array(["a", "b", "c", "d"]),
             "grp": np.array(["x", "x", "y", "y"])}
    dd = str(base / "d0")
    build_segment(dim_schema, dcols, dd, dcfg, "xd_s0")
    eng.add_segment("xd", ImmutableSegment(dd))
    eng.table("xd").is_dim_table = True
    return eng


GROUPBY_SQL = "SELECT k, COUNT(*), SUM(v) FROM xf GROUP BY k ORDER BY k"
JOIN_SQL = ("SELECT xd.grp, SUM(xf.v) FROM xf JOIN xd ON xf.k = xd.k "
            "GROUP BY xd.grp ORDER BY xd.grp")


# ---------------------------------------------------------------------------
# tentpole 1: kernel roofline accounting
# ---------------------------------------------------------------------------


class TestRooflineAccounting:
    def test_query_response_carries_roofline(self, xray_engine):
        r = xray_engine.execute(GROUPBY_SQL)
        assert not r.get("exceptions")
        recs = r.get("roofline")
        assert recs, "device query recorded no roofline flight"
        rec = recs[0]
        assert rec["kernel"].startswith("groupby")
        assert rec["bytesMoved"] > 0 or rec["cacheHit"]
        # the per-query stat sums agree with the flight records
        assert r["deviceKernelMs"] >= 0
        assert r["deviceBytesMoved"] == sum(
            x.get("bytesMoved", 0) for x in recs)
        if not rec["cacheHit"]:
            assert rec["gbps"] > 0

    def test_hbm_stats_roofline_section(self, xray_engine):
        xray_engine.execute(GROUPBY_SQL)
        roof = xray_engine.device.hbm_stats()["roofline"]
        kernels = roof["kernels"]
        assert any(k.startswith("groupby") for k in kernels)
        entry = next(v for k, v in kernels.items()
                     if k.startswith("groupby"))
        assert entry["queries"] >= 1
        assert entry["kernel_ms"] >= 0
        # the achieved GB/s divides by the device's time on the launches
        assert entry["run_ms"] > 0 and entry["queue_ms"] >= 0
        assert entry["gbps"] == pytest.approx(
            entry["bytes_moved"] / entry["run_ms"] / 1e6, rel=0.01, abs=2e-3)

    def test_kernel_gbps_histogram_feeds_metrics(self, xray_engine):
        from pinot_tpu.common.metrics import get_metrics

        xray_engine.device.partials_cache_enabled = False
        try:
            xray_engine.execute(GROUPBY_SQL)
        finally:
            xray_engine.device.partials_cache_enabled = True
        snap = get_metrics("server").snapshot()
        assert "server.deviceKernelGbps" in snap["histograms"]
        assert snap["histograms"]["server.deviceKernelGbps"]["count"] >= 1

    def test_cache_hit_flights_marked_not_rated(self, xray_engine):
        dev = xray_engine.device
        dev.partials_cache_enabled = True
        xray_engine.execute(GROUPBY_SQL)  # warm / insert
        r = xray_engine.execute(GROUPBY_SQL)  # hit
        if r.get("partialsCacheHit"):
            rec = (r.get("roofline") or [{}])[0]
            assert rec.get("cacheHit") is True
            assert "gbps" not in rec  # no kernel ran: nothing to rate


@pytest.fixture(scope="module")
def regime_engine(tmp_path_factory):
    """One table that reaches every key-space regime: ``a`` x ``b`` is
    168 x 256 = 43,008 cells (past NARROW_MIN_CELLS) of which ``sel = 1``
    leaves 20 blocks live; ``h1`` x ``h2`` is 2,100 x 2,100 cells (past
    MAX_DENSE_GROUPS: the sorted regime)."""
    from pinot_tpu.common.table_config import IndexingConfig

    n = 4_500
    rng = np.random.default_rng(34)
    sel = (np.arange(n) % 9 == 0).astype(np.int32)
    h1, h2 = (rng.integers(0, 2_100, n).astype(np.int32) for _ in "12")
    h1[:2_100] = h2[:2_100] = np.arange(2_100, dtype=np.int32)
    cols = {
        "a": np.where(sel == 1, rng.integers(0, 10, n),
                      rng.integers(0, 168, n)).astype(np.int32),
        "b": rng.integers(0, 256, n).astype(np.int32),
        "sel": sel, "h1": h1, "h2": h2,
        "v": rng.integers(0, 1_000, n).astype(np.int32),
    }
    cols["a"][:168] = np.arange(168)  # the stated cardinalities
    cols["b"][:256] = np.arange(256)
    cols["sel"][:256] = 0
    schema = Schema.build(
        name="rg",
        dimensions=[(c, DataType.INT) for c in ("a", "b", "sel", "h1", "h2")],
        metrics=[("v", DataType.INT)])
    cfg = TableConfig(table_name="rg", indexing=IndexingConfig(
        no_dictionary_columns=["v"]))
    d = str(tmp_path_factory.mktemp("xray_regimes") / "rg_s0")
    build_segment(schema, cols, d, cfg, "rg_s0")
    eng = QueryEngine()
    eng.add_segment("rg", ImmutableSegment(d))
    # EXPLAIN ANALYZE runs the statement again: it must launch, not hit
    eng.device.partials_cache_enabled = False
    return eng


FLIGHT_STATEMENTS = {
    "agg": ("SELECT SUM(v), COUNT(*) FROM rg WHERE sel = 1", None),
    "dense": ("SELECT a, SUM(v) FROM rg GROUP BY a ORDER BY a LIMIT 200",
              "dense"),
    "narrowed": ("SELECT a, b, SUM(v) FROM rg WHERE sel = 1 "
                 "GROUP BY a, b ORDER BY a, b LIMIT 1000", "narrowed"),
    "sorted": ("SELECT h1, h2, SUM(v) FROM rg GROUP BY h1, h2 "
               "ORDER BY h1, h2 LIMIT 10", "sorted"),
}


@pytest.mark.parametrize("name", list(FLIGHT_STATEMENTS))
def test_flight_record_fields(regime_engine, name):
    """A launch of every regime leaves one flight record with the model's
    bytes, both waits and the achieved GB/s — and no share of any peak —
    and EXPLAIN ANALYZE prints it as one ``KERNEL(`` line."""
    sql, space = FLIGHT_STATEMENTS[name]
    r = regime_engine.execute(sql)
    assert not r.get("exceptions"), r
    assert r.get("numSegmentsOnHost", 0) == 0
    (rec,) = r["roofline"]
    assert {"kernel", "bytesMoved", "kernelMs", "queueMs", "runMs", "linkMs",
            "gbps"} <= set(rec)
    assert rec["bytesMoved"] > 0 and rec["gbps"] > 0 and rec["runMs"] > 0
    assert rec["gbps"] == pytest.approx(
        rec["bytesMoved"] / rec["runMs"] / 1e6, rel=0.01, abs=2e-3)
    assert not [k for k in rec if "peak" in k.lower()], rec
    assert rec.get("groupbyKeySpace") == space
    kernels = regime_engine.device.hbm_stats()["roofline"]["kernels"]
    assert kernels[rec["kernel"]]["gbps"] > 0
    assert not [k for k in kernels[rec["kernel"]] if "peak" in k.lower()]
    lines = _lines(regime_engine.execute("EXPLAIN ANALYZE " + sql))
    (kernel,) = [ln.strip() for ln in lines
                 if ln.strip().startswith("KERNEL(")]
    assert " GB/s, bytes=" in kernel and "kernelMs=" in kernel \
        and "queueMs=" in kernel and "runMs=" in kernel \
        and "linkMs=" in kernel and "peak" not in kernel.lower(), kernel
    if space is not None:
        assert f"groupbyKeySpace={space}" in kernel, kernel
    if rec.get("groupbyOperands"):
        assert f"groupbyOperands={rec['groupbyOperands']}" in kernel, kernel


# ---------------------------------------------------------------------------
# tentpole 2: EXPLAIN ANALYZE
# ---------------------------------------------------------------------------


def _lines(resp):
    return [r[0] for r in resp["resultTable"]["rows"]]


class TestExplainAnalyzeParsing:
    def test_parser_flags(self):
        from pinot_tpu.sql.parser import parse_sql

        stmt = parse_sql("EXPLAIN ANALYZE SELECT * FROM t")
        assert stmt.explain and stmt.analyze
        stmt = parse_sql("EXPLAIN PLAN FOR SELECT * FROM t")
        assert stmt.explain and not stmt.analyze
        stmt = parse_sql("SELECT * FROM t")
        assert not stmt.explain and not stmt.analyze

    def test_strip_preserves_set_prefix(self):
        from pinot_tpu.sql.parser import strip_explain_analyze

        sql = "SET timeoutMs = 5000; EXPLAIN ANALYZE SELECT 1 FROM t"
        assert strip_explain_analyze(sql) == \
            "SET timeoutMs = 5000; SELECT 1 FROM t"
        plain = "SELECT 1 FROM t"
        assert strip_explain_analyze(plain) == plain


class TestExplainAnalyzeEmbedded:
    def test_groupby_renders_actuals_and_kernel_line(self, xray_engine):
        ra = xray_engine.execute("EXPLAIN ANALYZE " + GROUPBY_SQL)
        assert not ra.get("exceptions")
        lines = _lines(ra)
        assert any("(actual: rows=" in ln for ln in lines), lines
        assert any(ln.strip().startswith("ROWS(") for ln in lines)
        assert any(ln.strip().startswith("SEGMENTS(") for ln in lines)
        assert any(ln.strip().startswith("PHASE(") for ln in lines)
        kernel = [ln for ln in lines if ln.strip().startswith("KERNEL(")]
        assert kernel and any("GB/s" in ln for ln in kernel), lines
        assert any(ln.strip().startswith("CACHE(") for ln in lines)

    def test_results_bit_identical(self, xray_engine):
        plain = xray_engine.execute(GROUPBY_SQL)
        ra = xray_engine.execute("EXPLAIN ANALYZE " + GROUPBY_SQL)
        assert ra["analyzedResponse"]["resultTable"] == plain["resultTable"]

    def test_join_renders_per_node_actuals(self, xray_engine):
        plain = xray_engine.execute(JOIN_SQL)
        assert not plain.get("exceptions")
        ra = xray_engine.execute("EXPLAIN ANALYZE " + JOIN_SQL)
        lines = _lines(ra)
        join_lines = [ln for ln in lines if ln.strip().startswith("JOIN_")]
        assert join_lines and "(actual: out=" in join_lines[0], lines
        scan_lines = [ln for ln in lines if ln.strip().startswith("SCAN(")]
        assert all("(actual: out=" in ln for ln in scan_lines), lines
        assert any(ln.strip().startswith("KERNEL(") and "GB/s" in ln
                   for ln in lines), lines
        # the embedded multistage path fills the waterfall via its
        # thread-local tracer (host_scan + stage2 spans)
        phase = [ln for ln in lines if ln.strip().startswith("PHASE(")]
        assert phase and "stage2=" in phase[0], lines
        # per-table pushdown filters must NOT carry the cluster-wide
        # docsScanned total (single-stage-only annotation)
        assert not any(ln.strip().startswith("FILTER_")
                       and "matched=" in ln for ln in lines), lines
        assert ra["analyzedResponse"]["resultTable"] == plain["resultTable"]

    def test_plain_explain_unchanged(self, xray_engine):
        rp = xray_engine.execute("EXPLAIN PLAN FOR " + GROUPBY_SQL)
        assert not any("ANALYZE" in ln for ln in _lines(rp))


@pytest.fixture()
def xray_cluster(tmp_path):
    """1 broker + 2 servers over a real registry; device executors on
    (the roofline records must cross the wire)."""
    from pinot_tpu.broker.broker import Broker

    registry = ClusterRegistry()
    controller = Controller(registry, str(tmp_path / "ds"))
    servers = [
        ServerInstance(f"xsrv_{i}", registry, str(tmp_path / f"x{i}"))
        for i in range(2)
    ]
    for s in servers:
        s.heartbeat_interval_s = 0.3
        s.start()
    schema = Schema.build(
        name="xt",
        dimensions=[("k", DataType.STRING)],
        metrics=[("v", DataType.LONG)],
    )
    cfg = TableConfig(table_name="xt", replication=1)
    controller.add_table(cfg, schema)
    rng = np.random.default_rng(2)
    for i in range(2):
        d = str(tmp_path / f"up{i}")
        build_segment(
            schema,
            {"k": np.array(["a", "b", "c"])[rng.integers(0, 3, 4000)],
             "v": rng.integers(0, 50, 4000).astype(np.int64)},
            d, cfg, f"xt_s{i}")
        controller.upload_segment("xt", d)
    # a replicated dim table so joins route through the broker too
    dim_schema = Schema.build(
        name="xdim",
        dimensions=[("k", DataType.STRING), ("grp", DataType.STRING)],
        metrics=[],
    )
    dcfg = TableConfig(table_name="xdim", replication=1, is_dim_table=True)
    controller.add_table(dcfg, dim_schema)
    dd = str(tmp_path / "updim")
    build_segment(dim_schema,
                  {"k": np.array(["a", "b", "c"]),
                   "grp": np.array(["x", "x", "y"])},
                  dd, dcfg, "xdim_s0")
    controller.upload_segment("xdim", dd)
    assert wait_until(lambda: len(registry.external_view("xt_OFFLINE")) == 2)
    assert wait_until(
        lambda: len(registry.external_view("xdim_OFFLINE")) == 1)
    broker = Broker(registry, timeout_s=30.0)
    yield registry, servers, broker
    broker.close()
    for s in servers:
        try:
            s.stop(drain_timeout_s=0.2)
        except Exception:  # noqa: BLE001
            pass


CLUSTER_SQL = "SELECT k, SUM(v) FROM xt GROUP BY k ORDER BY k"


class TestExplainAnalyzeCluster:
    def test_broker_explain_analyze(self, xray_cluster):
        _registry, _servers, broker = xray_cluster
        broker.execute(CLUSTER_SQL)  # warm the templates
        plain = broker.execute(CLUSTER_SQL)
        assert not plain.get("exceptions")
        ra = broker.execute("EXPLAIN ANALYZE " + CLUSTER_SQL)
        assert not ra.get("exceptions"), ra
        lines = _lines(ra)
        # per-instance kernel lines with their GB/s
        kernel = [ln for ln in lines if ln.strip().startswith("KERNEL(")]
        assert kernel and any("GB/s" in ln for ln in kernel), lines
        assert any("@xsrv_" in ln for ln in kernel), kernel
        # the phase waterfall came from the merged per-server traceInfo
        assert any(ln.strip().startswith("PHASE(") for ln in lines), lines
        assert ra["analyzedResponse"]["resultTable"] == \
            plain["resultTable"]

    def test_broker_multistage_explain_analyze(self, xray_cluster):
        """Regression: the multistage traceInfo nests per-leaf dicts —
        annotate_analyze's waterfall must recurse them, not crash into
        a generic 450 (phase_breakdown used to assume span lists)."""
        _registry, _servers, broker = xray_cluster
        jsql = ("SELECT xdim.grp, SUM(xt.v) FROM xt "
                "JOIN xdim ON xt.k = xdim.k "
                "GROUP BY xdim.grp ORDER BY xdim.grp")
        plain = broker.execute(jsql)
        assert not plain.get("exceptions"), plain
        ra = broker.execute("EXPLAIN ANALYZE " + jsql)
        assert not ra.get("exceptions"), ra
        lines = _lines(ra)
        # STAGE_2 actual-in is the JOINED row count, not the leaf docs
        stage2 = next(ln for ln in lines
                      if ln.strip().startswith("STAGE_2_"))
        n_joined = ra["analyzedResponse"]["numJoinedRows"]
        assert f"in={n_joined} rows" in stage2, stage2
        assert any(ln.strip().startswith("PHASE(") for ln in lines), lines
        assert any("GB/s" in ln for ln in lines), lines
        assert ra["analyzedResponse"]["resultTable"] == \
            plain["resultTable"]

    def test_server_partials_ship_roofline_records(self, xray_cluster):
        _registry, _servers, broker = xray_cluster
        r = broker.execute(
            "SET usePartialsCache = false; " + CLUSTER_SQL)
        assert not r.get("exceptions")
        recs = r.get("roofline") or []
        assert recs, "scattered query shipped no roofline records"
        assert all("instance" in rec for rec in recs)
        assert r.get("deviceBytesMoved", 0) > 0


# ---------------------------------------------------------------------------
# tentpole 3: segment-temperature telemetry
# ---------------------------------------------------------------------------


class TestHeatTracker:
    def test_note_and_decay(self):
        from pinot_tpu.server.heat import SegmentHeatTracker

        h = SegmentHeatTracker(half_life_s=10.0)
        t0 = 1000.0
        h.note("t", "s0", bytes_scanned=100, now=t0)
        h.note("t", "s0", bytes_scanned=100, now=t0)
        snap = h.snapshot(now=t0)["t"]["s0"]
        assert snap["accesses"] == 2 and snap["bytes"] == 200
        assert snap["rate"] == pytest.approx(2.0)
        # one half-life later the decayed rate halves; totals persist
        snap2 = h.snapshot(now=t0 + 10.0)["t"]["s0"]
        assert snap2["rate"] == pytest.approx(1.0, rel=1e-3)
        assert snap2["accesses"] == 2

    def test_top_per_table_cap_keeps_hottest(self):
        from pinot_tpu.server.heat import SegmentHeatTracker

        h = SegmentHeatTracker(half_life_s=60.0)
        t0 = 1000.0
        for i in range(6):
            for _ in range(i + 1):  # s5 hottest
                h.note("t", f"s{i}", now=t0)
        snap = h.snapshot(top_per_table=2, now=t0)["t"]
        assert set(snap) == {"s5", "s4"}

    def test_entry_bound_evicts_lru(self):
        from pinot_tpu.server.heat import SegmentHeatTracker

        h = SegmentHeatTracker(max_entries=16)
        for i in range(40):
            h.note("t", f"s{i}", now=1000.0 + i)
        assert h.size() == 16

    def test_aggregate_heat_merges_instances(self):
        from pinot_tpu.cluster.registry import InstanceInfo, Role

        registry = ClusterRegistry()
        for i in range(2):
            info = InstanceInfo(f"hsrv_{i}", Role.SERVER)
            info.heat = {"ht_OFFLINE": {
                "seg_a": {"rate": 1.5, "bytesRate": 10.0, "accesses": 3,
                          "bytes": 30, "lastAccessTs": 100.0 + i}}}
            registry.register_instance(info)
        agg = aggregate_heat(registry, "ht")
        assert agg["instancesReporting"] == 2
        seg = agg["segments"]["seg_a"]
        assert seg["rate"] == pytest.approx(3.0)
        assert seg["accesses"] == 6
        assert seg["instances"] == 2
        assert seg["lastAccessTs"] == 101.0

    def test_cluster_heartbeat_and_endpoint(self, xray_cluster, tmp_path):
        from pinot_tpu.controller.http_api import ControllerHttpServer

        registry, servers, broker = xray_cluster
        for _ in range(3):
            assert not broker.execute(CLUSTER_SQL).get("exceptions")
        # the heartbeat piggyback lands within the (shortened) cadence
        assert wait_until(
            lambda: aggregate_heat(registry, "xt").get("segments"),
            timeout=10.0), "no heat reported via heartbeats"
        agg = aggregate_heat(registry, "xt")
        assert agg["instancesReporting"] >= 1
        seg = next(iter(agg["segments"].values()))
        assert seg["accesses"] >= 1 and seg["bytes"] > 0
        # the controller REST face (GET /tables/{t}/heat)
        http = ControllerHttpServer(registry)
        http.start()
        try:
            with urllib.request.urlopen(
                    http.url + "/tables/xt/heat", timeout=10) as resp:
                doc = json.loads(resp.read().decode())
            assert doc["segments"], doc
            # the clusterstat CLI renders the same payload
            from pinot_tpu.tools import clusterstat

            out = clusterstat.render(clusterstat.gather(
                http.url, table="xt"))
            assert "xt" in out and "rate=" in out
            assert clusterstat.main([http.url, "--table", "xt",
                                     "--json"]) == 0
            # a table literally named "heat" keeps its metadata route:
            # GET /tables/heat must NOT be shadowed into an aggregation
            # over the empty table name
            req = urllib.request.Request(http.url + "/tables/heat")
            try:
                with urllib.request.urlopen(req, timeout=10) as resp:
                    doc2 = json.loads(resp.read().decode())
            except urllib.error.HTTPError as e:
                assert e.code == 404  # no table named "heat" registered
            else:
                assert "instancesReporting" not in doc2
        finally:
            http.stop()


# ---------------------------------------------------------------------------
# satellites: prometheus sanitization, summarizer
# ---------------------------------------------------------------------------


class TestPrometheusSanitize:
    def test_nasty_keys_round_trip_under_prometheus_client(self):
        from prometheus_client.parser import text_string_to_metric_families

        from pinot_tpu.common.metrics import MetricsRegistry

        reg = MetricsRegistry("bro ker")
        reg.gauge("latency", 1.5, tag="inst (retry)")
        reg.count("queries", 2, tag="inst (hedge)")
        reg.time_ms("serverLatencyMs", 12.0, tag="t.x-y (retry)")
        text = reg.prometheus_text()
        fams = list(text_string_to_metric_families(text))
        names = {f.name for f in fams}
        assert any("inst__retry_" in n for n in names), names
        # every emitted name is legal
        import re

        legal = re.compile(r"^[a-zA-Z_:][a-zA-Z0-9_:]*$")
        for f in fams:
            for s in f.samples:
                assert legal.match(s.name), s.name

    def test_sanitize_function(self):
        from pinot_tpu.common.metrics import sanitize

        assert sanitize("a.b-c d(e)") == "pinot_tpu_a_b_c_d_e_"

    def test_reset_metrics_clears_roofline_histograms(self):
        from pinot_tpu.common.metrics import get_metrics, reset_metrics

        m = get_metrics("xraytest")
        m.observe("deviceKernelGbps", 3.0)
        m.gauge("heatTrackedSegments", 10.0, tag="i0")
        assert m.snapshot()["histograms"]
        reset_metrics("xraytest")
        snap = m.snapshot()
        assert not snap["histograms"] and not snap["gauges"]


class TestQuerylogSummarizer:
    def _entry(self, tpl, ms, partials=False, result=False):
        return {"template": tpl, "timeUsedMs": ms,
                "counters": {"partialsCacheHit": partials,
                             "resultCacheHit": result}}

    def test_per_template_result_cache_rate(self):
        from pinot_tpu.tools.querylog import summarize

        entries = [self._entry("t1", 10.0, result=True),
                   self._entry("t1", 12.0, result=False),
                   self._entry("t1", 11.0, partials=True)]
        s = summarize(entries, per_template=True)
        row = s["templates"]["t1"]
        assert row["resultCacheHitRate"] == pytest.approx(1 / 3, abs=1e-3)
        assert row["cacheHitRate"] == pytest.approx(1 / 3, abs=1e-3)

    def test_phase_breakdown_recurses_multistage_nesting(self):
        """Multistage entries nest leaf traceInfo dicts under
        ``leaf:<alias>`` keys — the waterfall must recurse, not crash."""
        from pinot_tpu.tools.querylog import phase_breakdown

        entry = {"traceInfo": {"leaf:f": {
            "srv_0": [{"phase": "server.compile", "startMs": 0,
                       "durationMs": 2.0}],
            "broker": [{"phase": "broker.reduce", "startMs": 0,
                        "durationMs": 1.5}],
        }}}
        phases = phase_breakdown(entry)
        assert phases.get("compile") == pytest.approx(2.0)
        assert phases.get("reduce") == pytest.approx(1.5)

    def test_waterfall_includes_broker_scatter(self):
        from pinot_tpu.tools.querylog import phase_breakdown

        entry = {"traceInfo": {"broker": [
            {"phase": "broker.scatter_gather", "startMs": 0,
             "durationMs": 7.5},
            {"phase": "broker.reduce", "startMs": 8, "durationMs": 1.0},
        ]}}
        phases = phase_breakdown(entry)
        assert phases.get("scatter") == pytest.approx(7.5)
        assert phases.get("reduce") == pytest.approx(1.0)

    def test_waterfall_splits_the_device_wait_into_queue_and_run(self):
        """A launch's wait shows as its queue behind other launches and
        its run on the device; a log from before the split keeps
        ``kernel``."""
        from pinot_tpu.tools.querylog import phase_breakdown

        entry = {"traceInfo": {"server_1": [
            {"phase": "server.queue", "startMs": 0, "durationMs": 0.5},
            {"phase": "executor.device_wait", "startMs": 1,
             "durationMs": 9.0,
             "attrs": {"launchId": 4, "deviceQueueMs": 6.0,
                       "deviceRunMs": 3.25, "launchesAhead": 2}},
            {"phase": "executor.link", "startMs": 10, "durationMs": 0.4},
        ]}}
        phases = phase_breakdown(entry)
        assert phases.get("queue") == pytest.approx(6.5)
        assert phases.get("run") == pytest.approx(3.25)
        assert "kernel" not in phases
        assert phases.get("link") == pytest.approx(0.4)
        old = {"traceInfo": {"server_1": [
            {"phase": "executor.device_wait", "startMs": 1,
             "durationMs": 9.0, "attrs": {"launchId": 4}}]}}
        assert phase_breakdown(old) == {"kernel": 9.0}
