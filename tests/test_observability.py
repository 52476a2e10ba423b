"""Metrics registry, request tracing, FS SPI, plugin loader.

Reference analogs: AbstractMetrics + yammer reporters (histogram
percentiles included), Tracing.java / trace query option surfaced in
BrokerResponse (cross-process since ISSUE 7: trace id + per-server span
ladders merged into per-instance traceInfo, retries/hedges tagged),
PinotFS + LocalPinotFS, PluginManager + ServiceLoader-style
registration, and the broker QueryLogger (structured JSONL query log).
"""

import json
import os
import sys
import threading
import time
import urllib.request

import numpy as np
import pytest

from pinot_tpu.broker.broker import Broker
from pinot_tpu.broker.http_api import BrokerHttpServer
from pinot_tpu.cluster.registry import ClusterRegistry
from pinot_tpu.common.datatypes import DataType
from pinot_tpu.common.metrics import MetricsRegistry, get_metrics
from pinot_tpu.common.plugins import plugin_registry
from pinot_tpu.common.schema import Schema
from pinot_tpu.common.table_config import TableConfig
from pinot_tpu.controller.controller import Controller
from pinot_tpu.server.server import ServerInstance
from pinot_tpu.storage.creator import build_segment
from pinot_tpu.storage.fs import LocalFS, create_fs


def wait_until(cond, timeout=15.0, interval=0.05):
    t0 = time.time()
    while time.time() - t0 < timeout:
        if cond():
            return True
        time.sleep(interval)
    return False


class TestMetricsRegistry:
    def test_counters_gauges_timers(self):
        reg = MetricsRegistry("test")
        reg.count("q")
        reg.count("q", 4)
        reg.gauge("depth", 7)
        reg.gauge("dynamic", lambda: 3)
        with reg.timed("phase"):
            pass
        reg.time_ms("phase", 5.0)
        snap = reg.snapshot()
        assert snap["counters"]["test.q"] == 5
        assert snap["gauges"]["test.depth"] == 7
        assert snap["gauges"]["test.dynamic"] == 3
        t = snap["timers"]["test.phase"]
        assert t["count"] == 2 and t["maxMs"] >= 5.0

    def test_tags_and_prometheus(self):
        reg = MetricsRegistry("b")
        reg.count("queries", tag="t1")
        reg.gauge("g", 1.5)
        reg.time_ms("lat", 10)
        text = reg.prometheus_text()
        assert "pinot_tpu_b_queries_t1_total 1" in text
        assert "pinot_tpu_b_g 1.5" in text
        assert "pinot_tpu_b_lat_ms_count 1" in text

    def test_reporter(self):
        reg = MetricsRegistry("r")
        seen = []
        reg.add_reporter(seen.append)
        reg.count("x")
        reg.report()
        assert seen and seen[0]["counters"]["r.x"] == 1

    def test_gauge_sampling_never_throws(self):
        reg = MetricsRegistry("g")
        reg.gauge("bad", lambda: 1 / 0)
        assert reg.snapshot()["gauges"]["g.bad"] is None


class TestFsSpi:
    def test_localfs_ops(self, tmp_path):
        fs = LocalFS()
        d = str(tmp_path / "a")
        fs.mkdir(d)
        assert fs.exists(d)
        with open(os.path.join(d, "f.txt"), "w") as f:
            f.write("hi")
        fs.copy(d, str(tmp_path / "b"))
        assert fs.list_files(str(tmp_path / "b")) == ["f.txt"]
        fs.copy(os.path.join(d, "f.txt"), str(tmp_path / "c" / "f.txt"))
        assert fs.exists(str(tmp_path / "c" / "f.txt"))
        fs.delete(d)
        assert not fs.exists(d)
        assert fs.exists("file://" + str(tmp_path / "b"))

    def test_create_fs_via_plugin_registry(self, tmp_path, monkeypatch):
        import sys

        assert isinstance(create_fs(str(tmp_path)), LocalFS)
        assert isinstance(create_fs("file:///x"), LocalFS)
        # s3 registers (pinot-s3 analog) but gates on boto3 — force-absent
        # so the assertion holds even on hosts that ship the SDK
        monkeypatch.setitem(sys.modules, "boto3", None)
        with pytest.raises(RuntimeError, match="boto3"):
            create_fs("s3://bucket/x")
        monkeypatch.setitem(sys.modules, "google", None)
        monkeypatch.setitem(sys.modules, "google.cloud", None)
        with pytest.raises(RuntimeError, match="google-cloud"):
            create_fs("gs://bucket/x")
        # hdfs is now a real plugin (WebHDFS, stdlib-only — no gating)
        from pinot_tpu.storage.hdfsfs import HdfsFS

        assert isinstance(create_fs("hdfs://nn:9870/x"), HdfsFS)
        with pytest.raises(KeyError, match="no 'fs' plugin"):
            create_fs("ipfs://nn/x")


class TestPluginRegistry:
    def test_builtins_registered(self):
        assert "memory" in plugin_registry.available("stream")
        assert "json" in plugin_registry.available("decoder")
        assert {"csv", "json", "parquet"} <= set(
            plugin_registry.available("record_reader"))
        assert "mergerolluptask" in plugin_registry.available("minion_task")
        assert plugin_registry.load("fs", "file") is LocalFS

    def test_unknown_plugin_raises_with_inventory(self):
        with pytest.raises(KeyError, match="registered"):
            plugin_registry.load("stream", "kafka")

    def test_env_plugin_module_loads(self, tmp_path, monkeypatch):
        mod_dir = tmp_path / "plugmod"
        mod_dir.mkdir()
        (mod_dir / "my_plugin.py").write_text(
            "from pinot_tpu.common.plugins import plugin_registry\n"
            "plugin_registry.register('decoder', 'upper', lambda b: b.upper())\n"
        )
        monkeypatch.syspath_prepend(str(mod_dir))
        monkeypatch.setenv("PINOT_TPU_PLUGINS", "my_plugin")
        # plugin modules register on the GLOBAL registry at import
        assert plugin_registry.load_env_plugins()
        assert plugin_registry.load("decoder", "upper")(b"x") == b"X"


class TestClusterObservability:
    @pytest.fixture()
    def cluster(self, tmp_path):
        registry = ClusterRegistry()
        controller = Controller(registry, str(tmp_path / "ds"))
        server = ServerInstance("server_0", registry, str(tmp_path / "s0"),
                                device_executor=None)
        server.start()
        broker = Broker(registry, timeout_s=10.0)
        http = BrokerHttpServer(broker)
        http.start()
        schema = Schema.build(
            name="sales",
            dimensions=[("k", DataType.STRING)],
            metrics=[("v", DataType.LONG)],
        )
        cfg = TableConfig(table_name="sales")
        controller.add_table(cfg, schema)
        d = str(tmp_path / "up")
        build_segment(
            schema,
            {"k": np.array(["a", "b"] * 50), "v": np.arange(100, dtype=np.int64)},
            d, cfg, "s0")
        controller.upload_segment("sales", d)
        assert wait_until(lambda: len(registry.external_view("sales_OFFLINE")) == 1)
        yield broker, http
        http.stop()
        broker.close()
        server.stop()

    def test_trace_option_returns_phase_spans(self, cluster):
        broker, _ = cluster
        r = broker.execute(
            "SET trace = true; SELECT k, SUM(v) FROM sales GROUP BY k")
        assert not r.get("exceptions"), r
        info = r["traceInfo"]
        assert "broker" in info and "server_0" in info
        broker_phases = {s["phase"] for s in info["broker"]}
        assert {"broker.scatter_gather", "broker.reduce"} <= broker_phases
        server_phases = {s["phase"] for s in info["server_0"]}
        assert "server.execute" in server_phases
        assert all(s["durationMs"] >= 0 for s in info["server_0"])
        # tracing off → no traceInfo
        r2 = broker.execute("SELECT COUNT(*) FROM sales")
        assert "traceInfo" not in r2

    def test_metrics_http_endpoints(self, cluster):
        broker, http = cluster
        broker.execute("SELECT COUNT(*) FROM sales")
        with urllib.request.urlopen(http.url + "/metrics", timeout=5) as resp:
            snap = json.loads(resp.read())
        assert snap["broker"]["counters"]["broker.queries"] >= 1
        assert snap["server"]["counters"]["server.queries"] >= 1
        assert snap["server"]["timers"]["server.query"]["count"] >= 1
        gauges = snap["server"]["gauges"]
        assert gauges["server.segmentsLoaded.server_0"] >= 1
        with urllib.request.urlopen(http.url + "/metrics/prometheus",
                                    timeout=5) as resp:
            text = resp.read().decode()
        assert "pinot_tpu_broker_queries_total" in text

    def test_parse_error_counts_query_before_error(self, tmp_path):
        """The server counts ``queries`` at RECEIVE time (pre-compile), so
        a stream of parse errors can never push queryErrors above queries
        on the dashboard (the old inner-count, incremented only after a
        successful compile + admission, made the invariant violable)."""
        from pinot_tpu.common.metrics import get_metrics
        from pinot_tpu.transport.grpc_transport import make_instance_request

        registry = ClusterRegistry()
        server = ServerInstance("server_m", registry, str(tmp_path / "sm"),
                                device_executor=None)
        m = get_metrics("server")
        snap0 = m.snapshot()["counters"]
        q0 = snap0.get("server.queries", 0)
        e0 = snap0.get("server.queryErrors", 0)
        bad = make_instance_request("SELEKT garbage FRM nowhere", [], 1, "b0")
        resp = server._handle_submit(bad)
        assert b"query_error" in resp
        snap = m.snapshot()["counters"]
        assert snap.get("server.queryErrors", 0) == e0 + 1
        assert snap.get("server.queries", 0) == q0 + 1


# ---------------------------------------------------------------------------
# ISSUE 7: histogram metrics
# ---------------------------------------------------------------------------


class TestHistogram:
    def test_quantiles_vs_numpy_across_bucket_boundaries(self):
        """Log-bucket interpolation must track exact percentiles within
        one bucket width (~19% worst case; far tighter in practice)
        across distributions that straddle many bucket boundaries."""
        from pinot_tpu.common.metrics import Histogram

        rng = np.random.default_rng(7)
        for dist in (
            rng.uniform(0.5, 200.0, 4000),          # flat across buckets
            rng.lognormal(2.0, 1.5, 4000),          # heavy tail
            np.arange(1, 301, dtype=np.float64),    # exact ladder
            np.repeat([0.9, 1.1, 99.0, 101.0], 50), # boundary-straddling
        ):
            h = Histogram()
            for v in dist:
                h.update(float(v))
            s = np.sort(dist)
            for q in (0.5, 0.9, 0.99):
                # nearest-rank oracle (the histogram's own definition —
                # numpy's default interpolates ACROSS distribution gaps,
                # which no bucketed histogram can reproduce)
                exact = float(s[max(0, int(np.ceil(q * len(s))) - 1)])
                est = h.quantile(q)
                assert abs(est - exact) <= max(0.20 * exact, 1e-3), \
                    (q, est, exact)

    def test_quantiles_clamped_to_observed_range(self):
        from pinot_tpu.common.metrics import Histogram

        h = Histogram()
        for v in (5.0, 5.0, 5.0):
            h.update(v)
        assert h.quantile(0.5) == 5.0
        assert h.quantile(0.999) == 5.0
        snap = h.snapshot()
        assert snap["count"] == 3 and snap["p99Ms"] == 5.0

    def test_registry_one_update_feeds_timer_and_histogram(self):
        from pinot_tpu.common.metrics import MetricsRegistry

        reg = MetricsRegistry("h")
        for v in range(1, 101):
            reg.time_ms("lat", float(v))
        snap = reg.snapshot()
        assert snap["timers"]["h.lat"]["count"] == 100
        hist = snap["histograms"]["h.lat"]
        assert hist["count"] == 100
        assert 40 <= hist["p50Ms"] <= 60
        assert 85 <= hist["p90Ms"] <= 100
        # quantile() is the shared-read surface (hedge delay et al.)
        assert reg.quantile("lat", 0.9) == pytest.approx(
            hist["p90Ms"], abs=1e-3)  # snapshot rounds to 3 decimals
        assert reg.quantile("nothing", 0.9) is None
        # observe() is the histogram-forward alias of time_ms
        reg.observe("lat2", 5.0)
        assert reg.snapshot()["histograms"]["h.lat2"]["count"] == 1

    def test_prometheus_histogram_exposition_parses(self):
        """The exposition must hold up under prometheus_client's
        text-format parser: histogram family with monotone cumulative
        buckets, +Inf, _sum/_count consistency."""
        from pinot_tpu.common.metrics import MetricsRegistry

        prom_parser = pytest.importorskip("prometheus_client.parser")
        reg = MetricsRegistry("p")
        reg.count("queries")
        reg.gauge("depth", 3)
        for v in (0.5, 5.0, 50.0, 500.0, 5000.0):
            reg.time_ms("query", v)
        text = reg.prometheus_text()
        fams = {f.name: f for f in
                prom_parser.text_string_to_metric_families(text)}
        assert fams["pinot_tpu_p_queries"].type == "counter"
        hist = fams["pinot_tpu_p_query_ms"]
        assert hist.type == "histogram"
        buckets = [(s.labels["le"], s.value) for s in hist.samples
                   if s.name.endswith("_bucket")]
        assert buckets[-1][0] == "+Inf" and buckets[-1][1] == 5
        values = [v for _le, v in buckets]
        assert values == sorted(values), "cumulative buckets must be monotone"
        count = next(s.value for s in hist.samples
                     if s.name.endswith("_count"))
        total = next(s.value for s in hist.samples
                     if s.name.endswith("_sum"))
        assert count == 5 and total == pytest.approx(5555.5)


class TestMetricsLifecycle:
    def test_reset_clears_registry(self):
        from pinot_tpu.common.metrics import MetricsRegistry

        reg = MetricsRegistry("x")
        reg.count("a")
        reg.gauge("g", lambda: 1)
        reg.time_ms("t", 1.0)
        reg.reset()
        snap = reg.snapshot()
        assert not snap["counters"] and not snap["gauges"]
        assert not snap["timers"] and not snap["histograms"]

    def test_reset_metrics_by_component(self):
        from pinot_tpu.common.metrics import get_metrics, reset_metrics

        get_metrics("resettest").count("a")
        reset_metrics("resettest")
        assert not get_metrics("resettest").snapshot()["counters"]
        get_metrics("resettest").count("a")
        reset_metrics()  # all registries
        assert not get_metrics("resettest").snapshot()["counters"]

    def test_server_stop_unregisters_every_gauge(self, tmp_path):
        """Leak guard (ISSUE 7 satellite): get_metrics registries are
        process-global and survive ServerInstance.stop() — every
        callable gauge the instance registered (segments, scheduler,
        device HBM/quarantine family) must unregister on stop, or the
        closure pins the dead instance and a restarted same-id server
        double-reports."""
        from pinot_tpu.cluster.registry import ClusterRegistry
        from pinot_tpu.common.metrics import get_metrics
        from pinot_tpu.server.server import ServerInstance

        m = get_metrics("server")
        for round_i in range(2):  # restart with the SAME instance id
            server = ServerInstance(
                "leakguard_0", ClusterRegistry(),
                str(tmp_path / f"lg{round_i}"))
            server.start()
            keys = m.gauge_keys("leakguard_0")
            assert "server.segmentsLoaded.leakguard_0" in keys
            # the device gauge family (PR-5/PR-6) registers too
            assert any("deviceResidentBytes" in k for k in keys)
            assert any("deviceQuarantinedPipelines" in k for k in keys)
            # ISSUE 11: the temperature gauge joins the tracked family —
            # a restart must not leak it either
            assert any("heatTrackedSegments" in k for k in keys)
            server.stop(drain_timeout_s=0.2)
            assert m.gauge_keys("leakguard_0") == [], \
                "stop() leaked callable gauges into the global registry"


# ---------------------------------------------------------------------------
# ISSUE 7: explicit tracer across the async launch/fetch split + cohorts
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def traced_engine(tmp_path_factory):
    """Small two-segment device-eligible table for tracer plumbing."""
    from pinot_tpu.common.datatypes import DataType
    from pinot_tpu.common.schema import Schema
    from pinot_tpu.common.table_config import TableConfig
    from pinot_tpu.engine.engine import QueryEngine
    from pinot_tpu.storage.creator import build_segment
    from pinot_tpu.storage.segment import ImmutableSegment

    base = tmp_path_factory.mktemp("traced")
    schema = Schema.build(
        name="t",
        dimensions=[("tag", DataType.STRING)],
        metrics=[("v", DataType.INT)],
    )
    cfg = TableConfig(table_name="t")
    rng = np.random.default_rng(3)
    segs = []
    for i in range(2):
        cols = {
            "tag": np.array(["a", "b", "c"])[rng.integers(0, 3, 20_000)],
            "v": rng.integers(0, 100, 20_000).astype(np.int32),
        }
        d = str(base / f"s{i}")
        build_segment(schema, cols, d, cfg, f"s{i}")
        segs.append(ImmutableSegment(d))
    eng = QueryEngine()
    for s in segs:
        eng.add_segment("t", s)
    return eng, segs


class TestTracerAcrossAsyncSplit:
    def _compile(self, sql):
        from pinot_tpu.query.optimizer import optimize_query
        from pinot_tpu.sql.compiler import compile_query

        return optimize_query(compile_query(sql))

    def test_async_query_reports_launch_and_fetch_spans(self, traced_engine):
        """Regression for the PR-2 thread-split span loss: the tracer is
        carried EXPLICITLY through execute_segments_async and the device
        handle, so a traced async query reports both launch-phase spans
        (gather/dispatch) and fetch-phase spans (device_wait, merge) —
        even when fetch() runs on a different thread than launch."""
        from pinot_tpu.common.trace import Tracer

        eng, _segs = traced_engine
        q = self._compile("SELECT tag, SUM(v) FROM t GROUP BY tag")
        tracer = Tracer("test-trace-1")
        tdm = eng.tables["t"]
        segs = tdm.acquire()
        try:
            fetch = eng.execute_segments_async(q, segs, tracer=tracer)
            result_box = []
            th = threading.Thread(  # the deferred fetch on ANOTHER thread
                target=lambda: result_box.append(fetch()))
            th.start()
            th.join(60)
        finally:
            tdm.release(segs)
        assert result_box, "fetch thread died"
        phases = {s["phase"] for s in tracer.to_json()}
        # launch: template build, column gather, XLA dispatch
        assert {"engine.template", "executor.gather",
                "executor.dispatch"} <= phases, phases
        # fetch: the device wait and the link split, unpack, partial merge
        assert {"executor.device_wait", "executor.link",
                "executor.unpack", "engine.merge"} <= phases, phases

    def test_cohort_members_each_get_fetch_spans(self, traced_engine):
        """Coalesced cohort launches: every MEMBER's tracer records its
        own wait for the device (the link span lands on the trace of the
        member that ran the one fetch) — previously cohort spans landed
        on whichever thread's thread-local happened to be installed, or
        nowhere."""
        from pinot_tpu.common.trace import Tracer

        eng, _segs = traced_engine
        dev = eng.device
        dev.partials_cache_enabled = False  # pin cohorts, not cache hits
        co = dev.coalescer
        co.force = True
        co.window_s = 0.25
        n = 3
        tracers = [Tracer(f"cohort-{i}") for i in range(n)]
        results = [None] * n
        errors = []
        barrier = threading.Barrier(n)
        c0 = co.queries_coalesced
        tdm = eng.tables["t"]

        def worker(i):
            q = self._compile(
                f"SELECT tag, SUM(v) FROM t WHERE v < {90 + i} GROUP BY tag")
            segs = tdm.acquire()
            try:
                barrier.wait(10)
                fetch = eng.execute_segments_async(q, segs,
                                                   tracer=tracers[i])
                results[i] = fetch()
            except Exception as e:  # noqa: BLE001
                errors.append(e)
            finally:
                tdm.release(segs)

        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(n)]
        try:
            for t in threads:
                t.start()
            for t in threads:
                t.join(60)
        finally:
            co.force = False
            co.window_s = 0.003
        assert not errors, errors
        assert all(r is not None for r in results)
        assert co.queries_coalesced > c0, "queries never coalesced"
        for i, tr in enumerate(tracers):
            phases = {s["phase"] for s in tr.to_json()}
            assert "executor.gather" in phases, (i, phases)
            assert "executor.device_wait" in phases, (i, phases)
        links = [tr for tr in tracers
                 if any(s["phase"] == "executor.link" for s in tr.to_json())]
        assert len(links) == 1, "one member runs the cohort's one fetch"


# ---------------------------------------------------------------------------
# ISSUE 7: traceInfo merge under retry/hedge + structured query log
# ---------------------------------------------------------------------------


@pytest.fixture()
def replicated_cluster(tmp_path):
    """2 servers x replication 2 (every segment on both) — the retry and
    hedge paths always have a covering replica."""
    from pinot_tpu.common import faults

    registry = ClusterRegistry()
    controller = Controller(registry, str(tmp_path / "ds"))
    servers = [
        ServerInstance(f"rsrv_{i}", registry, str(tmp_path / f"r{i}"),
                       device_executor=None)
        for i in range(2)
    ]
    for s in servers:
        s.start()
    schema = Schema.build(
        name="rt",
        dimensions=[("k", DataType.STRING)],
        metrics=[("v", DataType.LONG)],
    )
    cfg = TableConfig(table_name="rt", replication=2)
    controller.add_table(cfg, schema)
    rng = np.random.default_rng(1)
    for i in range(2):
        d = str(tmp_path / f"up{i}")
        build_segment(
            schema,
            {"k": np.array(["a", "b", "c"])[rng.integers(0, 3, 3000)],
             "v": rng.integers(0, 50, 3000).astype(np.int64)},
            d, cfg, f"rt_s{i}")
        controller.upload_segment("rt", d)
    ev_ok = wait_until(lambda: (
        len(registry.external_view("rt_OFFLINE")) == 2
        and all(len(v) == 2
                for v in registry.external_view("rt_OFFLINE").values())))
    assert ev_ok, "segments never fully replicated"
    yield registry, servers
    faults.clear()
    for s in servers:
        try:
            s.stop(drain_timeout_s=0.2)
        except Exception:  # noqa: BLE001
            pass


TRACED_SQL = "SET trace = true; SELECT k, SUM(v) FROM rt GROUP BY k ORDER BY k"


class TestTraceMergeRetryHedge:
    def _server_keys(self, info):
        return {k for k in info if k != "broker"}

    def test_retry_attempt_traces_tagged_and_merged(self, replicated_cluster):
        """A replica that hard-fails forces a retry; the retry attempt's
        server spans must arrive in traceInfo TAGGED as a retry, with no
        duplicate and no dropped span lists, and the recovered result
        must be complete (no partialResult)."""
        from pinot_tpu.common import faults

        registry, _servers = replicated_cluster
        reference = None
        broker = Broker(registry, timeout_s=10.0)
        try:
            reference = broker.execute(
                "SELECT k, SUM(v) FROM rt GROUP BY k ORDER BY k")
            assert not reference.get("exceptions")
        finally:
            broker.close()

        faults.install(faults.Fault(
            point="transport.submit", target="rsrv_0", mode="error"))
        broker = Broker(registry, timeout_s=10.0)
        try:
            saw_retry = False
            for _ in range(3):  # round-robin: one of these routes rsrv_0
                r = broker.execute(TRACED_SQL)
                assert not r.get("exceptions"), r
                assert not r.get("partialResult")
                assert r["resultTable"]["rows"] == \
                    reference["resultTable"]["rows"]
                info = r["traceInfo"]
                keys = self._server_keys(info)
                assert keys, "no server spans at all"
                for k in keys:
                    spans = info[k]
                    assert spans, f"empty span list under {k!r}"
                    # merged-by-extend, not overwritten: exactly one
                    # server.total per answering attempt part
                    totals = [s for s in spans
                              if s["phase"] == "server.total"]
                    assert len(totals) >= 1
                    assert all(s["durationMs"] >= 0 for s in spans)
                if any("(retry)" in k for k in keys):
                    saw_retry = True
                    assert r.get("numRetries", 0) >= 1
                    # the failed primary contributed NO span list of its
                    # own (its RPC died before the server ran)
                    assert not any(k.startswith("rsrv_0")
                                   and "(retry)" not in k for k in keys)
            assert saw_retry, "no query exercised the retry path"
        finally:
            faults.clear()
            broker.close()

    def test_hedge_attempt_traces_tagged(self, replicated_cluster):
        """A slow replica triggers a hedge; the winning hedge attempt's
        spans arrive tagged '(hedge)' and the response counts it."""
        from pinot_tpu.common import faults

        registry, _servers = replicated_cluster
        faults.install(faults.Fault(
            point="transport.submit", target="rsrv_0", mode="delay",
            delay_ms=400))
        broker = Broker(registry, timeout_s=10.0)
        broker.hedging_enabled = True
        broker.hedge_delay_s = 0.02
        try:
            saw_hedge = False
            for _ in range(3):
                r = broker.execute(TRACED_SQL)
                assert not r.get("exceptions"), r
                keys = self._server_keys(r["traceInfo"])
                if any("(hedge)" in k for k in keys):
                    saw_hedge = True
                    assert r.get("numHedges", 0) >= 1
            assert saw_hedge, "no query exercised the hedge path"
        finally:
            faults.clear()
            broker.close()

    def test_hedge_delay_driven_by_shared_histogram(self):
        """The acceptance wire: LatencyTracker.p90_s reads the SHARED
        metrics histogram — a recorded latency profile shows up both in
        the hedge delay and in the registry's histogram snapshot."""
        from pinot_tpu.broker.broker import LatencyTracker

        reg = MetricsRegistry("hb")
        lt = LatencyTracker(default_s=0.07, registry=reg)
        assert lt.p90_s("sX") == 0.07  # no samples: default
        for v in range(100):
            lt.record("sX", v / 1000.0)  # 0..99 ms
        p90 = lt.p90_s("sX")
        assert 0.075 <= p90 <= 0.11, p90
        hist = reg.snapshot()["histograms"]["hb.serverLatencyMs.sX"]
        assert hist["count"] == 100
        assert abs(hist["p90Ms"] / 1e3 - p90) < 1e-6


class TestQueryLog:
    def _resp(self, used_ms, exceptions=(), partial=False):
        return {"timeUsedMs": used_ms, "exceptions": list(exceptions),
                "partialResult": partial, "requestId": 1}

    def test_policy_always_on_for_abnormal(self, tmp_path):
        from pinot_tpu.broker.querylog import QueryLogger

        ql = QueryLogger(slow_threshold_ms=500.0, sample_rate=0.0)
        # fast + healthy: dropped
        assert ql.record("SELECT 1", self._resp(3.0), 3.0) is None
        # slow: kept
        assert ql.record("SELECT 2", self._resp(900.0), 900.0) is not None
        # fast but errored: kept
        assert ql.record(
            "SELECT 3",
            self._resp(3.0, [{"errorCode": 250, "message": "t"}]),
            3.0) is not None
        # fast but partial: kept
        assert ql.record(
            "SELECT 4", self._resp(3.0, partial=True), 3.0) is not None
        entries = ql.recent()
        assert len(entries) == 3
        assert entries[0]["sql"] == "SELECT 4"  # newest first

    def test_jsonl_write_and_rotation(self, tmp_path):
        from pinot_tpu.broker.querylog import QueryLogger

        path = str(tmp_path / "q.jsonl")
        ql = QueryLogger(path=path, slow_threshold_ms=0.0, max_bytes=2000)
        for i in range(40):
            ql.record(f"SELECT {i}", self._resp(10.0 + i), 10.0 + i)
        assert os.path.exists(path)
        assert os.path.exists(path + ".1"), "rotation never triggered"
        assert os.path.getsize(path) <= 2000 + 1024
        with open(path) as f:
            lines = [json.loads(line) for line in f if line.strip()]
        assert lines and all("timeUsedMs" in e for e in lines)

    def test_broker_logs_slow_query_with_trace_and_template(
            self, replicated_cluster, tmp_path):
        from pinot_tpu.broker.querylog import QueryLogger

        registry, _servers = replicated_cluster
        broker = Broker(registry, timeout_s=10.0)
        path = str(tmp_path / "bq.jsonl")
        # threshold 0: every query is "slow" — deterministic capture
        broker.querylog = QueryLogger(path=path, slow_threshold_ms=0.0)
        try:
            r = broker.execute(TRACED_SQL)
            assert not r.get("exceptions"), r
            entries = broker.querylog.recent()
            assert entries
            e = entries[0]
            assert e["table"] == "rt"
            assert e["traceId"] == r["traceId"]
            assert e["template"].startswith("rt|group_by|sum|k")
            assert "traceInfo" in e
            assert e["counters"]["numServersQueried"] >= 1
            # error queries log too, with their exception in place
            broker.execute("SELECT nope(v) FROM rt")
            bad = broker.querylog.recent()[0]
            assert bad["exceptions"]
        finally:
            broker.close()

    def test_debug_queries_endpoint(self, replicated_cluster, tmp_path):
        from pinot_tpu.broker.querylog import QueryLogger

        registry, _servers = replicated_cluster
        broker = Broker(registry, timeout_s=10.0)
        broker.querylog = QueryLogger(slow_threshold_ms=0.0, ring_size=8)
        http = BrokerHttpServer(broker)
        http.start()
        try:
            for _ in range(3):
                broker.execute("SELECT COUNT(*) FROM rt")
            with urllib.request.urlopen(
                    http.url + "/debug/queries?limit=2", timeout=5) as resp:
                doc = json.loads(resp.read())
            assert len(doc["queries"]) == 2
            assert all("timeUsedMs" in e for e in doc["queries"])
        finally:
            http.stop()
            broker.close()

    def test_summarizer_tool(self, tmp_path, capsys):
        from pinot_tpu.broker.querylog import QueryLogger
        from pinot_tpu.tools import querylog as qtool

        path = str(tmp_path / "sum.jsonl")
        ql = QueryLogger(path=path, slow_threshold_ms=0.0)
        for i in range(10):
            resp = self._resp(10.0 * (i + 1))
            resp["traceInfo"] = {"s0": [
                {"phase": "server.queue", "startMs": 0, "durationMs": 0.1},
                {"phase": "server.fetch.kernel", "startMs": 1,
                 "durationMs": 5.0},
                {"phase": "server.fetch.link", "startMs": 6,
                 "durationMs": 2.0},
            ]}
            ql.record(f"SELECT {i} FROM t", resp, 10.0 * (i + 1), table="t")
        rc = qtool.main([path, "--top", "2", "--json"])
        assert rc == 0
        out = json.loads(capsys.readouterr().out)
        assert out["queries"] == 10
        assert out["latencyMs"]["p50"] > 0
        assert out["phaseP50Ms"]["kernel"] == 5.0
        assert out["phaseP50Ms"]["link"] == 2.0
        assert len(out["slowest"]) == 2


# ---------------------------------------------------------------------------
# ISSUE 29: one tree of spans per request, from the HTTP door to the launch
# ---------------------------------------------------------------------------


def _post(url, sql):
    req = urllib.request.Request(
        url + "/query/sql", data=json.dumps({"sql": sql}).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=60) as resp:
        return json.loads(resp.read())


def _joined(trace_id):
    """Every span of one request, from every kept tracer of its trace id,
    on the wall clock (ms): what benchmark/harness/spans.py joins."""
    from pinot_tpu.common import trace

    def kept():
        return [t for t in trace.finished() if t.trace_id == trace_id]

    # the door closes its root after the answer is on the wire: the
    # client can be here before the door's tracer is in the ring
    assert wait_until(lambda: any(t.parent_id is None for t in kept()))
    out = []
    for t in kept():
        for s in t.to_json():
            start = t.wall0 * 1000 + s["startMs"]
            out.append({**s, "start": start,
                        "end": start + s["durationMs"]})
    return out


def _covered(spans, parent):
    """Share of ``parent`` its children cover (their union)."""
    kids = sorted((s["start"], s["end"]) for s in spans
                  if s["parentId"] == parent["spanId"])
    total, edge = 0.0, parent["start"]
    for a, b in kids:
        if b > edge:
            total += b - max(a, edge)
            edge = b
    return total / max(parent["durationMs"], 1e-9)


SPAN_SQL = "SELECT tag, SUM(v) FROM st WHERE v < {} GROUP BY tag ORDER BY tag"


@pytest.fixture(scope="module")
def span_cluster(tmp_path_factory):
    """One server with the device executor behind broker and HTTP door."""
    base = tmp_path_factory.mktemp("spans")
    registry = ClusterRegistry()
    controller = Controller(registry, str(base / "ds"))
    server = ServerInstance("server_0", registry, str(base / "s0"))
    server.start()
    # a broker id of its own: trace ids are made of it, and the ring
    # outlives the other tests' brokers
    broker = Broker(registry, broker_id="span_broker", timeout_s=60.0)
    http = BrokerHttpServer(broker)
    http.start()
    schema = Schema.build(name="st", dimensions=[("tag", DataType.STRING)],
                          metrics=[("v", DataType.INT)])
    cfg = TableConfig(table_name="st")
    controller.add_table(cfg, schema)
    rng = np.random.default_rng(5)
    for i in range(2):
        d = str(base / f"up{i}")
        build_segment(schema, {
            "tag": np.array(["a", "b", "c"])[rng.integers(0, 3, 200_000)],
            "v": rng.integers(0, 100, 200_000).astype(np.int32)},
            d, cfg, f"st_s{i}")
        controller.upload_segment("st", d)
    assert wait_until(
        lambda: len(registry.external_view("st_OFFLINE")) == 2)
    server.engine.device.partials_cache_enabled = False
    yield broker, http, server
    http.stop()
    broker.close()
    server.stop(drain_timeout_s=0.2)


def _traced_round(http, server, literal):
    """{role: the joined spans of one traced request in that role}: a
    request alone, then a forced cohort of three."""
    pre = "SET trace = true; SET useResultCache = false; "
    solo = _post(http.url, pre + SPAN_SQL.format(literal))
    assert not solo.get("exceptions"), solo
    co = server.engine.device.coalescer
    co.force, co.window_s = True, 0.3
    answers = [None] * 3
    barrier = threading.Barrier(3)

    def caller(i):
        barrier.wait(10)
        answers[i] = _post(http.url, pre + SPAN_SQL.format(literal + 1 + i))

    threads = [threading.Thread(target=caller, args=(i,)) for i in range(3)]
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(120)
    finally:
        co.force, co.window_s = False, 0.003
    assert all(a and not a.get("exceptions") for a in answers), answers
    cohort = [_joined(a["traceId"]) for a in answers]
    out = {"solo": _joined(solo["traceId"]), "cohort": cohort}
    for spans in cohort:
        role = next(s["attrs"]["role"] for s in spans
                    if "role" in s.get("attrs", {}))
        out[role] = spans
    return out


@pytest.fixture(scope="module")
def traced_requests(span_cluster):
    """The first of three rounds of ``_traced_round``, with all three
    under ``"rounds"`` for the check that a busy host can disturb."""
    _broker, http, server = span_cluster
    _post(http.url, SPAN_SQL.format(50))  # compiles the solo pipeline
    rounds = [_traced_round(http, server, 60 + 4 * k) for k in range(3)]
    return {**rounds[0], "rounds": rounds}


class TestSpanTree:
    """Every check runs on a request that launched alone, on the leader
    of a coalesced launch and on one of its members."""

    ROLES = ["solo", "leader", "member"]

    @pytest.mark.parametrize("role", ROLES)
    def test_every_span_has_its_parent_around_it(self, traced_requests,
                                                 role):
        spans = traced_requests[role]
        by_id = {s["spanId"]: s for s in spans}
        assert len(by_id) == len(spans), "span ids repeat inside a trace"
        roots = [s for s in spans if s["parentId"] is None]
        assert [s["phase"] for s in roots] == ["http.request"]
        for s in spans:
            if s["parentId"] is None:
                continue
            parent = by_id.get(s["parentId"])
            assert parent is not None, f"{s['phase']} has no parent here"
            # one clock: a span lies inside the span that caused it (the
            # slack is the rounding of startMs/durationMs, and the two
            # clock reads a tracer's wall-clock origin is made of)
            assert s["start"] >= parent["start"] - 0.5, (s, parent)
            assert s["end"] <= parent["end"] + 0.5, (s, parent)

    @pytest.mark.parametrize("role", ROLES)
    def test_the_layers_are_all_there(self, traced_requests, role):
        names = {s["phase"] for s in traced_requests[role]}
        assert {"http.request", "http.read", "http.write",
                "broker.total", "broker.parse", "broker.admit",
                "broker.route", "broker.scatter_gather", "broker.reduce",
                "broker.respond",
                "server.total", "server.decode", "server.queue",
                "server.plan", "server.execute", "server.fetch",
                "server.trim", "server.encode",
                "engine.template", "engine.merge", "executor.gather",
                "executor.device_wait"} <= names, names
        launched = {"executor.stack", "executor.dispatch"} & names
        waited = "executor.launch_wait" in names
        assert (launched, waited) == {
            "solo": ({"executor.dispatch"}, False),
            "leader": ({"executor.stack", "executor.dispatch"}, True),
            "member": (set(), True)}[role]
        route = next(s for s in traced_requests[role]
                     if s["phase"] == "broker.route")
        assert route["attrs"] == {"segmentsRouted": 2, "servers": 1,
                                  "segmentsPrunedByBroker": 0}
        counts = {}
        for s in traced_requests[role]:
            counts.update(s.get("attrs", {}))
        assert counts["segments"] == 2 and counts["bytesOut"] > 0
        assert (counts["segmentsOnDevice"], counts["segmentsOnHost"]) \
            == (2, 0)

    @pytest.mark.parametrize("role", ROLES)
    def test_children_cover_each_total(self, traced_requests, role):
        # the spans tile their total; a thread that loses the processor
        # between two of them (the tests run six at a time) is not a
        # hole in the tiling, so the best of three requests counts
        for name in ("broker.total", "server.total"):
            shares = []
            for r in traced_requests["rounds"]:
                total = next(s for s in r[role] if s["phase"] == name)
                shares.append(_covered(r[role], total))
            assert max(shares) >= 0.9, (name, shares)

    @pytest.mark.parametrize("role", ROLES)
    def test_broker_and_server_on_one_clock(self, traced_requests, role):
        spans = traced_requests[role]
        scatter = next(s for s in spans
                       if s["phase"] == "broker.scatter_gather")
        server = next(s for s in spans if s["phase"] == "server.total")
        assert server["parentId"] == scatter["spanId"]
        assert server["attrs"]["attempt"] == "primary"
        assert scatter["start"] - 0.5 <= server["start"]
        assert server["end"] <= scatter["end"] + 0.5
        # cpu is of the thread that ran the span; it cannot pass its wall
        for s in spans:
            if "cpuMs" in s:
                assert s["cpuMs"] <= s["durationMs"] + 1.0, s

    def test_cohort_shares_one_launch(self, traced_requests):
        waits = [next(s for s in spans
                      if s["phase"] == "executor.device_wait")["attrs"]
                 for spans in traced_requests["cohort"]]
        assert len({w["launchId"] for w in waits}) == 1
        assert [w["cohortSize"] for w in waits] == [3, 3, 3]
        assert [w["cohortPadded"] for w in waits] == [4, 4, 4]
        assert sorted(w["role"] for w in waits) == [
            "leader", "member", "member"]
        assert {w["windowKind"] for w in waits} == {"fixed"}
        solo = next(s for s in traced_requests["solo"]
                    if s["phase"] == "executor.device_wait")["attrs"]
        assert (solo["role"], solo["cohortSize"], solo["windowKind"]) \
            == ("solo", 1, "none")
        assert solo["launchId"] != waits[0]["launchId"]
        # the one fetch of the cohort: one member's trace has the link
        links = [spans for spans in traced_requests["cohort"]
                 if any(s["phase"] == "executor.link" for s in spans)]
        assert len(links) == 1

    def test_callers_together_each_launch_their_own(self, span_cluster):
        """Four callers send one template under different literals while
        a launch of the template is dispatched and unfetched (the
        executor's ``inflight`` is above 1 for every one of them): each
        gets a launch of its own, at once — no window, no stacking —
        and the host executor's answer."""
        from pinot_tpu.engine.engine import QueryEngine
        from pinot_tpu.query.optimizer import optimize_query
        from pinot_tpu.sql.compiler import compile_query

        _broker, http, server = span_cluster
        engine = server.engine
        dev = engine.device
        segs = list(engine.tables["st_OFFLINE"].segments.values())
        host = QueryEngine(device_executor=None)
        for seg in segs:
            host.add_segment("st", seg)
        pre = "SET trace = true; SET useResultCache = false; "
        literals = [11, 22, 33, 44]
        q = engine._expand_star(optimize_query(compile_query(
            SPAN_SQL.format(77).replace("FROM st", "FROM st_OFFLINE"))),
            segs[0])
        seen = []
        dispatch = dev._dispatch

        def counting(*args, **kwargs):
            seen.append(dev.inflight)
            return dispatch(*args, **kwargs)

        answers = [None] * len(literals)
        barrier = threading.Barrier(len(literals))

        def caller(i):
            barrier.wait(10)
            answers[i] = _post(http.url, pre + SPAN_SQL.format(literals[i]))

        threads = [threading.Thread(target=caller, args=(i,))
                   for i in range(len(literals))]
        held = dev.launch(q, segs)
        dev._dispatch = counting
        try:
            for t in threads:
                t.start()
            for t in threads:
                t.join(120)
            assert not held._done  # they answered with it still out
        finally:
            del dev._dispatch
            held.fetch()
        assert all(a and not a.get("exceptions") for a in answers), answers
        assert len(seen) == len(literals) and min(seen) > 1, seen
        waits = []
        for a, literal in zip(answers, literals):
            assert a["resultTable"]["rows"] == host.execute(
                SPAN_SQL.format(literal))["resultTable"]["rows"]
            spans = _joined(a["traceId"])
            names = {s["phase"] for s in spans}
            assert "executor.dispatch" in names
            assert not {"executor.launch_wait", "executor.stack"} & names
            waits.append(next(s for s in spans if s["phase"]
                              == "executor.device_wait")["attrs"])
        assert len({w["launchId"] for w in waits}) == len(literals)
        assert {(w["role"], w["cohortSize"], w["cohortPadded"],
                 w["windowKind"]) for w in waits} == {("solo", 1, 1, "none")}

    def test_untraced_request_leaves_nothing(self, span_cluster,
                                             monkeypatch):
        """No Tracer, no kept trace, no profiler annotation — and the
        same rows."""
        from pinot_tpu.common import trace

        _broker, http, _server = span_cluster
        sql = "SET useResultCache = false; " + SPAN_SQL.format(40)
        traced = _post(http.url, "SET trace = true; " + sql)
        _joined(traced["traceId"])  # until the door has kept its tracer
        made = []
        init = trace.Tracer.__init__
        annotate = trace._annotate
        monkeypatch.setattr(
            trace.Tracer, "__init__",
            lambda self, *a, **kw: (made.append("tracer"),
                                    init(self, *a, **kw))[1])
        monkeypatch.setattr(
            trace, "_annotate",
            lambda *a, **kw: (made.append("annotation"),
                              annotate(*a, **kw))[1])
        kept = len(trace.finished())
        plain = _post(http.url, sql)
        assert not plain.get("exceptions"), plain
        assert made == [] and len(trace.finished()) == kept
        assert "traceInfo" not in plain and "traceId" not in plain
        assert plain["resultTable"] == traced["resultTable"]
        assert plain["numSegmentsOnHost"] == 0
        # the same door with the option on makes both
        _post(http.url, "SET trace = true; " + sql)
        assert "tracer" in made and "annotation" in made

    def test_ring_is_bounded(self):
        from pinot_tpu.common import trace

        for i in range(trace.RING_BOUND + 10):
            trace.Tracer(f"ring-{i}").open("http.request").close()
        kept = trace.finished()
        assert len(kept) == trace.RING_BOUND
        assert kept[-1].trace_id == f"ring-{trace.RING_BOUND + 9}"
        now = time.time()
        assert trace.finished(now + 60) == []
        assert len(trace.finished(now - 600, now + 60)) == trace.RING_BOUND

    def test_spans_reach_the_profiler_only_when_it_runs(self, tmp_path):
        """A running span is written into the profiler's trace under a
        stable name; a quiet one (a root, a wait for another span of the
        request) is not; without a session both just record."""
        import jax

        from pinot_tpu.common import trace

        def request(trace_id):
            t = trace.Tracer(trace_id)
            root = t.open("server.total")
            with t.span("server.execute", quiet=True):
                with t.span("executor.gather"):
                    time.sleep(0.002)
            root.close()
            return [s["phase"] for s in t.to_json()]

        names = ["server.total", "server.execute", "executor.gather"]
        assert request("no-session") == names
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            assert request("in-session") == names
        finally:
            jax.profiler.stop_trace()
        import glob

        from jax.profiler import ProfileData

        pb = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))[0]
        seen = {ev.name for plane in ProfileData.from_file(pb).planes
                for line in plane.lines for ev in line.events
                if ev.name.startswith("pinot.")}
        assert seen == {"pinot.executor.gather"}

    @pytest.mark.parametrize("fault", [False, True])
    def test_segments_on_host_counts_a_fallback(self, span_cluster, fault):
        from pinot_tpu.common import faults

        broker, _http, server = span_cluster
        sql = "SET useResultCache = false; " + SPAN_SQL.format(30)
        clean = broker.execute(sql)
        assert clean["numSegmentsOnHost"] == 0
        if not fault:
            return
        counted = server.metrics.snapshot()["counters"].get(
            "server.segmentsOnHost", 0)
        faults.install(faults.Fault(point="device.fetch", mode="error",
                                    times=1))
        try:
            fell = broker.execute("SET trace = true; " + sql)
        finally:
            faults.clear()
            server.engine.device.reset_quarantine()
        assert not fell.get("exceptions"), fell
        assert fell["resultTable"] == clean["resultTable"]
        assert fell["numSegmentsOnHost"] == 2
        assert server.metrics.snapshot()["counters"][
            "server.segmentsOnHost"] == counted + 2
        spans = [s for v in fell["traceInfo"].values() for s in v]
        assert any(s["phase"] == "engine.host_fallback" for s in spans)
        merge = next(s for s in spans if s["phase"] == "engine.merge")
        assert merge["attrs"] == {"segmentsOnDevice": 0,
                                  "segmentsOnHost": 2}
