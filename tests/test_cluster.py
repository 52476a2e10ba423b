"""Cluster integration: controller + servers + broker over real gRPC.

Reference analogs: pinot-integration-test-base ClusterTest (all roles in one
process, real transport), OfflineClusterIntegrationTest (push segments,
query via broker), MultiNodesOfflineClusterIntegrationTest, LLCRealtime-
ClusterIntegrationTest (stream → consuming → commit → broker-visible),
ChaosMonkey-style server kill with partial results, rebalance, retention.
"""

import time

import numpy as np
import pytest

from pinot_tpu.broker.broker import Broker
from pinot_tpu.cluster.registry import ClusterRegistry, Role, SegmentState
from pinot_tpu.common.datatypes import DataType
from pinot_tpu.common.schema import Schema
from pinot_tpu.common.table_config import StreamConfig, TableConfig, TableType
from pinot_tpu.controller.controller import Controller
from pinot_tpu.server.server import ServerInstance
from pinot_tpu.storage.creator import build_segment
from pinot_tpu.stream.memory_stream import TopicRegistry


def wait_until(cond, timeout=10.0, interval=0.05):
    t0 = time.time()
    while time.time() - t0 < timeout:
        if cond():
            return True
        time.sleep(interval)
    return False


@pytest.fixture()
def cluster(tmp_path):
    registry = ClusterRegistry()
    controller = Controller(registry, str(tmp_path / "deepstore"))
    servers = [
        ServerInstance(f"server_{i}", registry, str(tmp_path / f"srv{i}"),
                       device_executor=None)
        for i in range(3)
    ]
    for s in servers:
        s.start()
    broker = Broker(registry, timeout_s=10.0)
    yield registry, controller, servers, broker
    broker.close()
    for s in servers:
        try:
            s.stop()
        except Exception:
            pass


def _offline_table(tmp_path, controller, n_segments=4, rows=2000, replication=2):
    schema = Schema.build(
        name="sales",
        dimensions=[("region", DataType.STRING), ("product", DataType.STRING)],
        metrics=[("amount", DataType.INT)],
    )
    cfg = TableConfig(table_name="sales", replication=replication)
    controller.add_table(cfg, schema)
    rng = np.random.default_rng(9)
    all_cols = []
    for i in range(n_segments):
        cols = {
            "region": np.array(["na", "eu", "apac"])[rng.integers(0, 3, rows)],
            "product": np.array([f"p{j}" for j in range(50)])[rng.integers(0, 50, rows)],
            "amount": rng.integers(1, 500, rows).astype(np.int32),
        }
        all_cols.append(cols)
        d = str(tmp_path / f"upload_s{i}")
        build_segment(schema, cols, d, cfg, f"sales_s{i}")
        controller.upload_segment("sales", d)
    return schema, cfg, all_cols


class TestOfflineCluster:
    def test_push_and_query(self, cluster, tmp_path):
        registry, controller, servers, broker = cluster
        _, _, all_cols = _offline_table(tmp_path, controller)
        # servers pick up assignments via sync loop
        assert wait_until(lambda: sum(
            len(s.engine.tables.get("sales_OFFLINE").segments) if s.engine.tables.get("sales_OFFLINE") else 0
            for s in servers
        ) >= 8)  # 4 segments x 2 replicas

        total = sum(int(c["amount"].sum()) for c in all_cols)
        r = broker.execute("SELECT COUNT(*), SUM(amount) FROM sales")
        assert not r["exceptions"], r
        assert r["resultTable"]["rows"][0] == [8000, total]
        assert r["numServersResponded"] >= 1
        # every segment counted exactly once despite replication
        assert r["numSegmentsQueried"] == 4
        # case-insensitive table resolution at the broker
        # (BaseBrokerRequestHandler.java:245-254 / TableCache ignore-case)
        for variant in ("SALES", "Sales", "sAlEs_OFFLINE"):
            r2 = broker.execute(f"SELECT COUNT(*) FROM {variant}")
            assert not r2["exceptions"], (variant, r2)
            assert r2["resultTable"]["rows"][0][0] == 8000

    def test_group_by_through_broker(self, cluster, tmp_path):
        registry, controller, servers, broker = cluster
        _, _, all_cols = _offline_table(tmp_path, controller)
        assert wait_until(lambda: len(registry.external_view("sales_OFFLINE")) == 4)
        r = broker.execute(
            "SELECT region, COUNT(*), SUM(amount) FROM sales GROUP BY region ORDER BY region"
        )
        assert not r["exceptions"], r
        import collections

        want = collections.Counter()
        wsum = collections.Counter()
        for c in all_cols:
            for reg, amt in zip(c["region"], c["amount"]):
                want[reg] += 1
                wsum[reg] += int(amt)
        got = {row[0]: (row[1], row[2]) for row in r["resultTable"]["rows"]}
        assert got == {k: (want[k], wsum[k]) for k in want}

    def test_server_death_partial_results(self, cluster, tmp_path):
        registry, controller, servers, broker = cluster
        _offline_table(tmp_path, controller, replication=1)
        assert wait_until(lambda: len(registry.external_view("sales_OFFLINE")) == 4)
        ok = broker.execute("SELECT COUNT(*) FROM sales")
        assert not ok["exceptions"]
        # kill one server hard (ChaosMonkey): with replication=1 its segments
        # are lost → partial results + SERVER_NOT_RESPONDING exception
        victim = next(
            s for s in servers if registry.assigned_segments(s.instance_id)
        )
        victim.transport.stop(grace=0)
        r = broker.execute("SELECT COUNT(*) FROM sales")
        assert r.get("partialResult") is True
        assert any("SERVER_NOT_RESPONDING" in e["message"] for e in r["exceptions"])
        assert r["resultTable"]["rows"][0][0] < 8000  # partial data

    def test_failover_with_replication(self, cluster, tmp_path):
        registry, controller, servers, broker = cluster
        _offline_table(tmp_path, controller, replication=2)
        assert wait_until(lambda: sum(
            len(v) for v in registry.external_view("sales_OFFLINE").values()
        ) >= 8)
        victim = next(s for s in servers if registry.assigned_segments(s.instance_id))
        victim.transport.stop(grace=0)
        # first query may be partial (failure detected); retried queries
        # route around the dead server to the surviving replicas
        deadline = time.time() + 10
        while time.time() < deadline:
            r = broker.execute("SELECT COUNT(*) FROM sales")
            if not r.get("exceptions") and r["resultTable"]["rows"][0][0] == 8000:
                break
            time.sleep(0.1)
        assert r["resultTable"]["rows"][0][0] == 8000, r

    def test_rebalance_after_server_join(self, cluster, tmp_path):
        registry, controller, servers, broker = cluster
        _offline_table(tmp_path, controller, replication=1)
        late = ServerInstance("server_late", registry, str(tmp_path / "late"),
                              device_executor=None)
        late.start()
        try:
            mapping = controller.rebalance("sales")
            hosts = {i for insts in mapping.values() for i in insts}
            # late server participates after rebalance OR load stays balanced
            counts = {}
            for insts in mapping.values():
                for i in insts:
                    counts[i] = counts.get(i, 0) + 1
            assert max(counts.values()) - min(counts.values()) <= 1
            assert wait_until(
                lambda: broker.execute("SELECT COUNT(*) FROM sales")
                .get("resultTable", {}).get("rows", [[0]])[0][0] == 8000
            )
        finally:
            late.stop()

    def test_retention(self, cluster, tmp_path):
        registry, controller, servers, broker = cluster
        schema = Schema.build(
            name="logs",
            dimensions=[("k", DataType.STRING)],
            metrics=[("v", DataType.INT)],
            datetimes=[("ts", DataType.LONG)],
        )
        cfg = TableConfig(table_name="logs", retention_days=7, time_column="ts")
        controller.add_table(cfg, schema)
        now = int(time.time() * 1000)
        old_ts = now - 30 * 86_400_000
        for name, ts in (("old", old_ts), ("new", now)):
            d = str(tmp_path / f"logs_{name}")
            build_segment(
                schema,
                {"k": ["a"] * 10, "v": list(range(10)), "ts": [ts] * 10},
                d, cfg, f"logs_{name}",
            )
            controller.upload_segment("logs", d)
        assert len(registry.segments("logs_OFFLINE")) == 2
        dropped = controller.run_retention()
        assert ("logs_OFFLINE", "logs_old") in dropped
        assert "logs_new" in registry.segments("logs_OFFLINE")


class TestRealtimeCluster:
    def test_stream_to_broker_visibility(self, cluster, tmp_path):
        registry, controller, servers, broker = cluster
        TopicRegistry.delete("clicks")
        topic = TopicRegistry.create("clicks", 2)
        schema = Schema.build(
            name="clicks",
            dimensions=[("page", DataType.STRING)],
            metrics=[("n", DataType.INT)],
        )
        cfg = TableConfig(
            table_name="clicks", table_type=TableType.REALTIME,
            stream=StreamConfig(
                stream_type="memory", topic="clicks", decoder="json",
                segment_flush_threshold_rows=60, segment_flush_threshold_seconds=3600,
            ),
        )
        controller.add_table(cfg, schema)
        for i in range(200):
            topic.publish_json({"page": f"page{i % 8}", "n": 1}, partition=i % 2)

        def broker_count():
            r = broker.execute("SELECT COUNT(*) FROM clicks")
            if r.get("exceptions"):
                return -1
            return r["resultTable"]["rows"][0][0]

        assert wait_until(lambda: broker_count() == 200, timeout=15), broker_count()
        # commits happened and sealed segments are registered ONLINE
        assert wait_until(lambda: any(
            rec.state == SegmentState.ONLINE
            for rec in registry.segments("clicks_REALTIME").values()
        ))
        r = broker.execute(
            "SELECT page, COUNT(*) FROM clicks GROUP BY page ORDER BY page LIMIT 10"
        )
        assert [row[1] for row in r["resultTable"]["rows"]] == [25] * 8


    def test_kill_consuming_server_no_loss(self, cluster, tmp_path):
        """Multi-replica consumption survives a consumer death: the replica
        keeps serving, the controller re-homes the dead server's partitions,
        and every row stays queryable exactly once (SegmentCompletionManager
        + RealtimeSegmentValidationManager semantics)."""
        registry, controller, servers, broker = cluster
        TopicRegistry.delete("mrclicks")
        topic = TopicRegistry.create("mrclicks", 1)
        schema = Schema.build(
            name="mrclicks",
            dimensions=[("page", DataType.STRING)],
            metrics=[("n", DataType.INT)],
        )
        cfg = TableConfig(
            table_name="mrclicks", table_type=TableType.REALTIME, replication=2,
            stream=StreamConfig(
                stream_type="memory", topic="mrclicks", decoder="json",
                segment_flush_threshold_rows=40, segment_flush_threshold_seconds=3600,
            ),
        )
        controller.add_table(cfg, schema)
        pa = registry.partition_assignment("mrclicks_REALTIME")
        assert all(len(v) == 2 for v in pa.values())

        def broker_count():
            r = broker.execute("SELECT COUNT(*) FROM mrclicks")
            if r.get("exceptions"):
                return -1
            return r["resultTable"]["rows"][0][0]

        for i in range(100):
            topic.publish_json({"page": f"p{i % 4}", "n": 1})
        assert wait_until(lambda: broker_count() == 100, timeout=20), broker_count()

        # kill one of the consuming replicas hard, mid-stream
        victims = set(pa["0"])
        victim = next(s for s in servers if s.instance_id in victims)
        victim.transport.stop(grace=0)
        victim._stop.set()  # sync loop (and its consumers' publishes) halt
        for mgr in victim._realtime_managers.values():
            mgr.stop(commit_remaining=False)
        for i in range(100):
            topic.publish_json({"page": f"p{i % 4}", "n": 1})
        controller.run_realtime_repair()

        deadline = time.time() + 20
        count = -1
        while time.time() < deadline:
            count = broker_count()
            if count == 200:
                break
            time.sleep(0.1)
        assert count == 200, count
        r = broker.execute(
            "SELECT page, COUNT(*) FROM mrclicks GROUP BY page ORDER BY page"
        )
        assert [row[1] for row in r["resultTable"]["rows"]] == [50] * 4


class TestHybridTable:
    def test_time_boundary_split(self, cluster, tmp_path):
        """Hybrid table: offline covers old time range, realtime covers new;
        the broker splits at the boundary so overlapping rows dedupe
        (TimeBoundaryManager + BaseBrokerRequestHandler.java:387-395)."""
        registry, controller, servers, broker = cluster
        schema = Schema.build(
            name="metrics",
            dimensions=[("host", DataType.STRING)],
            metrics=[("v", DataType.INT)],
            datetimes=[("ts", DataType.LONG)],
        )
        off_cfg = TableConfig(table_name="metrics", time_column="ts")
        controller.add_table(off_cfg, schema)
        # offline segment: ts 0..99 (100 rows)
        d = str(tmp_path / "metrics_off")
        build_segment(
            schema,
            {"host": ["h1"] * 100, "v": [1] * 100, "ts": list(range(100))},
            d, off_cfg, "metrics_off_0",
        )
        controller.upload_segment("metrics", d)

        TopicRegistry.delete("metrics_stream")
        topic = TopicRegistry.create("metrics_stream", 1)
        rt_cfg = TableConfig(
            table_name="metrics", table_type=TableType.REALTIME, time_column="ts",
            stream=StreamConfig(
                stream_type="memory", topic="metrics_stream", decoder="json",
                segment_flush_threshold_rows=10_000,
                segment_flush_threshold_seconds=3600,
            ),
        )
        controller.add_table(rt_cfg, schema)
        # realtime overlaps offline for ts 80..99 (late replay), then extends
        for ts in range(80, 150):
            topic.publish_json({"host": "h1", "v": 1, "ts": ts})

        def total():
            r = broker.execute("SELECT COUNT(*) FROM metrics")
            if r.get("exceptions"):
                return -1
            return r["resultTable"]["rows"][0][0]

        # boundary = offline max ts (99): offline answers ts<=99 (100 rows),
        # realtime answers ts>99 (50 rows) — overlap NOT double counted
        assert wait_until(lambda: total() == 150, timeout=15), total()


class TestBrokerHttp:
    def test_http_query(self, cluster, tmp_path):
        import json as _json
        import urllib.request

        registry, controller, servers, broker = cluster
        _offline_table(tmp_path, controller, n_segments=1, rows=100)
        assert wait_until(lambda: len(registry.external_view("sales_OFFLINE")) == 1)

        from pinot_tpu.broker.http_api import BrokerHttpServer

        http_srv = BrokerHttpServer(broker)
        http_srv.start()
        try:
            req = urllib.request.Request(
                http_srv.url + "/query/sql",
                data=_json.dumps({"sql": "SELECT COUNT(*) FROM sales"}).encode(),
                headers={"Content-Type": "application/json"},
            )
            with urllib.request.urlopen(req, timeout=10) as resp:
                body = _json.loads(resp.read())
            assert body["resultTable"]["rows"][0][0] == 100
            with urllib.request.urlopen(http_srv.url + "/health", timeout=5) as resp:
                assert _json.loads(resp.read())["status"] == "OK"
        finally:
            http_srv.stop()


class TestServerErrors:
    def test_query_error_does_not_poison_failure_detector(self, cluster, tmp_path):
        registry, controller, servers, broker = cluster
        _offline_table(tmp_path, controller, n_segments=1, rows=100)
        assert wait_until(lambda: len(registry.external_view("sales_OFFLINE")) == 1)
        r = broker.execute("SELECT nosuchcolumn FROM sales LIMIT 1")
        assert r["exceptions"], r
        assert "SERVER_NOT_RESPONDING" not in r["exceptions"][0]["message"]
        # servers stay healthy: a correct query right after must succeed fully
        r2 = broker.execute("SELECT COUNT(*) FROM sales")
        assert not r2["exceptions"], r2
        assert r2["resultTable"]["rows"][0][0] == 100

    def test_select_star_through_broker(self, cluster, tmp_path):
        registry, controller, servers, broker = cluster
        _offline_table(tmp_path, controller, n_segments=1, rows=50)
        assert wait_until(lambda: len(registry.external_view("sales_OFFLINE")) == 1)
        r = broker.execute("SELECT * FROM sales LIMIT 5")
        assert not r["exceptions"], r
        assert r["resultTable"]["dataSchema"]["columnNames"] == [
            "region", "product", "amount"
        ]
        assert len(r["resultTable"]["rows"]) == 5
        assert all(len(row) == 3 for row in r["resultTable"]["rows"])


# ---------------------------------------------------------------------------
# ISSUE 10: replica-group assignment, load-aware routing, broker result cache
# ---------------------------------------------------------------------------

TABLE_OFF = "sales_OFFLINE"


def _assignment_by_group(registry, table=TABLE_OFF):
    """{group name: {segment: instance}} from the written assignment."""
    groups = registry.replica_groups(table)
    assign = registry.assignment(table)
    out = {}
    for gname, members in groups.items():
        mset = set(members)
        out[gname] = {
            seg: next((i for i in insts if i in mset), None)
            for seg, insts in assign.items()
        }
    return out


class TestReplicaGroupAssignment:
    def test_every_segment_r_covered(self, cluster, tmp_path):
        registry, controller, servers, broker = cluster
        _offline_table(tmp_path, controller, n_segments=6, replication=2)
        controller.setup_replica_groups("sales")
        groups = registry.replica_groups(TABLE_OFF)
        assert len(groups) == 2
        # groups partition the live servers (no instance in two groups)
        members = [m for ms in groups.values() for m in ms]
        assert len(members) == len(set(members)) == 3
        # every segment: exactly one copy per group, R copies total
        assign = registry.assignment(TABLE_OFF)
        assert len(assign) == 6
        for seg, insts in assign.items():
            assert len(insts) == 2, (seg, insts)
            for gname, ms in groups.items():
                assert len(set(insts) & set(ms)) == 1, (seg, gname)

    def test_rebalance_on_join_moves_minimum(self, cluster, tmp_path):
        registry, controller, servers, broker = cluster
        _offline_table(tmp_path, controller, n_segments=8, replication=1)
        controller.setup_replica_groups("sales")
        before = registry.assignment(TABLE_OFF)
        groups_before = registry.replica_groups(TABLE_OFF)
        # a 4th server joins; repair rebuilds groups with minimal movement
        s_new = ServerInstance("server_3", registry,
                               str(tmp_path / "srv3"), device_executor=None)
        s_new.start()
        try:
            controller.run_replica_group_repair()
            after = registry.assignment(TABLE_OFF)
            groups_after = registry.replica_groups(TABLE_OFF)
            # survivors keep their group membership (no leveling can
            # trigger here: R=1 means one group before and after)
            for gname, ms in groups_before.items():
                assert set(ms) <= set(groups_after[gname]), \
                    (gname, ms, groups_after)
            # the new server lands in exactly one group
            placed = [g for g, ms in groups_after.items()
                      if "server_3" in ms]
            assert len(placed) == 1
            # minimal movement: only segments filling the joiner's fair
            # share move — fair share = ceil(8 segments / group size)
            group = groups_after[placed[0]]
            fair = -(-8 // len(group))
            moved = sum(
                1 for seg in before
                if set(before[seg]) != set(after.get(seg, ()))
            )
            assert moved <= fair, (moved, fair, before, after)
            # coverage invariant survives the join
            for seg, insts in after.items():
                assert len(insts) == 1
        finally:
            s_new.stop()

    def test_partition_aware_placement(self, cluster, tmp_path):
        from pinot_tpu.common.table_config import SegmentPartitionConfig

        registry, controller, servers, broker = cluster
        schema = Schema.build(
            name="sales",
            dimensions=[("region", DataType.STRING)],
            metrics=[("store_id", DataType.INT),
                     ("amount", DataType.INT)],
        )
        cfg = TableConfig(
            table_name="sales", replication=1,
            partition=SegmentPartitionConfig(
                column_partition_map={"store_id": ("modulo", 4)}),
        )
        controller.add_table(cfg, schema)
        rng = np.random.default_rng(4)
        # two segments per modulo-partition: co-partitioned segments must
        # co-locate (the broker prunes partition-EQ queries with the same
        # common/pruning.py algebra the server uses — placement has to
        # agree or the pruned route would miss its one holder)
        for i in range(8):
            part = i % 4
            store = np.full(300, part, dtype=np.int64) + \
                4 * rng.integers(0, 20, 300)
            cols = {
                "region": np.array(["na", "eu"])[rng.integers(0, 2, 300)],
                "store_id": store.astype(np.int32),
                "amount": rng.integers(1, 100, 300).astype(np.int32),
            }
            d = str(tmp_path / f"pseg{i}")
            build_segment(schema, cols, d, cfg, f"sales_p{i}")
            controller.upload_segment("sales", d)
        controller.setup_replica_groups("sales")
        records = registry.segments(TABLE_OFF)
        by_group = _assignment_by_group(registry)
        for gname, seg_map in by_group.items():
            # the controller indexes the group list in REGISTRY order
            # (build_replica_groups insertion order), not sorted
            group_list = registry.replica_groups(TABLE_OFF)[gname]
            by_part = {}
            for seg, inst in seg_map.items():
                rec = records[seg]
                assert rec.partition_ids, seg
                pid = int(rec.partition_ids[0])
                by_part.setdefault(pid, set()).add(inst)
                # deterministic pick: partition id -> member
                assert inst == group_list[pid % len(group_list)], \
                    (seg, pid, inst, group_list)
            for pid, insts in by_part.items():
                assert len(insts) == 1, (gname, pid, insts)


class TestLoadAwareRouting:
    def _registry_with_groups(self):
        from pinot_tpu.cluster.registry import InstanceInfo, SegmentRecord

        registry = ClusterRegistry()
        for inst in ("a", "b"):
            registry.register_instance(
                InstanceInfo(instance_id=inst, role=Role.SERVER))
        schema = Schema.build(name="t", dimensions=[("d", DataType.STRING)],
                              metrics=[("m", DataType.INT)])
        registry.add_table(TableConfig(table_name="t"), schema)
        for seg in ("t_s0", "t_s1"):
            registry.add_segment(
                SegmentRecord(name=seg, table="t_OFFLINE", n_docs=10),
                ["a", "b"])
        registry.update_external_view("a", {"t_OFFLINE": ["t_s0", "t_s1"]})
        registry.update_external_view("b", {"t_OFFLINE": ["t_s0", "t_s1"]})
        registry.set_replica_groups("t_OFFLINE",
                                    {"rg_0": ["a"], "rg_1": ["b"]})
        return registry

    def test_least_loaded_group_wins(self):
        from pinot_tpu.broker.broker import FailureDetector, RoutingManager

        registry = self._registry_with_groups()
        rm = RoutingManager(registry, FailureDetector())
        # instance "a" reports a saturated scheduler, "b" reports idle
        rm.loads.observe("a", pressure=8.0)
        rm.loads.observe("b", pressure=0.0)
        picks = set()
        for _ in range(6):
            routing, replicas, info = rm.routing_with_replicas("t_OFFLINE")
            assert info["numReplicaGroupsQueried"] == 1
            assert info["loadScore"] is not None
            picks.add(info["replicaGroup"])
            assert set(routing) == {"b"}, routing
        assert picks == {"rg_1"}

    def test_tied_groups_share_round_robin(self):
        from pinot_tpu.broker.broker import FailureDetector, RoutingManager

        registry = self._registry_with_groups()
        rm = RoutingManager(registry, FailureDetector())
        rm.loads.observe("a", pressure=0.0)
        rm.loads.observe("b", pressure=0.0)
        picks = [rm.routing_with_replicas("t_OFFLINE")[2]["replicaGroup"]
                 for _ in range(8)]
        assert set(picks) == {"rg_0", "rg_1"}

    def test_reservation_counts_concurrent_arrivals(self):
        from pinot_tpu.broker.broker import FailureDetector, RoutingManager

        registry = self._registry_with_groups()
        rm = RoutingManager(registry, FailureDetector())
        rm.loads.observe("a", pressure=0.0)
        rm.loads.observe("b", pressure=0.0)
        # two reserving queries that never release must land on DIFFERENT
        # groups: the second pick sees the first's outstanding count
        _, _, i1 = rm.routing_with_replicas("t_OFFLINE", reserve=True)
        _, _, i2 = rm.routing_with_replicas("t_OFFLINE", reserve=True)
        assert {i1["replicaGroup"], i2["replicaGroup"]} == {"rg_0", "rg_1"}
        for info in (i1, i2):
            for inst in info.get("reserved", ()):
                rm.release([inst])

    def test_unhealthy_group_skipped(self):
        from pinot_tpu.broker.broker import FailureDetector, RoutingManager

        registry = self._registry_with_groups()
        det = FailureDetector(initial_backoff_s=30.0)
        rm = RoutingManager(registry, det)
        rm.loads.observe("a", pressure=0.0)
        rm.loads.observe("b", pressure=5.0)  # loaded BUT healthy
        det.mark_failure("a")  # idle group's only member is down
        for _ in range(4):
            routing, _, info = rm.routing_with_replicas("t_OFFLINE")
            assert info["replicaGroup"] == "rg_1"
            assert set(routing) == {"b"}


def _every_replica_online(registry, n_segments, replication=2):
    """Every replica of every segment serves. A segment is in the external
    view with its FIRST replica; each later one's report moves the routing
    generation, and a result-cache entry filled before it is stale after."""
    view = registry.external_view(TABLE_OFF)
    return len(view) == n_segments and all(
        len(instances) == replication for instances in view.values())


class TestBrokerResultCache:
    def test_hit_miss_parity_and_invalidation(self, cluster, tmp_path):
        from pinot_tpu.common import freshness

        registry, controller, servers, broker = cluster
        _offline_table(tmp_path, controller, n_segments=3, rows=500)
        assert wait_until(lambda: _every_replica_online(registry, 3))
        cbroker = Broker(registry, broker_id="cache_broker",
                         timeout_s=10.0, result_cache=True)
        try:
            sql = ("SELECT region, COUNT(*), SUM(amount) FROM sales "
                   "GROUP BY region ORDER BY region")
            miss = cbroker.execute(sql)
            assert not miss["exceptions"], miss
            assert miss["resultCacheHit"] is False
            hit = cbroker.execute(sql)
            assert hit["resultCacheHit"] is True
            # parity: hit == miss == cache-off broker, bit-exact
            off = broker.execute(sql)
            assert hit["resultTable"]["rows"] == \
                miss["resultTable"]["rows"] == off["resultTable"]["rows"]
            assert cbroker.result_cache.stats()["hits"] == 1
            # a routing change (new segment uploaded) invalidates: the
            # next execution is a MISS and sees the new rows
            schema = registry.table_schema(TABLE_OFF)
            rng = np.random.default_rng(77)
            cols = {
                "region": np.array(["apac"] * 40),
                "product": np.array([f"p{j}" for j in range(50)])[
                    rng.integers(0, 50, 40)],
                "amount": np.full(40, 7, dtype=np.int32),
            }
            d = str(tmp_path / "late_seg")
            build_segment(schema, cols, d,
                          TableConfig(table_name="sales"), "sales_late")
            controller.upload_segment("sales", d)
            assert wait_until(lambda: _every_replica_online(registry, 4))
            r2 = cbroker.execute(sql)
            assert r2["resultCacheHit"] is False
            assert r2["resultTable"]["rows"] != hit["resultTable"]["rows"]
            # an epoch bump (in-place mutation, e.g. a consuming append)
            # invalidates even with the segment set unchanged. Servers
            # report epochs via heartbeat + piggyback; in-process they
            # share the freshness module, so bump + heartbeat directly.
            assert cbroker.execute(sql)["resultCacheHit"] is True  # r2 filled
            freshness.bump("sales")
            for s in servers:
                registry.heartbeat(s.instance_id,
                                   table_epochs=freshness.snapshot())
            time.sleep(0.3)  # ride out the broker's instances memo
            r3 = cbroker.execute(sql)
            assert r3["resultCacheHit"] is False
            assert r3["resultTable"]["rows"] == \
                r2["resultTable"]["rows"]
            assert cbroker.result_cache.stats()["invalidations"] >= 1
        finally:
            cbroker.close()
            freshness.reset()

    def test_opt_out_and_uncacheable_queries(self, cluster, tmp_path):
        registry, controller, servers, broker = cluster
        _offline_table(tmp_path, controller, n_segments=1, rows=100)
        assert wait_until(lambda: _every_replica_online(registry, 1))
        cbroker = Broker(registry, broker_id="cache_broker2",
                         timeout_s=10.0, result_cache=True)
        try:
            sql = "SELECT COUNT(*) FROM sales"
            cbroker.execute(sql)
            assert cbroker.execute(sql)["resultCacheHit"] is True
            r = cbroker.execute("SET useResultCache = false; " + sql)
            assert "resultCacheHit" not in r
            # cache-off broker can opt IN per query
            r2 = broker.execute("SET useResultCache = true; " + sql)
            assert r2["resultCacheHit"] is False
            r3 = broker.execute("SET useResultCache = true; " + sql)
            assert r3["resultCacheHit"] is True
        finally:
            cbroker.close()

    def test_epoch_bump_seams(self, tmp_path):
        """The three in-place mutation seams (append, upsert-invalidate,
        seal) and chunklet promotion all bump the table freshness epoch —
        the contract the broker cache's staleness view rests on."""
        from pinot_tpu.common import freshness
        from pinot_tpu.common.table_config import ChunkletConfig
        from pinot_tpu.storage.mutable import MutableSegment

        freshness.reset()
        schema = Schema.build(
            name="rt", dimensions=[("zone", DataType.STRING)],
            metrics=[("fare", DataType.INT)],
            primary_key_columns=["zone"],
        )
        # ChunkletIndex floors rows_per_chunklet at 1024, so index past
        # that to make promote() actually freeze a block
        cfg = TableConfig(
            table_name="rt",
            chunklets=ChunkletConfig(enabled=True, rows_per_chunklet=1024,
                                     device_min_rows=0))
        seg = MutableSegment(schema, "rt__0", cfg, enable_upsert=True)
        assert freshness.epoch("rt") == 0
        seg.index({"zone": "z1", "fare": 3})
        e1 = freshness.epoch("rt")
        assert e1 >= 1
        seg.index_batch([{"zone": f"z{i}", "fare": i} for i in range(1100)])
        e2 = freshness.epoch("rt")
        assert e2 > e1
        if seg.chunklet_index is not None:
            made = seg.chunklet_index.promote()
            assert made >= 1
            assert freshness.epoch("rt") > e2
        e3 = freshness.epoch("rt")
        seg.invalidate(0)
        assert freshness.epoch("rt") > e3
        e4 = freshness.epoch("rt")
        seg.seal(str(tmp_path / "sealed"))
        assert freshness.epoch("rt") > e4
        freshness.reset()


class TestClusterQpsSmoke:
    def test_three_server_replica_group_qps(self, cluster, tmp_path):
        """3 in-process servers over real gRPC, replica groups R=3 (one
        full copy each): concurrent traffic routes whole queries to single
        groups, spreads across all three, and answers correctly."""
        import threading

        registry, controller, servers, broker = cluster
        _offline_table(tmp_path, controller, n_segments=4, rows=1500,
                       replication=3)
        controller.setup_replica_groups("sales")
        assert wait_until(lambda: all(
            len(v) == 3
            for v in registry.external_view(TABLE_OFF).values()) and len(
            registry.external_view(TABLE_OFF)) == 4, timeout=30)
        expected = broker.execute(
            "SELECT region, COUNT(*) FROM sales GROUP BY region "
            "ORDER BY region")
        assert not expected["exceptions"]
        assert expected["numReplicaGroupsQueried"] == 1
        assert expected.get("loadScore") is not None
        rows = expected["resultTable"]["rows"]
        errors = []
        groups_seen = set()
        lock = threading.Lock()

        def worker():
            for _ in range(8):
                r = broker.execute(
                    "SELECT region, COUNT(*) FROM sales GROUP BY region "
                    "ORDER BY region")
                with lock:
                    if r.get("exceptions") or \
                            r["resultTable"]["rows"] != rows:
                        errors.append(r)
                    groups_seen.add(r.get("replicaGroup"))

        ts = [threading.Thread(target=worker) for _ in range(4)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert not errors, errors[:1]
        # ties share round-robin traffic: all three groups serve
        assert groups_seen == {"rg_0", "rg_1", "rg_2"}, groups_seen
