"""Broker per-table query quota (queryquota/ analog)."""

import time

import numpy as np

from pinot_tpu.broker.broker import Broker
from pinot_tpu.cluster.registry import ClusterRegistry
from pinot_tpu.common.datatypes import DataType
from pinot_tpu.common.schema import Schema
from pinot_tpu.common.table_config import QuotaConfig, TableConfig
from pinot_tpu.controller.controller import Controller
from pinot_tpu.server.server import ServerInstance
from pinot_tpu.storage.creator import build_segment


def wait_until(cond, timeout=15.0):
    t0 = time.time()
    while time.time() - t0 < timeout:
        if cond():
            return True
        time.sleep(0.05)
    return False


def test_quota_rejects_above_rate_and_refills(tmp_path):
    registry = ClusterRegistry()
    controller = Controller(registry, str(tmp_path / "ds"))
    server = ServerInstance("s0", registry, str(tmp_path / "sd"),
                            device_executor=None)
    server.start()
    broker = Broker(registry, timeout_s=10.0)
    try:
        schema = Schema.build(name="limited",
                              dimensions=[("k", DataType.STRING)],
                              metrics=[("v", DataType.LONG)])
        cfg = TableConfig(table_name="limited",
                          quota=QuotaConfig(max_queries_per_second=2))
        controller.add_table(cfg, schema)
        build_segment(schema, {"k": np.array(["a"]), "v": np.array([1])},
                      str(tmp_path / "up"), cfg, "s0seg")
        controller.upload_segment("limited", str(tmp_path / "up"))
        assert wait_until(
            lambda: len(registry.external_view("limited_OFFLINE")) == 1)

        # the bucket's clock stands still: how long a query takes on this
        # machine must not refill a 2-a-second bucket between the three
        clock = [1_000.0]
        broker.quota._now = lambda: clock[0]
        sql = "SELECT COUNT(*) FROM limited"
        ok = [broker.execute(sql) for _ in range(2)]
        assert all(not r.get("exceptions") for r in ok), ok
        rejected = broker.execute(sql)
        assert rejected["exceptions"][0]["errorCode"] == 429

        clock[0] += 1.1  # bucket refills at 2 tokens/s
        again = broker.execute(sql)
        assert not again.get("exceptions"), again
    finally:
        broker.close()
        server.stop()


def test_typed_table_name_shares_bucket(tmp_path):
    """'limited' and 'limited_OFFLINE' draw from ONE bucket (r3 review:
    suffixing the name must not multiply the quota)."""
    registry = ClusterRegistry()
    controller = Controller(registry, str(tmp_path / "ds"))
    server = ServerInstance("s0", registry, str(tmp_path / "sd"),
                            device_executor=None)
    server.start()
    broker = Broker(registry, timeout_s=10.0)
    try:
        schema = Schema.build(name="limited",
                              dimensions=[("k", DataType.STRING)],
                              metrics=[("v", DataType.LONG)])
        cfg = TableConfig(table_name="limited",
                          quota=QuotaConfig(max_queries_per_second=2))
        controller.add_table(cfg, schema)
        build_segment(schema, {"k": np.array(["a"]), "v": np.array([1])},
                      str(tmp_path / "up"), cfg, "s0seg")
        controller.upload_segment("limited", str(tmp_path / "up"))
        assert wait_until(
            lambda: len(registry.external_view("limited_OFFLINE")) == 1)
        # a frozen clock: the third query is refused however long the
        # first two took (the bucket holds 2 and refills 2 a second)
        broker.quota._now = lambda: 1_000.0
        assert not broker.execute(
            "SELECT COUNT(*) FROM limited").get("exceptions")
        assert not broker.execute(
            "SELECT COUNT(*) FROM limited_OFFLINE").get("exceptions")
        r = broker.execute("SELECT COUNT(*) FROM limited_OFFLINE")
        assert r["exceptions"][0]["errorCode"] == 429
    finally:
        broker.close()
        server.stop()


def test_non_positive_quota_rejected_at_config():
    import pytest

    with pytest.raises(ValueError, match="positive"):
        TableConfig(table_name="t",
                    quota=QuotaConfig(max_queries_per_second=0))


def test_timeout_ms_query_option(tmp_path):
    """SET timeoutMs overrides the broker's per-query fan-out timeout
    (the reference's timeoutMs query option)."""
    registry = ClusterRegistry()
    controller = Controller(registry, str(tmp_path / "ds"))
    server = ServerInstance("s0", registry, str(tmp_path / "sd"),
                            device_executor=None)
    server.start()
    broker = Broker(registry, timeout_s=10.0)
    try:
        schema = Schema.build(name="t", dimensions=[("k", DataType.STRING)],
                              metrics=[("v", DataType.LONG)])
        cfg = TableConfig(table_name="t")
        controller.add_table(cfg, schema)
        build_segment(schema, {"k": np.array(["a"]), "v": np.array([1])},
                      str(tmp_path / "up"), cfg, "s0seg")
        controller.upload_segment("t", str(tmp_path / "up"))
        assert wait_until(
            lambda: len(registry.external_view("t_OFFLINE")) == 1)
        from pinot_tpu.transport.grpc_transport import QueryRouterChannel

        seen = []
        real_submit = QueryRouterChannel.submit

        def recording(self, payload, timeout):
            import json as _json

            seen.append((timeout, _json.loads(payload.decode())["timeoutMs"]))
            return real_submit(self, payload, timeout)

        QueryRouterChannel.submit = recording
        try:
            ok = broker.execute("SET timeoutMs = 2500; SELECT COUNT(*) FROM t")
            assert not ok.get("exceptions"), ok
            # deadline propagation: the wire carries the REMAINING budget
            # (<= the SET value; > 0 minus routing overhead) and the RPC
            # deadline is that budget plus a small grace so the server's
            # own typed QUERY_TIMEOUT answers first
            rpc_timeout, budget_ms = seen[-1]
            assert 2000.0 < budget_ms <= 2500.0, seen
            assert abs(rpc_timeout - (budget_ms / 1e3 + 0.25)) < 1e-6, seen
            ok = broker.execute("SELECT COUNT(*) FROM t")
            rpc_timeout, budget_ms = seen[-1]
            assert 9500.0 < budget_ms <= 10000.0  # broker default budget
        finally:
            QueryRouterChannel.submit = real_submit
    finally:
        broker.close()
        server.stop()


def test_no_quota_config_unlimited(tmp_path):
    registry = ClusterRegistry()
    controller = Controller(registry, str(tmp_path / "ds"))
    server = ServerInstance("s0", registry, str(tmp_path / "sd"),
                            device_executor=None)
    server.start()
    broker = Broker(registry, timeout_s=10.0)
    try:
        schema = Schema.build(name="free", dimensions=[("k", DataType.STRING)],
                              metrics=[("v", DataType.LONG)])
        cfg = TableConfig(table_name="free")
        controller.add_table(cfg, schema)
        build_segment(schema, {"k": np.array(["a"]), "v": np.array([1])},
                      str(tmp_path / "up"), cfg, "s0seg")
        controller.upload_segment("free", str(tmp_path / "up"))
        assert wait_until(
            lambda: len(registry.external_view("free_OFFLINE")) == 1)
        rs = [broker.execute("SELECT COUNT(*) FROM free") for _ in range(20)]
        assert all(not r.get("exceptions") for r in rs)
    finally:
        broker.close()
        server.stop()
