"""Token-bucket priority scheduler: fairness, accounting, selection.

Reference analogs: tokenbucket/TokenPriorityScheduler.java:1,
MultiLevelPriorityQueue, resources/BoundedAccountingExecutor — a heavy
tenant drains its token bucket and yields slots to light tenants instead
of starving them.
"""

import threading
import time

import numpy as np
import pytest

from pinot_tpu.engine.scheduler import (
    QueryScheduler,
    SchedulerSaturated,
    TokenBucketScheduler,
    make_scheduler,
)


def _percentile(xs, p):
    return float(np.percentile(np.asarray(xs), p))


def _light_waits(sched, n=30, work_s=0.004):
    """Submit light-tenant queries at a steady trickle, one at a time
    (closed loop), returning how long the SCHEDULER held each before it
    ran (its own ``scheduler_wait_ms``): the query's sleep and the
    trickle's are the test's, and overshoot on a busy machine."""
    waits = []
    for _ in range(n):
        out = {}
        sched.run(lambda: time.sleep(work_s), group="light", stats_out=out)
        waits.append(out["scheduler_wait_ms"] / 1e3)
        time.sleep(0.002)
    return waits


class TestTokenBucketFairness:
    def test_heavy_tenant_cannot_starve_light(self):
        """VERDICT round-3 acceptance: heavy tenant at saturation QPS must
        not push the light tenant's p99 wait for a slot past 2x its solo
        p99 (+ a fixed 5ms scheduling epsilon for CI jitter; a heavy job
        holds a slot for 50)."""
        def solo_sched():
            return TokenBucketScheduler(
                max_concurrent=2, max_queued=64,
                rate_ms_per_s=50.0, burst_ms=100.0)

        solo = _light_waits(solo_sched())
        solo_p99 = _percentile(solo, 99)

        sched = solo_sched()
        stop = threading.Event()

        def heavy_loop():
            while not stop.is_set():
                try:
                    sched.run(lambda: time.sleep(0.05), group="heavy",
                              queue_timeout_s=0.5)
                except SchedulerSaturated:
                    pass

        threads = [threading.Thread(target=heavy_loop, daemon=True)
                   for _ in range(8)]
        for t in threads:
            t.start()
        try:
            # the heavy tenant overdraws its bucket (burst 100 ms, 50 a job)
            deadline = time.monotonic() + 10
            while sched.group_stats().get("heavy", {}).get(
                    "tokens_ms", 0.0) >= 0 and time.monotonic() < deadline:
                time.sleep(0.01)
            contended = _light_waits(sched)
        finally:
            stop.set()
            for t in threads:
                t.join(2)
        contended_p99 = _percentile(contended, 99)
        assert contended_p99 <= 2 * solo_p99 + 0.005, (
            f"light p99 {contended_p99 * 1e3:.1f}ms vs solo "
            f"{solo_p99 * 1e3:.1f}ms — heavy tenant starved the light one")
        # and the heavy tenant is overdrawn while light stays solvent
        gs = sched.group_stats()
        assert gs["heavy"]["executed"] > 0
        assert gs["heavy"]["tokens_ms"] < gs["light"]["tokens_ms"]

    def test_fifo_within_group(self):
        sched = TokenBucketScheduler(max_concurrent=1, max_queued=16)
        order = []
        hold = threading.Event()
        t0 = threading.Thread(
            target=lambda: sched.run(lambda: hold.wait(2), group="g"))
        t0.start()
        time.sleep(0.05)
        threads = []
        for i in range(4):
            th = threading.Thread(
                target=lambda i=i: sched.run(
                    lambda: order.append(i), group="g"))
            th.start()
            time.sleep(0.02)  # deterministic arrival order
            threads.append(th)
        hold.set()
        for th in threads:
            th.join(3)
        assert order == [0, 1, 2, 3]


class TestAccountingAndSelection:
    def test_stats_out_accounting(self):
        def busy():
            t = time.thread_time()
            while time.thread_time() - t < 0.01:
                pass
            return 42

        # both schedulers publish the wait BEFORE fn runs (so fn can fold
        # it into the response it serializes)
        for sched in (QueryScheduler(), TokenBucketScheduler()):
            acct = {}
            assert sched.run(busy, stats_out=acct, group="t1") == 42
            assert acct["scheduler_wait_ms"] >= 0
        # the token bucket additionally reports CPU post-fn (it needs the
        # measurement for group accounting anyway)
        assert acct["thread_cpu_time_ns"] >= 5_000_000

    def test_group_stats_snapshot(self):
        sched = TokenBucketScheduler(rate_ms_per_s=100, burst_ms=200)
        sched.run(lambda: time.sleep(0.01), group="tableA")
        sched.run(lambda: None, group="tableB")
        gs = sched.group_stats()
        assert gs["tableA"]["executed"] == 1
        assert gs["tableB"]["executed"] == 1
        assert gs["tableA"]["wall_ms_total"] >= 10
        assert gs["tableA"]["tokens_ms"] < gs["tableB"]["tokens_ms"]

    def test_queue_cap_rejects(self):
        sched = TokenBucketScheduler(max_concurrent=1, max_queued=1,
                                     queue_timeout_s=0.05)
        hold = threading.Event()
        t = threading.Thread(
            target=lambda: sched.run(lambda: hold.wait(2), group="g"))
        t.start()
        time.sleep(0.05)
        waiter = threading.Thread(target=lambda: _swallow(
            lambda: sched.run(lambda: None, group="g", queue_timeout_s=2)))
        waiter.start()
        time.sleep(0.05)
        with pytest.raises(SchedulerSaturated):
            sched.run(lambda: None, group="g")  # queue already full
        hold.set()
        t.join(2)
        waiter.join(3)
        assert sched.num_rejected >= 1

    def test_make_scheduler_selection(self):
        assert isinstance(make_scheduler("fcfs", 4, 8), QueryScheduler)
        assert isinstance(make_scheduler("tokenbucket", 4, 8),
                          TokenBucketScheduler)
        with pytest.raises(ValueError):
            make_scheduler("nope", 4, 8)


def _swallow(fn):
    try:
        fn()
    except SchedulerSaturated:
        pass


class TestServerIntegration:
    def test_server_ships_cpu_accounting(self, tmp_path):
        """threadCpuTimeNs + schedulerWaitMs flow server -> wire -> broker
        response (reference DataTable V3 metadata)."""
        from pinot_tpu.broker.broker import Broker
        from pinot_tpu.cluster.registry import ClusterRegistry
        from pinot_tpu.common.datatypes import DataType
        from pinot_tpu.common.schema import Schema
        from pinot_tpu.common.table_config import TableConfig
        from pinot_tpu.controller.controller import Controller
        from pinot_tpu.server.server import ServerInstance
        from pinot_tpu.storage.creator import build_segment

        registry = ClusterRegistry()
        controller = Controller(registry, str(tmp_path / "ds"))
        server = ServerInstance("s0", registry, str(tmp_path / "srv"),
                                device_executor=None,
                                scheduler_name="tokenbucket")
        server.start()
        broker = Broker(registry)
        try:
            schema = Schema.build(
                name="t", dimensions=[("k", DataType.STRING)],
                metrics=[("v", DataType.INT)])
            cfg = TableConfig(table_name="t")
            controller.add_table(cfg, schema)
            d = str(tmp_path / "seg")
            # enough rows that the query's CPU burst reliably crosses the
            # container clock's thread_time granularity (a 1000-row query
            # can finish inside one tick and report a flaky 0)
            n = 200_000
            build_segment(schema, {
                "k": np.array(["a", "b"] * (n // 2)),
                "v": np.arange(n, dtype=np.int32)}, d, cfg, "t_0")
            controller.upload_segment("t", d)
            deadline = time.time() + 10
            r = None
            while time.time() < deadline:
                r = broker.execute("SELECT k, SUM(v) FROM t GROUP BY k")
                if not r.get("exceptions") and r["threadCpuTimeNs"] > 0:
                    break
                time.sleep(0.1)
            assert not r.get("exceptions"), r
            assert r["threadCpuTimeNs"] > 0
            assert r["schedulerWaitMs"] >= 0
            from pinot_tpu.engine.scheduler import TokenBucketScheduler

            assert isinstance(server.scheduler, TokenBucketScheduler)
            # group = table as written in the SQL (TableBasedGroupMapper)
            assert "t" in server.scheduler.group_stats()
        finally:
            broker.close()
            server.stop()
