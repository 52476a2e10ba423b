"""Concurrent-submission parity + executor thread-safety (tier-1).

The async launch/fetch split (engine/inflight.py, DeviceExecutor.launch)
lets N queries overlap their host↔device round trips; these tests pin the
correctness half of that contract: N threads submitting a mixed query set
against one engine/server must produce results byte-identical to serial
submission — across the thread-safe executor caches, batch refcounting vs
LRU eviction, and coalesced vs solo launches.

Reference analog: a Pinot server's QueryExecutor serves many concurrent
scatter-gather requests over shared segment state; correctness under that
concurrency is assumed, here it is asserted.
"""

import threading
import time

import numpy as np
import pytest

from pinot_tpu.common.datatypes import DataType
from pinot_tpu.common.schema import Schema
from pinot_tpu.engine.engine import QueryEngine
from pinot_tpu.engine.scheduler import QueryScheduler, TokenBucketScheduler
from pinot_tpu.storage.creator import build_segment
from pinot_tpu.storage.segment import ImmutableSegment


def canonical(resp: dict) -> dict:
    """Response minus wall-clock/cache-state fields — everything else
    must be byte-identical across serial and concurrent submission
    (partialsCacheHit legitimately flips between a cold and a repeat
    execution of the same query)."""
    out = dict(resp)
    out.pop("timeUsedMs", None)
    out.pop("partialsCacheHit", None)
    # advisor stamps (ISSUE 17) are plan-state metadata: a repeat
    # execution of a trained template carries ADVISOR(...) lines the
    # cold run didn't — results stay bit-exact by construction
    out.pop("advisorDecisions", None)
    # roofline accounting (ISSUE 11) is measurement, not results: kernel
    # wall and modeled bytes differ run to run (cohort members attribute
    # the shared kernel to the leader; cache hits move zero bytes)
    for k in ("deviceBytesMoved", "deviceKernelMs", "deviceQueueMs",
              "deviceRunMs", "deviceLinkMs", "roofline"):
        out.pop(k, None)
    return out


def run_threads(n, target):
    """Run target(i) on n threads; re-raise the first failure."""
    errors = []

    def wrapped(i):
        try:
            target(i)
        except BaseException as e:  # noqa: BLE001 — surfaced after join
            errors.append(e)

    threads = [threading.Thread(target=wrapped, args=(i,)) for i in range(n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(60)
    hung = [t for t in threads if t.is_alive()]
    assert not hung, (
        f"{len(hung)} worker thread(s) hung past the join timeout "
        "(executor deadlock?)")
    if errors:
        raise errors[0]


@pytest.fixture(scope="module")
def tables(tmp_path_factory):
    """Two tables through ONE engine: 't' (dense group-by shapes) and 'hc'
    (global cartesian cardinality 2100×2100 > MAX_DENSE_GROUPS → the
    sorted/radix regime), so concurrent queries contend for the executor's
    batch LRU across regimes."""
    rng = np.random.default_rng(23)
    base = tmp_path_factory.mktemp("concseg")

    n = 4000
    cols_t = {
        "dim1": np.array([f"d{i:02d}" for i in range(40)])[
            rng.integers(0, 40, n)],
        "dim2": np.array(["a", "b", "c"])[rng.integers(0, 3, n)],
        "ivalue": rng.integers(0, 10_000, n).astype(np.int32),
        "fvalue": rng.uniform(0, 100, n).astype(np.float64),
    }
    schema_t = Schema.build(
        name="t",
        dimensions=[("dim1", DataType.STRING), ("dim2", DataType.STRING)],
        metrics=[("ivalue", DataType.INT), ("fvalue", DataType.DOUBLE)],
    )

    m = 4500
    hc1 = rng.integers(0, 2100, m).astype(np.int32)
    hc2 = rng.integers(0, 2100, m).astype(np.int32)
    # pin the GLOBAL dictionary cardinality at exactly 2100 per column
    # (2100^2 ≈ 4.41M > MAX_DENSE_GROUPS) so this really takes the sorted
    # regime regardless of random draws
    hc1[:2100] = np.arange(2100, dtype=np.int32)
    hc2[:2100] = np.arange(2100, dtype=np.int32)
    cols_hc = {
        "hc1": hc1,
        "hc2": hc2,
        "v": rng.integers(-100, 100, m).astype(np.int64),
    }
    schema_hc = Schema.build(
        name="hc",
        dimensions=[("hc1", DataType.INT), ("hc2", DataType.INT)],
        metrics=[("v", DataType.LONG)],
    )

    t_segs, hc_segs = [], []
    for i in range(3):
        sl_t = slice(i * (n // 3), (i + 1) * (n // 3) if i < 2 else n)
        build_segment(schema_t, {k: v[sl_t] for k, v in cols_t.items()},
                      str(base / f"t{i}"), segment_name=f"t{i}")
        t_segs.append(ImmutableSegment(str(base / f"t{i}")))
        sl_h = slice(i * (m // 3), (i + 1) * (m // 3) if i < 2 else m)
        build_segment(schema_hc, {k: v[sl_h] for k, v in cols_hc.items()},
                      str(base / f"hc{i}"), segment_name=f"hc{i}")
        hc_segs.append(ImmutableSegment(str(base / f"hc{i}")))
    return t_segs, hc_segs


def make_engine(t_segs, hc_segs):
    eng = QueryEngine()  # device executor auto
    for s in t_segs:
        eng.add_segment("t", s)
    for s in hc_segs:
        eng.add_segment("hc", s)
    return eng


MIXED_QUERIES = [
    # device scalar aggregation
    "SELECT COUNT(*), SUM(ivalue), MIN(ivalue), MAX(ivalue) FROM t",
    # device dense group-by (+ matmul-eligible sums)
    "SELECT dim1, COUNT(*), SUM(ivalue), AVG(fvalue) FROM t "
    "GROUP BY dim1 ORDER BY dim1 LIMIT 50",
    # filter templates with distinct literals (same compiled template)
    "SELECT COUNT(*) FROM t WHERE ivalue > 2000 AND dim2 = 'a'",
    "SELECT COUNT(*) FROM t WHERE ivalue > 7000 AND dim2 = 'c'",
    # sketchy shapes: presence + HLL
    "SELECT dim2, DISTINCTCOUNT(dim1) FROM t GROUP BY dim2 ORDER BY dim2",
    "SELECT DISTINCTCOUNTHLL(dim1) FROM t",
    # host fallback (percentile is host-only)
    "SELECT PERCENTILE(ivalue, 90) FROM t",
    # sorted/radix high-cardinality regime on the second table
    "SELECT hc1, hc2, COUNT(*), SUM(v) FROM hc GROUP BY hc1, hc2 "
    "ORDER BY COUNT(*) DESC, hc1, hc2 LIMIT 20",
]


class TestConcurrentSubmissionParity:
    def test_mixed_queries_match_serial(self, tables):
        """N threads × mixed query set == serial, byte-identical."""
        eng = make_engine(*tables)
        serial = {sql: canonical(eng.execute(sql)) for sql in MIXED_QUERIES}
        for sql, r in serial.items():
            assert not r.get("exceptions"), (sql, r)

        def worker(i):
            order = MIXED_QUERIES[i % len(MIXED_QUERIES):] + \
                MIXED_QUERIES[:i % len(MIXED_QUERIES)]
            for _ in range(2):
                for sql in order:
                    got = canonical(eng.execute(sql))
                    assert got == serial[sql], (sql, got, serial[sql])

        run_threads(6, worker)

    def test_parity_under_batch_eviction(self, tables):
        """MAX_CACHED_BATCHES=1 while two tables' queries interleave: every
        execute evicts the OTHER table's batch, so in-flight launches
        survive only through the refcount pin (_retain_launch vs _evict)."""
        eng = make_engine(*tables)
        dev = eng.device
        assert dev is not None
        dev.MAX_CACHED_BATCHES = 1  # instance override
        sql_t = "SELECT dim1, SUM(ivalue) FROM t GROUP BY dim1 ORDER BY dim1"
        sql_hc = ("SELECT hc1, COUNT(*) FROM hc GROUP BY hc1 "
                  "ORDER BY COUNT(*) DESC, hc1 LIMIT 10")
        want = {s: canonical(eng.execute(s)) for s in (sql_t, sql_hc)}

        def worker(i):
            mine = (sql_t, sql_hc) if i % 2 == 0 else (sql_hc, sql_t)
            for _ in range(3):
                for sql in mine:
                    assert canonical(eng.execute(sql)) == want[sql]

        run_threads(6, worker)
        # pins all drained: nothing left refcounted, LRU bound restored
        assert dev.inflight == 0
        assert not dev._inflight_launches
        assert len(dev._batches) <= 1

    def test_inflight_launch_pins_batch(self, tables):
        """A dispatched-but-unfetched launch keeps its batch out of LRU
        eviction; fetch() still answers correctly after churn, and the pin
        drains afterward."""
        from pinot_tpu.query.optimizer import optimize_query
        from pinot_tpu.sql.compiler import compile_query

        t_segs, hc_segs = tables
        eng = make_engine(t_segs, hc_segs)
        dev = eng.device
        dev.MAX_CACHED_BATCHES = 1
        sql = "SELECT dim2, COUNT(*), SUM(ivalue) FROM t GROUP BY dim2"
        expected = canonical(eng.execute(sql))
        q = optimize_query(compile_query(sql))
        q = eng._expand_star(q, t_segs[0])
        handle = dev.launch(q, t_segs)
        key = dev._batch_key(t_segs)
        assert dev._inflight_launches.get(key) == 1
        # churn the LRU past its cap with the other table's batch
        dev.batch_for(hc_segs)
        assert key in dev._batches, "in-flight batch was evicted"
        result = handle.fetch()
        assert int(result.stats.num_docs_scanned) > 0
        assert dev._inflight_launches.get(key) is None
        assert dev.inflight == 0
        # and the engine still answers identically afterward
        assert canonical(eng.execute(sql)) == expected


class TestLaunchCoalescing:
    COHORT_SQLS = [
        f"SELECT dim1, COUNT(*), SUM(ivalue) FROM t WHERE ivalue > {lit} "
        "GROUP BY dim1 ORDER BY SUM(ivalue) DESC, dim1 LIMIT 15"
        for lit in (100, 1500, 3000, 4500, 6000, 7500, 9000, 9900)
    ]

    def _cohort_run(self, eng):
        """Solo results first (idle executor ⇒ no windows), then the same
        8 queries released together through a forced window."""
        expected = [canonical(eng.execute(s)) for s in self.COHORT_SQLS]
        # repeats of the warm pass would hit the device partials cache
        # and never reach the coalescer — this test pins cohorts
        eng.device.partials_cache_enabled = False
        co = eng.device.coalescer
        co.force = True
        co.window_s = 0.05
        co.max_cohort = 8
        c0 = (co.cohorts_launched, co.queries_coalesced)
        try:
            barrier = threading.Barrier(len(self.COHORT_SQLS))
            got = [None] * len(self.COHORT_SQLS)

            def worker(i):
                barrier.wait()
                got[i] = canonical(eng.execute(self.COHORT_SQLS[i]))

            run_threads(len(self.COHORT_SQLS), worker)
        finally:
            co.force = False
        for i, (g, e) in enumerate(zip(got, expected)):
            assert g == e, (self.COHORT_SQLS[i], g, e)
        assert co.cohorts_launched > c0[0]
        assert co.queries_coalesced > c0[1], \
            "no query actually joined a cohort"

    def test_cohort_matches_solo(self, tables):
        """A coalesced cohort's unpacked per-query outputs equal per-query
        solo launches (same template, different literals — the dashboard
        fan-out case)."""
        self._cohort_run(make_engine(*tables))

    def test_cohort_matches_solo_on_mesh(self, tables):
        """Same contract through shard_pipeline(cohort=True): the vmapped
        cohort composes with the 8-device mesh combine."""
        from pinot_tpu.engine.device import DeviceExecutor
        from pinot_tpu.parallel.mesh import make_mesh

        t_segs, hc_segs = tables
        eng = QueryEngine(device_executor=DeviceExecutor(mesh=make_mesh(8)))
        for s in t_segs:
            eng.add_segment("t", s)
        for s in hc_segs:
            eng.add_segment("hc", s)
        self._cohort_run(eng)

    def test_sketch_final_cohort(self, tables):
        """Terminal sketch queries (device finalize AFTER the combine)
        coalesce correctly too: _finalize_sketch_outs runs per member
        under the vmap — single-device and via shard_pipeline's ``post``
        hook on the mesh."""
        from pinot_tpu.engine.device import DeviceExecutor
        from pinot_tpu.parallel.mesh import make_mesh

        t_segs, _ = tables
        sqls = [
            f"SELECT dim2, DISTINCTCOUNT(dim1), DISTINCTCOUNTHLL(dim1) "
            f"FROM t WHERE ivalue > {lit} GROUP BY dim2 ORDER BY dim2"
            for lit in (100, 3000, 6000, 9000)
        ]
        for mesh in (None, make_mesh(8)):
            eng = QueryEngine(device_executor=DeviceExecutor(mesh=mesh))
            for s in t_segs:
                eng.add_segment("t", s)
            expected = [canonical(eng.execute(s)) for s in sqls]
            eng.device.partials_cache_enabled = False  # pin cohorts, not hits
            co = eng.device.coalescer
            co.force = True
            co.window_s = 0.05
            try:
                barrier = threading.Barrier(len(sqls))
                got = [None] * len(sqls)

                def worker(i, _b=barrier, _g=got, _e=eng, _s=sqls):
                    _b.wait()
                    _g[i] = canonical(_e.execute(_s[i]))

                run_threads(len(sqls), worker)
            finally:
                co.force = False
            assert got == expected, ("mesh" if mesh else "single")

    @pytest.mark.parametrize("unfetched", [0, 1, 4])
    def test_idle_executor_skips_window(self, tables, unfetched):
        """No load opens a micro-batch window: with nothing, one or four
        launches of the template dispatched and unfetched
        (``executor.inflight`` that many), a query pays no window nor
        mints a cohort. Only ``force``, the tests' switch, opens one."""
        from pinot_tpu.query.optimizer import optimize_query
        from pinot_tpu.sql.compiler import compile_query

        t_segs, _ = tables
        eng = make_engine(*tables)
        dev = eng.device
        dev.partials_cache_enabled = False  # every request must launch
        co = dev.coalescer
        expected = canonical(eng.execute(self.COHORT_SQLS[0]))
        c0 = (co.cohorts_launched, co.queries_coalesced)
        held = []
        try:
            for sql in self.COHORT_SQLS[1:1 + unfetched]:
                q = eng._expand_star(optimize_query(compile_query(sql)),
                                     t_segs[0])
                held.append(dev.launch(q, t_segs))
            assert dev.inflight == unfetched
            assert co.should_window() is False
            assert canonical(eng.execute(self.COHORT_SQLS[0])) == expected
            assert (co.cohorts_launched, co.queries_coalesced) == c0
            co.force = True
            assert co.should_window() is True
        finally:
            co.force = False
            for handle in held:
                handle.fetch()
        assert dev.inflight == 0


class TestAbandonedLaunchRelease:
    def test_host_partial_failure_releases_pin(self, tables):
        """A host-segment failure between device launch and fetch must
        release the in-flight handle: otherwise the batch stays
        unevictable forever and executor.inflight never drains."""
        from pinot_tpu.query.optimizer import optimize_query
        from pinot_tpu.sql.compiler import compile_query

        t_segs, _ = tables
        eng = make_engine(*tables)
        dev = eng.device
        # an upsert-masked segment forces a host partial alongside the
        # device batch; a poisoned host executor then fails the launch
        # phase AFTER the device dispatch succeeded
        class _Boom(Exception):
            pass

        def boom(q, s):
            raise _Boom()

        orig = eng.host.execute_segment
        eng.host.execute_segment = boom
        bad = t_segs[0]
        try:
            bad.valid_docs_mask = np.ones(bad.n_docs, dtype=bool)
            q = optimize_query(compile_query(
                "SELECT dim2, COUNT(*) FROM t GROUP BY dim2"))
            with pytest.raises(_Boom):
                eng.execute_query(q)
        finally:
            bad.valid_docs_mask = None
            eng.host.execute_segment = orig
        assert dev.inflight == 0, "abandoned launch leaked the pin"
        assert not dev._inflight_launches
        # and the engine recovers fully
        r = eng.execute("SELECT dim2, COUNT(*) FROM t GROUP BY dim2 "
                        "ORDER BY dim2")
        assert not r.get("exceptions"), r


class TestFetchTimeFallbackGate:
    def test_overflow_fallback_routes_through_gate(self, tables):
        """Sorted group-table overflow detected at FETCH time re-runs on
        the host THROUGH the caller's admission gate (the fetch phase is
        slot-free by design; the heavy host scan must not be)."""
        t_segs, hc_segs = tables
        eng = QueryEngine(num_groups_limit=50)  # 4500 distinct ⇒ overflow
        host_eng = QueryEngine(device_executor=None, num_groups_limit=50)
        for e in (eng, host_eng):
            for s in hc_segs:
                e.add_segment("hc", s)
        from pinot_tpu.query.optimizer import optimize_query
        from pinot_tpu.sql.compiler import compile_query

        sql = ("SELECT hc1, hc2, COUNT(*), SUM(v) FROM hc "
               "GROUP BY hc1, hc2 ORDER BY COUNT(*) DESC, hc1, hc2 LIMIT 5")
        q = optimize_query(compile_query(sql))
        gated = []

        def gate(fn):
            gated.append(1)
            return fn()

        fetch = eng.execute_segments_async(q, hc_segs, terminal=True,
                                           fallback_gate=gate)
        merged = fetch()
        assert gated, "host fallback bypassed the admission gate"
        want = host_eng.execute_segments(q, hc_segs, terminal=True)
        assert merged.stats.num_groups_limit_reached \
            == want.stats.num_groups_limit_reached
        assert canonical(eng.execute(sql)) == canonical(host_eng.execute(sql))


class TestObservabilityCounters:
    def test_counters_consistent_under_parallel_executes(self, tables):
        """CI guard: fetch_bytes_total / fetch_leaves_total and the device
        timeline's stamps stay consistent under parallel executes — every
        request its own launch, K device queries of one shape account
        exactly K× the solo deltas (repeats of one statement: the partials
        cache answers them), and with the cache off each of K launches has
        its end stamped once, in dispatch order."""
        eng = make_engine(*tables)
        dev = eng.device
        sql = "SELECT dim1, COUNT(*), SUM(ivalue) FROM t GROUP BY dim1"
        eng.execute(sql)  # warm: compile + batch caches
        b0, l0 = dev.fetch_bytes_total, dev.fetch_leaves_total
        eng.execute(sql)
        per_bytes = dev.fetch_bytes_total - b0
        per_leaves = dev.fetch_leaves_total - l0
        assert per_bytes > 0 and 1 <= per_leaves <= 2

        b1, l1 = dev.fetch_bytes_total, dev.fetch_leaves_total
        run_threads(4, lambda i: [eng.execute(sql) for _ in range(5)])
        assert dev.fetch_bytes_total - b1 == 20 * per_bytes
        assert dev.fetch_leaves_total - l1 == 20 * per_leaves

        timeline = dev.device_timeline
        e1 = timeline.ended
        launches = []
        real = timeline.dispatched

        def dispatched(*a, **kw):
            launch = real(*a, **kw)
            launches.append(launch)
            return launch

        timeline.dispatched = dispatched
        dev.partials_cache_enabled = False
        try:
            run_threads(4, lambda i: [eng.execute(sql) for _ in range(5)])
        finally:
            del timeline.dispatched
            dev.partials_cache_enabled = True
        assert timeline.ended - e1 == len(launches) == 20
        for launch in launches:
            assert launch.t_end >= launch.t_dispatched
            assert launch.queue_s >= 0 and launch.run_s >= 0
            assert 0 <= launch.ahead < 20
        ends = sorted(launch.t_end for launch in launches)
        assert sum(launch.run_s for launch in launches) \
            <= ends[-1] - min(x.t_dispatched for x in launches) + 1e-9


class TestSchedulerPressure:
    def test_fcfs_pressure_counts_running(self):
        sched = QueryScheduler(max_concurrent=2, max_queued=8)
        assert sched.pressure() == 0
        seen = sched.run(lambda: sched.pressure())
        assert seen == 1
        assert sched.pressure() == 0

    def test_tokenbucket_pressure_counts_running_and_waiting(self):
        sched = TokenBucketScheduler(max_concurrent=1, max_queued=8)
        release = threading.Event()
        inner_pressure = []

        def blocker():
            sched.run(lambda: (inner_pressure.append(sched.pressure()),
                               release.wait(5)))

        t = threading.Thread(target=blocker)
        t.start()
        for _ in range(100):
            if inner_pressure:
                break
            time.sleep(0.01)
        waiter = threading.Thread(
            target=lambda: sched.run(lambda: None, queue_timeout_s=5))
        waiter.start()
        for _ in range(100):
            if sched.pressure() >= 2:
                break
            time.sleep(0.01)
        assert sched.pressure() >= 2  # one running + one queued
        release.set()
        t.join(5)
        waiter.join(5)
        assert sched.pressure() == 0


class TestServerConcurrentSubmission:
    def test_server_parity_and_compile_bound(self, tables, tmp_path):
        """End-to-end: N threads through a real ServerInstance (gRPC
        handler path: compile semaphore → scheduler slot for the launch
        phase → slot-free fetch) answer byte-identically to serial, and
        the compileQueueMs timer records every compile."""
        from pinot_tpu.cluster.registry import ClusterRegistry
        from pinot_tpu.server.server import ServerInstance
        from pinot_tpu.transport.grpc_transport import make_instance_request

        t_segs, _ = tables
        registry = ClusterRegistry()
        server = ServerInstance("s0", registry, str(tmp_path / "sd"),
                                max_concurrent_queries=4)
        for s in t_segs:
            server.engine.add_segment("t", s)
        seg_names = [s.name for s in t_segs]
        try:
            from pinot_tpu.engine.datatable import decode

            sqls = [
                "SELECT dim1, COUNT(*), SUM(ivalue) FROM t GROUP BY dim1 "
                "ORDER BY dim1 LIMIT 50",
                "SELECT COUNT(*) FROM t WHERE dim2 = 'b'",
                "SELECT PERCENTILE(ivalue, 50) FROM t",
            ]

            def submit(sql, rid):
                payload = server._handle_submit(
                    make_instance_request(sql, seg_names, rid))
                res = decode(payload)
                # scheduler wait + cpu accounting are load-dependent
                res.stats.scheduler_wait_ms = 0.0
                res.stats.thread_cpu_time_ns = 0
                return res

            serial = {sql: submit(sql, i) for i, sql in enumerate(sqls)}

            def worker(i):
                for j, sql in enumerate(sqls):
                    got = submit(sql, 100 + i * 10 + j)
                    want = serial[sql]
                    assert got.shape == want.shape
                    assert str(got.agg_partials) == str(want.agg_partials)
                    assert got.stats.num_docs_scanned == \
                        want.stats.num_docs_scanned

            run_threads(6, worker)
            snap = server.metrics.snapshot()
            timer = snap["timers"].get("server.compileQueueMs")
            assert timer is not None and \
                timer["count"] >= len(sqls) * 7  # serial + 6 threads
        finally:
            # the server was never start()ed (its sync loop would unload
            # the directly-injected segments); drop just its gauges so the
            # process-global registry doesn't pin this instance
            server.metrics.remove_gauge("segmentsLoaded", tag="s0")
            server.metrics.remove_gauge("schedulerRejected", tag="s0")
