"""Sub-RTT serving tests (ISSUE 9).

1. ON-DEVICE FINAL REDUCE (ops/device_reduce.py): the in-kernel ORDER BY
   trim must be bit-identical to the host reduce across dense + sorted
   regimes, solo + 8-dev mesh, sealed + consuming(chunklet), asc/desc,
   group-column and aggregation order keys — and must NOT engage for the
   shapes whose reduce needs the full table (HAVING, post-aggregation
   order expressions, numGroupsLimit pressure → host fallback).
2. DEVICE PARTIALS CACHE: repeat executions hit (flagged in the
   response), literal changes miss, and every invalidation edge —
   chunklet promotion, upsert-mask change, seal, batch-LRU eviction
   churn, entry-cap churn — stays bit-identical to a cold cache.
3. A LAUNCH WAITS FOR NO OTHER (PR 35): with a predecessor's launch
   dispatched and unfetched, the next request of the template dispatches
   a program of its own and answers; handles released unfetched drain
   the executor's in-flight count.
"""

import threading

import numpy as np
import pytest

from pinot_tpu.common.datatypes import DataType
from pinot_tpu.common.schema import Schema
from pinot_tpu.common.table_config import ChunkletConfig, TableConfig
from pinot_tpu.engine.engine import QueryEngine
from pinot_tpu.storage.creator import build_segment
from pinot_tpu.storage.mutable import MutableSegment
from pinot_tpu.storage.segment import ImmutableSegment

N = 9000
N_ZONES = 120


def _data(n=N, seed=7):
    rng = np.random.default_rng(seed)
    return {
        "zone": np.array([f"z{i:03d}" for i in range(N_ZONES)])[
            rng.integers(0, N_ZONES, n)],
        "hour": rng.integers(0, 24, n).astype(np.int32),
        "fare": rng.integers(1, 10_000, n).astype(np.int64),
    }


def _schema(name="t"):
    return Schema.build(
        name=name,
        dimensions=[("zone", DataType.STRING)],
        metrics=[("hour", DataType.INT), ("fare", DataType.LONG)])


@pytest.fixture(scope="module")
def segs(tmp_path_factory):
    base = tmp_path_factory.mktemp("subrtt")
    data = _data()
    cfg = TableConfig(table_name="t")
    out = []
    for i in range(3):
        sl = slice(i * N // 3, (i + 1) * N // 3)
        build_segment(_schema(), {k: v[sl] for k, v in data.items()},
                      str(base / f"s{i}"), cfg, f"s{i}")
        out.append(ImmutableSegment(str(base / f"s{i}")))
    return out


def make_engine(segs, device="auto"):
    eng = QueryEngine(device_executor=device)
    for s in segs:
        eng.add_segment("t", s)
    return eng


@pytest.fixture(scope="module")
def engines(segs):
    return make_engine(segs), make_engine(segs, device=None)


def rows_of(eng, sql):
    r = eng.execute(sql)
    assert not r.get("exceptions"), (sql, r)
    return r["resultTable"]["rows"]


TRIMMED_QUERIES = [
    # aggregation order keys, asc + desc, with group-col tiebreaks
    "SELECT zone, COUNT(*) FROM t GROUP BY zone "
    "ORDER BY COUNT(*) DESC LIMIT 10",
    "SELECT zone, SUM(fare) FROM t GROUP BY zone "
    "ORDER BY SUM(fare) DESC, zone LIMIT 5",
    "SELECT zone, SUM(fare) FROM t GROUP BY zone "
    "ORDER BY SUM(fare), zone DESC LIMIT 5",
    "SELECT zone, AVG(fare) FROM t WHERE hour < 12 GROUP BY zone "
    "ORDER BY AVG(fare) LIMIT 7",
    "SELECT zone, MIN(fare), MAX(fare) FROM t GROUP BY zone "
    "ORDER BY MIN(fare), zone LIMIT 6",
    "SELECT zone, MINMAXRANGE(fare) FROM t GROUP BY zone "
    "ORDER BY MINMAXRANGE(fare) DESC, zone LIMIT 4",
    # group-column order keys
    "SELECT zone, COUNT(*) FROM t GROUP BY zone ORDER BY zone LIMIT 9",
    "SELECT zone, COUNT(*) FROM t GROUP BY zone ORDER BY zone DESC LIMIT 9",
    # no ORDER BY: terminal truncation in group order
    "SELECT zone, COUNT(*), SUM(fare) FROM t GROUP BY zone LIMIT 12",
    # ORDER BY an agg that is NOT selected (aggregations() carries it)
    "SELECT zone FROM t GROUP BY zone ORDER BY SUM(fare) DESC LIMIT 8",
    # OFFSET pagination rides the keep bound
    "SELECT zone, COUNT(*) FROM t GROUP BY zone "
    "ORDER BY COUNT(*) DESC, zone LIMIT 10 OFFSET 5",
]


class TestDeviceReduceParity:
    @pytest.mark.parametrize("sql", TRIMMED_QUERIES)
    def test_trimmed_matches_host_and_untrimmed(self, engines, sql):
        dev, host = engines
        want = rows_of(host, sql)
        assert rows_of(dev, sql) == want
        off = "SET useDeviceReduce=false; SET usePartialsCache=false; " + sql
        assert rows_of(dev, off) == want

    def test_trim_actually_ran(self, segs):
        eng = make_engine(segs)
        d0 = eng.device.device_reduce_queries
        rows_of(eng, TRIMMED_QUERIES[0])
        assert eng.device.device_reduce_queries == d0 + 1
        # and the trimmed fetch moves fewer bytes than the full table
        b0 = eng.device.fetch_bytes_total
        rows_of(eng, "SET usePartialsCache=false; " + TRIMMED_QUERIES[1])
        trimmed = eng.device.fetch_bytes_total - b0
        b0 = eng.device.fetch_bytes_total
        rows_of(eng, "SET useDeviceReduce=false; SET usePartialsCache=false; "
                + TRIMMED_QUERIES[1])
        untrimmed = eng.device.fetch_bytes_total - b0
        assert 0 < trimmed < untrimmed

    def test_mesh_parity(self, segs, engines):
        from pinot_tpu.engine.device import DeviceExecutor
        from pinot_tpu.parallel.mesh import make_mesh

        _, host = engines
        eng = QueryEngine(device_executor=DeviceExecutor(mesh=make_mesh(8)))
        for s in segs:
            eng.add_segment("t", s)
        for sql in TRIMMED_QUERIES[:4] + TRIMMED_QUERIES[8:9]:
            assert rows_of(eng, sql) == rows_of(host, sql), sql

    def test_sorted_regime_topk(self, tmp_path):
        """High-cardinality (radix) regime: the trim consumes the keyed
        merged table (skeys), solo and on the mesh."""
        from pinot_tpu.engine.device import DeviceExecutor
        from pinot_tpu.parallel.mesh import make_mesh

        rng = np.random.default_rng(3)
        n = 12000
        cols = {
            "a": np.array([f"a{i:04d}" for i in range(2500)])[
                rng.integers(0, 2500, n)],
            "b": np.array([f"b{i:04d}" for i in range(2500)])[
                rng.integers(0, 2500, n)],
            "v": rng.integers(1, 1000, n).astype(np.int64),
        }
        schema = Schema.build(
            name="hc", dimensions=[("a", DataType.STRING),
                                   ("b", DataType.STRING)],
            metrics=[("v", DataType.LONG)])
        build_segment(schema, cols, str(tmp_path / "s0"),
                      TableConfig(table_name="hc"), "s0")
        seg = ImmutableSegment(str(tmp_path / "s0"))
        host = QueryEngine(device_executor=None)
        solo = QueryEngine()
        mesh = QueryEngine(device_executor=DeviceExecutor(mesh=make_mesh(8)))
        for e in (host, solo, mesh):
            e.add_segment("hc", seg)
        sql = ("SELECT a, b, SUM(v) FROM hc GROUP BY a, b "
               "ORDER BY SUM(v) DESC, a, b LIMIT 8")
        want = rows_of(host, sql)
        assert rows_of(solo, sql) == want
        assert rows_of(mesh, sql) == want
        shapes = {t[0] for (t, *_rest) in solo.device._pipelines}
        assert "groupby_sorted" in shapes

    def test_consuming_chunklet_parity(self):
        cfg = TableConfig(
            table_name="rt",
            chunklets=ChunkletConfig(enabled=True, rows_per_chunklet=2048,
                                     device_min_rows=0))
        data = _data(n=7000, seed=11)
        rows = [{"zone": str(data["zone"][i]), "hour": int(data["hour"][i]),
                 "fare": int(data["fare"][i])} for i in range(7000)]
        seg = MutableSegment(_schema("rt"), "rt__0__0__0", cfg)
        seg.index_batch(rows)
        seg.chunklet_index.promote()
        dev = QueryEngine()
        host = QueryEngine(device_executor=None)
        dev.table("rt").add_segment(seg)
        host.table("rt").add_segment(seg)
        sql = ("SELECT zone, COUNT(*), SUM(fare) FROM rt GROUP BY zone "
               "ORDER BY SUM(fare) DESC, zone LIMIT 10")
        assert rows_of(dev, sql) == rows_of(host, sql)

    def test_having_and_post_agg_order_not_trimmed(self, engines, segs):
        """Shapes whose reduce needs every group must skip the trim and
        still match the host bit-for-bit."""
        dev, host = engines
        eng = make_engine(segs)  # fresh executor: clean counters
        for sql in (
            "SELECT zone, COUNT(*) FROM t GROUP BY zone "
            "HAVING COUNT(*) > 50 ORDER BY COUNT(*) DESC, zone LIMIT 10",
            "SELECT zone, SUM(fare) FROM t GROUP BY zone "
            "ORDER BY SUM(fare) / COUNT(*) DESC, zone LIMIT 10",
        ):
            assert rows_of(eng, sql) == rows_of(host, sql), sql
        assert eng.device.device_reduce_queries == 0

    def test_num_groups_limit_fallback(self, engines):
        """numGroupsLimit pressure makes the trimmed table unable to
        reproduce the host's present-order drop: the fetch falls back to
        the host path, results and flags stay identical."""
        dev, host = engines
        sql = ("SET numGroupsLimit=15; SELECT zone, COUNT(*) FROM t "
               "GROUP BY zone ORDER BY COUNT(*) DESC LIMIT 10")
        rd, rh = dev.execute(sql), host.execute(sql)
        assert not rd.get("exceptions") and not rh.get("exceptions")
        assert rd["resultTable"]["rows"] == rh["resultTable"]["rows"]
        assert rd["numGroupsLimitReached"] == rh["numGroupsLimitReached"]

    def test_server_partial_mode_sorted(self, tmp_path):
        """Non-terminal sole partial (server→broker): the in-kernel trim
        applies the trim_group_by keep bound, and the finalized answer
        matches the host server's."""
        from pinot_tpu.engine.reduce import finalize, trim_group_by
        from pinot_tpu.query.optimizer import optimize_query
        from pinot_tpu.sql.compiler import compile_query

        rng = np.random.default_rng(5)
        n = 10000
        cols = {
            "a": np.array([f"a{i:04d}" for i in range(2500)])[
                rng.integers(0, 2500, n)],
            "b": np.array([f"b{i:04d}" for i in range(2500)])[
                rng.integers(0, 2500, n)],
            "v": rng.integers(1, 1000, n).astype(np.int64),
        }
        schema = Schema.build(
            name="hc", dimensions=[("a", DataType.STRING),
                                   ("b", DataType.STRING)],
            metrics=[("v", DataType.LONG)])
        build_segment(schema, cols, str(tmp_path / "s0"),
                      TableConfig(table_name="hc"), "s0")
        seg = ImmutableSegment(str(tmp_path / "s0"))
        dev = QueryEngine()
        host = QueryEngine(device_executor=None)
        dev.add_segment("hc", seg)
        host.add_segment("hc", seg)
        q = optimize_query(compile_query(
            "SELECT a, b, SUM(v) FROM hc GROUP BY a, b "
            "ORDER BY SUM(v) DESC, a, b LIMIT 8"))
        got, want = [], []
        for eng, out in ((dev, got), (host, want)):
            tdm = eng.tables["hc"]
            acq = tdm.acquire()
            try:
                merged = eng.execute_segments(q, acq, terminal=False)
                merged = trim_group_by(q, merged)  # the server-side step
                out.append(finalize(q, merged).rows)
            finally:
                tdm.release(acq)
        assert got == want
        # the sorted table (100k slots) exceeds the 5000-row keep bound,
        # so the partial-mode trim genuinely engaged
        assert dev.device.device_reduce_queries >= 1


class TestPartialsCache:
    def test_repeat_hits_and_flag(self, segs):
        eng = make_engine(segs)
        d = eng.device
        sql = TRIMMED_QUERIES[0]
        r1 = eng.execute(sql)
        h0, m0 = d.partials_hits, d.partials_misses
        r2 = eng.execute(sql)
        assert d.partials_hits == h0 + 1
        assert r1["resultTable"]["rows"] == r2["resultTable"]["rows"]
        assert r1["partialsCacheHit"] is False
        assert r2["partialsCacheHit"] is True
        # a different literal is a different digest: miss, correct result
        r3 = eng.execute(
            "SELECT zone, AVG(fare) FROM t WHERE hour < 5 GROUP BY zone "
            "ORDER BY AVG(fare) LIMIT 7")
        assert d.partials_misses > m0
        assert r3["partialsCacheHit"] is False
        # SET usePartialsCache=false bypasses both lookup and insert
        h1, m1 = d.partials_hits, d.partials_misses
        eng.execute("SET usePartialsCache=false; " + sql)
        assert (d.partials_hits, d.partials_misses) == (h1, m1)

    def test_hbm_stats_and_bytes(self, segs):
        eng = make_engine(segs)
        rows_of(eng, TRIMMED_QUERIES[0])
        stats = eng.device.hbm_stats()
        assert stats["partials_cache_entries"] == 1
        assert stats["partials_cache_bytes"] > 0
        assert stats["device_reduce_queries"] == 1
        assert stats["device_reduce_ms"] >= 0

    def test_entry_cap_eviction_churn(self, segs, engines):
        _, host = engines
        eng = make_engine(segs)
        d = eng.device
        d.MAX_CACHED_PARTIALS = 1
        sqls = [f"SELECT SUM(fare) FROM t WHERE hour < {h}"
                for h in (3, 9, 15)]
        want = [rows_of(host, s) for s in sqls]
        for _round in range(3):
            for s, w in zip(sqls, want):
                assert rows_of(eng, s) == w
        assert d.partials_evictions > 0
        assert len(d._partials) <= 1
        assert d.partials_bytes >= 0

    def test_batch_eviction_drops_entries(self, segs, tmp_path, engines):
        """MAX_CACHED_BATCHES=1 churn: alternating tables evict batches;
        their cached partials die with them and every result stays
        bit-identical to a cold cache (the host oracle)."""
        _, host = engines
        data2 = _data(n=4000, seed=23)
        build_segment(_schema("t2"), data2, str(tmp_path / "u0"),
                      TableConfig(table_name="t2"), "u0")
        seg2 = ImmutableSegment(str(tmp_path / "u0"))
        host2 = QueryEngine(device_executor=None)
        host2.add_segment("t2", seg2)
        eng = make_engine(segs)
        eng.add_segment("t2", seg2)
        eng.device.MAX_CACHED_BATCHES = 1
        q1 = TRIMMED_QUERIES[1]
        q2 = ("SELECT zone, COUNT(*) FROM t2 GROUP BY zone "
              "ORDER BY COUNT(*) DESC, zone LIMIT 6")
        w1, w2 = rows_of(host, q1), rows_of(host2, q2)
        for _round in range(3):
            assert rows_of(eng, q1) == w1
            assert rows_of(eng, q2) == w2
        assert eng.device.batch_evictions > 0
        # entries for evicted batches are gone: at most the live batch's
        assert all(k[1] in eng.device._batches
                   for k in eng.device._partials)

    def _consuming(self, rows_per=1024, n=5000, seed=29, upsert=False):
        cfg = TableConfig(
            table_name="rt",
            chunklets=ChunkletConfig(enabled=True,
                                     rows_per_chunklet=rows_per,
                                     device_min_rows=0))
        data = _data(n=n, seed=seed)
        rows = [{"zone": str(data["zone"][i]), "hour": int(data["hour"][i]),
                 "fare": int(data["fare"][i])} for i in range(n)]
        seg = MutableSegment(_schema("rt"), "rt__0__0__0", cfg,
                             enable_upsert=upsert)
        seg.index_batch(rows)
        seg.chunklet_index.promote()
        dev = QueryEngine()
        host = QueryEngine(device_executor=None)
        dev.table("rt").add_segment(seg)
        host.table("rt").add_segment(seg)
        return seg, rows, dev, host

    RT_SQL = ("SELECT zone, COUNT(*), SUM(fare) FROM rt GROUP BY zone "
              "ORDER BY SUM(fare) DESC, zone LIMIT 10")

    def test_promotion_invalidation(self):
        seg, rows, dev, host = self._consuming()
        assert rows_of(dev, self.RT_SQL) == rows_of(host, self.RT_SQL)
        assert dev.execute(self.RT_SQL)["partialsCacheHit"] is True
        # more rows + promotion: the chunklet set changes; the repeat
        # query must see the new rows, never a stale cached buffer
        extra = [{"zone": "z000", "hour": 1, "fare": 9999}] * 2100
        seg.index_batch(extra)
        seg.chunklet_index.promote()
        r = dev.execute(self.RT_SQL)
        assert r["partialsCacheHit"] is False
        assert r["resultTable"]["rows"] == rows_of(host, self.RT_SQL)

    def test_upsert_invalidation(self):
        seg, rows, dev, host = self._consuming(upsert=True)
        assert rows_of(dev, self.RT_SQL) == rows_of(host, self.RT_SQL)
        assert dev.execute(self.RT_SQL)["partialsCacheHit"] is True
        # an upsert invalidation INSIDE a promoted block dirties the
        # chunklet: the device batch re-forms without it, the cached
        # entry cannot serve, results match the masked host scan
        seg.invalidate(10)
        r = dev.execute(self.RT_SQL)
        assert r["partialsCacheHit"] is False
        assert r["resultTable"]["rows"] == rows_of(host, self.RT_SQL)

    def test_seal_invalidation(self, tmp_path):
        seg, rows, dev, host = self._consuming(seed=31)
        rows_of(dev, self.RT_SQL)
        pref = f"<chunklet:{seg.segment_name}:"
        assert any(any(pref in d for d in k[1])
                   for k in dev.device._partials)
        seg.seal(str(tmp_path / "sealed"))
        assert not any(any(pref in d for d in k[1])
                       for k in dev.device._partials)

    def test_invalidate_partials_direct(self, segs):
        from pinot_tpu.engine.device import invalidate_cached_partials

        eng = make_engine(segs)
        rows_of(eng, TRIMMED_QUERIES[0])
        assert len(eng.device._partials) == 1
        invalidate_cached_partials(segs[0].dir)
        assert len(eng.device._partials) == 0
        assert eng.device.partials_bytes == 0


def _compiled(eng, segs, sql):
    from pinot_tpu.query.optimizer import optimize_query
    from pinot_tpu.sql.compiler import compile_query

    return eng._expand_star(optimize_query(compile_query(sql)), segs[0])


class TestLaunchWaitsForNoOther:
    SQL = ("SELECT zone, COUNT(*), SUM(fare) FROM t WHERE hour < {} "
           "GROUP BY zone ORDER BY zone LIMIT 200")

    def test_answers_with_the_predecessor_unfetched(self, segs):
        """A predecessor of the same template and batch is dispatched and
        NOT fetched: the next request dispatches at once, as a launch of
        its own, and answers while the predecessor is still out."""
        eng, host = make_engine(segs), make_engine(segs, device=None)
        dev = eng.device
        dev.partials_cache_enabled = False  # every request must launch
        rows_of(eng, self.SQL.format(3))    # builds the program
        co = dev.coalescer
        windows = (co.cohorts_launched, co.queries_coalesced)
        first = dev.launch(_compiled(eng, segs, self.SQL.format(5)),
                           list(segs))
        try:
            assert dev.inflight == 1
            answered = threading.Event()
            got = []

            def second():
                got.append(rows_of(eng, self.SQL.format(9)))
                answered.set()

            t = threading.Thread(target=second, daemon=True)
            t.start()
            assert answered.wait(120), \
                "the second request waited for the first one's fetch"
            t.join(10)
            # ... which is still out: nothing but the test fetches it
            assert dev.inflight == 1 and not first._done
            assert got[0] == rows_of(host, self.SQL.format(9))
            assert (co.cohorts_launched, co.queries_coalesced) == windows
        finally:
            first.fetch()
        assert dev.inflight == 0 and not dev._inflight_launches

    def test_a_forced_cohort_released_unfetched_drains(self, segs):
        """Members that release() without fetching (deadline expiry,
        upstream failure) leave no pin and no in-flight count behind,
        the whole cohort of them included."""
        eng = make_engine(segs)
        dev = eng.device
        dev.partials_cache_enabled = False  # handles must reach the cohort
        rows_of(eng, self.SQL.format(3))
        co = dev.coalescer
        launched = co.cohorts_launched
        co.force, co.window_s = True, 0.2
        try:
            barrier = threading.Barrier(2)

            def abandon(i):
                q = _compiled(eng, segs, self.SQL.format(5 + i))
                barrier.wait(10)
                dev.launch(q, list(segs)).release()  # never fetched

            threads = [threading.Thread(target=abandon, args=(i,))
                       for i in range(2)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(120)
        finally:
            co.force, co.window_s = False, 0.003
        assert co.cohorts_launched > launched
        assert dev.inflight == 0 and not dev._inflight_launches
        # and the executor still answers
        assert rows_of(eng, self.SQL.format(7)) == rows_of(
            make_engine(segs, device=None), self.SQL.format(7))


class TestExplainAndLog:
    def test_explain_lines(self, engines):
        dev, _ = engines
        r = dev.execute("EXPLAIN PLAN FOR " + TRIMMED_QUERIES[0])
        ops = [row[0] for row in r["resultTable"]["rows"]]
        assert any(op.strip().startswith("DEVICE_REDUCE(trim=10")
                   for op in ops), ops
        assert any(op.strip().startswith("CACHED_PARTIALS(")
                   for op in ops), ops
        # HAVING: no trim line
        r2 = dev.execute(
            "EXPLAIN PLAN FOR SELECT zone, COUNT(*) FROM t GROUP BY zone "
            "HAVING COUNT(*) > 2 ORDER BY COUNT(*) DESC LIMIT 10")
        ops2 = [row[0] for row in r2["resultTable"]["rows"]]
        assert not any("DEVICE_REDUCE" in op for op in ops2), ops2

    def test_querylog_per_template_hit_rate(self):
        from pinot_tpu.tools.querylog import summarize

        entries = [
            {"template": "T1", "timeUsedMs": 5.0,
             "counters": {"partialsCacheHit": True}},
            {"template": "T1", "timeUsedMs": 9.0,
             "counters": {"partialsCacheHit": False}},
            {"template": "T2", "timeUsedMs": 4.0,
             "counters": {"partialsCacheHit": True}},
        ]
        s = summarize(entries, per_template=True)
        assert s["templates"]["T1"]["cacheHitRate"] == 0.5
        assert s["templates"]["T2"]["cacheHitRate"] == 1.0
