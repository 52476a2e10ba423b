"""Device-executor tests: parity vs host path, template cache behavior.

Reference analog: InnerSegment* vs InterSegment* query suites asserting the
same results through different operator paths.
"""

import numpy as np
import pytest

from pinot_tpu.common.datatypes import DataType
from pinot_tpu.common.schema import Schema
from pinot_tpu.common.table_config import IndexingConfig, TableConfig
from pinot_tpu.engine.engine import QueryEngine
from pinot_tpu.storage.creator import build_segment
from pinot_tpu.storage.segment import ImmutableSegment


@pytest.fixture(scope="module")
def engines(tmp_path_factory):
    rng = np.random.default_rng(11)
    n = 4000
    cols = {
        "dim1": np.array([f"d{i:02d}" for i in range(40)])[rng.integers(0, 40, n)],
        "dim2": np.array(["a", "b", "c"])[rng.integers(0, 3, n)],
        "ivalue": rng.integers(0, 10_000, n).astype(np.int32),
        "fvalue": rng.uniform(0, 100, n).astype(np.float64),
    }
    schema = Schema.build(
        name="t",
        dimensions=[("dim1", DataType.STRING), ("dim2", DataType.STRING)],
        metrics=[("ivalue", DataType.INT), ("fvalue", DataType.DOUBLE)],
    )
    cfg = TableConfig(table_name="t", indexing=IndexingConfig())
    base = tmp_path_factory.mktemp("devseg")
    dev = QueryEngine()               # device executor auto
    host = QueryEngine(device_executor=None)
    third = n // 3
    for i, sl in enumerate([slice(0, third), slice(third, 2 * third), slice(2 * third, n)]):
        part = {k: v[sl] for k, v in cols.items()}
        build_segment(schema, part, str(base / f"s{i}"), cfg, f"s{i}")
        seg = ImmutableSegment(str(base / f"s{i}"))
        dev.add_segment("t", seg)
        host.add_segment("t", seg)
    return dev, host, cols


PARITY_QUERIES = [
    "SELECT COUNT(*) FROM t",
    "SELECT SUM(ivalue), MIN(ivalue), MAX(ivalue), AVG(ivalue) FROM t",
    "SELECT SUM(fvalue) FROM t WHERE dim2 = 'a'",
    "SELECT COUNT(*) FROM t WHERE dim1 IN ('d01','d05','d39') AND ivalue > 5000",
    "SELECT COUNT(*) FROM t WHERE dim1 LIKE 'd1%' OR dim2 != 'b'",
    "SELECT MINMAXRANGE(ivalue) FROM t WHERE ivalue BETWEEN 100 AND 9000",
    "SELECT DISTINCTCOUNT(dim1) FROM t WHERE dim2 = 'c'",
    "SELECT dim2, COUNT(*), SUM(ivalue) FROM t GROUP BY dim2 ORDER BY dim2",
    "SELECT dim1, dim2, MAX(ivalue), AVG(fvalue) FROM t GROUP BY dim1, dim2 "
    "ORDER BY dim1, dim2 LIMIT 200",
    "SELECT dim1, SUM(ivalue) FROM t WHERE ivalue + 10 < 8000 GROUP BY dim1 "
    "ORDER BY SUM(ivalue) DESC, dim1 LIMIT 15",
    "SELECT dim2, DISTINCTCOUNT(dim1) FROM t GROUP BY dim2 ORDER BY dim2",
    "SELECT dim1, COUNT(*) FROM t GROUP BY dim1 HAVING COUNT(*) > 90 "
    "ORDER BY COUNT(*) DESC, dim1 LIMIT 20",
    "SELECT SUM(ivalue) / COUNT(*) FROM t WHERE dim2 = 'b'",
    "SELECT COUNT(*) FROM t WHERE ivalue = 3",
]


def _close(a, b):
    if a is None or b is None:
        return a is None and b is None
    if isinstance(a, str) or isinstance(b, str):
        return a == b
    return np.isclose(float(a), float(b), rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("sql", PARITY_QUERIES)
def test_device_host_parity(engines, sql):
    dev, host, _ = engines
    rd = dev.execute(sql)
    rh = host.execute(sql)
    assert not rd.get("exceptions"), rd
    assert not rh.get("exceptions"), rh
    rows_d = rd["resultTable"]["rows"]
    rows_h = rh["resultTable"]["rows"]
    assert len(rows_d) == len(rows_h), (rows_d[:5], rows_h[:5])
    for a, b in zip(rows_d, rows_h):
        assert all(_close(x, y) for x, y in zip(a, b)), (a, b)


def test_device_path_actually_used(engines):
    dev, _, _ = engines
    dev.execute("SELECT dim1, SUM(ivalue) FROM t GROUP BY dim1")
    assert dev.device is not None and len(dev.device._pipelines) > 0


def test_template_cache_reuse_across_literals(engines):
    dev, _, _ = engines
    dev.execute("SELECT COUNT(*) FROM t WHERE dim2 = 'a' AND ivalue > 100")
    n_templates = len(dev.device._pipelines)
    dev.execute("SELECT COUNT(*) FROM t WHERE dim2 = 'c' AND ivalue > 9000")
    assert len(dev.device._pipelines) == n_templates  # same compiled template


def test_hll_estimate_accuracy(engines):
    dev, host, cols = engines
    r = dev.execute("SELECT DISTINCTCOUNTHLL(dim1) FROM t")
    est = r["resultTable"]["rows"][0][0]
    true = len(np.unique(cols["dim1"]))
    assert abs(est - true) / true < 0.05

    # host/device registers must merge consistently (same canonical hash)
    rh = host.execute("SELECT DISTINCTCOUNTHLL(dim1) FROM t")
    assert rh["resultTable"]["rows"][0][0] == est


def test_host_fallback_for_unsupported(engines):
    dev, host, _ = engines
    # percentile is host-only; must still answer correctly
    rd = dev.execute("SELECT PERCENTILE(ivalue, 90) FROM t")
    rh = host.execute("SELECT PERCENTILE(ivalue, 90) FROM t")
    assert rd["resultTable"]["rows"] == rh["resultTable"]["rows"]


def test_large_value_sum_exact(tmp_path):
    """Regression: SUM over large int values must use the exact single-stage
    path (two-stage int32 blocks would overflow)."""
    from pinot_tpu.common.datatypes import DataType
    from pinot_tpu.common.schema import Schema
    from pinot_tpu.common.table_config import TableConfig

    big = np.full(600, 2**30, dtype=np.int64)
    keys = np.array(["a", "b"])[np.arange(600) % 2]
    schema = Schema.build(
        name="big", dimensions=[("k", DataType.STRING)], metrics=[("v", DataType.LONG)]
    )
    build_segment(schema, {"k": keys, "v": big}, str(tmp_path / "s0"),
                  TableConfig(table_name="big"), "s0")
    eng = QueryEngine()
    eng.add_segment("big", ImmutableSegment(str(tmp_path / "s0")))
    r = eng.execute("SELECT k, SUM(v) FROM big GROUP BY k ORDER BY k")
    assert len(eng.device._pipelines) > 0  # device path taken
    assert r["resultTable"]["rows"] == [["a", 300 * 2**30], ["b", 300 * 2**30]], r


@pytest.fixture(scope="module")
def mm_engine(engines, tmp_path_factory):
    """Device engine with the factored matmul group-by kernel forced on
    (Pallas interpret mode on the CPU test mesh)."""
    from pinot_tpu.engine.device import DeviceExecutor

    dev, _, _ = engines
    eng = QueryEngine(device_executor=DeviceExecutor(mm_mode="interpret"))
    for seg in dev.tables["t"].segments.values():
        eng.add_segment("t", seg)
    return eng


MM_QUERIES = [
    "SELECT dim2, COUNT(*), SUM(ivalue) FROM t GROUP BY dim2 ORDER BY dim2",
    "SELECT dim2, DISTINCTCOUNTHLL(dim1) FROM t GROUP BY dim2 ORDER BY dim2",
    "SELECT DISTINCTCOUNTHLL(dim1) FROM t",
    "SELECT dim1, dim2, COUNT(*), AVG(fvalue) FROM t GROUP BY dim1, dim2 "
    "ORDER BY dim1, dim2 LIMIT 200",
    "SELECT dim1, SUM(ivalue), SUM(fvalue), MAX(ivalue) FROM t "
    "WHERE dim2 != 'b' GROUP BY dim1 ORDER BY dim1 LIMIT 50",
]


@pytest.mark.parametrize("sql", MM_QUERIES)
def test_matmul_groupby_parity(mm_engine, engines, sql):
    """The factored one-hot matmul kernel must agree with the host path
    (exact ints, float sums to f32-level tolerance)."""
    _, host, _ = engines
    rd = mm_engine.execute(sql)
    rh = host.execute(sql)
    assert not rd.get("exceptions"), rd
    rows_d, rows_h = rd["resultTable"]["rows"], rh["resultTable"]["rows"]
    assert len(rows_d) == len(rows_h)
    for a, b in zip(rows_d, rows_h):
        assert all(_close(x, y) for x, y in zip(a, b)), (a, b)


class TestSortedHighCardGroupBy:
    """Radix-partitioned high-cardinality device regime (MAP_BASED
    analog): the cartesian dict-id product exceeds MAX_DENSE_GROUPS, so
    the packed keys ride ops/radix_groupby.py (chunk-local sorts +
    run-end partials + compacted merge) into a capped table."""

    @pytest.fixture(scope="class")
    def hc(self, tmp_path_factory):
        rng = np.random.default_rng(23)
        n = 30_000
        # 5000 users x 4096 items >> 4M dense cap; ~25k distinct pairs
        cols = {
            "user": np.array([f"u{i:04d}" for i in range(5000)])[
                rng.integers(0, 5000, n)],
            "item": np.array([f"i{i:04d}" for i in range(4096)])[
                rng.integers(0, 4096, n)],
            "spend": rng.integers(1, 500, n).astype(np.int64),
        }
        schema = Schema.build(
            name="hc",
            dimensions=[("user", DataType.STRING), ("item", DataType.STRING)],
            metrics=[("spend", DataType.LONG)],
        )
        cfg = TableConfig(table_name="hc")
        base = tmp_path_factory.mktemp("hcseg")
        dev = QueryEngine()
        host = QueryEngine(device_executor=None)
        half = n // 2
        for i, sl in enumerate([slice(0, half), slice(half, n)]):
            part = {k: v[sl] for k, v in cols.items()}
            build_segment(schema, part, str(base / f"s{i}"), cfg, f"s{i}")
            seg = ImmutableSegment(str(base / f"s{i}"))
            dev.add_segment("hc", seg)
            host.add_segment("hc", seg)
        return dev, host, cols

    @pytest.mark.parametrize("sql", [
        "SELECT user, item, SUM(spend), COUNT(*) FROM hc "
        "GROUP BY user, item ORDER BY SUM(spend) DESC, user, item LIMIT 25",
        "SELECT user, item, MIN(spend), MAX(spend), AVG(spend) FROM hc "
        "WHERE spend > 100 GROUP BY user, item "
        "ORDER BY MAX(spend) DESC, user, item LIMIT 40",
        "SELECT user, MINMAXRANGE(spend) FROM hc GROUP BY user "
        "ORDER BY user LIMIT 30",
    ])
    def test_parity_with_host(self, hc, sql):
        dev, host, _ = hc
        rd, rh = dev.execute(sql), host.execute(sql)
        assert not rd.get("exceptions"), rd
        assert not rh.get("exceptions"), rh
        assert rd["resultTable"]["rows"] == rh["resultTable"]["rows"], sql

    def test_sorted_template_used(self, hc):
        dev, _, _ = hc
        dev.execute("SELECT user, item, SUM(spend) FROM hc GROUP BY user, item")
        shapes = {k[0][0] for k in dev.device._pipelines}
        assert "groupby_sorted" in shapes

    def test_unsupported_agg_falls_back_to_host(self, hc):
        dev, host, _ = hc
        sql = ("SELECT user, item, DISTINCTCOUNT(item) FROM hc "
               "GROUP BY user, item ORDER BY user, item LIMIT 10")
        rd, rh = dev.execute(sql), host.execute(sql)
        assert rd["resultTable"]["rows"] == rh["resultTable"]["rows"]

    def test_group_table_overflow_falls_back_to_host(self, hc):
        """More distinct groups than the cap: the device result would be
        key-order-truncated, so it must defer to the host path (r3
        review)."""
        dev_small = QueryEngine(num_groups_limit=1000)
        host_small = QueryEngine(device_executor=None, num_groups_limit=1000)
        src, _, _ = hc
        for seg in src.tables["hc"].segments.values():
            dev_small.add_segment("hc", seg)
            host_small.add_segment("hc", seg)
        sql = ("SELECT user, item, SUM(spend) FROM hc GROUP BY user, item "
               "ORDER BY user, item LIMIT 20")
        rd, rh = dev_small.execute(sql), host_small.execute(sql)
        assert rd["resultTable"]["rows"] == rh["resultTable"]["rows"]

    def test_float_sums_no_cancellation(self, tmp_path):
        """Float SUMs use the order-independent scatter, not a global
        cumsum difference — a tiny group next to huge ones must not lose
        its value to cancellation (r3 review)."""
        n = 20_000
        rng = np.random.default_rng(9)
        vals = rng.uniform(1e9, 1e10, n)
        # one tiny-magnitude group buried at a random key position
        cols = {
            # dtype wide enough for the injected key: assigning "a_tiny"
            # into a '<U4' array would silently truncate to "a_ti"
            "a": np.array([f"a{i:03d}" for i in range(300)],
                          dtype="<U8")[rng.integers(0, 300, n)],
            "b": np.array([f"b{i:05d}" for i in range(n)]),
            "v": vals,
        }
        cols["a"][:3] = "a_tiny"
        cols["b"][:3] = np.array(["b_t0", "b_t1", "b_t2"])
        cols["v"][:3] = [1.25, 2.5, 1.25]
        schema = Schema.build(
            name="fs",
            dimensions=[("a", DataType.STRING), ("b", DataType.STRING)],
            metrics=[("v", DataType.DOUBLE)],
        )
        build_segment(schema, cols, str(tmp_path / "s0"),
                      TableConfig(table_name="fs"), "s0")
        seg = ImmutableSegment(str(tmp_path / "s0"))
        dev = QueryEngine()
        dev.add_segment("fs", seg)
        r = dev.execute("SELECT a, b, SUM(v) FROM fs WHERE a = 'a_tiny' "
                        "GROUP BY a, b ORDER BY b")
        shapes = {k[0][0] for k in dev.device._pipelines}
        assert "groupby_sorted" in shapes
        got = [row[2] for row in r["resultTable"]["rows"]]
        assert got == [1.25, 2.5, 1.25], got

    def test_large_int_sums_exact(self, tmp_path):
        """Integer payloads accumulate in int64 on the sorted path — per-doc
        f64 adds would round past 2^53 (r3 review)."""
        rng = np.random.default_rng(4)
        n = 20_000
        big = (rng.integers(1, 1 << 40, n) << 14).astype(np.int64)
        cols = {
            # every row a distinct b: global cards 300 x 20000 = 6M > dense
            # cap, while the ~20k real groups fit the sorted table
            "a": np.array([f"a{i:03d}" for i in range(300)])[
                rng.integers(0, 300, n)],
            "b": np.array([f"b{i:05d}" for i in range(n)]),
            "v": big,
        }
        schema = Schema.build(
            name="bigs",
            dimensions=[("a", DataType.STRING), ("b", DataType.STRING)],
            metrics=[("v", DataType.LONG)],
        )
        build_segment(schema, cols, str(tmp_path / "s0"),
                      TableConfig(table_name="bigs"), "s0")
        seg = ImmutableSegment(str(tmp_path / "s0"))
        dev = QueryEngine()
        host = QueryEngine(device_executor=None)
        dev.add_segment("bigs", seg)
        host.add_segment("bigs", seg)
        sql = ("SELECT a, b, SUM(v) FROM bigs GROUP BY a, b "
               "ORDER BY SUM(v) DESC, a, b LIMIT 50")
        rd, rh = dev.execute(sql), host.execute(sql)
        shapes = {k[0][0] for k in dev.device._pipelines}
        assert "groupby_sorted" in shapes
        assert rd["resultTable"]["rows"] == rh["resultTable"]["rows"]


class TestDeviceDistinct:
    """SELECT DISTINCT executes as group-keys-only on the device
    (DistinctAggregationFunction analog)."""

    def test_distinct_parity_and_device_used(self, engines):
        dev, host, _ = engines
        for sql in (
            "SELECT DISTINCT dim2 FROM t ORDER BY dim2",
            "SELECT DISTINCT dim1, dim2 FROM t ORDER BY dim1, dim2 LIMIT 500",
            "SELECT DISTINCT dim1 FROM t WHERE ivalue > 9000 ORDER BY dim1",
        ):
            rd, rh = dev.execute(sql), host.execute(sql)
            assert not rd.get("exceptions"), rd
            assert rd["resultTable"]["rows"] == rh["resultTable"]["rows"], sql
        shapes = {k[0][0] for k in dev.device._pipelines}
        assert "groupby" in shapes

    def test_distinct_expression_falls_back(self, engines):
        dev, host, _ = engines
        sql = "SELECT DISTINCT ivalue + 1 FROM t ORDER BY ivalue + 1 LIMIT 5"
        rd, rh = dev.execute(sql), host.execute(sql)
        assert rd["resultTable"]["rows"] == rh["resultTable"]["rows"]


class TestSortedProjection:
    def test_cached_projection_matches_cold_and_host(self, tmp_path):
        """The lazily-built sorted (group, hash) projection answers
        filterless terminal HLL scans bit-identically to the in-query-sort
        and host paths, and is actually CACHED on the batch."""
        import numpy as np

        from pinot_tpu.common.datatypes import DataType
        from pinot_tpu.common.schema import Schema
        from pinot_tpu.engine.engine import QueryEngine
        from pinot_tpu.storage.creator import build_segment

        rng = np.random.default_rng(13)
        n = 60_000
        # u must be a DIMENSION (dict-encoded): the device HLL path
        # prehashes dictionary values
        schema = Schema.build(
            name="sp", dimensions=[("g", DataType.INT), ("u", DataType.LONG)],
            metrics=[("v", DataType.INT)])
        segs = []
        for i in range(2):
            cols = {
                # global card high enough that G*m exceeds the mm register
                # kernel's bound -> the sorted paths engage (log2m=10)
                "g": rng.integers(0, 3000, n).astype(np.int32),
                "u": rng.integers(0, 500_000, n).astype(np.int64),
                "v": rng.integers(0, 9, n).astype(np.int32),
            }
            segs.append(build_segment(
                schema, cols, str(tmp_path / f"s{i}"), segment_name=f"s{i}"))
        sql = ("SET useStarTree = false; "
               "SELECT g, COUNT(*), DISTINCTCOUNTHLL(u) FROM sp "
               "GROUP BY g ORDER BY COUNT(*) DESC, g LIMIT 20")
        cold_sql = sql.replace("SET useStarTree = false; ",
                               "SET useStarTree = false; "
                               "SET useSortedProjection = false; ")
        from pinot_tpu.engine.device import DeviceExecutor

        eng = QueryEngine(device_executor=DeviceExecutor(mm_mode="interpret"))
        for s in segs:
            eng.add_segment("sp", s)
        warm = eng.execute(sql)
        assert not warm.get("exceptions"), warm
        # the projection is resident on the batch after the first execute
        ctx = next(iter(eng.device._batches.values()))
        assert ctx._sorted_hll, "sorted projection was not cached"
        again = eng.execute(sql)
        cold = eng.execute(cold_sql)
        host_eng = QueryEngine(device_executor=None)
        for s in segs:
            host_eng.add_segment("sp", s)
        host = host_eng.execute(sql)
        rows = warm["resultTable"]["rows"]
        assert rows == again["resultTable"]["rows"]
        assert rows == cold["resultTable"]["rows"]
        assert rows == host["resultTable"]["rows"]


class TestSortedRegimeBoundaries:
    """Satellite for the radix tentpole: drive group counts across the
    sorted_k = min(numGroupsLimit, MAX_SORTED_GROUPS) table-cap and the
    host-overflow boundaries, asserting device == host on every side and
    numGroupsLimitReached semantics on both paths. The fixture pins BOTH
    column dictionaries at full cardinality (3000 x 1500 = 4.5M key space
    > MAX_DENSE_GROUPS) with EXACTLY 5000 distinct pairs, so each engine
    limit below/above 5000 picks the regime deterministically."""

    U, I, D, N = 3000, 1500, 5000, 40_000

    @pytest.fixture(scope="class")
    def bc(self, tmp_path_factory):
        rng = np.random.default_rng(31)
        U, I, D, n = self.U, self.I, self.D, self.N
        base = sorted({j * I + (j % I) for j in range(U)}  # covers every u
                      | set(range(I)))                     # covers every i
        pool = rng.choice(U * I, size=2 * D, replace=False)
        bset = set(base)
        extra = [int(p) for p in pool if p not in bset][:D - len(base)]
        pids = np.array(base + extra)
        assert len(pids) == D
        draw = np.concatenate([pids, rng.choice(pids, n - D)])
        rng.shuffle(draw)
        cols = {
            "u": (draw // I).astype(np.int32),
            "i": (draw % I).astype(np.int32),
            "v": rng.integers(-1000, 1000, n).astype(np.int64),
            "f": np.round(rng.uniform(-5, 5, n), 6),
        }
        schema = Schema.build(
            name="bc",
            dimensions=[("u", DataType.INT), ("i", DataType.INT)],
            metrics=[("v", DataType.LONG), ("f", DataType.DOUBLE)],
        )
        base_dir = tmp_path_factory.mktemp("bcseg")
        segs = []
        quarter = n // 4
        for s in range(4):
            part = {k: v[s * quarter:(s + 1) * quarter]
                    for k, v in cols.items()}
            build_segment(schema, part, str(base_dir / f"s{s}"),
                          TableConfig(table_name="bc"), f"s{s}")
            segs.append(ImmutableSegment(str(base_dir / f"s{s}")))
        return segs

    SQL = ("SELECT u, i, COUNT(*), SUM(v), AVG(v), MIN(v), MAX(v), "
           "MINMAXRANGE(v), SUM(f) FROM bc GROUP BY u, i "
           "ORDER BY SUM(v) DESC, u, i LIMIT 30")

    def _engines(self, segs, limit):
        dev = QueryEngine(num_groups_limit=limit)
        host = QueryEngine(device_executor=None, num_groups_limit=limit)
        for s in segs:
            dev.add_segment("bc", s)
            host.add_segment("bc", s)
        return dev, host

    def _assert_parity(self, dev, host, sql=None):
        rd, rh = dev.execute(sql or self.SQL), host.execute(sql or self.SQL)
        assert not rd.get("exceptions"), rd
        assert not rh.get("exceptions"), rh
        rows_d, rows_h = rd["resultTable"]["rows"], rh["resultTable"]["rows"]
        assert len(rows_d) == len(rows_h)
        for a, b in zip(rows_d, rows_h):
            assert all(_close(x, y) for x, y in zip(a, b)), (a, b)
        return rd, rh

    def test_below_cap_device_radix_regime(self, bc):
        """D < sorted_k: the radix regime answers on device, exactly."""
        dev, host = self._engines(bc, limit=6000)
        rd, rh = self._assert_parity(dev, host)
        shapes = {k[0][0] for k in dev.device._pipelines}
        assert "groupby_sorted" in shapes
        assert rd["numGroupsLimitReached"] is False
        assert rh["numGroupsLimitReached"] is False

    def test_above_cap_host_overflow_fallback(self, bc):
        """D > sorted_k: the device table would truncate, so the executor
        must detect overflow and defer to the host path (both engines
        then flag the limit and answer identically)."""
        dev, host = self._engines(bc, limit=4000)
        rd, rh = self._assert_parity(dev, host)
        assert rd["numGroupsLimitReached"] is True
        assert rh["numGroupsLimitReached"] is True

    def test_max_sorted_groups_ceiling(self, bc, monkeypatch):
        """sorted_k is min(numGroupsLimit, MAX_SORTED_GROUPS): with the
        hard ceiling lowered below D, even a generous numGroupsLimit must
        route through the host fallback — and raising it back re-enables
        the device regime."""
        from pinot_tpu.engine import device as devmod

        monkeypatch.setattr(devmod, "MAX_SORTED_GROUPS", 4500)
        dev, host = self._engines(bc, limit=100_000)
        self._assert_parity(dev, host)
        monkeypatch.setattr(devmod, "MAX_SORTED_GROUPS", 1 << 17)
        dev2, host2 = self._engines(bc, limit=100_000)
        rd, _rh = self._assert_parity(dev2, host2)
        shapes = {k[0][0] for k in dev2.device._pipelines}
        assert "groupby_sorted" in shapes
        assert rd["numGroupsLimitReached"] is False

    def test_set_num_groups_limit_flags_both_paths(self, bc):
        """Per-query SET numGroupsLimit below D: results are plan-
        dependent-partial by reference contract — BOTH paths must say so
        (rows are not compared; the flag is the contract)."""
        dev, host = self._engines(bc, limit=6000)
        sql = ("SET numGroupsLimit = 1000; "
               "SELECT u, i, COUNT(*) FROM bc GROUP BY u, i "
               "ORDER BY COUNT(*) DESC LIMIT 5")
        for eng in (dev, host):
            r = eng.execute(sql)
            assert not r.get("exceptions"), r
            assert r["numGroupsLimitReached"] is True, r

    def test_chunked_plan_parity(self, bc, monkeypatch):
        """Force the multi-chunk radix plan at engine scale (CHUNK_ROWS
        shrunk + compaction ratio tightened so the 40k-row batch splits
        into level-1 chunks + a merge level) — results must not depend on
        the chunk plan."""
        from pinot_tpu.ops import radix_groupby as radix

        orig_plan = radix.plan_chunks
        monkeypatch.setattr(radix, "CHUNK_ROWS", 256)
        monkeypatch.setattr(
            radix, "plan_chunks",
            lambda n, k, chunk_rows=None, min_ratio=None:
            orig_plan(n, k, chunk_rows, radix.HLL_COMPACT_RATIO))
        C, _L = radix.plan_chunks(self.N, 6000)
        assert C > 1, "plan must actually chunk at this scale"
        dev, host = self._engines(bc, limit=6000)
        self._assert_parity(dev, host)

    def test_unsupported_agg_family_falls_back(self, bc):
        """DISTINCTCOUNTHLL is not in SORTED_AGGS: the sorted regime must
        defer to the host rather than mis-aggregate."""
        dev, host = self._engines(bc, limit=6000)
        sql = ("SELECT u, i, DISTINCTCOUNTHLL(v) FROM bc GROUP BY u, i "
               "ORDER BY u, i LIMIT 10")
        self._assert_parity(dev, host, sql)
