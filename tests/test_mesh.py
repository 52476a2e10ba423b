"""Mesh-parallel combine tests on the virtual 8-device CPU mesh.

The multi-chip contract: sharding the segment axis over a Mesh and combining
accumulators with psum/pmin/pmax must give bit-identical results to the
single-device batched launch (the reference's equivalent guarantee is
combine-operator merge correctness, operator/combine/).
"""

import numpy as np
import pytest

import jax

from pinot_tpu.common.datatypes import DataType
from pinot_tpu.common.schema import Schema
from pinot_tpu.common.table_config import TableConfig
from pinot_tpu.engine.device import DeviceExecutor
from pinot_tpu.engine.engine import QueryEngine
from pinot_tpu.parallel.mesh import make_mesh
from pinot_tpu.storage.creator import build_segment
from pinot_tpu.storage.segment import ImmutableSegment


@pytest.fixture(scope="module")
def mesh_engines(tmp_path_factory):
    assert len(jax.devices()) == 8, "conftest must provide 8 virtual devices"
    rng = np.random.default_rng(23)
    n = 5000
    cols = {
        "k1": np.array([f"g{i}" for i in range(20)])[rng.integers(0, 20, n)],
        "k2": np.array(["x", "y"])[rng.integers(0, 2, n)],
        "v": rng.integers(0, 1000, n).astype(np.int32),
    }
    schema = Schema.build(
        name="m",
        dimensions=[("k1", DataType.STRING), ("k2", DataType.STRING)],
        metrics=[("v", DataType.INT)],
    )
    base = tmp_path_factory.mktemp("meshseg")
    mesh = make_mesh(8)
    sharded = QueryEngine(device_executor=DeviceExecutor(mesh=mesh))
    single = QueryEngine()
    # 6 segments of uneven sizes: exercises padding to the mesh multiple
    bounds = [0, 400, 1400, 2000, 3100, 4200, n]
    for i in range(6):
        part = {k: v[bounds[i]:bounds[i + 1]] for k, v in cols.items()}
        build_segment(schema, part, str(base / f"s{i}"), TableConfig(table_name="m"), f"s{i}")
        seg = ImmutableSegment(str(base / f"s{i}"))
        sharded.add_segment("m", seg)
        single.add_segment("m", seg)
    return sharded, single


MESH_QUERIES = [
    "SELECT COUNT(*) FROM m",
    "SELECT SUM(v), MIN(v), MAX(v), AVG(v) FROM m WHERE k2 = 'x'",
    "SELECT k1, COUNT(*), SUM(v) FROM m GROUP BY k1 ORDER BY k1 LIMIT 25",
    "SELECT k1, k2, MAX(v) FROM m WHERE v > 100 GROUP BY k1, k2 ORDER BY k1, k2 LIMIT 50",
    "SELECT DISTINCTCOUNT(k1) FROM m WHERE k2 = 'y'",
    "SELECT k2, DISTINCTCOUNTHLL(k1) FROM m GROUP BY k2 ORDER BY k2",
    "SELECT COUNT(*) FROM m WHERE k1 IN ('g1','g5') OR v BETWEEN 10 AND 50",
]


@pytest.mark.parametrize("sql", MESH_QUERIES)
def test_sharded_equals_single(mesh_engines, sql):
    sharded, single = mesh_engines
    rs = sharded.execute(sql)
    r1 = single.execute(sql)
    assert not rs.get("exceptions"), rs
    assert rs["resultTable"]["rows"] == r1["resultTable"]["rows"], (
        rs["resultTable"]["rows"][:4],
        r1["resultTable"]["rows"][:4],
    )
    assert rs["numDocsScanned"] == r1["numDocsScanned"]


def test_sharded_uses_device(mesh_engines):
    sharded, _ = mesh_engines
    sharded.execute("SELECT k1, SUM(v) FROM m GROUP BY k1")
    assert len(sharded.device._pipelines) > 0


class TestSortedRegimeMesh:
    """High-cardinality (radix) regime ON the mesh: per-shard group tables
    are KEYED, so parallel/mesh.py merges them by key (merge_tables) —
    the shape that used to route every multi-chip high-card query to the
    host. Sharded == single-device == host, exactly."""

    @pytest.fixture(scope="class")
    def hc_engines(self, tmp_path_factory):
        rng = np.random.default_rng(37)
        n, U, I = 12_000, 2300, 2000  # 4.6M key space > MAX_DENSE_GROUPS
        # pin both dictionaries at full cardinality, then draw ~3k extra
        # distinct pairs; groups deliberately SPAN segments so the merge
        # must combine cross-shard partials
        u = rng.integers(0, U, n).astype(np.int32)
        i = rng.integers(0, I, n).astype(np.int32)
        u[:U] = np.arange(U, dtype=np.int32)
        i[:I] = np.arange(I, dtype=np.int32)
        cols = {
            "u": u, "i": i,
            "v": rng.integers(-500, 500, n).astype(np.int64),
        }
        schema = Schema.build(
            name="hcm",
            dimensions=[("u", DataType.INT), ("i", DataType.INT)],
            metrics=[("v", DataType.LONG)],
        )
        base = tmp_path_factory.mktemp("hcmesh")
        sharded = QueryEngine(device_executor=DeviceExecutor(mesh=make_mesh(8)))
        single = QueryEngine()
        host = QueryEngine(device_executor=None)
        bounds = [0, 1500, 2600, 4800, 6400, 9000, n]  # mesh-unaligned
        for s in range(6):
            part = {k: v[bounds[s]:bounds[s + 1]] for k, v in cols.items()}
            build_segment(schema, part, str(base / f"s{s}"),
                          TableConfig(table_name="hcm"), f"s{s}")
            seg = ImmutableSegment(str(base / f"s{s}"))
            for eng in (sharded, single, host):
                eng.add_segment("hcm", seg)
        return sharded, single, host

    @pytest.mark.parametrize("sql", [
        "SELECT u, i, COUNT(*), SUM(v) FROM hcm GROUP BY u, i "
        "ORDER BY COUNT(*) DESC, u, i LIMIT 30",
        "SELECT u, i, MIN(v), MAX(v), AVG(v) FROM hcm WHERE v > -200 "
        "GROUP BY u, i ORDER BY MIN(v), u, i LIMIT 40",
    ])
    def test_mesh_equals_single_equals_host(self, hc_engines, sql):
        sharded, single, host = hc_engines
        rs, r1, rh = (e.execute(sql) for e in (sharded, single, host))
        for r in (rs, r1, rh):
            assert not r.get("exceptions"), r
        assert rs["resultTable"]["rows"] == r1["resultTable"]["rows"]
        assert rs["resultTable"]["rows"] == rh["resultTable"]["rows"]

    def test_mesh_sorted_template_on_device(self, hc_engines):
        sharded, _, _ = hc_engines
        sharded.execute("SELECT u, i, SUM(v) FROM hcm GROUP BY u, i")
        shapes = {k[0][0] for k in sharded.device._pipelines}
        assert "groupby_sorted" in shapes

    def test_mesh_overflow_still_falls_back(self, hc_engines):
        """Distinct > sorted_k under the mesh: merged n_groups_total must
        trip the SAME host fallback as single-device."""
        sharded, _, host = hc_engines
        small = QueryEngine(
            device_executor=DeviceExecutor(mesh=make_mesh(8),
                                           num_groups_limit=1000),
            num_groups_limit=1000)
        host_small = QueryEngine(device_executor=None, num_groups_limit=1000)
        for seg in sharded.tables["hcm"].segments.values():
            small.add_segment("hcm", seg)
            host_small.add_segment("hcm", seg)
        sql = ("SELECT u, i, SUM(v) FROM hcm GROUP BY u, i "
               "ORDER BY u, i LIMIT 20")
        rs, rh = small.execute(sql), host_small.execute(sql)
        assert not rs.get("exceptions"), rs
        assert rs["resultTable"]["rows"] == rh["resultTable"]["rows"]
