"""Pallas scatter-kernel tier differential suite (ISSUE 15).

Pins the tier three ways against its compiled-in references:
kernel-level (pallas_scatter primitives vs numpy oracles, partitioned
launches forced), engine-level (Pallas pipelines == XLA scatter
pipelines BIT-EXACT, == host within the established float tolerance —
across int64 two-stage sums, float accumulation, group-count
boundaries, sealed + consuming(chunklet), solo + 8-dev mesh, and
cohort-coalesced launches), and routing-level (PINOT_TPU_PALLAS=0 /
SET usePallas=false escape hatches, and the quarantine XLA rung that
keeps a Pallas-only failure on device).

All kernels run in interpret mode here (JAX_PLATFORMS=cpu) — the same
compiled structure the TPU executes, per the ops/groupby_mm.py pattern.
"""

import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from pinot_tpu.common.datatypes import DataType
from pinot_tpu.common.schema import Schema
from pinot_tpu.common.table_config import (
    ChunkletConfig,
    IndexingConfig,
    TableConfig,
)
from pinot_tpu.engine.device import DeviceExecutor
from pinot_tpu.engine.engine import QueryEngine
from pinot_tpu.ops import groupby_mm as mm
from pinot_tpu.ops import pallas_scatter as ps
from pinot_tpu.storage.creator import build_segment
from pinot_tpu.storage.segment import ImmutableSegment


def _rows_close(rows_a, rows_b, rtol=1e-5):
    if len(rows_a) != len(rows_b):
        return False
    for ra, rb in zip(rows_a, rows_b):
        for x, y in zip(ra, rb):
            if isinstance(x, str) or x is None:
                if x != y:
                    return False
            elif not np.isclose(float(x), float(y), rtol=rtol, atol=1e-6):
                return False
    return True


# ---------------------------------------------------------------------------
# kernel-level: primitives vs numpy oracles
# ---------------------------------------------------------------------------


class TestPlaneGroupSums:
    def _check(self, G, span_hpad=None, n=20000):
        rng = np.random.default_rng(7)
        gid = rng.integers(0, G + 1, n).astype(np.int32)  # incl. overflow
        val = rng.integers(-100, 100, n).astype(np.int64)
        off = -128
        chans = jnp.stack(
            [jnp.ones(n, jnp.bfloat16)]
            + mm.int_planes(jnp.asarray(val), off, 2))
        out = ps.plane_group_sums(
            jnp.asarray(gid), chans, G, interpret=True,
            first_channel_ones=True, span_hpad=span_hpad)
        cnt = np.round(np.asarray(out[0])).astype(np.int64)
        np.testing.assert_array_equal(
            cnt, np.bincount(gid, minlength=G + 1)[:G])
        s = np.asarray(mm.recombine_int(
            [out[1], out[2]], jnp.asarray(cnt), jnp.int64(off)))
        ref = np.zeros(G + 1, dtype=np.int64)
        np.add.at(ref, gid, val)
        np.testing.assert_array_equal(s, ref[:G])

    @pytest.mark.parametrize("G", [1, 255, 300, 4096])
    def test_vs_numpy(self, G):
        self._check(G)

    def test_partitioned_multi_pass(self):
        # span_hpad=8 → 1024 groups per partition → 5 partitions
        self._check(5000, span_hpad=8)

    def test_supported_bounds(self):
        assert ps.sums_supported(1, 2)
        assert ps.sums_supported(1 << 20, 4)
        # the partition sweep is bounded: absurd G declines
        assert not ps.sums_supported(1 << 27, 15)


class TestGroupMinMax:
    def test_vs_numpy_int(self):
        rng = np.random.default_rng(8)
        G, n = 300, 20000
        gid = rng.integers(0, G + 1, n).astype(np.int32)
        val = rng.integers(-1000, 1000, n).astype(np.int32)
        mn, mx = ps.group_minmax(
            jnp.asarray(gid), jnp.asarray(val), G, ("min", "max"),
            interpret=True)
        refmn = np.full(G + 1, np.iinfo(np.int32).max, np.int64)
        refmx = np.full(G + 1, np.iinfo(np.int32).min, np.int64)
        np.minimum.at(refmn, gid, val)
        np.maximum.at(refmx, gid, val)
        np.testing.assert_array_equal(np.asarray(mn), refmn[:G])
        np.testing.assert_array_equal(np.asarray(mx), refmx[:G])

    def test_partitioned_and_fills(self):
        # G=5000 → 5 partitions; empty groups keep the caller's fill
        rng = np.random.default_rng(9)
        G, n = 5000, 8000
        gid = (rng.integers(0, G // 2, n) * 2).astype(np.int32)  # evens only
        val = rng.uniform(-5, 5, n).astype(np.float32)
        mx, = ps.group_minmax(
            jnp.asarray(gid), jnp.asarray(val), G, ("max",),
            interpret=True, fills=(float("-inf"),))
        got = np.asarray(mx)
        refmx = np.full(G, -np.inf, np.float64)
        np.maximum.at(refmx, gid, val.astype(np.float64))
        np.testing.assert_array_equal(got, refmx.astype(np.float32))
        assert np.isneginf(got[1::2]).all()  # odd groups empty

    def test_supported(self):
        assert ps.minmax_supported(8000, np.int32)
        assert ps.minmax_supported(100, np.float32)
        assert not ps.minmax_supported(100, np.int64)   # no 64-bit vectors
        assert not ps.minmax_supported(100, np.float64)
        assert not ps.minmax_supported(1 << 16, np.int32)  # span bound


class TestHllRegisterMax:
    @pytest.mark.parametrize("span_hpad", [None, 8])
    def test_vs_numpy(self, span_hpad):
        rng = np.random.default_rng(10)
        nslots, nrho, n = 2048, 23, 30000
        slot = rng.integers(0, nslots + 1, n).astype(np.int32)
        rho = rng.integers(1, nrho + 1, n).astype(np.int32)
        regs = ps.hll_register_max(
            jnp.asarray(slot), jnp.asarray(rho), nslots, nrho,
            interpret=True, span_hpad=span_hpad)
        ref = np.zeros(nslots + 1, np.int32)
        np.maximum.at(ref, slot, rho)
        np.testing.assert_array_equal(np.asarray(regs), ref[:nslots])

    def test_matches_scatter_max_build(self):
        """The engine contract: the kernel's registers equal the XLA
        f32 scatter-max registers for the same (slot, rho) stream."""
        from pinot_tpu.ops import hll as hll_ops

        rng = np.random.default_rng(11)
        log2m = 10
        m = 1 << log2m
        keys = rng.integers(0, 500, 50000).astype(np.int32)
        h = hll_ops.hash32(jnp.asarray(keys))
        idx, rho = hll_ops.hll_idx_rho(h, log2m)
        regs_scatter = np.asarray(
            jnp.zeros(m + 1, jnp.float32).at[idx].max(
                rho.astype(jnp.float32))[:m]).astype(np.int32)
        regs_pallas = np.asarray(ps.hll_register_max(
            idx, rho, m, mm.hll_nrho(log2m), interpret=True))
        np.testing.assert_array_equal(regs_pallas, regs_scatter)

    def test_supported_bound(self):
        assert ps.hll_supported(1 << 10, 23)
        assert not ps.hll_supported(ps.HLL_MAX_SLOTS * 2, 23)


class TestFusedPlan:
    WIDTHS = {
        "d": ("uint8", 0, False, None),
        "iv": ("uint16", 0, True, "int64"),
        "fv": ("float32", 0, False, None),
        "sb": ("uint8", 4, False, None),  # sub-byte packed
    }
    RANGE = ("range_raw", ("raw", "iv"), "p1", "p2", True, True, True, False)

    def test_eligible(self):
        plan = ps.plan_fused(
            ("and", ("eq_dict", "d", "p0"), self.RANGE),
            (("count", None, None), ("sum", ("raw", "iv"), (2, 1 << 20)),
             ("minmaxrange", ("raw", "fv"), None)),
            self.WIDTHS)
        assert plan is not None
        assert plan.n_int == 2 and plan.n_flt == 2
        assert set(plan.pred_params) == {"p0", "p1", "p2"}

    def test_ineligible_shapes(self):
        count = (("count", None, None),)
        # sub-byte plane
        assert ps.plan_fused(("eq_dict", "sb", "p0"), count,
                             self.WIDTHS) is None
        # regex LUT node
        assert ps.plan_fused(("lut_dict", "d", "p0"), count,
                             self.WIDTHS) is None
        # float raw predicate (literal rounding would change compares)
        assert ps.plan_fused(
            ("range_raw", ("raw", "fv"), "p1", "p2", True, True, True,
             False), count, self.WIDTHS) is None
        # float SUM (order-sensitive accumulation stays on XLA)
        assert ps.plan_fused(
            ("eq_dict", "d", "p0"),
            (("sum", ("raw", "fv"), (None, None)),), self.WIDTHS) is None
        # int SUM whose per-block partial could overflow int32
        assert ps.plan_fused(
            ("eq_dict", "d", "p0"),
            (("sum", ("raw", "iv"), (2, 2048)),), self.WIDTHS) is None

    def test_params_ok_bounds_in_lists(self):
        plan = ps.plan_fused(("in_dict", "d", "p0"), (("count", None, None),),
                             self.WIDTHS)
        assert plan is not None
        assert ps.fused_params_ok(plan, {"p0": jnp.zeros(4, jnp.int32)})
        assert not ps.fused_params_ok(
            plan, {"p0": jnp.zeros(ps.FUSED_MAX_IN + 1, jnp.int32)})
        assert not ps.fused_params_ok(plan, {})


# ---------------------------------------------------------------------------
# engine-level differential: pallas == XLA scatter == host
# ---------------------------------------------------------------------------


def _build_table(base, seed=5, n=30000, card=220):
    """3 segments; ``ts`` ascends globally (time-ordered layout — the
    shape zone maps discriminate on; span < 65536 keeps its
    frame-of-reference plane uint16, inside the fused kernel's predicate
    surface), everything else unclustered."""
    rng = np.random.default_rng(seed)
    assert n < 65536
    cols = {
        "ts": np.arange(n, dtype=np.int64),
        "d": np.array([f"k{i:04d}" for i in range(card)])[
            rng.integers(0, card, n)],
        "e": np.array(["x", "y", "z"])[rng.integers(0, 3, n)],
        "iv": rng.integers(0, 9000, n).astype(np.int32),
        # int64 values past 2^31: exercises the two-stage exact sum planes
        "big": (rng.integers(0, 1 << 38, n)).astype(np.int64),
        "fv": rng.uniform(-100, 100, n).astype(np.float64),
    }
    schema = Schema.build(
        name="t",
        dimensions=[("ts", DataType.LONG), ("d", DataType.STRING),
                    ("e", DataType.STRING)],
        metrics=[("iv", DataType.INT), ("big", DataType.LONG),
                 ("fv", DataType.DOUBLE)],
    )
    cfg = TableConfig(table_name="t",
                      indexing=IndexingConfig(no_dictionary_columns=["ts"]))
    segs = []
    third = n // 3
    for i, sl in enumerate([slice(0, third), slice(third, 2 * third),
                            slice(2 * third, n)]):
        part = {k: v[sl] for k, v in cols.items()}
        build_segment(schema, part, str(base / f"s{i}"), cfg, f"s{i}")
        segs.append(ImmutableSegment(str(base / f"s{i}")))
    return segs, cols


@pytest.fixture(scope="module")
def engines(tmp_path_factory):
    base = tmp_path_factory.mktemp("pallas_seg")
    segs, cols = _build_table(base)
    pallas = QueryEngine(device_executor=DeviceExecutor(mm_mode="interpret"))
    xla = QueryEngine(device_executor=DeviceExecutor(
        mm_mode="interpret", pallas_mode="off"))
    host = QueryEngine(device_executor=None)
    for e in (pallas, xla, host):
        for s in segs:
            e.add_segment("t", s)
    return pallas, xla, host, cols


DIFF_QUERIES = [
    # int64 two-stage sums (values past 2^31 → multi-plane exact path)
    "SELECT d, SUM(big), COUNT(*) FROM t GROUP BY d ORDER BY d LIMIT 250",
    # float accumulation (3-way bf16 split planes)
    "SELECT d, SUM(fv), AVG(fv) FROM t GROUP BY d ORDER BY d LIMIT 250",
    # min/max scatter family (no MXU identity)
    "SELECT d, MIN(iv), MAX(iv), MINMAXRANGE(big) FROM t "
    "GROUP BY d ORDER BY d LIMIT 250",
    # scalar HLL: the register-max scatter
    "SELECT DISTINCTCOUNTHLL(d) FROM t",
    "SELECT DISTINCTCOUNTHLL(d) FROM t WHERE e = 'x'",
    # fused filter+gather+aggregate shapes (selective time range → the
    # block-skip SKIP branch actually executes: one candidate block)
    "SELECT COUNT(*) FROM t WHERE ts < 40",
    "SELECT COUNT(*), SUM(iv), MIN(iv), MAX(iv) FROM t WHERE ts BETWEEN "
    "100 AND 700",
    "SELECT COUNT(*), MAX(fv), SUM(big) FROM t WHERE ts >= 59000",
    "SELECT COUNT(*), MIN(fv) FROM t WHERE ts < 3000 AND d = 'k0003'",
    "SELECT COUNT(*) FROM t WHERE d IN ('k0001','k0007') AND e = 'y'",
    # NOT node rides the fused kernel too (~child in-kernel)
    "SELECT COUNT(*), SUM(iv) FROM t WHERE NOT e = 'x' AND ts < 300",
    # float SUM: fused-ineligible (order-sensitive) → generic gather branch
    "SELECT COUNT(*), SUM(fv) FROM t WHERE ts < 300",
    # dense + group-by over two keys
    "SELECT d, e, COUNT(*), SUM(iv) FROM t GROUP BY d, e "
    "ORDER BY d, e LIMIT 100",
]


# statements whose group-by sums a FLOAT column through the plane kernels
# (the xla engine keeps mm_mode="interpret", so its float sums ride
# ops/groupby_mm.py's kernel): a float's three bf16 planes are added up in
# f32 inside a superblock, and that is not order-independent the way byte
# planes, min/max and presence are. The Pallas kernel contracts at radix
# 128 and the matmul kernel at the radix _plan_lo picks (32 at 220
# groups), so the two dots add the 8192 rows of a step up in different
# orders and a group's sum can differ in its last f32 bit (seen: 2e-10
# relative in one group of 220: in every run of this file alone, in most
# whole runs under six workers, where the CPU's dot presumably splits the
# contraction among its threads another way). Each engine repeats its own
# answer exactly. These are held to f32 rounding, a tenth of the host
# comparison's tolerance.
FLOAT_SUM_QUERIES = {
    "SELECT d, SUM(fv), AVG(fv) FROM t GROUP BY d ORDER BY d LIMIT 250",
}


@pytest.mark.parametrize("sql", DIFF_QUERIES)
def test_pallas_xla_host_parity(engines, sql):
    pallas, xla, host, _ = engines
    rp, rx, rh = pallas.execute(sql), xla.execute(sql), host.execute(sql)
    for r in (rp, rx, rh):
        assert not r.get("exceptions"), (sql, r)
    if sql in FLOAT_SUM_QUERIES:
        assert _rows_close(rp["resultTable"]["rows"],
                           rx["resultTable"]["rows"], rtol=1e-6), (
            sql, rp["resultTable"]["rows"][:4], rx["resultTable"]["rows"][:4])
    else:
        # the two device paths are BIT-exact (order-independent kernels)
        assert rp["resultTable"]["rows"] == rx["resultTable"]["rows"], (
            sql, rp["resultTable"]["rows"][:4],
            rx["resultTable"]["rows"][:4])
    # host compares at the established float tolerance (device floats
    # live in the f32 value space)
    assert _rows_close(rp["resultTable"]["rows"], rh["resultTable"]["rows"]), (
        sql, rp["resultTable"]["rows"][:4], rh["resultTable"]["rows"][:4])


def test_fractional_literal_declines_fused(engines):
    """Review regression: a fractional literal over an integer column
    must NOT enter the fused kernel (the storage-space int cast would
    truncate it while the generic branch compares with float promotion).
    The plan declines via fused_params_ok and all three paths agree."""
    pallas, xla, host, _ = engines
    for sql in ("SELECT COUNT(*) FROM t WHERE ts < 10.5",
                "SELECT COUNT(*), SUM(iv) FROM t WHERE ts BETWEEN 99.5 "
                "AND 700.5"):
        rp, rx, rh = pallas.execute(sql), xla.execute(sql), host.execute(sql)
        for r in (rp, rx, rh):
            assert not r.get("exceptions"), (sql, r)
        assert rp["resultTable"]["rows"] == rx["resultTable"]["rows"] \
            == rh["resultTable"]["rows"], (
                sql, rp["resultTable"]["rows"], rx["resultTable"]["rows"],
                rh["resultTable"]["rows"])


def test_label_only_when_tier_routes(engines):
    """Review regression: the "+pallas" roofline label claims the tier
    only for pipelines that actually compile a Pallas kernel — a scalar
    shape with no HLL (its min/max/sum are dense reductions, not
    scatters) must keep its XLA label even with the tier enabled."""
    pallas, _, _, _ = engines
    r = pallas.execute(
        "SET usePartialsCache=false; SELECT COUNT(*), SUM(fv) FROM t "
        "WHERE ts < 300")
    recs = [rec.get("kernel", "") for rec in (r.get("roofline") or [])]
    assert recs and all("+pallas" not in k and "+fused" not in k
                        for k in recs), recs


def test_pallas_pipelines_and_labels(engines):
    """The tier actually ran: pallas-keyed pipelines compiled and the
    roofline attributes them under their own labels."""
    pallas, _, _, _ = engines
    pallas.execute("SELECT d, MIN(iv) FROM t GROUP BY d LIMIT 5")
    modes = {k[5] for k in pallas.device._pipelines}
    assert "interpret" in modes
    labels = set(pallas.device.roofline_stats()["kernels"])
    assert any("+pallas" in lb for lb in labels), labels


def test_fused_label_and_gather_model(engines):
    """A selective fused query earns the +fused label, actually prunes
    blocks, and its roofline record does NOT carry the gather round-trip
    term the XLA form pays."""
    pallas, xla, _, _ = engines
    r = pallas.execute(
        "SET usePartialsCache=false; SELECT COUNT(*), SUM(iv) FROM t "
        "WHERE ts < 25")
    assert r["numBlocksPruned"] > 0, r  # the skip branch really ran
    labels = set(pallas.device.roofline_stats()["kernels"])
    assert any("+fused" in lb for lb in labels), labels
    recs = [rec for rec in (r.get("roofline") or [])
            if "+fused" in rec.get("kernel", "")]
    assert recs and all("gatherBytes" not in rec for rec in recs), \
        r.get("roofline")
    # the XLA form of the same query pays the gather round trip
    rx = xla.execute(
        "SET usePartialsCache=false; SELECT COUNT(*), SUM(iv) FROM t "
        "WHERE ts < 25")
    assert rx["numBlocksPruned"] > 0, rx
    xrecs = [rec for rec in (rx.get("roofline") or [])
             if "bskip" in rec.get("kernel", "") and not rec.get("cacheHit")]
    assert any(rec.get("gatherBytes") for rec in xrecs), rx.get("roofline")


class TestGroupCountBoundaries:
    @pytest.mark.parametrize("card", [1, 255, 65536])
    def test_boundary_cardinality(self, tmp_path, card):
        rng = np.random.default_rng(card)
        n = max(4000, card)
        vals = np.arange(card)
        d = vals[rng.integers(0, card, n - card)] if n > card else vals
        d = np.concatenate([vals, d])[:n]  # every id present
        cols = {"d": np.array([f"v{i:06d}" for i in range(card)])[d],
                "m": rng.integers(0, 100, n).astype(np.int32)}
        schema = Schema.build(name="b",
                              dimensions=[("d", DataType.STRING)],
                              metrics=[("m", DataType.INT)])
        build_segment(schema, cols, str(tmp_path / "s0"),
                      TableConfig(table_name="b"), "s0")
        seg = ImmutableSegment(str(tmp_path / "s0"))
        pallas = QueryEngine(
            device_executor=DeviceExecutor(mm_mode="interpret"))
        xla = QueryEngine(device_executor=DeviceExecutor(
            mm_mode="interpret", pallas_mode="off"))
        pallas.add_segment("b", seg)
        xla.add_segment("b", seg)
        sql = ("SELECT d, COUNT(*), SUM(m), MIN(m) FROM b GROUP BY d "
               "ORDER BY d LIMIT 20")
        rp, rx = pallas.execute(sql), xla.execute(sql)
        assert not rp.get("exceptions") and not rx.get("exceptions"), rp
        assert rp["resultTable"]["rows"] == rx["resultTable"]["rows"]

    def test_num_groups_limit_overflow_policy_unchanged(self, tmp_path):
        """numGroupsLimit pressure: the Pallas tier must not change the
        dense regime's deterministic gid-order drop policy — pallas and
        XLA device paths drop identically and both flag the limit."""
        rng = np.random.default_rng(1)
        n = 5000
        cols = {"d": np.array([f"v{i:04d}" for i in range(900)])[
            rng.integers(0, 900, n)],
            "m": rng.integers(0, 100, n).astype(np.int32)}
        schema = Schema.build(name="b", dimensions=[("d", DataType.STRING)],
                              metrics=[("m", DataType.INT)])
        build_segment(schema, cols, str(tmp_path / "s0"),
                      TableConfig(table_name="b"), "s0")
        seg = ImmutableSegment(str(tmp_path / "s0"))
        pallas = QueryEngine(
            device_executor=DeviceExecutor(mm_mode="interpret"))
        xla = QueryEngine(device_executor=DeviceExecutor(
            mm_mode="interpret", pallas_mode="off"))
        pallas.add_segment("b", seg)
        xla.add_segment("b", seg)
        sql = ("SET numGroupsLimit=50; SELECT d, SUM(m) FROM b GROUP BY d "
               "ORDER BY d LIMIT 900")
        rp, rx = pallas.execute(sql), xla.execute(sql)
        assert rp["resultTable"]["rows"] == rx["resultTable"]["rows"]
        assert rp["numGroupsLimitReached"] and rx["numGroupsLimitReached"]


def test_consuming_chunklet_parity(tmp_path):
    """Promoted chunklets ride the Pallas pipelines like sealed segments;
    answers match the all-host scan and the XLA device form bit-exactly."""
    from pinot_tpu.storage.mutable import MutableSegment

    schema = Schema.build(
        name="rt", dimensions=[("tag", DataType.STRING)],
        metrics=[("m", DataType.INT), ("big", DataType.LONG)])
    cfg = TableConfig(
        table_name="rt",
        chunklets=ChunkletConfig(enabled=True, rows_per_chunklet=8192,
                                 device_min_rows=8192))
    rng = np.random.default_rng(41)
    n = 20000
    tags = np.array([f"t{i:02d}" for i in range(40)])[rng.integers(0, 40, n)]
    ms = rng.integers(0, 1000, n)
    bigs = rng.integers(0, 1 << 36, n)
    rows = [{"tag": str(t), "m": int(v), "big": int(b)}
            for t, v, b in zip(tags, ms, bigs)]
    seg = MutableSegment(schema, "rt__0__0__0", cfg)
    for i in range(0, n, 8192):
        seg.index_batch(rows[i:i + 8192])
        seg.chunklet_index.promote()
    assert seg.chunklet_index.chunklets, "no chunklets promoted"

    pallas = QueryEngine(device_executor=DeviceExecutor(mm_mode="interpret"))
    xla = QueryEngine(device_executor=DeviceExecutor(
        mm_mode="interpret", pallas_mode="off"))
    host = QueryEngine(device_executor=None)
    for e in (pallas, xla, host):
        e.add_segment("rt", seg)
    for sql in (
        "SELECT tag, COUNT(*), SUM(big), MIN(m), MAX(m) FROM rt "
        "GROUP BY tag ORDER BY tag LIMIT 50",
        "SELECT DISTINCTCOUNTHLL(tag) FROM rt WHERE m < 500",
    ):
        rp, rx, rh = pallas.execute(sql), xla.execute(sql), host.execute(sql)
        assert not rp.get("exceptions"), rp
        assert rp["resultTable"]["rows"] == rx["resultTable"]["rows"], sql
        assert _rows_close(rp["resultTable"]["rows"],
                           rh["resultTable"]["rows"]), sql
    assert any(k[5] == "interpret" for k in pallas.device._pipelines)


def test_mesh_parity(tmp_path):
    """8-dev mesh: sharded Pallas pipelines combine to the same answers
    as the solo launch (psum/pmax of the same order-independent
    accumulators)."""
    from pinot_tpu.parallel.mesh import make_mesh

    assert len(jax.devices()) == 8, "conftest must provide 8 devices"
    segs, _ = _build_table(tmp_path, seed=6, n=12000, card=60)
    mesh = make_mesh(8)
    sharded = QueryEngine(device_executor=DeviceExecutor(
        mesh=mesh, mm_mode="interpret"))
    solo = QueryEngine(device_executor=DeviceExecutor(mm_mode="interpret"))
    for e in (sharded, solo):
        for s in segs:
            e.add_segment("t", s)
    for sql in (
        "SELECT d, COUNT(*), SUM(big), MIN(iv), MAX(iv) FROM t "
        "GROUP BY d ORDER BY d LIMIT 80",
        "SELECT DISTINCTCOUNTHLL(d) FROM t WHERE e != 'z'",
    ):
        rs, r1 = sharded.execute(sql), solo.execute(sql)
        assert not rs.get("exceptions"), rs
        assert rs["resultTable"]["rows"] == r1["resultTable"]["rows"], sql


def test_cohort_coalesced_parity(engines):
    """Cohort-coalesced launches (vmapped pipeline, dense form) over the
    Pallas tier equal their solo executions."""
    pallas, _, _, _ = engines
    sqls = [
        f"SELECT d, COUNT(*), SUM(iv), MIN(iv) FROM t WHERE iv > {lit} "
        "GROUP BY d ORDER BY SUM(iv) DESC, d LIMIT 10"
        for lit in (500, 2500, 4500, 6500)
    ]
    expected = [pallas.execute(s)["resultTable"]["rows"] for s in sqls]
    pallas.device.partials_cache_enabled = False
    co = pallas.device.coalescer
    co.force = True
    co.window_s = 0.05
    co.max_cohort = 4
    c0 = co.queries_coalesced
    try:
        barrier = threading.Barrier(len(sqls))
        got = [None] * len(sqls)

        def worker(i):
            barrier.wait()
            got[i] = pallas.execute(sqls[i])["resultTable"]["rows"]

        ts = [threading.Thread(target=worker, args=(i,))
              for i in range(len(sqls))]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
    finally:
        co.force = False
        pallas.device.partials_cache_enabled = True
    assert co.queries_coalesced > c0, "no query joined a cohort"
    for s, g, e in zip(sqls, got, expected):
        assert g == e, (s, g, e)


# ---------------------------------------------------------------------------
# routing: escape hatches + the quarantine XLA rung
# ---------------------------------------------------------------------------


class TestRouting:
    def test_env_kill_switch(self, engines, monkeypatch):
        pallas, _, _, _ = engines
        sql = "SELECT d, MIN(iv) FROM t GROUP BY d ORDER BY d LIMIT 7"
        want = pallas.execute(sql)["resultTable"]["rows"]
        monkeypatch.setenv("PINOT_TPU_PALLAS", "0")
        r = pallas.execute(sql)
        assert r["resultTable"]["rows"] == want
        # the forced-off execution compiled the XLA variant alongside
        assert any(k[5] == "off" for k in pallas.device._pipelines)

    def test_set_option_off_and_coexistence(self, engines):
        pallas, _, _, _ = engines
        sql = "SELECT e, MAX(iv) FROM t GROUP BY e ORDER BY e"
        r_on = pallas.execute(sql)
        r_off = pallas.execute("SET usePallas=false; " + sql)
        assert r_on["resultTable"]["rows"] == r_off["resultTable"]["rows"]
        tpls = {(k[0], k[5]) for k in pallas.device._pipelines}
        # both variants live in the cache for the same template
        both = {t for t, _m in tpls if (t, "interpret") in tpls
                and (t, "off") in tpls}
        assert both, tpls

    def test_zero_pallas_template_failure_skips_the_rung(self, tmp_path):
        """Review regression: a device failure on a template that routes
        NOTHING to the tier (scalar shape, no HLL) must take the normal
        XLA retry + host-quarantine strike path — not burn a Pallas-rung
        drop that recompiles a byte-identical pipeline and skips the
        strike."""
        from pinot_tpu.common import faults

        faults.clear()
        try:
            segs, _ = _build_table(tmp_path, seed=13, n=6000, card=20)
            eng = QueryEngine(
                device_executor=DeviceExecutor(mm_mode="interpret"))
            for s in segs:
                eng.add_segment("t", s)
            # float SUM with a filter: runs on device (a filterless
            # scalar agg answers from metadata) but is fused-ineligible
            # and scalar — zero Pallas kernels compile for it
            sql = "SELECT SUM(fv) FROM t WHERE e = 'x'"
            want = eng.execute(sql)["resultTable"]["rows"]
            faults.install(faults.Fault(point="device.launch",
                                        mode="error", times=1))
            r = eng.execute(sql + " LIMIT 1")
            assert not r.get("exceptions"), r
            assert r["resultTable"]["rows"] == want
            stats = eng.device.hbm_stats()
            assert stats["pallas_fallbacks"] == 0, stats
            assert stats["pallas_quarantined"] == 0, stats
            assert stats["device_failures"] == 1, stats
        finally:
            faults.clear()

    def test_pallas_failure_drops_to_xla_rung_on_device(self, tmp_path):
        """A device-runtime failure on a Pallas pipeline blocks only the
        Pallas rung: the launch retries the XLA scatter form ON DEVICE in
        the same call, no host-quarantine strike is recorded, and the
        (template, batch) pair keeps answering from the device."""
        from pinot_tpu.common import faults

        faults.clear()
        try:
            segs, _ = _build_table(tmp_path, seed=12, n=6000, card=30)
            eng = QueryEngine(
                device_executor=DeviceExecutor(mm_mode="interpret"))
            for s in segs:
                eng.add_segment("t", s)
            sql = ("SELECT d, SUM(iv), MIN(iv) FROM t GROUP BY d "
                   "ORDER BY d LIMIT 30")
            want = eng.execute(sql)["resultTable"]["rows"]
            dev = eng.device
            # next device launch fails once (the Pallas attempt)
            faults.install(faults.Fault(point="device.launch",
                                        mode="error", times=1))
            r = eng.execute(sql + " OFFSET 0")  # same template, fresh SQL
            assert not r.get("exceptions"), r
            assert r["resultTable"]["rows"] == want
            stats = dev.hbm_stats()
            assert stats["pallas_fallbacks"] >= 1
            assert stats["pallas_quarantined"] >= 1
            # the XLA rung kept the query ON DEVICE: no host quarantine
            assert stats["quarantined_pipelines"] == 0
            # and the rung's pipeline is the off-variant
            assert any(k[5] == "off" for k in dev._pipelines)
            # recovery: reset clears the rung; the Pallas form returns
            dev.reset_quarantine()
            assert dev.hbm_stats()["pallas_quarantined"] == 0
            r2 = eng.execute(sql)
            assert r2["resultTable"]["rows"] == want
        finally:
            faults.clear()
