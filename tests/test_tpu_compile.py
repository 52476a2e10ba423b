"""Compile the chip's kernels for a DESCRIBED TPU v5e — no chip attached.

Interpret-mode tests cannot see what Mosaic and the TPU compiler refuse:
a weak 64-bit scalar in a kernel body (the package runs with x64 on), a
layout that pads a column 128x, a reshape that relays out 800 MB. The
TPU compiler is installed with jax and compiles for a topology that is
only described, so these cases guard every later PR at no chip time:
each kernel of ops/groupby_mm.py and ops/pallas_scatter.py at the SSB
batch's real widths, and whole pipelines at the (8, 12_500_992) batch
shape chip_smoke.py serves.

Nothing runs, so nothing here says a result is right or fast — the
differential suites (test_pallas_scatter.py, test_groupby_mm.py) and
chip_smoke.py do that.

The topology is described inside a module-scoped fixture (never at
import: only one process may load the TPU library, and every xdist
worker imports every test file), and all cases live in this ONE file so
the worker that loads the library runs them all.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import pinot_tpu  # noqa: F401 — x64 on, as the package runs
from pinot_tpu.engine import device as dev
from pinot_tpu.ops import groupby_mm as mm
from pinot_tpu.ops import pallas_scatter as ps

SEG_ROWS = 12_500_000          # one SSB segment (pinot_tpu/tools/ssb.py)
ALL_ROWS = 100_000_000         # the table
BATCH = (8, 12_500_992)        # the padded (S, L) batch the executor builds
NB = BATCH[1] // 4096          # zone blocks per segment
HBM = 16 << 30


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip: keep these out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def spec(topo):
    """shape/dtype -> ShapeDtypeStruct placed on the described chip 0."""
    one_chip = SingleDeviceSharding(topo.devices[0])

    def make(shape, dtype):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype),
                                    sharding=one_chip)

    return make


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < HBM, mem
    return compiled, mem


def _compile_kernel(fn, *args):
    compiled, mem = _compile(fn, *args)
    assert "tpu_custom_call" in compiled.as_text()
    return mem


# ---- ops/groupby_mm.py: the one-hot matmul kernels, whole table -----------


def test_group_sums_100m(spec):
    _compile_kernel(
        lambda g, c: mm.group_sums(g, c, 6240, first_channel_ones=True),
        spec((ALL_ROWS,), "int32"), spec((4, ALL_ROWS), "bfloat16"))


def test_hll_registers_100m(spec):
    _compile_kernel(
        lambda s, r: mm.hll_registers(s, r, 8, 10),
        spec((ALL_ROWS,), "int32"), spec((ALL_ROWS,), "int32"))


# ---- ops/pallas_scatter.py: the scatter tier, one segment -----------------


@pytest.mark.parametrize("num_groups", [2_000, 100_000])
def test_plane_group_sums(spec, num_groups):
    _compile_kernel(
        lambda g, c: ps.plane_group_sums(g, c, num_groups,
                                         first_channel_ones=True),
        spec((SEG_ROWS,), "int32"), spec((4, SEG_ROWS), "bfloat16"))


@pytest.mark.parametrize("dtype", ["int32", "float32"])
def test_group_minmax(spec, dtype):
    _compile_kernel(
        lambda g, v: ps.group_minmax(g, v, 2_000, ("min", "max")),
        spec((SEG_ROWS,), "int32"), spec((SEG_ROWS,), dtype))


def test_hll_register_max(spec):
    _compile_kernel(
        lambda s, r: ps.hll_register_max(s, r, 4096, 22),
        spec((SEG_ROWS,), "int32"), spec((SEG_ROWS,), "int32"))


# q2_range_sum as the executor plans it on the SSB batch (captured from a
# served run): filter template, column width plan, param shapes
Q2_FILTER = (
    "and",
    ("range_dict", "lo_orderdate", "pr0", "pr1"),
    ("range_dict", "lo_discount", "pr2", "pr3"),
    ("range_raw", ("raw", "lo_quantity"), "pr4", "pr5",
     False, True, True, False),
)
Q2_WIDTHS = {
    "lo_discount": ("|u1", 0, False, ""),
    "lo_orderdate": ("<u2", 0, False, ""),
    "lo_quantity": ("|u1", 0, False, "<i4"),
    "lo_revenue": ("<i4", 0, False, ""),
}
Q2_SUM_REVENUE = ("sum", ("raw", "lo_revenue"), (3, 256))


def test_fused_filter_agg_q2_plan(spec):
    """The fused filter+gather+aggregate kernel on q2's filter. q2's own
    SUM(lo_revenue) is declined by the plan (a 4096-row block of values up
    to 6M overflows the kernel's int32 partial), so the statement itself
    never reaches the kernel; the aggregates below are the ones it takes."""
    assert ps.plan_fused(Q2_FILTER, (Q2_SUM_REVENUE,), Q2_WIDTHS) is None
    aggs = (("count", None, None),
            ("sum", ("raw", "lo_quantity"), (1, 1 << 20)),
            ("min", ("raw", "lo_revenue"), None),
            ("max", ("raw", "lo_revenue"), None))
    plan = ps.plan_fused(Q2_FILTER, aggs, Q2_WIDTHS)
    assert plan is not None
    n_cand = -(-NB // 16)  # ops/blockskip.py CAND_FRACTION
    cols = {k: spec((NB, 4096 // 128, 128), Q2_WIDTHS[k][0])
            for k in plan.cols}
    pars = {k: spec((1,), "int32") for k in plan.pred_params}
    _compile_kernel(
        lambda cand, rows, c, p: ps.fused_filter_agg(cand, rows, c, p, plan),
        spec((n_cand,), "int32"), spec((n_cand,), "int32"), cols, pars)


# ---- whole pipelines at the served batch shape -----------------------------


def test_pipeline_q1_groupby_real_batch(spec):
    """q1_scan_agg as DeviceExecutor() builds it on a TPU (mm_mode and
    pallas_mode both "tpu"): SUM(lo_revenue) GROUP BY lo_suppkey."""
    template = ("groupby", ("true",), ("lo_suppkey",), (2000,),
                (Q2_SUM_REVENUE,), 0, False)
    widths = {"lo_revenue": ("<i4", 0, False, ""),
              "lo_suppkey": ("<u2", 0, False, "")}
    fn = dev.build_pipeline(template, mm_mode="tpu", sorted_hll_ok=True,
                            widths=widths, pallas_mode="tpu")
    cols = {"lo_revenue": spec(BATCH, "int32"),
            "lo_suppkey": spec(BATCH, "uint16")}
    params = {"off0": spec((), "int64"), "ps_alive": spec((8,), "bool")}
    compiled, _ = _compile(fn, cols, spec((8,), "int32"), params)
    assert "tpu_custom_call" in compiled.as_text()


def test_pipeline_q2_blockskip_real_batch(spec):
    """q2_range_sum's block-skip pipeline. The candidate gather must slice
    blocks out of the (S, L) columns: a (S*NB, 4096) reshape is a relayout
    on the TPU (segments sit on sublanes) — it cost 221 s of compile and a
    400 MB copy of lo_revenue per query before ops/blockskip.py stopped
    doing it. The temp bound is what catches its return."""
    template = ("agg", Q2_FILTER, (), (), (Q2_SUM_REVENUE,), 0, False)
    fn = dev.build_pipeline(template, mm_mode="tpu", sorted_hll_ok=True,
                            blockskip=True, widths=Q2_WIDTHS,
                            pallas_mode="tpu")
    cols = {k: spec(BATCH, w[0]) for k, w in Q2_WIDTHS.items()}
    for k in ("lo_discount", "lo_orderdate", "lo_quantity"):
        cols["zlo::" + k] = spec((8, NB), Q2_WIDTHS[k][0])
        cols["zhi::" + k] = spec((8, NB), Q2_WIDTHS[k][0])
    params = {"off0": spec((), "int64"), "ps_alive": spec((8,), "bool"),
              **{f"pr{i}": spec((), "int32") for i in range(4)},
              "pr4": spec((), "int64"), "pr5": spec((), "int64")}
    _, mem = _compile(fn, cols, spec((8,), "int32"), params)
    assert mem.temp_size_in_bytes < 64 << 20, mem


# ---- the dense group-by over the batch's prepared operands (ISSUE 30) ------

# the benchmark's banded group-by (benchmark/traffic/groupby_bands_c4.json)
# as the executor plans it on the SSB batch
BANDS_TEMPLATE = ("groupby", ("range_dict", "lo_discount", "pr0", "pr1"),
                  ("lo_suppkey",), (2000,), (Q2_SUM_REVENUE,), 0, False)
BANDS_WIDTHS = {"lo_revenue": ("<i4", 0, False, ""),
                "lo_suppkey": ("<u2", 0, False, ""),
                "lo_discount": ("|u1", 0, False, "")}
N_BATCH = BATCH[0] * BATCH[1]  # a whole number of superblocks: no padding


def _big_results(hlo_text: str, op: str = "", rows: int = 0) -> list:
    """(dtype, instruction line) of every non-parameter instruction whose
    result has at least one element a row of the batch (``rows``: of
    another batch than the 8-segment one); ``op`` keeps one kind of
    instruction."""
    import re

    out = []
    for line in hlo_text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = (\w+)\[([\d,]+)\]", line)
        if not m or " parameter(" in line or (op and f" {op}(" not in line):
            continue
        n = 1
        for d in m.group(2).split(","):
            n *= int(d)
        if n >= (rows or N_BATCH):
            out.append((m.group(1), line.strip()[:160]))
    return out


def _assert_split_relayout(hlo_text: str, n: int):
    """``n`` (S, L) -> lanes relayouts, each the layout-changing copy of
    the (8, 97664, 128) view that ops/groupby_mm.py _to_lanes asks for,
    and no row-scale ``reshape`` instruction: that is the one-step
    flatten, which costs the compiler 17 s at 32 bits and minutes at 8."""
    assert not _big_results(hlo_text, "reshape")
    copies = _big_results(hlo_text, "copy")
    assert len(copies) == n and all(
        "[8,97664,128]" in line for _dt, line in copies), copies


@pytest.mark.parametrize("width", [1, 2, 4])
def test_pipeline_prepared_groupby_real_batch(spec, width):
    """The cell's statement over prepared operands, alone and as a cohort
    of two and of four: a launch computes the mask, relays it out and
    calls the kernel. No byte planes, ones channel or masked ids are
    written (nothing row-scale in bf16 or 64 bits), the value and key
    columns are not read, and the one (S, L) -> lanes relayout left is
    the mask's (alone: at one byte a row; in a cohort XLA relays the
    filter's column out once, widened, and compares in lanes), written
    as the split of L (ops/groupby_mm.py _to_lanes): the one-step flatten
    of an 8-bit plane costs this compiler three to four minutes."""
    prepared = dev.plan_prepared_groupby(
        BANDS_TEMPLATE, BANDS_WIDTHS, N_BATCH, "tpu", "tpu", {0: 100})
    assert prepared == ("pallas", ("gk::lo_suppkey",),
                        ((0, "gv::lo_revenue::100::3", 3),))
    fn = dev.build_pipeline(BANDS_TEMPLATE, mm_mode="tpu",
                            sorted_hll_ok=True, widths=BANDS_WIDTHS,
                            pallas_mode="tpu", prepared=prepared)
    cols = {"lo_revenue": spec(BATCH, "int32"),
            "lo_suppkey": spec(BATCH, "uint16"),
            "lo_discount": spec(BATCH, "uint8"),
            "gk::lo_suppkey": spec((N_BATCH // 128, 128), "uint16"),
            "gv::lo_revenue::100::3": spec((3, N_BATCH // 128, 128), "uint8")}
    params = {"off0": spec((), "int64"), "ps_alive": spec((8,), "bool"),
              "pr0": spec((), "int32"), "pr1": spec((), "int32")}
    if width > 1:
        params = {k: spec((width,) + v.shape, v.dtype)
                  for k, v in params.items()}
        solo = fn

        def fn(c, nd, pstack):
            return jax.vmap(lambda p: solo(c, nd, p))(pstack)

    compiled, mem = _compile(fn, cols, spec((8,), "int32"), params)
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1
    # jit drops the (S, L) value and key columns: nothing reads them
    assert mem.argument_size_in_bytes < 620 << 20, mem
    big = _big_results(text)
    assert not [b for b in big if b[0] in ("bf16", "s64", "u64", "f64")], big
    _assert_split_relayout(text, 1)


@pytest.mark.parametrize("name", ["prepared_ids", "prepared_planes"])
def test_prepared_operand_builders_real_batch(spec, name):
    """The once-a-batch builders: one relayout each, by the split of L."""
    if name == "prepared_ids":
        compiled, _ = _compile(
            lambda c, nd: mm.prepared_ids(c, nd, num_groups=2000),
            spec(BATCH, "uint16"), spec((8,), "int32"))
    else:
        compiled, _ = _compile(
            lambda c: mm.prepared_planes(c, delta=-100, nplanes=3),
            spec(BATCH, "int32"))
    _assert_split_relayout(compiled.as_text(), 1)


# ---- the narrowed key space (ISSUE 32): SSB Q3.2 and Q4.3, flat -------------

# Q3.2 and Q4.3 of benchmark/traffic/ssb_flat_13q_c4.json as the executor
# plans them on ssb_sf100_chipshare's batch (captured from a served run at
# the tiny size; the cardinalities are SF100's)
FLAT_BATCH = (3, BATCH[1])     # 3 segments of 12,500,000 rows, padded
FLAT_NB = NB
NARROWED = {
    "q3_2": dict(
        template=(
            "groupby_narrow",
            ("and", ("eq_dict", "c_nation", "pr0"),
             ("eq_dict", "s_nation", "pr1"),
             ("range_dict", "d_year", "pr2", "pr3")),
            ("c_city", "s_city", "d_year"), (250, 250, 7),
            (("sum", ("raw", "lo_revenue"), (3, None)),), 4096, False),
        trim=(1024, (("col", 2, True), ("agg", 0, "sum", False))),
        widths={"c_city": ("|u1", 0, False, ""),
                "c_nation": ("|u1", 0, False, ""),
                "d_year": ("|u1", 0, False, ""),
                "lo_revenue": ("<i4", 0, False, ""),
                "s_city": ("|u1", 0, False, ""),
                "s_nation": ("|u1", 0, False, "")},
        zones=("c_nation", "d_year", "s_nation"), offsets={0: 81_000},
        params={"pr0": ((), "int32"), "pr1": ((), "int32"),
                "pr2": ((), "int32"), "pr3": ((), "int32")}),
    "q4_3": dict(
        template=(
            "groupby_narrow",
            ("and", ("eq_dict", "c_region", "pr0"),
             ("eq_dict", "s_nation", "pr1"), ("in_dict", "d_year", "pr2", 2),
             ("eq_dict", "p_category", "pr3")),
            ("d_year", "s_city", "p_brand1"), (7, 250, 1000),
            (("sum", ("minus", ("raw", "lo_revenue"),
                      ("raw", "lo_supplycost")), (3, None)),), 4096, False),
        trim=(1024, (("col", 0, True), ("col", 1, True), ("col", 2, True))),
        widths={"c_region": ("|u1", 0, False, ""),
                "d_year": ("|u1", 0, False, ""),
                "lo_revenue": ("<i4", 0, False, ""),
                "lo_supplycost": ("<u2", 0, True, "<i4"),
                "p_brand1": ("<u2", 0, False, ""),
                "p_category": ("|u1", 0, False, ""),
                "s_city": ("|u1", 0, False, ""),
                "s_nation": ("|u1", 0, False, "")},
        zones=("c_region", "d_year", "p_category", "s_nation"),
        offsets={0: -44_941},
        params={"pr0": ((), "int32"), "pr1": ((), "int32"),
                "pr2": ((2,), "int32"), "pr3": ((), "int32"),
                "fo::lo_supplycost": ((), "int32")}),
}
N_FLAT = FLAT_BATCH[0] * FLAT_BATCH[1]
N_FLAT_PAD = -(-N_FLAT // mm.SUPERBLOCK) * mm.SUPERBLOCK


def _prepared_case(spec, case):
    """(plan, cols with the plan's operands beside the (S, L) planes)."""
    template, widths = case["template"], case["widths"]
    prepared = dev.plan_prepared_groupby(
        template, widths, N_FLAT, "tpu", "tpu", case["offsets"])
    assert prepared is not None and prepared[0] == "pallas"
    cols = {k: spec(FLAT_BATCH, w[0]) for k, w in widths.items()}
    for key, col in zip(prepared[1], template[2]):
        cols[key] = spec((N_FLAT_PAD // 128, 128), widths[col][0])
    for _i, key, nplanes in prepared[2]:
        cols[key] = spec((nplanes, N_FLAT_PAD // 128, 128), "uint8")
    return prepared, cols


# the first answer of these took the chip's host 304 to 366 s (PR 31): the
# trim's sort over the whole cartesian table (437,500 and 1,750,000 cells;
# compiled for the described v5e in this sandbox: 32 s at 7,000 entries,
# 160 s at 16,384, 22 minutes at 437,500). The narrowed table is 4,096
# entries: either program compiles here in 18 to 37 s beside the driver's
# five other test workers. The limit is three times that, and leaves no
# room for the sort's return.
NARROWED_COMPILE_LIMIT_S = 120


@pytest.mark.parametrize("width", [1, 2])
@pytest.mark.parametrize("operands", ["perLaunch", "prepared"])
@pytest.mark.parametrize("name", list(NARROWED))
def test_pipeline_narrowed_groupby_real_batch(spec, name, operands, width):
    """The executor's own programs: solo as it launches it (the block-skip
    form, its dense branch under lax.cond, then trim and pack) and the
    cohort of two (the dense form under vmap, trimmed member by member:
    the batched form of the trim's sort took this compiler 398 s at 4,096
    entries). Two kernel calls a branch (blocks, then slots), no table of
    the cartesian product, and a compile inside the limit. ``prepared``
    (ISSUE 33): both passes of the dense branch read the batch's operands
    - pass 1 shifts the combined id to its block in VMEM, pass 2 compares
    it against the live table; the solo form's gathered branch keeps the
    per-launch preparation."""
    import time

    case = NARROWED[name]
    template, widths = case["template"], case["widths"]
    cols = {k: spec(FLAT_BATCH, w[0]) for k, w in widths.items()}
    prepared = None
    if operands == "prepared":
        prepared, cols = _prepared_case(spec, case)
    executor = dev.DeviceExecutor(mm_mode="tpu", pallas_mode="tpu")
    entry = executor._pipeline_entry(
        template, template[4], False, True, widths,
        tuple(sorted(widths.items())), case["trim"], "tpu", prepared)
    n_seg = FLAT_BATCH[0]
    params = {"off0": spec((), "int64"), "ps_alive": spec((n_seg,), "bool"),
              "tr_k": spec((), "int32"),
              **{k: spec(*v) for k, v in case["params"].items()}}
    if width == 1:
        fn = entry["pipeline"]
        for k in case["zones"]:
            cols["zlo::" + k] = spec((n_seg, FLAT_NB), widths[k][0])
            cols["zhi::" + k] = spec((n_seg, FLAT_NB), widths[k][0])
    else:
        fn = executor._cohort_pipeline(entry)[0]
        params = {k: spec((width,) + v.shape, v.dtype)
                  for k, v in params.items()}
        params["__member__"] = spec((width,), "int32")
    t0 = time.perf_counter()
    compiled = fn.lower(cols, spec((n_seg,), "int32"), params).compile()
    seconds = time.perf_counter() - t0
    mem = compiled.memory_analysis()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == (4 if width == 1 else 2)
    cells = 1
    for c in template[3]:
        cells *= c
    assert f"[{cells}]" not in text and f"[{cells + 1}]" not in text
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 4 << 30, mem
    assert seconds < NARROWED_COMPILE_LIMIT_S, seconds


# ---- multi-key and expression group-bys over prepared operands (ISSUE 33) ---

# Q3.1 and Q4.2 of the flat mix as the executor plans them: dense, three key
# columns at one byte each; Q4.2 sums lo_revenue - lo_supplycost
PREPARED_DENSE = {
    "q3_1": dict(
        template=(
            "groupby",
            ("and", ("eq_dict", "c_region", "pr0"),
             ("eq_dict", "s_region", "pr1"),
             ("range_dict", "d_year", "pr2", "pr3")),
            ("c_nation", "s_nation", "d_year"), (25, 25, 7),
            (("sum", ("raw", "lo_revenue"), (3, None)),), 0, False),
        widths={"c_nation": ("|u1", 0, False, ""),
                "c_region": ("|u1", 0, False, ""),
                "d_year": ("|u1", 0, False, ""),
                "lo_revenue": ("<i4", 0, False, ""),
                "s_nation": ("|u1", 0, False, ""),
                "s_region": ("|u1", 0, False, "")},
        offsets={0: 81_000},
        params={"pr0": ((), "int32"), "pr1": ((), "int32"),
                "pr2": ((), "int32"), "pr3": ((), "int32")}),
    "q4_2": dict(
        template=(
            "groupby",
            ("and", ("eq_dict", "c_region", "pr0"),
             ("eq_dict", "s_region", "pr1"), ("in_dict", "d_year", "pr2", 2),
             ("in_dict", "p_mfgr", "pr3", 2)),
            ("d_year", "s_nation", "p_category"), (7, 25, 25),
            (("sum", ("minus", ("raw", "lo_revenue"),
                      ("raw", "lo_supplycost")), (3, None)),), 0, False),
        widths={"c_region": ("|u1", 0, False, ""),
                "d_year": ("|u1", 0, False, ""),
                "lo_revenue": ("<i4", 0, False, ""),
                "lo_supplycost": ("<u2", 0, True, "<i4"),
                "p_category": ("|u1", 0, False, ""),
                "p_mfgr": ("|u1", 0, False, ""),
                "s_nation": ("|u1", 0, False, ""),
                "s_region": ("|u1", 0, False, "")},
        offsets={0: -44_941},
        params={"pr0": ((), "int32"), "pr1": ((), "int32"),
                "pr2": ((2,), "int32"), "pr3": ((2,), "int32"),
                "fo::lo_supplycost": ((), "int32")}),
}
@pytest.mark.parametrize("width", [1, 2])
@pytest.mark.parametrize("name", list(PREPARED_DENSE))
def test_pipeline_prepared_multikey_real_batch(spec, name, width):
    """Q3.1 and Q4.2 over prepared operands: one id ref a key column,
    widened, multiplied and masked in VMEM (mixed widths and the int32
    multiply lower), the expression's planes read as a column's are.
    One kernel call, nothing row-scale in bf16 or 64 bits, and the
    mask's relayout the only one."""
    case = PREPARED_DENSE[name]
    prepared, cols = _prepared_case(spec, case)
    assert len(prepared[1]) == 3 and len(prepared[2]) == 1
    if name == "q4_2":
        assert prepared[2][0][1] == \
            "gv::minus(lo_revenue,lo_supplycost)::-44941::3"
    fn = dev.build_pipeline(case["template"], mm_mode="tpu",
                            sorted_hll_ok=True, widths=case["widths"],
                            pallas_mode="tpu", prepared=prepared)
    n_seg = FLAT_BATCH[0]
    params = {"off0": spec((), "int64"), "ps_alive": spec((n_seg,), "bool"),
              **{k: spec(*v) for k, v in case["params"].items()}}
    if width > 1:
        params = {k: spec((width,) + v.shape, v.dtype)
                  for k, v in params.items()}
        solo = fn

        def fn(c, nd, pstack):
            return jax.vmap(lambda p: solo(c, nd, p))(pstack)

    compiled, _mem = _compile(fn, cols, spec((n_seg,), "int32"), params)
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1
    big = _big_results(text, rows=N_FLAT)
    assert not [b for b in big if b[0] in ("bf16", "s64", "u64", "f64")], big
    assert not _big_results(text, "reshape", rows=N_FLAT)


def test_expression_planes_builder_real_batch(spec):
    """The once-a-batch builder of lo_revenue - lo_supplycost's planes:
    the expression over the stored planes, one relayout, the byte split."""
    case = PREPARED_DENSE["q4_2"]
    widths = case["widths"]
    leaves = ("lo_revenue", "lo_supplycost")
    compiled, _ = _compile(
        lambda c, fo: dev._expr_planes(
            c, fo, argt=case["template"][4][0][1],
            wsig=tuple((k, widths[k]) for k in leaves), off=-44_941,
            nplanes=3),
        {k: spec(FLAT_BATCH, widths[k][0]) for k in leaves},
        {"fo::lo_supplycost": spec((), "int32")})
    text = compiled.as_text()
    assert not _big_results(text, "reshape", rows=N_FLAT)
    copies = _big_results(text, "copy", rows=N_FLAT)
    assert len(copies) == 1 and "3,97664,128]" in copies[0][1], copies


# ---- the full key space (ISSUE 36): the ranking mix's two largest ----------

# supp_shipmode_disc (1.4M cells, a filter, SUM and COUNT) and
# year_city_brand_profit (1.75M cells, SUM of a - b) of
# benchmark/traffic/ssb_fullkeys_6q_c4.json as the executor plans them on
# ssb_sf100_fullkeys's batch (captured from a served run at the tiny size;
# the cardinalities are SF100's), trimmed as a server's partial is: 8,192
# rows kept by the selection.
FULL = {
    "supp_shipmode_disc": dict(
        template=(
            "groupby_full", ("range_dict", "lo_discount", "pr0", "pr1"),
            ("lo_suppkey", "lo_shipmode"), (200_000, 7),
            (("sum", ("raw", "lo_revenue"), (3, None)),
             ("count", None, None)), 0, False),
        widths={"lo_discount": ("|u1", 0, False, ""),
                "lo_revenue": ("<i4", 0, False, ""),
                "lo_shipmode": ("|u1", 0, False, ""),
                "lo_suppkey": ("<i4", 0, False, "")},
        fcols=("lo_discount",), value="gv::lo_revenue::81000::3",
        params={"pr0": ((), "int32"), "pr1": ((), "int32")}),
    "year_city_brand_profit": dict(
        template=(
            "groupby_full", ("true",),
            ("d_year", "s_city", "p_brand1"), (7, 250, 1000),
            (("sum", ("minus", ("raw", "lo_revenue"),
                      ("raw", "lo_supplycost")), (3, None)),), 0, False),
        widths={"d_year": ("|u1", 0, False, ""),
                "lo_revenue": ("<i4", 0, False, ""),
                "lo_supplycost": ("<u2", 0, True, "<i4"),
                "p_brand1": ("<u2", 0, False, ""),
                "s_city": ("|u1", 0, False, "")},
        fcols=(), value="gv::minus(lo_revenue,lo_supplycost)::-44941::3",
        params={"fo::lo_supplycost": ((), "int32")}),
}
FULL_TRIM = (8192, (("agg", 0, "sum", False),), "select")
# either program compiles here in 6 to 8 s alone: a cumulative sum a
# channel, the boundary reads, the selection's 64 counting passes and its
# pairwise ranking of 8,192 survivors. The sort at table length that the
# selection replaces took this compiler 22 minutes at 437,500 entries; the
# limit leaves no room for its return.
FULL_COMPILE_LIMIT_S = 90


def _full_pipeline(spec, name, plane_bits, slot_rows):
    """(compiled program, its HLO text, seconds to build, cells) of the
    executor's own pipeline for FULL[name] over the flat batch, its
    projected planes ordered (``slot_rows`` 0) or laid out cell by slot."""
    import time

    from pinot_tpu.engine.params import BatchContext
    from pinot_tpu.ops import keysorted as ks

    case = FULL[name]
    template, widths = case["template"], case["widths"]
    cells = 1
    for c in template[3]:
        cells *= c
    keys = ",".join(template[2])
    as_built = BatchContext.slotted_key if slot_rows else (lambda k: k)
    plane = (slot_rows, ks.slot_lanes(cells)) if slot_rows \
        else (N_FLAT_PAD // 128, 128)
    fcols = tuple((c, as_built(f"gp::{keys}::{c}")) for c in case["fcols"])
    planes = ((0, as_built(f"gp::{keys}::" + case["value"]), 3),)
    seg_key = as_built(f"gp::{keys}::seg")
    prepared = ("keysorted", (), (), (
        keys, "gs::" + keys, seg_key, fcols, planes, plane_bits, slot_rows))
    cols = {k: spec(FLAT_BATCH, w[0]) for k, w in widths.items()}
    cols["gs::" + keys] = spec((cells + 1,), "int32")
    cols[seg_key] = spec(plane, "uint8")
    for c, key in fcols:
        cols[key] = spec(plane, widths[c][0])
    cols[planes[0][1]] = spec(plane, "uint32")
    executor = dev.DeviceExecutor(mm_mode="tpu", pallas_mode="tpu")
    entry = executor._pipeline_entry(
        template, template[4], False, False, widths,
        tuple(sorted(widths.items())), FULL_TRIM, "tpu", prepared)
    n_seg = FLAT_BATCH[0]
    params = {"off0": spec((), "int64"), "ps_alive": spec((n_seg,), "bool"),
              "tr_k": spec((), "int32"),
              **{k: spec(*v) for k, v in case["params"].items()}}
    t0 = time.perf_counter()
    compiled = entry["pipeline"].lower(
        cols, spec((n_seg,), "int32"), params).compile()
    seconds = time.perf_counter() - t0
    mem = compiled.memory_analysis()
    text = compiled.as_text()
    assert "tpu_custom_call" not in text
    assert not _big_results(text, "sort", rows=cells)
    assert not _big_results(text, "scatter", rows=N_FLAT)
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 2 << 30, mem
    assert seconds < FULL_COMPILE_LIMIT_S, seconds
    return compiled, text, seconds, cells


@pytest.mark.parametrize("plane_bits", [24, 8])
@pytest.mark.parametrize("name", list(FULL))
def test_pipeline_full_groupby_real_batch(spec, name, plane_bits):
    """The executor's own program for a full key space whose planes stay
    in key order (a skewed key): the filter over the projected planes, the
    channels' cumulative sums read at the cells' boundaries, the table
    over the key space, the selection, the pack. No kernel call, no sort,
    no scatter at the rows' length, and a compile inside the limit.
    ``plane_bits`` 24: the mix's own (no cell holds 256 rows); 8: a batch
    whose fullest cell holds 65,536 or more."""
    _compiled, text, _seconds, cells = _full_pipeline(
        spec, name, plane_bits, 0)
    assert _big_results(text, "reduce-window", rows=N_FLAT)  # the sums
    assert _big_results(text, "gather", rows=cells)


# the fullest cell's rows of the mix's two largest statements at 37.5M
# uniform rows, to the sublane tile (PERF.md, PR 37: 56 of a mean 27 at
# 1.4M cells, 48 of 21 at 1.75M): 78M and 84M slots, x2.1 and x2.2
FULL_SLOT_ROWS = {"supp_shipmode_disc": 56, "year_city_brand_profit": 48}


@pytest.mark.parametrize("name", list(FULL))
def test_pipeline_full_groupby_slotted_real_batch(spec, name):
    """The same two statements as the mix's batch lays them out, cell by
    slot: the filter over the (K, cells) planes, the channels summed down
    the slot axis. No cumulative sum at the rows' length and no gather at the
    cells', beside what the ordered program has not either; built in
    5 to 10 s here (5.5 and 5.3 s, this sandbox, PR 37), as the ordered
    program is."""
    from pinot_tpu.ops import keysorted as ks

    k = FULL_SLOT_ROWS[name]
    compiled, text, seconds, cells = _full_pipeline(spec, name, 24, k)
    assert k * ks.slot_lanes(cells) <= dev.FULL_SLOT_PADDING * N_FLAT_PAD
    # (the selection's compaction keeps its cumulative sum over the cells)
    assert not _big_results(text, "reduce-window", rows=N_FLAT)
    assert not _big_results(text, "gather", rows=cells)
    # the planes are read once: no copy of one is made at its length
    assert not _big_results(text, "copy", rows=k * cells)
    print(f"slotted {name}: built in {seconds:.1f} s")


def test_key_order_projections_real_batch(spec):
    """The once-a-batch projections of the full regime's operands into the
    key order: one relayout (ops/groupby_mm.py _to_lanes's split of L:
    0.2 s here, never the one-step flatten's minutes: PR 30) and one
    gather each. The order itself is numpy's, on the host: the device's
    sort of 37.5M rows took this compiler 25 s to build, which beside the
    regime's own program did not fit a statement's 60 s (PERF.md, PR 36)."""
    import time

    from pinot_tpu.ops import keysorted as ks

    lanes = (N_FLAT_PAD // 128, 128)
    t0 = time.perf_counter()
    _compile(ks.project_plane, spec(FLAT_BATCH, "uint8"),
             spec((N_FLAT_PAD,), "int32"))
    _compile(ks.project_value, spec((3,) + lanes, "uint8"),
             spec((N_FLAT_PAD,), "int32"))
    _compile(lambda a, b: ks._cartesian((a, b), cards=(200_000, 7)),
             spec(lanes, "int32"), spec(lanes, "uint8"))
    # and a projected plane laid out cell by slot (1.75M cells x 48): one
    # gather more, its index made in the program and kept nowhere
    for dtype, width in (("uint32", 4), ("uint8", 1)):
        _compiled, mem = _compile(
            lambda o, st: ks.slot_plane(o, st, k=48),
            spec(lanes, dtype), spec((1_750_001,), "int32"))
        assert mem.output_size_in_bytes \
            >= 48 * ks.slot_lanes(1_750_000) * width
    assert time.perf_counter() - t0 < 90


def test_live_block_count_real_batch(spec):
    """A template's one count of its live 128-cell blocks (pass 1 alone,
    by the XLA scatter): supp_shipmode_disc's, 10,938 blocks. Seconds to
    build, where the narrowed pipeline it spares a full template is tens."""
    import time

    case = FULL["supp_shipmode_disc"]
    template, widths = case["template"], case["widths"]
    keys = ("lo_discount",) + template[2]
    t0 = time.perf_counter()
    _compile(lambda c, nd, p: dev._live_blocks(
        c, nd, p, filter_tpl=template[1], group_cols=template[2],
        group_cards=template[3],
        wsig=tuple(sorted((k, widths[k]) for k in keys))),
        {k: spec(FLAT_BATCH, widths[k][0]) for k in keys},
        spec((FLAT_BATCH[0],), "int32"),
        {k: spec(*v) for k, v in case["params"].items()})
    assert time.perf_counter() - t0 < 30
