"""Pallas scatter-kernel tier: tiled local-accumulate group-by, HLL
register-max, and fused filter+gather+aggregate (ISSUE 15).

The r05 micro table's standing indictment was the scatter family:
``masked_sum`` saturates HBM (822 GB/s on v5e) while ``scatter_group_sum``
runs at 0.7 GB/s, ``hll_register_scatter`` at 1.2 and the sorted HLL
dedup at 2.1 — XLA lowers ``.at[].add/.max`` on TPU to a serialized
scatter loop, so exactly the ops that decide high-cardinality group-bys
and HLL queries ran ~400x under the roofline. This module replaces those
scatters with purpose-built Pallas kernels following the pattern
``ops/groupby_mm.py`` proved: ``pl.pallas_call`` with TPU params on
device, **interpret mode under JAX_PLATFORMS=cpu** so tier-1 tests
exercise the real kernels, and the XLA scatter path kept compiled-in as
the differential reference and fallback (engine/device.py routes a
failing Pallas pipeline to the XLA rung, then host — never an error).

Three kernels:

1. **Tiled local-accumulate group scatter** (``plane_group_sums``): each
   program instance owns a *group-range partition* of the output
   accumulators; row tiles stream through every partition and accumulate
   locally in VMEM via a partition-relative hi/lo factored one-hot
   matmul (the MXU contraction of ops/groupby_mm.py, generalized), one
   HBM write per partition per superblock — no global sort, no serial
   scatter. Partitioning bounds the VMEM accumulator regardless of G:
   npart passes over the row stream trade bandwidth for unbounded group
   counts, extending the exact plane-sum coverage past the single-
   accumulator ceiling ``mm_supported`` enforces.
2. **Group min/max scatter** (``group_minmax``): the aggregation family
   with no MXU identity (max doesn't factor through a dot) — a masked
   broadcast-select over the partition's group range with a VPU lane
   reduction. O(span) work per row bounds it to moderate G, where the
   XLA scatter was slowest per row.
3. **HLL register-max scatter** (``hll_register_max``): rho-threshold
   indicator channels built in-kernel from the lane-major rho operand
   (groupby_mm's rho_mode), accumulated as *presence* (f32 counts —
   nonneg adds keep every touched slot >= 1 under rounding, so presence
   is exact) over slot-range partitions, registers extracted at flush.
   Replaces the serialized f32 scatter-max for slot spaces up to
   ``HLL_MAX_SLOTS``; beyond that the threshold-channel work per row
   grows linearly with the slot space and the sorted dedup basis
   (ops/radix_groupby.py) remains the right algorithm.
4. **Fused filter+gather+aggregate** (``fused_filter_agg``): the
   block-skip path's candidate blocks are gathered BY THE PIPELINE —
   scalar-prefetched candidate indices drive the BlockSpec index maps,
   so the kernel's DMA engine reads exactly the candidate blocks from
   HBM and the filter + aggregation run in VMEM; the (B, R) gather
   buffer the XLA path materializes (one extra HBM write + read of
   every gathered byte) never exists.

Exactness: every accumulation is order-independent by construction —
integer sums ride bf16 byte planes with f32 superblock partials reduced
in f64 outside (the groupby_mm argument), min/max/presence are
idempotent — so Pallas == XLA scatter == host is bit-exact, which is
what lets the differential suite (tests/test_pallas_scatter.py) pin the
tier against the compiled-in reference.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# ONE copy of the MXU-kernel tuning machinery: the VMEM/transient budgets
# and the block-size planner live in ops/groupby_mm.py (already
# re-measured and retuned there once) — a retune must reach both kernel
# tiers, so this module imports rather than restating them
from pinot_tpu.ops import groupby_mm as mm
from pinot_tpu.ops.groupby_mm import (  # noqa: F401 — re-exported budgets
    _plan_blk as _mm_plan_blk,
    BLK,
    MAX_ACC_CELLS,
    MAX_CHANNELS,
    NINNER,
    STACK_MAX_BYTES,
    SUPERBLOCK,
    TRANSIENT_BUDGET,
)

LO = 128                 # low-radix factor: the dot's N dim = one lane tile
NARROW_CHUNK = 32        # hi-table rows the narrowed kernel takes at a time
MAX_PARTITIONS = 8       # row re-reads per launch: npart passes over the
                         # tile stream bound the bandwidth trade
PALLAS_MIN_ROWS = 1 << 17  # below this the scatter's fixed cost wins (the
                           # MM_MIN_ROWS analog; interpret mode ignores it)

# min/max scatter: O(span) VPU work per row — profitable only against the
# serialized XLA scatter at moderate group counts
MINMAX_SPAN = 1024       # groups per partition (one-hot select width)
MINMAX_BLK = 2048        # rows per step (bounds the (span, blk) transient)
MAX_MINMAX_PARTS = 8     # → num_groups <= 8191

# HLL register-max: threshold-channel cost per row grows with the slot
# space (ceil(nrho*hpad/128) MXU cycles/row) — past this bound the sorted
# dedup basis wins and the kernel declines (env-tunable for bigger VMEM
# parts)
HLL_MAX_SLOTS = int(os.environ.get("PINOT_TPU_PALLAS_HLL_SLOTS", 1 << 12))

# fused filter+gather+aggregate
FUSED_BLOCK_ROWS = 4096  # rows per grid step; the fused plan is only
                         # built when storage.segment.ZONE_BLOCK_ROWS
                         # equals this (engine/device.py build_pipeline
                         # declines otherwise — a silent mismatch would
                         # read a prefix of every candidate block)
FUSED_MAX_IN = 8         # IN-list bound for the in-kernel OR chain
_i32 = jnp.int32
_NT = (((1,), (1,)), ((), ()))  # contract lanes-with-lanes (rows axis)


def _hpad_total(num_groups: int) -> int:
    """hi rows covering ``num_groups`` ids plus the overflow slot
    (masked/padded rows carry id == num_groups), in sublane multiples."""
    return max(8, ((num_groups // LO + 1 + 7) // 8) * 8)


def _span_hpad(a_real: int) -> int:
    """Per-partition hi-row budget from the VMEM accumulator cap."""
    h = MAX_ACC_CELLS // (a_real * LO)
    return max(8, (h // 8) * 8)


def _plan_blk(a_real: int, hpad: int):
    """(blk, ninner, stacked): ops/groupby_mm.py's planner with the
    radix fixed at LO — shrinks the row tile until the one-hot /
    stacked-channel transients fit the shared budget."""
    return _mm_plan_blk(a_real, hpad, LO)


def _vmem_limit(a_real: int, hpad: int, blk: int, stacked: bool) -> int:
    acc_bytes = a_real * hpad * LO * 4
    chh_rows = a_real * hpad if stacked else hpad
    transient_bytes = (LO + hpad + chh_rows) * blk * 2
    return max(16 * 2**20,
               min(110 * 2**20, 8 * acc_bytes + transient_bytes + 16 * 2**20))


def _pad_lane(x, n_pad: int, n: int, fill):
    if n_pad > n:
        x = jnp.concatenate(
            [x, jnp.full(n_pad - n, fill, dtype=x.dtype)])
    return x.reshape(-1, 128)


def _rel_onehots(ids_r, p, gp: int, hpad: int, blk: int):
    """Partition-relative factored one-hots: rows outside [p*gp, (p+1)*gp)
    map to the sentinel gp, whose hi row (== hpad) matches no iota row —
    out-of-partition rows contribute nothing, which is what makes the
    partition sweep a disjoint cover of the group space."""
    # every scalar entering the body is typed int32: the package runs with
    # x64 on, where a bare Python int becomes a weak 64-bit value that
    # Mosaic cannot convert
    gp = _i32(gp)
    rel = ids_r - p * gp
    rel = jnp.where((rel >= _i32(0)) & (rel < gp), rel, gp)
    lo_r = rel & _i32(LO - 1)
    hi_r = rel >> _i32(7)  # LO = 128
    jsub = jax.lax.broadcasted_iota(jnp.int32, (LO, blk), 0)
    oh_loT = jnp.where(lo_r == jsub, jnp.float32(1), jnp.float32(0)) \
        .astype(jnp.bfloat16)
    hsub = jax.lax.broadcasted_iota(jnp.int32, (hpad, blk), 0)
    oh_hi = jnp.where(hi_r == hsub, jnp.float32(1), jnp.float32(0)) \
        .astype(jnp.bfloat16)
    return oh_loT, oh_hi


def _narrow_kernel(*refs, ninner: int, hpad: int, a_real: int, blk: int,
                   ones_first: bool, prepared=None):
    """The plane-sum kernel over a NARROWED key space, one row tile: hi row
    ``h`` matches the rows whose 128-cell block (``id >> 7``) is
    ``tab_ref[h]``, so the accumulator holds only the blocks the table
    lists. A block that is not listed (a masked row's sentinel, a pad row)
    matches no row. The table is ascending with -1 past its last block
    and is taken NARROW_CHUNK rows at a time: a chunk that lists no block
    is skipped, so the tile costs what the live blocks cost and not what
    the table could hold (3 of 4 chunks skipped for SSB Q3.2's 15 live
    blocks). Channels are stacked into one dot a chunk, as the dense
    form stacks them. Operands as ``_sums_kernel``'s, the table between
    the ids (and mask) and the channels."""
    out_ref, acc_ref = refs[-2:]
    i = pl.program_id(2)

    @pl.when(i == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    if prepared is None:
        ids_ref, tab_ref, ch_ref = refs[:3]
        ids_r = ids_ref[:].reshape(1, blk)
    else:
        ids_r, nk, plane_rows = _prepared_ids(refs, prepared, blk)
        tab_ref = refs[nk + 1]
        plane_at = mm.prepared_plane_index(refs[nk + 2:-2], plane_rows)

    def chh(a, oh_hi):
        if a == 0 and ones_first:
            return oh_hi  # folded all-ones count channel
        if prepared is not None:
            return oh_hi * mm.prepared_plane_row(*plane_at[a - 1], blk)
        return oh_hi * ch_ref[pl.ds(a, 1), :]

    lo_r = ids_r & _i32(LO - 1)
    hi_r = ids_r >> _i32(7)  # LO = 128
    jsub = jax.lax.broadcasted_iota(jnp.int32, (LO, blk), 0)
    oh_loT = jnp.where(lo_r == jsub, jnp.float32(1), jnp.float32(0)) \
        .astype(jnp.bfloat16)
    hc = NARROW_CHUNK
    for c in range(hpad // hc):
        rows = pl.ds(c * hc, hc)
        # the table arrives lane-replicated, (hpad, 128)
        tab_c = tab_ref[rows, :]

        @pl.when(jnp.max(tab_c.astype(jnp.float32)) >= jnp.float32(0))
        def _(rows=rows, tab_c=tab_c):
            # tile the chunk over the row block's lanes
            tab = pltpu.repeat(tab_c, blk // 128, axis=1)
            oh_hi = jnp.where(hi_r == tab, jnp.float32(1), jnp.float32(0)) \
                .astype(jnp.bfloat16)
            chh_all = jnp.concatenate(
                [chh(a, oh_hi) for a in range(a_real)], axis=0)
            acc_ref[:, rows, :] += jax.lax.dot_general(
                chh_all, oh_loT, _NT, preferred_element_type=jnp.float32
            ).reshape(a_real, hc, LO)

    @pl.when(i == ninner - 1)
    def _():
        out_ref[0] = acc_ref[:]


def _prepared_ids(refs, prepared, blk: int):
    """A prepared launch's masked id row (ops/groupby_mm.py
    ``prepared_ids_row``) for every kernel form: the refs open with one
    ids block a key column, then the mask. Returns (the (1, blk) row, the
    number of key columns, the value operands' plane counts)."""
    cards, sentinel, shift, plane_rows = prepared
    nk = len(cards)
    ids_r = mm.prepared_ids_row(refs[:nk], refs[nk], cards, sentinel, blk,
                                shift)
    return ids_r, nk, plane_rows


# ---------------------------------------------------------------------------
# 1) tiled local-accumulate group scatter (sums / counts)
# ---------------------------------------------------------------------------


def sums_supported(num_groups: int, n_channels: int) -> bool:
    """True when the partitioned plane-sum kernel covers this shape:
    the group space splits into <= MAX_PARTITIONS VMEM-sized ranges."""
    if n_channels > MAX_CHANNELS + 1:
        return False
    hp = _span_hpad(n_channels)
    return -(-_hpad_total(num_groups) // hp) <= MAX_PARTITIONS


def _sums_kernel(*refs, ninner, hpad, a_real, blk, gp, stacked, ones_first,
                 prepared=None):
    out_ref, acc_ref = refs[-2:]
    p = pl.program_id(0)
    i = pl.program_id(2)

    @pl.when(i == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    if prepared is None:
        ids_ref, ch_ref = refs[:2]
        ids_r = ids_ref[:].reshape(1, blk)
    else:
        # batch-resident operands (ops/groupby_mm.py "prepared operands")
        ids_r, nk, plane_rows = _prepared_ids(refs, prepared, blk)
        plane_at = mm.prepared_plane_index(refs[nk + 1:-2], plane_rows)
    oh_loT, oh_hi = _rel_onehots(ids_r, p, gp, hpad, blk)

    def chh(a):
        if a == 0 and ones_first:
            return oh_hi  # folded all-ones count channel
        if prepared is not None:
            return oh_hi * mm.prepared_plane_row(*plane_at[a - 1], blk)
        return oh_hi * ch_ref[pl.ds(a, 1), :]

    if stacked:
        chh_all = jnp.concatenate([chh(a) for a in range(a_real)], axis=0)
        acc_flat = jax.lax.dot_general(
            chh_all, oh_loT, _NT, preferred_element_type=jnp.float32)
        acc_ref[:] += acc_flat.reshape(a_real, hpad, LO)
    else:
        for a in range(a_real):
            acc_ref[a] += jax.lax.dot_general(
                chh(a), oh_loT, _NT, preferred_element_type=jnp.float32)

    @pl.when(i == ninner - 1)
    def _():
        out_ref[0] = acc_ref[:]


def _sums_plan(num_groups: int, a_real: int, span_hpad: int | None = None):
    """(hp, npart, blk, ninner, stacked) of one plane-sum launch."""
    total_h = _hpad_total(num_groups)
    hp = min(span_hpad or _span_hpad(a_real), total_h)
    return (hp, -(-total_h // hp)) + _plan_blk(a_real, hp)


def sums_blk(num_groups: int, a_real: int) -> int:
    """The row tile a plane-sum launch of this shape runs at."""
    return _sums_plan(num_groups, a_real)[2]


def _lane_spec(blk: int, ninner: int):
    return pl.BlockSpec((blk // 128, 128),
                        lambda p, s, i: (s * ninner + i, _i32(0)),
                        memory_space=pltpu.VMEM)


def _sums_call(operands, operand_specs, num_groups: int,
               a_real: int, plan, *, interpret: bool, ones_first: bool,
               prepared=None, narrow: bool = False):
    """The one ``pallas_call`` of the plane-sum kernel, over per-launch
    operands (masked ids + stacked bf16 channels) or prepared ones (ids a
    key column, mask, uint8 lane planes); the first operand is lane-major
    ids either way. Returns (A, num_groups) float64."""
    hp, npart, blk, ninner, stacked = plan
    gp = hp * LO
    nsuper = operands[0].shape[0] * 128 // SUPERBLOCK
    if narrow:
        kern = functools.partial(
            _narrow_kernel, ninner=ninner, hpad=hp, a_real=a_real, blk=blk,
            ones_first=ones_first, prepared=prepared)
    else:
        kern = functools.partial(
            _sums_kernel, ninner=ninner, hpad=hp, a_real=a_real, blk=blk,
            gp=gp, stacked=stacked, ones_first=ones_first,
            prepared=prepared)
    out = pl.pallas_call(
        kern,
        grid=(npart, nsuper, ninner),
        in_specs=list(operand_specs),
        out_specs=pl.BlockSpec(
            (1, a_real, hp, LO),
            lambda p, s, i: (p * nsuper + s, _i32(0), _i32(0), _i32(0)),
            memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct(
            (npart * nsuper, a_real, hp, LO), jnp.float32),
        scratch_shapes=[pltpu.VMEM((a_real, hp, LO), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_vmem_limit(a_real, hp, blk, stacked)),
        interpret=interpret,
        name="pinot_scatter_sums",
    )(*operands)
    # (npart*nsuper, A, hp, LO) → superblock partials reduce in f64, then
    # partitions concatenate along the group axis
    tot = jnp.sum(out.reshape(npart, nsuper, a_real, hp, LO), axis=1,
                  dtype=jnp.float64)
    return jnp.transpose(tot, (1, 0, 2, 3)).reshape(
        a_real, npart * gp)[:, :num_groups]


def plane_group_sums(gid, channels, num_groups: int, *,
                     interpret: bool = False,
                     first_channel_ones: bool = False,
                     span_hpad: int | None = None):
    """Dense per-group sums of bf16 plane channels with group-range
    partitioning — the tiled local-accumulate scatter.

    gid: (n,) int32 in [0, num_groups]; id == num_groups is the overflow
    slot (sliced off). channels: (A, n) bf16 planes, |value| <= 255 for
    exact integer sums (ops/groupby_mm.py int_planes/float_planes build
    them). ``span_hpad`` overrides the per-partition budget (tests force
    multi-partition launches on small group counts). Returns
    (A, num_groups) float64 — f32 superblock partials reduced in f64, the
    exactness argument of the mm kernel, per partition.
    """
    a_real, n = channels.shape
    plan = _sums_plan(num_groups, a_real, span_hpad)
    _hp, _npart, blk, ninner, _stacked = plan
    n_pad = ((n + SUPERBLOCK - 1) // SUPERBLOCK) * SUPERBLOCK
    ids_lane = _pad_lane(gid.astype(jnp.int32), n_pad, n, num_groups)
    ch = jnp.concatenate(
        [channels, jnp.zeros((a_real, n_pad - n), channels.dtype)], axis=1
    ) if n_pad > n else channels
    ch_spec = pl.BlockSpec((a_real, blk),
                           lambda p, s, i: (_i32(0), s * ninner + i),
                           memory_space=pltpu.VMEM)
    return _sums_call((ids_lane, ch), [_lane_spec(blk, ninner), ch_spec],
                      num_groups, a_real, plan, interpret=interpret,
                      ones_first=first_channel_ones)


def _prepared_operands(ids_lanes, mask_lane, planes, blk: int, ninner: int,
                       between=()):
    """(operands, their specs) of a prepared launch: the ids a key column,
    the mask, ``between`` [(operand, spec)], then the uint8 planes a
    value."""
    plane_specs = [
        pl.BlockSpec((p.shape[0], blk // 128, 128),
                     lambda p_, s, i: (_i32(0), s * ninner + i, _i32(0)),
                     memory_space=pltpu.VMEM)
        for p in planes]
    return ((*ids_lanes, mask_lane, *(o for o, _s in between), *planes),
            [_lane_spec(blk, ninner)] * (len(ids_lanes) + 1)
            + [spec for _o, spec in between] + plane_specs)


def plane_group_sums_prepared(ids_lanes, cards, mask_lane, planes,
                              num_groups: int, *, shift: int = 0,
                              interpret: bool = False,
                              span_hpad: int | None = None):
    """``plane_group_sums`` over the batch's prepared operands
    (ops/groupby_mm.py ``prepared_ids`` / ``prepared_planes``) and the
    launch's ``mask_lanes``. ``ids_lanes``: one lane-major id operand a
    key column, ``cards`` their cardinalities; the cartesian id, its
    ``shift`` (then ``num_groups`` counts the ids left: the narrowed
    form's blocks) and ``where(mask, ids, num_groups)`` happen in VMEM.
    Channel 0 counts (no operand rows), channels 1.. are the rows of
    ``planes`` in order. Same f32 superblock partials, same f64
    reduction: bit-identical to the per-launch operands."""
    a_real = 1 + sum(p.shape[0] for p in planes)
    plan = _sums_plan(num_groups, a_real, span_hpad)
    _hp, _npart, blk, ninner, _stacked = plan
    operands, specs = _prepared_operands(ids_lanes, mask_lane, planes, blk,
                                         ninner)
    return _sums_call(
        operands, specs, num_groups, a_real, plan, interpret=interpret,
        ones_first=True,
        prepared=(tuple(cards), num_groups, shift,
                  tuple(p.shape[0] for p in planes)))


def _narrow_tab(hi_table):
    """The live-block table as the kernel takes it: lane-replicated."""
    return jnp.broadcast_to(hi_table.astype(jnp.int32)[:, None],
                            (hi_table.shape[0], 128))


def _narrow_tab_spec(hp: int):
    return pl.BlockSpec((hp, 128), lambda p, s, i: (_i32(0), _i32(0)),
                        memory_space=pltpu.VMEM)


def plane_group_sums_narrow(gid, channels, hi_table, *,
                            interpret: bool = False,
                            first_channel_ones: bool = False):
    """``plane_group_sums`` over a NARROWED key space (engine/device.py
    "groupby_narrow"): ``hi_table`` (H,) int32 lists the live 128-cell
    blocks (``gid >> 7``) of a large, sparsely filled key space, -1 past
    the last; the accumulator holds those H blocks and nothing else, so
    the hi one-hot is H rows however large the key space is. A row whose
    block the table does not list adds nothing: masked rows carry an id
    past the key space. Returns (A, H * 128) float64; row ``h * 128 + j``
    is the cell ``hi_table[h] * 128 + j``. Same planes, same f32
    superblock partials, same f64 reduction as the dense form."""
    a_real, n = channels.shape
    hp = hi_table.shape[0]
    assert hp % NARROW_CHUNK == 0, hp
    blk, ninner, stacked = _plan_blk(a_real, hp)
    n_pad = ((n + SUPERBLOCK - 1) // SUPERBLOCK) * SUPERBLOCK
    # a pad row's block (2^24 - 1) is in no table
    ids_lane = _pad_lane(gid.astype(jnp.int32), n_pad, n,
                         jnp.iinfo(jnp.int32).max)
    ch = jnp.concatenate(
        [channels, jnp.zeros((a_real, n_pad - n), channels.dtype)], axis=1
    ) if n_pad > n else channels
    tab, tab_spec = _narrow_tab(hi_table), _narrow_tab_spec(hp)
    ch_spec = pl.BlockSpec((a_real, blk),
                           lambda p, s, i: (_i32(0), s * ninner + i),
                           memory_space=pltpu.VMEM)
    return _sums_call((ids_lane, tab, ch),
                      [_lane_spec(blk, ninner), tab_spec, ch_spec], hp * LO,
                      a_real, (hp, 1, blk, ninner, stacked),
                      interpret=interpret, ones_first=first_channel_ones,
                      narrow=True)


def narrow_blk(a_real: int, hp: int) -> int:
    """The row tile a narrowed launch of this shape runs at."""
    return _plan_blk(a_real, hp)[0]


def plane_group_sums_narrow_prepared(ids_lanes, cards, mask_lane, planes,
                                     hi_table, *, interpret: bool = False):
    """``plane_group_sums_narrow`` over the batch's prepared operands, as
    ``plane_group_sums_prepared`` reads them: the cartesian id is formed
    and masked in VMEM (a dropped row's block is in no table), the uint8
    planes are the channels. Bit-identical to the per-launch operands."""
    a_real = 1 + sum(p.shape[0] for p in planes)
    hp = hi_table.shape[0]
    assert hp % NARROW_CHUNK == 0, hp
    blk, ninner, stacked = _plan_blk(a_real, hp)
    operands, specs = _prepared_operands(
        ids_lanes, mask_lane, planes, blk, ninner,
        between=[(_narrow_tab(hi_table), _narrow_tab_spec(hp))])
    return _sums_call(
        operands, specs, hp * LO, a_real, (hp, 1, blk, ninner, stacked),
        interpret=interpret, ones_first=True,
        prepared=(tuple(cards), jnp.iinfo(jnp.int32).max, 0,
                  tuple(p.shape[0] for p in planes)),
        narrow=True)


# ---------------------------------------------------------------------------
# 2) group min/max scatter
# ---------------------------------------------------------------------------

_MINMAX_KERNEL_DTYPES = {
    "int8": jnp.int32, "int16": jnp.int32, "int32": jnp.int32,
    "uint8": jnp.int32, "uint16": jnp.int32, "float32": jnp.float32,
}


def minmax_supported(num_groups: int, dtype) -> bool:
    """int64/float64 values stay on the XLA scatter (Mosaic has no 64-bit
    vector path); group count bounded by the O(span)-per-row select."""
    if str(jnp.dtype(dtype)) not in _MINMAX_KERNEL_DTYPES:
        return False
    return -(-(num_groups + 1) // MINMAX_SPAN) <= MAX_MINMAX_PARTS


def _minmax_kernel(ids_ref, v_ref, *refs, ops, span, blk, nsteps, fills):
    out_refs = refs[:len(ops)]
    acc_refs = refs[len(ops):]
    p = pl.program_id(0)
    s = pl.program_id(1)

    @pl.when(s == 0)
    def _():
        for a, fill in zip(acc_refs, fills):
            a[:] = jnp.full(a.shape, fill, a.dtype)

    ids_r = ids_ref[:].reshape(1, blk)
    span_ = _i32(span)  # typed: see _rel_onehots
    rel = ids_r - p * span_
    rel = jnp.where((rel >= _i32(0)) & (rel < span_), rel, span_)
    gsub = jax.lax.broadcasted_iota(jnp.int32, (span, blk), 0)
    onehot = rel == gsub  # rel == span matches no group row
    v = v_ref[:].reshape(1, blk)
    for op, acc, fill in zip(ops, acc_refs, fills):
        vm = jnp.where(onehot, v, jnp.asarray(fill, v.dtype))
        red = vm.min(axis=1, keepdims=True) if op == "min" \
            else vm.max(axis=1, keepdims=True)
        folded = jnp.broadcast_to(red, (span, 128))
        acc[:] = jnp.minimum(acc[:], folded) if op == "min" \
            else jnp.maximum(acc[:], folded)

    @pl.when(s == nsteps - 1)
    def _():
        for o, a in zip(out_refs, acc_refs):
            o[0] = a[:]


def group_minmax(gid, values, num_groups: int, ops: tuple, *,
                 interpret: bool = False, fills: tuple = None):
    """Per-group min and/or max via masked broadcast-select over group-
    range partitions. ``ops`` ⊆ ("min", "max"); ``fills`` overrides the
    empty-group fill per op (callers pass the ORIGINAL dtype's extremes
    so empty slots match the XLA scatter path bit-for-bit). Returns one
    (num_groups,) array per op, in the kernel compute dtype (callers cast
    back — min/max never leave the value set, so the cast is exact)."""
    kdt = _MINMAX_KERNEL_DTYPES[str(jnp.dtype(values.dtype))]
    v = values.astype(kdt).reshape(-1)
    n = v.shape[0]
    if fills is None:
        info = jnp.finfo(kdt) if kdt == jnp.float32 else jnp.iinfo(kdt)
        fills = tuple(info.max if op == "min" else info.min for op in ops)
    npart = -(-(num_groups + 1) // MINMAX_SPAN)
    blk = MINMAX_BLK
    n_pad = ((n + blk - 1) // blk) * blk
    nsteps = n_pad // blk
    ids_lane = _pad_lane(gid.reshape(-1).astype(jnp.int32), n_pad, n,
                         num_groups)
    # padded rows need a value; they target the overflow slot so any fill
    # works — reuse the first op's neutral
    v_lane = _pad_lane(v, n_pad, n, fills[0])
    kern = functools.partial(
        _minmax_kernel, ops=ops, span=MINMAX_SPAN, blk=blk, nsteps=nsteps,
        fills=fills)
    outs = pl.pallas_call(
        kern,
        grid=(npart, nsteps),
        in_specs=[
            pl.BlockSpec((blk // 128, 128), lambda p, s: (s, _i32(0)),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((blk // 128, 128), lambda p, s: (s, _i32(0)),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, MINMAX_SPAN, 128),
                         lambda p, s: (p, _i32(0), _i32(0)),
                         memory_space=pltpu.VMEM)
            for _ in ops],
        out_shape=[jax.ShapeDtypeStruct((npart, MINMAX_SPAN, 128), kdt)
                   for _ in ops],
        scratch_shapes=[pltpu.VMEM((MINMAX_SPAN, 128), kdt) for _ in ops],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=max(
                16 << 20, (len(ops) + 3) * MINMAX_SPAN * blk * 4)),
        interpret=interpret,
        name="pinot_scatter_minmax",
    )(ids_lane, v_lane)
    if not isinstance(outs, (list, tuple)):
        outs = [outs]
    return tuple(o[:, :, 0].reshape(npart * MINMAX_SPAN)[:num_groups]
                 for o in outs)


# ---------------------------------------------------------------------------
# 3) HLL register-max scatter
# ---------------------------------------------------------------------------


def hll_supported(nslots: int, nrho: int) -> bool:
    """Slot spaces the presence kernel beats the serialized scatter on:
    threshold-channel work per row is ~ceil(nrho*hpad/128) MXU cycles, so
    the advantage decays linearly with the slot space — past the bound
    the sorted dedup basis (ops/radix_groupby.py) is the right tool."""
    if nslots > HLL_MAX_SLOTS:
        return False
    hp = _span_hpad(nrho)
    return -(-_hpad_total(nslots) // hp) <= MAX_PARTITIONS


def _hll_kernel(ids_ref, rho_ref, out_ref, acc_ref, *,
                nsteps, hpad, nrho, blk, gp, stacked):
    p = pl.program_id(0)
    s = pl.program_id(1)

    @pl.when(s == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    ids_r = ids_ref[:].reshape(1, blk)
    oh_loT, oh_hi = _rel_onehots(ids_r, p, gp, hpad, blk)
    rho_r = rho_ref[:].reshape(1, blk)

    def chh(r):
        ch = jnp.where(rho_r == _i32(r + 1), jnp.float32(1),
                       jnp.float32(0)).astype(jnp.bfloat16)
        return oh_hi * ch

    # presence accumulates as f32 counts: nonneg adds never take a touched
    # slot below 1 (round-to-nearest of a value >= 1 stays >= 1), so the
    # >0.5 threshold at flush is exact without per-superblock flushes
    if stacked:
        chh_all = jnp.concatenate([chh(r) for r in range(nrho)], axis=0)
        acc_flat = jax.lax.dot_general(
            chh_all, oh_loT, _NT, preferred_element_type=jnp.float32)
        acc_ref[:] += acc_flat.reshape(nrho, hpad, LO)
    else:
        for r in range(nrho):
            acc_ref[r] += jax.lax.dot_general(
                chh(r), oh_loT, _NT, preferred_element_type=jnp.float32)

    @pl.when(s == nsteps - 1)
    def _():
        pres = acc_ref[:] > jnp.float32(0.5)
        rvals = jax.lax.broadcasted_iota(
            jnp.int32, (nrho, hpad, LO), 0) + _i32(1)
        out_ref[0] = jnp.max(jnp.where(pres, rvals, _i32(0)), axis=0)


def hll_register_max(slot, rho, nslots: int, nrho: int, *,
                     interpret: bool = False,
                     span_hpad: int | None = None):
    """(nslots,) int32 registers = per-slot max rho — the real register-
    max scatter. slot: int32 ids in [0, nslots] (== nslots masks the
    row); rho: int32 in [1, nrho] (0 on padded rows matches no channel).
    Exact max-of-rho, bit-identical to the f32 scatter-max and the host
    build (presence is idempotent — accumulation order can't matter)."""
    s = slot.reshape(-1).astype(jnp.int32)
    r = rho.reshape(-1).astype(jnp.int32)
    n = s.shape[0]
    total_h = _hpad_total(nslots)
    hp = min(span_hpad or _span_hpad(nrho), total_h)
    npart = -(-total_h // hp)
    gp = hp * LO
    blk, _ninner, stacked = _plan_blk(nrho, hp)
    n_pad = ((n + blk - 1) // blk) * blk
    nsteps = n_pad // blk
    ids_lane = _pad_lane(s, n_pad, n, nslots)
    rho_lane = _pad_lane(r, n_pad, n, 0)
    kern = functools.partial(
        _hll_kernel, nsteps=nsteps, hpad=hp, nrho=nrho, blk=blk, gp=gp,
        stacked=stacked)
    out = pl.pallas_call(
        kern,
        grid=(npart, nsteps),
        in_specs=[
            pl.BlockSpec((blk // 128, 128), lambda p, s: (s, _i32(0)),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((blk // 128, 128), lambda p, s: (s, _i32(0)),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec(
            (1, hp, LO), lambda p, s: (p, _i32(0), _i32(0)),
            memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((npart, hp, LO), jnp.int32),
        scratch_shapes=[pltpu.VMEM((nrho, hp, LO), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_vmem_limit(nrho, hp, blk, stacked)),
        interpret=interpret,
        name="pinot_scatter_hll",
    )(ids_lane, rho_lane)
    return out.reshape(npart * gp)[:nslots]


# ---------------------------------------------------------------------------
# 4) fused filter + gather + aggregate (block-skip candidates)
# ---------------------------------------------------------------------------

# storage dtypes the kernel loads directly; raw-space predicate literals
# additionally need a value range strictly inside int32 so host-side
# clipping into storage space preserves every comparison
_FUSED_COL_DTYPES = ("uint8", "uint16", "int8", "int16", "int32", "float32")
_FUSED_PRED_DTYPES = ("uint8", "uint16", "int8", "int16")

_FUSED_AGGS = ("count", "sum", "avg", "min", "max", "minmaxrange")


def _direct_colkey(expr_tpl):
    """Column key of a direct column read, or None for computed exprs."""
    if not isinstance(expr_tpl, tuple):
        return None
    if expr_tpl[0] == "raw":
        return expr_tpl[1]
    if expr_tpl[0] == "dictval":
        return "dv::" + expr_tpl[1]
    return None


class FusedPlan:
    """Static plan for one fused launch: operand order, per-agg output
    slots, and the parameter transforms the caller applies (shift raw
    literals into storage space, clip into the plane's value range)."""

    __slots__ = ("cols", "filter_tpl", "pred_params", "aggs",
                 "n_int", "n_flt")

    def __init__(self, cols, filter_tpl, pred_params, aggs, n_int, n_flt):
        self.cols = cols              # tuple of column keys (operand order)
        self.filter_tpl = filter_tpl
        # {param key: (colkey or None, "id" | "storage")} — "storage"
        # params subtract the column's FOR offset and clip to the plane's
        # value range before entering the kernel
        self.pred_params = pred_params
        # list of (agg index, name, colkey, buffer, slot, fill)
        self.aggs = aggs
        self.n_int = n_int
        self.n_flt = n_flt


def _plan_filter(tpl, widths, cols, pred_params) -> bool:
    """Walk the filter template: True iff every node is kernel-evaluable.
    Fills ``cols``/``pred_params`` as it goes."""
    kind = tpl[0]
    if kind in ("true", "false"):
        return True
    if kind in ("and", "or"):
        return all(_plan_filter(c, widths, cols, pred_params)
                   for c in tpl[1:])
    if kind == "not":
        return _plan_filter(tpl[1], widths, cols, pred_params)

    def col_ok(key, pred: bool) -> bool:
        if key is None or key.startswith("mv::"):
            return False
        w = widths.get(key) if widths else None
        if w is not None and w[1]:
            return False  # sub-byte packed plane: unpack not fused
        dt = str(jnp.dtype(w[0])) if w is not None else None
        if dt is None:
            return False  # unplanned plane (legacy wide) — dtype unknown
        allowed = _FUSED_PRED_DTYPES if pred else _FUSED_COL_DTYPES
        if dt not in allowed:
            return False
        cols.add(key)
        return True

    if kind == "eq_dict":
        if not col_ok(tpl[1], False) or str(jnp.dtype(
                widths[tpl[1]][0])) == "float32":
            return False
        pred_params[tpl[2]] = (tpl[1], "id")
        return True
    if kind == "in_dict":
        if not col_ok(tpl[1], False) or str(jnp.dtype(
                widths[tpl[1]][0])) == "float32":
            return False
        pred_params[tpl[2]] = (tpl[1], "id")
        return True
    if kind == "range_dict":
        if not col_ok(tpl[1], False) or str(jnp.dtype(
                widths[tpl[1]][0])) == "float32":
            return False
        pred_params[tpl[2]] = (tpl[1], "id")
        pred_params[tpl[3]] = (tpl[1], "id")
        return True
    if kind in ("eq_raw", "in_raw"):
        ck = _direct_colkey(tpl[1])
        if not col_ok(ck, True):
            return False
        pred_params[tpl[2]] = (ck, "storage")
        return True
    if kind == "range_raw":
        _, expr_tpl, klo, khi, has_lo, has_hi, _li, _hi_inc = tpl
        ck = _direct_colkey(expr_tpl)
        if not col_ok(ck, True):
            return False
        if has_lo:
            pred_params[klo] = (ck, "storage")
        if has_hi:
            pred_params[khi] = (ck, "storage")
        return True
    return False  # lut_dict / mv_any / anything new


def plan_fused(filter_tpl, agg_tpls, widths):
    """Static fused-launch plan for a scalar-shape block-skip template, or
    None when any node falls outside the kernel's surface (the generic
    gather path then runs, exactly as before)."""
    cols: set = set()
    pred_params: dict = {}
    if not _plan_filter(filter_tpl, widths, cols, pred_params):
        return None
    aggs = []
    n_int, n_flt = 1, 0  # int slot 0 = per-block matched count
    for i, (name, argt, extra) in enumerate(agg_tpls):
        if name not in _FUSED_AGGS:
            return None
        if name == "count":
            continue
        ck = _direct_colkey(argt)
        if ck is None or ck.startswith("mv::"):
            return None
        w = widths.get(ck) if widths else None
        if w is None or w[1]:
            return None
        dt = str(jnp.dtype(w[0]))
        if dt not in _FUSED_COL_DTYPES:
            return None
        is_float = dt == "float32"
        if name in ("sum", "avg"):
            if is_float:
                return None  # f32 sums are order-sensitive: stay on XLA
            rpb = extra[1] if isinstance(extra, tuple) else extra
            if rpb is None or rpb < FUSED_BLOCK_ROWS:
                return None  # per-block int32 partial could overflow
            cols.add(ck)
            aggs.append((i, "sum", ck, "int", n_int, 0))
            n_int += 1
            continue
        ops = ("min", "max") if name == "minmaxrange" else (name,)
        cols.add(ck)
        for op in ops:
            if is_float:
                fill = float("inf") if op == "min" else float("-inf")
                aggs.append((i, op, ck, "flt", n_flt, fill))
                n_flt += 1
            else:
                info = jnp.iinfo(jnp.dtype(w[0]))
                fill = info.max if op == "min" else info.min
                aggs.append((i, op, ck, "int", n_int, fill))
                n_int += 1
    return FusedPlan(tuple(sorted(cols)), filter_tpl, pred_params,
                     tuple(aggs), n_int, n_flt)


def fused_params_ok(plan: FusedPlan, params: dict) -> bool:
    """Trace-time runtime check: every predicate param present with a
    kernel-compatible shape (IN lists bounded) and dtype. Raw-space
    params must be INTEGER: a fractional literal (``ts < 10.5``) would
    truncate under the storage-space int cast while the generic branch
    compares with float promotion — the query falls to the generic
    gather path instead, keeping Pallas == XLA bit-exact."""
    for key, (_ck, kindp) in plan.pred_params.items():
        p = params.get(key)
        if p is None:
            return False
        if p.ndim > 1 or (p.ndim == 1 and p.shape[0] > FUSED_MAX_IN):
            return False
        if kindp == "storage" and not jnp.issubdtype(p.dtype, jnp.integer):
            return False
    return True


def _fused_eval(tpl, colv, parv, shape):
    """In-kernel filter evaluation over the gathered block — the VMEM
    mirror of engine/device.py _eval_filter's interval/dict subset."""
    kind = tpl[0]
    if kind == "true":
        return jnp.ones(shape, dtype=bool)
    if kind == "false":
        return jnp.zeros(shape, dtype=bool)
    if kind == "and":
        m = _fused_eval(tpl[1], colv, parv, shape)
        for c in tpl[2:]:
            m &= _fused_eval(c, colv, parv, shape)
        return m
    if kind == "or":
        m = _fused_eval(tpl[1], colv, parv, shape)
        for c in tpl[2:]:
            m |= _fused_eval(c, colv, parv, shape)
        return m
    if kind == "not":
        return ~_fused_eval(tpl[1], colv, parv, shape)
    if kind in ("eq_dict", "eq_raw"):
        key = tpl[1] if kind == "eq_dict" else _direct_colkey(tpl[1])
        return colv[key] == parv[tpl[2]][0]
    if kind in ("in_dict", "in_raw"):
        key = tpl[1] if kind == "in_dict" else _direct_colkey(tpl[1])
        v = colv[key]
        p = parv[tpl[2]]
        m = v == p[0]
        for k in range(1, len(p)):
            m |= v == p[k]
        return m
    if kind == "range_dict":
        v = colv[tpl[1]]
        return (v >= parv[tpl[2]][0]) & (v < parv[tpl[3]][0])
    if kind == "range_raw":
        _, expr_tpl, klo, khi, has_lo, has_hi, lo_inc, hi_inc = tpl
        v = colv[_direct_colkey(expr_tpl)]
        m = jnp.ones(shape, dtype=bool)
        if has_lo:
            b = parv[klo][0]
            m &= (v >= b) if lo_inc else (v > b)
        if has_hi:
            b = parv[khi][0]
            m &= (v <= b) if hi_inc else (v < b)
        return m
    raise AssertionError(f"fused filter node {kind}")


def _fused_kernel(cand_ref, rows_ref, *refs, plan: FusedPlan, sub, pshapes):
    ncols = len(plan.cols)
    i = pl.program_id(0)
    colv = {}
    for j, key in enumerate(plan.cols):
        blk = refs[j][0]  # (sub, 128) storage dtype
        if blk.dtype == jnp.float32:
            colv[key] = blk
        else:
            colv[key] = blk.astype(jnp.int32)
    # predicate params live in SMEM, which loads scalars only: one scalar
    # read per literal (IN lists are bounded by FUSED_MAX_IN)
    parv = {key: [refs[ncols + j][k] for k in range(pshapes[key][0])]
            for j, key in enumerate(sorted(pshapes))}
    out_i = refs[ncols + len(pshapes)]
    out_f = None if plan.n_flt == 0 else refs[ncols + len(pshapes) + 1]

    shape = (sub, 128)
    mask = _fused_eval(plan.filter_tpl, colv, parv, shape)
    rowid = jax.lax.broadcasted_iota(jnp.int32, shape, 0) * _i32(128) \
        + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    mask &= rowid < rows_ref[i]

    # every partial reduces to a (1, 1) tile, not a scalar: Mosaic's
    # reduce-to-scalar proxy re-enters jnp.sum without a dtype, which
    # under x64 promotes int32 to an int64 it cannot lower
    def red(fn, x, **kw):
        return fn(fn(x, axis=0, keepdims=True, **kw),
                  axis=1, keepdims=True, **kw)

    # slot 0: matched rows
    ints = [red(jnp.sum, mask.astype(jnp.int32), dtype=jnp.int32)]
    flts = []
    for (_i, op, ck, buf, _slot, fill) in plan.aggs:
        v = colv[ck]
        if op == "sum":
            ints.append(red(jnp.sum, jnp.where(mask, v, _i32(0)),
                            dtype=jnp.int32))
        elif buf == "int":
            vm = jnp.where(mask, v, _i32(fill))
            ints.append(red(jnp.min if op == "min" else jnp.max, vm))
        else:
            vm = jnp.where(mask, v, jnp.float32(fill))
            flts.append(red(jnp.min if op == "min" else jnp.max, vm))

    def slots(vals, ref, zero):
        # slot k of the (K, 128) output row-block = k-th partial, lane-
        # broadcast (a select per slot: scalars don't stack in VMEM)
        k = ref.shape[1]
        row = jax.lax.broadcasted_iota(jnp.int32, (k, 128), 0)
        out = jnp.full((k, 128), zero)
        for n, val in enumerate(vals):
            out = jnp.where(row == _i32(n), val, out)
        ref[0] = out

    slots(ints, out_i, _i32(0))
    if out_f is not None:
        slots(flts, out_f, jnp.float32(0))


def fused_filter_agg(cand, rows_in_block, col_arrays: dict,
                     param_arrays: dict, plan: FusedPlan, *,
                     interpret: bool = False):
    """ONE kernel: gather candidate blocks (scalar-prefetched indices
    drive the BlockSpec index maps — the pipeline DMAs exactly the
    candidate blocks out of HBM), evaluate the filter, aggregate. The
    XLA path's (B, R) gather buffer never materializes.

    cand: (B,) int32 candidate block ids into the flattened
    (S*NB, R) view; rows_in_block: (B,) int32 valid rows per candidate
    (0 for padding candidates). col_arrays: {key: (S*NB, R//128, 128)}
    storage-dtype views; param_arrays: {key: (K,) int32/float32} already
    shifted into storage space. Returns (ints (B, KI), flts (B, KF) or
    None): per-candidate partials — matched count in int slot 0, agg
    partials per the plan's slots. Combining them (answer-scale, outside)
    is exact: int sums never overflow their per-block int32 partial
    (plan-gated via rows_per_block bounds) and min/max are idempotent.
    """
    B = cand.shape[0]
    sub = FUSED_BLOCK_ROWS // 128
    ki = max(8, plan.n_int)
    kf = max(8, plan.n_flt) if plan.n_flt else 0
    pkeys = sorted(param_arrays)
    pshapes = {k: param_arrays[k].shape for k in pkeys}
    kern = functools.partial(_fused_kernel, plan=plan, sub=sub,
                             pshapes=pshapes)
    in_specs = [
        pl.BlockSpec((1, sub, 128),
                     lambda i, c, r: (c[i], _i32(0), _i32(0)),
                     memory_space=pltpu.VMEM)
        for _ in plan.cols
    ] + [
        # whole-array SMEM blocks with an explicit int32 index map (the
        # default map's Python zeros are 64-bit under x64)
        pl.BlockSpec(pshapes[k], lambda i, c, r: (_i32(0),),
                     memory_space=pltpu.SMEM) for k in pkeys
    ]
    out_specs = [pl.BlockSpec((1, ki, 128),
                              lambda i, c, r: (i, _i32(0), _i32(0)),
                              memory_space=pltpu.VMEM)]
    out_shape = [jax.ShapeDtypeStruct((B, ki, 128), jnp.int32)]
    if kf:
        out_specs.append(
            pl.BlockSpec((1, kf, 128),
                         lambda i, c, r: (i, _i32(0), _i32(0)),
                         memory_space=pltpu.VMEM))
        out_shape.append(jax.ShapeDtypeStruct((B, kf, 128), jnp.float32))
    gs = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B,),
        in_specs=in_specs,
        out_specs=out_specs if kf else out_specs[0],
    )
    outs = pl.pallas_call(
        kern, grid_spec=gs,
        out_shape=out_shape if kf else out_shape[0],
        interpret=interpret,
        name="pinot_fused_filter_agg",
    )(cand.astype(jnp.int32), rows_in_block.astype(jnp.int32),
      *[col_arrays[k] for k in plan.cols],
      *[param_arrays[k] for k in pkeys])
    if kf:
        return outs[0][:, :, 0], outs[1][:, :, 0]
    return outs[:, :, 0], None
