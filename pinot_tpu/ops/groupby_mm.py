"""Factored one-hot matmul group-by: the Pallas TPU kernel for dense
COUNT/SUM/AVG aggregation.

Replaces the per-channel scatter-add (ops/agg.py group_sum / group_count —
the DefaultGroupByExecutor.java:116-147 aggregateGroupBySV analog) for the
hot group-by shapes. Measured on v5e at 12M rows, G=6240, 6 channels:
scatter path ~250ms compute, this kernel ~26ms — channels are nearly free
because they ride the MXU.

Design (factored one-hot, planned low radix ``lo`` in {32, 64, 128}):
    gid = hi*lo + lo_bits.  Per row-block of ``blk`` rows:
      oh_loT (lo, blk)  : oh_loT[j, l] = (lo_l == j)  — rows on lanes
      oh_hi (hpad, blk) : oh_hi[h, l]  = (hi_l == h)  — rows on lanes
      per channel a:     chh_a = oh_hi * ch_a(1, blk)  (masked channel)
                         acc[a] += chh_a @ oh_loT^T    (NT dot_general,
                                                        MXU contracts rows)
    acc[a, h, j] == sum over rows with gid == h*lo+j of channel a.
    ``_plan_lo`` picks the radix that balances VPU one-hot build cost
    against hpad growth per shape; an all-ones first channel (the count
    channel every dense group-by carries) is FOLDED into oh_hi — its
    masked-channel multiply is the identity, so the kernel skips it
    (``first_channel_ones``).

The 3-way contraction channel x hi-onehot x lo-onehot never materializes
the full (blk, G) one-hot: the VPU builds two small one-hots (~0.3
cycles/row), the MXU does the G-wide work. Both one-hots keep the row
index on LANES, so ids stream in once, lane-major ``(n/128, 128)`` — no
degenerate-dim operand anywhere. (A previous revision fed ids a second
time as ``(n, 1)``; XLA tiles that layout to (8,128), padding the size-1
minor dim to 128 lanes — a 128x HBM blowup that OOMed at 100M rows. The
NT ``dot_general`` — the standard TPU flash-attention contraction — is
how the row axis gets contracted from a lane-major one-hot.)

Exactness: channels are bf16 *planes* — one-hot(bf16) x plane(bf16)
products are exact for plane values <= 255, and f32 accumulation over one
superblock (65536 rows x 255 < 2^24) stays exact; superblock partials
reduce in f64 outside the kernel, and integer recombination happens in
int64. Float channels use an exact 3-way bf16 split built by bit-masking
(immune to XLA excess-precision folding of bf16 round-trips), giving
~2e-12 relative error on f32 sums — tighter than the f32 scatter path.

HLL register builds run the same kernel in ``rho_mode``: the rho-threshold
indicator channels are built INSIDE the kernel from a lane-major rho
operand (4 bytes/row) instead of materializing (nrho, n) bf16 channels in
HBM (~46 bytes/row — several GB at 100M rows).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLK = 8192              # rows per grid step (64 lane-rows of 128); larger
                        # blocks amortize per-step overhead — measured 35.8
                        # -> 30.3ms for the 4-channel q1 shape at 100M rows
                        # on v5e (plateau at >=8192)
NINNER = 8              # steps per superblock: 65536 rows (f32-exact bound)
SUPERBLOCK = BLK * NINNER
MM_MIN_ROWS = 1 << 17   # below this the scatter path's fixed cost wins
MAX_CHANNELS = 15       # + the count channel; bounded by VMEM acc size
MAX_ACC_CELLS = 1 << 21 # A * hpad * 128 f32 cells (8MB VMEM accumulator;
                        # _launch raises the scoped-vmem limit to cover
                        # acc + double-buffered out block)
STACK_MAX_BYTES = 8 << 20   # stacked-channel operand cap: chh_all is
                            # (A*hpad, blk) bf16
TRANSIENT_BUDGET = 24 << 20  # in-kernel bf16 one-hot/channel transients;
                             # _plan_blk halves blk (floor 2048 = the
                             # pre-retune value) until they fit


def _plan_blk(a_real: int, hpad: int, lo: int):
    """(blk, ninner, stacked): per-shape block size. The one-hot and
    channel transients scale with hpad*blk, so large-hpad shapes (HLL rho
    mode near its support bound) shrink blk back toward 2048 — the value
    the VMEM budget was originally calibrated at — while small-hpad
    group-bys run at 8192 (measured 35.8 -> 30.3ms for the 4-channel
    G=2000 shape at 100M rows on v5e)."""
    blk = BLK
    while True:
        stacked = a_real * hpad * blk * 2 <= STACK_MAX_BYTES
        chh_rows = a_real * hpad if stacked else hpad
        transient = (lo + hpad + chh_rows) * blk * 2
        if transient <= TRANSIENT_BUDGET or blk <= 2048:
            return blk, SUPERBLOCK // blk, stacked
        blk //= 2

_i32 = jnp.int32
_NT = (((1,), (1,)), ((), ()))  # contract lanes-with-lanes (rows axis)


def _plan_lo(num_groups: int, a_real: int, ones_first: bool) -> int:
    """Low-radix factor of the factored one-hot (gid = hi*lo + lo_bits).
    The kernel is VPU-bound on building the one-hots: per row it compares
    ``lo`` lanes for the lo one-hot, ``hpad`` for the hi one-hot, and
    multiplies ``(a_real - folded) * hpad`` channel lanes, so the radix
    that balances the two one-hots beats a fixed 128 for small G (q1's
    G=2000 shape: lo=64 trades 128 lo-lanes for 32 hi-rows). The MXU pads
    the dot's N dim to the 128-lane tile either way — but so does VMEM:
    the accumulator's minor dim pads to 128 LANES regardless of ``lo``,
    so a small radix doubles the physical accumulator (hpad doubles,
    lanes don't shrink). Radixes whose physical acc would not fit are
    skipped, which keeps the support surface exactly the radix-128 one."""
    folded = 1 if ones_first else 0
    best, best_cost = 128, None
    for lo in (32, 64, 128):
        hpad = _hpad(num_groups, lo)
        if lo != 128 and a_real * hpad * 128 > MAX_ACC_CELLS:
            continue
        cost = 2 * lo + 2 * hpad + max(0, a_real - folded) * hpad
        if best_cost is None or cost < best_cost:
            best, best_cost = lo, cost
    return best


def mm_supported(num_groups: int, n_channels: int,
                 ones_first: bool = True) -> bool:
    lo = _plan_lo(num_groups, n_channels + 1, ones_first)
    hpad = _hpad(num_groups, lo)
    # physical cells: the acc minor dim pads to the 128-lane tile
    return (n_channels + 1) * hpad * 128 <= MAX_ACC_CELLS


def _hpad(num_groups: int, lo: int = 128) -> int:
    return max(8, ((num_groups // lo + 1 + 7) // 8) * 8)


def _kernel(*refs, ninner, hpad, a_real, blk, lo, rho_mode, stacked,
            ones_first, prepared=None):
    out_ref, acc_ref = refs[-2:]
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    lo_shift = lo.bit_length() - 1                  # lo is a power of two
    if prepared is None:
        ids_ref, ch_ref = refs[:2]
        ids_r = ids_ref[:].reshape(1, blk)          # sublane→lane merge: OK
    else:
        # batch-resident operands: ids, the launch's mask, byte planes
        num_groups, plane_rows = prepared
        ids_r = prepared_ids_row(refs[:1], refs[1], (num_groups,),
                                 num_groups, blk)
        plane_at = prepared_plane_index(refs[2:-2], plane_rows)
    lo_r = ids_r & (lo - 1)
    hi_r = ids_r >> lo_shift

    jsub = jax.lax.broadcasted_iota(jnp.int32, (lo, blk), 0)
    oh_loT = jnp.where(lo_r == jsub, jnp.float32(1), jnp.float32(0)) \
        .astype(jnp.bfloat16)
    hsub = jax.lax.broadcasted_iota(jnp.int32, (hpad, blk), 0)
    oh_hi = jnp.where(hi_r == hsub, jnp.float32(1), jnp.float32(0)) \
        .astype(jnp.bfloat16)

    if rho_mode:
        rho_r = ch_ref[:].reshape(1, blk)           # lane-major int32 rho

    def chh(a):
        if rho_mode:
            # channel a = indicator(rho == a+1), built in-VMEM
            ch = jnp.where(rho_r == a + 1, jnp.float32(1), jnp.float32(0)) \
                .astype(jnp.bfloat16)
            return oh_hi * ch
        if a == 0 and ones_first:
            # all-ones count channel: the masked-channel multiply is the
            # identity — oh_hi IS the product (one (hpad, blk) multiply
            # saved per block; callers guarantee overflow-slot slicing
            # absorbs the pad rows this also counts)
            return oh_hi
        if prepared is not None:
            return oh_hi * prepared_plane_row(*plane_at[a - 1], blk)
        return oh_hi * ch_ref[pl.ds(a, 1), :]       # (1, blk) bf16

    if stacked:
        # stack every channel's masked hi one-hot into ONE dot: per-channel
        # M=hpad dots underfill the MXU's M tile, so 4 channels cost ~4x one
        # — stacked to M = a_real*hpad they cost ~1x (measured 58.6 -> 27ms
        # for 4 channels at G=2000, 100M rows on v5e)
        chh_all = jnp.concatenate([chh(a) for a in range(a_real)], axis=0)
        acc_flat = jax.lax.dot_general(
            chh_all, oh_loT, _NT, preferred_element_type=jnp.float32)
        acc_ref[:] += acc_flat.reshape(a_real, hpad, lo)
    else:
        # large-hpad (HLL rho) shapes: a stacked operand would blow VMEM
        for a in range(a_real):
            acc_ref[a] += jax.lax.dot_general(
                chh(a), oh_loT, _NT, preferred_element_type=jnp.float32
            )

    @pl.when(i == ninner - 1)
    def _():
        out_ref[0] = acc_ref[:]


def _launch(ids_lane, ch_operand, ch_spec_kind, *, a_real, hpad, lo, nsuper,
            rho_mode, interpret, ones_first=False, num_groups=None):
    blk, ninner, stacked = _plan_blk(a_real, hpad, lo)
    lane_spec = pl.BlockSpec(
        (blk // 128, 128), lambda s, i: (s * ninner + i, _i32(0)),
        memory_space=pltpu.VMEM)
    prepared = None
    if ch_spec_kind == "channels":
        operands = (ch_operand,)
        ch_specs = [pl.BlockSpec(
            (a_real, blk), lambda s, i: (_i32(0), s * ninner + i),
            memory_space=pltpu.VMEM)]
    elif ch_spec_kind == "prepared":  # (mask_lane, [uint8 lane planes])
        mask_lane, planes = ch_operand
        operands = (mask_lane, *planes)
        prepared = (num_groups, tuple(p.shape[0] for p in planes))
        ch_specs = [lane_spec] + [
            pl.BlockSpec((p.shape[0], blk // 128, 128),
                         lambda s, i: (_i32(0), s * ninner + i, _i32(0)),
                         memory_space=pltpu.VMEM)
            for p in planes]
    else:  # lane-major rho operand
        operands = (ch_operand,)
        ch_specs = [lane_spec]
    kern = functools.partial(
        _kernel, ninner=ninner, hpad=hpad, a_real=a_real, blk=blk, lo=lo,
        rho_mode=rho_mode, stacked=stacked, ones_first=ones_first,
        prepared=prepared,
    )
    # acc scratch + out block are each a_real*hpad*128 f32; the out block is
    # double-buffered by the pipeline and Mosaic stacks further transient
    # copies. Default scoped-vmem limit is 16MB — raise it for large-G
    # accumulators (v5e has 128MB VMEM). Empirically the compiler's stack
    # peak reaches ~8x the accumulator at 400k groups (measured: 40.2MB at
    # acc=4.8MB), so budget 8x + headroom PLUS the blk-proportional
    # transients _plan_blk bounded; MAX_ACC_CELLS keeps the result under
    # the ceiling.
    acc_bytes = a_real * hpad * 128 * 4  # minor dim pads to 128 lanes
    chh_rows = a_real * hpad if stacked else hpad
    transient_bytes = (lo + hpad + chh_rows) * blk * 2
    vmem_limit = max(16 * 2**20,
                     min(110 * 2**20,
                         8 * acc_bytes + transient_bytes + 16 * 2**20))
    out = pl.pallas_call(
        kern,
        grid=(nsuper, ninner),
        in_specs=[lane_spec, *ch_specs],
        out_specs=pl.BlockSpec(
            (1, a_real, hpad, lo),
            lambda s, i: (s, _i32(0), _i32(0), _i32(0)),
            memory_space=pltpu.VMEM,
        ),
        out_shape=jax.ShapeDtypeStruct((nsuper, a_real, hpad, lo), jnp.float32),
        scratch_shapes=[pltpu.VMEM((a_real, hpad, lo), jnp.float32)],
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=vmem_limit),
        interpret=interpret,
        # the name the profiler's trace shows the kernel under
        name="pinot_hll_mm" if rho_mode else "pinot_groupby_mm",
    )(ids_lane, *operands)
    return jnp.sum(out, axis=0, dtype=jnp.float64)


def _pad_ids(gid, num_groups: int, n_pad: int, n: int):
    ids = jnp.concatenate(
        [gid.astype(jnp.int32), jnp.full(n_pad - n, num_groups, dtype=jnp.int32)]
    )
    return ids.reshape(-1, 128)


def group_sums(gid, channels, num_groups: int, *, interpret: bool = False,
               first_channel_ones: bool = False):
    """Dense per-group sums of bf16 plane channels.

    gid: (n,) int32 in [0, num_groups]; id == num_groups is the overflow
    slot for masked/padded rows (sliced off).
    channels: (A, n) bf16 planes, |value| <= 255 for exact integer sums.
    first_channel_ones: channels[0] is all-ones (the count channel) — the
    kernel folds its masked-channel multiply into the hi one-hot. Pad rows
    then count into the overflow slot, which this function slices off.
    Returns (A, num_groups) float64.
    """
    a_real, n = channels.shape
    lo = _plan_lo(num_groups, a_real, first_channel_ones)
    hpad = _hpad(num_groups, lo)
    n_pad = ((n + SUPERBLOCK - 1) // SUPERBLOCK) * SUPERBLOCK
    nsuper = n_pad // SUPERBLOCK

    ids_lane = _pad_ids(gid, num_groups, n_pad, n)
    ch = jnp.concatenate(
        [channels, jnp.zeros((a_real, n_pad - n), channels.dtype)], axis=1
    )
    tot = _launch(ids_lane, ch, "channels", a_real=a_real, hpad=hpad, lo=lo,
                  nsuper=nsuper, rho_mode=False, interpret=interpret,
                  ones_first=first_channel_ones)
    return tot.reshape(a_real, hpad * lo)[:, :num_groups]


# ---------------------------------------------------------------------------
# prepared operands: what a dense group-by's kernel reads that does not
# depend on the statement, kept in HBM in the kernel's own layout
# ---------------------------------------------------------------------------
# A launch used to rebuild, per statement and per cohort member, the value
# column's byte planes (64-bit shifts the TPU emulates), a ones channel the
# kernel never reads, the clipped key ids and their (S, L) → lanes
# relayouts. None of it depends on the statement; only the filter mask
# does. engine/params.py BatchContext builds these once a batch:
#
# - ``prepared_ids``: a key column's ids, clipped, rows past a segment's
#   end already at ``num_groups``, lane-major ``(n_pad/128, 128)`` at the
#   stored width (the kernel widens in VMEM). One operand a key COLUMN,
#   not a key set: a multi-key group-by's kernel takes one ref a column
#   and forms the cartesian id in VMEM (``prepared_ids_row``), so key sets
#   that share a column share its operand;
# - ``prepared_planes``: the byte planes of ``value - off`` as uint8,
#   ``(nplanes, n_pad/128, 128)`` (the kernel converts to bf16 in VMEM:
#   half the HBM of bf16 planes, and dense (32, 128) tiles). The value is
#   a stored column or an expression over stored columns alone
#   (engine/device.py ``_expr_planes``).
#
# The launch hands the kernel its mask as a third operand (``mask_lanes``,
# one byte a row) and ``where(mask, ids, num_groups)`` happens in VMEM: no
# masked id array is written. The count channel has no operand rows.
# (Evaluating the filter in lanes over a lane copy of its columns, so that
# a launch relays nothing out, was built and measured in PR 30: no faster
# than relaying the one-byte mask, 0.3 ms at 100M rows, and 100 MB more.)

PREP_SUBLANES = 32  # an 8-bit operand tiles at (32, 128)


def prepared_tile_ok(blk: int) -> bool:
    """The kernel's row tile holds whole (32, 128) tiles of an 8-bit
    operand. ``_plan_blk`` shrinks ``blk`` to 2048 for very large group
    counts; those launches keep the per-launch operands."""
    return (blk // 128) % PREP_SUBLANES == 0


def _to_lanes(x, fill):
    """(S, L) → lane-major (n_pad/128, 128), padded to whole superblocks.
    (S, L) has the segment axis on sublanes, so the flatten is a relayout.
    It is written as the split of L into (L/128, 128), a barrier, and the
    (free) merge of the two major axes: the TPU's compiler takes a fifth
    of a second over that at any width, and 17 s (32-bit) to 4 minutes
    (8-bit) over the one-step flatten XLA would make of it without the
    barrier (compiles for a described v5e at (8, 12,500,992), PR 30)."""
    S, L = x.shape
    if L % 128:
        lanes = x.reshape(-1)  # not a BatchContext's (S, L): pad the tail
        lanes = jnp.concatenate(
            [lanes, jnp.full(-lanes.shape[0] % 128, fill, x.dtype)]
        ).reshape(-1, 128)
    else:
        lanes = jax.lax.optimization_barrier(
            x.reshape(S, L // 128, 128)).reshape(-1, 128)
    pad_rows = -lanes.shape[0] % (SUPERBLOCK // 128)
    if pad_rows:
        lanes = jnp.concatenate(
            [lanes, jnp.full((pad_rows, 128), fill, x.dtype)])
    return lanes


@functools.partial(jax.jit, static_argnames=("num_groups", "bits"))
def prepared_ids(col, n_docs, *, num_groups: int, bits: int = 0):
    """Stored (S, L) id plane (sub-byte planes at ``bits``) → lane-major
    ids in [0, num_groups]; padding rows carry ``num_groups``. Unsigned
    planes keep their width (their pad sentinel C == num_groups fits by
    the width plan's tier rule)."""
    from pinot_tpu.ops import masks as mask_ops

    if bits:
        col = mask_ops.unpack_subbyte(col, bits)
    valid = mask_ops.valid_mask(n_docs, col.shape[1], batched=True)
    dt = col.dtype if jnp.issubdtype(col.dtype, jnp.unsignedinteger) \
        else jnp.int32
    ids = jnp.where(valid, jnp.clip(col.astype(jnp.int32), 0, num_groups - 1),
                    num_groups)
    return _to_lanes(ids.astype(dt), num_groups)


@functools.partial(jax.jit, static_argnames=("delta", "nplanes"))
def prepared_planes(col, *, delta: int, nplanes: int):
    """Stored (S, L) integer plane → (nplanes, n_pad/128, 128) uint8 byte
    planes of ``stored + delta`` (``delta`` = the plane's frame-of-
    reference offset less the agg's ``off``: 0 when they agree, and then
    the planes are the stored integer's own bytes)."""
    wide = jnp.uint32 if nplanes <= 4 else jnp.uint64
    if delta:
        v = (col.astype(jnp.int64) + delta).astype(wide)
    else:
        v = col.astype(wide)
    lanes = _to_lanes(v, 0)  # one relayout, at the value's own width
    return jnp.stack([((lanes >> (8 * k)) & 0xFF).astype(jnp.uint8)
                      for k in range(nplanes)])


def mask_lanes(mask):
    """The launch's (S, L) filter mask → the kernel's third operand:
    lane-major uint8, one byte a row (padding rows 0)."""
    return _to_lanes(mask.astype(jnp.uint8), 0)


def prepared_ids_row(id_refs, mask_ref, cards, sentinel: int, blk: int,
                     shift: int = 0):
    """In-kernel: one (blk/128, 128) ids block a key column and the mask
    block → the (1, blk) int32 row of masked ids the one-hots compare
    against. The columns' ids (each at its stored width, ``cards`` their
    cardinalities) combine to the cartesian id ``(id0 * c1 + id1) * c2 +
    id2``; ``shift`` keeps its high bits (the narrowed form's 128-cell
    block); a row the mask drops, pad rows among them, carries
    ``sentinel``. Widening, the multiply-adds and the select run on the
    dense tile, before the sublane→lane merge; for one key column this is
    the widen and the select alone."""
    ids = id_refs[0][:].astype(jnp.int32)
    for ref, card in zip(id_refs[1:], cards[1:]):
        ids = ids * _i32(card) + ref[:].astype(jnp.int32)
    if shift:
        ids = ids >> _i32(shift)
    keep = mask_ref[:].astype(jnp.int32) != _i32(0)
    return jnp.where(keep, ids, _i32(sentinel)).reshape(1, blk)


def prepared_plane_index(plane_refs, plane_rows):
    """[(ref, row)] per value channel, in channel order (channel 0, the
    folded count channel, has no operand rows)."""
    return [(ref, k) for ref, rows in zip(plane_refs, plane_rows)
            for k in range(rows)]


def prepared_plane_row(ref, k: int, blk: int):
    """In-kernel: one uint8 plane block → its (1, blk) bf16 channel row
    (bytes are bf16-exact)."""
    return ref[k].astype(jnp.int32).astype(jnp.float32) \
        .reshape(1, blk).astype(jnp.bfloat16)


def group_sums_blk(num_groups: int, a_real: int) -> int:
    """The row tile ``group_sums_prepared`` runs at."""
    lo = _plan_lo(num_groups, a_real, True)
    return _plan_blk(a_real, _hpad(num_groups, lo), lo)[0]


def group_sums_prepared(ids_lane, mask_lane, planes, num_groups: int, *,
                        interpret: bool = False):
    """``group_sums`` over prepared operands: channel 0 counts, channels
    1.. are the rows of ``planes`` in order. Returns (1 + Σ rows,
    num_groups) float64."""
    a_real = 1 + sum(p.shape[0] for p in planes)
    lo = _plan_lo(num_groups, a_real, True)
    hpad = _hpad(num_groups, lo)
    nsuper = ids_lane.shape[0] * 128 // SUPERBLOCK
    tot = _launch(ids_lane, (mask_lane, list(planes)), "prepared",
                  a_real=a_real, hpad=hpad, lo=lo, nsuper=nsuper,
                  rho_mode=False, interpret=interpret, ones_first=True,
                  num_groups=num_groups)
    return tot.reshape(a_real, hpad * lo)[:, :num_groups]


def rho_group_counts(slot, rho, num_groups: int, nrho: int, *,
                     interpret: bool = False):
    """counts[r, g] = #rows with slot == g and rho == r+1, r in [0, nrho).

    The nrho indicator channels are built inside the kernel from the
    lane-major rho operand — nothing rho-shaped ever hits HBM beyond the
    (n,) int32 itself. Padded rows get rho = 0, matching no channel.
    Returns (nrho, num_groups) float64 counts.
    """
    n = slot.shape[0]
    lo = _plan_lo(num_groups, nrho, False)
    hpad = _hpad(num_groups, lo)
    n_pad = ((n + SUPERBLOCK - 1) // SUPERBLOCK) * SUPERBLOCK
    nsuper = n_pad // SUPERBLOCK

    ids_lane = _pad_ids(slot, num_groups, n_pad, n)
    rho_lane = jnp.concatenate(
        [rho.astype(jnp.int32), jnp.zeros(n_pad - n, dtype=jnp.int32)]
    ).reshape(-1, 128)
    tot = _launch(ids_lane, rho_lane, "rho_lane", a_real=nrho, hpad=hpad,
                  lo=lo, nsuper=nsuper, rho_mode=True, interpret=interpret)
    return tot.reshape(nrho, hpad * lo)[:, :num_groups]


# ---------------------------------------------------------------------------
# channel planes: values → bf16 channels + recombination
# ---------------------------------------------------------------------------


def int_planes_needed(lo: float, hi: float) -> int:
    """Byte planes needed for ints in [lo, hi] after offset-by-floor(lo).
    Ceil/floor (not truncation) so fractional metadata bounds — e.g. from a
    float column behind a CAST — can't under-count the span."""
    import math

    rng = math.ceil(hi) - math.floor(lo)
    planes = 1
    while rng > (1 << (8 * planes)) - 1:
        planes += 1
    return planes


def int_planes(values, offset, nplanes: int):
    """values - offset split into ``nplanes`` byte planes (bf16-exact)."""
    v = values.astype(jnp.int64) - offset
    out = []
    for k in range(nplanes):
        out.append(((v >> (8 * k)) & 0xFF).astype(jnp.bfloat16))
    return out


def recombine_int(plane_sums, count, offset):
    """int64 recombination: Σv = Σ_k 256^k·S_k + count·offset (exact)."""
    tot = jnp.zeros_like(plane_sums[0], dtype=jnp.int64)
    for k, s in enumerate(plane_sums):
        tot = tot + (s.astype(jnp.int64) << (8 * k))
    return tot + count.astype(jnp.int64) * offset


def hll_nrho(log2m: int) -> int:
    """Max rho value: clz over (32 - log2m) value bits + 1 (sentinel caps)."""
    return 32 - log2m + 1


def hll_supported(num_groups: int, log2m: int) -> bool:
    nslots = num_groups * (1 << log2m)
    # rho mode has no folded count channel (ones_first=False)
    return mm_supported(nslots, hll_nrho(log2m), ones_first=False) \
        and nslots <= (1 << 20)


def hll_registers(slot, rho, num_groups: int, log2m: int, *,
                  interpret: bool = False):
    """HLL register build as rho-threshold indicator channels through the
    factored matmul kernel: counts[r, slot] = #rows with rho == r, register
    = max r with count > 0. Replaces the 12M-row scatter-max (~100ms on
    v5e) with a ~20ms matmul when G·m is small enough for VMEM.

    slot: (n,) int32 = gid * m + idx, masked rows → num_groups * m.
    rho:  (n,) int32 in [1, nrho].
    Returns (num_groups, m) int32 registers.
    """
    m = 1 << log2m
    nslots = num_groups * m
    nrho = hll_nrho(log2m)
    counts = rho_group_counts(slot, rho, nslots, nrho, interpret=interpret)
    rvals = jnp.arange(1, nrho + 1, dtype=jnp.int32)[:, None]
    regs = jnp.max(jnp.where(counts > 0.5, rvals, 0), axis=0).astype(jnp.int32)
    return regs.reshape(num_groups, m)


def _bf16_hi(v):
    """Top-16-bit truncation of f32 — exactly bf16-representable, built by
    bit-masking so XLA's excess-precision pass cannot fold it away."""
    bits = jax.lax.bitcast_convert_type(v, jnp.uint32)
    return jax.lax.bitcast_convert_type(bits & jnp.uint32(0xFFFF0000), jnp.float32)


def float_planes(values):
    """f32 → 3 bf16 channels summing exactly to the f32 value."""
    v = values.astype(jnp.float32)
    m0 = _bf16_hi(v)
    r1 = v - m0
    m1 = _bf16_hi(r1)
    r2 = r1 - m1
    m2 = _bf16_hi(r2)
    return [m0.astype(jnp.bfloat16), m1.astype(jnp.bfloat16),
            m2.astype(jnp.bfloat16)]


def recombine_float(plane_sums):
    tot = plane_sums[0]
    for s in plane_sums[1:]:
        tot = tot + s
    return tot
