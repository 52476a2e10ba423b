"""Group sums over a batch's rows in KEY ORDER: the FULL key-space regime.

A group-by whose key space is large (past engine/device.py
NARROW_MIN_CELLS) and FULL - no slice on the hierarchy, every cell alive -
fits neither the dense kernel (its hi one-hot is cells / 128 rows: 13,672
at 1.75M cells) nor the narrowed table (128 live blocks). What such a
statement groups BY is the batch's alone: the key columns do not depend on
a launch. So the batch keeps, once a set of key columns, the permutation
that sorts its rows by cartesian key (``key_order``) and each cell's first
row in that order (``starts``); a column a statement reads (a filter's, a
value's) is projected into that order once (``project_plane``,
``project_value``), like the ``gk::`` and ``gv::`` operands of the dense
kernel. The cells are ragged in that order, and the batch pays for that
once too, in one of two layouts of the projected planes:

SLOTTED (``slot_plane``, ``slot_sums``): every cell gets the same number
of slots K - the fullest cell's rows, rounded up to the sublane tile - and
a plane is ``(K, cells padded to a lane tile)``, the cells along the
lanes: slot ``(j, c)`` holds the ``j``-th row of cell ``c``. A launch
masks (its filter over the slotted columns, ``j < rows[c]``) and sums down
the slot axis: one streaming fusion, no cumulative sum, no gather, no
scatter and no one-hot at launch time. Uniform keys pad little (dbgen's at
37.5M rows: x1.2 at 62,500 cells, x2.2 at 1.75M); the layout is taken
where K x cells stays within engine/device.py FULL_SLOT_PADDING times
the batch's rows.

ORDERED (``segment_sums``): the planes stay one row a row, in key order,
and a launch runs one cumulative sum a channel down the rows and reads
each cell's sum as the difference at its two boundaries (a gather of
cells + 1 elements a channel: 54% of a launch at 1.75M cells, the
cumulative sums 31%: PERF.md, PR 36). It stays for a skewed key - one
cell many times the mean, where K x cells would be many times the rows -
and where the slotted planes do not fit the batch's byte budget.

Either way the table is the key space itself: the dense regime's output.

Sums are exact integers. A channel is summed modulo 2^32, and a cell's
sum (ordered: a difference of two such sums) is its own as long as that
stays below 2^32: the executor splits a value into planes of
``plane_bits`` so that the fullest cell's rows times a plane's largest
value does (``plane_bits_for``), and recombines the planes in int64.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

SLOT_TILE = 8      # sublanes of a 32-bit tile: a cell's slots, K, to it
LANE_TILE = 128    # the slotted planes' cells, to it


def plane_bits_for(max_cell_rows: int) -> int:
    """Widest value plane (a multiple of 8 bits) whose per-cell sum stays
    under 2^32 with ``max_cell_rows`` rows in the fullest cell; 0 where
    not even a byte plane does (a cell of 2^24 rows or more)."""
    bits = (32 - max(int(max_cell_rows), 1).bit_length()) // 8 * 8
    return max(bits, 0)


@functools.partial(jax.jit, static_argnames=("cards",))
def _cartesian(ids, *, cards):
    """Lane-major id planes -> each row's cartesian key, flat; a padding
    row (an id at its column's cardinality) past the key space."""
    num_groups = 1
    for c in cards:
        num_groups *= c
    gid = bad = None
    for plane, c in zip(ids, cards):
        i = plane.astype(jnp.int32)
        oob = i >= c
        bad = oob if bad is None else bad | oob
        i = jnp.minimum(i, c - 1)
        gid = i if gid is None else gid * c + i
    return jnp.where(bad, num_groups, gid).reshape(-1)


def key_order(ids, *, cards):
    """Lane-major id planes of the key columns (``gk::`` operands: a
    padding row carries its column's cardinality) -> ``(perm, starts,
    max_cell_rows)``: the rows' stable order by cartesian key, padding
    last; ``starts[c]`` the first position of cell ``c`` in that order and
    ``starts[G]`` the number of real rows; the fullest cell's rows.

    Once a batch and set of key columns, and on the HOST: the keys come
    off the device (150 MB at 37.5M rows), numpy sorts them (two stable
    radix passes over 16-bit halves: ~3 s there) and the order goes back.
    The device's own sort of the rows is a program the TPU's compiler
    takes 25 s to build: beside the full regime's program that did not
    fit a first answer into a statement's 60 s (PERF.md, PR 36)."""
    num_groups = 1
    for c in cards:
        num_groups *= c
    gid = np.asarray(_cartesian(tuple(ids), cards=tuple(cards)))
    perm = np.argsort((gid & 0xFFFF).astype(np.uint16), kind="stable")
    perm = perm[np.argsort((gid >> 16).astype(np.uint16)[perm],
                           kind="stable")].astype(np.int32)
    starts = np.searchsorted(
        gid[perm], np.arange(num_groups + 1, dtype=np.int32),
        side="left").astype(np.int32)
    return (jnp.asarray(perm), jnp.asarray(starts),
            int(np.max(np.diff(starts), initial=0)))


@jax.jit
def project_plane(stored, perm):
    """A stored (S, L) plane's rows in key order, lane-major
    (n_pad / 128, 128) at the plane's own width."""
    from pinot_tpu.ops.groupby_mm import _to_lanes

    lanes = _to_lanes(stored, 0)
    return lanes.reshape(-1)[perm].reshape(lanes.shape)


@jax.jit
def project_value(planes, perm):
    """A value's lane-major uint8 byte planes (a ``gv::`` operand, at most
    four) -> the value as one uint32 a row, in key order."""
    lanes = planes[0].astype(jnp.uint32)
    for k in range(1, planes.shape[0]):
        lanes = lanes | (planes[k].astype(jnp.uint32) << (8 * k))
    return lanes.reshape(-1)[perm].reshape(lanes.shape)


def slot_rows(max_cell_rows: int) -> int:
    """Slots a cell of the SLOTTED layout, K: the fullest cell's rows, to
    the sublane tile."""
    return -(-max(int(max_cell_rows), 1) // SLOT_TILE) * SLOT_TILE


def slot_lanes(cells: int) -> int:
    """The slotted planes' lane axis: the cells, to a lane tile."""
    return -(-cells // LANE_TILE) * LANE_TILE


@functools.partial(jax.jit, static_argnames=("k",))
def slot_plane(ordered, starts, *, k):
    """A plane in key order (``project_plane`` / ``project_value``) laid
    out cell by slot: ``(k, slot_lanes(cells))`` at the plane's own width,
    slot ``(j, c)`` the row at ``starts[c] + j``. Past a cell's rows a slot
    holds whatever follows (the next cells' rows, the batch's padding): a
    launch masks it by ``j < rows[c]``. A padding row of the batch (sorted
    last, past ``starts[cells]``) has no slot. The slot index lives in
    this program alone; nothing of ``(k, cells)`` stays but the plane."""
    cells = starts.shape[0] - 1
    lanes = slot_lanes(cells)
    first = jnp.concatenate(
        [starts[:-1], jnp.full(lanes - cells, starts[-1], starts.dtype)])
    at = first[None, :] + jax.lax.broadcasted_iota(jnp.int32, (k, lanes), 0)
    flat = ordered.reshape(-1)
    return flat[jnp.minimum(at, flat.shape[0] - 1)]


def slot_mask(starts, shape):
    """``(k, lanes)`` bool: the slots that hold a row of their cell."""
    rows = starts[1:] - starts[:-1]
    rows = jnp.concatenate(
        [rows, jnp.zeros(shape[1] - rows.shape[0], rows.dtype)])
    return jax.lax.broadcasted_iota(jnp.int32, shape, 0) < rows[None, :]


def slot_sums(channels, cells: int):
    """Per-cell sums modulo 2^32 of ``channels`` - uint32 slotted planes,
    a masked slot 0 - as ``(len(channels), cells)`` uint32: the sum down
    the slot axis."""
    return jnp.stack([jnp.sum(ch, axis=0, dtype=jnp.uint32)
                      for ch in channels])[:, :cells]


def segment_sums(starts, channels):
    """Per-cell sums modulo 2^32 of ``channels`` - uint32 planes in key
    order, at any one shape, a masked row 0 - as ``(len(channels), G)``
    uint32: the cumulative sum down the rows, read at each cell's two
    boundaries."""
    at = starts - 1
    first = starts > 0
    at = jnp.maximum(at, 0)
    out = []
    for ch in channels:
        run = jnp.cumsum(ch.reshape(-1), dtype=jnp.uint32)
        below = jnp.where(first, run[at], jnp.uint32(0))
        out.append(below[1:] - below[:-1])
    return jnp.stack(out)
