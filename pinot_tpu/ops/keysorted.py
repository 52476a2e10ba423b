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
once too, in one of three layouts, chosen from the key order's own row
counts (``layout_rows``; engine/device.py has the bound and the cost):

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

SPILLED (``spill_layout``, ``spill_sums``): a skewed key, where a few fat
cells would set K for every cell. K is a power of two near the mean
(``layout_rows``); each cell keeps its first K rows in its home column, as
SLOTTED, and a fat cell's rows past K go into chunk columns of K slots of
their own, after the home columns' lane tiles, in cell order then chunk
order. A launch masks each column by its own row count and sums down the
slot axis as SLOTTED does; the home sums are the table, and the chunk sums
are combined per fat cell by one cumulative sum over the CHUNK axis read at
the fat cells' boundaries, then added at the fat cells' ids: a cumulative
sum over S chunks, a gather and a scatter of F fat cells, where ORDERED
reads every row and every cell.

ORDERED (``segment_sums``): the planes stay one row a row, in key order,
and a launch runs one cumulative sum a channel down the rows and reads
each cell's sum as the difference at its two boundaries (a gather of
cells + 1 elements a channel: 54% of a launch at 1.75M cells, the
cumulative sums 31%: PERF.md §6). It stays where neither slotted
layout fits the batch's byte budget.

Either way the table is the key space itself, in cell order: the dense
regime's output.

Sums are exact integers. A channel is summed modulo 2^32, and a cell's
sum is its own as long as that stays below 2^32: the executor splits a
value into planes of ``plane_bits`` so that the fullest cell's rows times
a plane's largest value does (``plane_bits_for`` of the fullest cell, not
of K), and recombines the planes in int64. Every partial sum on the way -
a home column, a chunk, the chunks' cumulative sum, a difference of two of
its entries, the add into the table; ordered, the rows' cumulative sum and
its differences - is a uint32 taken modulo 2^32, so the residue that
reaches the table is the cell's true sum modulo 2^32, which is the sum.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

SLOT_TILE = 8      # sublanes of a 32-bit tile: a cell's slots, K, to it
LANE_TILE = 128    # the slotted planes' cells, to it
SCAN_TILE = 1024   # the SPILLED chunks' cumulative sum runs in blocks of it


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


def layout_rows(rows, max_slots: int, fat_slots: int) -> int:
    """K, the slots a column, of a key order whose cells hold ``rows``
    (host numpy, once a key order). SLOTTED's, ``slot_rows`` of the
    fullest cell, where its slots stay within ``max_slots``. Else
    SPILLED's: of the candidates ``SLOT_TILE`` x 2^i below that - powers of
    two, so that batches of one shape meet each other's programs - the one
    whose slots (home and chunk columns, each to a lane tile) plus
    ``fat_slots`` a fat cell (what the chunks' combine pays a fat cell, in
    slots) are fewest, among those within ``max_slots``. 0 where none is
    (ORDERED)."""
    rows = np.asarray(rows, dtype=np.int64)
    home = slot_lanes(rows.size)
    fullest = slot_rows(int(rows.max(initial=0)))
    if fullest * home <= max_slots:
        return fullest
    best, best_cost = 0, None
    k = SLOT_TILE
    while k < fullest and k * home <= max_slots:
        over = rows[rows > k]
        slots = k * (home + slot_lanes(int(np.sum(-(-over // k) - 1))))
        cost = slots + fat_slots * over.size
        if slots <= max_slots and (best_cost is None or cost < best_cost):
            best, best_cost = k, cost
        k *= 2
    return best


class SpillLayout(NamedTuple):
    """The SPILLED layout of a key order at K (``spill_layout``), host
    numpy int32. A column a lane: the cells' home columns to a lane tile,
    then the chunk columns to a lane tile. ``first``: the row (in key
    order) a column's slot 0 holds; ``col_rows``: how many of its slots
    hold a row of its cell (0 in a padding column). The ``fat_cells``
    cells of more than K rows, in cell order: the ``f``-th owns chunks
    ``chunks[f]`` up to ``chunks[f + 1]`` (chunk ``i`` of a cell holds its
    rows ``(i + 1) K`` on) and is cell ``fat[f]``. ``fat`` and ``chunks``
    are padded to a lane tile: ``chunks`` repeats ``n_chunks``, so a
    padding entry's total is 0, and ``fat`` goes on from the home columns'
    lanes, so that its ids stay distinct and sorted and a padding one
    lands past the table."""
    first: np.ndarray
    col_rows: np.ndarray
    chunks: np.ndarray
    fat: np.ndarray
    fat_cells: int
    n_chunks: int


def spill_layout(rows, k: int) -> SpillLayout:
    """The SPILLED layout at ``k`` of a key order whose cells hold
    ``rows`` (host numpy, once a batch and set of key columns)."""
    rows = np.asarray(rows, dtype=np.int64)
    cells = rows.size
    starts = np.concatenate([[0], np.cumsum(rows)])
    fat = np.flatnonzero(rows > k)
    n_chunks = -(-rows[fat] // k) - 1
    chunks = np.concatenate([[0], np.cumsum(n_chunks)])
    n = int(chunks[-1])
    owner = np.repeat(fat, n_chunks)
    past = k * (np.arange(n) - np.repeat(chunks[:-1], n_chunks) + 1)
    home = slot_lanes(cells)
    first = np.full(home + slot_lanes(n), starts[-1], np.int32)
    first[:cells] = starts[:-1]
    first[home:home + n] = starts[owner] + past
    col_rows = np.zeros(first.size, np.int32)
    col_rows[:cells] = np.minimum(rows, k)
    col_rows[home:home + n] = np.minimum(rows[owner] - past, k)
    at = np.full(slot_lanes(fat.size) + 1, n, np.int32)
    at[:fat.size + 1] = chunks
    # the padding entries' ids run on past the home columns' lanes: every
    # id is distinct and in order, and a padding one lands off the table
    ids = np.arange(slot_lanes(fat.size), dtype=np.int32) + home - fat.size
    ids[:fat.size] = fat
    return SpillLayout(first, col_rows, at, ids, int(fat.size), n)


def _gather_slots(ordered, first, k):
    at = first[None, :] + jax.lax.broadcasted_iota(
        jnp.int32, (k, first.shape[0]), 0)
    flat = ordered.reshape(-1)
    return flat[jnp.minimum(at, flat.shape[0] - 1)]


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
    return _gather_slots(ordered, first, k)


@functools.partial(jax.jit, static_argnames=("k",))
def spill_plane(ordered, first, *, k):
    """A plane in key order laid out SPILLED (``SpillLayout.first``):
    ``(k, columns)`` at the plane's own width, slot ``(j, col)`` the row at
    ``first[col] + j`` - ``slot_plane``'s one gather over more columns."""
    return _gather_slots(ordered, first, k)


def column_mask(rows, shape):
    """``(k, lanes)`` bool: slot ``j`` of column ``c`` holds a row of its
    cell where ``j < rows[c]`` (``rows`` zero-padded to the lanes)."""
    rows = jnp.concatenate(
        [rows, jnp.zeros(shape[1] - rows.shape[0], rows.dtype)])
    return jax.lax.broadcasted_iota(jnp.int32, shape, 0) < rows[None, :]


def slot_mask(starts, shape):
    """``(k, lanes)`` bool: the slots that hold a row of their cell."""
    return column_mask(starts[1:] - starts[:-1], shape)


def slot_sums(channels, cells: int):
    """Per-cell sums modulo 2^32 of ``channels`` - uint32 slotted planes,
    a masked slot 0 - as ``(len(channels), cells)`` uint32: the sum down
    the slot axis."""
    return jnp.stack([jnp.sum(ch, axis=0, dtype=jnp.uint32)
                      for ch in channels])[:, :cells]


def _scan_chunks(x):
    """Cumulative sums modulo 2^32 of a 1-D uint32: within blocks of
    ``SCAN_TILE``, then over the blocks' totals. The TPU's compiler builds
    one cumulative sum of 10^4 to 10^6 elements in 30-45 s, in blocks in
    1-2 s (PERF.md §6)."""
    n = x.shape[0]
    x = jnp.concatenate([x, jnp.zeros(-n % SCAN_TILE, x.dtype)])
    inner = jnp.cumsum(x.reshape(-1, SCAN_TILE), axis=1, dtype=jnp.uint32)
    total = inner[:, -1]
    below = jnp.cumsum(total, dtype=jnp.uint32) - total
    return (inner + below[:, None]).reshape(-1)[:n]


def spill_sums(channels, cells: int, chunks, fat):
    """Per-cell sums modulo 2^32 of ``channels`` - uint32 planes laid out
    SPILLED, a masked slot 0 - as ``(len(channels), cells)`` uint32: the
    sum down the slot axis, whose home columns are the table; the chunk
    columns' sums run cumulatively over the chunk axis, read at the fat
    cells' boundaries (``chunks``) and added at their ids (``fat``).

    Channel by channel, every array one-dimensional, as ``segment_sums``
    does: over the channels stacked, the TPU's windowed scatter moved one
    channel's totals between fat cells in a served program (PERF.md
    §6). ``fat``'s ids are distinct and sorted, a padding one past the
    home lanes (``spill_layout``), so the scatter is told so."""
    home = slot_lanes(cells)
    out = []
    for ch in channels:
        col = jnp.sum(ch, axis=0, dtype=jnp.uint32)
        with jax.named_scope("pinot.full_spill"):
            run = jnp.concatenate(
                [jnp.zeros(1, jnp.uint32), _scan_chunks(col[home:])])
            at = run[chunks]
            table = jnp.concatenate(
                [col[:home], jnp.zeros(LANE_TILE, jnp.uint32)])
            out.append(table.at[fat].add(
                at[1:] - at[:-1], indices_are_sorted=True,
                unique_indices=True)[:cells])
    return jnp.stack(out)


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
