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
``project_value``), like the ``gk::``
and ``gv::`` operands of the dense kernel. A launch then computes only what
is its own: the filter's mask over the projected columns, one cumulative
sum a channel down the rows, and each cell's sum as the difference of the
cumulative sum at its two boundaries (``segment_sums``). No scatter, no sort
and no one-hot at launch time, and the table is the key space itself: the
dense regime's output form.

Sums are exact integers. A channel is summed modulo 2^32, and a
difference of two such sums is the cell's own sum as long as that stays
below 2^32: the executor splits a value into planes of ``plane_bits`` so
that the fullest cell's rows times a plane's largest value does
(``plane_bits_for``), and recombines the planes in int64.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


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
