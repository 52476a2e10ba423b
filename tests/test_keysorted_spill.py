"""The full key space's SPILLED layout (ops/keysorted.py): a skewed key's
cells slotted at a K near their mean, each fat cell's rows past K in chunk
columns of K slots of their own, the chunks combined over the chunk axis.

The layout laid out by hand; the sums channel by channel against
``numpy.bincount`` and against the ORDERED layout's cumulative sums, over
hand-made and Zipf row counts, cells of 0, 1, K-1, K, K+1, 2K and 2K+1
rows, one fat cell over several lane tiles, the batch's padding rows,
values whose top byte is 255 at 8-, 16- and 24-bit planes and a filter
that masks rows inside chunks; an error confined to one value plane; and
the rule that picks K from the row counts alone."""

import jax.numpy as jnp
import numpy as np
import pytest

from pinot_tpu.engine import device as device_mod
from pinot_tpu.ops import keysorted as ks

VALUE_BITS = 24


def _edges(rng, k=8):
    """Every edge a column has, a few times over, shuffled: 0, 1, K-1, K,
    K+1, 2K and 2K+1 rows, among cells of 0-K rows."""
    edges = np.array([0, 1, k - 1, k, k + 1, 2 * k, 2 * k + 1] * 9)
    return rng.permutation(np.concatenate([edges, rng.integers(0, k + 1,
                                                               300)]))


def _one_fat_cell(rng, k=8):
    """One cell of 3 lane tiles and 5 rows of chunks beside cells of 0-K
    rows, and a fat cell first and last besides."""
    rows = rng.integers(0, k + 1, 1_000)
    rows[[0, 417, -1]] = (2 * k + 3, (3 * ks.LANE_TILE + 1) * k + 5, 3 * k)
    return rows


def _zipf(rng, k=None):
    """Rows a cell of 20,000 keys drawn Zipf z = 1, 150,000 rows."""
    p = 1.0 / np.arange(1, 20_001)
    return rng.permutation(rng.multinomial(150_000, p / p.sum()))


CASES = {"edges": (_edges, 8), "one_fat_cell": (_one_fat_cell, 8),
         "zipf": (_zipf, None)}


def _rule(rows):
    """K by the executor's rule over a batch of exactly these rows."""
    lane_rows = -(-int(rows.sum()) // ks.LANE_TILE) * ks.LANE_TILE
    return ks.layout_rows(rows, device_mod.FULL_SLOT_PADDING * lane_rows,
                          device_mod.FULL_SPILL_FAT_SLOTS)


def _planes(rng, n, n_pad):
    """A 24-bit value a row (a third of them 0xFFFFFF: every byte 255) and
    a 0-10 filter column, in key order; the batch's padding rows past
    ``n`` hold what a launch must never count."""
    value = rng.integers(0, 1 << VALUE_BITS, n_pad).astype(np.uint32)
    value[rng.random(n_pad) < 1 / 3] = (1 << VALUE_BITS) - 1
    value[n:] = 0xFFFFFFFF
    filt = rng.integers(0, 11, n_pad).astype(np.uint8)
    filt[n:] = 2
    return value, filt


def _channels(value, filt, keep, bits):
    """The launch's channels: a count, then the value in planes of
    ``bits`` - of the rows the filter (1 <= f <= 3) and ``keep`` leave."""
    keep = keep & (filt >= 1) & (filt <= 3)
    v = jnp.where(keep, value, jnp.uint32(0))
    return [keep.astype(jnp.uint32)] + [
        (v >> (bits * j)) & ((1 << bits) - 1)
        for j in range(-(-VALUE_BITS // bits))]


def _spilled(rows, k, value, filt, bits):
    """(channels, cells) uint32: the SPILLED layout's sums."""
    layout = ks.spill_layout(rows, k)
    first = jnp.asarray(layout.first)
    vs = ks.spill_plane(jnp.asarray(value.reshape(-1, 128)), first, k=k)
    fs = ks.spill_plane(jnp.asarray(filt.reshape(-1, 128)), first, k=k)
    keep = ks.column_mask(jnp.asarray(layout.col_rows), vs.shape)
    return np.asarray(ks.spill_sums(
        _channels(vs, fs, keep, bits), rows.size,
        jnp.asarray(layout.chunks), jnp.asarray(layout.fat)))


def _want(rows, value, filt, bits):
    """(channels, cells) int64: the same sums by numpy, exactly."""
    n = int(rows.sum())
    cell = np.repeat(np.arange(rows.size), rows)
    keep = (filt[:n] >= 1) & (filt[:n] <= 3)
    out = [np.bincount(cell[keep], minlength=rows.size)]
    for j in range(-(-VALUE_BITS // bits)):
        plane = (value[:n][keep] >> (bits * j)) & ((1 << bits) - 1)
        out.append(np.bincount(cell[keep], weights=plane.astype(np.float64),
                               minlength=rows.size).astype(np.int64))
    return np.stack(out)


def test_the_layout_by_hand():
    """Cells of 3, 0, 20, 8 and 9 rows at K = 8: the home columns hold
    each cell's first 8 rows; cell 2's rows 8-15 and 16-19 and cell 4's row
    8 are three chunk columns after the home columns' lane tile; the
    padding entries add nothing, and land past the table."""
    layout = ks.spill_layout(np.array([3, 0, 20, 8, 9]), 8)
    assert (layout.fat_cells, layout.n_chunks) == (2, 3)
    assert layout.first.size == layout.col_rows.size == 2 * ks.LANE_TILE
    assert layout.first[:5].tolist() == [0, 3, 3, 23, 31]
    assert layout.first[128:131].tolist() == [11, 19, 39]
    assert (layout.first[5:128] == 40).all()
    assert (layout.first[131:] == 40).all()
    assert layout.col_rows[:5].tolist() == [3, 0, 8, 8, 8]
    assert layout.col_rows[128:131].tolist() == [8, 4, 1]
    assert not layout.col_rows[5:128].any()
    assert not layout.col_rows[131:].any()
    assert layout.chunks.size == ks.LANE_TILE + 1
    assert layout.chunks[:3].tolist() == [0, 2, 3]
    assert (layout.chunks[3:] == 3).all()
    # the padding ids go on past the home lanes: distinct, sorted, off
    # the table
    assert layout.fat.size == ks.LANE_TILE
    assert layout.fat[:2].tolist() == [2, 4]
    assert layout.fat[2:].tolist() == list(range(128, 128 + 126))
    assert all(a.dtype == np.int32 for a in layout[:4])


@pytest.mark.parametrize("bits", [8, 16, 24])
@pytest.mark.parametrize("case", list(CASES))
def test_spilled_sums_are_numpys_channel_by_channel(case, bits):
    """Each channel - the count and every value plane - cell by cell
    against numpy and against the ORDERED layout's cumulative sums over
    the same rows. A channel is summed modulo 2^32: a plane as wide as
    the FULLEST cell allows (``plane_bits_for``, not K's) never wraps, a
    wider one may, and then agrees with numpy modulo 2^32."""
    make, k = CASES[case]
    rng = np.random.default_rng(100 * len(case) + bits)
    rows = make(rng)
    k = k or _rule(rows)
    assert 0 < k < ks.slot_rows(rows.max())
    n = int(rows.sum())
    n_pad = -(-(n + 77) // 128) * 128          # 77 or more padding rows
    value, filt = _planes(rng, n, n_pad)
    got = _spilled(rows, k, value, filt, bits)
    want = _want(rows, value, filt, bits)
    assert got.shape == want.shape == (1 + -(-VALUE_BITS // bits), rows.size)
    starts = jnp.asarray(np.concatenate([[0], np.cumsum(rows)])
                         .astype(np.int32))
    at = jnp.arange(n_pad, dtype=jnp.int32).reshape(-1, 128)
    ordered = np.asarray(ks.segment_sums(starts, _channels(
        jnp.asarray(value.reshape(-1, 128)), jnp.asarray(filt.reshape(
            -1, 128)), at < n, bits)))
    for ch in range(got.shape[0]):
        assert (got[ch] == ordered[ch]).all(), ch
        assert (got[ch] == want[ch] % (1 << 32)).all(), ch
    if bits <= ks.plane_bits_for(rows.max()):
        assert (got == want).all()
        total = sum(got[1 + j].astype(np.int64) << (bits * j)
                    for j in range(got.shape[0] - 1))
        cell = np.repeat(np.arange(rows.size), rows)
        keep = (filt[:n] >= 1) & (filt[:n] <= 3)
        assert (total == np.bincount(cell[keep], weights=value[:n][keep]
                                     .astype(np.float64),
                                     minlength=rows.size)).all()
    # the filter keeps rows inside the fat cells' chunks and masks others
    cell = np.repeat(np.arange(rows.size), rows)
    past_k = np.arange(n) - np.asarray(starts)[cell] >= k
    keep = (filt[:n] >= 1) & (filt[:n] <= 3)
    assert (past_k & keep).any() and (past_k & ~keep).any()


def test_a_fat_cell_past_k_needs_the_fullest_cells_planes():
    """The planes' width is the fullest cell's, not K's: at K = 8 a 24-bit
    plane would do for every column, but the fat cell's 600 rows of
    0xFFFFFF sum past 2^32 in it. At ``plane_bits_for(600)`` = 16 bits
    the planes are exact."""
    rows = np.array([4] * 200 + [600] + [8] * 100)
    k = 8
    assert ks.plane_bits_for(k) == 24 and ks.plane_bits_for(600) == 16
    n = int(rows.sum())
    n_pad = -(-(n + 1) // 128) * 128
    value = np.full(n_pad, (1 << VALUE_BITS) - 1, np.uint32)
    filt = np.full(n_pad, 2, np.uint8)
    wide = _spilled(rows, k, value, filt, 24)
    want = _want(rows, value, filt, 24)
    assert want[1][200] >= 1 << 32 and wide[1][200] != want[1][200]
    assert (np.delete(wide, 200, axis=1) == np.delete(want, 200, axis=1)
            ).all()
    narrow = _spilled(rows, k, value, filt, 16)
    assert (narrow == _want(rows, value, filt, 16)).all()


def test_an_error_in_one_plane_is_seen_in_that_plane_alone():
    """An answer off by 116 x 2^16 with its low 16 bits right is one value
    plane of one cell off by 116 while the count and the other plane
    agree: the check channel by channel names that plane and that cell,
    where the count alone would pass."""
    rng = np.random.default_rng(41)
    rows = _one_fat_cell(rng)
    n = int(rows.sum())
    value, filt = _planes(rng, n, -(-(n + 77) // 128) * 128)
    got = _spilled(rows, 8, value, filt, 16)
    want = _want(rows, value, filt, 16)
    assert (got == want).all()
    fat = 417
    bad = got.copy()
    bad[2, fat] += 116                 # the high plane of the fat cell
    off = [(int(ch), int(c)) for ch, c in zip(*np.nonzero(bad != want))]
    assert off == [(2, fat)]
    assert (bad[0] == want[0]).all()   # the counts agree
    total = (bad[1].astype(np.int64) + (bad[2].astype(np.int64) << 16))
    exact = want[1] + (want[2] << 16)
    assert int(total[fat] - exact[fat]) == 116 << 16 == 7_602_176
    assert (total[fat] - exact[fat]) % (1 << 16) == 0


def test_uniform_counts_keep_todays_k_and_no_chunk():
    """Where the fullest cell's K x the cells stay within the bound the
    rule gives SLOTTED's K - the fullest cell's rows to the sublane tile -
    and a layout at that K has no fat cell and no chunk."""
    rng = np.random.default_rng(3)
    for rows in (np.full(10_000, 12), rng.integers(5, 20, 30_000),
                 np.full(500, 64)):
        k = _rule(rows)
        assert k == ks.slot_rows(rows.max())
        layout = ks.spill_layout(rows, k)
        assert (layout.fat_cells, layout.n_chunks) == (0, 0)
        assert layout.first.size == ks.slot_lanes(rows.size)
        assert (layout.col_rows[:rows.size] == rows).all()


@pytest.mark.parametrize("fat_slots", [0, 800, 3_000, 1 << 20])
def test_a_skewed_key_takes_the_cheapest_k_within_the_bound(fat_slots):
    """Zipf counts: of K = 8 x 2^i below the fullest cell, the one whose
    home and chunk slots plus ``fat_slots`` a fat cell are fewest, among
    those within the bound - found here by trying every one. A dearer fat
    cell never lowers K."""
    rng = np.random.default_rng(11)
    rows = _zipf(rng)
    lane_rows = -(-int(rows.sum()) // 128) * 128
    bound = device_mod.FULL_SLOT_PADDING * lane_rows
    k = ks.layout_rows(rows, bound, fat_slots)
    costs = {}
    cand = ks.SLOT_TILE
    while cand < ks.slot_rows(rows.max()):
        layout = ks.spill_layout(rows, cand)
        slots = cand * layout.first.size
        if slots <= bound:
            costs[cand] = slots + fat_slots * layout.fat_cells
        cand *= 2
    assert k == min(costs, key=lambda c: (costs[c], c))
    cheaper = ks.layout_rows(rows, bound, fat_slots // 2)
    assert cheaper <= k


def test_no_k_within_the_bound_is_ordered():
    """More cells than the bound's slots at K = 8: 0, the ORDERED layout."""
    rows = np.zeros(100_000, np.int64)
    rows[7] = 1_000
    assert ks.layout_rows(rows, 4 * 1_024, 3_000) == 0
    assert ks.layout_rows(rows, 8 * ks.slot_lanes(100_000) + 8 * 128,
                          3_000) == 8
