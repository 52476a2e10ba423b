"""Radix-partitioned high-cardinality group-by: the chunked-sort basis.

Replaces the monolithic-``lax.sort`` basis of the sorted/high-cardinality
device regime (the MAP_BASED analog of DictionaryBasedGroupKeyGenerator).
The old basis sorted the full (n,) int64 combined-key array once per payload
family — at 100M rows that single sort ran at ~1.6 GB/s (0.4% of v5e HBM
peak; BENCH_r05 ``micro.sortkey_int64``), because XLA's comparator network
over a 0.8GB operand is HBM-bound on O(log^2 n) passes. This module keeps
the *sortedness* the regime depends on but restructures WHERE the sorting
happens so almost all comparator passes run over VMEM-resident operands:

1. **Radix key packing** (``pack_keys``): the cartesian dict-id key packs
   into int32 whenever the key space fits (< 2^31) — half the bytes through
   every comparator pass. The int32 key is viewed as (high radix bits =
   partition, low bits = in-partition id); int64 remains the fallback basis
   for wider key spaces, through the same code path.
2. **Chunked level-1 sorts**: rows split into C chunks of L rows (L sized
   for VMEM-resident sorting, ``CHUNK_ROWS``) and ONE batched ``lax.sort``
   sorts all chunks independently — log^2(L) passes instead of log^2(n),
   each over an L-row operand instead of the full array.
3. **Run-end partials, no scatters, no secondary sorts**: within a sorted
   chunk every group is a contiguous run. COUNT/integer-SUM come from
   position/cumsum differences at run ends (two's-complement-exact for
   ints); float sums and MIN/MAX come from *segmented* associative scans
   (``jax.lax.associative_scan``) over the single sorted order — the old
   basis paid a full extra (key, value) sort per MIN/MAX argument and an
   n-row position scatter for the table build; both are gone.
4. **Static-bound compaction**: each chunk's run-end entries are compacted
   to the front by a second batched sort of the end-masked keys and sliced
   to E = min(L, K+1) entries, where K is the group-table cap
   (numGroupsLimit). A chunk with more than E distinct groups proves the
   whole query overflows K (chunk-distinct <= global-distinct), so the
   slice can never silently drop a surviving group — overflow is detected
   and reported through ``n_groups_total`` exactly like the old basis.
5. **Level-2+ merge**: the C*E compacted partials (~n / (L/E) rows)
   re-enter the same chunk/sort/combine/compact structure until chunking
   stops paying, then one answer-scale sort builds the final (K,) group
   table — no pass ever sorts a monolithic row-scale operand. The same
   merge, applied to device-gathered (D, K) tables, makes the regime
   MESH-COMBINABLE (``merge_tables``; parallel/mesh.py) — the old basis
   had to route every multi-chip high-card query to the host.

The radix histogram (``bucket_histogram``) rides the factored one-hot
matmul kernel (ops/groupby_mm.py) over the key's high bits — the
bandwidth-shaped occupancy probe for the partition structure (tests pin
it against np.bincount).

Everything here is trace-time static in shapes: chunk plans derive from
array lengths and the template's K, so jit caches stay keyed on the same
(template, batch-shape) pairs the executor already uses.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

INT32_SENTINEL = (1 << 31) - 1   # masked/padded rows: sorts after real keys
INT64_SENTINEL = (1 << 63) - 1   # same role for the int64 fallback basis
# int32 packing bound: keys must stay strictly below the sentinel
MAX_KEYSPACE_32 = (1 << 31) - 1

CHUNK_ROWS = 1 << 20          # level-1 chunk length target (VMEM-scale sort)
CHUNK_ROWS_MAX = 1 << 23      # growth cap when K forces bigger chunks (the
                              # q4 HLL slot space — 2000 groups x 1024
                              # registers ≈ 2M keys — needs 8M-row chunks
                              # before even ratio-2 compaction engages)
MIN_COMPACT_RATIO = 4         # chunking pays only when E <= L / this
HLL_COMPACT_RATIO = 2         # the HLL dedup keeps ONE entry per slot per
                              # chunk and iterates, so even a 2x shrink per
                              # pass converges in O(log) passes


def _sentinel_for(dtype) -> int:
    return INT32_SENTINEL if jnp.dtype(dtype) == jnp.int32 else INT64_SENTINEL


def pack_keys(per_col_gids, cardinalities, mask):
    """Cartesian combined key in the NARROWEST dtype the key space allows:
    int32 when the product of cardinalities fits (< 2^31), else int64.
    Masked docs get the dtype's sentinel so they sort to the tail. Same
    cartesian arithmetic as ops/agg.py group_ids_combine, uncapped — the
    caller guarantees the product fits int64."""
    total = 1
    for c in cardinalities:
        total *= int(c)
    dt = jnp.int32 if total < MAX_KEYSPACE_32 else jnp.int64
    sentinel = _sentinel_for(dt)
    key = None
    for g, c in zip(per_col_gids, cardinalities):
        g = jnp.clip(g, 0, c - 1).astype(dt)
        key = g if key is None else key * c + g
    return jnp.where(mask, key, sentinel)


def plan_chunks(n: int, table_k: int, chunk_rows: int | None = None,
                min_ratio: int = MIN_COMPACT_RATIO):
    """(C, L): level-1 chunk count and length. Static per (n, K). Chunking
    engages only when the compaction width E = min(L, K+1) shrinks the
    next merge level by at least ``min_ratio`` — otherwise C=1 degenerates
    to a single monolithic sort (still through the run-end/segmented-scan
    aggregation, which needs no secondary sorts either way)."""
    L = chunk_rows or CHUNK_ROWS
    cap = max(L, CHUNK_ROWS_MAX)
    while L < min_ratio * (table_k + 1) and L < cap:
        L *= 2
    if n < 2 * L or min(L, table_k + 1) * min_ratio > L:
        return 1, n
    return -(-n // L), L


def _pad_chunks(x, C: int, L: int, fill):
    n = x.shape[0]
    if C * L > n:
        x = jnp.concatenate([x, jnp.full(C * L - n, fill, x.dtype)])
    return x.reshape(C, L)


# ---------------------------------------------------------------------------
# segmented scans (the scatter-free / secondary-sort-free aggregation core)
# ---------------------------------------------------------------------------


def _seg_scan(values, is_start, op, axis):
    """Inclusive segmented scan along ``axis``: ``op`` accumulates within
    runs, resetting wherever ``is_start`` is True (the standard segmented
    monoid — associative, so it rides jax.lax.associative_scan)."""

    def comb(a, b):
        av, af = a
        bv, bf = b
        return jnp.where(bf, bv, op(av, bv)), af | bf

    v, _ = jax.lax.associative_scan(comb, (values, is_start), axis=axis)
    return v


def seg_sum(values, is_start, axis=1):
    return _seg_scan(values, is_start, lambda a, b: a + b, axis)


def seg_min(values, is_start, axis=1):
    return _seg_scan(values, is_start, jnp.minimum, axis)


def seg_max(values, is_start, axis=1):
    return _seg_scan(values, is_start, jnp.maximum, axis)


def _red_for(name):
    """Segmented reduction for a partial-column name (the ``min::``/
    ``max::`` prefixes pick the extremal monoid; counts and sums add)."""
    if name.startswith("min::"):
        return seg_min
    if name.startswith("max::"):
        return seg_max
    return seg_sum


def _boundaries(sk):
    """(is_start, is_end) along the last axis of a sorted key array."""
    lead = jnp.ones(sk.shape[:-1] + (1,), dtype=bool)
    is_start = jnp.concatenate([lead, sk[..., 1:] != sk[..., :-1]], axis=-1)
    is_end = jnp.concatenate([sk[..., :-1] != sk[..., 1:], lead], axis=-1)
    return is_start, is_end


# ---------------------------------------------------------------------------
# the two-level aggregation
# ---------------------------------------------------------------------------


def chunked_group_aggregate(key, payloads, sums, mins, maxs, table_k: int,
                            chunk_rows: int | None = None):
    """Radix-partitioned group aggregation over a packed key array.

    key:      (n,) int32/int64 packed keys; masked rows carry the dtype
              sentinel (pack_keys).
    payloads: {name: (values(n,), kind)} with kind "int" | "float" — each
              distinct argument rides the level-1 sort exactly once.
    sums/mins/maxs: payload names needing that reduction.
    table_k:  group-table cap (min(numGroupsLimit, MAX_SORTED_GROUPS)).

    Returns {"skeys": (K,) int64 (INT64_SENTINEL empties),
             "empty": (K,) bool, "gcount": (K,) int64,
             "sum::<name>"/"min::<name>"/"max::<name>": (K,) raw columns
             (callers apply empty-slot fills), "n_groups_total": scalar}.
    Overflow contract: n_groups_total counts every distinct real key; when
    any level-1 chunk holds more than E = min(L, K+1) distinct keys (which
    implies global distinct > K), the total is forced above K so the
    executor's host fallback fires exactly as on the old basis.
    """
    n = key.shape[0]
    K = table_k
    sentinel = _sentinel_for(key.dtype)
    C, L = plan_chunks(n, K, chunk_rows)
    E = min(L, K + 1)

    kc = _pad_chunks(key, C, L, sentinel)
    names = list(payloads)
    ops = [kc] + [_pad_chunks(payloads[nm][0], C, L, 0) for nm in names]
    sorted_ops = jax.lax.sort(ops, dimension=1, num_keys=1)
    sk = sorted_ops[0]
    pv = dict(zip(names, sorted_ops[1:]))
    is_start, is_end = _boundaries(sk)
    real = sk != sentinel
    chunk_distinct = jnp.sum(is_start & real, axis=1)

    # level-1 per-run partials, read at run ends. Counts and integer sums
    # are *differences of plain cumulatives* taken after compaction (the
    # compacted prefix preserves end order, so entry j-1 is the previous
    # run's end); int64 cumsum differences stay exact even if the running
    # total wraps. Float sums use a SEGMENTED scan: a global-cumsum
    # difference suffers catastrophic cancellation when a tiny group sits
    # next to huge ones (r3 review), while the segmented form only ever
    # adds a run's own values. Min/max are segmented scans too — this is
    # what retires the old basis's per-argument secondary sorts.
    pos = jnp.broadcast_to(jnp.arange(L, dtype=jnp.int32), (C, L))
    cols = {"pos": pos}
    for nm in sums:
        v = pv[nm]
        if payloads[nm][1] == "int":
            cols["csum::" + nm] = jnp.cumsum(v, axis=1, dtype=jnp.int64)
        else:
            cols["ssum::" + nm] = seg_sum(v, is_start)
    for nm in mins:
        cols["min::" + nm] = seg_min(pv[nm], is_start)
    for nm in maxs:
        cols["max::" + nm] = seg_max(pv[nm], is_start)

    # compaction: end-masked keys sort to the front (non-ends become the
    # sentinel), slice to the static E bound. Keys are unique per chunk
    # among ends, so stability is irrelevant.
    cnames = list(cols)
    comp = jax.lax.sort(
        [jnp.where(is_end, sk, sentinel)] + [cols[nm] for nm in cnames],
        dimension=1, num_keys=1)
    ck = comp[0][:, :E]
    cc = {nm: arr[:, :E] for nm, arr in zip(cnames, comp[1:])}

    # cumulative -> per-run partials via neighbor differences
    def _diff(arr, first):
        prev = jnp.concatenate(
            [jnp.full((C, 1), first, arr.dtype), arr[:, :-1]], axis=1)
        return arr - prev

    part = {"cnt": _diff(cc["pos"], -1).astype(jnp.int64)}
    for nm in sums:
        part["sum::" + nm] = _diff(cc["csum::" + nm], 0) \
            if payloads[nm][1] == "int" else cc["ssum::" + nm]
    for nm in mins:
        part["min::" + nm] = cc["min::" + nm]
    for nm in maxs:
        part["max::" + nm] = cc["max::" + nm]

    # level-2+ merge: the C*E compacted partials re-enter the SAME
    # chunk/sort/segmented-combine/compact structure until chunking stops
    # paying, then ONE answer-scale sort combines what is left — every
    # merge pass runs over chunk-local operands too, so no pass ever sorts
    # a monolithic row-scale array
    pnames = list(part)
    overflow = jnp.any(chunk_distinct > E)
    mk = ck.reshape(-1)
    mval = {nm: part[nm].reshape(-1) for nm in pnames}
    while True:
        C2, L2 = plan_chunks(mk.shape[0], K, chunk_rows)
        if C2 == 1:
            break
        E2 = min(L2, K + 1)
        ops2 = [_pad_chunks(mk, C2, L2, sentinel)] + [
            _pad_chunks(mval[nm], C2, L2, 0) for nm in pnames]
        sorted2 = jax.lax.sort(ops2, dimension=1, num_keys=1)
        sk2 = sorted2[0]
        pv2 = dict(zip(pnames, sorted2[1:]))
        st2, en2 = _boundaries(sk2)
        overflow = overflow | jnp.any(
            jnp.sum(st2 & (sk2 != sentinel), axis=1) > E2)
        cols2 = {nm: _red_for(nm)(pv2[nm], st2) for nm in pnames}
        comp2 = jax.lax.sort(
            [jnp.where(en2, sk2, sentinel)] + [cols2[nm] for nm in pnames],
            dimension=1, num_keys=1)
        mk = comp2[0][:, :E2].reshape(-1)
        mval = {nm: arr[:, :E2].reshape(-1)
                for nm, arr in zip(pnames, comp2[1:])}

    merged = jax.lax.sort([mk] + [mval[nm] for nm in pnames], num_keys=1)
    mk = merged[0]
    mval = dict(zip(pnames, merged[1:]))
    mstart, mend = _boundaries(mk)
    mreal = mk != sentinel
    out_cols = {nm: _red_for(nm)(mval[nm], mstart, axis=0) for nm in pnames}

    n_groups_total = jnp.sum(mstart & mreal, dtype=jnp.int64)
    # chunk-local compaction overflow at ANY level implies global overflow
    # (> K): force the total past the cap so the executor defers to the
    # host path
    n_groups_total = jnp.where(
        overflow, jnp.maximum(n_groups_total, jnp.int64(K + 1)),
        n_groups_total)

    fnames = list(out_cols)
    final = jax.lax.sort(
        [jnp.where(mend, mk, sentinel)] + [out_cols[nm] for nm in fnames],
        num_keys=1)
    fk = final[0][:K]
    fv = {nm: arr[:K] for nm, arr in zip(fnames, final[1:])}
    empty = fk == sentinel

    outs = {
        "skeys": jnp.where(empty, INT64_SENTINEL, fk.astype(jnp.int64)),
        "empty": empty,
        "gcount": jnp.where(empty, 0, fv["cnt"]),
        "n_groups_total": n_groups_total,
    }
    for nm in fnames:
        if nm != "cnt":
            outs[nm] = fv[nm]
    return outs


# ---------------------------------------------------------------------------
# mesh table merge (parallel/mesh.py)
# ---------------------------------------------------------------------------


def merge_tables(skeys, columns, reductions, table_k: int):
    """Merge device-gathered radix group tables: skeys (D, K) int64 with
    INT64_SENTINEL empties; columns {name: (D, K)}; reductions {name:
    "sum" | "min" | "max"}. Shards' tables align by KEY, not slot — one
    answer-sized sort of the D*K entries re-runs the level-2 combine.
    Returns ({name: (K,)}, skeys (K,), empty (K,), merged_distinct)."""
    D, K = skeys.shape
    names = list(columns)
    merged = jax.lax.sort(
        [skeys.reshape(-1)] + [columns[nm].reshape(-1) for nm in names],
        num_keys=1)
    mk = merged[0]
    mval = dict(zip(names, merged[1:]))
    mstart, mend = _boundaries(mk)
    mreal = mk != INT64_SENTINEL
    out = {}
    for nm in names:
        red = {"sum": seg_sum, "min": seg_min, "max": seg_max}[reductions[nm]]
        out[nm] = red(mval[nm], mstart, axis=0)
    merged_distinct = jnp.sum(mstart & mreal, dtype=jnp.int64)
    final = jax.lax.sort(
        [jnp.where(mend, mk, INT64_SENTINEL)] + [out[nm] for nm in names],
        num_keys=1)
    fk = final[0][:table_k]
    empty = fk == INT64_SENTINEL
    # the sentinel region of the final sort holds NON-run-end entries whose
    # columns carry partial scan values — re-fill every empty slot with its
    # reduction's neutral element so merged tables look exactly like a
    # single device's (gcount 0, sums 0, extremal fills)
    cols = {}
    for nm, arr in zip(names, final[1:]):
        arr = arr[:table_k]
        red = reductions[nm]
        if red == "sum":
            fill = jnp.zeros((), arr.dtype)
        elif jnp.issubdtype(arr.dtype, jnp.integer):
            fill = jnp.array(jnp.iinfo(arr.dtype).max if red == "min"
                             else jnp.iinfo(arr.dtype).min, arr.dtype)
        else:
            fill = jnp.array(jnp.inf if red == "min" else -jnp.inf,
                             arr.dtype)
        cols[nm] = jnp.where(empty, fill, arr)
    return cols, fk, empty, merged_distinct


# ---------------------------------------------------------------------------
# HLL register-plane variant (engine/device.py _hll_sorted_sums)
# ---------------------------------------------------------------------------


def hll_chunked_sorted_keys(packed, n_slots: int,
                            chunk_rows: int | None = None):
    """Chunked dedup-to-slot-max for the terminal sorted HLL build: packed
    (n,) int32 ``slot << 5 | rho`` keys in, a (possibly much smaller)
    SORTED int32 key array out with the same per-slot max-rho structure —
    a drop-in operand for _hll_sums_from_sorted, which only reads slot-run
    ends. Each pass sorts chunk-locally (VMEM-scale), keeps one entry per
    slot per chunk (its run end = the chunk's max rho, since rho occupies
    the low bits), and compacts to E = min(L, n_slots + 2) entries (slots
    + the masked-row overflow slot + the pad sentinel — a bound, not a
    heuristic: the slice can never drop a slot). Passes ITERATE on the
    C*E survivors — dedup is idempotent, so even the ratio-2 shrink the
    wide q4 slot space allows (HLL_COMPACT_RATIO) converges in O(log)
    chunk-local passes — until chunking stops paying and one final
    answer-scale sort restores global order. Degenerates to the monolithic
    sort when the slot space is too wide for any compaction to pay."""
    out = packed
    while True:
        C, L = plan_chunks(out.shape[0], n_slots + 1, chunk_rows,
                           min_ratio=HLL_COMPACT_RATIO)
        if C == 1:
            return jax.lax.sort(out)
        E = min(L, n_slots + 2)
        kc = _pad_chunks(out, C, L, INT32_SENTINEL)
        sk = jax.lax.sort(kc, dimension=1)
        slot = sk >> 5
        lead = jnp.ones((C, 1), dtype=bool)
        slot_end = jnp.concatenate(
            [slot[:, :-1] != slot[:, 1:], lead], axis=1)
        out = jax.lax.sort(
            jnp.where(slot_end, sk, INT32_SENTINEL),
            dimension=1)[:, :E].reshape(-1)


# ---------------------------------------------------------------------------
# radix histogram (occupancy probe; test-pinned primitive)
# ---------------------------------------------------------------------------


def bucket_histogram(key, keyspace: int, n_buckets: int, *,
                     interpret: bool = False):
    """(n_buckets,) int64 row counts per radix partition (the key's high
    bits), via the factored one-hot matmul kernel — the histogram half of
    the radix scheme.
    Sentinel/masked keys land in the kernel's overflow slot. n_buckets
    must be a power of two; the bucket shift derives from ``keyspace``."""
    from pinot_tpu.ops import groupby_mm as mm

    shift = 0
    while (keyspace - 1) >> shift >= n_buckets:
        shift += 1
    flat = key.reshape(-1)
    bucket = jnp.clip(
        (flat >> shift).astype(jnp.int32), 0, n_buckets)
    bucket = jnp.where(flat == _sentinel_for(key.dtype), n_buckets, bucket)
    n = flat.shape[0]
    ones = jnp.ones((1, n), dtype=jnp.bfloat16)
    counts = mm.group_sums(bucket, ones, n_buckets, interpret=interpret,
                           first_channel_ones=True)
    return jnp.round(counts[0]).astype(jnp.int64)
