"""Device hash-join kernels for the multi-stage engine (query2/).

The reference snapshot predates Pinot's multi-stage engine ("no
pinot-query-planner/pinot-query-runtime" — PAPER.md), whose
``HashJoinOperator`` builds a Java hash map per worker. TPU-first, a hash
table is the wrong shape: the device equivalent of hashing into buckets is
SORTING the packed key array (the radix basis ops/radix_groupby.py already
established for the group-by) and probing with ``searchsorted`` — the same
O(n log n) comparator passes a radix partition pays, with no data-dependent
memory access. The kernels here are the three phases of that join:

1. ``sort_build``: order the build side's packed keys once; the argsort
   permutation maps sorted positions back to build rows.
2. ``probe_ranges``: two vectorized binary searches give each probe row its
   [lo, hi) run of matching build rows. ``probe_unique`` is the 1:1 fast
   path when build keys are unique (a dimension table's primary key — the
   LOOKUP-transform case), where the probe IS the join.
3. ``expand_pairs``: materialize matched (probe_row, build_row) pairs under
   a STATIC output bound — the same static-bound-compaction idea the radix
   group-by uses. The bound comes from a host read of the total match
   count, rounded to the next power of two so jit caches stay small.

Key packing reuses ``radix_groupby.pack_keys``'s cartesian arithmetic:
multi-column equi-keys factorize host-side into one int64 code per row
(query2/runner.py), so every kernel sees a single (n,) key array.

Mesh execution (parallel/mesh.py): the BROADCAST strategy replicates the
sorted build table to every device and shards the probe axis inside one
``shard_map`` (``mesh_probe_ranges`` / ``mesh_probe_unique``) — the
distributed form of the reference's fan-out of a dim table to all servers,
but over ICI instead of a wire. The SHUFFLE strategy partitions BOTH sides
by key radix into one bucket per device (host-side scatter standing in for
the wire exchange) and runs every bucket's sort+probe in parallel in one
``shard_map`` (``mesh_bucket_ranges``); per-bucket pair expansion rides a
vmapped ``expand_pairs``.
"""

from __future__ import annotations

from functools import partial

import numpy as np

import jax
import jax.numpy as jnp

from pinot_tpu.parallel.mesh import SEG_AXIS
from jax.sharding import PartitionSpec as P


def next_pow2(n: int) -> int:
    m = 1
    while m < max(n, 1):
        m <<= 1
    return m


# ---------------------------------------------------------------------------
# solo kernels
# ---------------------------------------------------------------------------


@jax.jit
def sort_build(keys):
    """(n,) int64 packed build keys → (sorted_keys, perm): perm maps sorted
    positions back to original build rows."""
    perm = jnp.argsort(keys)
    return keys[perm], perm


@jax.jit
def probe_ranges(sorted_keys, probe):
    """Each probe key's matching run [lo, lo+count) in the sorted build."""
    lo = jnp.searchsorted(sorted_keys, probe, side="left")
    hi = jnp.searchsorted(sorted_keys, probe, side="right")
    return lo, hi - lo


@jax.jit
def probe_unique(sorted_keys, perm, probe):
    """1:1 probe against UNIQUE build keys (dim-table pk / LOOKUP case):
    (found(n,), build_row(n,) with -1 misses)."""
    n = sorted_keys.shape[0]
    idx = jnp.clip(jnp.searchsorted(sorted_keys, probe, side="left"),
                   0, n - 1)
    found = sorted_keys[idx] == probe
    return found, jnp.where(found, perm[idx], -1)


@partial(jax.jit, static_argnames=("bound",))
def expand_pairs(lo, counts, bound: int):
    """Materialize matched pairs under a static bound.

    Output slot j belongs to the probe row whose cumulative-count interval
    contains j; its offset within the row's run picks the build position.
    Returns (probe_row, build_pos, valid) of length ``bound``; slots past
    the true total are invalid (-1). ``bound`` must be >= counts.sum().
    """
    n = counts.shape[0]
    cum = jnp.cumsum(counts)
    total = cum[n - 1]
    j = jnp.arange(bound, dtype=counts.dtype)
    row = jnp.clip(jnp.searchsorted(cum, j, side="right"), 0, n - 1)
    start = cum[row] - counts[row]
    build_pos = lo[row] + (j - start)
    valid = j < total
    return (jnp.where(valid, row, -1),
            jnp.where(valid, build_pos, -1),
            valid)


# ---------------------------------------------------------------------------
# mesh (shard_map) kernels — BROADCAST: replicated build, sharded probe
# ---------------------------------------------------------------------------


def _mesh_call(mesh, fn, in_specs, out_specs, *args):
    sm = jax.shard_map(fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                       check_vma=False)
    return jax.jit(sm)(*args)


def mesh_probe_ranges(mesh, sorted_keys, probe):
    """probe (D*Lp,) sharded over the mesh; build replicated. One shard_map,
    no collectives needed — reassembly along the probe axis is the gather."""

    def local(sk, pr):
        lo = jnp.searchsorted(sk, pr, side="left")
        hi = jnp.searchsorted(sk, pr, side="right")
        return lo, hi - lo

    return _mesh_call(
        mesh, local, (P(), P(SEG_AXIS)), (P(SEG_AXIS), P(SEG_AXIS)),
        sorted_keys, probe)


def mesh_probe_unique(mesh, sorted_keys, perm, probe):
    """Sharded 1:1 probe against a replicated unique-key build table."""

    def local(sk, pm, pr):
        n = sk.shape[0]
        idx = jnp.clip(jnp.searchsorted(sk, pr, side="left"), 0, n - 1)
        found = sk[idx] == pr
        return found, jnp.where(found, pm[idx], -1)

    return _mesh_call(
        mesh, local, (P(), P(), P(SEG_AXIS)), (P(SEG_AXIS), P(SEG_AXIS)),
        sorted_keys, perm, probe)


# ---------------------------------------------------------------------------
# mesh (shard_map) kernels — SHUFFLE: both sides partitioned by key radix
# ---------------------------------------------------------------------------


def mesh_bucket_ranges(mesh, build_buckets, probe_buckets):
    """One device per key bucket: sort the local build bucket, probe the
    local probe bucket. build_buckets (D, Lb) / probe_buckets (D, Lp) are
    the host-partitioned key arrays (pads: build INT64 sentinel > any real
    key, probe -1 < any real key — neither side ever matches a pad).

    Returns (lo (D, Lp), counts (D, Lp), perm (D, Lb)): positions are
    LOCAL to each bucket; the caller maps them back through its bucket →
    global row index arrays."""

    def local(bk, pk):
        perm = jnp.argsort(bk[0])
        sk = bk[0][perm]
        lo = jnp.searchsorted(sk, pk[0], side="left")
        hi = jnp.searchsorted(sk, pk[0], side="right")
        return lo[None], (hi - lo)[None], perm[None]

    return _mesh_call(
        mesh, local, (P(SEG_AXIS, None), P(SEG_AXIS, None)),
        (P(SEG_AXIS, None), P(SEG_AXIS, None), P(SEG_AXIS, None)),
        build_buckets, probe_buckets)


@partial(jax.jit, static_argnames=("bound",))
def expand_pairs_buckets(lo, counts, bound: int):
    """Vmapped expand_pairs over the bucket axis: lo/counts (D, Lp) →
    (probe_row, build_pos, valid) each (D, bound), positions bucket-local."""
    return jax.vmap(lambda l, c: expand_pairs(l, c, bound))(lo, counts)


# ---------------------------------------------------------------------------
# host-side partition helper (the exchange stand-in for SHUFFLE)
# ---------------------------------------------------------------------------

BUILD_PAD = (1 << 62)   # sorts after every real key, never probed
PROBE_PAD = -1          # below every real (non-negative) key code


def partition_by_key(keys: np.ndarray, n_buckets: int, pad_value: int):
    """Host-side radix scatter: rows → n_buckets buckets by key modulo
    (codes are dense factorized ints, so modulo spreads uniformly). Returns
    (bucketed (D, L) keys padded with pad_value, row_index (D, L) int64
    with -1 pads) — the wire-exchange stand-in; the per-bucket join runs
    sharded on the mesh."""
    keys = np.asarray(keys, dtype=np.int64)
    bucket = keys % n_buckets
    order = np.argsort(bucket, kind="stable")
    sorted_bucket = bucket[order]
    counts = np.bincount(sorted_bucket, minlength=n_buckets)
    L = max(int(counts.max()) if len(keys) else 0, 1)
    out_keys = np.full((n_buckets, L), pad_value, dtype=np.int64)
    out_rows = np.full((n_buckets, L), -1, dtype=np.int64)
    starts = np.concatenate([[0], np.cumsum(counts)[:-1]])
    for d in range(n_buckets):
        sl = order[starts[d]: starts[d] + counts[d]]
        out_keys[d, : counts[d]] = keys[sl]
        out_rows[d, : counts[d]] = sl
    return out_keys, out_rows


def hash_partition_rows(part_ids: np.ndarray, n_parts: int) -> list:
    """Ragged counterpart of ``partition_by_key`` for the WIRE exchange
    (query2/exchange.py): given each row's partition id (hash % n_parts,
    already computed from the join key), return one int64 row-index array
    per partition. No padding — partitions ship server-to-server as
    variable-length payloads, so the dense (D, L) layout the mesh kernels
    want would only inflate the wire bytes; the receiving server re-packs
    for its device locally."""
    part_ids = np.asarray(part_ids, dtype=np.int64)
    order = np.argsort(part_ids, kind="stable")
    counts = np.bincount(part_ids, minlength=n_parts)
    starts = np.concatenate([[0], np.cumsum(counts)[:-1]])
    return [order[starts[p]: starts[p] + counts[p]]
            for p in range(n_parts)]
