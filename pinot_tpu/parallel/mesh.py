"""Mesh-parallel execution: shard the segment axis over TPU chips.

This is the distributed-combine layer — the TPU-native replacement for both
of the reference's parallel layers (SURVEY.md §2.9):

- intra-server combine (BaseCombineOperator's thread fan-out + BlockingQueue
  merge, operator/combine/BaseCombineOperator.java:79-145) → the batched
  (S, L) kernel already combines segments in one launch; here the S axis is
  *sharded* over a ``jax.sharding.Mesh`` and partial accumulators merge with
  XLA collectives riding ICI:
    sums/counts → psum, min → pmin, max/presence/HLL-registers → pmax.
- broker scatter-gather across servers stays host-side (broker/), exactly as
  the reference keeps Netty between nodes.

Because group-by accumulators live in *global dictionary id space*
(engine/params.py), the cross-chip psum is a dense elementwise reduce — no
key exchange, no IndexedTable merge, no all-to-all. The one exception is
the sorted/high-cardinality (radix) regime, whose per-shard tables are
keyed, not slot-aligned: those merge by KEY over an all-gather
(_combine_sorted_table — answer-sized work, the IndexedTable-merge analog
done once per query on ICI).
"""

from __future__ import annotations

from functools import partial

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

SEG_AXIS = "segments"


def make_mesh(n_devices: int | None = None, devices=None) -> Mesh:
    """1-D mesh over the segment axis (data-parallel OLAP scan)."""
    if devices is None:
        devices = jax.devices()
        if n_devices is not None:
            devices = devices[:n_devices]
    return Mesh(np.array(devices), (SEG_AXIS,))


def _combine_out(key: str, v):
    """Collective per output name — the psum-combine replacing the reference's
    blocking-queue merge."""
    if key == "seg_matched":
        return v  # stays per-shard; out_spec P(SEG_AXIS) reassembles (S,)
    if key.endswith(("_min", "_tmin")):
        return jax.lax.pmin(v, SEG_AXIS)
    if key.endswith(("_max", "_tmax", "_pres", "_regs")):
        return jax.lax.pmax(v, SEG_AXIS)
    # doc_count, gcount, *_sum, counts
    return jax.lax.psum(v, SEG_AXIS)


def _combine_sorted_table(outs: dict) -> dict:
    """KEY-ALIGNED merge for the sorted/high-cardinality (radix) regime:
    each shard emits a (K,) group table whose slots are keyed by ``skeys``
    (INT64_SENTINEL empties) with NEUTRAL empty-slot fills, so the same
    group can sit in different slots on different shards and a dense psum
    would be wrong. All-gather the (K,) tables to (D, K) and re-run the
    radix level-2 combine over them (ops/radix_groupby.py merge_tables) —
    answer-sized work, riding ICI. Overflow stays host-detected: if any
    shard's table overflowed (shard_total > K, so its table is truncated
    and the gathered keys are incomplete) the combined total is forced
    past K so the executor's host fallback fires, exactly like
    single-device."""
    from pinot_tpu.ops import radix_groupby as radix_ops

    # per-shard table length is min(shard_rows, sorted_k) — a SHARD-shape
    # quantity. The merged table must hold every gathered entry (D*K), not
    # one shard's length: merged distinct can legitimately exceed any
    # single shard's table. numGroupsLimit semantics stay host-side, via
    # the executor's n_groups_total check against sorted_k.
    # scalar observability leaves ride the ordinary psum combine, not the
    # keyed table merge (they are per-shard counts, not table columns);
    # the list is the SHARED ops/device_reduce.py STAT_KEYS contract plus
    # skeys (consumed by the key merge itself)
    from pinot_tpu.ops.device_reduce import STAT_KEYS

    stat_keys = STAT_KEYS | {"skeys"}
    K = outs["skeys"].shape[-1]
    reds, cols = {}, {}
    for k, v in outs.items():
        if k in stat_keys:
            continue
        reds[k] = "min" if k.endswith("_min") \
            else "max" if k.endswith("_max") else "sum"
        cols[k] = jax.lax.all_gather(v, SEG_AXIS)
    skeys = jax.lax.all_gather(outs["skeys"], SEG_AXIS)
    merged, fk, empty, merged_distinct = radix_ops.merge_tables(
        skeys, cols, reds, skeys.shape[0] * K)
    shard_total = outs["n_groups_total"]
    overflow_total = jax.lax.pmax(
        jnp.where(shard_total > K, shard_total, 0), SEG_AXIS)
    combined = {
        "doc_count": jax.lax.psum(outs["doc_count"], SEG_AXIS),
        "seg_matched": outs["seg_matched"],
        "skeys": jnp.where(empty, radix_ops.INT64_SENTINEL, fk),
        "n_groups_total": jnp.maximum(merged_distinct, overflow_total),
    }
    for k in ("n_alive", "rows_filter", "blocks_total", "blocks_scanned"):
        if k in outs:
            combined[k] = jax.lax.psum(outs[k], SEG_AXIS)
    if "narrow_live" in outs:  # the fullest shard's live blocks
        combined["narrow_live"] = jax.lax.pmax(outs["narrow_live"], SEG_AXIS)
    combined.update(merged)
    return combined


def _combine_outs(outs: dict) -> dict:
    """Combine a pipeline's outputs across shards. Most keys combine
    independently (_combine_out); the FIRSTWITHTIME/LASTWITHTIME value
    planes (``*_vtmin`` / ``*_vtmax``) combine as an argmin/argmax-by-time
    PAIR with their ``*_tmin`` / ``*_tmax`` sibling: resolve the global
    winning time with pmin/pmax, mask each shard's values to rows that
    carry it, then pmax the values — associative, deterministic (ties on
    time break toward the largest value, matching
    engine/aggspec.py FirstLastWithTimeSpec). The sorted/high-cardinality
    regime's keyed group tables take the key-aligned merge instead
    (_combine_sorted_table)."""
    if "skeys" in outs:
        return _combine_sorted_table(outs)
    combined = {}
    for k, v in outs.items():
        if k.endswith("_vtmin") or k.endswith("_vtmax"):
            tkey = k[:-6] + ("_tmin" if k.endswith("_vtmin") else "_tmax")
            t = outs[tkey]
            tg = jax.lax.pmin(t, SEG_AXIS) if k.endswith("_vtmin") \
                else jax.lax.pmax(t, SEG_AXIS)
            combined[k] = jax.lax.pmax(
                jnp.where(t == tg, v, -jnp.inf), SEG_AXIS)
        else:
            combined[k] = _combine_out(k, v)
    return combined


def shard_pipeline(pipeline_fn, mesh: Mesh, cohort: bool = False, post=None):
    """Wrap a device pipeline (engine/device.py build_pipeline inner fn) in
    shard_map over the segment axis.

    Input convention: any param/column whose leading dim == n_segments is
    sharded; everything else (literals, (K,) id lists) is replicated.
    Output convention: 'seg_matched' is gathered back to (S,); all other
    outputs are combined to replicated accumulators via psum/pmin/pmax.

    ``cohort=True``: params carry a LEADING cohort axis — a stack of
    same-template queries coalesced into one launch (engine/inflight.py).
    The per-shard pipeline AND the cross-shard combine are vmapped over
    that axis inside ONE shard_map, so a whole cohort costs one dispatch
    and its collectives batch over ICI. ``post`` (cohort only): a
    replicated post-combine transform ``post(outs, params)`` (device
    sketch finalize and/or the device-reduce trim, which reads its
    ``tr_k`` bound from the member's params) applied per member INSIDE
    the vmap — its per-member semantics (regs → est, table → top-K)
    must see unbatched shapes.
    """

    def one(cols, n_docs, p):
        outs = _combine_outs(pipeline_fn(cols, n_docs, p))
        return post(outs, p) if post is not None else outs

    def sharded(cols, n_docs, params):
        if cohort:
            return jax.vmap(lambda p: one(cols, n_docs, p))(params)
        return one(cols, n_docs, params)

    # global-id design: every param (literals, (C,) LUTs, the per-batch
    # "fo::" frame-of-reference offsets from width planning) is batch-wide
    # and replicated; only columns, n_docs, and "ps"-prefixed per-segment
    # params (e.g. the Level-1 ``ps_alive`` vector) carry the segment
    # axis. Narrow/sub-byte column planes shard like any column — the
    # (S, L//f) packed byte axis is position 1 either way. Cohort stacks
    # add a leading member axis, so the segment axis shifts to position 1
    # there.
    def param_spec(key: str, x) -> P:
        if key.startswith("ps"):
            if cohort:
                return P(None, SEG_AXIS, *([None] * (x.ndim - 2)))
            return P(SEG_AXIS, *([None] * (x.ndim - 1)))
        return P()

    def wrapper(cols, n_docs, params):
        in_specs = (
            {k: P(SEG_AXIS, None) for k in cols},
            P(SEG_AXIS),
            {k: param_spec(k, v) for k, v in params.items()},
        )
        # output KEYS (and ranks) come from the collective-free parts:
        # pipeline_fn (+ post, which only renames sketch leaves) — the
        # combine itself preserves the key set, so eval_shape never has to
        # trace an unbound collective
        shape_params = params
        if cohort:
            shape_params = {
                k: jax.ShapeDtypeStruct(v.shape[1:], v.dtype)
                for k, v in params.items()
            }
        keys_fn = pipeline_fn if post is None else (
            lambda c, nd, p: post(pipeline_fn(c, nd, p), p))
        outs_shape = jax.eval_shape(keys_fn, cols, n_docs, shape_params)

        def out_spec(k: str) -> P:
            if k != "seg_matched":
                return P()
            # per-shard seg_matched is (S_shard,) — or (N, S_shard) with a
            # leading cohort axis — and reassembles along the segment dim
            return P(None, SEG_AXIS) if cohort else P(SEG_AXIS)

        out_specs = {k: out_spec(k) for k in outs_shape}
        fn = jax.shard_map(
            sharded, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
            check_vma=False,
        )
        return fn(cols, n_docs, params)

    return jax.jit(wrapper)


def pad_to_multiple(cols: dict, n_docs, params: dict, multiple: int):
    """Pad the segment axis so it divides the mesh: extra segments carry
    n_docs = 0, so every kernel masks them out."""
    S = int(n_docs.shape[0])
    rem = S % multiple
    if rem == 0:
        return cols, n_docs, params, S
    pad = multiple - rem

    def pad_arr(x):
        if hasattr(x, "ndim") and x.ndim >= 1 and x.shape[0] == S:
            widths = [(0, pad)] + [(0, 0)] * (x.ndim - 1)
            return jnp.pad(x, widths)
        return x

    cols = {k: pad_arr(v) for k, v in cols.items()}
    params = {
        k: (pad_arr(v) if k.startswith("ps") else v) for k, v in params.items()
    }
    n_docs = jnp.pad(n_docs, (0, pad))
    return cols, n_docs, params, S + pad
