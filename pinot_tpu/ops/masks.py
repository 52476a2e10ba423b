"""Predicate-mask kernels: the device replacement for filter operators.

The reference walks per-doc iterators (pinot-core/.../operator/dociditerators/
SVScanDocIdIterator.java:56-94) and RoaringBitmap algebra
(AndFilterOperator/OrFilterOperator). On TPU the filter result is a dense
boolean mask over the padded (S, L) segment batch — fixed shape, fuse-friendly
— and AND/OR/NOT are elementwise ops XLA fuses into the surrounding kernel.

Dict-encoded columns arrive in **global dictionary id space** (the batch
loader remapped them on upload, engine/params.py), so predicate literals
resolve to batch-wide scalars/vectors on the host — one binary search over
the global dictionary replaces the reference's per-segment
PredicateEvaluator, and the kernel is a bare vector comparison with no
per-segment indirection. Id planes arrive at their cardinality-chosen width
(uint8/uint16/int32, optionally sub-byte-packed — engine/params.py
ColPlan); predicates compare at native width (the int32 literal promotes
in-register, HBM traffic stays narrow). Padding docs carry id -1 (signed
planes) or the cardinality C (unsigned planes — ids are < C, so C matches
no literal) and literal params use -2 for "absent", so padding never
matches; callers still AND with valid_mask.

All functions here are shape-polymorphic jnp ops, traced inside the engine's
jitted pipeline; nothing allocates per-doc.
"""

from __future__ import annotations

import jax.numpy as jnp


def unpack_subbyte(packed, bits: int):
    """(…, Lp) uint8 sub-byte plane → (…, Lp * 8//bits) uint8 dict ids,
    unpacked with shifts/masks at REGISTER level (the in-kernel analog of
    FixedBitSVForwardIndexReader's bit extraction): the HBM read stays at
    the packed width, XLA fuses the shift/mask into whatever consumes the
    ids. Values are little-endian within each byte — id j lives in byte
    j // f at bit offset (j % f) * bits (f = 8 // bits), matching
    engine/params.py's host-side packer."""
    f = 8 // bits
    shifts = jnp.arange(f, dtype=jnp.uint8) * jnp.uint8(bits)
    sub = (packed[..., None] >> shifts) & jnp.uint8((1 << bits) - 1)
    return sub.reshape(packed.shape[:-1] + (packed.shape[-1] * f,))


def valid_mask(n_docs, padded_len: int, batched: bool):
    """(S, L) or (L,) mask of real (non-padding) docs.

    ``n_docs``: int32 (S,) vector when batched, scalar otherwise.
    """
    iota = jnp.arange(padded_len, dtype=jnp.int32)
    if batched:
        return iota[None, :] < n_docs[:, None]
    return iota < n_docs


# ---- global-dict-id space predicates (DICT-encoded columns) ---------------


def eq_dict(ids, target_id):
    """EQ: ``target_id`` int32 scalar global id (-2 if value absent)."""
    return ids == target_id


IN_UNROLL_MAX = 16


def in_dict(ids, id_vector):
    """IN: ``id_vector`` int32 (K,) global ids, padded with -2. A short
    list is K compares OR-ed at the ids' own shape: the (..., K) compare
    with a reduction over its minor axis, relaid out to lanes under
    ``vmap`` (a cohort's mask, ops/groupby_mm.py mask_lanes), took the
    TPU's compiler 210 s at K = 2 where this takes 2 (PR 33, compiled
    for a described v5e)."""
    k_in = id_vector.shape[-1]
    if k_in > IN_UNROLL_MAX:
        return jnp.any(ids[..., None] == id_vector, axis=-1)
    hit = ids == id_vector[..., 0]
    for k in range(1, k_in):
        hit |= ids == id_vector[..., k]
    return hit


def range_dict(ids, lo, hi):
    """RANGE: global id interval [lo, hi) — a value range on the sorted
    global dictionary is contiguous in id space (the dictionary-based range
    evaluator trick, RangePredicateEvaluatorFactory)."""
    return (ids >= lo) & (ids < hi)


def lut_dict(ids, lut):
    """Arbitrary predicate via a (C,) boolean LUT over global ids: the host
    evaluated the predicate once per dictionary entry (e.g. regex over a few
    thousand strings instead of millions of rows). Padding ids clamp to 0;
    callers AND with valid_mask, so the value is irrelevant."""
    return lut[jnp.clip(ids, 0, lut.shape[0] - 1)]


# ---- raw-value space predicates (RAW-encoded columns / computed exprs) ----


def eq_raw(values, literal):
    return values == literal


def neq_raw(values, literal):
    return values != literal


def in_raw(values, literals):
    """``literals``: (K,) device vector."""
    return jnp.any(values[..., None] == literals, axis=-1)


def range_raw(values, lower, upper, lower_inclusive: bool, upper_inclusive: bool,
              has_lower: bool, has_upper: bool):
    """Static inclusivity/boundedness (part of the jit template); bounds are
    traced scalars."""
    m = jnp.ones(values.shape, dtype=bool)
    if has_lower:
        m &= (values >= lower) if lower_inclusive else (values > lower)
    if has_upper:
        m &= (values <= upper) if upper_inclusive else (values < upper)
    return m
