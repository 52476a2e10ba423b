"""Zone-map block-skip primitives for the device filter path.

Pinot's performance contract is that a selective filter touches only the
docs an index says it must (sorted/inverted/range indexes narrow the doc-id
set before projection). The batched device pipeline had no analog: every
predicate ran as a dense mask over every padded row of every segment, so a
1e-4-selectivity query cost the same HBM traffic as a full scan. This
module supplies the device-side analog of ColumnValueSegmentPruner's
min/max check, pushed down to ``ZONE_BLOCK_ROWS``-row granularity:

1. **Zone verdicts** (``zone_verdict``): the filter template evaluated in
   INTERVAL semantics over small (S, n_blocks) per-block min/max arrays
   resident in HBM (engine/params.py BatchContext.zone_map). Tri-state
   collapsed to "may match" booleans exactly like broker/segment_pruner.py:
   AND = all children may match, OR = any, NOT / regex-LUT / MV = always
   "may match" (conservative).
2. **Static-bound compaction** (``compact_candidates``): candidate block
   indices sort to the front of an index array and slice to a trace-time
   bound B = ceil(total_blocks / CAND_FRACTION). More candidates than B is
   OVERFLOW — detected on device as a scalar and routed to the dense path
   by the caller (same detect-and-fall-back pattern as
   ops/radix_groupby.py's group-table bound, except the fallback is the
   already-compiled dense branch of the same kernel, not a host re-run).
3. **Block gather**: each needed column reshapes to (total_blocks, R, ...)
   and gathers only the candidate blocks; the filter + aggregation then run
   over B*R rows instead of S*L. When the Pallas scatter tier is on and
   the template fits its surface, the gather/filter/aggregate step runs
   instead as ONE fused kernel (ops/pallas_scatter.py fused_filter_agg):
   the candidate indices from step 2 scalar-prefetch into the kernel's
   BlockSpec index maps, so the (B, R) gather buffer this step would
   materialize in HBM never exists.

Everything is trace-time static in shapes: B derives from the (S, L) batch
shape, so jit caches stay keyed on the same (template, batch-shape) pairs
the executor already uses, and the per-query verdict depends only on
params (predicate literals + the per-segment alive vector) — one compiled
template serves all literal values.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from pinot_tpu.storage.segment import ZONE_BLOCK_ROWS as BLOCK_ROWS

# static candidate bound: B = ceil(total_blocks / CAND_FRACTION). The skip
# branch always gathers B blocks (static shape), so the kernel's best case
# reads total/CAND_FRACTION of the batch; queries selecting more blocks
# than B overflow to the dense branch, bounding the worst-case overhead to
# the verdict + compaction work (a few thousand elements).
CAND_FRACTION = 16

ZLO = "zlo::"  # zone-map column key prefixes (cols dict)
ZHI = "zhi::"


def _expr_colkey(expr_tpl):
    """Column key a raw-space predicate's expression reads directly, or
    None when the expression computes (no interval structure we track)."""
    if not isinstance(expr_tpl, tuple):
        return None
    if expr_tpl[0] == "raw":
        return expr_tpl[1]
    if expr_tpl[0] == "dictval":
        return "dv::" + expr_tpl[1]
    return None


def prunable_columns(tpl) -> tuple[bool, set]:
    """(prunable, column keys) for a filter template: ``prunable`` is True
    when the zone verdict can exclude at least some blocks (a conservative
    node at the top of an OR poisons the whole disjunct, and NOT proves
    nothing about a block — same tri-state algebra as the broker pruner);
    the column set names the zone-map arrays the verdict will read."""
    kind = tpl[0]
    if kind == "and":
        cols: set = set()
        any_p = False
        for c in tpl[1:]:
            p, cc = prunable_columns(c)
            any_p |= p
            cols |= cc
        return any_p, cols
    if kind == "or":
        cols = set()
        for c in tpl[1:]:
            p, cc = prunable_columns(c)
            if not p:
                return False, set()  # one conservative child: OR never prunes
            cols |= cc
        return bool(cols), cols
    if kind == "false":
        return True, set()
    if kind in ("eq_dict", "in_dict", "range_dict"):
        if tpl[1].startswith("mv::"):
            return False, set()
        return True, {tpl[1]}
    if kind in ("eq_raw", "in_raw", "range_raw"):
        ck = _expr_colkey(tpl[1])
        if ck is None:
            return False, set()
        return True, {ck}
    # true / not / lut_dict / mv_any: conservative "may match"
    return False, set()


def _zones(cols, params, colkey, widths=None):
    """(lo, hi) zone arrays for a column key, DECODED to the column's
    register value space. Zone planes narrow WITH their column
    (engine/params.py ColPlan): id-space zones compare at native width
    (the int32 literal promotes in-register), but frame-of-reference
    (min-offset) planes store zones in FOR space — widen and add the
    per-batch "fo::<key>" offset param so predicate literals (raw value
    space) compare correctly. The (S, NB) zone arrays are a few thousand
    elements; the widening is register noise."""
    lo = cols.get(ZLO + colkey)
    hi = cols.get(ZHI + colkey)
    if lo is None or hi is None:
        return None, None
    w = widths.get(colkey) if widths else None
    if w is not None and w[3]:  # (dtype, bits, has_offset, wide)
        wd = jnp.dtype(w[3])
        lo = lo.astype(wd)
        hi = hi.astype(wd)
        fo = params.get("fo::" + colkey)
        if w[2] and fo is not None:
            lo = lo + fo
            hi = hi + fo
    return lo, hi


def zone_verdict(tpl, cols, params, shape, widths=None):
    """(S, n_blocks) bool: True where the block MAY contain a matching doc.
    Mirrors device.py's ``_eval_filter`` node set in interval semantics;
    any node without interval structure returns all-True (never prunes a
    block the dense mask would match). ``widths``: the pipeline's column
    width plan (build_pipeline) — zone planes decode like their column."""
    kind = tpl[0]
    ones = jnp.ones(shape, dtype=bool)
    if kind == "true":
        return ones
    if kind == "false":
        return jnp.zeros(shape, dtype=bool)
    if kind == "and":
        v = zone_verdict(tpl[1], cols, params, shape, widths)
        for c in tpl[2:]:
            v &= zone_verdict(c, cols, params, shape, widths)
        return v
    if kind == "or":
        v = zone_verdict(tpl[1], cols, params, shape, widths)
        for c in tpl[2:]:
            v |= zone_verdict(c, cols, params, shape, widths)
        return v
    if kind == "eq_dict":
        lo, hi = _zones(cols, params, tpl[1], widths)
        if lo is None:
            return ones
        t = params[tpl[2]]  # -2 when the value is absent: matches no block
        return (t >= lo) & (t <= hi)
    if kind == "in_dict":
        lo, hi = _zones(cols, params, tpl[1], widths)
        if lo is None:
            return ones
        ids = params[tpl[2]]  # (K,) with -2 padding (< any real zone lo)
        return jnp.any((ids >= lo[..., None]) & (ids <= hi[..., None]),
                       axis=-1)
    if kind == "range_dict":
        lo, hi = _zones(cols, params, tpl[1], widths)
        if lo is None:
            return ones
        rlo, rhi = params[tpl[2]], params[tpl[3]]  # id interval [rlo, rhi)
        return (lo < rhi) & (hi >= rlo)
    if kind == "eq_raw":
        lo, hi = _zones(cols, params, _expr_colkey(tpl[1]) or "", widths)
        if lo is None:
            return ones
        t = params[tpl[2]]
        return (t >= lo) & (t <= hi)
    if kind == "in_raw":
        lo, hi = _zones(cols, params, _expr_colkey(tpl[1]) or "", widths)
        if lo is None:
            return ones
        lits = params[tpl[2]]
        return jnp.any((lits >= lo[..., None]) & (lits <= hi[..., None]),
                       axis=-1)
    if kind == "range_raw":
        _, expr_tpl, klo, khi, has_lo, has_hi, lo_inc, hi_inc = tpl
        lo, hi = _zones(cols, params, _expr_colkey(expr_tpl) or "", widths)
        if lo is None:
            return ones
        v = ones
        if has_lo:
            b = params[klo]
            v &= (hi >= b) if lo_inc else (hi > b)
        if has_hi:
            b = params[khi]
            v &= (lo <= b) if hi_inc else (lo < b)
        return v
    # not / lut_dict / mv_any / anything new: conservative
    return ones


def compact_candidates(flat_verdict, bound: int):
    """Compact the True positions of a flat (total_blocks,) verdict to the
    front with a static bound: (candidate indices (bound,), valid mask
    (bound,)). Padding candidates point at block 0 with valid=False — the
    caller masks their rows out, so they contribute nothing. The sort runs
    over total_blocks int32 keys (thousands, not rows), trivially
    VMEM-resident."""
    total = flat_verdict.shape[0]
    iota = jnp.arange(total, dtype=jnp.int32)
    keyed = jnp.where(flat_verdict, iota, jnp.int32(total))
    cand = jax.lax.sort(keyed)[:bound]
    valid = cand < total
    return jnp.where(valid, cand, 0), valid


def gather_blocks(x, cand, n_blocks_per_seg: int, block_rows: int):
    """Gather candidate blocks out of an (S, L, ...) column — the device
    analog of an index handing the scan a doc-id subset. Each candidate is
    sliced straight out of the column at (segment, block start): no
    (S * n_blocks, block_rows) reshape, which on the TPU is a relayout of
    the whole column (the segment axis sits on sublanes) — a full-column
    copy per query for 32-bit planes and minutes of compile time for
    8/16-bit ones at the 8 x 12.5M-row batch shape."""
    tail = x.shape[2:]
    zeros = (jnp.int32(0),) * len(tail)
    nb = jnp.int32(n_blocks_per_seg)

    def one(c):
        start = (c // nb, (c % nb) * jnp.int32(block_rows)) + zeros
        return jax.lax.dynamic_slice(x, start, (1, block_rows) + tail)[0]

    return jax.vmap(one)(cand)
