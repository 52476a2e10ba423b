"""On-device final reduce: ORDER-BY-aware group trim inside the kernel.

The reference runs its final combine + trim on the broker/server host
(BrokerReduceService + TableResizer): every server ships its FULL group
table, and the reduce walks it in numpy. On this engine the group table
already lives on the device — shipping all (G,) accumulators over a
~100ms host link just so the host can keep the top-K rows made the link,
not the kernel, the cost of every interactive group-by (ROADMAP item 1;
BENCH_r05: single-digit kernel ms under ~115ms p50s).

``apply_trim`` is the device-side replacement: applied AFTER the mesh
combine (so multi-shard tables trim exactly once, reusing the existing
psum/_combine_sorted_table merge algebra in parallel/mesh.py), it

1. computes the query's ORDER BY keys from the combined accumulators —
   group-by COLUMNS order by their global-dict id component (the global
   dictionary is sorted, so id order == value order, including strings),
   aggregations by their finalized value in float64 (the host reduce
   compares finalized float64 partials, engine/reduce.py);
2. sorts the table by (present-first, keys..., slot) with one
   multi-operand ``lax.sort`` — the trailing slot operand reproduces the
   host's stable-sort tie-break (present/slot order) bit for bit;
3. keeps the first ``tr_k`` rows (a runtime PARAM — one compiled
   pipeline serves any LIMIT within the same static bound) under the
   static pow2 bound ``T``, masking the rest with each reduction's
   NEUTRAL fill, and emits the kept rows' packed int64 group keys as
   ``trim_keys``.

Only the trimmed (T,) leaves + scalar stats cross the host link in the
packed buffer (engine/device.py _pack_outs) — the fetch for a trimmed
top-K group-by shrinks from O(G) accumulators to O(K) answer rows.

Policy mirrors engine/reduce.py exactly (single-sourced through
``reduce.trim_bound``): the SOLE-partial condition and the keep bound
decide where trimming is EXACT vs reference-approximate —

- ``mode="terminal"`` (the device batch is the whole answer and nothing
  merges after): keep ``offset+limit`` — exact, ORDER BY or not, because
  finalize's own ordering/slicing sees every row it would have kept.
- ``mode="partial"`` (sole local partial, but a broker merges server
  partials afterwards): keep ``max(5*(offset+limit), group_trim_size)``
  with ORDER BY only — byte-for-byte the policy trim_group_by applies to
  the same partial on the host, including its reference-inherited
  approximation (a globally-top-K-but-not-locally-top-K group can drop).
- HAVING / gapfill / post-aggregation order expressions / DISTINCT:
  no trim (the host reduce needs every group), exactly like
  trim_group_by.

``neutral_fill`` lives here (ops level, import-cycle-free) as the ONE
copy of the kernels' empty/masked fill convention — engine/device.py
re-exports it for the fully-pruned synthesis and blockskip cond padding
(pinned by tests/test_blockskip.py::TestKernelNeutralFills).
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from pinot_tpu.ops import radix_groupby as radix_ops
from pinot_tpu.ops.join import next_pow2

# observability/stat leaves every pipeline emits regardless of shape —
# passed through the trim untouched (they are per-launch scalars or (S,)
# vectors, not group-table columns)
STAT_KEYS = frozenset((
    "doc_count", "seg_matched", "n_alive", "rows_filter",
    "blocks_total", "blocks_scanned", "n_groups_total", "narrow_live",
))

# the longest table whose trim a cohort applies under vmap; longer ones
# are trimmed member by member (engine/device.py _cohort_pipeline)
VMAP_SORT_MAX = 2048

# the SELECTION (``select_top``): an ORDER BY of one exact-integer
# aggregate over a table longer than SELECT_MIN_TABLE keeps its ``T`` rows
# (at most SELECT_MAX_T: the survivors are ranked pair by pair) without the
# sort at table length, which the TPU's compiler takes 32 s to build at
# 7,000 entries and 22 minutes at 437,500 (PERF.md, PR 32)
SELECT_MIN_TABLE = 1 << 13
SELECT_MAX_T = 1 << 13
SELECT_FIELDS = ("sum", "count")
# an int64 sum orders as its float64 does (the host's comparison) below this
EXACT_INT_ORDER = 1 << 53

# aggregations whose finalized value the device can order by; the field
# names the finalize produces (engine/aggspec.py → engine/reduce.py env)
ORDER_AGG_FIELDS = {
    "count": "count",
    "sum": "sum",
    "avg": "avg",
    "min": "min",
    "max": "max",
    "minmaxrange": "range",
}


def neutral_fill(name: str, dt):
    """The kernels' empty/masked fill for an output leaf, by naming
    convention — ONE copy shared by the fully-pruned synthesis
    (engine/device.py _neutral_outs), the blockskip cond-branch table
    padding, the sorted-regime empty-slot fills, and the device trim's
    beyond-kept masking, so the sites can't drift: extremal sentinels
    for min/max/time planes, -inf for the arg-time value planes ("no
    winner" encoding), the radix key sentinel for sorted tables and
    trimmed keys, zero elsewhere."""
    kind = np.dtype(dt).kind
    if name in ("skeys", "trim_keys"):
        return radix_ops.INT64_SENTINEL
    if name.endswith(("_vtmin", "_vtmax")):
        return -np.inf
    if name.endswith(("_min", "_tmin")):
        return np.iinfo(dt).max if kind in "iu" else np.inf
    if name.endswith(("_max", "_tmax")):
        return np.iinfo(dt).min if kind in "iu" else -np.inf
    return 0


def trim_keep_count(q, mode: str, group_trim_size: int = 5000) -> int:
    """How many groups the trim keeps — the EXACT bound (rides as the
    ``tr_k`` runtime param; the static template bound is its pow2
    ceiling). Mirrors engine/reduce.py trim_group_by via trim_bound so
    the two policies cannot drift."""
    if mode == "terminal":
        return q.offset + q.limit
    from pinot_tpu.engine.reduce import trim_bound

    return trim_bound(q, group_trim_size)


def plan_trim(q, group_exprs, aggs, shape: str, table_len: int,
              mode, group_trim_size: int = 5000, exact_int=frozenset()):
    """Host-side static analysis → trim spec ``(T, order_sig)`` or None;
    ``(T, order_sig, "select")`` where the selection takes the trim and not
    the sort at table length (``trim_select`` says which of a spec).

    ``group_exprs`` / ``aggs`` are the template-build enumerations (the
    order_sig indexes into them); ``table_len`` is the full table the
    trim would shrink (dense num_groups, or sorted_k for the radix
    regime); ``mode`` is None (not a sole partial — trimming would lose
    contributions a later merge needs), "partial" (sole local partial,
    server→broker), or "terminal" (the whole answer).

    The spec is hashable and literal-free: LIMIT/OFFSET ride as the
    ``tr_k`` param, only their pow2 ceiling ``T`` shapes the template.

    ``exact_int``: indexes into ``aggs`` of the SUMs whose leaf is an
    int64 the caller knows to stay under ``EXACT_INT_ORDER`` in size (an
    integer argument of known range): the selection orders by the
    integer, the host by its float64, and below that bound they agree.
    """
    if mode not in ("terminal", "partial"):
        return None
    if shape not in ("groupby", "groupby_sorted", "groupby_narrow",
                     "groupby_full"):
        return None
    if q.distinct or q.having is not None:
        return None
    from pinot_tpu.common.options import bool_option

    opts = q.options_ci()
    if bool_option(opts, "usedevicereduce", None) is False:
        return None
    if opts.get("gapfillbucketms") is not None:
        return None  # gapfill synthesizes buckets from the FULL group set
    order = []
    if q.order_by:
        for ob in q.order_by:
            e = ob.expression
            ent = None
            for j, g in enumerate(group_exprs):
                if e == g:
                    ent = ("col", j, bool(ob.ascending))
                    break
            if ent is None:
                for i, a in enumerate(aggs):
                    if e == a and a.name in ORDER_AGG_FIELDS:
                        ent = ("agg", i, ORDER_AGG_FIELDS[a.name],
                               bool(ob.ascending))
                        break
            if ent is None:
                return None  # post-aggregation order expr: host reduce
            order.append(ent)
    elif mode != "terminal":
        # a server partial without ORDER BY has no trim the broker merge
        # could survive — exactly trim_group_by's refusal
        return None
    k = trim_keep_count(q, mode, group_trim_size)
    if k <= 0:
        return None
    T = next_pow2(k)
    if T >= table_len:
        return None  # nothing to shrink; the full table is the answer
    if (len(order) == 1 and order[0][0] == "agg"
            and order[0][2] in SELECT_FIELDS
            and (order[0][2] == "count" or order[0][1] in exact_int)
            and table_len > SELECT_MIN_TABLE and T <= SELECT_MAX_T):
        return (T, tuple(order), "select")
    return (T, tuple(order))


def trim_select(spec) -> str:
    """Which selection a trim spec runs, as the spans say it
    (``trimSelect``): ``select:<T>`` or ``sort:<T>``."""
    return f"{'select' if len(spec) > 2 else 'sort'}:{spec[0]}"


def select_top(rank, T: int):
    """The table slots of the ``T`` largest ``rank`` (int64, one a slot;
    more slots than ``T``), in order: rank descending, a tie to the lower
    slot - what the sort by (rank descending, slot) has in its first
    ``T`` places, exactly, without sorting the table. The ``T``-th
    largest rank is found by bisection over the values (64 counting
    passes over the table); slots above it are kept, and of the slots at
    it the lowest as far as ``T`` reaches; the ``T`` kept are compacted
    in slot order and ranked against each other pair by pair."""
    G = rank.shape[0]

    def halve(_, lo_hi):
        lo, hi = lo_hi
        # the upper middle, in a form that cannot pass int64's range
        mid = lo + ((hi - lo) >> 1) + ((hi - lo) & 1)
        enough = jnp.sum(rank >= mid, dtype=jnp.int32) >= T
        return (jnp.where(enough, mid, lo), jnp.where(enough, hi, mid - 1))

    edge, _ = jax.lax.fori_loop(
        0, 64, halve, (jnp.min(rank), jnp.max(rank)))
    above = rank > edge
    at_edge = rank == edge
    room = T - jnp.sum(above, dtype=jnp.int32)
    keep = above | (at_edge & (jnp.cumsum(at_edge, dtype=jnp.int32) <= room))
    slots = jnp.searchsorted(
        jnp.cumsum(keep, dtype=jnp.int32),
        jnp.arange(1, T + 1, dtype=jnp.int32), side="left").astype(jnp.int32)
    slots = jnp.minimum(slots, G - 1)
    kept = rank[slots]
    j = jnp.arange(T, dtype=jnp.int32)
    ahead = (kept[None, :] > kept[:, None]) | (
        (kept[None, :] == kept[:, None]) & (j[None, :] < j[:, None]))
    place = jnp.sum(ahead, axis=1, dtype=jnp.int32)
    return jnp.zeros(T, jnp.int32).at[place].set(slots)


def _desc(v):
    """Descending sort key. Integer keys here are non-negative (ids,
    counts, slot indexes), so two's-complement negation is order-exact;
    float keys mirror the host's ``-v`` in float64 (engine/host.py
    _negate)."""
    return -v


def _f64(v):
    return v.astype(jnp.float64)


def apply_trim(outs: dict, params: dict, template, spec) -> dict:
    """Traced post-combine trim: outs (full table) → outs (T-row table).

    Runs INSIDE the jitted pipeline after the cross-shard combine (and
    after the terminal sketch finalize when one applies), so the packed
    buffer the host fetches only carries the kept rows. Emits

    - ``trim_keys``  (T,) int64 packed group keys of the kept rows
      (mixed-radix over group_cards — the dense gid itself, or the
      sorted regime's skeys), INT64_SENTINEL beyond ``trim_n``;
    - ``trim_n``     scalar int64 = min(n_present, tr_k);
    - ``n_present_total`` scalar int64 — the UNtrimmed non-empty group
      count, so the host can detect a numGroupsLimit truncation it can
      no longer reproduce (it falls back to the host path rather than
      let the trim reorder the limit's drop policy);
    - every group-table leaf gathered to (T, ...) with neutral fills
      beyond ``trim_n``.
    """
    shape, _f, _gcols, group_cards, _aggs, _k, _final = template[:7]
    T, order = spec[:2]
    tr_k = params["tr_k"].astype(jnp.int64)
    gcount = outs["gcount"]
    G = gcount.shape[0]
    present = gcount > 0
    n_present = jnp.sum(present, dtype=jnp.int64)
    if "skeys" in outs:  # a keyed table: the sorted and narrowed regimes
        keys64 = outs["skeys"].astype(jnp.int64)
    else:
        keys64 = jnp.arange(G, dtype=jnp.int64)

    def col_component(j: int):
        stride = 1
        for c in group_cards[j + 1:]:
            stride *= c
        return (keys64 // stride) % group_cards[j]

    if len(spec) > 2:
        # the selection: one exact-integer aggregate orders the table
        # (plan_trim), empties below every present cell
        _tag, i, field, asc = order[0]
        v = gcount.astype(jnp.int64) if field == "count" \
            else outs[f"a{i}_sum"].astype(jnp.int64)
        with jax.named_scope("pinot.full_select"):
            perm = select_top(jnp.where(
                present, _desc(v) if asc else v, -(1 << 62)), T)
        return _gather_trimmed(outs, perm, keys64, n_present, tr_k, T)

    # sort operands: empties last, then the ORDER BY keys, then the slot
    # index — the host's stable lexsort tie-break (present order) made
    # explicit, so kept sets and their sequence match the host bit-exact
    operands = [jnp.where(present, jnp.int32(0), jnp.int32(1))]
    for ent in order:
        if ent[0] == "col":
            _tag, j, asc = ent
            k = col_component(j)
            operands.append(k if asc else _desc(k))
        else:
            _tag, i, field, asc = ent
            if field == "count":
                v = gcount.astype(jnp.int64)
            elif field == "sum":
                v = _f64(outs[f"a{i}_sum"])
            elif field == "avg":
                v = _f64(outs[f"a{i}_sum"]) / _f64(gcount)
            elif field == "min":
                v = _f64(outs[f"a{i}_min"])
            elif field == "max":
                v = _f64(outs[f"a{i}_max"])
            else:  # minmaxrange
                v = _f64(outs[f"a{i}_max"]) - _f64(outs[f"a{i}_min"])
            operands.append(v if asc else _desc(v))
    operands.append(jnp.arange(G, dtype=jnp.int64))
    sorted_ops = jax.lax.sort(tuple(operands), num_keys=len(operands))
    return _gather_trimmed(outs, sorted_ops[-1][:T], keys64, n_present,
                           tr_k, T)


def _gather_trimmed(outs, perm, keys64, n_present, tr_k, T: int) -> dict:
    """The ``T`` table rows ``perm`` names, in its order, as the trimmed
    outs ``apply_trim`` documents."""
    valid = jnp.arange(T, dtype=jnp.int64) < jnp.minimum(n_present, tr_k)

    trimmed = {}
    for name, v in outs.items():
        if name in STAT_KEYS:
            trimmed[name] = v
            continue
        if name == "skeys":
            continue  # replaced by trim_keys below
        g = v[perm]
        fill = neutral_fill(name, g.dtype)
        mask = valid.reshape((T,) + (1,) * (g.ndim - 1))
        trimmed[name] = jnp.where(mask, g, jnp.asarray(fill, g.dtype))
    trimmed["trim_keys"] = jnp.where(
        valid, keys64[perm], radix_ops.INT64_SENTINEL)
    trimmed["trim_n"] = jnp.minimum(n_present, tr_k)
    trimmed["n_present_total"] = n_present
    return trimmed
