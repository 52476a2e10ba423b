"""Device (JAX/XLA) query executor: the TPU hot path.

Replaces the reference's per-segment operator chains + combine thread pool
(§3.1 of SURVEY.md, BaseCombineOperator.java:79-145) with ONE jitted kernel
pipeline over the whole (S, L) segment batch:

    filter masks → (optional) global-id group keys → dense scatter aggregation

compiled once per *query template* (literals parameterized out — the explicit
form of InstancePlanMakerImplV2's per-shape plan dispatch) and cached. The
segment axis is the axis parallel/mesh.py shards over the device mesh; the
per-chip result is the same dense accumulator, combined with psum.

Group-by runs in global dictionary id space (engine/params.py), so the dense
(G,) accumulator directly replaces Pinot's ARRAY_BASED group-key regime
(DictionaryBasedGroupKeyGenerator.java:43-45) *and* its ConcurrentIndexedTable
merge: groups are already aligned across segments when the scatter lands.
"""

from __future__ import annotations

import functools
import hashlib
import itertools
import logging
import os
import threading
import time
import weakref

import numpy as np

import jax
import jax.numpy as jnp

from pinot_tpu.common import faults
from pinot_tpu.common.metrics import get_metrics
from pinot_tpu.common.options import bool_option
from pinot_tpu.common.trace import span as trace_span
from pinot_tpu.engine import aggspec
from pinot_tpu.engine.advisor import PlanAdvisor, advisor_enabled
from pinot_tpu.engine.inflight import (
    DeviceTimeline,
    InflightLaunch,
    LaunchCoalescer,
)
from pinot_tpu.engine.params import (
    BatchContext,
    DeviceUnsupported,
    KeySpaceFull,
    build_expr,
    build_filter,
    expr_bounds,
)
from pinot_tpu.engine.result import ExecutionStats, IntermediateResult
from pinot_tpu.ops import agg as agg_ops
from pinot_tpu.ops import blockskip as bs_ops
from pinot_tpu.ops import device_reduce as dr_ops
from pinot_tpu.ops import hll as hll_ops
from pinot_tpu.ops import keysorted as ks_ops
from pinot_tpu.ops import masks as mask_ops
from pinot_tpu.ops import radix_groupby as radix_ops
from pinot_tpu.ops.transform import get_function
from pinot_tpu.query.context import Expression, QueryContext
from pinot_tpu.storage.segment import Encoding

DEVICE_AGGS = {
    "count", "sum", "min", "max", "avg", "minmaxrange",
    "distinctcount", "distinctcountbitmap", "distinctcounthll",
    "segmentpartitioneddistinctcount",
    "hllmerge",  # star-tree sketch-state re-merge (engine/startree_exec.py)
    "firstwithtime", "lastwithtime",  # argmax-by-time combine family
}

MAX_DENSE_GROUPS = 1 << 22        # ARRAY_BASED regime guard (~4M groups)
MAX_PRESENCE_CELLS = 1 << 24      # distinctcount (G, C) presence guard
# sort-based high-cardinality regime (MAP_BASED analog): hard ceiling on
# the per-launch group table (the effective cap is
# min(num_groups_limit, this)); overflow falls back to the host path
MAX_SORTED_GROUPS = 1 << 17
SORTED_AGGS = ("count", "sum", "avg", "min", "max", "minmaxrange")
# NARROWED key space: the regime between the dense table over the whole
# cartesian product and the sort of every row. A group-by over two or
# more columns whose product is large and whose filter leaves few keys
# (SSB Q3.2: 250 cities x 250 cities x 7 years = 437,500 cells, 600 of
# them live) finds, under the launch's mask, which 128-cell BLOCKS of
# the key space hold a row (one count pass of the dense kernel over
# cells / 128 groups), and sums into those blocks alone: the kernel's hi
# one-hot compares against the table of live blocks, NARROW_BLOCKS rows
# however large the key space is. The live cells leave as a keyed table
# of NARROW_GROUPS entries, the sorted regime's output form, so the trim
# sorts 4,096 entries and not the product (that sort is what took the
# TPU's compiler five minutes at 437,500 cells). More live blocks or
# cells than that is an overflow: counted, and answered by the host.
# The floor is where the hi one-hot outgrows the rest of the kernel's
# VPU work: a dense launch costs 2*128 + (2 + planes) * cells/128 lane
# compares and multiplies a row (ops/groupby_mm.py _plan_lo), the two
# narrowed passes 2*128 + 2 * cells/16,384 and 2*128 + (2 + planes) * the
# live blocks in whole chunks of 32 (ops/pallas_scatter.py NARROW_CHUNK:
# the kernel skips a chunk of the table that lists no block); with a
# full table and three value planes they meet at 23,000 cells, with
# one chunk at 11,000, with none and no plane at 34,000 and 22,000.
NARROW_MIN_CELLS = 1 << 15
NARROW_BLOCK = 128       # cells a block: the lo one-hot, one lane tile
NARROW_BLOCKS = 128      # live blocks a launch keeps (hi one-hot rows)
NARROW_GROUPS = 1 << 12  # live cells a launch hands on
NARROW_AGGS = ("count", "sum", "avg")
_COUNT_ONLY = (("count", None, None),)
# FULL key space: the same large key space (past NARROW_MIN_CELLS, within
# MAX_DENSE_GROUPS) where the filter does NOT leave few keys - a ranking
# over whole hierarchies or an entity key, no slice: pass 1 finds more
# live blocks than the narrowed table holds. Its rows are summed in KEY
# ORDER (ops/keysorted.py): the batch keeps, a set of key columns, the
# rows' order by cartesian key, each cell's first row in it, and the
# statement's filter and value columns projected into it. The projected
# planes are laid out cell by slot (SLOTTED: every cell the fullest cell's
# K slots, a plane (K, cells) with the cells along the lanes) and a launch
# masks and sums down the slot axis - no cumulative sum, no gather - where
# the key order found K x cells within FULL_SLOT_PADDING times the batch's
# rows and the planes pass the batch's byte budget; a skewed key (one cell
# many times the mean) keeps them one row a row (ORDERED) and a launch runs
# one cumulative sum a channel and reads it at the cells' boundaries. What
# a launch took is its ``groupbyKeyLayout``; no option chooses it.
# The table is the key space itself (the dense regime's output form), and
# the trim's selection (ops/device_reduce.py select_top) takes its top
# rows without a sort at table length. Which of the two regimes a
# template takes on a batch is what the executor OBSERVED and remembers
# (DeviceExecutor._key_spaces): its first launch there counts the live
# blocks by a program of its own (_live_blocks: pass 1 alone), and a
# narrowed launch that overflows all the same (few blocks, over 4,096
# live cells; other literals) is launched again full and the template
# marked full. One key column has no hierarchy to slice by: past the
# floor it is full from the first.
# The filter's mask over the projected rows needs each row's segment for
# the launch's ``ps_alive``: compared against, segment by segment.
FULL_MAX_SEGMENTS = 16
FULL_MAX_PLANES = 4      # a value's projected plane is one uint32 a row
# slotted planes may hold this many slots a row of the batch: dbgen's
# uniform keys at 37.5M rows need x1.2 (62,500 cells) to x2.2 (1.75M) and
# x3.2 at a 3M-cell key (PERF.md, PR 37); past it the key is skewed
FULL_SLOT_PADDING = 4
# what executor.dispatch / device_wait, the flight record and EXPLAIN
# ANALYZE call a group-by's key space (``groupbyKeySpace``)
KEY_SPACES = {"groupby": "dense", "groupby_narrow": "narrowed",
              "groupby_sorted": "sorted", "groupby_full": "full"}

log = logging.getLogger("pinot_tpu.engine.device")

# device-runtime failure detection (launch/fetch recovery): jaxlib raises
# XlaRuntimeError for device-side faults (RESOURCE_EXHAUSTED / INTERNAL /
# device OOM); exact types vary across jax versions, so match by type
# name across the MRO, plus the fault harness's simulated form
_DEVICE_ERROR_NAMES = frozenset(
    ("XlaRuntimeError", "InternalError", "ResourceExhausted",
     "ResourceExhaustedError"))


def _is_device_runtime_error(e) -> bool:
    """True for failures of the DEVICE runtime (recoverable by evict +
    retry + host fallback) as opposed to template-build/user errors."""
    if isinstance(e, faults.InjectedDeviceError):
        return True
    if any(t.__name__ in _DEVICE_ERROR_NAMES for t in type(e).__mro__):
        return True
    return isinstance(e, RuntimeError) and "RESOURCE_EXHAUSTED" in str(e)


def segment_device_eligible(seg) -> bool:
    """Sealed, non-upsert-masked segments only: consuming (mutable) segments
    and segments with a validDocIds mask execute on the host scan path (the
    one place this rule lives — the engine partitions with it and the
    executor guards with it). Consuming segments re-enter through their
    CHUNKLETS (realtime/chunklet.py): the sealed frozen-prefix blocks pass
    this check (immutable, mask None while clean) and join the batch LRU +
    in-flight refcounting like any sealed segment — an upsert invalidation
    inside a block flips its mask non-None, failing this check back to the
    host path.

    Tiering (ISSUE 12, server/tiering.py): segments demoted below the
    hot tier route to the host too — warm segments scan their lazily
    mmap'd planes without ever occupying HBM, and cold placeholders are
    split out by the engine before this check matters. Segments without
    a tier attribute (every pre-tiering caller) are hot."""
    return not getattr(seg, "is_mutable", False) and \
        getattr(seg, "valid_docs_mask", None) is None and \
        (getattr(seg, "tier", None) or "hot") == "hot"


# ---------------------------------------------------------------------------
# template evaluation (traced inside jit)
# ---------------------------------------------------------------------------


def _col_width(widths, key):
    """Width-plan tuple (dtype, bits, has_offset, wide) for a cols key, or
    None (legacy wide plane / keys the planner doesn't narrow)."""
    return widths.get(key) if widths else None


def _ids_col(cols, key, widths):
    """Dict-id plane at LOGICAL width: sub-byte plans unpack in-register
    (ops/masks.py unpack_subbyte); byte-aligned narrow ids pass through —
    predicates/group arithmetic consume them at native width."""
    v = cols[key]
    w = _col_width(widths, key)
    if w is not None and w[1]:
        return mask_ops.unpack_subbyte(v, w[1])
    return v


def _data_col(cols, params, key, widths):
    """Raw / decoded (dv::) value plane DECODED to its plan's wide dtype:
    frame-of-reference planes add the per-batch "fo::<key>" offset param.
    Both the cast and the add are register-level (XLA fuses them into the
    consumer); the HBM read stays at the stored width. Decoding always
    widens — two narrow planes multiplied in an expression must not wrap
    at the storage width."""
    v = cols[key]
    w = _col_width(widths, key)
    if w is None or not w[3]:
        return v
    v = v.astype(jnp.dtype(w[3]))
    if w[2]:
        fo = params.get("fo::" + key)
        if fo is not None:
            v = v + fo
    return v


def _eval_expr(tpl, cols, params, widths=None):
    kind = tpl[0]
    if kind == "lit":
        return params[tpl[1]]
    if kind == "raw":
        return _data_col(cols, params, tpl[1], widths)
    if kind == "dictval":
        # decoded on the host at upload (BatchContext.decoded_column) — a
        # device (C,)-LUT gather here costs ~80ms/query at 12M docs on v5e
        return _data_col(cols, params, "dv::" + tpl[1], widths)
    if kind == "cast":
        return get_function("cast").jnp_fn(
            _eval_expr(tpl[1], cols, params, widths), tpl[2])
    fn = get_function(kind)
    args = [_eval_expr(a, cols, params, widths) for a in tpl[1:]]
    return fn.jnp_fn(*args)


def _eval_filter(tpl, cols, params, shape, widths=None):
    kind = tpl[0]
    if kind == "true":
        return jnp.ones(shape, dtype=bool)
    if kind == "false":
        return jnp.zeros(shape, dtype=bool)
    if kind == "and":
        m = _eval_filter(tpl[1], cols, params, shape, widths)
        for c in tpl[2:]:
            m &= _eval_filter(c, cols, params, shape, widths)
        return m
    if kind == "or":
        m = _eval_filter(tpl[1], cols, params, shape, widths)
        for c in tpl[2:]:
            m |= _eval_filter(c, cols, params, shape, widths)
        return m
    if kind == "not":
        return ~_eval_filter(tpl[1], cols, params, shape, widths)
    if kind == "mv_any":
        # per-entry mask over the (S, L, K) id block, -1 padding masked out,
        # reduced match-any over K (ForwardIndexReader.getDictIdMV semantics)
        ids = cols[tpl[1]]
        m = _eval_filter(tpl[2], cols, params, ids.shape, widths)
        return jnp.any(m & (ids >= 0), axis=-1)
    if kind == "eq_dict":
        return mask_ops.eq_dict(_ids_col(cols, tpl[1], widths), params[tpl[2]])
    if kind == "in_dict":
        return mask_ops.in_dict(_ids_col(cols, tpl[1], widths), params[tpl[2]])
    if kind == "range_dict":
        return mask_ops.range_dict(
            _ids_col(cols, tpl[1], widths), params[tpl[2]], params[tpl[3]])
    if kind == "lut_dict":
        return mask_ops.lut_dict(_ids_col(cols, tpl[1], widths), params[tpl[2]])
    if kind == "eq_raw":
        return mask_ops.eq_raw(
            _eval_expr(tpl[1], cols, params, widths), params[tpl[2]])
    if kind == "in_raw":
        return mask_ops.in_raw(
            _eval_expr(tpl[1], cols, params, widths), params[tpl[2]])
    if kind == "range_raw":
        _, expr_tpl, klo, khi, has_lo, has_hi, lo_inc, hi_inc = tpl
        return mask_ops.range_raw(
            _eval_expr(expr_tpl, cols, params, widths), params[klo],
            params[khi], lo_inc, hi_inc, has_lo, has_hi,
        )
    raise AssertionError(f"bad filter template node {kind}")


def _rows_per_block(values, int_rpb):
    """Two-stage sum block size at trace time: ints use the planner's
    metadata-derived bound (None → single-stage 64-bit scatter, exact but
    slow); floats always block at 2048 (f32 block partials, f64 reduce)."""
    if jnp.issubdtype(values.dtype, jnp.integer):
        return int_rpb if int_rpb else 1 << 62
    return 2048


def _legacy_rpb(extra):
    """Agg-template ``extra`` is (nplanes, rpb) since the matmul kernel;
    accept the bare legacy rpb int/None (older templates, __graft_entry__)."""
    return extra[1] if isinstance(extra, tuple) else extra


def _hll_regs(slot, rho, num_groups, log2m, mm_mode, pallas_mode="off"):
    """(num_groups, m) HLL registers: the Pallas register-max scatter
    (ops/pallas_scatter.py — partitioned presence channels, ISSUE 15)
    when the slot space is in its regime, else the matmul threshold-
    channel build when VMEM allows, else the scatter-max (all exact
    max-of-rho, bit-identical). Returned as int8 (rho <= 33 - log2m <
    127): the register matrix crosses the device->host link 4x smaller."""
    from pinot_tpu.ops import groupby_mm as mm

    m = 1 << log2m
    n_total = 1
    for d in slot.shape:
        n_total *= d
    if pallas_mode != "off":
        from pinot_tpu.ops import pallas_scatter as ps

        nrho = mm.hll_nrho(log2m)
        if ps.hll_supported(num_groups * m, nrho) and (
                pallas_mode == "interpret"
                or n_total >= ps.PALLAS_MIN_ROWS):
            regs = ps.hll_register_max(
                slot, rho, num_groups * m, nrho,
                interpret=(pallas_mode == "interpret"))
            return regs.reshape(num_groups, m).astype(jnp.int8)
    use_mm = (
        mm_mode != "off"
        and mm.hll_supported(num_groups, log2m)
        and (mm_mode == "interpret" or n_total >= mm.MM_MIN_ROWS)
    )
    if use_mm:
        regs = mm.hll_registers(
            slot.reshape(-1), rho.reshape(-1), num_groups, log2m,
            interpret=(mm_mode == "interpret"),
        )
        return regs.astype(jnp.int8)
    # f32 scatter-max: ~16% faster than int32 on v5e at 100M rows (951 vs
    # 1136 ms) and exact for rho <= 23 < 2^24
    regs = jnp.zeros(num_groups * m + 1, dtype=jnp.float32)
    regs = regs.at[slot.reshape(-1)].max(rho.reshape(-1).astype(jnp.float32))
    return regs[: num_groups * m].reshape(num_groups, m).astype(jnp.int8)


def _sum_route(num_groups: int, total_ch: int, n_total: int, mm_mode: str,
               pallas_mode: str):
    """Which kernel takes a dense group-by's COUNT/SUM/AVG channels:
    "pallas" (ops/pallas_scatter.py plane_group_sums), "mm"
    (ops/groupby_mm.py group_sums) or None (the XLA scatter). One
    decision for the trace-time routing and the template-build plan of
    the prepared operands."""
    from pinot_tpu.ops import groupby_mm as mm
    from pinot_tpu.ops import pallas_scatter as ps

    if (pallas_mode != "off" and ps.sums_supported(num_groups, total_ch)
            and (pallas_mode == "interpret"
                 or n_total >= ps.PALLAS_MIN_ROWS)):
        return "pallas"
    if (mm_mode != "off" and mm.mm_supported(num_groups, total_ch - 1)
            and (mm_mode == "interpret" or n_total >= mm.MM_MIN_ROWS)):
        return "mm"
    return None


# functions that keep whole numbers whole, and whose range expr_bounds
# (engine/params.py) knows from the columns' metadata
_INT_CLOSED = ("plus", "minus", "times", "abs")


def _column_expr_key(argt, widths):
    """Canonical form of a SUM/AVG argument whose value depends on the
    batch alone: a stored integer column (its cols key, as ever) or an
    expression with such columns as its only leaves. None for anything a
    launch has a say in (a literal is a launch parameter) or that may
    leave the integers (float columns, casts, division)."""
    from pinot_tpu.ops.pallas_scatter import _direct_colkey

    ck = _direct_colkey(argt)
    if ck:
        w = _col_width(widths, ck)
        return ck if w is not None \
            and np.dtype(w[3] or w[0]).kind in "iu" else None
    if not isinstance(argt, tuple) or argt[0] not in _INT_CLOSED:
        return None
    leaves = [_column_expr_key(a, widths) for a in argt[1:]]
    return None if None in leaves else f"{argt[0]}({','.join(leaves)})"


def plan_prepared_groupby(template, widths, n_total: int, mm_mode: str,
                          pallas_mode: str, offsets: dict):
    """Template-build plan of the PREPARED operand form of a dense or
    narrowed group-by (ops/groupby_mm.py "prepared operands"): ``(route,
    (ids cols key a group column, ...), ((agg index, planes cols key,
    nplanes), ...))``, or None where the per-launch preparation has to
    run. It engages on what the template shows: every SUM/AVG argument
    an integer column or a column-only integer expression
    (``_column_expr_key``) with a plane count known from metadata, a
    kernel route chosen (several group columns and the narrowed form:
    the Pallas route, whose kernel combines the ids), and row tiles that
    hold whole tiles of the 8-bit operands.
    ``offsets``: {agg index: the python int behind its ``off{i}`` param}.
    The byte budget and the mesh are the executor's to check."""
    from pinot_tpu.ops import groupby_mm as mm
    from pinot_tpu.ops import pallas_scatter as ps

    shape, _ft, group_cols, group_cards, aggs, _sk, _final = template
    mm_mode = _resolve_mm_mode(mm_mode)
    if shape not in ("groupby", "groupby_narrow") or not group_cols \
            or (mm_mode == "off" and pallas_mode == "off"):
        return None
    num_groups = 1
    for c in group_cards:
        num_groups *= c
    planes = []
    total_ch = 1  # the count channel
    for i, (name, argt, extra) in enumerate(aggs):
        if name not in ("sum", "avg") or not isinstance(extra, tuple):
            continue
        ek = _column_expr_key(argt, widths)
        if ek is None:
            return None  # a launch parameter or a float in it: per launch
        nplanes = extra[0]
        if nplanes is None or total_ch + nplanes > mm.MAX_CHANNELS + 1:
            continue  # the exact scatter takes this one, as per launch
        planes.append((i, BatchContext.groupby_planes_key(
            ek, offsets[i], nplanes), nplanes))
        total_ch += nplanes
    if not (planes or any(a[0] in ("count", "avg") for a in aggs)):
        return None
    if shape == "groupby_narrow":
        # pass 1 counts by block, pass 2 sums into the table's slots
        n_blocks = -(-num_groups // NARROW_BLOCK)
        slots = NARROW_BLOCKS * NARROW_BLOCK
        routes = (_sum_route(n_blocks, 1, n_total, mm_mode, pallas_mode),
                  _sum_route(slots, total_ch, n_total, mm_mode, pallas_mode))
        blks = (ps.sums_blk(n_blocks, 1),
                ps.narrow_blk(total_ch, NARROW_BLOCKS))
        route = "pallas" if routes == ("pallas", "pallas") else None
    else:
        route = _sum_route(num_groups, total_ch, n_total, mm_mode,
                           pallas_mode)
        if route == "mm" and len(group_cols) > 1:
            route = None  # the matmul kernel takes one ids operand
        blks = (ps.sums_blk(num_groups, total_ch) if route == "pallas"
                else mm.group_sums_blk(num_groups, total_ch),)
    if route is None or not all(mm.prepared_tile_ok(b) for b in blks):
        return None
    return (route, tuple("gk::" + c for c in group_cols), tuple(planes))


def plan_full_groupby(filter_tpl, group_cols, aggs, widths, offsets: dict,
                      n_segments: int):
    """Template-build plan of the FULL regime's operands (KEY_SPACES has
    the why), short of what only the built key order can say: ``(key
    columns' joined name, ((filter cols key, its projected cols key), ...),
    ((agg index, projected value cols key, nplanes), ...))``, or None
    where the rows cannot be summed in key order: a SUM/AVG argument that
    is no integer column or column-only integer expression of known range
    within FULL_MAX_PLANES bytes, a filter column that is no plain (S, L)
    plane (multi-value, packed below a byte), more segments than the mask
    compares against. The launch then sums by the XLA scatter. The byte
    budget and the mesh are the executor's to check."""
    if n_segments > FULL_MAX_SEGMENTS:
        return None
    name = ",".join(group_cols)
    planes = []
    for i, (agg, argt, extra) in enumerate(aggs):
        if agg not in ("sum", "avg"):
            continue
        ek = _column_expr_key(argt, widths) \
            if isinstance(extra, tuple) else None
        if ek is None or not extra[0] or extra[0] > FULL_MAX_PLANES:
            return None
        planes.append((i, f"gp::{name}::" + BatchContext.groupby_planes_key(
            ek, offsets[i], extra[0]), extra[0]))
    fcols = []
    for key in sorted(DeviceExecutor._needed_columns(filter_tpl)):
        w = _col_width(widths, key)
        if key.startswith("mv::") or w is None or w[1]:
            return None
        fcols.append((key, f"gp::{name}::{key}"))
    return (name, tuple(fcols), tuple(planes))


@functools.partial(jax.jit, static_argnames=(
    "filter_tpl", "group_cols", "group_cards", "wsig"))
def _live_blocks(cols, n_docs, params, *, filter_tpl, group_cols,
                 group_cards, wsig):
    """How many 128-cell blocks of the cartesian key space hold a row the
    filter keeps: the narrowed regime's pass 1 as a program of its own
    (the filter, the combined id, one XLA scatter-add of the rows into
    their blocks), run ONCE a template and batch, before either regime's
    program is built - a template that is full builds no narrowed program
    and waits for no overflow. Seconds to build and a third of a second
    to run at 37.5M rows, where the narrowed pipeline is 20-35 s to build
    (PERF.md, PR 36)."""
    widths = dict(wsig)
    per_col = [_ids_col(cols, c, widths) for c in group_cols]
    shape = per_col[0].shape
    mask = _eval_filter(filter_tpl, cols, params, shape, widths) \
        & mask_ops.valid_mask(n_docs, shape[1], batched=True)
    num_groups = 1
    for c in group_cards:
        num_groups *= c
    n_blocks = -(-num_groups // NARROW_BLOCK)
    gid = agg_ops.group_ids_combine(per_col, group_cards, mask,
                                    n_blocks * NARROW_BLOCK)
    rows_in = agg_ops.group_count(
        gid >> (NARROW_BLOCK.bit_length() - 1), n_blocks)
    return jnp.sum(rows_in > 0, dtype=jnp.int32)


@functools.partial(jax.jit,
                   static_argnames=("argt", "wsig", "off", "nplanes"))
def _expr_planes(cols, fo, *, argt, wsig, off: int, nplanes: int):
    """The ``gv::`` operand of an expression argument: the expression over
    the stored (S, L) planes (``fo``: their frame-of-reference offsets),
    then the byte planes of ``value - off`` in lanes, one program."""
    from pinot_tpu.ops import groupby_mm as mm

    v = _eval_expr(argt, cols, fo, dict(wsig))
    return mm.prepared_planes(v, delta=-off, nplanes=nplanes)


def _prepared_groupby(prepared, cols, params, mask_lane, group_cards,
                      num_groups, outs, mm_mode, pallas_mode,
                      hi_table=None) -> set:
    """A group-by's COUNT/SUM/AVG channels over the batch's PREPARED
    operands (``plan_prepared_groupby``'s plan): the kernel reads them
    out of ``cols`` as they are, and the launch's mask relaid out to
    lanes at one byte a row (``mask_lane``); nothing else row-scale is
    computed. ``hi_table``: the narrowed form's live blocks, and the
    sums are then its slots'. Fills ``outs`` as ``_try_mm_groupby`` does
    and returns the agg indexes handled."""
    from pinot_tpu.ops import groupby_mm as mm
    from pinot_tpu.ops import pallas_scatter as ps

    route, ids_keys, plane_plan = prepared
    ids = tuple(cols[k] for k in ids_keys)
    planes = [cols[key] for _i, key, _n in plane_plan]
    with jax.named_scope("pinot.groupby_kernel"):
        if hi_table is not None:
            sums = ps.plane_group_sums_narrow_prepared(
                ids, group_cards, mask_lane, planes, hi_table,
                interpret=(pallas_mode == "interpret"))
        elif route == "pallas":
            sums = ps.plane_group_sums_prepared(
                ids, group_cards, mask_lane, planes, num_groups,
                interpret=(pallas_mode == "interpret"))
        else:
            sums = mm.group_sums_prepared(
                ids[0], mask_lane, planes, num_groups,
                interpret=(mm_mode == "interpret"))
    specs, row = [], 1
    for i, _key, nplanes in plane_plan:
        specs.append((i, "int", slice(row, row + nplanes)))
        row += nplanes
    return _recombine_sums(sums, specs, params, outs)


def _try_mm_groupby(aggs, gid, cols, params, num_groups, mm_mode, outs,
                    widths=None, pallas_mode="off", narrow=None):
    """Route COUNT/SUM/AVG through ONE factored one-hot launch when
    eligible: the Pallas tiled local-accumulate scatter
    (ops/pallas_scatter.py plane_group_sums — group-range partitioned,
    so its coverage extends past the single-VMEM-accumulator ceiling)
    when the pallas tier is on, else the single-accumulator matmul
    kernel (ops/groupby_mm.py). Fills outs["gcount"] +
    outs[f"a{i}_sum"] and returns the set of agg indexes handled;
    scatter code covers the rest. All decisions are trace-time static.
    This is the PER-LAUNCH preparation (ids combined and masked, planes
    split, channels stacked, every launch); ``_prepared_groupby`` is the
    form that reads the batch's operands.

    ``narrow``: ``(hi_table, slot_ids)`` of a narrowed key space
    (``_aggregate_narrowed``). ``gid`` is then the cartesian id, masked
    rows past the key space, and ``num_groups`` the slots of the
    narrowed table: the Pallas kernel compares against ``hi_table``
    itself, the matmul kernel takes ``slot_ids()``."""
    from pinot_tpu.ops import groupby_mm as mm
    from pinot_tpu.ops import pallas_scatter as ps

    if mm_mode == "off" and pallas_mode == "off":
        return set()
    n_total = 1
    for d in gid.shape:
        n_total *= d

    # plan: which aggs become channels, and how many
    plans = []  # (i, kind, nplanes, values)
    total_ch = 1  # ones channel
    for i, (name, argt, extra) in enumerate(aggs):
        if name not in ("sum", "avg") or not isinstance(extra, tuple):
            continue
        nplanes_int = extra[0]
        v = _eval_expr(argt, cols, params, widths)
        if jnp.issubdtype(v.dtype, jnp.integer):
            if nplanes_int is None:  # unknown range → exact scatter instead
                continue
            kind, nplanes = "int", nplanes_int
        else:
            kind, nplanes = "float", 3
        if total_ch + nplanes > mm.MAX_CHANNELS + 1:
            continue
        plans.append((i, kind, nplanes, v))
        total_ch += nplanes
    route = _sum_route(num_groups, total_ch, n_total, mm_mode, pallas_mode)
    if route is None:
        return set()
    has_count_or_avg = any(a[0] in ("count", "avg") for a in aggs)
    if not plans and not has_count_or_avg:
        return set()

    channels = [jnp.ones(n_total, dtype=jnp.bfloat16)]
    specs = []  # (i, kind, slice into channel rows, offset param key)
    row = 1
    with jax.named_scope("pinot.plane_split"):
        for i, kind, nplanes, v in plans:
            flat = v.reshape(-1)
            if kind == "int":
                off = params[f"off{i}"]
                channels.extend(mm.int_planes(flat, off, nplanes))
            else:
                channels.extend(mm.float_planes(flat))
            specs.append((i, kind, slice(row, row + nplanes)))
            row += nplanes
        stacked = jnp.stack(channels)

    # pad, relayout to lanes and the Pallas kernel itself
    # (pinot_scatter_sums / pinot_groupby_mm in the device trace)
    with jax.named_scope("pinot.groupby_kernel"):
        if route == "pallas" and narrow is not None:
            sums = ps.plane_group_sums_narrow(
                gid.reshape(-1), stacked, narrow[0],
                interpret=(pallas_mode == "interpret"),
                first_channel_ones=True,
            )
        elif route == "pallas":
            sums = ps.plane_group_sums(
                gid.reshape(-1), stacked, num_groups,
                interpret=(pallas_mode == "interpret"),
                first_channel_ones=True,
            )
        else:
            if narrow is not None:
                gid = narrow[1]()
            sums = mm.group_sums(
                gid.reshape(-1), stacked, num_groups,
                interpret=(mm_mode == "interpret"), first_channel_ones=True,
            )
    return _recombine_sums(sums, specs, params, outs)


def _recombine_sums(sums, specs, params, outs) -> set:
    """(A, G) f64 channel sums → outs["gcount"] and the int64 / float
    SUMs of ``specs`` [(agg index, kind, channel rows)]."""
    from pinot_tpu.ops import groupby_mm as mm

    with jax.named_scope("pinot.recombine"):
        gcount = jnp.round(sums[0]).astype(jnp.int64)
        outs["gcount"] = gcount
        done = set()
        for i, kind, sl in specs:
            planes = [sums[j] for j in range(sl.start, sl.stop)]
            if kind == "int":
                outs[f"a{i}_sum"] = mm.recombine_int(
                    planes, gcount, params[f"off{i}"])
            else:
                outs[f"a{i}_sum"] = mm.recombine_float(planes)
            done.add(i)
    return done


def _resolve_mm_mode(mm_mode: str) -> str:
    if mm_mode == "auto":
        return "tpu" if jax.default_backend() == "tpu" else "off"
    return mm_mode


def _template_uses_pallas(template, widths, fused: bool,
                          pallas_mode: str = "interpret",
                          n_total: int | None = None) -> bool:
    """Static: does this template route at least one op to the Pallas
    tier?  Gates the roofline label's "+pallas" suffix AND the failure
    attribution of launch()'s fallback ladder — a pipeline that compiles
    ZERO Pallas kernels (the sorted radix regime, plain scalar
    aggregations, out-of-regime group counts, sub-PALLAS_MIN_ROWS
    batches on TPU) must not be attributed to the tier, or
    roofline/EXPLAIN ANALYZE rows silently change between tier-on and
    tier-off rounds and a device failure burns a Pallas-rung drop on a
    byte-identical recompile. Mirrors the trace-time routing
    conservatively: dtypes of computed expressions are unknowable here
    and count as routed. ``n_total``: batch rows (S * L) — the same
    minimum-rows gate every routing site applies outside interpret
    mode (None = unknown, treated as large)."""
    from pinot_tpu.ops import groupby_mm as mmod
    from pinot_tpu.ops import pallas_scatter as ps

    if fused:
        return True  # the fused kernel has no minimum-rows gate
    if pallas_mode != "interpret" and n_total is not None \
            and n_total < ps.PALLAS_MIN_ROWS:
        return False
    shape, _ft, _gc, group_cards, agg_tpls, _sk, _final = template

    def _arg_dtype(argt):
        ck = ps._direct_colkey(argt)
        w = (widths or {}).get(ck) if ck else None
        if w is None:
            return None
        return np.dtype(w[3]) if w[3] else np.dtype(w[0])

    if shape == "agg":
        # scalar shape: only the HLL register-max routes (scalar
        # min/max/sum are dense reductions, never scatters)
        return any(
            name == "distinctcounthll"
            and ps.hll_supported(1 << extra, mmod.hll_nrho(extra))
            for name, _a, extra in agg_tpls)
    if shape not in ("groupby", "groupby_narrow"):
        return False  # the sorted radix regime never consults the tier
    num_groups = 1
    for c in group_cards:
        num_groups *= c
    if shape == "groupby_narrow":  # both passes: blocks, then slots
        return ps.sums_supported(-(-num_groups // NARROW_BLOCK), 2) \
            or ps.sums_supported(NARROW_BLOCKS * NARROW_BLOCK, 2)
    for name, argt, extra in agg_tpls:
        if name in ("count", "sum", "avg"):
            if ps.sums_supported(num_groups, 2):
                return True
        elif name in ("min", "max", "minmaxrange"):
            dt = _arg_dtype(argt) or np.dtype(np.int32)
            if ps.minmax_supported(num_groups, dt):
                return True
        elif name == "distinctcounthll":
            if ps.hll_supported(num_groups * (1 << extra),
                                mmod.hll_nrho(extra)):
                return True
    return False


def _group_extreme(gid, v, num_groups: int, ops: tuple, pallas_mode: str):
    """Per-group min/max: the Pallas masked-select scatter
    (ops/pallas_scatter.py group_minmax — the aggregation family with no
    MXU identity) when the value dtype and group count are in its
    regime, else the XLA scatter. Empty-group fills come from the
    ORIGINAL value dtype's extremes on both paths, so results are
    bit-identical."""
    from pinot_tpu.ops import pallas_scatter as ps

    n_total = 1
    for d in v.shape:
        n_total *= d
    if (pallas_mode != "off" and ps.minmax_supported(num_groups, v.dtype)
            and (pallas_mode == "interpret"
                 or n_total >= ps.PALLAS_MIN_ROWS)):
        if jnp.issubdtype(v.dtype, jnp.integer):
            info = jnp.iinfo(v.dtype)
            fills = tuple(info.max if op == "min" else info.min
                          for op in ops)
        else:
            fills = tuple(agg_ops.POS_INF if op == "min" else
                          agg_ops.NEG_INF for op in ops)
        res = ps.group_minmax(gid, v, num_groups, ops,
                              interpret=(pallas_mode == "interpret"),
                              fills=fills)
        return tuple(r.astype(v.dtype) for r in res)
    return tuple(
        agg_ops.group_min(gid, v, num_groups) if op == "min"
        else agg_ops.group_max(gid, v, num_groups) for op in ops)


def _finalize_sketch_outs(outs, agg_tpls):
    """TERMINAL-query device finalize (traced, applied AFTER the mesh
    combine so multi-shard presence/register merges stay max-semantics):
    HLL registers → int64 estimates, distinct presence → int64 popcounts.
    Only answer-sized arrays cross the host link instead of G×m mergeable
    state — a 2000-group log2m=11 register plane is 4MB of transfer for
    16KB of answers, which a slow link turns into most of the query."""
    outs = dict(outs)
    for i, (name, _argt, _extra) in enumerate(agg_tpls):
        k = f"a{i}"
        if name == "distinctcount" and f"{k}_pres" in outs:
            pres = outs.pop(f"{k}_pres")
            outs[f"{k}_cnt"] = jnp.sum(pres, axis=-1, dtype=jnp.int64)
        elif name == "distinctcounthll" and f"{k}_hs" in outs:
            # sorted register-free build (_hll_sorted_sums): scaled sums →
            # estimates, bit-identical to the dense-register math
            sums = outs.pop(f"{k}_hs")
            outs[f"{k}_est"] = hll_ops.estimate_from_sums_jnp(sums, _extra)
        elif name in ("distinctcounthll", "hllmerge") and f"{k}_regs" in outs:
            regs = outs.pop(f"{k}_regs")
            if regs.ndim == 1:
                outs[f"{k}_est"] = hll_ops.estimate_jnp(regs[None, :])[0]
            else:
                outs[f"{k}_est"] = hll_ops.estimate_jnp(regs)
    return outs


def _hll_sums_from_sorted(sk, num_groups, log2m, mm_mode):
    """(3, G) scaled register sums from an already-SORTED packed key array
    (slot << 5 | rho): each slot's run ends at its MAX rho; three bf16
    power-of-two channels over the boundary rows ride ONE group_sums
    matmul (see estimate_from_sums_jnp for the exactness argument)."""
    from pinot_tpu.ops import groupby_mm as mm

    m = 1 << log2m
    rho_max = 33 - log2m
    split = rho_max // 2
    slot_s = sk >> 5
    is_end = jnp.concatenate(
        [slot_s[1:] != slot_s[:-1], jnp.ones(1, dtype=bool)])
    valid = slot_s < num_groups * m  # masked rows pack the overflow slot
    e = is_end & valid
    rho_s = (sk & 31).astype(jnp.float32)
    gid_s = jnp.where(valid, slot_s >> log2m, num_groups).astype(jnp.int32)
    zero = jnp.float32(0)
    ch1 = jnp.where(e, jnp.float32(1), zero).astype(jnp.bfloat16)
    ch2 = jnp.where(e & (rho_s <= split),
                    jnp.exp2(jnp.float32(split) - rho_s),
                    zero).astype(jnp.bfloat16)
    ch3 = jnp.where(e & (rho_s > split),
                    jnp.exp2(jnp.float32(rho_max) - rho_s),
                    zero).astype(jnp.bfloat16)
    return mm.group_sums(gid_s, jnp.stack([ch1, ch2, ch3]), num_groups,
                         interpret=(mm_mode == "interpret"))


def _hll_sorted_sums(slot, rho, num_groups, log2m, mm_mode):
    """TERMINAL-only register-free HLL build for group counts too large
    for the matmul register kernel: chunk-local sorts of packed
    (slot << 5 | rho) int32 keys dedupe (register, rank) pairs down to
    per-slot maxima (ops/radix_groupby.py hll_chunked_sorted_keys — the
    radix-partitioned replacement for the old monolithic lax.sort, which
    ran HBM-bound at ~1.6 GB/s over the full row-scale key array), then
    _hll_sums_from_sorted reduces the surviving keys to per-GROUP scaled
    sums that recombine to the exact Σ 2^-reg (ops/hll.py
    estimate_from_sums_jnp). NOT mergeable across shards/servers (same
    slot on two shards would double-count), hence terminal-only; the
    scatter path remains the mergeable form. FILTERLESS queries skip the
    sort entirely via the batch's cached sorted projection
    (params.BatchContext.sorted_hll_keys)."""
    key = (slot.reshape(-1).astype(jnp.int32) << 5) \
        | rho.reshape(-1).astype(jnp.int32)
    sk = radix_ops.hll_chunked_sorted_keys(key, num_groups * (1 << log2m))
    return _hll_sums_from_sorted(sk, num_groups, log2m, mm_mode)


def _hll_sort_eligible(final, sorted_hll_ok, num_groups, log2m, mm_mode):
    """Shared gate for the sorted terminal HLL paths (build_pipeline AND
    the executor's needed-columns resolution must agree)."""
    from pinot_tpu.ops import groupby_mm as mm

    m = 1 << log2m
    return (final and sorted_hll_ok and mm_mode != "off"
            and not mm.hll_supported(num_groups, log2m)
            and num_groups * m < (1 << 26)
            and mm.mm_supported(num_groups, 3))


def _with_time_partial(name: str, outs: dict, k: str, present):
    """Device (time, value) outputs → the canonical {"val","time"} partial
    of FirstLastWithTimeSpec; empty groups keep the time sentinel and a
    NaN value (the device's -inf fill is a kernel artifact, not a value)."""
    first = name == "firstwithtime"
    suff = "tmin" if first else "tmax"
    t = np.asarray(outs[f"{k}_{suff}"]).reshape(-1)
    v = np.asarray(outs[f"{k}_v{suff}"], dtype=np.float64).reshape(-1)
    if present is not None:
        t, v = t[present], v[present]
    t = t.astype(np.int64)
    sentinel = np.iinfo(np.int64).max if first else np.iinfo(np.int64).min
    # -inf is the kernel's "no non-NaN winner" encoding (all-NaN winner
    # rows), kept as -inf through the mesh pmax so it stays associative;
    # it becomes NaN only here at the canonical boundary
    return {"val": np.where((t == sentinel) | np.isneginf(v), np.nan, v),
            "time": t}


def _is_f64(dt) -> bool:
    return np.dtype(dt) == np.float64


def _pack_outs(outs):
    """Flatten the output leaves into at most TWO arrays: a uint8 buffer
    (bitcast + concat) and a float64 buffer (concat only).

    The result crosses the host link as few arrays as possible:
    jax.device_get fetches tree leaves serially, and on a high-latency
    host<->device link each extra leaf is an extra round trip — a 3-leaf
    scalar aggregation pays 3x the floor. float64
    rides its own buffer because the TPU AOT x64 rewriter has no
    bitcast-convert lowering for f64 (i64 works). Bitcast leaves are
    ordered by descending itemsize so every offset stays naturally
    aligned for zero-copy np views on the host side."""
    names = sorted(outs, key=lambda n: (-jnp.dtype(outs[n].dtype).itemsize, n))
    bleaves, fleaves = [], []
    with jax.named_scope("pinot.pack"):
        for n in names:
            x = outs[n]
            if _is_f64(x.dtype):
                fleaves.append(x.reshape(-1))
                continue
            if x.dtype == jnp.bool_:
                x = x.astype(jnp.uint8)
            bleaves.append(
                jax.lax.bitcast_convert_type(x, jnp.uint8).reshape(-1))
        packed = {}
        if bleaves:
            packed["b"] = jnp.concatenate(bleaves) if len(bleaves) > 1 \
                else bleaves[0]
        if fleaves:
            packed["f"] = jnp.concatenate(fleaves) if len(fleaves) > 1 \
                else fleaves[0]
    return packed


def _out_layout(out_shapes) -> list:
    """[(name, np_dtype, shape, buffer_key, offset_elems_or_bytes, nbytes)]
    matching _pack_outs order, from a jax.eval_shape result (no device
    work). Offsets are bytes in the "b" buffer, elements in "f"."""
    items = sorted(
        out_shapes.items(),
        key=lambda kv: (-np.dtype(kv[1].dtype).itemsize, kv[0]),
    )
    layout, boff, foff = [], 0, 0
    for name, sds in items:
        dt = np.dtype(sds.dtype)
        n_elems = int(np.prod(sds.shape, dtype=np.int64))
        if _is_f64(dt):
            layout.append((name, dt, tuple(sds.shape), "f", foff, n_elems))
            foff += n_elems
            continue
        if dt == np.bool_:
            dt = np.dtype(np.uint8)
        nbytes = dt.itemsize * n_elems
        layout.append((name, dt, tuple(sds.shape), "b", boff, nbytes))
        boff += nbytes
    return layout


# the kernels' empty/masked fill convention moved to ops/device_reduce.py
# (the trim masks beyond-kept rows with the same fills); this alias keeps
# the one-copy contract and the historical import site
# (tests/test_blockskip.py::TestKernelNeutralFills)
_neutral_fill = dr_ops.neutral_fill


# device executors alive in this process: the chunklet/seal/upsert
# invalidation hooks (realtime/chunklet.py, storage/mutable.py) fan out
# partials-cache drops through this registry without holding an executor
# reference in ingest code
_EXECUTORS: "weakref.WeakSet" = weakref.WeakSet()


def invalidate_cached_partials(match: str) -> None:
    """Drop cached device partials whose batch involves a segment dir
    containing ``match`` on EVERY live executor — the chunklet
    promotion/seal/upsert-invalidation hook. Correctness never depends
    on it (batch keys change with the chunklet set, so stale entries are
    unreachable); it frees the HBM bytes those entries pin."""
    for ex in list(_EXECUTORS):
        ex.invalidate_partials(match)


def _neutral_outs(layout) -> dict:
    """Host-synthesized pipeline outputs for a FULLY-pruned launch: every
    leaf takes the exact fill its kernel produces under an all-false mask,
    keyed off the eval_shape layout so dtypes match the compiled pipeline
    bit-for-bit."""
    return {name: np.full(shp, _neutral_fill(name, dt), dtype=dt)
            for name, dt, shp, _which, _off, _size in layout}


def _width_audit(ctx, cols: dict, widths: dict) -> None:
    """PINOT_TPU_WIDTH_AUDIT=1 debug mode: after the column gather, assert
    no plane silently upcast past its planned storage dtype and log the
    per-column width table (plane dtype, sub-byte bits, FOR offset,
    register decode target, resident bytes). EXPLAIN renders the same
    table (engine/explain.py)."""
    import logging

    rows = []
    for key, sig in sorted(widths.items()):
        dt, bits, has_off, wide = sig
        arr = cols.get(key)
        if arr is None:
            continue
        got = np.dtype(arr.dtype)
        planned = np.dtype(np.uint8) if bits else np.dtype(dt)
        if got != planned:
            raise AssertionError(
                f"width audit: plane {key!r} upcast to {got} past its "
                f"planned {planned} (plan {sig})")
        rows.append(
            f"{key}: {np.dtype(dt).name}"
            + (f" packed={bits}b" if bits else "")
            + (" for-offset" if has_off else "")
            + (f" wide={np.dtype(wide).name}" if wide else "")
            + f" bytes={arr.nbytes}")
    logging.getLogger("pinot_tpu.device").info(
        "width audit (%d segments, pad_to=%d):\n  %s",
        ctx.S, ctx.pad_to, "\n  ".join(rows) if rows else "(no data planes)")


def _unpack_outs(bufs: dict, layout) -> dict:
    outs = {}
    for name, dt, shp, which, off, size in layout:
        buf = bufs[which]
        if which == "f":
            outs[name] = buf[off:off + size].reshape(shp)
        else:
            outs[name] = buf[off:off + size].view(dt).reshape(shp)
    return outs


def _then_join(resolve, thread):
    """``resolve`` that also waits for ``thread``, with the attributes
    the fetch phase reads off it (stamp, cohort, index)."""
    def joined():
        outs = resolve()
        thread.join()
        return outs

    joined.__dict__.update(resolve.__dict__)
    return joined


def _table_len(template) -> int:
    """Entries of a group-by template's table: the keyed table's length,
    or the cartesian product of a dense one."""
    if template[5]:
        return template[5]
    n = 1
    for c in template[3]:
        n *= c
    return n


def _key_space_outcome(outs: dict, space=None) -> dict:
    """What only a group-by's result says of its key space. Narrowed:
    the cells of the blocks it kept (the fullest member's, for a
    cohort), and ``overflow`` where a member's did not fit. Full
    (``space``: what the launch said it was): the cells that hold a row."""
    if space == "full":
        live = outs["n_present_total"] if "n_present_total" in outs \
            else (np.asarray(outs["gcount"]) > 0).sum(axis=-1)
        return {"keySpaceLive": int(np.max(live))}
    live = outs.get("narrow_live")
    if live is None:
        return {}
    live = int(np.max(live))
    read = {"keySpaceLive": min(live, NARROW_BLOCKS) * NARROW_BLOCK}
    if live > NARROW_BLOCKS \
            or int(np.max(outs["n_groups_total"])) > NARROW_GROUPS:
        read["groupbyKeySpace"] = "overflow"
    return read


# cols keys of a group-by's prepared operands (BatchContext.
# groupby_operand), none of them (S, L): the dense kernel's lane-major ids
# and byte planes; the full regime's key order ("go::<key columns>": the
# permutation, "gs::<key columns>": the cells' first rows) and the planes
# projected into it ("gp::<key columns>::<what>")
_GB_OPERAND_PREFIXES = ("gk::", "gv::", "go::", "gs::", "gp::")


def build_pipeline(template, mm_mode: str = "auto",
                   sorted_hll_ok: bool = False, blockskip=False,
                   widths=None, pallas_mode: str = "off", prepared=None):
    """template (hashable) → jitted fn(cols, n_docs, params) → outputs dict.

    ``mm_mode``: "auto" → the factored one-hot matmul kernel
    (ops/groupby_mm.py) on TPU, scatter elsewhere; "interpret" forces the
    kernel in Pallas interpret mode (CPU tests); "off" forces scatter.

    The trailing ``final`` template field is mostly consumed OUTSIDE this
    function (``_finalize_sketch_outs``, applied after the mesh combine);
    with ``sorted_hll_ok`` (single-device executors only — the sorted
    sums are not shard-mergeable) a final template routes large-G HLL
    through the register-free sorted build (_hll_sorted_sums).

    ``blockskip``: compile the zone-map block-skip form (ops/blockskip.py):
    per-block verdicts from (S, NB) zone arrays, static-bound candidate
    compaction, and a gathered (B, R) filter+aggregation — with the dense
    form as the in-kernel overflow fallback (lax.cond), so an unselective
    query costs only the verdict + compaction work extra. The executor
    requests it for templates whose filter has interval structure.
    Truthiness selects the form; an int value > 1 additionally overrides
    the candidate-bound fraction (``ceil(total/frac)`` candidates instead
    of the static ``CAND_FRACTION``) — the plan advisor tightens it for
    templates whose measured selectivity leaves headroom, and a bound
    overflow still lands on the in-kernel dense fallback bit-exactly.

    Every pipeline honors the optional ``ps_alive`` param — the per-query
    (S,) segment-alive vector from launch-time stats pruning (Level 1).
    It is a PARAM, not part of the batch: the (S, L) batch, its compiled
    templates, and the cohort coalescer key stay stable across queries
    that prune different segment subsets.

    ``widths``: the batch's column width plan — {cols key: (dtype, bits,
    has_offset, wide)} from BatchContext.width_plan (None = every plane at
    its legacy wide dtype, the pre-narrowing form __graft_entry__ and the
    kernel-parity tests build directly). The executor folds the same
    mapping into its pipeline cache key, so one compiled template serves
    exactly the batches that share its width plan.

    ``pallas_mode``: "off" (the XLA scatter reference — the default, and
    the form the PINOT_TPU_PALLAS=0 / SET usePallas=false escape hatch
    and the quarantine XLA rung compile), "tpu", or "interpret" (CPU
    tests) — routes the scatter-bound ops through the Pallas kernel tier
    (ops/pallas_scatter.py): tiled local-accumulate group sums, min/max
    scatter, HLL register-max, and the fused filter+gather+aggregate
    form of the block-skip path.

    ``prepared``: plan_prepared_groupby's plan, or None. With it the
    DENSE form's COUNT/SUM/AVG kernel (shapes "groupby" and
    "groupby_narrow", both of the latter's passes) reads the batch's
    prepared operands (``gk::`` / ``gv::`` entries of ``cols``) and the
    launch computes only the mask; the block-skip form's gathered branch
    has other rows and keeps the per-launch preparation.
    """
    shape, filter_tpl, group_cols, group_cards, aggs, sorted_k, _final = template
    mm_mode = _resolve_mm_mode(mm_mode)
    num_groups = 1
    for c in group_cards:
        num_groups *= c
    fused_plan = None
    if pallas_mode != "off" and blockskip and shape == "agg":
        from pinot_tpu.ops import pallas_scatter as ps_ops

        # the fused kernel gathers ONE zone block per grid step: a
        # retuned ZONE_BLOCK_ROWS must decline the plan, not silently
        # read a FUSED_BLOCK_ROWS prefix of every candidate block
        if bs_ops.BLOCK_ROWS == ps_ops.FUSED_BLOCK_ROWS:
            fused_plan = ps_ops.plan_fused(filter_tpl, aggs, widths or {})

    def _kfactor(key: str) -> int:
        """ids per stored byte-axis element (sub-byte plans pack 8//bits
        ids per uint8; everything else is 1:1)."""
        w = _col_width(widths, key)
        return 8 // w[1] if (w is not None and w[1]) else 1

    def pipeline(cols, n_docs, params):
        # zone cols are (S, NB) and sk:: sorted projections are 1-D — the
        # (S, L) shape inference must skip both; sub-byte planes store
        # L // factor bytes, so the LOGICAL row count multiplies back
        data_cols = {k: v for k, v in cols.items()
                     if not k.startswith((bs_ops.ZLO, bs_ops.ZHI))}
        any_key = next(k for k in data_cols if not k.startswith(
            ("sk::",) + _GB_OPERAND_PREFIXES))
        any_col = data_cols[any_key]
        S = any_col.shape[0]  # MV blocks are (S, L, K); masks are (S, L)
        L = any_col.shape[1] * _kfactor(any_key)
        alive = params.get("ps_alive")
        alive_b = jnp.ones((S,), dtype=bool) if alive is None \
            else alive.astype(bool)
        nd64 = n_docs.astype(jnp.int64)
        R = bs_ops.BLOCK_ROWS

        def _stat_outs(seg_matched, rows_filter, blocks_total, blocks_scanned):
            """Observability leaves every branch emits identically (mesh:
            seg_matched reassembles per-shard, the rest psum)."""
            return {
                "doc_count": jnp.sum(seg_matched),
                "seg_matched": seg_matched,
                "n_alive": jnp.sum(alive_b, dtype=jnp.int64),
                "rows_filter": rows_filter,
                "blocks_total": blocks_total,
                "blocks_scanned": blocks_scanned,
            }

        def dense(blocks_total):
            with jax.named_scope("pinot.mask"):
                valid = mask_ops.valid_mask(n_docs, L, batched=True) \
                    & alive_b[:, None]
                mask = _eval_filter(filter_tpl, data_cols, params, (S, L),
                                    widths) & valid
                seg_matched = jnp.sum(mask, axis=1, dtype=jnp.int64)
            outs = _stat_outs(
                seg_matched, jnp.sum(jnp.where(alive_b, nd64, 0)),
                blocks_total, blocks_total)
            return _aggregate(data_cols, params, mask, outs, prepared)

        if not blockskip or L % R:
            return dense(jnp.int64(0))

        # ---- zone-map block skip (ops/blockskip.py) ----------------------
        NB = L // R
        total = S * NB
        frac = bs_ops.CAND_FRACTION if blockskip is True \
            or int(blockskip) <= 1 else int(blockskip)
        B = min(total, max(1, -(-total // frac)))
        with jax.named_scope("pinot.zone_verdict"):
            blocks_total = jnp.sum(
                jnp.where(alive_b, (nd64 + R - 1) // R, 0))
            verdict = bs_ops.zone_verdict(filter_tpl, cols, params, (S, NB),
                                          widths)
            block_start = jnp.arange(NB, dtype=jnp.int32) * R
            verdict = verdict & (block_start[None, :] < n_docs[:, None]) \
                & alive_b[:, None]
            flat = verdict.reshape(-1)
            n_cand = jnp.sum(flat, dtype=jnp.int32)
            cand, cand_valid = bs_ops.compact_candidates(flat, B)

        def fused_skip(ps_ops):
            """Fused filter+gather+aggregate (ops/pallas_scatter.py): the
            kernel's scalar-prefetched candidate indices drive its DMA,
            so the (B, R) gather buffer the generic branch materializes
            never exists. Aggregation runs over STORAGE-space values;
            decode (widening + frame-of-reference offsets) applies to the
            answer-scale per-block partials here — Σ(v+fo) = Σv + fo·n
            and min(v+fo) = min(v)+fo are exact — so the leaves match the
            dense branch's dtypes and values bit-for-bit (lax.cond
            requires the former; the differential suite pins the
            latter)."""
            seg_of = cand // NB
            rows_in = jnp.where(
                cand_valid,
                jnp.clip(n_docs[seg_of] - (cand % NB) * R, 0, R),
                0).astype(jnp.int32)
            col_arrays = {
                key: data_cols[key].reshape(S * NB, R // 128, 128)
                for key in fused_plan.cols}
            par_arrays = {}
            for key, (ck, kindp) in fused_plan.pred_params.items():
                p = params[key].reshape(-1)
                if kindp == "storage":
                    w = widths.get(ck)
                    p64 = p.astype(jnp.int64)
                    if w[2]:
                        fo = params.get("fo::" + ck)
                        if fo is not None:
                            p64 = p64 - fo.astype(jnp.int64)
                    # clip into the plane's value range ±1: storage values
                    # are a strict subset, so every comparison survives
                    info = np.iinfo(np.dtype(w[0]))
                    p64 = jnp.clip(p64, int(info.min) - 1,
                                   int(info.max) + 1)
                    par_arrays[key] = p64.astype(jnp.int32)
                else:
                    par_arrays[key] = p.astype(jnp.int32)
            ints, flts = ps_ops.fused_filter_agg(
                cand, rows_in, col_arrays, par_arrays, fused_plan,
                interpret=(pallas_mode == "interpret"))
            block_matched = ints[:, 0].astype(jnp.int64)
            seg_matched = jnp.zeros(S + 1, dtype=jnp.int64).at[
                jnp.where(cand_valid, seg_of, S)].add(block_matched)[:S]
            outs = _stat_outs(
                seg_matched, jnp.sum(rows_in, dtype=jnp.int64),
                blocks_total, n_cand.astype(jnp.int64))
            dc = outs["doc_count"]
            by_idx: dict = {}
            for spec in fused_plan.aggs:
                by_idx.setdefault(spec[0], []).append(spec)
            for i, (name, argt, extra) in enumerate(aggs):
                k = f"a{i}"
                if name == "count" or i not in by_idx:
                    continue
                for (_i, op, ck, buf, slot, _fill) in by_idx[i]:
                    w = widths.get(ck)
                    wide = jnp.dtype(w[3]) if w[3] else jnp.dtype(w[0])
                    fo = params.get("fo::" + ck) if w[2] else None
                    if op == "sum":
                        s = jnp.sum(ints[:, slot].astype(jnp.int64))
                        if fo is not None:
                            s = s + fo.astype(jnp.int64) * dc
                        outs[f"{k}_sum"] = s
                    elif buf == "int":
                        col = ints[:, slot]
                        red = (col.min() if op == "min" else
                               col.max()).astype(wide)
                        if fo is not None:
                            red = red + fo
                        info = jnp.iinfo(wide)
                        empty = info.max if op == "min" else info.min
                        outs[f"{k}_{op}"] = jnp.where(dc > 0, red, empty)
                    else:
                        col = flts[:, slot]
                        red = col.min() if op == "min" else col.max()
                        outs[f"{k}_{op}"] = red.astype(wide)
            return outs

        def skip():
            if fused_plan is not None:
                from pinot_tpu.ops import pallas_scatter as ps_ops

                if ps_ops.fused_params_ok(fused_plan, params):
                    return fused_skip(ps_ops)
            seg_of = cand // NB
            row_idx = ((cand % NB) * R)[:, None] \
                + jnp.arange(R, dtype=jnp.int32)[None, :]
            rvalid = cand_valid[:, None] & (row_idx < n_docs[seg_of][:, None])
            # sub-byte planes gather at their PACKED block width (R // f
            # bytes per block; R = 4096 divides by every pack factor) and
            # unpack post-gather at the access site (_ids_col)
            with jax.named_scope("pinot.gather_blocks"):
                g_cols = {
                    k: bs_ops.gather_blocks(v, cand, NB, R // _kfactor(k))
                    for k, v in data_cols.items()
                    if not k.startswith(_GB_OPERAND_PREFIXES)}
            with jax.named_scope("pinot.mask"):
                mask = _eval_filter(filter_tpl, g_cols, params, (B, R),
                                    widths) & rvalid
                block_matched = jnp.sum(mask, axis=1, dtype=jnp.int64)
            seg_matched = jnp.zeros(S + 1, dtype=jnp.int64).at[
                jnp.where(cand_valid, seg_of, S)].add(block_matched)[:S]
            outs = _stat_outs(
                seg_matched, jnp.sum(rvalid, dtype=jnp.int64),
                blocks_total, n_cand.astype(jnp.int64))
            return _aggregate(g_cols, params, mask, outs)

        def _pad_table(outs):
            """Sorted-regime (radix) tables size as min(rows, K), and the
            cond's branches see different row counts — pad both to the
            template K with each reduction's NEUTRAL fill (identical to
            the kernel's own empty-slot fills, so merges see nothing
            new). Non-sorted shapes are already K-independent."""
            if shape != "groupby_sorted":
                return outs
            out2 = {}
            for k, v in outs.items():
                # ops/device_reduce.py STAT_KEYS is the ONE list of
                # non-group-table leaves (apply_trim shares it — a new
                # stat leaf added to _stat_outs must land there or the
                # trim would gather it as a table column)
                if k in dr_ops.STAT_KEYS or v.ndim == 0 \
                        or v.shape[0] >= sorted_k:
                    out2[k] = v
                    continue
                fill = _neutral_fill(k, v.dtype)
                out2[k] = jnp.concatenate(
                    [v, jnp.full((sorted_k - v.shape[0],), fill, v.dtype)])
            return out2

        # overflow (candidates past the static bound) falls back to the
        # DENSE branch of the same compiled kernel — no host round trip,
        # no result-shape change; just the verdict work wasted
        return jax.lax.cond(n_cand > B,
                            lambda: _pad_table(dense(blocks_total)),
                            lambda: _pad_table(skip()))

    def _aggregate(cols, params, mask, outs, prep=None):
        with jax.named_scope("pinot.aggregate"):
            return _aggregate_stages(cols, params, mask, outs, prep)

    def _aggregate_stages(cols, params, mask, outs, prep=None):
        """Filter mask → aggregation outputs; shape-agnostic over the row
        layout (dense (S, L) or gathered (B, R) — every reduction lands in
        template-shaped accumulators either way). ``prep``: the prepared
        operand plan, from the dense (S, L) form only."""
        if shape == "groupby_sorted":
            # RADIX-PARTITIONED high-cardinality regime (the MAP_BASED
            # analog of DictionaryBasedGroupKeyGenerator): dense
            # accumulators would blow HBM past MAX_DENSE_GROUPS, so the
            # packed group key rides ops/radix_groupby.py — chunk-local
            # sorts + run-end partials + compacted multi-level merge —
            # instead of the old monolithic lax.sort of the full (n,)
            # int64 key array (~1.6 GB/s at 100M rows; BENCH_r05
            # micro.sortkey_int64). Keys pack int32 when the cartesian
            # key space allows (half the comparator bytes). K comes from
            # the engine's num_groups_limit (template-encoded); overflow
            # is detected host-side and falls back to the host path so
            # device truncation policy never leaks into results. The
            # (K,) table this emits is keyed, so parallel/mesh.py can
            # merge per-shard tables (merge_tables) — the old basis was
            # not mesh-combinable at all.
            K = sorted_k
            per_col = [_ids_col(cols, c, widths) for c in group_cols]
            key = radix_ops.pack_keys(per_col, group_cards, mask)
            # dedup payloads by argument template: MIN(x)+MAX(x)+AVG(x)
            # must carry ONE copy of x through the level-1 sort, not three
            payloads, pname_of = {}, {}
            sums, mins, maxs = set(), set(), set()
            for i, (name, argt, extra) in enumerate(aggs):
                if name == "count":
                    continue
                if argt not in pname_of:
                    v = _eval_expr(argt, cols, params, widths)
                    # integer args accumulate exactly in int64 (the host /
                    # dense paths are exact; per-doc f64 adds would round)
                    as_int = jnp.issubdtype(v.dtype, jnp.integer)
                    dt = jnp.int64 if as_int else jnp.float64
                    pname = f"p{len(payloads)}"
                    pname_of[argt] = pname
                    payloads[pname] = (v.astype(dt).reshape(-1),
                                       "int" if as_int else "float")
                pname = pname_of[argt]
                if name in ("sum", "avg"):
                    sums.add(pname)
                if name in ("min", "minmaxrange"):
                    mins.add(pname)
                if name in ("max", "minmaxrange"):
                    maxs.add(pname)
            tbl = radix_ops.chunked_group_aggregate(
                key.reshape(-1), payloads, sums, mins, maxs, K)
            empty = tbl["empty"]
            outs["n_groups_total"] = tbl["n_groups_total"]
            outs["skeys"] = tbl["skeys"]
            outs["gcount"] = tbl["gcount"]
            # empty-slot fills are each reduction's NEUTRAL element, so a
            # cross-shard merge of partially-filled tables stays exact
            for i, (name, argt, extra) in enumerate(aggs):
                k = f"a{i}"
                if name == "count":
                    continue
                pname = pname_of[argt]
                if name in ("sum", "avg"):
                    s = tbl["sum::" + pname]
                    outs[f"{k}_sum"] = jnp.where(
                        empty, jnp.zeros((), s.dtype), s)
                if name in ("min", "minmaxrange"):
                    col = tbl["min::" + pname]
                    outs[f"{k}_min"] = jnp.where(
                        empty, _neutral_fill(f"{k}_min", col.dtype), col)
                if name in ("max", "minmaxrange"):
                    col = tbl["max::" + pname]
                    outs[f"{k}_max"] = jnp.where(
                        empty, _neutral_fill(f"{k}_max", col.dtype), col)
            return outs

        if shape == "groupby_narrow":
            return _aggregate_narrowed(cols, params, mask, outs, prep)

        if shape == "groupby_full" and prep is not None:
            return _aggregate_full(cols, params, outs, prep)

        if shape in ("groupby", "groupby_full"):
            # columns are already global ids: the group key IS the column
            per_col = [_ids_col(cols, c, widths) for c in group_cols]
            gid = agg_ops.group_ids_combine(per_col, group_cards, mask, num_groups)
            if shape == "groupby_full":
                # no key order to sum in (plan_full_groupby declined, or
                # the gathered rows of the block-skip form): the exact XLA
                # scatter into the key space, not a hi one-hot of
                # cells / 128 rows
                mm_done = set()
            elif prep is not None:
                mm_done = _prepared_groupby(
                    prep, cols, params, _mask_lanes(mask), group_cards,
                    num_groups, outs, mm_mode, pallas_mode)
            else:
                mm_done = _try_mm_groupby(
                    aggs, gid, cols, params, num_groups, mm_mode, outs,
                    widths, pallas_mode=pallas_mode)
            if "gcount" not in outs:
                outs["gcount"] = agg_ops.group_count(gid, num_groups)
            for i, (name, argt, extra) in enumerate(aggs):
                k = f"a{i}"
                if i in mm_done or name == "count":
                    pass  # produced by the matmul kernel / gcount reused
                elif name in ("sum", "avg"):
                    v = _eval_expr(argt, cols, params, widths)
                    rpb = _rows_per_block(v, _legacy_rpb(extra))
                    outs[f"{k}_sum"] = agg_ops.group_sum(gid, v, num_groups, rpb)
                elif name == "min":
                    v = _eval_expr(argt, cols, params, widths)
                    outs[f"{k}_min"], = _group_extreme(
                        gid, v, num_groups, ("min",), pallas_mode)
                elif name == "max":
                    v = _eval_expr(argt, cols, params, widths)
                    outs[f"{k}_max"], = _group_extreme(
                        gid, v, num_groups, ("max",), pallas_mode)
                elif name == "minmaxrange":
                    v = _eval_expr(argt, cols, params, widths)
                    outs[f"{k}_min"], outs[f"{k}_max"] = _group_extreme(
                        gid, v, num_groups, ("min", "max"), pallas_mode)
                elif name == "distinctcount":
                    card = extra
                    # ids widen in-register: uint8 * weak-int arithmetic
                    # would wrap at the storage width
                    sub = jnp.clip(_ids_col(cols, argt, widths), 0,
                                   card - 1).astype(jnp.int32)
                    gid2 = jnp.where(mask, gid * card + sub, num_groups * card)
                    pres = jnp.zeros(num_groups * card + 1, dtype=jnp.int8)
                    pres = pres.at[gid2.reshape(-1)].max(1)
                    outs[f"{k}_pres"] = pres[: num_groups * card].reshape(num_groups, card)
                elif name == "distinctcounthll":
                    log2m = extra
                    m = 1 << log2m
                    if _hll_sort_eligible(_final, sorted_hll_ok, num_groups,
                                          log2m, mm_mode):
                        sk_key = f"sk::{argt}::{log2m}"
                        if filter_tpl == ("true",) and sk_key in cols:
                            # FILTERLESS: the batch's cached sorted
                            # projection already holds the packed keys —
                            # no per-query sort at all
                            outs[f"{k}_hs"] = _hll_sums_from_sorted(
                                cols[sk_key], num_groups, log2m, mm_mode)
                            continue
                        h = cols["hh::" + argt]
                        idx, rho = hll_ops.hll_idx_rho(h, log2m)
                        slot = jnp.where(mask, gid * m + idx,
                                         num_groups * m)
                        outs[f"{k}_hs"] = _hll_sorted_sums(
                            slot, rho, num_groups, log2m, mm_mode)
                    else:
                        # per-doc value hashes, gathered host-side at upload
                        h = cols["hh::" + argt]
                        idx, rho = hll_ops.hll_idx_rho(h, log2m)
                        slot = jnp.where(mask, gid * m + idx,
                                         num_groups * m)
                        outs[f"{k}_regs"] = _hll_regs(
                            slot, rho, num_groups, log2m, mm_mode,
                            pallas_mode,
                        )
                elif name == "hllmerge":
                    # cube rows carry whole register planes: scatter-max the
                    # (rows, m) planes into (G, m) — rows ≈ distinct dim
                    # combos, so this is answer-sized work
                    m = 1 << extra
                    planes = cols["bp::" + argt].astype(jnp.int32)
                    gid2 = jnp.where(mask, gid, num_groups).reshape(-1)
                    regs = jnp.zeros((num_groups + 1, m), dtype=jnp.int32)
                    regs = regs.at[gid2].max(planes.reshape(-1, m))
                    outs[f"{k}_regs"] = regs[:num_groups]
                elif name in ("firstwithtime", "lastwithtime"):
                    v = _eval_expr(argt[0], cols, params, widths)
                    t = _eval_expr(argt[1], cols, params, widths)
                    first = name == "firstwithtime"
                    tb, vb = agg_ops.group_arg_time(gid, v, t, num_groups, first)
                    suff = "tmin" if first else "tmax"
                    outs[f"{k}_{suff}"] = tb
                    outs[f"{k}_v{suff}"] = vb
            return outs

        # scalar aggregation shape
        for i, (name, argt, extra) in enumerate(aggs):
            k = f"a{i}"
            if name == "count":
                pass  # doc_count reused
            elif name in ("sum", "avg"):
                v = _eval_expr(argt, cols, params, widths)
                outs[f"{k}_sum"] = agg_ops.agg_sum(v, mask)
            elif name == "min":
                outs[f"{k}_min"] = agg_ops.agg_min(
                    _eval_expr(argt, cols, params, widths), mask)
            elif name == "max":
                outs[f"{k}_max"] = agg_ops.agg_max(
                    _eval_expr(argt, cols, params, widths), mask)
            elif name == "minmaxrange":
                v = _eval_expr(argt, cols, params, widths)
                outs[f"{k}_min"] = agg_ops.agg_min(v, mask)
                outs[f"{k}_max"] = agg_ops.agg_max(v, mask)
            elif name == "distinctcount":
                card = extra
                sub = jnp.clip(_ids_col(cols, argt, widths), 0,
                               card - 1).astype(jnp.int32)
                slot = jnp.where(mask, sub, card)
                outs[f"{k}_pres"] = agg_ops.distinct_presence(slot, card)
            elif name == "distinctcounthll":
                log2m = extra
                m = 1 << log2m
                h = cols["hh::" + argt]
                idx, rho = hll_ops.hll_idx_rho(h, log2m)
                slot = jnp.where(mask, idx, m)
                outs[f"{k}_regs"] = _hll_regs(
                    slot, rho, 1, log2m, mm_mode, pallas_mode)[0]
            elif name == "hllmerge":
                m = 1 << extra
                planes = cols["bp::" + argt].astype(jnp.int32)
                outs[f"{k}_regs"] = jnp.max(
                    jnp.where(mask[..., None], planes, 0), axis=(0, 1))
            elif name in ("firstwithtime", "lastwithtime"):
                v = _eval_expr(argt[0], cols, params, widths)
                t = _eval_expr(argt[1], cols, params, widths)
                first = name == "firstwithtime"
                tb, vb = agg_ops.agg_arg_time(v, t, mask, first)
                suff = "tmin" if first else "tmax"
                outs[f"{k}_{suff}"] = tb
                outs[f"{k}_v{suff}"] = vb
        return outs

    def _mask_lanes(mask):
        from pinot_tpu.ops import groupby_mm as mm

        with jax.named_scope("pinot.mask"):
            return mm.mask_lanes(mask)

    def _aggregate_narrowed(cols, params, mask, outs, prep=None):
        """COUNT/SUM/AVG over a NARROWED key space (NARROW_MIN_CELLS has
        the why). Pass 1 counts the mask's rows by 128-cell block of the
        cartesian key space and ranks the blocks that hold any into
        ``hi_table``; pass 2 is the dense kernel over those blocks alone
        (``_try_mm_groupby(narrow=)``); the cells that hold a row leave
        as the first ``sorted_k`` entries of a keyed table, ``skeys``
        their cartesian ids in ascending order: the sorted regime's
        output form, which the trim, the mesh combine and the unpack
        already read. Every shape is static; under ``vmap`` each member
        narrows by its own mask. More live blocks than NARROW_BLOCKS, or
        more live cells than ``sorted_k``, and ``n_groups_total`` says
        so by passing ``sorted_k``: the host answers (the executor
        counts it), as for the sorted regime's overflow.

        ``prep``: the prepared operand plan. Both passes then read the
        batch's id and plane operands and the mask in lanes
        (``_prepared_groupby``): the cartesian id, its shift to a block
        and the select are the kernel's, in VMEM."""
        shift = NARROW_BLOCK.bit_length() - 1
        slots = NARROW_BLOCKS * NARROW_BLOCK
        n_blocks = -(-num_groups // NARROW_BLOCK)
        per_col = [_ids_col(cols, c, widths) for c in group_cols]
        mask_lane = None if prep is None else _mask_lanes(mask)
        with jax.named_scope("pinot.narrow"):
            # a masked row's id lies past the key space, in a block of
            # its own: the count's overflow slot, and in no table
            gid = agg_ops.group_ids_combine(
                per_col, group_cards, mask, n_blocks * NARROW_BLOCK)
            block = gid >> shift
            seen = {}
            if prep is not None:
                from pinot_tpu.ops import pallas_scatter as ps

                seen["gcount"] = jnp.round(ps.plane_group_sums_prepared(
                    tuple(cols[k] for k in prep[1]), group_cards, mask_lane,
                    (), n_blocks, shift=shift,
                    interpret=(pallas_mode == "interpret"))[0]
                ).astype(jnp.int64)
            else:
                _try_mm_groupby(_COUNT_ONLY, block, cols, params, n_blocks,
                                mm_mode, seen, widths,
                                pallas_mode=pallas_mode)
            rows_in = seen["gcount"] if seen \
                else agg_ops.group_count(block, n_blocks)
            live = rows_in > 0
            n_live = jnp.sum(live, dtype=jnp.int64)
            hi_table = jnp.nonzero(live, size=NARROW_BLOCKS,
                                   fill_value=-1)[0].astype(jnp.int32)

        memo = []

        def slot_ids():
            """A row's slot in the (blocks, 128) table, ``slots`` where
            its block is not kept: what the kernels that cannot compare
            against the table (matmul tier, XLA scatter) group by."""
            if not memo:
                tab = jnp.where(hi_table < 0, n_blocks + 1, hi_table)
                at = jnp.clip(jnp.searchsorted(tab, block), 0,
                              NARROW_BLOCKS - 1).astype(jnp.int32)
                memo.append(jnp.where(
                    tab[at] == block,
                    at * NARROW_BLOCK + (gid & (NARROW_BLOCK - 1)), slots))
            return memo[0]

        table = {}
        if prep is not None:
            done = _prepared_groupby(
                prep, cols, params, mask_lane, group_cards, slots, table,
                mm_mode, pallas_mode, hi_table=hi_table)
        else:
            done = _try_mm_groupby(
                aggs, gid, cols, params, slots, mm_mode, table, widths,
                pallas_mode=pallas_mode, narrow=(hi_table, slot_ids))
        if "gcount" not in table:
            table["gcount"] = agg_ops.group_count(slot_ids(), slots)
        for i, (name, argt, extra) in enumerate(aggs):
            if i not in done and name != "count":
                v = _eval_expr(argt, cols, params, widths)
                table[f"a{i}_sum"] = agg_ops.group_sum(
                    slot_ids(), v, slots,
                    _rows_per_block(v, _legacy_rpb(extra)))
        with jax.named_scope("pinot.narrow_table"):
            present = table["gcount"] > 0
            n_present = jnp.sum(present, dtype=jnp.int64)
            at = jnp.nonzero(present, size=sorted_k, fill_value=slots)[0]
            kept = at < slots
            at = jnp.minimum(at, slots - 1)
            cell = hi_table[at >> shift].astype(jnp.int64) * NARROW_BLOCK \
                + (at & (NARROW_BLOCK - 1))
            outs["skeys"] = jnp.where(kept, cell, radix_ops.INT64_SENTINEL)
            for k, v in table.items():
                outs[k] = jnp.where(kept, v[at], jnp.zeros((), v.dtype))
            outs["n_groups_total"] = jnp.where(
                n_live > NARROW_BLOCKS, sorted_k + n_live, n_present)
            outs["narrow_live"] = n_live
        return outs

    def _aggregate_full(cols, params, outs, prep):
        """COUNT/SUM/AVG over a FULL key space, from the batch's rows in
        key order (KEY_SPACES and ops/keysorted.py have the why). The
        launch's own work: the filter over the projected columns, each
        row's segment against ``ps_alive`` and which positions hold a row
        (slotted: a cell's first ``rows[cell]`` slots; ordered: up to the
        number of real rows); a count channel and each value split into
        planes narrow enough that the fullest cell's sum stays under
        2^32; slotted, the sum down the slot axis, ordered, one
        cumulative sum a channel read at the cells' boundaries. The table
        is the key space, as the dense form's."""
        _name, starts_key, seg_key, fcols, planes, plane_bits, slotted = \
            prep[3]  # slotted: a cell's slots K, 0 in the ordered layout
        starts = cols[starts_key]
        seg = cols[seg_key]
        with jax.named_scope("pinot.mask"):
            if slotted:
                keep = ks_ops.slot_mask(starts, seg.shape)
            else:
                at = jax.lax.broadcasted_iota(jnp.int32, seg.shape, 0) \
                    * seg.shape[1] \
                    + jax.lax.broadcasted_iota(jnp.int32, seg.shape, 1)
                keep = at < starts[num_groups]
            alive = params.get("ps_alive")
            if alive is not None:
                of_alive = jnp.zeros(seg.shape, dtype=bool)
                for s_i in range(alive.shape[0]):
                    of_alive |= (seg == s_i) & alive[s_i].astype(bool)
                keep &= of_alive
            keep &= _eval_filter(
                filter_tpl, {key: cols[pkey] for key, pkey in fcols},
                params, seg.shape, widths)
        with jax.named_scope("pinot.full_sums"):
            channels = [keep.astype(jnp.uint32)]
            split = []
            for i, pkey, nplanes in planes:
                v = jnp.where(keep, cols[pkey], jnp.uint32(0))
                n_split = -(-8 * nplanes // plane_bits)
                first = len(channels)
                if n_split == 1:
                    channels.append(v)
                else:
                    channels.extend(
                        (v >> (plane_bits * k)) & ((1 << plane_bits) - 1)
                        for k in range(n_split))
                split.append((i, first, n_split))
            sums = ks_ops.slot_sums(channels, num_groups) if slotted \
                else ks_ops.segment_sums(starts, channels)
        with jax.named_scope("pinot.recombine"):
            gcount = sums[0].astype(jnp.int64)
            outs["gcount"] = gcount
            for i, first, n_split in split:
                tot = jnp.zeros_like(gcount)
                for k in range(n_split):
                    tot = tot + (sums[first + k].astype(jnp.int64)
                                 << (plane_bits * k))
                outs[f"a{i}_sum"] = tot + gcount * params[f"off{i}"]
        return outs

    return pipeline  # caller jits (single-device) or shard_maps (mesh)


# ---------------------------------------------------------------------------
# executor
# ---------------------------------------------------------------------------


class DeviceExecutor:
    MAX_CACHED_BATCHES = 4  # LRU cap: a batch holds full columns in HBM
    # byte-aware cap: column blocks are materialized lazily, so the byte
    # check runs again as each in-flight launch drains (_release_launch)
    MAX_CACHED_BYTES = int(os.environ.get("PINOT_TPU_BATCH_CACHE_BYTES", 6 << 30))

    def __init__(self, mesh=None, mm_mode: str = "auto",
                 num_groups_limit: int = 100_000,
                 pallas_mode: str | None = None):
        """``mesh``: optional jax Mesh — shard the segment axis over it with
        psum-combined accumulators (parallel/mesh.py) instead of a
        single-device batched launch. ``mm_mode``: see build_pipeline.
        ``num_groups_limit``: the sorted high-card regime's group-table
        cap, matching the engine's numGroupsLimit. ``pallas_mode``:
        the scatter-kernel tier's mode (None = follow ``mm_mode``, so
        DeviceExecutor(mm_mode="interpret") exercises the Pallas tier in
        CPU tests exactly like the matmul kernel); per-process
        PINOT_TPU_PALLAS=0 and per-query SET usePallas=false force the
        XLA scatter path end to end."""
        self.mesh = mesh
        # build a block-skip template's dense twin with its first launch
        # (_prebuild_dense); None: on a TPU, where a program takes
        # seconds to build, and not elsewhere (asked at the first launch:
        # constructing an executor does not touch the backend)
        self.prebuild_dense = None
        self.mm_mode = mm_mode
        self.pallas_mode = pallas_mode
        self.num_groups_limit = max(1, num_groups_limit)
        self._batches: dict = {}     # segment-set key -> BatchContext (LRU)
        # (template, mm_mode, blockskip, width_sig, trim, pallas) -> entry
        self._pipelines: dict = {}
        # thread safety: server query threads launch/fetch concurrently —
        # one lock guards the caches, refcounts, and observability fields
        # (BatchContext guards its own lazy column materialization)
        self._lock = threading.RLock()
        self._inflight_launches: dict = {}  # batch key -> in-flight count
        self.inflight = 0            # launches between dispatch and fetch
        self._launch_ids = itertools.count(1)  # one per device launch
        self.coalescer = LaunchCoalescer()
        # the served launches in dispatch order and each one's end on the
        # device: a fetch's wait split into queue and run
        self.device_timeline = DeviceTimeline()
        # cumulative host-link observability
        self.fetch_bytes_total = 0
        self.fetch_leaves_total = 0
        # device-resident per-template partials cache (sub-RTT serving): a
        # repeat query — same pipeline entry, same batch, same literal
        # values / ps_alive verdicts — skips the column gather, dispatch,
        # and kernel entirely and re-fetches the CACHED packed output
        # buffer (one link RTT, zero device work). Keys are
        # (pipeline-key, batch_key, host-bytes digest): PR-4 made
        # template/cohort keys literal-independent, so the literal VALUES
        # digest is exactly what distinguishes repeat executions. Entries
        # die with their batch (_drop_partials_for_batch at every evict
        # site) and on chunklet promotion/seal/upsert via
        # invalidate_partials; bytes/hit/miss/eviction counters surface
        # through hbm_stats() and the server's /metrics gauges.
        self.partials_cache_enabled = os.environ.get(
            "PINOT_TPU_PARTIALS_CACHE", "1") not in ("", "0")
        self.MAX_CACHED_PARTIALS = int(os.environ.get(
            "PINOT_TPU_PARTIALS_CACHE_ENTRIES", 256))
        self.MAX_PARTIALS_BYTES = int(os.environ.get(
            "PINOT_TPU_PARTIALS_CACHE_BYTES", 128 << 20))
        self.PARTIALS_ENTRY_MAX_BYTES = 4 << 20  # don't pin huge tables
        self._partials: dict = {}  # key -> (bufs_dev, layout, nbytes)
        self.partials_bytes = 0
        self.partials_hits = 0
        self.partials_misses = 0
        # evictions = capacity pressure (size the cache from this);
        # invalidations = batch-eviction/chunklet/upsert/seal drops
        # (ingest churn — conflating the two would misread a realtime
        # table's promote cycle as an undersized cache)
        self.partials_evictions = 0
        self.partials_invalidations = 0
        # on-device final-reduce observability: queries whose group trim
        # ran in-kernel, and the host-side completion time of that reduce
        # (decode of the trimmed table — the full host reduce this
        # replaces walked O(G) accumulators)
        self.device_reduce_queries = 0
        self.device_reduce_ms_total = 0.0
        # server-partial trim bound (engine/reduce.py trim_bound's
        # min_trim_size); ServerInstance overwrites it with its
        # group_trim_size so device and host trims share one policy
        self.group_trim_size = 5000
        _EXECUTORS.add(self)
        # batch-LRU / HBM observability: cache hit/miss/eviction counters
        # plus per-batch resident bytes and bytes the width planning saved
        # (hbm_stats — surfaced through server /metrics gauges)
        self.batch_hits = 0
        self.batch_misses = 0
        self.batch_evictions = 0
        # dense and narrowed group-by launches by where the kernel's
        # operands came from: the batch's prepared ones, this launch built
        # them, or per-launch preparation (BatchContext.groupby_operand)
        self.groupby_operand_launches = {
            "prepared": 0, "built": 0, "perLaunch": 0}
        # launches of the narrowed key space, and queries whose live keys
        # did not fit it (answered by the host)
        self.groupby_narrowed_launches = 0
        # narrowed launches whose overflow the HOST answered (one that is
        # launched again in the full regime is not among them)
        self.groupby_narrow_overflows = 0
        self.groupby_full_launches = 0
        self.groupby_full_table_bytes = 0  # the largest key-space table
        # of them, those that summed planes laid out cell by slot, and the
        # most such planes a launch read; and those that summed prepared
        # planes in key order (ORDERED), and the most of those
        self.groupby_slotted_launches = 0
        self.groupby_slotted_bytes = 0
        self.groupby_ordered_launches = 0
        self.groupby_ordered_bytes = 0
        # (filter template, key columns, aggregates, batch) -> is its
        # large key space full? What the first launch's count of live
        # blocks said, or a narrowed launch's overflow since. Insertion-
        # ordered, the oldest dropped past MAX_FAILURE_KEYS
        self._key_spaces: dict = {}
        self.groupby_key_space_probes = 0
        # device-error recovery (failure-domain hardening): per-(template,
        # batch) failure counts feed a quarantine circuit breaker — a
        # pipeline that keeps failing on device routes to the host path
        # so one poisoned shape can't take down the executor. Counters
        # surface through hbm_stats() and the server's /metrics gauges.
        self.launch_failures = 0         # device-runtime failures observed
        self._pipeline_failures: dict = {}   # (template, batch_key) -> n
        self._quarantined: dict = {}         # key -> quarantined-at ts
        self._poisoned_batches: set = set()  # evict once their pins drain
        # Pallas-tier quarantine rung (ISSUE 15): a failing Pallas
        # pipeline drops to the XLA scatter form ON DEVICE first — host
        # only when the XLA rung fails too. One failure blocks the
        # (template, batch) pair for QUARANTINE_TTL_S; the host-path
        # quarantine's strike counting only ever sees XLA-rung failures.
        self._pallas_blocked: dict = {}      # (template, batch_key) -> ts
        self.pallas_fallbacks = 0            # pallas → XLA rung drops
        # kernel roofline accounting (ISSUE 11): per-pipeline-label
        # aggregates of the static bytes-moved cost model (ColPlan-width
        # column planes, block-skip gather ratio, trimmed fetch bytes)
        # against the device's time on the launch — achieved GB/s, surfaced
        # through hbm_stats()["roofline"], the deviceKernelGbps histogram,
        # and per-query IntermediateResult.roofline records
        self._roofline: dict = {}
        # device launch/fetch latency histograms ride the server registry
        # (ISSUE 7: the hot timers share ONE histogram-backed truth)
        self.metrics = get_metrics("server")
        # feedback-driven plan advisor (engine/advisor.py): per-template
        # memos of measured skip selectivity / rung GB/s / group counts /
        # cohort cohesion feed the next execution's candidate-bound, rung,
        # trim, and cohort-window choices. None disables process-wide
        # (pinot.advisor.enabled=false); SET useAdvisor=false per query.
        self.advisor = PlanAdvisor.from_config()
        # stateless launch-time stats pruner (engine.SegmentPruner), built
        # lazily to keep the engine module import one-directional
        self._stats_pruner = None
        # NOTE: predicate-literal device caching lives in params._slot —
        # keyed on host bytes BEFORE upload (keying device arrays here
        # would cost a blocking device→host read per literal)

    # cheap static check (EXPLAIN backend display)
    def supports(self, q: QueryContext) -> bool:
        aggs = q.aggregations()
        if q.distinct:
            return not aggs and all(e.is_identifier
                                    for e in q.select_expressions)
        if not aggs:
            return False
        return all(a.name in DEVICE_AGGS for a in aggs)

    @staticmethod
    def _batch_key(segments):
        return tuple(s.dir for s in segments)

    def batch_for(self, segments, retain: bool = False) -> BatchContext:
        """LRU-cached BatchContext for this segment set. ``retain=True``
        takes the in-flight pin ATOMICALLY with the cache insert (same
        lock hold) — pinning after return would leave a window where a
        concurrent _evict drops the still-unpinned batch and the next hit
        rebuilds a duplicate at transiently ~2x the byte budget."""
        key = self._batch_key(segments)
        with self._lock:
            ctx = self._batches.pop(key, None)
            if ctx is None:
                ctx = BatchContext(segments, mesh=self.mesh)
                self.batch_misses += 1
            else:
                self.batch_hits += 1
            self._batches[key] = ctx
            if retain:
                self._retain_launch(key)  # RLock: reentrant
        self._evict(keep=key)
        return ctx

    def _evict(self, keep=None):
        """LRU eviction by count AND resident HBM bytes (a 100M-row batch's
        decoded/prehashed blocks alone can approach HBM capacity — count
        caps alone don't bound that). Batches with in-flight launches are
        PINNED (refcounted via _retain_launch): evicting one would drop
        HBM blocks a dispatched-but-unfetched query is still reading.

        The byte sum runs OUTSIDE the executor lock: device_bytes takes
        each batch's materialization lock, and a cold multi-GB column
        build can hold that for seconds — holding the executor lock
        across it would serialize every concurrent launch/fetch. The
        snapshot is racy by design; eviction is best-effort LRU."""
        while True:
            with self._lock:
                batches = list(self._batches.values())
                over = len(batches) > self.MAX_CACHED_BATCHES
            if not over:
                total = sum(b.device_bytes() for b in batches)
                if not (total > self.MAX_CACHED_BYTES and len(batches) > 1):
                    return
            with self._lock:
                lru = next(
                    (k for k in self._batches
                     if k != keep and k not in self._inflight_launches), None)
                if lru is None:
                    return  # everything else is pinned by in-flight launches
                self._batches.pop(lru)
                self.batch_evictions += 1
                # cached partials read from the evicted batch's launch:
                # they die with it (a rebuilt same-key batch would answer
                # identically, but the entries' HBM buffers must not
                # outlive the LRU decision that freed the batch)
                self._drop_partials_for_batch(lru)

    def _batch_list(self) -> list:
        with self._lock:
            return list(self._batches.values())

    def resident_bytes(self) -> int:
        """Total HBM bytes of cached batches (lock-free per-batch counter
        reads; one short lock hold to snapshot the batch list)."""
        return sum(b.device_bytes() for b in self._batch_list())

    def narrow_saved_bytes(self) -> int:
        """Total bytes the width planning saved vs the wide layout across
        cached batches."""
        return sum(b.narrow_saved_bytes() for b in self._batch_list())

    def groupby_operand_bytes(self) -> int:
        """Of ``resident_bytes``: the dense group-by's prepared operands."""
        return sum(b.groupby_operand_bytes() for b in self._batch_list())

    # ---- device partials cache (sub-RTT repeat queries) ------------------
    def _partials_get(self, key):
        """LRU lookup; counts the hit/miss. Returns (bufs_dev, layout) or
        None."""
        with self._lock:
            ent = self._partials.pop(key, None)
            if ent is None:
                self.partials_misses += 1
                return None
            self._partials[key] = ent  # LRU touch
            self.partials_hits += 1
            return ent[0], ent[1]

    def _partials_put(self, key, bufs_dev, layout) -> None:
        """Insert a just-dispatched packed buffer. The buffer is the
        SAME device array the in-flight fetch resolves — jax arrays are
        immutable, so caching it costs no extra HBM beyond keeping it
        alive. Entries past the per-entry byte cap are skipped (a huge
        untrimmed table would evict the whole cache for one query)."""
        nbytes = sum(sz if which == "b" else sz * 8
                     for _n, _dt, _shp, which, _off, sz in layout)
        if nbytes > self.PARTIALS_ENTRY_MAX_BYTES:
            return
        with self._lock:
            if key in self._partials:
                return
            self._partials[key] = (bufs_dev, layout, nbytes)
            self.partials_bytes += nbytes
            while self._partials and (
                    len(self._partials) > self.MAX_CACHED_PARTIALS
                    or self.partials_bytes > self.MAX_PARTIALS_BYTES):
                old = next(iter(self._partials))
                self._partials_drop_locked(old)

    def _partials_drop_locked(self, key, invalidation: bool = False) -> None:
        ent = self._partials.pop(key, None)
        if ent is not None:
            self.partials_bytes -= ent[2]
            if invalidation:
                self.partials_invalidations += 1
            else:
                self.partials_evictions += 1

    def _drop_partials_for_batch(self, batch_key) -> None:
        """Caller holds self._lock (RLock): drop every cache entry tied
        to an evicted/poisoned batch."""
        for k in [k for k in self._partials if k[1] == batch_key]:
            self._partials_drop_locked(k, invalidation=True)

    def invalidate_partials(self, match: str) -> None:
        """Drop entries whose batch contains a segment dir matching
        ``match`` (substring) — the chunklet promotion/seal/upsert hook
        (module-level invalidate_cached_partials fans this out)."""
        with self._lock:
            dead = [k for k in self._partials
                    if any(match in d for d in k[1])]
            for k in dead:
                self._partials_drop_locked(k, invalidation=True)

    def hbm_stats(self) -> dict:
        """HBM / batch-LRU observability snapshot: per-batch resident
        bytes and narrowing savings, cumulative hit/miss/eviction
        counters, and the byte budget. Byte reads are the batches'
        lock-free insert-time counters (see BatchContext.device_bytes), so
        this never stalls a cold column build."""
        with self._lock:
            batches = list(self._batches.items())
            snap = {
                "batch_hits": self.batch_hits,
                "batch_misses": self.batch_misses,
                "batch_evictions": self.batch_evictions,
                # device-error recovery counters (failure-domain view):
                # launch/fetch device-runtime failures and pipelines the
                # circuit breaker has routed to host
                "device_failures": self.launch_failures,
                "quarantined_pipelines": len(self._quarantined),
                # Pallas scatter tier (ISSUE 15): (template, batch) pairs
                # currently dropped to the XLA scatter rung, and the
                # cumulative drop count
                "pallas_quarantined": len(self._pallas_blocked),
                "pallas_fallbacks": self.pallas_fallbacks,
                # sub-RTT serving (ISSUE 9): device partials cache +
                # on-device final-reduce counters
                "partials_cache_entries": len(self._partials),
                "partials_cache_bytes": self.partials_bytes,
                "partials_cache_hits": self.partials_hits,
                "partials_cache_misses": self.partials_misses,
                "partials_cache_evictions": self.partials_evictions,
                "partials_cache_invalidations": self.partials_invalidations,
                "device_reduce_queries": self.device_reduce_queries,
                "device_reduce_ms": round(self.device_reduce_ms_total, 3),
                # dense group-by launches by their operands' origin
                "groupby_operand_launches":
                    dict(self.groupby_operand_launches),
                "groupby_narrowed_launches": self.groupby_narrowed_launches,
                "groupby_narrow_overflows": self.groupby_narrow_overflows,
                "groupby_full_launches": self.groupby_full_launches,
                "groupby_full_table_bytes": self.groupby_full_table_bytes,
                "groupby_slotted_launches": self.groupby_slotted_launches,
                "groupby_slotted_bytes": self.groupby_slotted_bytes,
                "groupby_ordered_launches": self.groupby_ordered_launches,
                "groupby_ordered_bytes": self.groupby_ordered_bytes,
                "groupby_key_space_probes": self.groupby_key_space_probes,
            }
        per_batch = [
            {
                "segments": len(key),
                "resident_bytes": ctx.device_bytes(),
                "narrow_saved_bytes": ctx.narrow_saved_bytes(),
                "groupby_operand_bytes": ctx.groupby_operand_bytes(),
            }
            for key, ctx in batches
        ]
        snap.update(
            cached_batches=len(per_batch),
            resident_bytes=sum(b["resident_bytes"] for b in per_batch),
            # of resident_bytes: the prepared group-by operands
            groupby_operand_bytes=sum(
                b["groupby_operand_bytes"] for b in per_batch),
            narrow_saved_bytes=sum(
                b["narrow_saved_bytes"] for b in per_batch),
            max_cached_bytes=self.MAX_CACHED_BYTES,
            batches=per_batch,
        )
        # kernel roofline accounting (ISSUE 11): per-pipeline achieved
        # GB/s vs the probed HBM peak
        snap["roofline"] = self.roofline_stats()
        return snap

    def _retain_launch(self, key) -> None:
        with self._lock:
            self._inflight_launches[key] = \
                self._inflight_launches.get(key, 0) + 1
            self.inflight += 1

    def _release_launch(self, key) -> None:
        with self._lock:
            n = self._inflight_launches.get(key, 0) - 1
            if n > 0:
                self._inflight_launches[key] = n
            else:
                self._inflight_launches.pop(key, None)
            self.inflight -= 1
            # a fetch-time device failure marked this batch poisoned:
            # evict it as soon as the last in-flight pin drains, so the
            # next query re-uploads fresh device buffers
            if key in self._poisoned_batches \
                    and key not in self._inflight_launches:
                self._poisoned_batches.discard(key)
                if self._batches.pop(key, None) is not None:
                    self.batch_evictions += 1
                self._drop_partials_for_batch(key)
        # byte cap re-check after the fetch (columns materialize lazily,
        # so the batch may have grown during this query)
        self._evict(keep=key)

    # ---- device-error recovery (launch/fetch failures) -------------------
    QUARANTINE_AFTER = 2       # failures of one (template, batch) → host
    QUARANTINE_TTL_S = 300.0   # then probe the device again (half-open)
    MAX_FAILURE_KEYS = 1024    # failure-count map bound (diverse workloads)

    def _record_device_failure(self, template, batch_key) -> bool:
        """Count a device-runtime failure against (template, batch) and
        trip the quarantine breaker past the threshold. Compiled
        pipelines for the template are dropped (a retry recompiles from
        scratch). Returns True when the key is now quarantined."""
        with self._lock:
            self.launch_failures += 1
            key = (template, batch_key)
            if key not in self._pipeline_failures and \
                    len(self._pipeline_failures) >= self.MAX_FAILURE_KEYS:
                self._pipeline_failures.pop(
                    next(iter(self._pipeline_failures)))
            n = self._pipeline_failures.get(key, 0) + 1
            self._pipeline_failures[key] = n
            if n >= self.QUARANTINE_AFTER:
                self._quarantined[key] = time.monotonic()
            for pk in [pk for pk in self._pipelines if pk[0] == template]:
                self._pipelines.pop(pk)
            return key in self._quarantined

    def _note_device_success(self, template, batch_key) -> None:
        """A successful fetch clears the key's strike count: the breaker
        trips on failures close together, not on two transient faults a
        week apart over thousands of good launches."""
        with self._lock:
            self._pipeline_failures.pop((template, batch_key), None)

    def _resolve_pallas(self, opts: dict) -> str:
        """Per-launch Pallas-tier mode: env kill switch, per-query SET
        opt-out, then the executor's configured mode (None = follow
        mm_mode, mirroring how the tier is exercised in interpret-mode
        tests)."""
        if os.environ.get("PINOT_TPU_PALLAS", "1") in ("", "0"):
            return "off"
        if bool_option(opts, "usepallas", None) is False:
            return "off"
        mode = self.mm_mode if self.pallas_mode is None else self.pallas_mode
        return _resolve_mm_mode(mode)

    def _is_pallas_blocked(self, template, batch_key) -> bool:
        with self._lock:
            ts = self._pallas_blocked.get((template, batch_key))
            if ts is None:
                return False
            if time.monotonic() - ts >= self.QUARANTINE_TTL_S:
                # half-open: probe the Pallas form again after cooldown
                self._pallas_blocked.pop((template, batch_key), None)
                return False
            return True

    def _block_pallas(self, template, batch_key) -> None:
        """Drop a failing (template, batch) pair to the XLA scatter rung:
        the NEXT launch compiles the pallas_mode="off" pipeline variant —
        still on device. Compiled Pallas-form entries for the template
        are dropped so the rung takes effect immediately."""
        with self._lock:
            if (template, batch_key) not in self._pallas_blocked and \
                    len(self._pallas_blocked) >= self.MAX_FAILURE_KEYS:
                self._pallas_blocked.pop(next(iter(self._pallas_blocked)))
            self._pallas_blocked[(template, batch_key)] = time.monotonic()
            self.pallas_fallbacks += 1
            for pk in [pk for pk in self._pipelines
                       if pk[0] == template and pk[5] != "off"]:
                self._pipelines.pop(pk)

    def _is_quarantined(self, template, batch_key) -> bool:
        with self._lock:
            key = (template, batch_key)
            ts = self._quarantined.get(key)
            if ts is None:
                return False
            if time.monotonic() - ts >= self.QUARANTINE_TTL_S:
                # half-open: after the cooldown the next launch probes the
                # device again with a fresh strike count — two more
                # failures re-quarantine for another window
                self._quarantined.pop(key, None)
                self._pipeline_failures.pop(key, None)
                return False
            return True

    def reset_quarantine(self) -> None:
        """Operational reset (tests / admin): forget failure history."""
        with self._lock:
            self._pipeline_failures.clear()
            self._quarantined.clear()
            self._pallas_blocked.clear()

    def evict_segment_dir(self, seg_dir: str) -> int:
        """Evict every cached batch whose key contains ``seg_dir`` — the
        tier-demotion hook (server/tiering.py): a segment leaving the hot
        tier must free its HBM blocks NOW, not at LRU depth. Batches a
        dispatched launch still pins defer to _release_launch via the
        poisoned set, exactly like the device-failure eviction path.
        Returns the number of batches dropped immediately."""
        with self._lock:
            keys = [k for k in self._batches if seg_dir in k]
        return sum(1 for k in keys if self._evict_batch(k))

    def _evict_batch(self, key) -> bool:
        """Drop the implicated BatchContext after a device failure so a
        retry re-uploads fresh buffers (RESOURCE_EXHAUSTED usually means
        this batch's blocks are what needs freeing). Batches other
        launches still pin are deferred to _release_launch via the
        poisoned set."""
        with self._lock:
            if key in self._inflight_launches:
                self._poisoned_batches.add(key)
                return False
            dropped = self._batches.pop(key, None) is not None
            if dropped:
                self.batch_evictions += 1
            # a device failure taints anything derived from the batch's
            # buffers: cached partials go with it either way
            self._drop_partials_for_batch(key)
            return dropped

    def on_fetch_device_error(self, e, template, batch_key,
                              used_pallas: bool = False) -> None:
        """InflightLaunch.fetch error hook: a device-runtime failure on
        the blocking fetch counts toward the quarantine breaker, marks
        the batch for eviction, and converts to DeviceUnsupported — the
        engine then re-runs THIS query's batch on the host through its
        fallback gate (a dispatched flight can't be relaunched). When the
        failing pipeline was the Pallas form, the failure blocks only the
        Pallas rung — the NEXT query on this (template, batch) compiles
        the XLA scatter form and stays on device, and no host-quarantine
        strike is recorded. Non-device errors return so the caller
        re-raises the original."""
        if not _is_device_runtime_error(e):
            return
        # a coalesced cohort re-raises ONE shared exception to every
        # member: count the failure event once, not once per member —
        # otherwise a single transient fault on a 2+-member cohort trips
        # the 2-strike quarantine instantly
        if not getattr(e, "_pinot_failure_counted", False):
            try:
                e._pinot_failure_counted = True
            except Exception:  # noqa: BLE001 — slotted exceptions
                pass
            if used_pallas:
                with self._lock:
                    self.launch_failures += 1
                self._block_pallas(template, batch_key)
                self._evict_batch(batch_key)
                log.warning(
                    "pallas pipeline fetch failed (%s: %s); batch "
                    "evicted, XLA scatter rung takes over — this query "
                    "falls back to host", type(e).__name__, e)
            else:
                quarantined = self._record_device_failure(template,
                                                          batch_key)
                self._evict_batch(batch_key)
                log.warning(
                    "device fetch failed (%s: %s); batch evicted%s — host "
                    "fallback", type(e).__name__, e,
                    ", pipeline QUARANTINED to host" if quarantined else "")
        raise DeviceUnsupported(
            f"device fetch failed ({type(e).__name__}); host fallback"
        ) from e

    @staticmethod
    def _fault_target(q) -> str:
        """Stable per-query-shape label the fault harness matches
        ``target`` filters against (lets a chaos test poison ONE
        template while others keep running on device)."""
        bits = [q.table_name or ""]
        for a in (q.aggregations() or ()):
            arg = a.args[0].name if a.args and a.args[0].is_identifier \
                else ""
            bits.append(f"{a.name}({arg})")
        bits.extend(g.name for g in (q.group_by or ()) if g.is_identifier)
        return ":".join(bits)

    def _make_resolve(self, bufs_dev, layout, flight=None, attrs=None,
                      launch=None):
        """fetch-phase closure shared by solo and cohort launches: ONE
        blocking device_get of the dispatched packed buffer, observability
        accounting under the lock, unpack by the precomputed layout.

        The blocking wait always splits into a KERNEL wait
        (block_until_ready — remaining device compute since dispatch:
        ``kernelMs``, the fetch's wait) and a LINK wait (device_get — the
        host transfer). ``launch``, the launch on the device's timeline
        (None where nothing was launched), splits the device's part
        further: ``deviceQueueMs`` behind the launches dispatched before
        it, ``deviceRunMs`` on the device, ``launchesAhead``. They feed
        the ALWAYS-ON accounting — the wait span, the flight record, the
        response's stats and ``/metrics`` — and the achieved GB/s divides
        by the run. Whoever runs the one fetch —
        the launching query, or the first member of a cohort to get here
        — also records the pair, and the unpack, as spans on ITS trace
        (the thread's active tracer: InflightLaunch._traced_resolve). The
        untraced overhead is one extra no-op call on an already-complete
        buffer.

        ``flight``: the launch's roofline flight dict (None = no
        accounting: a prebuilt cohort program's first run); filled with
        the per-flight record via _note_flight after the unpack.
        ``attrs``: what the members' traces say of this launch (``launchId``,
        ``cohortSize``, ``cohortPadded``; ``partialsCacheHit`` where
        nothing was launched); rides the closure as ``resolve.stamp``
        with the wait's span, so the member that fetched can add its own
        role to it."""
        stamp = {"attrs": attrs or {}, "wait_span": None}

        def resolve():
            import time as _time

            if faults.ACTIVE:
                faults.inject("device.fetch")
            wait_span = stamp["wait_span"] = trace_span(
                "executor.device_wait")
            wait_span.set(**stamp["attrs"])
            _t_get = _time.perf_counter()
            with wait_span:
                jax.block_until_ready(bufs_dev)
            _t_kernel = _time.perf_counter()
            if launch is not None:
                # the fetch saw its launch ready: stamped now, if the
                # device timeline's waiter has not stamped it already
                self.device_timeline.seen(launch, _t_kernel)
            with trace_span("executor.link"):
                bufs = jax.device_get(bufs_dev)
            # blocking wait = link round trip + kernel
            _t_link = _time.perf_counter()
            wait = _t_link - _t_get
            with trace_span("executor.unpack"):
                bufs = {k: np.asarray(v) for k, v in bufs.items()}
                fetched = sum(v.nbytes for v in bufs.values())
                with self._lock:
                    # observability: what actually crossed the host link
                    self.fetch_bytes_total += fetched
                    self.fetch_leaves_total += len(bufs)
                self.metrics.time_ms("deviceFetchMs", wait * 1e3)
                outs = _unpack_outs(bufs, layout)
                # what only the result can say of the key space, and the
                # launch's queue and run on the device
                read = _key_space_outcome(
                    outs, stamp["attrs"].get("groupbyKeySpace"))
                if launch is not None:
                    read.update(launch.on_device())
                    self.metrics.time_ms("deviceQueueMs", launch.queue_s * 1e3)
                    self.metrics.time_ms("deviceRunMs", launch.run_s * 1e3)
                    self.metrics.observe("deviceLaunchesAhead", launch.ahead)
                stamp["attrs"].update(read)
                wait_span.set(**read)
                if flight is not None:
                    self._note_flight(flight, outs, fetched,
                                      _t_kernel - _t_get,
                                      _t_link - _t_kernel, launch)
            return outs

        resolve.stamp = stamp
        return resolve

    # ---- kernel roofline accounting (ISSUE 11) ---------------------------
    @staticmethod
    def _pipeline_label(template, blockskip: bool, trim,
                        pallas: bool = False, fused: bool = False) -> str:
        """Human-stable per-pipeline label the roofline aggregates key on:
        the template SHAPE plus the compile-affecting execution modes —
        coarse on purpose (per-template keys would fragment the stats
        into one-row buckets per literal-free query shape). The Pallas
        scatter tier and the fused filter+gather+aggregate form carry
        their own suffixes so hbm_stats()["roofline"] and EXPLAIN
        ANALYZE's KERNEL line attribute each kernel correctly."""
        label = template[0]
        if blockskip:
            label += "+bskip"
        if fused:
            label += "+fused"
        if pallas:
            label += "+pallas"
        if trim is not None:
            label += "+trim"
        return label

    def _new_flight(self, label: str, cache_hit: bool = False,
                    fused: bool = False) -> dict:
        """Per-launch roofline flight record skeleton. ``data_bytes`` /
        ``zone_bytes`` are the static cost model's inputs (filled after
        the column gather); the resolve fills timings and the final
        record via _note_flight. ``fused``: the block-skip gather runs
        inside the fused Pallas kernel — the bytes-moved model must not
        charge the (B, R) gather-buffer round trip the XLA form pays."""
        return {"label": label, "cache_hit": cache_hit, "fused": fused,
                "data_bytes": 0, "zone_bytes": 0, "record": None}

    def _note_flight(self, flight: dict, outs: dict, fetched_bytes: int,
                     kernel_s: float, link_s: float, launch=None) -> None:
        """Fold one resolved flight into the roofline accounting: the
        modeled bytes (column planes at their ColPlan widths, data planes
        scaled by the block-skip gather ratio the kernel reported, plus
        the packed fetch buffer) over the device's time on the launch
        (``launch.run_s``: not the fetch's wait, which holds the queue
        behind other launches) → achieved GB/s. Cache hits (no kernel
        ran) count separately and never feed the GB/s histogram."""
        try:
            cache_hit = bool(flight.get("cache_hit"))
            ratio = 1.0
            skip_obs = None  # measured selectivity (skip path only)
            bt, bs = outs.get("blocks_total"), outs.get("blocks_scanned")
            if bt is not None and bs is not None:
                total_b = float(np.sum(np.asarray(bt)))
                if total_b > 0:
                    ratio = min(1.0, float(np.sum(np.asarray(bs))) / total_b)
                    skip_obs = ratio
            # block-skip gather-buffer round trip: the XLA form
            # materializes the gathered (B, R) planes in HBM (one write +
            # one read of every gathered byte) before the filter runs;
            # the fused Pallas kernel streams candidate blocks straight
            # into VMEM, so it must NOT be charged for the eliminated
            # round trip (ISSUE 15 bytes-moved model fix)
            gather_bytes = 0
            if ratio < 1.0 and not flight.get("fused"):
                gather_bytes = int(2 * flight["data_bytes"] * ratio)
            bytes_moved = 0 if cache_hit else int(
                flight["zone_bytes"] + flight["data_bytes"] * ratio
                + gather_bytes + fetched_bytes)
            kernel_ms = kernel_s * 1e3
            link_ms = link_s * 1e3
            rec = {"kernel": flight["label"],
                   "bytesMoved": bytes_moved,
                   "bytesFetched": int(fetched_bytes),
                   "kernelMs": round(kernel_ms, 3),
                   "linkMs": round(link_ms, 3),
                   "cacheHit": cache_hit}
            queue_ms = run_ms = 0.0
            if launch is not None:
                queue_ms, run_ms = launch.queue_s * 1e3, launch.run_s * 1e3
                rec["queueMs"] = round(queue_ms, 3)
                rec["runMs"] = round(run_ms, 3)
            if gather_bytes:
                rec["gatherBytes"] = gather_bytes
            rec.update(flight.get("origin") or {})
            rec.update(_key_space_outcome(outs, rec.get("groupbyKeySpace")))
            gbps = None
            if not cache_hit and run_ms > 1e-6:
                gbps = bytes_moved / run_ms / 1e6
                rec["gbps"] = round(gbps, 3)
            flight["record"] = rec
            with self._lock:
                agg = self._roofline.setdefault(
                    flight["label"],
                    {"queries": 0, "cache_hits": 0, "bytes_moved": 0,
                     "kernel_ms": 0.0, "queue_ms": 0.0, "run_ms": 0.0,
                     "link_ms": 0.0})
                agg["queries"] += 1
                agg["link_ms"] += link_ms
                if cache_hit:
                    agg["cache_hits"] += 1
                else:
                    agg["bytes_moved"] += bytes_moved
                    agg["kernel_ms"] += kernel_ms
                    agg["queue_ms"] += queue_ms
                    agg["run_ms"] += run_ms
            if gbps is not None:
                self.metrics.observe("deviceKernelGbps", gbps)
            # plan-advisor feedback: measured skip selectivity (only the
            # skip path emits blocks_total>0 — the dense form measures
            # nothing, by design) and per-rung achieved GB/s keyed by the
            # pipeline label (advisor splits off the +pallas suffix)
            adv_key = flight.get("adv_key")
            if adv_key and self.advisor is not None and not cache_hit:
                self.advisor.observe(
                    adv_key, skip_ratio=skip_obs,
                    label=flight["label"], gbps=gbps)
        except Exception:  # noqa: BLE001 — accounting must never fail a fetch
            log.exception("roofline flight accounting failed")

    def roofline_stats(self) -> dict:
        """Per-pipeline roofline snapshot: modeled bytes / the device's
        time on the launches (``run_ms``) → achieved GB/s per label.
        ``kernel_ms`` sums the fetches' waits, ``queue_ms`` the part of
        them spent behind other launches."""
        with self._lock:
            aggs = {k: dict(v) for k, v in self._roofline.items()}
        kernels = {}
        for label, agg in aggs.items():
            entry = dict(agg)
            for k in ("kernel_ms", "queue_ms", "run_ms", "link_ms"):
                entry[k] = round(entry[k], 3)
            if agg["run_ms"] > 0:
                gbps = agg["bytes_moved"] / (agg["run_ms"] / 1e3) / 1e9
                entry["gbps"] = round(gbps, 3)
            kernels[label] = entry
        return {"kernels": kernels}

    # ---- template build --------------------------------------------------
    def _note_key_space(self, key, full: bool) -> None:
        with self._lock:
            self._key_spaces[key] = full
            while len(self._key_spaces) > self.MAX_FAILURE_KEYS:
                self._key_spaces.pop(next(iter(self._key_spaces)))

    def _key_space_is_full(self, ctx, batch_key, filter_tpl, group_cols,
                           group_cards, agg_tpls, params) -> bool:
        """Is this template's large key space full on this batch? What
        was observed of it before; else its live 128-cell blocks are
        counted now (``_live_blocks``), once, and the answer kept: more
        than the narrowed table holds is full. A filter over planes the
        count cannot read (multi-value blocks) is taken as narrowed, as
        before the full regime."""
        key = (filter_tpl, group_cols, agg_tpls, batch_key)
        with self._lock:
            known = self._key_spaces.get(key)
        if known is not None:
            return known
        needed = self._needed_columns(filter_tpl) | set(group_cols)
        widths = self._plain_widths(ctx, needed)
        full = False
        if set(widths) == needed:
            cols = {c: ctx.decoded_column(c[4:]) if c.startswith("dv::")
                    else ctx.column(c) for c in needed}
            lits = {k: v for k, v in params.items()
                    if isinstance(v, jax.Array)}
            for c in needed:
                offset = ctx.width_plan(c).offset
                if offset is not None:
                    lits["fo::" + c] = jnp.asarray(np.asarray(
                        offset, dtype=np.dtype(ctx.width_plan(c).wide)))
            with self._lock:
                self.groupby_key_space_probes += 1
            full = int(_live_blocks(
                cols, ctx.n_docs_dev, lits, filter_tpl=filter_tpl,
                group_cols=group_cols, group_cards=group_cards,
                wsig=tuple(sorted(widths.items())))) > NARROW_BLOCKS
        self._note_key_space(key, full)
        return full

    @staticmethod
    def _plain_widths(ctx, keys) -> dict:
        """{cols key: its width plan's signature} for the stored and
        decoded planes among ``keys`` (zone maps, hashes, byte planes,
        multi-value blocks and prepared operands have none)."""
        return {c: ctx.width_plan(c).sig() for c in sorted(keys)
                if c.startswith("dv::") or not c.startswith(
                    (bs_ops.ZLO, bs_ops.ZHI, "sk::", "hh::", "bp::", "mv::")
                    + _GB_OPERAND_PREFIXES)}

    def _groups_limit(self, opts: dict) -> int:
        """numGroupsLimit as the statement has it: its SET, else the
        engine's default."""
        if "numgroupslimit" in opts:
            return max(1, int(opts["numgroupslimit"]))
        return self.num_groups_limit

    def _agg_template(self, i: int, a: Expression, ctx: BatchContext, params, counter):
        name = a.name
        if name in ("distinctcountbitmap", "segmentpartitioneddistinctcount"):
            name = "distinctcount"
        if name not in DEVICE_AGGS:
            raise DeviceUnsupported(f"aggregation {name} not on device")
        if name == "count":
            return ("count", None, None)
        if name == "distinctcount":
            arg = a.args[0]
            if not arg.is_identifier or ctx.encoding(arg.name) != Encoding.DICT:
                raise DeviceUnsupported("distinctcount needs a dict column")
            return ("distinctcount", arg.name, ctx.cardinality(arg.name))
        if name == "distinctcounthll":
            arg = a.args[0]
            if not arg.is_identifier or ctx.encoding(arg.name) != Encoding.DICT:
                raise DeviceUnsupported("distinctcounthll device path needs a dict column")
            spec = aggspec.make_spec(a)
            return ("distinctcounthll", arg.name, spec.log2m)
        if name == "hllmerge":
            arg = a.args[0]
            if not arg.is_identifier or ctx.encoding(arg.name) != Encoding.DICT:
                raise DeviceUnsupported("hllmerge needs a dict BYTES column")
            spec = aggspec.make_spec(a)
            width = ctx.bytes_width(arg.name)
            if width != spec.m:
                raise DeviceUnsupported(
                    f"hllmerge plane width {width} != m {spec.m}")
            return ("hllmerge", arg.name, spec.log2m)
        if name in ("firstwithtime", "lastwithtime"):
            # value + time expression pair; STRING dataType can't ride the
            # float64 value plane — build_expr already rejects non-numeric
            # dict columns, sending those to the host path
            vt = build_expr(a.args[0], ctx, params, counter)
            tt = build_expr(a.args[1], ctx, params, counter)
            return (name, (vt, tt), "pair")
        # numeric-arg aggregations
        argt = build_expr(a.args[0], ctx, params, counter)
        rpb = None
        nplanes = None
        if name in ("sum", "avg"):
            # metadata interval arithmetic sizes the two-stage scatter blocks
            # AND the matmul kernel's byte planes (ops/groupby_mm.py)
            bounds = expr_bounds(a.args[0], ctx)
            if bounds is not None:
                from pinot_tpu.ops import groupby_mm as mm

                rpb = agg_ops.rows_per_block_for(max(abs(bounds[0]), abs(bounds[1])))
                nplanes = mm.int_planes_needed(bounds[0], bounds[1])
                import math

                off = math.floor(bounds[0])
                params[f"off{i}"] = jnp.int64(off)
                # the python int, for the prepared operands' plan (a side
                # channel like __hostsig__; _launch_pinned pops it)
                params.setdefault("__offsets__", {})[i] = off
                sig = params.get("__hostsig__")
                if sig is not None:
                    sig.append((f"off{i}", "<i8", (),
                                np.int64(off).tobytes()))
            return (name, argt, (nplanes, rpb))
        return (name, argt, rpb)

    def launch(self, q: QueryContext, segments,
               final: bool = False, alive=None,
               tracer=None, reduce_mode=None) -> InflightLaunch:
        """LAUNCH phase: template build + column gather + NON-BLOCKING XLA
        dispatch (JAX dispatch is async; only device_get blocks). Returns
        an InflightLaunch whose ``fetch()`` resolves the packed output
        buffer — N concurrent queries overlap their link round trips
        instead of serializing them. Under concurrency, same-cohort
        launches (one batch, one template, same param shapes) coalesce
        into a single vmapped dispatch (engine/inflight.py). Raises
        DeviceUnsupported for shapes the device path doesn't cover.

        ``alive``: optional per-segment bool sequence from a caller that
        already ran the stats pruner (engine.execute_segments_async) —
        skips re-deriving Level-1 verdicts here. None = derive them.

        ``tracer``: the query's explicit Tracer (common/trace.py) —
        carried by reference through the handle and the fetch closure so
        spans recorded on OTHER threads (deferred fetch, cohort leader)
        land on THIS query's trace, not a thread-local's.

        ``reduce_mode``: None | "partial" | "terminal" — whether this
        batch is the SOLE partial of its execution (engine decides), and
        whether anything merges after it. Gates the on-device final
        reduce (ops/device_reduce.py): trimming a non-sole partial would
        lose group contributions a later merge needs."""
        t_launch = time.perf_counter()
        aggs = q.aggregations()
        if q.distinct:
            # DISTINCT == group-by over the select columns with no aggs:
            # the dense/sorted group machinery yields the distinct combos
            # (the reference's DistinctAggregationFunction is the same
            # group-keys-only special case)
            if aggs:
                raise DeviceUnsupported("DISTINCT over aggregations")
            aggs = []
        elif not aggs:
            raise DeviceUnsupported("selection on host path")
        for a in aggs:
            if a.name not in DEVICE_AGGS:
                raise DeviceUnsupported(f"agg {a.name}")
        for s in segments:
            if not segment_device_eligible(s):
                raise DeviceUnsupported("mutable/upsert segment needs host scan path")

        # the batch stays pinned for the WHOLE launch — template build and
        # column materialization included, not just the dispatched flight
        # (retain=True takes the pin atomically with the cache insert)
        batch_key = self._batch_key(segments)
        last_err = None
        xla_attempts = 0
        # fallback ladder: Pallas form → XLA scatter form (still on
        # device) → one XLA retry → host. A Pallas-only failure never
        # leaves the device (ISSUE 15 quarantine rung); host-quarantine
        # strikes count XLA-rung failures only.
        for _attempt in range(3):
            ctx = self.batch_for(segments, retain=True)
            tpl_box: list = []
            # the template build, up to the gather: closed by
            # _launch_pinned where it ends, or here if it raises
            tpl_span = trace_span("engine.template", tracer)
            tpl_span.__enter__()
            try:
                handle = self._launch_pinned(q, ctx, batch_key, segments,
                                             aggs, final, alive, tpl_box,
                                             tracer, reduce_mode, tpl_span)
                handle.tracer = tracer
                if tpl_box and tpl_box[0][0] == "groupby_narrow":
                    # one that finds its key space full is launched
                    # again, full, by its own fetch
                    handle.relaunch = functools.partial(
                        self.launch, q, segments, final, alive, tracer,
                        reduce_mode)
                self.metrics.time_ms(
                    "deviceLaunchMs",
                    (time.perf_counter() - t_launch) * 1e3)
                return handle
            except BaseException as e:
                tpl_span.close()
                self._release_launch(batch_key)
                if not _is_device_runtime_error(e):
                    raise
                # device-runtime failure (XlaRuntimeError /
                # RESOURCE_EXHAUSTED, real or injected): evict the
                # implicated batch so the retry re-uploads fresh buffers
                last_err = e
                tpl = tpl_box[0] if tpl_box else None
                pmode_used = tpl_box[1] if len(tpl_box) > 1 else "off"
                if pmode_used != "off" and tpl is not None:
                    # Pallas rung: block the Pallas form for this
                    # (template, batch) and retry the XLA scatter form on
                    # device — no host-quarantine strike
                    with self._lock:
                        self.launch_failures += 1
                    self._block_pallas(tpl, batch_key)
                    self._evict_batch(batch_key)
                    log.warning(
                        "pallas pipeline failed (%s: %s); batch evicted, "
                        "dropping to the XLA scatter rung on device",
                        type(e).__name__, e)
                    continue
                quarantined = False
                if tpl is not None:
                    quarantined = self._record_device_failure(
                        tpl, batch_key)
                else:
                    with self._lock:
                        self.launch_failures += 1
                self._evict_batch(batch_key)
                xla_attempts += 1
                if xla_attempts <= 1 and not quarantined:
                    log.warning(
                        "device launch failed (%s: %s); batch evicted, "
                        "retrying once on device", type(e).__name__, e)
                    continue
                break
        raise DeviceUnsupported(
            f"device launch failed after retry "
            f"({type(last_err).__name__}: {last_err}); host fallback"
        ) from last_err

    def _launch_pinned(self, q, ctx, batch_key, segments, aggs,
                       final, alive_hint=None, tpl_box=None,
                       tracer=None, reduce_mode=None,
                       tpl_span=None) -> InflightLaunch:
        params: dict = {}
        # host-bytes side channel: engine/params.py _slot records each
        # literal's (dtype, shape, bytes) here BEFORE upload, so the
        # partials-cache digest never reads a device array back. Only
        # installed when the cache could actually be consulted — a big
        # IN-list/regex LUT would otherwise be memcpy'd per launch just
        # to be thrown away
        opts = q.options_ci()
        cacheable = (self.partials_cache_enabled
                     and bool_option(opts, "usepartialscache", None)
                     is not False)
        # feedback-driven plan advisor (engine/advisor.py): keyed by the
        # PR-7 literal-free template key. SET useAdvisor=false bypasses
        # BOTH the reads (advice) and the writes (observation) — a
        # bypassed query leaves zero memo effect, so advisor-off runs are
        # bit-exact against advisor-on by construction.
        adv_key = None
        adv_notes: list = []
        if self.advisor is not None and advisor_enabled(opts):
            from pinot_tpu.broker.querylog import template_key

            adv_key = template_key(q)
        if cacheable:
            params["__hostsig__"] = []
        counter = [0]

        filter_tpl = ("true",) if q.filter is None else build_filter(
            q.filter, ctx, params, counter
        )

        group_cols, group_cards = (), ()
        group_exprs = q.select_expressions if q.distinct else q.group_by
        if group_exprs:
            gcols = []
            gcards = []
            for g in group_exprs:
                if not g.is_identifier or ctx.encoding(g.name) != Encoding.DICT:
                    raise DeviceUnsupported("group-by must be dict columns on device")
                gcols.append(g.name)
                gcards.append(ctx.cardinality(g.name))
            group_cols, group_cards = tuple(gcols), tuple(gcards)
            total = 1
            for c in group_cards:
                total *= c
        elif q.distinct:
            raise DeviceUnsupported("DISTINCT needs dict columns on device")

        agg_tpls = tuple(
            self._agg_template(i, a, ctx, params, counter) for i, a in enumerate(aggs)
        )
        offsets = params.pop("__offsets__", {})
        shape = "groupby" if group_cols else "agg"
        full_plan = None  # plan_full_groupby's, for shape "groupby_full"
        if group_cols and total > MAX_DENSE_GROUPS:
            # sort-based high-cardinality regime (MAP_BASED analog): no
            # dense accumulators, so only the additive/extremal aggs fit
            if total >= (1 << 62):
                raise DeviceUnsupported(
                    f"combined group key overflows int64 ({total})")
            # per-shard radix tables are KEYED (skeys + neutral empty-slot
            # fills), so the mesh combine merges them by key
            # (parallel/mesh.py _combine_sorted_table via
            # ops/radix_groupby.py merge_tables) — no dense psum alignment
            # needed; multi-chip high-card no longer routes to the host
            for a in aggs:
                if a.name not in SORTED_AGGS:
                    raise DeviceUnsupported(
                        f"agg {a.name} not on the sorted group-by path")
            shape = "groupby_sorted"
        elif group_cols and total > NARROW_MIN_CELLS and all(
                a.name in NARROW_AGGS for a in aggs):
            # large key space: narrowed until a launch has seen it full
            # (one key column: full from the first, where its rows can
            # be summed in key order - decided below, with the plan)
            if len(group_cols) > 1:
                shape = "groupby_narrow"
            if self.mesh is None and (
                    len(group_cols) == 1 or self._key_space_is_full(
                        ctx, batch_key, filter_tpl, group_cols, group_cards,
                        agg_tpls, params)):
                full_plan = plan_full_groupby(
                    filter_tpl, group_cols, agg_tpls, self._plain_widths(
                        ctx, self._needed_columns(("of", filter_tpl) + tuple(
                            t[1] for t in agg_tpls))),
                    offsets, ctx.S)
                if full_plan is not None or len(group_cols) > 1:
                    shape = "groupby_full"
        for name, argt, extra in agg_tpls:
            if shape == "groupby" and name in (
                    "distinctcount", "distinctcounthll", "hllmerge"):
                cells = extra if name == "distinctcount" else (1 << extra)
                for c in group_cards:
                    cells *= c
                if cells > MAX_PRESENCE_CELLS:
                    raise DeviceUnsupported(f"{name} per-group state too large ({cells})")
        # the keyed table's length (0: the table is the key space itself);
        # the sorted regime's is the statement's numGroupsLimit, as
        # _to_intermediate reads it
        sorted_k = {"groupby_sorted": min(self._groups_limit(opts),
                                          MAX_SORTED_GROUPS),
                    "groupby_narrow": NARROW_GROUPS}.get(shape, 0)
        # final only changes sketch outputs; don't fork the jit cache for
        # templates where it is a no-op
        final = final and any(
            name in ("distinctcount", "distinctcounthll", "hllmerge")
            for name, _, _ in agg_tpls
        )
        template = (shape, filter_tpl, group_cols, group_cards, agg_tpls,
                    sorted_k, final)
        if tpl_box is not None:
            # publish the template to launch()'s recovery handler so a
            # device-runtime failure below is counted per-(template, batch)
            tpl_box.append(template)
        # Pallas scatter tier (ISSUE 15): env kill switch + per-query SET
        # usePallas opt-out + the quarantine XLA rung — a blocked
        # (template, batch) pair compiles the pallas_mode="off" variant
        # and stays ON DEVICE
        pmode = self._resolve_pallas(opts)
        if pmode != "off" and self._is_pallas_blocked(template, batch_key):
            pmode = "off"
        # failure ATTRIBUTION for launch()'s fallback ladder: a template
        # that routes nothing to the tier must not charge its failures
        # to the Pallas rung (the "XLA retry" would recompile a
        # byte-identical pipeline and skip the host-quarantine strike).
        # Widths aren't planned yet, so this conservative estimate is
        # refined once the width plan and fused eligibility exist.
        routes_pallas = pmode != "off" and _template_uses_pallas(
            template, None, False, pmode, ctx.S * ctx.pad_to)
        if tpl_box is not None:
            tpl_box.append(pmode if routes_pallas else "off")
        if self._is_quarantined(template, batch_key):
            # circuit breaker: this (template, batch) failed on device
            # QUARANTINE_AFTER times — route it to the host path while
            # every other template keeps running on device
            raise DeviceUnsupported(
                "pipeline quarantined to host after repeated device "
                "failures")
        if faults.ACTIVE:
            faults.inject("device.launch", target=self._fault_target(q))

        # Level-2 eligibility: the filter has interval structure the zone
        # maps can act on, the batch is block-aligned, and the query didn't
        # opt out (SET useBlockSkip = false — the force-dense form the
        # differential parity suite compares against)
        use_bs, zone_cols = False, set()
        if filter_tpl[0] not in ("true", "false") \
                and bool_option(opts, "useblockskip", None) is not False \
                and ctx.pad_to % bs_ops.BLOCK_ROWS == 0:
            prunable, zone_cols = bs_ops.prunable_columns(filter_tpl)
            # a full key space is no slice of the table: its rows are read
            # in key order, where a zone block is no run of rows
            use_bs = prunable and bool(zone_cols) \
                and shape != "groupby_full"
        # advisor: skip-vs-dense and candidate-bound selection from the
        # template's MEASURED selectivity. ``use_bs`` carries the choice
        # as its truthiness: False = dense, True = static CAND_FRACTION,
        # int>1 = tightened fraction — the pipeline key/entry/label all
        # fork on the value, so each advised form compiles once. Either
        # way the results are bit-exact: the dense form and the skip form
        # agree by the differential suite, and an over-tight bound
        # overflows onto the in-kernel dense fallback.
        if use_bs and adv_key is not None:
            frac, note = self.advisor.advise_blockskip(
                adv_key, bs_ops.CAND_FRACTION)
            if frac == 0:
                use_bs, zone_cols = False, set()
            elif frac != bs_ops.CAND_FRACTION:
                use_bs = frac
            if note:
                adv_notes.append(note)

        # Level-1 launch-time segment skip: evaluate the filter tree against
        # per-segment column stats (min/max, dictionary membership, bloom
        # for EQ/IN) with the broker pruner's conservative tri-state
        # semantics. The result is a per-query VECTOR PARAM, not a batch
        # key: pruned members stay in the (S, L) batch, dead.
        if alive_hint is not None:
            alive = np.asarray(alive_hint, dtype=bool)
        else:
            alive = np.ones(ctx.S, dtype=bool)
            if q.filter is not None:
                pruner = self._stats_pruner
                if pruner is None:
                    from pinot_tpu.engine.engine import SegmentPruner

                    pruner = self._stats_pruner = SegmentPruner()
                for i, s in enumerate(segments):
                    alive[i] = not pruner.prune(q, s)
        params["ps_alive"] = jnp.asarray(alive)

        # SET useSortedProjection=false keeps the per-query in-pipeline
        # sort (the cold-scan measurement form); default taps the batch's
        # cached sorted projection for filterless terminal HLL
        sorted_proj_ok = bool_option(
            opts, "usesortedprojection", None) is not False
        needed = self._needed_columns(filter_tpl) | set(group_cols)
        if use_bs:
            for zc in zone_cols:
                needed.add(bs_ops.ZLO + zc)
                needed.add(bs_ops.ZHI + zc)
        for name, argt, extra in agg_tpls:
            if name == "distinctcount":
                needed.add(argt)
            elif name == "distinctcounthll":
                if (shape == "groupby" and filter_tpl == ("true",)
                        and sorted_proj_ok
                        and _hll_sort_eligible(final, self.mesh is None,
                                               total, extra, self.mm_mode)):
                    needed.add(f"sk::{argt}::{extra}")
                else:
                    needed.add("hh::" + argt)
            elif name == "hllmerge":
                needed.add("bp::" + argt)
            elif name in ("firstwithtime", "lastwithtime"):
                needed |= self._needed_columns(argt[0])
                needed |= self._needed_columns(argt[1])
            elif argt is not None:
                needed |= self._needed_columns(argt)
        if not needed:  # COUNT(*) no filter: one column carries the shape
            needed.add(segments[0].column_names()[0])

        # per-column width plan (engine/params.py ColPlan): part of the
        # pipeline cache key — narrow dict-id planes, frame-of-reference
        # raw/decoded planes, and the opt-in sub-byte tier each compile
        # their own template form, and cohort coalescing keys on the entry
        # so same-plan queries still stack. FOR offsets ride as per-batch
        # "fo::<key>" params (replicated on the mesh, stacked per cohort
        # member) — the offset VALUE stays out of the compiled template.
        widths = self._plain_widths(ctx, needed)
        host_sigs = params.pop("__hostsig__", [])
        for c in sorted(widths):
            plan = ctx.width_plan(c)
            if plan.offset is not None:
                fo = np.asarray(plan.offset, dtype=np.dtype(plan.wide))
                params["fo::" + c] = jnp.asarray(fo)
                host_sigs.append(("fo::" + c, fo.dtype.str, (),
                                  fo.tobytes()))
        wsig = tuple(sorted(widths.items()))

        # on-device final reduce (ops/device_reduce.py): plan the ORDER
        # BY trim when this batch is the sole partial of its execution.
        # The spec is static (pow2 bound + order signature) and keys the
        # pipeline entry; the exact keep count rides as the tr_k param.
        trim = None
        adv_trim_keep = None
        if reduce_mode is not None and shape in KEY_SPACES:
            # advisor: group_trim_size tightened toward the template's
            # observed group count (trim_bound still floors the keep at
            # the reference's 5*(offset+limit), so parity semantics
            # hold; the tightened bound covers every observed group with
            # headroom — overflow observations stand the advice down)
            gts = self.group_trim_size
            if adv_key is not None:
                gts2, note = self.advisor.advise_trim(adv_key, gts)
                if note:
                    gts = gts2
                    adv_notes.append(note)
            table_len = sorted_k or total
            # the SUMs whose int64 leaf the selection may order by: an
            # integer argument of known range whose sum over every row
            # stays where float64 holds each whole number
            rows = ctx.S * ctx.pad_to
            exact_int = frozenset(
                i for i, (name, _argt, extra) in enumerate(agg_tpls)
                if name == "sum" and isinstance(extra, tuple) and extra[0]
                and i in offsets and rows * (abs(offsets[i]) + (
                    1 << (8 * extra[0]))) < dr_ops.EXACT_INT_ORDER)
            trim = dr_ops.plan_trim(q, group_exprs, aggs, shape, table_len,
                                    reduce_mode, gts, exact_int)
            if trim is not None:
                tr_k = np.int32(dr_ops.trim_keep_count(
                    q, reduce_mode, gts))
                params["tr_k"] = jnp.asarray(tr_k)
                host_sigs.append(("tr_k", "<i4", (), tr_k.tobytes()))
                if adv_key is not None:
                    adv_trim_keep = int(tr_k)

        # advisor: Pallas-vs-XLA rung selection — demote to the XLA
        # scatter rung when BOTH rungs have measured GB/s for this
        # pipeline label and XLA measured meaningfully faster (the rungs
        # are differential-pinned, so the flip is bit-exact)
        if adv_key is not None and pmode != "off":
            prov_label = self._pipeline_label(template, use_bs, trim,
                                              pallas=True)
            pmode2, note = self.advisor.advise_pallas(adv_key, pmode,
                                                      prov_label)
            if note:
                pmode = pmode2
                adv_notes.append(note)

        # the group-by kernel's statement-invariant operands (dense and
        # narrowed forms alike): taken from the batch where the template
        # allows it and the batch's bytes plus theirs stay under the byte
        # cap (plan_prepared_groupby has the rest of the conditions); else
        # the per-launch preparation runs. A mesh keeps the per-launch
        # form: the lane blocks are not laid out by segment shard.
        prepared = None
        gb_exprs = {}  # an expression operand's cols key -> how to build it
        if shape in ("groupby", "groupby_narrow") and self.mesh is None:
            prepared = plan_prepared_groupby(
                template, widths, ctx.S * ctx.pad_to, self.mm_mode, pmode,
                offsets)
            if prepared is not None:
                gb_keys = prepared[1] + tuple(
                    k for _i, k, _n in prepared[2])
                # what is built already costs nothing more
                cost = ctx.groupby_operand_cost(gb_keys)
                if cost and ctx.device_bytes() + cost > self.MAX_CACHED_BYTES:
                    prepared = None
                else:
                    needed.update(gb_keys)
                    for i, key, nplanes in prepared[2]:
                        argt = agg_tpls[i][1]
                        if argt[0] not in ("raw", "dictval"):
                            gb_exprs[key] = functools.partial(
                                self._build_expr_planes, ctx, argt, widths,
                                params, offsets[i], nplanes)
        key_layout = {}
        if full_plan is not None:
            # the full regime's operands: the key order first (the fullest
            # cell's rows say how wide a value plane may be, and whether
            # the planes are laid out cell by slot), then what the
            # statement reads, projected into it
            name, fcols, vplanes = full_plan
            # what the order and the projections are built from: the key
            # columns' ids, the values' byte planes
            sources = ("go::" + name, "gs::" + name) \
                + tuple("gk::" + c for c in group_cols) \
                + tuple(k.split("::", 2)[2] for _i, k, _n in vplanes)
            ordered = (f"gp::{name}::seg",) + tuple(k for _c, k in fcols) \
                + tuple(k for _i, k, _n in vplanes)

            def fits(keys):
                # what is built already costs nothing more
                cost = ctx.groupby_operand_cost(keys)
                return not cost \
                    or ctx.device_bytes() + cost <= self.MAX_CACHED_BYTES

            plane_bits = slot_rows = 0
            if fits(sources + ordered):
                fullest = ctx.key_order_rows(group_cols)
                plane_bits = ks_ops.plane_bits_for(fullest)
                slot_rows = ks_ops.slot_rows(fullest)
                # the slots a row of the batch the slotted planes would
                # hold: what FULL_SLOT_PADDING bounds
                padding = slot_rows * ks_ops.slot_lanes(total) \
                    / ctx.lane_rows()
                if padding > FULL_SLOT_PADDING or not fits(
                        sources + tuple(map(ctx.slotted_key, ordered))):
                    slot_rows = 0  # a skewed key, or the budget: ordered
            if plane_bits:
                as_built = ctx.slotted_key if slot_rows else str
                seg_key = as_built(f"gp::{name}::seg")
                fcols = tuple((c, as_built(k)) for c, k in fcols)
                vplanes = tuple((i, as_built(k), n) for i, k, n in vplanes)
                needed.update(
                    ("gs::" + name, seg_key) + tuple(k for _c, k in fcols)
                    + tuple(k for _i, k, _n in vplanes))
                for i, key, nplanes in vplanes:
                    argt = agg_tpls[i][1]
                    if argt[0] not in ("raw", "dictval"):
                        gb_exprs[key] = functools.partial(
                            self._build_expr_planes, ctx, argt, widths,
                            params, offsets[i], nplanes)
                prepared = ("keysorted", (), (), (
                    name, "gs::" + name, seg_key, fcols, vplanes,
                    plane_bits, slot_rows))
                key_layout = {"groupbyKeyLayout": "slotted", "slotRows":
                              slot_rows} if slot_rows else \
                    {"groupbyKeyLayout": "ordered"}
                # and why: the key order's fullest cell, the padding the
                # slotted planes would cost, the value planes' width
                key_layout.update(fullestCellRows=fullest,
                                  slotPadding=round(padding, 2),
                                  planeBits=plane_bits)
        # what the launch's spans, its flight record and EXPLAIN ANALYZE
        # say of it: prepared | built (this launch built them) | perLaunch
        gb_operands = None if shape not in (
            "groupby", "groupby_narrow", "groupby_full") \
            else "perLaunch" if prepared is None else "prepared"
        # and of its key space: dense | narrowed | sorted | full (|
        # overflow, which only the result can say: _make_resolve)
        key_space = {"groupbyKeySpace": KEY_SPACES[shape],
                     "keySpaceCells": total} if shape in KEY_SPACES else {}
        if shape == "groupby_full" and trim is not None:
            key_space["trimSelect"] = dr_ops.trim_select(trim)
        # a full launch's layout of the projected planes: slotted | ordered
        key_space.update(key_layout)

        pkey = self._pipeline_key(template, use_bs, wsig, trim, pmode,
                                  prepared)
        entry = self._pipeline_entry(template, agg_tpls, final, use_bs,
                                     widths, wsig, trim, pmode, prepared)
        # fused filter+gather+aggregate eligibility (label + bytes-moved
        # model): the plan walk is cheap and mirrors the one
        # build_pipeline compiled into the pipeline
        fused = False
        if pmode != "off" and use_bs and shape == "agg":
            from pinot_tpu.ops import pallas_scatter as ps_ops

            if bs_ops.BLOCK_ROWS == ps_ops.FUSED_BLOCK_ROWS:
                fplan = ps_ops.plan_fused(filter_tpl, agg_tpls, widths)
                fused = fplan is not None and ps_ops.fused_params_ok(
                    fplan, params)
        # refine the rung attribution now that the width plan and fused
        # eligibility are known (labels, handles, and launch()'s handler
        # all read the same verdict)
        routes_pallas = pmode != "off" and _template_uses_pallas(
            template, widths, fused, pmode, ctx.S * ctx.pad_to)
        if tpl_box is not None and len(tpl_box) > 1:
            tpl_box[1] = pmode if routes_pallas else "off"
        # roofline flight (ISSUE 11): always on
        flight = self._new_flight(
            self._pipeline_label(template, use_bs, trim,
                                 pallas=routes_pallas, fused=fused),
            fused=fused)
        if adv_key is not None:
            # _note_flight's observation hook: measured skip selectivity
            # and per-rung GB/s feed the template's memo at resolve time
            flight["adv_key"] = adv_key

        if tpl_span is not None:
            tpl_span.close()
        # device partials cache: a repeat execution — same pipeline, same
        # batch, same literal/ps_alive/param VALUES — skips the gather +
        # dispatch + kernel and re-fetches the cached packed buffer (one
        # link RTT of trimmed bytes, zero device work)
        cache_key = None
        if cacheable and alive.any():
            h = hashlib.blake2b(digest_size=16)
            h.update(repr(sorted(
                (k, d, s) for k, d, s, _b in host_sigs)).encode())
            for _k, _d, _s, b in sorted(host_sigs,
                                        key=lambda e: (e[0], e[1], e[2])):
                h.update(b)
            h.update(b"ps_alive")
            h.update(alive.tobytes())
            cache_key = (pkey, batch_key, h.digest())
            hit = self._partials_get(cache_key)
            if hit is not None:
                bufs_dev, clayout = hit
                flight["cache_hit"] = True
                resolve = self._make_resolve(
                    bufs_dev, clayout, flight,
                    attrs={"partialsCacheHit": True})
                handle = InflightLaunch(self, q, ctx, template, aggs,
                                        batch_key, resolve)
                handle.cache_hit = True
                handle.flight = flight
                handle.used_pallas = routes_pallas
                handle.adv_key = adv_key
                handle.advisor_notes = adv_notes
                handle.adv_trim_keep = adv_trim_keep
                return handle
        cols = {}
        with trace_span("executor.gather", tracer):
            for c in sorted(needed):
                if c.startswith(bs_ops.ZLO):
                    cols[c] = ctx.zone_map(c[len(bs_ops.ZLO):])[0]
                elif c.startswith(bs_ops.ZHI):
                    cols[c] = ctx.zone_map(c[len(bs_ops.ZHI):])[1]
                elif c.startswith("dv::"):
                    cols[c] = ctx.decoded_column(c[4:])
                elif c.startswith("sk::"):
                    _, colname, l2m = c.split("::")
                    cols[c] = ctx.sorted_hll_keys(
                        group_cols, group_cards, colname, int(l2m))
                elif c.startswith("hh::"):
                    cols[c] = ctx.prehashed_column(c[4:])
                elif c.startswith("bp::"):
                    cols[c] = ctx.bytes_plane_column(c[4:])
                elif c.startswith("mv::"):
                    cols[c] = ctx.mv_column(c[4:])
                elif c.startswith(_GB_OPERAND_PREFIXES):
                    cols[c], built = ctx.groupby_operand(c, gb_exprs.get(c))
                    if built:
                        gb_operands = "built"
                else:
                    cols[c] = ctx.column(c)
        origin = dict(key_space)
        if gb_operands is not None:
            origin["groupbyOperands"] = gb_operands
            with self._lock:
                self.groupby_operand_launches[gb_operands] += 1
        if shape == "groupby_narrow":
            with self._lock:
                self.groupby_narrowed_launches += 1
        elif shape == "groupby_full":
            # the key-space table a launch builds on the device: a count
            # and each SUM/AVG's total, 8 bytes a cell each
            table_bytes = 8 * total * (1 + sum(
                t[0] in ("sum", "avg") for t in agg_tpls))
            # and the projected planes it streams, slotted or in key order
            # (the largest launch's of each layout)
            plane_bytes = sum(int(v.nbytes) for k, v in cols.items()
                              if k.startswith("gp::"))
            with self._lock:
                self.groupby_full_launches += 1
                self.groupby_full_table_bytes = max(
                    self.groupby_full_table_bytes, table_bytes)
                if key_layout.get("groupbyKeyLayout") == "slotted":
                    self.groupby_slotted_launches += 1
                    self.groupby_slotted_bytes = max(
                        self.groupby_slotted_bytes, plane_bytes)
                elif key_layout.get("groupbyKeyLayout") == "ordered":
                    self.groupby_ordered_launches += 1
                    self.groupby_ordered_bytes = max(
                        self.groupby_ordered_bytes, plane_bytes)
        flight["origin"] = origin
        if os.environ.get("PINOT_TPU_WIDTH_AUDIT", "") not in ("", "0"):
            _width_audit(ctx, cols, widths)

        n_docs = ctx.n_docs_dev
        if self.mesh is not None:
            from pinot_tpu.parallel.mesh import pad_to_multiple

            cols, n_docs, params, _ = pad_to_multiple(
                cols, n_docs, params, self.mesh.devices.size
            )
        # static cost-model inputs: plane bytes at their ColPlan widths
        # (the arrays ARE stored narrow), split data vs zone — the
        # block-skip form reads zone planes fully but data planes only for
        # gathered blocks (_note_flight applies the ratio the kernel
        # reports)
        # with prepared operands the dense form reads them and the
        # filter's columns, not the (S, L) value and key planes
        read = None if prepared is None else \
            self._needed_columns(filter_tpl)
        for ck, cv in cols.items():
            nb = int(getattr(cv, "nbytes", 0))
            if ck.startswith((bs_ops.ZLO, bs_ops.ZHI)):
                flight["zone_bytes"] += nb
            elif read is None or ck in read \
                    or ck.startswith(_GB_OPERAND_PREFIXES):
                flight["data_bytes"] += nb

        # ONE packed buffer crosses the host link: device_get fetches tree
        # leaves serially, so on a high-RTT link every leaf would be a full
        # round trip. The layout
        # is shape-deterministic per (template, batch shapes) — eval_shape
        # traces without touching the device.
        lkey = (ctx.S, next(
            v for k, v in cols.items()
            if not k.startswith(("sk::", bs_ops.ZLO, bs_ops.ZHI)
                                + _GB_OPERAND_PREFIXES)).shape[1])
        layout = entry["layouts"].get(lkey)
        if layout is None:
            layout = _out_layout(
                jax.eval_shape(entry["inner"], cols, n_docs, params))
            with self._lock:
                entry["layouts"][lkey] = layout
        if not alive.any():
            # FULLY pruned: skip the device launch (and its link round
            # trip) entirely — synthesize the outputs host-side from the
            # layout with the kernels' own all-masked fills, so pruned vs
            # force-dense results stay bit-identical
            synth = _neutral_outs(layout)
            return InflightLaunch(self, q, ctx, template, aggs, batch_key,
                                  lambda: synth)
        resolve = self._dispatch(
            entry, batch_key, cols, n_docs, params, lkey, layout, tracer,
            cache_key, flight, origin=origin)
        handle = InflightLaunch(self, q, ctx, template, aggs, batch_key,
                                resolve)
        handle.flight = flight
        handle.used_pallas = routes_pallas
        handle.adv_key = adv_key
        handle.advisor_notes = adv_notes
        handle.adv_trim_keep = adv_trim_keep
        return handle

    def _build_expr_planes(self, ctx, argt, widths, params, off, nplanes):
        """Builder of an expression argument's ``gv::`` operand
        (BatchContext.groupby_operand calls it once a batch)."""
        leaves = sorted(self._needed_columns(argt))
        cols = {c: ctx.decoded_column(c[4:]) if c.startswith("dv::")
                else ctx.column(c) for c in leaves}
        fo = {"fo::" + c: params["fo::" + c] for c in leaves
              if "fo::" + c in params}
        return _expr_planes(
            cols, fo, argt=argt, wsig=tuple((c, widths[c]) for c in leaves),
            off=off, nplanes=nplanes)

    # ---- dispatch: solo vs coalesced -------------------------------------
    def _pipeline_key(self, template, blockskip, wsig, trim,
                      pallas: str = "off", prepared=None) -> tuple:
        """The ONE composition of the compiled-pipeline cache key — the
        partials cache namespaces its entries by the same tuple, so a
        future compile-affecting component added here automatically
        splits both caches together. ``pallas`` keys the scatter-tier
        mode so the Pallas form and the XLA scatter form (the
        PINOT_TPU_PALLAS=0 / SET usePallas=false escape hatch and the
        quarantine XLA rung) coexist compiled in one process; ``prepared``
        (plan_prepared_groupby's plan) keys the operand form."""
        return (template, self.mm_mode, blockskip, wsig, trim, pallas,
                prepared)

    @staticmethod
    def _post_chain(template, agg_tpls, final, trim):
        """Post-combine transform list, applied in order AFTER the
        cross-shard combine: terminal sketch finalize (regs → estimates),
        then the device-reduce trim (full table → top-K rows). Shared by
        the solo inner fn and the cohort per-member post."""
        post_fns = []
        if final:
            post_fns.append(
                lambda outs, p, _t=agg_tpls: _finalize_sketch_outs(outs, _t))
        if trim is not None:
            post_fns.append(
                lambda outs, p, _tpl=template, _s=trim:
                dr_ops.apply_trim(outs, p, _tpl, _s))
        return tuple(post_fns)

    def _pipeline_entry(self, template, agg_tpls, final,
                        blockskip=False, widths=None,
                        wsig: tuple = (), trim=None,
                        pallas: str = "off", prepared=None) -> dict:
        """Compiled-pipeline cache entry for (template, mm_mode, blockskip,
        width-plan sig, trim sig): the solo jitted pipeline, the pre-pack
        inner fn (eval_shape layouts), the raw pipeline (cohort rebuilds
        compose vmap/mesh from it), and the layout caches. The width sig
        keys the entry because plane dtypes shape BOTH the compiled
        kernels and the packed output layouts (a uint8 MIN emits a uint8
        leaf); the trim sig keys it because the device reduce reshapes
        the output table to its static bound. Cohort coalescing keys on
        id(entry), so only same-width same-trim queries stack. Built
        under the executor lock so concurrent same-template launches
        share ONE entry."""
        pkey = self._pipeline_key(template, blockskip, wsig, trim,
                                  pallas, prepared)
        with self._lock:
            entry = self._pipelines.get(pkey)
            if entry is not None:
                return entry
            raw = build_pipeline(template, self.mm_mode,
                                 sorted_hll_ok=(self.mesh is None),
                                 blockskip=blockskip, widths=widths,
                                 pallas_mode=pallas, prepared=prepared)
            # cohorts vmap the pipeline over stacked member params, and a
            # vmapped lax.cond lowers to select — BOTH branches would run
            # for every member. Cohorts therefore ride the DENSE form;
            # per-member ps_alive still applies Level-1 segment pruning
            # inside the vmap, so members pruning different segment
            # subsets stay correct. The dense form is the template's
            # entry without block skip, which also holds the cohort's
            # programs: the advisor's switch from the block-skip form to
            # the dense one (advise_blockskip) then builds nothing new.
            dense = self._pipeline_entry(
                template, agg_tpls, final, False, widths, wsig, trim,
                pallas, prepared) if blockskip else None
            if self.mesh is not None:
                from pinot_tpu.parallel.mesh import shard_pipeline

                sharded = shard_pipeline(raw, self.mesh)
            else:
                sharded = raw
            # sketch finalize and the device-reduce trim both run AFTER
            # the cross-shard combine (on replicated combined outs)
            post_fns = self._post_chain(template, agg_tpls, final, trim)
            if post_fns:
                def inner(cols, n_docs, params, _fn=sharded, _pfs=post_fns):
                    outs = _fn(cols, n_docs, params)
                    for pf in _pfs:
                        outs = pf(outs, params)
                    return outs
            else:
                inner = sharded
            # named: the jitted entry points show in the profiler's
            # trace under these names (XLA Modules: jit_pinot_pipeline)
            def pinot_pipeline(cols, n_docs, params):
                return _pack_outs(inner(cols, n_docs, params))

            pipeline = jax.jit(pinot_pipeline)
            entry = {
                "pipeline": pipeline, "inner": inner, "raw": raw,
                "agg_tpls": agg_tpls, "final": final,
                "template": template, "trim": trim, "pallas": pallas,
                "layouts": {}, "cohort": None, "cohort_layouts": {},
                "prebuilt": set(),  # batch shapes whose dense twin is built
                "dense": dense,     # a block-skip entry's dense twin
            }
            self._pipelines[pkey] = entry
            return entry

    def _dispatch(self, entry, batch_key, cols, n_docs, params, lkey, layout,
                  tracer=None, cache_key=None, flight=None, origin=None):
        """Dispatch one query, at once and as a program of its own: a
        served launch waits for no other request and for no other
        launch's fetch (PERF.md, PR 35). Returns the resolve() closure
        the InflightLaunch fetch phase blocks on. Only under the
        coalescer's ``force``, the tests' switch, does it go through a
        cohort's window.

        ``tracer`` records this query's own launch-phase spans: its
        ``dispatch``; under ``force`` the leader's window wait
        (``executor.launch_wait``) and its ``stack`` as well. A member's
        join returns at once — it records its waits in its fetch phase
        (InflightLaunch._traced_resolve). ``origin``: what the dispatch
        and device_wait spans say of a group-by (the leader's, for a
        cohort): ``groupbyOperands``, where a dense group-by's kernel
        operands came from; ``groupbyKeySpace`` and ``keySpaceCells``."""
        builder = self._prebuild_dense(entry, cols, n_docs, params, lkey)
        if builder is not None:
            # the template's first answer waits for its dense twin: after
            # it no launch of the template builds a program
            return _then_join(self._dispatch(
                entry, batch_key, cols, n_docs, params, lkey, layout,
                tracer, cache_key, flight, origin), builder)
        co = self.coalescer
        if co.should_window():
            # cohort key: same pipeline entry + same batch + same column
            # set + same param shapes/dtypes → params stack along a
            # leading axis into one vmapped launch
            sig = tuple(sorted(
                (k, tuple(v.shape), str(v.dtype)) for k, v in params.items()))
            ckey = (id(entry), batch_key, lkey, tuple(sorted(cols)), sig)

            # the leader's window: a wait for OTHER requests, so it is
            # written to the profiler; closed when the window does
            window = trace_span("executor.launch_wait", tracer)

            def _launch(members):
                window.close()
                return self._cohort_launch(
                    entry, cols, n_docs, members, lkey, tracer, flight,
                    origin)

            window.__enter__()
            try:
                cohort, idx = co.join(ckey, params, _launch)
            finally:
                window.cancel()  # a member: the window was not its own

            def resolve(_c=cohort, _i=idx):
                return _c.resolve_member(_i)

            resolve.cohort, resolve.index = cohort, idx
            return resolve
        return self._solo_launch(entry, cols, n_docs, params, layout, tracer,
                                 cache_key, flight, origin)

    def _prebuild_dense(self, entry, cols, n_docs, params, lkey):
        """With a block-skip entry's first launch on a batch shape, build
        its dense twin on a thread of its own, beside the block-skip
        program's build, so that the first answer takes the slower build
        and not their sum. The advisor switches a template to the dense
        form at its fourth launch: first met under load, that stalled
        its callers for the seconds the program takes to build (PERF.md,
        PR 31 and PR 32). Returns the building thread; None where built
        or being built, or where there is no twin."""
        dense = entry["dense"]
        if dense is None:
            return None
        on = self.prebuild_dense
        if on is None:
            on = self.prebuild_dense = jax.default_backend() == "tpu"
        if not on:
            return None
        with self._lock:
            if lkey in entry["prebuilt"]:
                return None
            entry["prebuilt"].add(lkey)

        def build():
            # the program the advisor switches the template to once it
            # has seen that block skip prunes nothing
            try:
                jax.block_until_ready(dense["pipeline"](
                    {k: v for k, v in cols.items()
                     if not k.startswith((bs_ops.ZLO, bs_ops.ZHI))},
                    n_docs, params))
            except Exception:  # noqa: BLE001 — its first launch builds it
                log.exception("prebuild of a template's dense twin failed")

        builder = threading.Thread(target=build, daemon=True,
                                   name="pinot-dense-prebuild")
        builder.start()
        return builder

    def _solo_launch(self, entry, cols, n_docs, params, layout, tracer=None,
                     cache_key=None, flight=None, origin=None):
        pipeline = entry["pipeline"]
        launch_id = next(self._launch_ids)
        origin = origin or {}
        dispatch = trace_span("executor.dispatch", tracer)
        dispatch.set(launchId=launch_id, **origin)
        with dispatch:
            bufs_dev = pipeline(cols, n_docs, params)  # async dispatch
        launch = self.device_timeline.dispatched(
            launch_id, bufs_dev, traced=dispatch.tracer is not None)
        if cache_key is not None:
            # cache the dispatched buffer itself (immutable): the repeat
            # query fetches it again without gather/dispatch/kernel.
            # Cohort members never insert — their buffer interleaves the
            # whole cohort's rows
            self._partials_put(cache_key, bufs_dev, layout)
        return self._make_resolve(
            bufs_dev, layout, flight,
            attrs={"launchId": launch_id, "cohortSize": 1,
                   "cohortPadded": 1, **origin}, launch=launch)

    def _cohort_launch(self, entry, cols, n_docs, members, lkey, tracer=None,
                       flight=None, origin=None):
        """Leader side of a coalesced cohort: stack every member's params
        along a leading axis and dispatch ONE vmapped launch; the shared
        resolve() fetches ONE packed buffer for the whole cohort (each
        member then slices its row — engine/inflight.py _Cohort)."""
        if len(members) == 1:
            # window opened but nobody joined: the already-compiled solo
            # pipeline serves it — a size-1 vmapped variant would be a
            # whole extra compile of the template for nothing
            layout = entry["layouts"][lkey]
            base = self._solo_launch(entry, cols, n_docs, members[0], layout,
                                     tracer, flight=flight, origin=origin)

            def alone():
                return {k: v[None] for k, v in base().items()}

            alone.stamp = base.stamp
            return alone
        launch_id = next(self._launch_ids)
        pipeline_v, inner_v = self._cohort_pipeline(entry)
        # the dense form reads no zone map: a block-skip entry's cohort and
        # its dense twin's are one program over the same operands
        entry = entry["dense"] or entry
        cols = {k: v for k, v in cols.items()
                if not k.startswith((bs_ops.ZLO, bs_ops.ZHI))}
        # pad the cohort to the next power of two (repeating the last
        # member's params): jit re-specializes per stack size, and ragged
        # cohort sizes under churn would compile up to max_cohort variants
        # of the whole pipeline — pow2 bucketing caps that at
        # log2(max_cohort) for at most 2x padded lanes, and member slices
        # (idx < real size) never see the padding
        n_real = len(members)
        n_pad = 1 << (n_real - 1).bit_length()
        with trace_span("executor.stack", tracer):
            padded = list(members) + [members[-1]] * (n_pad - n_real)
            pstack = {k: jnp.stack([m[k] for m in padded])
                      for k in members[0]}
            # literal-free templates have EMPTY params; vmap needs at
            # least one batched leaf, so every cohort rides a synthetic
            # member index (templates index params by name — an extra key
            # is never read)
            pstack["__member__"] = jnp.arange(n_pad, dtype=jnp.int32)
            ck = (lkey, n_pad)
            layout = entry["cohort_layouts"].get(ck)
            if layout is None:
                layout = _out_layout(
                    jax.eval_shape(inner_v, cols, n_docs, pstack))
                with self._lock:
                    entry["cohort_layouts"][ck] = layout
        origin = origin or {}
        dispatch = trace_span("executor.dispatch", tracer)
        dispatch.set(launchId=launch_id, **origin)
        with dispatch:
            bufs_dev = pipeline_v(cols, n_docs, pstack)  # async dispatch
        launch = self.device_timeline.dispatched(
            launch_id, bufs_dev, traced=dispatch.tracer is not None)
        return self._make_resolve(
            bufs_dev, layout, flight,
            attrs={"launchId": launch_id, "cohortSize": n_real,
                   "cohortPadded": n_pad, **origin}, launch=launch)

    def _cohort_pipeline(self, entry):
        """(jitted packed pipeline, inner fn) over params carrying a
        leading cohort axis. Single device: vmap the solo inner (finalize
        included) over the stacked params. Mesh: one shard_map whose body
        vmaps pipeline + combine (+ finalize) per member —
        parallel/mesh.py shard_pipeline(cohort=True). jit re-specializes
        per cohort size; the coalescer's max_cohort bounds that."""
        entry = entry["dense"] or entry
        with self._lock:
            cached = entry["cohort"]
        if cached is not None:
            return cached
        raw, agg_tpls, final = entry["raw"], entry["agg_tpls"], entry["final"]
        post_fns = self._post_chain(
            entry["template"], agg_tpls, final, entry["trim"])
        post = None
        if post_fns:
            def post(outs, p, _pfs=post_fns):
                for pf in _pfs:
                    outs = pf(outs, p)
                return outs
        if self.mesh is not None:
            from pinot_tpu.parallel.mesh import shard_pipeline

            inner_v = shard_pipeline(raw, self.mesh, cohort=True, post=post)
        else:
            # a trim over thousands of entries runs member by member: the
            # TPU's compiler takes minutes over the batched form of its
            # multi-operand 64-bit sort (two members of 4,096 entries:
            # 398 s where one member takes 7, compiled for a described
            # v5e), and the loop's body is the one-member sort
            by_member = post is not None and entry["trim"] is not None \
                and _table_len(entry["template"]) > dr_ops.VMAP_SORT_MAX
            one = raw
            if post is not None and not by_member:
                def one(cols, n_docs, p, _raw=raw, _post=post):
                    return _post(_raw(cols, n_docs, p), p)

            def inner_v(cols, n_docs, pstack, _one=one):
                def pinot_cohort_member(p):
                    return _one(cols, n_docs, p)

                outs = jax.vmap(pinot_cohort_member)(pstack)
                if by_member:
                    outs = jax.lax.map(lambda op: post(*op), (outs, pstack))
                return outs

        def pinot_cohort_pipeline(cols, n_docs, pstack):
            return _pack_outs(inner_v(cols, n_docs, pstack))

        pipeline_v = jax.jit(pinot_cohort_pipeline)
        with self._lock:
            if entry["cohort"] is None:
                entry["cohort"] = (pipeline_v, inner_v)
            return entry["cohort"]

    @staticmethod
    def _needed_columns(tpl) -> set:
        out = set()

        def walk(t):
            if not isinstance(t, tuple):
                return
            if t[0] == "raw":
                out.add(t[1])
                return
            if t[0] == "dictval":
                out.add("dv::" + t[1])
                return
            if t[0] in ("eq_dict", "in_dict", "range_dict", "lut_dict", "mv_any"):
                out.add(t[1])
            for x in t[1:]:
                walk(x)

        walk(tpl)
        return out

    # ---- device outputs → canonical IntermediateResult -------------------
    def _to_intermediate(self, q, ctx: BatchContext, template, outs, aggs,
                         cache_hit: bool = False, adv_key=None,
                         adv_trim_keep=None):
        shape, _, group_cols, group_cards, agg_tpls, sorted_k, _final = template
        doc_count = int(outs["doc_count"])
        # mirror the host executor's stats accounting so responses are
        # backend-independent (host.py execute_segment) — HONEST under
        # pruning: entries count only alive segments' rows, and only the
        # gathered blocks' rows when the block-skip path ran
        n_alive = min(int(outs["n_alive"]), ctx.S) \
            if "n_alive" in outs else ctx.S
        entries_in_filter = 0
        if q.filter is not None:
            rows_filter = int(outs["rows_filter"]) if "rows_filter" in outs \
                else int(ctx.n_docs.sum())
            entries_in_filter = rows_filter * len(q.filter.columns())
        entries_post = sum(
            doc_count * len(aggspec.make_spec(a).args) for a in q.aggregations()
        )
        blocks_total = int(outs.get("blocks_total", 0))
        blocks_scanned = int(outs.get("blocks_scanned", 0))
        stats = ExecutionStats(
            num_docs_scanned=doc_count,
            num_entries_scanned_in_filter=entries_in_filter,
            num_entries_scanned_post_filter=entries_post,
            num_segments_processed=n_alive,
            num_segments_queried=ctx.S,
            num_segments_matched=int((outs["seg_matched"] > 0).sum()),
            num_segments_pruned=ctx.S - n_alive,
            num_blocks_pruned=max(0, blocks_total - blocks_scanned),
            # pruned segments still count toward totalDocs (reference
            # semantics)
            total_docs=int(ctx.n_docs.sum()),
        )

        if shape == "agg":
            partials = [
                self._scalar_partial(i, t, outs, ctx) for i, t in enumerate(agg_tpls)
            ]
            return IntermediateResult("aggregation", agg_partials=partials, stats=stats)

        if sorted_k and int(outs["n_groups_total"]) > sorted_k:
            # the capped table dropped groups: re-run on the host so device
            # truncation policy never shapes results (host applies its own
            # numGroupsLimit semantics)
            why = (f"{KEY_SPACES[shape]} group table overflow "
                   f"({int(outs['n_groups_total'])} > {sorted_k})")
            if shape == "groupby_narrow" and self.mesh is None:
                # the key space is full: remember it of this template on
                # this batch; the launch's fetch launches it again
                self._note_key_space(
                    (template[1], group_cols, agg_tpls,
                     self._batch_key(ctx.segments)), True)
                raise KeySpaceFull(why)
            if shape == "groupby_narrow":
                with self._lock:
                    self.groupby_narrow_overflows += 1
            raise DeviceUnsupported(why)
        opts = q.options_ci()
        # numGroupsLimit applies on the device path too (engine default or
        # per-query SET override): excess groups drop arbitrarily-but-
        # deterministically (gid order), like the reference's hash-order
        # drops, and the stats flag marks the result plan-dependent-partial
        limit = self._groups_limit(opts)
        trimmed = "trim_keys" in outs
        t_reduce = time.perf_counter()
        # plan-advisor group-count feedback: the template's OBSERVED
        # group count (trimmed tables report n_present_total — the real
        # present count, not the kept count — so an advised keep that
        # proved too tight registers as an overflow and the trim advice
        # stands down). Cache hits replay the original execution's
        # buffer and are not re-observed.
        if adv_key is not None and self.advisor is not None \
                and not cache_hit:
            if trimmed:
                obs_groups = int(outs["n_present_total"])
            elif sorted_k:
                obs_groups = int(outs["n_groups_total"])
            else:
                obs_groups = int((np.asarray(outs["gcount"]) > 0).sum())
            self.advisor.observe(adv_key, groups=obs_groups,
                                 trim_keep=adv_trim_keep)
        if trimmed:
            # on-device final reduce ran (ops/device_reduce.py): the
            # fetched table is already ordered + trimmed, keys packed in
            # trim_keys. If numGroupsLimit would have truncated the FULL
            # table, its present-order drop policy is irreproducible from
            # the ORDER-BY-trimmed rows — host fallback keeps the limit
            # semantics device-independent.
            if int(outs["n_present_total"]) > limit:
                raise DeviceUnsupported(
                    f"device-trimmed table under numGroupsLimit pressure "
                    f"({int(outs['n_present_total'])} > {limit})")
            present = np.arange(int(outs["trim_n"]))
            rem = np.asarray(outs["trim_keys"])[present].astype(np.int64)
        else:
            gcount = outs["gcount"]
            present = np.nonzero(gcount > 0)[0]
            if len(present) > limit:
                present = present[:limit]
                stats.num_groups_limit_reached = True
            # decode the combined key (dense: the gid itself; sorted: the
            # int64 key recorded per table slot) → per-column global ids
            # → values
            if sorted_k:
                rem = outs["skeys"][present].astype(np.int64)
            else:
                rem = present.copy()
        keys = []
        for card in reversed(group_cards[1:]):
            keys.append(rem % card)
            rem = rem // card
        keys.append(rem)
        keys.reverse()
        key_values = tuple(
            ctx.global_dict(col).take(k) for col, k in zip(group_cols, keys)
        )
        if q.distinct:
            return IntermediateResult(
                "distinct", group_keys=key_values, stats=stats)
        partials = [
            self._group_partial(i, t, outs, ctx, present) for i, t in enumerate(agg_tpls)
        ]
        if trimmed and not cache_hit:
            # host-side completion of the device reduce: key decode +
            # partial assembly over the KEPT rows only (the host reduce
            # this replaces walked the full (G,) table). Cache hits
            # re-read a buffer whose trim ran on the ORIGINAL execution —
            # counting them would overstate in-kernel reduces by ~the
            # cache hit rate.
            dt_ms = (time.perf_counter() - t_reduce) * 1e3
            with self._lock:
                self.device_reduce_queries += 1
                self.device_reduce_ms_total += dt_ms
            self.metrics.time_ms("deviceReduceMs", dt_ms)
        return IntermediateResult(
            "group_by", group_keys=key_values, agg_partials=partials, stats=stats
        )

    def _scalar_partial(self, i, tpl, outs, ctx):
        name, argt, extra = tpl
        k = f"a{i}"
        if name == "count":
            return {"count": np.array([outs["doc_count"]], dtype=np.int64)}
        if name == "sum":
            return {"sum": np.asarray([outs[f"{k}_sum"]], dtype=np.float64)}
        if name == "avg":
            return {
                "sum": np.asarray([outs[f"{k}_sum"]], dtype=np.float64),
                "count": np.array([outs["doc_count"]], dtype=np.int64),
            }
        if name == "min":
            return {"min": np.asarray([outs[f"{k}_min"]], dtype=np.float64)}
        if name == "max":
            return {"max": np.asarray([outs[f"{k}_max"]], dtype=np.float64)}
        if name == "minmaxrange":
            return {
                "min": np.asarray([outs[f"{k}_min"]], dtype=np.float64),
                "max": np.asarray([outs[f"{k}_max"]], dtype=np.float64),
            }
        if name == "distinctcount":
            if f"{k}_cnt" in outs:  # terminal: popcount came from device
                return {"cnt": np.asarray([outs[f"{k}_cnt"]], dtype=np.int64)}
            pres = outs[f"{k}_pres"]
            vals = ctx.global_dict(argt).take(np.nonzero(pres > 0)[0])
            s = np.empty(1, dtype=object)
            s[0] = set(np.asarray(vals).tolist())
            return {"sets": s}
        if name in ("distinctcounthll", "hllmerge"):
            if f"{k}_est" in outs:  # terminal: estimated on device
                return {"est": np.asarray([outs[f"{k}_est"]], dtype=np.int64)}
            return {"regs": outs[f"{k}_regs"].reshape(1, -1)}
        if name in ("firstwithtime", "lastwithtime"):
            return _with_time_partial(name, outs, k, None)
        raise AssertionError(name)

    def _group_partial(self, i, tpl, outs, ctx, present):
        name, argt, extra = tpl
        k = f"a{i}"
        if name == "count":
            return {"count": outs["gcount"][present].astype(np.int64)}
        if name == "sum":
            return {"sum": outs[f"{k}_sum"][present].astype(np.float64)}
        if name == "avg":
            return {
                "sum": outs[f"{k}_sum"][present].astype(np.float64),
                "count": outs["gcount"][present].astype(np.int64),
            }
        if name == "min":
            return {"min": outs[f"{k}_min"][present].astype(np.float64)}
        if name == "max":
            return {"max": outs[f"{k}_max"][present].astype(np.float64)}
        if name == "minmaxrange":
            return {
                "min": outs[f"{k}_min"][present].astype(np.float64),
                "max": outs[f"{k}_max"][present].astype(np.float64),
            }
        if name == "distinctcount":
            if f"{k}_cnt" in outs:  # terminal: popcounts came from device
                return {"cnt": outs[f"{k}_cnt"][present].astype(np.int64)}
            pres = outs[f"{k}_pres"][present]
            gvals = np.asarray(ctx.global_dict(argt).values)
            sets = np.empty(len(present), dtype=object)
            for j in range(len(present)):
                sets[j] = set(gvals[np.nonzero(pres[j] > 0)[0]].tolist())
            return {"sets": sets}
        if name in ("distinctcounthll", "hllmerge"):
            if f"{k}_est" in outs:  # terminal: estimated on device
                return {"est": outs[f"{k}_est"][present].astype(np.int64)}
            return {"regs": outs[f"{k}_regs"][present]}
        if name in ("firstwithtime", "lastwithtime"):
            return _with_time_partial(name, outs, k, present)
        raise AssertionError(name)
