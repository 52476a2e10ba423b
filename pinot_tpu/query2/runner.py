"""Multi-stage stage runner: execute a MultiStagePlan.

Two stages over the existing partial-result machinery:

- **Stage 1** — leaf scans. Embedded/server-local execution rides the same
  SegmentEvaluator the host executor uses (pushdown filters lowered
  through the single-stage FilterNode path, upsert validDocIds honored,
  consuming segments scanned through their mutable reader), producing
  columnar row sets per table. The broker gathers the same row sets by
  scatter-gathering plain single-stage SELECT leaf queries instead
  (broker/broker.py) — stage 1 IS the existing engine either way.
- **Join** — packed int64 key codes (both sides factorized into one shared
  code space, multi-column keys combined with the radix cartesian
  arithmetic) drive the device hash-join kernels (ops/join.py): sort the
  build side, probe with binary search, expand matched pairs under a
  static bound. BROADCAST replicates the sorted build table across the
  mesh and shards the probe axis; SHUFFLE partitions both sides by key
  radix with one bucket per device, all inside one shard_map. A host
  (numpy) mirror covers engines without a device executor — the
  differential reference.
- **Windows** — ops/window.py's one-sort segmented-scan kernel, with a
  per-partition numpy mirror as the host path.
- **Stage 2** — the joined row set feeds the SAME aggregation specs,
  factorize/merge, HAVING, ORDER BY and finalize code the single-stage
  engine uses (engine/aggspec.py + engine/reduce.py): the stage-2
  QueryContext is a plain QueryContext over the canonical joined
  namespace.

LEFT JOIN misses fill build columns with the column TYPE's default ("" /
0), matching the LOOKUP transform's miss semantics — the broadcast join
is a strict superset of LOOKUP and tests pin the two bit-identical.
"""

from __future__ import annotations

import os
import time
from typing import Optional

import numpy as np

from pinot_tpu.engine import aggspec
from pinot_tpu.engine.host import (
    SegmentEvaluator,
    factorize_multi,
    like_to_regex,
)
from pinot_tpu.engine.reduce import finalize, merge_intermediates
from pinot_tpu.engine.result import ExecutionStats, IntermediateResult
from pinot_tpu.ops.transform import get_function
from pinot_tpu.query.context import (
    Expression,
    FilterNode,
    FilterNodeType,
    PredicateType,
)
from pinot_tpu.query.optimizer import optimize_filter
from pinot_tpu.query2.logical import (
    BROADCAST_MAX_BUILD_ROWS,
    MultiStagePlan,
    compile_plan,
)
from pinot_tpu.sql.compiler import _to_filter
from pinot_tpu.sql.parser import SqlAnalysisError

MAX_STAGE1_ROWS = int(os.environ.get("PINOT_TPU_MAX_JOIN_ROWS", 4_000_000))
MAX_JOIN_PAIRS = int(os.environ.get("PINOT_TPU_MAX_JOIN_PAIRS", 16_000_000))

# combined key-code space guard: the cartesian pack must stay in int64
_MAX_KEYSPACE = 1 << 62


# ---------------------------------------------------------------------------
# expression evaluation over a columnar row set
# ---------------------------------------------------------------------------


def _eval(cols: dict, expr: Expression, env: Optional[dict] = None,
          n: Optional[int] = None):
    """Evaluate an expression over canonical joined columns. ``env`` maps
    precomputed expressions (window results) to value arrays."""
    if env and expr in env:
        return env[expr]
    if expr.is_literal:
        return np.asarray(expr.value)
    if expr.is_identifier:
        if expr.name not in cols:
            raise KeyError(f"column {expr.name!r} not in joined row set")
        return cols[expr.name]
    if expr.name == "__window__":
        raise SqlAnalysisError(
            "window expression evaluated outside its stage")
    fn = get_function(expr.name)
    if expr.name == "cast":
        return fn.np_fn(_eval(cols, expr.args[0], env, n),
                        expr.args[1].value)
    args = [_eval(cols, a, env, n) for a in expr.args]
    return fn.np_fn(*args)


def _eval_rows(cols: dict, expr: Expression, env: Optional[dict],
               n: int) -> np.ndarray:
    v = np.asarray(_eval(cols, expr, env, n))
    if v.ndim == 0:
        return np.broadcast_to(v, (n,))
    return v


def _predicate_mask(v: np.ndarray, p) -> np.ndarray:
    import re as _re

    t = p.type
    if t is PredicateType.EQ:
        return v == _coerce(p.value, v)
    if t is PredicateType.NOT_EQ:
        return v != _coerce(p.value, v)
    if t is PredicateType.IN:
        return np.isin(v, _coerce_list(p.values, v))
    if t is PredicateType.NOT_IN:
        return ~np.isin(v, _coerce_list(p.values, v))
    if t is PredicateType.RANGE:
        m = np.ones(len(v), dtype=bool)
        if p.lower is not None:
            lo = _coerce(p.lower, v)
            m &= (v >= lo) if p.lower_inclusive else (v > lo)
        if p.upper is not None:
            hi = _coerce(p.upper, v)
            m &= (v <= hi) if p.upper_inclusive else (v < hi)
        return m
    if t in (PredicateType.LIKE, PredicateType.REGEXP_LIKE):
        pat = p.value if t is not PredicateType.LIKE \
            else like_to_regex(p.value)
        rx = _re.compile(pat)
        search = rx.search if t is not PredicateType.LIKE else rx.match
        return np.fromiter((bool(search(s)) for s in v.astype(str)),
                           dtype=bool, count=len(v))
    raise SqlAnalysisError(f"predicate {t.value} is not supported on "
                           f"joined rows")


def _coerce(value, v: np.ndarray):
    return str(value) if v.dtype.kind in ("U", "S") else value


def _coerce_list(values, v: np.ndarray):
    if v.dtype.kind in ("U", "S"):
        return np.asarray([str(x) for x in values])
    return np.asarray(list(values))


def _filter_mask(cols: dict, f: FilterNode, env, n: int) -> np.ndarray:
    t = f.type
    if t is FilterNodeType.CONSTANT_TRUE:
        return np.ones(n, dtype=bool)
    if t is FilterNodeType.CONSTANT_FALSE:
        return np.zeros(n, dtype=bool)
    if t is FilterNodeType.AND:
        m = _filter_mask(cols, f.children[0], env, n)
        for c in f.children[1:]:
            m = m & _filter_mask(cols, c, env, n)
        return m
    if t is FilterNodeType.OR:
        m = _filter_mask(cols, f.children[0], env, n)
        for c in f.children[1:]:
            m = m | _filter_mask(cols, c, env, n)
        return m
    if t is FilterNodeType.NOT:
        return ~_filter_mask(cols, f.children[0], env, n)
    return _predicate_mask(_eval_rows(cols, f.predicate.lhs, env, n),
                           f.predicate)


def _expr_mask(cols: dict, expr: Expression, env, n: int) -> np.ndarray:
    """Boolean expression → row mask, through the single-stage filter
    lowering so predicate semantics are identical to stage 1."""
    return _filter_mask(cols, optimize_filter(_to_filter(expr)), env, n)


# ---------------------------------------------------------------------------
# stage 1: local leaf scans (embedded / server-local execution)
# ---------------------------------------------------------------------------


def _tdm_for(engine, table: str):
    for key in (table, f"{table}_OFFLINE", f"{table}_REALTIME"):
        tdm = engine.tables.get(key)
        if tdm is not None:
            return tdm
    raise KeyError(f"table {table!r} not found")


def scan_local_rows(engine, table: str, filter_expr: Optional[Expression],
                    need_cols: tuple, stats: ExecutionStats,
                    segments: Optional[list] = None) -> dict:
    """Matched rows of one table over all locally hosted segments →
    {bare column -> np array}. Pushdown filters lower through the SAME
    FilterNode path as single-stage queries; upsert validDocIds and
    consuming (mutable) segments behave exactly like the host executor.
    ``segments`` restricts the scan to the named segments — the
    distributed exchange ships each worker its routed slice so two
    replicas of one segment never both scan it."""
    seg_filter = None if segments is None else set(segments)
    tdm = _tdm_for(engine, table)
    hosted = tdm.acquire()
    try:
        if not hosted:
            raise ValueError(f"table {table!r} has no segments")
        fnode = None if filter_expr is None \
            else optimize_filter(_to_filter(filter_expr))
        parts: dict[str, list] = {c: [] for c in need_cols}
        total = 0
        for seg in hosted:
            if seg_filter is not None and \
                    getattr(seg, "name", None) not in seg_filter:
                continue
            if getattr(seg, "is_cold", False):
                # cold tier (server/tiering.py): planes live only in the
                # deep store — honest in-flight partial, the touch
                # schedules the async hydration
                stats.num_segments_queried += 1
                stats.num_segments_cold += 1
                stats.total_docs += seg.n_docs
                touch = getattr(seg, "touch", None)
                if touch is not None:
                    touch()
                continue
            ev = SegmentEvaluator(
                seg, lookup_resolver=getattr(engine.host, "lookup_resolver",
                                             None))
            vd = getattr(seg, "valid_docs_mask", None)
            if vd is not None:
                vd = np.asarray(vd)[: ev.n].copy()
            elif hasattr(seg, "valid_docs"):
                m = seg.valid_docs(ev.n)
                vd = None if m is None else np.asarray(m).copy()
            mask = ev.filter_mask(fnode) if fnode is not None \
                else np.ones(ev.n, dtype=bool)
            if vd is not None:
                mask = mask & vd
            doc_idx = np.nonzero(mask)[0]
            stats.num_segments_queried += 1
            stats.num_segments_processed += 1
            stats.num_docs_scanned += int(len(doc_idx))
            stats.num_entries_scanned_in_filter += ev.entries_scanned_in_filter
            stats.num_entries_scanned_post_filter += \
                len(doc_idx) * len(need_cols)
            stats.total_docs += ev.n
            if len(doc_idx):
                stats.num_segments_matched += 1
            total += len(doc_idx)
            if total > MAX_STAGE1_ROWS:
                raise SqlAnalysisError(
                    f"stage-1 row set for table {table!r} exceeds "
                    f"{MAX_STAGE1_ROWS} rows; add a more selective filter "
                    f"(PINOT_TPU_MAX_JOIN_ROWS overrides)")
            for c in need_cols:
                parts[c].append(
                    np.asarray(ev.eval(Expression.identifier(c), doc_idx)))
        return {
            c: (np.concatenate(parts[c]) if parts[c]
                else np.empty(0)) for c in need_cols
        }
    finally:
        tdm.release(hosted)


def needed_columns(plan: MultiStagePlan) -> dict:
    """alias → tuple of bare columns the post-scan stages reference."""
    names: set[str] = set()
    q = plan.stage2
    for e in q.select_expressions:
        names |= e.columns()
    for g in q.group_by:
        names |= g.columns()
    if q.having is not None:
        names |= q.having.columns()
    for ob in q.order_by:
        names |= ob.expression.columns()
    for j in plan.joins:
        for k in j.left_keys + j.right_keys:
            names |= k.columns()
        if j.residual is not None:
            names |= j.residual.columns()
    if plan.post_filter is not None:
        names |= plan.post_filter.columns()
    for w in plan.windows:
        for e in w.args + w.partition_by + tuple(e for e, _ in w.order_by):
            names |= e.columns()
    out: dict[str, list] = {s.alias: [] for s in plan.sources}
    for name in sorted(names):
        if "." not in name:
            continue
        alias, col = name.split(".", 1)
        if alias in out and col not in out[alias]:
            out[alias].append(col)
    # a table joined purely for existence still needs its key columns,
    # which the loop above covers; guarantee at least one column per
    # source so empty projections keep a row count
    for s in plan.sources:
        if not out[s.alias]:
            out[s.alias].append(s.columns[0])
    return {a: tuple(c) for a, c in out.items()}


# ---------------------------------------------------------------------------
# join execution
# ---------------------------------------------------------------------------


def _factorize_codes(left_vals: list, right_vals: list):
    """Shared-code-space factorization: per key column, both sides'
    values unify into one np.unique code table; multi-column keys combine
    with the radix cartesian arithmetic (ops/radix_groupby.pack_keys'
    scheme, host-side). Returns (codes_l, codes_r, impossible):
    ``impossible`` is True when a key pair mixes string and numeric
    operands — strict typing means such an equi-key can never match (the
    sqlite oracle's int = text is always false), so the caller skips the
    match phase entirely."""
    n_l = len(left_vals[0]) if left_vals else 0
    n_r = len(right_vals[0]) if right_vals else 0
    codes_l = np.zeros(n_l, dtype=np.int64)
    codes_r = np.zeros(n_r, dtype=np.int64)
    space = 1
    for lv, rv in zip(left_vals, right_vals):
        lv, rv = np.asarray(lv), np.asarray(rv)
        if (lv.dtype.kind in ("U", "S", "O")) != \
                (rv.dtype.kind in ("U", "S", "O")):
            return codes_l, codes_r, True
        u, inv = np.unique(np.concatenate([lv, rv]), return_inverse=True)
        c = max(len(u), 1)
        if space > _MAX_KEYSPACE // c:
            raise SqlAnalysisError(
                "join key space too wide to pack into int64; reduce the "
                "number of join key columns")
        space *= c
        codes_l = codes_l * c + inv[:n_l]
        codes_r = codes_r * c + inv[n_l:]
    return codes_l, codes_r, False


def _match_pairs_host(probe: np.ndarray, build: np.ndarray):
    """numpy mirror of the device sort/probe/expand pipeline."""
    from pinot_tpu.engine.host import concat_ranges

    order = np.argsort(build, kind="stable")
    sk = build[order]
    lo = np.searchsorted(sk, probe, side="left")
    hi = np.searchsorted(sk, probe, side="right")
    counts = hi - lo
    total = int(counts.sum())
    if total > MAX_JOIN_PAIRS:
        raise SqlAnalysisError(
            f"join produces more than {MAX_JOIN_PAIRS} matched pairs")
    probe_idx = np.repeat(np.arange(len(probe), dtype=np.int64), counts)
    build_pos = concat_ranges(lo.astype(np.int64), counts.astype(np.int64))
    return probe_idx, order[build_pos]


def _match_pairs_device(probe: np.ndarray, build: np.ndarray, mesh,
                        strategy: str):
    """Device pipeline: ops/join.py kernels, solo or across the mesh."""
    import jax.numpy as jnp

    from pinot_tpu.ops import join as join_ops

    if mesh is not None and strategy == "SHUFFLE":
        return _match_pairs_mesh_shuffle(probe, build, mesh)
    unique_build = len(np.unique(build)) == len(build)
    if mesh is not None:
        return _match_pairs_mesh_broadcast(probe, build, mesh,
                                           unique_build)
    jp = jnp.asarray(probe)
    sk, perm = join_ops.sort_build(jnp.asarray(build))
    if unique_build:
        # dim-table pk probe (the LOOKUP shape): 1:1, no pair expansion
        found, build_row = join_ops.probe_unique(sk, perm, jp)
        found = np.asarray(found)
        build_row = np.asarray(build_row)
        probe_idx = np.nonzero(found)[0]
        return probe_idx, build_row[probe_idx]
    lo, counts = join_ops.probe_ranges(sk, jp)
    total = int(np.asarray(counts).sum())
    if total > MAX_JOIN_PAIRS:
        raise SqlAnalysisError(
            f"join produces more than {MAX_JOIN_PAIRS} matched pairs")
    if total == 0:
        return (np.empty(0, dtype=np.int64), np.empty(0, dtype=np.int64))
    bound = join_ops.next_pow2(total)
    pr, bp, valid = join_ops.expand_pairs(
        lo.astype(jnp.int64), counts.astype(jnp.int64), bound)
    pr, bp, valid = np.asarray(pr), np.asarray(bp), np.asarray(valid)
    perm = np.asarray(perm)
    return pr[valid], perm[bp[valid]]


def _match_pairs_mesh_broadcast(probe: np.ndarray, build: np.ndarray, mesh,
                                unique_build: bool = False):
    """BROADCAST on the mesh: replicated sorted build table, probe axis
    sharded inside one shard_map (ops/join.py mesh_probe_ranges;
    mesh_probe_unique for the 1:1 dim-table pk shape)."""
    import jax.numpy as jnp

    from pinot_tpu.ops import join as join_ops

    D = mesh.devices.size
    n = len(probe)
    pad = (-n) % D
    probe_p = np.concatenate(
        [probe, np.full(pad, join_ops.PROBE_PAD, dtype=np.int64)]) \
        if pad else probe
    sk, perm = join_ops.sort_build(jnp.asarray(build))
    if unique_build:
        found, build_row = join_ops.mesh_probe_unique(
            mesh, sk, perm, jnp.asarray(probe_p))
        found = np.asarray(found)[:n]
        build_row = np.asarray(build_row)[:n]
        probe_idx = np.nonzero(found)[0]
        return probe_idx, build_row[probe_idx]
    lo, counts = join_ops.mesh_probe_ranges(mesh, sk, jnp.asarray(probe_p))
    lo = np.asarray(lo)[:n].astype(np.int64)
    counts = np.asarray(counts)[:n].astype(np.int64)
    total = int(counts.sum())
    if total > MAX_JOIN_PAIRS:
        raise SqlAnalysisError(
            f"join produces more than {MAX_JOIN_PAIRS} matched pairs")
    if total == 0:
        return (np.empty(0, dtype=np.int64), np.empty(0, dtype=np.int64))
    bound = join_ops.next_pow2(total)
    pr, bp, valid = join_ops.expand_pairs(
        jnp.asarray(lo), jnp.asarray(counts), bound)
    pr, bp, valid = np.asarray(pr), np.asarray(bp), np.asarray(valid)
    perm = np.asarray(perm)
    return pr[valid], perm[bp[valid]]


def _match_pairs_mesh_shuffle(probe: np.ndarray, build: np.ndarray, mesh):
    """SHUFFLE on the mesh: both sides partitioned by key radix, one
    bucket per device, every bucket's sort+probe in ONE shard_map; pair
    expansion rides a vmapped static-bound kernel per bucket."""
    import jax.numpy as jnp

    from pinot_tpu.ops import join as join_ops

    D = mesh.devices.size
    bkeys, brows = join_ops.partition_by_key(build, D, join_ops.BUILD_PAD)
    pkeys, prows = join_ops.partition_by_key(probe, D, join_ops.PROBE_PAD)
    lo, counts, perm = join_ops.mesh_bucket_ranges(
        mesh, jnp.asarray(bkeys), jnp.asarray(pkeys))
    counts_np = np.asarray(counts).astype(np.int64)
    total = int(counts_np.sum())
    if total > MAX_JOIN_PAIRS:
        raise SqlAnalysisError(
            f"join produces more than {MAX_JOIN_PAIRS} matched pairs")
    if total == 0:
        return (np.empty(0, dtype=np.int64), np.empty(0, dtype=np.int64))
    bound = join_ops.next_pow2(int(counts_np.sum(axis=1).max()))
    pr, bp, valid = join_ops.expand_pairs_buckets(
        jnp.asarray(np.asarray(lo).astype(np.int64)),
        jnp.asarray(counts_np), bound)
    pr, bp, valid = np.asarray(pr), np.asarray(bp), np.asarray(valid)
    perm = np.asarray(perm)
    out_probe, out_build = [], []
    for d in range(D):
        v = valid[d]
        if not v.any():
            continue
        local_pr = pr[d][v]
        local_bp = bp[d][v]
        out_probe.append(prows[d][local_pr])
        out_build.append(brows[d][perm[d][local_bp]])
    return (np.concatenate(out_probe), np.concatenate(out_build))


def _default_fill(arr: np.ndarray, k: int) -> np.ndarray:
    """LEFT-join miss fill: the build column TYPE's default — identical to
    the LOOKUP transform's miss semantics ("" for strings, 0 for
    numbers)."""
    kind = arr.dtype.kind
    if kind in ("U", "S"):
        return np.zeros(k, dtype=arr.dtype)  # empty strings
    if kind == "O":
        return np.full(k, "", dtype=object)
    if kind == "b":
        return np.zeros(k, dtype=bool)
    return np.zeros(k, dtype=arr.dtype)


def execute_join_step(left_cols: dict, n_left: int, step, build_cols: dict,
                      device, mesh, strategy: str):
    """One join: match, expand, gather, residual-filter, LEFT-append.
    Returns (joined cols dict, new row count)."""
    lkeys = [_eval_rows(left_cols, k, None, n_left) for k in step.left_keys]
    n_build = len(next(iter(build_cols.values()))) if build_cols else 0
    rkeys = [_eval_rows(build_cols, k, None, n_build)
             for k in step.right_keys]
    pc, bc, impossible = _factorize_codes(lkeys, rkeys)

    if n_left == 0 or n_build == 0 or impossible:
        probe_idx = np.empty(0, dtype=np.int64)
        build_idx = np.empty(0, dtype=np.int64)
    elif device is not None:
        probe_idx, build_idx = _match_pairs_device(pc, bc, mesh, strategy)
    else:
        probe_idx, build_idx = _match_pairs_host(pc, bc)

    joined = {name: np.asarray(arr)[probe_idx]
              for name, arr in left_cols.items()}
    for name, arr in build_cols.items():
        joined[name] = np.asarray(arr)[build_idx]

    if step.residual is not None and len(probe_idx):
        m = _expr_mask(joined, step.residual, None, len(probe_idx))
        probe_idx = probe_idx[m]
        joined = {k: v[m] for k, v in joined.items()}

    n = len(probe_idx)
    if step.kind == "LEFT":
        matched = np.zeros(n_left, dtype=bool)
        matched[probe_idx] = True
        miss = np.nonzero(~matched)[0]
        if len(miss):
            for name, arr in left_cols.items():
                joined[name] = np.concatenate(
                    [joined[name], np.asarray(arr)[miss]])
            for name, arr in build_cols.items():
                joined[name] = np.concatenate(
                    [joined[name], _default_fill(np.asarray(arr),
                                                 len(miss))])
            n += len(miss)
    return joined, n


# ---------------------------------------------------------------------------
# window execution
# ---------------------------------------------------------------------------


def _partition_codes(cols: dict, exprs: tuple, n: int) -> np.ndarray:
    if not exprs:
        return np.zeros(n, dtype=np.int64)
    vals = [_eval_rows(cols, e, None, n) for e in exprs]
    _, ginv = factorize_multi(vals)
    return ginv.astype(np.int64)


def _order_codes(cols: dict, order_by: tuple, n: int) -> np.ndarray:
    """Dense lexicographic rank codes over (expr, asc) keys: peer rows
    (equal tuples) share a code; descending keys negate their per-column
    rank so one combined int64 preserves the full ordering."""
    if not order_by:
        return np.zeros(n, dtype=np.int64)
    keys = []
    for e, asc in order_by:
        v = _eval_rows(cols, e, None, n)
        u, inv = np.unique(np.asarray(v), return_inverse=True)
        code = inv.astype(np.int64)
        if not asc:
            code = (len(u) - 1) - code
        keys.append(code)
    # combined dense rank via one more factorize pass (no overflow: the
    # pairwise-chained combine re-densifies at every step)
    combined = keys[0]
    for c in keys[1:]:
        u, inv = np.unique(combined * (c.max() + 1 if len(c) else 1) + c,
                           return_inverse=True)
        combined = inv.astype(np.int64)
    return combined


def _windows_host(part: np.ndarray, order: np.ndarray, specs: list,
                  values: list, n: int) -> list:
    """Per-partition numpy mirror of ops/window.window_eval (engines
    without a device executor — the differential reference)."""
    sorter = np.lexsort((np.arange(n), order, part))
    p, o = part[sorter], order[sorter]
    bounds = np.nonzero(np.concatenate(
        [[True], p[1:] != p[:-1]]))[0].tolist() + [n]
    outs = [np.zeros(n, dtype=np.int64 if fn in
                     ("row_number", "rank", "dense_rank", "count")
                     else np.float64) for fn, _ in specs]
    for s, e in zip(bounds[:-1], bounds[1:]):
        po = o[s:e]
        peer_start = np.concatenate([[True], po[1:] != po[:-1]])
        peer_id = np.cumsum(peer_start) - 1
        rn = np.arange(1, e - s + 1, dtype=np.int64)
        first_of_peer = np.nonzero(peer_start)[0]
        last_of_peer = np.concatenate([first_of_peer[1:] - 1, [e - s - 1]])
        for oi, (fn, vi) in enumerate(specs):
            if fn == "row_number":
                res = rn
            elif fn == "rank":
                res = rn[first_of_peer][peer_id]
            elif fn == "dense_rank":
                res = peer_id + 1
            elif fn == "count":
                res = rn[last_of_peer][peer_id]
            else:
                v = values[vi][sorter][s:e].astype(np.float64)
                if fn == "sum":
                    run = np.cumsum(v)
                elif fn == "avg":
                    run = np.cumsum(v)
                elif fn == "min":
                    run = np.minimum.accumulate(v)
                else:
                    run = np.maximum.accumulate(v)
                res = run[last_of_peer][peer_id]
                if fn == "avg":
                    res = res / rn[last_of_peer][peer_id]
            outs[oi][sorter[s:e]] = res
    return outs


def apply_windows(cols: dict, windows: tuple, n: int, device) -> dict:
    """Compute every WindowSpec → {window Expression: value array}.
    Specs sharing a (PARTITION BY, ORDER BY) pair share one sort."""
    env: dict = {}
    groups: dict = {}
    for w in windows:
        groups.setdefault((w.partition_by, w.order_by), []).append(w)
    for (part_by, order_by), ws in groups.items():
        part = _partition_codes(cols, part_by, n)
        order = _order_codes(cols, order_by, n)
        values: list = []
        val_index: dict = {}
        specs = []
        for w in ws:
            if w.args:
                key = w.args[0]
                if key not in val_index:
                    val_index[key] = len(values)
                    values.append(
                        _eval_rows(cols, key, None, n).astype(np.float64))
                vi = val_index[key]
            else:
                # COUNT(*) / rank family need no operand; COUNT rides the
                # row counter inside the kernel
                vi = -1
            specs.append((w.fn, vi))
        if n == 0:
            for w, (fn, _) in zip(ws, specs):
                dt = np.int64 if fn in ("row_number", "rank", "dense_rank",
                                        "count") else np.float64
                env[w.expr] = np.empty(0, dtype=dt)
            continue
        if device is not None:
            import jax.numpy as jnp

            from pinot_tpu.ops import window as window_ops

            pp, oo, rr, vv = window_ops.pad_inputs(
                part, order, np.arange(n, dtype=np.int64), tuple(values))
            outs = window_ops.window_eval(
                jnp.asarray(pp), jnp.asarray(oo), jnp.asarray(rr),
                tuple(jnp.asarray(v) for v in vv), tuple(specs))
            outs = [np.asarray(o)[:n] for o in outs]
        else:
            outs = _windows_host(part, order, specs, values, n)
        for w, out in zip(ws, outs):
            env[w.expr] = out
    return env


# ---------------------------------------------------------------------------
# stage 2: aggregate / having / order / finalize (engine/reduce.py reuse)
# ---------------------------------------------------------------------------


def _pallas_groupby_partials(aggs, specs, cols, env, ginv, n_groups: int,
                             n: int, device) -> dict:
    """Route COUNT + integer SUM/AVG stage-2 group partials through the
    PR-14 Pallas tiled local-accumulate scatter (ops/pallas_scatter.py
    plane_group_sums), mirroring device.py's ``_try_mm_groupby``
    channel-planning: each eligible agg contributes byte-plane bf16
    channels, the ones channel carries the per-group count, and
    ``recombine_int`` reassembles EXACT int64 sums (converted to the
    canonical float64 ``{"sum"}`` partial — exact for in-range ints, so
    results stay bit-identical to the host scatter). Float sums keep the
    host path: f32 plane recombination can round differently from the
    float64 ``np.add.at`` accumulator and stage-2 parity is pinned
    bit-exact. Returns {agg index: partial dict}; {} when the tier is
    off or out of regime, and the caller falls back per-agg."""
    mode = device._resolve_pallas({}) if device is not None else "off"
    if mode == "off" or n == 0 or n_groups == 0:
        return {}
    try:
        import jax.numpy as jnp

        from pinot_tpu.ops import groupby_mm as mm
        from pinot_tpu.ops import pallas_scatter as ps
    except Exception:  # noqa: BLE001 — tier is an optimization, not a dep
        return {}

    count_idx = [i for i, s in enumerate(specs) if s.name == "count"]
    plans = []  # (i, int64 values, offset, nplanes)
    total_ch = 1  # ones channel
    for i, spec in enumerate(specs):
        if spec.name not in ("sum", "avg") or spec.mv or not spec.args:
            continue
        v = np.asarray(_eval_rows(cols, spec.args[0], env, n))
        if v.dtype.kind not in ("i", "u", "b"):
            continue
        lo, hi = int(v.min()), int(v.max())
        nplanes = mm.int_planes_needed(lo, hi)
        if total_ch + nplanes > mm.MAX_CHANNELS + 1:
            continue
        plans.append((i, v.astype(np.int64), lo, nplanes))
        total_ch += nplanes
    if not plans and not count_idx:
        return {}
    if not (ps.sums_supported(n_groups, total_ch)
            and (mode == "interpret" or n >= ps.PALLAS_MIN_ROWS)):
        return {}

    channels = [jnp.ones(n, dtype=jnp.bfloat16)]
    for _, v, off, nplanes in plans:
        channels.extend(mm.int_planes(jnp.asarray(v), off, nplanes))
    sums = ps.plane_group_sums(
        jnp.asarray(np.asarray(ginv, dtype=np.int64)),
        jnp.stack(channels), n_groups,
        interpret=(mode == "interpret"), first_channel_ones=True)
    gcount = jnp.round(sums[0]).astype(jnp.int64)
    gcount_np = np.asarray(gcount)
    out = {}
    row = 1
    for i, _, off, nplanes in plans:
        planes = [sums[j] for j in range(row, row + nplanes)]
        row += nplanes
        s = np.asarray(mm.recombine_int(planes, gcount, off)) \
            .astype(np.float64)
        out[i] = ({"sum": s, "count": gcount_np.copy()}
                  if specs[i].name == "avg" else {"sum": s})
    for i in count_idx:
        out[i] = {"count": gcount_np.copy()}
    return out


def stage2_partial(plan: MultiStagePlan, cols: dict, n: int, env: dict,
                   device=None) -> IntermediateResult:
    """Joined rows → one MERGEABLE IntermediateResult (the canonical
    partial engine/reduce.py merges). The distributed exchange runs this
    per owned partition on each server — partials ship back as
    DataTables and the broker's merge_intermediates + finalize is the
    only stage-2 work left above the fleet. ``device`` routes eligible
    group-bys through the Pallas scatter tier."""
    q = plan.stage2
    stats = ExecutionStats(num_docs_scanned=n)
    aggs = q.aggregations()

    if q.distinct:
        key_cols = [_eval_rows(cols, e, env, n) for e in
                    q.select_expressions]
        if n == 0:
            keys = tuple(np.asarray(k)[:0] for k in key_cols)
        else:
            keys, _ = factorize_multi(key_cols)
        return IntermediateResult("distinct", group_keys=keys, stats=stats)

    if aggs and q.group_by:
        key_cols = [_eval_rows(cols, g, env, n) for g in q.group_by]
        specs = [aggspec.make_spec(a) for a in aggs]
        if n == 0:
            return IntermediateResult(
                "group_by",
                group_keys=tuple(np.asarray(k)[:0] for k in key_cols),
                agg_partials=[s.empty(0) for s in specs], stats=stats)
        keys, ginv = factorize_multi(key_cols)
        n_groups = len(keys[0])
        for a, spec in zip(aggs, specs):
            if spec.mv:
                raise SqlAnalysisError(
                    f"multi-value aggregation {a.name}() is not supported "
                    f"over joined rows")
        fast = _pallas_groupby_partials(aggs, specs, cols, env, ginv,
                                        n_groups, n, device)
        partials = []
        for i, (a, spec) in enumerate(zip(aggs, specs)):
            if i in fast:
                partials.append(fast[i])
                continue
            arg_values = [_eval_rows(cols, arg, env, n)
                          for arg in spec.args]
            partials.append(spec.host_groups(arg_values, ginv, n_groups))
        return IntermediateResult("group_by", group_keys=keys,
                                  agg_partials=partials, stats=stats)

    if aggs:
        specs = [aggspec.make_spec(a) for a in aggs]
        zero = np.zeros(n, dtype=np.int64)
        partials = []
        for a, spec in zip(aggs, specs):
            if spec.mv:
                raise SqlAnalysisError(
                    f"multi-value aggregation {a.name}() is not supported "
                    f"over joined rows")
            arg_values = [_eval_rows(cols, arg, env, n)
                          for arg in spec.args]
            partials.append(spec.host_groups(arg_values, zero, 1))
        return IntermediateResult("aggregation", agg_partials=partials,
                                  stats=stats)

    # selection: evaluate select + order-by columns, let finalize trim
    rows: dict = {}
    for i, e in enumerate(q.select_expressions):
        rows[i] = _eval_rows(cols, e, env, n)
    for j, ob in enumerate(q.order_by):
        rows[f"__ob{j}"] = _eval_rows(cols, ob.expression, env, n)
    return IntermediateResult("selection", rows=rows, stats=stats)


def run_stage2(plan: MultiStagePlan, cols: dict, n: int, env: dict,
               device=None):
    """Joined rows → ResultTable through the single-stage reduce path."""
    return finalize(plan.stage2, stage2_partial(plan, cols, n, env, device))


# ---------------------------------------------------------------------------
# plan execution over materialized stage-1 row sets (engine + broker shared)
# ---------------------------------------------------------------------------


def run_plan(plan: MultiStagePlan, table_rows: dict, device=None,
             advisor=None, advisor_key=None):
    """table_rows: alias → {bare column: np array}. Returns (ResultTable,
    meta dict with join/window execution facts).

    ``advisor``/``advisor_key`` (ISSUE 17): the plan advisor's memo for
    this template feeds the join-strategy pick (measured build-side rows
    from prior executions beat the catalog's dim-table heuristic), and
    every step's ACTUAL build rows + effective strategy are observed
    back. Overrides land in meta["advisorDecisions"]."""
    mesh = getattr(device, "mesh", None) if device is not None else None
    probe = plan.probe
    left_cols = {f"{probe.alias}.{c}": np.asarray(v)
                 for c, v in table_rows[probe.alias].items()}
    n = len(next(iter(left_cols.values()))) if left_cols else 0

    strategies = []
    roofline_recs = []
    adv_notes = []
    for step in plan.joins:
        build_cols = {f"{step.build.alias}.{c}": np.asarray(v)
                      for c, v in table_rows[step.build.alias].items()}
        n_build = len(next(iter(build_cols.values()))) if build_cols else 0
        strat = plan.strategy
        if strat == "DISTRIBUTED":
            # the wire exchange lives in the broker's orchestration
            # (broker/broker.py _execute_distributed); when the plan
            # reaches THIS runner — embedded engine, or a broker that
            # found the plan ineligible — the local execution form of a
            # distributed join IS the shuffle mirror
            strat = "SHUFFLE"
        if advisor is not None and advisor_key \
                and not plan.strategy_forced:
            # measured build rows beat the static dim-table heuristic:
            # a fact build that filters down tiny broadcasts, a dim
            # build that grew past the threshold shuffles. Both sides
            # compute identical joined rows — strategy is pure perf.
            strat2, note = advisor.advise_join_strategy(
                advisor_key, strat, step.build.alias,
                BROADCAST_MAX_BUILD_ROWS)
            if note:
                strat = strat2
                adv_notes.append(note)
        if strat == "BROADCAST" and not plan.strategy_forced \
                and n_build > BROADCAST_MAX_BUILD_ROWS:
            # a heuristic BROADCAST must not replicate a huge build table
            # to every device; SET joinStrategy='broadcast' overrides
            strat = "SHUFFLE"
        bytes_in = sum(int(v.nbytes) for v in left_cols.values()) \
            + sum(int(v.nbytes) for v in build_cols.values())
        t_join = time.perf_counter()
        left_cols, n = execute_join_step(
            left_cols, n, step, build_cols, device, mesh, strat)
        join_ms = (time.perf_counter() - t_join) * 1e3
        strategies.append(strat)
        if advisor is not None and advisor_key:
            advisor.observe(advisor_key,
                            build_rows={step.build.alias: n_build},
                            join_strategy=strat)
        # roofline record for the join step (ISSUE 11): probe+build
        # bytes in, expanded pairs out, over the step's wall — a coarser
        # model than the leaf-scan kernels' (host glue is inside the
        # wall), but it makes EXPLAIN ANALYZE on a join render the same
        # per-kernel GB/s line the single-stage path gets
        roofline_recs.append(_join_roofline_record(
            step, strat, bytes_in, left_cols, join_ms))

    if plan.post_filter is not None and n:
        m = _expr_mask(left_cols, plan.post_filter, None, n)
        left_cols = {k: v[m] for k, v in left_cols.items()}
        n = int(m.sum())

    env = apply_windows(left_cols, plan.windows, n, device) \
        if plan.windows else {}

    result = run_stage2(plan, left_cols, n, env, device)
    effective = None
    if strategies:
        effective = strategies[0] if len(set(strategies)) == 1 else "MIXED"
    meta = {
        "numStages": 2 if (plan.joins or plan.windows) else 1,
        "joinStrategy": effective,
        "numJoinedRows": n,
        "backend": "device" if device is not None else "host",
        "mesh": mesh is not None,
        "roofline": roofline_recs,
        # partition fan-out of the executed join (the broker-local
        # SHUFFLE baseline column vs the distributed exchange's
        # numPartitions): one bucket per mesh device, 1 when solo/host
        "joinFanout": (mesh.devices.size
                       if (mesh is not None and effective == "SHUFFLE")
                       else 1) if strategies else 0,
    }
    if adv_notes:
        meta["advisorDecisions"] = adv_notes
    return result, meta


def _join_roofline_record(step, strat: str, bytes_in: int, out_cols: dict,
                          join_ms: float) -> dict:
    """Roofline flight record for one executed join step."""
    bytes_out = sum(int(v.nbytes) for v in out_cols.values())
    bytes_moved = bytes_in + bytes_out
    rec = {"kernel": f"join_{step.kind.lower()}+{strat.lower()}",
           "bytesMoved": bytes_moved, "bytesFetched": bytes_out,
           "kernelMs": round(join_ms, 3), "linkMs": 0.0,
           "cacheHit": False}
    if join_ms > 0:
        rec["gbps"] = round(bytes_moved / (join_ms / 1e3) / 1e9, 3)
    return rec


def run_local(engine, plan: MultiStagePlan):
    """Embedded / server-local execution: stage-1 scans over the engine's
    hosted segments, then the shared plan runner."""
    from pinot_tpu.common.trace import span

    stats = ExecutionStats()
    need = needed_columns(plan)
    table_rows = {}
    # spans are no-ops untraced; under EXPLAIN ANALYZE's thread-local
    # tracer they fill the embedded waterfall (scan_local_rows drives
    # SegmentEvaluator directly, below the engine's instrumented paths)
    for src in plan.sources:
        with span("host_scan"):
            table_rows[src.alias] = scan_local_rows(
                engine, src.table, plan.pushdown.get(src.alias),
                need[src.alias], stats)
    # plan-advisor hookup (ISSUE 17): the device executor's advisor (one
    # per process) also memoizes multi-stage templates — join-strategy
    # advice from measured build rows. SET useAdvisor=false bypasses.
    advisor = getattr(engine.device, "advisor", None) \
        if engine.device is not None else None
    adv_key = None
    if advisor is not None:
        from pinot_tpu.engine.advisor import advisor_enabled

        try:
            opts = plan.stage2.options_ci()
        except Exception:  # noqa: BLE001 — advice is optional
            opts = {}
        if advisor_enabled(opts):
            from pinot_tpu.broker.querylog import template_key

            adv_key = template_key(plan)
    with span("stage2"):
        result, meta = run_plan(plan, table_rows, device=engine.device,
                                advisor=advisor, advisor_key=adv_key)
    meta["leafRows"] = {
        alias: (len(next(iter(cols.values()))) if cols else 0)
        for alias, cols in table_rows.items()
    }
    return result, stats, meta


def run_exchange_stage(engine, plan: MultiStagePlan, spec: dict, mailbox,
                       send, done, deadline, device=None):
    """One worker's slice of DISTRIBUTED stage 2 (the mailbox-exchange
    tentpole, ISSUE 16): scan the locally routed stage-1 segments, hash-
    partition every row set by join key (``exchange.stable_hash64`` —
    data-independent, so all workers agree without coordination), hand
    each partition to ``send`` (the server routes it to its owner: a
    self-offer or an ExchangeTransfer RPC), then join + partially
    aggregate every OWNED partition locally and merge those partials
    into the one IntermediateResult the broker's final merge consumes —
    stage 2 runs on the fleet, the broker only merges, exactly like
    stage 1.

    ``spec``: {"partitions": P, "partitionOwners": {str(p): instance},
    "senders": [instances], "selfId": str, "routing": {alias: {"table",
    "segments", optional "dtypes"}}}. The broker gates this path to
    single-join, window-free plans.
    """
    from pinot_tpu.common.trace import span
    from pinot_tpu.ops import join as join_ops
    from pinot_tpu.query2 import exchange

    if len(plan.joins) != 1 or plan.windows:
        raise SqlAnalysisError(
            "distributed exchange supports exactly one join and no "
            "window functions")
    step = plan.joins[0]
    probe_alias = plan.probe.alias
    build_alias = step.build.alias
    P = int(spec["partitions"])
    owners = {int(p): o for p, o in spec["partitionOwners"].items()}
    self_id = spec["selfId"]
    mesh = getattr(device, "mesh", None) if device is not None else None

    stats = ExecutionStats()
    need = needed_columns(plan)
    key_exprs = {probe_alias: step.left_keys, build_alias: step.right_keys}

    # ---- stage 1 + scatter: scan routed segments, partition, ship ----
    for src in plan.sources:
        route = spec["routing"].get(src.alias) or {}
        segs = route.get("segments")
        with span(f"exchange_scan:{src.alias}"):
            if segs:
                cols = scan_local_rows(
                    engine, src.table, plan.pushdown.get(src.alias),
                    need[src.alias], stats, segments=segs)
            else:
                cols = {c: np.empty(0) for c in need[src.alias]}
        # empty scans surface float64-empty arrays; the broker ships the
        # schema dtypes so a worker with zero routed rows still sends
        # correctly-typed (empty) payloads — the empty-leaf dtype guard
        dtypes = route.get("dtypes") or {}
        cols = {c: (v.astype(dtypes[c]) if len(v) == 0 and c in dtypes
                    else v) for c, v in cols.items()}
        n_rows = len(next(iter(cols.values()))) if cols else 0
        stats.leaf_rows[src.alias] = \
            stats.leaf_rows.get(src.alias, 0) + n_rows
        prefixed = {f"{src.alias}.{c}": v for c, v in cols.items()}
        key_vals = [_eval_rows(prefixed, k, None, n_rows)
                    for k in key_exprs[src.alias]]
        part = exchange.stable_hash64(key_vals, n_rows) % P
        deadline.check("exchange.partition")
        with span(f"exchange_send:{src.alias}"):
            for p, rows in enumerate(
                    join_ops.hash_partition_rows(part, P)):
                # EVERY partition ships, empty included: the owner's
                # gather then always sees dtyped arrays for both sides
                send(owners[p], src.alias, p,
                     {c: np.asarray(v)[rows] for c, v in cols.items()},
                     len(rows))
    done()

    # ---- barrier: all senders done, all announced payloads arrived ----
    with span("exchange_barrier"):
        mailbox.wait_ready(spec["senders"], deadline)

    # ---- stage 2 per owned partition: build+probe join + partials ----
    owned = sorted(p for p, o in owners.items() if o == self_id)
    partials = []
    total_joined = 0
    for p in owned:
        deadline.check("exchange.stage2")
        probe_cols, n_probe = mailbox.gather(probe_alias, p)
        build_cols, n_build = mailbox.gather(build_alias, p)
        if not probe_cols:
            probe_cols = {c: np.empty(0) for c in need[probe_alias]}
        if not build_cols:
            build_cols = {c: np.empty(0) for c in need[build_alias]}
        left = {f"{probe_alias}.{c}": np.asarray(v)
                for c, v in probe_cols.items()}
        build = {f"{build_alias}.{c}": np.asarray(v)
                 for c, v in build_cols.items()}
        with span(f"exchange_join:p{p}"):
            joined, n_j = execute_join_step(
                left, n_probe, step, build, device, mesh, "SHUFFLE")
        if plan.post_filter is not None and n_j:
            m = _expr_mask(joined, plan.post_filter, None, n_j)
            joined = {k: v[m] for k, v in joined.items()}
            n_j = int(m.sum())
        total_joined += n_j
        with span(f"exchange_stage2:p{p}"):
            partials.append(stage2_partial(plan, joined, n_j, {}, device))
    if partials:
        merged = merge_intermediates(plan.stage2, partials)
    else:
        # belt-and-braces: a worker that owns no partition still returns
        # a well-typed empty partial over the canonical joined namespace
        empty = {f"{a}.{c}": np.empty(0)
                 for a, cs in need.items() for c in cs}
        merged = stage2_partial(plan, empty, 0, {}, device)
    stats.stage2_rows = total_joined
    merged.stats = stats
    return merged


def execute_multistage(engine, stmt, t0: Optional[float] = None) -> dict:
    """Full embedded path: parsed multi-stage statement → broker-style
    response dict (the engine.execute integration point)."""
    t0 = time.time() if t0 is None else t0

    def catalog(table: str):
        tdm = _tdm_for(engine, table)
        segs = tdm.acquire()
        try:
            if not segs:
                raise ValueError(f"table {table!r} has no segments")
            cols = tuple(segs[0].column_names())
        finally:
            tdm.release(segs)
        return cols, bool(getattr(tdm, "is_dim_table", False))

    plan = compile_plan(stmt, catalog)
    analyze = plan.explain and getattr(plan, "analyze", False)
    if plan.explain and not analyze:
        from pinot_tpu.engine.explain import explain_multistage

        return explain_multistage(engine, plan)
    tracer = None
    if analyze:
        # EXPLAIN ANALYZE needs the phase waterfall: install a
        # thread-local tracer so the leaf scans' span() sites (same
        # thread on the embedded path) fill the ladder — matching the
        # broker EA paths, which force SET trace = true
        from pinot_tpu.common import trace as _trace

        tracer = _trace.start_trace("analyze")
    try:
        result, stats, meta = run_local(engine, plan)
    finally:
        if tracer is not None:
            from pinot_tpu.common import trace as _trace

            _trace.end_trace()
    resp = result.to_json()
    resp.update({
        "exceptions": [],
        "numDocsScanned": stats.num_docs_scanned,
        "numEntriesScannedInFilter": stats.num_entries_scanned_in_filter,
        "numEntriesScannedPostFilter": stats.num_entries_scanned_post_filter,
        "numSegmentsQueried": stats.num_segments_queried,
        "numSegmentsProcessed": stats.num_segments_processed,
        "numSegmentsMatched": stats.num_segments_matched,
        "numSegmentsPrunedByServer": stats.num_segments_pruned,
        "numBlocksPruned": stats.num_blocks_pruned,
        "numSegmentsCold": stats.num_segments_cold,
        # cold leaves answered honestly-partial rows: the joined result
        # is partial too (matches the broker multistage path)
        "partialResult": stats.num_segments_cold > 0,
        "numGroupsLimitReached": stats.num_groups_limit_reached,
        "totalDocs": stats.total_docs,
        "numStages": meta["numStages"],
        "numJoinedRows": meta["numJoinedRows"],
        "leafRows": meta.get("leafRows") or {},
        "timeUsedMs": round((time.time() - t0) * 1000, 3),
    })
    if meta.get("roofline"):
        resp["roofline"] = meta["roofline"]
    if meta["joinStrategy"]:
        resp["joinStrategy"] = meta["joinStrategy"]
    # plan-advisor stamps (ISSUE 17): stage-2 strategy overrides from
    # the plan runner + leaf-scan overrides the stats carried up
    adv_lines = list(meta.get("advisorDecisions") or [])
    for line in (stats.advisor_decisions or []):
        if line not in adv_lines:
            adv_lines.append(line)
    if adv_lines:
        resp["advisorDecisions"] = adv_lines
    if analyze:
        # EXPLAIN ANALYZE (ISSUE 11): the plan ran for real above —
        # annotate the static tree with its actuals; the executed
        # response rides as analyzedResponse (bit-identical contract)
        from pinot_tpu.engine.explain import (
            annotate_analyze,
            explain_multistage,
        )

        if tracer is not None and tracer.spans:
            resp["traceInfo"] = {"server": tracer.to_json()}
        out = annotate_analyze(explain_multistage(engine, plan), resp)
        out["analyzedResponse"] = resp
        return out
    return resp
