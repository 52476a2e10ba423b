"""EXPLAIN PLAN FOR: render the logical plan as rows.

Reference: ServerQueryExecutorV1Impl.processExplainPlanQueries (:338-352)
renders the operator tree via Operator.toExplainString; here the plan is the
engine's shape dispatch + filter tree + backend choice.
"""

from __future__ import annotations

import os

from pinot_tpu.common.options import bool_option
from pinot_tpu.query.context import FilterNode, FilterNodeType, QueryContext


def _width_lines(engine, q: QueryContext, segs, out: list) -> None:
    """PINOT_TPU_WIDTH_AUDIT=1: render the device width plan per referenced
    column (engine/params.py ColPlan) — the EXPLAIN face of the debug
    width-audit mode. Best-effort: anything the device path would reject
    simply renders no WIDTH lines (the host path has no width plan)."""
    import numpy as np

    from pinot_tpu.engine.params import BatchContext
    from pinot_tpu.storage.segment import Encoding

    try:
        # a THROWAWAY context: planning reads only metadata/dictionaries,
        # and going through the executor's batch_for here would insert a
        # display-only batch into the production LRU (evicting a hot one)
        # and skew the hit/miss gauges
        ctx = BatchContext(segs)
        for name in sorted(q.columns()):
            plan = ctx.width_plan(name)
            desc = np.dtype(plan.dtype).name
            if plan.bits:
                desc += f" packed={plan.bits}b"
            if plan.offset is not None:
                desc += f" for-offset={plan.offset}"
            if plan.wide:
                desc += f" wide={np.dtype(plan.wide).name}"
            if ctx.encoding(name) == Encoding.DICT:
                desc += f" card={ctx.cardinality(name)}"
            out.append(f"    WIDTH({name}: {desc})")
    except Exception:  # noqa: BLE001 — display only
        pass


def _filter_lines(f: FilterNode, depth: int, out: list, seg=None) -> None:
    pad = "  " * depth
    if f.type is FilterNodeType.PREDICATE:
        op = "PREDICATE"
        if seg is not None:
            from pinot_tpu.engine.host import filter_operator_for

            op = filter_operator_for(seg, f.predicate)
        out.append(f"{pad}FILTER_{op}({f.predicate})")
        return
    if f.type in (FilterNodeType.CONSTANT_TRUE, FilterNodeType.CONSTANT_FALSE):
        out.append(f"{pad}FILTER_{f.type.value}")
        return
    out.append(f"{pad}FILTER_{f.type.value}")
    for c in f.children:
        _filter_lines(c, depth + 1, out, seg)


def _rows_response(lines: list) -> dict:
    rows = [[ln, i, i - 1] for i, ln in enumerate(lines)]
    return {
        "resultTable": {
            "dataSchema": {
                "columnNames": ["Operator", "Operator_Id", "Parent_Id"],
                "columnDataTypes": ["STRING", "INT", "INT"],
            },
            "rows": rows,
        },
        "exceptions": [],
    }


def explain_multistage(engine, plan) -> dict:
    """EXPLAIN for a two-stage (join / window) plan: the stage boundary,
    the join strategy with build/probe sides, window spec lines, and the
    per-table stage-1 scans with their pushed-down filters."""
    from pinot_tpu.query2.logical import to_sql
    from pinot_tpu.sql.compiler import _to_filter

    q = plan.stage2
    aggs = q.aggregations()
    if q.distinct:
        shape = "DISTINCT"
    elif aggs and q.group_by:
        shape = "AGGREGATE_GROUPBY_ORDERBY"
    elif aggs:
        shape = "AGGREGATE"
    elif plan.windows:
        shape = "SELECT_WINDOW"
    else:
        shape = "SELECT_ORDERBY" if q.order_by else "SELECT"

    device = getattr(engine, "device", None) if engine is not None else None
    backend = "DEVICE(jax/xla)" if device is not None else "HOST(numpy)"
    mesh = getattr(device, "mesh", None) if device is not None else None

    lines: list[str] = []
    lines.append(f"BROKER_REDUCE(limit:{q.limit})")
    lines.append(f"  STAGE_2_{shape}"
                 f"({', '.join(str(e) for e in q.select_expressions)})"
                 f" [{backend}]")
    if q.group_by:
        lines.append(
            f"    GROUP_BY({', '.join(str(g) for g in q.group_by)})")
    if q.having is not None:
        lines.append(f"    HAVING({q.having})")
    for w in plan.windows:
        lines.append(f"    WINDOW({w.describe()})")
    if plan.post_filter is not None:
        lines.append(f"    POST_JOIN_FILTER({to_sql(plan.post_filter)})")
    # DISTRIBUTED runs stage 2 on the server fleet (ISSUE 16): the
    # boundary is a wire exchange between servers, whatever mesh the
    # broker-side renderer happens to see
    if plan.strategy == "DISTRIBUTED" and plan.joins:
        exchange = "server-fleet"
    else:
        exchange = "mesh-collective" if mesh is not None else "local"
    if plan.joins:
        lines.append(f"  STAGE_BOUNDARY(exchange:{plan.strategy} "
                     f"[{exchange}])")
    else:
        lines.append("  STAGE_BOUNDARY(exchange:SORT [window])")
    probe_desc = f"{plan.probe.alias}={plan.probe.table}"
    for j in plan.joins:
        dim = " dim" if j.build.is_dim else ""
        lines.append(
            f"  JOIN_{j.kind}(strategy={plan.strategy}, "
            f"build={j.build.alias}={j.build.table}{dim}, "
            f"probe={probe_desc})")
        keys = ", ".join(f"{lk} = {rk}"
                         for lk, rk in zip(j.left_keys, j.right_keys))
        lines.append(f"      KEYS({keys})")
        if j.residual is not None:
            lines.append(f"      RESIDUAL({to_sql(j.residual)})")
    for src in plan.sources:
        role = "probe" if src is plan.probe else \
            ("build/broadcast" if plan.strategy == "BROADCAST"
             else "build/shuffle")
        lines.append(f"  SCAN({src.alias}={src.table} [{role}])")
        push = plan.pushdown.get(src.alias)
        if push is not None:
            _filter_lines(_to_filter(push), 2, lines)
        else:
            lines.append("    FILTER_MATCH_ENTIRE_SEGMENT")
    return _rows_response(lines)


def _fmt_ms(v) -> str:
    try:
        return f"{float(v):.2f}ms"
    except (TypeError, ValueError):
        return "?"


def _kernel_line(rec: dict) -> str:
    """One roofline flight → the per-kernel ``KERNEL(<label>: x GB/s,
    …)`` line EXPLAIN ANALYZE renders (ISSUE 11)."""
    label = rec.get("kernel", "kernel")
    inst = rec.get("instance")
    where = f"@{inst}" if inst else ""
    if rec.get("cacheHit"):
        return (f"    KERNEL({label}{where}: CACHED_PARTIALS, "
                f"linkMs={rec.get('linkMs')})")
    gbps = rec.get("gbps")
    perf = "n/a" if gbps is None else f"{gbps} GB/s"
    # a dense group-by says where its kernel's operands came from
    operands = "".join(
        f", {k}={rec[k]}" for k in ("groupbyOperands", "groupbyKeySpace",
                                    "keySpaceCells", "keySpaceLive",
                                    "groupbyKeyLayout", "slotRows",
                                    "fullestCellRows", "slotPadding",
                                    "planeBits")
        if rec.get(k))
    return (f"    KERNEL({label}{where}: {perf}, "
            f"bytes={rec.get('bytesMoved')}, "
            f"kernelMs={rec.get('kernelMs')}, queueMs={rec.get('queueMs')}, "
            f"runMs={rec.get('runMs')}, linkMs={rec.get('linkMs')}"
            f"{operands})")


def annotate_analyze(plan: dict, resp: dict) -> dict:
    """EXPLAIN ANALYZE rendering (ISSUE 11): the static plan tree from
    explain_plan / explain_multistage, annotated in place with per-node
    actuals from the EXECUTED response — rows in/out on the reduce /
    combine / join / scan nodes, matched rows + blocks pruned on the
    filter root — followed by an ANALYZE subtree carrying the segment
    counters, the per-phase ms waterfall (merged traceInfo), one KERNEL
    line per roofline flight (achieved GB/s), and the
    cache-hit provenance (device partials / broker result cache)."""
    from pinot_tpu.tools.querylog import phase_breakdown

    lines = [r[0] for r in plan["resultTable"]["rows"]]
    nrows = len(((resp.get("resultTable") or {}).get("rows")) or [])
    docs = resp.get("numDocsScanned")
    leaf_rows = resp.get("leafRows") or {}
    # multistage plans carry PER-TABLE pushdown filters; the cluster-wide
    # docsScanned total belongs to none of them, so the filter-root
    # annotation is single-stage-only (leafRows is the multistage marker)
    multistage = bool(leaf_rows) or resp.get("numJoinedRows") is not None
    filter_done = multistage
    out = []
    for ln in lines:
        s = ln.strip()
        if s.startswith("BROKER_REDUCE"):
            ln += (f" (actual: rows={nrows}, "
                   f"timeMs={resp.get('timeUsedMs')})")
        elif s.startswith("STAGE_2_"):
            # stage 2 consumes the JOINED row set, not the stage-1 scan
            # docs (a 1M-doc scan joining down to 500 rows must say 500)
            n_in = resp.get("numJoinedRows")
            ln += (f" (actual: in={docs if n_in is None else n_in} rows, "
                   f"out={nrows} rows)")
        elif s.startswith("COMBINE_"):
            ln += f" (actual: in={docs} rows, out={nrows} rows)"
        elif s.startswith("STAGE_BOUNDARY(") and resp.get("exchange"):
            # distributed stage-2 ran (possibly a RUNTIME demotion the
            # static plan did not know about): render the strategy that
            # actually executed, plus the exchange actuals — partition
            # count, shipped bytes, spill count, per-server stage-2 rows
            import re as _re

            ex = resp["exchange"]
            if "exchange:DISTRIBUTED" not in ln:
                ln = _re.sub(r"exchange:\w+ \[[^\]]*\]",
                             "exchange:DISTRIBUTED [server-fleet]", ln)
            per = ", ".join(
                f"{w}={v.get('stage2Rows')}"
                for w, v in sorted((ex.get("servers") or {}).items()))
            ln += (f" (actual: partitions={ex.get('partitions')}, "
                   f"shippedBytes={resp.get('exchangeBytes')}, "
                   f"spills={resp.get('exchangeSpillCount')}, "
                   f"stage2Rows[{per}])")
        elif s.startswith("JOIN_") and resp.get("numJoinedRows") is not None:
            ln += f" (actual: out={resp['numJoinedRows']} rows)"
        elif s.startswith("SCAN("):
            alias = s[len("SCAN("):].split("=", 1)[0]
            if alias in leaf_rows:
                ln += f" (actual: out={leaf_rows[alias]} rows)"
        elif (s.startswith("FILTER_") and not filter_done
              and not s.startswith("FILTER_MATCH_ENTIRE")
              and docs is not None):
            filter_done = True  # annotate the ROOT filter node only
            ln += (f" (actual: matched={docs} rows, "
                   f"blocksPruned={resp.get('numBlocksPruned', 0)})")
        out.append(ln)

    out.append("  ANALYZE")
    out.append(f"    ROWS(scanned={docs}, returned={nrows}, "
               f"totalDocs={resp.get('totalDocs')})")
    out.append(
        "    SEGMENTS("
        f"queried={resp.get('numSegmentsQueried')}, "
        f"processed={resp.get('numSegmentsProcessed')}, "
        f"matched={resp.get('numSegmentsMatched')}, "
        f"prunedByServer={resp.get('numSegmentsPrunedByServer')}, "
        f"prunedByBroker={resp.get('numSegmentsPrunedByBroker', 0)}, "
        f"blocksPruned={resp.get('numBlocksPruned')})")
    phases = phase_breakdown({"traceInfo": resp.get("traceInfo") or {}})
    if phases:
        out.append("    PHASE(" + ", ".join(
            f"{k}={_fmt_ms(v)}" for k, v in sorted(phases.items())) + ")")
    for rec in resp.get("roofline") or ():
        out.append(_kernel_line(rec))
    out.append(
        f"    CACHE(partialsCacheHit={bool(resp.get('partialsCacheHit'))}, "
        f"resultCacheHit={bool(resp.get('resultCacheHit'))})")
    # plan advisor (ISSUE 17): one line per measurement-driven override
    # this execution ran with — already formatted as
    # ADVISOR(<decision>: measured=X default=Y) at the decision site, so
    # a mis-advised plan is debuggable straight from EXPLAIN ANALYZE
    for line in resp.get("advisorDecisions") or ():
        out.append(f"    {line}")
    return _rows_response(out)


def explain_plan(engine, q: QueryContext) -> dict:
    lines: list[str] = []
    aggs = q.aggregations()
    if q.distinct:
        shape = "DISTINCT"
    elif aggs and q.group_by:
        shape = "AGGREGATE_GROUPBY_ORDERBY"
    elif aggs:
        shape = "AGGREGATE"
    else:
        shape = "SELECT_ORDERBY" if q.order_by else "SELECT"

    backend = "HOST(numpy)"
    if engine.device is not None and engine.device.supports(q):
        backend = "DEVICE(jax/xla)"

    lines.append(f"BROKER_REDUCE(limit:{q.limit})")
    lines.append(f"  COMBINE_{shape} [{backend}]")
    lines.append(f"    PLAN_START(table:{q.table_name})")
    lines.append(f"    {shape}({', '.join(str(e) for e in q.select_expressions)})")
    if q.group_by:
        lines.append(f"    GROUP_BY({', '.join(str(g) for g in q.group_by)})")
    if q.filter is not None:
        # index choice is per-segment; EXPLAIN (like the reference's
        # non-verbose mode) describes it against one representative segment
        seg = None
        segs = []
        tdm = engine.tables.get(q.table_name)
        if tdm is not None and tdm.segments:
            segs = list(tdm.segments.values())
            seg = segs[0]
        # server-side stats pruning (min/max + dictionary membership +
        # bloom, engine.SegmentPruner — the same tri-state the device
        # launch masks segments with): provably-false-everywhere renders
        # as FILTER_EMPTY, partial prunes as a PRUNE line under the tree
        n_pruned = 0
        pruner = getattr(engine, "pruner", None)
        if pruner is not None and segs:
            n_pruned = sum(1 for s in segs if pruner.prune(q, s))
        if segs and n_pruned == len(segs):
            lines.append("    FILTER_EMPTY")
        else:
            _filter_lines(q.filter, 2, lines, seg)
            if n_pruned:
                lines.append(
                    f"      PRUNE(zone-map: {n_pruned}/{len(segs)} segments)")
    else:
        lines.append("    FILTER_MATCH_ENTIRE_SEGMENT")
    lines.append("    PROJECT(" + ", ".join(sorted(q.columns())) + ")")
    if backend.startswith("DEVICE"):
        # sub-RTT serving surfaces (ISSUE 9): the on-device final reduce
        # (when the query's ORDER/LIMIT shape supports an in-kernel trim)
        # and the device partials cache state
        dev = engine.device
        if q.group_by and not q.distinct:
            from pinot_tpu.ops.device_reduce import plan_trim, trim_keep_count

            # render the trim only when it would actually engage: the
            # static bound must sit BELOW the real group-table length
            # (product of cardinalities from a THROWAWAY context, like
            # _width_lines — never batch_for; best-effort, host-only
            # shapes simply render no line). The embedded explain path
            # is terminal semantics (nothing merges after finalize).
            spec = None
            try:
                from pinot_tpu.engine.device import (
                    MAX_DENSE_GROUPS,
                    MAX_SORTED_GROUPS,
                )
                from pinot_tpu.engine.params import BatchContext

                tdm = engine.tables.get(q.table_name)
                segs = list(tdm.segments.values()) if tdm is not None else []
                if segs:
                    ctx = BatchContext(segs)
                    total = 1
                    for g in q.group_by:
                        total *= ctx.cardinality(g.name)
                    if total > MAX_DENSE_GROUPS:
                        total = min(dev.num_groups_limit, MAX_SORTED_GROUPS)
                    spec = plan_trim(
                        q, tuple(q.group_by), tuple(q.aggregations()),
                        "groupby", total, "terminal",
                        getattr(dev, "group_trim_size", 5000))
            except Exception:  # noqa: BLE001 — display only
                spec = None
            if spec is not None:
                lines.append(
                    f"    DEVICE_REDUCE(trim={trim_keep_count(q, 'terminal')})")
        if getattr(dev, "partials_cache_enabled", False) \
                and bool_option(q.options_ci(), "usepartialscache",
                                None) is not False:
            lines.append(
                f"    CACHED_PARTIALS(entries={len(dev._partials)})")
    if (backend.startswith("DEVICE")
            and os.environ.get("PINOT_TPU_WIDTH_AUDIT", "") not in ("", "0")):
        tdm = engine.tables.get(q.table_name)
        segs = list(tdm.segments.values()) if tdm is not None else []
        if segs:
            _width_lines(engine, q, segs, lines)

    rows = [[ln, i, i - 1] for i, ln in enumerate(lines)]
    return {
        "resultTable": {
            "dataSchema": {
                "columnNames": ["Operator", "Operator_Id", "Parent_Id"],
                "columnDataTypes": ["STRING", "INT", "INT"],
            },
            "rows": rows,
        },
        "exceptions": [],
    }
