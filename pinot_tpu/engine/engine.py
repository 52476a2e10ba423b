"""Query engine entry point: SQL → response, over locally-held segments.

Mirrors the reference's in-process server execution path
(ServerQueryExecutorV1Impl.java:120-133 — acquire segments, prune, plan,
execute, build response) plus the broker reduce, the way the reference's
query-correctness fixture runs both in one process (BaseQueriesTest.java).

Backend selection: the device (JAX) executor handles the accelerated shapes;
anything it reports as unsupported falls back to the host numpy path — the
moral equivalent of the reference falling back from index-based to
scan-based operators (FilterOperatorUtils.java:165-194).
"""

from __future__ import annotations

import logging
import threading
import time
from typing import Optional

from pinot_tpu.common.deadline import QueryTimeout
from pinot_tpu.engine.host import HostExecutor
from pinot_tpu.engine.reduce import finalize, merge_intermediates
from pinot_tpu.query.context import (
    Expression,
    FilterNode,
    FilterNodeType,
    PredicateType,
    QueryContext,
)
from pinot_tpu.query.optimizer import optimize_query
from pinot_tpu.sql.compiler import compile_select
from pinot_tpu.storage.segment import ImmutableSegment

log = logging.getLogger("pinot_tpu.engine")


class SegmentPruner:
    """Server-side pruning on column metadata min/max + bloom filters
    (query/pruner/ColumnValueSegmentPruner.java analog)."""

    def prune(self, q: QueryContext, seg: ImmutableSegment) -> bool:
        """True → segment cannot match; skip it."""
        f = q.filter
        if f is None:
            return False
        return self._cannot_match(f, seg)

    def _cannot_match(self, f: FilterNode, seg: ImmutableSegment) -> bool:
        if f.type is FilterNodeType.CONSTANT_FALSE:
            return True
        if f.type is FilterNodeType.AND:
            return any(self._cannot_match(c, seg) for c in f.children)
        if f.type is FilterNodeType.OR:
            return all(self._cannot_match(c, seg) for c in f.children)
        if f.type is not FilterNodeType.PREDICATE:
            return False
        p = f.predicate
        if not p.lhs.is_identifier or p.lhs.name not in seg.metadata.columns:
            return False
        meta = seg.column_metadata(p.lhs.name)
        # min/max interval exclusion: the SAME algebra the broker prunes
        # routing with (common/pruning.py) — strict about incomparable
        # literals, so a mis-typed literal surfaces from the scan instead
        # of silently pruning to empty
        from pinot_tpu.common.pruning import interval_may_match

        if p.type in (PredicateType.EQ, PredicateType.IN,
                      PredicateType.RANGE):
            if not interval_may_match(p, meta.min_value, meta.max_value):
                return True
        if p.type is PredicateType.EQ and \
                self._provably_absent(seg, p.lhs.name, [p.value]):
            return True
        if p.type is PredicateType.IN and p.values and \
                self._provably_absent(seg, p.lhs.name, list(p.values)):
            return True
        return False

    @staticmethod
    def _provably_absent(seg, col: str, values: list) -> bool:
        from pinot_tpu.common.pruning import provably_absent

        return provably_absent(seg, col, values)



class TableDataManager:
    """Segments of one table (data/manager/offline/OfflineTableDataManager
    analog): acquire/release refcounting so an unload (retention, minion
    swap, rebalance) during an in-flight query defers teardown — the
    reference's ``acquireSegment``/``releaseSegment`` on TableDataManager.
    ``on_unload`` fires once the last reference drains (the server deletes
    its local working copy there)."""

    def __init__(self, name: str, host_name: Optional[str] = None):
        self.name = name
        self.segments: dict[str, ImmutableSegment] = {}
        self._refs: dict[str, int] = {}
        self._doomed: dict[str, ImmutableSegment] = {}
        self._lock = threading.Lock()
        self.on_unload = None  # callback(segment) after last ref drops
        self.host_name = host_name  # stamps $hostName on hosted segments
        self.generation = 0  # bumped on add/remove; dim-lookup cache key
        # None = unknown (embedded engines allow LOOKUP on any local table);
        # the server layer sets True/False from the registry's TableConfig
        self.is_dim_table = None

    def add_segment(self, seg: ImmutableSegment) -> None:
        if self.host_name is not None and getattr(seg, "host_name", None) is None:
            seg.host_name = self.host_name
        with self._lock:
            self.segments[seg.name] = seg
            self.generation += 1
            self._doomed.pop(seg.name, None)  # re-add wins over unload

    def replace_if_idle(self, name: str, seg) -> bool:
        """Atomically swap the hosted object for ``name`` when NO query
        holds a reference (tier transitions, server/tiering.py): an
        in-flight scan must never lose its mmaps mid-query, so a held
        reference refuses the swap (False — the caller retries next
        tick). The doomed map is untouched: a swap is not an unload."""
        with self._lock:
            if name not in self.segments or self._refs.get(name, 0) > 0:
                return False
            if self.host_name is not None \
                    and getattr(seg, "host_name", None) is None:
                seg.host_name = self.host_name
            self.segments[name] = seg
            self.generation += 1
            return True

    def remove_segment(self, name: str) -> None:
        with self._lock:
            seg = self.segments.pop(name, None)
            if seg is None:
                return
            self.generation += 1
            if self._refs.get(name, 0) > 0:
                self._doomed[name] = seg  # teardown deferred to release()
                return
            self._refs.pop(name, None)
        self._fire_unload(seg)

    def acquire(self) -> list:
        with self._lock:
            segs = list(self.segments.values())
            for s in segs:
                self._refs[s.name] = self._refs.get(s.name, 0) + 1
            return segs

    def release(self, segments) -> None:
        to_unload = []
        with self._lock:
            for s in segments:
                left = self._refs.get(s.name, 1) - 1
                if left > 0:
                    self._refs[s.name] = left
                    continue
                self._refs.pop(s.name, None)
                doomed = self._doomed.pop(s.name, None)
                if doomed is not None:
                    to_unload.append(doomed)
        for seg in to_unload:
            self._fire_unload(seg)

    def _fire_unload(self, seg) -> None:
        if self.on_unload is not None:
            try:
                self.on_unload(seg)
            except Exception:  # noqa: BLE001 — unload cleanup is best-effort
                log.exception("segment unload callback failed for %s", seg.name)


class QueryEngine:
    """SQL in, response out, over in-process tables."""

    def __init__(self, device_executor="auto", num_groups_limit: int = 100_000,
                 host_name: Optional[str] = None):
        self.tables: dict[str, TableDataManager] = {}
        self.host_name = host_name  # server instance id for $hostName
        self.host = HostExecutor(num_groups_limit=num_groups_limit)
        self.pruner = SegmentPruner()
        if device_executor == "auto":
            from pinot_tpu.engine.device import DeviceExecutor

            device_executor = DeviceExecutor(num_groups_limit=num_groups_limit)
        self.device = device_executor  # None → host-only
        self._dim_cache: dict = {}  # (table, pk, val) -> (generation, map)
        self.host.lookup_resolver = self.dim_table_lookup

    # ---- table management -----------------------------------------------
    def table(self, name: str) -> TableDataManager:
        if name not in self.tables:
            self.tables[name] = TableDataManager(name, host_name=self.host_name)
        return self.tables[name]

    def add_segment(self, table: str, seg: ImmutableSegment) -> None:
        self.table(table).add_segment(seg)

    # ---- query -----------------------------------------------------------
    def execute(self, sql: str) -> dict:
        """Full path: SQL string → broker-response dict. Join / window
        queries route to the multi-stage engine (query2/); plain
        single-table queries take the single-stage path untouched."""
        t0 = time.time()
        try:
            from pinot_tpu.sql.compiler import is_multistage
            from pinot_tpu.sql.parser import parse_sql

            stmt = parse_sql(sql)
            if is_multistage(stmt):
                from pinot_tpu.query2.runner import execute_multistage

                return execute_multistage(self, stmt, t0)
            q = optimize_query(compile_select(stmt))
            if q.explain:
                if q.analyze:
                    return self._explain_analyze(q, t0)
                return self._explain(q)
            result, merged = self._execute_merged(q)
        except Exception as e:  # noqa: BLE001 — reference returns exceptions in-band
            return {"exceptions": [{"errorCode": 200, "message": f"{type(e).__name__}: {e}"}]}
        return self._stats_response(result, merged, t0)

    @staticmethod
    def _stats_response(result, merged, t0: float) -> dict:
        """Broker-response-shaped dict from a finalized result + merged
        intermediate (the one shared by execute and EXPLAIN ANALYZE)."""
        stats = merged.stats
        resp = result.to_json()
        resp.update(
            {
                "exceptions": [],
                "numDocsScanned": stats.num_docs_scanned,
                "numEntriesScannedInFilter": stats.num_entries_scanned_in_filter,
                "numEntriesScannedPostFilter": stats.num_entries_scanned_post_filter,
                "numSegmentsQueried": stats.num_segments_queried,
                "numSegmentsProcessed": stats.num_segments_processed,
                "numSegmentsMatched": stats.num_segments_matched,
                "numSegmentsPrunedByServer": stats.num_segments_pruned,
                "numBlocksPruned": stats.num_blocks_pruned,
                # cold-tier segments that answered as in-flight partials
                # while their deep-store download proceeds (ISSUE 12)
                "numSegmentsCold": stats.num_segments_cold,
                # segments the host executor answered for any reason
                "numSegmentsOnHost": stats.num_segments_on_host,
                "numGroupsLimitReached": stats.num_groups_limit_reached,
                "partialsCacheHit": stats.partials_cache_hit,
                "totalDocs": stats.total_docs,
                # kernel roofline accounting (ISSUE 11)
                "deviceBytesMoved": stats.device_bytes_moved,
                "deviceKernelMs": round(stats.device_kernel_ms, 3),
                "deviceQueueMs": round(stats.device_queue_ms, 3),
                "deviceRunMs": round(stats.device_run_ms, 3),
                "deviceLinkMs": round(stats.device_link_ms, 3),
                "timeUsedMs": round((time.time() - t0) * 1000, 3),
            }
        )
        if getattr(merged, "roofline", None):
            resp["roofline"] = merged.roofline
        if stats.advisor_decisions:
            # plan-advisor stamps (ISSUE 17): every measurement-driven
            # override this execution ran with, for responses / querylog
            # / EXPLAIN ANALYZE
            resp["advisorDecisions"] = list(stats.advisor_decisions)
        return resp

    def execute_query(self, q: QueryContext, tracer=None):
        result, merged = self._execute_merged(q, tracer=tracer)
        return result, merged.stats

    def _execute_merged(self, q: QueryContext, tracer=None):
        """(finalized ResultTable, merged IntermediateResult) — the inner
        execute path; keeps the merged result (trace/roofline/stat
        leaves) available to callers that render more than rows."""
        tdm = self.tables.get(q.table_name)
        if tdm is None:
            raise KeyError(f"table {q.table_name!r} not found")
        segments = tdm.acquire()
        try:
            if not segments:
                raise ValueError(f"table {q.table_name!r} has no segments")
            merged = self.execute_segments_async(
                q, segments, terminal=True, tracer=tracer)()
            q = self._expand_star(q, segments[0])
            return finalize(q, merged), merged
        finally:
            tdm.release(segments)

    def execute_segments(self, q: QueryContext, segments, terminal: bool = False,
                         trim_ok: bool = True):
        """Server-side partial execution over an explicit segment list →
        merged (unfinalized) IntermediateResult — what a server ships to the
        broker as a DataTable (ServerQueryExecutorV1Impl.processQuery).

        ``terminal=True`` (the local execute_query path): nothing upstream
        will merge this result, so when the device batch is the SOLE
        partial, sketch aggregations may finalize on device and skip
        shipping G×m mergeable state over the host link. Server-shipped
        partials stay mergeable (the broker combines them).

        ``trim_ok=False`` disables the on-device final reduce for callers
        whose finalize runs under a DIFFERENT QueryContext than the one
        executed here (star-tree substitution plans)."""
        return self.execute_segments_async(q, segments, terminal,
                                           trim_ok=trim_ok)()

    def execute_segments_async(self, q: QueryContext, segments,
                               terminal: bool = False, fallback_gate=None,
                               deadline=None, tracer=None,
                               trim_ok: bool = True):
        """LAUNCH phase of execute_segments → zero-arg fetch() closure.

        ``tracer`` (common/trace.py Tracer, optional): the query's
        explicit trace object, carried BY REFERENCE through the device
        launch handles and into the returned fetch closure — spans
        recorded during the deferred fetch (possibly another thread) or
        inside a coalesced cohort land on this query's trace, never on
        whatever tracer the executing thread happens to hold.

        ``deadline`` (common/deadline.py Deadline, optional): the query's
        propagated end-to-end budget. Checked before each host segment
        scan, before each blocking device fetch, and before each
        host-fallback re-scan — an expired budget aborts with a typed
        QueryTimeout (releasing every still-pinned in-flight launch)
        instead of finishing work the client already abandoned.

        TIER SPLIT (ISSUE 12, server/tiering.py): cold segments
        (``is_cold`` placeholders whose planes live only in the deep
        store) are split out FIRST — each counts as ``numSegmentsCold``
        in the merged stats and its ``touch()`` enqueues an asynchronous
        hydration, so the query returns an honest in-flight partial
        instead of blocking its scheduler slot on a download. Warm
        segments fail ``segment_device_eligible`` and take the host
        scan path over their lazily-mmap'd planes; hot segments ride
        the device batch exactly as before.

        Everything CPU-bound runs here — pruning, star-tree/metadata fast
        paths, the device template build + NON-BLOCKING dispatch
        (DeviceExecutor.launch), and the host scan partials (which overlap
        the device launch's link round trip). The returned closure does
        only the blocking device fetch + merge, so a server can release
        its scheduler slot before the host↔device round trip and N
        concurrent queries overlap their link waits (server/server.py
        _handle_submit). Fetch-time device fallbacks (sorted group-table
        overflow) re-run the device batch on the host inside the closure;
        ``fallback_gate`` (callable(fn) → fn()) wraps THAT re-run so a
        server can put the heavy host scan back under scheduler admission
        — the fetch phase itself runs slot-free by design, and without
        the gate a fallback storm would escape the concurrency cap."""
        all_segments = segments
        cold_refs = [s for s in segments if getattr(s, "is_cold", False)]
        if cold_refs:
            segments = [s for s in segments
                        if not getattr(s, "is_cold", False)]
            for s in cold_refs:
                touch = getattr(s, "touch", None)
                if touch is not None:
                    touch()  # async hydration; never blocks this query
        q = self._expand_star(q, (segments or cold_refs)[0])

        from pinot_tpu.common.trace import span
        from pinot_tpu.engine.device import DeviceUnsupported, \
            segment_device_eligible

        results = []
        executed = []
        scan = []
        scan_pruned: set = set()  # id(s) of scan segments the pruner excluded
        pruned = 0                # segments dropped HERE (non-device paths)
        if segments:
            # per-segment fast paths first: metadata-only aggregation, then
            # star-tree substitution (AggregationPlanNode.java:186-210).
            # Star-tree-eligible segments are GROUPED by tree signature and
            # executed as one batch — a single device launch over all
            # pre-aggregated child segments.
            from pinot_tpu.engine.startree_exec import (
                execute_star_tree_group,
                fitting_tree,
                try_metadata_only,
            )

            remaining = []
            st_groups: dict = {}
            for s in segments:
                is_pruned = self.pruner.prune(q, s)
                if not is_pruned:
                    r = try_metadata_only(q, s)
                    if r is not None:
                        results.append(r)
                        executed.append(s)
                        continue
                hit = fitting_tree(q, s)
                if hit is not None:
                    if is_pruned:
                        pruned += 1
                        continue
                    sig, meta, st_seg = hit
                    grp = st_groups.setdefault(sig, {"meta": meta, "sts": [], "docs": 0})
                    grp["sts"].append(st_seg)
                    grp["docs"] += s.n_docs
                    executed.append(s)
                    continue
                if is_pruned:
                    # device-eligible sealed segments STAY in the scan batch,
                    # alive-masked at launch (DeviceExecutor Level-1) — the
                    # (S, L) batch key, its compiled templates, and the
                    # cohort coalescer key must not depend on which filter
                    # literals pruned what. Other backends drop them here.
                    if not (self.device is not None
                            and segment_device_eligible(s)):
                        pruned += 1
                        continue
                    scan_pruned.add(id(s))
                remaining.append(s)
                executed.append(s)
            # a lone star-tree group with nothing to merge against stays
            # terminal: its cube execution may finalize sketches on device
            st_terminal = (terminal and not results and not remaining
                           and len(st_groups) == 1)
            for grp in st_groups.values():
                results.append(
                    execute_star_tree_group(self, q, grp["meta"], grp["sts"],
                                            grp["docs"], terminal=st_terminal)
                )
            scan = remaining
        device_handles, host_results = [], []
        if scan:
            # consuming (mutable) and upsert-masked segments run on the host
            # scan path; sealed immutables go to the device in one batch.
            # A consuming segment with PROMOTED CHUNKLETS splits: the clean
            # frozen-prefix blocks go to the device, the unfrozen row tail
            # (+ any upsert-dirtied blocks, mask applied) stays on the
            # host, and the partials merge below like any backend mix
            # (realtime/chunklet.py). Chunklets launch as their OWN device
            # batch: promotion changes the chunklet set every 64k rows, and
            # a combined batch key would evict + re-upload the (stable)
            # sealed columns on every promotion.
            from pinot_tpu.realtime.chunklet import split_for_query

            device_sealed, device_chunklets, host_segs = [], [], []
            for s in scan:
                if segment_device_eligible(s):
                    device_sealed.append(s)
                    continue
                split = split_for_query(s) if self.device is not None else None
                if split is None:
                    host_segs.append(s)
                else:
                    device_chunklets.extend(split[0])
                    host_segs.extend(split[1])
            groups = [g for g in (device_sealed, device_chunklets) if g]
            if self.device is not None and groups:
                # device finalize is safe only when ONE device batch is the
                # whole answer: no host segments, no star-tree/metadata
                # partials, no second batch to merge with. The same
                # sole-partial condition gates the on-device final reduce
                # (ops/device_reduce.py): "terminal" when nothing merges
                # after (exact trim to offset+limit), "partial" when a
                # broker still combines server partials (the
                # trim_group_by keep bound, ORDER BY only).
                sole = (not results and not host_segs and len(groups) == 1)
                final = terminal and sole
                reduce_mode = None
                if trim_ok and sole:
                    reduce_mode = "terminal" if terminal else "partial"
                try:
                    for g in groups:
                        # the sealed group's Level-1 verdicts were already
                        # computed by self.pruner above — hand them to the
                        # launch so it doesn't re-derive them. Chunklet
                        # groups compute their OWN per-chunklet verdicts
                        # (the engine pruned the consuming segment as a
                        # whole, not per block).
                        hint = [id(s) not in scan_pruned for s in g] \
                            if g is device_sealed else None
                        handle = self.device.launch(q, g, final=final,
                                                    alive=hint,
                                                    tracer=tracer,
                                                    reduce_mode=reduce_mode)
                        handle.deadline = deadline
                        device_handles.append((handle, g))
                except DeviceUnsupported:
                    for h, _ in device_handles:
                        h.release()
                    device_handles = []
            if not device_handles:
                # launch refused: whole scan on the host — segments the
                # metadata pruner excluded (kept only for device batch-key
                # stability) drop back out rather than host-scan for nothing
                host_segs = [s for s in scan if id(s) not in scan_pruned]
                pruned += len(scan) - len(host_segs)
                if scan_pruned:
                    executed = [s for s in executed
                                if id(s) not in scan_pruned]
            # host partials execute in the launch phase, overlapping the
            # dispatched device batches' link round trip; a host failure
            # must release the in-flight handles or their batch pins leak
            try:
                host_results = []
                with span("engine.host_scan", tracer):
                    for s in host_segs:
                        if deadline is not None:
                            deadline.check("host scan")
                        host_results.append(self.host.execute_segment(q, s))
            except BaseException:
                for h, _ in device_handles:
                    h.release()
                raise

        def fetch():
            res = list(results)
            ran = executed
            fallback_pruned = []  # stats-pruned members of fallen-back handles
            # segments the device answered / the host executor answered
            # for any reason (host scan, fetch-time fallback, a refused
            # or failed launch): the second is ExecutionStats'
            # always-on num_segments_on_host
            on_device, on_host = 0, len(host_results)
            if device_handles:
                # ANY failure below must drop every remaining in-flight
                # launch's batch pin (handle.release is idempotent after
                # fetch), or the batches stay unevictable and the
                # executor's in-flight count never drains — the guard
                # covers QueryTimeout, fallback-gate rejections, AND
                # unexpected errors alike
                pending = list(device_handles)
                try:
                    while pending:
                        handle, segs_of_handle = pending.pop(0)
                        try:
                            res.append(handle.fetch())
                            on_device += sum(id(s) not in scan_pruned
                                             for s in segs_of_handle)
                        except DeviceUnsupported:
                            # fetch-time fallback (sorted group-table
                            # overflow, or a device-runtime failure the
                            # executor converted after counting it toward
                            # its quarantine breaker): the device must
                            # never shape truncation policy. The host
                            # re-scan is heavy CPU work — route it through
                            # the caller's admission gate when one is
                            # provided. Members the metadata pruner
                            # already proved empty (kept in the batch only
                            # for batch-key stability) don't re-scan; they
                            # count as pruned like the launch-refused
                            # path.
                            live = [s for s in segs_of_handle
                                    if id(s) not in scan_pruned]
                            fallback_pruned.extend(
                                s for s in segs_of_handle
                                if id(s) in scan_pruned)

                            on_host += len(live)

                            def _host_rerun(_segs=live):
                                out = []
                                with span("engine.host_fallback", tracer):
                                    for s in _segs:
                                        if deadline is not None:
                                            deadline.check(
                                                "host fallback scan")
                                        out.append(
                                            self.host.execute_segment(q, s))
                                return out

                            res.extend(
                                _host_rerun() if fallback_gate is None
                                else fallback_gate(_host_rerun))
                except BaseException:
                    for h, _ in pending:
                        h.release()
                    raise
            if fallback_pruned:
                dropped = {id(s) for s in fallback_pruned}
                ran = [s for s in ran if id(s) not in dropped]
            res.extend(host_results)
            if not res:
                if segments:
                    # everything pruned: empty result over first segment's
                    # schema
                    ran = [segments[0]]
                    res.append(self.host.execute_segment(
                        _impossible(q), segments[0]))
                else:
                    # EVERY routed segment is cold: honest empty partial
                    # shaped by the cold metadata's zero-doc view (its
                    # stats zero out — the cold docs count below)
                    ran = []
                    empty = self.host.execute_segment(
                        _impossible(q), cold_refs[0].empty_view())
                    empty.stats.num_segments_processed = 0
                    empty.stats.num_segments_queried = 0
                    res.append(empty)

            with span("engine.merge", tracer) as merge_span:
                merged = merge_intermediates(q, res)
            merge_span.set(segmentsOnDevice=on_device,
                           segmentsOnHost=on_host)
            merged.stats.num_segments_on_host += on_host
            # per-flight roofline records (ISSUE 11) concatenate across
            # partials (merge_intermediates builds a fresh result; the
            # single-partial shortcut passes its own list through)
            roofs = [rec for r in res if getattr(r, "roofline", None)
                     for rec in r.roofline]
            if roofs:
                merged.roofline = roofs
            # device partials carry their own launch-level pruned counts
            # (alive-masked batch members); add the segments dropped here
            merged.stats.num_segments_pruned += pruned + len(fallback_pruned)
            merged.stats.num_segments_queried = len(all_segments)
            # cold segments answered nothing this execution: the partial
            # is honest about it (numSegmentsCold) and their docs still
            # count toward totalDocs below like any unexecuted segment
            merged.stats.num_segments_cold += len(cold_refs)
            # pruned segments still count toward totalDocs (reference
            # semantics)
            executed_ids = {id(s) for s in ran}
            for s in all_segments:
                if id(s) not in executed_ids:
                    merged.stats.total_docs += s.n_docs
            return merged

        return fetch

    # ---- dimension-table lookup (DimensionTableDataManager analog) -------
    def dim_table_lookup(self, dim_table: str, value_col: str, pk_col: str):
        """(pk value → value_col value, miss default) over all hosted
        segments of the dimension table; cached until the table's segment
        set changes (LookupTransformFunction resolves against this map).
        The miss default comes from the value column's TYPE, not a sample
        row, so empty dim tables keep numeric semantics."""
        tdm = self.tables.get(dim_table) or self.tables.get(f"{dim_table}_OFFLINE")
        if tdm is None:
            raise KeyError(f"dimension table {dim_table!r} not hosted here")
        if getattr(tdm, "is_dim_table", None) is False:
            # cluster mode: a regular table's segments are spread across
            # servers, so a local pk map would be silently incomplete — the
            # reference's LookupTransformFunction rejects these the same way
            raise ValueError(f"LOOKUP target {dim_table!r} is not a "
                             f"dimension table (is_dim_table=false)")
        key = (tdm.name, pk_col, value_col)
        cached = self._dim_cache.get(key)
        if cached is not None and cached[0] == tdm.generation:
            return cached[1], cached[2]
        import numpy as np

        gen = tdm.generation
        mapping: dict = {}
        default = ""
        segs = tdm.acquire()
        try:
            if not segs:
                raise KeyError(f"dimension table {dim_table!r} has no "
                               f"segments loaded here")
            dt = segs[0].column_metadata(value_col).data_type
            default = "" if dt.is_string_like else dt.np_dtype.type(0).item()
            for seg in segs:
                pks = np.asarray(seg.values(pk_col))
                vals = np.asarray(seg.values(value_col))
                for k, v in zip(pks.tolist(), vals.tolist()):
                    mapping[k] = v
        finally:
            tdm.release(segs)
        self._dim_cache[key] = (gen, mapping, default)
        return mapping, default

    # ---- helpers ---------------------------------------------------------
    @staticmethod
    def _expand_star(q: QueryContext, seg: ImmutableSegment) -> QueryContext:
        from pinot_tpu.query.rewrite import expand_star

        return expand_star(q, seg.column_names())

    def _explain(self, q: QueryContext) -> dict:
        from pinot_tpu.engine.explain import explain_plan

        return explain_plan(self, q)

    def _explain_analyze(self, q: QueryContext, t0: float) -> dict:
        """EXPLAIN ANALYZE (ISSUE 11): execute the underlying query for
        real (traced, so the phase ladder fills), then render the plan
        tree annotated with per-node actuals. The executed response rides
        along as ``analyzedResponse`` so callers can verify the results
        are bit-identical to the non-ANALYZE form."""
        import dataclasses

        from pinot_tpu.common.trace import Tracer
        from pinot_tpu.engine.explain import annotate_analyze, explain_plan

        # the partials cache is bypassed for the analyzed run: a cache
        # hit skips the kernel entirely, and the point of ANALYZE is to
        # MEASURE it (results are bit-identical either way — pinned by
        # the subrtt differential suite)
        q_run = dataclasses.replace(
            q, explain=False, analyze=False,
            options=q.options + (("usePartialsCache", False),))
        tracer = Tracer("analyze")
        result, merged = self._execute_merged(q_run, tracer=tracer)
        resp = self._stats_response(result, merged, t0)
        resp["traceInfo"] = {"server": tracer.to_json()}
        out = annotate_analyze(explain_plan(self, q), resp)
        out["analyzedResponse"] = resp
        return out


def _impossible(q: QueryContext):
    import dataclasses

    return dataclasses.replace(q, filter=FilterNode.FALSE)
