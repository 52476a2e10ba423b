"""In-flight launch handles + cross-query launch coalescing.

The device executor's hot path splits into an async **launch** phase
(template build + column gather + non-blocking XLA dispatch — JAX dispatch
is already asynchronous, only ``jax.device_get`` blocks) and a **fetch**
phase that resolves the packed output buffer. ``InflightLaunch`` is the
handle between the two: N concurrent queries overlap their host↔device
round trips instead of serializing them on the transport threads, and the
server releases its scheduler slot before the link wait (the per-server
many-requests-in-flight posture of the reference's scatter-gather model —
a Pinot server keeps many segment queries in flight to hide exactly this
latency).

On the served path every request dispatches its own program at once
(``DeviceExecutor._solo_launch``): a launch waits for no other request to
arrive and for no other launch's fetch. A shared launch is not cheaper on
the device than its members apart, and it costs its leader the stacking
of the members' params and the hold (PERF.md, PR 35).

``LaunchCoalescer`` is what is left of the hold: under ``force``, the
tests' switch, concurrent queries sharing one (batch, template,
param-shape) cohort key — same SQL shape with different literals — stack
their params along a leading axis and execute as ONE vmapped launch whose
result comes back as ONE packed buffer. The leader holds a fixed
micro-batch window for its members. Nothing the load can set opens a
window. If a cell ever shows a device with a backlog of short launches,
the hold to write keys off that backlog (ROADMAP D7).

``DeviceTimeline`` is the device's side of a launch: the executor's
launches in the order they were dispatched, and the instant each ended on
the device, so that a fetch's wait splits into the time the launch queued
behind the launches before it and the time the device spent on it.
"""

from __future__ import annotations

import queue
import threading
import time
from collections import deque

from pinot_tpu.common import trace
from pinot_tpu.common.trace import span as trace_span
from pinot_tpu.engine.params import KeySpaceFull


class DeviceLaunch:
    """One launch on the device's timeline (``perf_counter`` seconds):
    enqueued at ``t_dispatched`` behind ``ahead`` launches that had not
    ended; ``t_end``, the first instant anyone saw it ready; ``prev_end``,
    the end of the launch dispatched before it. ``traced``: a traced
    request's, whose end is written to the profiler's trace."""

    __slots__ = ("launch_id", "t_dispatched", "ahead", "t_end", "prev_end",
                 "bufs", "traced")

    def __init__(self, launch_id, t_dispatched: float, ahead: int, bufs,
                 traced: bool = False):
        self.launch_id, self.t_dispatched, self.ahead = \
            launch_id, t_dispatched, ahead
        self.traced = traced
        self.t_end = None
        self.prev_end = float("-inf")
        self.bufs = bufs  # what the waiter blocks on; dropped at the end

    @property
    def queue_s(self) -> float:
        """Dispatch to the end of the launch before it: one device runs
        its launches in the order they were enqueued."""
        return max(0.0, min(self.prev_end, self.t_end) - self.t_dispatched)

    @property
    def run_s(self) -> float:
        """The device's time on this launch: from the later of its
        dispatch and the previous launch's end, to its own end. Holds the
        device's idle time between enqueue and first operation besides."""
        return self.t_end - max(self.t_dispatched, self.prev_end)

    def on_device(self) -> dict:
        """What its wait span and flight record say of it."""
        return {"deviceQueueMs": round(self.queue_s * 1e3, 3),
                "deviceRunMs": round(self.run_s * 1e3, 3),
                "launchesAhead": self.ahead}


class DeviceTimeline:
    """The served launches of one device (a mesh is one), in the order
    they were dispatched, each stamped with its end on the device.

    The ends are stamped in dispatch order by whoever first sees one: a
    waiter thread that blocks on the oldest launch not yet ended, or the
    launch's fetch once its own wait returns (``seen``) — the device ran
    every launch before it first, so those end no later. A launch fetched
    long after its end still gets the instant the waiter saw; a fetch
    never waits for the waiter. The waiter's blocking is written nowhere:
    a traced launch's end is a zero-length ``pinot.executor.device_end``
    in the profiler's trace, carrying ``launch_id``. Device work outside a
    served launch (an operand build, a key-space probe) is not on the
    timeline: it counts toward the next launch's run. A launch is
    registered once its program is enqueued; two callers whose enqueues
    cross before they register are stamped in the order they registered,
    which keeps the runs apart and their sum whole but gives the earlier
    one's run to the later."""

    # the waiter leaves after this long with nothing dispatched and is
    # started again by the next launch
    IDLE_EXIT_S = 1.0

    def __init__(self):
        self._lock = threading.Lock()
        self._open: deque = deque()  # dispatched, end not yet seen
        # the same launches, handed to the waiter: a queue of C, so that a
        # launch costs the interpreter two short wakes of the waiter and
        # no Python condition (an interpreter-bound server pays for each)
        self._to_wait: queue.SimpleQueue = queue.SimpleQueue()
        self._last_end = float("-inf")
        self._waiter = None
        self.ended = 0  # launches stamped so far

    def dispatched(self, launch_id, bufs, traced: bool = False
                   ) -> DeviceLaunch:
        """Register a launch whose program was just enqueued; ``bufs``:
        its output buffers."""
        with self._lock:
            launch = DeviceLaunch(launch_id, time.perf_counter(),
                                  len(self._open), bufs, traced)
            self._open.append(launch)
            self._to_wait.put(launch)
            if self._waiter is None:
                self._waiter = threading.Thread(
                    target=self._wait, daemon=True, name="pinot-device-end")
                self._waiter.start()
        return launch

    def seen(self, launch: DeviceLaunch, t: float) -> None:
        """``launch`` was seen ready at ``t``: stamp it and the open
        launches dispatched before it, unless someone saw it first."""
        ended = []
        with self._lock:
            while launch.t_end is None and self._open:
                head = self._open.popleft()
                head.prev_end = self._last_end
                head.t_end = self._last_end = max(t, self._last_end,
                                                  head.t_dispatched)
                head.bufs = None
                ended.append(head)
            self.ended += len(ended)
        for e in ended:
            if e.traced:
                trace.mark("executor.device_end", launch_id=e.launch_id)

    def _wait(self) -> None:
        import jax

        while True:
            try:
                launch = self._to_wait.get(timeout=self.IDLE_EXIT_S)
            except queue.Empty:
                with self._lock:
                    if self._to_wait.empty():
                        self._waiter = None
                        return
                continue
            bufs = launch.bufs
            if bufs is None:
                continue  # its fetch, or a later launch's, saw it end
            try:
                jax.block_until_ready(bufs)
            except Exception:  # noqa: BLE001 — a failed launch ended too;
                pass           # its fetch reports the failure
            self.seen(launch, time.perf_counter())


class InflightLaunch:
    """A dispatched-but-not-fetched device launch.

    ``fetch()`` blocks on the host link (the ONLY blocking step), unpacks
    the packed buffer, and builds the canonical IntermediateResult. The
    batch the launch reads from is refcounted against LRU eviction until
    the fetch completes (``DeviceExecutor._retain_launch`` /
    ``_release_launch``) — without the pin, a concurrent query's
    ``_evict`` could drop the HBM blocks this launch is still reading.
    """

    def __init__(self, executor, q, ctx, template, aggs, batch_key, resolve):
        self._executor = executor
        self._q = q
        self._ctx = ctx
        self._template = template
        self._aggs = aggs
        self._batch_key = batch_key
        self._resolve = resolve
        self._done = False
        # optional per-query Deadline (common/deadline.py), set by the
        # engine when the request carried a budget: an expired deadline
        # aborts BEFORE the blocking device_get (which itself cannot be
        # interrupted) with a typed QueryTimeout
        self.deadline = None
        # optional explicit Tracer (common/trace.py), set by the executor
        # when the query is traced: the fetch phase may run on a different
        # thread than the launch (PR-2 split) or ride a cohort whose
        # shared buffer another member resolves — every member records
        # its own waits on its own trace (_traced_resolve)
        self.tracer = None
        # True when the launch was served from the device partials cache
        # (no gather/dispatch/kernel — the fetch re-reads a cached packed
        # buffer); surfaces as the result's partialsCacheHit stat
        self.cache_hit = False
        # roofline flight dict (ISSUE 11), set by the executor when
        # accounting is on: the resolve fills flight["record"] with the
        # modeled-bytes/kernel-ms/GB/s record, and fetch() folds it into
        # the result's stats + roofline list. Cohort members other than
        # the leader carry an unfilled flight (the shared kernel is
        # attributed once, to the leader's trace and record).
        self.flight = None

    def fetch(self):
        """Blocking phase: resolve the packed buffer → IntermediateResult.
        Raises DeviceUnsupported on fetch-time fallbacks (sorted group
        table overflow) — the caller re-runs the batch on the host path —
        and QueryTimeout when the query's deadline expired before the
        link wait began. One-shot: the batch pin is dropped whether or
        not it succeeds."""
        if self._done:
            raise RuntimeError("InflightLaunch.fetch() called twice")
        self._done = True
        try:
            if self.deadline is not None:
                self.deadline.check("device fetch")
            try:
                outs = self._resolve() if self.tracer is None \
                    else self._traced_resolve(self.tracer)
            except Exception as e:  # noqa: BLE001 — may convert to fallback
                # device-runtime failures (XlaRuntimeError /
                # RESOURCE_EXHAUSTED, real or injected) convert to the
                # host-fallback signal after the executor records them
                # toward the quarantine breaker; anything else re-raises
                self._executor.on_fetch_device_error(
                    e, self._template, self._batch_key,
                    getattr(self, "used_pallas", False))
                raise
            # success clears the quarantine breaker's strike count — the
            # breaker is for failures close together, not two transient
            # faults a week apart
            self._executor._note_device_success(
                self._template, self._batch_key)
            adv_key = getattr(self, "adv_key", None)
            try:
                with trace_span("executor.unpack", self.tracer):
                    result = self._executor._to_intermediate(
                        self._q, self._ctx, self._template, outs,
                        self._aggs, cache_hit=self.cache_hit,
                        adv_key=adv_key,
                        adv_trim_keep=getattr(self, "adv_trim_keep", None))
            except KeySpaceFull:
                # the narrowed table overflowed and the executor now knows
                # the template full on this batch: the same statement,
                # launched again, sums over the whole key space on the
                # device (this launch's pin drops in the finally below;
                # the new launch holds its own)
                again = getattr(self, "relaunch", None)
                if again is None:
                    raise
                handle = again()
                handle.deadline = self.deadline
                return handle.fetch()
            result.stats.partials_cache_hit = self.cache_hit
            # plan-advisor stamps + cache-hit feedback (ISSUE 17): the
            # decisions this launch ran with ride the result's stats to
            # the response / querylog / EXPLAIN ANALYZE, and the
            # partials-cache outcome feeds the template's memo
            notes = getattr(self, "advisor_notes", None)
            if notes:
                result.stats.advisor_decisions.extend(notes)
            advisor = getattr(self._executor, "advisor", None)
            if adv_key is not None and advisor is not None:
                advisor.observe(adv_key, partials_hit=self.cache_hit)
            rec = None if self.flight is None else self.flight.get("record")
            if rec is not None:
                # per-query roofline accounting (ISSUE 11): the flight's
                # record rides the result so servers ship it in DataTable
                # metadata and the broker/EXPLAIN ANALYZE render it
                result.roofline = [rec]
                st = result.stats
                st.device_bytes_moved += int(rec.get("bytesMoved") or 0)
                st.device_kernel_ms += float(rec.get("kernelMs") or 0.0)
                st.device_queue_ms += float(rec.get("queueMs") or 0.0)
                st.device_run_ms += float(rec.get("runMs") or 0.0)
                st.device_link_ms += float(rec.get("linkMs") or 0.0)
            return result
        finally:
            self._executor._release_launch(self._batch_key)

    def _traced_resolve(self, tracer):
        """``_resolve()`` for a traced member, on its own thread and its
        own tracer. Whoever runs a launch's one fetch records
        ``executor.device_wait`` / ``link`` / ``unpack`` as it goes (the
        shared resolve spans the thread's ACTIVE tracer, so this member's
        is made that); every other member back-fills its wait from the
        clock here and the cohort's ``t_dispatched``. A member that is
        not the leader waited first for the leader's dispatch
        (``executor.launch_wait``), then for the device. Exactly one
        span of a launched request — its ``executor.device_wait`` —
        carries ``launchId``, ``cohortSize``, ``cohortPadded``, ``role``
        and ``windowKind`` (``fixed`` for a cohort, ``none`` for a launch
        of its own: every served one)."""
        from pinot_tpu.common import trace

        r = self._resolve
        prev = trace.activate(tracer)
        t_enter = time.perf_counter()
        try:
            outs = r()
        finally:
            trace.activate(prev)
        t_exit = time.perf_counter()
        cohort = getattr(r, "cohort", None)
        stamp = getattr(r, "stamp", None) if cohort is None else cohort.stamp
        if stamp is None:
            return outs  # nothing was launched (a fully pruned batch)
        mine = {"role": "solo", "windowKind": "none"} if cohort is None \
            else {"role": "member" if r.index else "leader",
                  "windowKind": "fixed"}
        t_dispatched = t_enter
        if mine["role"] == "member":
            t_dispatched = min(max(cohort.t_dispatched, t_enter), t_exit)
            tracer.record("executor.launch_wait", t_enter, t_dispatched)
        wait_span = stamp.get("wait_span")
        if wait_span is not None and wait_span.tracer is tracer:
            wait_span.set(**mine)  # this member ran the fetch
        else:
            tracer.record("executor.device_wait", t_dispatched, t_exit,
                          attrs={**stamp["attrs"], **mine})
        return outs

    def release(self):
        """Abandon without fetching: drop the batch pin. Callers that fail
        BETWEEN launch and fetch (e.g. a host-segment partial raising
        while the device batch is in flight) must call this, or the pin
        leaks — the batch would stay unevictable and the executor's
        inflight count never drains. Idempotent with fetch(); safe to
        call on an already-fetched handle."""
        if not self._done:
            self._done = True
            self._executor._release_launch(self._batch_key)


class _Cohort:
    """One coalesced launch: the leader stacks every member's params and
    dispatches once; the shared packed buffer is fetched once (first
    ``resolve_member`` wins) and each member slices its row."""

    # liveness poll: a member waits as long as the leader THREAD is alive
    # (a first dispatch jit-compiles the whole vmapped pipeline, which can
    # far exceed any fixed timeout) but must not wait forever on a leader
    # that died mid-window
    READY_POLL_S = 5.0

    def __init__(self, launch_fn):
        self._launch_fn = launch_fn
        # what the members' traces say of this launch: the instant the
        # one stacked launch was dispatched, and the shared resolve's
        # launch stamp (DeviceExecutor._make_resolve: launchId, sizes)
        self.t_dispatched = None
        self.stamp = None
        self.leader_thread = threading.current_thread()  # creator leads
        self.members = []          # per-member params dicts, join order
        self.open = True           # False once the window closed
        self.full = threading.Event()  # hit max_cohort: leader stops waiting
        self.ready = threading.Event()
        self.error = None          # leader's dispatch failure, if any
        self._shared_resolve = None
        self._fetch_lock = threading.Lock()
        self._outs = None
        self._exc = None
        self._fetched = False

    def dispatch(self):
        """Leader only: one stacked launch for the whole cohort."""
        try:
            self._shared_resolve = self._launch_fn(self.members)
            self.stamp = getattr(self._shared_resolve, "stamp", None)
            self.t_dispatched = time.perf_counter()
        except BaseException as e:  # noqa: BLE001 — members must observe it
            self.error = e
        finally:
            self.ready.set()

    def resolve_member(self, idx: int) -> dict:
        """Member ``idx``'s unpacked outputs. The shared buffer crosses
        the link ONCE; every member's slice comes from that one fetch."""
        while not self.ready.wait(self.READY_POLL_S):
            # slow-but-alive leader (e.g. first jit compile of the cohort
            # pipeline) keeps members waiting; a dead one fails them fast
            if not self.leader_thread.is_alive():
                raise RuntimeError(
                    "coalesced launch leader died before dispatch")
        if self.error is not None:
            raise self.error
        with self._fetch_lock:
            if not self._fetched:
                try:
                    self._outs = self._shared_resolve()
                except BaseException as e:  # noqa: BLE001 — shared failure
                    self._exc = e
                self._fetched = True
        if self._exc is not None:
            raise self._exc
        return {k: v[idx] for k, v in self._outs.items()}


class LaunchCoalescer:
    """Micro-batches concurrent same-template launches into one vmapped
    dispatch, under ``force`` only. Pure synchronization — the executor
    supplies the actual stacked-launch closure
    (``DeviceExecutor._cohort_launch``)."""

    def __init__(self, window_s: float = 0.003, max_cohort: int = 8):
        self.window_s = window_s      # leader's micro-batch window
        self.max_cohort = max_cohort  # vmap width cap (bounds recompiles)
        self.force = False            # tests: every launch opens a window
        self._lock = threading.Lock()
        self._pending: dict = {}      # cohort key -> open _Cohort
        # observability
        self.cohorts_launched = 0
        self.queries_coalesced = 0    # members that joined past the leader

    def should_window(self) -> bool:
        """Gate: no served launch waits for another request, whatever the
        load — a window opens under ``force`` and never otherwise."""
        return self.force

    def join(self, key, params: dict, launch_fn):
        """Join (or open) the cohort for ``key`` → (cohort, member index).

        The FIRST arrival becomes leader: it holds the window open for
        ``window_s``, then closes the cohort and dispatches one stacked
        launch built by ``launch_fn(members)``. Later arrivals append
        their params and return immediately — they block only inside
        ``resolve_member`` (their fetch phase), so a member's scheduler
        slot is released while the leader's launch is still in flight.
        """
        with self._lock:
            c = self._pending.get(key)
            if c is not None and c.open:
                idx = len(c.members)
                c.members.append(params)
                if len(c.members) >= self.max_cohort:
                    c.open = False          # full: stop accepting members
                    self._pending.pop(key, None)
                    c.full.set()            # leader dispatches immediately
                self.queries_coalesced += 1
                return c, idx
            c = _Cohort(launch_fn)
            c.members.append(params)
            self._pending[key] = c
        # leader: hold the micro-batch window open — but a cohort that
        # fills to max_cohort early dispatches immediately (the remaining
        # window would be pure added latency for everyone in it)
        c.full.wait(self.window_s)
        with self._lock:
            c.open = False
            if self._pending.get(key) is c:
                self._pending.pop(key, None)
            self.cohorts_launched += 1
        c.dispatch()
        return c, 0
