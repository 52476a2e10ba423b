"""Request tracing: one tree of spans per query, from the HTTP door to the
device launch.

Equivalent of the reference's trace SPI
(pinot-spi/.../trace/Tracing.java:32 + RequestContext /
DefaultRequestContext and the broker's ``trace`` query option): when the
query sets ``SET trace = true`` every layer records named spans; they
ride back in the broker response as ``traceInfo`` (the reference's
BrokerResponse trace payload), stay in a bounded in-memory ring for
whoever reads them after the fact (``finished``), and the spans in which
a thread of the program *runs* are written into the JAX profiler's trace
as ``pinot.<name>`` so the device trace and the program's phases share
one clock.

A span is ``name, span_id, parent_id, start, end, cpu_ms, attrs``. The
parent is the span that caused it: the enclosing span on the same thread
or, across a thread or process seam, the span whose id travelled with
the work (the scatter request ships ``traceId`` and ``parentSpanId``).
``cpu_ms`` is ``time.thread_time()`` over the span on the thread that ran
it; wall less CPU is time spent waiting (interpreter lock, locks, device,
queue). ``attrs`` is a small dict of counts set where the work happens.

The tracer is an EXPLICIT, wire-portable object, not thread state: the
broker mints one per traced request, the server mints its own under the
same ``trace_id`` and threads it through the async launch/fetch split
(``InflightLaunch`` and the ``execute_segments_async`` fetch closure carry
it by reference). A thread-local slot remains for call sites that span
the CURRENT request without plumbing (``span(name)`` with no tracer).
Tracing off costs one attribute read per span: no ``Tracer``, no kept
trace, no profiler annotation.
"""

from __future__ import annotations

import itertools
import os
import threading
import time
from collections import deque
from typing import Optional

# finished tracers kept in memory, newest last; a request that crossed
# one server is two of them (broker's and server's, one trace_id)
RING_BOUND = 16384


def _cpu_read_cost() -> float:
    """Seconds one ``time.thread_time()`` costs on this host."""
    best = float("inf")
    for _ in range(5):
        t = time.perf_counter()
        time.thread_time()
        best = min(best, time.perf_counter() - t)
    return best


# time.thread_time() can be a system call where perf_counter() is not
# (6 us against 0.07 on the chip's host, 0.2 us elsewhere): a span that
# opens or closes within a hundred reads' worth of its thread's last
# CPU-clock read reuses that read, so the clock costs a thread at most a
# hundredth of its time. Back-to-back spans and a parent with its first
# child then cost one read, not two; a span shorter than this reads 0 and
# its neighbour takes its CPU, the sum over a thread's spans stays whole.
CPU_READ_REUSE_S = max(20e-6, 100 * _cpu_read_cost())

_local = threading.local()
_ring: deque = deque(maxlen=RING_BOUND)
_ring_lock = threading.Lock()
# span ids are unique across the tracers of one trace: a process-wide
# counter, offset by the pid so broker and server processes do not clash
_ids = itertools.count(((os.getpid() & 0xFFFFF) << 32) | 1)
_annotation = None


def _annotate(name: str, **ids):
    """``jax.profiler.TraceAnnotation`` — a no-op TraceMe unless a
    profiler session is on; stable name, ids only as arguments."""
    global _annotation
    if _annotation is None:
        from jax.profiler import TraceAnnotation

        _annotation = TraceAnnotation
    return _annotation("pinot." + name, **ids)


def mark(name: str, **ids) -> None:
    """A zero-length ``pinot.<name>`` in the profiler's trace: an instant
    of a traced request that the device trace can be lined up against.
    Nothing is kept."""
    ann = _annotate(name, **ids)
    ann.__enter__()
    ann.__exit__(None, None, None)


class Span:
    """One span, and the context manager that records it. With
    ``tracer`` None every method is a no-op."""

    __slots__ = ("tracer", "name", "span_id", "parent_id", "t0", "t1",
                 "cpu_ms", "attrs", "quiet", "_c0", "_ann")

    def __init__(self, tracer, name: str, quiet: bool = False):
        self.tracer, self.name, self.quiet = tracer, name, quiet
        self.span_id = self.parent_id = self.attrs = self.t1 = None

    def set(self, **attrs) -> None:
        """Counts of the work done, set where it happens."""
        if self.tracer is not None:
            if self.attrs is None:
                self.attrs = attrs
            else:
                self.attrs.update(attrs)

    def _open(self, t0: float = None, c0: float = None) -> "Span":
        t = self.tracer
        stack = t._stack()
        self.parent_id = stack[-1].span_id if stack else t._default_parent()
        self.span_id = next(_ids)
        stack.append(self)
        self._ann = None
        if not self.quiet:
            ids = {"trace_id": t.trace_id}
            if self.attrs and "launchId" in self.attrs:
                ids["launch_id"] = self.attrs["launchId"]
            self._ann = _annotate(self.name, **ids)
            self._ann.__enter__()
        self.t0 = time.perf_counter() if t0 is None else t0
        self._c0 = t._thread_time(self.t0) if c0 is None else c0
        return self

    def _leave(self, t1: float) -> None:
        self.t1 = t1
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
            self._ann = None
        stack = self.tracer._stack()
        if self in stack:
            stack.remove(self)

    def close(self, t1: float = None) -> None:
        t = self.tracer
        if t is None or self.t1 is not None:
            return
        now = time.perf_counter()
        self.cpu_ms = (t._thread_time(now) - self._c0) * 1000
        self._leave(now if t1 is None else t1)
        with t._lock:
            t.spans.append(self)
        if self is t.root:
            _keep(t)

    def cancel(self) -> None:
        """Leave without a record: a span opened for a wait that turned
        out not to be this thread's (a member's join returns at once)."""
        if self.tracer is not None and self.t1 is None:
            self._leave(self.t0)

    def __enter__(self):
        if self.tracer is not None:
            self._open()
        return self

    def __exit__(self, *exc):
        self.close()
        return False


_NO_SPAN = Span(None, "")


class Entry:
    """The clock reads the HTTP door takes for EVERY request, before the
    parse that finds the ``trace`` option: two around the body's read and
    decode. Once the option is known the broker back-fills
    ``http.request`` and ``http.read`` from them and leaves its tracer
    here, for ``http.write`` and the close of the root."""

    __slots__ = ("t0", "c0", "t1", "c1", "bytes_in", "tracer")

    def __init__(self):
        self.tracer = None
        self.c0 = time.thread_time()
        self.t0 = time.perf_counter()

    def read_done(self, bytes_in: int) -> None:
        self.t1 = time.perf_counter()
        self.c1 = time.thread_time()
        self.bytes_in = bytes_in


class Tracer:
    """One query's spans on one side of a process seam. Thread-safe: the
    launch thread, the fetch thread, and a cohort's fetching member may
    all record concurrently. Nesting is tracked PER THREAD so concurrent
    recorders can't take each other for a parent. The first span
    ``open()``ed is the ROOT; when it closes the tracer is kept in the
    ring."""

    __slots__ = ("trace_id", "parent_id", "spans", "root", "wall0", "_t0",
                 "_lock", "_stacks", "_cpu_reads")

    def __init__(self, trace_id: Optional[str] = None,
                 parent_id: Optional[int] = None, t0: float = None):
        """``parent_id``: the span, in another tracer of this trace, that
        caused this tracer's root. ``t0``: a ``perf_counter`` reading
        taken before the tracer could exist (the request's entry), the
        origin every ``startMs`` counts from."""
        now = time.perf_counter()
        self._t0 = now if t0 is None else t0
        # the wall clock of the origin: every span has a start on the
        # clock the benchmark stamps its traced slice with
        self.wall0 = time.time() - (now - self._t0)
        self.trace_id = trace_id
        self.parent_id = parent_id
        self.spans: list = []  # closed Span objects
        self.root: Optional[Span] = None
        self._lock = threading.Lock()
        self._stacks: dict = {}  # thread ident -> open spans, outermost first
        self._cpu_reads: dict = {}  # thread ident -> (perf_counter, thread_time)

    def _stack(self) -> list:
        ident = threading.get_ident()
        stack = self._stacks.get(ident)
        if stack is None:
            stack = self._stacks[ident] = []
        return stack

    def _thread_time(self, now: float) -> float:
        """The calling thread's CPU clock at ``now``, read at most once
        per ``CPU_READ_REUSE_S``."""
        ident = threading.get_ident()
        last = self._cpu_reads.get(ident)
        if last is not None and now - last[0] < CPU_READ_REUSE_S:
            return last[1]
        cpu = time.thread_time()
        self._cpu_reads[ident] = (now, cpu)
        return cpu

    def _default_parent(self) -> Optional[int]:
        """The parent of a span opened on a thread with no open span:
        the root (work handed to another thread), else the remote one."""
        return self.root.span_id if self.root is not None else self.parent_id

    # ---- recording -------------------------------------------------------
    def span(self, name: str, quiet: bool = False) -> Span:
        return Span(self, name, quiet)

    def open(self, name: str, t0: float = None, c0: float = None) -> Span:
        """Open a span that a later ``close()`` ends — the roots, whose
        start may be a clock reading taken before the tracer existed.
        Never written to the profiler: an enclosing span would be the
        label of every idle gap under it."""
        s = Span(self, name, quiet=True)._open(t0, c0)
        if self.root is None:
            self.root = s
        return s

    def record(self, name: str, t_start: float, t_end: float,
               cpu_ms: float = None, attrs: dict = None) -> None:
        """Back-fill one finished span from ``perf_counter`` endpoints
        measured by someone else (clock reads taken before the ``trace``
        option was known; a wait the cohort stamped). Its parent is the
        innermost open span of the calling thread."""
        s = Span(self, name, quiet=True)
        stack = self._stack()
        s.parent_id = stack[-1].span_id if stack else self._default_parent()
        s.span_id = next(_ids)
        s.t0, s.t1, s.cpu_ms, s.attrs = t_start, t_end, cpu_ms, attrs
        with self._lock:
            self.spans.append(s)

    def end(self) -> None:
        """Close the root if it is still open (an error path left it)."""
        if self.root is not None:
            self.root.close()

    # ---- export ----------------------------------------------------------
    def to_json(self) -> list:
        """Every span so far; the ones still open (the roots, the span
        that builds the response) end now."""
        now = time.perf_counter()
        with self._lock:
            spans = list(self.spans)
        spans += [s for stack in list(self._stacks.values()) for s in stack]
        out = []
        for s in sorted(spans, key=lambda s: s.t0):
            d = {"phase": s.name,
                 "startMs": round((s.t0 - self._t0) * 1000, 3),
                 "durationMs": round(
                     ((now if s.t1 is None else s.t1) - s.t0) * 1000, 3),
                 "spanId": s.span_id, "parentId": s.parent_id}
            if s.t1 is not None and s.cpu_ms is not None:
                d["cpuMs"] = round(s.cpu_ms, 3)
            if s.attrs:
                d["attrs"] = s.attrs
            out.append(d)
        return out


def _keep(tracer: Tracer) -> None:
    with _ring_lock:
        _ring.append(tracer)


def finished(since: float = float("-inf"),
             until: float = float("inf")) -> list:
    """The kept tracers whose root started in ``[since, until)`` on the
    wall clock, oldest first."""
    with _ring_lock:
        kept = list(_ring)
    out = []
    for t in kept:
        start = t.wall0 + (t.root.t0 - t._t0)
        if since <= start < until:
            out.append(t)
    return out


def start_trace(trace_id: Optional[str] = None, t0: float = None) -> Tracer:
    """Install a tracer for this thread (request entry point). The
    returned object should ALSO be carried explicitly across thread
    seams — the thread-local slot only covers same-thread call sites."""
    t = Tracer(trace_id, t0=t0)
    _local.tracer = t
    return t


def end_trace() -> None:
    _local.tracer = None


def activate(tracer: Optional[Tracer]) -> Optional[Tracer]:
    """Make ``tracer`` the calling thread's active one and return the one
    it replaces — for a stretch of shared code that must record on the
    tracer of whoever runs it (a cohort's one fetch)."""
    prev = getattr(_local, "tracer", None)
    _local.tracer = tracer
    return prev


def active() -> Optional[Tracer]:
    return getattr(_local, "tracer", None)


def span(name: str, tracer: Optional[Tracer] = None,
         quiet: bool = False) -> Span:
    """Context manager recording a span on ``tracer`` (explicit — works
    from any thread) or, when omitted, on the calling thread's active
    tracer; a shared no-op when tracing is off. ``quiet``: recorded in
    the tree but not written to the profiler — for a span that encloses
    others or only waits for another span of the same request."""
    t = tracer if tracer is not None else getattr(_local, "tracer", None)
    return _NO_SPAN if t is None else Span(t, name, quiet)
