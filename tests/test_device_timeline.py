"""A launch's wait on the device, split where it happens.

``DeviceTimeline`` (engine/inflight.py) keeps the executor's served
launches in the order they were dispatched and stamps each one's end on
the device, in that order, by whoever first sees it: its waiter thread or
the launch's own fetch. A launch's ``deviceQueueMs`` runs from its
dispatch to the end of the launch before it, its ``deviceRunMs`` from
there to its own end. These tests hold the arithmetic, the order, the
carriers (span attributes, flight record, response stats, ``/metrics``)
and the benchmark's two readers of them.
"""

import os
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pinot_tpu.common import trace
from pinot_tpu.common.datatypes import DataType
from pinot_tpu.common.metrics import get_metrics
from pinot_tpu.common.schema import Schema
from pinot_tpu.common.table_config import TableConfig
from pinot_tpu.engine.engine import QueryEngine
from pinot_tpu.engine.inflight import DeviceTimeline
from pinot_tpu.storage.creator import build_segment
from pinot_tpu.storage.segment import ImmutableSegment

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SQL = "SELECT tag, COUNT(*), SUM(v) FROM t GROUP BY tag ORDER BY tag"


class Gate:
    """An output buffer whose readiness the test decides."""

    def __init__(self):
        self.done = threading.Event()

    def block_until_ready(self):
        assert self.done.wait(30), "the gate was never opened"
        return self


def _ended(timeline, n, timeout=10.0):
    t0 = time.perf_counter()
    while timeline.ended < n:
        assert time.perf_counter() - t0 < timeout, timeline.ended
        time.sleep(0.002)


@jax.jit
def _slow(x):
    for _ in range(6):
        x = jnp.tanh(x @ x)
    return x.sum()


@jax.jit
def _quick(x):
    return x.sum()


# ---------------------------------------------------------------------------
# the timeline
# ---------------------------------------------------------------------------


def test_back_to_back_launches_on_the_cpu_backend():
    """The second launch, dispatched while the first runs, queues until
    the first's end and runs from there; queue + run is dispatch to end."""
    big = jnp.ones((1200, 1200), jnp.float32) * 1e-3
    small = jnp.ones((8,), jnp.float32)
    _slow(big).block_until_ready()
    _quick(small).block_until_ready()
    tl = DeviceTimeline()
    first = tl.dispatched(1, _slow(big))
    second = tl.dispatched(2, _quick(small))
    assert (first.ahead, second.ahead) == (0, 1)
    jax.block_until_ready(_quick(small))
    _ended(tl, 2)
    assert second.prev_end == first.t_end
    assert second.t_dispatched < first.t_end
    assert second.t_dispatched + second.queue_s \
        == pytest.approx(first.t_end, abs=1e-9)
    assert second.run_s == pytest.approx(second.t_end - first.t_end)
    for launch in (first, second):
        assert launch.queue_s + launch.run_s == pytest.approx(
            launch.t_end - launch.t_dispatched, abs=1e-9)
        assert launch.bufs is None  # the buffers are not held past the end
    assert first.queue_s == 0.0


def test_the_second_queues_exactly_until_the_first_ends():
    """Ends decided by the test: the second's queue ends at the first's
    end, whoever stamps it."""
    tl = DeviceTimeline()
    a, b = Gate(), Gate()
    la = tl.dispatched("a", a)
    lb = tl.dispatched("b", b)
    time.sleep(0.05)
    a.done.set()
    _ended(tl, 1)
    time.sleep(0.05)
    b.done.set()
    _ended(tl, 2)
    assert la.queue_s == 0.0 and la.run_s >= 0.05
    assert lb.t_dispatched + lb.queue_s == pytest.approx(la.t_end, abs=1e-9)
    assert lb.run_s == pytest.approx(lb.t_end - la.t_end, abs=1e-9)
    assert lb.run_s >= 0.04
    assert lb.queue_s + lb.run_s == pytest.approx(
        lb.t_end - lb.t_dispatched, abs=1e-9)
    assert lb.on_device()["launchesAhead"] == 1


def test_an_idle_device_gives_no_queue():
    tl = DeviceTimeline()
    g = Gate()
    g.done.set()
    launch = tl.dispatched(1, g)
    _ended(tl, 1)
    time.sleep(0.02)
    later = tl.dispatched(2, _quick(jnp.ones((4,))))
    _ended(tl, 2)
    for x in (launch, later):
        assert x.ahead == 0 and x.queue_s == 0.0
        assert x.on_device()["deviceQueueMs"] == 0.0
        assert x.run_s == pytest.approx(x.t_end - x.t_dispatched)


def test_the_runs_of_a_burst_never_overlap():
    """More threads than cores dispatch and fetch at once, the
    interpreter switching threads every few microseconds: every launch is
    stamped once, in dispatch order, and in that order each run starts
    where the one before ended, or later."""
    tl = DeviceTimeline()
    x = jnp.ones((300, 300), jnp.float32) * 1e-3
    _slow(x).block_until_ready()
    launches, lock = [], threading.Lock()

    def burst(i):
        for j in range(5):
            buf = _slow(x) if (i + j) % 2 else _quick(x)
            launch = tl.dispatched((i, j), buf)
            with lock:
                launches.append(launch)
            jax.block_until_ready(buf)  # the fetch, racing the waiter
            tl.seen(launch, time.perf_counter())

    n = (os.cpu_count() or 4) + 4
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=burst, args=(i,))
                   for i in range(n)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    _ended(tl, 5 * n)
    assert tl.ended == len(launches) == 5 * n
    # dispatch order: by end, and among launches one fetch stamped at
    # one instant, by the end of the launch before
    ordered = sorted(launches, key=lambda x: (x.t_end, x.prev_end))
    assert [launch.prev_end for launch in ordered[1:]] \
        == [launch.t_end for launch in ordered[:-1]]
    for before, after in zip(ordered, ordered[1:]):
        assert after.t_end - after.run_s >= before.t_end - 1e-12
    for launch in ordered:
        assert launch.run_s >= 0.0 and launch.queue_s >= 0.0
        assert launch.queue_s + launch.run_s == pytest.approx(
            launch.t_end - launch.t_dispatched, abs=1e-9)
    busy = sum(launch.run_s for launch in ordered)
    assert busy <= ordered[-1].t_end - min(
        launch.t_dispatched for launch in ordered) + 1e-9


def test_a_fetch_after_the_end_still_reads_the_true_end():
    """The waiter stamps a launch nobody fetched yet; the late fetch
    changes nothing."""
    tl = DeviceTimeline()
    g = Gate()
    launch = tl.dispatched(1, g)
    g.done.set()
    t_ready = time.perf_counter()
    _ended(tl, 1)
    time.sleep(0.2)
    t_fetch = time.perf_counter()
    tl.seen(launch, t_fetch)
    assert launch.t_end < t_ready + 0.1 < t_fetch
    assert launch.run_s < 0.1


def test_a_fetch_stamps_what_the_waiter_has_not_seen():
    """A fetch that sees its launch ready stamps it, and the open
    launches before it, without waiting for the waiter."""
    tl = DeviceTimeline()
    a, b = Gate(), Gate()
    la, lb = tl.dispatched("a", a), tl.dispatched("b", b)
    t = time.perf_counter()
    tl.seen(lb, t)  # b was seen ready: a, before it, ended no later
    assert la.t_end == lb.t_end == t
    assert lb.run_s == 0.0 and lb.queue_s == pytest.approx(
        t - lb.t_dispatched)
    a.done.set()
    b.done.set()
    time.sleep(0.05)
    assert (la.t_end, lb.t_end) == (t, t) and tl.ended == 2


def test_only_a_traced_launch_marks_its_end(monkeypatch):
    """A traced launch's end is a zero-length ``pinot.executor.device_end``
    carrying its id, for lining the device trace up against; an untraced
    one writes nothing to the profiler."""
    marks = []
    monkeypatch.setattr(trace, "mark",
                        lambda name, **ids: marks.append((name, ids)))
    tl = DeviceTimeline()
    g = Gate()
    g.done.set()
    tl.dispatched(5, g, traced=True)
    tl.dispatched(6, g)
    _ended(tl, 2)
    assert marks == [("executor.device_end", {"launch_id": 5})]


def test_the_waiter_leaves_when_idle_and_comes_back(monkeypatch):
    monkeypatch.setattr(DeviceTimeline, "IDLE_EXIT_S", 0.05)
    tl = DeviceTimeline()
    g = Gate()
    g.done.set()
    tl.dispatched(1, g)
    _ended(tl, 1)
    t0 = time.perf_counter()
    while tl._waiter is not None:
        assert time.perf_counter() - t0 < 5
        time.sleep(0.01)
    tl.dispatched(2, g)
    _ended(tl, 2)


# ---------------------------------------------------------------------------
# through the executor
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def engine(tmp_path_factory):
    base = tmp_path_factory.mktemp("timeline")
    schema = Schema.build(name="t", dimensions=[("tag", DataType.STRING)],
                          metrics=[("v", DataType.INT)])
    rng = np.random.default_rng(43)
    eng = QueryEngine()
    for i in range(2):
        cols = {"tag": np.array(["a", "b", "c"])[rng.integers(0, 3, 12_000)],
                "v": rng.integers(0, 100, 12_000).astype(np.int32)}
        d = str(base / f"s{i}")
        build_segment(schema, cols, d, TableConfig(table_name="t"), f"s{i}")
        eng.add_segment("t", ImmutableSegment(d))
    eng.device.partials_cache_enabled = False
    eng.execute(SQL)  # compile
    return eng


def _traced(eng, sql):
    tracer = trace.start_trace("timeline-test")
    try:
        resp = eng.execute(sql)
    finally:
        trace.end_trace()
    assert not resp.get("exceptions"), resp
    waits = [s.get("attrs") or {} for s in tracer.to_json()
             if s["phase"] == "executor.device_wait"]
    return resp, waits


def test_a_traced_launch_carries_its_split_everywhere(engine):
    resp, (wait,) = _traced(engine, SQL)
    assert {"deviceQueueMs", "deviceRunMs", "launchesAhead"} <= set(wait)
    assert wait["launchesAhead"] == 0 and wait["deviceQueueMs"] == 0.0
    (rec,) = resp["roofline"]
    assert rec["queueMs"] == wait["deviceQueueMs"]
    assert rec["runMs"] == wait["deviceRunMs"] > 0
    assert resp["deviceRunMs"] == pytest.approx(rec["runMs"], abs=1e-3)
    assert resp["deviceQueueMs"] == pytest.approx(rec["queueMs"], abs=1e-3)
    # the achieved GB/s divides by the run, not by the fetch's wait
    assert rec["gbps"] == pytest.approx(
        rec["bytesMoved"] / rec["runMs"] / 1e6, rel=0.01, abs=2e-3)
    kernels = engine.device.hbm_stats()["roofline"]["kernels"]
    agg = kernels[rec["kernel"]]
    assert agg["run_ms"] > 0
    assert agg["gbps"] == pytest.approx(
        agg["bytes_moved"] / agg["run_ms"] / 1e6, rel=0.01, abs=2e-3)
    (line,) = [r[0].strip() for r in engine.execute(
        "EXPLAIN ANALYZE " + SQL)["resultTable"]["rows"]
        if r[0].strip().startswith("KERNEL(")]
    assert "queueMs=" in line and "runMs=" in line, line


def test_an_untraced_request_feeds_stats_and_metrics(engine):
    m = get_metrics("server")

    def count(key):
        h = m.snapshot()["histograms"].get("server." + key)
        return h["count"] if h else 0

    keys = ("deviceRunMs", "deviceQueueMs", "deviceLaunchesAhead")
    before = {k: count(k) for k in keys}
    assert trace.active() is None
    ended = engine.device.device_timeline.ended
    resp = engine.execute(SQL)
    assert resp["deviceRunMs"] > 0 and resp["deviceQueueMs"] >= 0
    assert engine.device.device_timeline.ended == ended + 1
    for k in keys:
        assert count(k) == before[k] + 1, k


def test_a_late_fetch_reads_the_end_the_waiter_stamped(engine):
    """The launch is fetched well after its end: its run is the device's
    time, not the time until the fetch."""
    from pinot_tpu.query.optimizer import optimize_query
    from pinot_tpu.sql.compiler import compile_query

    q = optimize_query(compile_query(SQL))
    timeline = engine.device.device_timeline
    launches = []
    real = timeline.dispatched
    timeline.dispatched = lambda *a, **kw: launches.append(
        real(*a, **kw)) or launches[-1]
    tdm = engine.tables["t"]
    segs = tdm.acquire()
    try:
        fetch = engine.execute_segments_async(q, segs)
        time.sleep(0.5)
        t_fetch = time.perf_counter()
        merged = fetch()
    finally:
        tdm.release(segs)
        del timeline.dispatched
    (launch,) = launches
    assert launch.t_end < t_fetch
    assert merged.stats.device_run_ms < 400


def test_a_partials_cache_hit_carries_no_split(engine):
    dev = engine.device
    dev.partials_cache_enabled = True
    try:
        engine.execute(SQL + " LIMIT 7")
        resp, (wait,) = _traced(engine, SQL + " LIMIT 7")
    finally:
        dev.partials_cache_enabled = False
    assert resp["partialsCacheHit"] and wait.get("partialsCacheHit")
    assert not {"deviceQueueMs", "deviceRunMs", "launchesAhead"} & set(wait)
    assert resp["deviceQueueMs"] == resp["deviceRunMs"] == 0
    (rec,) = resp["roofline"]
    assert "runMs" not in rec and "gbps" not in rec


def test_cohort_members_carry_their_launchs_values(engine):
    """Under the tests' forced cohorts every member's wait span carries
    the one launch's queue, run and launches ahead."""
    from pinot_tpu.common.trace import Tracer
    from pinot_tpu.query.optimizer import optimize_query
    from pinot_tpu.sql.compiler import compile_query

    co = engine.device.coalescer
    n = 3
    tracers = [Tracer(f"timeline-cohort-{i}") for i in range(n)]
    errors, barrier = [], threading.Barrier(n)
    tdm = engine.tables["t"]

    def worker(i):
        q = optimize_query(compile_query(
            f"SELECT tag, SUM(v) FROM t WHERE v < {90 + i} GROUP BY tag"))
        segs = tdm.acquire()
        try:
            barrier.wait(10)
            engine.execute_segments_async(q, segs, tracer=tracers[i])()
        except Exception as e:  # noqa: BLE001
            errors.append(e)
        finally:
            tdm.release(segs)

    co.force, co.window_s = True, 0.25
    try:
        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(n)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
    finally:
        co.force, co.window_s = False, 0.003
    assert not errors, errors
    by_launch = {}
    for tr in tracers:
        (wait,) = [s["attrs"] for s in tr.to_json()
                   if s["phase"] == "executor.device_wait"]
        by_launch.setdefault(wait["launchId"], []).append(wait)
    assert any(len(w) > 1 for w in by_launch.values()), by_launch
    for waits in by_launch.values():
        split = {(w["deviceQueueMs"], w["deviceRunMs"], w["launchesAhead"])
                 for w in waits}
        assert len(split) == 1, waits


# ---------------------------------------------------------------------------
# the benchmark's readers
# ---------------------------------------------------------------------------


def _reader(name):
    sys.path.insert(0, os.path.join(ROOT, "benchmark"))
    try:
        import importlib

        spec = importlib.import_module("harness.spec")
    finally:
        sys.path.remove(os.path.join(ROOT, "benchmark"))
    return spec.found("layer_metrics", name)


def _request(span_id, *launches):
    """One request's spans: a root and a wait span a launch, each
    (queue, run), or None for a wait on the partials cache."""
    spans = [{"phase": "http.request", "spanId": span_id, "parentId": None,
              "start": 0.0, "end": 50.0}]
    for i, split in enumerate(launches):
        attrs = {"launchId": span_id + i}
        if split is None:
            attrs = {"partialsCacheHit": True}
        else:
            attrs.update(deviceQueueMs=split[0], deviceRunMs=split[1],
                         launchesAhead=1)
        spans.append({"phase": "executor.device_wait",
                      "spanId": span_id + 1 + i, "parentId": span_id,
                      "start": 10.0, "end": 40.0, "attrs": attrs})
    return spans


@pytest.mark.parametrize("requests,run,queue", [
    # one launch a request
    ([[(2.0, 10.0)], [(4.0, 20.0)], [(0.0, 30.0)]], 20.0, 2.0),
    # several launches of one request are summed before the mean/median
    ([[(1.0, 5.0), (2.0, 7.0)], [(0.0, 3.0)]], 7.5, 1.5),
    # requests that launched nothing (a partials-cache hit, no wait span)
    # are not the metrics'
    ([[(3.0, 9.0)], [None], []], 9.0, 3.0),
    ([[None]], None, None),
])
def test_device_run_and_queue_readers(requests, run, queue):
    traces = [_request(100 * (i + 1), *launches)
              for i, launches in enumerate(requests)]
    run_mod, queue_mod = _reader("device_run_ms"), _reader("device_queue_ms")
    got_run = run_mod.read({"spans_in_slice": traces})
    got_queue = queue_mod.read({"spans_in_slice": traces})
    assert got_run == pytest.approx(run) if run is not None \
        else got_run is None
    assert got_queue == pytest.approx(queue) if queue is not None \
        else got_queue is None


def test_the_readers_read_nothing_without_traces():
    for name, entry in (("device_run_ms", ("device", "ms", "queries_per_s")),
                        ("device_queue_ms",
                         ("executor", "ms", "query_p50_ms"))):
        mod = _reader(name)
        assert mod.read({}) is None
        assert mod.read({"spans_in_slice": None}) is None
        # a program that stamps no split: its wait spans say nothing
        assert mod.read({"spans_in_slice": [[{
            "phase": "executor.device_wait", "spanId": 2, "parentId": 1,
            "start": 0.0, "end": 5.0, "attrs": {"launchId": 3}}]]}) is None
        assert (mod.LAYER, mod.UNIT, mod.MOVES) == entry


def test_the_readers_stand_in_the_benchmark():
    import json

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        per_layer = {m["name"]: m for m in json.load(f)["per_layer"]}
    for name in ("device_run_ms", "device_queue_ms"):
        m = per_layer[name]
        mod = _reader(name)
        assert (m["layer"], m["unit"], m["moves"]) \
            == (mod.LAYER, mod.UNIT, mod.MOVES)
        assert m["source"] == "program_span" and m["better"] == "lower"
        assert "workloads" not in m  # every cell launches
