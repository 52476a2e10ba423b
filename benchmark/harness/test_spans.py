"""``harness/spans.py`` and the eight readers on top of it: self time on a
hand-built trace (nested, overlapping and cross-thread children), the cut
to the slice by the root's start, every reader on that fixture and ``None``
where nothing is kept; then one whole run at a tiny size on the CPU whose
statements carry ``SET trace=true``, with the profiler's part (there is no
device to trace here) stood in for."""

import importlib.util
import os
import time

import pytest

import run as run_mod
from harness import spans, trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
TINY = os.path.join(HERE, "testdata", "benchmark_tiny_spans.json")
NEW = ("http_self_ms", "broker_self_ms", "server_self_ms",
       "executor_self_ms", "launch_queue_ms", "cohort_size", "host_cpu_ms",
       "on_device_share")


def reader(name):
    spec = importlib.util.spec_from_file_location(
        "layer_metric_" + name,
        os.path.join(os.path.dirname(HERE), "layer_metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def span(phase, span_id, parent, start, end, cpu=None, **attrs):
    d = {"phase": phase, "spanId": span_id, "parentId": parent,
         "start": float(start), "end": float(end),
         "durationMs": float(end - start)}
    if cpu is not None:
        d["cpuMs"] = cpu
    if attrs:
        d["attrs"] = attrs
    return d


# one request, in ms: the door 0-100, the broker 5-90 inside it; the
# server's tracer (another thread) hangs under the scatter's wait
TRACE = [
    span("http.request", 1, None, 0, 100),
    span("http.read", 2, 1, 0, 4, cpu=1.0, bytesIn=80),
    span("broker.total", 3, 1, 5, 90),
    span("broker.parse", 4, 3, 5, 10, cpu=4.0),
    span("broker.scatter_gather", 5, 3, 12, 82),
    span("broker.reduce", 6, 3, 82, 88, cpu=2.0),
    span("http.write", 7, 1, 92, 99, cpu=3.0, bytesOut=500),
    span("server.total", 8, 5, 15, 80),
    span("server.queue", 9, 8, 15, 17),
    span("server.plan", 10, 8, 17, 20, cpu=3.0),
    span("server.execute", 11, 8, 20, 40),
    span("engine.template", 12, 11, 21, 25, cpu=3.5),
    span("executor.gather", 13, 11, 25, 28, cpu=2.5),
    # two overlapping children and one that runs past its parent's end
    span("executor.launch_wait", 14, 11, 28, 36),
    span("executor.stack", 15, 11, 34, 38, cpu=1.0),
    span("executor.dispatch", 16, 11, 38, 44, cpu=0.5, launchId=7),
    span("server.fetch", 17, 8, 44, 76),
    span("executor.device_wait", 18, 17, 44, 70, cpu=0.1, launchId=7,
         cohortSize=2, role="leader"),
    span("executor.link", 19, 17, 70, 72, cpu=0.2),
    span("executor.unpack", 20, 17, 72, 73, cpu=0.9),
    span("engine.merge", 21, 17, 73, 75, cpu=1.5, segmentsOnDevice=3,
         segmentsOnHost=1),
    span("server.encode", 22, 8, 77, 79, cpu=1.8),
]


def test_self_time_nested_overlapping_and_cross_thread():
    # http.request 100 less read 4, broker.total 85, write 7
    assert spans.self_ms(TRACE, "http") == pytest.approx(4 + 4 + 7)
    # broker.total 85 less its three children (5 + 70 + 6) = 4; parse 5,
    # reduce 6; the scatter's 70 less the server's 65 (a child from
    # another tracer and thread) = 5
    assert spans.self_ms(TRACE, "broker") == pytest.approx(4 + 5 + 5 + 6)
    # server.execute 20-40: children cover 21-38 once (28-36 and 34-38
    # overlap) and 38-40 of the dispatch that ends at 44 -> self 1
    assert spans.self_ms(TRACE, "server.execute") == pytest.approx(1.0)
    # server.total 65 less queue 2, plan 3, execute 20, fetch 32,
    # encode 2 = 6; plan 3; execute 1; fetch 32 less 31 = 1; encode 2;
    # engine.template 4 + engine.merge 2
    assert spans.self_ms(TRACE, ("server", "engine"),
                         skip=("server.queue",)) == pytest.approx(
        6 + 3 + 1 + 1 + 2 + 4 + 2)
    assert spans.wall_ms(TRACE, ("server.queue", "executor.launch_wait")) \
        == pytest.approx(2 + 8)
    # the spans with no child, whichever thread ran them
    assert spans.leaf_cpu_ms(TRACE) == pytest.approx(
        1 + 4 + 2 + 3 + 3 + 3.5 + 2.5 + 1 + 0.5 + 0.1 + 0.2 + 0.9 + 1.5
        + 1.8)
    assert spans.attr_values(TRACE, "launchId") == [7, 7]


@pytest.fixture()
def kept(monkeypatch):
    """Three requests in the program's ring, built with its own tracer:
    roots at 100 s, 101 s and 103 s; the first two share a launch."""
    from pinot_tpu.common import trace

    monkeypatch.setattr(trace, "_ring", type(trace._ring)(maxlen=64))
    for i, (at, launch) in enumerate([(100.0, 1), (101.0, 1), (103.0, 2)]):
        door = trace.Tracer(f"b-{i}", t0=0.0)
        door.wall0 = at
        root = door.open("http.request", 0.0, 0.0)
        door.record("http.read", 0.0, 0.001, cpu_ms=0.5)
        total = door.open("broker.total", 0.001, 0.0)
        scatter = door.span("broker.scatter_gather", quiet=True)._open(
            0.002, 0.0)
        # the server's root starts later — for the last request past the
        # slice's end — and still belongs to its request
        srv = trace.Tracer(f"b-{i}", parent_id=scatter.span_id, t0=0.0)
        srv.wall0 = at + 0.002
        sroot = srv.open("server.total", 0.0, 0.0)
        srv.record("server.queue", 0.0, 0.001)
        srv.record("executor.launch_wait", 0.001, 0.003 + i / 1000)
        srv.record("executor.gather", 0.001, 0.002, cpu_ms=0.75)
        srv.record("executor.device_wait", 0.004, 0.010,
                   attrs={"launchId": launch, "cohortSize": 2})
        srv.record("engine.merge", 0.010, 0.011, cpu_ms=0.25,
                   attrs={"segmentsOnDevice": 2, "segmentsOnHost": 0})
        sroot.close(0.012)
        scatter.close(0.015)
        total.close(0.016)
        root.close(0.020)
    return trace


def test_slice_is_cut_by_the_roots_start(kept):
    run = {"slice": (100.5, 103.001)}
    traces = spans.in_slice(run)
    assert sorted(min(s["start"] for s in t) for t in traces) \
        == [101_000.0, 103_000.0]
    # server spans are joined on the door's clock, under its scatter
    for t in traces:
        by_name = {s["phase"]: s for s in t}
        assert by_name["server.total"]["parentId"] \
            == by_name["broker.scatter_gather"]["spanId"]
        assert by_name["server.total"]["start"] \
            == pytest.approx(by_name["http.request"]["start"] + 2.0)
    assert spans.in_slice({"slice": (90.0, 99.0)}) is None
    assert spans.in_slice({"slice": None}) is None


def test_every_reader_on_kept_traces(kept):
    run = {"slice": (99.0, 104.0)}
    got = {name: reader(name).read(run) for name in NEW}
    # the door: request 20 less read 1 and broker.total 15, plus read 1
    assert got["http_self_ms"] == pytest.approx(4 + 1)
    # broker.total 15 less scatter 13 = 2; scatter 13 less server 12 = 1
    assert got["broker_self_ms"] == pytest.approx(2 + 1)
    # server.total 12 less the union of its children (0-3+i, 4-11)
    # = 2 - i; engine.merge 1
    assert got["server_self_ms"] == pytest.approx(1 + 1)
    assert got["executor_self_ms"] == pytest.approx(1.0)
    assert got["launch_queue_ms"] == pytest.approx(1 + 3)
    assert got["cohort_size"] == pytest.approx(3 / 2)
    assert got["host_cpu_ms"] == pytest.approx(0.5 + 0.75 + 0.25)
    assert got["on_device_share"] == 100.0


def test_readers_say_nothing_on_an_empty_ring(monkeypatch):
    from pinot_tpu.common import trace

    monkeypatch.setattr(trace, "_ring", type(trace._ring)(maxlen=4))
    for name in NEW:
        assert reader(name).read({"slice": (0.0, 1e12)}) is None
    # a program without kept traces, as this PR's parent is
    monkeypatch.delattr(trace, "finished")
    for name in NEW:
        assert reader(name).read({"slice": (0.0, 1e12)}) is None


def test_whole_run_reports_all_eight(monkeypatch):
    """``--trace 1`` at a tiny size on the CPU: every statement carries
    the mix's trace prefix, the program keeps the spans, the readers
    report. The profiler's slice and its reduction are stood in for:
    there is no device plane in a CPU trace."""
    import jax

    def middle(_trace_dir, seconds):
        time.sleep(seconds / 4)
        t_a = time.time()
        time.sleep(seconds / 2)
        return t_a, time.time()

    monkeypatch.setattr(run_mod, "trace_middle", middle)
    monkeypatch.setattr(trace_reduce, "load_events", lambda d: [])
    monkeypatch.setattr(
        trace_reduce, "reduce",
        lambda events, window_s: {
            "busy_s": 0.0, "window_s": window_s, "modules_s": 0.0,
            "n_ops": 0, "n_modules": 0, "chips": 1, "device_ops": [],
            "idle_gaps": []})
    args = run_mod.parse(["--workload", "tiny.groupby_scan", "--seed", "7",
                          "--seconds", "2", "--trace", "1",
                          "--benchmark-json", TINY])
    result = run_mod.run(args, lambda chips: jax.devices()[:chips])
    assert result["correct"], result["compared"]
    metrics = result["metrics"]
    assert set(NEW) <= set(metrics), sorted(metrics)
    assert metrics["on_device_share"]["value"] == 100.0
    assert metrics["cohort_size"]["value"] >= 1.0
    for name in NEW[:5] + ("host_cpu_ms",):
        assert metrics[name]["value"] >= 0.0
    # the layers' self times and waits add up to the request at the door
    assert metrics["host_cpu_ms"]["value"] > 0.0
