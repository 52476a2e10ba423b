"""``layer_metrics/groupby_prepared_share.py``: on hand-built traces, on
traces kept by the program's own tracer, on a program whose dispatch spans
carry no ``groupbyOperands`` (the parent of the PR that added it), and in
one whole ``--trace 1`` run at a tiny size on the CPU. Beside
``test_spans.py``, whose data this extends without editing it."""

import json
import time

import pytest

import run as run_mod
from harness import trace_reduce
from harness.test_spans import TINY, TRACE, reader, span

NAME = "groupby_prepared_share"


def request(origin, span_id=1):
    """One traced request that led a launch (or, with None, joined one:
    no dispatch span on a member's trace)."""
    t = [span("http.request", span_id, None, 0, 50),
         span("executor.device_wait", span_id + 2, span_id, 10, 40,
              launchId=span_id)]
    if origin is not None:
        attrs = {} if origin == "" else {"groupbyOperands": origin}
        t.append(span("executor.dispatch", span_id + 1, span_id, 5, 10,
                      launchId=span_id, **attrs))
    return t


@pytest.mark.parametrize("origins, want", [
    (["prepared", "prepared", None, "prepared"], 100.0),
    (["built", "prepared", "prepared", "perLaunch"], 50.0),
    (["perLaunch"], 0.0),
    # launches of statements with no GROUP BY say nothing and do not count
    (["prepared", ""], 100.0),
    # nothing that says anything: the parent's spans, or cell 2's
    (["", None], None),
    ([], None),
])
def test_share_of_dispatch_spans(origins, want):
    traces = [request(o, 10 * i + 1) for i, o in enumerate(origins)]
    assert reader(NAME).read({"spans_in_slice": traces or None}) == want


def test_the_fixture_of_test_spans_says_nothing():
    # its dispatch span is the parent's: a launchId and no origin
    assert reader(NAME).read({"spans_in_slice": [TRACE]}) is None
    assert reader(NAME).read({"slice": None}) is None


def test_on_traces_the_program_kept(monkeypatch):
    from pinot_tpu.common import trace

    monkeypatch.setattr(trace, "_ring", type(trace._ring)(maxlen=64))
    for i, origin in enumerate(["built", "prepared", "prepared"]):
        t = trace.Tracer(f"b-{i}", t0=0.0)
        t.wall0 = 100.0 + i
        root = t.open("http.request", 0.0, 0.0)
        t.record("executor.dispatch", 0.001, 0.002,
                 attrs={"launchId": i, "groupbyOperands": origin})
        root.close(0.010)
    assert reader(NAME).read({"slice": (99.0, 104.0)}) \
        == pytest.approx(200.0 / 3)
    assert reader(NAME).read({"slice": (100.5, 104.0)}) == 100.0
    monkeypatch.delattr(trace, "finished")
    assert reader(NAME).read({"slice": (99.0, 104.0)}) is None


def test_whole_run_reports_it_in_the_groupby_cell_only(monkeypatch, tmp_path):
    """The tiny group-by cell reports the share — 0 here, where the
    executor resolves both kernel tiers to "off" and every launch is
    ``perLaunch`` (on the chip they are on and it reads 100); the
    range-sum cell, whose statements have no GROUP BY, is not listed and
    does not report it."""
    import jax

    with open(TINY) as f:
        spec = json.load(f)
    spec["per_layer"].append({
        "name": NAME, "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "kernels",
        "moves": "queries_per_s", "workloads": ["tiny.groupby_scan"]})
    tiny = tmp_path / "benchmark_tiny_prepared.json"
    tiny.write_text(json.dumps(spec))

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
    got = {}
    for cell in ("tiny.groupby_scan", "tiny_bydate.range_sum"):
        args = run_mod.parse(["--workload", cell, "--seed", "11",
                              "--seconds", "2", "--trace", "1",
                              "--benchmark-json", str(tiny)])
        result = run_mod.run(args, lambda chips: jax.devices()[:chips])
        assert result["correct"], result["compared"]
        got[cell] = result["metrics"]
    assert got["tiny.groupby_scan"][NAME] == {"value": 0.0, "unit": "%"}
    assert NAME not in got["tiny_bydate.range_sum"]
    assert "cohort_size" in got["tiny_bydate.range_sum"]
