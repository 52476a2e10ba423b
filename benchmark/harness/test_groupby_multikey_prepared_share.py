"""``layer_metrics/groupby_multikey_prepared_share.py`` on recorded spans:
shaped as the parent of the PR that added it writes them (a dense
group-by says ``perLaunch``, a narrowed one nothing of its operands: 0),
as the change does (every group-by ``prepared``: 100), with no group-by
in the slice (nothing to read), and on traces kept by the program's own
tracer. Beside ``test_spans.py``, whose data this extends without editing
it."""

import json
import os

import pytest

from harness.test_spans import TRACE, reader, span

NAME = "groupby_multikey_prepared_share"


def request(space, origin, span_id=1):
    """One traced request that led a launch: ``space`` its
    ``groupbyKeySpace`` (None: no GROUP BY), ``origin`` its
    ``groupbyOperands`` (None: the launch says nothing of them)."""
    attrs = {} if space is None else {
        "groupbyKeySpace": space, "keySpaceCells": 4375}
    if origin is not None:
        attrs["groupbyOperands"] = origin
    return [span("http.request", span_id, None, 0, 50),
            span("executor.dispatch", span_id + 1, span_id, 5, 10,
                 launchId=span_id, **attrs),
            span("executor.device_wait", span_id + 2, span_id, 10, 40,
                 launchId=span_id, **attrs)]


PARENT = [("dense", "perLaunch"), ("narrowed", None), (None, None),
          ("dense", "perLaunch"), ("narrowed", None)]
CHANGE = [("dense", "prepared"), ("narrowed", "prepared"), (None, None),
          ("dense", "prepared"), ("narrowed", "prepared")]


@pytest.mark.parametrize("launches, want", [
    (PARENT, 0.0),
    (CHANGE, 100.0),
    # step 3 left out: the dense statements prepared, the narrowed not
    ([("dense", "prepared")] * 6 + [("narrowed", "perLaunch")] * 4, 60.0),
    # the launch that builds the operands is not one that read them
    ([("dense", "built"), ("dense", "prepared")], 50.0),
    # one key column counts as any other group-by does
    ([("dense", "prepared"), ("sorted", None)], 50.0),
    # no group-by in the slice: plain sums say nothing of a key space
    ([(None, None), (None, None)], None),
    ([], None),
])
def test_share_of_group_by_dispatch_spans(launches, want):
    traces = [request(space, origin, 10 * i + 1)
              for i, (space, origin) in enumerate(launches)]
    assert reader(NAME).read({"spans_in_slice": traces or None}) == want


def test_the_fixture_of_test_spans_says_nothing():
    # its dispatch span carries a launchId and no key space
    assert reader(NAME).read({"spans_in_slice": [TRACE]}) is None
    assert reader(NAME).read({"slice": None}) is None


def test_on_traces_the_program_kept(monkeypatch):
    from pinot_tpu.common import trace

    monkeypatch.setattr(trace, "_ring", type(trace._ring)(maxlen=64))
    kept = [("dense", "built"), ("narrowed", "prepared"),
            ("dense", "prepared"), (None, None)]
    for i, (space, origin) in enumerate(kept):
        t = trace.Tracer(f"b-{i}", t0=0.0)
        t.wall0 = 100.0 + i
        root = t.open("http.request", 0.0, 0.0)
        attrs = {"launchId": i}
        if space:
            attrs.update(groupbyKeySpace=space, groupbyOperands=origin)
        t.record("executor.dispatch", 0.001, 0.002, attrs=attrs)
        root.close(0.010)
    assert reader(NAME).read({"slice": (99.0, 105.0)}) \
        == pytest.approx(200.0 / 3)
    assert reader(NAME).read({"slice": (100.5, 105.0)}) == 100.0
    monkeypatch.delattr(trace, "finished")
    assert reader(NAME).read({"slice": (99.0, 105.0)}) is None


def test_benchmark_json_lists_it_for_the_flat_cell_alone():
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        entry, = (e for e in json.load(f)["per_layer"] if e["name"] == NAME)
    module = reader(NAME)
    assert entry == {
        "name": NAME, "unit": module.UNIT, "better": "higher",
        "source": "program_counter", "layer": module.LAYER,
        "moves": module.MOVES,
        "workloads": ["ssb_sf100_chipshare.flat_13q"]}
