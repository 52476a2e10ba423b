"""``layer_metrics/groupby_spilled_share.py``: on hand-built traces (every
full launch spilled; the byte budget's ordered fallback; a launch whose
operands the budget declined or this launch built; a request the host
answered; launches of other regimes, which do not count), on spans from
before the spilled layout, which name none, on a program whose spans name no
key space, and without a trace; and its entry in ``BENCHMARK.json``, found
by name. Beside ``test_groupby_full_share.py``, whose requests it builds
on."""

import pytest

from harness import spec
from harness.test_groupby_full_share import BIG, SMALL, request
from harness.test_spans import TRACE, reader

NAME = "groupby_spilled_share"
CELL = "ssb_sf100_skewkeys.rank_keys_6q"


def laid_out(space, cells, layout=None, operands="prepared", host=None,
             span_id=1):
    """``test_groupby_full_share.request`` whose dispatch and wait name
    ``layout`` (None: none, as a launch whose operands were declined) and
    ``operands``; a spilled one its K, fat cells and chunks."""
    t = request(space, cells, host=host, span_id=span_id)
    for s in t:
        if s["phase"] not in ("executor.dispatch", "executor.device_wait"):
            continue
        s["attrs"]["groupbyOperands"] = operands
        if layout is not None:
            s["attrs"]["groupbyKeyLayout"] = layout
        if layout == "spilled":
            s["attrs"].update(slotRows=32, spillCells=75_700,
                              spillChunks=861_348)
    return t


@pytest.mark.parametrize("launches, want", [
    ([("full", BIG, "spilled"), ("full", 62_500, "spilled")], 100.0),
    # the byte budget kept the ordered planes, or declined the operands
    ([("full", BIG, "spilled"), ("full", BIG, "ordered")], 50.0),
    ([("full", BIG, "spilled")] * 3 + [("full", BIG, None, "perLaunch")],
     75.0),
    ([("full", BIG, "spilled"), ("full", BIG, "spilled", "built")], 50.0),
    # a uniform key's slotted launch counts against
    ([("full", BIG, "slotted"), ("full", BIG, "spilled")], 50.0),
    # a request the host answered is not the share's; other regimes neither
    ([("full", BIG, "spilled"), ("full", BIG, "ordered", "prepared",
                                 "merge"),
      ("narrowed", BIG), ("dense", SMALL), ("sorted", 8 * BIG)], 100.0),
    ([("full", BIG, "ordered")], 0.0),
    # no full launch in the slice: nothing to read
    ([("dense", SMALL), ("narrowed", BIG)], None),
    ([], None),
])
def test_share_of_full_launches(launches, want):
    traces = [laid_out(*launch, span_id=10 * i + 1)
              for i, launch in enumerate(launches)]
    got = reader(NAME).read({"spans_in_slice": traces or None})
    assert got == pytest.approx(want) if want is not None else got is None
    assert got is None or 0.0 <= got <= 100.0


def test_the_parents_full_launches_read_zero_not_nothing():
    """A program from before the spilled layout names ``ordered`` on the
    skewed keys' full launches, and the share says 0 there, so that a
    comparison across the change reads the metric on both sides."""
    traces = [laid_out("full", BIG, "ordered", span_id=1),
              laid_out("full", 62_500, "ordered", span_id=11)]
    assert reader(NAME).read({"spans_in_slice": traces}) == 0.0


def test_spans_without_key_spaces_or_a_trace_say_nothing():
    # test_spans.py's fixture: a dispatch span with a launchId alone
    assert reader(NAME).read({"spans_in_slice": [TRACE]}) is None
    assert reader(NAME).read({"slice": None}) is None
    assert reader(NAME).read({}) is None
    traces = [request("dense", SMALL), request("narrowed", BIG, span_id=11)]
    assert reader(NAME).read({"spans_in_slice": traces}) is None


def test_the_entry_is_the_skewed_cell_alone():
    cell = spec.Cell(CELL)
    entry = next(m for m, _mod in cell.layer_readers() if m["name"] == NAME)
    assert entry == {
        "name": NAME, "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "kernels",
        "moves": "queries_per_s", "workloads": [CELL]}
    mod = reader(NAME)
    assert (mod.LAYER, mod.UNIT, mod.MOVES) \
        == (entry["layer"], entry["unit"], entry["moves"])
    for other in ("ssbproxy100m.groupby_scan", "ssbproxy100m_bydate.range_sum",
                  "ssb_sf100_chipshare.flat_13q",
                  "ssb_sf100_fullkeys.rank_6q"):
        assert NAME not in [m["name"] for m, _ in
                            spec.Cell(other).layer_readers()]
    # found by name, and one entry of that name
    assert [m for m in cell.spec["per_layer"] if m["name"] == NAME] \
        == [entry]
