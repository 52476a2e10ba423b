"""``layer_metrics/groupby_slotted_share.py``: on hand-built traces (every
full launch slotted; a skewed key's ordered launch; a launch whose operands
the byte budget refused; launches of other regimes, which do not count), on
the parent's spans, which name the key space and no layout, on a program
whose spans name neither, and without a trace. Beside
``test_groupby_full_share.py``, whose requests it builds on."""

import pytest

from harness import spec
from harness.test_groupby_full_share import BIG, SMALL, request
from harness.test_spans import TRACE, reader

NAME = "groupby_slotted_share"


def laid_out(space, cells, layout=None, slot_rows=None, span_id=1):
    """``test_groupby_full_share.request`` with the layout its dispatch and
    wait name (None: a span that names none)."""
    t = request(space, cells, span_id=span_id)
    for s in t:
        if s["phase"] in ("executor.dispatch", "executor.device_wait") \
                and layout is not None:
            s["attrs"]["groupbyKeyLayout"] = layout
            if slot_rows:
                s["attrs"]["slotRows"] = slot_rows
    return t


@pytest.mark.parametrize("launches, want", [
    ([("full", BIG, "slotted", 56), ("full", 62_500, "slotted", 712)], 100.0),
    # a skewed key keeps the ordered planes
    ([("full", BIG, "slotted", 56), ("full", BIG, "ordered")], 50.0),
    # the byte budget refused the operands: full, per launch, no layout
    ([("full", BIG, "slotted", 48), ("full", BIG), ("full", BIG, "slotted", 48),
      ("full", BIG, "slotted", 48)], 75.0),
    # other regimes are not the share's: two full launches, one slotted
    ([("dense", SMALL), ("narrowed", BIG), ("full", BIG, "slotted", 48),
      ("sorted", 8 * BIG), ("full", BIG, "ordered")], 50.0),
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
    """The parent of the PR that added the layout names ``full`` and no
    ``groupbyKeyLayout``: its launches ran the ordered form, and the share
    says 0 there, where the driver reads the new metric on both sides."""
    traces = [request("full", BIG, span_id=1),
              request("full", 62_500, span_id=11)]
    assert reader(NAME).read({"spans_in_slice": traces}) == 0.0


def test_spans_without_key_spaces_or_a_trace_say_nothing():
    # test_spans.py's fixture: a dispatch span with a launchId alone
    assert reader(NAME).read({"spans_in_slice": [TRACE]}) is None
    assert reader(NAME).read({"slice": None}) is None
    assert reader(NAME).read({}) is None
    # an accepted cell's trace: group-bys, none of them full
    traces = [request("dense", SMALL), request("narrowed", BIG, span_id=11)]
    assert reader(NAME).read({"spans_in_slice": traces}) is None


def test_the_entry_is_the_ranking_cells_alone():
    cell = spec.Cell("ssb_sf100_fullkeys.rank_6q")
    entry = next(m for m, _mod in cell.layer_readers() if m["name"] == NAME)
    assert entry == {
        "name": NAME, "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "kernels",
        "moves": "queries_per_s",
        "workloads": ["ssb_sf100_fullkeys.rank_6q"]}
    mod = reader(NAME)
    assert (mod.LAYER, mod.UNIT, mod.MOVES) \
        == (entry["layer"], entry["unit"], entry["moves"])
    for other in ("ssbproxy100m.groupby_scan", "ssbproxy100m_bydate.range_sum",
                  "ssb_sf100_chipshare.flat_13q"):
        assert NAME not in [m["name"] for m, _ in
                            spec.Cell(other).layer_readers()]
    # the last of its list: an entry put elsewhere reads as an edit
    assert cell.spec["per_layer"][-1] == entry
