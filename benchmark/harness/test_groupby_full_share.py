"""``layer_metrics/groupby_full_share.py``: on hand-built traces (every
launch full; a request that carries a host answer; a narrowed launch that
overflowed and was launched again; a large key space that took another
regime), on a program whose spans carry no ``keySpaceCells``, without a
trace - and the algorithmic bytes ``scan_roofline`` reads for
``ssb_sf100_fullkeys``'s statements. Beside ``test_spans.py``, whose data
this extends without editing it."""

import pytest

from harness import algbytes, spec
from harness.test_spans import TRACE, reader, span

NAME = "groupby_full_share"
BIG, SMALL = 1_400_000, 4_375


def request(space, cells, host=None, again=None, span_id=1):
    """One traced request that led a launch of a group-by over ``cells``
    cells whose dispatch said ``space``. ``host``: how the host came to
    answer, if it did (``fallback``: an ``engine.host_fallback`` span;
    ``merge``: the merge counted segments on the host). ``again``: the
    regime of a second launch of the same request (a narrowed launch that
    overflowed is launched again full)."""
    t = [span("http.request", span_id, None, 0, 50),
         span("executor.dispatch", span_id + 1, span_id, 5, 10,
              launchId=span_id, groupbyKeySpace=space, keySpaceCells=cells),
         span("executor.device_wait", span_id + 2, span_id, 10, 40,
              launchId=span_id, groupbyKeySpace=space, keySpaceCells=cells,
              keySpaceLive=cells, trimSelect="select:8192")]
    if again:
        t.append(span("executor.dispatch", span_id + 3, span_id, 40, 42,
                      launchId=span_id + 3, groupbyKeySpace=again,
                      keySpaceCells=cells))
    if host == "fallback":
        t.append(span("engine.host_fallback", span_id + 4, span_id, 40, 48))
    t.append(span("engine.merge", span_id + 5, span_id, 48, 49,
                  segmentsOnDevice=0 if host else 3,
                  segmentsOnHost=3 if host else 0))
    return t


@pytest.mark.parametrize("launches, want", [
    ([("full", BIG), ("full", 62_500), ("dense", SMALL)], 100.0),
    # a request whose answer the host gave (the default numGroupsLimit
    # under a trimmed table): its launch ran full, and counts against
    ([("full", BIG), ("full", BIG, "fallback")], 50.0),
    ([("full", BIG), ("full", BIG, "merge"), ("full", BIG), ("full", BIG)],
     75.0),
    # a narrowed launch that overflowed and was launched again full: two
    # launches over the floor, one of them full
    ([("full", BIG), ("narrowed", BIG, None, "full")], 200.0 / 3),
    # a large key space that took the dense table or the sort
    ([("full", BIG), ("dense", BIG), ("sorted", 8 * BIG), ("full", BIG)],
     50.0),
    # no statement's key space passes the floor: nothing to read
    ([("dense", SMALL), ("dense", 1 << 15)], None),
    ([], None),
])
def test_share_of_large_key_space_launches(launches, want):
    traces = [request(*launch, span_id=10 * i + 1)
              for i, launch in enumerate(launches)]
    got = reader(NAME).read({"spans_in_slice": traces or None})
    assert got == pytest.approx(want) if want is not None else got is None
    assert got is None or 0.0 <= got <= 100.0


def test_spans_without_key_spaces_say_nothing():
    # test_spans.py's fixture is a trace of the program before the key
    # spaces were named: its dispatch span carries a launchId alone
    assert reader(NAME).read({"spans_in_slice": [TRACE]}) is None
    assert reader(NAME).read({"slice": None}) is None
    assert reader(NAME).read({}) is None


def test_the_parents_spans_of_this_mix_read_zero_not_nothing():
    """The parent of the PR that added the regime names the key spaces:
    on this mix its launches say narrowed, overflow and fall to the host,
    and the share says so (the parent's own run of the cell ends before
    any window opens: it is never read there)."""
    traces = [request("narrowed", BIG, span_id=1, host="fallback")]
    assert reader(NAME).read({"spans_in_slice": traces}) == 0.0


def test_the_floor_is_the_programs_and_the_entry_is_the_cells():
    from pinot_tpu.engine import device

    assert reader(NAME).MIN_CELLS == device.NARROW_MIN_CELLS
    cell = spec.Cell("ssb_sf100_fullkeys.rank_6q")
    names = [m["name"] for m, _mod in cell.layer_readers()]
    assert NAME in names and "scan_roofline" in names
    # the accepted cells' lists are as they were
    assert "groupby_narrowed_share" not in names
    assert "groupby_multikey_prepared_share" not in names
    assert NAME not in [m["name"] for m, _ in spec.Cell(
        "ssb_sf100_chipshare.flat_13q").layer_readers()]


def test_algorithmic_bytes_of_the_ranking_statements():
    """What ``scan_roofline`` divides by in ``ssb_sf100_fullkeys.rank_6q``:
    37.5M rows x the named columns' widths, all 3 segments read (generated
    order prunes nothing), for all six statements."""
    cell = spec.Cell("ssb_sf100_fullkeys.rank_6q")
    got = {s["name"]: algbytes.statement_bytes(cell.config, s)
           for s in cell.traffic["statements"]}
    assert len(got) == 6 and all(got.values())
    rows = 37_500_000
    assert got["citypair_1997"] == rows * (1 + 1 + 1 + 3)         # 225 MB
    assert got["city_brand"] == rows * (1 + 2 + 3)
    assert got["city_day"] == rows * (1 + 2 + 3)
    assert got["supp_year"] == rows * (3 + 1 + 3)
    assert got["supp_shipmode_disc"] == rows * (1 + 3 + 1 + 3)
    assert got["year_city_brand_profit"] == rows * (1 + 1 + 2 + 3 + 3)
