"""``layer_metrics/groupby_narrowed_share.py``: on hand-built traces, on a
program whose spans carry no ``keySpaceCells`` (the parent of the PR that
added it), and the algorithmic bytes ``scan_roofline`` reads for
``ssb_sf100_chipshare``'s statements. Beside ``test_spans.py``, whose data
this extends without editing it."""

import pytest

from harness import algbytes, spec
from harness.test_spans import TRACE, reader, span

NAME = "groupby_narrowed_share"
BIG, SMALL = 437_500, 4_375


def request(space, cells, span_id=1, result=None):
    """One traced request that led a launch of a group-by over ``cells``
    cells whose dispatch said ``space``; ``result``: what its wait said
    once the answer was read (``overflow``), if anything other."""
    wait = {"launchId": span_id, "groupbyKeySpace": result or space,
            "keySpaceCells": cells}
    return [span("http.request", span_id, None, 0, 50),
            span("executor.dispatch", span_id + 1, span_id, 5, 10,
                 launchId=span_id, groupbyKeySpace=space,
                 keySpaceCells=cells),
            span("executor.device_wait", span_id + 2, span_id, 10, 40,
                 **wait)]


@pytest.mark.parametrize("launches, want", [
    ([("narrowed", BIG), ("narrowed", 4 * BIG), ("dense", SMALL)], 100.0),
    # an overflow is a narrowed launch whose result said so: the host
    # answered, slowly, and the share shows it
    ([("narrowed", BIG), ("narrowed", BIG, "overflow")], 50.0),
    # a large key space that took the dense table or the sort
    ([("narrowed", BIG), ("dense", BIG), ("sorted", 8 * BIG),
      ("narrowed", BIG)], 50.0),
    # no statement's key space passes the floor: nothing to read
    ([("dense", SMALL), ("dense", 1 << 15)], None),
    ([], None),
])
def test_share_of_large_key_space_launches(launches, want):
    traces = [request(*launch[:2], span_id=10 * i + 1,
                      result=launch[2] if len(launch) > 2 else None)
              for i, launch in enumerate(launches)]
    assert reader(NAME).read({"spans_in_slice": traces or None}) == want


def test_the_parents_spans_say_nothing():
    # test_spans.py's fixture is a trace of the program before the PR: its
    # dispatch span carries a launchId and no key space
    assert reader(NAME).read({"spans_in_slice": [TRACE]}) is None
    assert reader(NAME).read({"slice": None}) is None


def test_the_floor_is_the_programs():
    from pinot_tpu.engine import device

    assert reader(NAME).MIN_CELLS == device.NARROW_MIN_CELLS


def test_algorithmic_bytes_of_the_flat_statements():
    """What ``scan_roofline`` divides by in ``ssb_sf100_chipshare.flat_13q``:
    37.5M rows x the named columns' widths, all 3 segments read (generated
    order prunes nothing), for all 13 statements."""
    cell = spec.Cell("ssb_sf100_chipshare.flat_13q")
    got = {s["name"]: algbytes.statement_bytes(cell.config, s)
           for s in cell.traffic["statements"]}
    assert len(got) == 13 and all(got.values())
    rows = 37_500_000
    assert got["q1_1"] == rows * (1 + 1 + 1 + 3)           # 225 MB
    assert got["q2_1"] == rows * (1 + 1 + 1 + 2 + 3)       # 300 MB
    assert got["q3_2"] == rows * (1 + 1 + 1 + 1 + 1 + 3)   # 300 MB
    assert got["q4_3"] == rows * (5 + 2 + 3 + 3)           # 487.5 MB
