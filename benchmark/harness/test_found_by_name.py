"""A generator kind and an operator are files found by name: one that no
file of the harness names is found from a directory of its own, through the
draw, the layout, the reference and the algorithmic bytes; an unknown one
fails before a row is drawn, naming the path it looked for."""

import copy
import json
import os

import numpy as np
import pytest

from harness import algbytes, reference, spec, table

HERE = os.path.dirname(os.path.abspath(__file__))

STEPPED = '''
"""``stepped``: ``of`` rounded down to a multiple of ``step``, drawn by
nothing; the test's own kind."""
import numpy as np

def needs(spec):
    return [spec["of"]]

def column(spec, raw, cols, seed):
    return cols[spec["of"]] // spec["step"] * spec["step"]

def domain_size(spec):
    return -(-spec["high"] // spec["step"])

def value_of(spec, index):
    return index * spec["step"]

def index_of(spec, values):
    return values.astype(np.int64) // spec["step"]
'''

LARGER = '''
"""``larger``: the larger of two; the test's own operator."""
import numpy as np

def apply(a, b):
    return np.maximum(a, b)
'''


@pytest.fixture
def own(tmp_path, monkeypatch):
    """Directories of the test's own, looked in after the benchmark's."""
    for family, name, text in (("generators", "stepped", STEPPED),
                               ("operators", "larger", LARGER)):
        (tmp_path / family).mkdir()
        (tmp_path / family / f"{name}.py").write_text(text)
        monkeypatch.setitem(spec.FOUND_IN, family,
                            spec.FOUND_IN[family] + [str(tmp_path / family)])
    with open(os.path.join(HERE, "testdata", "ssb_tiny_bydate.json")) as f:
        config = json.load(f)
    config["generator"].append({"column": "revenue_band", "kind": "stepped",
                                "of": "lo_revenue", "step": 1_000_000,
                                "high": 6_000_000})
    statement = {"name": "by_band", "columns": ["revenue_band", "lo_quantity"],
                 "reference": {
                     "where": [["lo_orderdate", "between", 19930101, 19931231]],
                     "group_by": ["revenue_band"],
                     "aggregates": [["sum", ["larger", "lo_quantity",
                                             ["mul", 3, "lo_discount"]]]],
                     "order_by": [["key", 0, "desc"]]}}
    return config, statement


def test_a_kind_and_an_operator_of_the_tests_own_are_found(own):
    config, statement = own
    for family, name in (("generators", "stepped"), ("operators", "larger")):
        assert not os.path.exists(
            os.path.join(spec.BENCH_DIR, family, name + ".py"))
    table.check_generator(config)
    ref = reference.Reference(config, [statement])
    assert ref.columns == {"lo_orderdate", "revenue_band", "lo_quantity",
                           "lo_discount"}
    want = {}
    for k, cols in enumerate(table.reference_segments(config, 9, ref.columns)):
        ref.add(cols)
        whole = table.lay_out(config, table.draw_segment(
            config, table.segment_rng(9, k), seed=9, k=k))
        assert (whole["revenue_band"]
                == whole["lo_revenue"] // 1_000_000 * 1_000_000).all()
        year = (whole["lo_orderdate"] // 10000) == 1993
        for band, q, d in zip(whole["revenue_band"][year].tolist(),
                              whole["lo_quantity"][year].tolist(),
                              whole["lo_discount"][year].tolist()):
            want[band] = want.get(band, 0) + max(q, 3 * d)
    assert ref.rows()["by_band"] == [
        [band, want[band]] for band in sorted(want, reverse=True)]
    assert len(want) == table.domain_size(config, "revenue_band") == 6
    # one byte for six bands, one for the quantity; a year meets 2 of 8
    assert algbytes.statement_bytes(config, statement) \
        == 2 * config["rows_per_segment"] * 2


def test_an_unknown_kind_or_operator_fails_before_the_table(own, tmp_path):
    config, statement = own
    wrong = copy.deepcopy(config)
    wrong["generator"][-1]["kind"] = "zipf"
    said = []
    with pytest.raises(SystemExit) as e:
        table.build_table(wrong, 1, str(tmp_path / "built"), None, said.append)
    assert os.path.join(spec.BENCH_DIR, "generators", "zipf.py") in str(e.value)
    assert str(tmp_path / "generators" / "zipf.py") in str(e.value)
    assert not said and not (tmp_path / "built").exists()
    wrong = copy.deepcopy(statement)
    wrong["reference"]["aggregates"] = [["sum", ["pow", "lo_quantity", 2]]]
    with pytest.raises(SystemExit) as e:
        reference.Reference(config, [wrong])
    assert os.path.join(spec.BENCH_DIR, "operators", "pow.py") in str(e.value)
    # so does an arithmetic column's, and a name that is no file's name
    wrong = copy.deepcopy(config)
    wrong["generator"].append({"column": "x", "kind": "arith", "low": 0,
                               "high": 9, "expr": ["pow", "lo_quantity", 2]})
    with pytest.raises(SystemExit, match="pow.py"):
        table.check_generator(wrong)
    wrong["generator"][-1] = {"column": "x", "kind": "../harness/table"}
    with pytest.raises(SystemExit, match="no name"):
        table.check_generator(wrong)
    with pytest.raises(ValueError, match="helper"):
        reference.Reference(
            dict(config, generator=[dict(g, helper=True)
                                    for g in config["generator"]]),
            [statement])
