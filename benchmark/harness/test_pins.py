"""The accepted cells draw and answer as before: what ``draw_segment``,
``lay_out`` and ``Reference.rows()`` gave on the parent of PR 31 (commit
583541c, taken before any edit, the sha256 of every column's name, dtype and
bytes in generated order; of the answers' JSON), and every statement's
algorithmic bytes. A change to the generator, the layout or the reference
that moves one bit of an accepted configuration fails here."""

import hashlib
import json
import os

import numpy as np
import pytest

from harness import algbytes, reference, spec, table

HERE = os.path.dirname(os.path.abspath(__file__))
MIXES = ("groupby_bands_c4", "range_years_c4", "range_bands_c4")

TINY = {
    ("ssb_tiny", 7): (
        "a880d8178c96834feb1d5a4831da24590e47e79af8f3e3d35bbc883b71de2a67",
        "a880d8178c96834feb1d5a4831da24590e47e79af8f3e3d35bbc883b71de2a67",
        "ef21fb5b80f1094999c0edc97f3a4e6decf468db751affcb3a66266c6f0f0c06"),
    ("ssb_tiny", 11): (
        "90b8a65b3a2aedd46104496f6790fec9f349850616e4b049221aa6ee74063485",
        "90b8a65b3a2aedd46104496f6790fec9f349850616e4b049221aa6ee74063485",
        "40186fe58d9a15e425fa74aa18e07668b35bb037471c7f68ec75587db07aaff8"),
    ("ssb_tiny_bydate", 7): (
        "a880d8178c96834feb1d5a4831da24590e47e79af8f3e3d35bbc883b71de2a67",
        "8a3c81284d7015feb3831fc9e4fea17a0dc3a7682c0bd2bb7665c6e6ef713958",
        "11ca4970a29818e20447c091cb8a3fc2b6f5f1c499b526d8640404e938f8c4b8"),
    ("ssb_tiny_bydate", 11): (
        "90b8a65b3a2aedd46104496f6790fec9f349850616e4b049221aa6ee74063485",
        "2b3d26cd82f5f08ebbe382035599a0c1358f1aec02a3d57c921c235bc149761b",
        "c312342d3decf0d7b67e8a59a03d885cc08e14885e1cf53824de5af0754d145a"),
}
# the first 1,000 rows of segment 0 at seed 2147495001, 12,500,000 rows drawn
FIRST_ROWS = {
    "ssbproxy_lineorder_100m": (
        "ef4f61016a6b72f21889da4a0d66000bc8f039a0f8f2f8a0ad3980fea0a6d598",
        "ef4f61016a6b72f21889da4a0d66000bc8f039a0f8f2f8a0ad3980fea0a6d598"),
    "ssbproxy_lineorder_100m_bydate": (
        "ef4f61016a6b72f21889da4a0d66000bc8f039a0f8f2f8a0ad3980fea0a6d598",
        "e080967f9dee6322aaaca3937a66864ab335b5788a346ef1332ead93ea22898d"),
}
# bytes a row: a banded group-by 6, a range sum 7; by date a year meets 2
# of 8 segments
BYTES = {"ssbproxy_lineorder_100m": (600_000_000, 700_000_000),
         "ssbproxy_lineorder_100m_bydate": (600_000_000, 175_000_000),
         "ssb_tiny": (9_600_000, 11_200_000),
         "ssb_tiny_bydate": (9_600_000, 2_800_000)}


def _json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def _config(name):
    if name.startswith("ssbproxy"):
        return _json(spec.BENCH_DIR, "configs", name + ".json")
    return _json(HERE, "testdata", name + ".json")


def _statements():
    return [s for m in MIXES for s in
            _json(spec.BENCH_DIR, "traffic", m + ".json")["statements"]]


def _sha(cols, rows=None):
    h = hashlib.sha256()
    for name, v in cols.items():
        h.update(name.encode())
        h.update(str(v.dtype).encode())
        h.update(np.ascontiguousarray(v[:rows]).tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("name,seed", sorted(TINY))
def test_tiny_tables_and_answers_are_the_parents(name, seed):
    config = _config(name)
    drawn, laid = hashlib.sha256(), hashlib.sha256()
    for k in range(config["segments"]):
        drawn.update(_sha(table.draw_segment(
            config, table.segment_rng(seed, k))).encode())
        laid.update(_sha(table.lay_out(config, table.draw_segment(
            config, table.segment_rng(seed, k), seed=seed, k=k))).encode())
    ref = reference.Reference(config, _statements())
    for cols in table.reference_segments(config, seed, ref.columns):
        ref.add(cols)
    rows = hashlib.sha256(
        json.dumps(ref.rows(), sort_keys=True).encode()).hexdigest()
    assert (drawn.hexdigest(), laid.hexdigest(), rows) == TINY[name, seed]


@pytest.mark.parametrize("name", sorted(FIRST_ROWS))
def test_accepted_configurations_first_rows_are_the_parents(name):
    config, seed = _config(name), 2147495001
    drawn = table.draw_segment(config, table.segment_rng(seed, 0))
    laid = table.lay_out(config, table.draw_segment(
        config, table.segment_rng(seed, 0), seed=seed, k=0))
    assert (_sha(drawn, 1000), _sha(laid, 1000)) == FIRST_ROWS[name]


@pytest.mark.parametrize("name", sorted(BYTES))
def test_algorithmic_bytes_are_the_parents(name):
    config = _config(name)
    groupby, range_sum = BYTES[name]
    for s in _statements():
        want = groupby if s["name"].startswith("groupby") else range_sum
        assert algbytes.statement_bytes(config, s) == want, s["name"]
