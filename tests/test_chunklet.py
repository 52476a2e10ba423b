"""Chunklet subsystem correctness: columnar batch ingest equivalence and
device-promotion differentials.

The two contracts the subsystem must never bend (realtime/chunklet.py):

1. ``index_batch`` is byte-for-byte EQUIVALENT to row-at-a-time ``index``
   — same query results while consuming AND after seal (the seal-
   equivalence tests);
2. splitting a consuming segment into device chunklets + host tail changes
   WHERE rows execute, never WHAT they answer: device+host mixed results
   == all-host == post-seal immutable, including under upsert validDocIds
   masks (the differential tests).
"""

import threading
import time

import numpy as np
import pytest

from pinot_tpu.common.datatypes import DataType
from pinot_tpu.common.schema import Schema
from pinot_tpu.common.table_config import (
    ChunkletConfig,
    TableConfig,
    UpsertConfig,
)
from pinot_tpu.engine.engine import QueryEngine
from pinot_tpu.realtime.chunklet import split_for_query
from pinot_tpu.realtime.upsert import PartitionUpsertMetadataManager
from pinot_tpu.storage.mutable import MutableSegment


def make_schema(pk=False, mv=False):
    return Schema.build(
        name="rt",
        dimensions=[("zone", DataType.STRING), ("hour", DataType.INT)],
        multi_value_dimensions=[("tags", DataType.STRING)] if mv else [],
        metrics=[("fare", DataType.INT)],
        datetimes=[("ts", DataType.LONG)],
        primary_key_columns=["zone"] if pk else [],
    )


def make_rows(n, zones=40, seed=0, with_nulls=True):
    rng = np.random.default_rng(seed)
    rows = []
    for i in range(n):
        r = {
            "zone": f"z{int(rng.integers(0, zones)):03d}",
            "hour": int(rng.integers(0, 24)),
            "fare": int(rng.integers(0, 10_000)),
            "ts": i,
        }
        if with_nulls and i % 37 == 0:
            del r["fare"]  # -> null default + null vector entry
        rows.append(r)
    return rows


def chunklet_config(rows_per=1024, min_rows=0):
    return TableConfig(
        table_name="rt",
        chunklets=ChunkletConfig(enabled=True, rows_per_chunklet=rows_per,
                                 device_min_rows=min_rows))


QUERIES = [
    "SELECT COUNT(*), SUM(fare) FROM rt",
    "SELECT zone, COUNT(*), SUM(fare), MIN(fare), MAX(fare) FROM rt "
    "GROUP BY zone ORDER BY zone LIMIT 100",
    "SELECT hour, AVG(fare) FROM rt WHERE zone <> 'z001' "
    "GROUP BY hour ORDER BY hour LIMIT 30",
    "SELECT COUNT(*) FROM rt WHERE fare IS NULL",
    "SELECT COUNT(*) FROM rt WHERE fare > 5000 AND hour BETWEEN 3 AND 20",
]


def rows_of(engine, sql):
    r = engine.execute(sql)
    assert not r.get("exceptions"), (sql, r)
    return r["resultTable"]["rows"]


class TestIndexBatchEquivalence:
    def test_seal_equivalence_batch_vs_rows(self, tmp_path):
        rows = make_rows(3000)
        a = MutableSegment(make_schema(), "a", chunklet_config())
        a.index_batch(rows)
        b = MutableSegment(make_schema(), "b")
        for r in rows:
            b.index(r)
        assert a.n_docs == b.n_docs == 3000
        ea = QueryEngine(device_executor=None)
        ea.table("rt").add_segment(a)
        eb = QueryEngine(device_executor=None)
        eb.table("rt").add_segment(b)
        for sql in QUERIES:
            assert rows_of(ea, sql) == rows_of(eb, sql), sql
        # sealed outputs answer identically too (chunklet seal-reuse path
        # on one side: a has promoted blocks, b never had any)
        a.chunklet_index.promote()
        assert len(a.chunklet_index.chunklets) > 0
        sa = a.seal(str(tmp_path / "sa"))
        sb = b.seal(str(tmp_path / "sb"))
        e1 = QueryEngine(device_executor=None)
        e1.table("rt").add_segment(sa)
        e2 = QueryEngine(device_executor=None)
        e2.table("rt").add_segment(sb)
        for sql in QUERIES:
            assert rows_of(e1, sql) == rows_of(e2, sql), sql

    def test_mv_and_missing_columns(self):
        schema = make_schema(mv=True)
        rows = [
            {"zone": "a", "hour": 1, "fare": 10, "ts": 0,
             "tags": ["x", "y"]},
            {"zone": "b", "hour": 2, "ts": 1, "tags": []},  # fare null
            {"zone": "a", "hour": 3, "fare": 30, "ts": 2, "tags": ["y"]},
        ]
        a = MutableSegment(schema, "a")
        a.index_batch(rows)
        b = MutableSegment(schema, "b")
        for r in rows:
            b.index(r)
        # MV schema: no chunklet index (host path keeps the whole segment)
        assert a.chunklet_index is None
        for seg in (a, b):
            e = QueryEngine(device_executor=None)
            e.table("rt").add_segment(seg)
            assert rows_of(e, "SELECT COUNT(*) FROM rt WHERE tags = 'y'") \
                == [[2]]
            assert rows_of(e, "SELECT COUNT(*) FROM rt WHERE fare IS NULL") \
                == [[1]]

    def test_bad_row_fails_batch_atomically(self):
        seg = MutableSegment(make_schema(), "a")
        with pytest.raises(Exception):
            seg.index_batch([
                {"zone": "a", "hour": 1, "fare": 1, "ts": 0},
                {"zone": "b", "hour": "not-an-int", "fare": 2, "ts": 1},
            ])
        assert seg.n_docs == 0  # nothing published
        # and state is not corrupted for subsequent appends
        seg.index_batch([{"zone": "c", "hour": 3, "fare": 3, "ts": 2}])
        assert seg.n_docs == 1
        assert seg.row_value("zone", 0) == "c"

    def test_upsert_keeps_row_path_semantics(self):
        # index_batch is not used for upsert tables by the manager; the
        # segment-level API still grows validDocIds correctly if called
        seg = MutableSegment(make_schema(pk=True), "a",
                             chunklet_config(), enable_upsert=True)
        seg.index_batch(make_rows(5000, with_nulls=False))
        assert seg.valid_docs(5000).all()


class TestChunkletPromotion:
    def test_promotion_boundaries(self):
        seg = MutableSegment(make_schema(), "a", chunklet_config(1024))
        ci = seg.chunklet_index
        seg.index_batch(make_rows(1023))
        assert ci.promote() == 0  # one short of a block
        seg.index_batch(make_rows(1))
        assert ci.promote() == 1
        assert ci.frozen_docs == 1024
        seg.index_batch(make_rows(5000))
        assert ci.promote() == 4
        assert ci.chunklets[-1].stop == 5120
        # chunklet metadata matches its slice
        ck = ci.chunklets[0]
        assert ck.n_docs == 1024
        assert ck.column_metadata("zone").cardinality > 0
        np.testing.assert_array_equal(
            ck.flat_values("fare"),
            np.asarray(seg._cols["fare"].values(1024)))

    def test_crossover_threshold_gates_split(self):
        seg = MutableSegment(make_schema(), "a",
                             chunklet_config(1024, min_rows=10_000))
        seg.index_batch(make_rows(4096, with_nulls=False))
        seg.chunklet_index.promote()
        assert split_for_query(seg) is None  # frozen 4096 < 10_000
        seg.index_batch(make_rows(8000, with_nulls=False))
        seg.chunklet_index.promote()
        split = split_for_query(seg)
        assert split is not None
        device, host = split
        assert sum(c.n_docs for c in device) == 11 * 1024
        assert sum(h.n_docs for h in host) == seg.n_docs - 11 * 1024


class TestMixedBackendDifferential:
    """device-chunklet + host-tail == all-host == post-seal immutable."""

    def _twins(self, rows):
        a = MutableSegment(make_schema(), "a", chunklet_config())
        a.index_batch(rows)
        a.chunklet_index.promote()
        assert len(a.chunklet_index.chunklets) >= 2
        b = MutableSegment(make_schema(), "b")
        for r in rows:
            b.index(r)
        dev = QueryEngine()
        dev.table("rt").add_segment(a)
        host = QueryEngine(device_executor=None)
        host.table("rt").add_segment(b)
        return a, dev, host

    def test_differential_consuming_vs_host_vs_sealed(self, tmp_path):
        rows = make_rows(5500)
        a, dev, host = self._twins(rows)
        # the split actually engages (device chunklets exist)
        assert split_for_query(a) is not None
        for sql in QUERIES:
            assert rows_of(dev, sql) == rows_of(host, sql), sql
        sealed = a.seal(str(tmp_path / "s"))
        es = QueryEngine()
        es.table("rt").add_segment(sealed)
        for sql in QUERIES:
            assert rows_of(es, sql) == rows_of(host, sql), sql

    def test_differential_under_upsert_masks(self):
        schema = make_schema(pk=True)
        cfg = TableConfig(
            table_name="rt",
            upsert=UpsertConfig(mode="FULL", comparison_column="ts"),
            chunklets=ChunkletConfig(enabled=True, rows_per_chunklet=1024,
                                     device_min_rows=0))
        rng = np.random.default_rng(9)
        n = 4000
        rows = [{"zone": f"z{int(rng.integers(0, 2500)):04d}",
                 "hour": int(rng.integers(0, 24)),
                 "fare": int(rng.integers(0, 1000)), "ts": i}
                for i, _ in enumerate(range(n))]

        def build(table_config, with_chunklets):
            seg = MutableSegment(schema, "s", table_config,
                                 enable_upsert=True)
            ups = PartitionUpsertMetadataManager("ts")
            for r in rows:
                did = seg.index(r)
                ups.add_record(seg, did, (r["zone"],), r["ts"])
            if with_chunklets:
                seg.chunklet_index.promote()
            # late updates: invalidations land INSIDE the frozen prefix
            for i in range(600):
                r = {"zone": f"z{i % 2500:04d}", "hour": 0,
                     "fare": 99_999, "ts": n + i}
                did = seg.index(r)
                ups.add_record(seg, did, (r["zone"],), r["ts"])
            if with_chunklets:
                seg.chunklet_index.promote()
            return seg

        a = build(cfg, True)
        dirty = sum(0 if c.is_clean else 1
                    for c in a.chunklet_index.chunklets)
        assert dirty > 0  # masks actually engaged over the prefix
        b = build(TableConfig(table_name="rt", upsert=cfg.upsert), False)
        dev = QueryEngine()
        dev.table("rt").add_segment(a)
        host = QueryEngine(device_executor=None)
        host.table("rt").add_segment(b)
        for sql in QUERIES[:3] + [
                "SELECT COUNT(*) FROM rt WHERE fare = 99999"]:
            assert rows_of(dev, sql) == rows_of(host, sql), sql

    def test_differential_while_ingesting(self):
        """Snapshot consistency: queries during concurrent batch ingest +
        promotion never error and counts only grow."""
        seg = MutableSegment(make_schema(), "a", chunklet_config())
        eng = QueryEngine()
        eng.table("rt").add_segment(seg)
        stop = threading.Event()
        errors = []

        def ingest():
            try:
                for i in range(40):
                    seg.index_batch(make_rows(256, seed=i,
                                              with_nulls=False))
                    seg.chunklet_index.promote()
                    time.sleep(0.001)
            except Exception as e:  # noqa: BLE001
                errors.append(repr(e))
            finally:
                stop.set()

        t = threading.Thread(target=ingest)
        t.start()
        last = 0
        while not stop.is_set():
            r = eng.execute("SELECT COUNT(*) FROM rt")
            assert not r.get("exceptions"), r
            c = r["resultTable"]["rows"][0][0]
            assert c >= last
            last = c
        t.join()
        assert not errors, errors
        assert rows_of(eng, "SELECT COUNT(*) FROM rt") == [[40 * 256]]


class TestProcessHarness:
    def test_ingest_worker_subprocess(self):
        """The per-partition OS-process consume loop runs standalone and
        reports its rows/s."""
        import json
        import os
        import subprocess
        import sys

        spec = json.dumps({"rows": 30_000, "partition": 3,
                           "rows_per_chunklet": 8192, "payload": "json"})
        env = {**os.environ, "JAX_PLATFORMS": "cpu"}
        out = subprocess.run(
            [sys.executable, "-m", "pinot_tpu.realtime.chunklet", spec],
            capture_output=True, timeout=120, env=env)
        assert out.returncode == 0, out.stderr.decode()[-2000:]
        rep = json.loads(out.stdout)
        assert rep["rows"] == 30_000 and rep["errors"] == 0
        assert rep["chunklets"] == 30_000 // 8192
        assert rep["rows_per_s"] > 0


class TestConfig:
    def test_chunklet_config_json_roundtrip(self):
        cfg = TableConfig(
            table_name="t",
            chunklets=ChunkletConfig(enabled=False, rows_per_chunklet=2048,
                                     device_min_rows=123))
        cfg2 = TableConfig.from_json(cfg.to_json())
        assert cfg2.chunklets == cfg.chunklets
        seg = MutableSegment(
            Schema.build(name="t", dimensions=[("d", DataType.STRING)],
                         metrics=[("m", DataType.INT)]),
            "s", cfg2)
        assert seg.chunklet_index is None  # disabled honors the knob
