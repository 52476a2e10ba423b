"""The SSB-shaped ``lineorder`` table: schema, table config, seeded column
generator and the suite's statements — the one definition ``chip_smoke.py``
and the benchmark's ``ssbproxy_…`` configurations both import.

Nine columns, two star-tree configs (the 3-dim revenue cube and the
``lo_suppkey`` cube carrying COUNT/SUM/HLL planes) and an inverted index
on ``lo_suppkey``. ``BASELINE.json`` names the target size: 8 segments of
12,500,000 rows.
"""

from __future__ import annotations

import numpy as np

SEGMENTS = 8
SEGMENT_ROWS = 12_500_000  # x8 = 100M
SEED = 7

_NATIONS = np.array([f"nation_{i:02d}" for i in range(25)])
_REGIONS = np.array(["AFRICA", "AMERICA", "ASIA", "EUROPE", "MIDEAST"])


def lineorder_schema():
    from pinot_tpu.common.datatypes import DataType
    from pinot_tpu.common.schema import Schema

    return Schema.build(
        name="lineorder",
        dimensions=[
            ("d_year", DataType.INT),
            ("c_region", DataType.STRING),
            ("s_nation", DataType.STRING),
            ("lo_suppkey", DataType.INT),
            ("lo_custkey", DataType.INT),
            ("lo_orderdate", DataType.INT),
            ("lo_discount", DataType.INT),
        ],
        metrics=[("lo_quantity", DataType.INT), ("lo_revenue", DataType.INT)],
    )


def lineorder_table_config():
    from pinot_tpu.common.table_config import (
        IndexingConfig,
        StarTreeIndexConfig,
        TableConfig,
    )

    return TableConfig(
        table_name="lineorder",
        indexing=IndexingConfig(
            inverted_index_columns=["lo_suppkey"],
            star_tree_configs=[
                StarTreeIndexConfig(
                    dimensions_split_order=["d_year", "c_region", "s_nation"],
                    function_column_pairs=["SUM__lo_revenue", "COUNT__*"],
                ),
                # the q4 shape: high-card group-by + HLL — sketch (register
                # plane) pre-aggregation in the cube
                StarTreeIndexConfig(
                    dimensions_split_order=["lo_suppkey"],
                    function_column_pairs=[
                        "COUNT__*", "SUM__lo_quantity",
                        "DISTINCTCOUNTHLL__lo_custkey",
                    ],
                ),
            ],
        ),
    )


def segment_columns(rng: np.random.Generator, n: int) -> dict:
    """One segment's columns, drawn from ``rng`` in a fixed order: calling
    this once per segment on ONE generator seeded with ``SEED`` reproduces
    the table every earlier round was measured on."""
    return {
        "d_year": rng.integers(1992, 1999, n).astype(np.int32),
        "c_region": _REGIONS[rng.integers(0, 5, n)],
        "s_nation": _NATIONS[rng.integers(0, 25, n)],
        "lo_suppkey": rng.integers(0, 2000, n).astype(np.int32),
        "lo_custkey": rng.integers(0, 100_000, n).astype(np.int32),
        # date-like ints spanning 1992-01-01..1998-08-02 (SSB's range) so
        # Q1.x's 1993 BETWEEN actually selects rows (a prior generator
        # capped at 19922405 — every segment min/max-pruned and "q2" was
        # a 1.6ms no-op)
        "lo_orderdate": (
            19920101
            + (rng.integers(0, 7, n) * 10000)
            + (rng.integers(0, 12, n) * 100)
            + rng.integers(0, 28, n)
        ).astype(np.int32),
        "lo_discount": rng.integers(0, 11, n).astype(np.int32),
        "lo_quantity": rng.integers(1, 51, n).astype(np.int32),
        "lo_revenue": rng.integers(1000, 6_000_000, n).astype(np.int32),
    }


QUERIES = {
    # 1. baseballStats shape: full scan-agg group-by
    "q1_scan_agg": (
        "SET useStarTree = false; "
        "SELECT lo_suppkey, SUM(lo_revenue) FROM lineorder "
        "GROUP BY lo_suppkey ORDER BY SUM(lo_revenue) DESC LIMIT 10"
    ),
    # 2. SSB Q1.x shape: date range + discount/quantity bands
    "q2_range_sum": (
        "SELECT SUM(lo_revenue) FROM lineorder WHERE "
        "lo_orderdate BETWEEN 19930101 AND 19931231 "
        "AND lo_discount BETWEEN 1 AND 3 AND lo_quantity < 25"
    ),
    # 3. inverted-index shape: IN + range
    "q3_in_range": (
        "SELECT COUNT(*), SUM(lo_revenue) FROM lineorder WHERE "
        "lo_suppkey IN (11, 234, 567, 890, 1203, 1456, 1789) "
        "AND lo_discount BETWEEN 4 AND 6"
    ),
    # 4. NYC-taxi shape: high-cardinality group-by + HLL (cube-eligible:
    # the lo_suppkey star-tree pre-aggregates COUNT/SUM/HLL planes)
    # lo_suppkey tiebreaker: groups tied on COUNT(*) at the LIMIT boundary
    # must order identically on the cube and scan plans or the exactness
    # gate below flakes on tied data
    "q4_highcard_hll": (
        "SELECT lo_suppkey, COUNT(*), AVG(lo_quantity), "
        "DISTINCTCOUNTHLL(lo_custkey) FROM lineorder "
        "GROUP BY lo_suppkey ORDER BY COUNT(*) DESC, lo_suppkey LIMIT 10"
    ),
    # 4b. the same shape forced off the cube: DEFAULT engine behavior,
    # which lazily builds a sorted (group, hash) projection on first use
    # (BatchContext.sorted_hll_keys) and reuses it — steady state pays
    # boundaries + one matmul, not the sort
    "q4_scan_hll": (
        "SET useStarTree = false; "
        "SELECT lo_suppkey, COUNT(*), AVG(lo_quantity), "
        "DISTINCTCOUNTHLL(lo_custkey) FROM lineorder "
        "GROUP BY lo_suppkey ORDER BY COUNT(*) DESC, lo_suppkey LIMIT 10"
    ),
    # 4c. the COLD frontier: no cube AND no cached projection — every
    # query pays the full sort (the conservative number the headline uses)
    "q4_scan_hll_cold": (
        "SET useStarTree = false; SET useSortedProjection = false; "
        "SELECT lo_suppkey, COUNT(*), AVG(lo_quantity), "
        "DISTINCTCOUNTHLL(lo_custkey) FROM lineorder "
        "GROUP BY lo_suppkey ORDER BY COUNT(*) DESC, lo_suppkey LIMIT 10"
    ),
    # 5. SSB Q4.x shape: star-tree 3-dim pre-aggregated group-by
    "q5_startree": (
        "SELECT d_year, c_region, SUM(lo_revenue), COUNT(*) FROM lineorder "
        "GROUP BY d_year, c_region ORDER BY d_year, c_region LIMIT 50"
    ),
}
