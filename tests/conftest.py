"""Test environment: force JAX onto CPU with 8 virtual devices.

Must run before the first ``import jax`` anywhere in the test process so the
multi-chip sharding paths (parallel/mesh.py) are exercised on a virtual
8-device mesh, per the driver's dryrun contract.
"""

import os

# force, don't setdefault: a machine with a chip defaults JAX to it, and
# tests must not contend for the chip
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()
# hermetic: neither this process nor the servers the tests spawn read or
# write the persistent compile cache pinot_tpu/__init__.py configures
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

import jax

# the config values too: env mutation alone is too late if anything
# imported jax before this file ran
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)
jax.config.update("jax_enable_compilation_cache", False)

import numpy as np
import pytest

from pinot_tpu.common.datatypes import DataType
from pinot_tpu.common.schema import Schema


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(42)


@pytest.fixture(scope="session")
def baseball_schema():
    return Schema.build(
        name="baseballStats",
        dimensions=[
            ("playerName", DataType.STRING),
            ("teamID", DataType.STRING),
            ("league", DataType.STRING),
            ("yearID", DataType.INT),
        ],
        metrics=[
            ("runs", DataType.INT),
            ("hits", DataType.INT),
            ("homeRuns", DataType.INT),
            ("salary", DataType.DOUBLE),
        ],
    )


def make_baseball_columns(rng, n=5000):
    players = np.array([f"player_{i:03d}" for i in range(200)])
    teams = np.array([f"team_{i}" for i in range(30)])
    leagues = np.array(["AL", "NL"])
    return {
        "playerName": players[rng.integers(0, len(players), n)],
        "teamID": teams[rng.integers(0, len(teams), n)],
        "league": leagues[rng.integers(0, 2, n)],
        "yearID": rng.integers(1980, 2020, n).astype(np.int32),
        "runs": rng.integers(0, 150, n).astype(np.int32),
        "hits": rng.integers(0, 200, n).astype(np.int32),
        "homeRuns": rng.integers(0, 60, n).astype(np.int32),
        "salary": np.round(rng.uniform(1e4, 1e7, n), 2),
    }


@pytest.fixture(scope="session")
def baseball_columns(rng):
    return make_baseball_columns(rng)


@pytest.fixture(scope="session")
def baseball_segment(tmp_path_factory, baseball_schema, baseball_columns):
    from pinot_tpu.common.table_config import IndexingConfig, TableConfig
    from pinot_tpu.storage.creator import build_segment

    out = tmp_path_factory.mktemp("segments") / "baseball_0"
    cfg = TableConfig(
        table_name="baseballStats",
        indexing=IndexingConfig(
            inverted_index_columns=["teamID", "league"],
            bloom_filter_columns=["playerName"],
        ),
    )
    return build_segment(baseball_schema, baseball_columns, str(out), cfg, "baseball_0")
