"""What the program may read from its surroundings, held as lists: the
environment switches its sources name, and the measurement code (the
benchmark, its harness, the chip smoke run) it must never import. A new
switch or a new dependency shows as a diff of this file."""

import ast
import os
import re

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PROGRAM = os.path.join(ROOT, "pinot_tpu")

# every literal name; ``common/config.py`` also reads any key under its
# ``PINOT_TPU_`` prefix (dots as underscores), which is no literal
ENV_SWITCHES = [
    "PINOT_TPU_BATCH_CACHE_BYTES",
    "PINOT_TPU_EXCHANGE_BUFFER_BYTES",
    "PINOT_TPU_FAILURE_DOMAIN",
    "PINOT_TPU_FAULTS",
    "PINOT_TPU_FORCE_WIDE",
    "PINOT_TPU_MAX_JOIN_PAIRS",
    "PINOT_TPU_MAX_JOIN_ROWS",
    "PINOT_TPU_NO_NATIVE",
    "PINOT_TPU_PALLAS",
    "PINOT_TPU_PALLAS_HLL_SLOTS",
    "PINOT_TPU_PARTIALS_CACHE",
    "PINOT_TPU_PARTIALS_CACHE_BYTES",
    "PINOT_TPU_PARTIALS_CACHE_ENTRIES",
    "PINOT_TPU_PLUGINS",
    "PINOT_TPU_S3_ENDPOINT",
    "PINOT_TPU_SUBBYTE",
    "PINOT_TPU_WIDTH_AUDIT",
]
MEASUREMENT_CODE = {"bench", "chip_smoke", "benchmark", "harness"}


def _sources():
    for folder, _dirs, files in os.walk(PROGRAM):
        for name in sorted(files):
            if name.endswith((".py", ".cpp")):
                path = os.path.join(folder, name)
                with open(path, encoding="utf-8") as f:
                    yield path, f.read()


def test_program_env_switches_are_the_listed_ones():
    found = set()
    for _path, text in _sources():
        found.update(re.findall(r"PINOT_TPU_[A-Z0-9_]*[A-Z0-9]", text))
    assert sorted(found) == ENV_SWITCHES


def test_program_imports_no_measurement_code():
    offenders = []
    for path, text in _sources():
        if not path.endswith(".py"):
            continue
        for node in ast.walk(ast.parse(text, path)):
            if isinstance(node, ast.Import):
                names = [a.name for a in node.names]
            elif isinstance(node, ast.ImportFrom) and node.level == 0:
                names = [node.module or ""]
            else:
                continue
            offenders += [(os.path.relpath(path, ROOT), n) for n in names
                          if n.split(".")[0] in MEASUREMENT_CODE]
    assert not offenders, offenders
