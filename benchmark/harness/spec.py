"""Finding a cell's files by name. ``BENCHMARK.json`` names a workload; its
``config`` entry names the configuration's file; its ``traffic`` is the file
``benchmark/traffic/<traffic>.json``; a per-layer metric ``<name>`` is read
by ``benchmark/layer_metrics/<name>.py``; a ``generator`` entry's ``kind``
``<K>`` is drawn by ``benchmark/generators/<K>.py``; an operator ``<op>`` of
an integer expression is ``benchmark/operators/<op>.py``; peaks come from
``benchmark/peaks.json`` by ``device_kind``. No file here names a kind, an
operator or a metric: a PR that brings one adds its file.
"""

from __future__ import annotations

import importlib.util
import json
import os
import re

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


# where each family's files are looked for, in order (a test adds a
# directory of its own to show that no file here knows a name)
FOUND_IN = {family: [os.path.join(BENCH_DIR, family)]
            for family in ("generators", "operators", "layer_metrics")}
_found: dict = {}


def _load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def found(family: str, name: str):
    """The module ``<family>/<name>.py``, loaded once. A name that no file
    answers to is an error that says where it looked."""
    if not (isinstance(name, str) and re.fullmatch(r"[A-Za-z0-9_.-]+", name)):
        raise SystemExit(f"{name!r} is no name of one of the {family}")
    tried = [os.path.join(d, f"{name}.py") for d in FOUND_IN[family]]
    path = next((p for p in tried if os.path.isfile(p)), None)
    if path is None:
        raise SystemExit(f"nothing in the {family} is called {name!r}: "
                         f"looked for {' and '.join(tried)}")
    if path not in _found:
        mod_spec = importlib.util.spec_from_file_location(
            f"{family}_" + re.sub(r"[.-]", "_", name), path)
        mod = importlib.util.module_from_spec(mod_spec)
        mod_spec.loader.exec_module(mod)
        _found[path] = mod
    return _found[path]


class Cell:
    def __init__(self, workload: str, benchmark_json: str | None = None):
        path = benchmark_json or os.path.join(ROOT, "BENCHMARK.json")
        # a configuration's file is named from the checkout's root; a test's
        # BENCHMARK.json sits elsewhere and names its files the same way
        self.spec = _load(path)
        cells = {w["name"]: w for w in self.spec["workloads"]}
        if workload not in cells:
            raise SystemExit(f"no workload {workload!r} in {path}; it has "
                             f"{sorted(cells)}")
        self.workload = cells[workload]
        self.name = workload
        self.chips = self.workload["chips"]
        configs = {c["name"]: c for c in self.spec["configs"]}
        self.config = _load(os.path.join(
            ROOT, configs[self.workload["config"]]["file"]))
        self.traffic_file = os.path.join(
            BENCH_DIR, "traffic", self.workload["traffic"] + ".json")
        self.traffic = _load(self.traffic_file)

    def _applies(self, metric: dict) -> bool:
        # the contract's ``workloads`` key: a metric that only the cells it
        # lists can report. No entry has one yet; later PRs add such
        # metrics and may not edit this file.
        return self.name in metric.get("workloads", [self.name])

    def end_to_end(self) -> list:
        return [m for m in self.spec["end_to_end"] if self._applies(m)]

    def layer_readers(self) -> list:
        """[(metric entry, its module)] for this cell's per-layer metrics."""
        out = []
        for m in self.spec["per_layer"]:
            if not self._applies(m):
                continue
            mod = found("layer_metrics", m["name"])
            for key, attr in (("layer", "LAYER"), ("unit", "UNIT"),
                              ("moves", "MOVES")):
                if getattr(mod, attr) != m[key]:
                    raise SystemExit(
                        f"{mod.__file__}: {attr}={getattr(mod, attr)!r} but "
                        f"BENCHMARK.json says {m[key]!r}")
            out.append((m, mod))
        return out


def peaks(device_kind: str) -> dict:
    table = _load(os.path.join(BENCH_DIR, "peaks.json"))["devices"]
    if device_kind not in table:
        raise SystemExit(f"device kind {device_kind!r} is not in "
                         f"benchmark/peaks.json ({sorted(table)})")
    return table[device_kind]
