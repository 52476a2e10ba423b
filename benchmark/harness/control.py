#!/usr/bin/env python3
"""The controls of a cell at its own size: the plain reference put in the
program's place with one stated guarantee broken (``reference.MODES``), held
against the exact reference by the run's own comparison. Each has to come
out as not correct. numpy only — no window, no chip is touched.

    python3 benchmark/harness/control.py --workload <name> --seeds 1 2 3
"""

from __future__ import annotations

import argparse
import json
import os
import sys


sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from harness import reference, spec, table  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--benchmark-json", default=None)
    args = ap.parse_args()
    cell = spec.Cell(args.workload, args.benchmark_json)
    config, statements = cell.config, cell.traffic["statements"]
    caught = True
    for seed in args.seeds:
        refs = {m: reference.Reference(config, statements, m)
                for m in reference.MODES}
        for cols in table.reference_segments(config, seed,
                                             refs["exact"].columns):
            for ref in refs.values():
                ref.add(cols)
        want = refs["exact"].rows()
        for mode, ref in refs.items():
            records = [{"ok": True, "statement": name, "rows": rows}
                       for name, rows in ref.rows().items()]
            verdict = reference.compare(records, want)
            print(json.dumps({
                "workload": cell.name, "seed": seed, "mode": mode,
                "correct": verdict["correct"],
                **{k: v["value"] for k, v in verdict["numbers"].items()}}),
                flush=True)
            caught &= verdict["correct"] == (mode == "exact")
    return 0 if caught else 1


if __name__ == "__main__":
    sys.exit(main())
