"""Median of the responses' ``deviceKernelMs``: the executor's host clock
from dispatch to ready. A wait, named for what it is — not a kernel time."""

import statistics

LAYER = "executor"
UNIT = "ms"
MOVES = "query_p50_ms"


def read(run):
    v = [r["stats"]["deviceKernelMs"] for r in run["records"]
         if r["ok"] and r["stats"].get("deviceKernelMs")]
    return statistics.median(v) if v else None
