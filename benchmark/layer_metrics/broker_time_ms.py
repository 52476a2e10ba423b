"""Median of the responses' ``timeUsedMs``: the broker's own clock around a
query, from parse to reduced result."""

import statistics

LAYER = "broker"
UNIT = "ms"
MOVES = "query_p50_ms"


def read(run):
    v = [r["stats"]["timeUsedMs"] for r in run["records"]
         if r["ok"] and "timeUsedMs" in r["stats"]]
    return statistics.median(v) if v else None
