"""Share of the table's segments that no executor had to process, whichever
of broker or server pruned them: 1 - sum(numSegmentsProcessed) / (responses
x the table's segments). A count: it repeats exactly."""

LAYER = "server"
UNIT = "%"
MOVES = "queries_per_s"


def read(run):
    v = [r["stats"]["numSegmentsProcessed"] for r in run["records"]
         if r["ok"] and "numSegmentsProcessed" in r["stats"]]
    if not v:
        return None
    return 100.0 * (1.0 - sum(v) / (len(v) * run["config"]["segments"]))
