"""The scan kernels' share of the HBM roofline over the traced slice: the
least time the chip could take to read what the answered statements must
read (their algorithmic bytes, ``harness/algbytes.py``, over the peak of
``peaks.json``) divided by the device time of every jitted program
(``XLA Modules``) in the slice. Bound by bytes: these statements do a few
integer operations a row. Requests count for the slice by when they
answered. Nothing to read without a trace."""

from harness import algbytes

LAYER = "kernels"
UNIT = "%"
MOVES = "queries_per_s"


def read(run):
    trace = run["trace"]
    if not trace or not trace["modules_s"] or not run["peaks"]:
        return None
    t_a, t_b = run["slice"]
    by_name = {s["name"]: algbytes.statement_bytes(run["config"], s)
               for s in run["traffic"]["statements"]}
    total = sum(by_name[r["statement"]] for r in run["records"]
                if r["ok"] and t_a <= r["t_done"] <= t_b)
    if not total:
        return None
    least_s = total / (run["peaks"]["hbm_gbytes_per_s"] * 1e9)
    return 100.0 * least_s / trace["modules_s"]
