"""Share of the slice's device launches of a dense group-by whose kernel
read the batch's PREPARED operands: of the ``executor.dispatch`` spans
that carry ``groupbyOperands``, those that say ``prepared`` (the others:
``built``, the launch that built them, and ``perLaunch``, the preparation
redone by the launch). One dispatch span a launch; a cohort's is on its
leader's trace. Nothing to read where no trace is kept, or where the
program's dispatch spans carry no such count (the parent of the PR that
added it; a cell whose statements have no GROUP BY)."""

from harness import spans

LAYER = "kernels"
UNIT = "%"
MOVES = "queries_per_s"


def read(run):
    traces = spans.in_slice(run)
    if not traces:
        return None
    origins = [s["attrs"]["groupbyOperands"] for t in traces for s in t
               if s["phase"] == "executor.dispatch"
               and "groupbyOperands" in s.get("attrs", ())]
    if not origins:
        return None
    return 100.0 * origins.count("prepared") / len(origins)
