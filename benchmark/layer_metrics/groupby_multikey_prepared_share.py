"""Share of the slice's device launches of a group-by, whatever its key
space (dense, narrowed or sorted) and however many key columns, whose
kernel read the batch's PREPARED operands: of the ``executor.dispatch``
spans that carry ``groupbyKeySpace``, those whose ``groupbyOperands`` says
``prepared``. A launch that says ``built`` (it built them), ``perLaunch``
(the preparation redone by the launch) or nothing of its operands (a
narrowed launch of the parent of the PR that added this; the sorted
regime) counts against the share. One dispatch span a launch; a cohort's
is on its leader's trace. Nothing to read where no trace is kept or no
launch in the slice is a group-by's."""

from harness import spans

LAYER = "kernels"
UNIT = "%"
MOVES = "queries_per_s"


def read(run):
    traces = spans.in_slice(run)
    if not traces:
        return None
    origins = [s["attrs"].get("groupbyOperands") for t in traces for s in t
               if s["phase"] == "executor.dispatch"
               and "groupbyKeySpace" in s.get("attrs", ())]
    if not origins:
        return None
    return 100.0 * origins.count("prepared") / len(origins)
