"""Share of the slice's FULL-regime launches that summed planes laid out
cell by slot: of the ``executor.dispatch`` spans whose ``groupbyKeySpace``
says ``full``, those whose ``groupbyKeyLayout`` says ``slotted``. A full
launch that says ``ordered`` (a skewed key past the padding bound, or
slotted planes the batch's byte budget refused), ``perLaunch`` operands
(no layout at all) or nothing (a program from before the layout was
named: it ran the ordered form) counts against the share. Expected 100:
dbgen's keys are uniform. Nothing to read where no trace is kept or no
launch of the slice was full."""

from harness import spans

LAYER = "kernels"
UNIT = "%"
MOVES = "queries_per_s"


def read(run):
    traces = spans.in_slice(run)
    if not traces:
        return None
    full = slotted = 0
    for t in traces:
        for s in t:
            attrs = s.get("attrs") or {}
            if s["phase"] != "executor.dispatch" \
                    or attrs.get("groupbyKeySpace") != "full":
                continue
            full += 1
            slotted += attrs.get("groupbyKeyLayout") == "slotted"
    if not full:
        return None
    return 100.0 * slotted / full
