"""Share of the slice's FULL-regime launches that summed prepared planes
laid out SPILLED - a skewed key's cells slotted at a K near their mean,
each fat cell's rows past K in chunk columns of their own: of the
``executor.dispatch`` spans whose ``groupbyKeySpace`` says ``full`` and
whose request carries no host answer (an ``engine.host_fallback`` span, or
an ``engine.merge`` that counts a segment on the host: ``on_device_share``
and ``groupby_full_share`` count those), those whose ``groupbyOperands``
says ``prepared`` and whose ``groupbyKeyLayout`` says ``spilled``. A full
launch laid out ``slotted`` or ``ordered`` (the byte budget's fallback),
one whose operands the byte budget declined (``perLaunch``: the XLA
scatter, no layout) or one whose operands this launch built counts against
the share; a program that names no spilled layout reads 0. Expected 100 on
the skewed keys. Nothing to read where no trace is kept or no launch of
the slice was full."""

from harness import spans

LAYER = "kernels"
UNIT = "%"
MOVES = "queries_per_s"


def _host_answered(trace) -> bool:
    for s in trace:
        if s["phase"] == "engine.host_fallback":
            return True
        if s["phase"] == "engine.merge" \
                and (s.get("attrs") or {}).get("segmentsOnHost", 0) > 0:
            return True
    return False


def read(run):
    traces = spans.in_slice(run)
    if not traces:
        return None
    full = spilled = 0
    for t in traces:
        if _host_answered(t):
            continue
        for s in t:
            attrs = s.get("attrs") or {}
            if s["phase"] != "executor.dispatch" \
                    or attrs.get("groupbyKeySpace") != "full":
                continue
            full += 1
            spilled += attrs.get("groupbyOperands") == "prepared" \
                and attrs.get("groupbyKeyLayout") == "spilled"
    if not full:
        return None
    return 100.0 * spilled / full
