"""Share of the slice's device launches of a group-by over a large key
space that took the FULL regime and stayed on the device: of the
``executor.dispatch`` spans whose ``keySpaceCells`` (the cartesian product
of the group columns' cardinalities) passes the regime's floor, those whose
launch says ``full`` and whose request carries no host answer (an
``engine.host_fallback`` span, or an ``engine.merge`` that counts a segment
on the host: the default ``numGroupsLimit`` under a trimmed table, a
refused launch). A launch that says ``narrowed``, ``dense`` or ``sorted``
counts against the share: in a mix whose key spaces are all full, a
narrowed launch is one that overflowed and was launched again. Expected
100. Nothing to read where no trace is kept, where no statement's key
space passes the floor, or where the program's spans carry no
``keySpaceCells`` (a program from before the key spaces were named)."""

from harness import spans

LAYER = "kernels"
UNIT = "%"
MOVES = "queries_per_s"
# engine/device.py NARROW_MIN_CELLS: the floor of both large-key regimes
MIN_CELLS = 1 << 15


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
    launches = full = 0
    for t in traces:
        on_host = _host_answered(t)
        for s in t:
            attrs = s.get("attrs") or {}
            if s["phase"] != "executor.dispatch" \
                    or attrs.get("keySpaceCells", 0) <= MIN_CELLS:
                continue
            launches += 1
            full += attrs.get("groupbyKeySpace") == "full" and not on_host
    if not launches:
        return None
    return 100.0 * full / launches
