"""Share of the slice's device launches of a group-by over a large key
space that ran NARROWED: of the ``executor.dispatch`` spans whose
``keySpaceCells`` (the cartesian product of the group columns'
cardinalities) passes the regime's floor, those whose launch says
``narrowed`` and whose result did not say ``overflow``. One dispatch span
a launch; a cohort's is on its leader's trace. A launch whose live keys do
not fit the narrowed table is answered again by the host: its
``executor.device_wait`` (same ``launchId``) says ``overflow``, and it
counts against the share, as does a launch that took the dense table over
the whole product (``dense``) or the sort of every row (``sorted``).
Expected 100: anything less is a slow answer the end-to-end numbers would
carry unexplained. Nothing to read where no trace is kept, where no
statement's key space passes the floor, or where the program's spans
carry no ``keySpaceCells`` (the parent of the PR that added it)."""

from harness import spans

LAYER = "kernels"
UNIT = "%"
MOVES = "queries_per_s"
# engine/device.py NARROW_MIN_CELLS: the regime's floor
MIN_CELLS = 1 << 15


def read(run):
    traces = spans.in_slice(run)
    if not traces:
        return None
    launches, overflowed = {}, set()
    for t in traces:
        for s in t:
            attrs = s.get("attrs") or {}
            if attrs.get("keySpaceCells", 0) <= MIN_CELLS:
                continue
            if s["phase"] == "executor.dispatch":
                launches[attrs.get("launchId")] = attrs.get("groupbyKeySpace")
            elif s["phase"] == "executor.device_wait" \
                    and attrs.get("groupbyKeySpace") == "overflow":
                overflowed.add(attrs.get("launchId"))
    if not launches:
        return None
    narrowed = sum(space == "narrowed" and launch not in overflowed
                   for launch, space in launches.items())
    return 100.0 * narrowed / len(launches)
