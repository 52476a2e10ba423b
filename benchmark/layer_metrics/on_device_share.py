"""Share of the segments the traced requests scanned that the device
answered: sum of ``segmentsOnDevice`` over sum of ``segmentsOnDevice`` +
``segmentsOnHost`` (the engine counts both where it merges a request's
partials). 100 unless something fell back to the host executor. Nothing
to read where no trace is kept."""

from harness import spans

LAYER = "server"
UNIT = "%"
MOVES = "queries_per_s"


def read(run):
    traces = spans.in_slice(run)
    if not traces:
        return None
    dev = sum(v for t in traces
              for v in spans.attr_values(t, "segmentsOnDevice"))
    host = sum(v for t in traces
               for v in spans.attr_values(t, "segmentsOnHost"))
    return 100.0 * dev / (dev + host) if dev + host else None
