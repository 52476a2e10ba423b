"""Mean over the traced slice's requests that launched of the device's
time on their launches: the sum of ``deviceRunMs`` over a request's
``executor.device_wait`` spans. The executor stamps each launch's end on
the device in the order the launches were dispatched; a launch's run is
from the later of its dispatch and the previous launch's end to its own
end, so the runs of one device never overlap, and they hold the device's
idle time between a launch's enqueue and its first operation. A mean and
not a median: it is a request's occupancy of the device, and where the
device is saturated 1000 over it bounds ``queries_per_s``; a mix's
statements differ several times over in their run. Nothing to read where
no trace is kept, no request of the slice launched, or the program
stamps no run."""

import statistics

from harness import spans

LAYER = "device"
UNIT = "ms"
MOVES = "queries_per_s"


def per_request(run, key: str):
    """[Σ ``key`` over a request's launches] for the slice's requests
    whose spans carry it; None where no trace is kept."""
    traces = spans.in_slice(run)
    if not traces:
        return None
    return [sum(v) for v in (spans.attr_values(t, key) for t in traces)
            if v]


def read(run):
    v = per_request(run, "deviceRunMs")
    return statistics.fmean(v) if v else None
