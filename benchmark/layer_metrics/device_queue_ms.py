"""Median over the traced slice's requests that launched of the time
their launches queued on the device behind the launches dispatched
before them: the sum of ``deviceQueueMs`` over a request's
``executor.device_wait`` spans, from a launch's dispatch to the end of
the launch before it (the executor stamps each end in dispatch order).
With ``device_run_ms`` it splits the wait that ``device_wait_ms`` reads
from outside. Nothing to read where no trace is kept, no request of the
slice launched, or the program stamps no queue."""

import statistics

from harness import spec

LAYER = "executor"
UNIT = "ms"
MOVES = "query_p50_ms"


def read(run):
    v = spec.found("layer_metrics", "device_run_ms").per_request(
        run, "deviceQueueMs")
    return statistics.median(v) if v else None
