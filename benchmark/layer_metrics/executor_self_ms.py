"""Median over the traced requests of the executor's own host work: the
column gather, the leader's stacking of its cohort's params, the jitted
call's return, the ``device_get`` (on whoever fetched) and the unpack.
The waits are elsewhere (``launch_queue_ms``, ``device_wait_ms``). Nothing
to read where no trace is kept."""

from harness import spans

LAYER = "executor"
UNIT = "ms"
MOVES = "query_p50_ms"


def read(run):
    return spans.median_term(run, "executor_self")
