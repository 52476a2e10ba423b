"""Median over the traced requests of the time queued before the device
launch: ``server.queue`` (the scheduler's admission wait) plus
``executor.launch_wait`` (a leader's window or stream wait, a member's
wait for its leader's dispatch). Nothing to read where no trace is
kept."""

from harness import spans

LAYER = "executor"
UNIT = "ms"
MOVES = "query_p50_ms"


def read(run):
    return spans.median_term(run, "launch_queue")
