"""Mean over the traced requests of the CPU time a request costs the
serving process: the sum of ``cpuMs`` (``time.thread_time`` over the span,
on the thread that ran it) of the spans that have no child, over every
thread the request ran on. One process runs one thread's Python at a
time, so 1000 divided by this bounds the queries a second it answers.
Nothing to read where no trace is kept."""

import statistics

from harness import spans

LAYER = "server"
UNIT = "ms"
MOVES = "queries_per_s"


def read(run):
    traces = spans.in_slice(run)
    if not traces:
        return None
    return statistics.fmean(spans.leaf_cpu_ms(t) for t in traces)
