"""Median self time of the ``server.*`` and ``engine.*`` spans of a traced
request — decode, plan, execute, fetch, trim and encode less what the
executor's spans cover, plus the engine's template build, host scans and
merge. ``server.queue`` is a wait and is left out (``launch_queue_ms``
reads it). Nothing to read where no trace is kept."""

from harness import spans

LAYER = "server"
UNIT = "ms"
MOVES = "query_p50_ms"


def read(run):
    return spans.median_term(run, "server_self")
