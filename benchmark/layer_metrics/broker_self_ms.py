"""Median self time of the ``broker.*`` spans of a traced request: parse,
admission, routing, reduce and response, and of ``broker.scatter_gather``
only what the servers' ``server.total`` does not cover (transport), so
without the scatter's wait. Nothing to read where no trace is kept."""

from harness import spans

LAYER = "broker"
UNIT = "ms"
MOVES = "query_p50_ms"


def read(run):
    return spans.median_term(run, "broker_self")
