"""Requests a device launch answers: the traced requests of the slice that
carry a ``launchId`` divided by the distinct ``launchId``s among them. One
launch's device time is shared by its cohort, so this is the number that
turns milliseconds a launch into queries a second. Nothing to read where
no trace is kept."""

from harness import spans

LAYER = "executor"
UNIT = "queries/launch"
MOVES = "queries_per_s"


def read(run):
    traces = spans.in_slice(run)
    if not traces:
        return None
    ids = [i for t in traces for i in set(spans.attr_values(t, "launchId"))]
    return len(ids) / len(set(ids)) if ids else None
