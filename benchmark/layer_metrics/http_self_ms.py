"""Median self time of the ``http.*`` spans of a traced request: the door's
own work — reading and decoding the body, encoding and sending the answer,
and what ``http.request`` spends outside them and outside the broker. Read
from the traces the program keeps (``harness/spans.py``); nothing to read
where it keeps none."""

from harness import spans

LAYER = "http"
UNIT = "ms"
MOVES = "query_p50_ms"


def read(run):
    return spans.median_term(run, "http_self")
