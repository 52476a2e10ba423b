"""The program's own spans of the traced slice, for the per-layer metrics
that read them (``layer_metrics/http_self_ms.py`` and its neighbours).

Under ``SET trace=true`` — every statement of a ``--trace 1`` run carries
the mix's ``trace_prefix`` — each layer of the served path records spans
(``pinot_tpu/common/trace.py``): name, id, parent's id, start, end, CPU
time of the thread that ran it, a few counts. The program keeps the
finished tracers of a request in a bounded ring in memory; a request that
crossed one server left two (the broker's, rooted at ``http.request``,
and the server's, rooted at ``server.total`` and hung under the broker's
``broker.scatter_gather`` by the id the scatter request shipped), under
one trace id. They are read here after the window, in the process that
holds the chip: no file, no endpoint.

A *trace* below is the list of one request's spans from all its tracers,
each a dict with ``phase``, ``spanId``, ``parentId``, ``start`` and
``end`` (ms on the wall clock ``run.py`` stamps the slice with),
``durationMs``, ``cpuMs`` and ``attrs`` where the program set them.

This imports ``pinot_tpu.common.trace`` and nothing else of the program.
A program that keeps no traces (the parent of the PR that added them) has
nothing to read: ``in_slice`` returns ``None`` and so does every reader.
"""

from __future__ import annotations

import statistics


def _finished():
    """``trace.finished(since, until)``, or None where the program has
    no such thing."""
    try:
        from pinot_tpu.common import trace
    except ImportError:
        return None
    return getattr(trace, "finished", None)


def join(tracers: list) -> dict:
    """{trace id: its spans on the wall clock, from every tracer of it}."""
    traces: dict = {}
    for t in tracers:
        spans = traces.setdefault(t.trace_id, [])
        for s in t.to_json():
            start = t.wall0 * 1000 + s["startMs"]
            spans.append({**s, "start": start,
                          "end": start + s["durationMs"]})
    return traces


def in_slice(run: dict) -> list | None:
    """The traces whose root — the span with no parent, ``http.request``
    — started inside ``run["slice"]``, each joined with what the servers
    kept under its trace id. ``None`` without a slice or kept traces."""
    if "spans_in_slice" in run:
        return run["spans_in_slice"]
    out = None
    finished = _finished()
    if finished is not None and run.get("slice"):
        t_a, t_b = run["slice"]
        ids = {t.trace_id for t in finished(t_a, t_b)
               if t.parent_id is None}
        # a server's root starts after the door's: look past the slice
        out = list(join([t for t in finished(t_a)
                         if t.trace_id in ids]).values()) or None
    run["spans_in_slice"] = out
    return out


def _named(span: dict, names) -> bool:
    """``names``: layer prefixes (``"server"`` takes ``server.*``) and
    whole span names."""
    return span["phase"] in names or span["phase"].split(".")[0] in names


def self_ms(trace: list, prefix, skip=()) -> float:
    """Σ over the spans named ``prefix.*`` (one prefix or several; whole
    names count too) of their duration less the part their children
    cover — a child counts once however its siblings overlap, and only
    as far as it lies inside its parent. ``skip``: names left out."""
    names = (prefix,) if isinstance(prefix, str) else tuple(prefix)
    children: dict = {}
    for s in trace:
        children.setdefault(s["parentId"], []).append(s)
    total = 0.0
    for s in trace:
        if not _named(s, names) or s["phase"] in skip:
            continue
        covered, edge = 0.0, s["start"]
        for c in sorted(children.get(s["spanId"], ()),
                        key=lambda c: c["start"]):
            a, b = max(c["start"], edge), min(c["end"], s["end"])
            if b > a:
                covered += b - a
                edge = b
        total += (s["end"] - s["start"]) - covered
    return total


def wall_ms(trace: list, names) -> float:
    """Σ of the durations of the spans named in ``names``."""
    return sum(s["end"] - s["start"] for s in trace if s["phase"] in names)


# the six terms a request's time is cut into; they tile ``http.request``.
# The waits are spans of their own, so the executor's work is a sum of
# named spans and not a self time.
EXECUTOR_WORK = ("executor.gather", "executor.stack", "executor.dispatch",
                 "executor.link", "executor.unpack")
LAUNCH_WAITS = ("server.queue", "executor.launch_wait")
LAYER_TERMS = {
    "http_self": lambda t: self_ms(t, "http"),
    "broker_self": lambda t: self_ms(t, "broker"),
    "server_self": lambda t: self_ms(t, ("server", "engine"),
                                     skip=("server.queue",)),
    "executor_self": lambda t: wall_ms(t, EXECUTOR_WORK),
    "launch_queue": lambda t: wall_ms(t, LAUNCH_WAITS),
    "device_wait": lambda t: wall_ms(t, ("executor.device_wait",)),
}


def leaf_cpu_ms(trace: list) -> float:
    """Σ ``cpuMs`` of the spans that have no child: every thread the
    request ran on, each stretch counted once."""
    parents = {s["parentId"] for s in trace}
    return sum(s.get("cpuMs") or 0.0 for s in trace
               if s["spanId"] not in parents)


def attr_values(trace: list, key: str) -> list:
    return [s["attrs"][key] for s in trace if key in s.get("attrs", ())]


def median_term(run: dict, term: str) -> float | None:
    """Median over the slice's requests of one of ``LAYER_TERMS``."""
    traces = in_slice(run)
    if not traces:
        return None
    return statistics.median(LAYER_TERMS[term](t) for t in traces)
