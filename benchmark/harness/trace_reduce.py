"""From a profiler trace to device numbers: busy time (the union of the
intervals in which an operation ran on the device), the summed time of the
jitted programs (``XLA Modules``), the operations that took most time and
the longest idle gaps with what the host was doing in them.

``load_events`` turns an ``.xplane.pb`` into plain tuples with
``jax.profiler.ProfileData``; everything after that is arithmetic on
tuples, so the tests drive it with a hand-built event list.
"""

from __future__ import annotations

import glob
import os
import re
from collections import defaultdict

DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
# the opcode follows the result's shape: "...} fusion(", "...) custom-call("
_OPCODE = re.compile(r"[\]\)\}] ([a-z][\w-]*)\(")


def load_events(trace_dir: str) -> list:
    """[(plane, line, name, start_ns, duration_ns)] of the newest trace
    under ``trace_dir``."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(files[-1])
    events = []
    for plane in data.planes:
        for line in plane.lines:
            for ev in line.events:
                events.append((plane.name, line.name, ev.name,
                               float(ev.start_ns), float(ev.duration_ns)))
    return events


def union_ns(intervals: list) -> float:
    """Total length covered by (start, end) intervals, overlaps once."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def _gaps(intervals: list) -> list:
    """(start, end) of the idle gaps between merged busy intervals."""
    gaps, cur_e = [], None
    for s, e in sorted(intervals):
        if cur_e is not None and s > cur_e:
            gaps.append((cur_e, s))
        cur_e = e if cur_e is None else max(cur_e, e)
    return gaps


def _host_label(gap, host_events: list) -> str:
    """The host span that covers most of the gap."""
    cover = defaultdict(float)
    for name, s, e in host_events:
        o = min(e, gap[1]) - max(s, gap[0])
        if o > 0:
            cover[name] += o
    return max(cover, key=cover.get) if cover else "no host span"


def short_name(name: str) -> str:
    """An operation's trace name is its whole HLO line; keep what it
    assigns to and the opcode: ``%fusion.5 = (...) fusion(...)`` ->
    ``fusion.5 fusion``."""
    lhs, sep, rhs = name.partition(" = ")
    if not sep:
        return name[:80]
    opcode = _OPCODE.search(rhs)
    return f"{lhs.lstrip('%')} {opcode.group(1) if opcode else ''}"[:80].strip()


def reduce(events: list, window_s: float, top: int = 10) -> dict:
    """The device numbers of one traced slice of ``window_s`` seconds.
    Times are averaged over the device planes found (one per chip). Raises
    where the trace holds no device operation: an idle share of such a
    trace would be a guess."""
    planes = sorted({p for p, *_ in events
                     if p.startswith(DEVICE_PLANE_PREFIX)})
    if not planes:
        raise ValueError(
            "the trace has no device plane: "
            f"{sorted({p for p, *_ in events})}")
    busy_ns = modules_ns = 0.0
    op_time = defaultdict(float)
    gap_time = defaultdict(float)
    host = [(name, s, s + d) for p, _line, name, s, d in events
            if p.startswith("/host:") and d > 0]
    n_ops = n_modules = 0
    for plane in planes:
        ops = [(s, s + d) for p, line, _n, s, d in events
               if p == plane and line == OPS_LINE]
        busy_ns += union_ns(ops)
        n_ops += len(ops)
        for p, line, name, _s, d in events:
            if p != plane:
                continue
            if line == OPS_LINE:
                op_time[short_name(name)] += d
            elif line == MODULES_LINE:
                modules_ns += d
                n_modules += 1
        for gap in sorted(_gaps(ops), key=lambda g: g[0] - g[1])[:50]:
            gap_time[_host_label(gap, host)] += gap[1] - gap[0]
    if not n_ops:
        lines = sorted({(p, line) for p, line, *_ in events if p in planes})
        raise ValueError("no operation ran on the device in the traced "
                         f"slice; its planes and lines are {lines}")
    k = len(planes)

    def ranked(d):
        return [[name, ns / k / 1e9] for name, ns in
                sorted(d.items(), key=lambda kv: -kv[1])[:top]]

    return {"busy_s": busy_ns / k / 1e9, "window_s": window_s,
            "modules_s": modules_ns / k / 1e9, "n_ops": n_ops,
            "n_modules": n_modules, "chips": k,
            "device_ops": ranked(op_time), "idle_gaps": ranked(gap_time)}
