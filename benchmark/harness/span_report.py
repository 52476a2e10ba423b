#!/usr/bin/env python3
"""Where a traced request's time goes, span by span: one ``--trace 1`` run
of a cell (``run.run``, in this process, on the chip) and then a table
from the traces the program kept for the slice — what the result line's
eight span metrics are medians and means of.

    python3 benchmark/harness/span_report.py --workload <name> --seed <n> --seconds <s>

Prints the result line's metrics, then for every span name the share of
requests that have it, its mean wall and CPU time a request (a request
without it counts 0), then the layers' sums beside the door's
``http.request`` and the client's latency of the same slice. The last
stdout line is the run's result, as ``run.py`` prints it.
"""

from __future__ import annotations

import json
import os
import statistics
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH_DIR)
sys.path.insert(1, os.path.dirname(BENCH_DIR))

import run as run_mod  # noqa: E402
from harness import spans  # noqa: E402
from harness.cluster import require_tpu  # noqa: E402


def report(traces: list, records: list, slice_ab: tuple) -> list:
    """The table's lines."""
    n = len(traces)
    wall, cpu, have = {}, {}, {}
    for t in traces:
        for name in {s["phase"] for s in t}:
            have[name] = have.get(name, 0) + 1
        for s in t:
            wall[s["phase"]] = wall.get(s["phase"], 0.0) \
                + s["end"] - s["start"]
            cpu[s["phase"]] = cpu.get(s["phase"], 0.0) \
                + (s.get("cpuMs") or 0.0)
    lines = [f"requests={n} spans_a_request="
             f"{sum(len(t) for t in traces) / n:.1f}",
             f"{'span':28s} {'share':>6s} {'wall_ms':>9s} {'cpu_ms':>8s}"]
    for name in sorted(wall):
        lines.append(f"{name:28s} {have[name] / n:6.2f} "
                     f"{wall[name] / n:9.3f} {cpu[name] / n:8.3f}")
    sums = [{k: of(t) for k, of in spans.LAYER_TERMS.items()}
            for t in traces]
    for key in sums[0]:
        v = [s[key] for s in sums]
        lines.append(f"layer {key:14s} mean={statistics.fmean(v):8.3f} "
                     f"median={statistics.median(v):8.3f}")
    door = [spans.wall_ms(t, ("http.request",)) for t in traces]
    t_a, t_b = slice_ab
    client = [(r["t_done"] - r["t_send"]) * 1000 for r in records
              if r["ok"] and t_a <= r["t_send"] < t_b]
    lines.append(
        f"mean layers_sum={statistics.fmean(sum(s.values()) for s in sums):.3f}"
        f" http.request={statistics.fmean(door):.3f}"
        f" client={statistics.fmean(client) if client else float('nan'):.3f}"
        f" (client requests sent in the slice: {len(client)})")
    lines.append(f"mean host_cpu={statistics.fmean(spans.leaf_cpu_ms(t) for t in traces):.3f}")
    launches = [set(spans.attr_values(t, "launchId")) for t in traces]
    roles: dict = {}
    for t in traces:
        for r in spans.attr_values(t, "role"):
            roles[r] = roles.get(r, 0) + 1
    lines.append(
        f"launch_ids distinct={len(set().union(*launches))} "
        f"dispatch_spans={sum(s['phase'] == 'executor.dispatch' for t in traces for s in t)} "
        f"requests_with_one_launch_id={sum(len(x) == 1 for x in launches)} "
        f"roles={dict(sorted(roles.items()))}")
    return lines


def main(argv=None) -> int:
    args = run_mod.parse(argv)
    args.trace = 1
    seen: dict = {}
    in_slice = spans.in_slice

    def watched(run):
        seen["run"] = run
        return in_slice(run)

    spans.in_slice = watched  # the readers call it through the module
    result = run_mod.run(args, require_tpu)
    for name, m in result["metrics"].items():
        print(f"metric {name}={m['value']}")
    traces = in_slice(seen["run"]) if seen else None
    if not traces:
        print("no kept trace in the slice")
    else:
        print("\n".join(report(traces, seen["run"]["records"],
                               seen["run"]["slice"])))
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
