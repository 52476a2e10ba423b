#!/usr/bin/env python3
"""benchmark/run.py — one run of one cell of ``BENCHMARK.json`` on the chip.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process holds the chip: it builds the cell's table from ``--seed`` in
spawned numpy-only children while folding the same columns into the plain
reference, brings the served path up (HTTP -> broker -> server ->
``DeviceExecutor``), warms the cell's statements at the window's own
concurrency, then lets the load generator (a child pinned to the CPU) drive
the broker's HTTP endpoint for ``--seconds``. Every answer of the window is
compared with the reference once the window has closed. The last stdout
line is the result; the numbers compared stand beside their limits on the
last stderr lines and under ``compared`` in the result.

Everything that belongs to one cell is data found by name
(``harness/spec.py``): the configuration's file, the traffic file, one
reader per per-layer metric. No TPU -> exit 2 and no result line.
"""

from __future__ import annotations

import time

T_PROCESS_START = time.time()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import signal  # noqa: E402
import statistics  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

import numpy as np  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH_DIR)                    # harness
sys.path.insert(1, os.path.dirname(BENCH_DIR))   # the program, pinot_tpu

TRACE_SLICE_S = 5.0
WORK_PREFIX = "pinot_tpu_benchmark_"


from harness.cluster import require_tpu  # noqa: E402 — imports no jax


def say(line: str) -> None:
    print(line, flush=True)


class SetupFailed(SystemExit):
    """Set-up did not reach a sound state: exit 3, no result line."""

    def __init__(self, why: str):
        print(f"benchmark: set-up failed: {why}", file=sys.stderr)
        super().__init__(3)


class LoadGenerator:
    """The child of ``harness/loadgen.py`` and its line protocol."""

    def __init__(self, traffic_file: str, seed: int, trace: bool):
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        self.proc = subprocess.Popen(
            [sys.executable, "-u",
             os.path.join(BENCH_DIR, "harness", "loadgen.py"),
             traffic_file, str(seed), "1" if trace else "0"],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, env=env, text=True)

    def ask(self, cmd: str) -> dict:
        self.proc.stdin.write(cmd + "\n")
        self.proc.stdin.flush()
        line = self.proc.stdout.readline()
        if not line:
            raise SetupFailed(f"the load generator died on {cmd!r} "
                              f"(exit {self.proc.poll()})")
        reply = json.loads(line)
        if "error" in reply:
            raise SetupFailed(f"load generator: {reply['error']}")
        return reply

    def close(self) -> None:
        if self.proc.poll() is None:
            try:
                self.proc.stdin.write("quit\n")
                self.proc.stdin.flush()
                self.proc.wait(timeout=10)
            except (OSError, subprocess.TimeoutExpired):
                self.proc.kill()
        self.proc.wait()


def check_warm(records: list, where: str, alone: bool = False) -> None:
    """The smoke's quiet-fallback checks, on a warm-up's answers. A
    statement sent ``alone`` leads its own launch, so it must have been
    charged device bytes and a device wait."""
    for r in records:
        if not r["ok"]:
            raise SetupFailed(f"{where}: {r['statement']}: {r.get('error')}")
        st = r["stats"]
        if r.get("off_device") or (alone and r["device"] and not (
                st.get("deviceBytesMoved") and st.get("deviceKernelMs"))):
            raise SetupFailed(
                f"{where}: {r['statement']} did not run on the device "
                f"(host_fallback span or deviceBytesMoved 0): {st}")


def check_counters(cluster, where: str) -> None:
    bad = {k: v for k, v in cluster.failure_counters().items() if v}
    if bad:
        raise SetupFailed(f"{where}: executor counters {bad}")


def end_to_end(records: list, t_start: float, t_stop: float,
               setup_s: float) -> dict:
    """The window's end-to-end numbers, over ALL of its requests: the rate
    counts what completed inside the window; the latencies are of every
    request sent in it that answered (those in flight at the close are
    waited for, and their wait counts)."""
    ok = [r for r in records if r["ok"]]
    lat = [(r["t_done"] - r["t_send"]) * 1000 for r in ok]
    inside = sum(r["t_done"] <= t_stop for r in ok)
    out = {"setup_s": setup_s,
           "queries_per_s": inside / (t_stop - t_start)}
    if lat:
        out["query_p50_ms"] = statistics.median(lat)
        out["query_p95_ms"] = float(np.percentile(lat, 95))
    return out


def stalls(records: list) -> str:
    """Where a window lost time, for whoever reads a run that reads far
    off: requests by caller, the slowest request, and the longest time in
    which no request was answered."""
    done = sorted(r["t_done"] for r in records)
    by_client: dict = {}
    for r in records:
        by_client[r["client"]] = by_client.get(r["client"], 0) + 1
    slowest = max((r["t_done"] - r["t_send"] for r in records), default=0.0)
    gap = max((b - a for a, b in zip(done, done[1:])), default=0.0)
    # the latencies' shape: a median that stands where few requests do
    # swings from run to run with the mix of cohort widths (PERF.md, PR 31)
    lat = [(r["t_done"] - r["t_send"]) * 1000 for r in records if r["ok"]]
    deciles = [round(float(q), 1) for q in
               np.percentile(lat, range(10, 100, 10))] if lat else []
    # only the leader of a coalesced launch is charged deviceKernelMs
    led = sum(bool(r.get("stats", {}).get("deviceKernelMs")) for r in records)
    return (f"by_client={[by_client[c] for c in sorted(by_client)]} "
            f"slowest_ms={slowest * 1000:.0f} longest_gap_ms={gap * 1000:.0f} "
            f"latency_deciles_ms={deciles} launches_led={led}")


def trace_middle(trace_dir: str, seconds: float) -> tuple:
    """Profile a slice in the middle of a window that has just opened;
    returns the slice's (start, end) on this host's clock."""
    import jax

    span = min(TRACE_SLICE_S, seconds / 2)
    time.sleep((seconds - span) / 2)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    t_a = time.time()
    time.sleep(span)
    t_b = time.time()
    jax.profiler.stop_trace()
    return t_a, t_b


def run(args, look_for_chip) -> dict:
    from harness import machine
    from harness import reference as reference_mod
    from harness import spec, table

    cell = spec.Cell(args.workload, args.benchmark_json)
    config, traffic = cell.config, cell.traffic
    trace = bool(args.trace)

    # outside the repo there is nothing to measure; where JAX is held to
    # another platform there is no chip: say so before the table is built
    if importlib.util.find_spec("pinot_tpu") is None:
        raise SystemExit("benchmark: no pinot_tpu package beside benchmark/")
    held_to = os.environ.get("JAX_PLATFORMS", "")
    if look_for_chip is require_tpu and held_to \
            and "tpu" not in held_to.split(","):
        print(f"benchmark: JAX_PLATFORMS={held_to!r} holds JAX off the TPU; "
              "no number is taken off the chip", file=sys.stderr)
        raise SystemExit(2)

    # a run that was killed reached no `finally`: its 4 GB of segment
    # files are still there, and nobody else removes them
    for gone in machine.sweep_stale(tempfile.gettempdir(), WORK_PREFIX):
        say(f"swept {gone}: its run is dead")
    work = tempfile.mkdtemp(prefix=WORK_PREFIX)
    loadgen = cluster = None
    try:
        machine.claim(work)
        loadgen = LoadGenerator(cell.traffic_file, args.seed, trace)
        ref = reference_mod.Reference(config, traffic["statements"])
        # The table is built BEFORE this process touches JAX: the TPU
        # runtime maps 14 GB into this process when it starts, and the
        # children are spawned from a process that has no JAX threads yet.
        # Built where the server serves in place: no deep-store copy, no
        # local copy — 4 GB written per run instead of 12.
        dirs = table.build_table(
            config, args.seed,
            os.path.join(work, "server_0", "built"), ref, say)
        want = ref.rows()

        import pinot_tpu  # noqa: F401 — turns x64 on and places the cache

        from harness import cluster as cluster_mod

        devices = look_for_chip(cell.chips)
        device = {"platform": devices[0].platform,
                  "kind": devices[0].device_kind, "count": len(devices)}
        say(f"device platform={device['platform']} kind={device['kind']} "
            f"count={device['count']} workload={cell.name} seed={args.seed}")
        on_chip = device["platform"] == "tpu"
        peaks = spec.peaks(device["kind"]) if on_chip else None
        readers = cell.layer_readers()
        compiles = cluster_mod.CompileCounter()
        say(f"compile_cache dir={cluster_mod.cache_dir()}")

        cluster = cluster_mod.Cluster(config, work)
        tiers = cluster.tiers()
        say(f"executor mm_mode={tiers[0]} pallas_mode={tiers[1]}")
        if on_chip and tiers != ("tpu", "tpu"):
            raise SetupFailed(f"DeviceExecutor resolved to {tiers}")
        cluster.load(dirs, say)

        # warm-up: each statement once (uploads its columns, compiles), then
        # callers sending together in twos, threes, .. (a cohort of every
        # width the coalescer can stack, on purpose), then rounds at the
        # window's own concurrency until one builds nothing
        loadgen.ask("url " + cluster.url)
        t0 = time.time()
        check_warm(loadgen.ask("once")["records"], "first pass", alone=True)
        say(f"warmup first_pass_seconds={time.time() - t0:.1f} "
            f"executables_built={compiles.built}")
        before = compiles.built
        burst = loadgen.ask("burst")["records"]
        check_warm(burst, "burst")
        say(f"warmup burst requests={len(burst)} "
            f"executables_built={compiles.built - before}")
        for round_no in range(1, 6):
            before = compiles.built
            loadgen.ask("start")
            time.sleep(traffic["warmup_seconds"])
            warm = loadgen.ask("stop")["records"]
            check_warm(warm, f"warm-up round {round_no}")
            say(f"warmup round={round_no} requests={len(warm)} "
                f"executables_built={compiles.built - before}")
            if compiles.built == before:
                break
        check_counters(cluster, "after the warm-up")

        # ---- the window -------------------------------------------------
        built_before = compiles.built
        t_start = loadgen.ask("start")["t_start"]
        setup_s = t_start - T_PROCESS_START
        trace_dir = os.path.join(work, "trace")
        slice_ab = trace_middle(trace_dir, args.seconds) if trace else None
        time.sleep(max(0.0, t_start + args.seconds - time.time()))
        reply = loadgen.ask("stop")
        records, t_stop = reply["records"], reply["t_stop"]
        built_in_window = compiles.built - built_before
        say(f"window seconds={t_stop - t_start:.3f} requests={len(records)} "
            f"compiles_in_window={built_in_window} "
            f"hung_clients={reply['hung_clients']} " + stalls(records))

        mem = devices[0].memory_stats() or {}
        peak_bytes = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                         for d in devices)
        counters = cluster.failure_counters()
        resident_bytes = cluster.resident_bytes()
        say("counters " + " ".join(f"{k}={v}" for k, v in counters.items())
            + f" resident_bytes={resident_bytes} "
            f"hbm_peak_bytes={peak_bytes} "
            f"bytes_limit={mem.get('bytes_limit')} "
            f"cache_hits={compiles.hits} cache_misses={compiles.misses}")

        # the window has closed and the peak is read: free the program's
        # state, then compare every answer with the reference
        loadgen.close()
        cluster.close()
        verdict = reference_mod.compare(records, want)
        numbers = verdict["numbers"]
        numbers["device_failures"] = {"value": sum(counters.values()),
                                      "limit": 0}
        numbers["hung_clients"] = {"value": reply["hung_clients"], "limit": 0}
        correct = verdict["correct"] and not any(
            numbers[k]["value"] for k in ("device_failures", "hung_clients"))
        failed = numbers["answers_missing"]["value"] \
            + numbers["answers_wrong"]["value"]

        device["memory_peak_bytes"] = peak_bytes
        result = {"correct": correct, "attempted": len(records),
                  "failed": failed, "metrics": None, "device": device}
        if trace:
            from harness import trace_reduce

            reduced = trace_reduce.reduce(
                trace_reduce.load_events(trace_dir),
                slice_ab[1] - slice_ab[0])
            say(f"trace window_s={reduced['window_s']:.3f} "
                f"busy_s={reduced['busy_s']:.4f} "
                f"modules_s={reduced['modules_s']:.4f} "
                f"ops={reduced['n_ops']} modules={reduced['n_modules']}")
            view = {"records": records, "config": config, "traffic": traffic,
                    "trace": reduced, "slice": slice_ab, "peaks": peaks,
                    "memory_peak_bytes": peak_bytes,
                    "resident_bytes": resident_bytes}
            metrics = {}
            for entry, mod in readers:
                value = mod.read(view)
                if value is not None:
                    metrics[entry["name"]] = {"value": value,
                                              "unit": entry["unit"]}
            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = reduced["window_s"]
            result["metrics"] = metrics
            result["breakdown"] = {"device_ops": reduced["device_ops"],
                                   "idle_gaps": reduced["idle_gaps"]}
        else:
            values = end_to_end(records, t_start, t_stop, setup_s)
            result["metrics"] = {
                m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                for m in cell.end_to_end() if m["name"] in values}
        result["compared"] = numbers
        return result
    finally:
        if loadgen:
            loadgen.close()
        if cluster:
            cluster.close()
        shutil.rmtree(work, ignore_errors=True)


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--benchmark-json", default=None,
                    help="another BENCHMARK.json (the tests' tiny cells)")
    return ap.parse_args(argv)


def _terminated(signum, frame):
    """SIGTERM leaves by the ``finally`` paths: children stopped, the work
    directory removed."""
    raise SystemExit(128 + signum)


def main(argv=None) -> int:
    signal.signal(signal.SIGTERM, _terminated)
    result = run(parse(argv), require_tpu)
    for name, n in result["compared"].items():
        print(f"compared {name}={n['value']} "
              + " ".join(f"{k}={v}" for k, v in n.items() if k != "value"),
              file=sys.stderr)
    print(f"correct={result['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
