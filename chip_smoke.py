#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that pinot-tpu still serves on the chip.

One process drives the normal served path once on one TPU: it builds the
SSB ``lineorder`` table of ``pinot_tpu/tools/ssb.py`` (8 segments x
12,500,000 rows, data from ``--seed``), brings up registry, controller
(deep store), ONE ``ServerInstance(device_executor="auto")``, broker and
the broker's HTTP endpoint in-process, pushes every segment through the
controller, and sends the six SSB statements twice (cold, warm) through
HTTP with the DB-API client. Every answer is compared with a plain numpy
reference computed from the generated columns; ``DISTINCTCOUNTHLL`` must
sit within the sketch's error of the exact distinct count AND equal the
host executor's answer on the same segments.

It is a smoke run, not a benchmark: the lines it prints say that the
system started, answered correctly and stayed on the device — the times
in them are one reading each.

It fails (non-zero exit) when JAX finds no TPU — there is no CPU carry-on
and no interpret mode — and on any quiet fallback: a non-empty
``exceptions`` list, a partial result, a device failure / Pallas drop /
quarantine counter above zero, a scan statement that moved no device
bytes or carries a ``host_fallback`` span, or a compile during the warm
pass. The only children it starts build segment files with numpy, pinned
to ``JAX_PLATFORMS=cpu`` before the package loads; none of them needs the
chip.

The last stdout line is
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}``.

``--chips 4`` runs ONLY the four-chip mesh check (no cluster, no HTTP):
the same table on ``DeviceExecutor(mesh=make_mesh(4))`` against a
single-device ``DeviceExecutor()``, the six statements, answers equal and
the batch columns spread over the four devices.
"""

from __future__ import annotations

import argparse
import json
import multiprocessing
import os
import shutil
import sys
import tempfile
import time
import traceback

STATEMENTS = ("q1_scan_agg", "q2_range_sum", "q3_in_range",
              "q4_highcard_hll", "q4_scan_hll", "q5_startree")
# statements that must scan on the device; the other two may answer from
# the star-tree cubes, so the smoke prints where they ran instead
SCAN_STATEMENTS = frozenset(
    ("q1_scan_agg", "q2_range_sum", "q3_in_range", "q4_scan_hll"))
# caches off so the warm pass reaches the device again; trace on so a host
# fallback shows as a span; a cold statement uploads columns and compiles
SET_PREFIX = ("SET useResultCache=false; SET usePartialsCache=false; "
              "SET trace=true; SET timeoutMs=900000; ")
FAILURE_COUNTERS = ("device_failures", "pallas_fallbacks",
                    "pallas_quarantined", "quarantined_pipelines")
HLL_LOG2M = 10                # ops/hll.py DEFAULT_LOG2M: m = 1024 registers
HLL_REL_ERR = 4 * 1.04 / (1 << HLL_LOG2M) ** 0.5  # four standard errors
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
N_SUPP, N_CUST, N_YEAR, N_REGION = 2000, 100_000, 7, 5


def say(line: str) -> None:
    print(line, flush=True)


class Failures(list):
    def add(self, what: str) -> None:
        self.append(what)
        say(f"FAIL {what}")


def require_tpu(chips: int):
    """The first JAX call of the process. No TPU -> exit 2, no result."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: JAX found platform {devices[0].platform!r}, "
              "not a TPU; this script has no CPU path", file=sys.stderr)
        sys.exit(2)
    if len(devices) < chips:
        print(f"chip_smoke: --chips {chips} needs {chips} devices, JAX "
              f"reports {len(devices)}", file=sys.stderr)
        sys.exit(2)
    return devices


def check_executor(executor, failures: Failures) -> None:
    """``DeviceExecutor()`` as ``tools/admin.py start-server`` builds it
    must have resolved both kernel tiers to the chip."""
    from pinot_tpu.engine import device as device_mod

    mm = device_mod._resolve_mm_mode(executor.mm_mode)
    pallas = executor._resolve_pallas({})
    say(f"executor mm_mode={mm} pallas_mode={pallas}")
    if (mm, pallas) != ("tpu", "tpu"):
        failures.add(f"DeviceExecutor() resolved to mm={mm} pallas={pallas}")


# --------------------------------------------------------------------------
# table build (children: numpy only) and the plain numpy reference
# --------------------------------------------------------------------------


def _build_segment_job(job):
    """Runs in a spawned child. JAX is pinned to the CPU before the package
    (whose __init__ imports jax) loads, and nothing here runs a JAX op."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    rng_state, n_rows, out_dir, name = job
    import numpy as np

    from pinot_tpu.storage.creator import build_segment
    from pinot_tpu.tools import ssb

    rng = np.random.default_rng()
    rng.bit_generator.state = rng_state
    t0 = time.time()
    build_segment(ssb.lineorder_schema(), ssb.segment_columns(rng, n_rows),
                  out_dir, ssb.lineorder_table_config(), name)
    return name, time.time() - t0


class Reference:
    """The six answers from the generated columns, by numpy alone —
    independent of pinot_tpu.engine. Accumulated one segment at a time."""

    def __init__(self):
        import numpy as np

        self.np = np
        self.q1 = np.zeros(N_SUPP, dtype=np.int64)
        self.q2 = 0
        self.q3 = [0, 0]
        self.q4_cnt = np.zeros(N_SUPP, dtype=np.int64)
        self.q4_qty = np.zeros(N_SUPP, dtype=np.int64)
        self.q4_seen = np.zeros(N_SUPP * N_CUST, dtype=bool)
        self.q5_sum = np.zeros(N_YEAR * N_REGION, dtype=np.int64)
        self.q5_cnt = np.zeros(N_YEAR * N_REGION, dtype=np.int64)
        self.regions = None

    def _isum(self, ids, weights, n):
        # float64 bincount is exact here: every per-segment partial stays
        # far below 2^53
        np = self.np
        return np.rint(np.bincount(ids, weights=weights, minlength=n)
                       ).astype(np.int64)

    def add(self, c: dict) -> None:
        np = self.np
        supp, rev = c["lo_suppkey"], c["lo_revenue"].astype(np.int64)
        qty, disc = c["lo_quantity"], c["lo_discount"]
        self.q1 += self._isum(supp, rev, N_SUPP)
        m2 = ((c["lo_orderdate"] >= 19930101) & (c["lo_orderdate"] <= 19931231)
              & (disc >= 1) & (disc <= 3) & (qty < 25))
        self.q2 += int(rev[m2].sum())
        m3 = np.isin(supp, (11, 234, 567, 890, 1203, 1456, 1789)) \
            & (disc >= 4) & (disc <= 6)
        self.q3[0] += int(m3.sum())
        self.q3[1] += int(rev[m3].sum())
        self.q4_cnt += np.bincount(supp, minlength=N_SUPP)
        self.q4_qty += self._isum(supp, qty, N_SUPP)
        self.q4_seen[supp.astype(np.int64) * N_CUST + c["lo_custkey"]] = True
        if self.regions is None:
            self.regions = np.unique(c["c_region"])
        cell = (c["d_year"] - 1992) * N_REGION \
            + np.searchsorted(self.regions, c["c_region"])
        self.q5_sum += self._isum(cell, rev, N_YEAR * N_REGION)
        self.q5_cnt += np.bincount(cell, minlength=N_YEAR * N_REGION)

    def rows(self) -> dict:
        np = self.np
        keys = np.arange(N_SUPP)
        top1 = np.lexsort((keys, -self.q1))[:10]
        top4 = np.lexsort((keys, -self.q4_cnt))[:10]
        distinct = self.q4_seen.reshape(N_SUPP, N_CUST).sum(axis=1)
        q4 = [[int(k), int(self.q4_cnt[k]),
               float(self.q4_qty[k]) / float(self.q4_cnt[k]),
               int(distinct[k])] for k in top4]
        q5 = [[1992 + i // N_REGION, str(self.regions[i % N_REGION]),
               int(self.q5_sum[i]), int(self.q5_cnt[i])]
              for i in range(N_YEAR * N_REGION) if self.q5_cnt[i]]
        return {
            "q1_scan_agg": [[int(k), int(self.q1[k])] for k in top1],
            "q2_range_sum": [[self.q2]],
            "q3_in_range": [list(self.q3)],
            "q4_highcard_hll": q4, "q4_scan_hll": q4,
            "q5_startree": q5,
        }


def build_table(work: str, n_segments: int, seed: int):
    """Build every segment (children, in parallel) while the parent draws
    the same columns from the same generator and folds them into the
    reference. Returns (segment dirs, Reference, rows)."""
    import numpy as np

    from pinot_tpu.tools import ssb

    n_rows = ssb.SEGMENT_ROWS
    rng = np.random.default_rng(seed)
    ref = Reference()
    dirs = [os.path.join(work, "built", f"s{i}") for i in range(n_segments)]
    workers = min(n_segments, max(1, (os.cpu_count() or 2) - 1))
    t0 = time.time()
    ctx = multiprocessing.get_context("spawn")
    with ctx.Pool(workers) as pool:
        pending = []
        for i, out in enumerate(dirs):
            # the child restores this state and draws the segment itself:
            # handing it a few hundred bytes beats pickling 1 GB of columns
            job = (rng.bit_generator.state, n_rows, out, f"s{i}")
            pending.append(pool.apply_async(_build_segment_job, (job,)))
            ref.add(ssb.segment_columns(rng, n_rows))
        ref_s = time.time() - t0
        per_seg = [p.get(timeout=1100)[1] for p in pending]
    say(f"build segments={n_segments} rows_per_segment={n_rows} "
        f"rows={n_segments * n_rows} seed={seed} workers={workers} "
        f"seconds={time.time() - t0:.1f} reference_seconds={ref_s:.1f} "
        f"slowest_segment_seconds={max(per_seg):.1f}")
    return dirs, ref, n_segments * n_rows


# --------------------------------------------------------------------------
# answers
# --------------------------------------------------------------------------


def _same_int(got, want) -> bool:
    return float(got) == float(int(want)) and int(got) == int(want)


def compare(name: str, got: list, want: list, twin: list,
            failures: Failures) -> None:
    """Integers exactly; q4's AVG as the same float64 quotient; the HLL
    estimate within the sketch's error of the exact distinct count and
    equal to ``twin`` — the host executor's rows for the same statement."""
    if len(got) != len(want):
        failures.add(f"{name}: {len(got)} rows, reference has {len(want)}")
        return
    hll = name.startswith("q4")
    worst = 0.0
    for r, (g, w) in enumerate(zip(got, want)):
        if hll:
            ok = (_same_int(g[0], w[0]) and _same_int(g[1], w[1])
                  and abs(g[2] - w[2]) <= 1e-12 * abs(w[2]))
            rel = abs(g[3] - w[3]) / w[3]
            worst = max(worst, rel)
            if rel > HLL_REL_ERR:
                failures.add(f"{name} row {r}: HLL {g[3]} vs exact {w[3]} "
                             f"(rel {rel:.4f} > {HLL_REL_ERR:.4f})")
        elif name == "q5_startree":
            ok = (g[:2] == w[:2] and _same_int(g[2], w[2])
                  and _same_int(g[3], w[3]))
        else:
            ok = len(g) == len(w) and all(map(_same_int, g, w))
        if not ok:
            failures.add(f"{name} row {r}: got {g}, reference {w}")
    extra = ""
    if hll:
        same = [list(x) for x in twin or ()] == got
        if not same:
            failures.add(f"{name}: device rows {got} != host rows {twin}")
        extra = f" hll_worst_rel_err={worst:.4f} hll_equals_host={same}"
    say(f"answer {name} rows={len(got)} checked_against=numpy{extra}")


def _phases(stats: dict) -> list:
    """Span names of every server's trace (common/trace.py to_json)."""
    return [span["phase"]
            for spans in (stats.get("traceInfo") or {}).values()
            if isinstance(spans, list) for span in spans]


def check_stats(name: str, which: str, stats: dict,
                failures: Failures) -> None:
    if stats.get("partialResult"):
        failures.add(f"{name} {which}: partialResult")
    moved = stats.get("deviceBytesMoved", 0)
    kernel_ms = stats.get("deviceKernelMs", 0)
    if name in SCAN_STATEMENTS:
        if not moved or not kernel_ms:
            failures.add(f"{name} {which}: deviceBytesMoved={moved} "
                         f"deviceKernelMs={kernel_ms} — did not run on "
                         "the device")
        if any("host_fallback" in p for p in _phases(stats)):
            failures.add(f"{name} {which}: host_fallback span")


# --------------------------------------------------------------------------
# one chip: the served path
# --------------------------------------------------------------------------


class CompileCounter:
    """Counts executables built (cache hit or not) and persistent-cache
    hits/misses, from jax.monitoring."""

    def __init__(self):
        import jax

        self.built = self.hits = self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._evt)

    def _dur(self, event, _secs, **_kw):
        if event == BACKEND_COMPILE_EVENT:
            self.built += 1

    def _evt(self, event, **_kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


def _cache_dir() -> str:
    import jax

    return os.environ.get("JAX_COMPILATION_CACHE_DIR") \
        or jax.config.jax_compilation_cache_dir


def _cache_entries() -> int:
    d = _cache_dir()
    return len(os.listdir(d)) if d and os.path.isdir(d) else 0


def check_packer(failures: Failures) -> None:
    from pinot_tpu import native

    loaded = native.native_available()
    say(f"packer {'native' if loaded else 'numpy'}")
    if not loaded and shutil.which("g++"):
        failures.add("g++ is present but the native packer did not build")


def run_served(args, devices, failures: Failures) -> None:
    from pinot_tpu import client
    from pinot_tpu.broker.broker import Broker
    from pinot_tpu.broker.http_api import BrokerHttpServer
    from pinot_tpu.cluster.registry import ClusterRegistry
    from pinot_tpu.controller.controller import Controller
    from pinot_tpu.engine.engine import QueryEngine
    from pinot_tpu.server.server import ServerInstance
    from pinot_tpu.storage.segment import ImmutableSegment
    from pinot_tpu.tools import ssb

    compiles = CompileCounter()
    entries_before = _cache_entries()
    say(f"compile_cache dir={_cache_dir()} entries_before={entries_before}")
    check_packer(failures)
    if args.segments < ssb.SEGMENTS:
        say(f"reduced segments {ssb.SEGMENTS} -> {args.segments} "
            f"(rows {ssb.SEGMENTS * ssb.SEGMENT_ROWS} -> "
            f"{args.segments * ssb.SEGMENT_ROWS}); segment length kept")

    work = tempfile.mkdtemp(prefix="pinot_tpu_chip_smoke_")
    server = broker = http = host = None
    try:
        dirs, ref, n_rows = build_table(work, args.segments, args.seed)

        registry = ClusterRegistry()
        controller = Controller(registry, os.path.join(work, "deepstore"))
        server = ServerInstance("server_0", registry,
                                os.path.join(work, "server_0"),
                                device_executor="auto")
        check_executor(server.engine.device, failures)
        server.start()
        broker = Broker(registry)
        http = BrokerHttpServer(broker)
        http.start()

        controller.add_table(ssb.lineorder_table_config(),
                             ssb.lineorder_schema())
        t0 = time.time()
        records = []
        for d in dirs:
            records.append(controller.upload_segment("lineorder", d))
            shutil.rmtree(d)  # the deep store holds it now
        upload_s = time.time() - t0
        table = records[0].table
        while len(registry.external_view(table)) < len(dirs):
            if time.time() - t0 > 600:
                failures.add(f"external view shows "
                             f"{len(registry.external_view(table))} of "
                             f"{len(dirs)} segments after 600 s")
                return
            time.sleep(0.1)
        say(f"upload seconds={upload_s:.1f} load_seconds="
            f"{time.time() - t0:.1f} segments_online={len(dirs)}")

        conn = client.connect(http.url, timeout_s=900)
        answers, stats = {}, {}
        for which in ("cold", "warm"):
            built_before = compiles.built
            for name in STATEMENTS:
                cur = conn.cursor()
                t0 = time.time()
                try:
                    cur.execute(SET_PREFIX + ssb.QUERIES[name])
                except client.Error as e:
                    failures.add(f"{name} {which}: {e}")
                    continue
                ms = (time.time() - t0) * 1000
                answers[name, which] = [list(r) for r in cur.fetchall()]
                stats[name, which] = dict(cur.stats, clientMs=ms)
                check_stats(name, which, cur.stats, failures)
            if which == "warm" and compiles.built != built_before:
                failures.add(f"warm pass built "
                             f"{compiles.built - built_before} executables")
        conn.close()

        # the host executor on the same segments: the HLL answers' twin
        host = QueryEngine(device_executor=None)
        for rec in records:
            host.add_segment("lineorder", ImmutableSegment(rec.location))
        want = ref.rows()
        for name in STATEMENTS:
            if (name, "warm") not in answers:
                continue
            host_rows = None
            if name.startswith("q4"):
                host_rows = host.execute(ssb.QUERIES[name])[
                    "resultTable"]["rows"]
            for which in ("cold", "warm"):
                if answers[name, which] != answers[name, "warm"]:
                    failures.add(f"{name}: cold and warm answers differ")
            compare(name, answers[name, "warm"], want[name], host_rows,
                    failures)
            c, w = stats[name, "cold"], stats[name, "warm"]
            labels = sorted({r.get("kernel", "?")
                             for r in w.get("roofline") or ()})
            say(f"query {name} cold_ms={c['clientMs']:.1f} "
                f"warm_ms={w['clientMs']:.1f} "
                f"deviceKernelMs={w.get('deviceKernelMs')} "
                f"deviceLinkMs={w.get('deviceLinkMs')} "
                f"deviceBytesMoved={w.get('deviceBytesMoved')} "
                f"ran={'device' if w.get('deviceBytesMoved') else 'host'} "
                f"kernel={'|'.join(labels) or '-'}")

        hbm = server.engine.device.hbm_stats()
        say("counters " + " ".join(f"{k}={hbm[k]}" for k in FAILURE_COUNTERS)
            + f" resident_bytes={hbm['resident_bytes']} rows={n_rows}")
        for k in FAILURE_COUNTERS:
            if hbm[k]:
                failures.add(f"hbm_stats {k}={hbm[k]}")
        mem = devices[0].memory_stats() or {}
        say(f"hbm peak_bytes_in_use={mem.get('peak_bytes_in_use')} "
            f"bytes_limit={mem.get('bytes_limit')}")
        say(f"compile_cache entries_after={_cache_entries()} "
            f"hits={compiles.hits} misses={compiles.misses} "
            f"executables_built={compiles.built}")
    finally:
        for stop in (http and http.stop, broker and broker.close,
                     server and server.stop):
            if stop:
                try:
                    stop()
                except Exception:  # noqa: BLE001 — teardown is best-effort
                    traceback.print_exc()
        shutil.rmtree(work, ignore_errors=True)


# --------------------------------------------------------------------------
# four chips: the mesh against one device (behind --chips 4)
# --------------------------------------------------------------------------


def _live_bytes_per_device(devices) -> list:
    """Bytes of every live jax array's shards, by device."""
    import jax

    per = {d: 0 for d in devices}
    for a in jax.live_arrays():
        for sh in a.addressable_shards:
            if sh.device in per:
                per[sh.device] += sh.data.nbytes
    return [per[d] for d in devices]


def run_mesh(args, devices, failures: Failures) -> None:
    """The same table on ``DeviceExecutor(mesh=make_mesh(4))`` and on a
    single-device ``DeviceExecutor()``, embedded engines on the same
    segments: the six statements twice each, answers equal to each other
    and to the numpy reference, and the mesh batch's column bytes spread
    over the four devices instead of sitting on the first."""
    from pinot_tpu.engine.device import DeviceExecutor
    from pinot_tpu.engine.engine import QueryEngine
    from pinot_tpu.parallel.mesh import make_mesh
    from pinot_tpu.storage.segment import ImmutableSegment
    from pinot_tpu.tools import ssb

    if args.segments < ssb.SEGMENTS:
        say(f"reduced segments {ssb.SEGMENTS} -> {args.segments}; "
            "segment length kept")
    work = tempfile.mkdtemp(prefix="pinot_tpu_chip_smoke_")
    try:
        dirs, ref, n_rows = build_table(work, args.segments, args.seed)
        segs = [ImmutableSegment(d) for d in dirs]
        want = ref.rows()
        answers = {}
        for label in ("mesh", "single"):
            executor = DeviceExecutor(mesh=make_mesh(devices=devices)) \
                if label == "mesh" else DeviceExecutor()
            check_executor(executor, failures)
            engine = QueryEngine(device_executor=executor)
            for seg in segs:
                engine.add_segment("lineorder", seg)
            for name in STATEMENTS:
                ms = {}
                for which in ("cold", "warm"):
                    t0 = time.time()
                    resp = engine.execute(
                        "SET usePartialsCache=false; " + ssb.QUERIES[name])
                    ms[which] = (time.time() - t0) * 1000
                    if resp.get("exceptions"):
                        failures.add(f"{label} {name} {which}: "
                                     f"{resp['exceptions']}")
                        break
                    check_stats(name, f"{label} {which}", resp, failures)
                    answers[label, name] = resp["resultTable"]["rows"]
                else:
                    say(f"query {label} {name} cold_ms={ms['cold']:.1f} "
                        f"warm_ms={ms['warm']:.1f} "
                        f"deviceKernelMs={resp.get('deviceKernelMs')} "
                        f"deviceBytesMoved={resp.get('deviceBytesMoved')}")
            hbm = executor.hbm_stats()
            say(f"counters {label} " + " ".join(
                f"{k}={hbm[k]}" for k in FAILURE_COUNTERS)
                + f" resident_bytes={hbm['resident_bytes']} rows={n_rows}")
            for k in FAILURE_COUNTERS:
                if hbm[k]:
                    failures.add(f"{label} hbm_stats {k}={hbm[k]}")
            if label == "mesh":
                # before the single-device executor exists: what is live
                # now is the mesh executor's batch
                per = _live_bytes_per_device(devices)
                in_use = [(d.memory_stats() or {}).get("bytes_in_use")
                          for d in devices]
                share0 = per[0] / max(1, sum(per))
                say(f"mesh_bytes live_per_device={per} "
                    f"device0_share={share0:.3f} memory_stats_in_use={in_use}")
                if share0 > 1 / len(devices) + 0.10:
                    failures.add(f"device 0 holds {share0:.0%} of the mesh "
                                 "batch's bytes")
        for name in STATEMENTS:
            if ("mesh", name) not in answers or ("single", name) not in answers:
                continue
            if answers["mesh", name] != answers["single", name]:
                failures.add(f"{name}: mesh and single-device answers differ")
            # the HLL twin here is the single-device answer
            compare(name, answers["mesh", name], want[name],
                    answers["single", name], failures)
    finally:
        shutil.rmtree(work, ignore_errors=True)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--segments", type=int, default=8, choices=(8, 4, 2),
                    help="segment count; below 8 only when the time limit "
                         "forces it (segment length is never cut)")
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the four-chip mesh check")
    args = ap.parse_args()

    # outside the repo there is nothing to prove: die on the import, with
    # no result line
    import pinot_tpu  # noqa: F401 — also turns x64 on and places the cache

    devices = require_tpu(args.chips)
    devices = devices[:args.chips] if args.chips > 1 else devices
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    say(f"device platform={device['platform']} kind={device['kind']} "
        f"count={device['count']}")
    failures = Failures()
    t0 = time.time()
    try:
        if args.chips == 4:
            run_mesh(args, devices, failures)
        else:
            run_served(args, devices, failures)
    except Exception:  # noqa: BLE001 — a crashed phase is a failed phase
        traceback.print_exc()
        failures.add("a phase raised; see the traceback on stderr")
    say(f"total seconds={time.time() - t0:.1f} failures={len(failures)}")
    print(json.dumps({"ok": not failures, "device": device}), flush=True)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
