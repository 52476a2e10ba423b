"""The load generator: a process of its own, started by ``run.py`` with
``JAX_PLATFORMS=cpu`` so that it never needs the chip and its client threads
do not share the server's interpreter lock.

One general generator reads a traffic file: ``clients`` closed-loop callers
(threads), each with its own DB-API connection to the broker's HTTP
endpoint, each pausing for a think time and then sending its next statement
once the last one answered. A client's statements come from a deck holding
every statement ``weight`` times, its pauses from the mix's ``think_ms``
deck; both are reshuffled by (seed, client) each time they are dealt out:
every seed sends the same mix with the same pauses in another order. A mix
has to state its pauses: callers that never pause fall into lockstep with
the executor's launches and stay in one of several lasting cohort patterns
for a whole run (PERF.md, PR 28).

Commands arrive as lines on stdin; each is answered by one JSON line on
stdout:

  url U  where the broker listens (first, once the cluster is up)
  once   every statement once, one after another, on one connection
  burst  2, 3, .. ``clients`` callers send at the same instant, three times
         each, distinct statements: the warm-up's cohorts of every width
  start  the clients begin; answered at once with the start time
  stop   the clients finish the request they are in and stop; answered with
         every request's record and the time the stop arrived
  quit   leave
"""

from __future__ import annotations

import json
import os
import random
import sys
import threading
import time

STATS_KEPT = ("timeUsedMs", "numSegmentsQueried", "numSegmentsProcessed",
              "numSegmentsPrunedByServer", "deviceKernelMs", "deviceLinkMs",
              "deviceBytesMoved", "partialResult", "numDocsScanned",
              "numSegmentsOnHost")


def _span_names(stats: dict) -> list:
    return [span.get("phase", "")
            for spans in (stats.get("traceInfo") or {}).values()
            if isinstance(spans, list) for span in spans]


def send(client, conn, statement: dict, text: str, traced: bool) -> dict:
    """One request: send -> rows parsed, on this process's clock.
    ``off_device``: a ``device`` statement that the host executor answered
    a segment of — every response counts those (``numSegmentsOnHost``), and
    a ``traced`` one carries the ``host_fallback`` span besides."""
    rec = {"statement": statement["name"], "ok": False,
           "device": bool(statement.get("device")), "t_send": time.time()}
    try:
        cur = conn.cursor()
        cur.execute(text)
        rows = [list(r) for r in cur.fetchall()]
        rec["t_done"] = time.time()
        stats = cur.stats
        rec["stats"] = {k: stats[k] for k in STATS_KEPT if k in stats}
        rec["rows"] = rows
        # under load only the leader of a coalesced launch is charged
        # deviceBytesMoved, so a 0 there proves nothing about its cohort
        rec["off_device"] = rec["device"] and bool(
            stats.get("numSegmentsOnHost") or (traced and any(
                "host_fallback" in p for p in _span_names(stats))))
        if stats.get("partialResult"):
            rec["error"] = "partialResult"
        else:
            rec["ok"] = True
    except client.Error as e:
        rec["t_done"] = time.time()
        rec["error"] = f"{type(e).__name__}: {e}"[:300]
    return rec


def _dealt(rng: random.Random, deck: list):
    """The deck's cards for ever, reshuffled each time it is dealt out."""
    while True:
        rng.shuffle(deck)
        yield from deck


class Generator:
    def __init__(self, url: str, traffic: dict, seed: int, trace: bool):
        from pinot_tpu import client

        self.client, self.url, self.traffic = client, url, traffic
        self.seed, self.trace = seed, trace
        prefix = traffic["set_prefix"] + (
            traffic.get("trace_prefix", "") if trace else "")
        self.statements = traffic["statements"]
        self.texts = {s["name"]: prefix + s["sql"] for s in self.statements}
        # the warm-up's single pass always asks for the trace, so that a
        # host fallback shows as a span
        self.traced_texts = {
            s["name"]: traffic["set_prefix"] + traffic.get("trace_prefix", "")
            + s["sql"] for s in self.statements}
        self.threads: list = []
        self.records: list = []
        self.halt = threading.Event()

    def _connect(self):
        return self.client.connect(self.url, timeout_s=120)

    def once(self) -> list:
        conn = self._connect()
        try:
            return [send(self.client, conn, s, self.traced_texts[s["name"]],
                         True) for s in self.statements]
        finally:
            conn.close()

    def burst(self, repeats: int = 3) -> list:
        """Cohorts of every width the window can meet, on purpose: the
        executor coalesces callers that arrive together into one launch and
        compiles one program a width, and rounds of paced traffic meet the
        wider ones by chance — a width first met in the window compiled
        there (PERF.md, PR 31). ``w`` callers wait at a barrier and send
        distinct statements at once, for each w from 2 to ``clients``."""
        conns = [self._connect() for _ in range(self.traffic["clients"])]
        records = []

        def one(barrier, conn, s):
            barrier.wait(timeout=60)
            records.append(send(self.client, conn, s, self.texts[s["name"]],
                                self.trace))

        try:
            for width in range(2, len(conns) + 1):
                for turn in range(repeats):
                    barrier = threading.Barrier(width)
                    callers = [threading.Thread(target=one, args=(
                        barrier, conns[i],
                        self.statements[(i + turn) % len(self.statements)]))
                        for i in range(width)]
                    for t in callers:
                        t.start()
                    for t in callers:
                        t.join(timeout=180)
        finally:
            for conn in conns:
                conn.close()
        return records

    def _client(self, index: int, out: list) -> None:
        rng = random.Random(self.seed * 1000 + index)
        statements = _dealt(rng, [s for s in self.statements
                                  for _ in range(int(s["weight"]))])
        pauses = _dealt(rng, list(self.traffic["think_ms"]))
        conn = self._connect()
        try:
            while not self.halt.wait(next(pauses) / 1000):
                s = next(statements)
                out.append(send(self.client, conn, s, self.texts[s["name"]],
                                self.trace))
                out[-1]["client"] = index
        finally:
            conn.close()

    def start(self) -> float:
        self.halt.clear()
        self.records = [[] for _ in range(self.traffic["clients"])]
        self.threads = [
            threading.Thread(target=self._client, args=(i, out), daemon=True)
            for i, out in enumerate(self.records)]
        t0 = time.time()
        for t in self.threads:
            t.start()
        return t0

    def stop(self) -> dict:
        t1 = time.time()
        self.halt.set()
        for t in self.threads:
            t.join(timeout=180)
        hung = sum(t.is_alive() for t in self.threads)
        recs = [r for out in self.records for r in out]
        return {"t_stop": t1, "records": recs, "hung_clients": hung}


def main() -> int:
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    sys.path.insert(0, root)
    traffic_file, seed, trace = sys.argv[1:4]
    with open(traffic_file) as f:
        traffic = json.load(f)
    if traffic.get("loop") != "closed":
        raise SystemExit("this generator runs closed loops only")
    if not traffic.get("think_ms"):
        raise SystemExit(f"{traffic_file} states no think_ms: a mix has to "
                         "say how long its callers pause ([0] for never)")
    from pinot_tpu import client  # noqa: F401 — loaded while the table builds

    gen = None
    for line in sys.stdin:
        cmd = line.strip()
        if cmd.startswith("url "):
            gen = Generator(cmd[4:], traffic, int(seed), trace == "1")
            reply = {"url": cmd[4:]}
        elif cmd == "once":
            reply = {"records": gen.once()}
        elif cmd == "burst":
            reply = {"records": gen.burst()}
        elif cmd == "start":
            reply = {"t_start": gen.start()}
        elif cmd == "stop":
            reply = gen.stop()
        elif cmd == "quit":
            break
        else:
            reply = {"error": f"unknown command {cmd!r}"}
        sys.stdout.write(json.dumps(reply) + "\n")
        sys.stdout.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
