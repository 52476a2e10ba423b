"""The system under test, brought up in this process as ``chip_smoke.py
run_served`` brings it up: registry, controller, ONE
``ServerInstance(device_executor="auto")``, broker and the broker's HTTP
endpoint. Also the look for the chip, the executor's tier check and the
compile counter, lifted from the smoke.
"""

from __future__ import annotations

import os
import sys
import time

FAILURE_COUNTERS = ("device_failures", "pallas_fallbacks",
                    "pallas_quarantined", "quarantined_pipelines")
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def require_tpu(chips: int):
    """The first JAX call of the process. No TPU, or fewer chips than the
    cell asks for -> exit 2, no result line: there is no CPU carry-on."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"benchmark: JAX found platform {devices[0].platform!r}, not "
              "a TPU; no number is taken off the chip", file=sys.stderr)
        sys.exit(2)
    if len(devices) < chips:
        print(f"benchmark: the cell needs {chips} chips, JAX reports "
              f"{len(devices)}", file=sys.stderr)
        sys.exit(2)
    return devices[:chips]


class CompileCounter:
    """Executables built (cache hit or not) and persistent-cache hits and
    misses, from jax.monitoring."""

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


def cache_dir() -> str:
    import jax

    return os.environ.get("JAX_COMPILATION_CACHE_DIR") \
        or jax.config.jax_compilation_cache_dir


class Cluster:
    """Owns everything it starts; ``close`` stops it all."""

    def __init__(self, config: dict, work: str):
        from pinot_tpu.broker.broker import Broker
        from pinot_tpu.broker.http_api import BrokerHttpServer
        from pinot_tpu.cluster.registry import ClusterRegistry
        from pinot_tpu.controller.controller import Controller
        from pinot_tpu.server.server import ServerInstance

        dep = config["deployment"]
        if (dep["servers"], dep["brokers"], dep["replication"]) != (1, 1, 1):
            raise SystemExit("this harness brings up one server, one broker, "
                             "replication 1")
        self.config = config
        self.registry = ClusterRegistry()
        self.controller = Controller(self.registry,
                                     os.path.join(work, "deepstore"))
        self.server_dir = os.path.join(work, "server_0")
        self.server = ServerInstance(
            "server_0", self.registry, self.server_dir,
            device_executor=dep["device_executor"])
        self.broker = self.http = None
        self.server.start()
        try:
            self.broker = Broker(self.registry)
            self.http = BrokerHttpServer(self.broker)
            self.http.start()
        except BaseException:
            self.close()
            raise

    @property
    def url(self) -> str:
        return self.http.url

    @property
    def executor(self):
        return self.server.engine.device

    def tiers(self) -> tuple:
        """(matmul tier, Pallas tier) the executor resolved to: ("tpu",
        "tpu") on the chip."""
        from pinot_tpu.engine import device as device_mod

        return (device_mod._resolve_mm_mode(self.executor.mm_mode),
                self.executor._resolve_pallas({}))

    def load(self, dirs: list, say) -> None:
        """Register every segment where it was built — under the server's
        data directory, which the server serves in place — and wait until
        the external view shows them all ONLINE."""
        from pinot_tpu.common.schema import Schema
        from pinot_tpu.common.table_config import TableConfig

        cfg = self.config
        self.controller.add_table(TableConfig.from_json(cfg["table_config"]),
                                  Schema.from_json(cfg["schema"]))
        t0 = time.time()
        records = [self.controller.upload_segment(
            cfg["table"], d, copy_to_deep_store=False) for d in dirs]
        table = records[0].table
        while len(self.registry.external_view(table)) < len(dirs):
            if time.time() - t0 > 600:
                raise SystemExit(
                    f"external view shows "
                    f"{len(self.registry.external_view(table))} of "
                    f"{len(dirs)} segments after 600 s")
            time.sleep(0.05)
        say(f"load seconds={time.time() - t0:.1f} "
            f"segments_online={len(dirs)}")

    def failure_counters(self) -> dict:
        hbm = self.executor.hbm_stats()
        return {k: hbm[k] for k in FAILURE_COUNTERS}

    def resident_bytes(self) -> int:
        return self.executor.hbm_stats()["resident_bytes"]

    def close(self) -> None:
        import traceback

        stops = (self.http and self.http.stop,
                 self.broker and self.broker.close,
                 self.server and self.server.stop)
        self.http = self.broker = self.server = None  # a second close is none
        for stop in stops:
            if stop:
                try:
                    stop()
                except Exception:  # noqa: BLE001 — teardown is best-effort
                    traceback.print_exc()
