"""pinot_tpu — a TPU-native realtime distributed OLAP framework.

A from-scratch re-design of the capabilities of Apache Pinot (reference:
/root/reference, 0.11.0-SNAPSHOT) for TPU hardware:

- Columnar segments live as padded, dict-encoded device arrays in HBM
  (replacing mmap'd ``PinotDataBuffer`` byte buffers,
  pinot-segment-spi/.../memory/PinotDataBuffer.java).
- The per-segment operator chain (filter -> doc-id-set -> projection ->
  transform -> aggregate, pinot-core/.../operator/) is replaced by fused,
  jitted mask-based kernel pipelines specialized per query shape.
- The per-server multi-segment combine (BaseCombineOperator thread fan-out +
  BlockingQueue merge) is replaced by batched kernel launches over a stacked
  segment axis and ``psum``/``all_gather`` collectives over a
  ``jax.sharding.Mesh``.
- Broker / controller / ingestion control planes stay host-side Python/C++.

int64 support is required for exact integral aggregation (SUM over 100M+
int32 rows overflows 32 bits); TPUs execute int64 as lowered int32 pairs.
"""

import os

import jax

jax.config.update("jax_enable_x64", True)

# Persistent compile cache: a cold pipeline at the 8 x 12.5M-row batch shape
# compiles in ~20 s, and a process that restarts must not pay that again.
# JAX_COMPILATION_CACHE_DIR places the cache from outside (JAX reads it
# itself, so nothing is set here); otherwise it sits at a fixed path inside
# the checkout — the path is part of the cache key, so it must not move.
if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    jax.config.update(
        "jax_compilation_cache_dir",
        os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), ".jax_cache"))

__version__ = "0.1.0"
