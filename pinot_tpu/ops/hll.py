"""HyperLogLog on device: DISTINCTCOUNTHLL's kernel.

The reference delegates to the clearspring HyperLogLog Java lib
(DistinctCountHLLAggregationFunction.java, ObjectSerDeUtils); here the
register update is a TPU-friendly scatter-max over (m,) int32 registers —
registers merge across segments/chips with an elementwise max (psum-style
combine), and the cardinality estimate runs host-side from the registers.
When the Pallas scatter tier is on, register spaces up to its slot bound
build through the rho-threshold-presence kernel instead
(ops/pallas_scatter.py hll_register_max; engine/device.py _hll_regs
routes) — the serialized scatter-max here stays compiled-in as the
differential reference and fallback rung.

Hashing: 32-bit murmur3 finalizer (avalanche) over int32 keys — global dict
ids for dictionary columns (value-consistent across segments), raw bits for
numeric columns.
"""

from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

DEFAULT_LOG2M = 10  # reference default is log2m=8 (DistinctCountHLL...); we
# default finer (±3.2% vs ±6.5%) since device registers are cheap — and
# small enough that the matmul register build (ops/groupby_mm.py
# hll_registers) stays within its VMEM accumulator budget


def hash32(x):
    """Murmur3 fmix32 avalanche over int32 lanes (device)."""
    h = x.astype(jnp.uint32)
    h ^= h >> 16
    h *= jnp.uint32(0x85EBCA6B)
    h ^= h >> 13
    h *= jnp.uint32(0xC2B2AE35)
    h ^= h >> 16
    return h


def hll_idx_rho(h, log2m: int):
    """(register index, rank) from uint32 hashes — the one place the
    register math lives; host parity depends on registers_np matching."""
    idx = (h >> (32 - log2m)).astype(jnp.int32)
    w = (h << log2m) | jnp.uint32(1 << (log2m - 1))  # sentinel caps rho
    rho = jax.lax.clz(w.astype(jnp.int32)).astype(jnp.int32) + 1
    return idx, rho


def hll_registers_prehashed(h, mask, log2m: int = DEFAULT_LOG2M):
    """Register build from pre-computed uint32 hashes (e.g. a per-dictid hash
    LUT gathered on device). Masked-out docs land in an overflow register that
    is sliced away. Returns int32 (m,) registers."""
    m = 1 << log2m
    idx, rho = hll_idx_rho(h, log2m)
    idx = jnp.where(mask, idx, m)
    regs = jnp.zeros(m + 1, dtype=jnp.int32).at[idx.reshape(-1)].max(rho.reshape(-1))
    return regs[:m]


def hll_registers(keys, mask, log2m: int = DEFAULT_LOG2M):
    """Scatter-max HLL register build over an (S, L) or (L,) int32 key array."""
    return hll_registers_prehashed(hash32(keys), mask, log2m)


def murmur3_32(data: bytes, seed: int = 0) -> int:
    """Canonical murmur3_32 over bytes — deterministic across processes and
    restarts, unlike builtin ``hash()`` (PYTHONHASHSEED-salted), so HLL
    register partials for string columns built on different servers merge to
    the union, not the sum. Matches the reference's murmur-based hashing of
    raw values (clearspring HyperLogLog via DistinctCountHLLAggregationFunction)."""
    c1, c2 = 0xCC9E2D51, 0x1B873593
    h = seed & 0xFFFFFFFF
    n = len(data) & ~3
    for i in range(0, n, 4):
        k = int.from_bytes(data[i : i + 4], "little")
        k = (k * c1) & 0xFFFFFFFF
        k = ((k << 15) | (k >> 17)) & 0xFFFFFFFF
        k = (k * c2) & 0xFFFFFFFF
        h ^= k
        h = ((h << 13) | (h >> 19)) & 0xFFFFFFFF
        h = (h * 5 + 0xE6546B64) & 0xFFFFFFFF
    tail = data[n:]
    k = 0
    if len(tail) >= 3:
        k ^= tail[2] << 16
    if len(tail) >= 2:
        k ^= tail[1] << 8
    if len(tail) >= 1:
        k ^= tail[0]
        k = (k * c1) & 0xFFFFFFFF
        k = ((k << 15) | (k >> 17)) & 0xFFFFFFFF
        k = (k * c2) & 0xFFFFFFFF
        h ^= k
    h ^= len(data)
    h ^= h >> 16
    h = (h * 0x85EBCA6B) & 0xFFFFFFFF
    h ^= h >> 13
    h = (h * 0xC2B2AE35) & 0xFFFFFFFF
    h ^= h >> 16
    return h


def hash32_np(values: np.ndarray) -> np.ndarray:
    """Host-side canonical hash, bit-identical to :func:`hash32` so host and
    device HLL partials merge consistently. 64-bit inputs fold hi^lo;
    strings/bytes hash via deterministic murmur3_32 over UTF-8 bytes
    (hashed once per unique value, mapped back through the inverse index)."""
    v = np.asarray(values)
    if v.dtype.kind in ("U", "S", "O"):
        uniq, inv = np.unique(v, return_inverse=True)
        uh = np.array(
            [
                murmur3_32(x.encode("utf-8") if isinstance(x, str) else bytes(x))
                for x in uniq.tolist()
            ],
            dtype=np.uint32,
        )
        h = uh[inv.reshape(v.shape)]
    elif v.dtype.itemsize == 8:
        bits = v.view(np.uint64)
        h = ((bits >> np.uint64(32)) ^ (bits & np.uint64(0xFFFFFFFF))).astype(np.uint32)
    elif v.dtype.itemsize == 4:
        h = v.view(np.uint32)
    else:
        h = v.astype(np.uint32)
    h = h.copy()
    h ^= h >> 16
    h = (h * np.uint32(0x85EBCA6B)).astype(np.uint32)
    h ^= h >> 13
    h = (h * np.uint32(0xC2B2AE35)).astype(np.uint32)
    h ^= h >> 16
    return h


def registers_np(values: np.ndarray, group_idx: np.ndarray, n_groups: int,
                 log2m: int = DEFAULT_LOG2M) -> np.ndarray:
    """Host-side register build over raw values (canonical form)."""
    m = 1 << log2m
    h = hash32_np(values)
    idx = (h >> np.uint32(32 - log2m)).astype(np.int64)
    w = ((h.astype(np.uint64) << np.uint64(log2m)) | np.uint64(1 << (log2m - 1))) \
        & np.uint64(0xFFFFFFFF)
    w = np.maximum(w, 1)
    rho = (32 - np.floor(np.log2(w.astype(np.float64))).astype(np.int32)).astype(np.int32)
    regs = np.zeros((n_groups, m), dtype=np.int32)
    np.maximum.at(regs, (np.asarray(group_idx), idx), rho)
    return regs


def merge_registers(a, b):
    return jnp.maximum(a, b)


def _alpha(m: int) -> float:
    if m >= 128:
        return 0.7213 / (1 + 1.079 / m)
    if m == 64:
        return 0.709
    if m == 32:
        return 0.697
    return 0.673


def estimate_batch_np(regs2d: np.ndarray) -> np.ndarray:
    """Vectorized host estimate over (G, m) register planes → (G,) int64.

    Must produce bit-identical results to ``estimate`` per row: the device
    finalize path (estimate_jnp) and the host finalize path both route
    through this math, and oracle tests compare them."""
    regs = np.asarray(regs2d, dtype=np.float64)
    G, m = regs.shape
    raw = _alpha(m) * m * m / np.sum(np.exp2(-regs), axis=1)
    zeros = np.sum(regs2d == 0, axis=1)
    small = (raw <= 2.5 * m) & (zeros > 0)
    lin = m * np.log(m / np.maximum(zeros, 1))
    big = raw > (1 << 32) / 30.0
    large = -float(1 << 32) * np.log(1.0 - raw / float(1 << 32))
    est = np.where(small, lin, np.where(big, large, raw))
    return np.round(est).astype(np.int64)


def estimate_jnp(regs):
    """Device (traced) estimate over (G, m) registers → (G,) int64 — the
    terminal-query finalize that spares shipping G*m register bytes over
    the host link (a 2000-group log2m=11 plane is 4MB of transfer for 16KB
    of answers)."""
    G, m = regs.shape
    rf = regs.astype(jnp.float64)
    raw = _alpha(m) * m * m / jnp.sum(jnp.exp2(-rf), axis=1)
    zeros = jnp.sum(regs == 0, axis=1)
    small = (raw <= 2.5 * m) & (zeros > 0)
    lin = m * jnp.log(m / jnp.maximum(zeros, 1).astype(jnp.float64))
    big = raw > (1 << 32) / 30.0
    large = -float(1 << 32) * jnp.log(1.0 - raw / float(1 << 32))
    est = jnp.where(small, lin, jnp.where(big, large, raw))
    return jnp.round(est).astype(jnp.int64)


def estimate(registers: np.ndarray) -> int:
    """Host-side cardinality estimate (standard HLL with corrections) —
    one row of the batch form, so the correction math lives in exactly one
    np implementation (plus its jnp mirror)."""
    return int(estimate_batch_np(np.asarray(registers)[None, :])[0])


def estimate_from_sums_jnp(sums, log2m: int):
    """(3, G) f64 scaled register sums → (G,) int64 estimates,
    BIT-IDENTICAL to ``estimate_jnp`` over the dense register planes.

    sums rows (engine/device.py _hll_sorted_sums):
      [0] count of registers with at least one row (so zeros = m - s0)
      [1] Σ 2^(split - reg)  over present registers with reg <= split
      [2] Σ 2^(rho_max - reg) over present registers with reg > split
    with split = rho_max // 2, rho_max = 33 - log2m. Every term is a
    power of two (bf16/f32-exact) and each scaled sum stays below 2^24
    (f32 matmul accumulation exact), so the f64 recombination below is
    the EXACT value of Σ 2^-reg — the same real number estimate_jnp's
    f64 summation produces — making the correction branches and the
    final round bit-identical."""
    m = 1 << log2m
    rho_max = 33 - log2m
    split = rho_max // 2
    s1, s2, s3 = sums[0], sums[1], sums[2]
    zeros = m - s1
    denom = zeros + s2 * (2.0 ** -split) + s3 * (2.0 ** -rho_max)
    raw = _alpha(m) * m * m / denom
    small = (raw <= 2.5 * m) & (zeros > 0)
    lin = m * jnp.log(m / jnp.maximum(zeros, 1.0))
    big = raw > (1 << 32) / 30.0
    large = -float(1 << 32) * jnp.log(1.0 - raw / float(1 << 32))
    est = jnp.where(small, lin, jnp.where(big, large, raw))
    return jnp.round(est).astype(jnp.int64)
