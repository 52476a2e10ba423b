"""Native runtime pieces: on-demand-compiled C++ with numpy fallbacks.

The compute path is JAX/XLA; the runtime around it uses native code where
the reference does (here: the bit-packing codec backing
``<col>.fwdpacked.bin``, the FixedBitSVForwardIndexWriter/PinotDataBitSet
analog). The shared library is compiled once per checkout with the system
``g++`` (no pip/pybind11 — plain ``extern "C"`` + ctypes) and cached next
to the source under a name that carries a hash of ``packer.cpp``'s bytes,
so a library is only ever loaded if it was built from the source beside
it; when no toolchain is available the vectorized numpy fallback serves
the same format, so segments stay portable either way.
"""

from __future__ import annotations

import ctypes
import hashlib
import logging
import os
import subprocess
import threading

import numpy as np

log = logging.getLogger("pinot_tpu.native")

_HERE = os.path.dirname(__file__)
_SRC = os.path.join(_HERE, "packer.cpp")


def _lib_path() -> str:
    """The library file for the CURRENT source bytes. A file mtime says
    nothing once a tree has been copied to another machine; the content
    hash does, and a stale build simply never matches the name."""
    with open(_SRC, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    return os.path.join(_HERE, f"_libpinot_packer.{digest}.so")


_lock = threading.Lock()
_lib = None
_lib_tried = False


def _compile(lib_path: str) -> bool:
    # compile to a pid-suffixed temp then os.replace: concurrent processes
    # racing through a fresh checkout must never dlopen a half-written .so
    tmp = f"{lib_path}.{os.getpid()}"
    base = ["g++", "-O3", "-shared", "-fPIC", "-o", tmp, _SRC]
    # degrade codec by codec: a host missing one dev header/library must
    # not cost the others their native path (python fallbacks read the
    # same bytes, slower). liblz4 often ships only the versioned .so.
    # probe each codec independently, then compile once with exactly the
    # available set — a host missing one dev header/library must not cost
    # the OTHERS their native path
    probes = {
        "zlib": (["-lz"], "#include <zlib.h>\nint main(){return 0;}"),
        "zstd": (["-lzstd"], "#include <zstd.h>\nint main(){return 0;}"),
        # liblz4 often ships only the versioned .so and no header; the
        # packer declares the stable ABI itself, so probe link-only
        "lz4": (["-l:liblz4.so.1"],
                "extern \"C\" int LZ4_compressBound(int);\n"
                "int main(){return LZ4_compressBound(1) > 0 ? 0 : 1;}"),
        "lz4alt": (["-llz4"],
                   "extern \"C\" int LZ4_compressBound(int);\n"
                   "int main(){return LZ4_compressBound(1) > 0 ? 0 : 1;}"),
    }
    import tempfile

    def _probe(flags, src_text) -> bool:
        with tempfile.TemporaryDirectory() as td:
            src = os.path.join(td, "probe.cpp")
            with open(src, "w") as f:
                f.write(src_text)
            try:
                subprocess.run(
                    ["g++", "-o", os.path.join(td, "probe"), src] + flags,
                    check=True, capture_output=True, timeout=60)
                return True
            except Exception:  # noqa: BLE001 — feature probe
                return False

    extra = []
    for name, define in (("zlib", "PINOT_NO_ZLIB"),
                         ("zstd", "PINOT_NO_ZSTD")):
        flags, src_text = probes[name]
        if _probe(flags, src_text):
            extra += flags
        else:
            extra.append(f"-D{define}")
    if _probe(*probes["lz4"]):
        extra.append("-l:liblz4.so.1")
    elif _probe(*probes["lz4alt"]):
        extra.append("-llz4")
    else:
        extra.append("-DPINOT_NO_LZ4")
    try:
        subprocess.run(base + extra, check=True, capture_output=True,
                       timeout=120)
        os.replace(tmp, lib_path)
        return True
    except Exception as e:  # noqa: BLE001 — numpy/python fallbacks serve
        log.warning("native packer build failed (%s) with %s", e, extra)
        try:
            os.unlink(tmp)
        except OSError:
            pass
    return False


_FORCE_NUMPY_ENV = "PINOT_TPU_NO_NATIVE"


def _load():
    """ctypes handle on the packer library, or None (numpy fallback).

    Every failure mode — no toolchain, a failed compile, a corrupt or
    unloadable library file — degrades to the pure-numpy codec
    (`_pack_np`/`_unpack_np`, same byte format), so ``<col>.fwdpacked.bin``
    segments stay readable on any host. ``PINOT_TPU_NO_NATIVE=1`` forces
    the numpy path outright (checked per call, ahead of the cached
    handle, so tests and constrained deployments can flip it without
    reloading the module)."""
    global _lib, _lib_tried
    if os.environ.get(_FORCE_NUMPY_ENV, "") not in ("", "0"):
        return None
    with _lock:
        if _lib_tried:
            return _lib
        _lib_tried = True
        try:
            lib_path = _lib_path()
            if not os.path.exists(lib_path) and not _compile(lib_path):
                return None
            lib = ctypes.CDLL(lib_path)
            lib.pack_bits.argtypes = [
                ctypes.POINTER(ctypes.c_int32), ctypes.c_int64,
                ctypes.c_int, ctypes.POINTER(ctypes.c_uint8),
            ]
            lib.unpack_bits.argtypes = [
                ctypes.POINTER(ctypes.c_uint8), ctypes.c_int64,
                ctypes.c_int, ctypes.POINTER(ctypes.c_int32),
            ]
            if hasattr(lib, "inflate_chunks"):  # absent under PINOT_NO_ZLIB
                lib.inflate_chunks.argtypes = [
                    ctypes.POINTER(ctypes.c_uint8),
                    ctypes.POINTER(ctypes.c_int64),
                    ctypes.c_int64, ctypes.POINTER(ctypes.c_uint8),
                    ctypes.POINTER(ctypes.c_int64),
                ]
                lib.inflate_chunks.restype = ctypes.c_int
            _chunk_args = [
                ctypes.POINTER(ctypes.c_uint8),
                ctypes.POINTER(ctypes.c_int64),
                ctypes.c_int64, ctypes.POINTER(ctypes.c_uint8),
                ctypes.POINTER(ctypes.c_int64),
            ]
            for fn in ("zstd_decompress_chunks", "lz4_decompress_chunks"):
                if hasattr(lib, fn):  # absent under PINOT_NO_ZSTD/_LZ4
                    getattr(lib, fn).argtypes = _chunk_args
                    getattr(lib, fn).restype = ctypes.c_int
            if hasattr(lib, "zstd_compress_chunk"):
                lib.zstd_compress_chunk.argtypes = [
                    ctypes.POINTER(ctypes.c_uint8), ctypes.c_int64,
                    ctypes.POINTER(ctypes.c_uint8), ctypes.c_int64,
                    ctypes.c_int,
                ]
                lib.zstd_compress_chunk.restype = ctypes.c_int64
                lib.zstd_bound.argtypes = [ctypes.c_int64]
                lib.zstd_bound.restype = ctypes.c_int64
            if hasattr(lib, "lz4_compress_chunk"):
                lib.lz4_compress_chunk.argtypes = [
                    ctypes.POINTER(ctypes.c_uint8), ctypes.c_int64,
                    ctypes.POINTER(ctypes.c_uint8), ctypes.c_int64,
                ]
                lib.lz4_compress_chunk.restype = ctypes.c_int64
                lib.lz4_bound.argtypes = [ctypes.c_int64]
                lib.lz4_bound.restype = ctypes.c_int64
            _lib = lib
        except Exception as e:  # noqa: BLE001
            log.warning("native packer load failed (%s); numpy fallback", e)
            _lib = None
        return _lib


def native_available() -> bool:
    return _load() is not None


def bits_needed(cardinality: int) -> int:
    """Bits per dict id (>=1), PinotDataBitSet.getNumBitsPerValue analog."""
    if cardinality <= 1:
        return 1
    return int(cardinality - 1).bit_length()


def packed_size(n: int, bits: int) -> int:
    return (n * bits + 7) // 8


def pack(ids: np.ndarray, bits: int) -> np.ndarray:
    """int32 dict ids -> packed uint8 buffer (little-endian bit order)."""
    ids = np.ascontiguousarray(ids, dtype=np.int32)
    n = len(ids)
    out = np.zeros(packed_size(n, bits), dtype=np.uint8)
    if n == 0:
        return out
    lib = _load()
    if lib is not None:
        lib.pack_bits(
            ids.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            ctypes.c_int64(n), ctypes.c_int(bits),
            out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        )
        return out
    return _pack_np(ids, bits, out)


def unpack(buf: np.ndarray, n: int, bits: int) -> np.ndarray:
    """Packed uint8 buffer -> int32 dict ids."""
    buf = np.ascontiguousarray(buf, dtype=np.uint8)
    out = np.empty(n, dtype=np.int32)
    if n == 0:
        return out
    lib = _load()
    if lib is not None:
        lib.unpack_bits(
            buf.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
            ctypes.c_int64(n), ctypes.c_int(bits),
            out.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        )
        return out
    return _unpack_np(buf, n, bits)


# ---------------------------------------------------------------------------
# Chunked compression for raw forward indexes (io/compression analog: the
# reference's per-chunk compressors behind Fixed/VarByteChunkSVForwardIndex,
# ChunkCompressionType = PASS_THROUGH | SNAPPY | ZSTANDARD | LZ4; here
# zlib | zstd | lz4, selectable per column via IndexingConfig). Each codec
# has a native C++ loop and a pure-python fallback reading the same bytes.
# ---------------------------------------------------------------------------

CHUNK_BYTES = 1 << 18  # 256 KiB uncompressed per chunk

CHUNK_CODECS = ("zlib", "zstd", "lz4")


def _lz4_compress_py(src: bytes) -> bytes:
    """Literal-only LZ4 block (valid format, no matches) — the build-path
    fallback when the native library is absent: round-trips correctly at
    roughly pass-through size."""
    out = bytearray()
    L = len(src)
    token_lit = min(L, 15)
    out.append(token_lit << 4)
    if token_lit == 15:
        rem = L - 15
        while rem >= 255:
            out.append(255)
            rem -= 255
        out.append(rem)
    out += src
    return bytes(out)


def _lz4_decompress_py(src: bytes, expected: int) -> bytes:
    """Pure-python LZ4 block decoder (load-path fallback)."""
    out = bytearray()
    i, n = 0, len(src)
    while i < n:
        token = src[i]
        i += 1
        lit = token >> 4
        if lit == 15:
            while True:
                b = src[i]
                i += 1
                lit += b
                if b != 255:
                    break
        out += src[i: i + lit]
        i += lit
        if i >= n:
            break  # last sequence carries no match
        off = src[i] | (src[i + 1] << 8)
        i += 2
        ml = token & 15
        if ml == 15:
            while True:
                b = src[i]
                i += 1
                ml += b
                if b != 255:
                    break
        ml += 4
        start = len(out) - off
        if start < 0:
            raise ValueError("corrupt LZ4 block (offset before start)")
        for _ in range(ml):  # byte-wise: matches may overlap themselves
            out.append(out[start])
            start += 1
    if len(out) != expected:
        raise ValueError(
            f"corrupt LZ4 block ({len(out)} bytes, expected {expected})")
    return bytes(out)


def _compress_chunk(raw: bytes, codec: str, lib) -> bytes:
    if codec == "zlib":
        import zlib

        return zlib.compress(raw, 6)
    if codec == "zstd":
        try:
            import zstandard

            return zstandard.ZstdCompressor(level=3).compress(raw)
        except ImportError:
            pass
        if lib is not None and hasattr(lib, "zstd_compress_chunk"):
            cap = int(lib.zstd_bound(len(raw)))
            dst = np.empty(max(cap, 64), dtype=np.uint8)
            src = np.frombuffer(raw, dtype=np.uint8)
            n = lib.zstd_compress_chunk(
                src.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
                ctypes.c_int64(len(raw)),
                dst.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
                ctypes.c_int64(len(dst)), ctypes.c_int(3))
            if n < 0:
                raise ValueError("zstd compression failed")
            return dst[:n].tobytes()
        raise RuntimeError(
            "zstd codec needs the zstandard package or the native library")
    if codec == "lz4":
        if lib is not None and hasattr(lib, "lz4_compress_chunk"):
            cap = int(lib.lz4_bound(len(raw))) if len(raw) else 64
            dst = np.empty(max(cap, 64), dtype=np.uint8)
            src = np.frombuffer(raw, dtype=np.uint8) if raw else \
                np.empty(0, dtype=np.uint8)
            n = lib.lz4_compress_chunk(
                src.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
                ctypes.c_int64(len(raw)),
                dst.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
                ctypes.c_int64(len(dst)))
            if n > 0:
                return dst[:n].tobytes()
        return _lz4_compress_py(raw)
    raise ValueError(f"unknown chunk codec {codec!r} (use {CHUNK_CODECS})")


def compress_chunks(data: np.ndarray,
                    codec: str = "zlib") -> tuple[np.ndarray, np.ndarray]:
    """Raw little-endian bytes -> (concatenated compressed chunks,
    offsets[n_chunks+1]). Build path (cold)."""
    lib = _load()
    data = np.ascontiguousarray(data).view(np.uint8).reshape(-1)
    raw = data.tobytes()
    pieces = [raw[i: i + CHUNK_BYTES]
              for i in range(0, len(raw), CHUNK_BYTES)] or [b""]
    chunks = [_compress_chunk(p, codec, lib) for p in pieces]
    offsets = np.zeros(len(chunks) + 1, dtype=np.int64)
    np.cumsum([len(c) for c in chunks], out=offsets[1:])
    return np.frombuffer(b"".join(chunks), dtype=np.uint8), offsets


_NATIVE_DECOMPRESS = {
    "zlib": "inflate_chunks",
    "zstd": "zstd_decompress_chunks",
    "lz4": "lz4_decompress_chunks",
}


def _decompress_chunk_py(buf: bytes, codec: str, expected: int) -> bytes:
    if codec == "zlib":
        import zlib

        return zlib.decompress(buf)
    if codec == "zstd":
        try:
            import zstandard
        except ImportError as e:
            raise RuntimeError(
                "loading a zstd-compressed segment needs the zstandard "
                "package or the native library") from e
        return zstandard.ZstdDecompressor().decompress(
            buf, max_output_size=max(expected, 1))
    if codec == "lz4":
        return _lz4_decompress_py(buf, expected)
    raise ValueError(f"unknown chunk codec {codec!r} (use {CHUNK_CODECS})")


def decompress_chunks(blob: np.ndarray, offsets: np.ndarray,
                      total_bytes: int, codec: str = "zlib") -> np.ndarray:
    """(compressed chunks, offsets) -> uncompressed uint8 array of
    total_bytes. Load path: native per-chunk loop, python fallback."""
    blob = np.ascontiguousarray(blob, dtype=np.uint8)
    offsets = np.ascontiguousarray(offsets, dtype=np.int64)
    n_chunks = len(offsets) - 1
    out = np.empty(total_bytes, dtype=np.uint8)
    if total_bytes == 0:
        return out
    dst_off = np.minimum(
        np.arange(n_chunks + 1, dtype=np.int64) * CHUNK_BYTES, total_bytes)
    lib = _load()
    fn_name = _NATIVE_DECOMPRESS.get(codec)
    if fn_name is None:
        raise ValueError(f"unknown chunk codec {codec!r} (use {CHUNK_CODECS})")
    if lib is not None and hasattr(lib, fn_name):
        rc = getattr(lib, fn_name)(
            blob.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
            offsets.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
            ctypes.c_int64(n_chunks),
            out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
            dst_off.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        )
        if rc != 0:
            raise ValueError(
                f"corrupt compressed forward index ({codec} rc={rc})")
        return out
    buf = blob.tobytes()
    pos = 0
    for c in range(n_chunks):
        expected = int(dst_off[c + 1] - dst_off[c])
        chunk = _decompress_chunk_py(
            buf[offsets[c]: offsets[c + 1]], codec, expected)
        out[pos: pos + len(chunk)] = np.frombuffer(chunk, dtype=np.uint8)
        pos += len(chunk)
    if pos != total_bytes:
        raise ValueError(f"corrupt compressed forward index "
                         f"({pos} bytes, expected {total_bytes})")
    return out


# ---------------------------------------------------------------------------
# numpy fallback (same byte format, vectorized via a per-value bit matrix)
# ---------------------------------------------------------------------------


def _pack_np(ids: np.ndarray, bits: int, out: np.ndarray) -> np.ndarray:
    n = len(ids)
    # (n, bits) value bits, little-endian per value, flattened to the
    # global little-endian bitstream then repacked 8 at a time
    shifts = np.arange(bits, dtype=np.uint32)
    bitmat = ((ids.astype(np.uint32)[:, None] >> shifts) & 1).astype(np.uint8)
    stream = bitmat.reshape(-1)
    pad = (-len(stream)) % 8
    if pad:
        stream = np.concatenate([stream, np.zeros(pad, dtype=np.uint8)])
    out[:] = np.packbits(stream.reshape(-1, 8), axis=1, bitorder="little").reshape(-1)
    return out


def _unpack_np(buf: np.ndarray, n: int, bits: int) -> np.ndarray:
    stream = np.unpackbits(buf, bitorder="little")[: n * bits]
    bitmat = stream.reshape(n, bits).astype(np.uint32)
    shifts = np.arange(bits, dtype=np.uint32)
    return (bitmat << shifts).sum(axis=1).astype(np.int32)
