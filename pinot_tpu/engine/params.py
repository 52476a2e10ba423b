"""Device batch context: segment batch + parameter resolution.

This is the host-side half of the device query pipeline — the analog of the
reference's per-segment plan construction (predicate → dict-id resolution in
operator/filter/predicate/ PredicateEvaluator factories) re-shaped for
batched TPU launches:

- **Global-id columns**: per-segment dictionaries are unioned per column and
  the forward index is remapped into global id space *on the host at upload
  time* (a one-off numpy gather, cached with the batch). Device kernels then
  never touch per-segment dictionaries: group-by keys are the column itself,
  cross-segment combine is a dense scatter, and predicate literals resolve to
  *batch-wide scalars* via one binary search on the global dictionary.
  (Measured on v5e: this removes a per-doc remap gather that cost ~100x the
  actual aggregation scatter.)
- **Predicate params**: literals become replicated scalar/vector params; the
  jitted pipeline is a pure function of these params, so one compiled
  template serves all literal values. Regex/LIKE evaluate once per global
  dictionary entry into a (C,) boolean LUT.

Raises ``DeviceUnsupported`` for anything the device path doesn't accelerate;
the engine falls back to the host executor.
"""

from __future__ import annotations

import dataclasses
import os
import re
import threading

import numpy as np

from pinot_tpu.engine.host import like_to_regex
from pinot_tpu.ops.hll import hash32_np
from pinot_tpu.ops.transform import get_function
from pinot_tpu.query.context import (
    Expression,
    FilterNode,
    FilterNodeType,
    Predicate,
    PredicateType,
)
from pinot_tpu.storage.device import padded_len
from pinot_tpu.storage.dictionary import Dictionary
from pinot_tpu.storage.segment import (
    ZONE_BLOCK_ROWS,
    Encoding,
    build_zone_map,
)

import jax.numpy as jnp


class DeviceUnsupported(Exception):
    """Query shape not handled by the device pipeline → host fallback."""


class KeySpaceFull(DeviceUnsupported):
    """A narrowed group-by launch found its key space full. The executor
    has remembered the template full on its batch; the launch's fetch
    launches it again in the full regime (engine/inflight.py), and only
    where it cannot does this reach the engine as a host fallback."""


_NUMERIC_KINDS = ("i", "u", "f")
# a SPILLED layout's operands (``gl::<cols>::<K>::<part>``) -> the field of
# ops/keysorted.py SpillLayout each holds
_SPILL_PARTS = {"rows": "col_rows", "chunks": "chunks", "fat": "fat"}

# ---------------------------------------------------------------------------
# cardinality-aware column width planning
# ---------------------------------------------------------------------------
# The reference never stores a forward index at full width
# (FixedBitSVForwardIndexReader reads ceil(log2(cardinality)) bits per dict
# id); the device path used to widen everything to int32/int64 before upload,
# making scans HBM-bandwidth-bound and the batch LRU evict batches that
# would fit 4-8x over at their true width. A ColPlan is the per-column
# device storage decision:
#
# - DICT id planes: uint8 (C <= 255), uint16 (C <= 65535), else int32 —
#   the pad sentinel is C itself on unsigned planes (ids are < C, so the
#   pad matches no literal) and -1 on signed ones (legacy). An OPT-IN
#   sub-byte tier (PINOT_TPU_SUBBYTE=1) packs 2-bit (C <= 3) / 4-bit
#   (C <= 15) ids into uint8 bytes, unpacked in-kernel with shifts/masks
#   (ops/masks.py unpack_subbyte).
# - RAW / decoded (dv::) int planes: frame-of-reference (min-offset)
#   downcast — values store as (v - min) in the narrowest unsigned dtype
#   whose span covers (max - min), decoding to the legacy wide dtype at
#   REGISTER level only (``wide`` + the per-batch "fo::<key>" offset
#   param). When values already fit the narrow dtype unsigned, the offset
#   is skipped entirely; int64 planes whose values fit int32 drop to a
#   plain int32.
# - Floats stay f32 (the pre-existing device value space).
#
# Zone-map (zlo::/zhi::) planes narrow WITH their column (stored in the
# same space the plane stores — id space or FOR space); ops/blockskip.py
# decodes them the same way the kernels decode the column.
#
# PINOT_TPU_FORCE_WIDE=1 restores the legacy widths end to end (the
# differential-parity reference form). Env knobs are read ONCE per
# BatchContext so a cached batch's plans never shift mid-life.


@dataclasses.dataclass(frozen=True)
class ColPlan:
    """Device storage plan for one column plane."""

    dtype: str          # numpy dtype .str of the STORED plane
    bits: int = 0       # sub-byte pack width (2 | 4); 0 = byte-aligned
    offset: int | None = None  # frame-of-reference offset (raw value space)
    wide: str = ""      # register decode target dtype ("" = none needed)

    @property
    def packed(self) -> bool:
        return self.bits > 0

    def sig(self) -> tuple:
        """Hashable template-key form (offset VALUE excluded — it is a
        runtime param, one compiled pipeline serves any offset)."""
        return (self.dtype, self.bits, self.offset is not None, self.wide)


def _env_flag(name: str) -> bool:
    return os.environ.get(name, "") not in ("", "0")


def _int_for_plan(lo: int, hi: int, base: np.dtype) -> ColPlan:
    """Frame-of-reference plan for an integer plane with exact (python
    int) bounds: narrowest unsigned dtype covering the RANGE, offset only
    when the values don't already fit unsigned, int32 fallback for int64
    planes whose values fit natively. Bounds arithmetic runs in python
    ints, so dtype-extreme columns (min near -2^63) can't overflow here."""
    rng = hi - lo
    for dt, span in ((np.uint8, 1 << 8), (np.uint16, 1 << 16)):
        ndt = np.dtype(dt)
        if ndt.itemsize >= base.itemsize:
            break  # no byte-width win at/past the base dtype
        if 0 <= lo and hi < span:
            return ColPlan(ndt.str, wide=base.str)
        if rng < span:
            return ColPlan(ndt.str, offset=int(lo), wide=base.str)
    if base.itemsize > 4:
        # same 4 bytes either way: prefer the offset-free native int32
        if -(1 << 31) <= lo and hi < (1 << 31):
            return ColPlan(np.dtype(np.int32).str, wide=base.str)
        if 0 <= lo and hi < (1 << 32):
            return ColPlan(np.dtype(np.uint32).str, wide=base.str)
        if rng < (1 << 32):
            return ColPlan(np.dtype(np.uint32).str, offset=int(lo),
                           wide=base.str)
    return ColPlan(base.str)


class BatchContext:
    """Host+device state for one batch of segments (cached per segment set)."""

    MAX_MV_K = 16  # (S, L, K) id blocks cost K x an SV column of HBM

    def __init__(self, segments: list, pad_multiple: int = 1024, mesh=None):
        """``mesh``: the executor's segment-axis Mesh, if it has one — the
        (S, ...) blocks are then placed sharded over it at upload, each
        device holding only its own segments. Placed whole on the default
        device they would put the entire batch on the first chip and have
        every launch re-shard it."""
        self.segments = list(segments)
        # a segment count the mesh does not divide is padded per launch by
        # parallel/mesh.py pad_to_multiple; those batches stay on the
        # default device as before
        self._mesh = mesh if mesh is not None \
            and len(self.segments) % mesh.devices.size == 0 else None
        # pad to a whole number of zone-map blocks so the block-skip path
        # (ops/blockskip.py) can reshape (S, L) -> (S * n_blocks, R) without
        # a second padding pass; worst case +3072 pad rows per segment
        pad_multiple = max(pad_multiple, ZONE_BLOCK_ROWS)
        self.pad_to = max(padded_len(s.n_docs, pad_multiple) for s in self.segments)
        self.S = len(self.segments)
        self.n_docs = np.array([s.n_docs for s in self.segments], dtype=np.int32)
        self.n_docs_dev = self._put(self.n_docs)
        self._columns: dict[str, object] = {}       # name -> (S, L) device array
        self._encodings: dict[str, str] = {}
        self._global_dicts: dict[str, Dictionary] = {}
        self._decoded: dict[str, object] = {}       # name -> (S, L) decoded values
        self._prehashed: dict[str, object] = {}     # name -> (S, L) value hashes
        self._mv_columns: dict[str, object] = {}    # name -> (S, L, K) id blocks
        self._sorted_hll: dict = {}   # (group_cols, hash_col, log2m) -> sorted keys
        # the dense group-by's statement-invariant kernel operands
        # (ops/groupby_mm.py "prepared operands"), by cols key: "gk::<col>"
        # lane-major key ids, "gv::<value>::<off>::<nplanes>" uint8 byte
        # planes of value - off. Built on the device at first use; they
        # live and die with this batch
        self._gb_operands: dict = {}
        self._gb_operand_bytes = 0
        # the full regime's key orders: "<cols>" -> rows of the fullest
        # cell, and rows a cell (host numpy); what the layout's choice
        # made of them: (cols, bounds) -> K, (cols, K) -> SPILLED layout
        self._key_order_rows: dict = {}
        self._key_cell_rows: dict = {}
        self._key_layout_rows: dict = {}
        self._spill_layouts: dict = {}
        # col key -> ((S, NB) lo, (S, NB) hi) device zone maps in the
        # column's device value space (global ids / decoded / raw); built
        # eagerly alongside the column block (the host data is in hand
        # there — rebuilding later would repeat the remap gather)
        self._zone_maps: dict = {}
        # concurrent queries share one cached BatchContext (the executor's
        # batch LRU): lazy materialization is locked so two threads never
        # build the same block twice. RLock: sorted_hll_keys re-enters
        # column. Resident bytes ride a LOCK-FREE counter updated at
        # block-insert time — the executor's _evict reads it from OTHER
        # queries' batches, and taking this lock there would stall
        # unrelated launches behind a cold multi-GB column build.
        self._lock = threading.RLock()
        self._resident_bytes = 0
        # width planning (ColPlan) — env knobs sampled ONCE so a cached
        # batch's plans (and the executor's width-keyed templates) never
        # shift mid-life; bytes the narrowing saved vs the legacy wide
        # layout accumulate lock-free like _resident_bytes
        self._force_wide = _env_flag("PINOT_TPU_FORCE_WIDE")
        self._subbyte = _env_flag("PINOT_TPU_SUBBYTE")
        self._plans: dict[str, ColPlan] = {}
        self._narrow_saved_bytes = 0

    def _put(self, blocks):
        """Host (S, ...) block -> device array, sharded over the segment
        axis when the batch has a mesh."""
        if self._mesh is None:
            return jnp.asarray(blocks)
        import jax
        from jax.sharding import NamedSharding, PartitionSpec

        from pinot_tpu.parallel.mesh import SEG_AXIS

        return jax.device_put(blocks, NamedSharding(
            self._mesh, PartitionSpec(SEG_AXIS, *[None] * (blocks.ndim - 1))))

    # ---- column access ---------------------------------------------------
    def column_meta(self, name: str):
        for s in self.segments:
            if name in s.metadata.columns:
                return s.column_metadata(name)
        raise DeviceUnsupported(f"unknown column {name}")

    def encoding(self, name: str) -> str:
        with self._lock:
            return self._encoding_locked(name)

    def _encoding_locked(self, name: str) -> str:
        if name not in self._encodings:
            metas = []
            for s in self.segments:
                if name not in s.metadata.columns:
                    raise DeviceUnsupported(f"column {name} missing from {s.name}")
                metas.append(s.column_metadata(name))
            enc = metas[0].encoding
            if any(m.encoding != enc for m in metas):
                raise DeviceUnsupported(f"mixed encodings for {name}")
            if any(not m.single_value for m in metas):
                raise DeviceUnsupported(f"multi-value column {name}")
            self._encodings[name] = enc
        return self._encodings[name]

    def is_mv(self, name: str) -> bool:
        for s in self.segments:
            if name not in s.metadata.columns:
                raise DeviceUnsupported(f"column {name} missing from {s.name}")
            if s.column_metadata(name).single_value:
                return False
        return True

    def mv_column(self, name: str):
        """(S, L, K) device array of GLOBAL dict ids for an MV column,
        entries padded with -1 (K = batch max entries per doc). The device
        form of getDictIdMV (ForwardIndexReader.java:99) — predicates
        evaluate per entry and reduce match-any over K."""
        with self._lock:
            return self._mv_column_locked(name)

    def _mv_column_locked(self, name: str):
        if name not in self._mv_columns:
            metas = [s.column_metadata(name) for s in self.segments]
            if any(m.encoding != Encoding.DICT for m in metas):
                raise DeviceUnsupported(f"raw MV column {name} on device")
            K = max(m.max_mv_entries for m in metas)
            if K == 0 or K > self.MAX_MV_K:
                raise DeviceUnsupported(
                    f"MV column {name} has up to {K} entries/doc (cap {self.MAX_MV_K})"
                )
            gdict = self.global_dict(name)
            blocks = np.full((self.S, self.pad_to, K), -1, dtype=np.int32)
            for i, s in enumerate(self.segments):
                d = s.dictionary(name)
                remap = np.searchsorted(
                    gdict.values, np.asarray(d.values)
                ).astype(np.int32)
                fwd = np.asarray(s.forward(name))
                off = np.asarray(s.mv_offsets(name))
                lens = np.diff(off)
                doc_of_entry = np.repeat(
                    np.arange(len(lens), dtype=np.int64), lens
                )
                rank = np.arange(len(fwd), dtype=np.int64) - np.repeat(off[:-1], lens)
                blocks[i, doc_of_entry, rank] = remap[fwd]
            self._mv_columns[name] = self._put(blocks)
            self._note_resident(self._mv_columns[name])
        return self._mv_columns[name]

    # ---- width planning (ColPlan) ---------------------------------------
    def width_plan(self, key: str) -> ColPlan:
        """Device storage plan for a cols-dict key (bare column name or
        "dv::name"); the executor folds these into its template cache key
        so cohort coalescing keeps stacking same-shape queries."""
        with self._lock:
            return self._width_plan_locked(key)

    def _width_plan_locked(self, key: str) -> ColPlan:
        plan = self._plans.get(key)
        if plan is None:
            if key.startswith("dv::"):
                plan = self._plan_decoded(key[4:])
            elif self._encoding_locked(key) == Encoding.DICT:
                plan = self._plan_dict(key)
            else:
                plan = self._plan_raw(key)
            self._plans[key] = plan
        return plan

    def _plan_dict(self, name: str) -> ColPlan:
        if self._force_wide:
            return ColPlan(np.dtype(np.int32).str)
        C = len(self._global_dict_locked(name))
        # sub-byte tiers reserve the pad sentinel C inside the bit width
        if self._subbyte and C <= 3:
            return ColPlan(np.dtype(np.uint8).str, bits=2)
        if self._subbyte and C <= 15:
            return ColPlan(np.dtype(np.uint8).str, bits=4)
        if C <= 255:  # ids 0..C-1, pad C: C == 255 still fits uint8
            return ColPlan(np.dtype(np.uint8).str)
        if C <= 65535:
            return ColPlan(np.dtype(np.uint16).str)
        return ColPlan(np.dtype(np.int32).str)

    def _plan_raw(self, name: str) -> ColPlan:
        from pinot_tpu.storage.device import _RAW_DEVICE_DTYPES

        base = np.dtype(_RAW_DEVICE_DTYPES[self.column_meta(name).data_type])
        if self._force_wide or base.kind == "f":
            return ColPlan(base.str)
        b = self._exact_int_bounds(name)
        if b is None:
            return ColPlan(base.str)
        return _int_for_plan(b[0], b[1], base)

    def _plan_decoded(self, name: str) -> ColPlan:
        if self._encoding_locked(name) != Encoding.DICT:
            return self._width_plan_locked(name)  # dv:: of RAW aliases raw
        per_seg = [np.asarray(s.dictionary(name).values)
                   for s in self.segments]
        if any(v.dtype.kind == "f" for v in per_seg):
            return ColPlan(np.dtype(np.float32).str)
        base = np.dtype(np.int64) if any(v.dtype.itemsize == 8
                                         for v in per_seg) \
            else np.dtype(np.int32)
        if self._force_wide or not any(len(v) for v in per_seg):
            return ColPlan(base.str)
        # dictionaries are sorted: batch bounds are the edge values
        lo = min(int(v[0]) for v in per_seg if len(v))
        hi = max(int(v[-1]) for v in per_seg if len(v))
        return _int_for_plan(lo, hi, base)

    def _exact_int_bounds(self, name: str):
        """(min, max) as exact python ints from segment metadata, or None
        (missing stats / non-integer values) — int_bounds() stays float
        for the two-stage-sum interval arithmetic; FOR offsets need
        exactness at dtype extremes."""
        mns, mxs = [], []
        for s in self.segments:
            m = s.column_metadata(name)
            if not isinstance(m.min_value, (int, np.integer)) \
                    or not isinstance(m.max_value, (int, np.integer)):
                return None
            mns.append(int(m.min_value))
            mxs.append(int(m.max_value))
        return (min(mns), max(mxs)) if mns else None

    def _dict_pad(self, name: str, plan: ColPlan) -> int:
        """Pad sentinel for an id plane: C on unsigned planes (< any real
        id's successor, matches no literal, fits by the tier rule), -1 on
        signed (legacy)."""
        if np.dtype(plan.dtype).kind == "u":
            return len(self._global_dict_locked(name))
        return -1

    @staticmethod
    def _pack_subbyte_np(blocks: np.ndarray, bits: int) -> np.ndarray:
        """(S, L) small ids → (S, L * bits // 8) uint8, little-endian
        within each byte (the host-side inverse of ops/masks.py
        unpack_subbyte)."""
        f = 8 // bits
        v = blocks.reshape(blocks.shape[0], -1, f).astype(np.uint16)
        shifts = np.arange(f, dtype=np.uint16) * bits
        return (v << shifts).sum(axis=-1, dtype=np.uint16).astype(np.uint8)

    def _note_saved(self, wide_nbytes: int, *arrays) -> None:
        """Caller holds self._lock: record bytes the width plan saved vs
        the legacy wide layout of the same logical plane(s)."""
        actual = sum(int(getattr(a, "nbytes", 0)) for a in arrays)
        if wide_nbytes > actual:
            self._narrow_saved_bytes += wide_nbytes - actual

    def narrow_saved_bytes(self) -> int:
        """HBM bytes saved by width planning vs the r05 wide layout
        (lock-free read, like device_bytes)."""
        return self._narrow_saved_bytes

    def column(self, name: str):
        """(S, L) device array at the column's PLANNED width: **global**
        dict ids (DICT — pad -1 signed / C unsigned; sub-byte plans pack
        8//bits ids per byte into an (S, L * bits // 8) plane) or raw
        values (RAW — frame-of-reference storage when the plan carries an
        offset, pad 0)."""
        with self._lock:
            return self._column_locked(name)

    def _column_locked(self, name: str):
        if name not in self._columns:
            enc = self.encoding(name)
            plan = self._width_plan_locked(name)
            sdt = np.dtype(plan.dtype)
            if enc == Encoding.DICT:
                gdict = self.global_dict(name)
                pad = self._dict_pad(name, plan)
                blocks = np.full((self.S, self.pad_to), pad, dtype=sdt)
                zlo, zhi = self._zone_fills(sdt)
                for i, s in enumerate(self.segments):
                    d = s.dictionary(name)
                    remap = np.searchsorted(
                        gdict.values, np.asarray(d.values)
                    ).astype(np.int32)
                    fwd = np.asarray(s.forward(name))
                    gids = remap[fwd]
                    blocks[i, : len(fwd)] = gids  # ids < C: fits the plan
                    zm = self._reader_zone_map(s, name, len(fwd))
                    # local->global id remap is monotone (both dictionaries
                    # are sorted), so per-block min/max ids survive it
                    z = remap[np.asarray(zm)] if zm is not None \
                        else build_zone_map(gids)
                    zlo[i, : z.shape[1]] = z[0]
                    zhi[i, : z.shape[1]] = z[1]
                if plan.packed:
                    blocks = self._pack_subbyte_np(blocks, plan.bits)
            else:
                off = plan.offset or 0
                blocks = np.zeros((self.S, self.pad_to), dtype=sdt)
                zlo, zhi = self._zone_fills(sdt)
                for i, s in enumerate(self.segments):
                    fwd = np.asarray(s.forward(name))
                    if off:
                        # FOR storage: python-int-exact metadata bounds
                        # guarantee (v - off) fits the plan dtype; the
                        # int64 intermediate never overflows (|off| and v
                        # both fit int64 and their difference fits uint32)
                        vals = (fwd.astype(np.int64) - off).astype(sdt)
                    else:
                        # astype matches the device narrowing (float
                        # round-to-nearest is monotone, so narrowed
                        # bounds still bound the narrowed values)
                        vals = fwd.astype(sdt)
                    blocks[i, : len(fwd)] = vals
                    zm = self._reader_zone_map(s, name, s.n_docs)
                    if zm is not None:
                        zm = np.asarray(zm)
                        z = ((zm.astype(np.int64) - off).astype(sdt)
                             if off else zm.astype(sdt))
                    else:
                        z = build_zone_map(blocks[i, : s.n_docs])
                    zlo[i, : z.shape[1]] = z[0]
                    zhi[i, : z.shape[1]] = z[1]
            self._columns[name] = self._put(blocks)
            self._note_resident(self._columns[name])
            self._store_zone_map(name, zlo, zhi)
            # legacy wide layout: int32 id plane / base-dtype raw plane,
            # plus two int32/base zone planes
            wide_item = 4 if enc == Encoding.DICT else \
                np.dtype(self._legacy_raw_dtype(name)).itemsize
            nb = self.pad_to // ZONE_BLOCK_ROWS
            self._note_saved(
                wide_item * self.S * (self.pad_to + 2 * nb),
                self._columns[name], *self._zone_maps[name])
        return self._columns[name]

    def _legacy_raw_dtype(self, name: str):
        from pinot_tpu.storage.device import _RAW_DEVICE_DTYPES

        return _RAW_DEVICE_DTYPES[self.column_meta(name).data_type]

    # ---- zone maps (device block-skip basis, ops/blockskip.py) ----------
    def _zone_fills(self, dtype):
        """(S, NB) lo/hi arrays pre-filled with never-match sentinels (lo =
        dtype max, hi = dtype min) so padding blocks past a segment's data
        satisfy no interval predicate."""
        nb = self.pad_to // ZONE_BLOCK_ROWS
        dtype = np.dtype(dtype)
        if dtype.kind in ("i", "u"):
            lof, hif = np.iinfo(dtype).max, np.iinfo(dtype).min
        else:
            lof, hif = np.finfo(dtype).max, np.finfo(dtype).min
        return (np.full((self.S, nb), lof, dtype=dtype),
                np.full((self.S, nb), hif, dtype=dtype))

    @staticmethod
    def _reader_zone_map(seg, name: str, n: int):
        """Segment-provided (2, n_blocks) zone map (sealed: <col>.zmap.npy;
        chunklets: computed at promotion), or None -> recompute from the
        column block (pre-zone-map segments)."""
        fn = getattr(seg, "zone_map", None)
        if fn is None:
            return None
        try:
            zm = fn(name)
        except Exception:  # noqa: BLE001 — corrupt file: recompute instead
            return None
        if zm is None:
            return None
        zm = np.asarray(zm)
        if zm.shape != (2, -(-n // ZONE_BLOCK_ROWS)):
            return None  # stale granularity: recompute
        return zm

    def _store_zone_map(self, key: str, zlo, zhi) -> None:
        self._zone_maps[key] = (self._put(zlo), self._put(zhi))
        for a in self._zone_maps[key]:
            self._note_resident(a)

    def zone_map(self, key: str):
        """((S, NB) lo, (S, NB) hi) device zone arrays for a cols-dict key
        (bare name -> global dict ids or raw values; "dv::name" -> decoded
        values), materializing the backing column on first use."""
        with self._lock:
            if key not in self._zone_maps:
                if key.startswith("dv::"):
                    self._decoded_column_locked(key[4:])
                else:
                    self._column_locked(key)
            return self._zone_maps[key]

    def global_dict(self, name: str) -> Dictionary:
        """Sorted union of per-segment dictionary values (global id space)."""
        with self._lock:
            return self._global_dict_locked(name)

    def _global_dict_locked(self, name: str) -> Dictionary:
        if name not in self._global_dicts:
            vals = []
            for s in self.segments:
                d = s.dictionary(name)
                if d is None:
                    raise DeviceUnsupported(f"column {name} lacks a dictionary")
                vals.append(np.asarray(d.values))
            self._global_dicts[name] = Dictionary(np.unique(np.concatenate(vals)))
        return self._global_dicts[name]

    def cardinality(self, name: str) -> int:
        return len(self.global_dict(name))

    def decoded_column(self, name: str):
        """(S, L) device array of DECODED numeric values for a dict column —
        the per-doc LUT gather runs on the host at upload (numpy fancy
        index, one-off, cached); device kernels never gather. Measured on
        v5e a (C,)-LUT gather over 12M docs costs ~80ms per query — this
        removes it entirely. Floats decode to f32 (the device value space,
        as the old value-LUT path did); ints keep the WIDEST dtype across
        segments."""
        with self._lock:
            return self._decoded_column_locked(name)

    def _decoded_column_locked(self, name: str):
        if name not in self._decoded:
            if self.encoding(name) != Encoding.DICT:
                return self.column(name)
            per_seg = []
            for s in self.segments:
                vals = np.asarray(s.dictionary(name).values)
                if vals.dtype.kind not in _NUMERIC_KINDS:
                    raise DeviceUnsupported(f"non-numeric dict column {name} in expression")
                per_seg.append(vals)
            plan = self._width_plan_locked("dv::" + name)
            sdt = np.dtype(plan.dtype)
            off = plan.offset or 0
            # legacy wide layout = the plan's decode target (un-narrowed
            # plans store the legacy dtype already)
            wide_item = np.dtype(plan.wide).itemsize if plan.wide \
                else sdt.itemsize
            blocks = np.zeros((self.S, self.pad_to), dtype=sdt)
            zlo, zhi = self._zone_fills(sdt)
            for i, (s, vals) in enumerate(zip(self.segments, per_seg)):
                fwd = np.asarray(s.forward(name))
                # FOR narrowing happens on the (C,)-sized LUT, not the
                # rows: one subtract per distinct value, then the same
                # one-off host gather as before
                lut = (vals.astype(np.int64) - off).astype(sdt) if off \
                    else vals.astype(sdt)
                blocks[i, : len(fwd)] = lut[fwd]
                zm = self._reader_zone_map(s, name, len(fwd))
                # id zone -> value zone through the sorted dictionary (id
                # order == value order, so min/max ids decode to min/max
                # values)
                z = lut[np.asarray(zm)] if zm is not None \
                    else build_zone_map(blocks[i, : len(fwd)])
                zlo[i, : z.shape[1]] = z[0]
                zhi[i, : z.shape[1]] = z[1]
            self._decoded[name] = self._put(blocks)
            self._note_resident(self._decoded[name])
            self._store_zone_map("dv::" + name, zlo, zhi)
            nb = self.pad_to // ZONE_BLOCK_ROWS
            self._note_saved(
                wide_item * self.S * (self.pad_to + 2 * nb),
                self._decoded[name], *self._zone_maps["dv::" + name])
        return self._decoded[name]

    def prehashed_column(self, name: str):
        """(S, L) device array of per-doc canonical value hashes for
        DISTINCTCOUNTHLL — host-side LUT gather at upload replaces the
        device hash-LUT gather (~80ms/query on v5e at 12M docs)."""
        with self._lock:
            return self._prehashed_column_locked(name)

    def _prehashed_column_locked(self, name: str):
        if name not in self._prehashed:
            blocks = np.zeros((self.S, self.pad_to), dtype=np.uint32)
            for i, s in enumerate(self.segments):
                h = hash32_np(np.asarray(s.dictionary(name).values))
                fwd = np.asarray(s.forward(name))
                blocks[i, : len(fwd)] = h[fwd]
            self._prehashed[name] = self._put(blocks)
            self._note_resident(self._prehashed[name])
        return self._prehashed[name]

    def bytes_width(self, name: str) -> int:
        """Fixed byte width of a BYTES dict column's values (0 = not a
        fixed-width bytes column)."""
        widths = set()
        for s in self.segments:
            d = s.dictionary(name)
            if d is None:
                return 0
            dt = np.asarray(d.values).dtype
            if dt.kind != "S":
                return 0
            widths.add(dt.itemsize)
        return widths.pop() if len(widths) == 1 else 0

    def bytes_plane_column(self, name: str):
        """(S, L, W) device array of raw byte planes for a fixed-width
        BYTES dict column (HLLMERGE's pre-aggregated register planes) —
        per-doc LUT gather on the host at upload, like decoded_column."""
        with self._lock:
            return self._bytes_plane_locked(name)

    def _bytes_plane_locked(self, name: str):
        key = "bp::" + name
        if key not in self._decoded:
            W = self.bytes_width(name)
            if W == 0:
                raise DeviceUnsupported(
                    f"column {name} is not a fixed-width BYTES dict column")
            blocks = np.zeros((self.S, self.pad_to, W), dtype=np.uint8)
            for i, s in enumerate(self.segments):
                vals = np.asarray(s.dictionary(name).values)
                planes = vals.view(np.uint8).reshape(len(vals), W)
                fwd = np.asarray(s.forward(name))
                blocks[i, : len(fwd)] = planes[fwd]
            self._decoded[key] = self._put(blocks)
            self._note_resident(self._decoded[key])
        return self._decoded[key]

    # ---- dense group-by kernel operands (ops/groupby_mm.py) -------------
    @staticmethod
    def groupby_planes_key(colkey: str, off: int, nplanes: int) -> str:
        return f"gv::{colkey}::{off}::{nplanes}"

    def groupby_operand(self, key: str, build=None):
        """(device array, built now?) for a "gk::" / "gv::" cols key: a
        group column's lane-major ids, or a value's uint8 byte planes,
        built once a batch by one jitted program over the stored planes
        and then kept like the batch's other derived blocks. ``build``
        makes the planes of a value that is an expression over columns
        (engine/device.py ``_expr_planes``); a column's are made here."""
        with self._lock:
            arr = self._gb_operands.get(key)
            if arr is not None:
                return arr, False
            from pinot_tpu.ops import groupby_mm as mm

            if key.startswith(("go::", "gs::", "gp::", "gl::")):
                arr = self._key_ordered_locked(key, build)
            elif build is not None:
                arr = build()
            elif key.startswith("gk::"):
                name = key[4:]
                arr = mm.prepared_ids(
                    self._column_locked(name), self.n_docs_dev,
                    num_groups=len(self._global_dict_locked(name)),
                    bits=self._width_plan_locked(name).bits)
            else:
                colkey, off, nplanes = key[4:].rsplit("::", 2)
                stored = self._decoded_column_locked(colkey[4:]) \
                    if colkey.startswith("dv::") else self._column_locked(colkey)
                fo = self._width_plan_locked(colkey).offset or 0
                arr = mm.prepared_planes(stored, delta=fo - int(off),
                                         nplanes=int(nplanes))
            self._gb_operands[key] = arr
            self._gb_operand_bytes += int(arr.nbytes)
            self._note_resident(arr)
            return arr, True

    def _key_ordered_locked(self, key: str, build=None):
        """The FULL key-space regime's operands (ops/keysorted.py), a set
        of key columns ``<cols>`` (joined by commas): ``go::<cols>`` the
        rows' order by cartesian key and ``gs::<cols>`` each cell's first
        row in it (one program builds both, from the columns' ``gk::``
        ids); ``gp::<cols>::<what>`` a plane projected into that order -
        ``seg`` each row's segment, a ``gv::`` key that value less its
        offset as one uint32 a row (from the byte planes, ``build`` making
        an expression's), any other cols key the stored plane itself;
        ``gp::<cols>::slot::<what>`` (``slotted_key``) the same plane laid
        out cell by slot and ``gp::<cols>::spill<K>::<what>``
        (``spilled_key``) laid out SPILLED at K, the ordered one a
        temporary of their build; ``gl::<cols>::<K>::<part>``
        (``spill_keys``) the SPILLED layout's own operands."""
        from pinot_tpu.ops import keysorted as ks

        kind, rest = key[:2], key[4:]
        if kind in ("go", "gs"):
            names = tuple(rest.split(","))
            ids = tuple(self.groupby_operand("gk::" + c)[0] for c in names)
            perm, starts, fullest = ks.key_order(ids, cards=tuple(
                len(self._global_dict_locked(c)) for c in names))
            self._key_order_rows[rest] = int(fullest)
            self._key_cell_rows[rest] = np.diff(np.asarray(starts))
            other = ("gs::" if kind == "go" else "go::") + rest
            self._gb_operands[other] = starts if kind == "go" else perm
            self._gb_operand_bytes += int(self._gb_operands[other].nbytes)
            self._note_resident(self._gb_operands[other])
            return perm if kind == "go" else starts
        if kind == "gl":
            names, k, part = rest.split("::")
            layout = self.spill_layout(names.split(","), int(k))
            return jnp.asarray(getattr(layout, _SPILL_PARTS[part]))
        names, what, form = self._projected(key)
        perm = self.groupby_operand("go::" + names)[0]
        if what.startswith("gv::"):
            plane = ks.project_value(self.groupby_operand(what, build)[0],
                                     perm)
        else:
            if what == "seg":
                stored = jnp.broadcast_to(
                    jnp.arange(self.S, dtype=jnp.uint8)[:, None],
                    (self.S, self.pad_to))
            else:
                stored = self._decoded_column_locked(what[4:]) \
                    if what.startswith("dv::") else self._column_locked(what)
            plane = ks.project_plane(stored, perm)
        if form == "slot":
            plane = ks.slot_plane(
                plane, self.groupby_operand("gs::" + names)[0],
                k=ks.slot_rows(self.key_order_rows(names.split(","))))
        elif form:
            plane = ks.spill_plane(plane, jnp.asarray(self.spill_layout(
                names.split(","), form).first), k=form)
        return plane

    @staticmethod
    def slotted_key(key: str) -> str:
        """A ``gp::<cols>::<what>`` key's twin in the slotted layout."""
        names, what = key[4:].split("::", 1)
        return f"gp::{names}::slot::{what}"

    @staticmethod
    def spilled_key(key: str, k: int) -> str:
        """A ``gp::<cols>::<what>`` key's twin in the SPILLED layout at
        ``k`` slots a column."""
        names, what = key[4:].split("::", 1)
        return f"gp::{names}::spill{k}::{what}"

    @staticmethod
    def spill_keys(names: str, k: int) -> tuple:
        """The SPILLED layout's operands at ``k`` over the key columns
        ``names`` (comma-joined): each column's rows, the fat cells' first
        chunks, the fat cells' ids (``_SPILL_PARTS``)."""
        return tuple(f"gl::{names}::{k}::{part}" for part in _SPILL_PARTS)

    @staticmethod
    def _projected(key: str):
        """A ``gp::`` key's (key columns, what it projects, layout): the
        layout None (in key order), ``"slot"`` or the SPILLED K."""
        names, what = key[4:].split("::", 1)
        form, _sep, rest = what.partition("::")
        if form == "slot":
            return names, rest, "slot"
        if form.startswith("spill") and form[5:].isdigit():
            return names, rest, int(form[5:])
        return names, what, None

    def _cell_rows(self, names: str):
        """Rows a cell of the key order over ``names`` (host numpy),
        building the order where it is not built yet."""
        self.groupby_operand("go::" + names)
        return self._key_cell_rows[names]

    def key_order_rows(self, group_cols) -> int:
        """Rows of the fullest cell of the key order over ``group_cols``,
        building the order where it is not built yet."""
        name = ",".join(group_cols)
        self.groupby_operand("go::" + name)
        return self._key_order_rows[name]

    def key_layout_rows(self, group_cols, max_slots: int,
                        fat_slots: int) -> int:
        """``ops/keysorted.py layout_rows`` of the key order over
        ``group_cols`` (the SLOTTED K, a SPILLED K, or 0), once a batch."""
        from pinot_tpu.ops import keysorted as ks

        key = (",".join(group_cols), max_slots, fat_slots)
        k = self._key_layout_rows.get(key)
        if k is None:
            k = self._key_layout_rows[key] = ks.layout_rows(
                self._cell_rows(key[0]), max_slots, fat_slots)
        return k

    def spill_layout(self, group_cols, k: int):
        """``ops/keysorted.py spill_layout`` of the key order over
        ``group_cols`` at ``k``, once a batch."""
        from pinot_tpu.ops import keysorted as ks

        names = ",".join(group_cols)
        layout = self._spill_layouts.get((names, k))
        if layout is None:
            layout = self._spill_layouts[(names, k)] = ks.spill_layout(
                self._cell_rows(names), k)
        return layout

    def lane_rows(self) -> int:
        """The batch's rows as the lane-major operands hold them: padded
        to whole superblocks (ops/groupby_mm.py _to_lanes)."""
        from pinot_tpu.ops.groupby_mm import SUPERBLOCK

        return -(-self.S * self.pad_to // SUPERBLOCK) * SUPERBLOCK

    def groupby_operand_cost(self, keys) -> int:
        """HBM bytes that building the not-yet-built operands among
        ``keys`` would add (lock-free: the byte budget's check). A slotted
        or spilled plane is reckoned at its own slots, which the built key
        order says: ask after ``key_order_rows``."""
        from pinot_tpu.ops import keysorted as ks

        n_pad = self.lane_rows()
        cost = 0
        for key in keys:
            if key in self._gb_operands:
                continue
            if key.startswith("gk::"):
                dt = np.dtype(self.width_plan(key[4:]).dtype)
                cost += n_pad * (dt.itemsize if dt.kind == "u" else 4)
            elif key.startswith("go::"):
                cost += n_pad * 4
            elif key.startswith("gs::"):
                cost += 4 * (self._key_cells(key[4:]) + 1)
            elif key.startswith("gl::"):
                names, k, part = key[4:].split("::")
                cost += 4 * getattr(self.spill_layout(
                    names.split(","), int(k)), _SPILL_PARTS[part]).size
            elif key.startswith("gp::"):
                names, what, form = self._projected(key)
                if form is None:
                    slots = n_pad
                elif form == "slot":
                    slots = ks.slot_rows(self.key_order_rows(
                        names.split(","))) * ks.slot_lanes(
                            self._key_cells(names))
                else:
                    slots = form * self.spill_layout(
                        names.split(","), form).first.size
                cost += slots * (
                    1 if what == "seg" else 4 if what.startswith("gv::")
                    else np.dtype(self.width_plan(what).dtype).itemsize)
            else:
                cost += n_pad * int(key.rsplit("::", 1)[1])
        return cost

    def _key_cells(self, names: str) -> int:
        """Cells of the cartesian key space over ``names`` (comma-joined)."""
        cells = 1
        for c in names.split(","):
            cells *= self.cardinality(c)
        return cells

    def groupby_operand_bytes(self) -> int:
        """Resident bytes of the operands (part of ``device_bytes``)."""
        return self._gb_operand_bytes

    def _note_resident(self, arr) -> None:
        """Caller holds self._lock; device_bytes reads the counter
        lock-free (int update under the GIL)."""
        self._resident_bytes += int(getattr(arr, "nbytes", 0))

    def device_bytes(self) -> int:
        """HBM resident bytes of materialized column blocks (columns +
        decoded + prehashed + sorted projections) — the executor's
        byte-aware LRU eviction key. LOCK-FREE read of the insert-time
        counter: _evict must never block behind another query's cold
        column build."""
        return self._resident_bytes

    def sorted_hll_keys(self, group_cols, group_cards, hash_col: str,
                        log2m: int):
        """(n_total,) device int32: SORTED packed ``slot << 5 | rho`` keys
        for the FILTERLESS HLL scan over these group columns — a lazily
        built sorted projection, cached per batch exactly like the
        prehashed/decoded columns (the role a sorted index plays in the
        reference: built once, reused by every later query of the shape).
        The first query pays the lax.sort (~320ms at 100M rows on v5e);
        repeats reduce boundaries + one matmul (~60ms)."""
        with self._lock:
            return self._sorted_hll_keys_locked(
                group_cols, group_cards, hash_col, log2m)

    def _sorted_hll_keys_locked(self, group_cols, group_cards, hash_col: str,
                                log2m: int):
        key = (tuple(group_cols), tuple(group_cards), hash_col, int(log2m))
        if key not in self._sorted_hll:
            import jax

            from pinot_tpu.ops import agg as agg_ops
            from pinot_tpu.ops import hll as hll_ops
            from pinot_tpu.ops import masks as mask_ops

            num_groups = 1
            for c in group_cards:
                num_groups *= int(c)
            m = 1 << log2m
            # sub-byte id planes unpack before the sort build (the sorted
            # projection is row-scale anyway; group_ids_combine widens ids
            # to int32 in-register regardless of plane width)
            per_col = []
            for c in group_cols:
                col = self._column_locked(c)
                plan = self._width_plan_locked(c)
                if plan.packed:
                    col = mask_ops.unpack_subbyte(col, plan.bits)
                per_col.append(col)
            hh = self.prehashed_column(hash_col)

            def build(cols_list, h, n_docs):
                valid = mask_ops.valid_mask(n_docs, h.shape[1], batched=True)
                gid = agg_ops.group_ids_combine(
                    cols_list, group_cards, valid, num_groups)
                idx, rho = hll_ops.hll_idx_rho(h, log2m)
                slot = jnp.where(valid, gid * m + idx, num_groups * m)
                k32 = (slot.reshape(-1).astype(jnp.int32) << 5) \
                    | rho.reshape(-1).astype(jnp.int32)
                return jax.lax.sort(k32)

            self._sorted_hll[key] = jax.jit(build)(
                per_col, hh, self.n_docs_dev)
            self._note_resident(self._sorted_hll[key])
        return self._sorted_hll[key]

    def int_bounds(self, name: str):
        """(min, max) over the batch from column metadata, or None."""
        mns, mxs = [], []
        for s in self.segments:
            m = s.column_metadata(name)
            if m.min_value is None or m.max_value is None:
                return None
            mns.append(m.min_value)
            mxs.append(m.max_value)
        try:
            return float(min(mns)), float(max(mxs))
        except (TypeError, ValueError):
            return None


# ---------------------------------------------------------------------------
# filter template + params
# ---------------------------------------------------------------------------

_DEVICE_PRED_TYPES = {
    PredicateType.EQ,
    PredicateType.NOT_EQ,
    PredicateType.IN,
    PredicateType.NOT_IN,
    PredicateType.RANGE,
    PredicateType.LIKE,
    PredicateType.REGEXP_LIKE,
}


def build_filter(f: FilterNode, ctx: BatchContext, params: dict, counter: list):
    """FilterNode → (template, params filled). Template is a nested hashable
    tuple; params dict maps slot names → device arrays (all replicated —
    global id space has no per-segment params)."""
    t = f.type
    if t is FilterNodeType.CONSTANT_TRUE:
        return ("true",)
    if t is FilterNodeType.CONSTANT_FALSE:
        return ("false",)
    if t is FilterNodeType.AND:
        return ("and",) + tuple(build_filter(c, ctx, params, counter) for c in f.children)
    if t is FilterNodeType.OR:
        return ("or",) + tuple(build_filter(c, ctx, params, counter) for c in f.children)
    if t is FilterNodeType.NOT:
        return ("not", build_filter(f.children[0], ctx, params, counter))
    return build_predicate(f.predicate, ctx, params, counter)


# device-resident literal/LUT cache: repeated query shapes re-upload the
# same predicate literals on every execute (one device_put each ≈ 1ms of
# host dispatch; measured ~5ms/query on a 6-literal filter). Keyed on the
# HOST bytes BEFORE upload — keying on the device array would need a
# blocking device→host read, costing a round trip instead of saving one.
# Locked: server query threads run _slot concurrently. Bounded at
# 256 × 64KB = 16MB of HBM worst case (big IN-list LUTs skip the cache —
# DeviceExecutor's batch budget doesn't know about this one).
_LITERAL_CACHE: dict = {}
_LITERAL_CACHE_LOCK = threading.Lock()
_LITERAL_CACHE_MAX = 256
_LITERAL_MAX_BYTES = 64 << 10


def _slot(params: dict, counter: list, arr) -> str:
    key = f"pr{counter[0]}"
    counter[0] += 1
    a = np.asarray(arr)
    if a.dtype == np.float64:
        a = a.astype(np.float32)  # device columns are f32; avoid f64 upcast
    sig = params.get("__hostsig__")
    if sig is not None:
        # host-bytes record for the executor's partials-cache digest
        # (engine/device.py): the VALUE identity of this literal, taken
        # BEFORE upload — reading it back off the device would cost the
        # very round trip the cache exists to save
        sig.append((key, a.dtype.str, a.shape, a.tobytes()))
    if a.nbytes <= _LITERAL_MAX_BYTES:
        ck = (a.dtype.str, a.shape, a.tobytes())
        with _LITERAL_CACHE_LOCK:
            hit = _LITERAL_CACHE.pop(ck, None)
        if hit is None:
            hit = jnp.asarray(a)
        with _LITERAL_CACHE_LOCK:
            _LITERAL_CACHE[ck] = hit  # LRU re-insert
            while len(_LITERAL_CACHE) > _LITERAL_CACHE_MAX:
                _LITERAL_CACHE.pop(next(iter(_LITERAL_CACHE)), None)
        params[key] = hit
    else:
        params[key] = jnp.asarray(a)
    return key


def build_predicate(p: Predicate, ctx: BatchContext, params: dict, counter: list):
    if p.type not in _DEVICE_PRED_TYPES:
        raise DeviceUnsupported(f"predicate {p.type} not device-supported")
    lhs = p.lhs
    if lhs.is_identifier:
        if ctx.is_mv(lhs.name):
            # match-any over the (S, L, K) id block: the inner template is
            # the ordinary dict predicate evaluated per entry; mv_any reduces
            # over K with -1 padding masked out (NOT_EQ's inner "not" stays
            # per-entry — reference MV semantics: ANY entry != value)
            ctx.mv_column(lhs.name)  # validates dict encoding + K cap
            tpl = _dict_predicate(p, ctx, params, counter, col_key="mv::" + lhs.name)
            return ("mv_any", "mv::" + lhs.name, tpl)
        enc = ctx.encoding(lhs.name)
        if enc == Encoding.DICT:
            return _dict_predicate(p, ctx, params, counter)
        return _raw_predicate(p, lhs, ctx, params, counter)
    # expression lhs: evaluate on device, compare in raw space
    return _raw_predicate(p, lhs, ctx, params, counter)


def _dict_predicate(p: Predicate, ctx: BatchContext, params: dict, counter: list,
                    col_key: str = None):
    col = col_key or p.lhs.name
    gdict = ctx.global_dict(p.lhs.name)
    t = p.type
    if t in (PredicateType.EQ, PredicateType.NOT_EQ):
        gid = gdict.index_of(p.value)
        key = _slot(params, counter, np.int32(gid if gid >= 0 else -2))
        tpl = ("eq_dict", col, key)
        return ("not", tpl) if t is PredicateType.NOT_EQ else tpl
    if t in (PredicateType.IN, PredicateType.NOT_IN):
        k = max(1, len(p.values))
        vec = np.full(k, -2, dtype=np.int32)
        ids = gdict.ids_of(list(p.values))
        vec[: len(ids)] = ids
        key = _slot(params, counter, vec)
        tpl = ("in_dict", col, key, k)
        return ("not", tpl) if t is PredicateType.NOT_IN else tpl
    if t is PredicateType.RANGE:
        lo, hi = gdict.range_ids(
            p.lower, p.upper, p.lower_inclusive, p.upper_inclusive
        )
        klo = _slot(params, counter, np.int32(lo))
        khi = _slot(params, counter, np.int32(hi))
        return ("range_dict", col, klo, khi)
    # LIKE / REGEXP_LIKE: evaluate once per global dictionary entry → bool LUT
    pat = like_to_regex(p.value) if t is PredicateType.LIKE else p.value
    rx = re.compile(pat)
    match = rx.match if t is PredicateType.LIKE else rx.search
    vals = np.asarray(gdict.values).astype(str)
    lut = np.fromiter((bool(match(s)) for s in vals), dtype=bool, count=len(vals))
    key = _slot(params, counter, lut)
    return ("lut_dict", col, key)


def _raw_predicate(p: Predicate, lhs: Expression, ctx: BatchContext, params: dict,
                   counter: list):
    expr_tpl = build_expr(lhs, ctx, params, counter)
    t = p.type
    if t in (PredicateType.LIKE, PredicateType.REGEXP_LIKE):
        raise DeviceUnsupported("regex over raw (non-dict) column")
    if t in (PredicateType.EQ, PredicateType.NOT_EQ):
        key = _slot(params, counter, np.asarray(p.value))
        tpl = ("eq_raw", expr_tpl, key)
        return ("not", tpl) if t is PredicateType.NOT_EQ else tpl
    if t in (PredicateType.IN, PredicateType.NOT_IN):
        key = _slot(params, counter, np.asarray(list(p.values)))
        tpl = ("in_raw", expr_tpl, key, len(p.values))
        return ("not", tpl) if t is PredicateType.NOT_IN else tpl
    # RANGE
    klo = _slot(params, counter, np.asarray(0 if p.lower is None else p.lower))
    khi = _slot(params, counter, np.asarray(0 if p.upper is None else p.upper))
    return (
        "range_raw",
        expr_tpl,
        klo,
        khi,
        p.lower is not None,
        p.upper is not None,
        p.lower_inclusive,
        p.upper_inclusive,
    )


# ---------------------------------------------------------------------------
# expression templates (device value-space evaluation)
# ---------------------------------------------------------------------------


def build_expr(e: Expression, ctx: BatchContext, params: dict, counter: list):
    if e.is_literal:
        if isinstance(e.value, str) or e.value is None:
            raise DeviceUnsupported("string/null literal in device expression")
        key = _slot(params, counter, np.asarray(e.value))
        return ("lit", key)
    if e.is_identifier:
        enc = ctx.encoding(e.name)
        if enc == Encoding.RAW:
            return ("raw", e.name)
        if np.asarray(ctx.global_dict(e.name).values).dtype.kind not in _NUMERIC_KINDS:
            raise DeviceUnsupported(f"non-numeric dict column {e.name} in expression")
        return ("dictval", e.name)
    fn = get_function(e.name)
    if not fn.device_capable:
        raise DeviceUnsupported(f"function {e.name} is host-only")
    if e.name == "cast":
        arg = build_expr(e.args[0], ctx, params, counter)
        return ("cast", arg, str(e.args[1].value).upper())
    return (e.name,) + tuple(build_expr(a, ctx, params, counter) for a in e.args)


def expr_bounds(e: Expression, ctx: BatchContext):
    """Interval arithmetic over column metadata: |bound| for two-stage sum
    block sizing (ops/agg.py rows_per_block_for). None = unknown."""
    if e.is_literal:
        try:
            v = float(e.value)
            return v, v
        except (TypeError, ValueError):
            return None
    if e.is_identifier:
        return ctx.int_bounds(e.name)
    if not e.is_function:
        return None
    if e.name in ("plus", "minus", "times"):
        a = expr_bounds(e.args[0], ctx)
        b = expr_bounds(e.args[1], ctx)
        if a is None or b is None:
            return None
        if e.name == "plus":
            return a[0] + b[0], a[1] + b[1]
        if e.name == "minus":
            return a[0] - b[1], a[1] - b[0]
        prods = [a[0] * b[0], a[0] * b[1], a[1] * b[0], a[1] * b[1]]
        return min(prods), max(prods)
    if e.name == "cast":
        return expr_bounds(e.args[0], ctx)
    if e.name == "abs":
        b = expr_bounds(e.args[0], ctx)
        if b is None:
            return None
        lo = 0.0 if b[0] <= 0 <= b[1] else min(abs(b[0]), abs(b[1]))
        return lo, max(abs(b[0]), abs(b[1]))
    return None
