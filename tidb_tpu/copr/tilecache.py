"""Columnar tile cache — the TiFlash-replica analog (SURVEY §2.12 TiFlash
row: "columnar replica + MPP engine"; here the columnar replica is a
lazily-built, version-tagged cache of decoded column batches per
(table, region), reused across queries so the scan hot path never touches
row decode).

Invalidation: `Storage.bump_version` increments a per-table counter on
every committed write; a batch built at an older version is rebuilt on
next access. Uncommitted reads (txn membuffer) bypass the cache: the cop client
builds the task batch from the txn's merged view (client.py send).
"""

from __future__ import annotations

import time
from dataclasses import dataclass
from threading import RLock

import numpy as np

from ..chunk.chunk import Chunk, Column, col_numpy_dtype, VARLEN
from ..codec import tablecodec
from ..codec.row import decode_row
from ..catalog.schema import TableInfo
from ..mysqltypes.datum import Datum
from ..utils import timeline as TL


@dataclass
class ColumnBatch:
    """All rows of one (table, region) decoded into dense numpy columns."""

    table: TableInfo
    handles: np.ndarray  # int64 row handles
    data: list[np.ndarray]  # per table column (offset order)
    valid: list[np.ndarray]
    version: tuple | int
    start: bytes = b""
    end: bytes = b""
    min_valid_ts: int = 0  # last table-commit ts at build time

    @property
    def n_rows(self) -> int:
        return len(self.handles)

    def to_chunk(self, col_offsets: list[int]) -> Chunk:
        cols = []
        for off in col_offsets:
            ft = self.table.columns[off].ft
            cols.append(Column(ft, self.data[off], self.valid[off]))
        return Chunk(cols)


# --- device tile codecs (host-side encode half; decode is fused into the
# --- jitted device program in tpu_engine._decode_lane) ----------------------
#
# Per-column encodings chosen at batch build so the WIRE/h2d form is the
# compressed form ("GPU Acceleration of SQL Analytics on Compressed Data",
# arXiv:2506.10092 — decompress-in-kernel beats transfer-then-process):
#
#   pack   strided frame of reference for int lanes: over the valid rows,
#          lo = min and g = gcd of (d - lo) (1 when that is 0 or 1; taken
#          over the whole lane, never a sample); upload (d - lo) // g as
#          uint8/16/32 plus the base lo and the stride g as 0-d scalars in
#          the ORIGINAL dtype (payload leaves, so neither enters a program
#          or fuse key); decode is one multiply-add, p * g + lo, bit-exact
#          because p * g <= hi - lo. DATE lanes (multiples of one day of
#          microseconds) and whole-number DECIMALs code at the width a
#          dictionary would give. A lane whose hi - lo does not fit its
#          own dtype takes no pack
#   dict   sorted-unique values + narrow codes for low-NDV lanes that
#          have NO arithmetic code: floats (skipped when the lane holds
#          NaN, which breaks searchsorted, or a negative zero, which
#          np.unique would bit-merge with +0.0) and ints whose strided
#          span is still past 2^32. Decode is one gather, which the chip
#          prices at thousands of HBM bytes an element (PERF.md, PR 27):
#          an int lane that can pack never takes dict, whatever the bytes
#   rle    run-length (vals, lens) for sorted/clustered/constant lanes and
#          few-run validity masks; decode is jnp.repeat with a static
#          total_repeat_length (pad tail rows are don't-care: every
#          kernel masks with row_valid / the per-lane valid bit first)
#   rv     zero-byte alias for the all-valid mask — it is bit-identical
#          to row_valid, which the kernel already holds
#   dense  the plain padded [T, R] lane — chosen whenever no codec beats
#          it (wide-range high-NDV ints, high-entropy floats)
#
# Invalid rows are normalized to 0 before encoding (kernels never read
# data under a false valid bit), and aux arrays (dict vocab, rle runs) pad
# to power-of-two lengths so compile-cache keys — which carry the codec
# signature — stay bounded.

MIN_TILE_ROWS = 256  # smallest row bucket a DeviceBatch pads to
DICT_MAX_NDV = 4096  # beyond this a dict vocab stops paying for itself
_AUX_MIN = 8  # smallest padded aux-array length (vocab / run buffers)


def pow2_rows(n: int, lo: int = MIN_TILE_ROWS) -> int:
    """Row-bucket for n rows: next power of two, floored at `lo`."""
    return max(lo, 1 << max(0, int(n - 1).bit_length()))


def _pow2_len(n: int, lo: int = _AUX_MIN) -> int:
    return pow2_rows(n, lo)


def _pad2d(a: np.ndarray, shape: tuple[int, int]) -> np.ndarray:
    t, r = shape
    out = np.zeros(t * r, dtype=a.dtype)
    out[: len(a)] = a
    return out.reshape(t, r)


def _pad1d(a: np.ndarray, length: int) -> np.ndarray:
    out = np.zeros(length, dtype=a.dtype)
    out[: len(a)] = a
    return out


def _rle_encode(x: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """(run values, run lengths) of x. NaN != NaN splits runs — harmless:
    each NaN becomes its own run and decodes back bit-exact."""
    n = len(x)
    if n == 0:
        return x[:0], np.zeros(0, np.int32)
    change = np.empty(n, dtype=bool)
    change[0] = True
    np.not_equal(x[1:], x[:-1], out=change[1:])
    idx = np.flatnonzero(change)
    return x[idx], np.diff(np.append(idx, n)).astype(np.int32)


def _code_dtype(span: int):
    """Smallest unsigned dtype holding values in [0, span]."""
    if span < (1 << 8):
        return np.uint8
    if span < (1 << 16):
        return np.uint16
    if span < (1 << 32):
        return np.uint32
    return None


def encode_valid_lane(v: np.ndarray, shape: tuple[int, int]):
    """Validity mask codec. The overwhelmingly common all-valid mask is
    EXACTLY row_valid (true for real rows, false for the pad tail), so it
    ships as a zero-byte alias — the kernel reuses the row_valid array it
    already holds, paying neither wire bytes nor a decode expand. Masks
    with few runs take RLE; ragged ones stay dense. Returns
    (payload | None for dense, sig)."""
    if v.all():
        return {}, ("rv",)
    padded = shape[0] * shape[1]
    vals, lens = _rle_encode(v)
    # +1 guarantees a trailing zero-value zero-length pad run: jnp.repeat
    # with total_repeat_length clamps the tail gather to the LAST run,
    # so without the pad an exactly-pow2 run count ending in True would
    # decode pad rows as valid
    np_len = _pow2_len(len(vals) + 1)
    rle_bytes = np_len * (vals.dtype.itemsize + 4)
    if rle_bytes < padded // 2:
        return (
            {"rv": _pad1d(vals, np_len), "rl": _pad1d(lens, np_len)},
            ("rle", np_len),
        )
    return None, ("dense",)


def encode_data_lane(d: np.ndarray, v: np.ndarray, shape: tuple[int, int]):
    """Pick + apply the cheapest codec for one numeric data lane.
    Returns (payload | None for dense, sig). `sig` is the static codec
    descriptor that joins the device program's compile-cache key (decode
    is traced into the program, so programs are codec-specific) AND the
    launch-group fuse key (stacked lanes must agree on aux shapes)."""
    padded = shape[0] * shape[1]
    item = d.dtype.itemsize
    dense_bytes = padded * item
    all_valid = bool(v.all())
    dz = d if all_valid else np.where(v, d, np.zeros((), d.dtype))
    any_valid = all_valid or bool(v.any())
    pres = dz if all_valid else dz[v]  # the valid rows: what pack and dict look at
    is_int = np.issubdtype(d.dtype, np.integer)

    # a float lane holding negative zero stays dense/pack-free of value
    # merging: -0.0 == 0.0 under np.unique AND run detection, so dict and
    # rle would canonicalize the sign bit the dense lane preserves
    has_negzero = (not is_int) and bool(np.any((dz == 0.0) & np.signbit(dz)))

    best = (dense_bytes, "dense", None)

    # rle — runs over the normalized lane (+1: always keep a zero pad
    # run so the decode's tail-clamp gathers 0, see encode_valid_lane)
    if not has_negzero:
        rvals, rlens = _rle_encode(dz)
        np_len = _pow2_len(len(rvals) + 1)
        rle_bytes = np_len * (item + 4)
        if rle_bytes < best[0]:
            best = (rle_bytes, "rle", (rvals, rlens, np_len))

    # pack — base + code x stride over the VALID rows. The gcd runs over
    # the whole lane (never a sample), in the lane's own dtype: a span
    # that does not fit it would wrap the differences, and such a lane
    # has no narrower code anyway
    arith = False
    if any_valid and is_int:
        lo = pres.min()
        span = int(pres.max()) - int(lo)
        if span <= np.iinfo(d.dtype).max:
            g = max(int(np.gcd.reduce(pres - lo)), 1)
            cdt = _code_dtype(span // g)
            arith = cdt is not None and cdt().itemsize < item
            if arith:
                pack_bytes = padded * cdt().itemsize + 2 * item
                if pack_bytes < best[0]:
                    best = (pack_bytes, "pack", (lo, g, cdt))

    # dict — only for lanes with no arithmetic code (floats of few values,
    # ints whose strided span is still past 2^32): its decode is a gather,
    # which the chip prices at thousands of HBM bytes an element, so no
    # byte count pays for it where one multiply-add would do
    if any_valid and not has_negzero and not arith:
        # sample NDV first so np.unique never runs on a lane that
        # obviously won't dictionary-compress; the stride comes from the
        # VALID subset being sampled (a sparse-valid lane would otherwise
        # be under-sampled into a spuriously high NDV estimate)
        sample = pres[:: max(1, len(pres) // 4096)][:4096]
        if len(np.unique(sample)) <= min(DICT_MAX_NDV, max(len(sample) // 2, 1)):
            if is_int or not np.isnan(pres).any():
                uniq = np.unique(pres)
                ndv = len(uniq)
                cdt = _code_dtype(ndv - 1) if ndv else None
                if ndv and ndv <= DICT_MAX_NDV and cdt is not None \
                        and cdt().itemsize < item:
                    vp = _pow2_len(ndv)
                    dict_bytes = padded * cdt().itemsize + vp * item
                    if dict_bytes < best[0]:
                        best = (dict_bytes, "dict", (uniq, vp, cdt))

    kind = best[1]
    if kind == "dense":
        return None, ("dense",)
    if kind == "rle":
        rvals, rlens, np_len = best[2]
        return (
            {"rv": _pad1d(rvals, np_len), "rl": _pad1d(rlens, np_len)},
            ("rle", np_len, d.dtype.str),
        )
    if kind == "pack":
        lo, g, cdt = best[2]
        q = dz - lo  # invalid rows may wrap: don't-care under their valid bit
        if g > 1:
            q //= np.asarray(g, d.dtype)
        return (
            {"p": _pad2d(q.astype(cdt), shape), "b": np.asarray(lo, dtype=d.dtype),
             "g": np.asarray(g, dtype=d.dtype)},
            ("pack", np.dtype(cdt).str, d.dtype.str),
        )
    uniq, vp, cdt = best[2]
    codes = np.searchsorted(uniq, dz).astype(cdt)
    codes[~v] = 0
    vocab = _pad1d(uniq, vp)
    if vp > len(uniq):
        vocab[len(uniq):] = uniq[-1]  # pad codes stay in-domain
    return (
        {"c": _pad2d(codes, shape), "v": vocab},
        ("dict", np.dtype(cdt).str, vp, d.dtype.str),
    )


def batch_nbytes(batch: ColumnBatch) -> float:
    """Approximate host bytes of a batch — the RU read-byte term and the
    arbiter's footprint proxy. numpy lanes answer exactly; object lanes
    count their pointer array (a cheap, stable underestimate — the RU
    model needs monotonic, not forensic). Cached: sibling tasks and
    retries re-ask for the same immutable batch."""
    cached = getattr(batch, "_nbytes", None)
    if cached is None:
        n = float(getattr(batch.handles, "nbytes", 0))
        for a in batch.data:
            n += getattr(a, "nbytes", 0)
        for v in batch.valid:
            n += getattr(v, "nbytes", 0)
        batch._nbytes = cached = n
    return cached


def device_nbytes(batch: ColumnBatch, lane_idx: int | None = None) -> float | None:
    """Actual device wire footprint of a batch's mirrors: the bytes the
    narrowed/compressed tiles REALLY moved (and hold resident), not the
    64Ki-padded fiction the RU/memory layers used to see. None when no
    mirror exists (host-path task). With `lane_idx` the SERVING lane's
    mirror answers — stale sibling mirrors (built under another layout
    flag, or spill copies with fewer lanes uploaded) must not set another
    lane's RU charge; without it, the smallest mirror stands in."""
    mirrors = getattr(batch, "_mirrors", None)
    if not mirrors:
        return None
    if lane_idx is not None:
        m = mirrors.get(lane_idx)
        if m is not None and getattr(m, "wire_nbytes", 0):
            return float(m.wire_nbytes)
    vals = [
        float(m.wire_nbytes)
        for m in mirrors.values()
        if getattr(m, "wire_nbytes", 0)
    ]
    return min(vals) if vals else None


def _decode_handles(keybuf: np.ndarray, n: int) -> np.ndarray:
    """(n, 19) record-key byte matrix → int64 handles (vectorized BE+sign)."""
    enc = np.ascontiguousarray(keybuf[:, 11:19]).view(">u8").reshape(n)
    return (enc.astype(np.uint64) ^ np.uint64(1 << 63)).view(np.int64)


def _decode_values_into(table, cols, big: np.ndarray, offs: np.ndarray, lens: np.ndarray, rows_idx: np.ndarray, handles: np.ndarray) -> None:
    """Decode row values (at byte offsets `offs`, byte lengths `lens`, in
    buffer `big`) into chunk columns at target positions `rows_idx`; v2
    rows vectorized, v1 rows per-row."""
    from ..codec import rowfast

    n = len(offs)
    if n == 0:
        return
    first = big[offs]
    v2 = first == rowfast.V2_FLAG
    v2_pos = np.nonzero(v2)[0]
    if len(v2_pos):
        # batch-decode header-identical rows; fall back on the rest
        bad = rowfast.decode_v2_batch(big, offs[v2_pos], table, cols, rows_idx[v2_pos])
        for b in bad:  # rare: schema drifted mid-table
            p = v2_pos[int(b)]
            end = int(offs[p]) + int(lens[p])
            _decode_one(table, cols, int(rows_idx[p]), big[offs[p] : end].tobytes(), int(handles[p]))
    for p in np.nonzero(~v2)[0]:
        end = int(offs[p]) + int(lens[p])
        _decode_one(table, cols, int(rows_idx[p]), big[offs[p] : end].tobytes(), int(handles[p]))


def decode_rows_to_batch(table: TableInfo, kvs: list[tuple[bytes, bytes]], version: int) -> ColumnBatch:
    """Row-format KV pairs → dense columnar batch (the once-per-version
    decode; ref: rowcodec ChunkDecoder decoding straight into chunks).

    v2 rows (bulk-loaded, identical headers) decode with vectorized numpy
    gathers; v1 rows (DML path) fall back to per-row decode. A mixed batch
    routes each row down the right path by its version flag.
    """
    n = len(kvs)
    chk = Chunk.empty([c.ft for c in table.columns], n)
    cols = chk.columns

    # handles: record keys are fixed 19 bytes → one vectorized BE decode
    keybuf = np.frombuffer(b"".join(k for k, _ in kvs), dtype=np.uint8)
    if n and len(keybuf) == 19 * n:
        handles = _decode_handles(keybuf.reshape(n, 19), n)
    else:  # ragged keys (shouldn't happen for record scans) — per-row
        handles = np.fromiter((tablecodec.decode_record_handle(k) for k, _ in kvs), np.int64, n)

    vals = [v for _, v in kvs]
    lens = np.fromiter((len(v) for v in vals), np.int64, n)
    big = np.frombuffer(b"".join(vals), dtype=np.uint8)
    offs = np.zeros(n, dtype=np.int64)
    if n > 1:
        np.cumsum(lens[:-1], out=offs[1:])
    _decode_values_into(table, cols, big, offs, lens, np.arange(n, dtype=np.int64), handles)

    # hidden rowid column mirrors handles
    for c in table.columns:
        if c.hidden and c.name == "_tidb_rowid":
            cols[c.offset].data[:] = handles
            cols[c.offset].valid[:] = True
    return ColumnBatch(table, handles, [c.data for c in cols], [c.valid for c in cols], version)


def _gather_columnar(table: TableInfo, cols, run, keep: np.ndarray,
                     rows_idx: np.ndarray) -> None:
    """ColumnarRun fast path: copy the run's column arrays straight into
    the chunk columns — no v2 row decode, no byte-matrix gather. Mirrors
    decode_v2_batch's routing exactly (decimal rescale to the table's
    scale, float/uint bit views, ascii/utf8 strings, defaults for table
    columns the run doesn't carry)."""
    from ..mysqltypes.datum import K_DEC, K_STR
    from ..table.table import datum_from_default

    by_id = {c.id: c for c in table.columns}
    contiguous = len(keep) == run.n  # whole-run scans skip the gather copy
    present: set[int] = set()
    for spec in run.cols:
        c = by_id.get(spec.cid)
        if c is None:
            continue
        present.add(spec.cid)
        col = cols[c.offset]
        data = spec.data if contiguous else spec.data[keep]
        if data.dtype.kind == "O":
            # still-object str lane: already the chunk form — no decode
            col.data[rows_idx] = data
        elif data.dtype.kind == "S":
            w = data.dtype.itemsize
            if spec.kind != K_STR:  # K_BYTES lanes keep bytes payloads
                strs = np.array([bytes(x) for x in data], dtype=object)
            elif w == 0:
                strs = np.full(len(rows_idx), "", dtype=object)
            elif (data.view(np.uint8) >= 0x80).any():  # non-ascii → utf8 per row
                strs = np.array([bytes(x).decode("utf8") for x in data], dtype=object)
            else:
                strs = data.astype("U").astype(object)
            col.data[rows_idx] = strs
        else:
            vals = data
            if spec.kind == K_DEC:
                want = max(c.ft.decimal, 0)
                sc = spec.scale
                if want != sc:
                    vals = vals * 10 ** (want - sc) if want > sc else vals // 10 ** (sc - want)
            col.data[rows_idx] = vals.astype(col.data.dtype, copy=False)
        if spec.valid is None:
            col.valid[rows_idx] = True
        else:
            col.valid[rows_idx] = spec.valid if contiguous else spec.valid[keep]
    for c in table.columns:
        if c.id in present:
            continue
        if c.hidden and c.name == "_tidb_rowid":
            continue  # caller fills from handles
        d = datum_from_default(c)
        col = cols[c.offset]
        if d.is_null:
            col.valid[rows_idx] = False
        else:
            for i in rows_idx:
                col.set_datum(int(i), d)


def build_batch_from_segments(table: TableInfo, segs, loose, version) -> ColumnBatch:
    """Segment scan results → columnar batch, gathering key/value bytes
    straight out of run buffers (zero per-row materialization for the
    bulk-loaded fast path; ColumnarRun segments copy their column arrays
    directly — no row decode at all)."""
    from ..storage.segment import ColumnarRun

    keeps = [s.keep_idx() for s in segs]
    n = sum(len(k) for k in keeps) + len(loose)
    chk = Chunk.empty([c.ft for c in table.columns], n)
    cols = chk.columns
    handles = np.zeros(n, dtype=np.int64)
    row0 = 0
    for s, keep in zip(segs, keeps):
        m = len(keep)
        if m == 0:
            continue
        run = s.run
        rows_idx = np.arange(row0, row0 + m, dtype=np.int64)
        if isinstance(run, ColumnarRun):
            seg_handles = run.handles_arr if m == run.n else run.handles_arr[keep]
            handles[row0 : row0 + m] = seg_handles
            _gather_columnar(table, cols, run, keep, rows_idx)
            row0 += m
            continue
        key_mat = run.key_mat[keep]
        if key_mat.shape[1] == 19:
            seg_handles = _decode_handles(key_mat, m)
        else:
            seg_handles = np.fromiter(
                (tablecodec.decode_record_handle(run.key_at(int(i))) for i in keep), np.int64, m
            )
        handles[row0 : row0 + m] = seg_handles
        big = run.value_buffer()
        _decode_values_into(table, cols, big, run.starts[keep], run.lens[keep], rows_idx, seg_handles)
        row0 += m
    for k, v in loose:
        h = tablecodec.decode_record_handle(k)
        handles[row0] = h
        _decode_one(table, cols, row0, v, h)
        row0 += 1
    for c in table.columns:
        if c.hidden and c.name == "_tidb_rowid":
            cols[c.offset].data[:] = handles
            cols[c.offset].valid[:] = True
    return ColumnBatch(table, handles, [c.data for c in cols], [c.valid for c in cols], version)


def _decode_one(table: TableInfo, cols, i: int, val: bytes, handle: int) -> None:
    from ..table.table import datum_from_default

    by_id = decode_row(val)
    for off, c in enumerate(table.columns):
        d = by_id.get(c.id)
        if d is None:
            if c.hidden and c.name == "_tidb_rowid":
                d = Datum.i(handle)
            else:
                d = datum_from_default(c)
        cols[off].set_datum(i, d)


class BuildSideCache:
    """Device-resident build-side join structures, shared store-wide
    (ISSUE 11; "Fine-Tuning Data Structures for Analytical Query
    Processing", arXiv:2112.13099 — specialize the join structure per
    build-side shape and keep it resident).

    TPC-H dimension tables rarely change between statements, so the MPP
    engine's specialized build sides (today: the direct-address LUT
    mapping packed join key → build row position, probed as a pure
    device gather) stay uploaded across statements instead of being
    re-sorted inside every fused program.

    Keying: `(table_id, span, schema_version, codec_sig)` where
    `codec_sig` carries the structure tag, the table DATA version and
    every layout parameter (key offsets, packing lo/strides, domain,
    lane codec form). A get() under a NEW schema/data version purges the
    stale entries of the same (table, span, tag) — a stale build side
    must never serve — and counts them as invalidations. Entries LRU
    under a byte budget, and `evict_all()` joins the server memory
    arbiter's soft-limit degrade sweep exactly like the tile cache (the
    arbiter snapshots its cache list OUTSIDE the registry lock, so this
    lock nests under nothing of lower rank)."""

    CAP_BYTES = 1 << 30

    def __init__(self):
        from collections import OrderedDict

        self._od: "OrderedDict[tuple, tuple]" = OrderedDict()  # key → (value, nbytes)
        self._lock = RLock()
        self.nbytes = 0
        self.hits = 0
        self.misses = 0
        self.evicts = 0
        self.invalidates = 0

    @staticmethod
    def _nbytes(value) -> int:
        n = 0
        for x in value if isinstance(value, (tuple, list)) else (value,):
            n += int(getattr(x, "nbytes", 64))
        return n

    def get(self, table_id: int, span: tuple, schema_ver: int, sig: tuple, build):
        """Cached device structure for the key, building (and uploading)
        via `build()` on miss. `sig[0]` is the structure tag: stale
        same-(table, span, tag) entries under any OTHER (schema_ver,
        sig) are purged here — version bumps invalidate, they don't
        linger until LRU pressure."""
        from ..utils import metrics as M

        key = (table_id, span, schema_ver, sig)
        with self._lock:
            ent = self._od.get(key)
            if ent is not None:
                self._od.move_to_end(key)
                self.hits += 1
                M.TPU_BUILD_CACHE.inc(outcome="hit")
                return ent[0]
            stale = [k for k in self._od
                     if k[0] == table_id and k[1] == span and k[3][0] == sig[0]
                     and (k[2] != schema_ver or k[3] != sig)]
            for k in stale:
                self.nbytes -= self._od.pop(k)[1]
                self.invalidates += 1
                M.TPU_BUILD_CACHE.inc(outcome="invalidate")
            self.misses += 1
            M.TPU_BUILD_CACHE.inc(outcome="miss")
        # build + upload OUTSIDE the lock: a slow h2d must not stall
        # every other statement's probe (a racing duplicate build is
        # benign — last writer wins, same content)
        value = build()
        nb = self._nbytes(value)
        with self._lock:
            old = self._od.pop(key, None)
            if old is not None:
                # a concurrent statement built the same key while we
                # were outside the lock — return the bytes its entry
                # held, or the ledger drifts up on every such race
                self.nbytes -= old[1]
            self._od[key] = (value, nb)
            self.nbytes += nb
            while self.nbytes > self.CAP_BYTES and len(self._od) > 1:
                _, (_, old_nb) = self._od.popitem(last=False)
                self.nbytes -= old_nb
                self.evicts += 1
                M.TPU_BUILD_CACHE.inc(outcome="evict")
        return value

    def invalidate_table(self, table_id: int) -> None:
        from ..utils import metrics as M

        with self._lock:
            for k in [k for k in self._od if k[0] == table_id]:
                self.nbytes -= self._od.pop(k)[1]
                self.invalidates += 1
                M.TPU_BUILD_CACHE.inc(outcome="invalidate")

    def evict_all(self) -> float:
        """Server soft-memory-limit degrade action (utils/memory
        ServerMemTracker sweep): drop every resident structure. Returns
        the device bytes released for collection."""
        from ..utils import metrics as M

        with self._lock:
            freed = float(self.nbytes)
            n = len(self._od)
            self._od.clear()
            self.nbytes = 0
            self.evicts += n
            for _ in range(n):
                M.TPU_BUILD_CACHE.inc(outcome="evict")
        return freed


class TileCache:
    def __init__(self, storage):
        self.storage = storage
        self._cache: dict[tuple[int, bytes], ColumnBatch] = {}
        self._lock = RLock()  # cop worker pool shares this cache
        self.hits = 0
        self.misses = 0

    def get_batch(self, table: TableInfo, start: bytes, end: bytes, read_ts: int) -> ColumnBatch:
        """Snapshot-correct cache: a batch built when the table's last
        commit was at `last_commit_ts` is valid for any read_ts ≥ that
        commit while the version counter is unchanged. Reads BELOW the
        last commit (historic snapshots) always rebuild, uncached."""
        ver, last_commit_ts = self.storage.data_version(tablecodec.table_prefix(table.id))
        key = (table.id, start)
        with self._lock:
            cached = self._cache.get(key)
            if (
                cached is not None
                and cached.version == ver
                and cached.end == end
                and read_ts >= cached.min_valid_ts
            ):
                self.hits += 1
                return cached
            self.misses += 1
        # `tile.build` (the host half: this region's columnar batch)
        # encloses `tile.gather` (segments → columns); the device half is
        # booked by the mirror (tpu_engine.DeviceBatch)
        t0 = time.perf_counter_ns()
        with TL.span("tile.gather") as gather:
            snap = self.storage.snapshot(read_ts)
            segs, loose = snap.scan_segments(start, end)
            batch = build_batch_from_segments(table, segs, loose, ver)
            gather.args["segments"] = len(segs)
        batch.start, batch.end = start, end
        batch.min_valid_ts = last_commit_ts
        if read_ts >= last_commit_ts:
            with self._lock:
                self._cache[key] = batch
        TL.boundary("tile.build", t0, time.perf_counter_ns(), part="host",
                    table=table.name, rows=batch.n_rows, columns=len(batch.data),
                    host_bytes=int(batch_nbytes(batch)))
        return batch

    def invalidate_table(self, table_id: int) -> None:
        with self._lock:
            for key in [k for k in self._cache if k[0] == table_id]:
                del self._cache[key]
        # build sides are DERIVED from these lanes: whoever invalidates
        # the tiles (DDL, TRUNCATE, RESTORE) invalidates the resident
        # join structures too — without instantiating the cache just to
        # empty it
        bc = getattr(self.storage, "_build_cache", None)
        if bc is not None:
            bc.invalidate_table(table_id)
        # the workload-history plane learned its walls against the OLD
        # tiles: schema-level invalidation drops its routing entries the
        # same lazy way (PR 20)
        wl = getattr(self.storage, "_workload", None)
        if wl is not None:
            wl.invalidate_table(table_id)

    def evict_all(self) -> float:
        """Server soft-memory-limit action (utils/memory ServerMemTracker):
        drop every cached column batch AND its device mirrors — the tile
        cache and the per-device DeviceBatch uploads hanging off it (the
        residency index placement routes by) are the store's biggest
        reclaimable pools. Batches still referenced by in-flight tasks
        keep working; only the cache lets go. Returns the bytes whose
        OWNERSHIP the cache dropped — host lane bytes plus each mirror's
        real (compressed) wire footprint, not a padded-tile estimate.
        Batches still referenced by in-flight tasks free only when those
        tasks finish, so the figure is what was released for collection,
        not an instantaneous RSS delta."""
        freed = 0.0
        with self._lock:
            for b in self._cache.values():
                freed += batch_nbytes(b)
                mirrors = getattr(b, "_mirrors", None)
                if mirrors is not None:
                    freed += sum(
                        float(getattr(m, "wire_nbytes", 0))
                        for m in mirrors.values()
                    )
                    b._mirrors = None
                if getattr(b, "_enc_cache", None) is not None:
                    b._enc_cache = None  # host-side encode cache goes too
            self._cache.clear()
        return freed
