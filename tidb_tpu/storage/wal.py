"""ctypes binding + journal layer over the native WAL engine
(native/wal.cpp). The durability spec is the reference's storage-node
model (unistore over badger's value-log, production TiKV over RocksDB
WAL): every mutation appends a framed record, commits group-flush +
fsync, recovery replays the intact prefix, and snapshots checkpoint the
full state so the log can reset.

Record payloads (framing/CRC live in C++; payloads are ours):
  b'P' u32 klen key value          put
  b'D' u32 klen key                delete
  b'X' u32 slen start u32 elen end delete_range
  b'R' run: u32 w, u64 n, u64 commit_ts, key_mat, starts, lens, vbuf
  b'G' / b'g' chunk / b'F'         frame group: ONE logical record
       streamed as bounded chunks (see GroupAssembler)

Group commit (PR 13): `sync_group` batches concurrent committers'
fsyncs — every committer appends its records, then ONE leader runs the
fsync for the whole group while followers wait on the flushed sequence
number. A failed group sync withholds EVERY ack in the group (leader and
followers all raise `StorageIOError`) and poisons the log exactly like a
per-commit fsync failure would. `tidb_wal_group_commit=OFF` routes
`Storage.wal_sync` back to plain `sync()` — bit-identical per-commit
behavior — as the live incident fallback.

Failure discipline (the durability fault domain, PR 10):

  * IO failure — ONE failed append or fsync poisons the `Wal` (the
    fsyncgate rule: after a failed fsync the kernel may have dropped the
    dirty pages, so re-trying and acking would be lying). Every later
    write raises `StorageIOError`; the owning Storage flips read-only.
    The commit IN FLIGHT at the failure is indeterminate — the error at
    the durability point means UNKNOWN outcome (the standard contract for
    an error after the commit point), never a false ack; every commit
    AFTER it fails before touching anything.
  * Corruption — recovery distinguishes a TORN TAIL (a crash cut the
    last frames; nothing with a valid CRC follows) from MID-LOG
    CORRUPTION (a bad frame with valid CRC frames after it — bit rot
    inside committed history). The first is truncated and tolerated;
    the second raises `WalCorruptionError` unless the operator opted
    into `drop-corrupt` (see Storage._open_durable / the
    `tidb_wal_recovery_mode` sysvar).
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import struct
import subprocess
import threading
import zlib
from dataclasses import dataclass, field

import numpy as np

from ..errors import StorageIOError
from ..utils import metrics as M
from ..utils.failpoint import inject as _fp

_SRC = os.path.join(os.path.dirname(__file__), "..", "..", "native", "wal.cpp")
_LIB: ctypes.CDLL | None = None
_LIB_LOCK = threading.Lock()


def _load_lib() -> ctypes.CDLL:
    """Build (once per source content) and load the native library.

    The binary is keyed on a hash of wal.cpp kept beside it, not on
    mtimes: a copied tree (the chip tool, a fresh checkout next to a
    leftover binary) does not keep the order of modification times, and
    a stale binary would load without complaint."""
    global _LIB
    with _LIB_LOCK:
        if _LIB is not None:
            return _LIB
        src = os.path.abspath(_SRC)
        so = os.path.join(os.path.dirname(src), "libtpuwal.so")
        stamp = so + ".src.sha256"
        with open(src, "rb") as f:
            want = hashlib.sha256(f.read()).hexdigest()
        have = None
        if os.path.exists(so) and os.path.exists(stamp):
            with open(stamp) as f:
                have = f.read().strip()
        if have != want:
            # build beside the target and rename: concurrent first users
            # (xdist workers) must never dlopen a half-written file
            tmp = f"{so}.{os.getpid()}.tmp"
            subprocess.run(
                ["g++", "-O2", "-shared", "-fPIC", "-std=c++17", "-o", tmp, src],
                check=True, capture_output=True,
            )
            os.replace(tmp, so)
            with open(stamp + f".{os.getpid()}.tmp", "w") as f:
                f.write(want)
            os.replace(stamp + f".{os.getpid()}.tmp", stamp)
        lib = ctypes.CDLL(so)
        lib.wal_open.restype = ctypes.c_void_p
        lib.wal_open.argtypes = [ctypes.c_char_p]
        lib.wal_append.restype = ctypes.c_longlong
        lib.wal_append.argtypes = [ctypes.c_void_p, ctypes.c_char_p, ctypes.c_uint64]
        lib.wal_sync.restype = ctypes.c_int
        lib.wal_sync.argtypes = [ctypes.c_void_p]
        lib.wal_flush.restype = ctypes.c_int
        lib.wal_flush.argtypes = [ctypes.c_void_p]
        lib.wal_fd.restype = ctypes.c_int
        lib.wal_fd.argtypes = [ctypes.c_void_p]
        lib.wal_close.argtypes = [ctypes.c_void_p]
        lib.wal_abort.argtypes = [ctypes.c_void_p]
        lib.wal_replay_open.restype = ctypes.c_void_p
        lib.wal_replay_open.argtypes = [ctypes.c_char_p]
        lib.wal_replay_next.restype = ctypes.c_int
        lib.wal_replay_next.argtypes = [
            ctypes.c_void_p,
            ctypes.POINTER(ctypes.POINTER(ctypes.c_uint8)),
            ctypes.POINTER(ctypes.c_uint64),
        ]
        lib.wal_replay_valid_bytes.restype = ctypes.c_uint64
        lib.wal_replay_valid_bytes.argtypes = [ctypes.c_void_p]
        lib.wal_replay_close.argtypes = [ctypes.c_void_p]
        lib.snap_write.restype = ctypes.c_int
        lib.snap_write.argtypes = [ctypes.c_char_p, ctypes.c_char_p, ctypes.c_uint64]
        lib.snap_read.restype = ctypes.POINTER(ctypes.c_uint8)
        lib.snap_read.argtypes = [ctypes.c_char_p, ctypes.POINTER(ctypes.c_uint64)]
        lib.snap_probe.restype = ctypes.c_int
        lib.snap_probe.argtypes = [ctypes.c_char_p]
        lib.snap_free.argtypes = [ctypes.POINTER(ctypes.c_uint8)]
        _LIB = lib
        return lib


class Wal:
    """One open write-ahead log.

    `on_io_error(op)` is the degrade hook the owning Storage installs:
    called exactly once, on the failure that poisons the log, BEFORE the
    `StorageIOError` is raised to the writer."""

    def __init__(self, path: str, on_io_error=None):
        self.lib = _load_lib()
        self.path = path
        self._h = self.lib.wal_open(path.encode())
        if not self._h:
            raise OSError(f"cannot open WAL at {path}")
        self._lock = threading.Lock()
        self.poisoned = False
        self.on_io_error = on_io_error
        # --- group commit (PR 13) -----------------------------------------
        # `_appended_seq` counts records accepted (guarded by `_lock`, like
        # the append itself); `_flushed_seq` is the highest count known
        # durably fsynced (guarded by `_gc_cond`). A committer's records
        # are all <= the seq it reads AFTER its last append, so waiting
        # for `_flushed_seq >= that` waits for exactly its durability.
        self._gc_cond = threading.Condition()
        self._appended_seq = 0
        self._flushed_seq = 0
        self._sync_leader = False  # a group fsync is in flight
        # targets of committers currently waiting for durability: a
        # fsync that covers a target satisfies that committer, and the
        # covering leader counts exactly those for the group-size metric
        # (entrants arriving mid-fsync with later targets stay queued
        # for the NEXT group instead of being silently absorbed)
        self._group_targets: list[int] = []
        # --- log shipping (PR 14) -----------------------------------------
        # `tap(wal, seq, payload)` observes every accepted append (called
        # under `_lock`, in append order — it must only enqueue, never
        # block); `on_durable(wal, covered_seq)` fires when `_flushed_seq`
        # advances (under `_gc_cond`) so a shipper can wake without
        # polling. Installed by storage/ship.WalShipper via Storage.
        self.tap = None
        self.on_durable = None

    def _io_failed(self, op: str, cause) -> None:
        """First failure poisons the log; callers see a typed error."""
        first = not self.poisoned
        self.poisoned = True
        if first:
            M.WAL_IO_ERRORS.inc(op=op)
            cb = self.on_io_error
            if cb is not None:
                cb(op)
        err = StorageIOError(
            f"WAL {op} failed on {self.path!r} ({cause}); the log is "
            f"poisoned and the store is read-only — no commit will ack "
            f"until the store is reopened on healthy media"
        )
        if isinstance(cause, BaseException):
            raise err from cause
        raise err

    def append(self, payload: bytes) -> None:
        with self._lock:
            self._append_locked(payload)
        # durability-gap crashpoint: record buffered, nothing fsynced yet
        _fp("wal/after-append-before-sync")

    def _append_locked(self, payload: bytes) -> None:
        if self.poisoned:
            self._io_failed("append", "log already poisoned")
        if self._h is None:
            raise StorageIOError(f"WAL {self.path!r} is closed")
        try:
            _fp("wal/io-error-append")
        except OSError as e:
            self._io_failed("append", e)
        if self.lib.wal_append(self._h, payload, len(payload)) < 0:
            self._io_failed("append", "native append error")
        self._appended_seq += 1
        if self.tap is not None:
            self.tap(self, self._appended_seq, payload)

    def append_group(self, chunks) -> int:
        """Append ONE logical record streamed as a bounded frame group:
        a bare b'G' frame, one b'g'-prefixed frame per chunk, a bare
        b'F' frame — all under the append lock, so no other committer's
        frames interleave. The logical record is the chunk concatenation;
        it is never materialized here, which is the point — a 16M-row
        ingest journals at per-chunk memory instead of holding its whole
        WAL image resident. Returns the logical record's byte length.
        Recovery (and a shipped standby) joins the group back into the
        monolithic record; an unterminated trailing group is truncated
        wholesale at its b'G' frame — atomic replay, same contract as
        the single-frame form."""
        total = 0
        with self._lock:
            self._append_locked(b"G")
            for chunk in _iter_bounded(chunks):
                total += len(chunk)
                self._append_locked(b"g" + chunk)
            self._append_locked(b"F")
        _fp("wal/after-append-before-sync")
        return total

    def sync(self) -> int:
        """Flush + fsync everything appended so far. Returns the record
        sequence the fsync covered (appends hold the same lock, so the
        count read after a successful fsync IS the durable high-water).
        Publishes the covered sequence to the group-commit state, so a
        per-commit sync (OFF mode, checkpoint) releases any concurrent
        group waiters it covered and the next group leader doesn't
        re-fsync already-durable records."""
        _fp("wal/before-sync")
        with self._lock:
            if self.poisoned:
                self._io_failed("sync", "log already poisoned")
            if self._h is None:
                covered = self._appended_seq  # closed: close() flushed + fsynced
            else:
                try:
                    _fp("wal/io-error-sync")
                except OSError as e:
                    self._io_failed("sync", e)
                if self.lib.wal_sync(self._h) != 0:
                    self._io_failed("sync", "native fsync error")
                covered = self._appended_seq
        with self._gc_cond:
            if covered > self._flushed_seq:
                self._flushed_seq = covered
            # waiters this fsync satisfied leave the queue uncounted —
            # the size histogram is leader-observed groups only
            self._group_targets = [t for t in self._group_targets if t > covered]
            if self.on_durable is not None:
                self.on_durable(self, covered)
            self._gc_cond.notify_all()
        return covered

    def sync_group(self, session=None, deadline=None) -> None:
        """Group-commit durability point: wait until everything this
        committer appended is fsynced, batching concurrent committers
        into one fsync.

        One leader at a time runs the real `sync()`; everyone else waits
        on `_flushed_seq`. The wait polls the shared interrupt gate, so a
        KILL or statement deadline releases a follower cleanly — its ack
        is withheld (the commit is indeterminate: the leader's fsync may
        still land it), never falsified. A failed group sync poisons the
        log; the leader raises from `sync()` and every follower observes
        `poisoned` and raises too — no ack in the group survives."""
        with self._lock:
            target = self._appended_seq
        with self._gc_cond:
            if self._flushed_seq >= target:
                M.WAL_GROUP_COMMIT.inc(outcome="follower")
                return  # an earlier leader already covered our records
            self._group_targets.append(target)
            while True:
                if self.poisoned:
                    self._io_failed("sync", "group sync failed; ack withheld")
                if self._flushed_seq >= target:
                    M.WAL_GROUP_COMMIT.inc(outcome="follower")
                    return
                if not self._sync_leader:
                    self._sync_leader = True
                    break  # this committer leads; all paths below are leader-only
                self._gc_cond.wait(0.05)
                if session is not None or deadline is not None:
                    from ..sched.scheduler import raise_if_interrupted

                    raise_if_interrupted(session, deadline)
        # --- leader: flush under the append lock, fsync OUTSIDE it — the
        # whole point of the group: committers keep appending (and piling
        # into the next group) while this group's fsync runs
        covered = -1
        try:
            try:
                # EIO/crash injection mid-group-sync: records appended
                # (possibly flushed), fsync not yet run — no committer in
                # the group may ack past this point on failure
                _fp("wal/group-sync-fail")
            except OSError as e:
                self._io_failed("sync", e)
            _fp("wal/before-sync")
            fd = -1
            with self._lock:
                if self.poisoned:
                    self._io_failed("sync", "log already poisoned")
                if self._h is not None:
                    try:
                        _fp("wal/io-error-sync")
                    except OSError as e:
                        self._io_failed("sync", e)
                    if self.lib.wal_flush(self._h) != 0:
                        self._io_failed("sync", "native flush error")
                    # dup so a concurrent close() can't invalidate the fd
                    # between releasing the lock and the fsync below
                    fd = os.dup(self.lib.wal_fd(self._h))
                high = self._appended_seq
            if fd >= 0:
                try:
                    os.fsync(fd)
                except OSError as e:
                    self._io_failed("sync", e)
                finally:
                    os.close(fd)
            covered = high
        finally:
            with self._gc_cond:
                self._sync_leader = False
                if covered >= 0:
                    self._flushed_seq = max(self._flushed_seq, covered)
                    if self.on_durable is not None:
                        self.on_durable(self, covered)
                    # the group = exactly the registered committers this
                    # fsync covered (leader included); later targets stay
                    # queued for the next leader
                    n = sum(1 for t in self._group_targets if t <= covered)
                    self._group_targets = [t for t in self._group_targets if t > covered]
                    M.WAL_GROUP_COMMIT.inc(outcome="leader")
                    if n:
                        M.WAL_GROUP_SIZE.observe(n)
                else:
                    # failed group sync: the log is poisoned, the whole
                    # queue will observe `poisoned` and raise — the
                    # group's acks are withheld, its targets moot
                    self._group_targets.clear()
                    M.WAL_GROUP_COMMIT.inc(outcome="error")
                self._gc_cond.notify_all()

    def durable_seq(self) -> int:
        """Highest record sequence KNOWN durable on this log. A cleanly
        closed log (checkpoint rotation flushed + fsynced everything) is
        durable through its whole append count; a poisoned log is durable
        only through the last successful fsync — frames past that must
        never ship to a standby (they may be gone with the page cache).
        A superseded log (spare-dir rotation snapshotted its in-memory
        effects) is fully durable THROUGH THE SNAPSHOT, which the
        rotation records by setting `_superseded`."""
        if getattr(self, "_superseded", False):
            with self._lock:
                return self._appended_seq
        with self._lock:
            closed = self._h is None
            appended = self._appended_seq
            poisoned = self.poisoned
        if closed and not poisoned:
            return appended
        with self._gc_cond:
            return self._flushed_seq

    def close(self) -> None:
        with self._lock:
            if self._h:
                if self.poisoned:
                    # NOTHING may be written after poisoning: drop the
                    # buffered (necessarily unacked) records like a crash
                    # would, instead of flushing them past the failure
                    self.lib.wal_abort(self._h)
                else:
                    self.lib.wal_close(self._h)
                self._h = None

    @staticmethod
    def replay(path: str):
        """Yield intact record payloads (stops at the first bad frame)."""
        recs, _ = Wal.replay_records(path)
        yield from recs

    @staticmethod
    def replay_records(path: str) -> tuple[list[bytes], int]:
        """→ (intact-prefix record payloads, intact byte prefix length).
        The caller must truncate the file to the prefix before appending,
        or post-recovery commits land beyond the torn bytes and are lost
        on the next replay. Corruption-agnostic: use `scan_log` to learn
        whether valid frames FOLLOW the first bad one."""
        lib = _load_lib()
        h = lib.wal_replay_open(path.encode())
        if not h:
            # distinguish "no log" from "log unreadable": truncating an
            # intact-but-unreadable log would destroy committed data
            if os.path.exists(path) and os.path.getsize(path) > 0:
                raise OSError(f"WAL {path!r} exists but could not be read")
            return [], 0
        try:
            out = ctypes.POINTER(ctypes.c_uint8)()
            n = ctypes.c_uint64()
            recs = []
            while lib.wal_replay_next(h, ctypes.byref(out), ctypes.byref(n)):
                recs.append(ctypes.string_at(out, n.value))
            return recs, int(lib.wal_replay_valid_bytes(h))
        finally:
            lib.wal_replay_close(h)

    @staticmethod
    def scan_log(path: str) -> "WalScan":
        """Full recovery scan: the intact prefix PLUS a look past the
        first bad frame, so recovery can tell a torn tail from mid-log
        corruption (see WalScan)."""
        recs, valid = Wal.replay_records(path)
        size = os.path.getsize(path) if os.path.exists(path) else 0
        salvage: list[bytes] = []
        gap = 0
        if valid < size:
            with open(path, "rb") as f:
                f.seek(valid)
                tail = f.read()
            salvage, gap = _scan_salvage(tail)
        return WalScan(recs, valid, size, salvage, gap)


@dataclass
class WalScan:
    """Result of Wal.scan_log.

    `records` is the intact prefix. When the file has a bad frame
    (`corrupt`), `salvage` holds the valid-CRC frames found AFTER it —
    non-empty salvage means MID-LOG corruption (committed history exists
    beyond the bad bytes; silently truncating would drop it), empty
    salvage means a plain torn tail. `salvage_gap` is the byte distance
    from the intact prefix to the first salvaged frame (the corrupt
    region recovery would discard under drop-corrupt)."""

    records: list = field(default_factory=list)
    valid_prefix: int = 0
    file_size: int = 0
    salvage: list = field(default_factory=list)
    salvage_gap: int = 0

    @property
    def corrupt(self) -> bool:
        return self.valid_prefix < self.file_size

    @property
    def mid_log(self) -> bool:
        return bool(self.salvage)


# resync scan window after a corrupt frame whose length header is ALSO
# gone: probing every byte offset is O(window * frame) worst case, so it
# is bounded — real logs resync at the first true frame boundary anyway
_SALVAGE_SCAN_CAP = 4 << 20
# CRC-work budget for the offset-probing fallback: pathological tails
# (e.g. long runs whose bytes keep decoding as in-range frame lengths)
# would otherwise cost O(window²) in checksums
_SALVAGE_CRC_BUDGET = 32 << 20


def _scan_salvage(tail: bytes) -> tuple[list[bytes], int]:
    """Hunt for a valid frame chain after the first bad frame.

    A chain only qualifies when it runs to EOF or ends in ONE incomplete
    trailing frame (bit rot leaves the rest of the file as intact frames;
    a crash may additionally tear the very last one). A torn tail's
    garbage bytes can contain pseudo-frames whose CRC happens to check
    out, but such a chain ends mid-garbage and is rejected — this errs
    toward classifying as torn (auto-recoverable) while never letting a
    real committed suffix be silently truncated. Zero-length frames also
    disqualify a chain: no real record is empty, but a zero-filled torn
    region chains as (len=0, crc=0) frames forever. Known limits: TWO
    separate corrupt regions read as a torn tail at the second one, and
    the offset-probing fallback (length header destroyed too) stops at a
    bounded CRC budget, classifying as torn past it."""
    n = len(tail)
    budget = [_SALVAGE_CRC_BUDGET]

    def chain(off: int) -> tuple[list[bytes], bool]:
        out: list[bytes] = []
        while off + 8 <= n:
            ln, crc = struct.unpack_from("<II", tail, off)
            if ln == 0:
                return out, False  # no real record is empty: garbage
            if off + 8 + ln > n:
                return out, True  # incomplete trailing frame: torn end
            budget[0] -= ln
            if zlib.crc32(tail[off + 8 : off + 8 + ln]) != crc:
                return out, False  # mid-data garbage: chain disqualified
            out.append(tail[off + 8 : off + 8 + ln])
            off += 8 + ln
        return out, True  # EOF (or < 8 trailing header bytes)

    # bit rot in a payload keeps the framing intact: the bad frame's
    # length header still points at the next frame
    if n >= 8:
        ln, _ = struct.unpack_from("<II", tail, 0)
        if ln and 8 + ln < n:
            got, clean_end = chain(8 + ln)
            if got and clean_end:
                return got, 8 + ln
    # length header corrupted too: resync by probing offsets (bounded)
    for off in range(1, max(0, min(n, _SALVAGE_SCAN_CAP) - 8)):
        if budget[0] <= 0:
            break
        got, clean_end = chain(off)
        if got and clean_end:
            return got, off
    return [], 0


def fsync_dir(path: str) -> None:
    """fsync a directory so renames/unlinks inside it are durable."""
    fd = os.open(path, os.O_RDONLY)
    try:
        os.fsync(fd)
    finally:
        os.close(fd)


def snap_write(path: str, payload: bytes) -> None:
    if _load_lib().snap_write(path.encode(), payload, len(payload)) != 0:
        raise OSError(f"snapshot write failed: {path}")


def snap_read(path: str) -> bytes | None:
    lib = _load_lib()
    n = ctypes.c_uint64()
    buf = lib.snap_read(path.encode(), ctypes.byref(n))
    if not buf:
        return None
    try:
        return ctypes.string_at(buf, n.value)
    finally:
        lib.snap_free(buf)


def snap_probe(path: str) -> int:
    """Classify a snapshot file: -1 absent, 0 intact, 1 corrupt (present
    but short / bad magic / bad CRC). `snap_read` returns None for both
    absent and corrupt; recovery must refuse on corrupt instead of
    booting an empty store over the wrong epoch's log."""
    return int(_load_lib().snap_probe(path.encode()))


# --------------------------------------------------------- record payloads


def rec_put(key: bytes, value: bytes) -> bytes:
    return b"P" + struct.pack("<I", len(key)) + key + value


def rec_delete(key: bytes) -> bytes:
    return b"D" + struct.pack("<I", len(key)) + key


def rec_delete_range(start: bytes, end: bytes) -> bytes:
    return b"X" + struct.pack("<I", len(start)) + start + struct.pack("<I", len(end)) + end


def rec_kill_runs(start: bytes, end: bytes) -> bytes:
    return b"K" + struct.pack("<I", len(start)) + start + struct.pack("<I", len(end)) + end


def rec_run(key_mat: np.ndarray, vbuf, starts: np.ndarray, lens: np.ndarray, commit_ts: int) -> bytes:
    n, w = key_mat.shape
    vb = bytes(vbuf) if not isinstance(vbuf, bytes) else vbuf
    return (
        b"R"
        + struct.pack("<IQQ", w, n, commit_ts)
        + np.ascontiguousarray(key_mat, dtype=np.uint8).tobytes()
        + np.ascontiguousarray(starts, dtype=np.int64).tobytes()
        + np.ascontiguousarray(lens, dtype=np.int64).tobytes()
        + struct.pack("<Q", len(vb))
        + vb
    )


def rec_crun(run) -> bytes:
    """Columnar record-run payload (storage/segment.ColumnarRun): the
    handles + column arrays ship as-is — no row-major value plane is ever
    materialized for the log, so the WAL write costs what the data weighs
    (the 'compressed tile form doubles as the ingest wire format' idea,
    arXiv:2506.10092)."""
    parts = [
        b"C",
        struct.pack("<QQq I", run.n, run.commit_ts, run.table_id, len(run.cols)),
        np.ascontiguousarray(run.handles_arr, dtype="<i8").tobytes(),
    ]
    for c in run.cols:
        data = c.data
        if data.dtype.kind in "OU":  # still-object str lanes canonicalize here
            from .segment import canonical_str_array

            data = canonical_str_array(data)
        data = np.ascontiguousarray(data)
        if data.dtype.kind == "S":
            if data.dtype.itemsize == 0:  # all-empty strings: keep width >= 1
                data = data.astype("S1")
            width = data.dtype.itemsize
            payload = data.tobytes()
        else:
            width = 0
            payload = data.astype(data.dtype.newbyteorder("<"), copy=False).tobytes()
        has_valid = 0 if c.valid is None else 1
        parts.append(struct.pack("<iBBBI", c.cid, c.kind, c.scale, has_valid, width))
        parts.append(payload)
        if has_valid:
            parts.append(np.ascontiguousarray(c.valid, dtype=np.uint8).tobytes())
    return b"".join(parts)


def rec_irun(run) -> bytes:
    """Int-index-run payload (storage/segment.IntIndexRun): sorted key
    columns + handles; the key byte matrix rebuilds lazily on demand."""
    parts = [
        b"N",
        struct.pack("<QQqqBB", run.n, run.commit_ts, run.table_id,
                    run.index_id, 1 if run.unique else 0, len(run.key_cols)),
    ]
    for c in run.key_cols:
        parts.append(np.ascontiguousarray(c, dtype="<i8").tobytes())
    parts.append(np.ascontiguousarray(run.handles_arr, dtype="<i8").tobytes())
    return b"".join(parts)


def rec_ingest(runs) -> bytes:
    """ONE logical bulk-ingest record (PR 15): every run of the ingest —
    record plane plus all index planes — nested in a single WAL frame,
    so recovery (and a shipped standby) replays the ingest atomically:
    the frame's CRC either admits the whole ingest or none of it."""
    subs = [r.to_wal_record() for r in runs]
    parts = [b"I", struct.pack("<I", len(subs))]
    for s in subs:
        parts.append(struct.pack("<Q", len(s)))
        parts.append(s)
    return b"".join(parts)


def rec_compact(table_id: int, fold_ts: int, spans, retire, runs) -> bytes:
    """ONE logical delta-main compaction (PR 16): the new segments, the
    mutable spans whose versions <= fold_ts they replace, and the retired
    source runs of a merge — a single WAL frame, so recovery (and a
    shipped standby) applies the whole fold-and-swap atomically or not at
    all. The frame does NOT carry per-key deletions: the fold decision is
    a pure function of (store state, span, fold_ts), recomputed at apply
    time (MVCCStore.apply_compaction) — replay walks the same state the
    live publish saw, so it reaches the same decision.

    retire entries are (kind, aux, commit_ts) identity tuples:
    kind 0 = ColumnarRun (aux unused), 1 = IntIndexRun (aux = index_id),
    2 = byte Run (aux = key width; scoped to table_id's key prefix)."""
    parts = [b"Z", struct.pack("<qQ", table_id, fold_ts),
             struct.pack("<I", len(spans))]
    for s, e in spans:
        parts.append(struct.pack("<I", len(s)))
        parts.append(s)
        parts.append(struct.pack("<I", len(e)))
        parts.append(e)
    parts.append(struct.pack("<I", len(retire)))
    for kind, aux, cts in retire:
        parts.append(struct.pack("<BqQ", kind, aux, cts))
    subs = [r.to_wal_record() for r in runs]
    parts.append(struct.pack("<I", len(subs)))
    for s in subs:
        parts.append(struct.pack("<Q", len(s)))
        parts.append(s)
    return b"".join(parts)


# ------------------------------------------------------------ frame groups
#
# A frame group streams ONE logical record to the log as bounded pieces:
#   b'G'            group begin (bare)
#   b'g' <chunk>    one chunk of the logical record
#   b'F'            group end (bare)
# The logical record is the concatenation of the chunks — byte-identical
# to the monolithic form, so `apply_record` never sees group tags. The
# writer holds the append lock across the whole group (Wal.append_group),
# so a group is always contiguous in the log and a torn group can only be
# the log's final frames.

GROUP_CHUNK_BYTES = 1 << 20


def _iter_bounded(chunks):
    """Re-chunk byte pieces to <= GROUP_CHUNK_BYTES each. Oversized
    pieces are split; small ones pass through un-coalesced (bounding
    resident memory is the goal, minimizing frame count is not)."""
    for piece in chunks:
        if len(piece) <= GROUP_CHUNK_BYTES:
            if piece:
                yield piece
        else:
            for off in range(0, len(piece), GROUP_CHUNK_BYTES):
                yield piece[off : off + GROUP_CHUNK_BYTES]


def iter_ingest_chunks(runs):
    """Stream the bulk-ingest record as chunks whose concatenation is
    byte-identical to `rec_ingest(runs)` — at most one run's WAL record
    is resident at a time instead of the whole ingest image."""
    yield b"I" + struct.pack("<I", len(runs))
    for r in runs:
        s = r.to_wal_record()
        yield struct.pack("<Q", len(s))
        yield s


def iter_compact_chunks(table_id: int, fold_ts: int, spans, retire, runs):
    """Stream the delta-main compaction record as chunks whose
    concatenation is byte-identical to `rec_compact(...)`."""
    parts = [b"Z", struct.pack("<qQ", table_id, fold_ts),
             struct.pack("<I", len(spans))]
    for s, e in spans:
        parts.append(struct.pack("<I", len(s)))
        parts.append(s)
        parts.append(struct.pack("<I", len(e)))
        parts.append(e)
    parts.append(struct.pack("<I", len(retire)))
    for kind, aux, cts in retire:
        parts.append(struct.pack("<BqQ", kind, aux, cts))
    parts.append(struct.pack("<I", len(runs)))
    yield b"".join(parts)
    for r in runs:
        s = r.to_wal_record()
        yield struct.pack("<Q", len(s))
        yield s


class GroupAssembler:
    """Join frame-group chunks back into logical records.

    `feed(payload)` returns the complete logical records the frame
    finished: a non-group frame passes straight through, group frames
    buffer until the closing b'F' joins them. Malformed sequences (a
    group tag outside a group, a non-chunk frame inside one) raise
    ValueError — the writer holds the append lock across a group, so
    they are unreachable from an honest log."""

    def __init__(self):
        self._chunks: list[bytes] | None = None

    @property
    def open(self) -> bool:
        return self._chunks is not None

    def feed(self, payload: bytes) -> list[bytes]:
        tag = payload[:1]
        if self._chunks is None:
            if tag == b"G":
                _need(len(payload) == 1, "G frame not bare")
                self._chunks = []
                return []
            _need(tag not in (b"g", b"F"), f"group frame {tag!r} outside a group")
            return [payload]
        if tag == b"g":
            self._chunks.append(payload[1:])
            return []
        if tag == b"F":
            _need(len(payload) == 1, "F frame not bare")
            rec = b"".join(self._chunks)
            self._chunks = None
            _need(len(rec) >= 1, "empty frame group")
            return [rec]
        raise ValueError(f"malformed WAL record: frame {tag!r} inside an open group")


def _apply_crun(payload: bytes):
    """Parse a 'C' payload → ColumnarRun (validating every length)."""
    from .segment import ColSpec, ColumnarRun

    _need(len(payload) >= 29, "C header short")
    n, commit_ts, table_id, ncols = struct.unpack_from("<QQq I", payload, 1)
    pos = 29
    _need(len(payload) >= pos + 8 * n, "C handles truncated")
    handles = np.frombuffer(payload, "<i8", n, pos).copy()
    pos += 8 * n
    cols = []
    for _ in range(ncols):
        _need(len(payload) >= pos + 11, "C column header short")
        # width is u32: a single TEXT value past 64KiB must not overflow
        # the lane-width field
        cid, kind, scale, has_valid, width = struct.unpack_from("<iBBBI", payload, pos)
        pos += 11
        from ..mysqltypes.datum import K_FLOAT, K_UINT

        if width:
            nb = width * n
            _need(len(payload) >= pos + nb, "C string column truncated")
            data = np.frombuffer(payload, f"S{width}", n, pos).copy()
        else:
            nb = 8 * n
            _need(len(payload) >= pos + nb, "C fixed column truncated")
            dt = "<f8" if kind == K_FLOAT else ("<u8" if kind == K_UINT else "<i8")
            data = np.frombuffer(payload, dt, n, pos).copy()
        pos += nb
        valid = None
        if has_valid:
            _need(len(payload) >= pos + n, "C valid mask truncated")
            valid = np.frombuffer(payload, np.uint8, n, pos).astype(bool)
            pos += n
        cols.append(ColSpec(cid, kind, scale, data, valid))
    _need(pos == len(payload), "C trailing bytes")
    return ColumnarRun(table_id, handles, cols, commit_ts)


def _apply_irun(payload: bytes):
    from .segment import IntIndexRun

    _need(len(payload) >= 35, "N header short")
    n, commit_ts, table_id, index_id, unique, k = struct.unpack_from("<QQqqBB", payload, 1)
    pos = 35
    _need(len(payload) == pos + 8 * n * (k + 1), "N arrays length mismatch")
    cols = []
    for _ in range(k):
        cols.append(np.frombuffer(payload, "<i8", n, pos).copy())
        pos += 8 * n
    handles = np.frombuffer(payload, "<i8", n, pos).copy()
    return IntIndexRun(table_id, index_id, cols, handles, bool(unique), commit_ts)


def _need(ok: bool, what: str) -> None:
    if not ok:
        raise ValueError(f"malformed WAL record: {what}")


def apply_record(payload: bytes, kv, mvcc) -> None:
    """Replay one journal record into the in-memory store.

    Every length field is validated BEFORE it is used to slice: a
    truncated or mutated payload must raise ValueError, never half-apply
    a short key/value (Python slices truncate silently) or hand
    np.frombuffer an out-of-range view. CRC framing makes malformed
    payloads unreachable in normal recovery; this is the defense for the
    drop-corrupt salvage path and for writer bugs."""
    _need(len(payload) >= 1, "empty payload")
    tag = payload[:1]
    if tag == b"P":
        _need(len(payload) >= 5, "P header short")
        (klen,) = struct.unpack_from("<I", payload, 1)
        _need(len(payload) >= 5 + klen, "P key truncated")
        key = payload[5 : 5 + klen]
        kv.put(key, payload[5 + klen :])
    elif tag == b"D":
        _need(len(payload) >= 5, "D header short")
        (klen,) = struct.unpack_from("<I", payload, 1)
        _need(len(payload) == 5 + klen, "D length mismatch")
        kv.delete(payload[5 : 5 + klen])
    elif tag in (b"X", b"K"):
        _need(len(payload) >= 5, "range header short")
        (slen,) = struct.unpack_from("<I", payload, 1)
        _need(len(payload) >= 9 + slen, "range start truncated")
        start = payload[5 : 5 + slen]
        (elen,) = struct.unpack_from("<I", payload, 5 + slen)
        _need(len(payload) == 9 + slen + elen, "range length mismatch")
        end = payload[9 + slen : 9 + slen + elen]
        if tag == b"X":
            kv.delete_range(start, end)
        else:
            mvcc.kill_runs_range(start, end)
    elif tag in (b"R", b"C", b"N"):
        mvcc.ingest_runs([_parse_run_record(payload)])
    elif tag == b"I":
        # ONE logical bulk ingest: parse EVERY nested run first (any
        # malformed sub-record refuses the whole frame — never a
        # half-applied ingest), then publish them as one atomic unit
        _need(len(payload) >= 5, "I header short")
        (nsub,) = struct.unpack_from("<I", payload, 1)
        pos = 5
        runs = []
        for _ in range(nsub):
            _need(len(payload) >= pos + 8, "I sub-record header short")
            (slen,) = struct.unpack_from("<Q", payload, pos)
            pos += 8
            _need(len(payload) >= pos + slen, "I sub-record truncated")
            runs.append(_parse_run_record(payload[pos : pos + slen]))
            pos += slen
        _need(pos == len(payload), "I trailing bytes")
        mvcc.ingest_runs(runs)
    elif tag == b"Z":
        # ONE logical compaction: parse EVERYTHING first (spans, retire
        # identities, every nested run — any malformed piece refuses the
        # whole frame), then fold-and-swap as one atomic unit
        _need(len(payload) >= 21, "Z header short")
        table_id, fold_ts = struct.unpack_from("<qQ", payload, 1)
        pos = 17
        (nspans,) = struct.unpack_from("<I", payload, pos)
        pos += 4
        spans = []
        for _ in range(nspans):
            _need(len(payload) >= pos + 4, "Z span header short")
            (slen,) = struct.unpack_from("<I", payload, pos)
            pos += 4
            _need(len(payload) >= pos + slen + 4, "Z span start truncated")
            s = payload[pos : pos + slen]
            pos += slen
            (elen,) = struct.unpack_from("<I", payload, pos)
            pos += 4
            _need(len(payload) >= pos + elen, "Z span end truncated")
            spans.append((s, payload[pos : pos + elen]))
            pos += elen
        _need(len(payload) >= pos + 4, "Z retire header short")
        (nret,) = struct.unpack_from("<I", payload, pos)
        pos += 4
        _need(len(payload) >= pos + 17 * nret, "Z retire truncated")
        retire = []
        for _ in range(nret):
            kind, aux, cts = struct.unpack_from("<BqQ", payload, pos)
            pos += 17
            retire.append((kind, aux, cts))
        _need(len(payload) >= pos + 4, "Z runs header short")
        (nruns,) = struct.unpack_from("<I", payload, pos)
        pos += 4
        runs = []
        for _ in range(nruns):
            _need(len(payload) >= pos + 8, "Z sub-record header short")
            (slen,) = struct.unpack_from("<Q", payload, pos)
            pos += 8
            _need(len(payload) >= pos + slen, "Z sub-record truncated")
            runs.append(_parse_run_record(payload[pos : pos + slen]))
            pos += slen
        _need(pos == len(payload), "Z trailing bytes")
        mvcc.apply_compaction(table_id, fold_ts, spans, retire, runs)
    else:
        raise ValueError(f"unknown WAL record tag {tag!r}")


def _parse_run_record(payload: bytes):
    """One run-shaped record payload → a Run/ColumnarRun/IntIndexRun
    (validated, NOT yet published)."""
    from .segment import Run

    _need(len(payload) >= 1, "empty run record")
    tag = payload[:1]
    if tag == b"C":
        return _apply_crun(payload)
    if tag == b"N":
        return _apply_irun(payload)
    _need(tag == b"R", f"unexpected run record tag {tag!r}")
    _need(len(payload) >= 21, "R header short")
    w, n, commit_ts = struct.unpack_from("<IQQ", payload, 1)
    pos = 21
    _need(len(payload) >= pos + n * w + 16 * n + 8, "R arrays truncated")
    key_mat = np.frombuffer(payload, np.uint8, n * w, pos).reshape(int(n), w).copy()
    pos += n * w
    starts = np.frombuffer(payload, np.int64, n, pos).copy()
    pos += 8 * n
    lens = np.frombuffer(payload, np.int64, n, pos).copy()
    pos += 8 * n
    (vlen,) = struct.unpack_from("<Q", payload, pos)
    _need(len(payload) == pos + 8 + vlen, "R value buffer length mismatch")
    vbuf = payload[pos + 8 : pos + 8 + vlen]
    if n:
        _need(
            bool(
                (starts >= 0).all() and (lens >= 0).all()
                and (starts <= vlen).all() and (lens <= vlen).all()
                and (starts + lens <= vlen).all()
            ),
            "R value slices out of range",
        )
    return Run(key_mat, vbuf, starts, lens, commit_ts)
