"""Segmented, CRC-chained, crash-tolerant write-ahead log.

Re-design of /root/reference/pkg/wal/ (writeaheadlog.go:60-806, reader.go,
util.go) with the same on-disk architecture:

- A WAL is a directory of files ``%016x.wal`` with strictly consecutive
  indexes starting at 1.
- Each file is a sequence of frames: an 8-byte little-endian header whose
  low 32 bits are the unpadded record length and high 32 bits the CRC, then
  the record bytes zero-padded to an 8-byte boundary.
- Records are ``LogRecord{type, truncate_to, data}`` with types
  ENTRY / CONTROL / CRC_ANCHOR (logrecord.proto:13-24), encoded with the
  canonical codec instead of protobuf.
- The CRC is CRC32-Castagnoli chained across records *and files*
  (seed 0xDEED0001): for ENTRY/CONTROL frames it covers payload+pad updated
  from the previous CRC; a file's first frame is a CRC_ANCHOR whose header
  carries the chain value forward without covering bytes
  (writeaheadlog.go:716-757, reader.go:109-144).
- Every append fsyncs (writeaheadlog.go:469-472) — or, via
  :meth:`WriteAheadLogFile.append_async`, writes the frame immediately and
  defers the fsync to the shared group-commit wave (see
  :mod:`.group_commit`); callers await durability before acting on it.
  Files rotate when the next frame might overflow ``file_size_bytes``;
  rotation deletes files older than the last truncation point
  (writeaheadlog.go:639-714).
- ``read_all`` replays entries from the last truncation point, then switches
  the log to write mode on a fresh file.  A torn tail in the *last* file
  raises :class:`RepairableWALError`; ``repair`` truncates the last file
  after the last good record, keeping a ``.copy`` (writeaheadlog.go:279-337,
  util.go:240-310).
"""

from __future__ import annotations

import os
import shutil
import struct
from dataclasses import dataclass
from time import perf_counter
from typing import BinaryIO, Optional

from ..api import Logger, WriteAheadLog
from ..codec import decode, encode
from ..metrics import Gauge, MetricOpts, Provider
from ..native import crc32c_update, wal_append as native_wal_append
from ..utils.logging import StdLogger

WAL_SUFFIX = ".wal"
RECORD_HEADER_SIZE = 8
CRC_SEED = 0xDEED0001
DEFAULT_FILE_SIZE_BYTES = 64 * 1024 * 1024

_HDR = struct.Struct("<Q")

# record types (logrecord.proto:15-19)
ENTRY = 0
CONTROL = 1
CRC_ANCHOR = 2


@dataclass(frozen=True)
class LogRecord:
    type: int = ENTRY
    truncate_to: bool = False
    data: bytes = b""


class WALError(Exception):
    pass


class CorruptWALError(WALError):
    """CRC mismatch / undecodable payload / broken file sequence."""


class RepairableWALError(WALError):
    """Torn tail in the last file — ``repair()`` can truncate it away."""


class WALClosedError(WALError):
    pass


class WALModeError(WALError):
    """Append in read mode / read_all in write mode."""


def _file_name(index: int) -> str:
    return f"{index:016x}{WAL_SUFFIX}"


def _parse_file_name(name: str) -> Optional[int]:
    if not name.endswith(WAL_SUFFIX):
        return None
    stem = name[: -len(WAL_SUFFIX)]
    if len(stem) != 16:
        return None
    try:
        return int(stem, 16)
    except ValueError:
        return None


def _dir_wal_indexes(dir_path: str) -> list[int]:
    try:
        names = os.listdir(dir_path)
    except FileNotFoundError:
        return []
    idx = [i for i in (_parse_file_name(n) for n in names) if i is not None]
    idx.sort()
    return idx


def _pad(length: int) -> bytes:
    return b"\x00" * ((8 - length % 8) % 8)


def _fsync_dir(dir_path: str) -> None:
    fd = os.open(dir_path, os.O_RDONLY)
    try:
        os.fsync(fd)
    finally:
        os.close(fd)


class LogRecordReader:
    """Sequential frame reader for one WAL file (reader.go:30-180).

    The first frame must be a CRC_ANCHOR; its header CRC initializes the
    chain.  ``read`` raises ``EOFError`` at a clean end,
    :class:`RepairableWALError` on a torn tail (short header/payload), and
    :class:`CorruptWALError` on a CRC/codec failure.
    """

    def __init__(self, path: str):
        self.path = path
        self._f: Optional[BinaryIO] = open(path, "rb")
        self.crc = 0
        try:
            rec = self._read_frame()
        except (EOFError, WALError) as e:
            self.close()
            raise RepairableWALError(f"wal: no CRC anchor in {path}: {e}") from e
        if rec.type != CRC_ANCHOR:
            self.close()
            raise RepairableWALError(f"wal: first record in {path} is not a CRC anchor")

    def close(self) -> None:
        if self._f is not None:
            self._f.close()
            self._f = None

    def tell(self) -> int:
        assert self._f is not None
        return self._f.tell()

    def read(self) -> LogRecord:
        return self._read_frame()

    def _read_frame(self) -> LogRecord:
        assert self._f is not None
        hdr = self._f.read(RECORD_HEADER_SIZE)
        if len(hdr) == 0:
            raise EOFError
        if len(hdr) < RECORD_HEADER_SIZE:
            raise RepairableWALError("wal: short frame header")
        header = _HDR.unpack(hdr)[0]
        length = header & 0xFFFFFFFF
        crc = header >> 32
        padded = length + len(_pad(length))
        payload = self._f.read(padded)
        if len(payload) < padded:
            raise RepairableWALError("wal: short frame payload")
        try:
            rec = decode(LogRecord, payload[:length])
        except Exception as e:
            raise CorruptWALError(f"wal: failed to decode payload: {e}") from e
        if rec.type in (ENTRY, CONTROL):
            expect = crc32c_update(self.crc, payload)
            if expect != crc:
                raise CorruptWALError(
                    f"wal: crc verification failed in {self.path}: "
                    f"got {crc:08X}, want {expect:08X}"
                )
            self.crc = crc
        elif rec.type == CRC_ANCHOR:
            self.crc = crc
        else:
            raise CorruptWALError(f"wal: unexpected record type {rec.type}")
        return rec


class WALMetrics:
    """pkg/wal/metrics.go — file-count gauge, plus the persistence-span
    histograms ISSUE 13 lights up: ``append_hist`` covers one whole
    append operation (write + CRC + the inline fsync when synchronous),
    ``fsync_hist`` the deferred group-commit fsync waves.  Fixed-bucket
    :class:`~smartbft_tpu.metrics.LogScaleHistogram` arrays — bounded
    memory at any append count, always on (an observe is a few integer
    ops next to a ~100 µs fsync)."""

    def __init__(self, provider: Optional[Provider] = None):
        if provider is None:
            from ..metrics import DisabledProvider

            provider = DisabledProvider()
        self.count_of_files: Gauge = provider.new_gauge(
            MetricOpts(namespace="consensus", subsystem="wal", name="count_of_files")
        )
        from ..metrics import LogScaleHistogram

        self.append_hist = LogScaleHistogram()
        self.fsync_hist = LogScaleHistogram()


class WriteAheadLogFile(WriteAheadLog):
    """The WAL object (writeaheadlog.go:82-102).  Not thread-safe by itself;
    the consensus core serializes all appends through the View/Controller
    event loops, and a lock guards cross-thread use anyway."""

    def __init__(
        self,
        dir_path: str,
        logger: Optional[Logger] = None,
        file_size_bytes: int = DEFAULT_FILE_SIZE_BYTES,
        metrics: Optional[WALMetrics] = None,
    ):
        import threading

        self._dir = os.path.normpath(dir_path)
        self._log = logger or StdLogger("smartbft.wal")
        self._file_size_bytes = file_size_bytes
        self._metrics = metrics or WALMetrics()
        # flight recorder (obs.TraceRecorder, disabled by default):
        # wal.append / wal.fsync busy spans when the embedder's Consensus
        # attaches its recorder (attach_recorder) and it is on.  Recording
        # is safe from the group-commit executor thread (telemetry, never
        # state).
        from ..obs.recorder import standby

        self._recorder = standby()
        self._lock = threading.RLock()
        self._f: Optional[BinaryIO] = None
        self._index = 0
        self._crc = CRC_SEED
        self._read_mode = True
        self._truncate_index = 0
        self._active_indexes: list[int] = []
        self._closed = False
        self._dirty = False  # unsynced frame bytes in the current file

    # -- lifecycle ---------------------------------------------------------

    @classmethod
    def _create(cls, dir_path, logger, file_size_bytes, metrics) -> "WriteAheadLogFile":
        if _dir_wal_indexes(dir_path):
            raise WALError(f"wal: already exists in {dir_path}")
        os.makedirs(dir_path, mode=0o700, exist_ok=True)
        w = cls(dir_path, logger, file_size_bytes, metrics)
        w._read_mode = False
        w._index = 0
        w._truncate_index = 0
        w._open_next_file()
        _fsync_dir(w._dir)
        w._log.infof("Write-Ahead-Log created successfully, mode: WRITE, dir: %s", w._dir)
        return w

    @classmethod
    def _open(cls, dir_path, logger, file_size_bytes, metrics) -> "WriteAheadLogFile":
        indexes = _dir_wal_indexes(dir_path)
        if not indexes:
            raise FileNotFoundError(f"wal: no files in {dir_path}")
        w = cls(dir_path, logger, file_size_bytes, metrics)
        w._log.infof(
            "Write-Ahead-Log discovered %d wal files in %s", len(indexes), w._dir
        )
        # verify continuous sequence + readable anchor per file
        # (util.go:88-143): failure on the last file is repairable.
        for pos, index in enumerate(indexes):
            if pos > 0 and index != indexes[pos - 1] + 1:
                raise CorruptWALError("wal: files not in sequence")
            path = os.path.join(dir_path, _file_name(index))
            try:
                r = LogRecordReader(path)
                r.close()
            except WALError as e:
                if pos == len(indexes) - 1:
                    raise RepairableWALError(
                        f"wal: failed reading last file {path}: {e}"
                    ) from e
                raise CorruptWALError(f"wal: failed reading file {path}: {e}") from e
        w._active_indexes = indexes
        w._index = indexes[0]
        w._read_mode = True
        w._metrics.count_of_files.set(len(indexes))
        w._log.infof("Write-Ahead-Log opened successfully, mode: READ, dir: %s", w._dir)
        return w

    def close(self) -> None:
        with self._lock:
            if self._closed:
                return
            if self._f is not None:
                if not self._read_mode:
                    # truncate preallocated/garbage tail so a reopen ends at EOF
                    self._f.truncate(self._f.tell())
                    self._f.flush()
                    os.fsync(self._f.fileno())
                    self._dirty = False
                self._f.close()
                self._f = None
            self._closed = True

    # -- append path -------------------------------------------------------

    def append(self, entry: bytes, truncate_to: bool) -> None:
        """api.WriteAheadLog.append — ENTRY record (writeaheadlog.go:402-419)."""
        if not entry:
            raise WALError("data is nil or empty")
        self._append_record(LogRecord(type=ENTRY, truncate_to=truncate_to, data=entry))

    def append_async(self, entry: bytes, truncate_to: bool) -> "asyncio.Future":
        """Group-commit append: write the frame now, fsync in a shared wave.

        The frame (and CRC chain) is written before this returns, so record
        order is call order; only durability is deferred.  The returned
        future resolves once an fsync covering this write completed —
        callers MUST await it before sending any message that depends on
        the record being durable (the WAL-first rule).  Requires a running
        event loop.
        """
        import asyncio

        from .group_commit import default_scheduler

        if not entry:
            raise WALError("data is nil or empty")
        self._append_record(
            LogRecord(type=ENTRY, truncate_to=truncate_to, data=entry), sync=False
        )
        with self._lock:
            dirty = self._dirty
        if not dirty:
            # rotation (or a concurrent sync append) already fsynced past us
            fut: asyncio.Future = asyncio.get_running_loop().create_future()
            fut.set_result(None)
            return fut
        return default_scheduler().schedule(self)

    def attach_recorder(self, recorder) -> None:
        """Arm the persistence spans: wal.append / wal.fsync events land
        in ``recorder`` (an obs.TraceRecorder; None keeps the WAL's own disabled one)."""
        if recorder is not None:
            self._recorder = recorder

    def span_block(self) -> dict:
        """The JSON-able WAL persistence-span summary (always measured,
        recorder or not): per-op append and group-fsync percentiles."""
        return {
            "append": self._metrics.append_hist.snapshot(),
            "fsync": self._metrics.fsync_hist.snapshot(),
        }

    def _group_sync(self) -> None:
        """Fsync the current file if it has unsynced frames.  Called by the
        GroupCommitScheduler on an executor thread; the lock is held across
        the fsync so the fd cannot rotate/close out from under it (loop-side
        contention is bounded by one ~100 us fsync — the price the inline
        path paid on every single append)."""
        rec = self._recorder
        with self._lock:
            if self._closed or self._f is None or not self._dirty:
                return  # already durable (rotation/close fsyncs before moving on)
            # busy span on the executor thread that runs the wave
            span = rec.begin("wal.fsync") if rec.enabled else None
            try:
                t0 = perf_counter()
                os.fsync(self._f.fileno())
                self._dirty = False
                dur = perf_counter() - t0
            finally:
                if span is not None:
                    rec.end(span)
        self._metrics.fsync_hist.observe(dur)

    def truncate_to(self) -> None:
        """Append a CONTROL record marking a truncation point
        (writeaheadlog.go:381-394)."""
        self._append_record(LogRecord(type=CONTROL, truncate_to=True, data=b""))

    def drop_stale_segments(self) -> int:
        """Immediately delete files wholly behind the truncation point
        (ISSUE 17 compaction).  Rotation already prunes them lazily
        (:meth:`_open_next_file`); the snapshot flow calls this EAGERLY
        after anchoring, so disk stays bounded by the snapshot interval
        instead of the 64 MiB rotation cadence.  The truncation point is
        keyed on the anchored sequence by construction: PersistedState
        marks ``truncate_to`` on every ProposedRecord, so every segment
        below ``_truncate_index`` holds only records the snapshot's
        anchor certificate already covers.  Returns files deleted."""
        with self._lock:
            if self._closed or self._read_mode:
                return 0
            removed = 0
            keep = []
            for idx in self._active_indexes:
                if idx < self._truncate_index and idx != self._index:
                    try:
                        os.remove(os.path.join(self._dir, _file_name(idx)))
                        removed += 1
                        self._log.debugf("Deleted log file: %s",
                                         _file_name(idx))
                    except OSError:
                        keep.append(idx)
                else:
                    keep.append(idx)
            self._active_indexes = keep
            self._metrics.count_of_files.set(len(keep))
            if removed:
                _fsync_dir(self._dir)
            return removed

    def disk_bytes(self) -> int:
        """Total bytes of the live WAL segments — the disk-bound gauge
        (``wal.disk_bytes``) the ISSUE 17 SLO watches for unbounded
        growth."""
        with self._lock:
            indexes = list(self._active_indexes)
        total = 0
        for idx in indexes:
            try:
                total += os.path.getsize(os.path.join(self._dir, _file_name(idx)))
            except OSError:
                pass
        return total

    def crc(self) -> int:
        with self._lock:
            return self._crc

    def _append_record(self, rec: LogRecord, sync: bool = True) -> None:
        # one busy span per append op; a synchronous append's span
        # INCLUDES its inline fsync (the native path fuses them), an async
        # one is write-only — the deferred fsync lands as wal.fsync
        recorder = self._recorder
        span = recorder.begin(
            "wal.append", extra={"sync": True} if sync else None,
        ) if recorder.enabled else None
        try:
            self._append_timed(rec, sync)
        finally:
            if span is not None:
                recorder.end(span)

    def _append_timed(self, rec: LogRecord, sync: bool) -> None:
        t0 = perf_counter()
        with self._lock:
            if self._closed:
                raise WALClosedError("wal: closed")
            if self._read_mode:
                raise WALModeError("wal: in READ mode")
            assert self._f is not None
            payload = encode(rec)
            length = len(payload)
            if length > 0xFFFFFFFF:
                raise WALError(f"wal: record too big: {length}")
            # native fast path: pack + CRC + write (+ fdatasync) in one call
            # (write-mode files are unbuffered, so fd-level writes are safe)
            res = native_wal_append(self._f.fileno(), payload, self._crc, True,
                                    do_sync=sync)
            if res is not None:
                _, self._crc = res
            else:
                padded = payload + _pad(length)
                crc = crc32c_update(self._crc, padded)
                self._f.write(_HDR.pack(length | (crc << 32)))
                self._f.write(padded)
                if sync:
                    self._f.flush()
                    os.fsync(self._f.fileno())
                self._crc = crc
            self._dirty = not sync
            if rec.truncate_to:
                self._truncate_index = self._index
            # switch if this or the next (>=16B) record could overflow
            if self._f.tell() > self._file_size_bytes - 16:
                self._switch_files()
        self._metrics.append_hist.observe(perf_counter() - t0)

    def _write_anchor(self) -> None:
        """CRC_ANCHOR frame carrying the chain value (writeaheadlog.go:716-757)."""
        assert self._f is not None
        payload = encode(LogRecord(type=CRC_ANCHOR, truncate_to=False, data=b""))
        if native_wal_append(self._f.fileno(), payload, self._crc, False) is not None:
            return
        length = len(payload)
        padded = payload + _pad(length)
        self._f.write(_HDR.pack(length | (self._crc << 32)))
        self._f.write(padded)
        self._f.flush()
        os.fsync(self._f.fileno())

    def _open_next_file(self) -> None:
        """deleteAndCreateFile (writeaheadlog.go:667-714): bump index, delete
        files older than the truncation point, create the file, anchor it."""
        self._index += 1
        if self._active_indexes and self._active_indexes[0] < self._truncate_index:
            keep = []
            for idx in self._active_indexes:
                if idx < self._truncate_index:
                    os.remove(os.path.join(self._dir, _file_name(idx)))
                    self._log.debugf("Deleted log file: %s", _file_name(idx))
                else:
                    keep.append(idx)
            self._active_indexes = keep
        path = os.path.join(self._dir, _file_name(self._index))
        # unbuffered: appends go straight to the fd (native fast path writes
        # at fd level; nothing may linger in a Python-side buffer)
        self._f = open(path, "wb", buffering=0)
        self._write_anchor()
        self._active_indexes.append(self._index)
        self._metrics.count_of_files.set(len(self._active_indexes))

    def _switch_files(self) -> None:
        assert self._f is not None
        self._f.truncate(self._f.tell())
        self._f.flush()
        os.fsync(self._f.fileno())
        self._dirty = False  # rotation makes every prior frame durable
        self._f.close()
        self._open_next_file()
        self._log.debugf("Switched to log file index %d", self._index)

    # -- read path ---------------------------------------------------------

    def read_all(self) -> list[bytes]:
        """Replay entries from the last truncation point, then move to write
        mode on a fresh file (writeaheadlog.go:506-608)."""
        with self._lock:
            if self._closed:
                raise WALClosedError("wal: closed")
            if not self._read_mode:
                raise WALModeError("wal: in WRITE mode")
            items: list[bytes] = []
            last_index = self._active_indexes[-1]
            for index in self._active_indexes:
                self._index = index
                path = os.path.join(self._dir, _file_name(index))
                r = LogRecordReader(path)
                if index != self._active_indexes[0] and r.crc != self._crc:
                    r.close()
                    raise CorruptWALError(
                        f"wal: anchor CRC of {path} does not match chain"
                    )
                try:
                    while True:
                        rec = r.read()
                        if rec.truncate_to:
                            items.clear()
                            self._truncate_index = index
                        if rec.type == ENTRY:
                            items.append(rec.data)
                except EOFError:
                    self._crc = r.crc
                    r.close()
                except (RepairableWALError, CorruptWALError) as e:
                    r.close()
                    if index == last_index:
                        raise RepairableWALError(
                            f"wal: error in last file, possibly repairable: {e}"
                        ) from e
                    raise
            # move to write mode on a new file
            self._read_mode = False
            self._open_next_file()
            self._log.infof(
                "Write-Ahead-Log read %d entries, mode: WRITE", len(items)
            )
            return items


# ---------------------------------------------------------------------------
# Module-level API (mirrors wal.Create/Open/Repair/InitializeAndReadAll)
# ---------------------------------------------------------------------------


def create(
    dir_path: str,
    logger: Optional[Logger] = None,
    file_size_bytes: int = DEFAULT_FILE_SIZE_BYTES,
    metrics: Optional[WALMetrics] = None,
) -> WriteAheadLogFile:
    return WriteAheadLogFile._create(dir_path, logger, file_size_bytes, metrics)


def open_wal(
    dir_path: str,
    logger: Optional[Logger] = None,
    file_size_bytes: int = DEFAULT_FILE_SIZE_BYTES,
    metrics: Optional[WALMetrics] = None,
) -> WriteAheadLogFile:
    return WriteAheadLogFile._open(dir_path, logger, file_size_bytes, metrics)


def repair(dir_path: str, logger: Optional[Logger] = None) -> None:
    """Truncate the last file after its last good record, keeping a ``.copy``
    (writeaheadlog.go:279-337, util.go:240-310)."""
    log = logger or StdLogger("smartbft.wal")
    indexes = _dir_wal_indexes(dir_path)
    if not indexes:
        raise FileNotFoundError(f"wal: no files in {dir_path}")

    # all files but the last must verify cleanly
    crc = 0
    for pos, index in enumerate(indexes[:-1]):
        path = os.path.join(dir_path, _file_name(index))
        r = LogRecordReader(path)
        if pos > 0 and r.crc != crc:
            r.close()
            raise CorruptWALError(f"wal: anchor CRC mismatch in {path}")
        try:
            while True:
                r.read()
        except EOFError:
            pass
        crc = r.crc
        r.close()

    last = os.path.join(dir_path, _file_name(indexes[-1]))
    shutil.copyfile(last, last + ".copy")
    log.infof("Write-Ahead-Log made a copy of the last file: %s", last + ".copy")

    try:
        r = LogRecordReader(last)
    except WALError:
        os.remove(last)
        log.warnf("Write-Ahead-Log DELETED the last file (a copy was saved): %s", last)
        return
    offset = r.tell()
    while True:
        try:
            r.read()
            offset = r.tell()
        except EOFError:
            r.close()
            return  # clean EOF — nothing to repair
        except WALError:
            r.close()
            break
    with open(last, "r+b") as f:
        f.truncate(offset)
        f.flush()
        os.fsync(f.fileno())
    log.infof("Write-Ahead-Log successfully repaired the last file: %s", last)


def initialize_and_read_all(
    dir_path: str,
    logger: Optional[Logger] = None,
    file_size_bytes: int = DEFAULT_FILE_SIZE_BYTES,
    metrics: Optional[WALMetrics] = None,
) -> tuple[WriteAheadLogFile, list[bytes]]:
    """Create-or-open + auto-repair convenience (writeaheadlog.go:760-806)."""
    log = logger or StdLogger("smartbft.wal")
    if not _dir_wal_indexes(dir_path):
        w = create(dir_path, log, file_size_bytes, metrics)
        return w, []
    try:
        w = open_wal(dir_path, log, file_size_bytes, metrics)
        items = w.read_all()
        return w, items
    except RepairableWALError:
        log.warnf("Write-Ahead-Log attempting repair of %s", dir_path)
        repair(dir_path, log)
        if not _dir_wal_indexes(dir_path):
            # repair deleted the only (anchor-less) file — start fresh
            w = create(dir_path, log, file_size_bytes, metrics)
            return w, []
        w = open_wal(dir_path, log, file_size_bytes, metrics)
        items = w.read_all()
        return w, items
