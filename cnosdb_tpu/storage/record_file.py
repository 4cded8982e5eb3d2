"""Generic CRC'd append-only record file.

Role-parity with reference tskv/src/record_file/ (format doc mod.rs:1-34):
the common container under the WAL and the Summary manifest. A file is
[8B magic header] then records of [len u32 | crc32 u32 | payload]. Reads
stop cleanly at truncation or corruption (torn tail after crash), which is
exactly the recovery contract the WAL needs.
"""
from __future__ import annotations

import os
import struct
import zlib
from typing import Iterator

from .. import faults
from ..errors import StorageError

FILE_MAGIC = b"CNOSREC1"
_HDR = struct.Struct("<II")

faults.register_point("record.append", __name__,
                      desc="record-file append (torn-write site)")
faults.register_point("record.sync", __name__,
                      desc="record-file fsync")


def _valid_prefix_len(path: str) -> int:
    """Byte length of the longest valid [magic + records] prefix, 0 when
    the magic itself is unreadable."""
    with open(path, "rb") as f:
        buf = f.read()
    if buf[:len(FILE_MAGIC)] != FILE_MAGIC:
        return 0
    off = len(FILE_MAGIC)
    n = len(buf)
    while off + _HDR.size <= n:
        ln, crc = _HDR.unpack_from(buf, off)
        end = off + _HDR.size + ln
        if end > n or zlib.crc32(buf[off + _HDR.size:end]) != crc:
            break
        off = end
    return off


class RecordWriter:
    def __init__(self, path: str):
        self.path = path
        exists = os.path.exists(path) and os.path.getsize(path) >= len(FILE_MAGIC)
        if exists:
            # Crash recovery: a torn tail (partial record from an
            # interrupted write) must be truncated BEFORE appending —
            # readers stop at the tear, so anything appended after it
            # would be durably written yet invisible to replay.
            valid = _valid_prefix_len(path)
            if valid and valid < os.path.getsize(path):
                with open(path, "r+b") as f:
                    f.truncate(valid)
        elif os.path.exists(path):
            # shorter than the magic: a segment creation that died
            # mid-header — restart it from scratch rather than appending
            # the magic after garbage
            with open(path, "r+b") as f:
                f.truncate(0)
        self._f = open(path, "ab")
        if not exists:
            self._f.write(FILE_MAGIC)
            self._f.flush()

    def append(self, payload: bytes) -> int:
        """Append one record, return its file offset."""
        off = self._f.tell()
        rec = _HDR.pack(len(payload), zlib.crc32(payload)) + payload
        if faults.ENABLED:
            hit = faults.fire("record.append", path=self.path)
            if hit and hit[0] == "torn":
                # crash mid-write: leave a truncated record on disk and die
                # the way the kernel would — readers must stop at the tear
                cut = min(int(hit[1]) if hit[1] else max(1, len(rec) // 2),
                          len(rec))
                self._f.write(rec[:len(rec) - cut])
                self._f.flush()
                raise faults.FaultInjected(
                    f"injected torn write ({cut}B short) at {self.path}")
        self._f.write(rec)
        return off

    def flush(self):
        """Hand every appended byte to the OS: what a SIGKILL of this
        process cannot take back (no fsync: a power loss still can)."""
        self._f.flush()

    def sync(self):
        if faults.ENABLED:
            faults.fire("record.sync", path=self.path)
        self._f.flush()
        os.fsync(self._f.fileno())

    @property
    def size(self) -> int:
        self._f.flush()
        return self._f.tell()

    def close(self):
        try:
            self.sync()
        finally:
            self._f.close()


def iter_records(buf: bytes) -> Iterator[bytes]:
    """Yield record payloads from an in-memory record-file image with the
    same stop-at-tear/corruption semantics as RecordReader. The DR plane
    (storage/backup.py) decodes archived WAL segments straight from
    object-store bytes through this."""
    off = len(FILE_MAGIC)
    n = len(buf)
    while off + _HDR.size <= n:
        ln, crc = _HDR.unpack_from(buf, off)
        start = off + _HDR.size
        end = start + ln
        if end > n:
            break  # torn tail
        payload = buf[start:end]
        if zlib.crc32(payload) != crc:
            break  # corruption: stop replay here
        yield payload
        off = end


class RecordReader:
    def __init__(self, path: str):
        self.path = path
        with open(path, "rb") as f:
            self._buf = f.read()
        if self._buf[:len(FILE_MAGIC)] != FILE_MAGIC:
            raise StorageError("bad record file magic", path=path)

    def __iter__(self) -> Iterator[bytes]:
        yield from iter_records(self._buf)

    def records(self) -> list[bytes]:
        return list(self)
