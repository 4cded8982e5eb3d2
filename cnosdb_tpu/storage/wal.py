"""Write-ahead log — also the replication log.

Mirrors the reference's WAL-is-the-raft-log design (tskv/src/wal/
wal_store.rs:22-150 RaftEntryStorage over wal files; recover :429): one WAL
per vnode, made of numbered segment files of CRC records. Entries carry a
monotonically increasing sequence; recovery replays entries with
seq > flushed watermark. The replication layer stores its raft entries
through this same API, so there is exactly one durable log per vnode.

Entry record layout (inside a record-file payload):
    seq u64 | entry_type u8 | term u64 | ts u64 | data...

`term` is 0 for unreplicated vnodes; the raft layer stores its term here so
one durable log serves both recovery paths. `ts` is the wall-clock append
time in ns — the disaster-recovery plane (storage/backup.py) replays
archived entries "up to TIMESTAMP T" by this stamp, so it rides every
entry rather than living in a side channel.
"""
from __future__ import annotations

import os
import re
import struct
import time
from dataclasses import dataclass

from .. import faults
from ..utils import stages
from ..errors import WalError
from .record_file import FILE_MAGIC, RecordReader, RecordWriter

SEGMENT_PATTERN = re.compile(r"^wal_(\d{10})\.log$")
_ENTRY_HDR = struct.Struct("<QBQQ")

faults.register_point("wal.append", __name__,
                      desc="WAL entry append (torn-tail site)")
faults.register_point("wal.sync", __name__, desc="WAL fsync")
faults.register_point("wal.roll", __name__, desc="WAL segment roll")


class WalEntryType:
    WRITE = 1          # point write batch
    DELETE_TABLE = 2
    DELETE_SERIES = 3
    UPDATE_TAGS = 4
    RAFT_BLANK = 5     # raft no-op/membership entries
    RAFT_MEMBERSHIP = 6
    DELETE_TIME_RANGE = 7


@dataclass
class WalEntry:
    seq: int
    entry_type: int
    data: bytes
    term: int = 0
    ts: int = 0          # wall-clock append time, ns (PITR replay bound)

    def encode(self) -> bytes:
        return _ENTRY_HDR.pack(self.seq, self.entry_type, self.term,
                               self.ts) + self.data

    @classmethod
    def decode(cls, payload: bytes) -> "WalEntry":
        seq, et, term, ts = _ENTRY_HDR.unpack_from(payload, 0)
        return cls(seq, et, payload[_ENTRY_HDR.size:], term, ts)


class Wal:
    """Segmented WAL for one vnode."""

    def __init__(self, dir_path: str, max_segment_size: int = 64 * 1024 * 1024,
                 sync_on_append: bool = False):
        self.dir = dir_path
        self.max_segment_size = max_segment_size
        self.sync_on_append = sync_on_append
        os.makedirs(dir_path, exist_ok=True)
        self._segments: list[int] = self._list_segments()
        self._next_seq = 1
        self._min_seq = 1
        self._writer: RecordWriter | None = None
        self.purge_listeners: list = []  # called with (seq) after purge_to
        # DR hooks (storage/backup.py): seal_listeners fire with the
        # sealed segment id after every roll (archive trigger);
        # archive_fence(seg_id)->bool gates purge_to so GC can never
        # outrun the archived watermark. Both default to seed behavior.
        self.seal_listeners: list = []
        self.archive_fence = None
        if self._segments:
            entries = list(self.replay())
            if entries:
                self._min_seq = entries[0].seq
                self._next_seq = entries[-1].seq + 1
        # Sequences must never restart below a previously handed-out seq even
        # when every segment holding them has been purged (roll + purge_to can
        # leave only an empty active segment). A durable tail marker records
        # the high-water next_seq; on open we take the max of replayed tail
        # and marker so post-restart appends stay above the flushed watermark.
        marker = self._read_tail_marker()
        if marker > self._next_seq:
            self._next_seq = marker
            self._min_seq = max(self._min_seq, marker)
        self._open_writer()

    # -- tail marker ------------------------------------------------------
    @property
    def _tail_path(self) -> str:
        return os.path.join(self.dir, "wal.tail")

    def _read_tail_marker(self) -> int:
        try:
            with open(self._tail_path, "rb") as f:
                return struct.unpack("<Q", f.read(8))[0]
        except Exception:
            return 1

    def _persist_tail_marker(self):
        tmp = self._tail_path + ".tmp"
        with open(tmp, "wb") as f:
            f.write(struct.pack("<Q", self._next_seq))
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, self._tail_path)

    # -- segments --------------------------------------------------------
    def _list_segments(self) -> list[int]:
        out = []
        for name in os.listdir(self.dir):
            m = SEGMENT_PATTERN.match(name)
            if m:
                out.append(int(m.group(1)))
        return sorted(out)

    def _seg_path(self, seg_id: int) -> str:
        return os.path.join(self.dir, f"wal_{seg_id:010d}.log")

    def _open_writer(self):
        if not self._segments:
            self._segments.append(0)
        self._writer = RecordWriter(self._seg_path(self._segments[-1]))

    def _roll(self):
        if faults.ENABLED:
            faults.fire("wal.roll", dir=self.dir)
        self._writer.close()
        self._persist_tail_marker()
        sealed = self._segments[-1]
        self._segments.append(sealed + 1)
        self._writer = RecordWriter(self._seg_path(self._segments[-1]))
        # archive trigger: a failed upload must never fail the write path
        # (catch_up() re-archives later); crash-action faults still fire
        for cb in self.seal_listeners:
            try:
                cb(sealed)
            except Exception:
                stages.count_error("swallow.wal.seal_listener")

    def seal_active(self) -> int | None:
        """Force-roll the active segment so its entries become archivable
        (BACKUP's consistency cut). → sealed segment id, or None when the
        active segment holds no entries."""
        if self._writer is None or self._writer.size <= len(FILE_MAGIC):
            return None
        sealed = self._segments[-1]
        self._roll()
        return sealed

    # -- append/replay ---------------------------------------------------
    @property
    def next_seq(self) -> int:
        return self._next_seq

    @property
    def min_seq(self) -> int:
        return self._min_seq

    def append(self, entry_type: int, data: bytes, seq: int | None = None,
               term: int = 0) -> int:
        """Append one entry; returns its seq. Explicit `seq` is used by the
        replication layer (raft log index); it must be >= current tail."""
        if seq is None:
            seq = self._next_seq
        elif seq < self._next_seq:
            # raft log truncation-on-conflict: drop tail entries >= seq first
            self.truncate_from(seq)
        if faults.ENABLED:
            faults.fire("wal.append", dir=self.dir, seq=seq,
                        entry_type=entry_type)
        e = WalEntry(seq, entry_type, data, term, time.time_ns())
        self._writer.append(e.encode())
        if self.sync_on_append:
            self._writer.sync()
        else:
            # the entry leaves the process before its write is
            # acknowledged: a kill -9 after the 200 loses nothing
            self._writer.flush()
        self._next_seq = seq + 1
        if self._writer.size >= self.max_segment_size:
            self._roll()
        return seq

    def sync(self):
        if faults.ENABLED:
            faults.fire("wal.sync", dir=self.dir)
        if self._writer:
            self._writer.sync()

    def replay(self, from_seq: int = 0):
        """Yield entries with seq >= from_seq in log order.

        Later duplicates of a seq win (post-truncation re-appends)."""
        entries: dict[int, WalEntry] = {}
        tail_seq = 0
        for seg in self._list_segments():
            try:
                rr = RecordReader(self._seg_path(seg))
            except Exception:
                continue
            for payload in rr:
                e = WalEntry.decode(payload)
                if e.seq <= tail_seq:
                    # append at seq s after truncation invalidates all > s
                    # (rare path: only on post-conflict rewrites)
                    entries = {k: v for k, v in entries.items() if k < e.seq}
                entries[e.seq] = e
                tail_seq = e.seq
        for seq in sorted(entries):
            if seq >= from_seq:
                yield entries[seq]

    def truncate_from(self, seq: int):
        """Logical truncation of entries >= seq (raft conflict). Physical
        bytes stay; replay() honors the rewrite rule above."""
        if seq < self._min_seq:
            self._min_seq = seq
        self._next_seq = seq
        if self._read_tail_marker() > seq:
            self._persist_tail_marker()

    # -- GC --------------------------------------------------------------
    def purge_to(self, seq: int):
        """Drop whole segments whose entries are all < seq (post-flush GC,
        reference SnapshotPolicy purge multi_raft.rs:107-138)."""
        self._min_seq = max(self._min_seq, seq)
        self._persist_tail_marker()
        segs = self._list_segments()
        # Delete only segments provably below the watermark; unreadable
        # segments and everything after them are kept (log order matters),
        # as is the active segment. The archive fence additionally keeps
        # any segment not yet uploaded — and everything after it, since
        # deleting later segments around a retained one would tear the
        # archived log's order.
        for seg in segs[:-1]:
            if self.archive_fence is not None \
                    and not self._fence_allows(seg):
                break
            try:
                max_seq = 0
                for payload in RecordReader(self._seg_path(seg)):
                    max_seq = max(max_seq, WalEntry.decode(payload).seq)
            except Exception:
                break
            if max_seq >= seq:
                break
            os.unlink(self._seg_path(seg))
        for cb in self.purge_listeners:
            try:
                cb(seq)
            except Exception:
                stages.count_error("swallow.wal.purge_listener")

    def _fence_allows(self, seg: int) -> bool:
        """A fence that errors fails CLOSED (segment kept): dropping WAL
        bytes on an archiver hiccup is the exact data loss the fence
        exists to prevent."""
        try:
            return bool(self.archive_fence(seg))
        except Exception:
            stages.count_error("swallow.wal.archive_fence")
            return False

    def total_size(self) -> int:
        return sum(os.path.getsize(self._seg_path(s)) for s in self._list_segments())

    def close(self):
        if self._writer:
            self._writer.close()
            self._writer = None
            self._persist_tail_marker()
