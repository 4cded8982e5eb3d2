"""Version manifest: the durable record of which TSM files form a vnode.

Role-parity with the reference's Summary (tskv/src/tsfamily/
summary.rs:28-240) + Version/LevelInfo (version.rs, level_info.rs:16-65):
every flush/compaction appends a VersionEdit (files added/removed, flushed
WAL seq) to a CRC'd record file; on open the edits replay into a Version —
the immutable picture of 5 levels of column files (L0 = delta, overlapping;
L1-L4 non-overlapping, time-descending levels).
"""
from __future__ import annotations

import os
from dataclasses import dataclass, field

import msgpack

from ..errors import StorageError
from .record_file import RecordReader, RecordWriter
from .tsm import TsmReader

MAX_LEVEL = 4  # levels 0..4 (reference kv_option.rs:56-59)


@dataclass
class FileMeta:
    file_id: int
    level: int
    min_ts: int
    max_ts: int
    size: int
    series_count: int

    def to_list(self):
        return [self.file_id, self.level, self.min_ts, self.max_ts,
                self.size, self.series_count]

    @classmethod
    def from_list(cls, l):
        return cls(*l)

    def overlaps(self, min_ts: int, max_ts: int) -> bool:
        return self.min_ts <= max_ts and min_ts <= self.max_ts


@dataclass
class VersionEdit:
    """One atomic manifest mutation (reference summary.rs VersionEdit)."""

    add_files: list[FileMeta] = field(default_factory=list)
    del_files: list[int] = field(default_factory=list)
    flushed_seq: int | None = None

    def encode(self) -> bytes:
        return msgpack.packb([
            [f.to_list() for f in self.add_files],
            self.del_files,
            self.flushed_seq,
        ])

    @classmethod
    def decode(cls, data: bytes) -> "VersionEdit":
        add, rm, seq = msgpack.unpackb(data, raw=False)
        return cls([FileMeta.from_list(f) for f in add], list(rm), seq)


class Version:
    """Immutable-ish view: levels of files + flushed seq + open readers.

    Readers are opened lazily and cached per file (reference version.rs
    TsmReader LRU cache).
    """

    def __init__(self, dir_path: str):
        self.dir = dir_path
        self.levels: list[dict[int, FileMeta]] = [dict() for _ in range(MAX_LEVEL + 1)]
        self.flushed_seq = 0
        self.max_file_id = 0
        self._readers: dict[int, TsmReader] = {}
        self._tombstones: dict[int, "TsmTombstone"] = {}

    # -- mutation (only via Summary.apply) -------------------------------
    def _apply(self, edit: VersionEdit):
        # `levels` is replaced, never mutated: a scan that took the list
        # (VnodeStorage.cut) keeps the file set it took. A dropped file's
        # reader is forgotten, not closed — a scan may be inside it; the
        # mmap and the descriptor go with the last reference, and the
        # unlinked file with them
        levels = [dict(lvl) for lvl in self.levels]
        moved = {fm.file_id for fm in edit.add_files}
        for fid in edit.del_files:
            for lvl in levels:
                lvl.pop(fid, None)
            self._tombstones.pop(fid, None)
            if fid not in moved:    # a promotion: the same bytes, one
                self._readers.pop(fid, None)    # level up, stay open
        for fm in edit.add_files:
            levels[fm.level][fm.file_id] = fm
            self.max_file_id = max(self.max_file_id, fm.file_id)
        self.levels = levels
        if edit.flushed_seq is not None:
            self.flushed_seq = max(self.flushed_seq, edit.flushed_seq)

    # -- queries ---------------------------------------------------------
    def file_path(self, fm: FileMeta) -> str:
        sub = "delta" if fm.level == 0 else "tsm"
        return os.path.join(self.dir, sub, f"_{fm.file_id:06d}.tsm")

    def all_files(self) -> list[FileMeta]:
        out = []
        for lvl in self.levels:
            out.extend(lvl.values())
        return out

    def _current(self, fm: FileMeta) -> FileMeta:
        """A scan's cut names a file by the level it had at the cut; a
        promotion since has moved it (same id, same bytes): → the file
        where it is now."""
        return next((lvl[fm.file_id] for lvl in self.levels
                     if fm.file_id in lvl), fm)

    def reader(self, fm: FileMeta) -> TsmReader:
        r = self._readers.get(fm.file_id)
        if r is None:
            # the single reader chokepoint: files recorded in the vnode's
            # cold registry (storage/tiering.py cold.json) open as cold
            # readers — sidecar metadata locally, page bytes via ranged
            # object-store GETs — so every scan/decode lane above stays
            # tier-transparent
            from . import tiering

            fm = self._current(fm)
            entry = tiering.cold_entry(self.dir, fm.file_id)
            if entry is not None:
                r = tiering.open_cold_reader(self.file_path(fm), entry)
            else:
                r = TsmReader(self.file_path(fm))
            self._readers[fm.file_id] = r
        return r

    def drop_reader(self, fid: int) -> None:
        """Close and forget one cached reader (tier/rehydrate flips the
        backing store; the next `reader()` call reopens the right kind)."""
        r = self._readers.pop(fid, None)
        if r:
            r.close()

    def tombstone(self, fm: FileMeta):
        """Cached per-file tombstone; all tombstone writes must go through
        this accessor so readers observe them without re-parsing disk."""
        from .tombstone import TsmTombstone

        tb = self._tombstones.get(fm.file_id)
        if tb is None:
            tb = self._tombstones[fm.file_id] = TsmTombstone(
                self.file_path(self._current(fm)))
        return tb

    def level_size(self, level: int) -> int:
        return sum(f.size for f in self.levels[level].values())

    def close(self):
        for r in self._readers.values():
            r.close()
        self._readers.clear()


class Summary:
    """The manifest writer/recoverer for one vnode."""

    def __init__(self, dir_path: str):
        self.dir = dir_path
        os.makedirs(dir_path, exist_ok=True)
        os.makedirs(os.path.join(dir_path, "delta"), exist_ok=True)
        os.makedirs(os.path.join(dir_path, "tsm"), exist_ok=True)
        self.path = os.path.join(dir_path, "summary")
        self.version = Version(dir_path)
        if os.path.exists(self.path):
            for payload in RecordReader(self.path):
                self.version._apply(VersionEdit.decode(payload))
        self._writer = RecordWriter(self.path)
        self._edit_count = 0

    def apply(self, edit: VersionEdit, sync: bool = True):
        """Durably record an edit, then mutate the live version
        (reference summary.rs:134 apply_version_edit)."""
        self.record(edit, sync)
        self.install(edit)

    def record(self, edit: VersionEdit, sync: bool = True):
        """The durable half of apply(): every recorded edit is installed
        next (flush does it under the vnode's cut lock, with no I/O)."""
        if self._edit_count >= 512:
            self._rewrite()
        self._writer.append(edit.encode())
        if sync:
            self._writer.sync()
        self._edit_count += 1

    def install(self, edit: VersionEdit):
        self.version._apply(edit)

    def _rewrite(self):
        """Compact the manifest to a single snapshot edit (reference
        rewrite-on-open summary.rs)."""
        self._writer.close()
        snapshot = VersionEdit(add_files=self.version.all_files(),
                               flushed_seq=self.version.flushed_seq)
        tmp = self.path + ".tmp"
        w = RecordWriter(tmp)
        w.append(snapshot.encode())
        w.close()
        os.replace(tmp, self.path)
        self._writer = RecordWriter(self.path)
        self._edit_count = 0

    def next_file_id(self) -> int:
        self.version.max_file_id += 1
        return self.version.max_file_id

    def close(self):
        self._writer.close()
        self.version.close()


def delete_unreferenced_files(version: Version):
    """GC: remove tsm files on disk not referenced by the version."""
    live = {version.file_path(f) for f in version.all_files()}
    for sub in ("delta", "tsm"):
        d = os.path.join(version.dir, sub)
        if not os.path.isdir(d):
            continue
        for name in os.listdir(d):
            p = os.path.join(d, name)
            if p not in live and name.endswith(".tsm"):
                try:
                    os.unlink(p)
                except OSError:
                    pass
