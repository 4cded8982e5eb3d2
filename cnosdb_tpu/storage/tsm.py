"""TSM file format: the immutable columnar store.

Role-parity with the reference's TSM v2 (tskv/src/tsm/writer.rs:40-540,
reader.rs, page.rs, chunk.rs, chunk_group.rs, footer.rs): a file holds, per
table (chunk group), per series (chunk), per column, encoded pages; the
footer carries a series-id bloom filter and the meta tree offset; pages
carry null bitsets and min/max/sum/count statistics used for pruning and
for metadata-only aggregates (reference pushdown_agg_reader.rs answers
COUNT from page meta without decoding).

The byte layout is a fresh design (not the reference's): meta sections are
msgpack (fast C codec), pages are [null bitset][codec block] with crc32,
and chunks keep whole-series column runs contiguous so a scan materializes
large numpy arrays per column — the shape the TPU staging path wants.

Layout:
    [magic u32 | version u8]
    page data ...                         (sequential, crc'd)
    meta: msgpack chunk tree              (zstd)
    bloom: series-id bloom bits
    footer (fixed 64B): meta_off u64 | meta_len u64 | bloom_off u64 |
        bloom_len u64 | min_ts i64 | max_ts i64 | series_count u32 |
        crc u32 | magic u32 | version u8 | pad
"""
from __future__ import annotations

import os
import struct
import zlib
from dataclasses import dataclass, field

import msgpack
import numpy as np

from .. import faults
from ..errors import TsmError, ChecksumMismatch
from ..utils.zstd_compat import zstandard
from ..models.codec import Encoding
from ..models.schema import ValueType
from ..models.strcol import DictArray
from ..utils import stages
from ..utils.bloom import BloomFilter
from . import codecs

MAGIC = 0x7C05DB01
VERSION = 1
FOOTER_SIZE = 64

faults.register_point("tsm.write", __name__,
                      desc="sealed TSM file finalize (corrupt-at-rest site)")

# thread-local contexts (parallel flush/compaction writers + query-pool
# readers; zstd contexts are not safe for concurrent use)
_ZC = codecs._TlsZstd(1)
_ZD = codecs._TlsZstd(None)


def _string_signature(dense) -> bytes | None:
    """Trigram page-skip signature for one string page (flush and
    compaction both land here via TsmWriter.write_series). Advisory:
    any failure yields None (page always admits), never a failed seal.
    Lazy import — strkernels lives in ops/, whose package init pulls jax;
    host-only storage paths must not pay that unless a string page is
    actually sealed."""
    try:
        from ..ops import strkernels

        if isinstance(dense, DictArray):
            uniques = dense.values[np.unique(dense.codes)]
        else:
            uniques = {v for v in dense if isinstance(v, str)}
        return strkernels.build_page_signature(uniques)
    except Exception:
        return None


# ---------------------------------------------------------------------------
# metadata model
# ---------------------------------------------------------------------------
@dataclass
class PageMeta:
    offset: int
    size: int
    n_rows: int           # logical rows in the page (incl. nulls)
    n_values: int         # non-null values
    value_type: int       # ValueType
    encoding: int         # Encoding id actually used
    min_ts: int
    max_ts: int
    stat_min: float | int | None = None
    stat_max: float | int | None = None
    stat_sum: float | int | None = None
    # stats format era. 0 = legacy writers whose float stats excluded ±inf
    # (a page holding inf rows could carry a finite-only interval); 1 =
    # ±inf-inclusive stats. Predicate pruning (scan._page_admits) must not
    # prune float pages below version 1 — their interval may lie.
    stats_version: int = 0
    # string pages: trigram bloom signature over the page's distinct
    # values (ops/strkernels.build_page_signature). None = pre-signature
    # file (never prunes); b"" = page provably holds no 3-byte substring.
    ngram: bytes | None = None

    def to_list(self):
        return [self.offset, self.size, self.n_rows, self.n_values,
                self.value_type, self.encoding, self.min_ts, self.max_ts,
                self.stat_min, self.stat_max, self.stat_sum,
                self.stats_version, self.ngram]

    @classmethod
    def from_list(cls, l):
        # length-tolerant: files sealed before stats_version existed carry
        # 11-element page lists and decode with the legacy default of 0
        return cls(*l)


@dataclass
class ColumnMeta:
    column_id: int
    name: str
    pages: list[PageMeta] = field(default_factory=list)

    def to_list(self):
        return [self.column_id, self.name, [p.to_list() for p in self.pages]]

    @classmethod
    def from_list(cls, l):
        return cls(l[0], l[1], [PageMeta.from_list(p) for p in l[2]])


@dataclass
class ChunkMeta:
    """All pages of one series (reference chunk.rs)."""

    series_id: int
    n_rows: int
    min_ts: int
    max_ts: int
    time_pages: list[PageMeta] = field(default_factory=list)
    columns: list[ColumnMeta] = field(default_factory=list)

    def column(self, name: str) -> ColumnMeta | None:
        for c in self.columns:
            if c.name == name:
                return c
        return None

    def to_list(self):
        return [self.series_id, self.n_rows, self.min_ts, self.max_ts,
                [p.to_list() for p in self.time_pages],
                [c.to_list() for c in self.columns]]

    @classmethod
    def from_list(cls, l):
        return cls(l[0], l[1], l[2], l[3],
                   [PageMeta.from_list(p) for p in l[4]],
                   [ColumnMeta.from_list(c) for c in l[5]])


@dataclass
class ChunkGroupMeta:
    """All chunks of one table (reference chunk_group.rs)."""

    table: str
    chunks: dict[int, ChunkMeta] = field(default_factory=dict)

    def to_list(self):
        return [self.table, [c.to_list() for c in self.chunks.values()]]

    @classmethod
    def from_list(cls, l):
        cm = {c[0]: ChunkMeta.from_list(c) for c in l[1]}
        return cls(l[0], cm)


# ---------------------------------------------------------------------------
# page index: the same metadata, held a column at a time
# ---------------------------------------------------------------------------
def ranges(lo: np.ndarray, n: np.ndarray) -> np.ndarray:
    """→ arange(lo[0], lo[0] + n[0]), arange(lo[1], lo[1] + n[1]), … as
    one int64 array."""
    ends = np.cumsum(n)
    return np.arange(int(ends[-1]) if len(ends) else 0, dtype=np.int64) \
        + np.repeat(lo - (ends - n), n)


def _desc_rows(pages: list) -> np.ndarray:
    """→ i64 [len(pages), 6], a page's [offset, size, 0, n_rows, 0,
    n_values]: native.decode_pages' descriptor less out_off and kind."""
    return np.array([(p.offset, p.size, 0, p.n_rows, 0, p.n_values)
                     for p in pages], dtype=np.int64).reshape(-1, 6)


class ColumnIndex:
    """One column of a (file, table) across the file's chunks. Row p of
    `desc` is the column's page beside time page p — an aligned chunk's
    pages stand one to one beside its time pages. Where a chunk lacks
    the column (or is not aligned) `present` is False, its rows are zero
    and its `vt0` is -1."""

    __slots__ = ("present", "everywhere", "desc", "metas", "vt0", "vt_all",
                 "encodings")

    def __init__(self, present, desc, metas, vt0, vt_all, encodings):
        self.present = present          # bool [C]
        self.everywhere = bool(present.all())
        self.desc = desc                # i64 [P, 6], see _desc_rows
        self.metas = metas              # ColumnMeta | None a chunk
        self.vt0 = vt0                  # i64 [C]: type of the first page
        self.vt_all = vt_all            # the one type of every page | None
        self.encodings = encodings      # frozenset of the pages' encodings

    @classmethod
    def of(cls, parts: list, index: "PageIndex") -> "ColumnIndex":
        """`parts`: (chunk row, ColumnMeta) of every chunk holding it."""
        n_chunks = len(index.sids)
        rows = np.fromiter((ci for ci, _c in parts), dtype=np.int64,
                           count=len(parts))
        present = np.zeros(n_chunks, dtype=bool)
        present[rows] = True
        metas = [None] * n_chunks
        for ci, c in parts:
            metas[ci] = c
        pages = [p for _ci, c in parts for p in c.pages]
        lo = index.page_lo[rows]
        desc = np.zeros((len(index.time), 6), dtype=np.int64)
        desc[ranges(lo, index.page_lo[rows + 1] - lo)] = _desc_rows(pages)
        vts = np.fromiter((p.value_type for p in pages), dtype=np.int64,
                          count=len(pages))
        vt0 = np.full(n_chunks, -1, dtype=np.int64)
        vt0[rows] = [c.pages[0].value_type for _ci, c in parts]
        return cls(present, desc, metas, vt0,
                   int(vts[0]) if (vts == vts[0]).all() else None,
                   frozenset(p.encoding for p in pages))

    @classmethod
    def first_of(cls, cols: list, index: "PageIndex") -> "ColumnIndex":
        """→ a chunk's column is the first of `cols` the chunk holds."""
        present = np.zeros(len(index.sids), dtype=bool)
        desc = np.zeros_like(cols[0].desc)
        metas = [None] * len(present)
        vt0 = np.full(len(present), -1, dtype=np.int64)
        n_pages = np.diff(index.page_lo)
        for col in cols:
            take = col.present & ~present
            pages = np.repeat(take, n_pages)
            desc[pages] = col.desc[pages]
            vt0[take] = col.vt0[take]
            for ci in np.flatnonzero(take).tolist():
                metas[ci] = col.metas[ci]
            present |= take
        vts = {col.vt_all for col in cols}
        return cls(present, desc, metas, vt0,
                   vts.pop() if len(vts) == 1 else None,
                   frozenset().union(*(col.encodings for col in cols)))


class PageIndex:
    """The chunk and page metadata of one (file, table) as arrays, built
    once for the life of the reader (a TSM file is immutable) so that a
    scan plans a file with array operations, not chunk by chunk and page
    by page. Chunks stand in series-id order; a chunk's time pages are
    rows page_lo[c]:page_lo[c + 1] of `time`, `t_min`, `t_max` and of
    every column's `desc`. ChunkMeta / PageMeta stay what every other
    reader of the metadata uses; `chunks` leads back to them."""

    __slots__ = ("sids", "chunks", "min_ts", "max_ts", "page_lo", "aligned",
                 "all_aligned", "single_pages", "time", "t_min", "t_max",
                 "_sids_end",
                 "_columns", "_resolved", "__weakref__")

    def __init__(self, group: ChunkGroupMeta):
        chunks = sorted(group.chunks.values(), key=lambda cm: cm.series_id)
        n = len(chunks)
        self.chunks = chunks
        self.sids = np.fromiter((cm.series_id for cm in chunks),
                                dtype=np.uint64, count=n)
        # searchsorted answers n for an id past the last: a row to read
        self._sids_end = np.append(self.sids, self.sids[-1:])
        self.min_ts = np.fromiter((cm.min_ts for cm in chunks),
                                  dtype=np.int64, count=n)
        self.max_ts = np.fromiter((cm.max_ts for cm in chunks),
                                  dtype=np.int64, count=n)
        self.page_lo = np.zeros(n + 1, dtype=np.int64)
        np.cumsum([len(cm.time_pages) for cm in chunks],
                  out=self.page_lo[1:])
        tps = [p for cm in chunks for p in cm.time_pages]
        self.single_pages = len(tps) == n and all(
            len(cm.time_pages) == 1 for cm in chunks)
        self.time = _desc_rows(tps)
        self.t_min = np.fromiter((p.min_ts for p in tps), dtype=np.int64,
                                 count=len(tps))
        self.t_max = np.fromiter((p.max_ts for p in tps), dtype=np.int64,
                                 count=len(tps))
        # every column's pages stand one to one beside the time pages
        # (what the writer produces; checked once here, not a request)
        self.aligned = np.fromiter(
            (all(len(c.pages) == len(cm.time_pages)
                 and all(cp.n_rows == tp.n_rows
                         for cp, tp in zip(c.pages, cm.time_pages))
                 for c in cm.columns) for cm in chunks),
            dtype=bool, count=n)
        self.all_aligned = bool(self.aligned.all())
        parts: dict[tuple, list] = {}
        for ci, cm in enumerate(chunks):
            if not self.aligned[ci]:
                continue
            for c in cm.columns:
                held = parts.setdefault((c.column_id, c.name), [])
                if c.pages and not (held and held[-1][0] == ci):
                    held.append((ci, c))
        self._columns = {key: ColumnIndex.of(held, self)
                         for key, held in parts.items() if held}
        self._resolved: dict[tuple, ColumnIndex | None] = {}

    def rows_of(self, series_ids: np.ndarray):
        """→ (positions in `series_ids` of the series with a chunk here,
        those chunks' rows), positions ascending."""
        at = np.searchsorted(self.sids, series_ids)
        found = np.flatnonzero(self._sids_end[at] == series_ids)
        return found, at[found]

    def column(self, key: tuple):
        """→ the ColumnIndex of one query column, `key` its (column id |
        None, (name, *prior names)); None where no chunk holds it. A
        chunk's column is the one carrying the id, else the first of the
        names a chunk column bears — one WITHOUT an id where the query
        column's is known: a chunk column carrying another id is
        provably another (renamed or dropped) column, even if its name
        matches. Resolved once a (file, table), not once a chunk."""
        try:
            return self._resolved[key]
        except KeyError:
            column_id, names = key
        cols = [col for (cid, _nm), col in self._columns.items()
                if cid and cid == column_id]
        for name in names:
            cols += [col for (cid, nm), col in self._columns.items()
                     if nm == name and (column_id is None or not cid)
                     and not any(col is c for c in cols)]
        got = None if not cols else cols[0] if len(cols) == 1 \
            else ColumnIndex.first_of(cols, self)
        self._resolved[key] = got
        return got


# ---------------------------------------------------------------------------
# stats
# ---------------------------------------------------------------------------
def _compute_stats(values: np.ndarray, vt: ValueType):
    if len(values) == 0:
        return None, None, None
    if vt == ValueType.FLOAT:
        # NaNs are excluded (they satisfy no comparison, and would poison
        # the interval) but ±inf MUST be included: predicate page-pruning
        # (scan._admit_pages) drops pages whose [min, max] cannot match,
        # and an inf row outside a finite-only interval does match
        nonnan = values[~np.isnan(values)]
        if len(nonnan) == 0:
            return None, None, None
        return float(nonnan.min()), float(nonnan.max()), float(nonnan.sum())
    if vt in (ValueType.INTEGER, ValueType.UNSIGNED):
        return int(values.min()), int(values.max()), int(values.sum())
    if vt == ValueType.BOOLEAN:
        return bool(values.min()), bool(values.max()), int(values.sum())
    return None, None, None  # strings: no numeric stats


# ---------------------------------------------------------------------------
# writer
# ---------------------------------------------------------------------------
class TsmWriter:
    """Streams series chunks into a TSM file; finish() seals meta+footer.

    Mirrors reference TsmWriter::write_record_batch/finish
    (tsm/writer.rs:249,503).
    """

    def __init__(self, path: str, max_page_rows: int = 256 * 1024):
        self.path = path
        self.max_page_rows = max_page_rows
        self._f = open(path + ".tmp", "wb")
        self._f.write(struct.pack("<IB", MAGIC, VERSION))
        self._off = self._f.tell()
        self._groups: dict[str, ChunkGroupMeta] = {}
        self._bloom = BloomFilter()
        self._min_ts = 2**63 - 1
        self._max_ts = -(2**63)
        self._finished = False

    # -- core append -----------------------------------------------------
    def _write_page(self, payload: bytes) -> tuple[int, int]:
        crc = zlib.crc32(payload)
        data = struct.pack("<II", len(payload), crc) + payload
        off = self._off
        self._f.write(data)
        self._off += len(data)
        return off, len(data)

    def write_series(self, table: str, series_id: int,
                     timestamps: np.ndarray,
                     columns: dict[str, tuple[int, ValueType, Encoding, np.ndarray, np.ndarray | None]]):
        """Write one series chunk.

        columns: name → (column_id, value_type, encoding, values, null_mask)
        `values` has one entry per row; rows where null_mask is True are
        nulls (their value slot is ignored; dense packing happens here).
        Timestamps must be sorted ascending and deduplicated.
        """
        if self._finished:
            raise TsmError("writer already finished")
        n = len(timestamps)
        if n == 0:
            return
        ts = np.ascontiguousarray(timestamps, dtype=np.int64)
        if n > 1 and bool(np.any(np.diff(ts) < 0)):
            raise TsmError("timestamps not sorted", series=series_id)
        group = self._groups.setdefault(table, ChunkGroupMeta(table))
        if series_id in group.chunks:
            raise TsmError("duplicate series chunk", series=series_id)
        chunk = ChunkMeta(series_id, n, int(ts[0]), int(ts[-1]))
        self._min_ts = min(self._min_ts, int(ts[0]))
        self._max_ts = max(self._max_ts, int(ts[-1]))
        self._bloom.insert_u64(series_id)

        # time pages
        for s in range(0, n, self.max_page_rows):
            seg = ts[s:s + self.max_page_rows]
            blk = codecs.encode_timestamps(seg)
            off, size = self._write_page(blk)
            chunk.time_pages.append(PageMeta(
                off, size, len(seg), len(seg), int(ValueType.INTEGER),
                int(Encoding.DELTA_TS), int(seg[0]), int(seg[-1]),
                int(seg[0]), int(seg[-1]), None, stats_version=1))

        # field pages
        for name, (cid, vt, enc, values, null_mask) in columns.items():
            cm = ColumnMeta(cid, name)
            for s in range(0, n, self.max_page_rows):
                e = min(s + self.max_page_rows, n)
                seg_ts = ts[s:e]
                vals = values[s:e]
                if null_mask is not None:
                    nm = np.ascontiguousarray(null_mask[s:e], dtype=bool)
                    dense = vals[~nm] if isinstance(vals, (np.ndarray, DictArray)) \
                        else [v for v, m in zip(vals, nm) if not m]
                    bitset = np.packbits(nm).tobytes()
                    has_nulls = bool(nm.any())
                else:
                    nm = None
                    dense = vals
                    bitset = b""
                    has_nulls = False
                ngram = None
                if vt in (ValueType.STRING, ValueType.GEOMETRY):
                    smin = smax = ssum = None
                    if vt == ValueType.STRING:
                        ngram = _string_signature(dense)
                else:
                    dense = np.ascontiguousarray(dense)
                    smin, smax, ssum = _compute_stats(dense, vt)
                blk = codecs.encode(dense, vt, enc)
                payload = (struct.pack("<BI", 1 if has_nulls else 0, len(bitset))
                           + (bitset if has_nulls else b"") + blk)
                off, size = self._write_page(payload)
                nvals = len(dense)
                cm.pages.append(PageMeta(
                    off, size, e - s, nvals, int(vt), blk[0],
                    int(seg_ts[0]), int(seg_ts[-1]), smin, smax, ssum,
                    stats_version=1, ngram=ngram))
            chunk.columns.append(cm)
        group.chunks[series_id] = chunk

    # -- finish ----------------------------------------------------------
    def finish(self) -> "TsmFooter":
        if self._finished:
            raise TsmError("writer already finished")
        meta_raw = msgpack.packb([g.to_list() for g in self._groups.values()])
        meta = _ZC.compress(meta_raw)
        meta_off = self._off
        self._f.write(meta)
        bloom = self._bloom.to_bytes()
        bloom_off = meta_off + len(meta)
        self._f.write(bloom)
        series_count = sum(len(g.chunks) for g in self._groups.values())
        body = struct.pack("<QQQQqqI", meta_off, len(meta), bloom_off,
                           len(bloom), self._min_ts, self._max_ts, series_count)
        crc = zlib.crc32(body)
        footer = body + struct.pack("<II B", crc, MAGIC, VERSION)
        footer += b"\x00" * (FOOTER_SIZE - len(footer))
        self._f.write(footer)
        self._f.flush()
        os.fsync(self._f.fileno())
        self._f.close()
        os.replace(self.path + ".tmp", self.path)
        self._finished = True
        if faults.ENABLED:
            # silent-corruption model: flip bytes INSIDE the already-durable
            # page region (header/meta/footer stay intact, so the file opens
            # fine and the flip is only caught by a page-crc check)
            hit = faults.fire("tsm.write", path=self.path)
            if hit and hit[0] == "corrupt":
                faults.corrupt_file(self.path, int(hit[1] or 1),
                                    lo=5, hi=meta_off)
        return TsmFooter(meta_off, len(meta), bloom_off, len(bloom),
                         self._min_ts, self._max_ts, series_count)

    def abort(self):
        if not self._finished:
            self._f.close()
            try:
                os.unlink(self.path + ".tmp")
            except FileNotFoundError:
                pass


@dataclass
class TsmFooter:
    meta_off: int
    meta_len: int
    bloom_off: int
    bloom_len: int
    min_ts: int
    max_ts: int
    series_count: int


# ---------------------------------------------------------------------------
# reader
# ---------------------------------------------------------------------------
def parse_tail(tail, path: str, tail_off: int = 0):
    """Parse a TSM file's trailing metadata section (zstd-msgpack chunk
    meta + bloom + fixed footer) → (groups, bloom, footer).

    `tail` holds the file bytes from absolute offset `tail_off` to EOF —
    the whole mmap for the hot reader (tail_off=0), or just the sidecar
    tail for the cold tier (tail_off = footer.meta_off). Footer offsets
    are absolute file offsets, rebased here."""
    if len(tail) < FOOTER_SIZE:
        raise TsmError("file too small", path=path)
    footer_raw = tail[-FOOTER_SIZE:]
    body = footer_raw[:52]
    crc, fmagic, fver = struct.unpack_from("<IIB", footer_raw, 52)
    if fmagic != MAGIC:
        raise TsmError("bad footer magic", path=path)
    if zlib.crc32(body) != crc:
        raise ChecksumMismatch("footer crc", path=path)
    (meta_off, meta_len, bloom_off, bloom_len,
     min_ts, max_ts, series_count) = struct.unpack("<QQQQqqI", body)
    footer = TsmFooter(meta_off, meta_len, bloom_off, bloom_len,
                       min_ts, max_ts, series_count)
    lo = meta_off - tail_off
    if lo < 0 or bloom_off - tail_off < 0:
        raise TsmError("tail section does not cover meta", path=path)
    meta_raw = _ZD.decompress(tail[lo:lo + meta_len])
    groups: dict[str, ChunkGroupMeta] = {}
    for g in msgpack.unpackb(meta_raw, strict_map_key=False):
        cg = ChunkGroupMeta.from_list(g)
        groups[cg.table] = cg
    blo = bloom_off - tail_off
    bloom = BloomFilter.from_bytes(tail[blo:blo + bloom_len])
    return groups, bloom, footer


class TsmReader:
    """Random-access TSM reader (reference tsm/reader.rs:825).

    Loads footer + meta eagerly (small), pages lazily via one mmap'd file.
    """

    # storage/tiering.py's ColdTsmReader overrides this: scan routing uses
    # it to keep cold pages off the mmap-dependent native batch lane
    is_cold = False

    def __init__(self, path: str):
        self.path = path
        self._f = open(path, "rb")
        import mmap as _mmap

        self._buf = _mmap.mmap(self._f.fileno(), 0, access=_mmap.ACCESS_READ)
        if len(self._buf) < FOOTER_SIZE + 5:
            raise TsmError("file too small", path=path)
        magic, version = struct.unpack_from("<IB", self._buf, 0)
        if magic != MAGIC:
            raise TsmError("bad magic", path=path)
        self.groups, self.bloom, self.footer = parse_tail(self._buf, path)
        self._page_indexes: dict[str, PageIndex] = {}
        self.min_ts = self.footer.min_ts
        self.max_ts = self.footer.max_ts
        self.series_count = self.footer.series_count

    def close(self):
        self._buf_arr = None
        if not isinstance(self._buf, bytes):
            try:
                self._buf.close()
            except BufferError:
                # a lock-free concurrent scan still holds a buffer_array()
                # view; the mmap stays alive until that array drops and GC
                # reclaims it — never crash the closer (compaction's
                # VersionEdit apply closes readers of deleted files)
                pass
        self._f.close()
        self._buf = b""

    def buffer_array(self) -> np.ndarray:
        """Whole-file u8 view over the mmap (zero-copy) — the base pointer
        the native batch page decoder reads from."""
        arr = getattr(self, "_buf_arr", None)
        if arr is None:
            arr = self._buf_arr = np.frombuffer(self._buf, dtype=np.uint8)
        return arr

    # -- meta queries ----------------------------------------------------
    def tables(self) -> list[str]:
        return list(self.groups)

    def chunk(self, table: str, series_id: int) -> ChunkMeta | None:
        g = self.groups.get(table)
        return g.chunks.get(series_id) if g else None

    def series_ids(self, table: str) -> np.ndarray:
        g = self.groups.get(table)
        if not g:
            return np.empty(0, dtype=np.uint64)
        return np.fromiter(g.chunks.keys(), dtype=np.uint64, count=len(g.chunks))

    def page_index(self, table: str) -> PageIndex | None:
        """→ the table's PageIndex, None where the file holds no chunk of
        it. Built by the first scan that asks and kept as long as the
        reader: the file never changes. Two scans may build it at once;
        both build the same, and one of the two is kept."""
        index = self._page_indexes.get(table)
        built = index is None
        if built:
            g = self.groups.get(table)
            if g is None or not g.chunks:
                return None
            index = self._page_indexes.setdefault(table, PageIndex(g))
        # booked where it is found too: a warmed store reads 0, not nothing
        stages.count("scan_plan.index_builds", int(built))
        return index

    def maybe_contains_series(self, series_id: int) -> bool:
        return self.bloom.maybe_contains_u64(series_id)

    # -- page reads ------------------------------------------------------
    def _read_page(self, pm: PageMeta) -> bytes:
        raw = self._buf[pm.offset:pm.offset + pm.size]
        plen, crc = struct.unpack_from("<II", raw, 0)
        payload = raw[8:8 + plen]
        if zlib.crc32(payload) != crc:
            raise ChecksumMismatch("page crc", path=self.path, offset=pm.offset)
        return payload

    def read_time_page(self, pm: PageMeta) -> np.ndarray:
        return codecs.decode_timestamps(self._read_page(pm))

    def read_field_page(self, pm: PageMeta) -> tuple[np.ndarray, np.ndarray | None]:
        """→ (dense_values, null_mask|None). null_mask[i] True → row i null."""
        payload = self._read_page(pm)
        has_nulls, blen = struct.unpack_from("<BI", payload, 0)
        off = 5
        nm = None
        if has_nulls:
            bits = np.frombuffer(payload[off:off + blen], dtype=np.uint8)
            nm = np.unpackbits(bits, count=pm.n_rows).astype(bool)
            off += blen
        vals = codecs.decode(payload[off:], ValueType(pm.value_type))
        return vals, nm

    def read_field_page_split(self, pm: PageMeta) -> tuple[bytes, np.ndarray | None]:
        """→ (encoded_block, null_mask|None) WITHOUT decoding values —
        the device-decode lane's entry point: the null bitset expands
        host-side (cheap), the codec block goes to
        codecs.split_for_device so its value transforms run on device."""
        payload = self._read_page(pm)
        has_nulls, blen = struct.unpack_from("<BI", payload, 0)
        off = 5
        nm = None
        if has_nulls:
            bits = np.frombuffer(payload[off:off + blen], dtype=np.uint8)
            nm = np.unpackbits(bits, count=pm.n_rows).astype(bool)
            off += blen
        return payload[off:], nm

    def read_series_timestamps(self, table: str, series_id: int) -> np.ndarray:
        cm = self.chunk(table, series_id)
        if cm is None:
            return np.empty(0, dtype=np.int64)
        return np.concatenate([self.read_time_page(p) for p in cm.time_pages]) \
            if len(cm.time_pages) != 1 else self.read_time_page(cm.time_pages[0])

    def read_series_column(self, table: str, series_id: int, name: str,
                           fill=None) -> tuple[np.ndarray, np.ndarray]:
        """→ (values_full, valid_mask) aligned to the series' timestamps.

        Nulls are expanded in place (fill value, default type-zero), with
        valid_mask False at null rows — the padded/masked shape the device
        kernels consume.
        """
        cm = self.chunk(table, series_id)
        if cm is None:
            return np.empty(0), np.empty(0, dtype=bool)
        col = cm.column(name)
        if col is None:
            # column absent in this chunk (schema evolution): all-null
            n = cm.n_rows
            return np.zeros(n), np.zeros(n, dtype=bool)
        outs, masks = [], []
        for pm in col.pages:
            dense, nm = self.read_field_page(pm)
            vt = ValueType(pm.value_type)
            if nm is None:
                outs.append(dense)
                masks.append(np.ones(pm.n_rows, dtype=bool))
            elif isinstance(dense, DictArray):
                # null expansion on codes: invalid rows carry code 0
                full_codes = np.zeros(pm.n_rows, dtype=np.int32)
                full_codes[~nm] = dense.codes
                outs.append(DictArray(full_codes, dense.values))
                masks.append(~nm)
            else:
                full = np.zeros(pm.n_rows, dtype=dense.dtype if isinstance(dense, np.ndarray) else object)
                if fill is not None:
                    full[:] = fill
                full[~nm] = dense
                outs.append(full)
                masks.append(~nm)
        if len(outs) == 1:
            return outs[0], masks[0]
        if any(isinstance(o, DictArray) for o in outs):
            return DictArray.concat(outs), np.concatenate(masks)
        return np.concatenate(outs), np.concatenate(masks)
