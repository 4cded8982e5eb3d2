"""In-memory write cache (one active + N immutable per vnode).

Role-parity with the reference's MemCache (tskv/src/mem_cache/
memcache.rs:30-295, series_data.rs): per-series row storage that absorbs
writes and converts to columnar pages at flush. Kept deliberately simple —
per-series Python lists of appended row chunks; sorting, last-write-wins
dedup and null-mask construction happen once, vectorized, at
`series_batches()` (flush or read) time, not per write.
"""
from __future__ import annotations

import bisect
from operator import itemgetter

import numpy as np

from ..models.points import SeriesRows
from ..models.schema import ValueType

# Per-row bookkeeping overhead charged on top of the payload bytes:
# timestamp (8) + WAL seq share + python list/chunk slots. The old flat
# _APPROX_ROW_BYTES = 48 heuristic ignored dtypes entirely, so a
# string-heavy workload blew far past the configured cap before
# should_flush() noticed while a sparse float workload flushed early;
# sizing is now dtype-aware (see _series_rows_bytes).
_ROW_OVERHEAD_BYTES = 16


def _series_rows_bytes(sr: SeriesRows) -> int:
    """Dtype-aware payload estimate for one appended chunk: actual
    ndarray nbytes where the chunk is typed, element sizes otherwise
    (strings cost their encoded length + an object-header share), plus
    8 bytes per row of timestamps and the per-row overhead."""
    n = len(sr.timestamps)
    total = n * (8 + _ROW_OVERHEAD_BYTES)
    for _name, (vt, vals) in sr.fields.items():
        nb = getattr(vals, "nbytes", None)
        if nb is not None:                      # typed ndarray chunk
            total += int(nb)
            continue
        if vt == int(ValueType.STRING):
            for v in vals:
                total += (len(v) if isinstance(v, (str, bytes)) else 0) + 49
        elif vt == int(ValueType.BOOLEAN):
            total += len(vals)
        else:                                   # numeric python lists
            total += 8 * len(vals)
    return total


_CHUNK_SEQ = itemgetter(0)     # a chunk is (wal_seq, timestamps, fields)


class SeriesData:
    """Accumulated rows of one series inside a memcache.

    A writer appends while scans read with no lock between them, so the
    one mutable thing here is `_chunks`, and a batch enters it WHOLE —
    timestamps and every field in one tuple, one `list.append`. A reader
    copies the list once (`chunks()`) and works on the copy: it sees a
    prefix of whole appended batches, every field of a row or none of the
    row. In-place edits (rename / drop of a field) swap in a rebuilt list.
    """

    __slots__ = ("sid", "table", "_chunks")

    def __init__(self, sid: int, table: str, chunks: list | None = None):
        self.sid = sid
        self.table = table
        # [(wal_seq, timestamps, {field: (value_type, values)})], seq
        # non-decreasing (appends follow log order): a reader cuts the
        # list at a seq, a delta scan takes the suffix newer than a token
        self._chunks: list[tuple[int, list, dict]] = \
            chunks if chunks is not None else []

    def append(self, sr: SeriesRows, seq: int = 0):
        self._chunks.append((seq, sr.timestamps, dict(sr.fields)))

    def chunks(self, upto_seq: int | None = None,
               after_seq: int | None = None) -> list:
        """→ a private copy of the whole batches appended so far, cut to
        after_seq < seq <= upto_seq where given."""
        chunks = self._chunks[:]
        if upto_seq is not None and chunks and chunks[-1][0] > upto_seq:
            chunks = chunks[:bisect.bisect_right(
                chunks, upto_seq, key=_CHUNK_SEQ)]
        if after_seq is not None and chunks and chunks[0][0] <= after_seq:
            chunks = chunks[bisect.bisect_right(
                chunks, after_seq, key=_CHUNK_SEQ):]
        return chunks

    @property
    def n_rows(self) -> int:
        return sum(len(ts) for _seq, ts, _f in self._chunks[:])

    def field_names(self) -> set[str]:
        return {name for _seq, _ts, f in self._chunks[:] for name in f}

    def rename_field(self, old: str, new: str):
        self._chunks = [
            (seq, ts, {new if n == old else n: v for n, v in f.items()})
            for seq, ts, f in self._chunks]

    def drop_field(self, name: str):
        self._chunks = [
            (seq, ts, {n: v for n, v in f.items() if n != name})
            for seq, ts, f in self._chunks]

    def suffix(self, after_seq: int,
               upto_seq: int | None = None) -> "SeriesData | None":
        """→ a SeriesData holding only the chunks with after_seq < seq
        (<= upto_seq), None when there are none. Shares the chunks'
        objects — callers must treat the result as read-only."""
        chunks = self.chunks(upto_seq, after_seq)
        if not any(len(ts) for _seq, ts, _f in chunks):
            return None
        return SeriesData(self.sid, self.table, chunks)

    def materialize(self, upto_seq: int | None = None) -> tuple[
            np.ndarray, dict[str, tuple[ValueType, np.ndarray, np.ndarray]],
            np.ndarray]:
        """→ (sorted unique ts, {field: (vt, values, valid_mask)}, order)

        Sorts by time. Duplicate timestamps merge PER FIELD: each field
        takes its latest non-missing value across the duplicate rows
        (reference memcache RowData::extend — a later partial row overrides
        only the fields it carries). Typed-array and None-free list chunks
        materialize fully vectorized; only chunks actually carrying Nones
        pay a per-element pass.
        """
        chunks = self.chunks(upto_seq)
        if len(chunks) == 1:
            ts = np.asarray(chunks[0][1], dtype=np.int64)
        else:
            ts = np.concatenate(
                [np.asarray(c[1], dtype=np.int64) for c in chunks]) \
                if chunks else np.empty(0, dtype=np.int64)
        n = len(ts)
        # field → [(row_offset, value_type, values)]; the offset aligns a
        # chunk's values with its rows in the concatenated timestamps
        field_chunks: dict[str, list[tuple[int, int, list]]] = {}
        off = 0
        for _seq, cts, fields in chunks:
            for name, (vt, vals) in fields.items():
                field_chunks.setdefault(name, []).append((off, vt, vals))
            off += len(cts)
        order = np.argsort(ts, kind="stable")  # stable: append order within ties
        ts_sorted = ts[order]
        group_starts = _group_starts(ts_sorted)
        uts = ts_sorted[group_starts]
        out_fields: dict[str, tuple[ValueType, np.ndarray, np.ndarray]] = {}
        idx = np.arange(n, dtype=np.int64)
        for name, fchunks in field_chunks.items():
            vt = ValueType(fchunks[0][1])
            np_dtype = vt.numpy_dtype()
            typed = np_dtype is not object
            vals_full = (np.zeros(n, dtype=np_dtype) if typed
                         else np.empty(n, dtype=object))
            valid_full = np.zeros(n, dtype=bool)
            for off, _vt, vals in fchunks:
                m = len(vals)
                if typed and isinstance(vals, np.ndarray):
                    vals_full[off:off + m] = vals
                    valid_full[off:off + m] = True
                elif typed and None not in vals:
                    vals_full[off:off + m] = np.asarray(vals, dtype=np_dtype)
                    valid_full[off:off + m] = True
                else:
                    for i, v in enumerate(vals):
                        if v is not None:
                            vals_full[off + i] = v
                            valid_full[off + i] = True
            vals_s = vals_full[order]
            valid_s = valid_full[order]
            # per-group index of last valid row (-1 if none), vectorized
            score = np.where(valid_s, idx, -1)
            last_valid = np.maximum.reduceat(score, group_starts) if n else score
            valid_out = last_valid >= 0
            gather = np.clip(last_valid, 0, None)
            vals_out = vals_s[gather]
            if not typed:
                vals_out = _typed_array(vals_out, valid_out, vt)
            out_fields[name] = (vt, vals_out, valid_out)
        return uts, out_fields, order

    def time_range(self) -> tuple[int, int]:
        lo, hi = 2**63 - 1, -(2**63)
        for _seq, c, _f in self._chunks[:]:
            a = np.asarray(c, dtype=np.int64)
            if len(a):
                lo = min(lo, int(a.min()))
                hi = max(hi, int(a.max()))
        return lo, hi


def _group_starts(sorted_arr: np.ndarray) -> np.ndarray:
    """Indices where a new run of equal values begins in a sorted array."""
    n = len(sorted_arr)
    if n == 0:
        return np.empty(0, dtype=np.int64)
    starts = np.empty(n, dtype=bool)
    starts[0] = True
    starts[1:] = sorted_arr[1:] != sorted_arr[:-1]
    return np.nonzero(starts)[0]


def _typed_array(obj_vals: np.ndarray, valid: np.ndarray, vt: ValueType) -> np.ndarray:
    np_dtype = vt.numpy_dtype()
    if np_dtype is object:
        out = np.empty(len(obj_vals), dtype=object)
        out[:] = [v if m else "" for v, m in zip(obj_vals, valid)]
        return out
    out = np.zeros(len(obj_vals), dtype=np_dtype)
    if valid.any():
        idx = np.nonzero(valid)[0]
        out[idx] = np.array([obj_vals[i] for i in idx], dtype=np_dtype)
    return out


class MemCache:
    """Active or immutable write cache for one vnode."""

    def __init__(self, vnode_id: int, max_bytes: int = 128 * 1024 * 1024):
        self.vnode_id = vnode_id
        self.max_bytes = max_bytes
        self.series: dict[tuple[str, int], SeriesData] = {}
        self.approx_bytes = 0
        # row-column count (rows × (1 + fields)) kept separately so the
        # reference's usage gauge stays exact while approx_bytes carries
        # the real dtype-aware payload size
        self.rowcols = 0
        self.min_seq: int | None = None
        self.max_seq: int = 0
        self.min_ts = 2**63 - 1
        self.max_ts = -(2**63)
        self.immutable = False

    def write_series(self, table: str, sid: int, sr: SeriesRows, seq: int):
        assert not self.immutable, "write to immutable memcache"
        key = (table, sid)
        sd = self.series.get(key)
        if sd is None:
            # a series enters the dict with its first batch in it
            sd = SeriesData(sid, table)
            sd.append(sr, seq)
            self.series[key] = sd
        else:
            sd.append(sr, seq)
        nb = len(sr.timestamps)
        self.approx_bytes += _series_rows_bytes(sr)
        self.rowcols += nb * (1 + len(sr.fields))
        if self.min_seq is None:
            self.min_seq = seq
        self.max_seq = max(self.max_seq, seq)
        if len(sr.timestamps):
            from ..models.points import ts_bounds

            lo, hi = ts_bounds(sr.timestamps)
            self.min_ts = min(self.min_ts, lo)
            self.max_ts = max(self.max_ts, hi)

    @property
    def is_empty(self) -> bool:
        return not self.series

    def should_flush(self) -> bool:
        return self.approx_bytes >= self.max_bytes

    @property
    def usage_size(self) -> int:
        """The reference's cache-memory estimate (80 bytes per
        row-column: a 1-row single-field write reads 160 —
        vnode_cache_size.slt), decoupled from the flush-threshold
        accounting so dtype-aware sizing can't change gauge parity."""
        return self.rowcols * 80

    def mark_immutable(self):
        self.immutable = True

    def series_batches(self):
        """Yield (table, sid, ts, fields) in sorted (table, sid) order —
        flush consumes this to write a delta TSM file."""
        for (table, sid) in sorted(self.series.keys()):
            sd = self.series[(table, sid)]
            ts, fields, _ = sd.materialize()
            yield table, sid, ts, fields

    def delete_series(self, table: str, sid: int):
        self.series.pop((table, sid), None)

    def delete_table(self, table: str):
        for key in [k for k in self.series if k[0] == table]:
            del self.series[key]

    def delete_time_range(self, table: str, sids, min_ts: int, max_ts: int):
        """Row-level delete inside cache (reference memcache delete):
        rebuild affected series without rows in [min_ts, max_ts]."""
        sidset = set(int(s) for s in sids) if sids is not None else None
        for (tbl, sid), sd in list(self.series.items()):
            if tbl != table or (sidset is not None and sid not in sidset):
                continue
            ts, fields, _ = sd.materialize()
            keep = (ts < min_ts) | (ts > max_ts)
            if keep.all():
                continue
            nd = SeriesData(sid, tbl)
            if keep.any():
                kts = ts[keep].tolist()
                nf = {}
                for name, (vt, vals, valid) in fields.items():
                    v = [vals[i] if valid[i] else None for i in np.nonzero(keep)[0]]
                    nf[name] = (int(vt), v)
                from ..models.series import SeriesKey
                # the rebuilt chunk carries the cache's max seq: it holds
                # survivors of older writes, so a delta suffix taken at an
                # older token must include it (the delete itself also bumps
                # destructive_version, which forces a full rescan anyway)
                nd.append(SeriesRows(SeriesKey(tbl, []), kts, nf),
                          self.max_seq)
                self.series[(tbl, sid)] = nd
            else:
                del self.series[(tbl, sid)]

    def series_keys(self) -> list[tuple[str, int]]:
        """The (table, sid) keys as one copy: scans run without the vnode
        lock, and a write may grow the dict while a reader walks it."""
        return list(self.series)

    def suffix_view(self, after_seq: int,
                    upto_seq: int | None = None) -> "MemCache | None":
        """→ a read-only MemCache exposing only rows appended with WAL
        seq > after_seq (and <= upto_seq, a scan's cut), or None when this
        cache has nothing newer. Used by the delta scan
        (storage/scan.DeltaVnodeView) so an incremental rescan decodes
        only post-token memcache chunks."""
        if self.max_seq <= after_seq:
            return None
        out = MemCache(self.vnode_id, self.max_bytes)
        out.immutable = True
        out.min_seq = self.min_seq
        out.max_seq = self.max_seq
        # the whole cache's bounds: a superset of the suffix's
        out.min_ts, out.max_ts = self.min_ts, self.max_ts
        for key, sd in list(self.series.items()):
            suf = sd.suffix(after_seq, upto_seq)
            if suf is not None:
                out.series[key] = suf
        return out if out.series else None
