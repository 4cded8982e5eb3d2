"""Scan assembly: merge memcaches + TSM files into device-ready batches.

Role-parity with the reference's read pipeline (tskv/src/reader/
iterator.rs:94-121 reader tree: SeriesReader → DataMerger → DataFilter →
Chunk/MemcacheReader), re-shaped for TPU: instead of a per-series stream
tree pulling one RecordBatch at a time, the scan materializes ONE large
columnar batch per vnode — timestamps, a series-ordinal segment array and
field columns with validity masks, already concatenated across series —
which is exactly the padded/masked layout `ops.tpu_exec` stages over PCIe.

Dedup priority on duplicate timestamps (low→high): L4..L1 files, L0 delta
files by ascending file id, immutable memcaches (oldest first), active
memcache. Within a priority, later rows win per FIELD (same rule as
memcache.materialize / compaction merge).
"""
from __future__ import annotations

from contextlib import nullcontext
from dataclasses import dataclass, field

import numpy as np

from ..models.predicate import TimeRange, TimeRanges
from ..models.schema import TskvTableSchema, ValueType
from ..utils import deadline as deadline_mod
from ..models.strcol import DictArray, as_dict_part as _as_dict_part, \
    unify_dictionaries
from .memcache import MemCache, _group_starts
from .vnode import VnodeCut, VnodeStorage, _CutSummary
from ..server import memory as memgov
from ..utils import lockwatch
from ..utils import stages
from . import compressed_domain


def _charge_decoded(batch):
    """Per-query accounting for one assembled vnode batch (decode-pool
    bytes): an oversized query dies here with MemoryExceeded before the
    next vnode materializes."""
    nb = batch.ts.nbytes + batch.sid_ordinal.nbytes
    for _vt, vals, valid in batch.fields.values():
        nb += int(getattr(vals, "nbytes", 0) or 0)
        nb += int(getattr(valid, "nbytes", 0) or 0)
    memgov.charge_query(nb, "decode")
    return batch


@dataclass
class ScanBatch:
    """One vnode's scan result, columnar, concatenated across series."""

    table: str
    series_ids: np.ndarray          # u64 [S]
    series_keys: list               # SeriesKey per ordinal (tags for GROUP BY)
    ts: np.ndarray                  # i64 [N]
    sid_ordinal: np.ndarray         # i32 [N] — segment id per row
    fields: dict[str, tuple[ValueType, np.ndarray, np.ndarray]] = field(default_factory=dict)
    # name → (vt, values [N], valid [N])

    @property
    def n_rows(self) -> int:
        return len(self.ts)

    @property
    def n_series(self) -> int:
        return len(self.series_ids)

    def ts_minmax(self) -> tuple[int, int]:
        """(min, max) of `ts`, (0, 0) for no rows: immutable per scan
        snapshot, so computed once and kept (two i64 passes over every
        row otherwise, again on every query and every device twin)."""
        mm = getattr(self, "_ts_minmax", None)
        if mm is None:
            mm = self._ts_minmax = (int(self.ts.min()), int(self.ts.max())) \
                if len(self.ts) else (0, 0)
        return mm


def _time_mask(ts: np.ndarray, trs: TimeRanges) -> np.ndarray | None:
    if trs.is_all:
        return None
    m = np.zeros(len(ts), dtype=bool)
    for r in trs.ranges:
        m |= (ts >= r.min_ts) & (ts <= r.max_ts)
    return m


def _series_parts(vnode: VnodeCut, table: str, sid: int,
                  field_names: list[str], trs: TimeRanges):
    """Collect (ts, {field: (vt, vals, valid)}) parts in priority order
    → (parts, how many of them came from memcaches, TSM pages read)."""
    parts = []
    n_pages = 0
    targets = _field_targets(vnode, table, field_names)
    version = vnode.summary.version
    # files: L4..L1 then L0, ascending file_id within level ⇒ ascending priority
    for level in (4, 3, 2, 1, 0):
        fms = sorted(version.levels[level].values(), key=lambda f: f.file_id)
        for fm in fms:
            if not trs.is_all and not trs.overlaps(TimeRange(fm.min_ts, fm.max_ts)):
                continue
            r = version.reader(fm)
            cm = r.chunk(table, sid)
            if cm is None:
                continue
            ts = r.read_series_timestamps(table, sid)
            keep = version.tombstone(fm).mask_for(table, sid, ts)
            tmask = _time_mask(ts, trs)
            if keep is None and tmask is None:
                sel = None
            else:
                sel = np.ones(len(ts), dtype=bool)
                if keep is not None:
                    sel &= keep
                if tmask is not None:
                    sel &= tmask
                if not sel.any():
                    continue
            fields = {}
            maps = _chunk_maps(cm)
            n_pages += len(cm.time_pages)
            for name in field_names:
                cid, cands = targets[name]
                col = _resolve_chunk_col(maps, cid, cands)
                if col is None:
                    continue
                n_pages += len(col.pages)
                vt = ValueType(col.pages[0].value_type)
                vals, valid = r.read_series_column(table, sid, col.name)
                if sel is not None:
                    vals, valid = vals[sel], valid[sel]
                fields[name] = (vt, vals, valid)
            parts.append(((ts[sel] if sel is not None else ts), fields))
    # memcaches: immutables old→new, then active — whole batches up to the
    # cut's seq, whatever a writer appends meanwhile
    sds = [sd for cache in _caches_in_range(vnode, trs)
           if (sd := cache.series.get((table, sid))) is not None]
    if not sds:
        return parts, 0, n_pages
    n_files = len(parts)
    with stages.stage("memcache_ms"):
        rows = 0
        for sd in sds:
            ts, mfields, _ = sd.materialize(vnode.mem_seq)
            rows += len(ts)
            tmask = _time_mask(ts, trs)
            if tmask is not None:
                if not tmask.any():
                    continue
                ts = ts[tmask]
            fields = {}
            for name in field_names:
                src = next((c for c in targets[name][1]
                            if c in mfields), None)
                if src is None:
                    continue
                vt, vals, valid = mfields[src]
                if tmask is not None:
                    vals, valid = vals[tmask], valid[tmask]
                fields[name] = (vt, vals, valid)
            parts.append((ts, fields))
    stages.count("memcache.series")
    stages.count("memcache.rows", rows)
    return parts, len(parts) - n_files, n_pages


def _merged_series(vnode: VnodeCut, table: str, sid: int,
                   field_names: list[str], trs: TimeRanges):
    """One series through the per-series path → (ts, fields, TSM pages
    read): its parts in priority order, merged. Where memcache rows take
    part, the merge is their cost too (`memcache_ms`)."""
    parts, n_mem, n_pages = _series_parts(vnode, table, sid, field_names,
                                          trs)
    with stages.stage("memcache_ms") if n_mem and len(parts) > 1 \
            else nullcontext():
        return (*merge_parts(parts, field_names), n_pages)


def merge_parts(parts, field_names: list[str]):
    """Merge priority-ordered parts → (ts, {field: (vt, vals, valid)})."""
    if not parts:
        return np.empty(0, dtype=np.int64), {}
    if len(parts) == 1:
        ts, fields = parts[0]
        return ts, fields
    # fast path: compacted output chunks are time-partitioned — when the
    # parts are individually strictly increasing and pairwise DISJOINT
    # after ordering by first timestamp, the merge is a concatenation
    # (no argsort, no dedup — the dominant cold-scan shape)
    nonempty = [p for p in parts if len(p[0])]
    if len(nonempty) > 1:
        ordered = sorted(nonempty, key=lambda p: int(p[0][0]))
        ok = all(bool((p[0][1:] > p[0][:-1]).all()) for p in ordered)
        if ok:
            for a, b in zip(ordered, ordered[1:]):
                if int(a[0][-1]) >= int(b[0][0]):
                    ok = False
                    break
        if ok:
            ts = np.concatenate([p[0] for p in ordered])
            out = {}
            for name in field_names:
                vt = next((f[name][0] for _, f in ordered if name in f),
                          None)
                if vt is None:
                    continue
                np_dtype = vt.numpy_dtype()
                if np_dtype is object:
                    break   # dictionary columns: generic path unifies
                vals_parts, valid_parts = [], []
                for ts_p, f in ordered:
                    if name in f:
                        vals_parts.append(f[name][1])
                        valid_parts.append(f[name][2])
                    else:
                        vals_parts.append(
                            np.zeros(len(ts_p), dtype=np_dtype))
                        valid_parts.append(
                            np.zeros(len(ts_p), dtype=bool))
                out[name] = (vt, np.concatenate(vals_parts),
                             np.concatenate(valid_parts))
            else:
                return ts, out
    ts_all = np.concatenate([p[0] for p in parts])
    total = len(ts_all)
    order = np.argsort(ts_all, kind="stable")
    ts_sorted = ts_all[order]
    group_starts = _group_starts(ts_sorted)
    uts = ts_sorted[group_starts]
    idx = np.arange(total, dtype=np.int64)
    out = {}
    for name in field_names:
        vt = None
        for _, fields in parts:
            if name in fields:
                vt = fields[name][0]
                break
        if vt is None:
            continue
        np_dtype = vt.numpy_dtype()
        is_str = np_dtype is object
        union = None
        if is_str:
            # strings merge as int32 codes under one union dictionary —
            # the dedup pick below is pure integer indexing either way
            das = {id(f): _as_dict_part(f[name][1])
                   for _, f in parts if name in f}
            union = unify_dictionaries(list(das.values()))
            vals_all = np.zeros(total, dtype=np.int32)
        else:
            vals_all = np.zeros(total, dtype=np_dtype)
        valid_all = np.zeros(total, dtype=bool)
        off = 0
        for ts_p, fields in parts:
            n = len(ts_p)
            if name in fields:
                _, vals, valid = fields[name]
                vals_all[off:off + n] = (das[id(fields)].remap_to(union)
                                         if is_str else vals)
                valid_all[off:off + n] = valid
            off += n
        vals_s = vals_all[order]
        valid_s = valid_all[order]
        score = np.where(valid_s, idx, -1)
        last_valid = np.maximum.reduceat(score, group_starts)
        valid_out = last_valid >= 0
        vals_out = vals_s[np.clip(last_valid, 0, None)]
        if is_str:
            vals_out = DictArray(vals_out, union)
        out[name] = (vt, vals_out, valid_out)
    return uts, out


# ---------------------------------------------------------------------------
# delta rescan: decode only what a ScanToken doesn't cover
# ---------------------------------------------------------------------------


class DeltaVnodeView(VnodeCut):
    """A cut exposing only data NEWER than a ScanToken: the TSM files in
    `new_fids` plus memcache rows with WAL seq > `after_seq` (and within
    the cut). scan_vnode runs against it unchanged — the result is the
    delta batch that merge_scan_batches folds into the cached snapshot.
    Index and schemas are the live ones (valid because the coordinator
    only takes this path when destructive_version matched)."""

    __slots__ = ()

    def __init__(self, vnode: VnodeStorage | VnodeCut, new_fids: frozenset,
                 after_seq: int):
        cut = vnode.cut()
        act = cut.active.suffix_view(after_seq, cut.mem_seq)
        super().__init__(
            cut.vnode_id, cut.index, cut.schemas,
            _CutSummary(cut.summary.version.only(new_fids)),
            [sv for c in cut.immutables
             if (sv := c.suffix_view(after_seq, cut.mem_seq)) is not None],
            act if act is not None else MemCache(cut.vnode_id),
            cut.mem_seq, cut.token)


def merge_scan_batches(cached: ScanBatch, delta: ScanBatch):
    """Fold a delta decode into a cached snapshot.

    → (merged, append_gather) or None when the batches disagree on a
    field's type (schema drift the caller resolves with a full rescan).
    `append_gather` is an int64 row-gather into concat(cached, delta)
    producing the merged batch, present iff no (series, ts) pair occurs
    in both inputs — the pure-append case the device twin can replay
    with one gather per column (ops/device_cache.merged_device_batch).

    Dedup semantics match a full rescan: every delta source (a freshly
    flushed L0 file, newer memcache chunks) outranks every cached source,
    and rows the delta re-decodes after a flush carry identical values,
    so per-field latest-valid-wins over [cached, delta] is exactly the
    scan's merge rule. Output is canonical: series ids ascending (the
    index returns sorted sid arrays), ts ascending and unique per series.
    """
    n_c, n_d = cached.n_rows, delta.n_rows
    for name, (vt, _v, _m) in delta.fields.items():
        cf = cached.fields.get(name)
        if cf is not None and cf[0] != vt:
            return None
    all_sids = np.union1d(cached.series_ids, delta.series_ids)
    sid_all = np.concatenate([cached.series_ids[cached.sid_ordinal],
                              delta.series_ids[delta.sid_ordinal]])
    ts_all = np.concatenate([cached.ts, delta.ts])
    n = n_c + n_d
    # stable (ts, sid) lexsort: within a duplicate (sid, ts) group the
    # cached rows precede the delta rows, so "last valid wins" = delta
    order = np.lexsort((ts_all, sid_all))
    sid_s = sid_all[order]
    ts_s = ts_all[order]
    newgrp = np.empty(n, dtype=bool)
    newgrp[0] = True
    newgrp[1:] = (sid_s[1:] != sid_s[:-1]) | (ts_s[1:] != ts_s[:-1])
    group_starts = np.nonzero(newgrp)[0]
    pure_append = len(group_starts) == n
    uts = ts_s[group_starts]
    usid = sid_s[group_starts]
    sid_ordinal = np.searchsorted(all_sids, usid).astype(np.int32)
    idx = np.arange(n, dtype=np.int64)
    out_fields: dict = {}
    names = list(cached.fields)
    names += [nm for nm in delta.fields if nm not in cached.fields]
    for name in names:
        vt = (cached.fields.get(name) or delta.fields[name])[0]
        np_dtype = vt.numpy_dtype()
        is_str = np_dtype is object
        if is_str:
            das = [_as_dict_part(b.fields[name][1])
                   if name in b.fields else None
                   for b in (cached, delta)]
            union = unify_dictionaries([d for d in das if d is not None])
            vals_all = np.zeros(n, dtype=np.int32)
        else:
            vals_all = np.zeros(n, dtype=np_dtype)
        valid_all = np.zeros(n, dtype=bool)
        off = 0
        for bi, b in enumerate((cached, delta)):
            m = b.n_rows
            if name in b.fields:
                _vt, vals, valid = b.fields[name]
                vals_all[off:off + m] = (das[bi].remap_to(union)
                                         if is_str else vals)
                valid_all[off:off + m] = valid
            off += m
        vals_s = vals_all[order]
        valid_s = valid_all[order]
        score = np.where(valid_s, idx, -1)
        last_valid = np.maximum.reduceat(score, group_starts)
        valid_out = last_valid >= 0
        vals_out = vals_s[np.clip(last_valid, 0, None)]
        if is_str:
            vals_out = DictArray(vals_out, union)
        out_fields[name] = (vt, vals_out, valid_out)
    keymap = {int(s): k for s, k in zip(cached.series_ids,
                                        cached.series_keys)}
    keymap.update((int(s), k) for s, k in zip(delta.series_ids,
                                              delta.series_keys))
    merged = ScanBatch(cached.table, all_sids.astype(np.uint64),
                       [keymap[int(s)] for s in all_sids],
                       uts, sid_ordinal, out_fields)
    return merged, (order[group_starts] if pure_append else None)


def _field_targets(vnode: VnodeCut, table: str,
                   field_names: list[str]) -> dict:
    """name → (column_id | None, [name, *prior_names]).

    TSM chunk columns are resolved by column id when both sides carry
    one: ids are never reused (models/schema.py), so data written under
    a renamed-away name can never conflate with a newer column that
    later took the name. The name-lineage candidates are the fallback
    for id-less chunks (flushed without a schema) and for name-keyed
    memcache rows."""
    schema = vnode.schemas.get(table)
    out = {}
    for n in field_names:
        cands = [n]
        cid = None
        if schema is not None:
            c = schema.column(n) if schema.contains_column(n) else None
            if c is not None:
                cid = c.id
                if getattr(c, "prior_names", None):
                    cands += list(c.prior_names)
        out[n] = (cid, cands)
    return out


def _chunk_maps(cm) -> tuple[dict, dict]:
    """Build one (by_id, by_name) lookup per chunk — resolve all query
    columns against it rather than re-scanning cm.columns per field."""
    by_id: dict = {}
    by_name: dict = {}
    for c in cm.columns:
        if c.column_id:
            by_id.setdefault(c.column_id, c)
        by_name.setdefault(c.name, c)
    return by_id, by_name


def _resolve_chunk_col(maps, cid, cands):
    """→ ColumnMeta for one query column inside one chunk, id-first.

    Name fallback only considers chunk columns WITHOUT an id when the
    query column's id is known — a chunk column carrying a different id
    is provably another (renamed/dropped) column, even if its name
    matches."""
    by_id, by_name = maps
    if cid is not None:
        c = by_id.get(cid)
        if c is not None:
            return c
    for nm in cands:
        c = by_name.get(nm)
        if c is not None and (cid is None or not c.column_id):
            return c
    return None


# fresh cuts a scan may take when a compaction unlinked a file under it
RECUTS = 2


def scan_vnode(vnode: VnodeStorage | VnodeCut, table: str,
               series_ids: np.ndarray | None = None,
               time_ranges: TimeRanges | None = None,
               field_names: list[str] | None = None,
               page_filter=None, page_constraints: dict | None = None,
               n_threads: int = 1, upload_hook=None,
               decode_hook=None, compressed_spec=None) -> ScanBatch:
    """Materialize a vnode scan into one ScanBatch.

    `page_filter` (an sql.expr tree, optional) enables predicate page
    pruning: pages whose statistics prove no row can satisfy a
    conjunct are never decoded. The resulting batch is only valid for
    queries applying that same filter — the coordinator keys its scan
    cache accordingly, and passes the constraints it already extracted
    as `page_constraints` so the tree is walked once per query, not per
    vnode. `n_threads` sizes the native decoder's pool (the coordinator
    divides the host's cores across concurrent vnode scans).
    `upload_hook`, when given, is `hook(total_rows) -> uploader`: as each
    field column finishes decoding cleanly it is handed to
    `uploader.put(...)` so device transfer overlaps the decode of the
    remaining columns (the double-buffer half of the pipeline; storage
    stays jax-free — the hook comes from ops/device_cache).
    `decode_hook`, when given, is `hook() -> DeviceDecodeLane | None`
    (ops/device_decode): pages whose codec has a device kernel stop host
    work at the byte container and decode as batched kernels on the
    accelerator — the third lane beside native pagedec and per-page
    Python. The lane says which goes first (`native_first`): the
    coordinator's auto-mode lane leaves the native decoder every page it
    can take, because the values land in this scan's host arrays; a
    forced lane, or the class handed in directly, is asked first.
    `compressed_spec` (storage/compressed_domain.CompressedSpec), when
    given, engages the compressed-domain lane AHEAD of the decode lanes:
    merge-free pages provably skippable/answerable from their encoded
    representation leave the plan entirely (contributions ride
    `batch.compressed_partials`), and mixed string/bool predicate pages
    decode but gather only surviving rows (late materialization). The
    batch is only valid for queries with that exact spec — the
    coordinator keys its cache accordingly.
    """
    if not isinstance(vnode, VnodeCut):
        # one cut for the whole scan: the file set, the memcaches and the
        # seq every series is read at. A compaction may replace files of
        # the cut and unlink them before the scan has opened them: the
        # state has moved on whole, so cut again (a caller that hands a
        # cut in holds a token to it, and cuts again itself)
        for recut in range(RECUTS + 1):
            try:
                return scan_vnode(
                    vnode.cut(), table, series_ids, time_ranges,
                    field_names, page_filter, page_constraints, n_threads,
                    upload_hook, decode_hook, compressed_spec)
            except FileNotFoundError:
                if recut == RECUTS:
                    raise
    trs = time_ranges if time_ranges is not None else TimeRanges.all()
    if series_ids is None:
        file_sids = set()
        for fm in vnode.summary.version.all_files():
            r = vnode.summary.version.reader(fm)
            file_sids.update(int(s) for s in r.series_ids(table))
        series_ids = np.array(
            sorted(file_sids | _mem_series_ids(vnode, table, trs)),
            dtype=np.uint64)
    if field_names is None:
        field_names = _discover_fields(vnode, table)

    import os

    if not os.environ.get("CNOSDB_NO_NATIVE_SCAN"):
        if page_constraints is None and page_filter is not None:
            page_constraints = _page_constraints(page_filter, field_names)
        batch = _scan_vnode_native(vnode, table, series_ids, trs,
                                   field_names, page_constraints or {},
                                   n_threads, upload_hook, decode_hook,
                                   compressed_spec)
        if batch is not None:
            return _charge_decoded(batch)

    ts_parts, ord_parts = [], []
    fparts: dict[str, list[tuple[int, np.ndarray, np.ndarray]]] = {n: [] for n in field_names}
    ftypes: dict[str, ValueType] = {}
    keys = []
    kept_sids = []
    total = 0
    for ordinal, sid in enumerate(series_ids):
        # cooperative checkpoint: a killed/expired request stops between
        # series instead of materializing the rest of the vnode
        deadline_mod.check_current()
        sid = int(sid)
        ts, fields, _pages = _merged_series(vnode, table, sid, field_names,
                                            trs)
        if len(ts) == 0:
            continue
        ts_parts.append(ts)
        ord_parts.append(np.full(len(ts), len(kept_sids), dtype=np.int32))
        for name in field_names:
            if name in fields:
                vt, vals, valid = fields[name]
                ftypes.setdefault(name, vt)
                fparts[name].append((total, vals, valid))
        kept_sids.append(sid)
        keys.append(vnode.index.get_series_key(sid))
        total += len(ts)

    if total == 0:
        return ScanBatch(table, np.empty(0, dtype=np.uint64), [],
                         np.empty(0, dtype=np.int64), np.empty(0, dtype=np.int32), {})
    ts_all = np.concatenate(ts_parts)
    ord_all = np.concatenate(ord_parts)
    out_fields = {}
    for name, parts in fparts.items():
        if not parts:
            continue
        vt = ftypes[name]
        np_dtype = vt.numpy_dtype()
        if np_dtype is object:
            das = [_as_dict_part(vals) for _, vals, _ in parts]
            union = unify_dictionaries(das)
            vals_all = np.zeros(total, dtype=np.int32)
            valid_all = np.zeros(total, dtype=bool)
            for (off, vals, valid), d in zip(parts, das):
                vals_all[off:off + len(d)] = d.remap_to(union)
                valid_all[off:off + len(valid)] = valid
            out_fields[name] = (vt, DictArray(vals_all, union), valid_all)
            continue
        vals_all = np.zeros(total, dtype=np_dtype)
        valid_all = np.zeros(total, dtype=bool)
        for off, vals, valid in parts:
            vals_all[off:off + len(vals)] = vals
            valid_all[off:off + len(valid)] = valid
        out_fields[name] = (vt, vals_all, valid_all)
    return _charge_decoded(
        ScanBatch(table, np.array(kept_sids, dtype=np.uint64), keys,
                  ts_all, ord_all, out_fields))


# ---------------------------------------------------------------------------
# native batch scan: the cold-path fast lane
# ---------------------------------------------------------------------------
# Most scans hit fully-compacted vnodes: per series, a handful of chunks
# whose time ranges are provably disjoint FROM METADATA ALONE (no decode
# needed to know the merge is a concatenation). For those, the whole
# vnode's page set is planned up front, a FILE at a time: every reader
# keeps a columnar copy of a table's chunk and page metadata
# (tsm.PageIndex, built by the first scan that touches it; the file never
# changes), and from it the asked series' chunk rows (one searchsorted),
# time admission and the "inside one range, so no trim" test (array
# comparisons against the ranges), the output row offsets (a cumulative
# sum in the batch's order: series as asked, a series' chunks by min_ts,
# pages in file order) and the (n_pages, 6) descriptors of
# native/pagedec.cpp's one GIL-free multithreaded call per (file, column)
# are array operations — no Python a series or a page. The query's columns
# are resolved against a file once (by id, then by name lineage). What
# metadata alone cannot plan keeps its own path, decided by what the scan
# sees in its input: a series with unflushed rows in range, a matching
# tombstone, chunks overlapping across files or pages not aligned is read
# and merged one at a time (`_merged_series`) and spliced into its
# reserved span; a (file, column) with a page the native decoder cannot
# take unasked (a cold reader, a string, another type or encoding, a
# device-first lane) is routed a page at a time; page constraints and the
# compressed-domain lane's verdicts run a page at a time over the
# time-admitted pages. This replaces the role of the reference's reader
# tree (tskv/src/reader/iterator.rs:94-121) for the dominant
# compacted-read shape, with page-statistics predicate pruning (reference
# column_group/statistics.rs) applied before any byte decodes.

_NATIVE_NUMERIC = {
    int(ValueType.FLOAT): 1,      # pagedec kind: gorilla f64
    int(ValueType.INTEGER): 2,    # delta i64
    int(ValueType.UNSIGNED): 2,   # delta (u64 bit pattern rides i64)
    int(ValueType.BOOLEAN): 3,    # bitpack u8
}
_NATIVE_ENC = {1: {6}, 2: {2, 11}, 3: {10}}   # kind → decodable encodings

# Why pages miss the native pagedec fast lane, by reason — the
# observability half of the decode plane (surfaced on /metrics as
# cnosdb_decode_fallback_total{reason=...}). A hot reason is actionable:
#   string        value type has no native lane (dictionary decode)
#   value_type    numeric type pagedec doesn't cover
#   encoding      codec outside the native decoder's set
#   schema_change page typed differently than the column (cast path)
#   native_reject native decoder refused the page at runtime
#   native_unavailable  no native library and the device lane declined
#   cold_tier     page lives in the object store; the native mmap lane
#                 cannot touch it (decodes via Python over the block cache)
#   device_decode.*     device lane examined the page but declined
#                       (reason suffix from codecs.split_for_device)
import threading as _threading

_FALLBACK_LOCK = lockwatch.Lock("scan.fallback")
_FALLBACK: dict[str, int] = {}


def _count_fallback(reason: str, n: int = 1) -> None:
    with _FALLBACK_LOCK:
        _FALLBACK[reason] = _FALLBACK.get(reason, 0) + n


def decode_fallback_snapshot() -> dict[str, int]:
    with _FALLBACK_LOCK:
        return dict(sorted(_FALLBACK.items()))


def _native_miss(native_ok: bool, cold: bool, pm, vt) -> str | None:
    """Why the native decoder cannot take field page `pm` of a column
    typed `vt` (`cold`: the page's reader is) — the page's
    cnosdb_decode_fallback_total reason should it end on the Python
    lane — or None when it can."""
    if vt in (ValueType.STRING, ValueType.GEOMETRY):
        return "string"
    if not native_ok:
        return "native_unavailable"
    if cold:
        # the native writer reads pages out of a local mmap
        # (buffer_array) — cold pages have no local bytes, so they decode
        # via the Python lane over the block cache
        return "cold_tier"
    kind = _NATIVE_NUMERIC.get(pm.value_type)
    if kind is None:
        return "value_type"
    if pm.encoding not in _NATIVE_ENC[kind]:
        return "encoding"
    if pm.value_type != int(vt):
        # schema evolution changed the column's type between chunks — the
        # output array is typed by ftypes, so a differently-typed page
        # must go through the casting Python path, never the width-blind
        # native writer
        return "schema_change"
    return None


def _count_cold_pruned(n: int) -> None:
    """Pages of a COLD file skipped by local zone-map/constraint pruning:
    each one is a page whose bytes were never downloaded."""
    from . import tiering

    stages.count("cold.pages_pruned", n)
    tiering._count_cold("prune", "pages_pruned", n)


def _caches_in_range(vnode: VnodeCut, trs: TimeRanges) -> list[MemCache]:
    """The cut's memcaches that can hold a row inside `trs`, in ascending
    priority. A cache whose [min_ts, max_ts] lies outside every range has
    nothing for this scan: a fleet writing at "now" does not take a query
    over last week off the page plan."""
    return [c for c in vnode.caches()
            if trs.is_all or trs.overlaps(TimeRange(c.min_ts, c.max_ts))]


def _mem_series_ids(vnode: VnodeCut, table: str, trs: TimeRanges) -> set:
    """Series ids of `table` with unflushed rows that may lie inside `trs`
    (active + immutables): they need the per-series merge."""
    return {sid for c in _caches_in_range(vnode, trs)
            for (t, sid) in c.series_keys() if t == table}


def _page_constraints(page_filter, field_names) -> dict:
    """Extract per-column interval conjuncts usable for page pruning.

    Walks AND nodes only; each supported conjunct (col CMP literal,
    BETWEEN, IN) contributes. Unsupported subtrees are simply ignored —
    pruning by any one conjunct is sound because a row dropped by it
    fails the whole conjunction (NULL rows fail comparisons too, and
    page stats exclude only NaNs, which satisfy no comparison).
    → {col: [("op", value) | ("between", (lo, hi)) | ("in", values)]}
    """
    import os

    from ..sql.expr import Between, BinOp, Column, InList, Like, Literal

    fields = set(field_names)
    out: dict[str, list] = {}
    ngram_on = os.environ.get("CNOSDB_NGRAM_SKIP", "1").lower() \
        not in ("0", "off", "false")

    def numeric(v):
        return isinstance(v, (int, float, np.integer, np.floating)) \
            and not isinstance(v, bool)

    def add_ngram(col, tris):
        # a subset of required trigrams only admits MORE pages — sound
        if ngram_on and tris:
            out.setdefault(col, []).append(("ngram", tris))

    def walk(e):
        if isinstance(e, BinOp):
            if e.op == "and":
                walk(e.left)
                walk(e.right)
                return
            if e.op in ("=", "!=", "<", "<=", ">", ">="):
                col = lit = op = None
                if isinstance(e.left, Column) and isinstance(e.right, Literal):
                    col, lit, op = e.left.name, e.right.value, e.op
                elif isinstance(e.right, Column) and isinstance(e.left, Literal):
                    flip = {"<": ">", "<=": ">=", ">": "<", ">=": "<=",
                            "=": "=", "!=": "!="}
                    col, lit, op = e.right.name, e.left.value, flip[e.op]
                if col in fields and numeric(lit):
                    out.setdefault(col, []).append((op, lit))
                elif col in fields and op == "=" and isinstance(lit, str):
                    from ..ops import strkernels

                    add_ngram(col, strkernels.value_trigrams(lit))
            return
        if isinstance(e, Like) and not e.negated \
                and isinstance(e.expr, Column) and isinstance(e.pattern, str) \
                and e.expr.name in fields:
            from ..ops import strkernels

            add_ngram(e.expr.name, strkernels.required_trigrams(e.pattern))
            return
        if isinstance(e, Between) and not e.negated \
                and isinstance(e.expr, Column) \
                and isinstance(e.low, Literal) and isinstance(e.high, Literal) \
                and e.expr.name in fields \
                and numeric(e.low.value) and numeric(e.high.value):
            out.setdefault(e.expr.name, []).append(
                ("between", (e.low.value, e.high.value)))
            return
        if isinstance(e, InList) and not e.negated \
                and isinstance(e.expr, Column) and e.expr.name in fields \
                and e.values and all(numeric(v) for v in e.values):
            out.setdefault(e.expr.name, []).append(("in", list(e.values)))
            return

    try:
        walk(page_filter)
    except Exception:
        return {}
    return out


def _page_admits(cols: dict, i: int, constraints: dict) -> bool:
    """Can page i of this chunk contain a row satisfying every constrained
    conjunct? Column absent from the chunk → all-NULL → no match."""
    for cname, cons in constraints.items():
        col = cols.get(cname)
        if col is None:
            return False
        pm = col.pages[i]
        ngram_cons = [c for c in cons if c[0] == "ngram"]
        if ngram_cons:
            # checked before the stats gate: string pages carry no
            # min/max (the `continue` below) but do carry signatures
            sig = getattr(pm, "ngram", None)
            if sig is not None:
                from ..ops import strkernels

                for _op, tris in ngram_cons:
                    if not strkernels.signature_admits(sig, tris):
                        stages.count("ngram_pages_skipped", 1)
                        strkernels.note_path("ngram_skip", "page")
                        return False
            cons = [c for c in cons if c[0] != "ngram"]
        lo, hi = pm.stat_min, pm.stat_max
        if lo is None or hi is None:
            continue   # no stats (e.g. all-null page): cannot prune
        if pm.value_type == int(ValueType.FLOAT) \
                and getattr(pm, "stats_version", 0) < 1:
            # legacy finite-only float stats: an ±inf row may lie outside
            # the recorded interval, so pruning on it could drop rows
            continue
        for op, val in cons:
            if op == ">":
                ok = hi > val
            elif op == ">=":
                ok = hi >= val
            elif op == "<":
                ok = lo < val
            elif op == "<=":
                ok = lo <= val
            elif op == "=":
                ok = lo <= val <= hi
            elif op == "!=":
                # cannot prune: page stats exclude NaN, and NaN rows DO
                # satisfy != (sql 3VL evaluates it as ~(a == b)); a
                # constant page [v..v] may still hide a matching NaN row
                ok = True
            elif op == "between":
                ok = hi >= val[0] and lo <= val[1]
            else:   # "in"
                ok = any(lo <= v <= hi for v in val)
            if not ok:
                return False
    return True


def _submit_device_page(dev_lane, r, pm, colname, out_off, vt,
                        numeric_cols, string_parts, string_valid,
                        ts_all) -> bool:
    """Try to queue one page on the device-decode lane. True = queued;
    False = the caller routes the page to a host lane, with the decline
    reason already booked on both counters (decode_fallback_snapshot's
    device_decode.* reasons and cnosdb_device_decode_total)."""
    from . import codecs as _codecs

    try:
        if colname is None:
            block, nm = r._read_page(pm), None
        else:
            block, nm = r.read_field_page_split(pm)
        plan, reason = _codecs.split_for_device(
            block, vt if colname is not None else ValueType.INTEGER)
    except Exception:
        plan, reason = None, "read_error"
    if plan is None:
        _count_fallback("device_decode." + reason)
        dev_lane.declined(reason)
        return False
    n = pm.n_rows
    token = (r, pm, colname, out_off, vt)
    if colname is None:
        dev_lane.submit(plan, token, ValueType.INTEGER, out_off, n,
                        None, ts_all, None)
        return True
    if vt in (ValueType.STRING, ValueType.GEOMETRY):
        parts, sv = string_parts[colname], string_valid[colname]
        values = plan["values"]

        def _sink(dense, _off=out_off, _n=n, _nm=nm, _values=values):
            if _nm is None:
                codes = dense.astype(np.int32, copy=False)
                valid_p = np.ones(_n, dtype=bool)
            else:
                codes = np.zeros(_n, dtype=np.int32)
                codes[~_nm] = dense
                valid_p = ~_nm
            parts.append((_off, DictArray(codes, _values)))
            sv[_off:_off + _n] = valid_p

        dev_lane.submit(plan, token, vt, out_off, n, nm,
                        None, None, sink=_sink)
        return True
    out_vals, out_valid = numeric_cols[colname]
    dev_lane.submit(plan, token, vt, out_off, n, nm,
                    out_vals, out_valid)
    return True


class _PlanFile:
    """One TSM file of a scan: its reader, the table's PageIndex, where
    the asked series stand in it and the query's columns resolved against
    it (once a file, not once a chunk) — then, once the plan is made, the
    file's own pages of it."""

    __slots__ = ("meta", "reader", "index", "cold", "series", "rows",
                 "cols", "page", "chunk", "off", "_chunk_cols")

    def __init__(self, meta, reader, index, series, rows, cols):
        self.meta = meta
        self.reader = reader
        self.index = index
        self.cold = reader.is_cold
        self.series = series    # positions in the asked series ids …
        self.rows = rows        # … and those series' chunk rows
        self.cols = cols        # query column → ColumnIndex | None
        self.page = None        # the file's planned pages: rows of the
        self.chunk = None       # index's page arrays, their chunk rows
        self.off = None         # and their first rows in the batch
        self._chunk_cols: dict[int, dict] = {}

    def chunk_cols(self, c: int) -> dict:
        """→ query column name → ColumnMeta of chunk row `c` (what the
        per-page paths read: constraints, the compressed-domain lane,
        the cold prefetch)."""
        cols = self._chunk_cols.get(c)
        if cols is None:
            cols = self._chunk_cols[c] = {
                name: col.metas[c] for name, col in self.cols.items()
                if col is not None and col.present[c]}
        return cols


class _Pages:
    """A scan's page plan: an array row a time page, in the order the
    batch has — series in `series_ids` order, a series' chunks by min_ts,
    a chunk's pages in file order."""

    __slots__ = ("file", "page", "chunk", "series", "n_rows", "inside")

    def __init__(self, file, page, chunk, series, n_rows, inside):
        self.file = file        # position in the scan's _PlanFile list
        self.page = page        # row of that file's index page arrays
        self.chunk = chunk      # row of its chunk arrays
        self.series = series    # position in the asked series ids
        self.n_rows = n_rows
        # the page lies inside ONE of the time ranges: all its rows pass
        # and it needs no row-level trim (anything else trims)
        self.inside = inside

    def __len__(self):
        return len(self.page)

    def take(self, keep: np.ndarray) -> None:
        for k in self.__slots__:
            setattr(self, k, getattr(self, k)[keep])

    def at(self, k: int, pfiles: list):
        """→ (file, chunk row, page's position in the chunk) of row k."""
        f = pfiles[int(self.file[k])]
        c = int(self.chunk[k])
        return f, c, int(self.page[k] - f.index.page_lo[c])


def _series_to_merge(vnode: VnodeCut, table: str, sids: np.ndarray,
                     pfiles: list, trs: TimeRanges) -> np.ndarray:
    """→ bool over `sids`: the series whose metadata alone does NOT prove
    the merge of their parts a concatenation — unflushed rows that may lie
    inside `trs`, a tombstone of one of their files that matches them,
    chunks that overlap in time across files, a chunk whose pages are not
    aligned. They take the per-series path (`_merged_series`)."""
    merge = np.zeros(len(sids), dtype=bool)
    mem = _mem_series_ids(vnode, table, trs)
    if mem:
        merge |= np.isin(sids, np.fromiter(mem, dtype=np.uint64,
                                           count=len(mem)))
    version = vnode.summary.version
    for f in pfiles:
        tb = version.tombstone(f.meta)
        if not tb.is_empty:
            for e in tb.entries:
                if e.table is None or e.table == table:
                    merge[f.series if e.series_id is None else f.series[
                        sids[f.series] == np.uint64(e.series_id)]] = True
        if not f.index.all_aligned:
            merge[f.series[~f.index.aligned[f.rows]]] = True
    spans = sorted((f.meta.min_ts, f.meta.max_ts) for f in pfiles)
    if any(a[1] >= b[0] for a, b in zip(spans, spans[1:])):
        # (files that do not meet in time hold no two chunks that do)
        series = np.concatenate([f.series for f in pfiles])
        lo = np.concatenate([f.index.min_ts[f.rows] for f in pfiles])
        hi = np.concatenate([f.index.max_ts[f.rows] for f in pfiles])
        order = np.lexsort((lo, series))
        series, lo, hi = series[order], lo[order], hi[order]
        clash = (series[1:] == series[:-1]) & (hi[:-1] >= lo[1:])
        merge[series[1:][clash]] = True
    return merge


def _plan_pages(pfiles: list, merge: np.ndarray | None,
                trs: TimeRanges) -> _Pages:
    """The time-admitted pages of every series the index plans (all but
    those under `merge`), from array operations a file."""
    from .tsm import ranges

    parts = []
    for fi, f in enumerate(pfiles):
        index = f.index
        series, rows = f.series, f.rows
        if merge is not None:
            keep = ~merge[series]
            series, rows = series[keep], rows[keep]
        if index.single_pages:      # a chunk's row is its page's row
            page = rows
        else:
            lo = index.page_lo[rows]
            n = index.page_lo[rows + 1] - lo
            page = ranges(lo, n)
            of = np.repeat(np.arange(len(rows)), n)   # a page's chunk
            series, rows = series[of], rows[of]
        if trs.is_all:
            inside = np.ones(len(page), dtype=bool)
        else:
            t_min, t_max = index.t_min[page], index.t_max[page]
            admitted = inside = None
            for r in trs.ranges:
                meets = (t_min <= r.max_ts) & (t_max >= r.min_ts)
                within = (t_min >= r.min_ts) & (t_max <= r.max_ts)
                admitted = meets if admitted is None else admitted | meets
                inside = within if inside is None else inside | within
            if admitted is None:
                admitted = inside = np.zeros(len(page), dtype=bool)
            if not admitted.all():
                if f.cold:
                    _count_cold_pruned(len(page) - int(admitted.sum()))
                page, series, rows, inside = page[admitted], \
                    series[admitted], rows[admitted], inside[admitted]
        parts.append((np.full(len(page), fi, dtype=np.int64), page, rows,
                      series, index.time[page, 3], inside,
                      index.min_ts[rows]))
    if not parts:
        none = np.empty(0, dtype=np.int64)
        return _Pages(none, none, none, none, none, np.empty(0, dtype=bool))
    if len(parts) == 1:
        return _Pages(*parts[0][:6])
    cols = [np.concatenate(c) for c in zip(*parts)]
    # pages of one chunk are admitted in file order; chunks of one series
    # that the index plans never share a min_ts (they do not overlap)
    order = np.lexsort((cols[1], cols[6], cols[3]))
    return _Pages(*(c[order] for c in cols[:6]))


def _constrain_pages(pages: _Pages, pfiles: list, constraints: dict) -> bool:
    """Drop the planned pages whose statistics prove no row can satisfy a
    constrained conjunct → whether any was dropped. Runs a page at a time
    over the time-admitted pages only."""
    keep = np.ones(len(pages), dtype=bool)
    for k in range(len(pages)):
        f, c, i = pages.at(k, pfiles)
        keep[k] = _page_admits(f.chunk_cols(c), i, constraints)
    if keep.all():
        return False
    for fi, f in enumerate(pfiles):
        if f.cold:
            n = int((~keep & (pages.file == fi)).sum())
            if n:
                _count_cold_pruned(n)
    pages.take(keep)
    return True


def _scan_vnode_native(vnode: VnodeCut, table: str,
                       series_ids, trs: TimeRanges,
                       field_names: list[str], constraints: dict,
                       n_threads: int,
                       upload_hook=None,
                       decode_hook=None,
                       compressed_spec=None) -> ScanBatch | None:
    from . import native

    dev_lane = decode_hook() if decode_hook is not None else None
    native_ok = native.pagedec_available()
    if not native_ok and dev_lane is None and compressed_spec is None:
        # no fast decode lane and no compressed-domain work: the simple
        # per-series fallback below is equivalent and cheaper to plan.
        # With a spec the page-level plan is still worth building — the
        # lane skips/answers pages before any decode, and survivors fall
        # through to the per-page Python jobs
        return None

    # ---------------------------------------------------------------- plan
    with stages.stage("scan.plan_ms"):
        sids = np.asarray(series_ids, dtype=np.uint64)
        version = vnode.summary.version
        targets = {n: (cid, tuple(names)) for n, (cid, names)
                   in _field_targets(vnode, table, field_names).items()}
        pfiles: list[_PlanFile] = []
        for level in (4, 3, 2, 1, 0):
            for fm in sorted(version.levels[level].values(),
                             key=lambda f: f.file_id):
                if not trs.is_all and not trs.overlaps(
                        TimeRange(fm.min_ts, fm.max_ts)):
                    continue
                r = version.reader(fm)
                index = r.page_index(table)
                if index is None:
                    continue
                series, rows = index.rows_of(sids)
                if len(series):
                    pfiles.append(_PlanFile(
                        fm, r, index, series, rows,
                        {n: index.column(targets[n]) for n in field_names}))

        # the series metadata cannot plan are read and merged here, one
        # at a time, and splice into their reserved span further down
        merge = _series_to_merge(vnode, table, sids, pfiles, trs)
        spliced: dict[int, tuple] = {}   # position in sids → (ts, fields)
        merged_pages = 0
        n_merged = int(np.count_nonzero(merge))
        for j in np.flatnonzero(merge).tolist() if n_merged else ():
            ts, fields, n_pages = _merged_series(vnode, table, int(sids[j]),
                                                 field_names, trs)
            merged_pages += n_pages
            if len(ts):
                spliced[j] = (ts, fields)
        if dev_lane is not None and merged_pages:
            # every page scanned is booked once: these went neither to the
            # device lane nor to the native decoder but through the
            # per-series merge (memcache rows, tombstones or overlapping
            # chunks)
            dev_lane.declined("series_merge", merged_pages)

        pages = _plan_pages(pfiles, merge if n_merged else None, trs)
        any_pruned = bool(constraints) and len(pages) > 0 \
            and _constrain_pages(pages, pfiles, constraints)
        any_trim = not pages.inside.all()

    # --------------------------------------------- compressed-domain lane
    # lane zero: before any bytes move, pages provably skippable or
    # answerable from their encoded representation leave the plan; their
    # aggregate contributions ride the batch as pre-aggregated partials
    lane = None
    if compressed_spec is not None:
        lane = compressed_domain.ScanLane(compressed_spec, trs,
                                          vnode.index)

        def lane_page(k):
            f, c, i = pages.at(k, pfiles)
            return (int(sids[pages.series[k]]), f.reader, f.index.chunks[c],
                    f.chunk_cols(c), i)

        with stages.stage("compressed_ms"):
            keep = lane.filter_pages(pages.inside, lane_page)
        if lane.engaged:
            any_pruned = True
            pages.take(keep)

    with stages.stage("scan.plan_ms"):
        # rows a series, and where each series and each page starts
        rows = np.bincount(pages.series, weights=pages.n_rows,
                           minlength=len(sids)).astype(np.int64)
        page_off = np.cumsum(pages.n_rows) - pages.n_rows
        series_off = None       # only a spliced series asks for its own
        if spliced:
            planned_off = np.cumsum(rows) - rows
            for j, (ts, _fields) in spliced.items():
                rows[j] = len(ts)
            series_off = np.cumsum(rows) - rows
            page_off += (series_off - planned_off)[pages.series]
        total = int(rows.sum())
        stages.count("scan_plan.indexed_series",
                     int(np.count_nonzero(rows)) - len(spliced))
        stages.count("scan_plan.merged_series", n_merged)
        for fi, f in enumerate(pfiles):
            of = slice(None) if len(pfiles) == 1 \
                else np.flatnonzero(pages.file == fi)
            f.page, f.chunk, f.off = pages.page[of], pages.chunk[of], \
                page_off[of]

    if total == 0:
        b = ScanBatch(table, np.empty(0, dtype=np.uint64), [],
                      np.empty(0, dtype=np.int64),
                      np.empty(0, dtype=np.int32), {})
        b._pages_pruned = any_pruned
        if lane is not None:
            lane_wants: dict[int, tuple] = {}
            lane.extend_cold_wants(lane_wants)
            for r, pms in lane_wants.values():
                r.fetch_pages(pms)
            with stages.stage("compressed_ms"):
                lane.run_jobs()
            lane.attach(b)
        return b

    # ------------------------------------------------- cold-tier prefetch
    # every page that survived pruning on a cold reader is fetched up
    # front in one coalesced ranged-GET pass, so the decode lanes below
    # hit the block cache instead of issuing a GET per page
    cold_wants: dict[int, tuple] = {}
    for f in pfiles:
        if not (f.cold and len(f.page)):
            continue
        lst = cold_wants.setdefault(id(f.reader), (f.reader, []))[1]
        for c, p in zip(f.chunk.tolist(), f.page.tolist()):
            i = p - int(f.index.page_lo[c])
            lst.append(f.index.chunks[c].time_pages[i])
            lst.extend(cm.pages[i] for cm in f.chunk_cols(c).values())
    if lane is not None:
        # closed-form jobs read only the pages they need (often just the
        # time page) — those ranges join the same coalesced GET pass, so
        # answered pages' VALUE bytes are never downloaded
        lane.extend_cold_wants(cold_wants)
    for r, pms in cold_wants.values():
        r.fetch_pages(pms)
    if lane is not None and lane.jobs:
        with stages.stage("compressed_ms"):
            lane.run_jobs()

    with stages.stage("scan.plan_ms"):
        # --------------------------------------------------- column typing
        # a column is typed by the first chunk of the batch that holds it,
        # and the columns stand in the order they first appear
        first_seen: dict[str, tuple] = {}   # name → (batch row, ValueType)

        def _seen(name, off, vt):
            if name not in first_seen or off < first_seen[name][0]:
                first_seen[name] = (off, vt)

        for f in pfiles:
            if not len(f.page):
                continue
            first = None
            for name, col in f.cols.items():
                if col is None:
                    continue
                if col.everywhere:
                    if first is None:
                        first = int(f.off[0]), int(f.chunk[0])
                    off, c = first
                else:
                    k = int(col.present[f.chunk].argmax())
                    off, c = int(f.off[k]), int(f.chunk[k])
                    if not col.present[c]:
                        continue
                _seen(name, off, ValueType(int(col.vt0[c])))
        for j, (_ts, fields) in spliced.items():
            for name, (vt, _v, _m) in fields.items():
                _seen(name, int(series_off[j]), vt)
        rank = {name: k for k, name in enumerate(field_names)}
        ftypes: dict[str, ValueType] = {
            name: first_seen[name][1] for name in sorted(
                first_seen, key=lambda n: (first_seen[n][0], rank[n]))}

        # ------------------------------------------------------- allocate
        numeric_cols: dict[str, tuple[np.ndarray, np.ndarray]] = {}
        string_parts: dict[str, list] = {}
        string_valid: dict[str, np.ndarray] = {}
        with stages.stage("scan.alloc_ms"):
            ts_all = np.empty(total, dtype=np.int64)
            for name, vt in ftypes.items():
                if vt in (ValueType.STRING, ValueType.GEOMETRY):
                    string_parts[name] = []
                    string_valid[name] = np.zeros(total, dtype=bool)
                    continue
                dt = vt.numpy_dtype()
                numeric_cols[name] = (np.zeros(total, dtype=dt),
                                      np.zeros(total, dtype=bool))

        # --------------------------------------- descriptors per (file, col)
        # one native task a (file, column): (group, column | None for the
        # time column, descriptors, job_at) — job_at(b) → (PageMeta,
        # out_off) of descriptor b, asked for a page the decoder rejects
        tasks: list = []
        groups: dict[int, dict] = {}   # id(reader) → its base + page lists
        py_jobs: list = []   # (reader, pm, colname|None, out_off, vt)

        def _group(r):
            g = groups.get(id(r))
            if g is None:
                g = groups[id(r)] = {"base": r.buffer_array(), "cols": {},
                                     "reader": r}
            return g

        def _add_page(r, pm, colname, out_off, kind):
            lst = _group(r)["cols"].setdefault(colname, ([], []))
            lst[0].append((pm.offset, pm.size, out_off, pm.n_rows, kind,
                           pm.n_values))
            lst[1].append((pm, out_off))

        # the route of a page: a lane that stands behind the native decoder
        # (auto mode — decoded values land in the host arrays allocated
        # above) leaves it every page it can take; a device-first lane
        # (forced, or handed in directly) is asked first. Where nobody is
        # asked first, a (file, column) whose index says that every page
        # is one the native decoder takes — a local reader, one value
        # type (the column's) in encodings it knows — gets its
        # descriptors by indexing; every other (file, column) is routed
        # a page at a time, in plan order
        native_first = dev_lane is not None and dev_lane.native_first
        unasked = native_ok and (dev_lane is None or native_first)
        n_native_first = 0
        bytes_materialized = 0   # page bytes routed into ANY decode lane
        paged: dict[int, tuple] = {}   # file → (time paged?, [columns])
        for fi, f in enumerate(pfiles):
            if not len(f.page):
                continue
            direct = unasked and not f.cold
            index = f.index
            if direct:
                desc = index.time[f.page]
                desc[:, 2] = f.off
                tasks.append((_group(f.reader), None, desc, _job_at(
                    index, None, f.chunk, f.page, f.off)))
            names = []
            for name in field_names:
                col = f.cols[name]
                if col is None:
                    continue
                page, chunk, off = f.page, f.chunk, f.off
                if not col.everywhere:
                    held = col.present[chunk]
                    page, chunk, off = page[held], chunk[held], off[held]
                    if not len(page):
                        continue
                kind = _NATIVE_NUMERIC.get(col.vt_all) \
                    if col.vt_all == int(ftypes[name]) else None
                if direct and kind is not None \
                        and col.encodings <= _NATIVE_ENC[kind]:
                    desc = col.desc[page]
                    desc[:, 2] = off
                    desc[:, 4] = kind
                    tasks.append((_group(f.reader), name, desc, _job_at(
                        index, col.metas, chunk, page, off)))
                else:
                    names.append(name)
            if names or not direct:
                paged[fi] = (not direct, names)
        if tasks:
            planned = np.concatenate([t[2] for t in tasks])
            n_native_first = len(planned)
            bytes_materialized = int(planned[:, 1].sum())

        for k in (np.flatnonzero(np.isin(pages.file, list(paged))).tolist()
                  if paged else ()):
            f, c, i = pages.at(k, pfiles)
            time_paged, names = paged[int(pages.file[k])]
            r, off = f.reader, int(page_off[k])
            if time_paged:
                tp = f.index.chunks[c].time_pages[i]
                bytes_materialized += tp.size
                time_native = native_ok and not f.cold
                if native_first and time_native:
                    n_native_first += 1
                    queued = False
                else:
                    queued = dev_lane is not None \
                        and dev_lane.accepts(int(ValueType.INTEGER),
                                             tp.encoding) \
                        and _submit_device_page(
                            dev_lane, r, tp, None, off, ValueType.INTEGER,
                            numeric_cols, string_parts, string_valid,
                            ts_all)
                if not queued:
                    if time_native:
                        _add_page(r, tp, None, off, 0)
                    else:
                        py_jobs.append((r, tp, None, off, None))
            for name in names:
                col = f.cols[name]
                if not col.present[c]:
                    continue   # absent column: stays zero/invalid
                pm = col.metas[c].pages[i]
                bytes_materialized += pm.size
                vt = ftypes[name]
                miss = _native_miss(native_ok, f.cold, pm, vt)
                if native_first and miss is None:
                    n_native_first += 1
                elif dev_lane is not None and pm.value_type == int(vt) \
                        and (vt in (ValueType.STRING, ValueType.GEOMETRY)
                             or dev_lane.accepts(pm.value_type,
                                                 pm.encoding)) \
                        and _submit_device_page(
                            dev_lane, r, pm, name, off, vt,
                            numeric_cols, string_parts, string_valid,
                            ts_all):
                    continue
                if miss is None:
                    _add_page(r, pm, name, off,
                              _NATIVE_NUMERIC[pm.value_type])
                else:
                    # neither the device lane nor the native decoder
                    # takes it: per-page Python path
                    _count_fallback(miss)
                    py_jobs.append((r, pm, name, off, vt))
        for g in groups.values():
            for colname, (desc_list, jobs) in g["cols"].items():
                tasks.append((g, colname, np.array(
                    desc_list, dtype=np.int64).reshape(-1, 6),
                    jobs.__getitem__))

        if lane is not None and lane.has_masks:
            for k in np.flatnonzero(pages.inside).tolist():
                f, c, i = pages.at(k, pfiles)
                lane.apply_page_masks(f.index.chunks[c], i,
                                      int(page_off[k]), total)

    if native_first and n_native_first:
        dev_lane.declined("native_first", n_native_first)

    # ------------------------------------------------------ device decode
    # the third lane runs BEFORE the native tasks: device writebacks land
    # in the shared output arrays first, so a column split between lanes
    # is already complete when _finish's eager upload sees it, and kernel
    # failures join py_jobs before dirty_cols is computed
    if dev_lane is not None and dev_lane.pending():
        with stages.stage("device_decode_ms"):
            py_jobs.extend(dev_lane.run())

    # ------------------------------------------------------- native decode
    # one task per (file, column): pages of one column across files write
    # DISJOINT row ranges of the same output array, so tasks run
    # concurrently on the shared decode pool. Eager upload: once every
    # task of a column has finished cleanly, its final array is handed to
    # the uploader while the remaining columns still decode (decode N+1
    # overlaps device_put of N — device_put enqueues are async).
    col_remaining: dict[str, int] = {}
    for _g, colname, _desc, _at in tasks:
        if colname is not None:
            col_remaining[colname] = col_remaining.get(colname, 0) + 1

    uploader = None
    if upload_hook is not None and not spliced \
            and not any_trim \
            and (lane is None or not lane.has_masks):
        # merged series splice into every column after decode, a time
        # trim re-slices the arrays, and compressed-domain survivor masks
        # gather a subset — all would invalidate an eagerly shipped copy,
        # so only clean scans pipeline uploads
        uploader = upload_hook(total)
    dirty_cols = {j[2] for j in py_jobs}
    if uploader is not None and dev_lane is not None:
        # a column the device lane decoded whole has no native task whose
        # end would ship it: its host array is complete now, so it ships
        # now, while the other columns' tasks run
        for name, (vals, valid) in numeric_cols.items():
            if name not in col_remaining and name not in dirty_cols:
                uploader.put(name, ftypes[name], vals, valid)

    def _run(task):
        g, colname, desc, _at = task
        out_vals, out_valid = (ts_all, None) if colname is None \
            else numeric_cols[colname]
        return native.decode_pages(g["base"], desc, out_vals, out_valid,
                                   n_threads=per_task_threads)

    def _finish(task, status) -> bool:
        """Fold one task's result back in (main thread); False = abort."""
        g, colname, _desc, job_at = task
        if status is None:
            return False   # library vanished mid-flight: legacy path
        for bi in np.nonzero(status)[0]:
            pm, out_off = job_at(bi)
            _count_fallback("native_reject")
            py_jobs.append((g["reader"], pm, colname, out_off,
                            ftypes.get(colname)))
            dirty_cols.add(colname)
        if colname is None:
            return True
        col_remaining[colname] -= 1
        if uploader is not None and col_remaining[colname] == 0 \
                and colname not in dirty_cols:
            vals, valid = numeric_cols[colname]
            uploader.put(colname, ftypes[colname], vals, valid)
        return True

    with stages.stage("scan.native_ms"):
        if len(tasks) > 1:
            from concurrent.futures import as_completed

            from ..utils.executor import submit as _submit

            per_task_threads = 1 if len(tasks) >= n_threads \
                else max(1, n_threads // len(tasks))
            futs = {_submit("decode", _run, t): t for t in tasks}
            aborted = False
            for fut in as_completed(futs):
                if not _finish(futs[fut], fut.result()):
                    aborted = True
            if aborted:
                return None
        else:
            per_task_threads = n_threads
            for t in tasks:
                if not _finish(t, _run(t)):
                    return None

    with stages.stage("scan.trim_ms"):
        # -------------------------------------------- python page fallbacks
        for r, pm, colname, out_off, vt in py_jobs:
            deadline_mod.check_current()
            n = pm.n_rows
            if colname is None:
                ts_all[out_off:out_off + n] = r.read_time_page(pm)
                continue
            dense, nm = r.read_field_page(pm)
            if vt in (ValueType.STRING, ValueType.GEOMETRY):
                da = _as_dict_part(dense)
                if nm is None:
                    codes = da.codes.astype(np.int32)
                    valid_p = np.ones(n, dtype=bool)
                else:
                    codes = np.zeros(n, dtype=np.int32)
                    codes[~nm] = da.codes
                    valid_p = ~nm
                string_parts[colname].append(
                    (out_off, DictArray(codes, da.values)))
                string_valid[colname][out_off:out_off + n] = valid_p
                continue
            vals, valid = numeric_cols[colname]
            if nm is None:
                vals[out_off:out_off + n] = dense
                valid[out_off:out_off + n] = True
            else:
                vals[out_off:out_off + n][~nm] = dense
                valid[out_off:out_off + n] = ~nm

        # -------------------------------------------- merged series splice
        for j, (ts, fields) in spliced.items():
            base_off = int(series_off[j])
            n = len(ts)
            ts_all[base_off:base_off + n] = ts
            for name, (vt, vals_p, valid_p) in fields.items():
                if vt in (ValueType.STRING, ValueType.GEOMETRY):
                    da = _as_dict_part(vals_p)
                    string_parts[name].append(
                        (base_off, DictArray(da.codes.astype(np.int32),
                                             da.values)))
                    string_valid[name][base_off:base_off + n] = valid_p
                else:
                    vals, valid = numeric_cols[name]
                    vals[base_off:base_off + n] = vals_p
                    valid[base_off:base_off + n] = valid_p

        kept = np.flatnonzero(rows)
        kept_sids = sids[kept]
        keys = [vnode.index.get_series_key(sid)
                for sid in kept_sids.tolist()]
        sid_ordinal = np.repeat(np.arange(len(kept), dtype=np.int32),
                                rows[kept])

        # ------------------------------------------------- assemble + trim
        out_fields: dict = {}
        for name, (vals, valid) in numeric_cols.items():
            out_fields[name] = (ftypes[name], vals, valid)
        for name, parts in string_parts.items():
            das = [p[1] for p in parts]
            union = unify_dictionaries(das) if das else np.array(
                [""], dtype=object)
            codes_all = np.zeros(total, dtype=np.int32)
            for (p_off, da), d in zip(parts, das):
                codes_all[p_off:p_off + len(da.codes)] = d.remap_to(union)
            out_fields[name] = (ftypes[name], DictArray(codes_all, union),
                                string_valid[name])

        row_mask = lane.row_mask if lane is not None else None
        if any_trim or row_mask is not None:
            keep = _time_mask(ts_all, trs) if any_trim else None
            if row_mask is not None:
                # late materialization: only rows surviving every
                # compressed-domain predicate mask are gathered
                keep = row_mask if keep is None else (keep & row_mask)
            if keep is not None and not keep.all():
                ts_all = ts_all[keep]
                sid_ordinal = sid_ordinal[keep]
                out_fields = {
                    name: (vt,
                           (DictArray(v.codes[keep], v.values)
                            if isinstance(v, DictArray) else v[keep]),
                           m[keep])
                    for name, (vt, v, m) in out_fields.items()}
                # drop series trimmed to zero rows and renumber ordinals
                pres = np.bincount(sid_ordinal, minlength=len(kept_sids))
                if (pres == 0).any():
                    keep_s = np.nonzero(pres > 0)[0]
                    remap = np.full(len(kept_sids), -1, dtype=np.int32)
                    remap[keep_s] = np.arange(len(keep_s), dtype=np.int32)
                    sid_ordinal = remap[sid_ordinal]
                    kept_sids = kept_sids[keep_s]
                    keys = [keys[i] for i in keep_s]

        b = ScanBatch(table, kept_sids, keys, ts_all, sid_ordinal,
                      out_fields)
        b._pages_pruned = any_pruned
        if lane is not None:
            bytes_materialized += lane.bytes_materialized
            lane.attach(b)
        if bytes_materialized:
            stages.count("compressed.bytes_materialized",
                         bytes_materialized)
        if uploader is not None:
            uploader.attach(b)
        return b


def _job_at(index, metas, chunk, page, off):
    """→ job_at(b) → (PageMeta, out_off) of row b of a task's
    descriptors, for a (file, column) planned from the index (`metas`:
    the column's ColumnMeta a chunk row, None for the time column)."""
    def job_at(b):
        c = int(chunk[b])
        i = int(page[b] - index.page_lo[c])
        held = index.chunks[c].time_pages if metas is None \
            else metas[c].pages
        return held[i], int(off[b])
    return job_at


def _discover_fields(vnode: VnodeCut, table: str) -> list[str]:
    names: set[str] = set()
    schema = vnode.schemas.get(table)
    if schema is not None:
        return schema.field_names()
    for fm in vnode.summary.version.all_files():
        r = vnode.summary.version.reader(fm)
        g = r.groups.get(table)
        if g:
            for cm in g.chunks.values():
                names.update(c.name for c in cm.columns)
    for cache in vnode.caches():
        for (t, _sid), sd in list(cache.series.items()):
            if t == table:
                names.update(sd.field_names())
    return sorted(names)
