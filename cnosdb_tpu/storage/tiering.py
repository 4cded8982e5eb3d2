"""Tiered object-store cold storage with near-data pruning.

ROADMAP open item 2 (Taurus, arxiv 2506.20010; "Should I Hide My Duck in
the Lake?", arxiv 2602.18775): retention stops being capped by local disk
by aging sealed TSM files into the object store while keeping a local
**skip-index sidecar** — the file's trailing metadata section (chunk/page
meta with zone maps and trigram ngram signatures, bloom filter, footer) —
so per-page pruning (time range, value stats, tag domains, LIKE '%x%')
runs entirely locally *before* any byte is downloaded. Surviving pages
fetch via byte-range GETs (utils/objstore.py) through a capped local
block cache and feed the existing device/native/py decode lanes
unchanged.

Physical layout per tiered file ``_{id:06d}.tsm``:

* object store: the complete original file at key
  ``{prefix}/vnode_{vid}/f{id:06d}.tsm`` (bit-identical — rehydration is
  a download, and scrub can verify it against the sidecar's footer);
* local sidecar ``_{id:06d}.tsmc`` (same delta/tsm subdir; the ``.tsm``
  suffix GC in summary.py never touches it):
  ``[magic u32][ver u8][orig_size u64][tail_off u64]`` + the original
  bytes ``[tail_off:]`` where ``tail_off = footer.meta_off`` — pages live
  in ``[5, meta_off)`` and stay remote;
* per-vnode registry ``cold.json`` mapping file_id → {key, size,
  tail_off}, consulted by ``Version.reader`` (summary.py) to open a
  :class:`ColdTsmReader` instead of the mmap reader.

Every exit out of the cold lane books a (lane, reason) into
``cnosdb_cold_tier_total`` — enforced by the ``cold-tier-accounting``
lint rule — so downloads and silent fallbacks stay visible on /metrics
(EXPLAIN ANALYZE shows ``cold.pages_pruned``).
"""
from __future__ import annotations

import json
import os
import struct
import threading
import time
import zlib

from .. import faults
from ..errors import ChecksumMismatch, StorageError, TsmError
from ..utils import lockwatch, stages
from ..utils import objstore
from .tombstone import tombstone_path
from .tsm import FOOTER_SIZE, TsmReader, parse_tail

SIDECAR_MAGIC = 0x7C05DBC1
SIDECAR_VERSION = 1
_SIDECAR_HDR = struct.Struct("<IBQQ")

faults.register_point("tiering.registry", __name__,
                      desc="cold.json rewrite, between fsync and rename")
SIDECAR_SUFFIX = ".tsmc"
REGISTRY_NAME = "cold.json"

# pruned-page gaps smaller than this ride along inside one coalesced
# range GET — a second request round-trip costs more than the bytes
COALESCE_GAP = int(os.environ.get("CNOSDB_COLD_COALESCE_GAP", 64 * 1024))


def enabled() -> bool:
    """Whether the tiering plane may *move* data (CNOSDB_COLD_TIER=0 is
    the parity knob: nothing tiers, everything scans hot). Reading
    already-tiered files is never gated — the bytes only exist remotely."""
    return os.environ.get("CNOSDB_COLD_TIER", "1") != "0" and configured()


# ---------------------------------------------------------------------------
# store configuration (process-global, set from config/server wiring;
# credentials live here and are never persisted into cold.json)
# ---------------------------------------------------------------------------
_cfg_lock = lockwatch.Lock("tiering.config")
_cfg: dict = {"uri": "", "options": {}, "store": None, "prefix": ""}


def configure(uri: str | None, options: dict | None = None) -> None:
    """Point the cold tier at `uri` (s3://…, gcs://…, azblob://…, or a
    local directory path); empty/None unconfigures."""
    with _cfg_lock:
        _cfg["uri"] = (uri or "").strip()
        _cfg["options"] = dict(options or {})
        _cfg["store"] = None
        _cfg["prefix"] = ""


def configured() -> bool:
    with _cfg_lock:
        return bool(_cfg["uri"])


def _store_and_prefix():
    """→ (store, key_prefix). The store client is built once per
    configure() and shared — stores are stateless over HTTP."""
    with _cfg_lock:
        if not _cfg["uri"]:
            raise StorageError("cold tier not configured (storage.tiering_uri)")
        if _cfg["store"] is None:
            store, prefix = objstore.store_for(_cfg["uri"], _cfg["options"])
            _cfg["store"] = store
            _cfg["prefix"] = prefix.rstrip("/")
        return _cfg["store"], _cfg["prefix"]


def _object_key(vnode_id: int, file_id: int) -> str:
    _, prefix = _store_and_prefix()
    rel = f"vnode_{vnode_id}/f{file_id:06d}.tsm"
    return f"{prefix}/{rel}" if prefix else rel


# ---------------------------------------------------------------------------
# accounting — cnosdb_cold_tier_total{lane,reason}
# ---------------------------------------------------------------------------
_counts_lock = lockwatch.Lock("tiering.counters")
_counts: dict[tuple[str, str], int] = {}


def _count_cold(lane: str, reason: str, n: int = 1) -> None:
    with _counts_lock:
        _counts[(lane, reason)] = _counts.get((lane, reason), 0) + n


def cold_tier_snapshot() -> dict[tuple[str, str], int]:
    with _counts_lock:
        return dict(_counts)


def counters_reset() -> None:
    with _counts_lock:
        _counts.clear()


# ---------------------------------------------------------------------------
# block cache — fetched page ranges, keyed (object_key, page_offset) and
# LRU'd by dict reinsertion with a byte cap, like the coordinator's scan
# cache (parallel/coordinator.py _cache_store)
# ---------------------------------------------------------------------------
BLOCK_CACHE_MAX_BYTES = int(os.environ.get(
    "CNOSDB_COLD_BLOCK_CACHE_MAX_BYTES", 64 * 1024 * 1024))

_cache_lock = lockwatch.Lock("tiering.block_cache")
_cache: dict[tuple[str, int], bytes] = {}
_cache_bytes = 0


def _cache_get(key: str, offset: int) -> bytes | None:
    with _cache_lock:
        raw = _cache.pop((key, offset), None)
        if raw is not None:
            _cache[(key, offset)] = raw   # LRU: reinsert on hit
        return raw


def _cache_put(key: str, offset: int, raw: bytes) -> None:
    global _cache_bytes
    if len(raw) > BLOCK_CACHE_MAX_BYTES:
        return
    with _cache_lock:
        old = _cache.pop((key, offset), None)
        if old is not None:
            _cache_bytes -= len(old)
        _cache[(key, offset)] = raw
        _cache_bytes += len(raw)
        while _cache_bytes > BLOCK_CACHE_MAX_BYTES and _cache:
            oldest = next(iter(_cache))     # LRU head: first-inserted key
            _cache_bytes -= len(_cache.pop(oldest))


def block_cache_stats() -> dict:
    with _cache_lock:
        return {"entries": len(_cache), "bytes": _cache_bytes,
                "max_bytes": BLOCK_CACHE_MAX_BYTES}


def block_cache_clear() -> None:
    global _cache_bytes
    with _cache_lock:
        _cache.clear()
        _cache_bytes = 0


def _block_cache_reclaim(target_bytes: int) -> int:
    """Broker reclaim callback: shed LRU block-cache entries until
    `target_bytes` are freed — a lost block is just a re-fetch."""
    global _cache_bytes
    freed = 0
    with _cache_lock:
        while _cache and freed < target_bytes:
            oldest = next(iter(_cache))
            freed += len(_cache.pop(oldest))
        _cache_bytes = max(0, _cache_bytes - freed)
    return freed


def _register_block_cache_pool() -> None:
    # module-level cache, module-level (import-time) registration: the
    # memory-governance broker can shrink the cold block cache when the
    # node crosses its soft watermark
    from ..server import memory as _memory

    _memory.register_pool(
        "block_cache",
        usage_fn=lambda: block_cache_stats()["bytes"],
        reclaim=_block_cache_reclaim)


_register_block_cache_pool()


# ---------------------------------------------------------------------------
# per-vnode cold registry (cold.json)
# ---------------------------------------------------------------------------
_reg_lock = lockwatch.Lock("tiering.registry")
_registry: dict[str, tuple[float, dict[int, dict]]] = {}   # dir → (mtime, map)


def _registry_path(dir_path: str) -> str:
    return os.path.join(dir_path, REGISTRY_NAME)


def cold_map(dir_path: str) -> dict[int, dict]:
    """file_id → {key, size, tail_off} for one vnode dir; {} when the
    vnode has no cold files. mtime-validated cache — tier/rehydrate go
    through _registry_mutate which rewrites the file atomically."""
    path = _registry_path(dir_path)
    try:
        mtime = os.stat(path).st_mtime_ns
    except OSError:
        return {}
    with _reg_lock:
        hit = _registry.get(dir_path)
        if hit is not None and hit[0] == mtime:
            return hit[1]
    try:
        with open(path, "r", encoding="utf-8") as f:
            raw = json.load(f)
        m = {int(fid): e for fid, e in raw.get("files", {}).items()}
    except (OSError, ValueError) as e:
        # a registry that exists but does not parse must be LOUD: treating
        # it as empty would drop every cold file from scans and let the
        # next _registry_mutate rewrite cold.json without them — silent
        # data loss (found by the crash-point sweep's torn-registry arm).
        # TsmError rides the coordinator's recover-and-retry path, where
        # recover_vnode() rebuilds the registry from the local sidecars.
        _count_cold("registry", "unreadable")
        stages.count_error("tiering.registry")
        raise TsmError(f"cold registry unreadable (rebuild via "
                       f"recover_vnode): {path}: {e}") from e
    with _reg_lock:
        _registry[dir_path] = (mtime, m)
    return m


def cold_entry(dir_path: str, file_id: int) -> dict | None:
    return cold_map(dir_path).get(file_id)


def cold_ids(dir_path: str) -> frozenset[int]:
    return frozenset(cold_map(dir_path))


def cold_objects(dir_path: str) -> list[str]:
    """The tiering-store object keys a vnode's cold files reference. The
    DR manifest (storage/backup.py) records these as referenced-not-
    copied: a restored vnode keeps reading the SAME tiering objects
    through the cold.json it restored (entries carry full keys, so a
    restore onto a different vnode id still resolves them), which keeps
    backups incremental over cold data."""
    return sorted(e["key"] for e in cold_map(dir_path).values())


def _registry_write(dir_path: str, m: dict[int, dict]) -> None:
    """Install a full registry image atomically (tmp + fsync + rename).
    The `tiering.registry` fault point sits between the durable tmp and
    the rename — `crash` there leaves the OLD registry intact (atomicity
    witness), `torn(n)` installs a truncated image (bit-rot model that
    cold_map now refuses loudly instead of reading as empty)."""
    path = _registry_path(dir_path)
    tmp = path + ".tmp"
    with open(tmp, "w", encoding="utf-8") as f:
        json.dump({"files": {str(fid): e for fid, e in sorted(m.items())}}, f)
        f.flush()
        os.fsync(f.fileno())
    torn = False
    if faults.ENABLED:
        hit = faults.fire("tiering.registry", dir=dir_path, path=path)
        if hit is not None and hit[0] == "torn":
            with open(tmp, "r+b") as tf:
                tf.truncate(int(hit[1] or 8))
            torn = True
    os.replace(tmp, path)
    with _reg_lock:
        if torn:
            # the on-disk image is damaged: caching the good in-memory
            # map would mask the tear from this very process and defer
            # discovery to the next boot — drop the entry so the next
            # read hits the disk image and the recover path
            _registry.pop(dir_path, None)
        else:
            _registry[dir_path] = (os.stat(path).st_mtime_ns, m)


def _registry_mutate(dir_path: str, file_id: int, entry: dict | None) -> None:
    """Add (entry != None) or remove one cold record, atomically. Callers
    hold the vnode lock, serializing mutators."""
    m = dict(cold_map(dir_path))
    if entry is None:
        m.pop(file_id, None)
    else:
        m[file_id] = entry
    _registry_write(dir_path, m)


# ---------------------------------------------------------------------------
# sidecar
# ---------------------------------------------------------------------------
def sidecar_path(data_path: str) -> str:
    base, _ = os.path.splitext(data_path)
    return base + SIDECAR_SUFFIX


def write_sidecar(data_path: str, orig_size: int, tail_off: int,
                  tail: bytes) -> str:
    side = sidecar_path(data_path)
    tmp = side + ".tmp"
    with open(tmp, "wb") as f:
        f.write(_SIDECAR_HDR.pack(SIDECAR_MAGIC, SIDECAR_VERSION,
                                  orig_size, tail_off))
        f.write(tail)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, side)
    return side


def read_sidecar(data_path: str) -> tuple[int, int, bytes]:
    """→ (orig_size, tail_off, tail_bytes); raises TsmError on a missing
    or malformed sidecar (recover_vnode rebuilds it from the store)."""
    side = sidecar_path(data_path)
    try:
        with open(side, "rb") as f:
            raw = f.read()
    except FileNotFoundError:
        raise TsmError("sidecar missing", path=side)
    if len(raw) < _SIDECAR_HDR.size + FOOTER_SIZE:
        raise TsmError("sidecar too small", path=side)
    magic, ver, orig_size, tail_off = _SIDECAR_HDR.unpack_from(raw, 0)
    if magic != SIDECAR_MAGIC or ver != SIDECAR_VERSION:
        raise TsmError("bad sidecar magic", path=side)
    return orig_size, tail_off, raw[_SIDECAR_HDR.size:]


# ---------------------------------------------------------------------------
# cold reader
# ---------------------------------------------------------------------------
class ColdTsmReader(TsmReader):
    """Reader over a tiered TSM file: metadata parses from the local
    sidecar, page bytes fetch on demand via byte-range GETs through the
    block cache. Inherits every decode path from TsmReader — the device
    lane (`read_field_page_split`), the py lane (`read_time_page` /
    `read_field_page`) and the per-series fallbacks all route through
    `_read_page`. The *native* batch lane needs a whole-file mmap and is
    routed away by scan.py (`is_cold`)."""

    is_cold = True

    def __init__(self, data_path: str, key: str, size: int, tail_off: int,
                 store=None):
        # no super().__init__ — there is no local data file to mmap.
        # self.path keeps the logical hot path so ChecksumMismatch ctx /
        # quarantine-by-path keep their identity.
        self.path = data_path
        self.key = key
        self.size = int(size)
        self._f = None
        self._buf = b""
        self._store = store if store is not None else _store_and_prefix()[0]
        orig_size, side_tail_off, tail = read_sidecar(data_path)
        if orig_size != self.size:
            _count_cold("open", "sidecar_size_mismatch")
            raise TsmError("sidecar/registry size mismatch", path=data_path)
        self.tail_off = int(side_tail_off)
        self.groups, self.bloom, self.footer = parse_tail(
            tail, data_path, tail_off=self.tail_off)
        self._page_indexes = {}
        self.min_ts = self.footer.min_ts
        self.max_ts = self.footer.max_ts
        self.series_count = self.footer.series_count

    def close(self):
        self._buf_arr = None
        self._buf = b""

    def buffer_array(self):
        _count_cold("scan", "buffer_array_refused")
        raise StorageError(
            f"cold reader {self.path} has no local buffer — the native "
            f"batch lane must not be routed cold pages")

    # -- page fetch ------------------------------------------------------
    def fetch_pages(self, pms) -> int:
        """Ensure every page in `pms` is block-cached, coalescing adjacent
        ranges (gap ≤ COALESCE_GAP) into few range GETs. → bytes actually
        downloaded. This is the scan prefetch entry: one batched round of
        GETs for all admitted pages instead of a request per page."""
        want = []
        for pm in pms:
            if _cache_get(self.key, pm.offset) is None:
                want.append((pm.offset, pm.size))
        if not want:
            _count_cold("fetch", "prefetch_all_cached")
            return 0
        want.sort()
        ranges: list[list[int]] = []
        for off, size in want:
            if ranges and off - (ranges[-1][0] + ranges[-1][1]) \
                    <= COALESCE_GAP:
                ranges[-1][1] = off + size - ranges[-1][0]
            else:
                ranges.append([off, size])
        downloaded = 0
        for start, length in ranges:
            raw = self._store.get_range(self.key, start, length)
            downloaded += len(raw)
            for off, size in want:
                if start <= off and off + size <= start + len(raw):
                    _cache_put(self.key, off,
                               raw[off - start:off - start + size])
        _count_cold("fetch", "range_gets", len(ranges))
        _count_cold("fetch", "pages_fetched", len(want))
        _count_cold("fetch", "bytes_downloaded", downloaded)
        return downloaded

    def _page_raw(self, pm) -> bytes:
        raw = _cache_get(self.key, pm.offset)
        if raw is not None:
            _count_cold("cache", "hit")
            return raw
        _count_cold("cache", "miss")
        self.fetch_pages([pm])
        raw = _cache_get(self.key, pm.offset)
        if raw is not None:
            _count_cold("cache", "miss_filled")
            return raw
        # page larger than the whole cache: fetch uncached
        _count_cold("cache", "page_exceeds_cache")
        return self._store.get_range(self.key, pm.offset, pm.size)

    def _read_page(self, pm) -> bytes:
        raw = self._page_raw(pm)
        if len(raw) < 8:
            _count_cold("fetch", "page_truncated")
            raise ChecksumMismatch("page truncated", path=self.path,
                                   offset=pm.offset)
        plen, crc = struct.unpack_from("<II", raw, 0)
        payload = raw[8:8 + plen]
        if len(payload) < plen:
            _count_cold("fetch", "page_truncated")
            raise ChecksumMismatch("page truncated", path=self.path,
                                   offset=pm.offset)
        if zlib.crc32(payload) != crc:
            _count_cold("fetch", "page_crc_mismatch")
            raise ChecksumMismatch("page crc", path=self.path,
                                   offset=pm.offset)
        return payload


def open_cold_reader(data_path: str, entry: dict) -> ColdTsmReader:
    """summary.Version.reader's hook: build the cold reader for a manifest
    file whose id appears in cold.json."""
    return ColdTsmReader(data_path, entry["key"], entry["size"],
                         entry["tail_off"])


# ---------------------------------------------------------------------------
# tiering operations
# ---------------------------------------------------------------------------
def eligible_files(vnode, boundary_ns: int, min_level: int = 1) -> list:
    """Sealed files wholly older than `boundary_ns` that may tier: level
    ≥ min_level (L0 delta churn belongs to compaction), not already cold,
    and carrying no tombstone sidecar (pending deletes must rewrite
    locally first)."""
    version = vnode.summary.version
    cold = cold_ids(vnode.dir)
    out = []
    for fm in version.all_files():
        if fm.file_id in cold or fm.level < min_level:
            continue
        if fm.max_ts >= boundary_ns:
            continue
        if os.path.exists(tombstone_path(version.file_path(fm))):
            continue
        out.append(fm)
    return out


def tier_vnode(vnode, boundary_ns: int, limit: int | None = None) -> int:
    """Age every eligible sealed file of `vnode` into the object store.
    → number of files tiered. Uploads run outside the vnode lock; the
    registry flip + local unlink revalidate under it."""
    if not enabled():
        _count_cold("tier", "disabled")
        return 0
    store, _ = _store_and_prefix()
    n = 0
    try:
        for fm in eligible_files(vnode, boundary_ns):
            if limit is not None and n >= limit:
                _count_cold("tier", "limit_reached")
                return n
            if _tier_file(vnode, store, fm):
                n += 1
    finally:
        if n:
            _serving_invalidate(vnode)
    return n


def _serving_invalidate(vnode) -> None:
    """Tiering moved this vnode's bytes WITHOUT bumping data_version
    (deliberate: a tiered scan is bit-identical, so coordinator scan
    caches stay valid) — which means ScanToken revalidation cannot see
    the move, and this push eviction is the only thing that retires
    serving-plane entries now backed by cold storage. Losing it is still
    safe (a hit serves identical bytes), just unhygienic. The owner
    string is the vnode directory's parent name (engine layout
    data/<owner>/<id>)."""
    try:
        from ..server import serving

        owner = os.path.basename(os.path.dirname(vnode.dir))
        if "." in owner:
            serving.invalidate_owner(owner)
    except Exception:
        from ..utils import stages

        stages.count_error("serving.invalidate")


def _tier_file(vnode, store, fm) -> bool:
    version = vnode.summary.version
    path = version.file_path(fm)
    try:
        with open(path, "rb") as f:
            data = f.read()
    except FileNotFoundError:
        _count_cold("tier", "file_vanished")
        return False
    if len(data) < FOOTER_SIZE + 5:
        _count_cold("tier", "file_malformed")
        return False
    # meta_off is the first u64 of the footer body — everything from it to
    # EOF (meta + bloom + footer) becomes the local skip-index sidecar
    (tail_off,) = struct.unpack_from("<Q", data, len(data) - FOOTER_SIZE)
    if not 5 <= tail_off <= len(data) - FOOTER_SIZE:
        _count_cold("tier", "file_malformed")
        return False
    key = _object_key(vnode.vnode_id, fm.file_id)
    store.put(key, data)                       # slow: outside the lock
    write_sidecar(path, len(data), tail_off, data[tail_off:])
    with vnode.lock:
        version = vnode.summary.version
        live = any(f2.file_id == fm.file_id for f2 in version.all_files())
        if not live:
            # compaction replaced the file mid-upload: the object + sidecar
            # are garbage; drop the sidecar, leave the object for purge
            _unlink_quiet(sidecar_path(path))
            _count_cold("tier", "file_vanished")
            return False
        _registry_mutate(vnode.dir, fm.file_id, {
            "key": key, "size": len(data), "tail_off": int(tail_off)})
        version.drop_reader(fm.file_id)
        _unlink_quiet(path)
    _count_cold("tier", "files_tiered")
    _count_cold("tier", "bytes_uploaded", len(data))
    return True


def rehydrate_file(vnode, file_id: int) -> bool:
    """Download a cold file back to its hot path (repair / un-tier): the
    object is bit-identical to the original, so this is a verify-and-
    rename. → True when the file is hot again."""
    entry = cold_entry(vnode.dir, file_id)
    if entry is None:
        _count_cold("rehydrate", "not_cold")
        return False
    store, _ = _store_and_prefix()
    data = store.get(entry["key"])
    if len(data) != entry["size"]:
        _count_cold("rehydrate", "size_mismatch")
        raise ChecksumMismatch("cold object size mismatch",
                               path=entry["key"])
    with vnode.lock:
        version = vnode.summary.version
        fm = next((f for f in version.all_files()
                   if f.file_id == file_id), None)
        if fm is None:
            _count_cold("rehydrate", "file_vanished")
            return False
        path = version.file_path(fm)
        os.makedirs(os.path.dirname(path), exist_ok=True)
        tmp = path + ".rehydrate"
        with open(tmp, "wb") as f:  # lint: disable=lock-blocking (registry flip + data landing must be atomic vs readers)
            f.write(data)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)
        _registry_mutate(vnode.dir, file_id, None)
        version.drop_reader(file_id)
        _unlink_quiet(sidecar_path(path))
    _count_cold("rehydrate", "files_rehydrated")
    return True


def rehydrate_vnode(vnode) -> int:
    """Bring every cold file of `vnode` back to the hot tier (disaster
    repair: the object store acts as an extra replica source)."""
    n = 0
    for fid in sorted(cold_map(vnode.dir)):
        if rehydrate_file(vnode, fid):
            n += 1
    return n


def _rebuild_registry(vnode) -> int:
    """Inverse disaster path: cold.json torn/corrupt while the sidecars
    survived — reconstruct each entry from its sidecar header (size and
    tail_off live there; the object key is re-derived from vnode/file id)
    and install a fresh registry atomically. A file with neither a hot
    copy nor a parseable sidecar cannot be recovered locally and is left
    out (the scrubber's repair re-vote handles it from a replica).
    → entries rebuilt."""
    m: dict[int, dict] = {}
    with vnode.lock:
        version = vnode.summary.version
        for fm in version.all_files():
            path = version.file_path(fm)
            if os.path.exists(path):
                continue               # hot: was never (or no longer) cold
            try:
                size, tail_off, _tail = read_sidecar(path)
            except (TsmError, OSError):
                _count_cold("registry", "entry_unrecoverable")
                continue
            m[fm.file_id] = {"key": _object_key(vnode.vnode_id, fm.file_id),
                             "size": int(size), "tail_off": int(tail_off)}
        _registry_write(vnode.dir, m)
    _count_cold("registry", "entries_rebuilt", len(m))
    return len(m)


def recover_vnode(vnode) -> int:
    """Disaster path: local skip-index sidecars lost or corrupt while
    cold.json survived — re-fetch each tiered file's tail section from
    the object store and rebuild the sidecar. Metadata-only rehydration:
    page bytes stay cold. The mirror-image failure (cold.json torn,
    sidecars intact) is healed first via _rebuild_registry. → sidecars
    rebuilt."""
    if not configured():
        _count_cold("rehydrate", "not_configured")
        return 0
    store, _ = _store_and_prefix()
    healed = 0
    try:
        cold_map(vnode.dir)
    except TsmError:
        # counts toward the return value even when the fresh image is
        # empty: a registry-only heal (sidecars intact) is still a
        # recovery, and callers retrying a failed scan key off a truthy
        # result
        healed = max(1, _rebuild_registry(vnode))
    with vnode.lock:
        version = vnode.summary.version
        work = [(fm, cold_entry(vnode.dir, fm.file_id))
                for fm in version.all_files()]
    n = 0
    for fm, entry in work:
        if entry is None:
            continue
        path = version.file_path(fm)
        intact = False
        if os.path.exists(sidecar_path(path)):
            try:
                r = ColdTsmReader(path, entry["key"], entry["size"],
                                  entry["tail_off"], store)
                r.close()
                intact = True
            except (TsmError, ChecksumMismatch, OSError):
                intact = False      # malformed: rebuild below
        if intact:
            continue
        tail_off = int(entry["tail_off"])
        tail = store.get_range(entry["key"], tail_off,
                               int(entry["size"]) - tail_off)
        # validate before installing: parse_tail CRC-checks the footer
        parse_tail(tail, path, tail_off=tail_off)
        with vnode.lock:
            write_sidecar(path, int(entry["size"]), tail_off, tail)
            vnode.summary.version.drop_reader(fm.file_id)
        n += 1
    _count_cold("rehydrate", "sidecars_rebuilt", n)
    return n + healed


def verify_cold_file(vnode, file_id: int) -> int:
    """Scrub hook: cheap integrity pass over one tiered file — the local
    sidecar must parse, and the remote object must still answer a ranged
    footer read that matches the sidecar's footer bytes. → bytes verified
    (0 when the file is not/no longer cold); raises ChecksumMismatch on
    divergence."""
    entry = cold_entry(vnode.dir, file_id)
    if entry is None:
        _count_cold("scrub", "not_cold")
        return 0
    version = vnode.summary.version
    fm = next((f for f in version.all_files() if f.file_id == file_id), None)
    if fm is None:
        _count_cold("scrub", "file_vanished")
        return 0
    path = version.file_path(fm)
    try:
        _size, tail_off, tail = read_sidecar(path)
        parse_tail(tail, path, tail_off=tail_off)
    except TsmError as e:
        _count_cold("scrub", "sidecar_damaged")
        raise ChecksumMismatch(f"cold sidecar: {e}", path=path)
    store, _ = _store_and_prefix()
    remote_footer = store.get_range(entry["key"],
                                    int(entry["size"]) - FOOTER_SIZE,
                                    FOOTER_SIZE)
    if remote_footer != tail[-FOOTER_SIZE:]:
        _count_cold("scrub", "remote_footer_mismatch")
        raise ChecksumMismatch("cold object footer diverged from sidecar",
                               path=path)
    _count_cold("scrub", "cold_files_verified")
    return len(tail) + FOOTER_SIZE


def purge_vnode(dir_path: str) -> int:
    """Best-effort deletion of a dropped vnode's cold objects (the
    tier-then-expire path): the replica's objects are private to it, so
    dropping the vnode orphans them unless removed here."""
    m = cold_map(dir_path)
    if not m or not configured():
        _count_cold("purge", "nothing_to_purge")
        return 0
    store, _ = _store_and_prefix()
    n = 0
    for fid in sorted(m):
        try:
            store.delete(m[fid]["key"])
            n += 1
        except objstore.ObjectStoreError:
            _count_cold("purge", "delete_failed")
    _count_cold("purge", "objects_deleted", n)
    return n


def _unlink_quiet(path: str) -> None:
    try:
        os.unlink(path)
    except OSError:
        pass   # already gone / racing cleanup: the manifest state holds


# ---------------------------------------------------------------------------
# background tiering job
# ---------------------------------------------------------------------------
class TieringJob:
    """Background aging daemon (server wiring mirrors the Scrubber): every
    `interval_s`, walk the engine's open vnodes and tier sealed files
    whose newest row is older than `cold_after_s`."""

    def __init__(self, engine, interval_s: float, cold_after_s: float,
                 on_error=None):
        self.engine = engine
        self.interval_s = float(interval_s)
        self.cold_after_s = float(cold_after_s)
        self.on_error = on_error
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None

    def _boundary_ns(self) -> int:
        # data timestamps ARE wall-clock ns; the age boundary must be too
        return time.time_ns() - int(self.cold_after_s * 1e9)

    def sweep_once(self) -> int:
        with self.engine.lock:
            vnodes = list(self.engine.vnodes.values())
        total = 0
        for v in vnodes:
            if self._stop.is_set():
                _count_cold("tier", "sweep_stopped")
                return total
            try:
                total += tier_vnode(v, self._boundary_ns())
            except (OSError, StorageError, objstore.ObjectStoreError) as e:
                _count_cold("tier", "sweep_error")
                if self.on_error is not None:
                    self.on_error(v, e)
        return total

    def _run(self):
        while not self._stop.wait(self.interval_s):
            self.sweep_once()

    def start(self):
        if self._thread is None:
            self._thread = threading.Thread(target=self._run,
                                            name="tiering", daemon=True)
            self._thread.start()

    def stop(self):
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5)
            self._thread = None
