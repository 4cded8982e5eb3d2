"""Disaster-recovery plane: WAL archiving, snapshots, point-in-time restore.

Closes the last fail-stop scenario class ("Should I Hide My Duck in the
Lake?" / Taurus, PAPERS.md: the object store IS the database): every
robustness plane so far assumed one healthy replica survives, while this
module makes the PR 12 object store (utils/objstore.py) a durability
root, so total node loss and operator-error DROP/DELETE both recover.

Three lanes, one store, laid out under the ``wal_archive_uri`` prefix:

* **continuous WAL archiving** — every sealed segment streams to
  ``wal/{owner}/{vnode_id}/wal_XXXXXXXXXX.log`` from the Wal's
  seal listener (storage/wal.py). ``Wal.archive_fence`` keeps local GC
  behind the archived watermark, so an upload hiccup can never let
  ``purge_to`` delete the only copy of an acked write. RPO is bounded by
  the ``archive_lag_seconds`` gauge (age of the oldest sealed-but-
  unarchived segment; the active segment is bounded by segment size).
* **incremental consistent snapshots** — ``create_backup`` cuts every
  placement via ``vnode.file_snapshot()`` (flush + file capture) and
  records the per-vnode ScanToken as the cut witness; content-addressed
  objects land at ``objects/{owner}/{sha256}`` so an INCREMENTAL backup
  uploads only blobs absent from the previous manifest. Cold-tiered
  bytes are NOT re-uploaded — the snapshot carries cold.json + the
  ``.tsmc`` sidecars, which keep referencing the tiering store's
  objects. Manifests are self-contained (full file list each time: no
  chain walk at restore) at ``manifests/{owner}/{id}.json``; the catalog
  entry is meta-replicated (MetaStore.record_backup).
* **point-in-time restore** — ``restore_backup`` picks the newest
  backup at-or-before T, recreates the database/table schemas from the
  manifest (``AS new_name`` re-owns them), maps each manifest vnode onto
  a placement (same vnode id when it still exists, else a fresh bucket
  placement by recorded bucket_start/shard), wipes + installs via
  ``install_file_snapshot``, then replays archived WAL entries with
  seq > flushed_seq and append-ts ≤ T.

Every exit out of the archive/backup/restore lanes books an
``(op, outcome)`` reason (``backup_snapshot()``, shown under
``counters`` on the admin backup page; ``backup-accounting`` lint);
fault points ``backup.archive`` / ``backup.manifest`` /
``restore.install`` ride the chaos sweep like every other node point.
"""
from __future__ import annotations

import json
import os
import time

from .. import faults
from ..errors import DatabaseNotFound, StorageError, TsmError
from ..utils import lockwatch, objstore, stages
from . import tiering
from .record_file import iter_records
from .wal import SEGMENT_PATTERN, WalEntry

faults.register_point("backup.archive", __name__,
                      desc="sealed WAL segment upload, before the put")
faults.register_point("backup.manifest", __name__,
                      desc="backup manifest write, after objects uploaded")
faults.register_point("restore.install", __name__,
                      desc="per-vnode restore, before wipe+install")


# ---------------------------------------------------------------------------
# archive-store configuration (process-global, mirrors tiering's _cfg:
# set from config/server wiring; credentials never persist in manifests)
# ---------------------------------------------------------------------------
_cfg_lock = lockwatch.Lock("backup.config")
_cfg: dict = {"uri": "", "options": {}, "store": None, "prefix": ""}


def configure_archive(uri: str | None, options: dict | None = None) -> None:
    """Point the DR plane at `uri` (s3://…, gcs://…, azblob://…, or a
    local directory path); empty/None unconfigures and detaches every
    archiver."""
    with _cfg_lock:
        _cfg["uri"] = (uri or "").strip()
        _cfg["options"] = dict(options or {})
        _cfg["store"] = None
        _cfg["prefix"] = ""
    if not (uri or "").strip():
        with _archivers_lock:
            _archivers.clear()


def archive_enabled() -> bool:
    with _cfg_lock:
        return bool(_cfg["uri"])


def _store_and_prefix():
    with _cfg_lock:
        if not _cfg["uri"]:
            raise StorageError(
                "WAL archive not configured (storage.wal_archive_uri)")
        if _cfg["store"] is None:
            store, prefix = objstore.store_for(_cfg["uri"], _cfg["options"])
            _cfg["store"] = store
            _cfg["prefix"] = prefix.rstrip("/")
        return _cfg["store"], _cfg["prefix"]


def _key(prefix: str, rel: str) -> str:
    return f"{prefix}/{rel}" if prefix else rel


def _wal_prefix(prefix: str, owner: str, vnode_id: int) -> str:
    return _key(prefix, f"wal/{owner}/{vnode_id}")


def _object_key(prefix: str, owner: str, sha: str) -> str:
    # content objects are scoped per owner: manifest GC walks this prefix
    # and must never see (or delete) another database's blobs
    return _key(prefix, f"objects/{owner}/{sha}")


def _manifest_key(prefix: str, owner: str, backup_id: str) -> str:
    return _key(prefix, f"manifests/{owner}/{backup_id}.json")


# ---------------------------------------------------------------------------
# accounting — (op, outcome) counts, read by backup_snapshot()
# ---------------------------------------------------------------------------
_counts_lock = lockwatch.Lock("backup.counters")
_counts: dict[tuple[str, str], int] = {}


def _count_backup(op: str, outcome: str, n: int = 1) -> None:
    with _counts_lock:
        _counts[(op, outcome)] = _counts.get((op, outcome), 0) + n


def backup_snapshot() -> dict[tuple[str, str], int]:
    with _counts_lock:
        return dict(_counts)


def counters_reset() -> None:
    with _counts_lock:
        _counts.clear()


# ---------------------------------------------------------------------------
# continuous WAL archiving
# ---------------------------------------------------------------------------
class WalArchiver:
    """Per-WAL archive pump: fires from the seal listener, uploads the
    sealed segment, maintains the per-vnode watermark object, and fences
    local GC (`may_purge`). Idempotent by construction — a crash between
    seal and upload (backup.archive:crash) is healed by `catch_up()` on
    the next attach re-uploading the same bytes to the same key."""

    def __init__(self, owner: str, vnode_id: int, wal):
        self.owner = owner
        self.vnode_id = vnode_id
        self.wal = wal
        self.archived: dict[int, dict] = {}   # seg → {max_seq, max_ts}
        self._loaded = False

    def _prefix(self):
        store, prefix = _store_and_prefix()
        return store, _wal_prefix(prefix, self.owner, self.vnode_id)

    def _load_watermark(self) -> None:
        """Seed the archived-set from the durable watermark object, so a
        restarted process neither re-uploads everything nor un-fences
        segments the previous incarnation already archived."""
        try:
            store, pfx = self._prefix()
            wm = json.loads(store.get(f"{pfx}/watermark.json"))
        except (OSError, ValueError, objstore.ObjectStoreError,
                StorageError):
            # first contact (no watermark yet) or a flaky store: start
            # empty — catch_up re-uploads, which is idempotent
            stages.count_error("backup.watermark_load")
            wm = {}
        self.archived = {int(k): dict(v)
                         for k, v in (wm.get("segments") or {}).items()}

    def _put_watermark(self, store, pfx: str) -> None:
        wm = dict(self.watermark())
        wm["segments"] = {str(k): v for k, v in sorted(self.archived.items())}
        store.put(f"{pfx}/watermark.json", json.dumps(wm).encode())

    def watermark(self) -> dict:
        """{max_seq, max_ts} over every archived segment — the durable
        point up to which this vnode's log survives total node loss."""
        if not self.archived:
            return {"max_seq": 0, "max_ts": 0}
        return {
            "max_seq": max(v["max_seq"] for v in self.archived.values()),
            "max_ts": max(v["max_ts"] for v in self.archived.values()),
        }

    def on_seal(self, seg_id: int) -> None:
        # seal-listener entry: Wal._roll swallows exceptions (an archive
        # outage must not fail the write path; catch_up heals later)
        self.archive_segment(seg_id)

    def archive_segment(self, seg_id: int) -> bool:
        """Upload one sealed segment; → True when newly archived."""
        if not self._loaded:
            self._load_watermark()
            self._loaded = True
        if seg_id in self.archived:
            _count_backup("archive", "already_archived")
            return False
        path = self.wal._seg_path(seg_id)
        if faults.ENABLED:
            # before the put: a crash here is the sealed-not-archived
            # window the catch_up/replay regression tests pin down
            faults.fire("backup.archive", dir=self.wal.dir, seg=seg_id)
        try:
            with open(path, "rb") as f:
                raw = f.read()
        except OSError as e:
            _count_backup("archive", "segment_unreadable")
            raise StorageError(f"archive: cannot read sealed segment "
                               f"{path}: {e}")
        max_seq = max_ts = 0
        for payload in iter_records(raw):
            e = WalEntry.decode(payload)
            max_seq = max(max_seq, e.seq)
            max_ts = max(max_ts, e.ts)
        store, pfx = self._prefix()
        store.put(f"{pfx}/{os.path.basename(path)}", raw)
        self.archived[seg_id] = {"max_seq": max_seq, "max_ts": max_ts}
        self._put_watermark(store, pfx)
        _count_backup("archive", "segments_archived")
        _count_backup("archive", "bytes_uploaded", len(raw))
        return True

    def catch_up(self) -> int:
        """Archive every sealed-but-unarchived local segment (attach-time
        crash healing + the BACKUP barrier). → segments uploaded."""
        n = 0
        for seg in self.wal._list_segments()[:-1]:
            if self.archive_segment(seg):
                n += 1
        return n

    def may_purge(self, seg_id: int) -> bool:
        """Wal.archive_fence: local GC may drop a segment only once its
        bytes are durably archived."""
        if not self._loaded:
            self._load_watermark()
            self._loaded = True
        return seg_id in self.archived

    def lag_seconds(self) -> float:
        """Age of the oldest sealed-but-unarchived segment (0.0 when
        fully caught up) — the RPO bound for everything already sealed."""
        oldest = None
        for seg in self.wal._list_segments()[:-1]:
            if seg in self.archived:
                continue
            try:
                m = os.path.getmtime(self.wal._seg_path(seg))
            except OSError:
                stages.count_error("swallow.backup.lag_mtime")
                continue
            oldest = m if oldest is None else min(oldest, m)
        if oldest is None:
            return 0.0
        return max(0.0, time.time() - oldest)  # lint: disable=wallclock-duration (segment mtimes are wall clock; the gauge measures real-world RPO, not a code interval)


_archivers_lock = lockwatch.Lock("backup.archivers")
_archivers: dict[str, WalArchiver] = {}     # wal dir → archiver


def attach_wal(owner: str, vnode_id: int, wal) -> WalArchiver:
    """Idempotently wire one Wal into the archive plane: registry entry,
    seal listener, purge fence, then a catch_up pass (heals the crash-
    between-seal-and-upload window on every boot)."""
    with _archivers_lock:
        arch = _archivers.get(wal.dir)
        if arch is None or arch.wal is not wal:
            arch = WalArchiver(owner, vnode_id, wal)
            _archivers[wal.dir] = arch
    wal.archive_fence = arch.may_purge
    if arch.on_seal not in wal.seal_listeners:
        wal.seal_listeners.append(arch.on_seal)
    try:
        arch.catch_up()
    except (OSError, StorageError, objstore.ObjectStoreError):
        # boot must not fail on an archive outage: the fence keeps the
        # unarchived segments local, so nothing is lost — only lagging
        stages.count_error("swallow.backup.attach_catch_up")
    return arch


def attach_vnode(vnode) -> WalArchiver | None:
    """VnodeStorage boot hook (vnode.py): owner is the vnode directory's
    parent name (engine layout data/<owner>/<id>)."""
    if not archive_enabled():
        return None
    owner = os.path.basename(os.path.dirname(vnode.dir))
    return attach_wal(owner, vnode.vnode_id, vnode.wal)


def archivers() -> list[WalArchiver]:
    with _archivers_lock:
        return list(_archivers.values())


def archive_lag_seconds() -> float:
    """The /metrics RPO gauge: worst lag over every attached WAL."""
    lags = [a.lag_seconds() for a in archivers()]
    return max(lags) if lags else 0.0


def cluster_watermark(owner: str) -> dict:
    """min over this owner's attached WALs of the archived watermark —
    the conservative "no acked write at-or-before this is lost" bound
    the client-history checker verifies after total node loss."""
    marks = [a.watermark() for a in archivers() if a.owner == owner]
    if not marks:
        return {"max_seq": 0, "max_ts": 0}
    return {"max_seq": min(m["max_seq"] for m in marks),
            "max_ts": min(m["max_ts"] for m in marks)}


# ---------------------------------------------------------------------------
# incremental consistent snapshots
# ---------------------------------------------------------------------------
def _local_cut(vnode) -> dict:
    """One vnode's consistency cut: flush + file capture, the ScanToken
    as the witness, and a forced seal + catch_up so the archived log
    covers everything up to the cut."""
    snap = vnode.file_snapshot()          # flushes first
    token = vnode.scan_token()
    arch = attach_vnode(vnode)
    if arch is not None:
        vnode.wal.seal_active()
        arch.catch_up()
    try:
        cold_refs = tiering.cold_objects(vnode.dir)
    except TsmError:
        # torn registry rides the snapshot as-is; the restored vnode's
        # own recover path rebuilds it from the shipped sidecars
        stages.count_error("backup.cold_refs")
        cold_refs = []
    return {"files": snap["files"], "digests": snap["digests"],
            "flushed_seq": vnode.summary.version.flushed_seq,
            "cold_refs": cold_refs,
            "token": {"file_ids": sorted(token.file_ids),
                      "mem_seq": token.mem_seq}}


def create_backup(meta, engine, tenant: str, db: str,
                  incremental: bool = False, fetch_cut=None) -> dict:
    """Cut + upload one database backup; → the meta-recorded catalog
    entry. `fetch_cut(vnode_id, node_id)` lets the coordinator supply
    cuts for non-local placements."""
    owner = f"{tenant}.{db}"
    if not archive_enabled():
        _count_backup("backup", "unconfigured")
        raise StorageError("BACKUP: no archive store configured — set "
                           "[storage] wal_archive_uri")
    schema = meta.database(tenant, db)     # raises DatabaseNotFound
    store, prefix = _store_and_prefix()
    catalog = meta.list_backups(owner)
    prev_shas: set[str] = set()
    base_id = None
    if incremental and catalog:
        base_id = catalog[-1]["id"]
        try:
            prev = json.loads(
                store.get(_manifest_key(prefix, owner, base_id)))
        except (OSError, ValueError, objstore.ObjectStoreError):
            # base manifest unreadable: fall back to a full upload — the
            # new manifest is self-contained either way
            _count_backup("backup", "base_manifest_unreadable")
            prev, base_id = {"vnodes": []}, None
        for vn in prev.get("vnodes", []):
            for info in vn["files"].values():
                prev_shas.add(info["sha256"])
    uploaded = reused = nbytes = 0
    seen = set(prev_shas)
    vnodes_meta = []
    for bucket in meta.buckets_for(tenant, db):
        for shard, rs in enumerate(bucket.shard_group):
            vid = rs.leader_vnode_id
            v = engine.vnode(owner, vid)
            if v is not None:
                cut = _local_cut(v)
            elif fetch_cut is not None:
                cut = fetch_cut(vid, rs.leader_node_id)
            else:
                cut = None
            entry = {"vnode_id": vid, "shard": shard,
                     "bucket_start": bucket.start_time,
                     "bucket_end": bucket.end_time,
                     "flushed_seq": 0, "files": {}, "token": None,
                     "cold_refs": []}
            if cut is None:
                # placement never materialized locally: nothing to cut,
                # but the slot is still recorded so restore re-creates it
                _count_backup("backup", "vnode_empty")
                vnodes_meta.append(entry)
                continue
            for rel, raw in cut["files"].items():
                sha = cut["digests"][rel]
                if sha not in seen:
                    store.put(_object_key(prefix, owner, sha), raw)
                    uploaded += 1
                    nbytes += len(raw)
                else:
                    reused += 1
                seen.add(sha)
                entry["files"][rel] = {"sha256": sha, "size": len(raw)}
            entry["flushed_seq"] = cut["flushed_seq"]
            entry["token"] = cut["token"]
            entry["cold_refs"] = cut.get("cold_refs", [])
            vnodes_meta.append(entry)
    backup_id = f"{db}-{len(catalog):06d}"
    manifest = {
        "backup_id": backup_id, "tenant": tenant, "db": db, "owner": owner,
        "incremental": bool(incremental and base_id is not None),
        "base": base_id, "created_ts": time.time(),
        "db_options": schema.options.to_dict(),
        "tables": {t: s.to_dict()
                   for t, s in meta.tables.get(owner, {}).items()},
        "vnodes": vnodes_meta,
    }
    if faults.ENABLED:
        # between object uploads and the manifest write: a crash here
        # leaves orphaned (content-addressed, re-usable) objects and NO
        # manifest — the catalog never references a torn backup
        faults.fire("backup.manifest", owner=owner, backup_id=backup_id)
    store.put(_manifest_key(prefix, owner, backup_id),
              json.dumps(manifest).encode())
    entry = {"id": backup_id, "owner": owner,
             "incremental": manifest["incremental"], "base": base_id,
             "created_ts": manifest["created_ts"],
             "vnodes": len(vnodes_meta), "objects_uploaded": uploaded,
             "objects_reused": reused, "bytes": nbytes,
             "manifest_key": _manifest_key(prefix, owner, backup_id)}
    meta.record_backup(owner, entry)
    _count_backup("backup", "ok")
    return entry


# ---------------------------------------------------------------------------
# point-in-time restore
# ---------------------------------------------------------------------------
def _pick(catalog: list[dict], backup_id: str | None,
          to_ts: int | None) -> dict | None:
    if backup_id is not None:
        for e in catalog:
            if e["id"] == backup_id:
                return e
        return None
    if to_ts is not None:
        ok = [e for e in catalog if e["created_ts"] * 1e9 <= to_ts]
        return ok[-1] if ok else None
    return catalog[-1] if catalog else None


def _archived_entries(store, prefix: str, owner: str, vnode_id: int,
                      from_seq: int, to_ts: int | None = None) -> list:
    """Replay-set from the archived log: later-dup-wins dedup (same rule
    as Wal.replay), then filter to seq ≥ from_seq and ts ≤ to_ts.
    → [(seq, entry_type, data, term, ts)] in seq order."""
    pfx = _wal_prefix(prefix, owner, vnode_id)
    segs = sorted(k for k in store.list_prefix(pfx + "/")
                  if SEGMENT_PATTERN.match(os.path.basename(k)))
    entries: dict[int, WalEntry] = {}
    tail_seq = 0
    for seg_key in segs:
        for payload in iter_records(store.get(seg_key)):
            e = WalEntry.decode(payload)
            if e.seq <= tail_seq:
                entries = {k: v for k, v in entries.items() if k < e.seq}
            entries[e.seq] = e
            tail_seq = e.seq
    out = []
    for seq in sorted(entries):
        e = entries[seq]
        if seq < from_seq:
            continue
        if to_ts is not None and e.ts > to_ts:
            continue
        out.append((e.seq, e.entry_type, e.data, e.term, e.ts))
    return out


def _ensure_target_schema(meta, tenant: str, target_db: str,
                          manifest: dict) -> None:
    """Recreate database + table schemas from the manifest (RESTORE AS
    re-owns them); existing objects are left untouched."""
    from ..models.schema import (DatabaseOptions, DatabaseSchema,
                                 TskvTableSchema)

    try:
        meta.database(tenant, target_db)
    except DatabaseNotFound:
        meta.create_database(
            DatabaseSchema(tenant, target_db,
                           DatabaseOptions.from_dict(
                               manifest["db_options"])),
            if_not_exists=True)
    for tdict in manifest.get("tables", {}).values():
        ts = TskvTableSchema.from_dict(tdict)
        ts.db = target_db
        meta.create_table(ts, if_not_exists=True)


def _target_vnode(meta, tenant: str, target_db: str, vn: dict) -> int:
    """Map one manifest vnode onto a live placement: the original vnode
    id when it still belongs to the target db (in-place / total-loss
    restore), else a fresh placement in the bucket covering the recorded
    bucket_start (RESTORE AS / restore after DROP)."""
    owner = f"{tenant}.{target_db}"
    hit = meta.find_vnode(vn["vnode_id"])
    if hit is not None and hit[0] == owner:
        return vn["vnode_id"]
    bucket = meta.locate_bucket_for_write(tenant, target_db,
                                          vn["bucket_start"])
    rs = bucket.shard_group[vn["shard"] % len(bucket.shard_group)]
    return rs.leader_vnode_id


def install_vnode(engine, owner: str, vnode_id: int, snap: dict,
                  entries: list) -> None:
    """Local per-vnode restore: wipe (stale WAL included — its higher
    seqs would otherwise replay over the restored summary), reopen,
    install the snapshot, replay the archived entries, make durable."""
    engine.drop_vnode(owner, vnode_id)
    v = engine.open_vnode(owner, vnode_id)
    if snap["files"]:
        v.install_file_snapshot(snap)
    for (seq, entry_type, data, term, _ts) in entries:
        v.wal.append(entry_type, data, seq=seq, term=term)
        v.apply_entry(entry_type, data, seq)
    v.wal.sync()
    v.flush(sync=True)
    _count_backup("restore", "vnodes_installed")


def restore_backup(meta, engine, tenant: str, db: str,
                   backup_id: str | None = None, to_ts: int | None = None,
                   new_name: str | None = None, install=None) -> dict:
    """Restore `db` (optionally AS `new_name`, optionally to timestamp
    `to_ts` ns): manifest closure download → schema recreation → per-
    vnode install + archived-WAL replay. `install(owner, vnode_id, vn,
    snap, entries)` lets the coordinator route non-local placements."""
    owner = f"{tenant}.{db}"
    if not archive_enabled():
        _count_backup("restore", "unconfigured")
        raise StorageError("RESTORE: no archive store configured — set "
                           "[storage] wal_archive_uri")
    store, prefix = _store_and_prefix()
    entry = _pick(meta.list_backups(owner), backup_id, to_ts)
    if entry is None:
        _count_backup("restore", "no_backup")
        raise StorageError(
            f"RESTORE: no backup of {owner}"
            + (f" with id {backup_id!r}" if backup_id else "")
            + (f" created at or before ts {to_ts}" if to_ts else ""))
    manifest = json.loads(
        store.get(_manifest_key(prefix, owner, entry["id"])))
    target_db = new_name or db
    target_owner = f"{tenant}.{target_db}"
    _ensure_target_schema(meta, tenant, target_db, manifest)
    restored = []
    for vn in manifest["vnodes"]:
        tvid = _target_vnode(meta, tenant, target_db, vn)
        snap = {"files": {}, "digests": {}}
        for rel, info in vn["files"].items():
            snap["files"][rel] = store.get(
                _object_key(prefix, owner, info["sha256"]))
            snap["digests"][rel] = info["sha256"]
        entries = _archived_entries(store, prefix, owner, vn["vnode_id"],
                                    from_seq=vn["flushed_seq"] + 1,
                                    to_ts=to_ts)
        if faults.ENABLED:
            # before the wipe: a crash at nth=1 must leave the SOURCE
            # database untouched (the sweep's recovery oracle)
            faults.fire("restore.install", owner=target_owner,
                        vnode_id=tvid, source_vnode=vn["vnode_id"])
        if install is not None:
            install(target_owner, tvid, vn, snap, entries)
        else:
            install_vnode(engine, target_owner, tvid, snap, entries)
        restored.append(tvid)
    out = {"backup_id": entry["id"], "database": target_db,
           "owner": target_owner, "vnodes": restored, "to_ts": to_ts,
           "tables": sorted(manifest.get("tables", {}))}
    _count_backup("restore", "ok")
    return out


# ---------------------------------------------------------------------------
# manifest GC
# ---------------------------------------------------------------------------
def gc_backups(meta, tenant: str, db: str, keep: int = 2) -> dict:
    """Retire catalog entries beyond the newest `keep`: delete their
    manifests, then every content object no kept manifest references
    (the list_prefix walk — objects are owner-scoped, so other databases'
    blobs are out of reach). keep=0 wipes the owner's whole backup area
    (delete_prefix), archived WAL included."""
    owner = f"{tenant}.{db}"
    store, prefix = _store_and_prefix()
    catalog = meta.list_backups(owner)
    if keep <= 0:
        n = store.delete_prefix(_key(prefix, f"manifests/{owner}/"))
        n += store.delete_prefix(_key(prefix, f"objects/{owner}/"))
        n += store.delete_prefix(_key(prefix, f"wal/{owner}/"))
        meta.prune_backups(owner, 0)
        _count_backup("gc", "wiped")
        return {"removed": len(catalog), "objects_deleted": n}
    if len(catalog) <= keep:
        _count_backup("gc", "nothing_to_do")
        return {"removed": 0, "objects_deleted": 0}
    drop, kept = catalog[:-keep], catalog[-keep:]
    live: set[str] = set()
    for entry in kept:
        man = json.loads(
            store.get(_manifest_key(prefix, owner, entry["id"])))
        for vn in man["vnodes"]:
            for info in vn["files"].values():
                live.add(info["sha256"])
    deleted = 0
    opfx = _key(prefix, f"objects/{owner}/")
    for key in store.list_prefix(opfx):
        if os.path.basename(key) not in live:
            store.delete(key)
            deleted += 1
    for entry in drop:
        store.delete(_manifest_key(prefix, owner, entry["id"]))
    meta.prune_backups(owner, keep)
    _count_backup("gc", "manifests_removed", len(drop))
    _count_backup("gc", "objects_deleted", deleted)
    return {"removed": len(drop), "objects_deleted": deleted}
