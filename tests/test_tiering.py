"""Cold tier (tentpole of the tiered-storage PR): age sealed TSM files
into an object store keeping a local skip-index sidecar, scan the COLD
tier transparently through byte-range GETs + block cache, prune pages
locally before any byte downloads, and recover/rehydrate/scrub/purge
against the store. The parity oracle throughout: a tiered scan is
bit-identical to the hot scan of the same writes."""
import glob
import os

import numpy as np
import pytest

from cnosdb_tpu import faults
from cnosdb_tpu.errors import ChecksumMismatch, StorageError, TsmError
from cnosdb_tpu.models.points import SeriesRows, WriteBatch
from cnosdb_tpu.models.schema import TskvTableSchema, ValueType
from cnosdb_tpu.models.series import SeriesKey
from cnosdb_tpu.storage import scrub, tiering
from cnosdb_tpu.storage.scan import scan_vnode
from cnosdb_tpu.storage.vnode import VnodeStorage


@pytest.fixture(autouse=True)
def _clean(monkeypatch):
    monkeypatch.delenv("CNOSDB_COLD_TIER", raising=False)
    tiering.counters_reset()
    tiering.block_cache_clear()
    yield
    faults.reset()
    tiering.configure(None)
    tiering.counters_reset()
    tiering.block_cache_clear()


@pytest.fixture
def store_dir(tmp_path):
    d = tmp_path / "bucket"
    d.mkdir()
    tiering.configure(str(d))
    return str(d)


def _schema():
    return {"cpu": TskvTableSchema.new_measurement(
        "t", "db", "cpu", tags=["host"],
        fields=[("usage", ValueType.FLOAT), ("s", ValueType.STRING)])}


def _wb(host, ts_list, usage_list, s_list=None):
    fields = {"usage": (int(ValueType.FLOAT), list(usage_list))}
    if s_list is not None:
        fields["s"] = (int(ValueType.STRING), list(s_list))
    wb = WriteBatch()
    wb.add_series("cpu", SeriesRows(
        SeriesKey("cpu", {"host": host}), list(ts_list), fields))
    return wb


def _build_vnode(dir_path, base_ts=0, words=("alpha", "beta"), n=200):
    """5 flushes + full compaction → one sealed L1 file. NaN floats and a
    NULL-string series ride along so parity covers the awkward values."""
    v = VnodeStorage(1, dir_path, schemas=_schema())
    for i in range(5):
        lo = base_ts + i * n
        usage = [float(j) * 0.5 for j in range(n)]
        usage[3] = float("nan")
        v.write(_wb("h1", range(lo, lo + n), usage,
                    [words[j % len(words)] for j in range(n)]))
        # second series writes no strings at all → NULL "s" column
        v.write(_wb("h2", range(lo, lo + n), [1.0] * n))
        v.flush()
    v.compact_full()
    fms = v.summary.version.all_files()
    assert len(fms) == 1 and fms[0].level >= 1, [f.level for f in fms]
    return v


def _batch_dict(b):
    def mat(x):
        return x.materialize() if hasattr(x, "materialize") else x
    out = {"ts": np.asarray(b.ts), "sids": np.asarray(b.series_ids)}
    for name, (vt, vals, valid) in b.fields.items():
        out[name] = (int(vt), np.asarray(mat(vals)),
                     None if valid is None else np.asarray(valid))
    return out


def _assert_same(a, b):
    a, b = _batch_dict(a), _batch_dict(b)
    assert a.keys() == b.keys()
    np.testing.assert_array_equal(a["ts"], b["ts"])
    np.testing.assert_array_equal(a["sids"], b["sids"])
    for k in a:
        if k in ("ts", "sids"):
            continue
        (vt1, v1, m1), (vt2, v2, m2) = a[k], b[k]
        assert vt1 == vt2
        np.testing.assert_array_equal(v1, v2)       # NaN == NaN here
        if m1 is None or m2 is None:
            assert m1 is m2
        else:
            np.testing.assert_array_equal(m1, m2)


def _tier_all(v):
    n = tiering.tier_vnode(v, boundary_ns=10 ** 18)
    assert n >= 1
    return n


# --------------------------------------------------------------- parity
def test_tier_then_cold_scan_is_bit_identical(tmp_engine_dir, store_dir):
    v = _build_vnode(tmp_engine_dir)
    hot = scan_vnode(v, "cpu")
    assert _tier_all(v) == 1
    # the data file left the hot tier; the skip-index sidecar stayed
    assert glob.glob(os.path.join(tmp_engine_dir, "tsm", "*.tsm")) == []
    assert len(glob.glob(os.path.join(tmp_engine_dir, "tsm", "*.tsmc"))) == 1
    assert len(tiering.cold_ids(v.dir)) == 1
    cold = scan_vnode(v, "cpu")
    _assert_same(hot, cold)
    snap = tiering.cold_tier_snapshot()
    assert snap[("fetch", "bytes_downloaded")] > 0
    v.close()


def test_cold_tier_0_knob_disables_tiering(tmp_engine_dir, store_dir,
                                           monkeypatch):
    monkeypatch.setenv("CNOSDB_COLD_TIER", "0")
    v = _build_vnode(tmp_engine_dir)
    assert not tiering.enabled()
    assert tiering.tier_vnode(v, boundary_ns=10 ** 18) == 0
    assert tiering.cold_ids(v.dir) == frozenset()
    assert len(glob.glob(os.path.join(tmp_engine_dir, "tsm", "*.tsm"))) == 1
    v.close()


def test_boundary_respects_file_age(tmp_engine_dir, store_dir):
    v = _build_vnode(tmp_engine_dir, base_ts=10 ** 6)
    # newest row is ~10**6 + 1000 ns; a boundary below it tiers nothing
    assert tiering.tier_vnode(v, boundary_ns=10 ** 6) == 0
    assert tiering.tier_vnode(v, boundary_ns=10 ** 9) == 1
    v.close()


# ----------------------------------------------------- near-data pruning
def _device_hook():
    from cnosdb_tpu.ops import device_decode
    return lambda: device_decode.DeviceDecodeLane()


def test_constraint_prune_downloads_nothing(tmp_engine_dir, store_dir):
    from cnosdb_tpu.sql.expr import BinOp, Column, Literal
    from cnosdb_tpu.storage.scan import _page_constraints

    v = _build_vnode(tmp_engine_dir)
    _tier_all(v)
    # zone maps exclude every page
    flt = BinOp(">", Column("usage"), Literal(1e9))
    b = scan_vnode(v, "cpu", page_constraints=_page_constraints(
        flt, ["usage"]), decode_hook=_device_hook())
    assert len(b.ts) == 0
    snap = tiering.cold_tier_snapshot()
    assert snap.get(("prune", "pages_pruned"), 0) > 0
    assert snap.get(("fetch", "bytes_downloaded"), 0) == 0
    v.close()


def test_like_trigram_prune_parity_on_cold(tmp_path, store_dir,
                                           monkeypatch):
    """Two cold files; the LIKE needle lives in one. With n-gram skipping
    on, the other file's pages never download — and the result matches
    the skip-disabled scan of the same cold vnode bit for bit."""
    from cnosdb_tpu.sql.expr import Column, Like
    from cnosdb_tpu.storage.scan import _page_constraints

    d = str(tmp_path / "engine")
    v = _build_vnode(d, base_ts=0, words=("alpha", "beta"))
    _tier_all(v)                       # file A cold → next compaction
    for i in range(5):                 # can't merge it with batch B
        lo = 10 ** 6 + i * 200
        v.write(_wb("h1", range(lo, lo + 200), [1.0] * 200,
                    ["rare_needle" if j % 7 == 0 else "gamma"
                     for j in range(200)]))
        v.flush()
    v.compact_full()
    assert _tier_all(v) >= 1
    assert len(tiering.cold_ids(v.dir)) >= 2

    flt = Like(Column("s"), "%rare_needle%")

    def run(skip_on):
        tiering.block_cache_clear()
        tiering.counters_reset()
        # the env knob is honored at constraint-extraction time
        monkeypatch.setenv("CNOSDB_NGRAM_SKIP", "1" if skip_on else "0")
        cons = _page_constraints(flt, ["s"])
        if skip_on:
            assert any(c[0] == "ngram" for c in cons.get("s", ())), cons
        b = scan_vnode(v, "cpu", page_constraints=cons,
                       decode_hook=_device_hook())
        return b, tiering.cold_tier_snapshot()

    def matching(b):
        _, vals, valid = b.fields["s"]
        if hasattr(vals, "materialize"):
            vals = vals.materialize()
        return sorted(
            (int(t), str(s)) for t, s, ok in zip(b.ts, vals, valid)
            if ok and "rare_needle" in str(s))

    pruned, snap_on = run(True)
    oracle, snap_off = run(False)
    rows = matching(pruned)
    assert len(rows) > 0 and rows == matching(oracle)
    assert snap_on.get(("prune", "pages_pruned"), 0) \
        > snap_off.get(("prune", "pages_pruned"), 0)
    assert snap_on[("fetch", "bytes_downloaded")] \
        < snap_off[("fetch", "bytes_downloaded")]
    v.close()


# ------------------------------------------------------------ block cache
def test_block_cache_serves_repeat_scans(tmp_engine_dir, store_dir):
    v = _build_vnode(tmp_engine_dir)
    _tier_all(v)
    scan_vnode(v, "cpu")
    first = tiering.cold_tier_snapshot()[("fetch", "bytes_downloaded")]
    assert first > 0
    scan_vnode(v, "cpu")
    snap = tiering.cold_tier_snapshot()
    assert snap[("fetch", "bytes_downloaded")] == first   # all cache hits
    stats = tiering.block_cache_stats()
    assert stats["entries"] > 0 and stats["bytes"] > 0
    v.close()


# ------------------------------------------------ chaos: recover / rehydrate
def test_sidecar_wipe_recovers_from_object_store(tmp_engine_dir, store_dir):
    v = _build_vnode(tmp_engine_dir)
    hot = scan_vnode(v, "cpu")
    _tier_all(v)
    for side in glob.glob(os.path.join(tmp_engine_dir, "tsm", "*.tsmc")):
        os.unlink(side)
    for fid in tiering.cold_ids(v.dir):
        v.summary.version.drop_reader(fid)
    tiering.block_cache_clear()
    with pytest.raises(TsmError):
        scan_vnode(v, "cpu")
    assert tiering.recover_vnode(v) == 1          # sidecars rebuilt remotely
    _assert_same(hot, scan_vnode(v, "cpu"))
    v.close()


def test_rehydrate_restores_the_hot_tier(tmp_engine_dir, store_dir):
    v = _build_vnode(tmp_engine_dir)
    hot = scan_vnode(v, "cpu")
    _tier_all(v)
    assert tiering.rehydrate_vnode(v) == 1
    assert tiering.cold_ids(v.dir) == frozenset()
    assert len(glob.glob(os.path.join(tmp_engine_dir, "tsm", "*.tsm"))) == 1
    (fm,) = v.summary.version.all_files()
    assert not getattr(v.summary.version.reader(fm), "is_cold", False)
    _assert_same(hot, scan_vnode(v, "cpu"))
    v.close()


def test_cold_reader_refuses_native_buffer(tmp_engine_dir, store_dir):
    v = _build_vnode(tmp_engine_dir)
    _tier_all(v)
    (fm,) = v.summary.version.all_files()
    r = v.summary.version.reader(fm)
    assert r.is_cold
    with pytest.raises(StorageError):
        r.buffer_array()
    v.close()


# ----------------------------------------------------------------- scrub
def test_scrub_verifies_cold_files_without_quarantine(tmp_engine_dir,
                                                      store_dir):
    scrub.counters_reset()
    v = _build_vnode(tmp_engine_dir)
    _tier_all(v)
    res = scrub.scrub_vnode(v)
    assert res["corrupt"] == [] and res["bytes"] > 0
    # flip a footer byte of the remote object → scrub must see divergence
    (obj,) = glob.glob(os.path.join(store_dir, "vnode_1", "*.tsm"))
    with open(obj, "r+b") as f:
        f.seek(-1, os.SEEK_END)
        b = f.read(1)
        f.seek(-1, os.SEEK_END)
        f.write(bytes([b[0] ^ 0xFF]))
    res = scrub.scrub_vnode(v)
    assert len(res["corrupt"]) == 1
    # the manifest entry is the ONLY pointer to the remote bytes: a cold
    # file must never be quarantined out of the Version
    assert v.quarantined_files() == []
    assert len(v.summary.version.all_files()) == 1
    v.close()


def test_verify_cold_file_raises_on_damaged_sidecar(tmp_engine_dir,
                                                    store_dir):
    v = _build_vnode(tmp_engine_dir)
    _tier_all(v)
    (fid,) = tiering.cold_ids(v.dir)
    assert tiering.verify_cold_file(v, fid) > 0
    (side,) = glob.glob(os.path.join(tmp_engine_dir, "tsm", "*.tsmc"))
    with open(side, "r+b") as f:
        f.seek(20)
        f.write(b"\xff\xff\xff\xff")
    with pytest.raises(ChecksumMismatch):
        tiering.verify_cold_file(v, fid)
    v.close()


# ------------------------------------------------------------- compaction
def test_compaction_never_consumes_cold_files(tmp_engine_dir, store_dir):
    v = _build_vnode(tmp_engine_dir)
    _tier_all(v)
    (cold_fid,) = tiering.cold_ids(v.dir)
    assert cold_fid in v._compaction_exclude()
    # hot backfill INTO the cold window joins the freeze (LWW ordering)
    v.write(_wb("h1", [10, 11], [9.0, 9.5], ["x", "y"]))
    v.flush()
    hot_fid = max(f.file_id for f in v.summary.version.all_files())
    assert hot_fid in v._compaction_exclude()
    while v.compact():
        pass
    ids = {f.file_id for f in v.summary.version.all_files()}
    assert cold_fid in ids and hot_fid in ids
    v.close()


# -------------------------------------------------- tier-then-expire, job
def test_drop_vnode_purges_cold_objects(tmp_path, store_dir):
    from cnosdb_tpu.storage.engine import TsKv

    engine = TsKv(str(tmp_path / "data"))
    engine.schemas.setdefault("db", {}).update(_schema())
    v = engine.open_vnode("db", 1)
    for i in range(5):
        v.write(_wb("h1", range(i * 10, i * 10 + 10), [1.0] * 10))
        v.flush()
    v.compact_full()
    assert tiering.tier_vnode(v, boundary_ns=10 ** 18) == 1
    assert glob.glob(os.path.join(store_dir, "vnode_1", "*.tsm"))
    engine.drop_vnode("db", 1, purge_cold=True)
    assert glob.glob(os.path.join(store_dir, "vnode_1", "*.tsm")) == []
    engine.close()


def test_tiering_job_sweeps_engine_vnodes(tmp_path, store_dir):
    from cnosdb_tpu.storage.engine import TsKv

    engine = TsKv(str(tmp_path / "data"))
    engine.schemas.setdefault("db", {}).update(_schema())
    v = engine.open_vnode("db", 1)
    for i in range(5):       # data timestamps ≪ wall clock → instantly cold
        v.write(_wb("h1", range(i * 10, i * 10 + 10), [1.0] * 10))
        v.flush()
    v.compact_full()
    job = tiering.TieringJob(engine, interval_s=3600, cold_after_s=3600)
    assert job.sweep_once() == 1
    assert len(tiering.cold_ids(v.dir)) == 1
    assert job.sweep_once() == 0            # idempotent: already cold
    engine.close()


def test_tiering_upload_fault_leaves_file_hot(tmp_engine_dir, store_dir):
    v = _build_vnode(tmp_engine_dir)
    faults.configure("seed=1;objstore.put:fail")
    try:
        with pytest.raises(Exception):
            tiering.tier_vnode(v, boundary_ns=10 ** 18)
    finally:
        faults.reset()
    # failed upload must not flip the registry or drop the local file
    assert tiering.cold_ids(v.dir) == frozenset()
    assert len(glob.glob(os.path.join(tmp_engine_dir, "tsm", "*.tsm"))) == 1
    scan_vnode(v, "cpu")
    v.close()


# ------------------------------------------------- coordinator failover
def test_query_path_recovers_wiped_sidecars(tmp_path, store_dir):
    """End-to-end chaos: tiered vnode loses its local skip-index state;
    the coordinator's TsmError handler rebuilds it from the object store
    and retries — the query answers with no lost rows."""
    from cnosdb_tpu.parallel.coordinator import Coordinator
    from cnosdb_tpu.parallel.meta import MetaStore
    from cnosdb_tpu.sql.executor import QueryExecutor
    from cnosdb_tpu.storage.engine import TsKv

    meta = MetaStore(str(tmp_path / "meta.json"))
    engine = TsKv(str(tmp_path / "data"))
    coord = Coordinator(meta, engine)
    db = QueryExecutor(meta, coord)
    db.execute_one("CREATE TABLE m (v DOUBLE, TAGS(h))")
    for i in range(5):
        db.execute_one(
            "INSERT INTO m (time, h, v) VALUES "
            + ",".join(f"({i * 10 + j},'a',{float(i * 10 + j)})"
                       for j in range(10)))
        for v in list(engine.vnodes.values()):
            v.flush()           # 5 sealed files per vnode → L1 compaction
    tiered = []
    for v in list(engine.vnodes.values()):
        v.compact_full()
        if tiering.tier_vnode(v, boundary_ns=10 ** 18):
            tiered.append(v)
    assert tiered

    rs = db.execute_one("SELECT count(v) FROM m")
    assert int(rs.columns[0][0]) == 50
    from cnosdb_tpu.models.predicate import ColumnDomains, TimeRanges

    splits = coord.table_vnodes("cnosdb", "public", "m",
                                TimeRanges.all(), ColumnDomains())
    assert "cold" in {s.tier for s in splits}

    for v in tiered:
        for side in glob.glob(os.path.join(v.dir, "tsm", "*.tsmc")):
            os.unlink(side)
        for fid in tiering.cold_ids(v.dir):
            v.summary.version.drop_reader(fid)
    with coord._scan_cache_lock:
        coord._scan_cache.clear()
    tiering.block_cache_clear()
    from cnosdb_tpu.server import serving as serving_mod

    serving_mod.invalidate("cnosdb", "public")   # the wipe bumps no token
    rs = db.execute_one("SELECT count(v) FROM m")
    assert int(rs.columns[0][0]) == 50      # recovered, not lost
    for v in tiered:
        assert glob.glob(os.path.join(v.dir, "tsm", "*.tsmc"))
    engine.close()
