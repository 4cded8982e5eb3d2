"""Native batch-scan fast path (storage.scan._scan_vnode_native +
native/pagedec.cpp): equivalence against the legacy per-series Python
decode across the tricky shapes — nulls, multiple disjoint flushes,
overlapping chunks (fallback), tombstones (fallback), memcache overlay
(fallback), time-range trims, string/bool/int columns — plus predicate
page pruning soundness."""
import os

import numpy as np
import pytest

from cnosdb_tpu.models.points import SeriesRows, WriteBatch
from cnosdb_tpu.models.predicate import TimeRange, TimeRanges
from cnosdb_tpu.models.schema import TskvTableSchema, ValueType
from cnosdb_tpu.models.series import SeriesKey
from cnosdb_tpu.models.strcol import DictArray
from cnosdb_tpu.storage import native
from cnosdb_tpu.storage.scan import scan_vnode
from cnosdb_tpu.storage.vnode import VnodeStorage

pytestmark = pytest.mark.skipif(
    not native.pagedec_available(), reason="native pagedec unavailable")


def _schema():
    return {"m": TskvTableSchema.new_measurement(
        "t", "db", "m", tags=["host"],
        fields=[("f", ValueType.FLOAT), ("i", ValueType.INTEGER),
                ("b", ValueType.BOOLEAN), ("s", ValueType.STRING)])}


def _write(v, host, ts, f=None, i=None, b=None, s=None):
    def py(xs):
        return [None if x is None else
                (x.item() if isinstance(x, np.generic) else x) for x in xs]

    fields = {}
    if f is not None:
        fields["f"] = (int(ValueType.FLOAT), py(f))
    if i is not None:
        fields["i"] = (int(ValueType.INTEGER), py(i))
    if b is not None:
        fields["b"] = (int(ValueType.BOOLEAN), py(b))
    if s is not None:
        fields["s"] = (int(ValueType.STRING), py(s))
    wb = WriteBatch()
    wb.add_series("m", SeriesRows(SeriesKey("m", {"host": host}),
                                  list(ts), fields))
    v.write(wb)


def _assert_batches_equal(a, b):
    assert a.n_rows == b.n_rows
    assert a.n_series == b.n_series
    np.testing.assert_array_equal(a.series_ids, b.series_ids)
    np.testing.assert_array_equal(a.ts, b.ts)
    np.testing.assert_array_equal(a.sid_ordinal, b.sid_ordinal)
    assert set(a.fields) == set(b.fields)
    for name in a.fields:
        vt_a, vals_a, valid_a = a.fields[name]
        vt_b, vals_b, valid_b = b.fields[name]
        assert vt_a == vt_b
        np.testing.assert_array_equal(valid_a, valid_b)
        if isinstance(vals_a, DictArray) or isinstance(vals_b, DictArray):
            obj_a = np.asarray(vals_a.materialize()
                               if isinstance(vals_a, DictArray) else vals_a)
            obj_b = np.asarray(vals_b.materialize()
                               if isinstance(vals_b, DictArray) else vals_b)
            np.testing.assert_array_equal(obj_a[valid_a], obj_b[valid_b])
        else:
            np.testing.assert_array_equal(vals_a[valid_a], vals_b[valid_b])


def _both_scans(v, **kw):
    got = scan_vnode(v, "m", **kw)
    os.environ["CNOSDB_NO_NATIVE_SCAN"] = "1"
    try:
        want = scan_vnode(v, "m", **kw)
    finally:
        del os.environ["CNOSDB_NO_NATIVE_SCAN"]
    return got, want


def test_flushed_basic(tmp_engine_dir):
    v = VnodeStorage(1, tmp_engine_dir, schemas=_schema())
    rng = np.random.default_rng(1)
    _write(v, "h1", range(0, 1000), f=rng.normal(size=1000),
           i=rng.integers(-50, 50, 1000), b=rng.integers(0, 2, 1000) > 0,
           s=[f"v{x}" for x in rng.integers(0, 5, 1000)])
    _write(v, "h2", range(500, 900), f=rng.normal(size=400))
    v.flush()
    got, want = _both_scans(v)
    _assert_batches_equal(got, want)
    v.close()


def test_multiple_disjoint_flushes(tmp_engine_dir):
    v = VnodeStorage(1, tmp_engine_dir, schemas=_schema())
    for base in (0, 1000, 2000):
        _write(v, "h1", range(base, base + 500),
               f=np.arange(base, base + 500) * 0.5)
        v.flush()
    got, want = _both_scans(v)
    _assert_batches_equal(got, want)
    assert (np.diff(got.ts) > 0).all()
    v.close()


def test_overlapping_chunks_fall_back(tmp_engine_dir):
    v = VnodeStorage(1, tmp_engine_dir, schemas=_schema())
    _write(v, "h1", range(0, 100), f=np.ones(100))
    v.flush()
    _write(v, "h1", range(50, 150), f=np.full(100, 2.0))  # overlap: dedup
    v.flush()
    got, want = _both_scans(v)
    _assert_batches_equal(got, want)
    assert got.n_rows == 150
    # overlap region takes the later write
    vt, vals, valid = got.fields["f"]
    assert vals[got.ts == 75][0] == 2.0
    v.close()


def test_memcache_overlay_falls_back(tmp_engine_dir):
    v = VnodeStorage(1, tmp_engine_dir, schemas=_schema())
    _write(v, "h1", range(0, 100), f=np.ones(100))
    v.flush()
    _write(v, "h1", range(90, 120), f=np.full(30, 9.0))  # unflushed
    got, want = _both_scans(v)
    _assert_batches_equal(got, want)
    assert got.fields["f"][1][got.ts == 95][0] == 9.0
    v.close()


def test_tombstone_falls_back(tmp_engine_dir):
    v = VnodeStorage(1, tmp_engine_dir, schemas=_schema())
    _write(v, "h1", range(0, 100), f=np.arange(100.0))
    _write(v, "h2", range(0, 100), f=np.arange(100.0))
    v.flush()
    v.delete_time_range("m", None, 10, 20)
    got, want = _both_scans(v)
    _assert_batches_equal(got, want)
    assert got.n_rows == 2 * (100 - 11)
    v.close()


def test_nulls_across_pages(tmp_engine_dir):
    v = VnodeStorage(1, tmp_engine_dir, schemas=_schema())
    # one field written on even rows only → other field null there
    n = 500
    ts = list(range(n))
    f = [float(x) if x % 2 == 0 else None for x in range(n)]
    i = [int(x) if x % 3 == 0 else None for x in range(n)]
    wb = WriteBatch()
    wb.add_series("m", SeriesRows(
        SeriesKey("m", {"host": "h1"}), ts,
        {"f": (int(ValueType.FLOAT), f),
         "i": (int(ValueType.INTEGER), i)}))
    v.write(wb)
    v.flush()
    got, want = _both_scans(v)
    _assert_batches_equal(got, want)
    vt, vals, valid = got.fields["f"]
    assert valid.sum() == sum(1 for x in f if x is not None)
    v.close()


def test_time_range_trim(tmp_engine_dir):
    v = VnodeStorage(1, tmp_engine_dir, schemas=_schema())
    _write(v, "h1", range(0, 1000), f=np.arange(1000.0))
    _write(v, "h2", range(2000, 3000), f=np.arange(1000.0))
    v.flush()
    trs = TimeRanges([TimeRange(250, 2200)])
    got, want = _both_scans(v, time_ranges=trs)
    _assert_batches_equal(got, want)
    assert got.ts.min() >= 250 and got.ts.max() <= 2200
    # h2 trimmed to 201 rows, h1 to 750
    assert got.n_rows == 750 + 201
    v.close()


def test_time_range_drops_series_entirely(tmp_engine_dir):
    v = VnodeStorage(1, tmp_engine_dir, schemas=_schema())
    _write(v, "h1", range(0, 100), f=np.arange(100.0))
    _write(v, "h2", range(5000, 5100), f=np.arange(100.0))
    v.flush()
    trs = TimeRanges([TimeRange(0, 200)])
    got, want = _both_scans(v, time_ranges=trs)
    _assert_batches_equal(got, want)
    assert got.n_series == 1
    v.close()


def test_field_projection(tmp_engine_dir):
    v = VnodeStorage(1, tmp_engine_dir, schemas=_schema())
    rng = np.random.default_rng(3)
    _write(v, "h1", range(0, 300), f=rng.normal(size=300),
           i=rng.integers(0, 9, 300))
    v.flush()
    got, want = _both_scans(v, field_names=["i"])
    _assert_batches_equal(got, want)
    assert set(got.fields) == {"i"}
    v.close()


def test_predicate_page_pruning_sound(tmp_engine_dir):
    """Pruned scan must keep every page that can hold a matching row;
    the aggregate over (pruned batch + row filter) must equal the
    aggregate over the full batch + row filter."""
    from cnosdb_tpu.sql.expr import BinOp, Column, Literal

    v = VnodeStorage(1, tmp_engine_dir, schemas=_schema())
    rng = np.random.default_rng(4)
    n = 600_000   # > 2 pages (256k rows each) with distinct stat ranges
    vals = np.concatenate([rng.uniform(0, 10, n // 2),
                           rng.uniform(50, 60, n // 2)])
    _write(v, "h1", range(n), f=vals)
    v.flush()
    flt = BinOp(">", Column("f"), Literal(55.0))
    pruned = scan_vnode(v, "m", page_filter=flt)
    full = scan_vnode(v, "m")
    assert pruned.n_rows < full.n_rows   # something actually pruned
    pm = pruned.fields["f"][1] > 55.0
    fm = full.fields["f"][1] > 55.0
    assert pm.sum() == fm.sum()
    assert pruned.fields["f"][1][pm].sum() == \
        pytest.approx(full.fields["f"][1][fm].sum())
    v.close()


def test_pruning_keeps_inf(tmp_engine_dir):
    """±inf participates in page stats (NaN doesn't): an inf row must
    survive pruning for a > comparison."""
    from cnosdb_tpu.sql.expr import BinOp, Column, Literal

    v = VnodeStorage(1, tmp_engine_dir, schemas=_schema())
    vals = np.zeros(1000)
    vals[500] = np.inf
    _write(v, "h1", range(1000), f=vals)
    v.flush()
    flt = BinOp(">", Column("f"), Literal(1e300))
    pruned = scan_vnode(v, "m", page_filter=flt)
    m = pruned.fields["f"][1] > 1e300
    assert m.sum() == 1
    v.close()


def test_no_prune_on_ne_with_nan(tmp_engine_dir):
    """`!=` must not prune: page stats exclude NaN but NaN satisfies !=
    (sql 3VL evaluates it as ~(a == b)) — a constant page may hide a
    matching NaN row."""
    from cnosdb_tpu.sql.expr import BinOp, Column, Literal

    v = VnodeStorage(1, tmp_engine_dir, schemas=_schema())
    vals = np.full(1000, 5.0)
    vals[123] = np.nan
    _write(v, "h1", range(1000), f=vals)
    v.flush()
    flt = BinOp("!=", Column("f"), Literal(5.0))
    pruned = scan_vnode(v, "m", page_filter=flt)
    assert pruned.n_rows == 1000   # page kept despite lo == hi == 5
    fv = pruned.fields["f"][1]
    with np.errstate(invalid="ignore"):
        m = ~(fv == 5.0)
    assert m.sum() == 1
    v.close()


def test_unsigned_and_bool_roundtrip(tmp_engine_dir):
    schemas = {"m": TskvTableSchema.new_measurement(
        "t", "db", "m", tags=["host"],
        fields=[("u", ValueType.UNSIGNED), ("b", ValueType.BOOLEAN)])}
    v = VnodeStorage(1, tmp_engine_dir, schemas=schemas)
    rng = np.random.default_rng(5)
    u = rng.integers(0, 2**63, 400, dtype=np.uint64) * 2  # exercises u64
    b = rng.integers(0, 2, 400) > 0
    wb = WriteBatch()
    wb.add_series("m", SeriesRows(
        SeriesKey("m", {"host": "h1"}), list(range(400)),
        {"u": (int(ValueType.UNSIGNED), u.tolist()),
         "b": (int(ValueType.BOOLEAN), b.tolist())}))
    v.write(wb)
    v.flush()
    got = scan_vnode(v, "m")
    os.environ["CNOSDB_NO_NATIVE_SCAN"] = "1"
    try:
        want = scan_vnode(v, "m")
    finally:
        del os.environ["CNOSDB_NO_NATIVE_SCAN"]
    np.testing.assert_array_equal(got.fields["u"][1], want.fields["u"][1])
    np.testing.assert_array_equal(got.fields["b"][1], want.fields["b"][1])
    v.close()


# ---------------------------------------------------------------------------
# the indexed plan against the per-series reference path
# ---------------------------------------------------------------------------
def _assert_bit_identical(a, b):
    """Two ScanBatches, array by array: series, keys, time, ordinals, each
    column's validity and every valid value (strings by what they read;
    the slot of a NULL holds whatever its lane left there)."""
    np.testing.assert_array_equal(a.series_ids, b.series_ids)
    assert a.series_ids.dtype == b.series_ids.dtype
    assert a.series_keys == b.series_keys
    np.testing.assert_array_equal(a.ts, b.ts)
    np.testing.assert_array_equal(a.sid_ordinal, b.sid_ordinal)
    assert a.sid_ordinal.dtype == b.sid_ordinal.dtype
    assert set(a.fields) == set(b.fields)
    for name, (vt_a, vals_a, valid_a) in a.fields.items():
        vt_b, vals_b, valid_b = b.fields[name]
        assert vt_a == vt_b, name
        np.testing.assert_array_equal(valid_a, valid_b, err_msg=name)
        if not (isinstance(vals_a, DictArray)
                or isinstance(vals_b, DictArray)):
            assert vals_a.dtype == vals_b.dtype, name
        np.testing.assert_array_equal(
            np.asarray(_as_objects(vals_a))[valid_a],
            np.asarray(_as_objects(vals_b))[valid_b], err_msg=name)


def _where(b, keep):
    """→ the rows of `b` under `keep`, with the series they leave."""
    from cnosdb_tpu.storage.scan import ScanBatch

    left, ordinal = np.unique(b.sid_ordinal[keep], return_inverse=True)
    return ScanBatch(
        b.table, b.series_ids[left], [b.series_keys[k] for k in left],
        b.ts[keep], ordinal.astype(np.int32),
        {name: (vt, _as_objects(vals)[keep], valid[keep])
         for name, (vt, vals, valid) in b.fields.items()})


def _as_objects(vals):
    return vals.materialize() if isinstance(vals, DictArray) else vals


def _small_pages(monkeypatch, rows=64):
    """Flushes write pages of `rows` rows, so a chunk has several."""
    from cnosdb_tpu.storage import tsm

    monkeypatch.setattr(tsm.TsmWriter.__init__, "__defaults__", (rows,))


def _fleet(v, hosts=6, lo=0, hi=400, seed=0, **only):
    """`hosts` series over [lo, hi) with all four field kinds, a few
    nulls; `only` narrows the fields written."""
    rng = np.random.default_rng(seed)
    n = hi - lo
    for h in range(hosts):
        cols = dict(
            f=[None if x % 17 == 3 else float(x) / 7 for x in range(n)],
            i=rng.integers(-9, 9, n), b=rng.integers(0, 2, n) > 0,
            s=[None if x % 29 == 5 else f"w{x % 7}" for x in range(n)])
        if only:
            cols = {k: c for k, c in cols.items() if only.get(k)}
        _write(v, f"h{h}", range(lo, hi), **cols)


def _sids(v):
    return scan_vnode(v, "m", field_names=["f"]).series_ids


def _disjoint_flushes(v, monkeypatch):
    for base in (0, 1000, 2000):
        _fleet(v, lo=base, hi=base + 300, seed=base)
        v.flush()
    return [{}, {"field_names": ["i", "s"]}]


def _overlapping_l0(v, monkeypatch):
    _fleet(v, hi=300)
    v.flush()
    _write(v, "h1", range(200, 500), f=np.full(300, 2.0))   # overlaps h1
    _write(v, "h9", range(0, 50), i=np.arange(50))
    v.flush()
    return [{}, {"time_ranges": TimeRanges([TimeRange(100, 250)])}]


def _tombstoned_series(v, monkeypatch):
    _fleet(v)
    v.flush()
    _fleet(v, lo=400, hi=800, seed=1)
    v.flush()
    sids = _sids(v)
    v.delete_time_range("m", [int(sids[2])], 100, 450)
    return [{}, {"series_ids": sids[[0, 2, 4]]}]


def _memcache_rows(v, monkeypatch):
    _fleet(v)
    v.flush()
    _write(v, "h1", range(380, 450), f=np.full(70, 9.0))   # in the range
    _write(v, "h3", range(9000, 9010), f=np.ones(10))      # outside it
    return [{}, {"time_ranges": TimeRanges([TimeRange(50, 500)])},
            {"time_ranges": TimeRanges([TimeRange(50, 379)])}]


def _renamed_and_absent_columns(v, monkeypatch):
    _fleet(v, hi=200, f=True, i=True)              # no b, no s
    v.flush()
    v.schemas["m"].rename_column("i", "count")     # the id stays
    _fleet(v, lo=200, hi=400, seed=2, f=True, b=True)
    _write(v, "h0", range(400, 500), i=None, f=np.ones(100))
    wb = WriteBatch()
    wb.add_series("m", SeriesRows(
        SeriesKey("m", {"host": "h1"}), list(range(400, 450)),
        {"count": (int(ValueType.INTEGER), list(range(50)))}))
    v.write(wb)
    v.flush()
    return [{}, {"field_names": ["count", "b"]},
            {"field_names": ["b", "s", "count"]}]


def _multi_range_trim(v, monkeypatch):
    _small_pages(monkeypatch)
    _fleet(v, hi=500)
    v.flush()
    _fleet(v, lo=500, hi=900, seed=3)
    v.flush()
    return [{"time_ranges": TimeRanges([TimeRange(30, 100),
                                        TimeRange(130, 191),
                                        TimeRange(450, 555)])},
            {"time_ranges": TimeRanges([TimeRange(64, 127)])},
            {"time_ranges": TimeRanges([TimeRange(0, 10**6)])}]


def _range_drops_a_series(v, monkeypatch):
    _small_pages(monkeypatch)
    _fleet(v, hosts=3, hi=300)
    _write(v, "late", range(5000, 5200), f=np.arange(200.0))
    v.flush()
    return [{"time_ranges": TimeRanges([TimeRange(10, 250)])},
            {"time_ranges": TimeRanges([TimeRange(4000, 6000)])},
            {"time_ranges": TimeRanges([TimeRange(10**6, 10**7)])}]


def _constraints_prune(v, monkeypatch):
    from cnosdb_tpu.sql.expr import BinOp, Column, Literal

    _small_pages(monkeypatch)
    for h in range(4):
        _write(v, f"h{h}", range(0, 512),
               f=np.repeat(np.arange(8.0) * 10 + h, 64))
    _write(v, "low", range(0, 128), f=np.zeros(128))   # pruned whole
    v.flush()
    flt = BinOp(">", Column("f"), Literal(45.0))
    return [{"page_filter": flt},
            {"page_filter": flt,
             "time_ranges": TimeRanges([TimeRange(100, 400)])}]


def _cold_reader(v, monkeypatch):
    from cnosdb_tpu.storage import tiering

    _small_pages(monkeypatch)
    v.picker.l0_trigger = 2
    for lo in (0, 200):
        _fleet(v, hosts=3, lo=lo, hi=lo + 200, seed=lo)
        v.flush()
    v.compact_full()                            # → one L1 file, [0, 400)
    _fleet(v, hosts=4, lo=400, hi=600, seed=4)
    v.flush()                                   # a hot file beside it
    bucket = os.path.join(os.path.dirname(v.dir), "bucket")
    os.makedirs(bucket)
    tiering.configure(bucket)
    assert tiering.tier_vnode(v, boundary_ns=400) == 1
    assert any(v.summary.version.reader(fm).is_cold
               for fm in v.summary.version.all_files())
    return [{}, {"time_ranges": TimeRanges([TimeRange(70, 450)])}]


def _subset_and_permutation(v, monkeypatch):
    _fleet(v, hosts=8)
    v.flush()
    _fleet(v, hosts=8, lo=400, hi=700, seed=5)
    v.flush()
    sids = _sids(v)
    return [{"series_ids": sids[[5, 1, 6]]},
            {"series_ids": sids[::-1].copy()},
            {"series_ids": np.concatenate(
                [sids[3:5], np.array([12345], dtype=np.uint64)])}]


@pytest.mark.parametrize("build", [
    _disjoint_flushes, _overlapping_l0, _tombstoned_series, _memcache_rows,
    _renamed_and_absent_columns, _multi_range_trim, _range_drops_a_series,
    _constraints_prune, _cold_reader, _subset_and_permutation],
    ids=lambda f: f.__name__.strip("_"))
def test_indexed_plan_equals_the_per_series_path(tmp_engine_dir,
                                                 monkeypatch, build):
    """The plan made from the files' page indexes gives the ScanBatch the
    per-series reference path gives, bit for bit, whatever route a series
    takes (indexed, or read and merged one at a time)."""
    from cnosdb_tpu.storage import tiering

    v = VnodeStorage(1, tmp_engine_dir, schemas=_schema())
    try:
        for kw in build(v, monkeypatch):
            got, want = _both_scans(v, **kw)
            if "page_filter" in kw:
                # a pruned batch holds every row the filter keeps, and is
                # compared by those (the reference path prunes nothing)
                assert got._pages_pruned and got.n_rows < want.n_rows
                got, want = (_where(b, b.fields["f"][1] > 45.0)
                             for b in (got, want))
            _assert_bit_identical(got, want)
            assert got.n_rows or "time_ranges" in kw
    finally:
        v.close()
        tiering.configure(None)
        tiering.block_cache_clear()


def test_a_compaction_drops_the_old_index_with_its_reader(tmp_engine_dir):
    """A file's page index lives and dies with the file's reader: the
    compaction's new file gets its own, built by the first scan of it."""
    import gc
    import weakref

    v = VnodeStorage(1, tmp_engine_dir, schemas=_schema())
    v.picker.l0_trigger = 2
    for base in (0, 500):
        _fleet(v, hosts=3, lo=base, hi=base + 200)
        v.flush()
    before = scan_vnode(v, "m")
    version = v.summary.version
    old = [version.reader(fm) for fm in version.all_files()]
    assert len(old) == 2 and all(r.page_index("m") is not None for r in old)
    assert old[0].page_index("m") is old[0].page_index("m")
    gone = [weakref.ref(r.page_index("m")) for r in old]
    old_ids = {fm.file_id for fm in version.all_files()}
    v.compact_full()
    (fm,) = version.all_files()
    assert fm.file_id not in old_ids
    assert not old_ids & set(version._readers)
    new = version.reader(fm)
    assert not new._page_indexes            # nobody has scanned it yet
    after = scan_vnode(v, "m")
    _assert_bit_identical(after, before)
    index = new.page_index("m")
    assert index is not None and len(index.sids) == 3
    assert len(index.time) == 3             # one chunk a series, one page
    del old
    gc.collect()
    assert all(ref() is None for ref in gone)
    v.close()
