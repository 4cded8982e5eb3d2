"""A batch's device meta is built for the program that takes it
(ops/device_cache.py): the i32 (sec, ns) pair in one native pass that
writes where the put reads, the first/last rank where a query first asks,
the epoch from the snapshot's one cached (min, max) pair. Every array the
fused program is handed equals, value for value, what the eager numpy
`_init_meta` built — held here on the native routine and on the fallback.
"""
import threading

import numpy as np
import pytest

from cnosdb_tpu.models.schema import ValueType
from cnosdb_tpu.models.series import SeriesKey
from cnosdb_tpu.ops import device_cache, fused
from cnosdb_tpu.ops.device_cache import DeviceBatch, device_batch
from cnosdb_tpu.ops.tpu_exec import (AggSpec, TpuQuery, _bucket_geometry,
                                     execute_scan_aggregate)
from cnosdb_tpu.storage import native
from cnosdb_tpu.storage.scan import ScanBatch, merge_scan_batches
from cnosdb_tpu.utils import stages

NS = 1_000_000_000
T0 = 1_600_000_000 * NS
EDGE = (2**31 - 3) * NS + 999_999_999      # the widest span a twin is given


# ---------------------------------------------------------------------------
# (a) the one-pass split against the numpy expressions it replaced
# ---------------------------------------------------------------------------
def _reference_split(ts, epoch):
    """`_init_meta`'s expressions at ed89731, verbatim."""
    rel = ts - epoch
    sec = (rel // 1_000_000_000).astype(np.int32)
    ns = (rel - sec.astype(np.int64) * 1_000_000_000).astype(np.int32)
    return sec, ns


def _strided(n):
    wide = np.empty((n, 2), dtype=np.int64)
    wide[:, 0] = T0 + np.arange(n) * 10 * NS + np.arange(n) % 3
    wide[:, 1] = -1
    return wide[:, 0]


SPLIT_CASES = {
    "second_aligned": lambda: T0 + np.tile(np.arange(700) * 10 * NS, 3),
    "remainders": lambda: T0 + np.arange(1500) * 30 * NS
    + np.arange(1500) % 7 * 142_857_143,
    "n0": lambda: np.empty(0, dtype=np.int64),
    "n1": lambda: np.array([T0 + 5], dtype=np.int64),
    "i32_seconds_edge": lambda: T0 + np.array(
        [0, 1, NS - 1, NS, EDGE - NS, EDGE], dtype=np.int64),
    # never handed to a twin (launch_scan_aggregate keeps such a span on
    # the host): numpy's wrap-around is still the routine's, bit for bit
    "past_i32_seconds": lambda: T0 + np.array(
        [0, 2**31 * NS + 7, (2**32 + 5) * NS + 11], dtype=np.int64),
    "n_equals_n_pad": lambda: T0 + np.arange(2048) * NS,
    "non_contiguous": lambda: _strided(1300),
    "above_the_thread_grain": lambda: T0 + np.arange(600_000) * 10 * NS
    + np.arange(600_000) % 2,
}


@pytest.fixture(params=["native", "fallback"])
def lane(request, monkeypatch):
    """Both ways `_split_ts` can take: the native routine, and numpy where
    the library is absent."""
    if request.param == "native":
        if native.split_ts_i32(np.zeros(1, np.int64), 0,
                               np.zeros(1, np.int32), None) is None:
            pytest.skip("native library not built")
    else:
        monkeypatch.setattr(native, "split_ts_i32", lambda *a, **k: None)
    return request.param


@pytest.mark.parametrize("case", sorted(SPLIT_CASES))
def test_split_equals_the_numpy_expressions(case, lane):
    ts = SPLIT_CASES[case]()
    n = len(ts)
    epoch = int(ts.min()) if n else 0
    n_pad = device_cache.pad_rows(max(n, 1))
    want_sec, want_ns = _reference_split(ts, epoch)
    for n_threads in (1, 3):
        sec, ns = device_cache._split_ts(ts, epoch, n_pad, n_threads)
        assert sec.dtype == np.int32 and sec.shape == (n_pad,)
        assert sec.flags.c_contiguous
        assert np.array_equal(sec[:n], want_sec)
        assert not sec[n:].any()                   # pad rows read 0
        if (want_ns == 0).all():
            assert ns is None                      # never allocated
        else:
            assert ns.dtype == np.int32 and ns.shape == (n_pad,)
            assert np.array_equal(ns[:n], want_ns) and not ns[n:].any()
    if case == "n_equals_n_pad":
        assert n == n_pad


# ---------------------------------------------------------------------------
# batches built by hand: series-major, INTEGER columns (exact on every lane)
# ---------------------------------------------------------------------------
def _batch(ts_of_series, seed=11):
    """One series a list entry; values drawn so first/last/max differ."""
    rng = np.random.default_rng(seed)
    ts = np.concatenate(ts_of_series).astype(np.int64)
    sid = np.concatenate([np.full(len(t), i, dtype=np.int32)
                          for i, t in enumerate(ts_of_series)])
    n = len(ts)
    valid = np.ones(n, dtype=bool)
    valid[rng.integers(0, n, size=max(n // 9, 1))] = False
    return ScanBatch(
        table="cpu",
        series_ids=np.arange(1, len(ts_of_series) + 1, dtype=np.uint64),
        series_keys=[SeriesKey("cpu", {"host": f"h{i}"})
                     for i in range(len(ts_of_series))],
        ts=ts, sid_ordinal=sid,
        fields={"cnt": (ValueType.INTEGER,
                        rng.integers(-1000, 1000, size=n).astype(np.int64),
                        np.ones(n, dtype=bool)),
                "gap": (ValueType.INTEGER,
                        rng.integers(0, 50, size=n).astype(np.int64),
                        valid)})


def _aligned(n_series=5, rows=240):
    return _batch([T0 + np.arange(rows) * 10 * NS for _ in range(n_series)])


def _ties_and_stagger(rows=180):
    """Series 0 and 1 share every timestamp (ties break by row order),
    2 is offset by 3 s + 7 ns, 3 starts late and runs at another cadence."""
    base = T0 + np.arange(rows) * 10 * NS
    return _batch([base, base.copy(), base + 3 * NS + 7,
                   T0 + 400 * NS + np.arange(rows // 2) * 17 * NS + 1])


def _run(batch, query, device, monkeypatch):
    monkeypatch.setenv("CNOSDB_TPU_FORCE_DEVICE_PATH", "1" if device else "0")
    monkeypatch.setenv("CNOSDB_TPU_REGULAR", "0")
    prof = stages.QueryProfile()
    before = fused.launch_count
    with stages.profile_scope(prof):
        r = execute_scan_aggregate(batch, query)
    assert (fused.launch_count > before) == device
    order = np.lexsort([np.asarray(r.columns[c]) for c in
                        reversed(("host", "time")) if c in r.columns]) \
        if r.n_rows else np.arange(0)
    table = {name: np.asarray(col)[order].tolist()
             for name, col in r.columns.items()}
    return table, prof


def _query(funcs, bucket=True):
    return TpuQuery(
        group_tags=["host"],
        time_bucket=(0, 300 * NS) if bucket else None,
        aggs=[AggSpec(f, c, f"{f}_{c}") for f in funcs
              for c in ("cnt", "gap")])


@pytest.mark.parametrize("shape", ["second_aligned", "remainders"])
def test_twin_holds_the_parents_arrays(shape, lane, monkeypatch):
    """The device twin's `ts_sec` / `ts_ns` / `sid_ordinal` are the eager
    build's, and a bucketed program over them answers as the host does;
    `ts_ns` exists (and `has_ts_ns` compiles in) only with a remainder."""
    batch = _aligned() if shape == "second_aligned" else _ties_and_stagger()
    n = batch.n_rows
    want_sec, want_ns = _reference_split(batch.ts, int(batch.ts.min()))
    q = _query(["sum", "max"])
    host, _ = _run(batch, q, False, monkeypatch)
    dev, prof = _run(batch, q, True, monkeypatch)
    assert dev == host
    db = batch._device_batch
    assert (db.n_rows, db.epoch_ns) == (n, int(batch.ts.min()))
    sec = np.asarray(db.ts_sec)
    assert sec.shape == (db.n_pad,) and np.array_equal(sec[:n], want_sec)
    assert not sec[n:].any()
    sid = np.asarray(db.sid_ordinal)
    assert np.array_equal(sid[:n], batch.sid_ordinal) and not sid[n:].any()
    if shape == "second_aligned":
        assert db.ns_all_zero and db.ts_ns is None
    else:
        ns = np.asarray(db.ts_ns)
        assert not db.ns_all_zero
        assert np.array_equal(ns[:n], want_ns) and not ns[n:].any()
    # has_ts_ns is the tenth field of the program's key
    assert any(k[9] == (shape == "remainders") and k[6] == db.n_pad
               for k in fused._kernel_cache)
    assert "upload.meta_ms" in prof.ms and "upload_ms" in prof.ms
    # read by nothing: gone with the passes that computed them
    assert not any(hasattr(db, k) for k in ("i32_ok", "ts_min", "ts_max"))


# ---------------------------------------------------------------------------
# (b) the rank is built where a query first asks for it, once
# ---------------------------------------------------------------------------
def _eager_rank(ts):
    """The rank `_init_meta` built for every batch at ed89731, verbatim."""
    n = len(ts)
    order = np.argsort(ts, kind="stable")
    rank = np.empty(n, dtype=np.int32)
    rank[order] = np.arange(n, dtype=np.int32)
    return rank


@pytest.mark.parametrize("func", ["avg", "sum", "max", "min", "count"])
def test_a_plain_aggregate_builds_no_rank(func, monkeypatch):
    batch = _ties_and_stagger()
    q = _query([func])
    host, _ = _run(batch, q, False, monkeypatch)
    dev, prof = _run(batch, q, True, monkeypatch)
    assert dev == host
    assert prof.counts["upload.rank_builds"] == 0    # booked, and 0
    db = batch._device_batch
    assert db._rank_np is None and db.rank is None
    assert db._ts is batch.ts


@pytest.mark.parametrize("func", ["first", "last"])
def test_first_and_last_build_the_rank_once(func, monkeypatch):
    batch = _ties_and_stagger()
    n = batch.n_rows
    _run(batch, _query(["avg"]), True, monkeypatch)
    db = batch._device_batch
    assert db._rank_np is None
    bytes_before = db.est_bytes
    q = _query([func])
    host, _ = _run(batch, q, False, monkeypatch)
    dev, prof = _run(batch, q, True, monkeypatch)
    assert dev == host
    assert batch._device_batch is db                 # the cached twin
    assert prof.counts["upload.rank_builds"] == 1
    # the sort is still booked as upload, where it happens: its own stage
    # inside upload.meta_ms, and the pad and put beside it as every put's
    assert 0 < prof.ms["upload.rank_ms"] <= prof.ms["upload.meta_ms"] \
        <= prof.ms["upload_ms"]
    assert "upload.stage_ms" in prof.ms and prof.ms["upload.put_ms"] > 0
    assert np.array_equal(db._rank_np, _eager_rank(batch.ts))
    on_device = np.asarray(db.rank)
    assert on_device.dtype == np.int32 and on_device.shape == (db.n_pad,)
    assert np.array_equal(on_device[:n], db._rank_np)
    assert not on_device[n:].any()
    assert db.est_bytes == bytes_before + on_device.nbytes
    kept = db.rank
    other = "last" if func == "first" else "first"
    dev2, prof2 = _run(batch, _query([other]), True, monkeypatch)
    assert dev2 == _run(batch, _query([other]), False, monkeypatch)[0]
    assert "upload.rank_builds" not in prof2.counts  # a second builds none
    assert "upload_ms" not in prof2.ms
    assert db.rank is kept


def test_threads_at_once_publish_one_rank(monkeypatch):
    """More askers than cores, a short switch interval: one sort, and
    every asker holds the one published array."""
    import sys

    batch = _ties_and_stagger()
    db = DeviceBatch(batch)
    built = []
    real = device_cache._time_rank

    def counted_rank(ts):
        built.append(threading.get_ident())
        return real(ts)

    monkeypatch.setattr(device_cache, "_time_rank", counted_rank)
    n_threads = 16
    start = threading.Barrier(n_threads)
    got = []

    def ask():
        start.wait(timeout=30)
        got.append(db.rank_dev())

    threads = [threading.Thread(target=ask) for _ in range(n_threads)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert len(built) == 1
    assert len(got) == n_threads and all(g is db.rank for g in got)
    assert np.array_equal(db._rank_np, _eager_rank(batch.ts))
    rank_bytes = np.asarray(db.rank).nbytes
    assert db.est_bytes == db._estimate_bytes()      # counted once
    assert db.est_bytes >= rank_bytes


def test_split_refuses_outputs_it_cannot_write():
    if not native.available():
        pytest.skip("native library not built")
    ts = T0 + np.arange(10, dtype=np.int64)
    for bad in (np.zeros(9, np.int32), np.zeros(16, np.int64),
                np.zeros(32, np.int32)[::2]):
        with pytest.raises(ValueError):
            native.split_ts_i32(ts, T0, bad, None)
    with pytest.raises(ValueError):
        native.split_ts_i32(ts, T0, None, None)


@pytest.mark.parametrize("shape", ["ties", "not_time_aligned", "one_row"])
def test_lazy_rank_equals_the_eager_rank(shape):
    if shape == "ties":
        base = T0 + np.arange(50) * NS
        batch = _batch([base, base.copy(), base.copy()])
    elif shape == "not_time_aligned":
        batch = _ties_and_stagger(rows=90)
    else:
        batch = _batch([np.array([T0])])
    db = device_batch(batch)
    assert db._rank_np is None
    rank = np.asarray(db.rank_dev())
    want = _eager_rank(batch.ts)
    assert np.array_equal(rank[:batch.n_rows], want)
    assert sorted(want.tolist()) == list(range(batch.n_rows))  # unique
    if shape == "ties":
        # equal timestamps rank in row order: series 0 before 1 before 2
        assert want[0] < want[50] < want[100]


# ---------------------------------------------------------------------------
# (c) the delta-merge twin gets the same meta, and a lazy rank
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("func", ["first", "last"])
def test_merged_twin_answers_first_and_last_as_the_host(func, monkeypatch):
    rows = 120
    cached = _batch([T0 + np.arange(rows) * 10 * NS + i for i in range(3)])
    tail = T0 + (rows + np.arange(30)) * 10 * NS
    delta = _batch([tail + i for i in range(3)], seed=5)
    monkeypatch.setenv("CNOSDB_TPU_REGULAR", "0")
    device_batch(cached)
    merged, gather = merge_scan_batches(cached, delta)
    assert gather is not None                        # the pure-append shape
    prof = stages.QueryProfile()
    with stages.profile_scope(prof):
        db = device_cache.merged_device_batch(merged, cached, delta, gather)
    assert db is merged._device_batch and db._rank_np is None
    assert prof.counts["upload.rank_builds"] == 0
    want_sec, want_ns = _reference_split(merged.ts, int(merged.ts.min()))
    n = merged.n_rows
    assert np.array_equal(np.asarray(db.ts_sec)[:n], want_sec)
    assert np.array_equal(np.asarray(db.ts_ns)[:n], want_ns)
    assert db.epoch_ns == _bucket_geometry(merged, None)[0]
    q = _query([func, "sum"])
    dev, prof = _run(merged, q, True, monkeypatch)
    assert merged._device_batch is db
    assert prof.counts["upload.rank_builds"] == 1
    assert np.array_equal(db._rank_np, _eager_rank(merged.ts))
    host, _ = _run(merged, q, False, monkeypatch)
    assert dev == host


# ---------------------------------------------------------------------------
# (d) one cached (min, max) pair: the twin's epoch is the geometry's ts_lo
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("order", ["twin_first", "geometry_first",
                                   "pair_set_before_both"])
def test_epoch_is_the_geometrys_ts_lo_by_construction(order, monkeypatch):
    batch = _ties_and_stagger()
    lo, hi = int(batch.ts.min()), int(batch.ts.max())
    tb = (0, 300 * NS)
    if order == "twin_first":
        db = device_batch(batch)
        geo = _bucket_geometry(batch, tb)
    elif order == "geometry_first":
        geo = _bucket_geometry(batch, tb)
        db = device_batch(batch)
    else:
        # a pair that is NOT ts.min(): were either side to take its own
        # min, the two would disagree; both read the one cached pair
        lo = lo - 3 * NS - 1
        batch._ts_minmax = (lo, hi)
        db = device_batch(batch)
        geo = _bucket_geometry(batch, tb)
    assert batch._ts_minmax == (lo, hi) == batch.ts_minmax()
    assert db.epoch_ns == geo[0] == lo
    assert geo[1] == hi
    want_sec, want_ns = _reference_split(batch.ts, lo)
    assert np.array_equal(np.asarray(db.ts_sec)[:batch.n_rows], want_sec)
    assert np.array_equal(np.asarray(db.ts_ns)[:batch.n_rows], want_ns)
    # and the bucket constants derived from that epoch answer as the host
    q = _query(["sum", "max"])
    dev, _ = _run(batch, q, True, monkeypatch)
    host, _ = _run(batch, q, False, monkeypatch)
    assert dev == host and batch._device_batch is db


def test_an_empty_batch_has_a_zero_pair():
    empty = ScanBatch(table="cpu", series_ids=np.empty(0, np.uint64),
                      series_keys=[], ts=np.empty(0, np.int64),
                      sid_ordinal=np.empty(0, np.int32))
    assert empty.ts_minmax() == (0, 0)
    db = DeviceBatch(empty)
    assert (db.n_rows, db.epoch_ns, db.ns_all_zero) == (0, 0, True)
    assert not np.asarray(db.ts_sec).any()
