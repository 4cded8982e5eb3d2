"""Compressed-domain execution lane (storage/compressed_domain.py).

Parity contract: everything the lane answers from the encoded
representation must be BIT-identical to the decode-lane oracle — the
property tests below drive randomized pages (NaN/±inf/denormals, int64
extremes, NULL runs, legacy v1 string pages) through both paths, and the
SQL-level suite A/Bs whole queries against `CNOSDB_COMPRESSED_DOMAIN=0`.
The cold-tier case additionally asserts the lane's point: strictly fewer
bytes fetched from the object store.
"""
import os
from types import SimpleNamespace

import numpy as np
import pytest

from cnosdb_tpu.models.codec import Encoding
from cnosdb_tpu.models.points import SeriesRows, WriteBatch
from cnosdb_tpu.models.schema import TskvTableSchema, ValueType
from cnosdb_tpu.models.series import SeriesKey
from cnosdb_tpu.storage import codecs, compressed_domain as cd, tiering
from cnosdb_tpu.storage.scan import scan_vnode
from cnosdb_tpu.storage.vnode import VnodeStorage

rng = np.random.default_rng(1234)


def _bits(x):
    """Exact-comparison key: floats by their bit pattern (NaN == NaN,
    -0.0 != 0.0 stays visible), everything else by value."""
    if isinstance(x, (float, np.floating)):
        return np.array([x], dtype=np.float64).view(np.uint64)[0]
    return x


# ---------------------------------------------------------------------------
# closed-form first/last vs the decode oracle
# ---------------------------------------------------------------------------
def _int_payloads():
    yield np.array([0], dtype=np.int64)
    yield np.array([2**63 - 1, -2**63, 0, -1, 1], dtype=np.int64)
    yield rng.integers(-2**62, 2**62, 257, dtype=np.int64)
    yield np.arange(1000, 5000, 7, dtype=np.int64)          # const stride
    yield np.full(100, -(2**63), dtype=np.int64)            # zero stride
    yield rng.integers(-5, 5, 64, dtype=np.int64).cumsum()


def test_closed_delta_first_last_int64():
    for vals in _int_payloads():
        blk = codecs.encode(vals, ValueType.INTEGER, Encoding.DELTA)
        plan, why = codecs.split_for_device(blk, ValueType.INTEGER)
        assert plan is not None, why
        first, last = cd._CLOSED[plan["kind"]]
        dec = codecs.decode(blk, ValueType.INTEGER)
        assert first(plan) == dec[0]
        assert last(plan) == dec[-1]
        assert isinstance(first(plan), np.int64)


def test_closed_delta_unsigned_wrap():
    vals = np.array([2**64 - 1, 0, 2**63, 17], dtype=np.uint64)
    blk = codecs.encode(vals, ValueType.UNSIGNED, Encoding.DELTA)
    plan, _ = codecs.split_for_device(blk, ValueType.UNSIGNED)
    dec = codecs.decode(blk, ValueType.UNSIGNED)
    first, last = cd._CLOSED[plan["kind"]]
    # lane reinterprets the wrapping-int64 closed form as uint64, exactly
    # like the decode lane's .view(uint64)
    assert np.uint64(int(first(plan)) & (2**64 - 1)) == dec[0]
    assert np.uint64(int(last(plan)) & (2**64 - 1)) == dec[-1]


def _float_payloads():
    yield np.array([0.0], dtype=np.float64)
    awkward = np.array([np.nan, np.inf, -np.inf, -0.0, 0.0,
                        5e-324, -5e-324, 2.2250738585072014e-308,
                        1.7976931348623157e308], dtype=np.float64)
    yield awkward
    v = rng.normal(size=311)
    v[::13] = np.nan
    v[7] = np.inf
    yield v
    yield np.full(64, np.nan)


def test_closed_gorilla_first_last_bitpattern():
    for vals in _float_payloads():
        blk = codecs.encode(vals, ValueType.FLOAT, Encoding.GORILLA)
        plan, why = codecs.split_for_device(blk, ValueType.FLOAT)
        assert plan is not None, why
        first, last = cd._CLOSED[plan["kind"]]
        dec = codecs.decode(blk, ValueType.FLOAT)
        assert _bits(first(plan)) == _bits(dec[0])
        assert _bits(last(plan)) == _bits(dec[-1])


def test_closed_bitpack_first_last():
    for n in (1, 7, 8, 9, 64, 65, 333):
        vals = rng.integers(0, 2, n).astype(bool)
        blk = codecs.encode(vals, ValueType.BOOLEAN, Encoding.BITPACK)
        plan, _ = codecs.split_for_device(blk, ValueType.BOOLEAN)
        first, last = cd._CLOSED[plan["kind"]]
        dec = codecs.decode(blk, ValueType.BOOLEAN)
        assert first(plan) == dec[0]
        assert last(plan) == dec[-1]


def test_time_value_at_prefix_sum():
    for ts in (np.arange(10**9, 10**9 + 500 * 7, 7, dtype=np.int64),
               np.sort(rng.integers(0, 10**12, 400)).astype(np.int64),
               np.array([42], dtype=np.int64)):
        blk = codecs.encode_timestamps(ts)
        plan, why = codecs.split_for_device(blk, ValueType.INTEGER)
        assert plan is not None, why
        for k in {0, len(ts) - 1, len(ts) // 2, len(ts) // 3}:
            assert cd._time_value_at(plan, k) == ts[k]


# ---------------------------------------------------------------------------
# straddling time-bucket counts, arithmetic vs bincount oracle
# ---------------------------------------------------------------------------
def _bucket_oracle(ts, origin, interval):
    b = (ts - origin) // interval
    lo = b.min()
    return np.bincount((b - lo).astype(np.int64)), int(lo)


@pytest.mark.parametrize("origin,interval", [(0, 1000), (17, 333),
                                             (-5000, 7777)])
def test_bucket_counts_const_stride(origin, interval):
    ts = np.arange(10_000, 10_000 + 350 * 97, 97, dtype=np.int64)
    blk = codecs.encode_timestamps(ts)
    plan, _ = codecs.split_for_device(blk, ValueType.INTEGER)
    assert plan["kind"] == "delta_const"
    lane = cd.ScanLane(
        cd.CompressedSpec((), (origin, interval), {}, {}), None, None)
    tp = SimpleNamespace(min_ts=int(ts[0]), max_ts=int(ts[-1]))
    counts, blo = lane._bucket_counts(plan, tp)
    want, wlo = _bucket_oracle(ts, origin, interval)
    assert blo == wlo
    np.testing.assert_array_equal(counts, want)
    assert counts.sum() == len(ts)


def test_bucket_counts_jittered_delta():
    ts = np.sort(rng.integers(0, 10**7, 500)).astype(np.int64)
    blk = codecs.encode_timestamps(ts)
    plan, _ = codecs.split_for_device(blk, ValueType.INTEGER)
    if plan["kind"] != "delta":
        pytest.skip("rng produced constant stride")
    lane = cd.ScanLane(
        cd.CompressedSpec((), (3, 12345), {}, {}), None, None)
    tp = SimpleNamespace(min_ts=int(ts[0]), max_ts=int(ts[-1]))
    counts, blo = lane._bucket_counts(plan, tp)
    want, wlo = _bucket_oracle(ts, 3, 12345)
    assert blo == wlo
    np.testing.assert_array_equal(counts, want)


# ---------------------------------------------------------------------------
# interval tri-state soundness (the predicate classifier)
# ---------------------------------------------------------------------------
def _eval_pred(op, val, x):
    if np.isnan(x):
        return op == "!=" if not isinstance(val, tuple) else False
    if op == "between":
        return val[0] <= x <= val[1]
    if op == "in":
        return any(x == v for v in val)
    return {"=": x == val, "!=": x != val, "<": x < val,
            "<=": x <= val, ">": x > val, ">=": x >= val}[op]


def test_interval_verdict_sound_int():
    for _ in range(200):
        vals = rng.integers(-50, 50, rng.integers(1, 30))
        lo, hi = int(vals.min()), int(vals.max())
        op = rng.choice(["=", "!=", "<", "<=", ">", ">=", "between", "in"])
        if op == "between":
            a, b = sorted(rng.integers(-60, 60, 2).tolist())
            pred = (a, b)
        elif op == "in":
            pred = rng.integers(-60, 60, 3).tolist()
        else:
            pred = int(rng.integers(-60, 60))
        v = cd._interval_verdict(op, pred, lo, hi, is_float=False)
        results = [_eval_pred(op, pred, float(x)) for x in vals]
        if v == cd._TRUE:
            assert all(results), (op, pred, vals)
        elif v == cd._FALSE:
            assert not any(results), (op, pred, vals)


def test_interval_verdict_sound_float_with_nan():
    for _ in range(200):
        vals = np.round(rng.normal(size=rng.integers(1, 30)) * 10, 1)
        has_nan = rng.random() < 0.5
        dense = np.concatenate([vals, [np.nan]]) if has_nan else vals
        lo, hi = float(np.nanmin(dense)), float(np.nanmax(dense))
        op = rng.choice(["=", "!=", "<", "<=", ">", ">="])
        pred = float(np.round(rng.normal() * 10, 1))
        v = cd._interval_verdict(op, pred, lo, hi, is_float=True)
        results = [_eval_pred(op, pred, x) for x in dense]
        # float pages may hide NaN rows the stats exclude: TRUE/FALSE
        # must hold over the dense stream INCLUDING them
        if v == cd._TRUE:
            assert all(results), (op, pred, dense)
        elif v == cd._FALSE:
            assert not any(results), (op, pred, dense)


# ---------------------------------------------------------------------------
# code-space row masks (dictionary strings, bitpacked bools, NULL runs)
# ---------------------------------------------------------------------------
class _FakeReader:
    def __init__(self, block, nm):
        self._block, self._nm = block, nm

    def read_field_page_split(self, pm):
        return self._block, self._nm


def _mask_lane():
    return cd.ScanLane(cd.CompressedSpec((), None, {}, {}), None, None)


def _string_page(values):
    """Encode object strings (None = NULL) → (reader, pm, oracle rows)."""
    vals = np.array(values, dtype=object)
    nulls = np.array([v is None for v in vals])
    dense = vals[~nulls]
    blk = codecs.encode(dense, ValueType.STRING, Encoding.ZSTD)
    nm = nulls if nulls.any() else None
    pm = SimpleNamespace(n_rows=len(vals), n_values=len(dense),
                         value_type=int(ValueType.STRING))
    return _FakeReader(blk, nm), pm, vals, nulls


def test_string_mask_eq_ne_in_with_nulls():
    words = ["alpha", "beta", "gamma", None, "alpha", None, "delta",
             "beta", "beta", "Ωμέγα"]
    r, pm, vals, nulls = _string_page(words)
    for ops, oracle in [
        ((("str_eq", "beta"),), lambda v: v == "beta"),
        ((("str_ne", "alpha"),), lambda v: v != "alpha"),
        ((("str_in", ("alpha", "Ωμέγα", "nope")),),
         lambda v: v in ("alpha", "Ωμέγα")),
        ((("str_ne", "alpha"), ("str_ne", "beta")),
         lambda v: v not in ("alpha", "beta")),
    ]:
        m = _mask_lane()._page_row_mask(r, pm, ValueType.STRING, ops)
        want = np.array([(not nulls[i]) and oracle(vals[i])
                         for i in range(len(vals))])
        np.testing.assert_array_equal(m, want)


def test_string_mask_v1_page_rejects_with_reason():
    # legacy v1 payload (no dict marker) wrapped in the container codec
    from cnosdb_tpu.utils.zstd_compat import zstandard

    lens = np.array([1, 2], dtype=np.uint32)
    v1 = np.uint32(2).tobytes() + lens.tobytes() + b"abb"
    blk = bytes([int(Encoding.ZSTD)]) \
        + zstandard.ZstdCompressor().compress(v1)
    dec = codecs.decode(blk, ValueType.STRING)
    dec = dec.materialize() if hasattr(dec, "materialize") else dec
    assert list(dec) == ["a", "bb"]
    pm = SimpleNamespace(n_rows=2, n_values=2,
                         value_type=int(ValueType.STRING))
    before = cd.outcomes_snapshot().get(("mat", "string_v1"), 0)
    m = _mask_lane()._page_row_mask(_FakeReader(blk, None), pm,
                                    ValueType.STRING, (("str_eq", "a"),))
    assert m is None   # sound: no mask keeps every row
    assert cd.outcomes_snapshot().get(("mat", "string_v1"), 0) == before + 1


def test_bool_mask_bitpack_with_nulls():
    flags = [True, False, None, True, None, False, True, True]
    nulls = np.array([f is None for f in flags])
    dense = np.array([f for f in flags if f is not None], dtype=bool)
    blk = codecs.encode(dense, ValueType.BOOLEAN, Encoding.BITPACK)
    pm = SimpleNamespace(n_rows=len(flags), n_values=len(dense),
                         value_type=int(ValueType.BOOLEAN))
    r = _FakeReader(blk, nulls)
    m = _mask_lane()._page_row_mask(r, pm, ValueType.BOOLEAN,
                                    (("bool_eq", True),))
    want = np.array([f is True for f in flags])
    np.testing.assert_array_equal(m, want)
    m = _mask_lane()._page_row_mask(r, pm, ValueType.BOOLEAN,
                                    (("bool_ne", True),))
    want = np.array([f is False for f in flags])
    np.testing.assert_array_equal(m, want)


# ---------------------------------------------------------------------------
# SQL-level A/B: lane on vs CNOSDB_COMPRESSED_DOMAIN=0, bit-identical
# ---------------------------------------------------------------------------
@pytest.fixture
def db(tmp_path):
    from cnosdb_tpu.parallel.coordinator import Coordinator
    from cnosdb_tpu.parallel.meta import MetaStore
    from cnosdb_tpu.sql.executor import QueryExecutor
    from cnosdb_tpu.storage.engine import TsKv

    meta = MetaStore(str(tmp_path / "meta.json"))
    engine = TsKv(str(tmp_path / "data"))
    coord = Coordinator(meta, engine)
    ex = QueryExecutor(meta, coord)
    yield ex, engine
    engine.close()


BASE = 1_672_531_200_000_000_000
DAY = 86_400_000_000_000


@pytest.fixture
def sealed(db):
    """Two hosts, four field types, NULL runs, sealed into TSM."""
    ex, engine = db
    ex.execute_one("CREATE TABLE m (ival BIGINT, fval DOUBLE, "
                   "status STRING, ok BOOLEAN, TAGS(host))")
    r = np.random.default_rng(77)
    rows = []
    for i in range(600):
        t = BASE + i * (DAY // 48)
        host = f"h{i % 2}"
        ival = int(r.integers(-1000, 1000)) if i % 7 else "NULL"
        fval = round(float(r.normal()), 3) if i % 5 else "NULL"
        status = ("'rare'" if i % 149 == 0 else
                  "'common'" if i % 3 else "NULL")
        ok = ("true" if i % 2 else "false") if i % 11 else "NULL"
        rows.append(f"({t}, '{host}', {ival}, {fval}, {status}, {ok})")
    ex.execute_one("INSERT INTO m (time, host, ival, fval, status, ok) "
                   "VALUES " + ", ".join(rows))
    engine.flush_all(sync=True)
    return ex


QUERIES = [
    "SELECT count(*) FROM m",
    "SELECT count(ival), count(fval), count(status) FROM m",
    "SELECT sum(ival), min(ival), max(ival) FROM m",
    "SELECT first(ival), last(ival) FROM m",
    "SELECT first(fval), last(fval), first(ok) FROM m",
    "SELECT host, count(*), sum(ival) FROM m GROUP BY host",
    "SELECT time_bucket(time, '1d') AS b, count(*), count(ival) "
    "FROM m GROUP BY b ORDER BY b",
    "SELECT count(*), sum(ival) FROM m WHERE status = 'rare'",
    "SELECT count(*) FROM m WHERE status != 'common'",
    "SELECT count(*), max(ival) FROM m WHERE ok = true",
    "SELECT count(*) FROM m WHERE ival BETWEEN -100 AND 100",
    "SELECT count(*) FROM m WHERE ival > 2000",         # page-FALSE
    "SELECT sum(ival) FROM m WHERE fval < 100.0",
    "SELECT host, time_bucket(time, '1d') AS b, count(*) FROM m "
    "WHERE status IN ('rare', 'missing') GROUP BY host, b ORDER BY b",
]


def _norm(rows):
    return sorted(tuple(_bits(c) for c in row) for row in rows)


def test_sql_parity_vs_decode_lane(sealed, monkeypatch):
    # lane pass FIRST: an oracle pass would seed the coordinator's scan
    # cache with full batches under the unfiltered key, which a spec'd
    # probe legitimately falls back to — and then nothing engages.
    # Engaged batches cache under a spec-extended key, so the oracle
    # pass below re-scans fresh (cache isolation is part of the test).
    ex = sealed
    before = cd.outcomes_snapshot()
    got = [_norm(ex.execute_one(q).rows()) for q in QUERIES]
    after = cd.outcomes_snapshot()
    answered = sum(n - before.get(k, 0) for k, n in after.items()
                   if k[0] in ("meta", "closed", "skip"))
    assert answered > 0, "lane never engaged on the sealed table"
    monkeypatch.setenv("CNOSDB_COMPRESSED_DOMAIN", "0")
    oracle = [_norm(ex.execute_one(q).rows()) for q in QUERIES]
    for q, a, b in zip(QUERIES, oracle, got):
        assert a == b, q


def test_sql_parity_unflushed_memcache_unaffected(db, monkeypatch):
    """Rows still in the memcache never classify; results stay exact."""
    ex, _engine = db
    ex.execute_one("CREATE TABLE w (v BIGINT, TAGS(k))")
    ex.execute_one("INSERT INTO w (time, k, v) VALUES "
                   + ", ".join(f"({BASE + i}, 'a', {i})" for i in range(50)))
    q = "SELECT count(v), sum(v), first(v), last(v) FROM w"
    got = _norm(ex.execute_one(q).rows())
    monkeypatch.setenv("CNOSDB_COMPRESSED_DOMAIN", "0")
    assert _norm(ex.execute_one(q).rows()) == got


# ---------------------------------------------------------------------------
# time dominance: a first / last page another planned page outranks
# ---------------------------------------------------------------------------
STEP = 54_000_000_000            # 200 rows = 3 h, BASE is 3 h-aligned
N_ROWS, CHUNK = 300, 100         # three flushes: three chunks a host


def _t(i: int) -> int:
    return BASE + i * STEP


@pytest.fixture
def chunked(db):
    """Two hosts, three chunks each (rows 0-99, 100-199, 200-299, one
    page a chunk); `c` is NULL on rows 100-149. → (executor, the written
    values: host → column → list, None for NULL)."""
    ex, engine = db
    ex.execute_one("CREATE TABLE d (a BIGINT, b DOUBLE, c BIGINT, "
                   "TAGS(host))")
    r = np.random.default_rng(5)
    data = {h: {"a": [], "b": [], "c": []} for h in ("h0", "h1")}
    for lo in range(0, N_ROWS, CHUNK):
        rows = []
        for i in range(lo, lo + CHUNK):
            for h, cols in data.items():
                cols["a"].append(int(r.integers(-1000, 1000)))
                cols["b"].append(round(float(r.normal()), 3))
                cols["c"].append(None if 100 <= i < 150
                                 else int(r.integers(-99, 99)))
                rows.append(f"({_t(i)}, '{h}', {cols['a'][-1]}, "
                            f"{cols['b'][-1]}, "
                            f"{'NULL' if cols['c'][-1] is None else cols['c'][-1]})")
        ex.execute_one("INSERT INTO d (time, host, a, b, c) VALUES "
                       + ", ".join(rows))
        engine.flush_all(sync=True)
    return ex, data


def _reference(data, aggs, lo, hi, bucket_rows=None):
    """The plain answer: per host (and bucket of `bucket_rows` rows) each
    (func, column)'s oldest / newest non-NULL value of rows [lo, hi)."""
    out = []
    for h, cols in data.items():
        edges = range(lo, hi) if bucket_rows is None else \
            range(lo - lo % bucket_rows, hi, bucket_rows)
        for b0 in ([lo] if bucket_rows is None else edges):
            b1 = hi if bucket_rows is None else min(hi, b0 + bucket_rows)
            row = []
            for func, col in aggs:
                got = [cols[col][i] for i in range(max(lo, b0), b1)
                       if cols[col][i] is not None]
                row.append(got[-1 if func == "last" else 0] if got else None)
            out.append(((h,) if bucket_rows is None else (_t(b0), h))
                       + tuple(row))
    return _norm(out)


# (name, aggs, rows [lo, hi), bucketed?, extra conjunct, jobs dominated,
#  pages answered)
DOMINANCE = [
    # the bound cuts chunk 2, whose first row proves a and b: chunk 1's
    # a and b lose; c is NULL there, so chunk 1 still answers c
    ("last_null_in_cut_page", (("last", "a"), ("last", "b"),
                               ("last", "c")), (0, 150), False, None, 4, 2),
    # a lower bound cutting chunk 1: its last row outranks chunks 2, 3
    ("first_lower_bound", (("first", "a"), ("first", "b")),
     (50, N_ROWS), False, None, 8, 0),
    # chunk 3 (bucket 1) proves nothing for bucket 0: only chunk 1 loses,
    # to chunk 2 — which the same query unbucketed also prunes
    ("last_bucketed", (("last", "a"),), (0, 250), True, None, 2, 2),
    ("last_unbucketed", (("last", "a"),), (0, 250), False, None, 4, 0),
    # a conjunct: rows may fail it, so nothing is proven
    ("last_with_conjunct", (("last", "a"),), (0, 250), False,
     "a > -100000", 0, 4),
]


@pytest.mark.parametrize("case", DOMINANCE, ids=[c[0] for c in DOMINANCE])
def test_outranked_first_last_pages_leave_the_plan(chunked, monkeypatch,
                                                   case):
    from cnosdb_tpu.utils import stages

    ex, data = chunked
    _name, aggs, (lo, hi), bucketed, conjunct, dominated, answered = case
    where = [f"time >= {_t(lo)}" if lo else None,
             f"time < {_t(hi)}" if hi < N_ROWS else None, conjunct]
    sel = ", ".join(f"{f}({c})" for f, c in aggs)
    q = ("SELECT " + ("time_bucket(time, '3h') AS t, " if bucketed else "")
         + f"host, {sel} FROM d WHERE "
         + " AND ".join(w for w in where if w)
         + " GROUP BY " + ("t, host" if bucketed else "host"))
    prof = stages.QueryProfile()
    with stages.profile_scope(prof):
        got = _norm(ex.execute_one(q).rows())
    assert got == _reference(data, aggs, lo, hi, 200 if bucketed else None)
    assert prof.counts["compressed.jobs_dominated"] == dominated
    assert prof.counts.get("compressed.pages_answered", 0) == answered
    monkeypatch.setenv("CNOSDB_COMPRESSED_DOMAIN", "0")
    assert _norm(ex.execute_one(q).rows()) == got


def _series_vnode(tmp_path):
    """One series, val == row index at ts == 10 × row, three chunks of
    100 rows (ts 0-990, 1000-1990, 2000-2990)."""
    v = VnodeStorage(1, str(tmp_path / "vn"), schemas=_cold_schema())
    for lo in range(0, 300, 100):
        wb = WriteBatch()
        wb.add_series("cpu", SeriesRows(
            SeriesKey("cpu", {"host": "h1"}),
            [10 * i for i in range(lo, lo + 100)],
            {"val": (int(ValueType.INTEGER), list(range(lo, lo + 100)))}))
        v.write(wb)
        v.flush()
    return v


def _scan_last(v, trs, spec):
    """→ (the newest (ts, val) of the scan, partials and rows alike,
    compressed.jobs_dominated)."""
    from cnosdb_tpu.utils import stages

    prof = stages.QueryProfile()
    with stages.profile_scope(prof):
        b = scan_vnode(v, "cpu", time_ranges=trs, field_names=["val"],
                       compressed_spec=spec)
    cands = []
    cp = getattr(b, "compressed_partials", None)
    for parts in (cp["rows"].values() if cp else ()):
        if "l" in parts:
            cands.append((int(parts["l__ts"]), int(parts["l"])))
    if b.n_rows:
        _vt, vals, valid = b.fields["val"]
        k = np.flatnonzero(np.asarray(valid))[-1]
        cands.append((int(b.ts[k]), int(vals[k])))
    return max(cands), prof.counts.get("compressed.jobs_dominated")


@pytest.mark.parametrize("ranges,want,dominated", [
    # chunk 2 is admitted (it meets the second range) but both its
    # endpoints lie in gaps and no row of it survives: it proves nothing,
    # and chunk 1 answers
    (((0, 990), (1505, 1506)), (990, 99), 0),
    # chunk 2's t_max lies in the gap: it proves its t_min (1000), which
    # outranks chunk 1; chunk 3's endpoints both lie in gaps
    (((0, 1200), (2500, 2600)), (2600, 260), 1),
])
def test_disjoint_ranges_prove_by_an_endpoint_inside_only(
        tmp_path, ranges, want, dominated):
    from cnosdb_tpu.models.predicate import TimeRange, TimeRanges

    v = _series_vnode(tmp_path)
    trs = TimeRanges([TimeRange(a, b) for a, b in ranges])
    spec = cd.CompressedSpec((("last", "val", "l"),), None, {},
                             {"val": ValueType.INTEGER})
    assert _scan_last(v, trs, spec) == (want, dominated)
    # the decode lanes alone: the same newest row
    b = scan_vnode(v, "cpu", time_ranges=trs, field_names=["val"])
    assert (int(b.ts[-1]), int(b.fields["val"][1][-1])) == want


# ---------------------------------------------------------------------------
# cold tier: answered pages are never downloaded
# ---------------------------------------------------------------------------
def _cold_schema():
    return {"cpu": TskvTableSchema.new_measurement(
        "t", "db", "cpu", tags=["host"],
        fields=[("val", ValueType.INTEGER)])}


def _cold_vnode(tmp_path, monkeypatch):
    """1500 rows, val == row index, split into 100-row pages (small
    max_page_rows so page-level verdicts are visible), tiered cold."""
    from cnosdb_tpu.storage import tsm

    orig = tsm.TsmWriter.__init__

    def small_pages(self, path, max_page_rows=100):
        orig(self, path, max_page_rows=100)

    monkeypatch.setattr(tsm.TsmWriter, "__init__", small_pages)
    v = VnodeStorage(1, str(tmp_path / "vn"), schemas=_cold_schema())
    for i in range(5):
        lo = i * 300
        wb = WriteBatch()
        wb.add_series("cpu", SeriesRows(
            SeriesKey("cpu", {"host": "h1"}),
            list(range(lo, lo + 300)),
            {"val": (int(ValueType.INTEGER),
                     [int(x) for x in range(lo, lo + 300)])}))
        v.write(wb)
        v.flush()
    v.compact_full()
    n = tiering.tier_vnode(v, boundary_ns=10**18)
    assert n >= 1
    return v


def _downloaded():
    return tiering.cold_tier_snapshot().get(("fetch", "bytes_downloaded"),
                                            0)


def test_cold_scan_parity_fewer_bytes(tmp_path, monkeypatch):
    """Selective predicate over cold pages: provably-false pages are
    never downloaded, provably-true pages answer from metadata, only the
    straddling page materializes — strictly fewer fetched bytes with the
    result bit-identical to the full-scan oracle."""
    store = tmp_path / "bucket"
    store.mkdir()
    tiering.configure(str(store))
    try:
        v = _cold_vnode(tmp_path, monkeypatch)
        spec = cd.CompressedSpec(
            (("count", None, "c"),), None,
            {"val": [(">", 1200)]}, {"val": ValueType.INTEGER})

        tiering.block_cache_clear()
        tiering.counters_reset()
        b0 = scan_vnode(v, "cpu", field_names=["val"])
        oracle_bytes = _downloaded()
        assert oracle_bytes > 0
        vals, valid = b0.fields["val"][1], b0.fields["val"][2]
        dense = np.asarray(vals)[np.asarray(valid)]
        oracle_count = int((dense > 1200).sum())
        assert oracle_count == 299

        tiering.block_cache_clear()
        tiering.counters_reset()
        b1 = scan_vnode(v, "cpu", field_names=["val"],
                        compressed_spec=spec)
        lane_bytes = _downloaded()
        cp = getattr(b1, "compressed_partials", None)
        assert cp, "lane did not answer any cold page"
        got = sum(int(p.get("c", 0)) for p in cp["rows"].values())
        # the straddling [1200, 1299] page materialized: count its
        # surviving rows the way the executor's re-applied filter would
        v1, m1 = b1.fields["val"][1], b1.fields["val"][2]
        got += int((np.asarray(v1)[np.asarray(m1)] > 1200).sum())
        assert got == oracle_count
        # skipped pages were never fetched; answered pages counted from
        # metadata alone — strictly fewer object-store bytes
        assert 0 < lane_bytes < oracle_bytes, (lane_bytes, oracle_bytes)
    finally:
        tiering.configure(None)
        tiering.counters_reset()
        tiering.block_cache_clear()


def test_cold_scan_stats_only_downloads_nothing(tmp_path, monkeypatch):
    """count/sum/min/max over every page need no page bytes: zero GETs."""
    store = tmp_path / "bucket2"
    store.mkdir()
    tiering.configure(str(store))
    try:
        v = _cold_vnode(tmp_path, monkeypatch)
        spec = cd.CompressedSpec(
            (("count", None, "c"), ("sum", "val", "s"),
             ("min", "val", "lo"), ("max", "val", "hi")),
            None, {}, {"val": ValueType.INTEGER})
        tiering.block_cache_clear()
        tiering.counters_reset()
        b = scan_vnode(v, "cpu", field_names=["val"],
                       compressed_spec=spec)
        cp = getattr(b, "compressed_partials", None)
        assert cp
        assert b.n_rows == 0
        parts: dict = {}
        for p in cp["rows"].values():
            for func, col, alias in spec.aggs:
                if alias in p:
                    cd._fold_partial(parts, func, alias, p[alias])
        assert int(parts["c"]) == 1500
        assert int(parts["s"]) == sum(range(1500))
        assert int(parts["lo"]) == 0 and int(parts["hi"]) == 1499
        assert _downloaded() == 0
    finally:
        tiering.configure(None)
        tiering.counters_reset()
        tiering.block_cache_clear()
