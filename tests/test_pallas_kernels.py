"""Pallas segment-aggregate kernel vs the numpy oracle.

Drives ops/pallas_kernels.segment_partials_pallas in interpreter mode on
the CPU backend (pallas_call(interpret=True)) against
kernels.numpy_segment_partials — NULL columns, empty segments,
window-boundary layouts, the applicable() fallback, and the
aggregate_column_host integration behind CNOSDB_TPU_PALLAS=1.
"""
import numpy as np

from cnosdb_tpu.ops import kernels, pallas_kernels as pk

ALL4 = {"want_count": True, "want_sum": True,
        "want_min": True, "want_max": True}


def _series_layout(rng, n_series, rows_per_series, n_buckets,
                   dtype=np.float64, null_frac=0.0):
    """Storage-shaped batch: series-contiguous rows, time-ordered buckets
    per series, seg = group(series) × n_buckets + bucket."""
    groups = rng.permutation(n_series).astype(np.int64)
    segs, vals, valid = [], [], []
    for s in range(n_series):
        m = rows_per_series
        buckets = np.sort(rng.integers(0, n_buckets, m))
        segs.append(groups[s] * n_buckets + buckets)
        if np.issubdtype(dtype, np.floating):
            vals.append(rng.normal(size=m).astype(dtype))
        else:
            vals.append(rng.integers(-1000, 1000, m).astype(dtype))
        valid.append(rng.random(m) >= null_frac)
    seg_ids = np.concatenate(segs).astype(np.int32)
    return (np.concatenate(vals), np.concatenate(valid), seg_ids,
            n_series * n_buckets)


def _check(values, valid, seg_ids, ns, wants=None):
    w = dict(ALL4 if wants is None else wants)
    got = pk.segment_partials_pallas(values, valid, seg_ids, ns,
                                     wants=w, interpret=True)
    assert got is not None, "layout unexpectedly disqualified"
    rank = np.arange(len(values), dtype=np.int32)
    exp = kernels.numpy_segment_partials(
        values, valid, seg_ids.astype(np.int64), rank, ns, w)
    counts = np.bincount(seg_ids[valid], minlength=ns)
    for k in exp:
        if k in ("min", "max"):
            # empty segments carry sentinels in both kernels by contract;
            # compare occupied segments only (callers mask by count)
            occ = counts > 0
            np.testing.assert_allclose(got[k][occ], exp[k][occ], rtol=1e-12,
                                       err_msg=k)
        else:
            np.testing.assert_allclose(got[k], exp[k], rtol=1e-12,
                                       err_msg=k)
    assert set(got) == set(exp)
    return got


def test_basic_float_matches_oracle():
    rng = np.random.default_rng(0)
    values, valid, seg_ids, ns = _series_layout(rng, 6, 700, 24)
    _check(values, valid, seg_ids, ns)


def test_nulls_and_empty_segments():
    rng = np.random.default_rng(1)
    # 40% NULLs; bucket space much larger than occupied → empty segments
    values, valid, seg_ids, ns = _series_layout(
        rng, 4, 300, 100, null_frac=0.4)
    got = _check(values, valid, seg_ids, ns)
    counts = np.bincount(seg_ids[valid], minlength=ns)
    # empty segments: count 0, sum 0, min/max sentinels (XLA convention)
    empty = counts == 0
    assert empty.any()
    assert (got["count"][empty] == 0).all()
    assert (got["sum"][empty] == 0).all()
    assert np.isposinf(got["min"][empty]).all()
    assert np.isneginf(got["max"][empty]).all()


def test_all_rows_invalid():
    n = 512
    values = np.ones(n)
    valid = np.zeros(n, dtype=bool)
    seg_ids = np.zeros(n, dtype=np.int32)
    got = pk.segment_partials_pallas(values, valid, seg_ids, 8,
                                     wants=dict(ALL4), interpret=True)
    assert got is not None
    assert (got["count"] == 0).all() and (got["sum"] == 0).all()


def test_integer_dtype_extrema():
    """Integer min/max identities must be iinfo extrema, not float inf."""
    rng = np.random.default_rng(2)
    values, valid, seg_ids, ns = _series_layout(
        rng, 3, 400, 16, dtype=np.int64, null_frac=0.2)
    got = _check(values, valid, seg_ids, ns)
    counts = np.bincount(seg_ids[valid], minlength=ns)
    empty = counts == 0
    if empty.any():
        assert (got["min"][empty] == np.iinfo(np.int64).max).all()
        assert (got["max"][empty] == np.iinfo(np.int64).min).all()


def test_window_boundary_series():
    """Series boundaries inside a tile: the window absorbs the group jump
    as long as the span stays under W_WIN."""
    # two series meeting mid-tile, group ids adjacent → span = n_buckets
    n_buckets = pk.W_WIN // 2
    a = np.arange(n_buckets, dtype=np.int32)                 # group 0
    b = n_buckets + np.arange(n_buckets, dtype=np.int32)     # group 1
    seg_ids = np.concatenate([a, b])
    values = np.linspace(-1, 1, len(seg_ids))
    valid = np.ones(len(seg_ids), dtype=bool)
    _check(values, valid, seg_ids, 2 * n_buckets)


def test_applicable_declines_wide_span():
    """A tile spanning ≥ W_WIN segments disqualifies the layout."""
    seg_ids = np.array([0, pk.W_WIN + 7] * (pk.R_TILE // 2), dtype=np.int32)
    assert pk.applicable(seg_ids) is None
    got = pk.segment_partials_pallas(
        np.ones(len(seg_ids)), np.ones(len(seg_ids), bool), seg_ids,
        pk.W_WIN + 8, wants=dict(ALL4), interpret=True)
    assert got is None


def test_declines_first_last():
    seg_ids = np.zeros(16, dtype=np.int32)
    got = pk.segment_partials_pallas(
        np.ones(16), np.ones(16, bool), seg_ids, 1,
        wants={**ALL4, "want_first": True}, interpret=True)
    assert got is None


def test_wants_subsetting():
    rng = np.random.default_rng(3)
    values, valid, seg_ids, ns = _series_layout(rng, 2, 300, 8)
    got = pk.segment_partials_pallas(
        values, valid, seg_ids, ns,
        wants={"want_count": True, "want_sum": False,
               "want_min": False, "want_max": True}, interpret=True)
    assert set(got) == {"count", "max"}


def test_aggregate_column_host_integration(monkeypatch):
    """CNOSDB_TPU_PALLAS=1 routes aggregate_column_host through the
    pallas kernel (interpret on the CPU backend) with identical results;
    =0 keeps the XLA kernel. A deliberately broken pallas result would
    fail the comparison."""
    rng = np.random.default_rng(4)
    values, valid, seg_ids, ns = _series_layout(
        rng, 5, 500, 20, null_frac=0.15)
    rank = np.arange(len(values), dtype=np.int32)
    wants = {"want_count": True, "want_sum": True, "want_min": True,
             "want_max": True, "want_first": False, "want_last": False}
    monkeypatch.setenv("CNOSDB_TPU_PALLAS", "0")
    base = kernels.aggregate_column_host(
        values, valid, seg_ids.astype(np.int32), rank, ns, wants)
    monkeypatch.setenv("CNOSDB_TPU_PALLAS", "1")
    before = pk.engagements()
    got = kernels.aggregate_column_host(
        values, valid, seg_ids.astype(np.int32), rank, ns, wants)
    assert pk.engagements() == before + 1, "pallas path did not engage"
    counts = np.bincount(seg_ids[valid], minlength=ns)
    occ = counts > 0
    for k in base:
        if k in ("min", "max"):
            np.testing.assert_allclose(got[k][occ], base[k][occ],
                                       err_msg=k)
        else:
            np.testing.assert_allclose(got[k], base[k], err_msg=k)
    assert got["count"].dtype == np.int64


def test_first_last_falls_back_to_xla(monkeypatch):
    """first/last keep the XLA rank-selection kernel even when pallas is
    forced on."""
    monkeypatch.setenv("CNOSDB_TPU_PALLAS", "1")
    n = 300
    values = np.arange(n, dtype=np.float64)
    valid = np.ones(n, dtype=bool)
    seg_ids = (np.arange(n, dtype=np.int32) // 100)
    rank = np.arange(n, dtype=np.int32)
    before = pk.engagements()
    out = kernels.aggregate_column_host(
        values, valid, seg_ids, rank, 3,
        {"want_count": True, "want_sum": False, "want_min": False,
         "want_max": False, "want_first": True, "want_last": True})
    assert pk.engagements() == before
    np.testing.assert_allclose(out["first"], [0.0, 100.0, 200.0])
    np.testing.assert_allclose(out["last"], [99.0, 199.0, 299.0])
