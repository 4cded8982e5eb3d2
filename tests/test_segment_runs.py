"""The run path of the device segment reduction (ops/kernels.
local_segment_partials with run_pad > 0): contiguous equal-segment runs
are reduced by prefix sums and run-restarting scans, and only the run
partials are scattered. Every case here runs the DEVICE body (the jitted
program, on the CPU backend) and holds it, bit for bit, to the row scatter
(run_pad = 0) and to the numpy twin — and checks the program's own word on
which branch it took.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from cnosdb_tpu.ops import fused, kernels
from cnosdb_tpu.sql.expr import BinOp, Column, Literal
from cnosdb_tpu.utils import stages

ALL_FOUR = dict(want_count=True, want_sum=True, want_min=True, want_max=True)
# aggregate_column_host hands segment_aggregate every flag
HOST_WANTS = dict(ALL_FOUR, want_first=False, want_last=False)


def _series_major(rng, n_series, rows_per, n_buckets, n_groups, n_pad, dtype,
                  null_share=0.1):
    """A scan batch's shape: series-major rows, time ascending inside a
    series, segment = group_of_series[sid] · n_buckets + bucket, the tail
    zero-padded to n_pad."""
    n = n_series * rows_per
    sid = np.repeat(np.arange(n_series), rows_per)
    bucket = (np.tile(np.arange(rows_per), n_series) * n_buckets) // rows_per
    group_of_series = rng.permutation(n_series) % n_groups
    seg = (group_of_series[sid] * n_buckets + bucket).astype(np.int32)
    if np.dtype(dtype) == np.int64:
        # ±2^62: a handful of rows already wrap the i64 prefix sum
        vals = rng.integers(-2**62, 2**62, n, dtype=np.int64)
    else:
        vals = rng.integers(-2**30, 2**30, n).astype(dtype)
    valid = rng.random(n) >= null_share
    pad = n_pad - n
    assert pad >= 0
    return (np.concatenate([vals, np.zeros(pad, vals.dtype)]),
            np.concatenate([valid, np.zeros(pad, bool)]),
            np.concatenate([seg, np.zeros(pad, np.int32)]))


def _both_ways(vals, valid, seg, num_segments, run_pad, wants=ALL_FOUR):
    rank = np.zeros(len(vals), np.int32)
    rows = kernels.segment_aggregate(vals, valid, seg, rank,
                                     num_segments=num_segments, **wants)
    runs = dict(kernels.segment_aggregate(vals, valid, seg, rank,
                                          num_segments=num_segments,
                                          run_pad=run_pad, **wants))
    by_runs = bool(runs.pop("by_runs"))
    assert "by_runs" not in rows
    assert sorted(rows) == sorted(runs)
    for k in rows:
        a, b = np.asarray(rows[k]), np.asarray(runs[k])
        assert a.dtype == b.dtype and np.array_equal(a, b), k
    ref = kernels.numpy_segment_partials(vals, valid, seg, rank,
                                         num_segments, wants)
    for k in rows:
        got = np.asarray(runs[k])
        if k in ("min", "max"):
            # an empty segment holds the type's extremum in both; the numpy
            # twin's is the same constant
            assert np.array_equal(got, ref[k]), k
        else:
            assert np.array_equal(got.astype(np.int64),
                                  ref[k].astype(np.int64)), k
    return by_runs, runs


# name → (n_series, rows_per, n_buckets, n_groups, n_pad, segments, run_pad,
#         dtype, null_share)
SORTED_CASES = {
    "i64_prefix_sum_wraps": (40, 300, 5, 40, 1 << 14, 256, 256, np.int64, 0.1),
    "i32_values": (40, 300, 5, 40, 1 << 14, 256, 256, np.int32, 0.1),
    "several_series_a_group": (60, 200, 4, 7, 1 << 14, 64, 256, np.int64, 0.1),
    "no_nulls": (16, 500, 8, 16, 1 << 13, 128, 256, np.int64, 0.0),
    "zero_padded_tail": (10, 333, 3, 10, 1 << 12, 64, 64, np.int64, 0.2),
    "rows_fill_the_size_class": (16, 256, 4, 16, 1 << 12, 64, 128, np.int64,
                                 0.1),
    "one_series_one_bucket": (1, 1000, 1, 1, 1 << 10, 64, 64, np.int64, 0.5),
    "bound_met_exactly": (8, 128, 8, 8, 1 << 10, 64, 64, np.int64, 0.1),
}


@pytest.mark.parametrize("case", list(SORTED_CASES))
def test_run_path_equals_row_scatter_and_numpy(rng, case):
    (n_series, rows_per, n_buckets, n_groups, n_pad, segments, run_pad,
     dtype, null_share) = SORTED_CASES[case]
    vals, valid, seg = _series_major(rng, n_series, rows_per, n_buckets,
                                     n_groups, n_pad, dtype, null_share)
    runs = int(np.count_nonzero(seg[1:] != seg[:-1])) + 1
    assert runs <= run_pad, "the case is meant to engage"
    by_runs, _ = _both_ways(vals, valid, seg, segments, run_pad)
    assert by_runs


def test_all_null_runs_leave_the_identity(rng):
    vals, valid, seg = _series_major(rng, 20, 100, 4, 20, 1 << 11, np.int64)
    # every row of every third run is null: count 0, sum 0, min/max extrema
    starts = np.flatnonzero(np.r_[True, seg[1:] != seg[:-1]])
    run_of_row = np.cumsum(np.r_[True, seg[1:] != seg[:-1]]) - 1
    valid &= (run_of_row % 3) != 0
    assert len(starts) <= 128
    by_runs, out = _both_ways(vals, valid, seg, 128, 128)
    assert by_runs
    dead = np.asarray(out["count"]) == 0
    assert dead.any()
    assert (np.asarray(out["sum"])[dead] == 0).all()
    assert (np.asarray(out["max"])[dead] == np.iinfo(np.int64).min).all()


def test_one_row_per_run(rng):
    n = 1 << 10
    seg = (np.arange(n) % 64).astype(np.int32)      # every row starts a run
    vals = rng.integers(-2**62, 2**62, n, dtype=np.int64)
    valid = rng.random(n) > 0.3
    by_runs, _ = _both_ways(vals, valid, seg, 64, n)
    assert by_runs


def test_unsorted_rows_fall_back_inside_the_program(rng):
    """Rows not run-contiguous (a merged or out-of-order batch): the
    program counts more runs than its bound, takes the row scatter, says
    so — and the answer does not move."""
    vals, valid, seg = _series_major(rng, 30, 200, 4, 30, 1 << 13, np.int64)
    perm = rng.permutation(30 * 200)
    for a in (vals, valid, seg):
        a[:len(perm)] = a[perm]
    assert np.count_nonzero(seg[1:] != seg[:-1]) > 256
    by_runs, _ = _both_ways(vals, valid, seg, 128, 256)
    assert not by_runs


def test_bound_set_too_low_falls_back(rng):
    vals, valid, seg = _series_major(rng, 40, 300, 5, 40, 1 << 14, np.int64)
    # 200 runs + the tail under a bound of 64
    by_runs, _ = _both_ways(vals, valid, seg, 256, 64)
    assert not by_runs


def test_fleet_shape(rng):
    """devops-fleet-groupby: 1 000 hosts × 1 800 rows × 5 hourly buckets,
    padded to 2^21 rows, 8 192 segments, a bound of 8 192 runs."""
    vals, valid, seg = _series_major(rng, 1000, 1800, 5, 1000, 1 << 21,
                                     np.int64)
    assert kernels.run_pad_for(1 << 21, 1000 * 5 + 1) == 8192
    by_runs, out = _both_ways(vals, valid, seg, 8192, 8192,
                              wants=dict(want_count=True, want_sum=True,
                                         want_min=False, want_max=True))
    assert by_runs
    assert int(np.asarray(out["count"]).sum()) == int(valid.sum())


@pytest.mark.parametrize("dtype", [np.float64, np.float32])
def test_a_float_sum_keeps_the_row_scatter(rng, monkeypatch, dtype):
    """A floating sum's association is the row scatter's (pinned by the
    parity tests); only its count goes by runs. Proved at trace time: the
    run reduction is never asked for a float's sum, min or max."""
    asked = []
    real = kernels._reduce_runs

    def spy(inputs, runs, num_segments):
        asked.append(tuple(inputs))
        return real(inputs, runs, num_segments)

    monkeypatch.setattr(kernels, "_reduce_runs", spy)

    @jax.jit        # a function of this test's own: nothing cached to reuse
    def fn(vals, valid, seg, rank):
        return kernels.local_segment_partials(
            vals, valid, seg, rank, num_segments=64, run_pad=64, **ALL_FOUR)

    n = 1 << 11
    _, valid, seg = _series_major(rng, 8, 200, 4, 8, n, np.int64)
    vals = (rng.standard_normal(n) * 1e6).astype(dtype)
    rank = np.zeros(n, np.int32)
    out = fn(vals, valid, seg, rank)
    assert asked == [("count",)]
    assert bool(out["by_runs"])
    rows = kernels.segment_aggregate(vals, valid, seg, rank, num_segments=64,
                                     **ALL_FOUR)
    for k in rows:
        assert np.array_equal(np.asarray(rows[k]), np.asarray(out[k]),
                              equal_nan=True), k
    asked.clear()
    fn(vals.astype(np.int64), valid, seg, rank)
    assert asked == [("count", "sum", "min", "max")]


@pytest.mark.parametrize("n_pad,max_runs,expect", [
    (1 << 21, 1000 * 5 + 1, 8192),      # fleet: 256 rows a run
    (1 << 19, 250 * 6 + 1, 2048),       # mesh4, a device
    (1 << 16, 1 << 13, 8192),           # 8 rows a run in the shortest batch
    (1 << 16, (1 << 13) + 1, 0),        # 4 rows a run: the row scatter
    (1 << 15, 64, 0),                   # a short batch
    (1 << 14, 8 * 6 + 2, 0),            # a panel: cpu-max-all-8
    (1 << 11, 61, 0),                   # a panel: single-groupby-1-1-1
    (1 << 20, 1 << 20, 0),              # a run a row
])
def test_run_pad_is_a_size_class_or_nothing(n_pad, max_runs, expect):
    assert kernels.run_pad_for(n_pad, max_runs) == expect


# ------------------------------------------------------------ fused program
def _fused_matrix(run_pad, flt, col_wants, args, n_pad, valid_flags):
    present = tuple(sorted(col_wants))
    fn, manifest = fused._build_kernel(
        flt, col_wants, present, 64, True, 60, False, valid_flags, False,
        False, n_pad, run_pad)
    return np.asarray(fn(*args)), manifest


@pytest.mark.parametrize("case", ["filter_cuts_runs", "no_filter",
                                  "nullable_column", "unsorted_series"])
def test_fused_program_by_runs_equals_by_rows(rng, case):
    """The whole fused program, run path against row scatter: a value
    filter that masks rows in the middle of runs must not cut them (the
    run structure is the unmasked ids'), and the packed matrix's last row
    carries the program's word."""
    n_series, rows_per, n_pad = 12, 80, 1 << 10
    n = n_series * rows_per
    sid = np.zeros(n_pad, np.int32)
    sid[:n] = np.repeat(np.arange(n_series), rows_per)
    ts_sec = np.zeros(n_pad, np.int32)
    ts_sec[:n] = np.tile(np.arange(rows_per) * 3, n_series)   # 4 minutes
    if case == "unsorted_series":
        order = rng.permutation(n)
        sid[:n], ts_sec[:n] = sid[:n][order], ts_sec[:n][order]
    vals = np.zeros(n_pad, np.int64)
    vals[:n] = rng.integers(-2**40, 2**40, n)
    valid = np.zeros(n_pad, bool)
    valid[:n] = rng.random(n) > 0.2
    nullable = case == "nullable_column"
    flt = BinOp(">", Column("v"), Literal(0)) \
        if case == "filter_cuts_runs" else None
    col_wants = {"v": {"want_sum": True, "want_min": True, "want_max": True}}
    params = np.zeros(fused._SCALARS + n_series, np.int32)
    params[3:5] = n, 4                            # rows, buckets
    params[fused._SCALARS:] = rng.permutation(n_series)   # group_of_series
    args = [ts_sec, sid, params, vals] + ([valid] if nullable else [])
    run_pad = 64                 # n_series * 4 buckets + the tail = 49
    by_rows, manifest0 = _fused_matrix(0, flt, col_wants, args, n_pad,
                                       (nullable,))
    by_runs, manifest = _fused_matrix(run_pad, flt, col_wants, args, n_pad,
                                      (nullable,))
    assert manifest[:-1] == manifest0 and manifest[-1][0] == "__runs__"
    assert np.array_equal(by_rows, by_runs[:-1])
    assert by_runs[0].sum() > 0                     # presence: rows counted
    engaged = by_runs[-1, 0] != 0
    assert engaged == (case != "unsorted_series")
    if flt is not None:
        # the filter left fewer rows than the batch holds, inside runs
        assert 0 < by_runs[0].sum() < n


def test_fetch_books_the_program_s_word(rng):
    """PendingFused.fetch pops the flag row and books the launch under the
    key of its path — and 0 under the other, so a request that never fell
    back reads `segment_runs.fallback` 0 and not nothing."""
    mat = np.zeros((3, 64))
    mat[0, :5] = 7
    for word, key, other in (
            (1.0, "segment_runs.engaged", "segment_runs.fallback"),
            (0.0, "segment_runs.fallback", "segment_runs.engaged")):
        mat[2] = word
        pending = fused.PendingFused(
            mat, [("__presence__", "count"), ("v", "sum"),
                  ("__runs__", "engaged")], 5, {"v"}, ("v",))
        prof = stages.QueryProfile()
        with stages.profile_scope(prof):
            out = pending.fetch()
        assert sorted(out) == ["__presence__", "v"]
        assert prof.counts == {key: 1, other: 0}, prof.counts


def test_host_wrapper_takes_the_callers_bound(rng):
    """aggregate_column_host turns the caller's bound (series × buckets,
    from the plan — it never counts runs on the rows itself) into the size
    class, books the program's word, and keeps the row scatter where no
    bound comes or the bound leaves too few rows a run."""
    vals, valid, seg = _series_major(rng, 8, 5000, 8, 8, 40000, np.int64)
    rank = np.zeros(40000, np.int32)
    wants = HOST_WANTS
    ref = kernels.numpy_segment_partials(vals, valid, seg, rank, 64, wants)
    for max_runs, booked in ((8 * 8, "segment_runs.engaged"),
                             (None, None), (20000, None),
                             (4, "segment_runs.fallback")):
        prof = stages.QueryProfile()
        with stages.profile_scope(prof):
            got = kernels.aggregate_column_host(vals, valid, seg, rank, 64,
                                                wants, max_runs=max_runs)
        runs = {k: v for k, v in prof.counts.items()
                if k.startswith("segment_runs") and v}
        assert runs == ({booked: 1} if booked else {}), (max_runs, runs)
        # a launch with the run path compiled in books both keys
        assert sum(k.startswith("segment_runs") for k in prof.counts) \
            == (2 if booked else 0), prof.counts
        for k in ref:
            assert np.array_equal(got[k], ref[k]), (max_runs, k)


# ------------------------------------------- the host wrapper's edge inputs
I64 = np.iinfo(np.int64)


def _basic(rng):
    # a FLOAT column: only its count goes by runs
    vals, valid, seg = _series_major(rng, 6, 7000, 24, 6, 42000, np.float64)
    return vals, valid, seg, 6 * 24, HOST_WANTS


def _nulls_and_empty_segments(rng):
    vals, valid, seg = _series_major(rng, 4, 9000, 100, 4, 36000, np.int64,
                                     null_share=0.4)
    valid &= (seg % 7) != 3          # whole segments null; 100 ids unused
    return vals, valid, seg, 500, HOST_WANTS


def _all_rows_invalid(rng):
    n = 40000
    return (np.ones(n, np.int64), np.zeros(n, bool), np.zeros(n, np.int32),
            8, HOST_WANTS)


def _integer_extrema(rng):
    _, valid, seg = _series_major(rng, 3, 12000, 16, 3, 36000, np.int64,
                                  null_share=0.2)
    vals = rng.choice(np.array([I64.min, I64.max, -1, 0, 1], np.int64), 36000)
    return vals, valid, seg, 64, HOST_WANTS


def _run_crossing_a_row_block(rng):
    # two series meet off any power of two; the second is ONE run of
    # segment 0 that crosses row 2^15 and ends where the zero-padded tail
    # (segment 0 too) begins
    a = (16 + (np.arange(20000) * 16) // 20000).astype(np.int32)
    seg = np.concatenate([a, np.zeros(20000, np.int32)])
    vals = rng.integers(-2**62, 2**62, 40000, dtype=np.int64)
    return vals, rng.random(40000) > 0.1, seg, 32, HOST_WANTS


def _wants_subsetting(rng):
    vals, valid, seg = _series_major(rng, 2, 20000, 8, 2, 40000, np.int64)
    return vals, valid, seg, 16, dict(HOST_WANTS, want_sum=False, want_min=False)


def _first_last(rng):
    vals, valid, seg = _series_major(rng, 5, 8000, 6, 5, 40000, np.int64)
    return vals, valid, seg, 30, dict(HOST_WANTS, want_sum=False, want_min=False,
                                      want_max=False, want_first=True,
                                      want_last=True)


HOST_CASES = {f.__name__[1:]: f for f in (
    _basic, _nulls_and_empty_segments, _all_rows_invalid, _integer_extrema,
    _run_crossing_a_row_block, _wants_subsetting, _first_last)}


@pytest.mark.parametrize("path", ["row_scatter", "by_runs"])
@pytest.mark.parametrize("case", list(HOST_CASES))
def test_host_wrapper_matches_the_numpy_oracle(rng, case, path):
    """kernels.aggregate_column_host — pad, launch, pull, slice — against
    the numpy twin on the inputs a scan can hand it, with no bound (one
    scatter update a row) and with the rows' true run count as the bound
    (every case is long enough to engage the run path)."""
    vals, valid, seg, num_segments, wants = HOST_CASES[case](rng)
    n = len(vals)
    rank = rng.permutation(n).astype(np.int32)
    runs = int(np.count_nonzero(seg[1:] != seg[:-1])) + 1
    assert kernels.pad_rows(n) >= kernels.RUN_PATH_MIN_ROWS
    ref = kernels.numpy_segment_partials(vals, valid, seg, rank,
                                         num_segments, wants)
    prof = stages.QueryProfile()
    with stages.profile_scope(prof):
        got = kernels.aggregate_column_host(
            vals, valid, seg, rank, num_segments, wants,
            max_runs=runs if path == "by_runs" else None)
    booked = {k: v for k, v in prof.counts.items()
              if k.startswith("segment_runs")}
    assert booked == ({"segment_runs.engaged": 1,
                       "segment_runs.fallback": 0} if path == "by_runs"
                      else {})
    assert sorted(got) == sorted(ref)
    for k in ref:
        assert got[k].shape == (num_segments,), k
        if k == "sum" and vals.dtype.kind == "f":
            np.testing.assert_allclose(got[k], ref[k], rtol=1e-12)
        else:
            assert got[k].dtype == ref[k].dtype, k
            assert np.array_equal(got[k], ref[k]), k
    if case in ("nulls_and_empty_segments", "all_rows_invalid"):
        # the convention callers mask by: an empty segment counts 0, sums
        # 0 and holds the type's extrema
        empty = got["count"] == 0
        assert empty.any()
        assert (got["sum"][empty] == 0).all()
        assert (got["min"][empty] == I64.max).all()
        assert (got["max"][empty] == I64.min).all()


# ------------------------------------------------------- the mesh lane's body
@pytest.mark.parametrize("case", ["sorted", "one_shard_unsorted"])
def test_mesh_merge_by_runs_equals_by_rows(rng, case):
    """mesh_merge on four virtual devices, two slots a device: every
    device checks its own shard; one unsorted shard makes the output's
    word False and moves no answer."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from cnosdb_tpu.parallel.distributed_agg import mesh_merge_kernel
    from cnosdb_tpu.parallel.mesh import SHARD_AXIS

    n_dev, slots, seg_pad, row_pad = 4, 2, 64, 1 << 11
    mesh = Mesh(np.array(jax.devices()[:n_dev]), (SHARD_AXIS,))
    vals = np.zeros(n_dev * row_pad, np.int64)
    valid = np.zeros(n_dev * row_pad, bool)
    seg = np.zeros(n_dev * row_pad, np.int32)
    for d in range(n_dev):
        at = d * row_pad
        for slot in range(slots):
            v, ok, s = _series_major(rng, 6, 150, 4, 6, 900, np.int64)
            vals[at:at + 900], valid[at:at + 900] = v, ok
            seg[at:at + 900] = slot * seg_pad + s
            at += 900
    if case == "one_shard_unsorted":
        perm = rng.permutation(1800)
        lo = 2 * row_pad
        for a in (vals, valid, seg):
            a[lo:lo + 1800] = a[lo:lo + 1800][perm]
    sh = NamedSharding(mesh, P(SHARD_AXIS))
    put = lambda a: jax.device_put(a, sh)   # noqa: E731
    dummy = put(np.zeros(n_dev, np.int32))
    args = (put(vals), put(valid), put(seg),
            put(np.zeros(n_dev * row_pad, np.int32)), dummy, dummy)
    kw = dict(mesh=mesh, slots=slots, num_segments=seg_pad,
              wants=("count", "max", "min", "sum"))
    rows = mesh_merge_kernel(*args, **kw)
    run_pad = 64                 # slots * 6 series * 4 buckets + the tail
    runs = dict(mesh_merge_kernel(*args, row_run_pad=run_pad, **kw))
    assert bool(runs.pop("by_runs")) == (case == "sorted")
    assert sorted(rows) == sorted(runs)
    for k in rows:
        assert np.array_equal(np.asarray(rows[k]), np.asarray(runs[k])), k
    # and against numpy: slots fold in batch order, integers exactly
    flat = seg % seg_pad
    ref = kernels.numpy_segment_partials(
        vals, valid, flat, np.zeros(len(vals), np.int32), seg_pad, ALL_FOUR)
    for k in ("count", "sum", "min", "max"):
        assert np.array_equal(np.asarray(runs[k]).astype(np.int64),
                              ref[k].astype(np.int64)), k


def test_jnp_is_x64_here():
    # the cases above lean on i64 wrap-around: no silent downcast
    assert jnp.asarray(np.int64(2**62)).dtype == jnp.int64
