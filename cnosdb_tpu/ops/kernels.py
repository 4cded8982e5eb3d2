"""Core device kernels: masked segment aggregation.

This replaces the reference's per-series CPU reader tree + DataFusion
AggregateExec (tskv/src/reader/iterator.rs:94-121, pushdown_agg_reader.rs)
with ONE fused XLA program: every (row → segment) mapping — segment =
group_id × n_buckets + time_bucket — feeds masked segment reductions for
count/sum/min/max and rank-argmin/argmax selections for first/last.

TPU-first choices:
- No int64 timestamps on device: the host precomputes `bucket` (i32) and a
  globally unique time-order `rank` (i32) per row; first/last become
  segment-argmin/argmax over rank. This keeps the hot path free of i64
  emulation and halves PCIe traffic vs shipping raw ns timestamps.
- Static shapes: rows and segment counts are padded to size classes
  (pad_rows/pad_segments) so jit caches a handful of programs, not one per
  query.
- All aggregates in one jit: masking, bucket math and reductions compile
  into one program per query shape.
- Runs, not rows: XLA lowers jax.ops.segment_sum/min/max to a scatter that
  applies one update per row in a serial loop (68 ns a row for an i64 sum
  on a v5e: 143 ms for 2^21 rows). Scan rows arrive series-major and
  time-ascending, so equal segment ids lie in contiguous runs; given a
  static bound on their number (`run_pad`) the reduction scans the rows
  and scatters one partial per run instead (see "run structure" below).

`local_segment_partials` is the single implementation of the reduction
body; the single-device jit here, the fused program (ops/fused.py) and
the shard_map bodies in parallel/distributed_agg.py all call it.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import numpy as np

# importing this module first executes the ops package __init__, which
# enables x64 before jax is used
import jax
import jax.numpy as jnp

from ..utils import stages
from . import program

I32_MAX = np.int32(2**31 - 1)
I32_MIN = np.int32(-(2**31) + 1)


def pad_rows(n: int, minimum: int = 1024) -> int:
    """Next power-of-two size class."""
    m = minimum
    while m < n:
        m <<= 1
    return m


def pad_segments(n: int, minimum: int = 64) -> int:
    m = minimum
    while m < n:
        m <<= 1
    return m


def type_extrema(dtype):
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.array(jnp.inf, dtype), jnp.array(-jnp.inf, dtype)
    info = jnp.iinfo(dtype)
    return jnp.array(info.max, dtype), jnp.array(info.min, dtype)


# ---------------------------------------------------------------------------
# run structure: reduce contiguous equal-segment runs, not rows
# ---------------------------------------------------------------------------
# A scan batch is series-major and time-ascending and a segment id is
# group_of_series[sid] · n_buckets + bucket, so equal ids lie in contiguous
# runs — far fewer runs than rows. XLA's scatter (what jax.ops.segment_*
# lowers to on the TPU) applies one update per ROW in a serial loop; the
# run path scans the rows once, reads the scan at each run's last row, and
# scatters only the run partials. The host lanes' twin is
# run_segment_partials (ufunc.reduceat over runs).

# Chip readings behind the constants below (one TPU v5 lite, PR 30, a
# scratch microbench of segment_aggregate, i64 values, count + sum; ms a
# blocking call / ms a call with ten to sixty in flight):
#   rows → segments, run_pad     row scatter        run path
#   2^21 → 8 192,   8 192      162.3  / 161.0      3.81 / 2.72   (fleet)
#   2^19 → 16 384,  2 048       41.5  / 40.3       1.74 / 0.71   (mesh4, a device)
#   2^14 → 64,      128          2.11 / 1.14       1.01 / 0.45   (a panel)
#   2^12 → 64,      512          1.17 / 0.36       1.00 / 0.43   (8 rows a run)
#   2^11 → 64,      64           1.02 / 0.36       0.96 / 0.46   (a panel)
#   2^10 → 64,      256          0.94 / 0.38       0.96 / 0.45   (4 rows a run)
# count + max reads the same to ±3 %. A blocking call's floor is ~0.9 ms
# and a queued call's 0.36, so under 2^13 rows the two paths answer
# equally fast; what differs is device time: a scatter update costs ~77 ns
# (68 i64 + 9 i32 count), the run path ~0.45 ms of small operations
# whatever the shape, ~1.3 ns a row of scans and, a run, its update plus
# log2(rows) search gathers at ~7.5 ns (~240 ns at 2^21 rows). The two
# meet near 5 800 rows and, for long batches, near 3 rows a run. But the
# device's time is not the request's: with the run path on from 2^13 rows,
# devops-host-panels (four clients under one GIL; only its 2^14-row
# launches engaged, ten a query) lost its device 72 % of its busy time
# (1.404 → 0.373 s of a 10 s window) and still ran 3 % SLOWER end to end in
# four pairs of four (query_p50_ms 93.6 → 96.3, 92.8 → 95.9): a launch
# compiled with the run path pulls one more output, its flag, and a device
# call more costs a crowded Python process more than 1.1 ms less of
# waiting gives back. So the floor sits where the wait saved is several
# milliseconds a launch (77 ns × 2^16 rows = 5 ms), not where the device
# breaks even.
RUN_PATH_MIN_ROWS = 1 << 16
RUN_PATH_MIN_ROWS_PER_RUN = 8
# The scans are Hillis–Steele: log2(N) shifted combines over the whole
# vector (2^21 rows: 0.8 ms i64 prefix sum, 1.1 ms run-restarting max;
# compiled for a v5e in 0.6–1.9 s). jnp.cumsum over the same axis runs in
# 1.0 / 3.0 ms (i32 / i64) but compiles in 7.5 s / 40–92 s, a flagged
# lax.associative_scan in 307 s; jnp.searchsorted's while loop takes 4.4 ms
# for 8 192 run ends where the unrolled search below takes 1.9.


class SegmentRuns(NamedTuple):
    """The contiguous equal-segment runs of one segment-id vector."""
    index: jax.Array     # [N] i32 — the run each row lies in (monotone)
    ends: jax.Array      # [run_pad] i32 — each run's last row
    seg: jax.Array       # [run_pad] i32 — each run's segment; unused run
    #                      slots carry the dead slot `num_segments`
    engaged: jax.Array   # bool scalar — the rows hold at most run_pad runs


def run_pad_for(n_pad: int, max_runs: int) -> int:
    """The static `run_pad` for a batch of n_pad rows holding at most
    max_runs runs (the caller's bound, e.g. n_series · n_buckets + 1 for
    the zero-padded tail), padded to a size class — or 0 (keep the row
    scatter) where the batch is short or its runs are not several times
    fewer than its rows: there the run path saves nothing end to end."""
    run_pad = pad_segments(max(int(max_runs), 1))
    pays = n_pad >= max(RUN_PATH_MIN_ROWS,
                        RUN_PATH_MIN_ROWS_PER_RUN * run_pad)
    return run_pad if pays else 0


def _shift(x, k: int, head):
    """x shifted k places to the right; `head` [k] fills the front."""
    return jnp.concatenate([head, x[:-k]])


def _prefix_sum(x):
    """Inclusive prefix sum of x [N]. Integer adds wrap, so differences of
    the prefix stay exact."""
    k = 1
    while k < x.shape[0]:
        x = x + _shift(x, k, jnp.zeros((k,), x.dtype))
        k <<= 1
    return x


def _run_scan(x, index, op):
    """Inclusive scan of `op` over x [N] that restarts at every run start
    (index = the monotone run index): a row combines with the row 2^k
    back while both lie in one run."""
    k = 1
    while k < x.shape[0]:
        same = _shift(index, k, jnp.full((k,), -1, index.dtype)) == index
        x = jnp.where(same, op(x, _shift(x, k, x[:k])), x)
        k <<= 1
    return x


def segment_runs(seg_ids, run_pad: int, num_segments: int) -> SegmentRuns:
    """Find the runs of seg_ids [N] i32 (trace-time body). The program
    counts its own runs: `engaged` is False when the rows hold more than
    run_pad (rows not run-contiguous, or a caller's bound that was wrong)
    and the reduction then takes the row scatter."""
    n = seg_ids.shape[0]
    start = jnp.concatenate(
        [jnp.ones((1,), jnp.int32),
         (seg_ids[1:] != seg_ids[:-1]).astype(jnp.int32)])
    index = _prefix_sum(start) - 1
    n_runs = index[n - 1] + 1
    slot = jax.lax.iota(jnp.int32, run_pad)
    # rows with index <= slot, by an unrolled binary search over the
    # monotone index, one bit a step — not a compaction: nonzero(size=…)
    # over N rows lowers to the same serial scatter
    below = jnp.zeros((run_pad,), jnp.int32)
    bit = 1 << (n.bit_length() - 1)
    while bit:
        cand = below + bit
        take = (cand <= n) & (index[jnp.minimum(cand, n) - 1] <= slot)
        below = jnp.where(take, cand, below)
        bit >>= 1
    ends = below - 1        # run r's last row; n - 1 for unused slots
    seg = jnp.where(slot < n_runs, seg_ids[ends], num_segments)
    return SegmentRuns(index, ends, seg, n_runs <= run_pad)


_SEGMENT_OPS = {"count": jax.ops.segment_sum, "sum": jax.ops.segment_sum,
                "min": jax.ops.segment_min, "max": jax.ops.segment_max}


def _masked_inputs(values, valid, names) -> dict:
    """name → the [N] vector its reduction runs over: masked and null rows
    carry the reduction's identity."""
    vmax, vmin = type_extrema(values.dtype)
    fill = {"sum": jnp.zeros((), values.dtype), "min": vmax, "max": vmin}
    # count is i32 on device (64-bit int ops are emulated on TPU); a batch
    # is bounded well below 2^31 rows, host wrappers upcast to i64
    return {n: valid.astype(jnp.int32) if n == "count"
            else jnp.where(valid, values, fill[n]) for n in names}


def _reduce_rows(inputs: dict, seg_ids, num_segments: int) -> dict:
    """One scatter update per row (XLA's segment lowering)."""
    return {n: _SEGMENT_OPS[n](x, seg_ids, num_segments)
            for n, x in inputs.items()}


def _reduce_runs(inputs: dict, runs: SegmentRuns, num_segments: int) -> dict:
    """The same by runs: each run's partial from a scan read at its last
    row (count and sum: the prefix sum's step from the run before), then
    one scatter update per run."""
    out = {}
    for n, x in inputs.items():
        if n in ("count", "sum"):
            at_end = _prefix_sum(x)[runs.ends]
            per_run = at_end - _shift(at_end, 1, jnp.zeros((1,), x.dtype))
        else:
            op = jnp.minimum if n == "min" else jnp.maximum
            per_run = _run_scan(x, runs.index, op)[runs.ends]
        # the dead slot absorbs unused run slots and is sliced off
        out[n] = _SEGMENT_OPS[n](per_run, runs.seg, num_segments + 1)[:-1]
    return out


def local_segment_partials(values, valid, seg_ids, rank, *, num_segments: int,
                           run_pad: int = 0,
                           want_count=True, want_sum=True, want_min=True,
                           want_max=True, want_first=False, want_last=False):
    """Masked segment reductions for one column (trace-time body, shared by
    the local jit, the fused program and the distributed shard_map
    programs).

    values [N], valid [N] bool, seg_ids [N] i32 (padded/filtered rows carry
    valid=False), rank [N] i32 globally-unique time order.
    → dict of [num_segments] arrays (plus first_rank/last_rank carrying the
    selection keys for cross-shard combination).

    `run_pad` (static; 0 = one scatter update per row) is the caller's
    bound on the number of contiguous equal-segment runs in seg_ids, from
    run_pad_for(). With it, count, integer sum, min and max reduce runs:
    prefix sums and run-restarting scans read at each run's last row, then
    a scatter of run_pad run partials. Masked and null rows carry the
    identity and do not cut a run — so hand in the UNMASKED ids and put a
    filter's mask into `valid` only. The bound is checked, not trusted:
    with more runs than run_pad the same program takes the row scatter
    (lax.cond), and the result says which under "by_runs" (bool scalar;
    present whenever run_pad > 0). Answers are bit-identical either way:
    integer sums are exact in any association, count / min / max
    order-free. A FLOATING sum / min / max keeps the row scatter (its
    association is pinned by the parity tests); first / last stay on the
    rank scatters. A program that reduces several columns over one seg_ids
    repeats segment_runs() per call; it is a pure function of seg_ids, so
    XLA folds the copies into one (tests/test_chip_compile.py counts).
    """
    names = [n for n, want in (("count", want_count), ("sum", want_sum),
                               ("min", want_min), ("max", want_max)) if want]
    integral = jnp.issubdtype(values.dtype, jnp.integer)
    by_runs = [n for n in names if run_pad and (n == "count" or integral)]
    out = _reduce_rows(
        _masked_inputs(values, valid, [n for n in names if n not in by_runs]),
        seg_ids, num_segments)
    if run_pad:
        runs = segment_runs(seg_ids, run_pad, num_segments)
        out["by_runs"] = runs.engaged
        out.update(jax.lax.cond(
            runs.engaged,
            lambda: _reduce_runs(_masked_inputs(values, valid, by_runs),
                                 runs, num_segments),
            lambda: _reduce_rows(_masked_inputs(values, valid, by_runs),
                                 seg_ids, num_segments)))
    zero = jnp.zeros((), values.dtype)
    if want_first:
        key = jnp.where(valid, rank, I32_MAX)
        rmin = jax.ops.segment_min(key, seg_ids, num_segments)
        sel = valid & (rank == rmin[seg_ids])
        out["first"] = jax.ops.segment_sum(
            jnp.where(sel, values, zero), seg_ids, num_segments)
        out["first_rank"] = rmin
    if want_last:
        key = jnp.where(valid, rank, I32_MIN)
        rmax = jax.ops.segment_max(key, seg_ids, num_segments)
        sel = valid & (rank == rmax[seg_ids])
        out["last"] = jax.ops.segment_sum(
            jnp.where(sel, values, zero), seg_ids, num_segments)
        out["last_rank"] = rmax
    return out


segment_aggregate = jax.jit(
    program("segment_aggregate")(local_segment_partials),
    static_argnames=("num_segments", "run_pad", "want_count", "want_sum",
                     "want_min", "want_max", "want_first", "want_last"))


def numpy_segment_partials(values: np.ndarray, valid: np.ndarray,
                           seg_ids: np.ndarray, rank: np.ndarray,
                           num_segments: int, wants: dict,
                           assume_all_valid: bool = False) -> dict:
    """Pure-numpy segment reductions — the CPU-placement twin of the XLA
    kernel. On one core, bincount/ufunc.at beat XLA's scatter lowering by
    ~2×, and no padding copies are needed; the device path remains the
    jitted kernel (placement decides, ops/placement.py)."""
    if not assume_all_valid and not valid.all():
        rows = np.nonzero(valid)[0]
        values = values[rows]
        seg_ids = seg_ids[rows]
        rank = rank[rows]
    out: dict[str, np.ndarray] = {}
    ns = num_segments
    if wants.get("want_count"):
        out["count"] = np.bincount(seg_ids, minlength=ns).astype(np.int64)
    integral = values.dtype.kind in "iu"
    if wants.get("want_sum"):
        if integral:
            # bincount sums in f64 and would round past 2^53; add.at is
            # slower but exact in the column's own integer arithmetic
            acc = np.zeros(ns, dtype=values.dtype)
            np.add.at(acc, seg_ids, values)
            out["sum"] = acc
        else:
            out["sum"] = np.bincount(seg_ids, weights=values, minlength=ns)
    if wants.get("want_min"):
        init = (np.iinfo(values.dtype).max if integral
                else np.asarray(np.inf, values.dtype))
        acc = np.full(ns, init, dtype=values.dtype)
        np.minimum.at(acc, seg_ids, values)
        out["min"] = acc
    if wants.get("want_max"):
        init = (np.iinfo(values.dtype).min if integral
                else np.asarray(-np.inf, values.dtype))
        acc = np.full(ns, init, dtype=values.dtype)
        np.maximum.at(acc, seg_ids, values)
        out["max"] = acc
    if wants.get("want_first") or wants.get("want_last"):
        sel_rank = {}
        if wants.get("want_first"):
            acc = np.full(ns, I32_MAX, dtype=rank.dtype)
            np.minimum.at(acc, seg_ids, rank)
            sel_rank["first"] = acc
        if wants.get("want_last"):
            acc = np.full(ns, I32_MIN, dtype=rank.dtype)
            np.maximum.at(acc, seg_ids, rank)
            sel_rank["last"] = acc
        for name, acc in sel_rank.items():
            pick = rank == acc[seg_ids]
            vals_out = np.zeros(ns, dtype=values.dtype)
            vals_out[seg_ids[pick]] = values[pick]
            out[name] = vals_out
            out[f"{name}_rank"] = acc
    return out


def run_boundaries(seg_ids: np.ndarray,
                   sid_ordinal: np.ndarray | None = None) -> np.ndarray:
    """Start indices of equal-segment runs (splitting additionally at
    series boundaries when sid_ordinal is given — first/last need time
    order WITHIN every run, which only holds per series).

    Correct for arbitrary seg arrays — a segment recurring in many runs
    just contributes several partials; the caller combines them. Fast
    when segments are contiguous, which the storage layout guarantees:
    scan batches are series-contiguous and time-ordered per series, so
    group×bucket segment ids form runs."""
    n = len(seg_ids)
    if n == 0:
        return np.zeros(0, dtype=np.int64)
    ch = np.diff(seg_ids) != 0
    if sid_ordinal is not None:
        ch = ch | (np.diff(sid_ordinal) != 0)
    return np.concatenate(([0], np.flatnonzero(ch) + 1)).astype(np.int64)


_I64_MAX = np.int64(np.iinfo(np.int64).max)
_I64_MIN = np.int64(np.iinfo(np.int64).min)


def run_segment_partials(values: np.ndarray, seg_ids: np.ndarray,
                         starts: np.ndarray, num_segments: int, wants: dict,
                         ts: np.ndarray | None = None,
                         run_counts: np.ndarray | None = None) -> dict:
    """Segment reductions over contiguous equal-segment runs.

    The storage-layout-aware twin of numpy_segment_partials: sequential
    ufunc.reduceat over runs replaces scatter bincount/ufunc.at, then
    tiny per-run combines fold
    runs into segments. ALL rows are assumed valid — callers compress
    invalid rows out first (compression preserves run structure).

    first/last require `ts` (row timestamps, time-ordered within each
    run) and return companion 'first_ts'/'last_ts' arrays — actual
    timestamps, which coordinators can merge across vnodes directly.
    Tie-breaking matches the rank kernels: earliest row position wins
    `first`, latest wins `last`."""
    out: dict[str, np.ndarray] = {}
    ns = num_segments
    n = len(values)
    if n == 0:
        starts = starts[:0]
    run_seg = seg_ids[starts] if n else np.zeros(0, dtype=np.int64)
    if run_counts is None:
        run_counts = np.diff(np.append(starts, n))
    if wants.get("want_count"):
        out["count"] = np.bincount(
            run_seg, weights=run_counts, minlength=ns).astype(np.int64)
    integral = values.dtype.kind in "iu"
    if wants.get("want_sum"):
        part = np.add.reduceat(values, starts) if n else values[:0]
        if integral:
            # bincount sums in f64 and would round past 2^53; add.at over
            # the (few) runs is exact in the column's own arithmetic
            acc = np.zeros(ns, dtype=values.dtype)
            np.add.at(acc, run_seg, part)
            out["sum"] = acc
        else:
            out["sum"] = np.bincount(run_seg, weights=part, minlength=ns)
    if wants.get("want_min"):
        init = (np.iinfo(values.dtype).max if integral
                else np.asarray(np.inf, values.dtype))
        part = np.minimum.reduceat(values, starts) if n else values[:0]
        acc = np.full(ns, init, dtype=values.dtype)
        np.minimum.at(acc, run_seg, part)
        out["min"] = acc
    if wants.get("want_max"):
        init = (np.iinfo(values.dtype).min if integral
                else np.asarray(-np.inf, values.dtype))
        part = np.maximum.reduceat(values, starts) if n else values[:0]
        acc = np.full(ns, init, dtype=values.dtype)
        np.maximum.at(acc, run_seg, part)
        out["max"] = acc
    if wants.get("want_first"):
        ft = ts[starts] if n else np.zeros(0, dtype=np.int64)
        acc_t = np.full(ns, _I64_MAX, dtype=np.int64)
        np.minimum.at(acc_t, run_seg, ft)
        pick = np.flatnonzero(ft == acc_t[run_seg])
        fvals = np.zeros(ns, dtype=values.dtype)
        # reversed assignment: among ties the EARLIEST run wins (stable
        # time-sort semantics of the rank kernel)
        fvals[run_seg[pick][::-1]] = values[starts][pick][::-1]
        out["first"] = fvals
        out["first_ts"] = acc_t
    if wants.get("want_last"):
        ends = (np.append(starts[1:], n) - 1) if n \
            else np.zeros(0, dtype=np.int64)
        lt = ts[ends] if n else np.zeros(0, dtype=np.int64)
        acc_t = np.full(ns, _I64_MIN, dtype=np.int64)
        np.maximum.at(acc_t, run_seg, lt)
        pick = np.flatnonzero(lt == acc_t[run_seg])
        lvals = np.zeros(ns, dtype=values.dtype)
        lvals[run_seg[pick]] = values[ends][pick]   # latest tied run wins
        out["last"] = lvals
        out["last_ts"] = acc_t
    return out


def aggregate_column_host(values: np.ndarray, valid: np.ndarray,
                          seg_ids: np.ndarray, rank: np.ndarray,
                          num_segments: int, wants: dict,
                          max_runs: int | None = None) -> dict:
    """Host wrapper: pads rows to a size class, runs the jit kernel, pulls
    results back as numpy (sliced to num_segments by the caller).

    `max_runs` is the caller's bound on the contiguous equal-segment runs
    of seg_ids, known from the plan (series × buckets), not counted here:
    under four clients every numpy call on the rows is one more release of
    the GIL, and counting the runs cost the panels ~4 % of their
    throughput on the chip (PR 30). None keeps the row scatter; a bound
    reduces runs where it leaves several times fewer runs than rows
    (run_pad_for), one scatter update a row otherwise."""
    n = len(values)
    np_pad = pad_rows(max(n, 1))
    ns_pad = pad_segments(max(num_segments, 1))
    # +1: the zero-padded tail is a run of its own
    run_pad = run_pad_for(np_pad, max_runs + 1) if max_runs else 0
    if np_pad != n:
        with stages.stage("kernel.pad_ms"):
            values = _pad(values, np_pad)
            valid = _pad(valid, np_pad, fill=False)
            seg_ids = _pad(seg_ids, np_pad, fill=0)
            rank = _pad(rank, np_pad, fill=0)
    # the call returns once the program is enqueued: numpy arguments are
    # put on the device inside it, and a new shape compiles here
    with stages.stage("kernel.dispatch_ms"):
        out = segment_aggregate(values, valid, seg_ids, rank,
                                num_segments=ns_pad, run_pad=run_pad,
                                **wants)
    with stages.stage("kernel.fetch_ms"):
        host = {k: np.asarray(v) for k, v in out.items()}  # lint: disable=host-sync (THE audited transfer point: one batched pull per aggregate call)
    note_run_path(host.pop("by_runs", None))
    host = {k: v[:num_segments] for k, v in host.items()}
    if "count" in host:
        host["count"] = host["count"].astype(np.int64)
    return host


def note_run_path(by_runs) -> None:
    """Book what a launched reduction said of its run path: the pulled
    "by_runs" flag of local_segment_partials, None where the path was not
    compiled in."""
    if by_runs is not None:
        # both keys a launch: a request that never fell back reads 0,
        # not nothing
        stages.count("segment_runs.engaged", int(bool(by_runs)))
        stages.count("segment_runs.fallback", int(not by_runs))


def _pad(a: np.ndarray, n: int, fill=0):
    out = np.full(n, fill, dtype=a.dtype)
    out[:len(a)] = a
    return out


# ---------------------------------------------------------------------------
# sort-based DISTINCT on device (ops/group_agg.py device path)
# ---------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("num_segments",))
@program("segment_distinct")
def _segment_distinct(pairs, nv, *, num_segments: int):
    """count(DISTINCT) from (group·nv + value) pair codes: sort, mark each
    first occurrence, segment-sum the indicators by group. Padded rows
    carry pair codes whose group lands >= num_segments, which segment_sum's
    out-of-range scatter semantics drop."""
    sp = jnp.sort(pairs)
    first = jnp.concatenate(
        [jnp.ones((1,), jnp.bool_), sp[1:] != sp[:-1]])
    seg = sp // nv
    return jax.ops.segment_sum(
        first.astype(jnp.int32), seg, num_segments)


_device_sort = jax.jit(program("sort")(jnp.sort))


def segment_distinct_count(gid: np.ndarray, vcodes: np.ndarray,
                           num_segments: int, n_values: int) -> np.ndarray:
    """Host wrapper for the single-chunk device DISTINCT: pads rows to a
    size class (sentinel pairs map past num_segments and are dropped),
    runs the jitted sort+boundary+segment_sum kernel, returns i64 counts."""
    n = len(gid)
    if n == 0:
        return np.zeros(num_segments, dtype=np.int64)
    nv = np.int64(max(int(n_values), 1))
    pairs = gid.astype(np.int64) * nv + vcodes.astype(np.int64)
    np_pad = pad_rows(n)
    ns_pad = pad_segments(max(num_segments, 1))
    if np_pad != n:
        pairs = _pad(pairs, np_pad, fill=np.int64(ns_pad) * nv)
    out = _segment_distinct(pairs, nv, num_segments=ns_pad)
    return np.asarray(out)[:num_segments].astype(np.int64)  # lint: disable=host-sync (audited transfer point: the i64 counts are the host result)


def sorted_pair_codes(gid: np.ndarray, vcodes: np.ndarray,
                      n_values: int) -> np.ndarray:
    """One chunk's DISTINCT partial: device-sorted unique (group, value)
    pair codes. Sentinel-padded rows sort to the tail and are sliced off;
    the dedup of the sorted run happens host-side so the partial is the
    plain sorted pair array parallel.distributed_agg.merge_distinct_pairs
    expects on the wire."""
    n = len(gid)
    if n == 0:
        return np.zeros(0, dtype=np.int64)
    nv = np.int64(max(int(n_values), 1))
    pairs = gid.astype(np.int64) * nv + vcodes.astype(np.int64)
    np_pad = pad_rows(n)
    if np_pad != n:
        pairs = _pad(pairs, np_pad, fill=np.iinfo(np.int64).max)
    sp = np.asarray(_device_sort(pairs))[:n]  # lint: disable=host-sync (audited transfer point: the sorted partial IS the on-wire format)
    keep = np.concatenate(([True], sp[1:] != sp[:-1]))
    return sp[keep]


@functools.partial(jax.jit, static_argnames=("k",))
@program("topk_threshold")
def _topk_threshold(vals, *, k: int):
    top, _ = jax.lax.top_k(vals, k)
    return top[k - 1]


def dict_mask_gather(mask: np.ndarray, codes):
    """Per-unique predicate mask → row mask on device: one integer gather
    through the dictionary codes (the strkernels broadcast for codes that
    already live on the accelerator)."""
    return _dict_mask_gather(jnp.asarray(mask), codes)


_dict_mask_gather = jax.jit(program("dict_mask_gather")(
    lambda mask, codes: jnp.take(mask, codes, axis=0, mode="clip")))


def topk_threshold(vals: np.ndarray, k: int):
    """k-th largest value of `vals` (descending top-K threshold) via
    jax.lax.top_k; only this scalar crosses back to host. Rows are padded
    to a size class with the dtype minimum so jit caches a handful of
    programs; caller guarantees 0 < k < len(vals) and no NaNs."""
    n = len(vals)
    np_pad = pad_rows(n)
    if np_pad != n:
        if vals.dtype.kind == "f":
            fill = vals.dtype.type(-np.inf)
        else:
            fill = np.iinfo(vals.dtype).min
        vals = _pad(vals, np_pad, fill=fill)
    return np.asarray(_topk_threshold(vals, k=int(k)))  # lint: disable=host-sync (audited transfer point: only this scalar crosses back)
