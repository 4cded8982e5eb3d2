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
- All aggregates in one jit: XLA fuses the mask/select/scatter pipeline
  over a single pass of the data.

`local_segment_partials` is the single implementation of the reduction
body; the single-device jit here and the shard_map body in
parallel/distributed_agg.py both call it.
"""
from __future__ import annotations

import functools

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


def local_segment_partials(values, valid, seg_ids, rank, *, num_segments: int,
                           want_count=True, want_sum=True, want_min=True,
                           want_max=True, want_first=False, want_last=False):
    """Masked segment reductions for one column (trace-time body, shared by
    the local jit and the distributed shard_map program).

    values [N], valid [N] bool, seg_ids [N] i32 (padded/filtered rows carry
    seg 0 with valid=False), rank [N] i32 globally-unique time order.
    → dict of [num_segments] arrays (plus first_rank/last_rank carrying the
    selection keys for cross-shard combination).
    """
    out = {}
    vmax, vmin = type_extrema(values.dtype)
    zero = jnp.zeros((), values.dtype)
    if want_count:
        # i32 on device (64-bit int ops are emulated on TPU); a batch is
        # bounded well below 2^31 rows, host wrappers upcast to i64
        out["count"] = jax.ops.segment_sum(
            valid.astype(jnp.int32), seg_ids, num_segments)
    if want_sum:
        out["sum"] = jax.ops.segment_sum(
            jnp.where(valid, values, zero), seg_ids, num_segments)
    if want_min:
        out["min"] = jax.ops.segment_min(
            jnp.where(valid, values, vmax), seg_ids, num_segments)
    if want_max:
        out["max"] = jax.ops.segment_max(
            jnp.where(valid, values, vmin), seg_ids, num_segments)
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
    static_argnames=("num_segments", "want_count", "want_sum", "want_min",
                     "want_max", "want_first", "want_last"))


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
    ufunc.reduceat over runs replaces scatter bincount/ufunc.at (5-8×
    faster on one core at bench scale), then tiny per-run combines fold
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
                          num_segments: int, wants: dict) -> dict:
    """Host wrapper: pads rows to a size class, runs the jit kernel, pulls
    results back as numpy (sliced to num_segments by the caller).

    When the pallas segment kernel is enabled (ops/pallas_kernels.enabled:
    CNOSDB_TPU_PALLAS=1 or a real TPU scan device) and this aggregation
    qualifies (pallas_kernels.decline_reason: no first/last, a narrow
    segment span per row tile, and on a TPU a 32-bit value dtype), the
    storage-layout-aware windowed kernel replaces XLA's sort/scatter
    segment lowering; everything else books the reason and takes the XLA
    kernel below."""
    n = len(values)
    np_pad = pad_rows(max(n, 1))
    ns_pad = pad_segments(max(num_segments, 1))
    from . import pallas_kernels as pk

    if pk.enabled() and n:
        # routing BEFORE any padding copy or launch (the layout check is
        # O(n/R_TILE))
        reason = pk.decline_reason(values.dtype, wants, seg_ids)
        if reason is not None:
            pk.note_declined(reason)
        else:
            # pad seg with the edge value (not 0) so trailing tiles keep
            # their narrow window; padded rows are valid=False either way
            v2 = _pad(values, np_pad)
            ok2 = _pad(valid, np_pad, fill=False)
            sg2 = _pad(seg_ids, np_pad, fill=seg_ids[n - 1])
            out = pk.segment_partials_pallas(
                v2, ok2, sg2.astype(np.int32, copy=False), ns_pad,
                wants=wants, interpret=pk.interpret_mode())
            pk.note_engaged()
            host = {k: v[:num_segments] for k, v in out.items()}
            if "count" in host:
                host["count"] = host["count"].astype(np.int64)
            return host
    if np_pad != n:
        values = _pad(values, np_pad)
        valid = _pad(valid, np_pad, fill=False)
        seg_ids = _pad(seg_ids, np_pad, fill=0)
        rank = _pad(rank, np_pad, fill=0)
    out = segment_aggregate(values, valid, seg_ids, rank,
                            num_segments=ns_pad, **wants)
    with stages.stage("kernel.fetch_ms"):
        host = {k: np.asarray(v)[:num_segments] for k, v in out.items()}  # lint: disable=host-sync (THE audited transfer point: one batched pull per aggregate call)
    if "count" in host:
        host["count"] = host["count"].astype(np.int64)
    return host


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
