"""Fused device-side query kernels over cached DeviceBatches.

One jitted program per (filter expression, aggregate set, segment shape)
runs the ENTIRE per-vnode query — predicate filter, time-bucket
computation, group mapping, masked segment reductions — against
device-resident columns. Per query, only the group-of-series vector and
scalar bucket parameters cross to the device and only [num_segments]
partials come back; the row data never moves again. This is what makes
repeated analytics queries fast: the columns are uploaded once.

Bucket math is pure int32 (64-bit integer ops are software-emulated on
TPU, measured ~1000× slower). For interval = I_s whole seconds, with batch
epoch E and query origin O:

    bucket(ts) = floor((ts - O)/interval)
    let A = E - O = qA*interval + rA,  rA = rA_s*1e9 + rA_ns  (host, exact)
    ts = E + sec*1e9 + rem             (device i32 pair)
    carry = (rem + rA_ns) >= 1e9
    bucket = qA + floor((sec + rA_s + carry) / I_s)            (all i32)

The final index subtracts bmin host-side (folded into `offset`), so no
per-query recompilation: rA_s, rA_ns, offset and the bucket count are
traced scalars (a window that happens to start on a bucket boundary has
one bucket fewer and must not be a new program: eight vnodes compiling
one each inside a request is seconds).

Segment reductions are kernels.local_segment_partials: the seg ids are
derived ON DEVICE (group_of_series[sid] × n_buckets + bucket), and a batch
is series-major and time-ascending, so they lie in at most
n_series × n_buckets + 1 contiguous runs — the static bound launch_fused
hands the program, which then reduces runs, not rows (count, integer sum,
min, max; the program counts its runs and takes the row scatter itself if
the bound does not hold, and says so in its last packed row).
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from ..sql.expr import Expr
from ..utils import lockwatch, stages
from . import program
from .device_cache import DeviceBatch
from .kernels import (local_segment_partials, note_run_path, pad_segments,
                      run_pad_for)

_kernel_cache: dict = {}

# One thread at a time calls a fused program. The call dispatches and
# returns (a few hundred µs) unless the argument shapes are new, and then
# it compiles: the per-vnode fan-out (sql/executor.py) reaches here from
# eight pool threads at once, each vnode with its own series count and
# so its own shapes, and eight of these compiles side by side brought the
# TPU compiler down with the server (SIGSEGV under
# xla::jellyfish::TpuBroadcastRewriter, a v5e, PR 33: three of five
# first requests of a `WITH SHARD 8` database on one chip).
_DISPATCH_LOCK = lockwatch.Lock("fused.dispatch")

# observability: how many fused device programs launched this process
# (tests assert the device path actually engaged)
launch_count = 0

NS_PER_SEC = 1_000_000_000
# the head of a launch's packed i32 params: ra_s, ra_ns, offset, n_rows,
# n_buckets; the group vector and the regular mode's run params follow
_SCALARS = 5


def bucket_arith_params(epoch_ns: int, origin: int, interval: int,
                        bmin: int, max_span_ns: int = 0,
                        ) -> tuple[int, int, int, int] | None:
    """Host-side derivation of the i32 bucket constants; None if the
    interval is not a whole number of seconds or any i32 step could
    overflow (host path handles those)."""
    if interval % NS_PER_SEC != 0:
        return None
    i_s = interval // NS_PER_SEC
    if i_s >= 2**31:
        return None
    a = epoch_ns - origin
    qa = a // interval
    ra = a - qa * interval
    ra_s = ra // NS_PER_SEC
    ra_ns = ra % NS_PER_SEC
    # sec_adj = ts_sec + ra_s + carry must stay inside i32
    if max_span_ns // NS_PER_SEC + ra_s + 2 >= 2**31:
        return None
    offset = qa - bmin
    if not (-(2**31) < offset < 2**31):
        return None
    return int(i_s), int(ra_s), int(ra_ns), int(offset)


class PendingFused:
    """A launched (asynchronous) fused kernel; fetch() pulls the single
    packed output matrix in ONE device→host transfer and unpacks it."""

    __slots__ = ("dev_out", "manifest", "num_segments", "int_cols", "agg_cols")

    def __init__(self, dev_out, manifest, num_segments, int_cols, agg_cols):
        self.dev_out = dev_out
        self.manifest = manifest
        self.num_segments = num_segments
        self.int_cols = int_cols
        self.agg_cols = agg_cols

    def fetch(self) -> dict[str, dict]:
        # blocks until the program has run, then moves its one matrix
        with stages.stage("kernel.fetch_ms"):
            mat = np.asarray(self.dev_out)  # [n_slots, ns_pad], one transfer
        out: dict[str, dict] = {}
        for i, (col, agg) in enumerate(self.manifest):
            if col == "__runs__":
                # the program's own word on its run path, booked per launch
                note_run_path(mat[i, 0] != 0)
                continue
            row = mat[i, :self.num_segments]
            if agg == "count" or agg.endswith("_rank") or col in self.int_cols:
                # exact below 2^53; integer sums beyond that would lose
                # precision in the packed f64 transfer (documented limit)
                row = row.astype(np.int64)
            out.setdefault(col, {})[agg] = row
        presence = out.get("__presence__", {}).get("count")
        if presence is not None:
            # all-valid columns elide their count slot (it IS presence); a
            # column whose ONLY slot was count must still appear in out
            for col in self.agg_cols:
                out.setdefault(col, {}).setdefault("count", presence)
        return out


def launch_fused(dbatch: DeviceBatch, filter_expr: Expr | None,
                 group_of_series: np.ndarray, n_groups: int, n_buckets: int,
                 arith: tuple[int, int, int, int] | None,
                 col_wants: dict[str, dict]) -> PendingFused:
    global launch_count
    launch_count += 1
    stages.count("fused_launches")
    num_segments = n_groups * n_buckets
    ns_pad = pad_segments(max(num_segments, 1))

    filter_key = filter_expr.to_sql() if filter_expr is not None else ""
    cols_key = tuple(sorted((c, tuple(sorted(w.items())))
                            for c, w in col_wants.items()))
    # ship every column the kernel touches: aggregated ones AND columns the
    # filter references but no aggregate does
    filt_cols = filter_expr.columns() if filter_expr is not None else set()
    present = [n for n in sorted(set(col_wants) | filt_cols)
               if n in dbatch.fields]
    dtypes_key = tuple((name, str(dbatch.fields[name][1].dtype))
                       for name in present)
    prof = stages.current_profile()
    if prof is not None:
        # telemetry: which column dtypes this query's programs took
        prof.device.setdefault("fused_column_dtypes", {}).update(dtypes_key)
    i_s, ra_s, ra_ns, offset = arith if arith is not None else (1, 0, 0, 0)
    use_bucket = arith is not None
    need_rank = any(w.get("want_first") or w.get("want_last")
                    for w in col_wants.values())
    valid_flags = tuple(dbatch.fields[n][2] is not None for n in present)
    has_ts_ns = use_bucket and not dbatch.ns_all_zero
    regular = dbatch.series_params is not None
    # series-major, time-ascending rows: every series passes each bucket
    # once, and the zero-padded tail adds a run
    run_pad = run_pad_for(dbatch.n_pad,
                          max(dbatch.n_series, 1) * n_buckets + 1)
    # the divisor i_s MUST be a compile-time constant: division by a traced
    # i32 is software-emulated on TPU (~1000× slower); XLA strength-reduces
    # constant divisors to multiplies. Intervals are few (1m/5m/1h/...), so
    # keying the kernel cache on i_s costs a handful of compiles. The
    # add/compare params (ra_s/ra_ns/offset) stay traced — they change per
    # batch/origin without recompilation, and so does n_buckets (the
    # size classes ns_pad and run_pad carry what shape it needs).
    # Optional inputs (ts_ns, rank, per-column validity) are kernel
    # variants: an absent buffer is one never uploaded.
    key = (filter_key, cols_key, dtypes_key, ns_pad,
           use_bucket, i_s, dbatch.n_pad, need_rank, valid_flags, has_ts_ns,
           regular, run_pad)
    entry = _kernel_cache.get(key)
    if entry is None:
        entry = _build_kernel(filter_expr, col_wants, tuple(present), ns_pad,
                              use_bucket, i_s, need_rank,
                              valid_flags, has_ts_ns, regular, dbatch.n_pad,
                              run_pad)
        _kernel_cache[key] = entry
    fn, manifest = entry

    ns = max(dbatch.n_series, 1)
    gos = np.zeros(ns, dtype=np.int32)
    gos[:len(group_of_series)] = group_of_series

    args = []
    if not regular:
        if use_bucket:
            args.append(dbatch.ts_sec)
            if has_ts_ns:
                args.append(dbatch.ts_ns)
        args.append(dbatch.sid_ordinal)
    if need_rank:
        args.append(dbatch.rank_dev())
    # one host→device transfer per launch: all per-query scalars + the
    # group vector + (regular mode) the per-series run params ride in ONE
    # i32 buffer
    sp = dbatch.series_params if regular else None
    sp_len = sp.size if sp is not None else 0
    params = np.empty(_SCALARS + ns + sp_len, dtype=np.int32)
    params[:_SCALARS] = ra_s, ra_ns, offset, dbatch.n_rows, n_buckets
    params[_SCALARS:_SCALARS + ns] = gos
    if sp is not None:
        params[_SCALARS + ns:] = sp.ravel()
    from .placement import scan_device

    args.append(jax.device_put(params, scan_device()))
    for name, has_valid in zip(present, valid_flags):
        _vt, vals, valid = dbatch.fields[name]
        args.append(vals)
        if has_valid:
            args.append(valid)
    # the wait for the lock is part of the dispatch: eight vnodes' threads
    # meet here
    with stages.stage("kernel.dispatch_ms"), _DISPATCH_LOCK:
        dev_out = fn(*args)
    int_cols = {name for name in present
                if jnp.issubdtype(dbatch.fields[name][1].dtype, jnp.integer)}
    agg_cols = tuple(n for n in present if n in col_wants)
    return PendingFused(dev_out, manifest, num_segments, int_cols, agg_cols)


def run_fused(dbatch: DeviceBatch, filter_expr: Expr | None,
              group_of_series: np.ndarray, n_groups: int, n_buckets: int,
              arith: tuple[int, int, int, int] | None,
              col_wants: dict[str, dict]) -> dict[str, dict]:
    return launch_fused(dbatch, filter_expr, group_of_series, n_groups,
                        n_buckets, arith, col_wants).fetch()


def _build_kernel(filter_expr: Expr | None, col_wants: dict,
                  present: tuple, ns_pad: int,
                  use_bucket: bool, i_s: int, need_rank: bool,
                  valid_flags: tuple, has_ts_ns: bool, regular: bool,
                  n_pad: int = 0, run_pad: int = 0):
    """→ (jitted fn, manifest). The kernel packs every partial into ONE
    [n_slots, ns_pad] float64 matrix so the host fetches a single transfer
    (one blocking pull per launch, not one per slot). f64 holds counts and
    i32 ranks exactly (< 2^53). Optional inputs are compile-time variants — see
    launch_fused. With run_pad > 0 (kernels.run_pad_for) the reductions go
    by runs and the matrix's last row says whether they did."""
    manifest: list[tuple[str, str]] = [("__presence__", "count")]
    agg_cols = [n for n in present if n in col_wants]
    valid_of = dict(zip(present, valid_flags))
    for name in agg_cols:
        w = col_wants[name]
        if valid_of.get(name):
            # nullable column: its count differs from presence → own slot
            manifest.append((name, "count"))
        for agg, flag in (("sum", "want_sum"), ("min", "want_min"),
                          ("max", "want_max"), ("first", "want_first"),
                          ("last", "want_last")):
            if w.get(flag):
                manifest.append((name, agg))
                if agg in ("first", "last"):
                    manifest.append((name, agg + "_rank"))
    if run_pad:
        manifest.append(("__runs__", "engaged"))

    def kernel(*args):
        i = 0
        ts_sec = ts_ns = None
        sid_ord = None
        if not regular:
            if use_bucket:
                ts_sec = args[i]; i += 1
                if has_ts_ns:
                    ts_ns = args[i]; i += 1
            sid_ord = args[i]; i += 1
        if need_rank:
            rank = args[i]; i += 1
        else:
            rank = None
        params = args[i]; i += 1
        ra_s, ra_ns, offset, n_rows, n_buckets = (
            params[k] for k in range(_SCALARS))
        fields = {}
        for name, has_valid in zip(present, valid_flags):
            vals = args[i]; i += 1
            valid = None
            if has_valid:
                valid = args[i]; i += 1
            fields[name] = (vals, valid)

        row = jax.lax.iota(jnp.int32, n_pad)
        if regular:
            # reconstruct sid + ts_sec from [n_series,3] run params
            n_series = (params.shape[0] - _SCALARS) // 4
            group_of_series = params[_SCALARS:_SCALARS + n_series]
            sp = params[_SCALARS + n_series:].reshape(n_series, 3)
            row_start, sec0, stride = sp[:, 0], sp[:, 1], sp[:, 2]
            sid_ord = (jnp.searchsorted(row_start, row, side="right") - 1
                       ).astype(jnp.int32)
            sid_ord = jnp.clip(sid_ord, 0, n_series - 1)
            if use_bucket:
                k = row - row_start[sid_ord]
                ts_sec = sec0[sid_ord] + k * stride[sid_ord]
        else:
            n_series = params.shape[0] - _SCALARS
            group_of_series = params[_SCALARS:]
        mask = row < n_rows
        if filter_expr is not None:
            env = {}
            for name, (vals, valid) in fields.items():
                env[name] = vals
                env[f"__valid__:{name}"] = (
                    valid if valid is not None
                    else jnp.ones(vals.shape, dtype=bool))
            fmask = filter_expr.eval(env, jnp)
            mask = mask & fmask
            # null operands exclude rows (host path does the same)
            for c in filter_expr.columns():
                if c in fields and fields[c][1] is not None:
                    mask = mask & fields[c][1]
        if use_bucket:
            if ts_ns is not None:
                carry = ((ts_ns + ra_ns) >= NS_PER_SEC).astype(jnp.int32)
            else:
                carry = (ra_ns >= NS_PER_SEC).astype(jnp.int32)
            sec_adj = ts_sec + ra_s + carry
            bucket = offset + sec_adj // jnp.int32(i_s)
            bucket = jnp.clip(bucket, 0, n_buckets - 1)
        else:
            bucket = jnp.zeros_like(sid_ord)
        seg = (group_of_series[sid_ord] * n_buckets + bucket).astype(jnp.int32)
        # the ids stay UNMASKED: the filter's mask goes into `valid` only
        # (a masked row carries the identity wherever it lands), or it
        # would cut a run at every filtered row
        part = local_segment_partials(
            seg, mask, seg, seg, num_segments=ns_pad, run_pad=run_pad,
            want_sum=False, want_min=False, want_max=False)
        presence = part["count"]
        results = {("__presence__", "count"): presence}
        if run_pad:
            results[("__runs__", "engaged")] = jnp.broadcast_to(
                part["by_runs"], (ns_pad,))
        for name in agg_cols:
            vals, valid = fields[name]
            w = col_wants[name]
            part = local_segment_partials(
                vals, (valid & mask) if valid is not None else mask, seg,
                rank if rank is not None else seg,  # rank unused w/o first/last
                num_segments=ns_pad, run_pad=run_pad,
                # an all-valid column's count IS the presence count: skip
                # the extra reduction
                want_count=valid is not None,
                want_sum=w.get("want_sum", False),
                want_min=w.get("want_min", False),
                want_max=w.get("want_max", False),
                want_first=w.get("want_first", False),
                want_last=w.get("want_last", False))
            part.pop("by_runs", None)
            if "count" not in part:
                part["count"] = presence
            for agg, arr in part.items():
                results[(name, agg)] = arr
        rows = [results[slot].astype(jnp.float64) for slot in manifest]
        return jnp.stack(rows)

    return jax.jit(program("fused_aggregate")(kernel)), manifest
