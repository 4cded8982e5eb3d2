"""Distributed scan aggregation: shard_map partials + ICI collectives.

The multi-chip form of ops.kernels.segment_aggregate (SURVEY §2.4
"Partial-agg distribution"): rows are sharded over the mesh axis, every
device reduces its shard into [num_segments] partials in one fused
program, then count/sum combine with `psum`, min/max with `pmin`/`pmax`,
and first/last resolve by all-gathering the per-device (rank, value)
candidates and selecting the global arg-min/max — all inside the same jit,
so XLA schedules compute and ICI traffic together. Output is replicated
(P()) on every device.
"""
from __future__ import annotations

import functools

import numpy as np

from .. import ops as _ops  # noqa: F401 - x64 config side effect
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
try:              # jax >= 0.6 exports shard_map at top level (check_vma)
    from jax import shard_map
except ImportError:   # jax 0.4.x: experimental module, check_rep kwarg
    from jax.experimental.shard_map import shard_map as _shard_map_exp

    def shard_map(f, *, mesh, in_specs, out_specs, check_vma=False):
        return _shard_map_exp(f, mesh=mesh, in_specs=in_specs,
                              out_specs=out_specs, check_rep=check_vma)

from ..ops import program
from ..ops.kernels import local_segment_partials, pad_rows, pad_segments, _pad
from .mesh import SHARD_AXIS, mesh_size


@functools.partial(
    jax.jit, static_argnames=("mesh", "num_segments", "want_first", "want_last"))
@program("dist_aggregate")
def _dist_kernel(values, valid, seg_ids, rank, *, mesh: Mesh,
                 num_segments: int, want_first: bool, want_last: bool):
    def body(v, m, s, r):
        local = local_segment_partials(
            v, m, s, r, num_segments=num_segments,
            want_first=want_first, want_last=want_last)
        out = {
            "count": jax.lax.psum(local["count"], SHARD_AXIS),
            "sum": jax.lax.psum(local["sum"], SHARD_AXIS),
            "min": jax.lax.pmin(local["min"], SHARD_AXIS),
            "max": jax.lax.pmax(local["max"], SHARD_AXIS),
        }
        if want_first:
            ranks = jax.lax.all_gather(local["first_rank"], SHARD_AXIS)  # [D,S]
            vals = jax.lax.all_gather(local["first"], SHARD_AXIS)
            dev = jnp.argmin(ranks, axis=0)
            out["first"] = jnp.take_along_axis(vals, dev[None, :], axis=0)[0]
            out["first_rank"] = jnp.min(ranks, axis=0)
        if want_last:
            ranks = jax.lax.all_gather(local["last_rank"], SHARD_AXIS)
            vals = jax.lax.all_gather(local["last"], SHARD_AXIS)
            dev = jnp.argmax(ranks, axis=0)
            out["last"] = jnp.take_along_axis(vals, dev[None, :], axis=0)[0]
            out["last_rank"] = jnp.max(ranks, axis=0)
        return out

    fn = shard_map(
        body, mesh=mesh,
        in_specs=(P(SHARD_AXIS), P(SHARD_AXIS), P(SHARD_AXIS), P(SHARD_AXIS)),
        out_specs=P(), check_vma=False)
    return fn(values, valid, seg_ids, rank)


@functools.partial(
    jax.jit, static_argnames=("mesh", "slots", "num_segments", "wants",
                              "run_pad", "row_run_pad"))
@program("mesh_merge")
def mesh_merge_kernel(values, valid, seg_ids, rank, run_sums, run_segs, *,
                      mesh: Mesh, slots: int, num_segments: int,
                      wants: tuple[str, ...], run_pad: int = 0,
                      row_run_pad: int = 0):
    """Deterministic-order collective merge for the mesh exec lane
    (ops/mesh_exec.py): each shard holds up to `slots` whole scan
    batches, rows carry slot-local segment ids (slot · num_segments +
    seg), and per-(slot, segment) partials fold in GLOBAL BATCH ORDER —
    shard-major, slot-minor — after an `all_gather` over the shard axis.

    That fold order is the whole point: `sql/executor._merge_results_vec`
    adds per-batch partials in batch order with np.add.at, so a psum
    (whose reduction order XLA owns) could drift f64 sums by an ulp. The
    unrolled fold reproduces the legacy addition order bit-for-bit;
    min/max/first/last are order-insensitive and ride the same gather.
    Output is replicated (P()) — one host fetch serves the coordinator.

    Float sums carry one more ordering constraint: the legacy CPU host
    kernels are run-aware (ufunc.reduceat per contiguous equal-segment
    run, then run partials folded per segment in run order —
    ops.kernels.run_segment_partials), and reduceat's within-run f64
    association is numpy's PAIRWISE reduce — unreproducible by any
    row-order device scatter. So when `run_pad` > 0 the host has staged
    the per-run reduceat partials themselves (`run_sums`, computed with
    the same numpy call the legacy kernel makes) and `run_segs` maps
    runs to slot-local segments (unused run slots → the dead segment
    slots·num_segments, sliced off). The device then folds run partials
    per segment in run order — bincount-over-runs association,
    bit-for-bit — and the cross-shard merge below stays collective.
    run_pad == 0 keeps the flat row-order sum (the legacy flat-scatter
    branches and integer columns).

    `row_run_pad` > 0 is the other run structure, the rows' own: a bound
    (kernels.run_pad_for) on the contiguous equal-segment runs of a
    shard's seg_ids — whole series-major batches side by side — with which
    local_segment_partials reduces runs, not rows (count, integer sum,
    min, max). Each device checks the bound for its shard and takes the
    row scatter otherwise; the output then carries "by_runs", true when
    every device reduced by runs.
    """
    want_first = "first" in wants
    want_last = "last" in wants
    two_level = run_pad > 0 and "sum" in wants

    def body(v, m, s, r, rsum, rseg):
        local = local_segment_partials(
            v, m, s, r, num_segments=slots * num_segments,
            run_pad=row_run_pad,
            want_count=True, want_sum="sum" in wants and not two_level,
            want_min="min" in wants, want_max="max" in wants,
            want_first=want_first, want_last=want_last)
        if two_level:
            # run partials → per-(slot, segment) sums in run order (the
            # bincount-over-runs association); the dead segment absorbs
            # unused run slots and is sliced off
            local["sum"] = jax.ops.segment_sum(
                rsum, rseg,
                num_segments=slots * num_segments + 1)[:-1]
        d = mesh_size(mesh)

        def folded(name, op, cast=None):
            a = jax.lax.all_gather(local[name], SHARD_AXIS)   # [D, slots·S]
            a = a.reshape(d * slots, num_segments)            # batch order
            if cast is not None:
                a = a.astype(cast)
            acc = a[0]
            for k in range(1, d * slots):
                acc = op(acc, a[k])
            return acc

        out = {"count": folded("count", jnp.add, cast=jnp.int64)}
        if "by_runs" in local:
            out["by_runs"] = jax.lax.pmin(
                local["by_runs"].astype(jnp.int32), SHARD_AXIS) > 0
        if "sum" in wants:
            out["sum"] = folded("sum", jnp.add)
        if "min" in wants:
            out["min"] = folded("min", jnp.minimum)
        if "max" in wants:
            out["max"] = folded("max", jnp.maximum)
        for nm, pick in (("first", jnp.argmin), ("last", jnp.argmax)):
            if nm not in wants:
                continue
            ranks = jax.lax.all_gather(local[f"{nm}_rank"], SHARD_AXIS) \
                .reshape(d * slots, num_segments)
            vals = jax.lax.all_gather(local[nm], SHARD_AXIS) \
                .reshape(d * slots, num_segments)
            # ranks are globally unique per valid row (stable argsort of
            # the concatenated timestamps), so the arg pick is exact —
            # ties exist only between empty slots' fill keys
            win = pick(ranks, axis=0)
            out[nm] = jnp.take_along_axis(vals, win[None, :], axis=0)[0]
            out[f"{nm}_rank"] = jnp.take_along_axis(
                ranks, win[None, :], axis=0)[0]
        return out

    fn = shard_map(
        body, mesh=mesh,
        in_specs=(P(SHARD_AXIS),) * 6,
        out_specs=P(), check_vma=False)
    return fn(values, valid, seg_ids, rank, run_sums, run_segs)


def merge_distinct_pairs(chunks: list[np.ndarray], n_values: int,
                         num_segments: int) -> np.ndarray:
    """Combine per-chunk/per-shard DISTINCT partials (sorted (group·nv +
    value) pair-code arrays from ops.kernels.sorted_pair_codes) into
    per-group distinct counts. The wire format is the plain sorted i64
    pair array — the same shape single-chip partials use, so multi-chip
    merging needs no new collective."""
    if not chunks:
        return np.zeros(num_segments, dtype=np.int64)
    pairs = np.unique(np.concatenate(chunks))
    nv = max(int(n_values), 1)
    return np.bincount((pairs // nv).astype(np.int64),
                       minlength=num_segments).astype(np.int64)[:num_segments]


def distributed_aggregate_host(values: np.ndarray, valid: np.ndarray,
                               seg_ids: np.ndarray, rank: np.ndarray,
                               num_segments: int, mesh: Mesh,
                               want_first: bool = False,
                               want_last: bool = False) -> dict:
    """Host wrapper: pad rows to devices × size class, shard, run, fetch."""
    n = len(values)
    d = mesh_size(mesh)
    np_pad = pad_rows(max(n, 1))
    if np_pad % d:
        np_pad = ((np_pad + d - 1) // d) * d
    ns_pad = pad_segments(max(num_segments, 1))
    values = _pad(values, np_pad)
    valid = _pad(valid, np_pad, fill=False)
    seg_ids = _pad(seg_ids, np_pad, fill=0)
    rank = _pad(rank, np_pad, fill=0)
    sharding = NamedSharding(mesh, P(SHARD_AXIS))
    dv = jax.device_put(values, sharding)
    dm = jax.device_put(valid, sharding)
    ds = jax.device_put(seg_ids, sharding)
    dr = jax.device_put(rank, sharding)
    out = _dist_kernel(dv, dm, ds, dr, mesh=mesh, num_segments=ns_pad,
                       want_first=want_first, want_last=want_last)
    host = {k: np.asarray(v)[:num_segments] for k, v in out.items()}
    if "count" in host:
        host["count"] = host["count"].astype(np.int64)
    return host
