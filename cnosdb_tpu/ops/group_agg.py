"""Vectorized grouped-aggregation plane: key factorization, sort-based
DISTINCT, and segment reductions.

The per-row Python accumulation paths (dict-of-set DISTINCT, scalar
min/max folds, per-hole gapfill) are the slowest thing the SQL layer
does — the opposite of the design, which wants grouped reductions over
dense integer codes (the shape both numpy and the TPU segment kernels
win at). This module is the shared engine:

  factorize      value column → dense int64 codes + dictionary, once
  distinct_count unique (group, value) code pairs + bincount
  group_min_max  ufunc.at / unique-code reductions, no scalar folds
  grouped_order  argsort + boundaries → bulk per-group slices (collect)
  device_*       jax segment-sum-family kernels over the same codes
                 (ops/kernels.py), partial pairs merged host-side via
                 parallel/distributed_agg.py — the wire format of the
                 multi-chip partials is unchanged

Counters are always on (cheap dict bumps) and surface on /metrics as
cnosdb_group_agg_total{kind=...}; the stages factorize_ms and
group_count ride the query's profile (utils.stages).
"""
from __future__ import annotations

import threading
from dataclasses import dataclass

import numpy as np

from ..utils import stages
from ..utils import lockwatch

_LOCK = lockwatch.Lock("group_agg.plan_cache")
_COUNTERS: dict[str, int] = {}


def _count(name: str, n: int = 1) -> None:
    with _LOCK:
        _COUNTERS[name] = _COUNTERS.get(name, 0) + n


def counters_snapshot() -> dict[str, int]:
    with _LOCK:
        return dict(sorted(_COUNTERS.items()))


# ---------------------------------------------------------------------------
# key factorization
# ---------------------------------------------------------------------------
@dataclass
class Factorization:
    codes: np.ndarray        # int64 [n], dense in [0, n_values)
    values: np.ndarray       # dictionary, values[codes] reproduces input
    n_values: int


def _object_kinds(arr: np.ndarray):
    """The set of element types in an object column (None excluded).
    C-level map(type) pass — the check that decides whether sort-based
    factorization preserves Python set/equality semantics."""
    return set(map(type, arr.tolist())) - {type(None)}


def factorize(arr: np.ndarray) -> Factorization | None:
    """Dense integer codes for one value column, or None when the column
    can't be factorized without changing Python equality semantics
    (mixed-type object payloads — the caller keeps its scalar fold).

    Invariants the DISTINCT/min-max paths rely on:
      - codes are dense in [0, n_values)
      - values is sorted ascending, so code order == value order
        (group min = values[min code], the string-agg rank trick)
      - equality of codes == Python `==` of the original elements
    """
    with stages.stage("factorize_ms"):
        if arr.dtype != object:
            vals, inv = np.unique(arr, return_inverse=True)
            return Factorization(inv.astype(np.int64).ravel(), vals,
                                 len(vals))
        kinds = _object_kinds(arr)
        if not kinds:
            return Factorization(np.zeros(len(arr), dtype=np.int64),
                                 np.empty(0, dtype=object), 0)
        if kinds <= {str, np.str_}:
            # homogeneous strings: numpy 'U' compare (C speed) is exactly
            # str equality
            vals, inv = np.unique(arr.astype("U"), return_inverse=True)
            dic = vals.astype(object)
        elif all(issubclass(k, (int, np.integer, np.bool_))
                 for k in kinds):
            # ints (+ bools: Python sets treat True == 1, and so does the
            # int64 cast); bigints overflow → scalar fallback
            try:
                vals, inv = np.unique(
                    np.array(arr.tolist(), dtype=np.int64),
                    return_inverse=True)
            except (OverflowError, ValueError, TypeError):
                _count("factorize_fallback")
                return None
            dic = vals.astype(object)
        elif all(issubclass(k, (int, float, np.integer, np.floating,
                                np.bool_)) for k in kinds):
            # mixed numerics: float64 compare matches Python == up to
            # 2^53; NaN payloads keep set-identity semantics → fall back
            flt = np.array([float(v) for v in arr.tolist()])
            if np.isnan(flt).any() or (np.abs(flt) >= 2.0 ** 53).any():
                _count("factorize_fallback")
                return None
            vals, inv = np.unique(flt, return_inverse=True)
            dic = vals.astype(object)
        else:
            _count("factorize_fallback")
            return None
        return Factorization(inv.astype(np.int64).ravel(), dic, len(vals))


def combine_codes(parts: list[tuple[np.ndarray, int]]) -> tuple[np.ndarray,
                                                                int]:
    """Chain per-axis dense codes into one combined code:
    ((c0·d1 + c1)·d2 + c2)… — the same layout the segment kernels use.
    Falls back to re-densifying via np.unique when the cardinality
    product would overflow int64."""
    codes = None
    dim = 1
    for c, d in parts:
        d = max(int(d), 1)
        if codes is None:
            codes, dim = c.astype(np.int64), d
            continue
        if dim > (2 ** 62) // max(d, 1):
            # re-densify the prefix before the product overflows
            uniq, codes = np.unique(codes, return_inverse=True)
            codes = codes.astype(np.int64)
            dim = len(uniq)
        codes = codes * d + c
        dim = dim * d
    if codes is None:
        return np.zeros(0, dtype=np.int64), 1
    return codes, dim


# ---------------------------------------------------------------------------
# sort-based DISTINCT
# ---------------------------------------------------------------------------
def distinct_pairs(gid: np.ndarray, vcodes: np.ndarray,
                   n_values: int) -> np.ndarray:
    """Sorted unique (group, value) pair codes: pair = gid·n_values + vc.
    This is the DISTINCT partial — mergeable across batches/shards by
    concatenate + unique (parallel.distributed_agg.merge_distinct_pairs)."""
    nv = max(int(n_values), 1)
    return np.unique(gid.astype(np.int64) * nv + vcodes)


def distinct_count(gid: np.ndarray, values: np.ndarray,
                   n_groups: int) -> np.ndarray | None:
    """count(DISTINCT values) per group — sort-based, no per-row sets.
    `values` must already be filtered to valid (non-NULL) rows aligned
    with `gid`. Returns None when the payload defeats factorization
    (caller keeps its scalar fold)."""
    f = factorize(values)
    if f is None:
        _count("distinct_fallback")
        return None
    if device_enabled() and len(gid) >= 65536:
        out = _device_distinct_count(gid, f.codes, n_groups, f.n_values)
        if out is not None:
            _count("distinct_device")
            return out
    pairs = distinct_pairs(gid, f.codes, f.n_values)
    out = np.bincount((pairs // max(f.n_values, 1)).astype(np.int64),
                      minlength=n_groups).astype(np.int64)
    _count("distinct_sort")
    return out[:n_groups]


# ---------------------------------------------------------------------------
# vectorized min / max (incl. object columns via the sorted-dictionary
# invariant: code order == value order)
# ---------------------------------------------------------------------------
def group_min_max(func: str, gid: np.ndarray, values: np.ndarray,
                  n_groups: int) -> tuple[np.ndarray, np.ndarray] | None:
    """→ (per-group result, filled mask) or None (unfactorizable object
    payload). `values` pre-filtered to valid rows aligned with gid."""
    filled = np.bincount(gid, minlength=n_groups) > 0 if len(gid) \
        else np.zeros(n_groups, dtype=bool)
    if values.dtype == object:
        f = factorize(values)
        if f is None:
            return None
        red = np.minimum if func == "min" else np.maximum
        init = f.n_values if func == "min" else -1
        best = np.full(n_groups, init, dtype=np.int64)
        red.at(best, gid, f.codes)
        out = np.full(n_groups, None, dtype=object)
        ok = filled & (best >= 0) & (best < f.n_values)
        if ok.any():
            out[ok] = f.values[best[ok]]
        return out, filled
    if np.issubdtype(values.dtype, np.floating):
        init = np.inf if func == "min" else -np.inf
        best = np.full(n_groups, init, dtype=values.dtype)
    elif values.dtype == bool:
        return group_min_max(func, gid, values.astype(np.int64), n_groups)
    else:
        info = np.iinfo(values.dtype)
        best = np.full(n_groups, info.max if func == "min" else info.min,
                       dtype=values.dtype)
    red = np.minimum if func == "min" else np.maximum
    red.at(best, gid, values)
    return best, filled


# ---------------------------------------------------------------------------
# bulk per-group slicing (collect / collect_ts / collect2)
# ---------------------------------------------------------------------------
def grouped_order(gid: np.ndarray) -> tuple[np.ndarray, np.ndarray,
                                            np.ndarray]:
    """→ (order, boundaries, group_code_per_run): a stable argsort of the
    group codes plus run boundaries, so callers slice each group's rows
    in bulk (arr[order[s:e]]) instead of appending row by row."""
    order = np.argsort(gid, kind="stable")
    sg = gid[order]
    if not len(sg):
        return order, np.zeros(1, dtype=np.int64), sg
    starts = np.nonzero(np.concatenate((
        [True], sg[1:] != sg[:-1])))[0]
    bounds = np.append(starts, len(sg)).astype(np.int64)
    return order, bounds, sg[starts]


# ---------------------------------------------------------------------------
# device path: jax segment-sum-family kernels over the same dense codes
# ---------------------------------------------------------------------------
def device_enabled() -> bool:
    """Route large dense-coded reductions through the jax segment kernels?
    Default: only on a real accelerator scan device (XLA's CPU scatter
    lowering loses to numpy); CNOSDB_TPU_GROUP_AGG=1 forces on (CI runs
    the device code on the CPU backend), =0 forces off."""
    import os

    mode = os.environ.get("CNOSDB_TPU_GROUP_AGG", "auto").lower()
    if mode in ("1", "on", "true"):
        return True
    if mode in ("0", "off", "false"):
        return False
    try:
        from .placement import scan_device

        return scan_device().platform == "tpu"
    except Exception:
        return False


def _device_distinct_count(gid: np.ndarray, vcodes: np.ndarray,
                           n_groups: int, n_values: int,
                           chunk_rows: int = 1 << 22) -> np.ndarray | None:
    """Sort-based DISTINCT on the accelerator: per chunk the device sorts
    the (group, value) pair codes (ops/kernels.segment_distinct_count for
    the single-chunk case); multi-chunk/multi-shard partial pairs merge
    host-side (parallel.distributed_agg.merge_distinct_pairs) so the
    on-wire partial shape is the plain sorted pair-code array."""
    try:
        from . import kernels
        from ..parallel.distributed_agg import merge_distinct_pairs

        nv = max(int(n_values), 1)
        n = len(gid)
        if n == 0:
            return np.zeros(n_groups, dtype=np.int64)
        if n <= chunk_rows:
            # segment_distinct_count already materializes host i64 counts
            # sliced to n_groups — re-wrapping it was a second copy
            return kernels.segment_distinct_count(gid, vcodes, n_groups, nv)
        chunks = []
        for off in range(0, n, chunk_rows):
            e = min(off + chunk_rows, n)
            chunks.append(kernels.sorted_pair_codes(
                gid[off:e], vcodes[off:e], nv))
        return merge_distinct_pairs(chunks, nv, n_groups)
    except Exception:
        _count("distinct_device_error")
        return None


def device_segment_reduce(values: np.ndarray, valid: np.ndarray,
                          seg_ids: np.ndarray, num_segments: int,
                          wants: dict) -> dict | None:
    """Dense-coded segment reductions (count/sum/min/max) through the
    jax.ops.segment_sum-family kernels with padded row/group counts —
    the TPU twin of the numpy reduceat path. Returns None when jax is
    unavailable so callers keep the host kernels."""
    try:
        from . import kernels

        return kernels.aggregate_column_host(
            values, valid, seg_ids.astype(np.int32),
            np.zeros(len(values), dtype=np.int32), num_segments, wants)
    except Exception:
        return None
