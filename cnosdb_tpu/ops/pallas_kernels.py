"""Pallas TPU kernel for the segment-aggregate hot op.

The framework's hottest program is the masked segment reduction behind
scan-fused GROUP BY (ops/kernels.local_segment_partials). XLA lowers
`segment_sum` through sort/scatter; this kernel exploits the STORAGE
LAYOUT instead: scan batches are series-contiguous and time-ordered, so
the `group × n_buckets + bucket` segment ids each row tile touches span a
narrow contiguous window. Every grid step reduces its row tile into a
LOCAL window of `W` segments relative to a per-tile base (one VPU-masked
pass over an [R, W] broadcast — VMEM-resident, no scatter), writing an
independent [W] output block per tile; a final O(tiles·W) XLA
segment-sum/min/max folds the windows into the global segment array
(tiles·W ≪ rows, so the combine is noise).

Preconditions checked by the host wrapper (`applicable`): every R-row
tile's segment span fits in W. Storage scans guarantee this by
construction except at series boundaries, which the window absorbs; the
wrapper falls back to the XLA kernel otherwise — same contract as
ops/placement choosing between device and host.

Integration (kernels.aggregate_column_host routes here): `enabled()`
reads CNOSDB_TPU_PALLAS — "1" forces the kernel on, "0" off, unset/auto
enables it only when the scan device is a real TPU. `decline_reason()`
is the per-call routing: first/last, a tile span past the window, and —
on a TPU — 64-bit values all go to the XLA kernel with the reason booked.
The last one is every numeric column the engine has today (f64/i64/u64):
XLA's 64-bit rewrite on TPU has no rule for a pallas_call operand
("UNIMPLEMENTED"), so on the chip the kernel compiles for f32/i32 only
(tests/test_chip_compile.py keeps that compile). Interpret mode exists
only off the TPU (`interpret_mode()`): tests and CNOSDB_TPU_PALLAS=1 on a
CPU backend; tests drive segment_partials_pallas with interpret=True
against the numpy_segment_partials oracle (tests/test_pallas_kernels.py).

Replaces the per-series reduction loop of the reference's reader tree
(tskv/src/reader/iterator.rs:94-121) on the device placement.
"""
from __future__ import annotations

import functools
import os

import numpy as np

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl

from . import program

R_TILE = 256     # rows per grid step
W_WIN = 2048     # local segment window (16 × 128-lane groups)


def enabled() -> bool:
    """Should aggregate_column_host route through this kernel?
    CNOSDB_TPU_PALLAS=1 forces on (interpret-mode on CPU backends), =0
    off; default: only on a real TPU scan device."""
    return disabled_reason() is None


def disabled_reason() -> str | None:
    """None when the kernel is usable, else WHY it is not (env override
    vs no TPU) — stamped into every query profile's device telemetry."""
    mode = os.environ.get("CNOSDB_TPU_PALLAS", "auto").lower()
    if mode in ("1", "on", "true"):
        return None
    if mode in ("0", "off", "false"):
        return f"disabled by env CNOSDB_TPU_PALLAS={mode}"
    from .placement import scan_device

    platform = scan_device().platform
    if platform != "tpu":
        return f"scan device is {platform!r}, not tpu (auto mode)"
    return None


def interpret_mode() -> bool:
    """Pallas kernels compile for the chip on a TPU, always; interpret
    mode is what a CPU backend runs them in (tests, CNOSDB_TPU_PALLAS=1)."""
    from .placement import scan_device

    return scan_device().platform != "tpu"


def decline_reason(dtype, wants: dict | None,
                   seg_ids: np.ndarray) -> str | None:
    """Why ONE aggregation cannot take the windowed kernel (None: it
    can). Checked before any padding copy or launch."""
    if wants and (wants.get("want_first") or wants.get("want_last")):
        return "first/last select by rank (XLA kernel)"
    if np.dtype(dtype).itemsize > 4 and not interpret_mode():
        return ("64-bit values: a pallas_call operand is unimplemented in "
                "the TPU compiler's 64-bit rewrite")
    if applicable(seg_ids) is None:
        return "a row tile's segment span exceeds the window"
    return None


def _extrema(dtype):
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.array(jnp.inf, dtype), jnp.array(-jnp.inf, dtype)
    info = jnp.iinfo(dtype)
    return jnp.array(info.max, dtype), jnp.array(info.min, dtype)


def _kernel(base_ref, values_ref, valid_ref, seg_ref,
            cnt_ref, sum_ref, min_ref, max_ref):
    """One row tile → [1, W] partials relative to this tile's window base.
    Rows ride the sublane axis ([R, 1] columns), window slots the lanes."""
    vals = values_ref[...]                      # [R, 1]
    seg = seg_ref[...] - base_ref[...]          # [R, 1] i32, in [0, W)
    # [R, W] membership mask: row r contributes to window slot seg[r]
    lanes = jax.lax.broadcasted_iota(jnp.int32, (R_TILE, W_WIN), 1)
    m = (seg == lanes) & (valid_ref[...] != 0)
    zero = jnp.zeros((), vals.dtype)
    hi, lo = _extrema(vals.dtype)
    # dtype= pins the accumulators: under x64 an i32 sum promotes to i64,
    # which the chip's kernel compiler does not take
    cnt_ref[...] = jnp.sum(m, axis=0, keepdims=True, dtype=jnp.int32)
    sum_ref[...] = jnp.sum(jnp.where(m, vals, zero), axis=0, keepdims=True,
                           dtype=vals.dtype)
    min_ref[...] = jnp.min(jnp.where(m, vals, hi), axis=0, keepdims=True)
    max_ref[...] = jnp.max(jnp.where(m, vals, lo), axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("num_segments", "interpret"))
@program("segment_aggregate_pallas")
def _windowed_partials(bases, values, valid, seg_ids, *, num_segments: int,
                       interpret: bool = False):
    """values/valid/seg_ids padded to a tile multiple; bases[t] = window
    base of tile t (padded rows carry valid=False, seg inside the tile's
    window)."""
    n = values.shape[0]
    tiles = n // R_TILE
    out_shape = [
        jax.ShapeDtypeStruct((tiles, 1, W_WIN), jnp.int32),    # count
        jax.ShapeDtypeStruct((tiles, 1, W_WIN), values.dtype),  # sum
        jax.ShapeDtypeStruct((tiles, 1, W_WIN), values.dtype),  # min
        jax.ShapeDtypeStruct((tiles, 1, W_WIN), values.dtype),  # max
    ]
    # Block shapes follow the chip's (8, 128) rule: the last two dims of
    # every block are a multiple of (8, 128) or the array's own. Index
    # maps return i32 explicitly — under x64 a literal 0 is an i64, which
    # Mosaic cannot legalize beside the i32 grid index.
    def at(rank):
        return lambda t: (t,) + (jnp.int32(0),) * (rank - 1)

    row_spec = pl.BlockSpec((R_TILE, 1), at(2))
    win_spec = pl.BlockSpec((None, 1, W_WIN), at(3))
    base_spec = pl.BlockSpec((None, 1, 1), at(3))
    cnt, s, mn, mx = pl.pallas_call(
        _kernel,
        grid=(tiles,),
        in_specs=[base_spec, row_spec, row_spec, row_spec],
        out_specs=[win_spec, win_spec, win_spec, win_spec],
        out_shape=out_shape,
        interpret=interpret,
    )(bases.reshape(-1, 1, 1), values.reshape(-1, 1),
      valid.astype(jnp.int32).reshape(-1, 1), seg_ids.reshape(-1, 1))

    # fold tile windows into global segments: tiny combine, plain XLA.
    # Window slots past num_segments-1 clip onto the last segment carrying
    # only identity values (count/sum 0, min/max extrema) — harmless.
    gids = (bases[:, None] + jnp.arange(W_WIN, dtype=jnp.int32)[None, :])
    gids = jnp.clip(gids.reshape(-1), 0, num_segments - 1)
    out = {
        "count": jax.ops.segment_sum(cnt.reshape(-1), gids, num_segments),
        "sum": jax.ops.segment_sum(s.reshape(-1), gids, num_segments),
        "min": jax.ops.segment_min(mn.reshape(-1), gids, num_segments),
        "max": jax.ops.segment_max(mx.reshape(-1), gids, num_segments),
    }
    return out


def applicable(seg_ids: np.ndarray) -> np.ndarray | None:
    """Per-tile window bases when every tile's segment span fits W_WIN;
    None → caller uses the XLA kernel. Vectorized host check."""
    n = len(seg_ids)
    if n == 0:
        return None
    pad = (-n) % R_TILE
    s = np.pad(seg_ids, (0, pad), mode="edge").reshape(-1, R_TILE)
    lo = s.min(axis=1)
    hi = s.max(axis=1)
    if int((hi - lo).max()) >= W_WIN:
        return None
    return lo.astype(np.int32)


_WANT_OF = {"count": "want_count", "sum": "want_sum",
            "min": "want_min", "max": "want_max"}

_engagements = 0


def note_engaged() -> None:
    global _engagements
    _engagements += 1
    from ..utils import stages

    stages.count("pallas_engagements")


def note_declined(reason: str) -> None:
    """Book one aggregation routed to the XLA kernel instead, and why."""
    from ..utils import stages

    stages.count("pallas_declined")
    prof = stages.current_profile()
    if prof is not None:
        prof.device["pallas_declined_reason"] = reason


def engagements() -> int:
    """How many aggregations ran through a pallas kernel this process."""
    return _engagements


def segment_partials_pallas(values: np.ndarray, valid: np.ndarray,
                            seg_ids: np.ndarray, num_segments: int,
                            wants: dict | None = None,
                            interpret: bool = False) -> dict | None:
    """Host wrapper: pad to a tile multiple, run the kernel, fold windows
    into global segments. Returns None when the layout disqualifies
    (`applicable`) or when `wants` asks for first/last (rank selection
    stays on the XLA kernel). Output follows
    the XLA kernel's conventions: empty segments carry count 0, sum 0 and
    dtype-extrema min/max sentinels; `wants` (same keys as
    local_segment_partials) subsets the returned aggregates."""
    if wants and (wants.get("want_first") or wants.get("want_last")):
        return None
    seg_ids = np.asarray(seg_ids)
    bases = applicable(seg_ids)
    if bases is None:
        return None
    n = len(values)
    pad = (-n) % R_TILE
    if pad:
        values = np.concatenate([values, np.zeros(pad, values.dtype)])
        valid = np.concatenate([valid, np.zeros(pad, bool)])
        seg_ids = np.concatenate(
            [seg_ids, np.full(pad, seg_ids[-1], seg_ids.dtype)])
    out = _windowed_partials(
        jnp.asarray(bases), jnp.asarray(values), jnp.asarray(valid),
        jnp.asarray(seg_ids, dtype=jnp.int32),
        num_segments=num_segments, interpret=interpret)
    host = {k: np.asarray(v) for k, v in out.items()}  # lint: disable=host-sync (audited transfer point: one batched pull per pallas window call)
    if wants is not None:
        host = {k: v for k, v in host.items() if wants.get(_WANT_OF[k])}
    return host
