"""Device-resident scan block cache.

The reference keeps hot TSM pages in a host LRU (tskv/src/tsfamily/
version.rs TsmReader cache). On TPU the equivalent — and the dominant
performance lever, since host↔device transfer is the bottleneck — is
keeping decoded scan columns resident in HBM: a ScanBatch ships to the
device ONCE (timestamps, series ordinals, field columns + validity; the
time-order rank when a first/last query first asks for it), and every
subsequent query against the same batch runs entirely device-side
(bucket/segment computation included), transferring only group
parameters in and [num_segments] partials out.

Invalidation: ScanBatches are immutable snapshots; the device arrays are
attached to the batch object itself, and batches are cached per vnode
snapshot token upstream (coordinator scan cache), so a write/flush/
compaction naturally rotates both layers. Two pipeline hooks keep the
COLD path off the critical PCIe+decode sum:

  * EagerUploader — handed into storage/scan via `upload_hook`; each
    field column device_puts as soon as its pages finish decoding, so
    transfer overlaps the decode of the remaining columns. The staged
    arrays ride along on the batch (`_preuploaded`) and DeviceBatch
    reuses them instead of re-staging.
  * merged_device_batch — after a delta rescan merged into a cached
    batch (coordinator delta path), the merged twin is built by GATHERING
    the unchanged columns from the cached twin on device; only the delta
    rows cross the wire.
"""
from __future__ import annotations

import numpy as np

import jax

from ..models.schema import ValueType
from ..storage import native
from ..utils import lockwatch, stages
from .kernels import pad_rows

# live device uploads, weakly held — the broker's device_uploads pool
# reads estimated resident bytes from here; no reclaim callback (device
# buffers die with their scan batch, evicting mid-query would corrupt
# the kernels referencing them)
import weakref as _weakref

_LIVE_BATCHES: "_weakref.WeakSet" = _weakref.WeakSet()


def device_bytes_used() -> int:
    return sum(getattr(b, "est_bytes", 0) for b in list(_LIVE_BATCHES))


def _register_device_pool() -> None:
    from ..server import memory as _memory

    _memory.register_pool("device_uploads", usage_fn=device_bytes_used)


_register_device_pool()


def _device_resident(vt: ValueType) -> bool:
    """Which columns get a device twin: numeric ones the device holds
    exactly (placement.exact_on_device). Strings aggregate host-side."""
    from .placement import exact_on_device

    return vt not in (ValueType.STRING, ValueType.GEOMETRY) \
        and exact_on_device(vt)


class DeviceBatch:
    """Padded, device-resident columns of one ScanBatch.

    Timestamps are stored as int32 (seconds, ns-remainder) pairs relative
    to the batch epoch — 64-bit integer/float arithmetic is software-
    emulated on TPU (measured ~1000× slower than i32 for division), so the
    device NEVER touches an i64 timestamp; bucket indices are derived from
    the i32 pair with exact integer math (see fused._bucket_arith). A span
    the i32 seconds cannot hold never gets here (`launch_scan_aggregate`
    keeps it on the host).

    The meta is built for the program that takes it: the ns remainder is
    materialised and put only where one is non-zero, and the first/last
    time-order rank is sorted, put and kept where `rank_dev()` is first
    asked — a query whose aggregates are avg / sum / count / min / max
    never pays for it.
    """

    __slots__ = ("n_rows", "n_pad", "n_series", "epoch_ns", "ts_sec", "ts_ns",
                 "sid_ordinal", "rank", "in_rows", "fields", "ns_all_zero",
                 "field_all_valid", "_ts", "_rank_np", "_rank_lock",
                 "series_params", "est_bytes", "__weakref__")

    def __init__(self, batch, n_threads: int = 1):
        with stages.stage("upload_ms"):
            self._init_meta(batch, n_threads)
            pre = getattr(batch, "_preuploaded", None)
            pre_cols = pre[1] if pre is not None and pre[0] == self.n_pad \
                else {}
            for name, (vt, vals, valid) in batch.fields.items():
                if not _device_resident(vt):
                    continue
                p = pre_cols.get(name)
                if p is not None and p[0] == vt:
                    # column staged by the scan's eager-upload pipeline
                    _vt, dev_vals, dev_valid, all_valid = p
                    self.field_all_valid[name] = all_valid
                    self.fields[name] = (vt, dev_vals, dev_valid)
                    continue
                dev_vals, dev_valid, all_valid = _put_column(
                    vt, vals, valid, self.n_pad)
                self.field_all_valid[name] = all_valid
                self.fields[name] = (vt, dev_vals, dev_valid)
            self.est_bytes = self._estimate_bytes()
            _LIVE_BATCHES.add(self)

    def _init_meta(self, batch, n_threads: int = 1):
        """Everything except the field columns: row counts, the i32
        timestamp pair, series ordinals; the rank waits for `rank_dev()`.
        `n_threads` sizes the native split's pool."""
        n = batch.n_rows
        self.n_rows = n
        self.n_pad = pad_rows(max(n, 1))
        self.n_series = batch.n_series
        with stages.stage("upload.meta_ms"):
            # the snapshot's one pair: `_bucket_geometry` derives the
            # launch's bucket constants from the same epoch
            self.epoch_ns = batch.ts_minmax()[0]
            # an optional input is skipped (static kernel flag) when
            # derivable — a buffer not passed is a buffer not uploaded or
            # kept in HBM: `ns` is None for second-aligned data
            sec, ns = _split_ts(batch.ts, self.epoch_ns, self.n_pad,
                                n_threads)
        stages.count("upload.rank_builds", 0)
        self.ns_all_zero = ns is None
        # Regular-series fast path: when every series is a contiguous run
        # with a constant whole-second stride (the normal telemetry shape),
        # ship ONLY [n_series, 3] params (row_start, sec0, stride_s); the
        # kernel reconstructs sid (searchsorted over row starts) and ts_sec
        # (sec0 + k*stride) — per-row timestamp/sid columns never cross the
        # wire or occupy HBM. This is TSM run-length structure carried onto
        # the device.
        self.series_params = None
        import os as _os

        # opt-in: reconstructing sid/ts_sec on device trades ~16MB of
        # transfer for extra gathers — a net loss on host XLA, not measured
        # on the chip
        if n and self.ns_all_zero and _os.environ.get(
                "CNOSDB_TPU_REGULAR", "0") == "1":
            with stages.stage("upload.meta_ms"):
                self.series_params = _regular_series_params(
                    batch.sid_ordinal, sec[:n], batch.n_series, self.n_pad)
        with stages.stage("upload.put_ms"):
            # both come out of the split padded: put as they lie
            self.ts_ns = None if ns is None else _put(ns)
            self.ts_sec = None if self.series_params is not None \
                else _put(sec)
        self.sid_ordinal = None if self.series_params is not None \
            else _put_padded(batch.sid_ordinal, self.n_pad)
        # in_rows derives from iota < n_rows inside the kernel (no buffer)
        self.in_rows = None
        # globally unique time-order rank (first/last selection key): only
        # first/last kernels reference it, so `rank_dev()` builds it from
        # the batch's `ts` (the ScanBatch owns both; the twin dies with it)
        self._ts = batch.ts
        self._rank_np = None
        self._rank_lock = lockwatch.Lock("device_cache.rank")
        self.rank = None
        self.fields: dict[str, tuple[ValueType, object, object]] = {}
        self.field_all_valid: dict[str, bool] = {}

    def _estimate_bytes(self) -> int:
        """Resident device-buffer bytes (feeds the broker's
        device_uploads pool; estimate only — the broker never reclaims
        uploads, they die with their scan batch)."""
        total = 0
        for a in (self.ts_sec, self.ts_ns, self.sid_ordinal, self.rank,
                  self.series_params):
            total += int(getattr(a, "nbytes", 0) or 0)
        for _vt, dev_vals, dev_valid in self.fields.values():
            total += int(getattr(dev_vals, "nbytes", 0) or 0)
            total += int(getattr(dev_valid, "nbytes", 0) or 0)
        return total

    def rank_dev(self):
        """The device's time-order rank, built by the first first/last
        query of the batch and kept: the sort is `upload.rank_ms` inside
        `upload.meta_ms`, its pad and put `upload.stage_ms` and
        `upload.put_ms`, all inside `upload_ms`. Queries that ask at the
        same moment get the one array: the sort runs once under the
        batch's lock; the put — device dispatch, so outside it — may be
        raced, and the first one is published."""
        if self.rank is None:
            with stages.stage("upload_ms"):
                with self._rank_lock:
                    if self._rank_np is None:
                        with stages.stage("upload.meta_ms"), \
                                stages.stage("upload.rank_ms"):
                            self._rank_np = _time_rank(self._ts)
                        stages.count("upload.rank_builds")
                rank = _put_padded(self._rank_np, self.n_pad)
            with self._rank_lock:
                if self.rank is None:
                    self.est_bytes += int(getattr(rank, "nbytes", 0) or 0)
                    self.rank = rank
        return self.rank


class EagerUploader:
    """Receives finished scan columns from storage/scan's decode pipeline
    and stages them on device immediately (device_put enqueues are async,
    so the transfer of column N overlaps the decode of column N+1). The
    staged columns attach to the ScanBatch as `_preuploaded`, which
    DeviceBatch.__init__ consumes instead of re-staging. Failures are
    swallowed (counted) — the batch then just uploads lazily as before."""

    def __init__(self, n_rows: int):
        self.n_pad = pad_rows(max(n_rows, 1))
        self._cols: dict = {}

    def put(self, name: str, vt: ValueType, vals: np.ndarray,
            valid: np.ndarray):
        if not _device_resident(vt):
            return
        try:
            with stages.stage("upload_ms"):
                self._cols[name] = (
                    vt, *_put_column(vt, vals, valid, self.n_pad))
        except Exception:
            stages.count_error("scan.eager_upload")

    def attach(self, batch):
        if self._cols:
            batch._preuploaded = (self.n_pad, self._cols)


def merged_device_batch(merged, cached, delta, append_gather: np.ndarray,
                        n_threads: int = 1) -> "DeviceBatch | None":
    """Build the device twin of a delta-merged batch by gathering the
    unchanged rows from the cached twin ON DEVICE — the cached field
    columns never re-cross the host↔device pipe; only the (small) delta
    rows upload. Only valid for the pure-append merge shape
    (`append_gather` from merge_scan_batches): with duplicate (sid, ts)
    groups, each field picks its winner independently and one shared
    row-gather would be wrong — callers fall back to a lazy full build.

    The i32 timestamp pair / ordinals rebuild on host (`_init_meta`, as
    for any batch; the rank waits for a first/last query); → the attached
    DeviceBatch, or None when the cached twin is missing or shaped
    incompatibly."""
    old = getattr(cached, "_device_batch", None)
    if old is None or old.series_params is not None:
        return None
    import jax.numpy as jnp

    with stages.stage("upload_ms"):
        n_c, n_d = cached.n_rows, delta.n_rows
        db = DeviceBatch.__new__(DeviceBatch)
        db._init_meta(merged, n_threads)
        # gather index into [cached rows | delta rows | zero sentinel];
        # pad rows hit the sentinel so they read (0, invalid) regardless
        # of kernel-side pad masking
        sent = n_c + n_d
        g = np.full(db.n_pad, sent, dtype=np.int32)
        g[:merged.n_rows] = append_gather
        with stages.stage("upload.put_ms"):
            g_dev = _put(g)
        pre = getattr(delta, "_preuploaded", None)
        pre_cols = pre[1] if pre is not None else {}
        for name, (vt, vals, valid) in merged.fields.items():
            if not _device_resident(vt):
                continue
            of = old.fields.get(name) if name in cached.fields else None
            if of is None or of[0] != vt or old.n_pad < n_c:
                # new/retyped column: plain upload of the merged array
                dev_vals, dev_valid, all_valid = _put_column(
                    vt, vals, valid, db.n_pad)
                db.field_all_valid[name] = all_valid
                db.fields[name] = (vt, dev_vals, dev_valid)
                continue
            _vt, old_vals, old_valid = of
            df = delta.fields.get(name)
            p = pre_cols.get(name)
            if p is not None and p[0] == vt and pre[0] >= n_d:
                d_vals_dev = p[1][:n_d]
                d_valid_dev = p[2][:n_d] if p[2] is not None else None
                d_all_valid = p[3]
            else:
                if df is not None:
                    d_vals = df[1] if vt != ValueType.BOOLEAN \
                        else df[1].astype(np.int64)
                    d_valid = df[2]
                else:   # field absent from the delta: all-invalid zeros
                    d_vals = np.zeros(
                        n_d, dtype=np.int64 if vt == ValueType.BOOLEAN
                        else vt.numpy_dtype())
                    d_valid = np.zeros(n_d, dtype=bool)
                d_all_valid = bool(d_valid.all())
                with stages.stage("upload.put_ms"):
                    d_vals_dev = _put(np.ascontiguousarray(d_vals))
                    d_valid_dev = None if d_all_valid \
                        else _put(np.ascontiguousarray(d_valid))
            zero = jnp.zeros(1, dtype=old_vals.dtype)
            cat = jnp.concatenate([old_vals[:n_c], d_vals_dev, zero])
            vals_dev = cat[g_dev]
            all_valid = bool(valid.all())
            db.field_all_valid[name] = all_valid
            if all_valid:
                valid_dev = None
            else:
                ov = old_valid[:n_c] if old_valid is not None \
                    else jnp.ones(n_c, dtype=bool)
                dv = d_valid_dev if d_valid_dev is not None \
                    else jnp.ones(n_d, dtype=bool)
                vcat = jnp.concatenate(
                    [ov, dv, jnp.zeros(1, dtype=bool)])
                valid_dev = vcat[g_dev]
            db.fields[name] = (vt, vals_dev, valid_dev)
        db.est_bytes = db._estimate_bytes()
        _LIVE_BATCHES.add(db)
        merged._device_batch = db
        return db


def _split_ts(ts: np.ndarray, epoch: int, n_pad: int,
              n_threads: int = 1) -> tuple[np.ndarray, np.ndarray | None]:
    """i64 ns timestamps → the i32 pair relative to `epoch`, each zero-
    padded to `n_pad` rows where the put reads it: (whole seconds, ns
    remainder | None where every remainder is 0). One native pass writes
    the seconds and says whether a remainder is non-zero; only then does a
    second materialise `ns`. Without the library the numpy expressions
    (eight whole-array passes) are the one fallback."""
    sec = np.empty(n_pad, dtype=np.int32)
    any_ns = native.split_ts_i32(ts, epoch, sec, None, n_threads)
    if any_ns is None:
        return _split_ts_numpy(ts, epoch, n_pad)
    if not any_ns:
        return sec, None
    ns = np.empty(n_pad, dtype=np.int32)
    native.split_ts_i32(ts, epoch, None, ns, n_threads)
    return sec, ns


def _split_ts_numpy(ts: np.ndarray, epoch: int, n_pad: int):
    rel = ts - epoch
    sec = (rel // 1_000_000_000).astype(np.int32)
    ns = (rel - sec.astype(np.int64) * 1_000_000_000).astype(np.int32)
    return _pad_to(sec, n_pad, 0), \
        _pad_to(ns, n_pad, 0) if ns.any() else None


def _time_rank(ts: np.ndarray) -> np.ndarray:
    """Globally unique i32 time-order rank of every row: position in the
    stable sort by timestamp, so ties between series break by row order."""
    n = len(ts)
    order = np.argsort(ts, kind="stable")
    rank = np.empty(n, dtype=np.int32)
    rank[order] = np.arange(n, dtype=np.int32)
    return rank


def _regular_series_params(sid_ordinal: np.ndarray, sec: np.ndarray,
                           n_series: int, n_pad: int) -> np.ndarray | None:
    """→ [n_series, 3] i32 (row_start, sec0, stride_s) when the batch is
    series-major with one contiguous, constant-whole-second-stride run per
    series; else None."""
    n = len(sid_ordinal)
    if n == 0 or n_series == 0:
        return None
    # series-major check: sid non-decreasing and covers 0..n_series-1
    d = np.diff(sid_ordinal)
    if (d < 0).any():
        return None
    starts = np.nonzero(np.concatenate(([True], d > 0)))[0]
    if len(starts) != n_series:
        return None
    ends = np.concatenate((starts[1:], [n]))
    params = np.empty((n_series, 3), dtype=np.int32)
    for s, (a, b) in enumerate(zip(starts, ends)):
        seg = sec[a:b]
        if len(seg) > 1:
            ds = np.diff(seg)
            stride = ds[0]
            if stride <= 0 or (ds != stride).any():
                return None
        else:
            stride = 1
        params[s] = (a, seg[0], stride)
    return params


def _put_column(vt: ValueType, vals: np.ndarray, valid: np.ndarray,
                n_pad: int):
    """One field column → (device values, device validity | None,
    all_valid): the one staging of a column, shared by DeviceBatch, the
    eager uploader and the delta merge. The host copies (a BOOLEAN's
    widening, `valid.all()`, the pads) are `upload.stage_ms`, the puts
    — values first — `upload.put_ms`; validity is neither padded nor
    shipped where every row is valid."""
    with stages.stage("upload.stage_ms"):
        if vt == ValueType.BOOLEAN:
            vals = vals.astype(np.int64)
        all_valid = bool(valid.all())
        host = [_pad_to(vals, n_pad, 0)]
        if not all_valid:
            host.append(_pad_to(valid, n_pad, False))
    with stages.stage("upload.put_ms"):
        dev = [_put(a) for a in host]
    return dev[0], None if all_valid else dev[1], all_valid


def _put_padded(a: np.ndarray, n_pad: int):
    """One per-row i32 array of the batch's meta (series ordinals, the
    rank) → its zero-padded device twin, split like a column's."""
    with stages.stage("upload.stage_ms"):
        host = _pad_to(a, n_pad, 0)
    with stages.stage("upload.put_ms"):
        return _put(host)


def _pad_to(a: np.ndarray, n: int, fill) -> np.ndarray:
    if len(a) == n:
        return np.ascontiguousarray(a)
    out = np.full(n, fill, dtype=a.dtype)
    out[:len(a)] = a
    return out


def _put(a: np.ndarray):
    from ..utils import stages
    from .placement import scan_device

    stages.count("upload_bytes", int(getattr(a, "nbytes", 0)))
    return jax.device_put(a, scan_device())


def put_sharded(a: np.ndarray, mesh, spec):
    """Upload one host array laid out for the execution mesh: rows split
    over the named shard axis per `spec` (a PartitionSpec). The mesh exec
    lane (ops/mesh_exec.py) stages every operand through here so sharded
    uploads book the same `upload_bytes` the single-device path does."""
    from jax.sharding import NamedSharding

    stages.count("upload_bytes", int(getattr(a, "nbytes", 0)))
    return jax.device_put(a, NamedSharding(mesh, spec))


def device_batch(batch, n_threads: int = 1) -> DeviceBatch:
    """Get-or-build the device twin of a ScanBatch (attached to it);
    `upload.twin_builds` books 1 where it is built, 0 where the batch's
    twin is handed back."""
    db = getattr(batch, "_device_batch", None)
    stages.count("upload.twin_builds", int(db is None))
    if db is None:
        db = DeviceBatch(batch, n_threads)
        batch._device_batch = db
    return db
