"""ctypes bindings for the native codec library (native/codecs.cpp).

Loads cnosdb_tpu/_native/libcnosdb_codecs.so when present (built via
`make -C native`; auto-built on first import when a compiler is around) and
exposes fused decode kernels; storage.codecs falls back to the vectorized
numpy pipeline when unavailable, so the package works without a toolchain.
"""
from __future__ import annotations

import ctypes
import os
import subprocess

import numpy as np

import threading
from ..utils import lockwatch

_LIB = None
_TRIED = False
_LOAD_LOCK = lockwatch.Lock("native.load")
_tls = threading.local()


def _lib_path() -> str:
    override = os.environ.get("CNOSDB_NATIVE_LIB")
    if override:
        return override   # e.g. the ASAN build in tests
    return os.path.join(os.path.dirname(os.path.dirname(__file__)),
                        "_native", "libcnosdb_codecs.so")


def _try_build() -> bool:
    native_dir = os.path.join(
        os.path.dirname(os.path.dirname(os.path.dirname(__file__))), "native")
    if not os.path.isdir(native_dir):
        return False
    try:
        subprocess.run(["make", "-C", native_dir], check=True,
                       capture_output=True, timeout=120)
        return os.path.exists(_lib_path())
    except Exception:
        return False


def get_lib():
    global _LIB, _TRIED
    if _LIB is not None or _TRIED:
        return _LIB
    with _LOAD_LOCK:
        return _get_lib_locked()


def _get_lib_locked():
    global _LIB, _TRIED
    if _LIB is not None or _TRIED:
        return _LIB
    _TRIED = True
    if os.environ.get("CNOSDB_NO_NATIVE"):
        return None
    path = _lib_path()
    if not os.path.exists(path):
        if not _try_build():
            return None
    try:
        lib = ctypes.CDLL(path)
        lib.decode_delta_i64.restype = ctypes.c_int
        lib.decode_delta_i64.argtypes = [
            ctypes.c_char_p, ctypes.c_size_t, ctypes.c_int, ctypes.c_int64,
            ctypes.POINTER(ctypes.c_int64), ctypes.c_size_t,
            ctypes.POINTER(ctypes.c_uint8), ctypes.c_size_t]
        lib.decode_xor_f64.restype = ctypes.c_int
        lib.decode_xor_f64.argtypes = [
            ctypes.c_char_p, ctypes.c_size_t,
            ctypes.POINTER(ctypes.c_uint64), ctypes.c_size_t,
            ctypes.POINTER(ctypes.c_uint8), ctypes.c_size_t]
        lib.version.restype = ctypes.c_int
        if lib.version() != 1:
            return None
        if hasattr(lib, "encode_delta_i64"):
            lib.encode_delta_i64.restype = ctypes.c_int
            lib.encode_delta_i64.argtypes = [
                ctypes.POINTER(ctypes.c_int64), ctypes.c_size_t,
                ctypes.POINTER(ctypes.c_uint8), ctypes.c_size_t]
        if hasattr(lib, "encode_xor_transpose_f64"):
            lib.encode_xor_transpose_f64.restype = None
            lib.encode_xor_transpose_f64.argtypes = [
                ctypes.POINTER(ctypes.c_uint64), ctypes.c_size_t,
                ctypes.POINTER(ctypes.c_uint8)]
        if hasattr(lib, "decode_pages"):
            lib.decode_pages.restype = ctypes.c_int
            lib.decode_pages.argtypes = [
                ctypes.c_void_p, ctypes.c_size_t,   # base, base_len
                ctypes.POINTER(ctypes.c_int64),     # desc (n_pages × 6)
                ctypes.c_int64,                     # n_pages
                ctypes.c_void_p, ctypes.c_void_p,   # out_vals, out_valid
                ctypes.c_int64,                     # out_rows capacity
                ctypes.c_int, ctypes.c_int,         # check_crc, n_threads
                ctypes.POINTER(ctypes.c_int32)]     # out_status
        if hasattr(lib, "fused_seg_agg_f64"):
            lib.fused_seg_agg_f64.restype = ctypes.c_int
            lib.fused_seg_agg_f64.argtypes = [
                ctypes.POINTER(ctypes.c_int64),    # ts
                ctypes.POINTER(ctypes.c_int32),    # sid_ord
                ctypes.POINTER(ctypes.c_int64),    # group_lut
                ctypes.c_int64,                    # n_rows
                ctypes.c_int64, ctypes.c_int64,    # origin, interval
                ctypes.c_int64, ctypes.c_int64,    # bmin, n_buckets
                ctypes.c_void_p,                   # vals (f64 or null)
                ctypes.c_void_p,                   # valid (u8 or null)
                ctypes.c_void_p,                   # row_mask
                ctypes.c_int64,                    # num_segments
                ctypes.c_void_p, ctypes.c_void_p,  # presence, count
                ctypes.c_void_p, ctypes.c_void_p,  # sum, min
                ctypes.c_void_p, ctypes.c_void_p,  # max, out_seg
                ctypes.c_void_p, ctypes.c_void_p,  # first, first_ts
                ctypes.c_void_p, ctypes.c_void_p,  # last, last_ts
                ctypes.c_int]                      # n_threads
        if hasattr(lib, "split_ts_i32"):
            lib.split_ts_i32.restype = ctypes.c_int
            lib.split_ts_i32.argtypes = [
                ctypes.c_void_p, ctypes.c_int64,   # ts, n
                ctypes.c_int64,                    # epoch
                ctypes.c_void_p, ctypes.c_void_p,  # out_sec, out_ns
                ctypes.c_int64, ctypes.c_int]      # n_pad, n_threads
        _LIB = lib
    except OSError:
        _LIB = None
    return _LIB


def available() -> bool:
    return get_lib() is not None


def _get_scratch(size: int) -> np.ndarray:
    """Per-thread scratch: decodes run concurrently (query pool + the
    background compaction worker), a shared buffer would corrupt both."""
    buf = getattr(_tls, "scratch", None)
    if buf is None or len(buf) < size:
        buf = _tls.scratch = np.empty(max(size, 1 << 20), dtype=np.uint8)
    return buf


def decode_delta_i64(comp: bytes, width: int, first: int, n: int) -> np.ndarray | None:
    lib = get_lib()
    if lib is None:
        return None
    out = np.empty(n, dtype=np.int64)
    scratch = _get_scratch((n - 1) * width if n > 1 else 1)
    rc = lib.decode_delta_i64(
        comp, len(comp), width, first,
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)), n,
        scratch.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)), len(scratch))
    return out if rc == 0 else None


def encode_delta_i64(values: np.ndarray) -> tuple[int, np.ndarray] | None:
    """Fused width-scan + zigzag-delta encode; returns (width, raw bytes of
    (n-1)*width) or None (unavailable / n<2 handled by caller)."""
    lib = get_lib()
    if lib is None or not hasattr(lib, "encode_delta_i64"):
        return None
    n = len(values)
    v = np.ascontiguousarray(values, dtype=np.int64)
    out = np.empty(max((n - 1) * 8, 1), dtype=np.uint8)
    width = lib.encode_delta_i64(
        v.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)), n,
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)), len(out))
    if width <= 0:
        return None
    return width, out[: (n - 1) * width]


def encode_xor_transpose_f64(values: np.ndarray) -> np.ndarray | None:
    """XOR-with-previous + byte-plane transpose in one native pass; returns
    the n*8 transposed bytes ready for zstd, or None when unavailable."""
    lib = get_lib()
    if lib is None or not hasattr(lib, "encode_xor_transpose_f64"):
        return None
    v = np.ascontiguousarray(values).view(np.uint64)
    out = np.empty(len(v) * 8, dtype=np.uint8)
    lib.encode_xor_transpose_f64(
        v.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)), len(v),
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)))
    return out


def fused_seg_agg_f64(ts, sid_ord, group_lut, origin, interval, bmin,
                      n_buckets, vals, valid, row_mask, num_segments,
                      wants: dict, out_seg: bool = False,
                      n_threads: int = 8):
    """One-pass segment partials (native/segagg.cpp) — presence always;
    count/sum/min/max of `vals` per `wants`. → dict of arrays (plus
    'seg' when out_seg) or None when the library / shape is unavailable
    or a segment falls out of range."""
    lib = get_lib()
    if lib is None or not hasattr(lib, "fused_seg_agg_f64"):
        return None
    n = len(ts)

    def p64(a):
        return a.ctypes.data_as(ctypes.POINTER(ctypes.c_int64))

    def voidp(a):
        return a.ctypes.data if a is not None else None

    presence = np.zeros(num_segments, dtype=np.int64)
    count = np.zeros(num_segments, dtype=np.int64) \
        if (wants.get("want_count") or wants.get("want_sum")) else None
    sum_ = np.zeros(num_segments, dtype=np.float64) \
        if wants.get("want_sum") else None
    mn = np.zeros(num_segments, dtype=np.float64) \
        if wants.get("want_min") else None
    mx = np.zeros(num_segments, dtype=np.float64) \
        if wants.get("want_max") else None
    first = np.zeros(num_segments, dtype=np.float64) \
        if wants.get("want_first") else None
    first_ts = np.zeros(num_segments, dtype=np.int64) \
        if first is not None else None
    last = np.zeros(num_segments, dtype=np.float64) \
        if wants.get("want_last") else None
    last_ts = np.zeros(num_segments, dtype=np.int64) \
        if last is not None else None
    seg = np.empty(n, dtype=np.int64) if out_seg else None
    rc = lib.fused_seg_agg_f64(
        p64(ts), sid_ord.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        p64(group_lut), n, origin, interval, bmin, n_buckets,
        voidp(vals), voidp(valid), voidp(row_mask), num_segments,
        voidp(presence), voidp(count), voidp(sum_), voidp(mn), voidp(mx),
        voidp(seg), voidp(first), voidp(first_ts), voidp(last),
        voidp(last_ts), n_threads)
    if rc != 0:
        return None
    out = {"presence": presence}
    if count is not None:
        out["count"] = count
    if sum_ is not None:
        out["sum"] = sum_
    if mn is not None:
        out["min"] = mn
    if mx is not None:
        out["max"] = mx
    if first is not None:
        out["first"] = first
        out["first_ts"] = first_ts
    if last is not None:
        out["last"] = last
        out["last_ts"] = last_ts
    if seg is not None:
        out["seg"] = seg
    return out


def pagedec_available() -> bool:
    lib = get_lib()
    return lib is not None and hasattr(lib, "decode_pages")


def decode_pages(base: np.ndarray, desc: np.ndarray,
                 out_vals: np.ndarray, out_valid: np.ndarray | None,
                 check_crc: bool = True,
                 n_threads: int = 1) -> np.ndarray | None:
    """Batch-decode TSM pages from one mmap'd file (native/pagedec.cpp).

    base: u8 view over the whole file; desc: (n_pages, 6) i64 page
    descriptors [src_off, src_size, out_off, n_rows, kind, n_values];
    out_vals/out_valid: preallocated columns the pages decode into.
    → per-page status array (0 = decoded; nonzero = caller must decode
    that page via the Python path), or None when unavailable.
    """
    lib = get_lib()
    if lib is None or not hasattr(lib, "decode_pages"):
        return None
    desc = np.ascontiguousarray(desc, dtype=np.int64)
    n_pages = len(desc)
    status = np.empty(n_pages, dtype=np.int32)
    lib.decode_pages(
        base.ctypes.data, len(base),
        desc.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)), n_pages,
        out_vals.ctypes.data,
        out_valid.ctypes.data if out_valid is not None else None,
        len(out_vals), 1 if check_crc else 0, n_threads,
        status.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)))
    return status


def split_ts_i32(ts: np.ndarray, epoch: int, out_sec: np.ndarray | None,
                 out_ns: np.ndarray | None,
                 n_threads: int = 1) -> bool | None:
    """One GIL-free pass over i64 ns timestamps → the i32 pair relative
    to `epoch` (native/segagg.cpp): whole seconds into `out_sec`, the ns
    remainder into `out_ns` — contiguous i32 arrays of one length ≥
    len(ts), either may be None; rows past len(ts) are zeroed.
    → whether any remainder is non-zero, or None when unavailable."""
    lib = get_lib()
    if lib is None or not hasattr(lib, "split_ts_i32"):
        return None
    ts = np.ascontiguousarray(ts, dtype=np.int64)
    outs = [a for a in (out_sec, out_ns) if a is not None]
    if not outs or any(a.dtype != np.int32 or a.ndim != 1
                       or not a.flags.c_contiguous
                       or len(a) != len(outs[0]) or len(a) < len(ts)
                       for a in outs):
        raise ValueError("split_ts_i32: outputs must be contiguous i32 "
                         "arrays of one length >= len(ts)")
    return bool(lib.split_ts_i32(
        ts.ctypes.data, len(ts), epoch,
        out_sec.ctypes.data if out_sec is not None else None,
        out_ns.ctypes.data if out_ns is not None else None,
        len(outs[0]), n_threads))


def decode_xor_f64(comp: bytes, n: int) -> np.ndarray | None:
    lib = get_lib()
    if lib is None:
        return None
    out = np.empty(n, dtype=np.uint64)
    scratch = _get_scratch(n * 8)
    rc = lib.decode_xor_f64(
        comp, len(comp),
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)), n,
        scratch.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)), len(scratch))
    return out.view(np.float64) if rc == 0 else None
