"""Device-side decode plane: the TSM codecs as batched accelerator kernels.

Cold scans were host-bound: every page decoded on the CPU (native or
numpy) and only the finished arrays crossed the PCIe pipe. Following "GPU
Acceleration of SQL Analytics on Compressed Data" (arxiv 2506.10092),
this module inverts that: host work stops at the byte-container stage
(zstd et al — storage/codecs.split_for_device), the still-narrow
post-container payloads ship to the device, and the per-value codec
transforms run there as batched jitted kernels:

  delta / delta_ts   widen -> unzigzag -> cumsum   (i64, u64 bit-rides)
  delta const-stride first + stride * iota          (18-byte pages)
  gorilla f64        byte-plane assembly -> log-step prefix-XOR scan
                     (native/bytetrans.h as lane-parallel u32 planes,
                     lax.associative_scan)
  bitpack bool       bit-expansion from packed u8
  string dict pages  narrow code widening (codes on device; the Python
                     dictionary itself stays host-side)

Batching: pages are padded into fixed-shape [B, L] buffers keyed by
(kind, width, pow2 length bucket) and B is padded to a pow2, so the jit
cache sees a handful of shapes regardless of page-size jitter. The page
group is also the lane's unit of device work: per group one put of each
packed operand, one kernel launch and one pull of the whole [B, L]
batch — a page's row is a numpy view of that pull, never a device array
of its own. Outputs
are bit-identical to storage/codecs.decode (verified by the property
suite in tests/test_device_decode.py) because every transform is
integer/bitwise: XOR scans, two's-complement cumsum and bitcasts have no
rounding.

Gating: CNOSDB_DEVICE_DECODE=1 forces the lane on (the XLA kernels on a
CPU backend included — how tests engage it), =0 off, auto hands scans a
lane only when the scan device is a real TPU. The scan layer
(storage/scan) receives a DeviceDecodeLane via `decode_hook` so storage
itself stays jax-free; every page the lane examines but does not decode
books a (lane, reason) outcome — surfaced as
cnosdb_device_decode_total{lane,reason} and required by the
device-decode-accounting lint rule.

The route of a page (PR 32): a page is decoded where its values land.
Every consumer of a scan reads the scan's HOST arrays, and the pipe down
from the chip runs at 0.29 GB/s, so a forced lane (=1) and a lane handed
to scan_vnode directly are device-first as before, but the lane the
coordinator's hook builds in auto mode is `behind_native()`: a numeric or
time page the native decoder can take (native/pagedec.cpp: a local
reader, a type and encoding it knows, the column's own type) goes to it
and books {lane="host", reason="native_first"}. In auto mode the device
kernels see what that decoder cannot take: cold readers' pages,
encodings outside its set, and STRING / GEOMETRY pages (it has no
dictionary lane).
"""
from __future__ import annotations

import functools
import os
import threading

import numpy as np

import jax
import jax.numpy as jnp

from ..models.codec import Encoding
from ..models.schema import ValueType
from ..utils import stages
from . import program
from .placement import exact_on_device

# TPU lane width: value buckets are pow2 multiples of this, so the last
# (vectorized) dimension always tiles cleanly
_MIN_LANE = 128
_WIDTH_DTYPE = {1: np.uint8, 2: np.uint16, 4: np.uint32, 8: np.uint64}


def enabled() -> bool:
    """Should scans be handed a lane of this plane?
    CNOSDB_DEVICE_DECODE=1 forces on (XLA on CPU backends — the test
    mode), =0 off; default: only on a real TPU."""
    return disabled_reason() is None


def forced() -> bool:
    """CNOSDB_DEVICE_DECODE=1: the lane is device-first, as it is when
    handed to scan_vnode directly; in auto mode it stands behind the
    native decoder (DeviceDecodeLane.behind_native)."""
    return _mode() in ("1", "on", "true")


def _mode() -> str:
    return os.environ.get("CNOSDB_DEVICE_DECODE", "auto").lower()


def disabled_reason() -> str | None:
    """None when the lane is usable, else WHY not — stamped into query
    profiles as device_decode_disabled_reason."""
    if forced():
        return None
    mode = _mode()
    if mode in ("0", "off", "false"):
        return f"disabled by env CNOSDB_DEVICE_DECODE={mode}"
    from .placement import scan_device

    platform = scan_device().platform
    if platform != "tpu":
        return f"scan device is {platform!r}, not tpu (auto mode)"
    return None


# ---------------------------------------------------------------------------
# engagement + outcome accounting
# ---------------------------------------------------------------------------
_LOCK = threading.Lock()
_engagements = 0
_outcomes: dict[tuple[str, str], int] = {}


def note_engaged(n: int = 1) -> None:
    global _engagements
    with _LOCK:
        _engagements += n
    stages.count("device_decode_engagements", n)


def engagements() -> int:
    """Pages decoded by the device lane this process."""
    with _LOCK:
        return _engagements


def count_outcome(lane: str, reason: str, n: int = 1) -> None:
    """Book n pages as handled by `lane` ("device" or "host") for
    `reason` — the raw series behind cnosdb_device_decode_total."""
    with _LOCK:
        _outcomes[(lane, reason)] = _outcomes.get((lane, reason), 0) + n


def outcomes_snapshot() -> dict[tuple[str, str], int]:
    with _LOCK:
        return dict(sorted(_outcomes.items()))


def _called(n: int = 1) -> None:
    """Book n calls the lane made to the device (a put, a kernel launch,
    a pull): with device_decode_engagements, the round trips a page."""
    stages.count("device_decode.device_calls", n)


def _pow2(n: int, minimum: int) -> int:
    n = max(int(n), minimum)
    return 1 << (n - 1).bit_length()


# ---------------------------------------------------------------------------
# kernels (pure XLA)
# ---------------------------------------------------------------------------
@jax.jit
@program("decode_delta")
def _delta_kernel(zz, firsts):
    """[B, L] narrow zigzag deltas + [B] firsts -> [B, L] i64 values.

    Row b carries n_b-1 deltas zero-padded to L; out[b, i] =
    first_b + sum(deltas[:i]) so out[b, :n_b] matches the host decode
    (two's-complement cumsum wraps identically to numpy's)."""
    u = zz.astype(jnp.uint64)
    one = jnp.uint64(1)
    dec = (u >> one) ^ (jnp.uint64(0) - (u & one))   # unzigzag, in u64
    d = jax.lax.bitcast_convert_type(dec, jnp.int64)
    csum = jnp.cumsum(d, axis=1)
    zero = jnp.zeros((d.shape[0], 1), jnp.int64)
    return firsts[:, None] + jnp.concatenate([zero, csum[:, :-1]], axis=1)


@functools.partial(jax.jit, static_argnames=("length",))
@program("decode_delta_const")
def _delta_const_kernel(firsts, strides, length):
    """Constant-stride timestamp fast path: first + stride * iota."""
    idx = jnp.arange(length, dtype=jnp.int64)
    return firsts[:, None] + strides[:, None] * idx[None, :]


@jax.jit
@program("decode_gorilla")
def _gorilla_xla_kernel(planes):
    """Gorilla f64: [B, 8, L] u8 byte planes (plane k = byte k of each
    u64, little endian) -> untranspose + prefix-XOR scan, XOR running as
    two independent u32 halves (XOR is bytewise, so the split is exact)."""
    p = planes.astype(jnp.uint32)
    lo = p[:, 0] | (p[:, 1] << 8) | (p[:, 2] << 16) | (p[:, 3] << 24)
    hi = p[:, 4] | (p[:, 5] << 8) | (p[:, 6] << 16) | (p[:, 7] << 24)
    lo = jax.lax.associative_scan(jnp.bitwise_xor, lo, axis=1)
    hi = jax.lax.associative_scan(jnp.bitwise_xor, hi, axis=1)
    u = lo.astype(jnp.uint64) | (hi.astype(jnp.uint64) << jnp.uint64(32))
    return jax.lax.bitcast_convert_type(u, jnp.float64)


@jax.jit
@program("decode_bitpack")
def _bitpack_kernel(packed):
    """[B, Lb] packed u8 -> [B, Lb*8] 0/1 u8 (MSB-first, np.packbits)."""
    shifts = jnp.arange(7, -1, -1, dtype=jnp.uint8)
    bits = (packed[:, :, None] >> shifts[None, None, :]) & jnp.uint8(1)
    return bits.reshape(packed.shape[0], -1)


@jax.jit
@program("decode_codes")
def _codes_kernel(codes):
    """Narrow dictionary codes -> i32 (the DictArray code dtype)."""
    return codes.astype(jnp.int32)


# ---------------------------------------------------------------------------
# the scan-facing lane
# ---------------------------------------------------------------------------
class _Job:
    __slots__ = ("plan", "token", "vt", "out_off", "n_rows", "nm",
                 "out_vals", "out_valid", "sink")


class DeviceDecodeLane:
    """One scan's device-decode batch builder.

    Driven by storage/scan._scan_vnode_native: `submit()` during page
    planning (plans come from codecs.split_for_device — storage stays
    jax-free, this object crosses the boundary via `decode_hook`), one
    `run()` that executes the batched kernels, pulls each group's batch
    once, writes host outputs back (null-mask expansion included) and
    returns the tokens of pages whose group failed (the caller re-routes
    those through the Python lane). The decoded columns then live in the
    scan's host arrays; the scan ships them to the device like any other
    finished column (EagerUploader.put).
    """

    _NUMERIC_ENC = {
        int(ValueType.FLOAT): {int(Encoding.GORILLA)},
        int(ValueType.INTEGER): {int(Encoding.DELTA),
                                 int(Encoding.DELTA_TS)},
        int(ValueType.UNSIGNED): {int(Encoding.DELTA),
                                  int(Encoding.DELTA_TS)},
        int(ValueType.BOOLEAN): {int(Encoding.BITPACK),
                                 int(Encoding.NULL)},
    }

    # True: the scan sends every page its native decoder can take there
    # (booked host / native_first) and this lane sees the rest
    native_first = False

    def __init__(self):
        self._jobs: list[_Job] = []

    @classmethod
    def behind_native(cls) -> "DeviceDecodeLane":
        """The lane of auto mode: decoded values land in host arrays, so
        the native decoder goes first (module docstring)."""
        lane = cls()
        lane.native_first = True
        return lane

    def accepts(self, value_type: int, encoding: int) -> bool:
        """Cheap pre-check: does (value_type, encoding) have a device
        kernel at all? (String pages always submit — the container
        codec id is not page-visible without reading the block.)"""
        ok = self._NUMERIC_ENC.get(int(value_type))
        if ok is None or int(encoding) not in ok:
            return False
        if not exact_on_device(value_type):
            # bit-identical decode is the lane's contract, and this device
            # rounds an f64 the moment it holds one
            self.declined("f64_inexact_on_device")
            return False
        return True

    def declined(self, reason: str, n: int = 1) -> None:
        """Book n pages the scan examined but routed to a host lane."""
        count_outcome("host", reason, n)

    def pending(self) -> int:
        return len(self._jobs)

    def submit(self, plan: dict, token, vt, out_off: int,
               n_rows: int, nm, out_vals, out_valid, sink=None) -> None:
        """Queue one page. Numeric/time pages write into
        out_vals/out_valid at out_off (nm = null mask, as
        read_field_page returns); string pages deliver dense i32 codes
        to `sink` instead."""
        j = _Job()
        j.plan, j.token, j.vt = plan, token, vt
        j.out_off, j.n_rows, j.nm = out_off, n_rows, nm
        j.out_vals, j.out_valid, j.sink = out_vals, out_valid, sink
        self._jobs.append(j)

    # ------------------------------------------------------------- execute
    def run(self) -> list:
        """Execute every submitted page as batched kernels; → failed
        tokens for the caller's Python lane. Every page leaves here
        either decoded or reason-booked (device-decode-accounting rule).

        The group is the unit of device work: put and launch every group
        (`_run_group`), then pull each group's whole batch once and write
        its pages back from numpy views of it — the device runs group k+1
        while the host lands group k. Dispatch is asynchronous, so a
        kernel's failure may only surface at its pull: both halves route
        the group's pages to the Python lane."""
        failed: list = []
        groups: dict = {}
        for j in self._jobs:
            groups.setdefault(self._group_key(j), []).append(j)
        launched = []
        for key, jobs in groups.items():
            try:
                launched.append((jobs, self._run_group(key, jobs)))
            except Exception:
                failed.extend(self._kernel_error(jobs))
        launched.reverse()
        while launched:
            jobs, out = launched.pop()   # a landed batch leaves the device
            try:
                with stages.stage("device_decode.pull_ms"):
                    # the lane's audited transfer point: one device→host
                    # pull per page group
                    host = np.asarray(out)
                    _called()
            except Exception:
                failed.extend(self._kernel_error(jobs))
                continue
            for bi, j in enumerate(jobs):
                self._writeback(j, host[bi, :j.plan["n"]])
            count_outcome("device", "ok", len(jobs))
            note_engaged(len(jobs))
        return failed

    @staticmethod
    def _kernel_error(jobs) -> list:
        """A group's put, launch or pull raised: book its pages to the
        host lane → their tokens."""
        stages.count_error("device_decode.kernel")
        count_outcome("host", "kernel_error", len(jobs))
        return [j.token for j in jobs]

    def _group_key(self, j: _Job):
        p = j.plan
        kind = p["kind"]
        if kind == "bitpack":
            return (kind, 1, _pow2((p["n"] + 7) // 8, _MIN_LANE // 8))
        width = p.get("width", 8)
        return (kind, width, _pow2(p["n"], _MIN_LANE))

    def _run_group(self, key, jobs):
        """One (kind, width, length-bucket) batch -> its decoded [B, L]
        batch, still on device and possibly still running. Pack the pages
        into padded host buffers, put them, launch the kernel: the two
        device steps are stages."""
        kind, width, lane_len = key
        packed = self._pack_group(kind, width, lane_len, jobs)
        with stages.stage("device_decode.put_ms"):
            operands = [self._put(a) for a in packed]
            _called(len(operands))
        with stages.stage("device_decode.launch_ms"):
            return self._launch_group(kind, lane_len, operands)

    def _pack_group(self, kind, width, lane_len, jobs) -> list:
        """→ the group's kernel operands as host arrays, rows padded to
        the lane length and the batch to a pow2."""
        b_pad = _pow2(len(jobs), 1)
        if kind == "delta_const":
            firsts = np.zeros(b_pad, np.int64)
            strides = np.zeros(b_pad, np.int64)
            for bi, j in enumerate(jobs):
                firsts[bi] = j.plan["first"]
                strides[bi] = j.plan["stride"]
            return [firsts, strides]
        if kind == "delta":
            zz = np.zeros((b_pad, lane_len), dtype=_WIDTH_DTYPE[width])
            firsts = np.zeros(b_pad, np.int64)
            for bi, j in enumerate(jobs):
                raw = np.frombuffer(j.plan["raw"], dtype=zz.dtype)
                zz[bi, :len(raw)] = raw
                firsts[bi] = j.plan["first"]
            return [zz, firsts]
        if kind == "gorilla":
            planes = np.zeros((b_pad, 8, lane_len), dtype=np.uint8)
            for bi, j in enumerate(jobs):
                n = j.plan["n"]
                planes[bi, :, :n] = np.frombuffer(
                    j.plan["raw"], dtype=np.uint8).reshape(8, n)
            return [planes]
        if kind == "bitpack":
            packed = np.zeros((b_pad, lane_len), dtype=np.uint8)
            for bi, j in enumerate(jobs):
                raw = np.frombuffer(j.plan["raw"], dtype=np.uint8)
                nb = (j.plan["n"] + 7) // 8
                packed[bi, :nb] = raw[:nb]
            return [packed]
        # dict codes
        codes = np.zeros((b_pad, lane_len), dtype=_WIDTH_DTYPE[width])
        for bi, j in enumerate(jobs):
            raw = np.frombuffer(j.plan["raw"], dtype=codes.dtype)
            codes[bi, :len(raw)] = raw
        return [codes]

    def _launch_group(self, kind, lane_len, operands):
        """→ the [B, L] decoded batch, on device."""
        _called()
        if kind == "delta_const":
            firsts, strides = operands
            return _delta_const_kernel(firsts, strides, length=lane_len)
        if kind == "delta":
            return _delta_kernel(*operands)
        if kind == "gorilla":
            return _gorilla_xla_kernel(*operands)
        if kind == "bitpack":
            return _bitpack_kernel(*operands)
        return _codes_kernel(*operands)

    def _put(self, a: np.ndarray):
        from .device_cache import _put

        return _put(a)

    def _writeback(self, j: _Job, dense: np.ndarray) -> None:
        """Host-side landing: expand the dense kernel output through the
        page's null mask into the scan's output arrays (same contract as
        the Python page lane)."""
        if j.sink is not None:
            j.sink(dense)
            return
        if j.vt == ValueType.UNSIGNED:
            dense = dense.view(np.uint64)
        elif j.vt == ValueType.BOOLEAN:
            dense = dense.astype(np.bool_)
        off, n = j.out_off, j.n_rows
        if j.nm is None:
            j.out_vals[off:off + n] = dense
            if j.out_valid is not None:
                j.out_valid[off:off + n] = True
        else:
            j.out_vals[off:off + n][~j.nm] = dense
            j.out_valid[off:off + n] = ~j.nm
