"""Operator placement: the one device the scan plane runs on.

CNOSDB_TPU_PLACEMENT = auto | device | cpu (default auto). `auto` and
`device` are the default backend's first device — whatever JAX was told
to use (or found) is what runs the kernels; `cpu` is the explicit
request for the host platform. Nothing here times the device and nothing
moves a visible accelerator's work to the CPU for speed: a process that
cannot initialize the backend it was given fails at `scan_device()`.

One property of the device is observed, because answers depend on it:
`f64_exact()`. A TPU has no f64; XLA carries one as a pair of f32 (about
49 bits of mantissa, f32's exponent range), so an f64 column changes the
moment it is uploaded — most values in their last bits, 1e300 to inf.
Exact min/max/first/last and bit-identical decode are guarantees, so on
such a device FLOAT columns stay on the host lanes, each lane booking
that it kept them (`f64_kept_on_host`, `cnosdb_device_decode_total`,
`cnosdb_mesh_total`). Integer, boolean and unsigned columns are exact on
the device (i64 is a pair of i32).

`scan_device()` is the single answer: DeviceBatches, the decode lane,
the fused programs and the execution mesh all ask it, and
`device_stamp()` is what query profiles and the server's start-up line
report.
"""
from __future__ import annotations

import os

import numpy as np

import jax

from ..models.schema import ValueType

_placement_device = None
_f64_exact = None


def scan_device():
    """The device the fused scan kernels (and DeviceBatches) live on."""
    global _placement_device
    if _placement_device is None:
        mode = os.environ.get("CNOSDB_TPU_PLACEMENT", "auto").lower()
        _placement_device = (jax.devices("cpu") if mode == "cpu"
                             else jax.devices())[0]
    return _placement_device


def mesh_devices() -> list:
    """Device pool for the execution mesh (parallel/mesh.get_mesh): every
    device on the platform `scan_device()` resolved to."""
    return list(jax.devices(scan_device().platform))


def f64_exact() -> bool:
    """Does an f64 survive the scan device bit for bit? Observed once: a
    value that needs 53 bits, one past f32's range and a signed zero go
    to the device and come back."""
    global _f64_exact
    if _f64_exact is None:
        probe = np.array([1.0 / 3.0, 1e300, -0.0])
        back = np.asarray(jax.device_put(probe, scan_device()))  # lint: disable=host-sync (three values, once per process)
        _f64_exact = bool((back.view(np.uint64)
                           == probe.view(np.uint64)).all())
    return _f64_exact


def exact_on_device(vt: ValueType) -> bool:
    """May a column of this value type live on the scan device without
    changing? The one place that decides: FLOAT only where f64 is exact."""
    return vt != ValueType.FLOAT or f64_exact()


def device_stamp() -> dict:
    """The resolved scan device as telemetry: what QueryProfile.device
    carries (EXPLAIN ANALYZE `device k=v` rows, /debug/profile) and what
    the server logs once at start."""
    dev = scan_device()
    return {"platform": dev.platform, "device_kind": dev.device_kind,
            "device_count": len(mesh_devices()), "f64_exact": f64_exact()}
