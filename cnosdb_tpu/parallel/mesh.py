"""Device mesh management + the mesh-lane accounting plane.

The rebuild's answer to the reference's scan fan-out + NCCL-style backend
(SURVEY §2.4): rows shard across a 1-D `jax.sharding.Mesh` axis ("shard"),
partial aggregates combine over ICI collectives. Multi-host extends the
same mesh across processes (jax distributed init), with DCN handled by
XLA. A second ("replica") axis name is reserved for replicated operand
placement — P() over it pins small tables to every device.

The process-wide mesh is built once (`get_mesh`) from the placement
plane's device pool (ops/placement.py `mesh_platform`), so vnode→device
placement and the NamedSharding specs the exec lane emits agree by
construction. `CNOSDB_MESH=0` disables the lane entirely — every query
takes the byte-identical legacy merge path.

Accounting: every mesh-lane engage/decline books here via
`count_outcome(lane, reason)` (the mesh-accounting lint rule holds the
exec lane to it) and is exported as `cnosdb_mesh_total{lane,reason}`
by the HTTP /metrics scrape.
"""
from __future__ import annotations

import os
import threading

import numpy as np

from .. import ops as _ops  # noqa: F401 - x64 config side effect
import jax
from jax.sharding import Mesh

SHARD_AXIS = "shard"
# reserved second axis name: replicated operands (label LUTs, bucket
# tables) are placed with P() which spans every named axis, so a 1-D
# mesh today grows to ("shard", "replica") without spec rewrites
REPLICA_AXIS = "replica"

_lock = threading.Lock()
_counters: dict[tuple[str, str], int] = {}
_cached_mesh: Mesh | None = None
_cached_key: tuple | None = None


def enabled() -> bool:
    """Master switch: CNOSDB_MESH=0 keeps every query on the legacy
    (byte-identical) host merge path."""
    return os.environ.get("CNOSDB_MESH", "1") != "0"


def count_outcome(lane: str, reason: str, n: int = 1) -> None:
    """Book one mesh-lane outcome (engage or decline) — the counter
    behind `cnosdb_mesh_total{lane,reason}`."""
    with _lock:
        _counters[(lane, reason)] = _counters.get((lane, reason), 0) + n


def outcomes_snapshot() -> dict[tuple[str, str], int]:
    with _lock:
        return dict(sorted(_counters.items()))


def reset_counters() -> None:
    """Test isolation only."""
    with _lock:
        _counters.clear()


def make_mesh(n_devices: int | None = None) -> Mesh:
    """A 1-D mesh over the default backend's first n devices; raises when
    it has fewer (no quiet mesh of host CPU devices beside a chip)."""
    devs = jax.devices()
    if n_devices is not None:
        if n_devices > len(devs):
            raise ValueError(
                f"requested {n_devices} devices, the {devs[0].platform} "
                f"backend has {len(devs)}")
        devs = devs[:n_devices]
    return Mesh(np.array(devs), (SHARD_AXIS,))


def get_mesh() -> Mesh | None:
    """The process-wide execution mesh, built once from the placement
    plane's device pool. CNOSDB_MESH_DEVICES caps the width; None when
    the pool is empty."""
    global _cached_mesh, _cached_key
    want = os.environ.get("CNOSDB_MESH_DEVICES")
    with _lock:
        if _cached_mesh is not None and _cached_key == want:
            return _cached_mesh
    from ..ops.placement import mesh_devices

    devs = mesh_devices()
    if not devs:
        return None
    if want:
        devs = devs[:max(1, int(want))]
    mesh = Mesh(np.array(devs), (SHARD_AXIS,))
    with _lock:
        _cached_mesh = mesh
        _cached_key = want
    return mesh


def mesh_size(mesh: Mesh) -> int:
    return mesh.shape[SHARD_AXIS]
