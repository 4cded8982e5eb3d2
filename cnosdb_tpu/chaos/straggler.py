"""In-process straggler bed for the gray-failure tolerance plane.

One REAL storage engine (a single vnode of mixed-type data with NULL
columns, NaN floats and an unflushed delta on top of sealed files) is
exposed through N replica `RpcServer`s, each with a settable service
delay — the msgpack-over-HTTP wire, the coordinator's hedged `_scan_
remote` lane, the health scorer and the cancel fan-out all run for
real; only the *placement* is synthetic (every "replica" serves the
same local vnode, which is exactly the raft-converged-replicas
assumption hedging relies on). Used by tests/test_health.py for the
bit-identical parity + cancellation proofs.
"""
from __future__ import annotations

import time

import numpy as np

from ..models.points import SeriesRows, WriteBatch
from ..models.predicate import ColumnDomains, TimeRanges
from ..models.schema import ValueType
from ..models.series import SeriesKey
from ..parallel.coordinator import Coordinator, PlacedSplit
from ..parallel.ipc import encode_scan_batch
from ..parallel.meta import MetaStore
from ..parallel.net import RpcServer
from ..sql.executor import QueryExecutor
from ..storage.engine import TsKv
from ..utils import deadline as deadline_mod

OWNER = "cnosdb.public"
TABLE = "sg"
SEC = 10**9


class ReplicaServer:
    """One synthetic replica: a real RpcServer whose scan_vnode handler
    serves the bed's vnode after `delay_s` of injected service time."""

    def __init__(self, bed: "StragglerBed", node_id: int):
        self.bed = bed
        self.node_id = node_id
        self.delay_s = 0.0
        self.scans = 0
        self.cancels: list[str] = []
        self.server = RpcServer("127.0.0.1", 0, {
            "scan_vnode": self._scan,
            "cancel_scan": self._cancel,
            "ping": lambda p: {"ok": True},
        }).start()
        self.addr = self.server.addr

    def _scan(self, p):
        self.scans += 1
        if self.delay_s:
            time.sleep(self.delay_s)
        split = PlacedSplit(p["owner"], p["vnode_id"], p["table"],
                            TimeRanges.from_wire(p["trs"]),
                            ColumnDomains.from_wire(p["doms"]))
        b = self.bed.coord._scan_local(split, p.get("field_names"))
        return {"ipc": None if b is None else encode_scan_batch(b)}

    def _cancel(self, p):
        qid = str(p.get("qid") or "")
        self.cancels.append(qid)
        return {"ok": True, "cancelled": deadline_mod.CANCELS.cancel(qid)}

    def close(self):
        self.server.stop()


class StragglerBed:
    """Coordinator + `n_replicas` delayable replica servers over one
    vnode of NULL/NaN/delta-bearing data."""

    def __init__(self, root: str, rows: int = 2000, n_replicas: int = 2):
        self.meta = MetaStore(f"{root}/meta.json")
        self.engine = TsKv(f"{root}/data")
        self.coord = Coordinator(self.meta, self.engine)
        self.executor = QueryExecutor(self.meta, self.coord)
        self._load(rows)
        self.replicas = [ReplicaServer(self, 2 + i)
                         for i in range(n_replicas)]
        for r in self.replicas:
            self.meta.register_node(r.node_id, grpc_addr=r.addr)
        # remote-path trigger: placement says "not my node" for the split
        # built below, so scan goes through _scan_remote / _rpc / wire
        self.coord.distributed = True
        base = self.coord.table_vnodes("cnosdb", "public", TABLE,
                                       TimeRanges.all(),
                                       ColumnDomains.all())
        assert base, "bed table produced no vnodes"
        self.vnode_id = base[0].vnode_id

    def _load(self, rows: int):
        self.executor.execute_one(
            f"CREATE TABLE {TABLE} (v DOUBLE, extra DOUBLE, TAGS(h))")
        rng = np.random.default_rng(11)
        half = rows // 2
        # sealed half: both fields, a few NaNs in v
        v = rng.normal(50, 10, half)
        v[::97] = np.nan
        ts = (np.arange(half, dtype=np.int64) + 1) * SEC
        wb = WriteBatch()
        wb.add_series(TABLE, SeriesRows(
            SeriesKey(TABLE, {"h": "h0"}), ts,
            {"v": (int(ValueType.FLOAT), v),
             "extra": (int(ValueType.FLOAT), rng.normal(0, 1, half))}))
        self.coord.write_points("cnosdb", "public", wb)
        self.engine.flush_all()
        # unflushed delta on top: only `v` present → NULL `extra` after
        # merge, so the parity check crosses the delta-merge + NULL paths
        ts2 = ts + half * SEC
        v2 = rng.normal(50, 10, half)
        v2[::89] = np.nan
        wb = WriteBatch()
        wb.add_series(TABLE, SeriesRows(
            SeriesKey(TABLE, {"h": "h1"}), ts2,
            {"v": (int(ValueType.FLOAT), v2)}))
        self.coord.write_points("cnosdb", "public", wb)

    # ------------------------------------------------------------- scans
    def split(self) -> PlacedSplit:
        """A split whose candidates are the replica servers, in id order
        (the health ranker reorders them from there)."""
        first, rest = self.replicas[0], self.replicas[1:]
        return PlacedSplit(OWNER, self.vnode_id, TABLE,
                           TimeRanges.all(), ColumnDomains.all(),
                           node_id=first.node_id,
                           alternates=[(self.vnode_id, r.node_id)
                                       for r in rest])

    def scan_once(self, qid: str = "bed", timeout_s: float | None = 5.0,
                  field_names=None):
        """One remote scan through the coordinator's read plane (hedged
        or legacy depending on CNOSDB_HEDGE), under its own deadline."""
        with deadline_mod.scope(deadline_mod.Deadline(timeout_s, qid=qid)):
            return self.coord._scan_remote(self.split(), field_names)

    def close(self):
        for r in self.replicas:
            r.close()
        self.coord.close()


def batch_bytes(b) -> bytes:
    """Canonical byte form of a ScanBatch for bit-identity assertions."""
    return b"" if b is None else encode_scan_batch(b)
