"""The canonical crash-sweep workload: write→flush→compact→tier→matview.

Runs single-node, single-process, entirely under one root directory —
``python -m cnosdb_tpu.chaos.workload run <root>`` — so an injected
``crash`` (os._exit inside a faults.fire site) kills a *real* process at
an arbitrary point of the storage lifecycle. The run crosses every
node-scope fault point: WAL append/sync/roll, record-file append/sync,
flush, compaction, TSM finalize, cold tiering (object put/get + registry
rewrite), matview persist and the scrubber's read hook.

Every client-visible operation is recorded through chaos.history with
durable invoke records; a write is only acked (ok event) after its WAL
has been fsync'd, making the no-lost-acked-write check airtight against
os._exit. :func:`verify` reopens the same directories — which IS the
recovery path — measures crash→first-successful-read, and runs the
checker.

Timestamps are synthetic (~1970, one row per second) and the matview
refresh takes an explicit now_ns, so nothing depends on the wall clock
and the same seed + spec replays the same firing sequence.
"""
from __future__ import annotations

import json
import os
import sys
import time

from .. import faults
from ..errors import CnosError
from .checker import book, check_matview_parity, run_client_checks
from .history import History, HistoryRecorder

SEC = 10**9
OWNER = "cnosdb.public"
HISTORY = "history.jsonl"
TRACE = "fault_trace.json"
# rows 0..179 are written by s1, 180..299 by s2; rows < DELETE_BEFORE
# are deleted; files wholly older than TIER_BOUNDARY age to cold — the
# boundary sits past the last row because major compaction leaves one
# file per vnode spanning the whole range, and the workload must cross
# the tier/objstore/cold-scan sites
DELETE_BEFORE = 60
TIER_BOUNDARY = 400 * SEC
NOW_NS = 900 * SEC


def _open_db(root: str):
    from ..parallel.coordinator import Coordinator
    from ..parallel.meta import MetaStore
    from ..sql.executor import QueryExecutor
    from ..storage.engine import TsKv
    from ..storage import backup, tiering

    os.environ.setdefault("CNOSDB_MATVIEW_AUTO", "0")
    tiering.configure(os.path.join(root, "bucket"))
    # DR plane shares the root: sealed WAL segments stream to archive/
    # from the moment each vnode opens, so the run crosses backup.archive
    # continuously and BACKUP/RESTORE below cross the other two sites
    backup.configure_archive(os.path.join(root, "archive"))
    meta = MetaStore(os.path.join(root, "meta.json"))
    engine = TsKv(os.path.join(root, "data"))
    coord = Coordinator(meta, engine)
    ex = QueryExecutor(meta, coord)
    return engine, coord, ex


def _sync_wals(engine) -> None:
    """Make everything written so far durable — the ack barrier."""
    for v in engine.local_vnodes(OWNER):
        v.wal.sync()


def _keys(rows) -> list[str]:
    return [f"{h}:{ts}" for ts, h, _v in rows]


def _write(ex, engine, hist, session, rows) -> None:
    inv = hist.invoke(session, "write", keys=_keys(rows))
    vals = ", ".join(f"({ts}, '{h}', {v})" for ts, h, v in rows)
    ex.execute_one(f"INSERT INTO w (time, h, v) VALUES {vals}")
    _sync_wals(engine)
    hist.ok(session, inv)


def _read(ex, hist, session, mono: bool = True) -> set[str]:
    inv = hist.invoke(session, "read", durable=False, mono=mono)
    rows = ex.execute_one("SELECT h, time FROM w").rows()
    keys = sorted(f"{h}:{int(ts)}" for h, ts in rows)
    hist.ok(session, inv, keys=keys)
    return set(keys)


def _ddl(ex, hist, session, name: str, sql: str) -> None:
    inv = hist.invoke(session, "ddl", name=name)
    ex.execute_one(sql)
    hist.ok(session, inv)


def _batch(start: int, n: int):
    return [(i * SEC, f"h{i % 2}", f"{i}.5") for i in range(start, start + n)]


def run(root: str) -> None:
    """Execute the canonical workload to completion (or until an armed
    fault crashes the process). Exceptions propagate — the sweep treats
    any exit other than a clean 0 or the crash code as a bug."""
    os.makedirs(root, exist_ok=True)
    engine, coord, ex = _open_db(root)
    hist = HistoryRecorder(os.path.join(root, HISTORY))
    try:
        _ddl(ex, hist, "s1", "create_table",
             "CREATE TABLE w (v DOUBLE, TAGS(h))")
        _write(ex, engine, hist, "s1", _batch(0, 60))
        # shrink WAL segments so later appends cross the wal.roll site
        for v in engine.local_vnodes(OWNER):
            v.wal.max_segment_size = 2048
        _write(ex, engine, hist, "s1", _batch(60, 60))
        _write(ex, engine, hist, "s1", _batch(120, 60))
        _read(ex, hist, "s1")
        _spill_groupby(ex, hist)
        _ddl(ex, hist, "s1", "flush", "FLUSH")
        _write(ex, engine, hist, "s2", _batch(180, 60))
        _write(ex, engine, hist, "s2", _batch(240, 60))
        del_keys = _keys(_batch(0, DELETE_BEFORE))
        inv = hist.invoke("s2", "delete", keys=del_keys)
        ex.execute_one(f"DELETE FROM w WHERE time < {DELETE_BEFORE * SEC}")
        _sync_wals(engine)
        hist.ok("s2", inv)
        _read(ex, hist, "s2")
        _ddl(ex, hist, "s2", "flush", "FLUSH")
        _ddl(ex, hist, "s1", "compact", "COMPACT DATABASE public")
        _tier(engine, hist)
        _read(ex, hist, "s1")           # crosses the cold tier
        _ddl(ex, hist, "s1", "create_view",
             "CREATE MATERIALIZED VIEW mv WATERMARK DELAY '10s' AS "
             "SELECT date_bin(INTERVAL '1 minute', time) AS t, h, "
             "sum(v), count(v) FROM w GROUP BY t, h")
        ex.matview_engine().refresh("mv", now_ns=NOW_NS)
        _scrub(engine, hist)
        _backup_restore(ex, hist)
        _read(ex, hist, "s1")
        _read(ex, hist, "s2")
    finally:
        hist.close()
    # clean completion: dump the fired log — the probe pass reads this to
    # learn how many times each fault point was crossed
    with open(os.path.join(root, TRACE), "w", encoding="utf-8") as f:
        json.dump({"fired": [list(t) for t in faults.fired_log()]}, f)
    coord.close()


def _spill_groupby(ex, hist) -> None:
    """Cross the memory.spill site: squeeze the group budget so a wide
    group-by's accumulator spills (spill-vs-in-memory bit-identity is
    proven by tests/test_memory.py; here the point just needs a real
    crossing for the crash sweep). count(DISTINCT) forces the host
    accumulator path where the spiller lives."""
    from ..server import memory as memgov

    inv = hist.invoke("s1", "ddl", name="spill_groupby")
    saved = memgov.GROUP_BYTES
    memgov.GROUP_BYTES = 1
    try:
        ex.execute_one("SELECT h, count(DISTINCT v), sum(v) FROM w "
                       "GROUP BY h")
    finally:
        memgov.GROUP_BYTES = saved
    hist.ok("s1", inv)


def _tier(engine, hist) -> None:
    from ..storage import tiering

    inv = hist.invoke("s1", "ddl", name="tier")
    n = 0
    for v in engine.local_vnodes(OWNER):
        n += tiering.tier_vnode(v, TIER_BOUNDARY)
    hist.ok("s1", inv, files=n)


def _backup_restore(ex, hist) -> None:
    """Cross the DR plane's backup.manifest + restore.install sites: one
    consistent backup, then a restore into a parallel database. The
    source database must come through untouched — the post-restore reads
    and the checker prove it."""
    inv = hist.invoke("s1", "ddl", name="backup")
    ex.execute_one("BACKUP DATABASE public")
    hist.ok("s1", inv)
    inv = hist.invoke("s1", "ddl", name="restore")
    ex.execute_one("RESTORE DATABASE public AS public_r")
    hist.ok("s1", inv)


def _scrub(engine, hist) -> None:
    from ..storage import scrub

    inv = hist.invoke("s1", "ddl", name="scrub")
    out = scrub.scrub_engine(engine)
    hist.ok("s1", inv, files=out.get("files", 0))


def verify(root: str) -> dict:
    """Reopen the workload's directories (the recovery path), measure
    crash→first-successful-read, and run the consistency checker.

    → {"mttr_s", "observed", "results": [CheckResult...]} — verdicts are
    also booked into the chaos counters for /metrics."""
    from .. import chaos
    from ..storage import tiering

    t0 = time.monotonic()
    engine, coord, ex = _open_db(root)
    try:
        try:
            rows = ex.execute_one("SELECT h, time FROM w").rows()
        except CnosError:
            # first read may trip over torn cold state; the
            # coordinator's recover-and-retry already ran once — a
            # second attempt proves recovery converged (or fails loud)
            rows = ex.execute_one("SELECT h, time FROM w").rows()
        mttr = time.monotonic() - t0
        chaos.note_recovery("crash_restart", mttr)
        observed = {f"{h}:{int(ts)}" for h, ts in rows}
        hist = History.load(os.path.join(root, HISTORY))
        results = run_client_checks(hist, observed)
        results.append(_matview_check(ex, hist))
        book(results)
        return {"mttr_s": mttr, "observed": len(observed),
                "results": results}
    finally:
        from ..storage import backup

        coord.close()
        tiering.configure(None)
        backup.configure_archive(None)


def _matview_check(ex, hist):
    """Matview-vs-scan parity after recovery — only judged when the view's
    creation was acked (an ambiguous CREATE may legitimately be absent)."""
    from .checker import CheckResult

    acked_view = any(o.op == "ddl" and o.data.get("name") == "create_view"
                     and o.acked for o in hist.ops)
    if not acked_view:
        return CheckResult("matview_parity", True, "view not acked: skipped")
    mv = ex.matview_engine()
    mv.sync_from_meta()        # fresh process: pull the replicated catalog
    mv.refresh("mv", now_ns=NOW_NS)
    q = ("SELECT date_bin(INTERVAL '1 minute', time) AS t, h, "
         "sum(v), count(v) FROM w GROUP BY t, h")
    ex.matview_rewrite_enabled = True
    view_rows = ex.execute_one(q).rows()
    ex.matview_rewrite_enabled = False
    scan_rows = ex.execute_one(q).rows()
    ex.matview_rewrite_enabled = True
    return check_matview_parity(view_rows, scan_rows)


def main(argv: list[str]) -> int:
    if len(argv) != 2 or argv[0] not in ("run", "verify"):
        print("usage: python -m cnosdb_tpu.chaos.workload run|verify <root>",
              file=sys.stderr)
        return 2
    if argv[0] == "run":
        run(argv[1])
        return 0
    out = verify(argv[1])
    ok = all(r.ok for r in out["results"])
    print(json.dumps({"mttr_s": out["mttr_s"], "ok": ok,
                      "results": [[r.name, r.ok, r.detail]
                                  for r in out["results"]]}))
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
