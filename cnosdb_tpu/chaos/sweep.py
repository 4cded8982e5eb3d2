"""Exhaustive crash-point sweep over the FAULT_POINTS registry.

Two passes. The **probe** pass arms the ``noop`` action at every
node-scope registered point and runs the canonical workload once to
completion: the fired log it leaves behind is the exact ordered sequence
of fault-point crossings, i.e. for each point the number k of times the
workload crosses it. The **crash** pass then runs one fresh workload per
(point, nth ≤ k) pair with ``crash`` armed — the subprocess dies with
os._exit at precisely that crossing — and recovery is judged by
reopening the directory and running the consistency checker.

Coverage is a gate, not a report: a node-scope point the probe never
crosses means the canonical workload silently stopped exercising part of
the storage lifecycle, and the sweep fails. Cluster-scope points (RPC,
meta raft) cannot crash a single-process workload meaningfully; they are
exercised by the nemesis suite in tests/test_chaos_cluster.py.

Every run's spec is a one-command reproduction::

    CNOSDB_FAULTS='seed=7;wal.append:crash:nth=3' \
        python -m cnosdb_tpu.chaos.workload run /tmp/dir
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

from .. import faults
from . import workload

CRASH_RC = 137          # faults.fire's os._exit code
RUN_TIMEOUT = 180.0
_REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# points whose first crossing happens early in the workload (pre-matview,
# so the subprocess stays cheap) — the fast tier-1 subset; the issue's
# named torn-state candidates (tiering registry, matview persist) ride in
# the full sweep
FAST_POINTS = ("wal.append", "flush.run", "tiering.registry",
               "backup.archive", "memory.spill")


def node_points() -> list[str]:
    """All node-scope registered fault points, importing every hook module
    so their register_point calls have run."""
    import cnosdb_tpu.parallel.net                 # noqa: F401
    import cnosdb_tpu.parallel.meta_service        # noqa: F401
    import cnosdb_tpu.server.serving               # noqa: F401
    import cnosdb_tpu.sql.executor                 # noqa: F401
    import cnosdb_tpu.sql.matview                  # noqa: F401
    import cnosdb_tpu.storage.backup               # noqa: F401
    import cnosdb_tpu.storage.compaction           # noqa: F401
    import cnosdb_tpu.storage.flush                # noqa: F401
    import cnosdb_tpu.storage.record_file          # noqa: F401
    import cnosdb_tpu.storage.scrub                # noqa: F401
    import cnosdb_tpu.storage.tiering              # noqa: F401
    import cnosdb_tpu.storage.tsm                  # noqa: F401
    import cnosdb_tpu.storage.wal                  # noqa: F401
    import cnosdb_tpu.utils.objstore               # noqa: F401

    return sorted(faults.registered_points(scope="node"))


def repro_command(spec: str, root: str) -> str:
    return (f"CNOSDB_FAULTS='{spec}' {os.path.basename(sys.executable)} "
            f"-m cnosdb_tpu.chaos.workload run {root}")


def _run_workload(root: str, spec: str) -> subprocess.CompletedProcess:
    env = dict(os.environ)
    env["CNOSDB_FAULTS"] = spec
    env.setdefault("JAX_PLATFORMS", "cpu")
    env.setdefault("CNOSDB_MATVIEW_AUTO", "0")
    return subprocess.run(
        [sys.executable, "-m", "cnosdb_tpu.chaos.workload", "run", root],
        env=env, cwd=_REPO, capture_output=True, text=True,
        timeout=RUN_TIMEOUT)


def probe(base: str, seed: int = 7,
          points: list[str] | None = None) -> dict[str, int]:
    """Run the workload once with noop armed everywhere → point → number
    of crossings. Raises on an unclean probe (it must run to completion
    with noop faults: they change nothing)."""
    pts = points if points is not None else node_points()
    spec = f"seed={seed};" + ";".join(f"{p}:noop" for p in pts)
    root = os.path.join(base, "probe")
    p = _run_workload(root, spec)
    if p.returncode != 0:
        raise RuntimeError(
            f"probe workload failed rc={p.returncode}\n"
            f"repro: {repro_command(spec, root)}\n{p.stdout}\n{p.stderr}")
    with open(os.path.join(root, workload.TRACE), encoding="utf-8") as f:
        fired = json.load(f)["fired"]
    hits: dict[str, int] = {pt: 0 for pt in pts}
    for point, _action, _hit in fired:
        hits[point] = hits.get(point, 0) + 1
    return hits


def run_one(base: str, point: str, nth: int, seed: int = 7) -> dict:
    """One crash run: fresh dir, crash armed at (point, nth), then verify
    (recovery + checker) in-process."""
    spec = f"seed={seed};{point}:crash:nth={nth}"
    root = os.path.join(base, f"{point.replace('.', '_')}_{nth}")
    p = _run_workload(root, spec)
    out = {"point": point, "nth": nth, "spec": spec, "root": root,
           "rc": p.returncode, "crashed": p.returncode == CRASH_RC,
           "repro": repro_command(spec, root)}
    if p.returncode not in (0, CRASH_RC):
        out.update(ok=False, error=(p.stderr or p.stdout)[-2000:])
        return out
    v = workload.verify(root)
    out.update(ok=all(r.ok for r in v["results"]),
               mttr_s=round(v["mttr_s"], 3), observed=v["observed"],
               results=[[r.name, r.ok, r.detail] for r in v["results"]])
    return out


def run_sweep(base: str, points: list[str] | None = None,
              nth_cap: int = 2, seed: int = 7) -> dict:
    """Probe, then crash every (point, nth ≤ min(k, nth_cap)) pair.

    → {"seed", "coverage": {...}, "runs": [...], "failed": [...]} where
    `failed` collects runs whose recovery or checker went wrong, each
    carrying its one-command repro string."""
    registered = points if points is not None else node_points()
    hits = probe(base, seed=seed, points=registered)
    uncovered = sorted(p for p in registered if hits.get(p, 0) == 0)
    runs = []
    for point in registered:
        for nth in range(1, min(hits.get(point, 0), nth_cap) + 1):
            runs.append(run_one(base, point, nth, seed=seed))
    failed = [r for r in runs if not r.get("ok") or not r.get("crashed")]
    return {"seed": seed,
            "coverage": {"registered": len(registered),
                         "crossed": sum(1 for p in registered
                                        if hits.get(p, 0)),
                         "hits": hits, "uncovered": uncovered},
            "runs": runs, "failed": failed}
