"""The sharded deployment, end to end, against a reference that shares no
code with it: TSBS devops rows in a `WITH SHARD 8` database through the
served path (line protocol over /api/v1/write, FLUSH, SQL over HTTP), the
fleet group-by of `benchmarks/traffic/fleet-groupby.json` and its siblings
answered at mesh widths 1, 2, 4 and 8 of conftest's virtual devices, every
answer held to `benchmarks/lib/devops.py` (plain numpy) under the
tolerances the benchmark's configuration states. `tests/test_mesh_parity.py`
compares the lane with `CNOSDB_MESH=0`; here the yardstick is outside the
program, which is what the benchmark's `correct` rests on.

One server for the file (loading 108 000 rows is most of its time); the
mesh's width is `CNOSDB_MESH_DEVICES`, which the lane reads per query.
"""
import json
import logging
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmarks.lib import devops                          # noqa: E402
from benchmarks.lib.server import metric, parse_metrics    # noqa: E402

SEED = 2147483659          # a --seed as the driver draws them: past 2**31
HOSTS, STEPS = 300, 360    # 1 h of data; a request scans 50 min = 90 000 rows
DB = "bench"
with open(os.path.join(ROOT, "benchmarks", "traffic", "fleet-groupby.json")) as f:
    FLEET = json.load(f)["classes"][0]            # TSBS double-groupby-1
with open(os.path.join(ROOT, "benchmarks", "traffic",
                       "fleet-groupby-all.json")) as f:
    FLEET_ALL = json.load(f)["classes"][0]        # TSBS double-groupby-all
FANOUT_KEYS = ("fanout.launch_ms", "fanout.fetch_ms", "fanout.vnodes",
               "merge.groups")


def _class(aggregate: str) -> dict:
    """double-groupby-1 with another aggregate, over two fields: what the
    configuration promises about each aggregate is checked on the same
    rows and groups."""
    if aggregate == "avg":
        return FLEET
    return {**FLEET, "name": f"double-groupby-{aggregate}",
            "aggregate": aggregate, "fields": 2}


class _Deployment:
    def __init__(self, harness, ds):
        self.h, self.ds = harness, ds
        self.rng = np.random.default_rng(SEED)

    def sql(self, sql: str, headers=None, db: str = DB):
        status, body, hdrs = self.h.request(
            "POST", f"/api/v1/sql?db={db}", sql,
            headers={"Accept": "application/csv", **(headers or {})})
        assert status == 200, body[:500]
        return body, hdrs

    def metrics(self) -> dict:
        status, body, _ = self.h.request("GET", "/metrics")
        assert status == 200
        return parse_metrics(body)

    def ask(self, cls: dict, db: str = DB, ds=None) -> dict:
        """One request of the class with a window nothing has had, answered
        and compared with the reference. → the stages of its profile, with
        `answer_rows`: the groups of the answer, which are the reference's
        once the comparison has passed."""
        ds = ds or self.ds
        req = devops.ClassGenerator(cls, ds, self.rng).draw()
        text, hdrs = self.sql(req.sql, {"X-CnosDB-Profile": "1"}, db=db)
        # devops.check_answer: the same set of (bucket, hostname) rows;
        # count / min / max / sum compared as integers — the columns are
        # BIGINT and the lane's sums are i64, so nothing may round; avg to
        # 1e-9 relative — the reference divides the exact integer sum by
        # the exact count in f64 and so does the program, the tolerance
        # is room for the CSV's shortest-repr digits, not for the merge
        devops.check_answer(ds, req, text)
        st = json.loads(hdrs["X-CnosDB-Profile-Summary"])["stages"]
        return {**st, "answer_rows": len(text.splitlines()) - 1}


def _mesh_outcomes(m: dict) -> dict:
    return {tuple(v for _k, v in labels): val for (name, labels), val
            in m.items() if name == "cnosdb_mesh_total"}


def _rise(before: dict, after: dict) -> dict:
    return {k: after[k] - before.get(k, 0) for k in after
            if after[k] != before.get(k, 0)}


def _mesh_errors(m: dict) -> float:
    return sum(v for (name, labels), v in m.items()
               if name == "cnosdb_errors_total"
               and dict(labels).get("area") == "mesh")


@pytest.fixture(scope="module")
def deployment(tmp_path_factory):
    from test_deadline import _Harness

    mp = pytest.MonkeyPatch()
    mp.setenv("CNOSDB_WAL_SYNC", "false")      # the configuration's server_env
    mp.delenv("CNOSDB_MESH", raising=False)
    mp.delenv("CNOSDB_MESH_MIN_ROWS", raising=False)
    mp.delenv("CNOSDB_MESH_MIN_DEVICES", raising=False)
    h = _Harness(str(tmp_path_factory.mktemp("shard8")))
    try:
        ds = devops.Dataset(SEED, HOSTS, STEPS)
        status, body, _ = h.request("POST", "/api/v1/sql?db=public",
                                    f"CREATE DATABASE {DB} WITH SHARD 8")
        assert status == 200, body
        for k in range(0, STEPS, 30):
            status, body, _ = h.request(
                "POST", f"/api/v1/write?db={DB}",
                ds.lines(k, min(k + 30, STEPS)).decode())
            assert status == 200, body      # acknowledged before the next
        d = _Deployment(h, ds)
        d.sql("FLUSH")
        text, _ = d.sql("SELECT count(*) FROM cpu")
        assert int(text.splitlines()[1]) == HOSTS * STEPS
        yield d
    finally:
        h.close()
        mp.undo()


@pytest.fixture
def width(monkeypatch):
    def set_width(n: int) -> None:
        monkeypatch.setenv("CNOSDB_MESH_DEVICES", str(n))
    return set_width


def test_series_are_spread_over_eight_vnodes(deployment):
    """`WITH SHARD 8` places series by hash of the series key: eight
    vnodes on this node, every host answered once."""
    from cnosdb_tpu.parallel.meta import DEFAULT_TENANT

    engine = deployment.h.server.coord.engine
    assert len(engine.local_vnodes(f"{DEFAULT_TENANT}.{DB}")) == 8
    text, _ = deployment.sql("SELECT hostname, count(*) FROM cpu "
                             "GROUP BY hostname")
    rows = [r.split(",") for r in text.splitlines()[1:]]
    assert len(rows) == HOSTS and all(int(n) == STEPS for _h, n in rows)


@pytest.mark.parametrize("aggregate", ["avg", "sum", "min", "max", "count"])
@pytest.mark.parametrize("n_dev", [1, 2, 4, 8])
def test_every_width_gives_the_references_answer(deployment, width, n_dev,
                                                  aggregate):
    """The shares add up to the whole: 8 vnodes over 1, 2, 4 or 8 devices
    (8, 4, 2, 1 batches a shard) answer as the reference does. A mesh of
    one declines (`few_devices`) and the host merge answers; every wider
    mesh engages once a query and books no other outcome."""
    width(n_dev)
    before = deployment.metrics()
    deployment.ask(_class(aggregate))
    after = deployment.metrics()
    rise = _rise(_mesh_outcomes(before), _mesh_outcomes(after))
    if n_dev == 1:
        assert rise == {("exec", "few_devices"): 1}, rise
    else:
        assert rise == {("exec", "engaged"): 1,
                        ("merge", "collective"): 1}, rise
    assert _mesh_errors(after) == _mesh_errors(before)


@pytest.mark.parametrize("lane", ["fused", "native"])
@pytest.mark.parametrize("cls", [FLEET, FLEET_ALL],
                         ids=lambda c: c["name"])
def test_one_device_fans_out_a_launch_a_vnode_and_merges_on_the_host(
        deployment, width, monkeypatch, cls, lane):
    """The deployment of `tsbs-devops-cpu-1k-shard8-1chip`: on one device
    the mesh lane declines once a query (`few_devices`) and the per-vnode
    fan-out + `_merge_results_vec` give the reference's answer, for one
    field and for all ten. The profile says so: a launch and a fetch a
    vnode with rows, both inside the `kernel_ms` section (thread-summed,
    so at most one `kernel_ms` a vnode), a merge that took time, and as
    many merged groups as the reference has. `fused` drives the device
    program on this backend; `native` is what a CPU node runs."""
    if lane == "fused":
        monkeypatch.setenv("CNOSDB_TPU_FORCE_DEVICE_PATH", "1")
    width(1)
    before = deployment.metrics()
    st = deployment.ask(cls)
    after = deployment.metrics()
    assert _rise(_mesh_outcomes(before), _mesh_outcomes(after)) \
        == {("exec", "few_devices"): 1}
    assert _mesh_errors(after) == _mesh_errors(before)
    assert st["fanout.vnodes"] == 8          # 300 hosts: no vnode is empty
    if lane == "fused":
        assert st["fused_launches"] == st["fanout.vnodes"]
        assert st["kernel.fetch_ms"] <= st["fanout.fetch_ms"] + 0.01
    else:
        assert "fused_launches" not in st
    assert st["fanout.launch_ms"] > 0 and st["fanout.fetch_ms"] >= 0
    assert st["fanout.launch_ms"] + st["fanout.fetch_ms"] \
        <= st["fanout.vnodes"] * st["kernel_ms"] + 0.01
    assert st["merge_ms"] > 0
    assert st["merge.groups"] == st["answer_rows"] == st["group_count"]
    assert not [k for k in st if k.startswith("mesh.")], sorted(st)


def test_the_fan_outs_launches_dispatch_one_at_a_time(deployment, width,
                                                      monkeypatch):
    """Eight pool threads reach the fused program at once, each vnode
    with shapes of its own; a call with new shapes compiles, and eight
    compiles side by side crashed the TPU compiler on a v5e (PR 33). So
    the call itself is serial: never two threads inside it."""
    import threading
    import time

    from cnosdb_tpu.ops import fused

    guard, inside, peak = threading.Lock(), [0], [0]
    build = fused._build_kernel

    def build_slow(*a, **k):
        fn, manifest = build(*a, **k)

        def slow(*args):
            with guard:
                inside[0] += 1
                peak[0] = max(peak[0], inside[0])
            try:
                time.sleep(0.02)
                return fn(*args)
            finally:
                with guard:
                    inside[0] -= 1
        return slow, manifest

    monkeypatch.setenv("CNOSDB_TPU_FORCE_DEVICE_PATH", "1")
    monkeypatch.setattr(fused, "_build_kernel", build_slow)
    monkeypatch.setattr(fused, "_kernel_cache", {})
    width(1)
    st = deployment.ask(FLEET)
    assert st["fused_launches"] == 8 and peak[0] == 1, (st, peak)


@pytest.mark.parametrize("where", ["one_shard", "mesh4"])
def test_no_fanout_key_off_the_fan_out_branch(deployment, width, where):
    """One shard finalizes straight from its one batch; a mesh of four
    merges by collective: neither books a `fanout.*` key or `merge.groups`,
    and both give the reference's answer."""
    if where == "mesh4":
        width(4)
        st = deployment.ask(FLEET)
        assert st["mesh.shards"] == 4
    else:
        small = devops.Dataset(SEED + 1, 40, 120)
        deployment.sql("CREATE DATABASE one", db="public")
        status, out, _ = deployment.h.request(
            "POST", "/api/v1/write?db=one", small.lines(0, 120).decode())
        assert status == 200, out
        deployment.sql("FLUSH", db="one")
        width(1)
        st = deployment.ask(FLEET, db="one", ds=small)
        assert st["answer_rows"] > 0 and "kernel_ms" in st
    assert not [k for k in FANOUT_KEYS if k in st], sorted(st)


@pytest.fixture(scope="module")
def compiles():
    """[n]: every backend compile of this process from here on, whatever
    asked for it — a jitted program, an eager operation, a sharded put's
    layout program."""
    import jax

    n = [0]

    def on_duration(event: str, _secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            n[0] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    return n


def test_shifted_windows_compile_nothing_after_the_warm_up(deployment, width,
                                                           compiles):
    """The benchmark's window must hold no compile: after two warm-up
    requests, ten more with windows drawn anew (another start, another
    count of rows a shard, 1 or 2 hour buckets) find every program — the
    merge, the sharded puts — compiled. The lane's shapes are size classes
    of what it uploads, not the sizes themselves."""
    from cnosdb_tpu.parallel.distributed_agg import mesh_merge_kernel

    width(4)
    for _ in range(2):
        deployment.ask(FLEET)
    n0, cached0 = compiles[0], mesh_merge_kernel._cache_size()
    before = deployment.metrics()
    for _ in range(10):
        deployment.ask(FLEET)
    rise = _rise(_mesh_outcomes(before), _mesh_outcomes(deployment.metrics()))
    assert rise == {("exec", "engaged"): 10, ("merge", "collective"): 10}
    assert mesh_merge_kernel._cache_size() == cached0
    assert compiles[0] == n0


class _Starts:
    """A stand-in for the generator's rng that hands out chosen window
    starts (seconds after the first point)."""

    def __init__(self, *starts):
        self.starts = list(starts)

    def integers(self, _lo, _hi):
        return self.starts.pop(0)


def test_a_window_on_a_bucket_boundary_is_no_new_program(
        deployment, width, monkeypatch, compiles):
    """A window that starts on a bucket boundary has one bucket fewer
    than its neighbours (TSBS's 12 h window cut to 5/6 of the loaded span
    is ten seconds short of whole buckets, so one window in 360 does).
    The bucket count rides in the fused program's params: after two
    warm-up requests such a window compiles nothing — on the chip it
    was one compile a vnode, seconds inside a request (PR 33)."""
    from cnosdb_tpu.ops import fused

    # 5-minute buckets over the 2 991 s window: 11 of them, 10 from a
    # boundary; 34-43 series a vnode keep both in one segment size class
    cls = {**FLEET, "name": "double-groupby-1-5min", "bucket_s": 300}
    monkeypatch.setenv("CNOSDB_TPU_FORCE_DEVICE_PATH", "1")
    monkeypatch.setattr(deployment, "rng", _Starts(150, 151, 0, 152))
    width(1)
    for _ in range(2):
        assert deployment.ask(cls)["answer_rows"] == 11 * HOSTS
    n0, cached0 = compiles[0], len(fused._kernel_cache)
    assert deployment.ask(cls)["answer_rows"] == 10 * HOSTS
    assert deployment.ask(cls)["answer_rows"] == 11 * HOSTS
    assert len(fused._kernel_cache) == cached0
    assert compiles[0] == n0


@pytest.mark.parametrize("step,exc,reason,kind", [
    ("_build_prep", ValueError("injected: no layout today"),
     "plan_error", "plan"),
    ("_run_collectives", RuntimeError("injected: a chip went away"),
     "device_loss", "collective")])
def test_a_failure_in_the_lane_is_booked_said_once_and_answered(
        deployment, width, monkeypatch, caplog, step, exc, reason, kind):
    """An exception inside the lane's plan or its collective:
    `cnosdb_errors_total{area=mesh}` rises (the benchmark turns that into
    `correct: false`), the decline is booked under its reason, the log says
    once what it was, and the host merge gives the reference's answer."""
    from cnosdb_tpu.ops import mesh_exec

    def boom(*_a, **_k):
        raise exc

    width(4)
    monkeypatch.setattr(mesh_exec, step, boom)
    monkeypatch.setattr(mesh_exec, "_logged_failures", set())
    before = deployment.metrics()
    with caplog.at_level(logging.WARNING, logger=mesh_exec.__name__):
        deployment.ask(FLEET)
        deployment.ask(FLEET)
    after = deployment.metrics()
    assert _rise(_mesh_outcomes(before), _mesh_outcomes(after)) \
        == {("exec", reason): 2}
    assert metric(after, "cnosdb_errors_total", area="mesh", kind=kind) \
        - metric(before, "cnosdb_errors_total", area="mesh", kind=kind) == 2
    said = [r for r in caplog.records if reason in r.getMessage()]
    assert len(said) == 1, [r.getMessage() for r in caplog.records]
    assert f"{type(exc).__name__}: {exc}" in said[0].getMessage()
    assert said[0].exc_info is not None


# ---- the scan's device hooks (parallel/coordinator.py): one expected error
@pytest.mark.parametrize("hook", ["_upload_hook", "_decode_hook"])
def test_scan_hooks_take_the_host_lanes_without_a_backend(monkeypatch, hook):
    """JAX cannot initialize the backend it was given (a host with no
    accelerator): the scan runs its host lanes."""
    from cnosdb_tpu.ops import placement
    from cnosdb_tpu.parallel.coordinator import Coordinator

    def no_backend():
        raise RuntimeError("Unable to initialize backend 'tpu'")

    monkeypatch.setattr(placement, "scan_device", no_backend)
    assert getattr(Coordinator, hook)(Coordinator.__new__(Coordinator)) is None


@pytest.mark.parametrize("hook,module,name", [
    ("_upload_hook", "cnosdb_tpu.ops.tpu_exec", "_FORCE_DEVICE"),
    ("_decode_hook", "cnosdb_tpu.ops.device_decode", "enabled")])
def test_scan_hooks_do_not_hide_a_broken_lane(monkeypatch, hook, module, name):
    """Anything else a device lane raises while it is probed fails the
    scan: with chips attached, a broken lane is not a quiet host path."""
    import importlib

    from cnosdb_tpu.parallel.coordinator import Coordinator

    def broken():
        raise AttributeError("the lane's probe is broken")

    monkeypatch.setattr(importlib.import_module(module), name, broken)
    with pytest.raises(AttributeError):
        getattr(Coordinator, hook)(Coordinator.__new__(Coordinator))
