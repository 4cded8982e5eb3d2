"""Mesh execution plane (ops/mesh_exec.py + parallel/distributed_agg.py).

Parity contract: everything the mesh lane merges through XLA collectives
must be BIT-identical to the legacy per-batch kernel fan-out +
`_merge_results_vec` host merge — including f64 sum association (the
run-aware reduceat staging), NULL/NaN propagation, first/last tie-breaks
and output dtypes. The suite A/Bs whole queries against `CNOSDB_MESH=0`
on the 8-virtual-device CPU mesh the conftest forces, checks a numpy
oracle on the order-insensitive aggregates, and drives the nemesis
`device_loss` fault through the lane's transparent host-merge fallback.

Counters double as the no-host-hops proof: an engaged query must book
(merge, collective) and nothing else — any msgpack host merge would
surface as a decline reason instead.
"""
import numpy as np
import pytest

from cnosdb_tpu import faults
from cnosdb_tpu.parallel import mesh

BASE = 1_700_000_000_000_000_000
MINUTE = 60_000_000_000


@pytest.fixture
def db(tmp_path, monkeypatch):
    """4-shard database so scans produce multiple mesh-local batches;
    thresholds opened so small test tables engage; serving cache off so
    every execute_one actually runs the lane."""
    monkeypatch.setenv("CNOSDB_SERVING", "0")
    monkeypatch.setenv("CNOSDB_MESH", "1")
    monkeypatch.setenv("CNOSDB_MESH_MIN_ROWS", "0")
    monkeypatch.setenv("CNOSDB_MESH_MIN_DEVICES", "2")
    from cnosdb_tpu.parallel.coordinator import Coordinator
    from cnosdb_tpu.parallel.meta import MetaStore
    from cnosdb_tpu.sql.executor import QueryExecutor, Session
    from cnosdb_tpu.storage.engine import TsKv

    meta = MetaStore(str(tmp_path / "meta.json"))
    engine = TsKv(str(tmp_path / "data"))
    ex = QueryExecutor(meta, Coordinator(meta, engine))
    ex.execute_one("CREATE DATABASE mesh WITH SHARD 4 REPLICA 1")
    yield ex, Session(database="mesh")
    engine.close()


def _run_all(ex, s, queries):
    """repr-compare columns so NaN/-0.0/dtype differences all surface."""
    outs = []
    for q in queries:
        rs = ex.execute_one(q, s)
        outs.append((rs.names, [repr(c.tolist()) for c in rs.columns],
                     [str(c.dtype) for c in rs.columns]))
    return outs


def _ab(ex, s, queries, monkeypatch, expect_engaged=None):
    """Mesh pass first (counters asserted), then CNOSDB_MESH=0 oracle;
    every query must match byte-for-byte."""
    mesh.reset_counters()
    got = _run_all(ex, s, queries)
    snap = mesh.outcomes_snapshot()
    engaged = snap.get(("exec", "engaged"), 0)
    if expect_engaged is not None:
        assert engaged == expect_engaged, snap
    else:
        assert engaged > 0, snap
    # the no-host-hops proof: every engaged merge went collective
    assert snap.get(("merge", "collective"), 0) == engaged, snap
    assert snap.get(("merge", "host"), 0) == 0, snap
    monkeypatch.setenv("CNOSDB_MESH", "0")
    legacy = _run_all(ex, s, queries)
    monkeypatch.setenv("CNOSDB_MESH", "1")
    for q, a, b in zip(queries, got, legacy):
        assert a == b, q
    return got


@pytest.fixture
def seeded(db):
    """2000 rows, 16 hosts x 3 regions, normal floats + small ints."""
    ex, s = db
    ex.execute_one("CREATE TABLE m (v DOUBLE, i BIGINT, "
                   "TAGS(host, region))", s)
    rng = np.random.default_rng(7)
    rows = []
    for i in range(2000):
        rows.append((BASE + i * MINUTE, f"h{i % 16}", f"r{i % 3}",
                     float(rng.standard_normal()),
                     int(rng.integers(0, 100))))
    vals = ", ".join(f"({t}, '{h}', '{r}', {v!r}, {iv})"
                     for t, h, r, v, iv in rows)
    ex.execute_one(f"INSERT INTO m (time, host, region, v, i) "
                   f"VALUES {vals}", s)
    return ex, s, rows


TAG_QUERIES = [
    "SELECT host, count(*) AS c, sum(v) AS sv, min(v) AS mn, "
    "max(i) AS mx FROM m GROUP BY host",
    "SELECT host, region, first(v) AS f, last(v) AS l FROM m "
    "GROUP BY host, region",
    "SELECT date_bin(INTERVAL '1 hour', time) AS t, sum(v) AS sv, "
    "count(i) AS c FROM m GROUP BY t",
    "SELECT host, date_bin(INTERVAL '2 hour', time) AS t, sum(v) sv, "
    "first(i) f FROM m GROUP BY host, t",
    "SELECT count(*) AS c, sum(v) AS sv FROM m",
    "SELECT host, sum(v) sv FROM m WHERE v > 0 GROUP BY host",
    "SELECT host, avg(v) a FROM m GROUP BY host",
    "SELECT host, min(i) mn, max(v) mx, last(i) l FROM m "
    "WHERE region = 'r1' GROUP BY host",
    "SELECT host, sum(v) sv, first(v) f FROM m GROUP BY host",
]


def test_tag_groupby_bit_parity(seeded, monkeypatch):
    """Every shape the lane owns engages and matches the legacy merge
    byte-for-byte: tag group-by, date_bin buckets, global aggregates,
    filters, avg rewrite, f64 sums, first/last."""
    ex, s, _rows = seeded
    _ab(ex, s, TAG_QUERIES, monkeypatch,
        expect_engaged=len(TAG_QUERIES))


def test_numpy_oracle_order_insensitive_aggs(seeded, monkeypatch):
    """count / integer sum / min / max / first / last per host against a
    pure-python+numpy oracle over the inserted rows — these aggregates
    are association-free, so the oracle equality is exact, not approx."""
    ex, s, rows = seeded
    mesh.reset_counters()
    rs = ex.execute_one(
        "SELECT host, count(*) c, sum(i) si, min(v) mn, max(v) mx, "
        "first(v) f, last(v) l FROM m GROUP BY host ORDER BY host", s)
    assert mesh.outcomes_snapshot().get(("exec", "engaged")) == 1
    by_host: dict = {}
    for t, h, _r, v, iv in rows:
        by_host.setdefault(h, []).append((t, v, iv))
    got = list(zip(*[c.tolist() for c in rs.columns]))
    assert [g[0] for g in got] == sorted(by_host)
    for h, c, si, mn, mx, f, last in got:
        ent = by_host[h]
        assert c == len(ent)
        assert si == sum(iv for _t, _v, iv in ent)
        assert mn == min(v for _t, v, _iv in ent)
        assert mx == max(v for _t, v, _iv in ent)
        assert f == min(ent)[1]      # value at earliest timestamp
        assert last == max(ent)[1]   # value at latest timestamp


def test_null_nan_string_dictionary_parity(db, monkeypatch):
    """NULL runs in values, real NaN payloads (0.0/0.0), NULL string
    group keys through the dictionary path (CNOSDB_MESH_FIELDS=1 with
    ORDER BY pinning row order), DISTINCT declining to the legacy lane,
    and a single-vnode filter falling back — all byte-identical."""
    monkeypatch.setenv("CNOSDB_MESH_FIELDS", "1")
    ex, s = db
    ex.execute_one("CREATE TABLE m (v DOUBLE, i BIGINT, w DOUBLE, "
                   "s STRING, TAGS(host))", s)
    rng = np.random.default_rng(11)
    parts = []
    for i in range(1200):
        t = BASE + i * MINUTE
        v = "NULL" if i % 5 == 0 else repr(float(rng.standard_normal()))
        w = "(0.0/0.0)" if i % 7 == 0 else \
            repr(float(rng.standard_normal()))
        iv = "NULL" if i % 11 == 0 else str(int(rng.integers(-5, 5)))
        sv = "NULL" if i % 13 == 0 else f"'s{i % 3}'"
        parts.append(f"({t}, 'h{i % 8}', {v}, {iv}, {w}, {sv})")
    ex.execute_one("INSERT INTO m (time, host, v, i, w, s) VALUES "
                   + ", ".join(parts), s)
    queries = [
        "SELECT host, count(v) c, sum(v) sv, min(v) mn, max(v) mx "
        "FROM m GROUP BY host",
        "SELECT host, sum(w) sw, min(w) mn, max(w) mx FROM m "
        "GROUP BY host",
        "SELECT host, first(v) f, last(v) l, sum(i) si FROM m "
        "GROUP BY host",
        "SELECT s, sum(v) sv, count(*) c FROM m GROUP BY s ORDER BY s",
        "SELECT host, s, avg(v) a FROM m GROUP BY host, s "
        "ORDER BY host, s",
        "SELECT host, sum(v) sv FROM m WHERE i > 100 GROUP BY host",
        "SELECT host, sum(v) sv FROM m WHERE host = 'h3' GROUP BY host",
        "SELECT host, count(DISTINCT s) cd FROM m GROUP BY host",
        "SELECT host, sum(v) sv, first(w) fw FROM m "
        "WHERE v IS NOT NULL GROUP BY host",
    ]
    _ab(ex, s, queries, monkeypatch)


def test_mesh_off_books_disabled_and_never_engages(seeded, monkeypatch):
    """CNOSDB_MESH=0 is the byte-identical legacy path: the lane books
    only `disabled` declines, and repeated runs are bytewise stable."""
    ex, s, _rows = seeded
    monkeypatch.setenv("CNOSDB_MESH", "0")
    mesh.reset_counters()
    a = _run_all(ex, s, TAG_QUERIES[:3])
    b = _run_all(ex, s, TAG_QUERIES[:3])
    snap = mesh.outcomes_snapshot()
    assert a == b
    assert snap.get(("exec", "engaged"), 0) == 0, snap
    assert snap.get(("exec", "disabled"), 0) == 6, snap


def test_float_aggregates_decline_on_a_device_that_rounds_f64(
        seeded, monkeypatch):
    """A TPU carries an f64 as an f32 pair: the lane books value_dtype for
    FLOAT aggregates there and the exact legacy merge answers; integer
    aggregates still engage."""
    from cnosdb_tpu.ops import placement

    ex, s, _rows = seeded
    monkeypatch.setattr(placement, "_f64_exact", False)
    mesh.reset_counters()
    _run_all(ex, s, ["SELECT host, min(v) mn, last(v) l FROM m GROUP BY host",
                     "SELECT host, max(i) mx, sum(i) si FROM m GROUP BY host"])
    snap = mesh.outcomes_snapshot()
    assert snap.get(("exec", "value_dtype")) == 1, snap
    assert snap.get(("exec", "engaged")) == 1, snap


def test_device_loss_falls_back_bit_identical(seeded, monkeypatch):
    """The nemesis `device_loss` injection (mesh.collective:fail) kills
    the merge kernel mid-collective: the lane must book device_loss,
    answer through the legacy host merge byte-identically, and re-engage
    once healed."""
    ex, s, _rows = seeded
    q = TAG_QUERIES[0]
    mesh.reset_counters()
    base = _run_all(ex, s, [q])
    assert mesh.outcomes_snapshot().get(("exec", "engaged")) == 1
    faults.configure("seed=1;mesh.collective:fail")
    try:
        mesh.reset_counters()
        faulted = _run_all(ex, s, [q])
        snap = mesh.outcomes_snapshot()
        assert snap.get(("exec", "device_loss")) == 1, snap
        assert snap.get(("exec", "engaged"), 0) == 0, snap
        assert faulted == base
    finally:
        faults.configure("seed=1")
    mesh.reset_counters()
    healed = _run_all(ex, s, [q])
    assert mesh.outcomes_snapshot().get(("exec", "engaged")) == 1
    assert healed == base


def test_nemesis_device_loss_plan_and_specs():
    """device_loss is a first-class nemesis kind: seeded plans include
    it, its spec arms the mesh.collective fault point on the victim only,
    and heal keeps the control surface armed (bare seed, not "")."""
    from cnosdb_tpu.chaos import nemesis

    plan = nemesis.generate_plan(31, n_nodes=3, steps=6,
                                 kinds=("device_loss",))
    assert plan == nemesis.generate_plan(31, n_nodes=3, steps=6,
                                         kinds=("device_loss",))
    assert all(ev.kind == "device_loss" for ev in plan)
    ev = plan[0]
    vspec, ospec = nemesis.event_specs(ev, "127.0.0.1:9999", 31)
    assert vspec == f"seed={31 + ev.step};mesh.collective:fail"
    assert ospec == ""
    assert nemesis.heal_spec(31, ev) == f"seed={31 + ev.step}"
    assert "device_loss" in nemesis.KINDS
