"""Device-placement CI coverage (round-3 verdict: the fused device path
shipped with zero test coverage — a broken fused.py would have gone green).

CNOSDB_TPU_FORCE_DEVICE_PATH=1 makes tpu_exec take the device placement on
the CPU backend: eligible queries run the fused DeviceBatch/launch_fused
program, ineligible ones the aggregate_column_host XLA wrapper. Every
query here executes twice — host placement then forced device placement —
and the results must agree bit-for-bit, so any defect in fused.py /
device_cache.py diverges from the host oracle and fails.
"""
import numpy as np
import pytest

from cnosdb_tpu.ops import fused
from cnosdb_tpu.parallel.coordinator import Coordinator
from cnosdb_tpu.parallel.meta import MetaStore
from cnosdb_tpu.sql.executor import QueryExecutor, Session
from cnosdb_tpu.storage.engine import TsKv


@pytest.fixture(scope="module")
def db(tmp_path_factory):
    d = tmp_path_factory.mktemp("devpath")
    meta = MetaStore(str(d / "meta.json"))
    engine = TsKv(str(d / "data"))
    coord = Coordinator(meta, engine)
    ex = QueryExecutor(meta, coord)
    ex.execute_one("CREATE TABLE cpu (usage DOUBLE, load DOUBLE, "
                   "cnt BIGINT, flag BOOLEAN, TAGS(host, region))")
    rng = np.random.default_rng(7)
    rows = []
    t0 = 1_600_000_000_000_000_000
    for h in range(6):
        region = "eu" if h % 2 == 0 else "us"
        for k in range(200):
            ts = t0 + k * 30_000_000_000 + h  # 30s cadence, staggered
            u = round(float(rng.normal(50, 10)), 3)
            ld = round(float(rng.normal(1, 0.2)), 3)
            c = int(rng.integers(-100, 100))
            fields = f"usage={u},cnt={c}i,flag={'t' if k % 3 else 'f'}"
            if k % 5 != 0:      # load is nullable: every 5th row missing
                fields += f",load={ld}"
            rows.append(f"cpu,host=h{h},region={region} {fields} {ts}")
    from cnosdb_tpu.protocol.line_protocol import parse_lines

    wb = parse_lines("\n".join(rows))
    from cnosdb_tpu.parallel.meta import DEFAULT_TENANT

    coord.write_points(DEFAULT_TENANT, "public", wb)
    yield ex
    coord.close()


QUERIES = [
    # fused-eligible: numeric aggs, tag group-by, time buckets, filters
    "SELECT count(*) FROM cpu",
    "SELECT count(usage), sum(usage), min(usage), max(usage) FROM cpu",
    "SELECT avg(usage) FROM cpu",
    "SELECT host, sum(usage) FROM cpu GROUP BY host ORDER BY host",
    "SELECT host, region, count(*), max(cnt) FROM cpu "
    "GROUP BY host, region ORDER BY host, region",
    "SELECT time_bucket(time, '5m') AS b, avg(usage) FROM cpu "
    "GROUP BY b ORDER BY b",
    "SELECT host, time_bucket(time, '10m') AS b, min(usage), max(load) "
    "FROM cpu GROUP BY host, b ORDER BY host, b",
    "SELECT host, count(load), sum(load) FROM cpu GROUP BY host "
    "ORDER BY host",                                  # nullable column
    "SELECT count(*) FROM cpu WHERE usage > 50",
    "SELECT host, sum(cnt) FROM cpu WHERE usage > 40 AND load < 1.2 "
    "GROUP BY host ORDER BY host",
    "SELECT max(usage) FROM cpu WHERE cnt >= 0",
    "SELECT first(usage), last(usage) FROM cpu",      # rank selection
    "SELECT host, first(load), last(cnt) FROM cpu GROUP BY host "
    "ORDER BY host",
    "SELECT time_bucket(time, '1h') AS b, first(usage), last(usage) "
    "FROM cpu GROUP BY b ORDER BY b",
    "SELECT count(flag), sum(cnt) FROM cpu WHERE flag = true",
    # device-INELIGIBLE shapes (strings/tags in filter, IS NULL, time agg):
    # forced mode must still answer correctly via aggregate_column_host
    "SELECT count(*) FROM cpu WHERE host = 'h1'",
    "SELECT host, count(*) FROM cpu WHERE load IS NULL GROUP BY host "
    "ORDER BY host",
    "SELECT min(time), max(time) FROM cpu",
]


def _run(ex, sql):
    rs = ex.execute_one(sql, Session(database="public"))
    return rs.names, [tuple(col.tolist()) for col in rs.columns]


@pytest.mark.parametrize("sql", QUERIES)
def test_forced_device_path_matches_host(db, sql, monkeypatch):
    monkeypatch.setenv("CNOSDB_TPU_FORCE_DEVICE_PATH", "0")
    host = _run(db, sql)
    monkeypatch.setenv("CNOSDB_TPU_FORCE_DEVICE_PATH", "1")
    dev = _run(db, sql)
    assert host[0] == dev[0]
    for hc, dc in zip(host[1], dev[1]):
        for a, b in zip(hc, dc):
            if isinstance(a, float) and isinstance(b, float):
                assert a == pytest.approx(b, rel=1e-12, nan_ok=True), sql
            else:
                assert a == b, sql


def test_fused_kernel_actually_launches(db, monkeypatch):
    """The forced run must go through launch_fused — guards against the
    override silently routing back to the host path."""
    monkeypatch.setenv("CNOSDB_TPU_FORCE_DEVICE_PATH", "1")
    before = fused.launch_count
    _run(db, "SELECT host, avg(usage) FROM cpu GROUP BY host ORDER BY host")
    assert fused.launch_count > before


# ---------------------------------------------------------------------------
# a device without exact f64 (a TPU carries one as an f32 pair: ~49 bits
# of mantissa, f32's range): FLOAT columns stay on the exact host lanes
# ---------------------------------------------------------------------------
@pytest.fixture
def rounding_device(monkeypatch):
    from cnosdb_tpu.ops import placement

    monkeypatch.setenv("CNOSDB_TPU_FORCE_DEVICE_PATH", "1")
    monkeypatch.setattr(placement, "_f64_exact", False)


def _profiled(ex, sql):
    from cnosdb_tpu.utils import stages

    prof = stages.QueryProfile()
    with stages.profile_scope(prof):
        out = _run(ex, sql)
    return out, prof.counts


@pytest.mark.parametrize("sql", [
    "SELECT host, min(usage), max(usage), first(usage), last(load) "
    "FROM cpu GROUP BY host ORDER BY host",
    "SELECT host, sum(cnt) FROM cpu WHERE usage > 40 GROUP BY host "
    "ORDER BY host",                       # FLOAT only in the filter
    "SELECT count(*) FROM cpu WHERE host = 'h1' AND load < 1.2",
])
def test_float_queries_stay_on_host_kernels(db, sql, monkeypatch,
                                            rounding_device):
    before = fused.launch_count
    got, counts = _profiled(db, sql)
    assert counts.get("f64_kept_on_host") and not counts.get("fused_launches")
    assert fused.launch_count == before
    monkeypatch.setenv("CNOSDB_TPU_FORCE_DEVICE_PATH", "0")
    assert got == _run(db, sql)            # bit for bit the host answer


def test_integer_queries_still_launch_fused(db, rounding_device):
    _got, counts = _profiled(
        db, "SELECT host, max(cnt), sum(cnt) FROM cpu GROUP BY host "
            "ORDER BY host")
    assert counts.get("fused_launches") and not counts.get("f64_kept_on_host")


def test_float_columns_get_no_device_twin(rounding_device):
    from cnosdb_tpu.models.schema import ValueType
    from cnosdb_tpu.ops.device_cache import EagerUploader, _device_resident

    assert not _device_resident(ValueType.FLOAT)
    assert _device_resident(ValueType.INTEGER)
    assert _device_resident(ValueType.BOOLEAN)
    up = EagerUploader(8)
    up.put("usage", ValueType.FLOAT, np.ones(8), np.ones(8, dtype=bool))
    up.put("cnt", ValueType.INTEGER, np.ones(8, dtype=np.int64),
           np.ones(8, dtype=bool))
    assert list(up._cols) == ["cnt"]


def test_gorilla_pages_decline_the_device_decode_lane(rounding_device):
    from cnosdb_tpu.models.codec import Encoding
    from cnosdb_tpu.models.schema import ValueType
    from cnosdb_tpu.ops import device_decode

    before = device_decode.outcomes_snapshot().get(
        ("host", "f64_inexact_on_device"), 0)
    lane = device_decode.DeviceDecodeLane()
    assert not lane.accepts(int(ValueType.FLOAT), int(Encoding.GORILLA))
    assert lane.accepts(int(ValueType.INTEGER), int(Encoding.DELTA))
    assert device_decode.outcomes_snapshot()[
        ("host", "f64_inexact_on_device")] == before + 1


def test_float_topk_threshold_stays_on_host(monkeypatch, rounding_device):
    from cnosdb_tpu.ops import strkernels
    from cnosdb_tpu.utils import stages

    monkeypatch.setenv("CNOSDB_TPU_TOPK", "1")
    vals = np.random.default_rng(3).normal(0, 1, 1000)
    prof = stages.QueryProfile()
    with stages.profile_scope(prof):
        idx = strkernels.topk_order_indices(vals, None, False, 10)
        strkernels.topk_order_indices(np.arange(1000), None, False, 10)
    assert idx.tolist() == np.argsort(-vals, kind="stable")[:10].tolist()
    assert prof.counts == {"f64_kept_on_host": 1, "topk.host": 1,
                           "topk.device": 1}


def test_sqllogic_aggregates_forced_device(db, monkeypatch, tmp_path):
    """The aggregate slt matrix re-runs under the forced device placement
    (fresh database per file, same golden expectations)."""
    import os

    from tests.test_sqllogic import CASES_DIR, _parse_slt
    from cnosdb_tpu.server.http import format_csv

    monkeypatch.setenv("CNOSDB_TPU_FORCE_DEVICE_PATH", "1")
    agg_cases = sorted(
        f for f in os.listdir(CASES_DIR)
        if f.startswith(("gen_agg", "gen_group", "gen_time_bucket",
                         "dql_agg", "dql_time_bucket", "dql_filter")))
    assert len(agg_cases) >= 8
    for case in agg_cases:
        d = tmp_path / case
        meta = MetaStore(str(d / "meta.json"))
        engine = TsKv(str(d / "data"))
        coord = Coordinator(meta, engine)
        ex = QueryExecutor(meta, coord)
        session = Session()
        try:
            for kind, sql, expected, lineno in _parse_slt(
                    os.path.join(CASES_DIR, case)):
                if kind == "ok":
                    ex.execute_one(sql, session)
                elif kind == "error":
                    with pytest.raises(Exception):
                        ex.execute_one(sql, session)
                else:
                    rs = ex.execute_one(sql, session)
                    got = format_csv(rs)[:-1].split("\n")
                    expected = [ln.replace("\\N", "") for ln in expected]
                    assert got == expected, f"{case}:{lineno} {sql!r}"
        finally:
            coord.close()
