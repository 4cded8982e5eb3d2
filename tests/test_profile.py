"""Per-query profiling plane: scoped QueryProfile, cross-thread/RPC
propagation, EXPLAIN ANALYZE breakdowns, slow-query log, /debug/profile,
and the streaming /metrics histograms (reference query_sql_process_ms +
DataFusion EXPLAIN ANALYZE metrics)."""
import json
import re
import threading
import time

import pytest

from cnosdb_tpu.errors import DeadlineExceeded, QueryError
from cnosdb_tpu.parallel.coordinator import Coordinator
from cnosdb_tpu.parallel.meta import DEFAULT_TENANT, MetaStore
from cnosdb_tpu.sql.executor import QueryExecutor, Session
from cnosdb_tpu.storage.engine import TsKv
from cnosdb_tpu.utils import deadline as deadline_mod
from cnosdb_tpu.utils import executor as pool_mod
from cnosdb_tpu.utils import stages


# ------------------------------------------------------------------ units
def test_stage_and_count_land_in_active_profile():
    prof = stages.QueryProfile(qid="q1")
    with stages.profile_scope(prof):
        with stages.stage("decode_ms"):
            time.sleep(0.002)
        stages.count("scan_hit")
        stages.count("upload_bytes", 4096)
    snap = prof.snapshot()
    assert snap["decode_ms"] >= 1.0
    assert snap["scan_hit"] == 1
    assert snap["upload_bytes"] == 4096
    # outside any scope both are no-ops, not errors
    with stages.stage("decode_ms"):
        pass
    stages.count("scan_hit")
    assert prof.snapshot() == snap


def test_profile_scope_nesting_and_clear():
    outer = stages.QueryProfile()
    with stages.profile_scope(outer):
        assert stages.current_profile() is outer
        with stages.profile_scope(None):   # background work: bill nobody
            assert stages.current_profile() is None
            stages.count("scan_hit")
        assert stages.current_profile() is outer
    assert stages.current_profile() is None
    assert outer.snapshot() == {}


def test_merge_child_and_node_stages():
    parent = stages.QueryProfile(node_id=1)
    child = stages.QueryProfile(node_id=1)
    child.add_ms("kernel_ms", 5.0)
    child.add_count("group_count", 7)
    child.merge_remote({"node": 2, "ms": {"rpc_scan_vnode_ms": 3.0},
                        "counts": {"scan_miss": 1}})
    parent.merge_child(child)
    nodes = parent.node_stages()
    assert nodes["1"]["kernel_ms"] == 5.0
    assert nodes["1"]["group_count"] == 7
    assert nodes["2"]["rpc_scan_vnode_ms"] == 3.0
    totals = parent.stage_totals()
    assert totals["kernel_ms"] == 5.0 and totals["scan_miss"] == 1


def test_profile_ring_is_bounded_and_queryable():
    ring = stages.ProfileRing(capacity=8)
    for i in range(20):
        ring.record(stages.QueryProfile(qid=str(i)).finish(wall_ms=float(i)))
    assert len(ring.recent(limit=256)) == 8
    assert ring.get("19")["wall_ms"] == 19.0
    assert ring.get("0") is None          # evicted
    assert ring.recent(limit=3)[-1]["qid"] == "19"


# ----------------------------------------------- cross-thread propagation
def test_profile_and_trace_cross_pool_workers():
    """The classic contextvar loss: work submitted to the shared pools
    must keep billing the submitting query's profile and trace."""
    from cnosdb_tpu.utils.spans import GLOBAL_COLLECTOR, current_trace_header

    prof = stages.QueryProfile()
    seen = []

    def task(i):
        stages.count("scan_hit")
        with stages.stage("decode_ms"):
            time.sleep(0.001)
        seen.append((threading.current_thread().name,
                     stages.current_profile(), current_trace_header()))
        return i

    with GLOBAL_COLLECTOR.span("query") as span:
        with stages.profile_scope(prof):
            out = pool_mod.run_all("decode", task, list(range(8)))
    assert out == list(range(8))
    snap = prof.snapshot()
    assert snap["scan_hit"] == 8, "counts lost crossing the pool boundary"
    assert snap["decode_ms"] >= 8 * 1.0
    workers = {name for name, _p, _t in seen}
    assert any(n != threading.current_thread().name for n in workers)
    for _name, p, hdr in seen:
        assert p is prof, "profile did not cross the pool boundary"
        assert hdr and hdr.startswith(span.trace_id + ":"), \
            "trace context did not cross the pool boundary"


# ----------------------------------------------------------- RPC envelope
def test_rpc_subprofile_round_trip():
    from cnosdb_tpu.parallel.net import RpcServer, rpc_call

    handler_profiled = []

    def handler(p):
        handler_profiled.append(stages.current_profile() is not None)
        with stages.stage("decode_ms"):
            time.sleep(0.002)
        stages.count("scan_miss")
        return {"ok": True, "vnode_id": p.get("vnode_id")}

    srv = RpcServer("127.0.0.1", 0, {"scan_vnode": handler},
                    node_id=7).start()
    try:
        # no profile in scope: no marker sent, handler runs unprofiled
        reply = rpc_call(srv.addr, "scan_vnode", {"vnode_id": 3})
        assert handler_profiled == [False]
        assert "_profile" not in reply
        prof = stages.QueryProfile(node_id=1)
        with stages.profile_scope(prof):
            reply = rpc_call(srv.addr, "scan_vnode", {"vnode_id": 3})
        assert handler_profiled == [False, True]
        assert "_profile" not in reply, "envelope must be stripped"
        assert len(prof.subprofiles) == 1
        sub = prof.subprofiles[0]
        assert sub["node"] == 7
        assert sub["method"] == "scan_vnode" and sub["vnode"] == 3
        assert sub["counts"]["scan_miss"] == 1
        assert sub["ms"]["decode_ms"] >= 1.0
        assert sub["ms"]["rpc_scan_vnode_ms"] >= sub["ms"]["decode_ms"]
        assert prof.node_stages()["7"]["scan_miss"] == 1
    finally:
        srv.stop()


# --------------------------------------------------------- EXPLAIN ANALYZE
@pytest.fixture
def db(tmp_path):
    meta = MetaStore(str(tmp_path / "meta.json"))
    engine = TsKv(str(tmp_path / "data"))
    coord = Coordinator(meta, engine)
    ex = QueryExecutor(meta, coord)
    yield ex
    coord.close()


def _seed(db, n=200):
    db.execute_one("CREATE TABLE m (v DOUBLE, TAGS(h))")
    rows = ", ".join(f"({i * 10**9}, 'h{i % 4}', {i}.5)" for i in range(n))
    db.execute_one(f"INSERT INTO m (time, h, v) VALUES {rows}")


def _stage_rows(rs):
    """Parse `stage node=<n> name=<s> value=<v>` result rows →
    [(node, name, value)]."""
    out = []
    for line in rs.columns[0]:
        m = re.match(r"stage node=(\S+) name=(\S+) value=(\S+)", str(line))
        if m:
            out.append((m.group(1), m.group(2), float(m.group(3))))
    return out


def test_explain_analyze_renders_stage_and_device_rows(db):
    _seed(db)
    rs = db.execute_one(
        "EXPLAIN ANALYZE SELECT h, count(*), max(v) FROM m GROUP BY h")
    text = "\n".join(str(x) for x in rs.columns[0])
    assert "Execution: 4 rows" in text
    assert "TpuAggregateExec" in text
    rows = _stage_rows(rs)
    names = {n for _node, n, _v in rows}
    assert "kernel_ms" in names and "group_count" in names
    for _node, name, value in rows:
        assert name in stages.STAGE_CATALOG \
            or name.startswith(stages.DYNAMIC_STAGE_PREFIXES)
        assert value >= 0
    assert "device platform=" in text


def test_explain_analyze_reconciles_with_scoped_profile(db):
    """The rendered breakdown and the ambient (caller-installed) profile must
    agree: the inner profile folds into the outer, so per-stage sums
    reconcile within 10%."""
    _seed(db)
    db.execute_one("SELECT h, count(*) FROM m GROUP BY h")   # warm caches
    outer = stages.QueryProfile()
    with stages.profile_scope(outer):
        rs = db.execute_one(
            "EXPLAIN ANALYZE SELECT h, count(*), max(v) FROM m GROUP BY h")
    rendered: dict[str, float] = {}
    for _node, name, value in _stage_rows(rs):
        rendered[name] = rendered.get(name, 0.0) + value
    totals = outer.stage_totals()
    assert rendered, "no stage rows rendered"
    for name, value in rendered.items():
        got = totals.get(name, 0.0)
        assert abs(got - value) <= max(0.1 * value, 0.5), \
            f"{name}: EXPLAIN={value} vs profile={got}"


def test_profile_sealed_by_executor_and_ring_recorded(db):
    _seed(db, n=50)
    prof = stages.QueryProfile()
    with stages.profile_scope(prof):
        db.execute_one("SELECT count(*) FROM m")
    assert prof.qid is not None
    assert prof.wall_ms is not None and prof.wall_ms > 0
    assert prof.sql == "SELECT count(*) FROM m"
    assert "platform" in prof.device
    d = stages.PROFILES.get(prof.qid)
    assert d is not None and d["wall_ms"] == prof.wall_ms


# ---------------------------------------------------------- slow-query log
def _slow_rows(db):
    db.slow_query_threshold_ms = 0
    rs = db.execute_one(
        "SELECT error, qid, sql FROM usage_schema.slow_queries")
    return list(zip(*(list(c) for c in rs.columns))) if rs.n_rows else []


def test_slow_query_log_threshold(db):
    _seed(db, n=50)
    db.slow_query_threshold_ms = 10_000   # nothing is that slow
    db.execute_one("SELECT count(*) FROM m")
    db.slow_query_threshold_ms = 1
    orig = db.execute_statement

    def slow_stmt(stmt, session):
        time.sleep(0.01)
        return orig(stmt, session)

    db.execute_statement = slow_stmt
    try:
        db.execute_one("SELECT max(v) FROM m")
    finally:
        db.execute_statement = orig
    rows = _slow_rows(db)
    assert rows, "threshold-exceeding query did not reach usage_schema"
    assert any("max(v)" in r[2] for r in rows)
    assert all("count(*)" not in r[2] for r in rows), \
        "query under threshold must not be logged"


def test_killed_and_deadline_exceeded_queries_still_log(db):
    """_finish_profile runs in execute_sql's finally: a query unwound by
    KILL or deadline expiry still lands in the slow-query log, with its
    error recorded."""
    _seed(db, n=50)
    db.slow_query_threshold_ms = 1
    orig = db.execute_statement

    def killed_stmt(stmt, session):
        qid = db._tls.qid
        db.tracker.kill(qid)                 # KILLed mid-flight
        time.sleep(0.01)
        db.tracker.check_cancelled(qid)      # raises: query killed
        return orig(stmt, session)

    db.execute_statement = killed_stmt
    try:
        with pytest.raises(QueryError):
            db.execute_one("SELECT min(v) FROM m")
    finally:
        db.execute_statement = orig

    def expired_stmt(stmt, session):
        time.sleep(0.01)
        deadline_mod.check_current()         # raises DeadlineExceeded
        return orig(stmt, session)

    db.slow_query_threshold_ms = 1
    db.execute_statement = expired_stmt
    try:
        with pytest.raises(DeadlineExceeded):
            with deadline_mod.scope(deadline_mod.Deadline(0.001)):
                db.execute_one("SELECT sum(v) FROM m")
    finally:
        db.execute_statement = orig
    rows = _slow_rows(db)
    errors = [r[0] for r in rows]
    assert any("killed" in e.lower() or "cancel" in e.lower()
               for e in errors), errors
    assert any("DeadlineExceeded" in e for e in errors), errors


# --------------------------------------------------- HTTP plane + metrics
@pytest.fixture
def http(tmp_path):
    from test_deadline import _Harness

    h = _Harness(str(tmp_path / "srv"))
    yield h
    h.close()


def _seed_http(h, n=40):
    lines = "\n".join(
        f"cpu,host=h{i % 4} usage={i}.5 {1672531200000000000 + i * 10**9}"
        for i in range(n))
    status, body, _ = h.request("POST", "/api/v1/write?db=public", lines)
    assert status == 200, body


def test_http_profile_header_and_debug_profile(http):
    _seed_http(http)
    # without the header: no summary
    status, _body, hdrs = http.request(
        "POST", "/api/v1/sql?db=public", "SELECT count(*) FROM cpu")
    assert status == 200 and "X-CnosDB-Profile-Summary" not in hdrs
    # opt-in: compact summary on the response
    status, _body, hdrs = http.request(
        "POST", "/api/v1/sql?db=public",
        "SELECT host, max(usage) FROM cpu GROUP BY host",
        headers={"X-CnosDB-Profile": "1"})
    assert status == 200
    summary = json.loads(hdrs["X-CnosDB-Profile-Summary"])
    assert summary["wall_ms"] > 0
    assert summary["stages"].get("group_count") == 4
    qid = summary["qid"]
    # full profile from the bounded ring
    status, body, _ = http.request("GET", f"/debug/profile?qid={qid}")
    assert status == 200
    full = json.loads(body)
    assert full["qid"] == qid and full["counts"]["group_count"] == 4
    assert "platform" in full["device"]
    status, body, _ = http.request("GET", "/debug/profile")
    recents = json.loads(body)
    assert any(d["qid"] == qid for d in recents)
    status, body, _ = http.request("GET", "/debug/profile?qid=nope")
    assert status == 404


# A strict (small) Prometheus text-format checker: every line must be a
# comment or `name{labels} value`; histograms must expose cumulative
# monotone buckets ending in +Inf == _count, plus _sum/_count.
_SAMPLE_RE = re.compile(
    r'^([a-zA-Z_:][a-zA-Z0-9_:]*)'
    r'(\{[a-zA-Z_][a-zA-Z0-9_]*="[^"]*"(,[a-zA-Z_][a-zA-Z0-9_]*="[^"]*")*\})?'
    r' (-?\d+(\.\d+)?([eE][+-]?\d+)?|[+-]Inf|NaN)$')


_LABEL_RE = re.compile(r'([a-zA-Z_][a-zA-Z0-9_]*)="([^"]*)"')


def _check_prometheus(text: str):
    types: dict[str, str] = {}
    samples: list[tuple[str, str, float]] = []
    assert text.endswith("\n")
    for line in text.splitlines():
        if not line:
            continue
        if line.startswith("#"):
            parts = line.split()
            assert parts[0] == "#" and parts[1] in ("TYPE", "HELP"), line
            if parts[1] == "TYPE":
                assert parts[3] in ("counter", "gauge", "histogram",
                                    "summary", "untyped"), line
                types[parts[2]] = parts[3]
            continue
        m = _SAMPLE_RE.match(line)
        assert m, f"malformed sample line: {line!r}"
        samples.append((m.group(1), m.group(2) or "", float(m.group(4))))
    # histogram families: cumulative buckets + _sum + _count per series
    for fam, t in types.items():
        if t != "histogram":
            continue
        by_series: dict[tuple, list] = {}
        sums, counts = {}, {}
        for name, labels, v in samples:
            pairs = dict(_LABEL_RE.findall(labels))
            le = pairs.pop("le", None)
            key = tuple(sorted(pairs.items()))
            if name == f"{fam}_bucket":
                assert le is not None, f"bucket sample without le: {labels}"
                by_series.setdefault(key, []).append((le, v))
            elif name == f"{fam}_sum":
                sums[key] = v
            elif name == f"{fam}_count":
                counts[key] = v
        assert by_series, f"histogram {fam} has no buckets"
        for key, buckets in by_series.items():
            values = [v for _le, v in buckets]
            assert values == sorted(values), \
                f"{fam}{key}: buckets not cumulative: {buckets}"
            assert buckets[-1][0] == "+Inf"
            assert buckets[-1][1] == counts.get(key), \
                f"{fam}{key}: +Inf bucket != _count"
            assert key in sums, f"{fam}{key}: missing _sum"
    return types, samples


def test_metrics_endpoint_full_prometheus_parse(http):
    _seed_http(http)
    for _ in range(3):
        status, _b, _h = http.request(
            "POST", "/api/v1/sql?db=public", "SELECT count(*) FROM cpu")
        assert status == 200
    status, text, _ = http.request("GET", "/metrics")
    assert status == 200
    types, samples = _check_prometheus(text)
    names = {n for n, _l, _v in samples}
    assert "cnosdb_http_queries_total" in names
    # the SQL latency histogram engaged and checks out strictly
    assert types.get("cnosdb_query_sql_process_ms") == "histogram"
    cnt = [v for n, _l, v in samples
           if n == "cnosdb_query_sql_process_ms_count"]
    assert cnt and cnt[0] >= 3


def test_histogram_memory_bounded_under_soak():
    """100k observations must not grow per-sample state (the old
    implementation appended every value to a list forever)."""
    from cnosdb_tpu.server.metrics import MetricsRegistry

    reg = MetricsRegistry()
    n = 100_000
    for i in range(n):
        reg.observe("cnosdb_soak_ms", (i % 1000) / 10.0, route="q")
    hists = list(reg._histograms.values())
    assert len(hists) == 1
    h = hists[0]
    assert not hasattr(h, "append"), "histogram state must not be a list"
    assert len(h.buckets) == len(reg._hist_bounds)
    assert h.count == n
    assert h.total == pytest.approx(sum((i % 1000) / 10.0
                                        for i in range(1000)) * (n // 1000))
    text = reg.prometheus_text()
    _check_prometheus(text)
    # spot-check one cumulative bucket against the definition
    m = re.search(r'cnosdb_soak_ms_bucket\{route="q",le="5"\} (\d+)', text)
    # values are (i % 1000)/10 ∈ [0, 99.9]; ≤5 → i%1000 ∈ [0, 50] → 51/1000
    assert m and int(m.group(1)) == 51 * (n // 1000)


# ------------------------------------------- one timeline per request
def _seed_flushed_ints(h, hosts=4, steps=300, db="public",
                       fields=("usage",)):
    """INTEGER fields in TSM files: what the device-decode lane takes."""
    lines = "\n".join(
        f"cpu,host=h{i} "
        + ",".join(f"{f}={(t * 7 + i + j) % 100}i"
                   for j, f in enumerate(fields))
        + f" {1672531200000000000 + t * 10 * 10**9}"
        for i in range(hosts) for t in range(steps))
    status, body, _ = h.request("POST", f"/api/v1/write?db={db}", lines)
    assert status == 200, body
    status, body, _ = h.request("POST", f"/api/v1/sql?db={db}", "FLUSH")
    assert status == 200, body


# a time-bucketed aggregate: no page statistic answers it, so pages decode
_BUCKETED = ("SELECT date_bin(INTERVAL '10 minutes', time) AS t, host, "
             "avg(usage) FROM cpu GROUP BY t, host")


def _trace_spans(h, trace_id):
    status, body, _ = h.request("GET", f"/debug/traces?trace_id={trace_id}")
    assert status == 200
    return json.loads(body)


def _ancestors(span, by_id):
    out = []
    while span.get("parent_id") in by_id:
        span = by_id[span["parent_id"]]
        out.append(span["name"])
    return out


def test_traced_request_is_one_tree_under_http_sql(http, monkeypatch):
    monkeypatch.setenv("CNOSDB_DEVICE_DECODE", "1")
    _seed_flushed_ints(http)
    tid = "feedc0de0001"
    status, _body, hdrs = http.request(
        "POST", "/api/v1/sql?db=public", _BUCKETED,
        headers={"X-CnosDB-Profile": "1", "cnos-trace-id": tid})
    assert status == 200
    spans = _trace_spans(http, tid)
    by_id = {s["span_id"]: s for s in spans}
    roots = [s for s in spans if s["parent_id"] is None]
    assert [r["name"] for r in roots] == ["http:sql"]
    assert len(spans) > 5
    for s in spans:
        assert s["trace_id"] == tid
        assert s["parent_id"] is None or s["parent_id"] in by_id, s
        assert s["duration_ns"] >= 0
        if s["parent_id"] is not None:
            assert _ancestors(s, by_id)[-1] == "http:sql"
    names = {s["name"] for s in spans}
    assert {"ingress_wait_ms", "plan_ms", "decode_ms", "device_decode_ms",
            "device_decode.put_ms", "device_decode.launch_ms",
            "device_decode.pull_ms", "kernel_ms", "render_ms"} <= names, sorted(names)
    # decode_ms → device_decode_ms → device_decode.pull_ms, in that order
    pull = next(s for s in spans if s["name"] == "device_decode.pull_ms")
    chain = _ancestors(pull, by_id)
    assert chain[:2] == ["device_decode_ms", "decode_ms"], chain
    # every span is a documented stage (or the root)
    for n in names - {"http:sql"}:
        assert n in stages.STAGE_CATALOG \
            or n.startswith(stages.DYNAMIC_STAGE_PREFIXES), n
    # children lie inside the root: it covers handler entry → rendered
    root = roots[0]
    for s in spans:
        assert s["start_ns"] >= root["start_ns"] - 10**6, s
        assert s["start_ns"] + s["duration_ns"] \
            <= root["start_ns"] + root["duration_ns"] + 10**6, s
    # the profile says what the intervals leave uncovered, and keeps none
    summary = json.loads(hdrs["X-CnosDB-Profile-Summary"])
    assert 0 <= summary["stages"]["untraced_ms"] <= summary["wall_ms"]
    status, body, _ = http.request(
        "GET", f"/debug/profile?qid={summary['qid']}")
    full = json.loads(body)
    assert full["traced"] and full["trace_id"] == tid
    assert full["dropped"] == 0 and "intervals" not in full


def test_decode_stage_terms_sum_to_decode_ms(http, monkeypatch):
    """What the three decode metrics would read: pull, put + launch, and
    the host remainder tile decode_ms; the lane's device stages lie inside
    device_decode_ms, itself inside decode_ms."""
    monkeypatch.setenv("CNOSDB_DEVICE_DECODE", "1")
    _seed_flushed_ints(http, hosts=12)
    status, _body, hdrs = http.request(
        "POST", "/api/v1/sql?db=public", _BUCKETED,
        headers={"X-CnosDB-Profile": "1"})
    assert status == 200
    st = json.loads(hdrs["X-CnosDB-Profile-Summary"])["stages"]
    pull = st["device_decode.pull_ms"]
    dispatch = st["device_decode.put_ms"] + st["device_decode.launch_ms"]
    host = st["decode_ms"] - pull - dispatch
    assert pull > 0 and dispatch > 0 and host > 0
    assert pull + dispatch + host == pytest.approx(st["decode_ms"])
    assert pull + dispatch <= st["device_decode_ms"] + 0.01 \
        <= st["decode_ms"] + 0.02
    assert st["device_decode_engagements"] > 0
    # stages are booked per page group, so the lane calls the device
    # fewer times than it decodes pages (12 hosts: 24 pages, 2 groups)
    assert 3 <= st["device_decode.device_calls"] \
        < st["device_decode_engagements"]


def test_auto_mode_profile_carries_no_device_decode_stage(http, monkeypatch):
    """Auto mode with the lane on, as on a TPU: the scan's values land in
    host arrays, so the native decoder takes every page — booked
    host / native_first — and the profile shows decode_ms alone, no
    device_decode.* stage, span or count."""
    from cnosdb_tpu.ops import device_decode

    monkeypatch.delenv("CNOSDB_DEVICE_DECODE", raising=False)
    monkeypatch.setattr(device_decode, "disabled_reason", lambda: None)
    _seed_flushed_ints(http, hosts=12)
    key = ("host", "native_first")
    before = device_decode.outcomes_snapshot()
    tid = "feedc0de0032"
    status, _body, hdrs = http.request(
        "POST", "/api/v1/sql?db=public", _BUCKETED,
        headers={"X-CnosDB-Profile": "1", "cnos-trace-id": tid})
    assert status == 200
    after = device_decode.outcomes_snapshot()
    assert after[key] - before.get(key, 0) == 24      # 12 time, 12 usage
    assert {k: n for k, n in after.items() if k != key} \
        == {k: n for k, n in before.items() if k != key}
    st = json.loads(hdrs["X-CnosDB-Profile-Summary"])["stages"]
    assert st["decode_ms"] > 0
    assert not [k for k in st if k.startswith("device_decode")], st
    assert not [s["name"] for s in _trace_spans(http, tid)
                if s["name"].startswith("device_decode")]


def test_scan_books_its_plan_native_and_trim_stages(http):
    """A scan's three sections are stages inside `decode_ms` — in the
    summary header, /debug/profile, EXPLAIN ANALYZE and the span tree —
    and it counts the series it planned from the page index, those it had
    to read and merge one at a time, and the indexes it had to build."""
    _seed_flushed_ints(http, hosts=6)
    sections = ("scan.plan_ms", "scan.native_ms", "scan.trim_ms")
    counts = ("scan_plan.indexed_series", "scan_plan.merged_series",
              "scan_plan.index_builds")

    def traced(sql, tid):
        status, body, hdrs = http.request(
            "POST", "/api/v1/sql?db=public", sql,
            headers={"X-CnosDB-Profile": "1", "cnos-trace-id": tid})
        assert status == 200, body
        summary = json.loads(hdrs["X-CnosDB-Profile-Summary"])
        full = json.loads(http.request(
            "GET", f"/debug/profile?qid={summary['qid']}")[1])
        return summary["stages"], full

    st, full = traced(_BUCKETED, "feedc0de0036")
    assert all(full["ms"][k] > 0 for k in sections), full["ms"]
    assert sum(full["ms"][k] for k in sections) <= full["ms"]["decode_ms"]
    assert all(k in st for k in sections)
    # a flushed store: every series off the index, which this scan built
    assert st["scan_plan.indexed_series"] == 6
    assert st["scan_plan.index_builds"] == 1
    # booked where the decision is taken, so "none" reads 0, not nothing
    assert st["scan_plan.merged_series"] == 0
    spans = _trace_spans(http, "feedc0de0036")
    by_id = {s["span_id"]: s for s in spans}
    for key in sections:
        got = [s for s in spans if s["name"] == key]
        assert got, key
        for s in got:
            chain = _ancestors(s, by_id)
            assert chain[0] == "decode_ms" and chain[-1] == "http:sql", chain
    # the same scan again (another range: the first one's batch is
    # cached) finds the index built
    later = _BUCKETED.replace(
        "FROM cpu", "FROM cpu WHERE time >= '2023-01-01T00:00:00Z'")
    st, _full = traced(later, "feedc0de0037")
    assert st["scan_miss"] == 1 and st["scan_plan.indexed_series"] == 6
    assert st["scan_plan.index_builds"] == 0
    # unflushed rows over two of the six: those two are read and merged
    lines = "\n".join(
        f"cpu,host=h{i} usage={t}i {1672531200000000000 + t * 10 * 10**9}"
        for i in range(2) for t in range(300, 310))
    status, body, _ = http.request("POST", "/api/v1/write?db=public", lines)
    assert status == 200, body
    st, _full = traced(later.replace("00:00:00Z", "00:00:10Z"),
                       "feedc0de0038")
    assert st["scan_miss"] == 1 and st["scan_plan.merged_series"] == 2
    assert st["scan_plan.indexed_series"] == 4
    assert st["scan_plan.index_builds"] == 0
    status, body, _ = http.request(
        "POST", "/api/v1/sql?db=public", "EXPLAIN ANALYZE " + later)
    assert status == 200, body
    for key in sections + counts:
        assert key in stages.STAGE_CATALOG
        # (a delta scan of the unflushed rows asks for no file's index)
        assert key in body or key == "scan_plan.index_builds", (key, body)


def _seed_sharded_ints(h, monkeypatch, hosts=16, steps=200):
    """A `WITH SHARD 4` database of two INTEGER fields, flushed, and the
    mesh lane opened to a table this small on four of the virtual devices."""
    monkeypatch.setenv("CNOSDB_MESH_MIN_ROWS", "0")
    monkeypatch.setenv("CNOSDB_MESH_DEVICES", "4")
    status, body, _ = h.request("POST", "/api/v1/sql?db=public",
                                "CREATE DATABASE mesh4 WITH SHARD 4")
    assert status == 200, body
    _seed_flushed_ints(h, hosts, steps, db="mesh4", fields=("usage", "idle"))
    return hosts * steps


_MESH_KEYS = ("mesh.plan_ms", "mesh.upload_ms", "mesh.collective_ms",
              "mesh.launch_ms", "mesh.fetch_ms", "mesh.assemble_ms",
              "mesh.columns", "mesh.rows", "mesh.shards")
_FANOUT_KEYS = ("fanout.launch_ms", "fanout.fetch_ms", "fanout.vnodes",
                "merge.groups")


def test_mesh_request_splits_the_collective_into_launch_and_fetch(
        http, monkeypatch):
    """What `mesh_launch_ms` / `mesh_fetch_ms` would read: dispatch of the
    per-column programs and the blocking pulls, both inside
    `mesh.collective_ms`; `mesh.columns` counts the programs."""
    rows = _seed_sharded_ints(http, monkeypatch)
    status, _body, hdrs = http.request(
        "POST", "/api/v1/sql?db=mesh4",
        "SELECT date_bin(INTERVAL '10 minutes', time) AS t, host, "
        "avg(usage), max(idle) FROM cpu GROUP BY t, host",
        headers={"X-CnosDB-Profile": "1"})
    assert status == 200
    st = json.loads(hdrs["X-CnosDB-Profile-Summary"])["stages"]
    for k in _MESH_KEYS:
        assert k in stages.STAGE_CATALOG, k
        assert k in st, (k, sorted(st))
    assert st["mesh.launch_ms"] > 0 and st["mesh.fetch_ms"] > 0
    assert st["mesh.launch_ms"] + st["mesh.fetch_ms"] \
        <= st["mesh.collective_ms"] + 0.01
    assert st["mesh.columns"] == 2          # usage and idle: one program each
    assert st["mesh.rows"] == rows and st["mesh.shards"] == 4
    assert not [k for k in _FANOUT_KEYS if k in st], sorted(st)


@pytest.mark.parametrize("lane", ["fused", "mesh"])
def test_sorted_scans_reduce_runs_and_say_so(http, monkeypatch, lane):
    """A flushed scan is series-major and time-ascending: every fused
    launch (resp. every mesh merge program) over a batch long enough for
    the run path (kernels.run_pad_for) reduces runs, and the profile
    counts it from the program's own flag — `segment_runs.engaged` equals
    the launches, `segment_runs.fallback` reads 0 (booked beside it, so
    a reader finds the key)."""
    if lane == "mesh":
        _seed_sharded_ints(http, monkeypatch, hosts=64, steps=2100)
        db, launches = "mesh4", "mesh.columns"
    else:
        monkeypatch.setenv("CNOSDB_TPU_FORCE_DEVICE_PATH", "1")
        monkeypatch.setenv("CNOSDB_MESH", "0")
        _seed_flushed_ints(http, hosts=40, steps=1700)
        db, launches = "public", "fused_launches"
    status, body, hdrs = http.request(
        "POST", f"/api/v1/sql?db={db}", _BUCKETED,
        headers={"X-CnosDB-Profile": "1"})
    assert status == 200, body
    st = json.loads(hdrs["X-CnosDB-Profile-Summary"])["stages"]
    for k in ("segment_runs.engaged", "segment_runs.fallback"):
        assert k in stages.STAGE_CATALOG, k
    assert st.get(launches, 0) >= 1, sorted(st)
    assert st.get("segment_runs.engaged") == st[launches], sorted(st.items())
    assert st["segment_runs.fallback"] == 0


def test_traced_mesh_request_holds_the_lane_under_http_sql(http, monkeypatch):
    _seed_sharded_ints(http, monkeypatch)
    tid = "feedc0de0029"
    status, _body, _hdrs = http.request(
        "POST", "/api/v1/sql?db=mesh4", _BUCKETED,
        headers={"X-CnosDB-Profile": "1", "cnos-trace-id": tid})
    assert status == 200
    spans = _trace_spans(http, tid)
    by_id = {s["span_id"]: s for s in spans}
    names = {s["name"] for s in spans}
    assert {k for k in _MESH_KEYS if k.endswith("_ms")} <= names, sorted(names)
    for leaf in ("mesh.launch_ms", "mesh.fetch_ms"):
        chain = _ancestors(next(s for s in spans if s["name"] == leaf), by_id)
        assert chain[0] == "mesh.collective_ms" and chain[-1] == "http:sql", \
            chain
    coll = next(s for s in spans if s["name"] == "mesh.collective_ms")
    for s in spans:
        if s["name"] in ("mesh.launch_ms", "mesh.fetch_ms"):
            assert s["start_ns"] >= coll["start_ns"]
            assert s["start_ns"] + s["duration_ns"] \
                <= coll["start_ns"] + coll["duration_ns"] + 10**6


def test_traced_fanout_request_holds_launch_and_fetch_inside_kernel(
        http, monkeypatch):
    """The same sharded table on a mesh of one: the lane declines, every
    vnode's batch gets its own launch and fetch on the pool, and the host
    merge answers. The four keys are booked here — a launch and a fetch
    span a vnode, each inside `kernel_ms` under `http:sql` — and on the
    mesh lane (the split test above) not at all."""
    _seed_sharded_ints(http, monkeypatch)
    monkeypatch.setenv("CNOSDB_MESH_DEVICES", "1")
    tid = "feedc0de0033"
    status, body, hdrs = http.request(
        "POST", "/api/v1/sql?db=mesh4", _BUCKETED,
        headers={"X-CnosDB-Profile": "1", "cnos-trace-id": tid})
    assert status == 200, body
    st = json.loads(hdrs["X-CnosDB-Profile-Summary"])["stages"]
    for k in _FANOUT_KEYS:
        assert k in stages.STAGE_CATALOG, k
        assert k in st, (k, sorted(st))
    assert not [k for k in st if k.startswith("mesh.")], sorted(st)
    assert st["fanout.vnodes"] == 4
    assert st["merge.groups"] == len(body.splitlines()) - 1
    assert st["fanout.launch_ms"] > 0 and st["fanout.fetch_ms"] >= 0
    # thread-summed inside a wall section: at most a kernel_ms a vnode
    assert st["fanout.launch_ms"] + st["fanout.fetch_ms"] \
        <= st["fanout.vnodes"] * st["kernel_ms"] + 0.01
    spans = _trace_spans(http, tid)
    by_id = {s["span_id"]: s for s in spans}
    kernel = next(s for s in spans if s["name"] == "kernel_ms")
    for leaf in ("fanout.launch_ms", "fanout.fetch_ms"):
        found = [s for s in spans if s["name"] == leaf]
        assert len(found) == 4, (leaf, len(found))
        for s in found:
            chain = _ancestors(s, by_id)
            assert chain[0] == "kernel_ms" and chain[-1] == "http:sql", chain
            assert s["start_ns"] >= kernel["start_ns"]
            assert s["start_ns"] + s["duration_ns"] \
                <= kernel["start_ns"] + kernel["duration_ns"] + 10**6


def test_unprofiled_request_leaves_one_span_and_no_intervals(http):
    from cnosdb_tpu.utils.spans import GLOBAL_COLLECTOR

    _seed_http(http)
    seen = {s["span_id"] for s in GLOBAL_COLLECTOR.spans(limit=10**6)}
    status, _b, hdrs = http.request(
        "POST", "/api/v1/sql?db=public",
        "SELECT host, max(usage) FROM cpu GROUP BY host")
    assert status == 200 and "X-CnosDB-Profile-Summary" not in hdrs
    new = [s for s in GLOBAL_COLLECTOR.spans(limit=10**6)
           if s["span_id"] not in seen]
    assert [s["name"] for s in new] == ["http:sql"]
    status, body, _ = http.request(
        "GET", f"/debug/profile?qid={new[0]['tags']['profile.qid']}")
    full = json.loads(body)
    assert full["traced"] is False and full["dropped"] == 0
    assert "untraced_ms" not in full["ms"]


def test_stage_with_flag_off_keeps_no_interval_and_opens_no_span():
    from cnosdb_tpu.utils.spans import GLOBAL_COLLECTOR

    before = len(GLOBAL_COLLECTOR.spans(limit=10**6))
    prof = stages.QueryProfile()
    with stages.profile_scope(prof):
        with stages.stage("decode_ms"):
            pass
        stages.book("ingress_wait_ms", time.perf_counter() - 0.001)
    assert prof.ms["decode_ms"] >= 0 and prof.ms["ingress_wait_ms"] >= 1.0
    assert prof.intervals == [] and prof.dropped == 0
    assert len(GLOBAL_COLLECTOR.spans(limit=10**6)) == before
    assert "untraced_ms" not in prof.finish(wall_ms=5.0).ms


# ------------------------------------------------- work told from wait
def test_cpu_time_is_booked_for_a_traced_stage_and_for_no_other(monkeypatch):
    """`cpu.<stage>` rides beside a traced stage's sum; a profile that is
    not traced reads no thread clock and books no `cpu.*` key, and
    `book()` — another thread's interval — books none either way."""
    traced = stages.QueryProfile()
    traced.traced = True
    with stages.profile_scope(traced):
        with stages.stage("decode_ms"):
            with stages.stage("scan.plan_ms"):
                pass
        stages.book("ingress_wait_ms", time.perf_counter() - 0.001)
    assert set(traced.ms) == {"decode_ms", "cpu.decode_ms", "scan.plan_ms",
                              "cpu.scan.plan_ms", "ingress_wait_ms"}
    assert 0 <= traced.ms["cpu.scan.plan_ms"] <= traced.ms["cpu.decode_ms"]
    assert "cpu.decode_ms" in traced.snapshot() \
        and "cpu.decode_ms" in traced.to_dict()["ms"] \
        and "cpu.decode_ms" in traced.stage_totals()
    assert "cpu.".startswith(stages.DYNAMIC_STAGE_PREFIXES)

    def no_clock():
        raise AssertionError("an untraced stage read the thread clock")

    monkeypatch.setattr(time, "thread_time", no_clock)
    for annotate in (False, True):      # a query's profile, a write's
        plain = stages.QueryProfile()
        plain.annotate = annotate
        with stages.profile_scope(plain):
            with stages.stage("decode_ms"):
                pass
            stages.book("ingress_wait_ms", time.perf_counter() - 0.001)
        assert set(plain.ms) == {"decode_ms", "ingress_wait_ms"}
    with stages.stage("decode_ms"):     # no profile in scope: nothing
        pass


def _sleep_50ms():
    time.sleep(0.05)


def _spin_50ms():
    end = time.perf_counter() + 0.05
    while time.perf_counter() < end:
        pass


@pytest.mark.parametrize("body,holds", [
    (_sleep_50ms, lambda wall, cpu: wall >= 50 and cpu < 5),
    (_spin_50ms, lambda wall, cpu: wall >= 50 and abs(wall - cpu)
     <= 0.2 * wall),
], ids=["a_sleep_is_wait", "a_spin_is_work"])
def test_cpu_beside_wall_tells_work_from_wait(body, holds):
    """A stage that sleeps 50 ms books under 5 ms of CPU; one that spins
    50 ms books its wall to within 20 % (best of three: a busy machine
    can take the core away from the spin)."""
    seen = []
    for _attempt in range(3):
        prof = stages.QueryProfile()
        prof.traced = True
        with stages.profile_scope(prof), stages.stage("decode_ms"):
            body()
        seen.append((prof.ms["decode_ms"], prof.ms["cpu.decode_ms"]))
        if holds(*seen[-1]):
            break
    assert holds(*seen[-1]), seen


# parent span → the stages PR 37 nests inside it
_NESTED = {
    "upload_ms": ("upload.meta_ms", "upload.stage_ms", "upload.put_ms"),
    "mesh.plan_ms": ("mesh.mask_ms", "mesh.layout_ms", "mesh.stage_ms"),
    "scan.plan_ms": ("scan.alloc_ms",),
    "kernel_ms": ("kernel.pad_ms", "kernel.dispatch_ms"),
}
_PANEL = ("SELECT date_bin(INTERVAL '10 minutes', time) AS t, max(usage), "
          "max(idle) FROM cpu WHERE host = 'h3' GROUP BY t")


def _traced(h, sql, tid, db="public"):
    status, body, hdrs = h.request(
        "POST", f"/api/v1/sql?db={db}", sql,
        headers={"X-CnosDB-Profile": "1", "cnos-trace-id": tid})
    assert status == 200, body
    summary = json.loads(hdrs["X-CnosDB-Profile-Summary"])
    return summary, _trace_spans(h, tid)


def _check_nested(summary, spans, parent):
    """Every child key of `parent` is documented, booked, a span whose
    nearest ancestor among the catalog's parents is `parent`, with its CPU
    time beside it; the children's sum does not exceed the parent."""
    st = summary["stages"]
    by_id = {s["span_id"]: s for s in spans}
    for key in _NESTED[parent]:
        assert key in stages.STAGE_CATALOG, key
        assert key in st and "cpu." + key in st, (key, sorted(st))
        found = [s for s in spans if s["name"] == key]
        assert found, key
        for s in found:
            chain = _ancestors(s, by_id)
            assert chain[0] == parent and chain[-1] == "http:sql", chain
            assert "cpu_ms" in s["tags"], s
    assert sum(st[k] for k in _NESTED[parent]) <= st[parent] + 0.05, \
        {k: st[k] for k in (parent,) + _NESTED[parent]}
    assert "dropped" not in summary


@pytest.mark.parametrize("parent", sorted(_NESTED))
def test_new_stages_nest_inside_their_parent_span(http, monkeypatch, parent):
    """The aggregate half split where the work changes hands: each new
    key lies inside the span the catalog names, on the thread that did
    the work, and the parts do not exceed the whole."""
    tid = "feedc0de37" + f"{sorted(_NESTED).index(parent):02d}"
    if parent == "mesh.plan_ms":
        _seed_sharded_ints(http, monkeypatch)
        summary, spans = _traced(http, _BUCKETED, tid, db="mesh4")
    else:
        # the fused lane (a DeviceBatch uploaded, one fused program) and,
        # for the tag-predicate panel, aggregate_column_host a column
        monkeypatch.setenv("CNOSDB_TPU_FORCE_DEVICE_PATH", "1")
        monkeypatch.setenv("CNOSDB_MESH", "0")
        _seed_flushed_ints(http, hosts=6, fields=("usage", "idle"))
        summary, spans = _traced(http, _BUCKETED, tid)
        if parent == "kernel_ms":
            st = summary["stages"]
            assert st["fused_launches"] == 1 and "kernel.pad_ms" not in st
            assert 0 < st["kernel.dispatch_ms"] <= st["kernel_ms"]
            summary, spans = _traced(http, _PANEL, tid + "aa")
            assert "fused_launches" not in summary["stages"]
    _check_nested(summary, spans, parent)


def test_counts_that_must_read_zero_are_booked_as_zero(http, monkeypatch):
    """A warmed, frozen store: the four counts that must read 0 are in the
    aggregate's profile as 0 — booked where each decision is taken — so a
    reader reads 0 and not nothing."""
    monkeypatch.setenv("CNOSDB_TPU_FORCE_DEVICE_PATH", "1")
    monkeypatch.setenv("CNOSDB_MESH", "0")
    _seed_flushed_ints(http, hosts=40, steps=1700)
    status, body, _ = http.request("POST", "/api/v1/sql?db=public",
                                   _BUCKETED)       # builds the index
    assert status == 200, body
    later = _BUCKETED.replace(
        "FROM cpu", "FROM cpu WHERE time >= '2023-01-01T00:00:10Z'")
    status, body, hdrs = http.request(
        "POST", "/api/v1/sql?db=public", later,
        headers={"X-CnosDB-Profile": "1"})
    assert status == 200, body
    st = json.loads(hdrs["X-CnosDB-Profile-Summary"])["stages"]
    assert st["scan_miss"] == 1 and st["segment_runs.engaged"] == 1
    for key in ("segment_runs.fallback", "render.percell_columns",
                "scan_plan.merged_series", "scan_plan.index_builds"):
        assert key in stages.STAGE_CATALOG
        assert st.get(key, "absent") == 0, (key, sorted(st.items()))


def test_fleet_shaped_summary_carries_every_booked_key(http, monkeypatch):
    """Ten fields by host and bucket, traced: the summary header holds
    every key the profile booked — the stages, their `cpu.*` and the
    counts — with nothing dropped under PROFILE_SUMMARY_MAX."""
    from cnosdb_tpu.server.http import PROFILE_SUMMARY_MAX

    monkeypatch.setenv("CNOSDB_TPU_FORCE_DEVICE_PATH", "1")
    monkeypatch.setenv("CNOSDB_MESH", "0")
    fields = tuple(f"usage_{i}" for i in range(10))
    _seed_flushed_ints(http, hosts=40, steps=1700, fields=fields)
    sql = ("SELECT date_bin(INTERVAL '1 hour', time) AS t, host, "
           + ", ".join(f"avg({f})" for f in fields)
           + " FROM cpu GROUP BY t, host")
    status, body, hdrs = http.request(
        "POST", "/api/v1/sql?db=public", sql,
        headers={"X-CnosDB-Profile": "1"})
    assert status == 200, body
    text = hdrs["X-CnosDB-Profile-Summary"]
    summary = json.loads(text)
    assert len(text) <= PROFILE_SUMMARY_MAX // 2 and "dropped" not in summary
    full = json.loads(http.request(
        "GET", f"/debug/profile?qid={summary['qid']}")[1])
    st = summary["stages"]
    # (the ring's copy is taken where the executor seals the profile,
    # before the render)
    assert set(st) == set(full["ms"]) | set(full["counts"]) | {
        "render_ms", "cpu.render_ms", "render.percell_columns"}
    for key in ("upload.meta_ms", "upload.stage_ms", "upload.put_ms",
                "kernel.dispatch_ms", "scan.alloc_ms", "untraced_ms"):
        assert key in st, (key, sorted(st))
    # a stage entered on one thread cannot burn more CPU than its wall
    for key in ("plan_ms", "render_ms", "upload.meta_ms", "kernel.fetch_ms"):
        assert st["cpu." + key] <= st[key] * 1.05 + 0.5, (key, st)
    # every duration has its CPU reading but those no stage() times: the
    # two book() back-dates, and what finish() derives
    walls = {k for k in st if k.endswith("_ms") and not k.startswith("cpu.")}
    assert {"cpu." + k for k in walls - {
        "ingress_wait_ms", "memcache_wait_ms", "untraced_ms"}} \
        == {k for k in st if k.startswith("cpu.")}


def test_new_stages_are_profiler_annotations_with_their_qid(
        http, monkeypatch, tmp_path):
    """Every stage of a traced request is a `cnosdb.<stage>` event of a
    profiler trace, on the thread that did the work, with the request's
    qid — the new ones too, by going through `stage()`: what a later
    reader of the trace needs of the program."""
    import glob

    import jax

    monkeypatch.setenv("CNOSDB_TPU_FORCE_DEVICE_PATH", "1")
    monkeypatch.setenv("CNOSDB_MESH", "0")
    _seed_flushed_ints(http, hosts=6, fields=("usage", "idle"))
    jax.profiler.start_trace(str(tmp_path / "trace"))
    try:
        fused, _ = _traced(http, _BUCKETED, "feedc0de3790")
        panel, _ = _traced(http, _PANEL, "feedc0de3791")
    finally:
        jax.profiler.stop_trace()
    paths = glob.glob(str(tmp_path / "trace" / "**" / "*.xplane.pb"),
                      recursive=True)
    assert len(paths) == 1, paths
    seen: dict[str, set] = {}
    for plane in jax.profiler.ProfileData.from_file(paths[0]).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("cnosdb."):
                    seen.setdefault(ev.name[len("cnosdb."):], set()).update(
                        str(v) for k, v in ev.stats if k == "qid")
    want = {k: {str(fused["qid"])} for k in
            ("upload_ms",) + _NESTED["upload_ms"] + _NESTED["scan.plan_ms"]}
    want["kernel.pad_ms"] = {str(panel["qid"])}
    want["kernel.dispatch_ms"] = {str(fused["qid"]), str(panel["qid"])}
    for key, qids in want.items():
        assert qids <= seen.get(key, set()), (key, seen.get(key))


@pytest.mark.parametrize("intervals,want", [
    ([], 100.0),
    ([(0.010, 0.030)], 80.0),
    # two threads overlapping: the union counts once
    ([(0.010, 0.050), (0.030, 0.070)], 40.0),
    # nested, touching, and outside the request's window
    ([(0.0, 0.040), (0.010, 0.020), (0.040, 0.060), (-1.0, -0.5),
      (0.090, 0.500)], 30.0),
    ([(-0.010, 0.200)], 0.0),
])
def test_untraced_is_wall_minus_union(intervals, want):
    assert stages.uncovered_ms(100.0, intervals, 0.0, 0.100) \
        == pytest.approx(want)


def test_finish_books_untraced_over_threads_and_bounds_intervals():
    prof = stages.QueryProfile(qid="u1")
    prof.traced = True
    now = time.perf_counter()
    # hand-made: main thread [−90, −60] ms, a pool thread [−70, −20] ms
    for i in range(stages.MAX_INTERVALS - 2):
        prof.add_interval(now - 0.5, now - 0.5)     # before the request
    prof.add_interval(now - 0.090, now - 0.060)
    prof.add_interval(now - 0.070, now - 0.020)
    for i in range(8):
        prof.add_interval(now - 0.010, now)         # over the bound: counted
    assert len(prof.intervals) == stages.MAX_INTERVALS
    assert prof.dropped == 8
    prof.finish(wall_ms=100.0)
    assert prof.ms["untraced_ms"] == pytest.approx(30.0, abs=1.0)
    # read once, then let go; a sealed profile takes no more (rendering)
    prof.add_interval(now, now)
    assert prof.intervals == [] and prof.dropped == 8


def test_span_clock_is_wall_start_monotonic_duration(monkeypatch):
    from cnosdb_tpu.utils import spans

    wall = [1_700_000_000_000_000_000]
    monkeypatch.setattr(spans.time, "time_ns", lambda: wall[0])
    col = spans.TraceCollector()
    with col.span("stepped") as s:
        wall[0] -= 3_600 * 10**9          # the wall clock steps back an hour
        time.sleep(0.002)
    d = col.spans()[0]
    assert d["start_ns"] == 1_700_000_000_000_000_000
    assert 1_000_000 <= d["duration_ns"] < 10**9 and s.duration_ns > 0


def test_new_metrics_series_at_zero_before_first_query(http):
    status, text, _ = http.request("GET", "/metrics")
    assert status == 200
    _types, samples = _check_prometheus(text)
    got = {(n, l): v for n, l, v in samples}
    assert got[("cnosdb_requests_queue_wait_ms_sum", "")] == 0.0
    assert got[("cnosdb_requests_queue_wait_ms_count", "")] == 0.0
    # the lifetime-average gauges gave way to the histogram
    assert not any("stat=" in l for n, l, _v in samples
                   if n == "cnosdb_requests_queue_wait_ms")
    # one observation per admitted request, zero waits included
    _seed_http(http)
    for _ in range(2):
        http.request("POST", "/api/v1/sql?db=public",
                     "SELECT count(*) FROM cpu")
    _t, samples = _check_prometheus(http.request("GET", "/metrics")[1])
    got = {(n, l): v for n, l, v in samples}
    assert got[("cnosdb_requests_queue_wait_ms_count", "")] == 2.0
    assert got[("cnosdb_requests_queue_wait_ms_sum", "")] == 0.0
    stats = http.server.gate.stats()
    assert stats["queue_wait_ms_max"] == 0.0
    assert "queue_wait_ms_avg" not in stats


def test_write_stage_series_at_zero_then_one_count_a_stage_a_batch(http):
    """`cnosdb_write_stage_ms{stage}` and the three write-path counters
    are on a fresh /metrics at 0 (a reader of a window's rise needs both
    ends); each acknowledged batch is one observation of every stage it
    ran — `flush` only for the batch whose inline flush ran."""
    def scrape():
        _t, samples = _check_prometheus(http.request("GET", "/metrics")[1])
        return {(n, l): v for n, l, v in samples}

    from cnosdb_tpu.server.http import WRITE_STAGES

    assert WRITE_STAGES == ("parse", "lock_wait", "wal", "apply", "flush")
    got = scrape()
    for st in WRITE_STAGES:
        assert got[("cnosdb_write_stage_ms_count", f'{{stage="{st}"}}')] == 0
        assert got[("cnosdb_write_stage_ms_sum", f'{{stage="{st}"}}')] == 0
    # process totals (a fresh process reads 0: the served-path test of
    # tests/test_ingest_under_queries.py takes their rise from one)
    base = {name: got[(name, "")] for name in (
        "cnosdb_wal_bytes_total", "cnosdb_memcache_flush_total",
        "cnosdb_memcache_flush_rows_total")}

    def rise(name):
        return got[(name, "")] - base[name]

    _seed_http(http)
    _seed_http(http)
    got = scrape()
    for st in ("parse", "lock_wait", "wal", "apply"):
        assert got[("cnosdb_write_stage_ms_count",
                    f'{{stage="{st}"}}')] == 2, st
    assert got[("cnosdb_write_stage_ms_count", '{stage="flush"}')] == 0
    for st in ("parse", "wal", "apply"):
        assert got[("cnosdb_write_stage_ms_sum", f'{{stage="{st}"}}')] > 0
    assert rise("cnosdb_wal_bytes_total") > 2 * 40 * 8
    assert rise("cnosdb_memcache_flush_total") == 0
    # a flush is counted with its rows wherever it was asked for; only an
    # inline one is a stage of a write
    status, body, _ = http.request("POST", "/api/v1/sql?db=public", "FLUSH")
    assert status == 200, body
    got = scrape()
    flushed = rise("cnosdb_memcache_flush_total")    # usage_schema's too
    assert flushed >= 1
    assert rise("cnosdb_memcache_flush_rows_total") >= 40
    assert got[("cnosdb_write_stage_ms_count", '{stage="flush"}')] == 0
    # the inline flush: a cache that one batch fills
    http.server.coord.engine.vnodes[("cnosdb.public", 1)].active.max_bytes = 1
    _seed_http(http)
    got = scrape()
    assert got[("cnosdb_write_stage_ms_count", '{stage="flush"}')] == 1
    assert got[("cnosdb_write_stage_ms_count", '{stage="apply"}')] == 3
    assert rise("cnosdb_memcache_flush_total") == flushed + 1
    for key in ("write.parse_ms", "write.lock_wait_ms", "write.wal_ms",
                "write.apply_ms", "write.flush_ms"):
        assert key in stages.STAGE_CATALOG


def test_traced_request_over_unflushed_rows_holds_the_memcache_stages(http):
    """A scan that finds unflushed rows books its memcache share: spans
    `memcache_ms` inside `decode_ms` and `memcache_wait_ms` under
    `http:sql`, counts `memcache.series` / `memcache.rows` — in the
    summary header, /debug/profile, EXPLAIN ANALYZE and the span tree."""
    _seed_flushed_ints(http, hosts=3, steps=40)
    lines = "\n".join(
        f"cpu,host=h{i} usage={t}i {1672531200000000000 + t * 10 * 10**9}"
        for i in range(2) for t in range(40, 50))
    status, body, _ = http.request("POST", "/api/v1/write?db=public", lines)
    assert status == 200, body
    tid = "feedc0de0035"
    status, body, hdrs = http.request(
        "POST", "/api/v1/sql?db=public", _BUCKETED,
        headers={"X-CnosDB-Profile": "1", "cnos-trace-id": tid})
    assert status == 200, body
    st = json.loads(hdrs["X-CnosDB-Profile-Summary"])["stages"]
    # two of the three series have unflushed rows, ten each
    assert st["memcache.series"] == 2 and st["memcache.rows"] == 20
    assert 0 < st["memcache_ms"] <= st["decode_ms"]
    assert st["memcache_wait_ms"] >= 0
    spans = _trace_spans(http, tid)
    by_id = {s["span_id"]: s for s in spans}
    mem = [s for s in spans if s["name"] == "memcache_ms"]
    assert len(mem) >= 2
    for s in mem:
        chain = _ancestors(s, by_id)    # the merge is part of the plan
        assert chain[:2] == ["scan.plan_ms", "decode_ms"] \
            and chain[-1] == "http:sql", chain
    wait = [s for s in spans if s["name"] == "memcache_wait_ms"]
    assert wait and all(_ancestors(s, by_id)[-1] == "http:sql"
                        for s in wait)
    qid = json.loads(hdrs["X-CnosDB-Profile-Summary"])["qid"]
    full = json.loads(http.request("GET", f"/debug/profile?qid={qid}")[1])
    assert full["counts"]["memcache.series"] == 2
    assert full["ms"]["memcache_ms"] > 0 and "memcache_wait_ms" in full["ms"]
    # (another time range: the first answer's scan is cached)
    status, body, _ = http.request(
        "POST", "/api/v1/sql?db=public", "EXPLAIN ANALYZE " + _BUCKETED.replace(
            "FROM cpu", "FROM cpu WHERE time >= '2023-01-01T00:00:00Z'"))
    assert status == 200, body
    for key in ("memcache_ms", "memcache_wait_ms", "memcache.series",
                "memcache.rows"):
        # (a delta scan of the unflushed rows asks for no file's index)
        assert key in body or key == "scan_plan.index_builds", (key, body)
        assert key in stages.STAGE_CATALOG
    # rows all in files: a scan books the wait for its cut, nothing else
    status, body, _ = http.request("POST", "/api/v1/sql?db=public", "FLUSH")
    assert status == 200, body
    status, _b, hdrs = http.request(
        "POST", "/api/v1/sql?db=public", _BUCKETED,
        headers={"X-CnosDB-Profile": "1"})
    st = json.loads(hdrs["X-CnosDB-Profile-Summary"])["stages"]
    assert "memcache_ms" not in st and "memcache.series" not in st
    assert "memcache_wait_ms" in st


def test_render_error_is_not_an_sql_error(http, monkeypatch):
    """Rendering runs outside the CnosError handler: an error raised there
    propagates (500) instead of counting as a failed query, and the root
    span is finished with it."""
    from cnosdb_tpu.server import http as http_mod

    _seed_http(http)

    def boom(_rs):
        raise QueryError("cannot render")

    monkeypatch.setattr(http_mod, "format_csv", boom)
    tid = "feedc0de0002"
    status, _b, _h = http.request(
        "POST", "/api/v1/sql?db=public", "SELECT count(*) FROM cpu",
        headers={"cnos-trace-id": tid})
    assert status == 500
    _t, samples = _check_prometheus(http.request("GET", "/metrics")[1])
    got = {(n, l): v for n, l, v in samples}
    assert got.get(("cnosdb_http_sql_errors_total", ""), 0.0) == 0.0
    by_name = {s["name"]: s for s in _trace_spans(http, tid)}
    assert "cannot render" in by_name["http:sql"]["tags"]["error"]
    assert "cannot render" in by_name["render_ms"]["tags"]["error"]


@pytest.mark.parametrize("sql, percell", [
    # str, float64 and int64 columns: every one has a by-column rule
    ("SELECT host, max(usage) FROM cpu GROUP BY host", 0),
    ("SELECT time, host, usage FROM cpu LIMIT 5", 0),
    # SHOW / DESCRIBE answers are columns of plain str: a rule takes them
    ("DESCRIBE TABLE cpu", 0),
    # a gauge / window composite is a dict in an object column: per cell
    ("SELECT gauge_agg(time, usage) FROM cpu", 1),
    ("SELECT time_window(time, interval '10 seconds') FROM cpu LIMIT 3", 1),
])
def test_served_request_books_render_and_its_percell_columns(http, sql,
                                                             percell):
    _seed_http(http)
    status, body, hdrs = http.request(
        "POST", "/api/v1/sql?db=public", sql,
        headers={"X-CnosDB-Profile": "1"})
    assert status == 200, body
    booked = json.loads(hdrs["X-CnosDB-Profile-Summary"])["stages"]
    assert booked["render_ms"] >= 0
    assert booked["render.percell_columns"] == percell
    assert body.endswith("\n") and body.count("\n") >= 2


def test_disconnect_ends_the_root_span_where_the_worker_ends(http,
                                                             monkeypatch):
    """The handler is cancelled while the worker thread still records
    stages: the root span is finished when the worker has unwound, so no
    child outlasts its parent."""
    import asyncio

    tid = "feedc0de0003"
    started, release = threading.Event(), threading.Event()

    def slow(_sql, _session):
        with stages.stage("decode_ms"):
            started.set()
            assert release.wait(10)
        return []

    monkeypatch.setattr(http.server.executor, "execute_sql", slow)

    class Req:
        headers = {"cnos-trace-id": tid}
        query = {"db": "public"}

        async def text(self):
            return "SELECT 1"

    async def drive():
        task = asyncio.ensure_future(http.server.handle_sql(Req()))
        while not started.is_set():
            await asyncio.sleep(0.005)
        task.cancel()
        with pytest.raises(asyncio.CancelledError):
            await task

    asyncio.run_coroutine_threadsafe(drive(), http._loop).result(10)
    # the worker is still in there: the root stays open
    assert "http:sql" not in {s["name"] for s in _trace_spans(http, tid)}
    release.set()
    for _ in range(200):
        spans = {s["name"]: s for s in _trace_spans(http, tid)}
        if "http:sql" in spans:
            break
        time.sleep(0.01)
    root, child = spans["http:sql"], spans["decode_ms"]
    assert root["tags"]["error"] == "client disconnected"
    assert child["parent_id"] == root["span_id"]
    assert child["start_ns"] + child["duration_ns"] \
        <= root["start_ns"] + root["duration_ns"] + 10**6


def test_jitted_programs_have_stable_names():
    """`ops.program`: the XLA module is `jit_cnosdb_<name>` and the body's
    operations lie under the `cnosdb.<name>` scope — what a chip trace's
    `XLA Modules` line shows."""
    import jax
    import jax.numpy as jnp

    from cnosdb_tpu import ops
    from cnosdb_tpu.ops import device_decode, kernels

    f = jax.jit(ops.program("test_named")(lambda x: x * 2 + 1))
    lowered = f.lower(jnp.ones(8))
    assert "jit_cnosdb_test_named" in lowered.as_text()
    assert "cnosdb.test_named" in lowered.as_text(debug_info=True)
    assert f(jnp.ones(8)).tolist() == [3.0] * 8
    for fn, name in [(kernels.segment_aggregate, "segment_aggregate"),
                     (kernels._device_sort, "sort"),
                     (device_decode._delta_kernel, "decode_delta"),
                     (device_decode._codes_kernel, "decode_codes")]:
        assert fn.__name__ == "cnosdb_" + name


def test_profile_summary_header_is_never_cut():
    from cnosdb_tpu.server.http import (PROFILE_SUMMARY_MAX,
                                        profile_summary_header)

    small = {"decode_ms": 12.5, "scan_miss": 1, "merge_ms": 0.0}
    d = json.loads(profile_summary_header("7", 20.0, small))
    assert d == {"qid": "7", "wall_ms": 20.0, "stages": small}
    # oversized: zero-valued keys go first, then the smallest
    big = {f"rpc_method_number_{i:04d}_ms": float(i) for i in range(400)}
    big.update({f"string_path.zero_{i:04d}": 0 for i in range(50)})
    text = profile_summary_header("8", 99.0, big)
    assert len(text) <= PROFILE_SUMMARY_MAX
    d = json.loads(text)                 # parses: never a cut string
    kept = d["stages"]
    assert d["qid"] == "8" and d["dropped"] == len(big) - len(kept)
    assert 0 < len(kept) < len(big)
    assert all(v for v in kept.values())
    assert min(kept.values()) > max(
        v for k, v in big.items() if k not in kept)
    # zero-valued keys alone were enough here: nothing else is dropped
    some_zero = dict(small, **{f"string_path.z{i:04d}": 0
                               for i in range(300)})
    d = json.loads(profile_summary_header("9", 1.0, some_zero))
    assert d["stages"] == {"decode_ms": 12.5, "scan_miss": 1}
    assert d["dropped"] == 301


def test_multi_batch_kernel_pool_keeps_the_query_context(tmp_path,
                                                         monkeypatch):
    """Two shards → two batches → the kernel pool: the batches' counters,
    stages and spans must reach the submitting query's profile and trace."""
    monkeypatch.setenv("CNOSDB_TPU_FORCE_DEVICE_PATH", "1")
    monkeypatch.setenv("CNOSDB_MESH", "0")
    meta = MetaStore(str(tmp_path / "meta.json"))
    engine = TsKv(str(tmp_path / "data"))
    coord = Coordinator(meta, engine)
    ex = QueryExecutor(meta, coord)
    try:
        ex.execute_one("CREATE DATABASE d2 WITH SHARD 2")
        sess = Session(database="d2")
        ex.execute_one("CREATE TABLE m (v BIGINT, TAGS(h))", sess)
        rows = ", ".join(f"({i * 10**9}, 'h{i % 16}', {i})"
                         for i in range(400))
        ex.execute_one(f"INSERT INTO m (time, h, v) VALUES {rows}", sess)
        from cnosdb_tpu.utils.spans import GLOBAL_COLLECTOR

        prof = stages.QueryProfile()
        prof.traced = True
        with GLOBAL_COLLECTOR.span("http:sql") as root, \
                stages.profile_scope(prof):
            rs = ex.execute_one(
                "SELECT h, max(v), count(v) FROM m GROUP BY h", sess)
        assert rs.n_rows == 16
        assert prof.counts.get("scan_miss") == 2, prof.counts
        assert prof.counts.get("fused_launches") == 2, prof.counts
        assert prof.counts.get("upload_bytes", 0) > 0
        assert prof.ms["kernel.fetch_ms"] > 0
        mine = GLOBAL_COLLECTOR.spans(root.trace_id)
        by_id = {s["span_id"]: s for s in mine}
        fetches = [s for s in mine if s["name"] == "kernel.fetch_ms"]
        assert len(fetches) == 2
        # each on its pool thread: the fan-out's fetch, inside kernel_ms
        assert all(_ancestors(s, by_id)[:2] == ["fanout.fetch_ms",
                                                "kernel_ms"]
                   for s in fetches)
        assert prof.counts.get("fanout.vnodes") == 2, prof.counts
        assert prof.counts.get("merge.groups") == 16, prof.counts
    finally:
        coord.close()


# ------------------------------------------------------- cluster breakdown
@pytest.mark.cluster
def test_explain_analyze_cluster_per_node_breakdown(tmp_path):
    """EXPLAIN ANALYZE on a multi-vnode distributed query: stage rows for
    every participating node, reconciling with the request's profile
    totals within 10%."""
    import base64
    import urllib.request

    from cluster_harness import Cluster

    c = Cluster(str(tmp_path / "cl"), n_nodes=2).start()
    try:
        n1 = c.nodes[0]
        n1.sql("CREATE DATABASE d1 WITH SHARD 4 REPLICA 1", db="public")
        lines = "\n".join(
            f"cpu,host=h{i} usage={i}.5 {1_700_000_000_000_000_000 + i * 10**3}"
            for i in range(64))
        n1.write_lp(lines, db="d1")

        def sql_with_profile(q):
            req = urllib.request.Request(
                f"http://127.0.0.1:{n1.http_port}/api/v1/sql?db=d1",
                data=q.encode(), method="POST",
                headers={"Authorization": "Basic "
                         + base64.b64encode(b"root:").decode(),
                         "X-CnosDB-Profile": "1"})
            with urllib.request.urlopen(req, timeout=30) as resp:
                return resp.read().decode(), dict(resp.headers)

        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            try:
                body, _h = sql_with_profile("SELECT count(*) FROM cpu")
                if body.strip().splitlines()[-1] == "64":
                    break
            except Exception:
                pass
            time.sleep(0.3)
        body, hdrs = sql_with_profile(
            "EXPLAIN ANALYZE SELECT host, max(usage) FROM cpu GROUP BY host")
        rows = []
        for line in body.splitlines():
            m = re.match(r'"?stage node=(\S+) name=(\S+) value=([\d.]+)"?',
                         line)
            if m:
                rows.append((m.group(1), m.group(2), float(m.group(3))))
        assert rows, f"no stage rows in:\n{body}"
        nodes = {node for node, _n, _v in rows}
        assert len(nodes) >= 2, \
            f"expected per-node attribution across the cluster, got {nodes}"
        remote = [n for n, name, _v in rows if name.startswith("rpc_")]
        assert remote, "remote nodes must report rpc_* handler stages"
        # reconcile the rendered rows against the request profile summary
        summary = json.loads(hdrs["X-CnosDB-Profile-Summary"])
        totals = summary["stages"]
        rendered: dict[str, float] = {}
        for _node, name, value in rows:
            rendered[name] = rendered.get(name, 0.0) + value
        for name, value in rendered.items():
            got = totals.get(name, 0.0)
            assert abs(got - value) <= max(0.1 * value, 0.5), \
                f"{name}: EXPLAIN={value} vs profile={got} ({totals})"
    finally:
        c.stop()
