"""Tracing, debug endpoints, TCP OpenTSDB listener, TLS config, gzip
(reference common/trace/, main/src/http/http_service.rs debug routes,
tcp/tcp_service.rs)."""
import asyncio
import threading
import time

import pytest

from cnosdb_tpu.utils.spans import (
    GLOBAL_COLLECTOR, TRACE_HEADER, TraceCollector, current_trace_header,
)


def test_span_nesting_and_collection():
    col = TraceCollector()
    with col.span("root") as root:
        root.set_tag("k", "v")
        with col.span("child"):
            pass
    spans = col.spans()
    assert [s["name"] for s in spans] == ["child", "root"]
    child, root_d = spans
    assert child["trace_id"] == root_d["trace_id"]
    assert child["parent_id"] == root_d["span_id"]
    assert root_d["tags"] == {"k": "v"}
    assert root_d["duration_ns"] > 0


def test_header_propagation():
    col = TraceCollector()
    with col.span("origin") as s:
        hdr = current_trace_header()
        assert hdr == f"{s.trace_id}:{s.span_id}"
    # remote side continues the trace
    with col.from_headers({TRACE_HEADER: hdr}, "remote") as r:
        assert r.trace_id == s.trace_id
        assert r.parent_id == s.span_id


def test_rpc_plane_propagates_trace():
    from cnosdb_tpu.parallel.net import RpcServer, rpc_call

    seen = []

    def handler(p):
        seen.append(current_trace_header())
        return {"ok": True}

    srv = RpcServer("127.0.0.1", 0, {"x": handler}).start()
    try:
        with GLOBAL_COLLECTOR.span("caller") as s:
            rpc_call(srv.addr, "x", {})
        assert seen and seen[0].startswith(s.trace_id + ":")
    finally:
        srv.stop()


@pytest.fixture
def http_server(tmp_path):
    from aiohttp import web

    from cnosdb_tpu.server.http import build_server

    srv = build_server(str(tmp_path / "data"))
    loop_holder = {}

    async def run():
        runner = web.AppRunner(srv.app)
        await runner.setup()
        site = web.TCPSite(runner, "127.0.0.1", 0)
        await site.start()
        loop_holder["port"] = site._server.sockets[0].getsockname()[1]
        loop_holder["tcp"] = await srv.start_tcp_opentsdb("127.0.0.1", 0)
        loop_holder["tcp_port"] = \
            loop_holder["tcp"].sockets[0].getsockname()[1]
        loop_holder["ready"] = True
        await asyncio.sleep(120)

    t = threading.Thread(target=lambda: asyncio.run(run()), daemon=True)
    t.start()
    deadline = time.monotonic() + 15
    while not loop_holder.get("ready") and time.monotonic() < deadline:
        time.sleep(0.05)
    yield srv, loop_holder["port"], loop_holder["tcp_port"]


def _get(port, path):
    import urllib.request

    with urllib.request.urlopen(f"http://127.0.0.1:{port}{path}") as r:
        return r.status, r.read()


def test_debug_endpoints_and_tcp_listener(http_server):
    import base64
    import socket
    import urllib.request

    srv, port, tcp_port = http_server
    # write through the TCP OpenTSDB listener
    s = socket.create_connection(("127.0.0.1", tcp_port), timeout=5)
    s.sendall(b"put sys.load 1000 1.5 host=tcp1\n"
              b"put sys.load 2000 2.5 host=tcp1\nquit\n")
    s.close()
    deadline = time.monotonic() + 10

    def sql(q):
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/api/v1/sql?db=public", data=q.encode())
        req.add_header("Authorization",
                       "Basic " + base64.b64encode(b"root:").decode())
        req.add_header("Accept-Encoding", "gzip")
        with urllib.request.urlopen(req) as r:
            raw = r.read()
            if r.headers.get("Content-Encoding") == "gzip":
                import gzip as _gz

                raw = _gz.decompress(raw)
            return raw.decode()

    while time.monotonic() < deadline:
        try:
            out = sql('SELECT count(*) AS c FROM "sys.load"')
            if out.strip().splitlines()[-1] == "2":
                break
        except Exception:
            pass
        time.sleep(0.2)
    assert out.strip().splitlines()[-1] == "2"
    # the sql call above created a span; /debug/traces shows it
    st, body = _get(port, "/debug/traces")
    assert st == 200 and b"http:sql" in body
    st, body = _get(port, "/debug/backtrace")
    assert st == 200 and b"thread" in body
    st, body = _get(port, "/debug/pprof?seconds=0.2")
    assert st == 200 and b"samples over" in body


def test_tls_config_loading(tmp_path):
    from cnosdb_tpu.config import Config

    cfg_path = tmp_path / "c.toml"
    cfg_path.write_text(
        '[security]\ntls_cert_path = "/x/cert.pem"\n'
        'tls_key_path = "/x/key.pem"\n')
    cfg = Config.load(str(cfg_path))
    assert cfg.security.enabled
    assert Config().security.enabled is False


def test_otlp_ingest_and_jaeger_query_api(http_server):
    """OTLP/HTTP JSON export → own-table storage → SQL AND jaeger API
    (reference otlp_to_jaeger.rs + http_service.rs jaeger endpoints)."""
    import json as _json
    import urllib.request

    srv, port, _tcp = http_server
    payload = {
        "resourceSpans": [{
            "resource": {"attributes": [
                {"key": "service.name",
                 "value": {"stringValue": "checkout"}}]},
            "scopeSpans": [{"spans": [
                {"traceId": "abc123", "spanId": "s1", "name": "GET /cart",
                 "kind": 2, "startTimeUnixNano": "1700000000000000000",
                 "endTimeUnixNano": "1700000000005000000",
                 "attributes": [{"key": "http.status_code",
                                 "value": {"intValue": "200"}}],
                 "status": {"code": 1}},
                {"traceId": "abc123", "spanId": "s2",
                 "parentSpanId": "s1", "name": "SELECT",
                 "kind": 3, "startTimeUnixNano": "1700000000001000000",
                 "endTimeUnixNano": "1700000000002000000"},
            ]}],
        }],
    }
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/api/v1/traces?db=public",
        data=_json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req) as r:
        assert r.status == 200

    # stored spans are plain SQL rows
    sreq = urllib.request.Request(
        f"http://127.0.0.1:{port}/api/v1/sql?db=public",
        data=b"SELECT count(*) AS c FROM trace_spans",
        headers={"Accept": "application/json"})
    with urllib.request.urlopen(sreq) as r:
        body = r.read().decode()
    assert '"c": 2' in body or '"c":2' in body, body

    st, body = _get(port, "/api/services")
    assert st == 200 and _json.loads(body)["data"] == ["checkout"]
    st, body = _get(port, "/api/services/checkout/operations")
    assert st == 200
    assert sorted(_json.loads(body)["data"]) == ["GET /cart", "SELECT"]

    st, body = _get(port, "/api/traces?service=checkout")
    traces = _json.loads(body)["data"]
    assert st == 200 and len(traces) == 1
    tr = traces[0]
    assert tr["traceID"] == "abc123" and len(tr["spans"]) == 2
    child = next(s for s in tr["spans"] if s["spanID"] == "s2")
    assert child["references"] == [{"refType": "CHILD_OF",
                                    "traceID": "abc123", "spanID": "s1"}]
    assert child["startTime"] == 1700000000001000  # µs
    assert child["duration"] == 1000               # µs
    procs = tr["processes"]
    assert [p["serviceName"] for p in procs.values()] == ["checkout"]

    st, body = _get(port, "/api/traces/abc123")
    assert st == 200 and _json.loads(body)["data"][0]["traceID"] == "abc123"


def test_otlp_span_export():
    """Own spans export as OTLP/HTTP JSON batches (reference
    global_tracing.rs minitrace → opentelemetry-otlp). A stock OTLP
    collector accepts the JSON encoding on /v1/traces."""
    import json
    import threading
    from http.server import BaseHTTPRequestHandler, HTTPServer

    from cnosdb_tpu.server.trace import OtlpExporter
    from cnosdb_tpu.utils.spans import TraceCollector

    received = []

    class Recv(BaseHTTPRequestHandler):
        def do_POST(self):
            body = self.rfile.read(int(self.headers["Content-Length"]))
            received.append((self.path, json.loads(body)))
            self.send_response(200)
            self.end_headers()

        def log_message(self, *a):
            pass

    srv = HTTPServer(("127.0.0.1", 0), Recv)
    t = threading.Thread(target=srv.serve_forever, daemon=True)
    t.start()
    try:
        coll = TraceCollector()
        exp = OtlpExporter(f"http://127.0.0.1:{srv.server_port}", coll,
                           flush_interval_s=0.2)
        with coll.span("parent") as p:
            p.set_tag("db", "public")
            with coll.span("child"):
                pass
        exp.close()
        assert received, "no OTLP batch arrived"
        path, payload = received[0]
        assert path == "/v1/traces"
        rs = payload["resourceSpans"][0]
        svc = rs["resource"]["attributes"][0]
        assert svc["key"] == "service.name"
        spans = rs["scopeSpans"][0]["spans"]
        names = {s["name"] for s in spans}
        assert {"parent", "child"} <= names
        by_name = {s["name"]: s for s in spans}
        # ids are OTLP fixed-width hex; the child links to its parent
        assert len(by_name["parent"]["traceId"]) == 32
        assert len(by_name["parent"]["spanId"]) == 16
        assert by_name["child"]["parentSpanId"] == \
            by_name["parent"]["spanId"]
        assert by_name["child"]["traceId"] == by_name["parent"]["traceId"]
        pa = {a["key"]: a["value"]["stringValue"]
              for a in by_name["parent"]["attributes"]}
        assert pa.get("db") == "public"
        assert int(by_name["parent"]["endTimeUnixNano"]) >= \
            int(by_name["parent"]["startTimeUnixNano"])
        assert exp.exported == len(spans)
    finally:
        srv.shutdown()
