"""The server child as the client sees it: start, stop, kill, HTTP
requests, the `/metrics` scrape and the control socket.

Copied from `chip_smoke.py`'s `Server` (proven on the chip in PR 22); the
child is `server_child.py`, which adds the control thread. Nothing here
imports JAX or `cnosdb_tpu`.
"""
from __future__ import annotations

import http.client
import json
import os
import signal
import socket
import subprocess
import sys
import time

LIB = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(LIB))
NATIVE_LIB = os.path.join(ROOT, "cnosdb_tpu", "_native",
                          "libcnosdb_codecs.so")
QUERY_DEADLINE_MS = 900_000      # a cold query compiles; default is 30 s
# error counters (cnosdb_errors_total{area,kind}) that mean a device lane
# failed and the answer came from somewhere else
DEVICE_ERROR_AREAS = {"device_decode", "mesh", "scan"}


class Fail(Exception):
    """The run cannot produce a result."""


def free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def build_native() -> float | None:
    """Build the native library if the `.so` is absent (git and chiprun
    ignore it, so the first run in a checkout builds it) → seconds."""
    if os.path.exists(NATIVE_LIB):
        return None
    t0 = time.monotonic()
    p = subprocess.run(["make", "-C", os.path.join(ROOT, "native")],
                       capture_output=True, text=True, timeout=600)
    if p.returncode != 0 or not os.path.exists(NATIVE_LIB):
        raise Fail(f"native build failed (rc={p.returncode}): "
                   f"{p.stderr[-1500:]}")
    return time.monotonic() - t0


class Connection:
    """One keep-alive HTTP connection, owned by one thread."""

    def __init__(self, port: int, timeout: float = 1200.0):
        self.port, self.timeout = port, timeout
        self._c: http.client.HTTPConnection | None = None

    def request(self, method: str, path: str, body: bytes | None = None,
                headers: dict | None = None):
        """→ (status, headers, body bytes). HTTP error statuses return; a
        transport failure raises OSError after one reconnect (a kept-alive
        socket the server closed meanwhile)."""
        for attempt in (0, 1):
            if self._c is None:
                self._c = http.client.HTTPConnection(
                    "127.0.0.1", self.port, timeout=self.timeout)
            try:
                self._c.request(method, path, body=body,
                                headers=headers or {})
                r = self._c.getresponse()
                return r.status, r.headers, r.read()
            except (http.client.HTTPException, OSError):
                self.close()
                if attempt:
                    raise
        raise AssertionError("unreachable")

    def close(self) -> None:
        if self._c is not None:
            self._c.close()
            self._c = None


class Server:
    """`benchmarks/lib/server_child.py` as a child process, from the repo
    root (the package is not pip-installed)."""

    def __init__(self, data_dir: str, log_path: str, env: dict):
        self.data_dir, self.log_path, self.env = data_dir, log_path, env
        self.port = free_port()
        self.control_port = free_port()
        self.proc: subprocess.Popen | None = None
        self._log = None
        self._conn = Connection(self.port)

    # ---- lifecycle
    def start(self, timeout: float = 300.0) -> float:
        t0 = time.monotonic()
        self._log = open(self.log_path, "ab")
        self.proc = subprocess.Popen(
            [sys.executable, os.path.join(LIB, "server_child.py"),
             "--data-dir", self.data_dir, "--http-port", str(self.port),
             "--control-port", str(self.control_port)],
            cwd=ROOT, env=self.env, stdout=self._log,
            stderr=subprocess.STDOUT, start_new_session=True,
            # SIGINT is the server's clean stop; a parent started with it
            # ignored (a background job) would hand that on to the child
            preexec_fn=lambda: signal.signal(signal.SIGINT, signal.SIG_DFL))
        while True:
            self.check_alive()
            try:
                status, _h, _b = Connection(self.port, 2.0).request(
                    "GET", "/api/v1/ping")
                if status == 200:
                    return time.monotonic() - t0
            except (http.client.HTTPException, OSError):
                pass
            if time.monotonic() - t0 > timeout:
                raise Fail(f"server not ready after {timeout:.0f}s: "
                           + self.log_tail())
            time.sleep(0.2)

    def log_tail(self, n: int = 2000) -> str:
        try:
            with open(self.log_path, "rb") as f:
                f.seek(0, os.SEEK_END)
                f.seek(max(0, f.tell() - n))
                return f.read().decode("utf-8", "replace")
        except OSError:
            return ""

    def check_alive(self) -> None:
        if self.proc is not None and self.proc.poll() is not None:
            raise Fail(f"server exited early (rc={self.proc.returncode}): "
                       + self.log_tail())

    def stop(self, kill: bool = False) -> None:
        """SIGINT is the server's clean shutdown (SIGKILL with `kill`: the
        crash of the durability check); then make sure nothing of its
        process group is left, and wait for it."""
        self._conn.close()
        if self.proc is None:
            return
        if self.proc.poll() is None and not kill:
            self.proc.send_signal(signal.SIGINT)
            try:
                self.proc.wait(timeout=60)
            except subprocess.TimeoutExpired:
                pass
        try:
            os.killpg(self.proc.pid, signal.SIGKILL)
        except (ProcessLookupError, PermissionError):
            pass
        self.proc.wait()
        self.proc = None
        if self._log is not None:
            self._log.close()
            self._log = None

    # ---- requests (the main thread's own connection)
    def request(self, method: str, path: str, body: bytes | None = None,
                headers: dict | None = None):
        try:
            return self._conn.request(method, path, body, headers)
        except (http.client.HTTPException, OSError) as e:
            self.check_alive()
            raise Fail(f"{method} {path}: {e!r}")

    def sql(self, db: str, sql: str, profile: bool = False):
        """→ (CSV text, profile summary or None). Raises Fail unless 200."""
        status, headers, body = self.request(
            "POST", f"/api/v1/sql?db={db}", sql.encode(),
            sql_headers(profile))
        if status != 200:
            raise Fail(f"sql failed ({status}): {body[:400]!r} :: {sql[:200]}")
        return body.decode(), profile_summary(headers)

    def full_profile(self, qid) -> dict:
        status, _h, body = self.request("GET", f"/debug/profile?qid={qid}")
        if status != 200:
            raise Fail(f"/debug/profile?qid={qid} → {status}")
        return json.loads(body)

    def metrics(self) -> dict:
        """/metrics → {(name, (sorted label pairs)): value}."""
        status, _h, body = self.request("GET", "/metrics")
        if status != 200:
            raise Fail(f"/metrics → {status}")
        return parse_metrics(body.decode())

    # ---- the control thread of the child
    def control(self, op: str, timeout: float = 120.0, **args) -> dict:
        """One request to the child's control thread → its answer."""
        try:
            with socket.create_connection(("127.0.0.1", self.control_port),
                                          timeout=timeout) as s:
                s.sendall(json.dumps({"op": op, **args}).encode() + b"\n")
                buf = b""
                while not buf.endswith(b"\n"):
                    chunk = s.recv(65536)
                    if not chunk:
                        break
                    buf += chunk
        except OSError as e:
            self.check_alive()
            raise Fail(f"control {op}: {e!r}")
        if not buf:
            raise Fail(f"control {op}: no answer")
        out = json.loads(buf)
        if out.get("error"):
            raise Fail(f"control {op}: {out['error']}")
        return out


def post_write(conn: Connection, db: str, body: bytes,
               max_sleep: float = 1.0) -> tuple[bool, int, str | None]:
    """One batch over /api/v1/write → (acknowledged, retries, error). A 503
    with Retry-After is the server's write backpressure; a client waits and
    resends."""
    retries = 0
    try:
        for _attempt in range(120):
            status, headers, resp = conn.request(
                "POST", f"/api/v1/write?db={db}", body)
            if status == 200:
                return True, retries, None
            retry_after = headers.get("Retry-After")
            if status != 503 or retry_after is None:
                return False, retries, f"{status}: {resp[:300]!r}"
            retries += 1
            time.sleep(min(float(retry_after), max_sleep))
    except (http.client.HTTPException, OSError) as e:
        return False, retries, repr(e)
    return False, retries, "still backpressured after 120 tries"


def sql_headers(profile: bool) -> dict:
    h = {"Accept": "application/csv",
         "X-CnosDB-Deadline-Ms": str(QUERY_DEADLINE_MS)}
    if profile:
        h["X-CnosDB-Profile"] = "1"
    return h


def profile_summary(headers) -> dict | None:
    """`X-CnosDB-Profile-Summary` → {qid, wall_ms, stages}; the server
    cuts the header at 4096 bytes, so one that does not parse is None."""
    raw = headers.get("X-CnosDB-Profile-Summary")
    if not raw:
        return None
    try:
        return json.loads(raw)
    except ValueError:
        return None


def parse_metrics(text: str) -> dict:
    out = {}
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        head, _, val = line.rpartition(" ")
        name, _, rest = head.partition("{")
        labels = tuple(sorted(
            (kv.partition("=")[0], kv.partition("=")[2].strip('"'))
            for kv in rest.rstrip("}").split(",") if kv))
        try:
            out[(name, labels)] = float(val)
        except ValueError:
            pass
    return out


def metric(m: dict, name: str, **labels) -> float:
    return m.get((name, tuple(sorted(labels.items()))), 0.0)


def device_errors(m: dict) -> dict:
    """Error counters of the device lanes booked since the server started
    (set-up included), and pages the decode lane handed back to the host
    after a kernel error."""
    out = {}
    for (name, labels), v in m.items():
        d = dict(labels)
        if v <= 0:
            continue
        if name == "cnosdb_errors_total" \
                and d.get("area") in DEVICE_ERROR_AREAS:
            out[f"errors.{d.get('area')}.{d.get('kind')}"] = int(v)
        if name == "cnosdb_device_decode_total" \
                and d.get("reason") == "kernel_error":
            out[f"device_decode.{d.get('lane')}.kernel_error"] = int(v)
    return out
