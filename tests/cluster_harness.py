"""Multi-process cluster harness for e2e tests.

Counterpart of the reference's declarative cluster bring-up
(e2e_test/src/cluster_def.rs:12-76 CnosdbClusterDefinition +
e2e_test/src/utils/ process management): spawns one meta process and N
data-node processes on localhost with distinct ports/dirs, exposes
HTTP write/sql helpers, and supports kill/restart of individual nodes.
"""
from __future__ import annotations

import base64
import os
import socket
import subprocess
import sys
import time
import urllib.error
import urllib.request


def free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    p = s.getsockname()[1]
    s.close()
    return p


def _env():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"  # a chip belongs to one process
    env.setdefault("PYTHONUNBUFFERED", "1")
    return env


class Node:
    def __init__(self, cluster: "Cluster", node_id: int):
        self.cluster = cluster
        self.node_id = node_id
        self.http_port = free_port()
        self.rpc_port = free_port()
        self.data_dir = os.path.join(cluster.root, f"node{node_id}")
        self.proc: subprocess.Popen | None = None

    def start(self):
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "cnosdb_tpu.server.main", "run",
             "--mode", "query_tskv",
             "--meta", f"127.0.0.1:{self.cluster.meta_port}",
             "--node-id", str(self.node_id),
             "--data-dir", self.data_dir,
             "--http-port", str(self.http_port),
             "--rpc-port", str(self.rpc_port)],
            env=self.cluster.env, stdout=self.cluster.log,
            stderr=self.cluster.log)
        return self

    def kill(self):
        if self.proc is not None:
            self.proc.kill()
            self.proc.wait(timeout=10)
            self.proc = None

    def wait_ready(self, timeout: float = 60.0):
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            try:
                self.http("GET", "/api/v1/ping")
                return self
            except Exception:
                if self.proc is not None and self.proc.poll() is not None:
                    raise RuntimeError(
                        f"node {self.node_id} exited rc={self.proc.returncode}")
                time.sleep(0.1)
        raise TimeoutError(f"node {self.node_id} not ready")

    def http(self, method: str, path: str, body: bytes | None = None,
             timeout: float = 30.0) -> str:
        req = urllib.request.Request(
            f"http://127.0.0.1:{self.http_port}{path}", data=body,
            method=method)
        req.add_header("Authorization",
                       "Basic " + base64.b64encode(b"root:").decode())
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return resp.read().decode()

    def write_lp(self, lines: str, db: str = "public"):
        return self.http("POST", f"/api/v1/write?db={db}", lines.encode())

    def sql(self, q: str, db: str = "public") -> str:
        return self.http("POST", f"/api/v1/sql?db={db}", q.encode())


class Cluster:
    def __init__(self, root: str, n_nodes: int = 3):
        self.root = root
        # snapshot the spawn env ONCE: fixtures set knobs (CNOSDB_FAULTS,
        # CNOSDB_LOCKWATCH, ...) around construction and drop them right
        # after, and a node RESTARTED mid-test (crash injection) must come
        # back with the same knobs as its first boot
        self.env = _env()
        self.meta_port = free_port()
        os.makedirs(root, exist_ok=True)
        self.log = open(os.path.join(root, "cluster.log"), "ab")
        self.meta_proc: subprocess.Popen | None = None
        self.nodes = [Node(self, i + 1) for i in range(n_nodes)]

    def start(self):
        self.meta_proc = subprocess.Popen(
            [sys.executable, "-m", "cnosdb_tpu.server.main", "run",
             "--mode", "meta",
             "--data-dir", os.path.join(self.root, "meta"),
             "--meta-port", str(self.meta_port)],
            env=self.env, stdout=self.log, stderr=self.log)
        for n in self.nodes:
            n.start()
        for n in self.nodes:
            n.wait_ready()
        return self

    def stop(self):
        for n in self.nodes:
            try:
                n.kill()
            except Exception:
                pass
        if self.meta_proc is not None:
            self.meta_proc.kill()
            self.meta_proc.wait(timeout=10)
            self.meta_proc = None
        self.log.close()

    def alive_node(self) -> Node:
        for n in self.nodes:
            if n.proc is not None:
                return n
        raise RuntimeError("no node alive")


def assert_lock_graph_acyclic(cluster: Cluster) -> int:
    """Teardown invariant for suites run with CNOSDB_LOCKWATCH=1: pull
    /debug/lockgraph from every node still alive and fail on any observed
    lock-order cycle (two code paths nesting the same locks in opposite
    order — a deadlock waiting for the right interleaving). Returns the
    number of nodes checked so callers can assert coverage."""
    import json as _json

    checked = 0
    for n in cluster.nodes:
        if n.proc is None or n.proc.poll() is not None:
            continue
        rep = _json.loads(n.http("GET", "/debug/lockgraph"))
        assert rep["enabled"], f"node {n.node_id}: lockwatch not enabled"
        assert rep["cycles"] == [], (
            f"node {n.node_id}: lock-order cycles {rep['cycles']} "
            f"(edges: {rep['edges']})")
        checked += 1
    return checked
