"""The one general load generator. A traffic mix is a data file,
`benchmarks/traffic/<name>.json`:

    clients          query clients, each a closed loop (a dashboard waits
                     for its panels): the next request goes out when the
                     last answer is in
    classes          the query classes (see devops.ClassGenerator) with
                     integer `share`s; every client walks shuffled blocks of
                     the same multiset, so every seed sends the same mix
    warmup_rounds    blocks each client sends before the window, at the
                     window's concurrency (every shape compiles in set-up)
    writers          optional {count, batch_steps, batches}: closed-loop
                     writers posting line-protocol batches that continue
                     the series past the loaded range; the bodies are built
                     before the window
    trace_seconds    length of the profiler trace in a `--trace 1` run

All loops run as threads of the client process: they wait on sockets, and
what they compute between requests is string formatting. A request counts
if it was sent inside the window; at the window's end the loops stop
sending and wait for what is in flight.
"""
from __future__ import annotations

import http.client
import threading
import time

import numpy as np

from . import devops
from .server import Connection, post_write, profile_summary, sql_headers

WARMUP, WINDOW = 0, 1          # phases, part of each loop's seed


def _block(classes: list[dict]) -> list[int]:
    return [i for i, c in enumerate(classes)
            for _ in range(int(c.get("share", 1)))]


class QueryLoop(threading.Thread):
    """One closed-loop query client. Records, per request: the Request,
    status, answer text, client ms (send to last byte), wall-clock send and
    done times, and the profile summary if one was asked for."""

    def __init__(self, idx: int, port: int, db: str, ds: devops.Dataset,
                 classes: list[dict], seed: int, phase: int, profile: bool,
                 gate: "Gate", max_requests: int | None = None):
        super().__init__(name=f"query-{idx}", daemon=True)
        self.rng = np.random.default_rng([seed, 1000 + idx, phase])
        self.gens = [devops.ClassGenerator(c, ds, self.rng) for c in classes]
        self.block = _block(classes)
        self.conn = Connection(port)
        self.path = f"/api/v1/sql?db={db}"
        self.headers = sql_headers(profile)
        self.gate, self.max_requests = gate, max_requests
        self.records: list[dict] = []

    def run(self) -> None:
        self.gate.wait_start()
        order: list[int] = []
        while self.gate.open() and (self.max_requests is None
                                    or len(self.records) < self.max_requests):
            if not order:
                order = self.rng.permutation(self.block).tolist()
            req = self.gens[order.pop()].draw()
            rec = {"req": req, "status": None, "text": None, "profile": None,
                   "sent_wall": time.time()}
            t0 = time.perf_counter()
            try:
                status, headers, body = self.conn.request(
                    "POST", self.path, req.sql.encode(), self.headers)
                rec["status"] = status
                rec["text"] = body.decode("utf-8", "replace")
                rec["profile"] = profile_summary(headers)
            except (http.client.HTTPException, OSError) as e:
                rec["text"] = repr(e)
            rec["ms"] = (time.perf_counter() - t0) * 1e3
            rec["done"] = time.monotonic()
            rec["done_wall"] = time.time()
            self.records.append(rec)
        self.conn.close()


class WriteLoop(threading.Thread):
    """One closed-loop writer: takes the next prepared batch, posts it,
    waits for the acknowledgement (`server.post_write`)."""

    def __init__(self, idx: int, port: int, db: str, batches: "Batches",
                 gate: "Gate"):
        super().__init__(name=f"writer-{idx}", daemon=True)
        self.conn = Connection(port, timeout=300.0)
        self.db, self.batches, self.gate = db, batches, gate
        self.records: list[dict] = []

    def run(self) -> None:
        self.gate.wait_start()
        while self.gate.open():
            item = self.batches.take()
            if item is None:
                break
            last_step, rows, body = item
            t0 = time.perf_counter()
            acked, retries, error = post_write(self.conn, self.db, body)
            self.records.append({
                "rows": rows, "last_step": last_step, "acked": acked,
                "retries": retries, "error": error,
                "ms": (time.perf_counter() - t0) * 1e3,
                "done": time.monotonic(), "done_wall": time.time()})
        self.conn.close()


class Batches:
    """Line-protocol bodies continuing every series past the loaded range,
    built before the window (set-up), handed out in time order."""

    def __init__(self, ds: devops.Dataset, batch_steps: int, count: int):
        k0 = ds.steps
        ds.extend(batch_steps * count)
        t0 = time.monotonic()
        self._items = [
            (k0 + (i + 1) * batch_steps - 1, batch_steps * ds.hosts,
             ds.lines(k0 + i * batch_steps, k0 + (i + 1) * batch_steps))
            for i in range(count)]
        self.build_seconds = time.monotonic() - t0
        self.rows = batch_steps * ds.hosts * count
        self._next = 0
        self._lock = threading.Lock()
        self.exhausted = False

    def take(self):
        with self._lock:
            if self._next >= len(self._items):
                self.exhausted = True
                return None
            item = self._items[self._next]
            self._items[self._next] = None      # the body is sent once
            self._next += 1
            return item


class Gate:
    """Start all loops together; close the window `seconds` later (or
    never, for a phase bounded by request counts)."""

    def __init__(self, seconds: float | None):
        self.seconds = seconds
        self._go = threading.Event()
        self.t_start = None
        self.deadline = None

    def start(self) -> None:
        self.t_start = time.monotonic()
        self.deadline = None if self.seconds is None \
            else self.t_start + self.seconds
        self._go.set()

    def wait_start(self) -> None:
        self._go.wait()

    def open(self) -> bool:
        return self.deadline is None or time.monotonic() < self.deadline


def run_phase(port: int, db: str, ds: devops.Dataset, traffic: dict,
              seed: int, phase: int, *, seconds: float | None,
              profile: bool, batches: Batches | None = None,
              during=None) -> dict:
    """One phase of the traffic: the window (`seconds`, writers too) or
    the warm-up (`warmup_rounds` blocks per client, no writers).
    `during(gate)` runs on the calling thread while the loops run (the
    trace of a traced run). → the loops' records and the phase's clock."""
    gate = Gate(seconds)
    classes = traffic["classes"]
    per_client = None if seconds is not None else \
        int(traffic.get("warmup_rounds", 1)) * len(_block(classes))
    loops: list[threading.Thread] = [
        QueryLoop(i, port, db, ds, classes, seed, phase, profile, gate,
                  per_client)
        for i in range(int(traffic["clients"]))]
    if batches is not None and seconds is not None:
        loops += [WriteLoop(i, port, db, batches, gate)
                  for i in range(int(traffic["writers"]["count"]))]
    for t in loops:
        t.start()
    gate.start()
    if during is not None:
        during(gate)
    for t in loops:
        t.join()
    queries = [r for t in loops if isinstance(t, QueryLoop)
               for r in t.records]
    writes = [r for t in loops if isinstance(t, WriteLoop)
              for r in t.records]
    return {"queries": queries, "writes": writes, "t_start": gate.t_start}


def percentile(values: list[float], q: float) -> float:
    """Nearest-rank percentile of all requests; a failed request is
    `inf` in `values`, slower than any limit."""
    s = sorted(values)
    return s[min(len(s) - 1, max(0, int(np.ceil(q / 100.0 * len(s))) - 1))]
