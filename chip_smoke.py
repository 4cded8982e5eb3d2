#!/usr/bin/env python3
"""chip_smoke.py — the one command that proves the served path starts on the chip.

Drives the main path once, through the entry point a user calls:

    HTTP line-protocol write -> FLUSH to TSM -> SQL over HTTP -> scan (page
    decode, device-decode lane) -> upload -> fused filter / time-bucket /
    segment-aggregate program -> merge -> CSV answer

against `python -m cnosdb_tpu.server.main run`, and checks every answer
against a plain numpy oracle written here (no code shared with cnosdb_tpu).

This process is a client: subprocess + urllib + numpy. It never imports
JAX or cnosdb_tpu.ops — the server child is the one process that holds
the chip, and there is one server at a time.

Data: the `cpu` measurement of TSBS devops cpu-only (10 tags, 10 usage_*
fields written as integers — the TSBS influx serializer emits `58i` — one
point per host per 10 s), made from --seed. Default 1000 hosts x 6 h =
2.16 M rows, 21.6 M field values. The shape is never cut; --hours is the
only size that may shrink, and the cut is printed.

Output: one JSON object per line. The readings in the earlier lines are
readings of a smoke, not benchmark numbers. The last line is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

and `ok` is true only when the server ran on a TPU and every check held.
Anything else — no accelerator, a wrong answer, a failed request, a device
lane that did not engage (the decode lane: where CNOSDB_DEVICE_DECODE=1
forces it; in auto mode it stands behind the native decoder and every page
must be booked), a booked kernel error — ends with `"ok": false` and a
non-zero exit code.

    python chip_smoke.py              the one-chip run the driver makes
    python chip_smoke.py --rehearse   same phases, toy size, any backend;
                                      never prints ok: true off the chip
    python chip_smoke.py --chips 4    the mesh lane across four chips and
                                      what it is compared with, nothing else
"""
from __future__ import annotations

import argparse
import csv
import dataclasses
import io
import json
import os
import shutil
import signal
import socket
import subprocess
import sys
import tempfile
import time
import traceback
import urllib.error
import urllib.request

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
NS = 1_000_000_000
STEP_S = 10                                  # TSBS devops: one point / 10 s
T0_S = 1_451_606_400                         # 2016-01-01T00:00:00Z (TSBS)
FIELDS = ["usage_user", "usage_system", "usage_idle", "usage_nice",
          "usage_iowait", "usage_irq", "usage_softirq", "usage_steal",
          "usage_guest", "usage_guest_nice"]
TAGS = ["hostname", "region", "datacenter", "rack", "os", "arch", "team",
        "service", "service_version", "service_environment"]
REGIONS = {
    "us-east-1": ["us-east-1a", "us-east-1b", "us-east-1c", "us-east-1e"],
    "us-west-1": ["us-west-1a", "us-west-1b"],
    "us-west-2": ["us-west-2a", "us-west-2b", "us-west-2c"],
    "eu-west-1": ["eu-west-1a", "eu-west-1b", "eu-west-1c"],
    "eu-central-1": ["eu-central-1a", "eu-central-1b"],
    "ap-southeast-1": ["ap-southeast-1a", "ap-southeast-1b"],
    "ap-southeast-2": ["ap-southeast-2a", "ap-southeast-2b"],
    "ap-northeast-1": ["ap-northeast-1a", "ap-northeast-1c"],
    "sa-east-1": ["sa-east-1a", "sa-east-1b", "sa-east-1c"],
}
OSES = ["Ubuntu16.10", "Ubuntu16.04LTS", "Ubuntu15.10"]
ARCHES = ["x64", "x86"]
TEAMS = ["SF", "NYC", "LON", "CHI"]
ENVS = ["production", "staging", "test"]
BATCH_STEPS = 10                 # timestamps per write batch (x hosts rows)
QUERY_DEADLINE_MS = 900_000      # a cold query compiles; default is 30 s
# error counters (cnosdb_errors_total{area,kind}) that mean a device lane
# failed and the answer came from somewhere else
DEVICE_ERROR_AREAS = {"device_decode", "mesh", "scan"}
NATIVE_LIB = os.path.join(ROOT, "cnosdb_tpu", "_native",
                          "libcnosdb_codecs.so")


class Fail(Exception):
    """A check of the smoke did not hold."""


def emit(obj: dict) -> None:
    print(json.dumps(obj, separators=(", ", ": "), default=str), flush=True)


def free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


# ---------------------------------------------------------------- the data
class Dataset:
    """TSBS devops cpu-only, generated in bulk: tags per host, ten clamped
    random walks per host (start uniform 0..100, N(0,1) steps, clamped to
    [0, 100], written as int64 like TSBS's ToPointAllInt64)."""

    def __init__(self, seed: int, hosts: int, steps: int):
        rng = np.random.default_rng(seed)
        self.hosts, self.steps = hosts, steps
        regions = list(REGIONS)
        self.tags = []
        for h in range(hosts):
            region = regions[rng.integers(len(regions))]
            dcs = REGIONS[region]
            self.tags.append({
                "hostname": f"host_{h}", "region": region,
                "datacenter": dcs[rng.integers(len(dcs))],
                "rack": str(rng.integers(100)),
                "os": OSES[rng.integers(len(OSES))],
                "arch": ARCHES[rng.integers(len(ARCHES))],
                "team": TEAMS[rng.integers(len(TEAMS))],
                "service": str(rng.integers(20)),
                "service_version": str(rng.integers(2)),
                "service_environment": ENVS[rng.integers(len(ENVS))]})
        self.hostnames = [t["hostname"] for t in self.tags]
        self._state = rng.uniform(0.0, 100.0, (len(FIELDS), hosts))
        self._rng = rng
        # v[f, h, k]: field f of host h at step k
        self.v = np.empty((len(FIELDS), hosts, steps), dtype=np.int64)
        for k in range(steps):
            self.v[:, :, k] = self._advance()
        self.ts = (T0_S + STEP_S * np.arange(steps, dtype=np.int64)) * NS
        self._prefix = [
            "cpu," + ",".join(f"{k}={t[k]}" for k in TAGS) + " "
            for t in self.tags]
        self._fmt = "%s" + ",".join(f"{f}=%di" for f in FIELDS) + " %d"

    def _advance(self) -> np.ndarray:
        np.clip(self._state + self._rng.normal(0.0, 1.0, self._state.shape),
                0.0, 100.0, out=self._state)
        return self._state.astype(np.int64)

    def append_step(self) -> int:
        """One more timestamp for every host (the late batch) → its step."""
        k = self.steps
        self.v = np.concatenate([self.v, self._advance()[:, :, None]], axis=2)
        self.ts = np.append(self.ts, (T0_S + STEP_S * k) * NS)
        self.steps += 1
        return k

    @property
    def n_rows(self) -> int:
        return self.hosts * self.steps

    def lines(self, k0: int, k1: int) -> bytes:
        """Line protocol for steps [k0, k1), time-major like tsbs_load."""
        slab = self.v[:, :, k0:k1].transpose(2, 1, 0).tolist()   # [k][h][f]
        fmt, prefix = self._fmt, self._prefix
        out = []
        for i, per_host in enumerate(slab):
            ts = int(self.ts[k0 + i])
            out.extend(fmt % (prefix[h], *row, ts)
                       for h, row in enumerate(per_host))
        return "\n".join(out).encode()

    def k_range(self, lo_ns: int, hi_ns: int) -> tuple[int, int]:
        """Step indices with lo <= ts < hi."""
        return (int(np.searchsorted(self.ts, lo_ns, side="left")),
                int(np.searchsorted(self.ts, hi_ns, side="left")))


# -------------------------------------------------------------- the oracle
def bucket_runs(ts: np.ndarray, interval_ns: int):
    """ts ascending → (bucket start per run, run start offsets): date_bin
    buckets are multiples of the interval since the epoch."""
    b = ts // interval_ns
    starts = np.flatnonzero(np.concatenate(([True], b[1:] != b[:-1])))
    return b[starts] * interval_ns, starts


def _bucketed(ds: Dataset, fields, hosts, lo_ns, hi_ns, interval_ns):
    """→ (bucket starts, run offsets, run lengths, [block per field]) for
    rows of `hosts` with lo <= ts < hi; block is [hosts, steps]."""
    k0, k1 = ds.k_range(lo_ns, hi_ns)
    bstart, starts = bucket_runs(ds.ts[k0:k1], interval_ns)
    lengths = np.diff(np.append(starts, k1 - k0))
    return bstart, starts, lengths, [
        ds.v[FIELDS.index(f)][hosts, k0:k1] for f in fields]


def oracle_max_by_bucket(ds, fields, hosts, lo_ns, hi_ns, interval_ns):
    """{(bucket_ns,): [max of each field over the hosts]}."""
    bstart, starts, _n, blocks = _bucketed(ds, fields, hosts, lo_ns, hi_ns,
                                           interval_ns)
    cols = [np.maximum.reduceat(b, starts, axis=1).max(axis=0)
            for b in blocks]
    return {(int(b),): [c[i] for c in cols] for i, b in enumerate(bstart)}


def oracle_avg_by_bucket_host(ds, fields, lo_ns, hi_ns, interval_ns):
    """{(bucket_ns, hostname): [mean of each field]} — the exact integer
    sum over the exact count, one division."""
    hosts = np.arange(ds.hosts)
    bstart, starts, n, blocks = _bucketed(ds, fields, hosts, lo_ns, hi_ns,
                                          interval_ns)
    cols = [np.add.reduceat(b, starts, axis=1) / n[None, :] for b in blocks]
    return {(int(b), ds.hostnames[h]): [c[h, i] for c in cols]
            for i, b in enumerate(bstart) for h in range(ds.hosts)}


def oracle_high_cpu(ds: Dataset, lo_ns, hi_ns, threshold: float) -> dict:
    """{(ts_ns, hostname): [all ten field values]} where usage_user > t."""
    k0, k1 = ds.k_range(lo_ns, hi_ns)
    hh, kk = np.nonzero(ds.v[0][:, k0:k1] > threshold)
    vals = ds.v[:, hh, kk + k0]                              # [F, n]
    return {(int(ds.ts[k + k0]), ds.hostnames[h]): vals[:, i].tolist()
            for i, (h, k) in enumerate(zip(hh.tolist(), kk.tolist()))}


def oracle_lastpoint(ds: Dataset, hi_ns: int | None) -> dict:
    """{(hostname,): [last value of each field]} over ts < hi."""
    k1 = ds.steps if hi_ns is None else ds.k_range(0, hi_ns)[1]
    if k1 == 0:
        return {}
    return {(ds.hostnames[h],): ds.v[:, h, k1 - 1].tolist()
            for h in range(ds.hosts)}


# ------------------------------------------------------------- the queries
def _bin(interval: str) -> str:
    return f"date_bin(INTERVAL '{interval}', time) AS t"


def _window(lo: int, hi: int) -> str:
    return f"time >= {lo} AND time < {hi}"


def _in_hosts(ds: Dataset, hosts) -> str:
    return "hostname IN (" + ", ".join(
        f"'{ds.hostnames[h]}'" for h in hosts) + ")"


@dataclasses.dataclass
class Query:
    """One TSBS devops query: its SQL, its oracle ({key tuple: values}),
    and how to read the answer. `fused` marks aggregate shapes
    tpu_exec._device_eligible accepts (numeric field aggregates, no
    aggregate over time, no tag predicate left in the residual filter): on
    a device backend their profile must show a fused launch. The two
    queries that name hosts keep `hostname = ...` in the residual filter,
    so they take the per-column segment kernel
    (kernels.aggregate_column_host) instead."""

    name: str
    sql: str
    want: dict
    keys: tuple          # the key columns' types, in answer order
    exact: bool = True   # integers compare equal; averages to 1e-9
    fused: bool = False


def make_queries(ds: Dataset, rng, variant: int) -> list[Query]:
    """The six queries for one pass; `variant` 0 is the cold pass, 1..3 the
    warm ones — hosts and windows are drawn anew each time, so the result
    cache (server/serving.py) cannot answer in place of the device."""
    span_s = (ds.steps - 1) * STEP_S
    lo0 = T0_S

    def window(tsbs_s: int) -> tuple[int, int]:
        # TSBS's window, cut to 5/6 of the data when the data is shorter,
        # placed at a random whole second
        w = min(tsbs_s, max(STEP_S, span_s * 5 // 6))
        start = lo0 + int(rng.integers(0, max(1, span_s - w)))
        return start * NS, (start + w) * NS

    qs = []
    h1 = int(rng.integers(ds.hosts))
    lo, hi = window(3600)
    qs.append(Query(
        "single-groupby-1-1-1",
        f"SELECT {_bin('1 minute')}, max(usage_user) AS max_usage_user "
        f"FROM cpu WHERE hostname = '{ds.hostnames[h1]}' AND "
        f"{_window(lo, hi)} GROUP BY t",
        oracle_max_by_bucket(ds, FIELDS[:1], [h1], lo, hi, 60 * NS),
        keys=(int,)))
    h8 = rng.choice(ds.hosts, size=min(8, ds.hosts), replace=False).tolist()
    lo, hi = window(8 * 3600)
    qs.append(Query(
        "cpu-max-all-8",
        f"SELECT {_bin('1 hour')}, "
        + ", ".join(f"max({f}) AS max_{f}" for f in FIELDS)
        + f" FROM cpu WHERE {_in_hosts(ds, h8)} AND {_window(lo, hi)} "
        "GROUP BY t",
        oracle_max_by_bucket(ds, FIELDS, h8, lo, hi, 3600 * NS),
        keys=(int,)))
    lo, hi = window(12 * 3600)
    qs.append(Query(
        "double-groupby-1",
        f"SELECT {_bin('1 hour')}, hostname, avg(usage_user) AS "
        f"mean_usage_user FROM cpu WHERE {_window(lo, hi)} "
        "GROUP BY t, hostname",
        oracle_avg_by_bucket_host(ds, FIELDS[:1], lo, hi, 3600 * NS),
        keys=(int, str), exact=False, fused=True))
    lo, hi = window(12 * 3600)
    qs.append(Query(
        "double-groupby-all",
        f"SELECT {_bin('1 hour')}, hostname, "
        + ", ".join(f"avg({f}) AS mean_{f}" for f in FIELDS)
        + f" FROM cpu WHERE {_window(lo, hi)} GROUP BY t, hostname",
        oracle_avg_by_bucket_host(ds, FIELDS, lo, hi, 3600 * NS),
        keys=(int, str), exact=False, fused=True))
    lo, hi = window(12 * 3600)
    qs.append(Query(
        "high-cpu-all",
        "SELECT time, hostname, " + ", ".join(FIELDS)
        + f" FROM cpu WHERE usage_user > 90.0 AND {_window(lo, hi)}",
        oracle_high_cpu(ds, lo, hi, 90.0), keys=(int, str)))
    # lastpoint carries no parameter in TSBS; the warm passes move an upper
    # time bound instead. The time column is left out: an aggregate over
    # `time` declines the device path (tpu_exec._device_eligible).
    hi_ns = None if variant == 0 else \
        int(ds.ts[-1]) - int(rng.integers(0, max(1, ds.steps // 2))) \
        * STEP_S * NS
    qs.append(Query(
        "lastpoint",
        "SELECT hostname, " + ", ".join(f"last({f}) AS {f}" for f in FIELDS)
        + " FROM cpu" + (f" WHERE time < {hi_ns}" if hi_ns else "")
        + " GROUP BY hostname",
        oracle_lastpoint(ds, hi_ns), keys=(str,), fused=True))
    return qs


def parse_answer(q: Query, text: str) -> dict:
    rows = list(csv.reader(io.StringIO(text)))
    if not rows:
        raise Fail(f"{q.name}: empty response")
    out = {}
    for r in rows[1:]:
        key = tuple(t(c) for t, c in zip(q.keys, r))
        if key in out:
            raise Fail(f"{q.name}: key {key} answered twice")
        out[key] = r[len(q.keys):]
    return out


def compare(q: Query, got: dict) -> None:
    if set(got) != set(q.want):
        missing = sorted(set(q.want) - set(got))[:3]
        extra = sorted(set(got) - set(q.want))[:3]
        raise Fail(f"{q.name}: {len(got)} answer rows, oracle has "
                   f"{len(q.want)}; missing {missing} extra {extra}")
    for key, want in q.want.items():
        cells = got[key]
        if len(cells) != len(want):
            raise Fail(f"{q.name}: row {key} has {len(cells)} values, "
                       f"oracle {len(want)}")
        for c, w in zip(cells, want):
            if q.exact:
                if int(c) != int(w):
                    raise Fail(f"{q.name}: row {key}: got {c}, oracle {w}")
            elif abs(float(c) - float(w)) > 1e-9 * max(1.0, abs(float(w))):
                raise Fail(f"{q.name}: row {key}: got {c}, oracle {w}")


# -------------------------------------------------------------- the server
class Server:
    """`python -m cnosdb_tpu.server.main run` as a child, from the repo
    root (the package is not pip-installed)."""

    def __init__(self, data_dir: str, log_path: str, env: dict):
        self.data_dir, self.log_path, self.env = data_dir, log_path, env
        self.port = free_port()
        self.base = f"http://127.0.0.1:{self.port}"
        self.proc: subprocess.Popen | None = None
        self._log = None

    def start(self, timeout: float = 300.0) -> float:
        t0 = time.monotonic()
        self._log = open(self.log_path, "ab")
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "cnosdb_tpu.server.main", "run",
             "--data-dir", self.data_dir, "--http-port", str(self.port)],
            cwd=ROOT, env=self.env, stdout=self._log,
            stderr=subprocess.STDOUT, start_new_session=True,
            # SIGINT is the server's clean stop; a parent started with it
            # ignored (a background job) would hand that on to the child
            preexec_fn=lambda: signal.signal(signal.SIGINT, signal.SIG_DFL))
        while True:
            self.check_alive()
            try:
                with urllib.request.urlopen(self.base + "/api/v1/ping",
                                            timeout=2) as r:
                    if r.status == 200:
                        return time.monotonic() - t0
            except (urllib.error.URLError, OSError):
                pass
            if time.monotonic() - t0 > timeout:
                raise Fail(f"server not ready after {timeout:.0f}s: "
                           + self.log_tail())
            time.sleep(0.2)

    def log_tail(self, n: int = 2000) -> str:
        try:
            with open(self.log_path, "rb") as f:
                return f.read()[-n:].decode("utf-8", "replace")
        except OSError:
            return ""

    def check_alive(self) -> None:
        if self.proc is not None and self.proc.poll() is not None:
            raise Fail(f"server exited early (rc={self.proc.returncode}): "
                       + self.log_tail())

    def request(self, method: str, path: str, body: bytes | None = None,
                headers: dict | None = None, timeout: float = 1200.0):
        """→ (status, headers, body bytes). HTTP error statuses return;
        a transport failure is a failed request."""
        req = urllib.request.Request(self.base + path, data=body,
                                     method=method, headers=headers or {})
        try:
            with urllib.request.urlopen(req, timeout=timeout) as r:
                return r.status, r.headers, r.read()
        except urllib.error.HTTPError as e:
            return e.code, e.headers, e.read()
        except (urllib.error.URLError, OSError) as e:
            self.check_alive()
            raise Fail(f"{method} {path}: {e!r}")

    def sql(self, db: str, sql: str) -> tuple[str, dict, float]:
        """→ (CSV text, the query's full profile, client-side ms)."""
        t0 = time.perf_counter()
        status, headers, body = self.request(
            "POST", f"/api/v1/sql?db={db}", sql.encode(),
            {"X-CnosDB-Profile": "1", "Accept": "application/csv",
             "X-CnosDB-Deadline-Ms": str(QUERY_DEADLINE_MS)})
        ms = (time.perf_counter() - t0) * 1e3
        if status != 200:
            raise Fail(f"sql failed ({status}): {body[:400]!r} :: {sql[:200]}")
        summary = json.loads(headers.get("X-CnosDB-Profile-Summary") or "{}")
        qid = summary.get("qid")
        if qid is None:
            raise Fail(f"no profile summary for: {sql[:120]}")
        status, _h, pbody = self.request("GET", f"/debug/profile?qid={qid}")
        if status != 200:
            raise Fail(f"/debug/profile?qid={qid} → {status}")
        return body.decode(), json.loads(pbody), ms

    def write(self, db: str, body: bytes) -> int:
        """One acknowledged batch → retries taken. A 503 with Retry-After
        is the server's write backpressure; a client waits and resends."""
        for attempt in range(60):
            status, headers, resp = self.request(
                "POST", f"/api/v1/write?db={db}", body, timeout=300.0)
            if status == 200:
                return attempt
            retry_after = headers.get("Retry-After")
            if status != 503 or retry_after is None:
                raise Fail(f"write failed ({status}): {resp[:400]!r}")
            time.sleep(min(float(retry_after), 5.0))
        raise Fail("write: still backpressured after 60 attempts")

    def metrics(self) -> dict:
        """/metrics → {(name, (sorted label pairs)): value}."""
        status, _h, body = self.request("GET", "/metrics")
        if status != 200:
            raise Fail(f"/metrics → {status}")
        out = {}
        for line in body.decode().splitlines():
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

    def stop(self) -> None:
        """SIGINT is the server's clean shutdown; then make sure nothing
        of its process group is left."""
        if self.proc is None:
            return
        if self.proc.poll() is None:
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


def metric(m: dict, name: str, **labels) -> float:
    return m.get((name, tuple(sorted(labels.items()))), 0.0)


def labelled(m: dict, name: str) -> dict:
    return {",".join(v for _k, v in labels): int(val)
            for (n, labels), val in sorted(m.items()) if n == name}


def device_errors(m: dict) -> dict:
    return {f"{dict(l).get('area')}.{dict(l).get('kind')}": int(v)
            for (n, l), v in m.items()
            if n == "cnosdb_errors_total" and v > 0
            and dict(l).get("area") in DEVICE_ERROR_AREAS}


def check_no_device_errors(m: dict) -> None:
    errs = device_errors(m)
    if errs:
        raise Fail(f"device error counters booked: {errs}")
    n = metric(m, "cnosdb_device_decode_total", lane="host",
               reason="kernel_error")
    if n:
        raise Fail(f"{int(n)} pages booked kernel_error and were decoded "
                   "again on the host lane")


def check_decode_lanes(m: dict, device: dict, force_dec: bool,
                       pages_floor: int) -> None:
    """Forced (CNOSDB_DEVICE_DECODE=1) the device lane goes first, so it
    must have decoded pages. In auto mode on a TPU it stands behind the
    native decoder and may well decode none: there every page scanned is
    still booked once on cnosdb_device_decode_total, so the series' sum
    reaches `pages_floor` (kernel_error: check_no_device_errors)."""
    table = labelled(m, "cnosdb_device_decode_total")
    if force_dec:
        if not metric(m, "cnosdb_device_decode_total",
                      lane="device", reason="ok"):
            raise Fail("no page was decoded by the device lane "
                       f"(device_decode_engagements = 0): {table}")
    elif device["platform"] != "cpu" and sum(table.values()) < pages_floor:
        raise Fail(f"the queries scanned at least {pages_floor} pages and "
                   f"cnosdb_device_decode_total books fewer: {table}")


# ------------------------------------------------------------ small phases
def build_native() -> dict:
    """Rebuild the native library from native/*.cpp: the one on disk may
    come from another machine's CPU, and git ignores it. The Makefile
    builds to a temporary name and renames into place."""
    t0 = time.monotonic()
    p = subprocess.run(["make", "-B", "-C", os.path.join(ROOT, "native")],
                       capture_output=True, text=True, timeout=600)
    if p.returncode != 0 or not os.path.exists(NATIVE_LIB):
        raise Fail(f"native build failed (rc={p.returncode}): "
                   f"{p.stderr[-1500:]}")
    flags = [w for w in p.stdout.split() if w.startswith("-march=")]
    return {"phase": "native_build", "built": True,
            "seconds": round(time.monotonic() - t0, 1),
            "march": flags[0] if flags else None}


def cache_entries(path: str) -> int:
    try:
        return sum(1 for n in os.listdir(path) if not n.endswith("-atime"))
    except FileNotFoundError:
        return 0


def ingest(srv: Server, db: str, ds: Dataset, k0: int, k1: int) -> dict:
    t0 = time.monotonic()
    batches = retries = nbytes = 0
    for a in range(k0, k1, BATCH_STEPS):
        body = ds.lines(a, min(a + BATCH_STEPS, k1))
        retries += srv.write(db, body)
        batches += 1
        nbytes += len(body)
    dt = time.monotonic() - t0
    rows = (k1 - k0) * ds.hosts
    return {"phase": "ingest", "rows": rows, "batches": batches,
            "acknowledged": batches, "backpressure_retries": retries,
            "line_protocol_bytes": nbytes, "seconds": round(dt, 2),
            "rows_per_s": round(rows / dt, 1),
            "note": "smoke reading: one client thread formats and posts"}


def device_of(profile: dict) -> dict:
    d = profile.get("device") or {}
    return {"platform": d.get("platform"), "kind": d.get("device_kind"),
            "count": d.get("device_count")}


def read_device(srv: Server, args, state: dict) -> tuple[dict, dict]:
    """→ (device, the whole stamp). The device comes from the server: the
    stamp its query profiles carry (QueryProfile.device). Off the chip only
    a rehearsal goes on."""
    _text, prof, _ms = srv.sql("public", "SHOW DATABASES")
    device, stamp = device_of(prof), prof.get("device") or {}
    state["device"] = device
    emit({"phase": "device", **device, "telemetry": stamp})
    if device["platform"] is None:
        raise Fail(f"no device stamp in the profile: {stamp}")
    if device["platform"] != "tpu" and not args.rehearse:
        raise Fail(f"the server runs on {device['platform']!r}, not on a "
                   "tpu; nothing was measured")
    return device, stamp


def run_query(srv: Server, db: str, q: Query) -> tuple[dict, float]:
    text, prof, ms = srv.sql(db, q.sql)
    compare(q, parse_answer(q, text))
    return prof, ms


def from_page_metadata(counts: dict) -> bool:
    """The compressed-domain lane answered every page from its statistics:
    no row was decoded, so no aggregate kernel had anything to run on."""
    return bool(counts.get("compressed.pages_answered")) \
        and not counts.get("compressed.bytes_materialized")


RUN_PATH_COUNTS = ("fused_launches", "mesh.columns", "segment_runs.engaged",
                   "segment_runs.fallback")


def tally_run_path(total: dict, q: Query, prof: dict) -> None:
    """Add a query's launches and what its reductions said of their run
    path. The smoke's scans are sorted, so one reduction that counted more
    runs than its bound and took the row scatter is a fault."""
    counts = prof.get("counts") or {}
    for k in RUN_PATH_COUNTS:
        total[k] = total.get(k, 0) + counts.get(k, 0)
    if counts.get("segment_runs.fallback"):
        raise Fail(f"{q.name}: a segment reduction fell back to the row "
                   f"scatter: {stage_row(prof)}")


def stage_row(prof: dict) -> dict:
    row = {k: v for k, v in (prof.get("ms") or {}).items()}
    row.update(prof.get("counts") or {})
    return row


def count_rows(srv: Server, db: str) -> int:
    text, _prof, _ms = srv.sql(db, "SELECT count(*) FROM cpu")
    return int(text.splitlines()[1])


def server_env(args, workdir: str, extra: dict | None = None) -> dict:
    env = dict(os.environ)
    env.setdefault("PYTHONUNBUFFERED", "1")
    if args.rehearse:
        # a rehearsal leaves nothing in the checkout: its compile cache
        # goes with the work directory (the program then sets none itself)
        env.setdefault("JAX_COMPILATION_CACHE_DIR",
                       os.path.join(workdir, "jax_cache"))
        if args.chips > 1 and "xla_force_host_platform_device_count" \
                not in env.get("XLA_FLAGS", ""):
            env["XLA_FLAGS"] = (
                env.get("XLA_FLAGS", "") + " --xla_force_host_platform_"
                f"device_count={args.chips}").strip()
    if extra:
        env.update(extra)
    return env


# ---------------------------------------------------------- one-chip phases
def one_chip(args, workdir: str, state: dict) -> None:
    rng = np.random.default_rng(args.seed + 1)
    steps = args.hours_steps
    t0 = time.monotonic()
    ds = Dataset(args.seed, args.hosts, steps)
    emit({"phase": "size", "hosts": ds.hosts, "hours": steps * STEP_S / 3600,
          "rows": ds.n_rows, "field_values": ds.n_rows * len(FIELDS),
          "tags": len(TAGS), "fields": len(FIELDS), "field_type": "int64",
          "cut": args.cut, "seed": args.seed,
          "generate_seconds": round(time.monotonic() - t0, 2)})

    env = server_env(args, workdir)
    data_dir = os.path.join(workdir, "data")
    srv = state["server"] = Server(
        data_dir, os.path.join(workdir, "server.log"), env)
    emit({"phase": "server_start", "seconds": round(srv.start(), 2),
          "first_log_lines": srv.log_tail().splitlines()[:4]})

    device, stamp = read_device(srv, args, state)
    # the env var's directory or <checkout>/.jax_cache: the server says
    cache_dir = stamp.get("compile_cache_dir")
    if cache_dir is None:
        raise Fail("the server runs with the persistent compile cache off")
    entries_before = cache_entries(cache_dir)

    emit(ingest(srv, "public", ds, 0, ds.steps))
    t0 = time.monotonic()
    srv.sql("public", "FLUSH")
    emit({"phase": "flush", "seconds": round(time.monotonic() - t0, 2)})

    # ---- queries: cold once, then three warm passes with new parameters
    force_dev = env.get("CNOSDB_TPU_FORCE_DEVICE_PATH") == "1"
    force_dec = env.get("CNOSDB_DEVICE_DECODE", "auto").lower() \
        in ("1", "on", "true")
    on_device = device["platform"] != "cpu" or force_dev
    cold_queries = make_queries(ds, rng, 0)
    readings: dict = {}
    dtypes_on_device: dict = {}
    run_path: dict = {}
    for variant in range(4):
        for q in cold_queries if variant == 0 \
                else make_queries(ds, rng, variant):
            prof, ms = run_query(srv, "public", q)
            if device_of(prof) != device:
                raise Fail(f"device changed between queries: "
                           f"{device_of(prof)}")
            dtypes_on_device.update(
                (prof.get("device") or {}).get("fused_column_dtypes") or {})
            counts = prof.get("counts") or {}
            tally_run_path(run_path, q, prof)
            if q.fused and on_device and not from_page_metadata(counts):
                if not counts.get("fused_launches"):
                    raise Fail(f"{q.name} (variant {variant}): no fused "
                               f"launch in its profile: {stage_row(prof)}")
                if variant == 0 and not counts.get("upload_bytes"):
                    raise Fail(f"{q.name}: cold pass uploaded nothing: "
                               f"{stage_row(prof)}")
            r = readings.setdefault(q.name, {
                "phase": "query", "name": q.name, "matches_oracle": True,
                "note": "smoke reading, not a benchmark number",
                "warm_ms": []})
            if variant == 0:
                r.update(cold_ms=round(ms, 1), answer_rows=len(q.want),
                         cold_stages=stage_row(prof))
            else:
                r["warm_ms"].append(round(ms, 1))
                r["warm_stages"] = stage_row(prof)
    for r in readings.values():
        emit(r)

    m = srv.metrics()
    emit({"phase": "lanes",
          "device_decode": labelled(m, "cnosdb_device_decode_total"),
          "mesh": labelled(m, "cnosdb_mesh_total"),
          "decode_fallback": labelled(m, "cnosdb_decode_fallback_total"),
          "errors": labelled(m, "cnosdb_errors_total"),
          "compile_cache": labelled(m, "cnosdb_compile_cache_total"),
          "run_path": run_path,
          "column_dtypes_on_device": dtypes_on_device,
          "native_library_built": os.path.exists(NATIVE_LIB)})
    check_no_device_errors(m)
    # double-groupby-all alone reads every host's time page and ten field
    # pages in each of the four passes
    check_decode_lanes(m, device, force_dec,
                       pages_floor=4 * ds.hosts * (len(FIELDS) + 1))

    # ---- guarantee: a late batch is acknowledged and read back
    k = ds.append_step()
    late = ingest(srv, "public", ds, k, k + 1)
    n = count_rows(srv, "public")
    if n != ds.n_rows:
        raise Fail(f"late write: count(*) = {n}, acknowledged {ds.n_rows}")
    emit({"phase": "late_write", "rows": late["rows"], "acknowledged": True,
          "count_star": n, "read_back": True})

    # ---- second start on the same data: the compile cache must hit, and
    # the unflushed late batch must come back from the WAL
    m1 = srv.metrics()
    srv.stop()
    entries_mid = cache_entries(cache_dir)
    t0 = time.monotonic()
    start_s = srv.start()
    n = count_rows(srv, "public")
    if n != ds.n_rows:
        raise Fail(f"after restart: count(*) = {n}, acknowledged "
                   f"{ds.n_rows}")
    again = {}
    for q in cold_queries:     # same SQL, same shapes: the cache's case
        if q.name in ("double-groupby-1", "cpu-max-all-8"):
            _prof, ms = run_query(srv, "public", q)
            again[q.name] = round(ms, 1)
    m2 = srv.metrics()
    check_no_device_errors(m2)
    hits = int(metric(m2, "cnosdb_compile_cache_total", outcome="hit"))
    misses = int(metric(m2, "cnosdb_compile_cache_total", outcome="miss"))
    first = labelled(m1, "cnosdb_compile_cache_total")
    emit({"phase": "second_start", "start_seconds": round(start_s, 2),
          "count_star_after_restart": n,
          "first_start_cold_ms": {k_: readings[k_]["cold_ms"]
                                  for k_ in again},
          "second_start_cold_ms": again,
          "compile_cache_dir": cache_dir,
          "cache_entries": {"before": entries_before,
                            "after_first_server": entries_mid,
                            "after_second_server": cache_entries(cache_dir)},
          "first_server_compiles": first,
          "second_server_compiles": {"hit": hits, "miss": misses},
          "seconds": round(time.monotonic() - t0, 2)})
    if sum(first.values()) and hits == 0:
        raise Fail(f"second start in {cache_dir}: no compile-cache hit "
                   f"({misses} misses; the first server compiled {first})")
    srv.stop()


# --------------------------------------------------------- four-chip phase
def four_chips(args, workdir: str, state: dict) -> None:
    """The mesh lane on real devices: eight shards over the chips,
    double-groupby-1 and -all against a default server, then against a
    server with CNOSDB_MESH=0 on the same data directory."""
    rng = np.random.default_rng(args.seed + 1)
    ds = Dataset(args.seed, args.hosts, args.hours_steps)
    emit({"phase": "size", "hosts": ds.hosts,
          "hours": ds.steps * STEP_S / 3600, "rows": ds.n_rows,
          "field_values": ds.n_rows * len(FIELDS), "shards": 8,
          "cut": args.cut, "seed": args.seed})
    data_dir = os.path.join(workdir, "data")
    log = os.path.join(workdir, "server.log")
    queries = [q for q in make_queries(ds, rng, 0)
               if q.name.startswith("double-groupby")]
    device = None
    for label, extra in (("mesh", {}), ("legacy", {"CNOSDB_MESH": "0"})):
        srv = state["server"] = Server(
            data_dir, log, server_env(args, workdir, extra))
        start_s = srv.start()
        if label == "mesh":
            device, _stamp = read_device(srv, args, state)
            srv.sql("public", "CREATE DATABASE smoke WITH SHARD 8")
            emit(ingest(srv, "smoke", ds, 0, ds.steps))
            srv.sql("smoke", "FLUSH")
        shards = 0
        readings = {}
        run_path: dict = {}
        for q in queries:
            prof, ms = run_query(srv, "smoke", q)
            tally_run_path(run_path, q, prof)
            shards = max(shards, (prof.get("counts") or {})
                         .get("mesh.shards", 0))
            readings[q.name] = {"ms": round(ms, 1), "stages": stage_row(prof)}
        m = srv.metrics()
        mesh_table = labelled(m, "cnosdb_mesh_total")
        emit({"phase": "four_chips", "server": label, "device": device,
              "start_seconds": round(start_s, 2), "queries": readings,
              "matches_oracle": True, "mesh": mesh_table,
              "mesh_shards": shards, "run_path": run_path,
              "errors": labelled(m, "cnosdb_errors_total"),
              "note": "smoke reading, not a benchmark number"})
        check_no_device_errors(m)
        engaged = metric(m, "cnosdb_mesh_total", lane="exec",
                         reason="engaged")
        if label == "mesh":
            collective = metric(m, "cnosdb_mesh_total", lane="merge",
                                reason="collective")
            bad = {r: metric(m, "cnosdb_mesh_total", lane="exec", reason=r)
                   for r in ("plan_error", "device_loss")}
            if not engaged or collective != engaged or any(bad.values()):
                raise Fail(f"mesh lane did not carry the queries: "
                           f"{mesh_table}")
            want = device["count"] if args.rehearse else 4
            if shards != want:
                raise Fail(f"mesh.shards = {shards}, expected {want}")
        elif engaged:
            raise Fail(f"CNOSDB_MESH=0 server engaged the mesh: {mesh_table}")
        srv.stop()


# --------------------------------------------------------------------- main
def parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--seed", type=int, default=123)
    p.add_argument("--hosts", type=int, default=None,
                   help="rehearsal only: the chip run never cuts hosts")
    p.add_argument("--hours", type=float, default=None,
                   help="hours of data (the only size that may be cut)")
    p.add_argument("--rehearse", action="store_true",
                   help="toy size on whatever backend there is; ok stays "
                        "false off the chip")
    p.add_argument("--chips", type=int, default=1, choices=(1, 4),
                   help="4: run the mesh phase across four chips, and "
                        "nothing else")
    p.add_argument("--keep", action="store_true",
                   help="keep the work directory (data, server log)")
    args = p.parse_args(argv)
    if args.hosts and not args.rehearse:
        p.error("--hosts is for --rehearse; cut --hours, never hosts")
    full_hosts, full_hours = 1000, 6.0
    if args.rehearse:
        # the mesh lane declines under 65536 rows, so its toy is larger
        full_hosts, full_hours = (300, 1.0) if args.chips > 1 \
            else (20, 1.0 / 6)
    hosts = args.hosts or full_hosts
    hours = args.hours or full_hours
    args.cut = None
    if not args.rehearse and hours != full_hours:
        args.cut = {"hours": [full_hours, hours]}
    args.hosts = hosts
    args.hours_steps = max(2, int(round(hours * 3600 / STEP_S)))
    return args


def main(argv=None) -> int:
    args = parse_args(argv)
    if not os.path.isdir(os.path.join(ROOT, "cnosdb_tpu")):
        print("chip_smoke.py: no cnosdb_tpu package beside this script",
              file=sys.stderr)
        return 2
    emit({"phase": "start", "mode": "rehearse" if args.rehearse else "chip",
          "chips": args.chips, "jax_in_parent": "jax" in sys.modules,
          "note": "readings below are a smoke's, not benchmark numbers"})
    workdir = tempfile.mkdtemp(prefix="chip_smoke_")
    state: dict = {"server": None, "device": None}
    error = None
    try:
        if not args.rehearse:
            # not under --rehearse: the tier-1 test runs beside workers
            # that have the library open or are about to load it
            emit(build_native())
        (four_chips if args.chips > 1 else one_chip)(args, workdir, state)
        emit({"phases_passed": True, "jax_in_parent": "jax" in sys.modules})
    except Fail as e:
        error = str(e)
    except Exception:   # a fault of the script itself fails the run too
        error = traceback.format_exc()
    finally:
        if state["server"] is not None:
            state["server"].stop()
        if args.keep:
            print(f"chip_smoke.py: work directory kept: {workdir}",
                  file=sys.stderr)
        else:
            shutil.rmtree(workdir, ignore_errors=True)
    device = state["device"] or {"platform": None, "kind": None,
                                 "count": None}
    if error is None and device["platform"] != "tpu":
        error = f"the server ran on {device['platform']!r}, not on a tpu"
    if error is None and device["count"] != args.chips:
        error = f"{device['count']} devices, expected {args.chips}"
    if error is not None:
        emit({"ok": False, "error": error[-3000:], "device": device})
        return 1
    emit({"ok": True, "device": device})
    return 0


if __name__ == "__main__":
    sys.exit(main())
