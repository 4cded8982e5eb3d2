"""TSBS devops cpu-only: the data, the query classes and their plain
numpy reference.

Copied from `chip_smoke.py` (proven on the chip in PR 22) and made
general over a class's parameters, so that a traffic file can express
every query class of `cmd/tsbs_generate_queries` devops. Nothing here
imports JAX or `cnosdb_tpu`: the reference shares no code with the
system under test.

Data: the `cpu` measurement — 10 tags, 10 `usage_*` fields written as
the TSBS influx serializer emits them (`58i`, so the engine types them
BIGINT), one point per host per 10 s, ten clamped random walks per host
(start uniform 0..100, N(0,1) steps, clamped to [0, 100]), all from the
seed.
"""
from __future__ import annotations

import csv
import dataclasses
import io

import numpy as np

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


class Mismatch(Exception):
    """An answer differs from the reference."""


# ---------------------------------------------------------------- the data
class Dataset:
    """`hosts` series over `steps` timestamps; `loaded_steps` of them are
    written before the window, the rest (`extend`) by a cell's writers."""

    def __init__(self, seed: int, hosts: int, steps: int):
        rng = np.random.default_rng(seed)
        self.hosts = hosts
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
        self.v = self._walk(steps)
        self.loaded_steps = steps
        # (class, window, hosts) of every request drawn in this run, by any
        # client in any phase: a draw that repeats one is drawn again
        self.drawn: set = set()
        self._prefix = [
            "cpu," + ",".join(f"{k}={t[k]}" for k in TAGS) + " "
            for t in self.tags]
        self._fmt = "%s" + ",".join(f"{f}=%di" for f in FIELDS) + " %d"

    def _walk(self, steps: int) -> np.ndarray:
        """`steps` more points of every walk, in bulk: the clamp makes the
        walk sequential in time, so the loop is over steps only."""
        out = np.empty(self._state.shape + (steps,), dtype=np.int64)
        state = self._state
        for k0 in range(0, steps, 64):         # noise in reused chunks
            noise = self._rng.standard_normal(
                (min(64, steps - k0),) + state.shape, dtype=np.float32)
            for i in range(len(noise)):
                state = np.clip(state + noise[i], 0.0, 100.0)
                out[:, :, k0 + i] = state
        self._state = state
        return out

    def extend(self, steps: int) -> None:
        """More timestamps for every host, past the loaded range."""
        self.v = np.concatenate([self.v, self._walk(steps)], axis=2)

    @property
    def steps(self) -> int:
        return self.v.shape[2]

    @property
    def ts(self) -> np.ndarray:
        return (T0_S + STEP_S * np.arange(self.steps, dtype=np.int64)) * NS

    def step_ns(self, k: int) -> int:
        return (T0_S + STEP_S * k) * NS

    def lines(self, k0: int, k1: int) -> bytes:
        """Line protocol for steps [k0, k1), time-major like tsbs_load."""
        slab = self.v[:, :, k0:k1].transpose(2, 1, 0).tolist()   # [k][h][f]
        fmt, prefix = self._fmt, self._prefix
        out = []
        for i, per_host in enumerate(slab):
            ts = self.step_ns(k0 + i)
            out.extend(fmt % (prefix[h], *row, ts)
                       for h, row in enumerate(per_host))
        return "\n".join(out).encode()

    def k_range(self, lo_ns: int, hi_ns: int) -> tuple[int, int]:
        """Step indices with lo <= ts < hi."""
        def first_at_or_after(ns: int) -> int:
            k = -((T0_S * NS - ns) // (STEP_S * NS))          # ceil
            return min(max(k, 0), self.steps)
        return first_at_or_after(lo_ns), first_at_or_after(hi_ns)


# ----------------------------------------------------------- the requests
@dataclasses.dataclass
class Request:
    """One query as sent: its class, its SQL, and the drawn parameters the
    reference needs to answer it."""

    cls: str
    sql: str
    spec: dict


_REDUCE = {"max": np.maximum, "min": np.minimum, "sum": np.add,
           "avg": np.add, "count": None}


class ClassGenerator:
    """Draws requests of one query class. The class is data (an entry of a
    traffic file's `classes`):

      select      "aggregate" | "rows" | "lastpoint"
      aggregate   max | min | sum | avg | count            (aggregate)
      fields      how many of the ten fields, TSBS order     (aggregate)
      hosts       hosts named in the predicate; 0 = no host predicate
      window_s    TSBS's window; cut to 5/6 of the loaded span if longer;
                  null = from the start of the data to a drawn end
      bucket_s    date_bin interval                          (aggregate)
      by_host     GROUP BY hostname as well                  (aggregate)
      last_buckets  ORDER BY t DESC LIMIT n                  (aggregate)
      threshold   usage_user > threshold                     (rows)
      repeat_window  draw the window once per run and ask it again
      random_fields  draw which fields (else the first `fields`)

    Hosts and windows are drawn anew for every request, so the result
    cache and the aggregate memo cannot answer in place of the scan.
    """

    def __init__(self, cls: dict, ds: Dataset, rng: np.random.Generator):
        self.c, self.ds, self.rng = cls, ds, rng
        self.name = cls["name"]
        self.select = cls["select"]
        if self.select not in ("aggregate", "rows", "lastpoint"):
            raise ValueError(f"class {self.name}: unknown select "
                             f"{self.select!r}")
        if self.select == "aggregate" and cls["aggregate"] not in _REDUCE:
            raise ValueError(f"class {self.name}: unknown aggregate "
                             f"{cls['aggregate']!r}")
        self._fixed_window = None

    # ---- draws
    def _window(self) -> tuple[int, int]:
        if self.c.get("repeat_window") and self._fixed_window:
            return self._fixed_window
        span_s = (self.ds.loaded_steps - 1) * STEP_S
        want = self.c.get("window_s")
        if want is None:
            # from the first point to a drawn end in the later half
            hi = T0_S + span_s - int(self.rng.integers(0, max(1, span_s // 2)))
            win = (T0_S * NS, hi * NS)
        else:
            w = min(int(want), max(STEP_S, span_s * 5 // 6))
            start = T0_S + int(self.rng.integers(0, max(1, span_s - w)))
            win = (start * NS, (start + w) * NS)
        if self.c.get("repeat_window"):
            self._fixed_window = win
        return win

    def _hosts(self) -> list[int]:
        n = min(int(self.c.get("hosts") or 0), self.ds.hosts)
        if n == 0:
            return []
        return sorted(self.rng.choice(self.ds.hosts, size=n,
                                      replace=False).tolist())

    def _fields(self) -> list[str]:
        n = int(self.c.get("fields") or len(FIELDS))
        if self.c.get("random_fields"):
            idx = sorted(self.rng.choice(len(FIELDS), size=n,
                                         replace=False).tolist())
            return [FIELDS[i] for i in idx]
        return FIELDS[:n]

    # ---- SQL
    def _host_pred(self, hosts: list[int]) -> str:
        names = [f"'{self.ds.hostnames[h]}'" for h in hosts]
        if not names:
            return ""
        if len(names) == 1:
            return f"hostname = {names[0]} AND "
        return "hostname IN (" + ", ".join(names) + ") AND "

    def _draw_new(self) -> tuple[int, int, list[int]]:
        """A window and hosts no request of this run has had: an exact
        repeat would be answered by the result cache. (`repeat_window`
        asks for the repeat.)"""
        for _ in range(50):
            lo, hi = self._window()
            hosts = self._hosts()
            key = (self.name, lo, hi, tuple(hosts))
            if self.c.get("repeat_window") or key not in self.ds.drawn:
                break
        self.ds.drawn.add(key)
        return lo, hi, hosts

    def draw(self) -> Request:
        lo, hi, hosts = self._draw_new()
        window = f"time >= {lo} AND time < {hi}"
        if self.select == "aggregate":
            agg, fields = self.c["aggregate"], self._fields()
            bucket_s = int(self.c["bucket_s"])
            by_host = bool(self.c.get("by_host"))
            cols = ", ".join(f"{agg}({f}) AS {agg}_{f}" for f in fields)
            sql = (f"SELECT date_bin(INTERVAL '{bucket_s} seconds', time) "
                   f"AS t, " + ("hostname, " if by_host else "") + cols
                   + f" FROM cpu WHERE {self._host_pred(hosts)}{window} "
                   "GROUP BY t" + (", hostname" if by_host else ""))
            last = self.c.get("last_buckets")
            if last:
                sql += f" ORDER BY t DESC LIMIT {int(last)}"
            spec = {"select": "aggregate", "aggregate": agg,
                    "fields": fields, "hosts": hosts, "lo": lo, "hi": hi,
                    "bucket_ns": bucket_s * NS, "by_host": by_host,
                    "last_buckets": last}
        elif self.select == "rows":
            thr = float(self.c["threshold"])
            sql = ("SELECT time, hostname, " + ", ".join(FIELDS)
                   + f" FROM cpu WHERE usage_user > {thr} AND "
                   f"{self._host_pred(hosts)}{window}")
            spec = {"select": "rows", "threshold": thr, "hosts": hosts,
                    "lo": lo, "hi": hi}
        else:
            # the time column is left out: an aggregate over `time`
            # declines the device path (tpu_exec._device_eligible)
            sql = ("SELECT hostname, "
                   + ", ".join(f"last({f}) AS {f}" for f in FIELDS)
                   + f" FROM cpu WHERE time < {hi} GROUP BY hostname")
            spec = {"select": "lastpoint", "hi": hi}
        return Request(self.name, sql, spec)


# -------------------------------------------------------------- the oracle
def _bucket_runs(ts: np.ndarray, interval_ns: int):
    """ts ascending → (bucket start per run, run start offsets): date_bin
    buckets are multiples of the interval since the epoch."""
    b = ts // interval_ns
    starts = np.flatnonzero(np.concatenate(([True], b[1:] != b[:-1])))
    return b[starts] * interval_ns, starts


def _oracle_aggregate(ds: Dataset, s: dict) -> tuple[dict, tuple, bool]:
    k0, k1 = ds.k_range(s["lo"], s["hi"])
    hosts = np.asarray(s["hosts"] or np.arange(ds.hosts), dtype=np.int64)
    if k1 <= k0:
        return {}, (int, str) if s["by_host"] else (int,), True
    bstart, starts = _bucket_runs(ds.ts[k0:k1], s["bucket_ns"])
    n = np.diff(np.append(starts, k1 - k0))                   # rows / bucket
    agg = s["aggregate"]
    cols = []
    for f in s["fields"]:
        block = ds.v[FIELDS.index(f)][hosts, k0:k1]           # [hosts, steps]
        if agg == "count":
            per = np.broadcast_to(n, (len(hosts), len(n)))
        else:
            per = _REDUCE[agg].reduceat(block, starts, axis=1)
        if s["by_host"]:
            # the exact integer sum over the exact count, one division
            cols.append(per / n[None, :] if agg == "avg" else per)
        elif agg in ("max", "min"):
            cols.append(_REDUCE[agg].reduce(per, axis=0))
        elif agg == "avg":
            cols.append(per.sum(axis=0) / (n * len(hosts)))
        else:
            cols.append(per.sum(axis=0))
    buckets = range(len(bstart))
    if s.get("last_buckets"):
        buckets = buckets[-int(s["last_buckets"]):]
    if s["by_host"]:
        want = {(int(bstart[i]), ds.hostnames[h]): [c[j, i] for c in cols]
                for i in buckets for j, h in enumerate(hosts.tolist())}
        keys = (int, str)
    else:
        want = {(int(bstart[i]),): [c[i] for c in cols] for i in buckets}
        keys = (int,)
    return want, keys, agg != "avg"


def _oracle_rows(ds: Dataset, s: dict) -> tuple[dict, tuple, bool]:
    k0, k1 = ds.k_range(s["lo"], s["hi"])
    hosts = np.asarray(s["hosts"] or np.arange(ds.hosts), dtype=np.int64)
    hh, kk = np.nonzero(ds.v[0][hosts, k0:k1] > s["threshold"])
    vals = ds.v[:, hosts[hh], kk + k0]                         # [F, n]
    want = {(ds.step_ns(k + k0), ds.hostnames[hosts[h]]): vals[:, i].tolist()
            for i, (h, k) in enumerate(zip(hh.tolist(), kk.tolist()))}
    return want, (int, str), True


def _oracle_lastpoint(ds: Dataset, s: dict) -> tuple[dict, tuple, bool]:
    k1 = ds.k_range(0, s["hi"])[1]
    if k1 == 0:
        return {}, (str,), True
    return ({(ds.hostnames[h],): ds.v[:, h, k1 - 1].tolist()
             for h in range(ds.hosts)}, (str,), True)


_ORACLES = {"aggregate": _oracle_aggregate, "rows": _oracle_rows,
            "lastpoint": _oracle_lastpoint}


def check_answer(ds: Dataset, req: Request, text: str) -> None:
    """Compare one CSV answer with the reference, under the
    configuration's guarantees: the same set of rows; count, min, max,
    last and integer sums equal; averages to 1e-9. Raises Mismatch."""
    want, keys, exact = _ORACLES[req.spec["select"]](ds, req.spec)
    rows = list(csv.reader(io.StringIO(text)))
    if not rows:
        raise Mismatch(f"{req.cls}: empty response")
    got = {}
    for r in rows[1:]:
        key = tuple(t(c) for t, c in zip(keys, r))
        if key in got:
            raise Mismatch(f"{req.cls}: key {key} answered twice")
        got[key] = r[len(keys):]
    if set(got) != set(want):
        missing = sorted(set(want) - set(got))[:3]
        extra = sorted(set(got) - set(want))[:3]
        raise Mismatch(f"{req.cls}: {len(got)} answer rows, reference has "
                       f"{len(want)}; missing {missing} extra {extra}")
    for key, w_row in want.items():
        cells = got[key]
        if len(cells) != len(w_row):
            raise Mismatch(f"{req.cls}: row {key} has {len(cells)} values, "
                           f"reference {len(w_row)}")
        for c, w in zip(cells, w_row):
            if exact:
                if int(c) != int(w):
                    raise Mismatch(f"{req.cls}: row {key}: got {c}, "
                                   f"reference {w}")
            elif abs(float(c) - float(w)) > 1e-9 * max(1.0, abs(float(w))):
                raise Mismatch(f"{req.cls}: row {key}: got {c}, "
                               f"reference {w}")
