"""The fleet status board, end to end, against references that share no
code with the program: the deployment of `tsbs-devops-cpu-1k-lastpoint`
(the serving plane on, as its configuration leaves it), TSBS devops rows
through the served path (line protocol over /api/v1/write, FLUSH, SQL over
HTTP) and the class of `benchmarks/traffic/lastpoint.json` — the last
value of all ten fields for every host below a bound — on the fused lane.

What the cell `devops-lastpoint` rests on: every answer is each host's
newest value below the bound, a NULL skipped, wherever the bound cuts a
page and wherever the newest rows lie (flushed or not); the rank is
sorted where a first/last query first asks, once a batch; and the two
counts its metrics read say what ran.
"""
import csv
import io
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmarks.lib import devops                          # noqa: E402
from test_refresh_same_window import _start                # noqa: E402

SEED = 2147483659          # a --seed as the driver draws them: past 2**31
HOSTS, STEPS = 100, 1080   # 3 h; a drawn bound reads 2.5-3 h = 90-108 k rows
F = len(devops.FIELDS)


def _class(traffic: str) -> dict:
    with open(os.path.join(ROOT, "benchmarks", "traffic",
                           traffic + ".json")) as f:
        return json.load(f)["classes"][0]


LASTPOINT = _class("lastpoint")
GROUPBY = _class("fleet-groupby")                 # TSBS double-groupby-1
with open(os.path.join(ROOT, "benchmarks", "configs",
                       "tsbs-devops-cpu-1k-lastpoint.json")) as f:
    SERVER_ENV = json.load(f)["server_env"]
# the configuration's server_env, and the fused lane on this backend
_ENV = {**SERVER_ENV, "CNOSDB_TPU_FORCE_DEVICE_PATH": "1"}


def _sql(hi: int) -> str:
    return ("SELECT hostname, "
            + ", ".join(f"last({f}) AS {f}" for f in devops.FIELDS)
            + f" FROM cpu WHERE time < {hi} GROUP BY hostname")


def _first_sql(lo: int) -> str:
    return ("SELECT hostname, "
            + ", ".join(f"first({f}) AS {f}" for f in devops.FIELDS)
            + f" FROM cpu WHERE time >= {lo} GROUP BY hostname")


def _profiled(node, sql: str) -> tuple[str, dict]:
    text, hdrs = node.sql(sql, {"X-CnosDB-Profile": "1"})
    return text, json.loads(hdrs["X-CnosDB-Profile-Summary"])["stages"]


class _Store:
    """What was written, as plain arrays: values and presence of every
    field of every host at every step (a step a host has no row at is
    absent in all ten)."""

    def __init__(self, ds, extra_steps: int):
        k = ds.steps + extra_steps
        self.ds = ds
        self.vals = np.zeros((F, ds.hosts, k), dtype=np.int64)
        self.vals[:, :, :ds.steps] = ds.v
        self.valid = np.zeros((F, ds.hosts, k), dtype=bool)
        self.valid[:, :, :ds.steps] = True
        self.ts = np.array([ds.step_ns(i) for i in range(k)])

    def write(self, node, step: int, host: int, fields: dict) -> None:
        tags = ",".join(f"{t}={self.ds.tags[host][t]}" for t in devops.TAGS)
        line = (f"cpu,{tags} " + ",".join(f"{f}={v}i" for f, v in
                                          fields.items())
                + f" {self.ts[step]}")
        status, body, _ = node.h.request("POST", "/api/v1/write?db=public",
                                         line)
        assert status == 200, body
        for f, v in fields.items():
            i = devops.FIELDS.index(f)
            self.vals[i, host, step], self.valid[i, host, step] = v, True

    def last(self, hi: int) -> dict:
        """hostname → each field's newest non-NULL value below `hi`: a
        host's rows read newest first, one field at a time."""
        return self._pick(0, int(np.searchsorted(self.ts, hi)), -1)

    def first(self, lo: int) -> dict:
        """hostname → each field's oldest non-NULL value at or after
        `lo`."""
        return self._pick(int(np.searchsorted(self.ts, lo)), len(self.ts), 0)

    def _pick(self, k0: int, k1: int, at: int) -> dict:
        out = {}
        for h in range(self.ds.hosts):
            row = []
            for f in range(F):
                present = k0 + np.flatnonzero(self.valid[f, h, k0:k1])
                row.append(int(self.vals[f, h, present[at]])
                           if len(present) else None)
            if any(v is not None for v in row):
                out[self.ds.hostnames[h]] = row
        return out


def _answer(text: str) -> dict:
    rows = list(csv.reader(io.StringIO(text)))
    assert rows[0] == ["hostname"] + devops.FIELDS, rows[0]
    return {r[0]: [int(c) for c in r[1:]] for r in rows[1:]}


@pytest.fixture(scope="module")
def node(tmp_path_factory):
    assert "CNOSDB_SERVING" not in SERVER_ENV            # the plane is on
    h, mp, n = _start(_ENV, tmp_path_factory, "lastpoint",
                      devops.Dataset(SEED, HOSTS, STEPS))
    try:
        yield n
    finally:
        h.close()
        mp.undo()


# the cell's layout: the load fills the memcache past a flush, so each
# host has an older chunk wholly below every drawn bound and a newer one
# the bound cuts
TWO_HOSTS, TWO_STEPS, TWO_FLUSH = 40, 720, 420


@pytest.fixture(scope="module")
def two_chunks(tmp_path_factory):
    h, mp, n = _start(_ENV, tmp_path_factory, "lastpoint_two_chunks",
                      devops.Dataset(SEED + 2, TWO_HOSTS, TWO_STEPS),
                      flush_at=TWO_FLUSH)
    try:
        yield n
    finally:
        h.close()
        mp.undo()


def test_an_outranked_chunk_is_never_read(two_chunks):
    """With two chunks a host, each drawn bound cuts the newer one, whose
    first row lies below the bound with all ten fields: it outranks the
    older chunk's page in every field. That page leaves the plan unread —
    `compressed.jobs_dominated` hosts × 10, no page answered, no partial
    to merge — and each answer is still the store's."""
    node = two_chunks
    gen = devops.ClassGenerator(LASTPOINT, node.ds,
                                np.random.default_rng([SEED, 5]))
    store = _Store(node.ds, 0)
    for _ in range(3):
        req = gen.draw()
        hi = req.spec["hi"]
        assert node.ds.step_ns(TWO_FLUSH) < hi
        st = node.ask(req)
        assert _answer(st["answer"]) == store.last(hi)
        assert st["compressed.jobs_dominated"] == TWO_HOSTS * F, st
        assert st.get("compressed.pages_answered", 0) == 0, st
        assert "merge_ms" not in st and st["fused.rank_columns"] == F

    # first() under a lower bound that cuts the older chunk: its last
    # row outranks the newer chunk's page in every field
    lo = node.ds.step_ns(TWO_FLUSH // 2) + 1
    text, st = _profiled(node, _first_sql(lo))
    rows = list(csv.reader(io.StringIO(text)))
    got = {r[0]: [int(c) for c in r[1:]] for r in rows[1:]}
    assert got == store.first(lo)
    assert st["compressed.jobs_dominated"] == TWO_HOSTS * F, st
    assert st.get("compressed.pages_answered", 0) == 0, st


def test_a_null_in_the_cut_page_proves_nothing_for_its_field(two_chunks):
    """A host's newest rows, flushed into a third chunk the bound cuts:
    its first row lacks usage_idle. That page proves the other nine
    fields' newer values, so the second chunk's page answers usage_idle
    alone — the value of its last row — and every other host's older
    chunk is outranked by its second as before."""
    node = two_chunks
    store = _Store(node.ds, 2)
    k = TWO_STEPS
    partial = {f: 50 + i for i, f in enumerate(devops.FIELDS)
               if f != "usage_idle"}
    store.write(node, k, 3, partial)
    store.write(node, k + 1, 3, {f: 2 for f in devops.FIELDS})
    node.sql("FLUSH")
    hi = int(store.ts[k]) + 1
    text, st = _profiled(node, _sql(hi))
    want = store.last(hi)
    idle = devops.FIELDS.index("usage_idle")
    assert want["host_3"][idle] == node.ds.v[idle, 3, k - 1]
    assert _answer(text) == want
    # every host's first chunk (all ten fields), host 3's second (nine)
    assert st["compressed.jobs_dominated"] == TWO_HOSTS * F + F - 1, st
    assert st["compressed.pages_answered"] == TWO_HOSTS, st


def test_the_status_board_is_the_references(node):
    """The cell's class, drawn as the benchmark draws it: a new bound in
    the last sixth of the loaded span a request, all ten fields, every
    host. Each answer is `devops.check_answer`'s (the numpy last a host)
    and the store's, and took one fused program that selects all ten
    columns by rank."""
    gen = devops.ClassGenerator(LASTPOINT, node.ds,
                                np.random.default_rng([SEED, 1]))
    store = _Store(node.ds, 0)
    span = (STEPS - 1) * devops.STEP_S * devops.NS
    bounds = set()
    for _ in range(6):
        req = gen.draw()
        st = node.ask(req)
        hi = req.spec["hi"]
        assert span * 5 // 6 <= hi - node.ds.step_ns(0) < span
        assert _answer(st["answer"]) == store.last(hi)
        assert len(_answer(st["answer"])) == HOSTS
        assert st["scan_miss"] == 1 and st["fused_launches"] == 1, st
        assert st["fused.rank_columns"] == F
        assert st["upload.rank_builds"] == 1
        bounds.add(hi)
    assert len(bounds) == 6


def test_a_null_a_cut_page_and_unflushed_rows(node):
    """Held to the store's plain last: (1) a bound inside every series'
    page; (2) a host whose newest row below the bound lacks one field —
    `last()` returns that field's newest non-NULL value, from the row
    before — in a flushed page the bound cuts (the other hosts' pages lie
    wholly below it, and the compressed-domain lane answers those from
    their statistics); (3) hosts with unflushed rows newer than their
    flushed ones, which the scan reads and merges a series at a time (the
    merged-series route). Each took the fused program."""
    store = _Store(node.ds, 3)
    k = STEPS
    hi = node.ds.step_ns(517) + 3 * devops.NS             # (1)
    text, st = _profiled(node, _sql(hi))
    assert _answer(text) == store.last(hi)
    assert st["fused.rank_columns"] == F

    partial = {f: 100 - i for i, f in enumerate(devops.FIELDS)
               if f != "usage_idle"}                      # (2)
    store.write(node, k, 3, partial)
    store.write(node, k + 1, 3, {f: 1 for f in devops.FIELDS})
    node.sql("FLUSH")
    hi = int(store.ts[k]) + 1
    text, st = _profiled(node, _sql(hi))
    want = store.last(hi)
    idle = devops.FIELDS.index("usage_idle")
    assert want["host_3"][idle] == node.ds.v[idle, 3, k - 1]
    assert want["host_3"][:idle] == list(partial.values())[:idle]
    assert _answer(text) == want
    assert st["fused_launches"] == 1 and st["fused.rank_columns"] == F

    for h in (5, 7):                                      # (3)
        store.write(node, k + 2, h, {f: 7 * h + i for i, f in
                                     enumerate(devops.FIELDS)})
    hi = int(store.ts[k + 2]) + 1
    text, st = _profiled(node, _sql(hi))
    want = store.last(hi)
    assert want["host_5"] == [35 + i for i in range(F)]
    assert _answer(text) == want
    assert st["scan_plan.merged_series"] >= 2, st
    assert st["fused_launches"] == 1 and st["fused.rank_columns"] == F


def test_the_counts_say_what_ran(node, tmp_path_factory):
    """`fused.rank_columns` reads 10 on a lastpoint and 0 on the fused
    cells' double-groupby-1; a new bound sorts the rank (`upload.rank_ms`
    inside `upload.meta_ms`, its put `upload.put_ms`), `upload.rank_builds`
    1. With the plane off, a repeat of the bound is a scan hit whose twin
    holds its rank: nothing is sorted or put again."""
    gen = devops.ClassGenerator(LASTPOINT, node.ds,
                                np.random.default_rng([SEED, 2]))
    st = node.ask(gen.draw())
    assert st["fused.rank_columns"] == F and st["upload.rank_builds"] == 1
    assert 0 < st["upload.rank_ms"] <= st["upload.meta_ms"] \
        <= st["upload_ms"] + 0.05
    assert st["upload.put_ms"] > 0
    gen = devops.ClassGenerator(GROUPBY, node.ds,
                                np.random.default_rng([SEED, 3]))
    st = node.ask(gen.draw())
    assert st["fused_launches"] == 1 and st["fused.rank_columns"] == 0
    assert st["upload.rank_builds"] == 0 and "upload.rank_ms" not in st

    h, mp, off = _start({**_ENV, "CNOSDB_SERVING": "0"}, tmp_path_factory,
                        "lastpoint_plane_off",
                        devops.Dataset(SEED + 1, 20, 360))
    try:
        req = devops.ClassGenerator(LASTPOINT, off.ds,
                                    np.random.default_rng([SEED, 4])).draw()
        first, again = off.ask(req), off.ask(req)
        assert first["scan_miss"] == 1 and first["upload.rank_builds"] == 1
        assert first["upload.rank_ms"] > 0
        assert again["scan_hit"] == 1 and again["fused.rank_columns"] == F
        assert again.get("upload.rank_builds", 0) == 0
        assert "upload.rank_ms" not in again and "upload_ms" not in again
        assert again["answer"] == first["answer"]
    finally:
        h.close()
        mp.undo()
