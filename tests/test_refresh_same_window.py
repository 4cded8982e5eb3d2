"""The refreshed fleet overview, end to end, against a reference that shares
no code with the program: the deployment of `tsbs-devops-cpu-1k-refresh`
(the serving plane off, as its `server_env` sets `CNOSDB_SERVING=0`),
TSBS devops rows through the served path (line protocol over
/api/v1/write, FLUSH, SQL over HTTP), the class of
`benchmarks/traffic/refresh-same-window.json` — one window asked again and
again, one of the ten fields a request — on the fused lane, every answer
held to `benchmarks/lib/devops.py`.

What the cell `devops-refresh-same-window` rests on: a repeat is answered
by the scan cache's snapshot and the batch's device twin, not decoded or
put again; a write inside the window is not hidden by either; and with the
plane on, the result cache would answer in their place.
"""
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmarks.lib import devops                          # noqa: E402
from benchmarks.lib.server import metric, parse_metrics    # noqa: E402

SEED = 2147483659          # a --seed as the driver draws them: past 2**31
HOSTS, STEPS = 100, 360    # 1 h of data; a request scans 2 991 s = 29 900 rows
with open(os.path.join(ROOT, "benchmarks", "traffic",
                       "refresh-same-window.json")) as f:
    REFRESH = json.load(f)["classes"][0]          # TSBS double-groupby-1
with open(os.path.join(ROOT, "benchmarks", "configs",
                       "tsbs-devops-cpu-1k-refresh.json")) as f:
    SERVER_ENV = json.load(f)["server_env"]


class _Rng:
    """The generator's rng with the window's start chosen (seconds after
    the first point): each test refreshes a window of its own."""

    def __init__(self, start: int, seed: int):
        self.start, self.rng = start, np.random.default_rng([SEED, seed])

    def integers(self, _lo, _hi):
        return self.start

    def choice(self, *a, **k):
        return self.rng.choice(*a, **k)


class _Node:
    def __init__(self, harness, ds):
        self.h, self.ds = harness, ds

    def sql(self, sql: str, headers=None):
        status, body, hdrs = self.h.request(
            "POST", "/api/v1/sql?db=public", sql,
            headers={"Accept": "application/csv", **(headers or {})})
        assert status == 200, body[:500]
        return body, hdrs

    def metrics(self) -> dict:
        status, body, _ = self.h.request("GET", "/metrics")
        assert status == 200
        return parse_metrics(body)

    def ask(self, req: devops.Request, headers=None) -> dict:
        """One request, answered and compared with the reference
        (`devops.check_answer`: the same (bucket, hostname) rows, averages
        to 1e-9). → the stages of its profile, with `answer`."""
        text, hdrs = self.sql(req.sql, {"X-CnosDB-Profile": "1",
                                        **(headers or {})})
        devops.check_answer(self.ds, req, text)
        st = json.loads(hdrs["X-CnosDB-Profile-Summary"])["stages"]
        return {**st, "answer": text}


def _start(env: dict, tmp_path_factory, name: str, ds,
           flush_at: int | None = None) -> tuple:
    """A served node with `env` set before it starts, `ds` loaded over
    /api/v1/write a batch at a time and flushed — also once the steps
    before `flush_at` are in, where given, so each series has two chunks."""
    from test_deadline import _Harness

    mp = pytest.MonkeyPatch()
    for k, v in env.items():
        if v is None:
            mp.delenv(k, raising=False)
        else:
            mp.setenv(k, v)
    h = _Harness(str(tmp_path_factory.mktemp(name)))
    node = _Node(h, ds)
    for k in range(0, ds.steps, 30):
        status, body, _ = h.request("POST", "/api/v1/write?db=public",
                                    ds.lines(k, min(k + 30, ds.steps)).decode())
        assert status == 200, body          # acknowledged before the next
        if flush_at is not None and k < flush_at <= k + 30:
            node.sql("FLUSH")
    node.sql("FLUSH")
    text, _ = node.sql("SELECT count(*) FROM cpu")
    assert int(text.splitlines()[1]) == ds.hosts * ds.steps
    return h, mp, node


# the configuration's server_env, and the fused lane on this backend
_PLANE_OFF = {**SERVER_ENV, "CNOSDB_TPU_FORCE_DEVICE_PATH": "1"}


@pytest.fixture(scope="module")
def node(tmp_path_factory):
    assert SERVER_ENV["CNOSDB_SERVING"] == "0"
    h, mp, n = _start(_PLANE_OFF, tmp_path_factory, "refresh",
                      devops.Dataset(SEED, HOSTS, STEPS))
    try:
        yield n
    finally:
        h.close()
        mp.undo()


def test_refreshes_are_answered_from_the_cached_scan_and_twin(node):
    """Twenty refreshes of one window, one field drawn a request: every
    answer is the reference's. A field's first ask decodes and builds the
    twin; from its second ask on the scan cache answers (`scan_hit`) and
    the twin is handed back (`upload.twin_builds` 0) — no `decode_ms`,
    no `upload_ms`, one fused program over columns already on the
    device."""
    gen = devops.ClassGenerator(REFRESH, node.ds, _Rng(0, 1))
    asked, windows = set(), set()
    for _ in range(20):
        req = gen.draw()
        st = node.ask(req)
        windows.add((req.spec["lo"], req.spec["hi"]))
        field = tuple(req.spec["fields"])
        assert st["fused_launches"] == 1
        if field in asked:
            assert st["scan_hit"] == 1 and "scan_miss" not in st, st
            assert st["upload.twin_builds"] == 0
            assert "decode_ms" not in st and "upload_ms" not in st, \
                sorted(st)
        else:
            assert st["scan_miss"] == 1 and st["upload.twin_builds"] == 1
            assert st["upload_ms"] > 0
        asked.add(field)
    assert len(windows) == 1 and len(asked) < 20
    # the plane off, the result cache's hits are still exported: as 0
    hit = ("cnosdb_serving_total",
           (("layer", "result_cache"), ("outcome", "hit")))
    assert node.metrics()[hit] == 0


def test_a_rewrite_inside_the_window_is_read_by_the_next_refresh(node):
    """One step inside the refreshed window is written again with new
    values: the last write of a (series, time) wins, as upstream's rule
    is. The next refresh takes the delta path (`delta_hit`) and answers
    over the rewritten rows; with the plane off nothing answers in its
    place. A rewrite is no pure append (its (series, time) pairs are in
    the cached batch), so `merged_device_batch` declines and the merged
    batch's twin is built again: `upload.twin_builds` reads 1 there, and
    0 on the refresh after."""
    cls = {**REFRESH, "random_fields": False}           # usage_user
    gen = devops.ClassGenerator(cls, node.ds, _Rng(100, 2))
    before = node.ask(gen.draw())
    assert node.ask(gen.draw())["scan_hit"] == 1
    req = gen.draw()
    k = node.ds.k_range(req.spec["lo"], req.spec["hi"])[0] + 7
    node.ds.v[:, :, k] = (node.ds.v[:, :, k] + 37) % 101
    status, body, _ = node.h.request("POST", "/api/v1/write?db=public",
                                     node.ds.lines(k, k + 1).decode())
    assert status == 200, body
    st = node.ask(req)                  # held to the rewritten reference
    assert st["delta_hit"] == 1 and "scan_miss" not in st, st
    assert st["answer"] != before["answer"]
    assert st["upload.twin_builds"] == 1
    again = node.ask(gen.draw())
    assert again["scan_hit"] == 1 and again["upload.twin_builds"] == 0
    assert again["answer"] == st["answer"]


def test_scan_cache_ms_is_booked_on_a_miss_and_on_a_hit(node):
    """`scan.cache_ms` times the scan cache's half of every local scan —
    the series, the key, the cut and the probe: on a miss it ends before
    `decode_ms` begins; on a hit it is the whole scan layer."""
    gen = devops.ClassGenerator(REFRESH, node.ds, _Rng(200, 3))
    req = gen.draw()
    miss = node.ask(req, {"cnos-trace-id": "feedc0de3901"})
    hit = node.ask(req, {"cnos-trace-id": "feedc0de3902"})
    assert miss["scan_miss"] == 1 and hit["scan_hit"] == 1
    for st in (miss, hit):
        assert st["scan.cache_ms"] > 0 and "cpu.scan.cache_ms" in st
    assert "decode_ms" in miss and "decode_ms" not in hit
    status, body, _ = node.h.request(
        "GET", "/debug/traces?trace_id=feedc0de3901")
    assert status == 200
    spans = {s["name"]: s for s in json.loads(body)}
    cache, decode = spans["scan.cache_ms"], spans["decode_ms"]
    assert cache["start_ns"] + cache["duration_ns"] <= decode["start_ns"]


def test_with_the_plane_on_the_result_cache_answers_a_repeat(
        tmp_path_factory):
    """Why the configuration turns the serving plane off: with it on (the
    default), the second identical request is answered by the result
    cache — `cnosdb_serving_total{layer=result_cache,outcome=hit}` rises
    and its profile has no scan at all — so a refresh would measure one
    dictionary lookup and an idle device."""
    env = {**_PLANE_OFF, "CNOSDB_SERVING": None}
    h, mp, n = _start(env, tmp_path_factory, "plane_on",
                      devops.Dataset(SEED + 1, 20, 360))
    try:
        req = devops.ClassGenerator(REFRESH, n.ds, _Rng(0, 4)).draw()
        first = n.ask(req)
        assert first["scan_miss"] == 1
        before = n.metrics()
        second = n.ask(req)
        after = n.metrics()
        hits = [metric(m, "cnosdb_serving_total", layer="result_cache",
                       outcome="hit") for m in (before, after)]
        assert hits[1] - hits[0] == 1, hits
        assert not {"scan_hit", "scan_miss", "scan.cache_ms",
                    "fused_launches"} & set(second), sorted(second)
        assert second["answer"] == first["answer"]
    finally:
        h.close()
        mp.undo()
