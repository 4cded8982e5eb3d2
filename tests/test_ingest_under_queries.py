"""Live ingest beside queries (ISSUE 35): what a reader may rely on while
a writer appends, a batch is published, caches switch and an inline flush
runs — and the served path under that traffic, against the benchmark's
plain numpy reference.

The interleavings of part 1 are driven, not slept for: `between_steps`
runs a function under a line tracer and calls a hook before every line of
the chosen functions, on the same thread — the hook is "the other thread,
scheduled exactly here". Each of these cases fails at the parent commit
(080a7ef): a reader met a half-appended series, a scan that an inline
flush overtook lost the flushed rows, a promotion closed the file under a
scan.
"""
import json
import os
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

from cluster_harness import _env, free_port
from cnosdb_tpu.models.points import SeriesRows, WriteBatch
from cnosdb_tpu.models.schema import TskvTableSchema, ValueType
from cnosdb_tpu.models.series import SeriesKey
from cnosdb_tpu.storage import scan as scan_mod
from cnosdb_tpu.storage.memcache import MemCache, SeriesData
from cnosdb_tpu.storage.record_file import RecordReader
from cnosdb_tpu.storage.scan import scan_vnode
from cnosdb_tpu.storage.vnode import VnodeStorage
from cnosdb_tpu.storage.wal import WalEntry

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

B = 5                      # rows a series gets from one batch
HOSTS = ("h1", "h2", "h3")
FIELDS = ("a", "b", "c")


def between_steps(fn, funcs, hook):
    """Run `fn()`; before every line (and at the return) of each function
    in `funcs`, call `hook()` on this thread. → (fn's result, hook calls)."""
    codes = {f.__code__ for f in funcs}
    calls = [0]

    def local(frame, event, arg):
        if event in ("line", "return"):
            calls[0] += 1
            hook()
        return local

    def tracer(frame, event, arg):
        return local if frame.f_code in codes else None

    old = sys.gettrace()
    sys.settrace(tracer)
    try:
        return fn(), calls[0]
    finally:
        sys.settrace(old)


def _rows(batch: int, host: str = "h1") -> SeriesRows:
    """Batch number `batch` (1-based) of one series: B rows, every field
    a function of the timestamp, so a torn row shows."""
    ts = [(batch - 1) * B + i for i in range(B)]
    return SeriesRows(
        SeriesKey("cpu", {"host": host}), ts,
        {f: (int(ValueType.INTEGER), [t * (j + 2) for t in ts])
         for j, f in enumerate(FIELDS)})


def _check_whole(ts, fields, what):
    """`ts` / `fields` of one series hold whole batches only."""
    assert len(ts) % B == 0, (what, len(ts))
    if len(ts) == 0:
        return
    assert set(fields) == set(FIELDS), (what, sorted(fields))
    for j, f in enumerate(FIELDS):
        _vt, vals, valid = fields[f]
        assert len(vals) == len(ts) and valid.all(), (what, f)
        np.testing.assert_array_equal(vals, np.asarray(ts) * (j + 2))


# ------------------------------------------------- part 1: SeriesData
def test_a_reader_between_any_two_steps_of_an_append_sees_whole_batches():
    sd = SeriesData(7, "cpu")
    sizes = set()

    def reader():
        ts, fields, _order = sd.materialize()
        _check_whole(ts, fields, "materialize")
        sizes.add(len(ts))
        suf = sd.suffix(1)               # what a delta scan takes
        if suf is not None:
            ts2, fields2, _ = suf.materialize()
            _check_whole(ts2, fields2, "suffix")
            assert len(ts2) == max(0, len(ts) - B)

    steps = 0
    for batch in (1, 2, 3):
        _r, n = between_steps(lambda: sd.append(_rows(batch), batch),
                              [SeriesData.append], reader)
        steps += n
    assert steps >= 3
    assert sizes <= {0, B, 2 * B, 3 * B} and 3 * B in sizes
    assert sd.n_rows == 3 * B


def test_a_cut_at_a_seq_takes_the_batches_up_to_it():
    sd = SeriesData(7, "cpu")
    for batch in (1, 2, 2, 4):           # two appends may share a seq
        sd.append(_rows(batch), batch)
    assert [len(sd.chunks(upto_seq=s)) for s in (0, 1, 2, 3, 4, 9)] \
        == [0, 1, 3, 3, 4, 4]
    assert [len(sd.chunks(after_seq=s)) for s in (0, 1, 2, 3, 4)] \
        == [4, 3, 1, 1, 0]
    assert len(sd.chunks(upto_seq=2, after_seq=1)) == 2
    ts, fields, _ = sd.materialize(upto_seq=1)
    _check_whole(ts, fields, "cut")
    assert len(ts) == B
    assert sd.suffix(4) is None and sd.suffix(1, upto_seq=1) is None
    # an in-place edit swaps the list: a reader's copy keeps the old names
    before = sd.chunks()
    sd.rename_field("a", "z")
    sd.drop_field("b")
    assert sd.field_names() == {"z", "c"}
    assert set(before[0][2]) == set(FIELDS)


def test_memcache_readers_beside_a_writer_of_new_series():
    """write_series step by step: a series is in the dict only with its
    first batch in it, and suffix_view walks a copy of the keys."""
    cache = MemCache(1)
    seen = set()

    def reader():
        view = cache.suffix_view(0)
        for (table, sid) in cache.series_keys():
            ts, fields, _ = cache.series[(table, sid)].materialize()
            assert len(ts) > 0
            _check_whole(ts, fields, "series in the dict")
        if view is not None:
            for sd in view.series.values():
                _check_whole(*sd.materialize()[:2], "suffix_view")
            seen.add(len(view.series))

    for seq, sid in enumerate((11, 12, 13, 11), start=1):
        between_steps(
            lambda: cache.write_series("cpu", sid, _rows(1 + seq // 4), seq),
            [MemCache.write_series, SeriesData.append], reader)
    assert seen == {1, 2, 3}
    assert cache.series[("cpu", 11)].n_rows == 2 * B


# ----------------------------------------------- part 1: the scan's view
def _schema():
    return {"cpu": TskvTableSchema.new_measurement(
        "t", "db", "cpu", tags=["host"],
        fields=[(f, ValueType.INTEGER) for f in FIELDS])}


def _batch(batch: int) -> WriteBatch:
    wb = WriteBatch()
    for host in HOSTS:
        wb.add_series("cpu", _rows(batch, host))
    return wb


def _check_scan(b, batches_allowed, what):
    """A scan's batch holds whole write batches: every series the same
    rows, every field of every row."""
    per_batch = B * len(HOSTS)
    assert b.n_rows % per_batch == 0, (what, b.n_rows)
    assert b.n_rows // per_batch in batches_allowed, (what, b.n_rows)
    if b.n_rows == 0:
        return
    assert b.n_series == len(HOSTS)
    assert (np.bincount(b.sid_ordinal) == b.n_rows // len(HOSTS)).all()
    for j, f in enumerate(FIELDS):
        _vt, vals, valid = b.fields[f]
        assert valid.all(), (what, f)
        np.testing.assert_array_equal(vals, b.ts * (j + 2))


def test_a_scan_between_any_two_steps_of_a_write_sees_whole_batches(
        tmp_engine_dir):
    v = VnodeStorage(1, tmp_engine_dir, schemas=_schema())
    v.write(_batch(1))
    v.flush()                            # batch 1 in a file, 2.. in memory
    tokens = []
    writing = [2]

    def reader():
        if v._cut_lock.locked():
            return       # a reader would wait here, for a few assignments
        cut = v.cut()
        # batch n has WAL seq n: the cut holds the batches up to its seq,
        # whole, and nothing of the one in flight
        assert cut.mem_seq in (writing[0] - 1, writing[0])
        tokens.append(cut.token)
        _check_scan(scan_vnode(cut, "cpu"), {cut.mem_seq},
                    "scan beside a write")

    steps = 0
    for batch in (2, 3):
        writing[0] = batch
        wb = _batch(batch)
        seq, n = between_steps(
            lambda: v.write(wb),
            [VnodeStorage.write, VnodeStorage._apply_write,
             MemCache.write_series, SeriesData.append], reader)
        assert seq == batch
        steps += n
    assert steps > 30
    _check_scan(scan_vnode(v, "cpu"), {3}, "after")
    # a token taken while a batch was being applied never equals the
    # state after it: a batch cached under it cannot be a later scan_hit
    final = v.cut().token
    assert final.mem_seq == 3
    stale = [t for t in tokens if t.mem_seq < 3]
    assert stale and all(t.data_version != final.data_version
                         for t in stale)
    v.close()


def _scan_steps(v):
    """How many steps one scan of `v` has (a dry run of the tracer)."""
    return between_steps(lambda: scan_vnode(v, "cpu"), SCAN_FUNCS,
                         lambda: None)[1]


SCAN_FUNCS = [scan_mod.scan_vnode, scan_mod._scan_vnode_native,
              scan_mod._series_to_merge, scan_mod._plan_pages,
              scan_mod._merged_series, scan_mod._series_parts,
              scan_mod._mem_series_ids]


@pytest.mark.parametrize("compact", [False, True],
                         ids=["flush", "flush+compaction"])
def test_an_inline_flush_between_any_two_steps_of_a_scan_loses_no_row(
        tmp_path, compact):
    """The writer overtakes the reader: at step k of a scan a batch is
    written and the cache flushed (what `_apply_write`'s caller does
    inline when the cache is full) — and, in the second case, the two L0
    files are merged into one and unlinked, as the background compactor
    does after a write. The scan answers with the state it cut — batches
    1 and 2, or all three — never with a file set from before the flush
    and memcaches from after it, and never with an error."""
    def fresh(i):
        v = VnodeStorage(1, str(tmp_path / f"v{i}"), schemas=_schema())
        v.picker.l0_trigger = 2
        v.write(_batch(1))
        v.flush()
        v.write(_batch(2))
        return v

    v = fresh("dry")
    total = _scan_steps(v)
    v.close()
    assert total > 40
    for k in range(1, total + 1, 3):
        v = fresh(k)
        n = [0]

        def writer():
            n[0] += 1
            if n[0] == k:
                v.write(_batch(3))
                v.flush()
                if compact:
                    assert v.compact()
                    assert not v.summary.version.levels[0]

        b, _n = between_steps(lambda: scan_vnode(v, "cpu"), SCAN_FUNCS,
                              writer)
        _check_scan(b, {2, 3}, f"flush at step {k} of {total}")
        _check_scan(scan_vnode(v, "cpu"), {3}, "after the flush")
        v.close()


def test_a_cut_outlives_the_flush_and_the_promotion_after_it(tmp_engine_dir):
    """A scan holds its cut while the cache it reads is flushed and the
    file it reads is promoted L0 → L1 (the first write after a FLUSH
    schedules that): the flushed cache still reads, the promoted file's
    reader is not closed under the scan, no row comes twice."""
    v = VnodeStorage(1, tmp_engine_dir, schemas=_schema())
    v.picker.promote_file_size = 1       # any flush is promotion-sized
    v.write(_batch(1))
    v.flush()
    v.write(_batch(2))
    cut = v.cut()
    fm = cut.summary.version.all_files()[0]
    reader = cut.summary.version.reader(fm)
    v.write(_batch(3))
    v.flush()
    assert v.compact()                   # metadata-only promotions
    assert not v.summary.version.levels[0]
    assert fm.file_id in v.summary.version.levels[1]
    # the cut: batches 1 (its file, by the reader opened before) and 2
    # (the memcache object the flush has since dropped)
    assert reader.read_series_timestamps("cpu", next(iter(
        reader.groups["cpu"].chunks))).size == B
    _check_scan(scan_vnode(cut, "cpu"), {2}, "the old cut")
    assert cut.token.file_ids < v.cut().token.file_ids
    _check_scan(scan_vnode(v, "cpu"), {3}, "a new cut")
    v.close()


def test_unflushed_rows_outside_the_time_range_leave_the_page_plan_alone(
        tmp_engine_dir):
    """A fleet writes at "now", a panel reads last week: a memcache whose
    [min_ts, max_ts] misses every range of the scan is not read, and its
    series stay on the native page plan (no `memcache.*` stage booked,
    pages booked to the native decoder's lane by a scan with a lane). A
    range that reaches the cache merges it, and so does a delta view."""
    from cnosdb_tpu.models.predicate import TimeRange, TimeRanges
    from cnosdb_tpu.storage.scan import DeltaVnodeView
    from cnosdb_tpu.utils import stages

    v = VnodeStorage(1, tmp_engine_dir, schemas=_schema())
    v.write(_batch(1))                   # ts 0..4, flushed
    v.flush()
    token = v.cut().token
    v.write(_batch(3))                   # ts 10..14, unflushed

    def scan(lo, hi):
        prof = stages.QueryProfile()
        with stages.profile_scope(prof):
            b = scan_vnode(v, "cpu", time_ranges=TimeRanges(
                [TimeRange(lo, hi)]))
        return b, prof

    b, prof = scan(0, 9)
    _check_scan(b, {1}, "the flushed range")
    assert "memcache.series" not in prof.counts and "memcache_ms" not in prof.ms
    b, prof = scan(0, 12)
    assert b.n_rows == len(HOSTS) * (B + 3)
    assert prof.counts["memcache.series"] == len(HOSTS)
    assert prof.counts["memcache.rows"] == len(HOSTS) * B
    b, prof = scan(10, 14)
    _check_scan(b, {1}, "the unflushed range")
    # the delta since the token: the suffix view keeps the cache's bounds
    delta = scan_vnode(DeltaVnodeView(v, frozenset(), token.mem_seq), "cpu",
                       time_ranges=TimeRanges([TimeRange(10, 14)]))
    _check_scan(delta, {1}, "the delta view")
    v.close()


def test_an_acknowledged_batch_has_left_the_process(tmp_engine_dir):
    """`[wal] sync = false` still hands the entry to the OS before
    write() returns: another reader of the segment file finds it whole
    (a SIGKILL after the HTTP 200 loses nothing)."""
    v = VnodeStorage(1, tmp_engine_dir, schemas=_schema())
    for batch in (1, 2, 3):
        seq = v.write(_batch(batch))
        seg = os.path.join(v.wal.dir, sorted(
            n for n in os.listdir(v.wal.dir) if n.endswith(".log"))[-1])
        seqs = [WalEntry.decode(p).seq for p in RecordReader(seg)]
        assert seqs[-1] == seq == batch
    v.close()


# ------------------------------------------------ part 2: the served path
# The server as the benchmark starts it, but with a memcache of 256 KiB so
# that inline flushes land inside the traffic; the cache's size has no
# option in a served node, so the child patches the constructor it uses.
LAUNCH = """
import functools, sys
from cnosdb_tpu.server import http, main
http.TsKv = functools.partial(http.TsKv, memcache_bytes=int(sys.argv[3]))
sys.exit(main.main(["run", "--data-dir", sys.argv[1],
                    "--http-port", sys.argv[2]]))
"""


class _Node:
    def __init__(self, tmp_path, memcache_bytes):
        self.dir, self.port = str(tmp_path / "data"), free_port()
        self.log_path = str(tmp_path / "server.log")
        self.memcache_bytes = memcache_bytes
        self.proc = None

    def start(self):
        from benchmarks.lib.server import Connection

        log = open(self.log_path, "ab")
        self.proc = subprocess.Popen(
            [sys.executable, "-c", LAUNCH, self.dir, str(self.port),
             str(self.memcache_bytes)],
            cwd=ROOT, env={**_env(), "CNOSDB_WAL_SYNC": "false"},
            stdout=log, stderr=subprocess.STDOUT)
        log.close()
        deadline = time.monotonic() + 120
        while True:
            assert self.proc.poll() is None, self.log()
            try:
                if Connection(self.port, 2.0).request(
                        "GET", "/api/v1/ping")[0] == 200:
                    return
            except OSError:
                pass
            assert time.monotonic() < deadline, "server not ready"
            time.sleep(0.1)

    def log(self):
        with open(self.log_path, errors="replace") as f:
            return f.read()[-3000:]

    def stop(self, sig=signal.SIGKILL):
        if self.proc is not None:
            self.proc.send_signal(sig)
            self.proc.wait(timeout=60)
            self.proc = None

    def sql(self, text):
        from benchmarks.lib.server import Connection

        status, _h, body = Connection(self.port, 120.0).request(
            "POST", "/api/v1/sql?db=public", text.encode(),
            {"Accept": "application/csv"})
        assert status == 200, (status, body[:400], self.log())
        return body.decode()

    def metrics(self):
        from benchmarks.lib.server import Connection, parse_metrics

        return parse_metrics(Connection(self.port, 30.0).request(
            "GET", "/metrics")[2].decode())


def test_served_ingest_beside_panels_against_the_reference(tmp_path):
    """`rehearse` size (20 hosts × 0.5 h), 4 closed-loop writers beside 2
    query clients walking the four panel classes, inline flushes inside
    the traffic: no request fails, every answer equals the numpy
    reference's, count(*) / max(time) = loaded + acknowledged — and again
    after SIGKILL and a restart on the same directory."""
    from benchmarks.lib import devops, traffic
    from benchmarks.lib.server import Connection, metric, post_write
    from benchmarks.run import parse_time_ns

    with open(os.path.join(ROOT, "benchmarks", "traffic",
                           "load-with-panels.json")) as f:
        mix = json.load(f)
    mix["writers"] = {**mix["writers"], "count": 4}
    ds = devops.Dataset(2147483659, 20, 180)
    node = _Node(tmp_path, 256 * 1024)
    node.start()
    try:
        conn = Connection(node.port, 120.0)
        for a in range(0, ds.loaded_steps, 10):
            acked, _r, err = post_write(conn, "public", ds.lines(a, a + 10))
            assert acked, err
        node.sql("FLUSH")
        batches = traffic.Batches(ds, 10, 300)
        before = node.metrics()
        win = traffic.run_phase(node.port, "public", ds, mix, 2147483659,
                                traffic.WINDOW, seconds=3.5, profile=False,
                                batches=batches)
        after = node.metrics()
        queries, writes = win["queries"], win["writes"]
        assert len(queries) >= 4 and len(writes) >= 15
        assert {q["req"].cls for q in queries} \
            == {c["name"] for c in mix["classes"]}
        for q in queries:
            assert q["status"] == 200, (q["status"], q["text"][:400],
                                        node.log())
            devops.check_answer(ds, q["req"], q["text"])
        assert all(w["acked"] for w in writes), \
            [w["error"] for w in writes if not w["acked"]][:3]
        flushes = metric(after, "cnosdb_memcache_flush_total") \
            - metric(before, "cnosdb_memcache_flush_total")
        assert flushes >= 1, "no inline flush landed inside the traffic"
        for st in ("parse", "lock_wait", "wal", "apply"):
            rise = metric(after, "cnosdb_write_stage_ms_count", stage=st) \
                - metric(before, "cnosdb_write_stage_ms_count", stage=st)
            assert rise == len(writes), (st, rise, len(writes))
        assert metric(after, "cnosdb_write_stage_ms_count", stage="flush") \
            - metric(before, "cnosdb_write_stage_ms_count",
                     stage="flush") == flushes
        want = (ds.hosts * ds.loaded_steps + sum(w["rows"] for w in writes),
                ds.step_ns(max(w["last_step"] for w in writes)))

        def read_back():
            n, t = node.sql("SELECT count(*), max(time) FROM cpu") \
                .splitlines()[1].split(",")[:2]
            return int(n), parse_time_ns(t)

        assert read_back() == want
        node.stop(signal.SIGKILL)
        node.start()
        assert read_back() == want
    finally:
        node.stop()
