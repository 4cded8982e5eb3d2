"""Device-decode plane (ops/device_decode + codecs.split_for_device):
bit-identical parity against the host decoder across every codec, dtype
and null pattern (the XLA kernels on the CPU backend), reason accounting
for rejected pages, and the end-to-end scan lane — engagements > 0 and batch
equivalence vs the legacy Python scan, the page group as the lane's unit
of device work, and the decoded columns' staging through the
EagerUploader."""
import os

import numpy as np
import pytest

from cnosdb_tpu.models.codec import Encoding
from cnosdb_tpu.models.points import SeriesRows, WriteBatch
from cnosdb_tpu.models.schema import TskvTableSchema, ValueType
from cnosdb_tpu.models.series import SeriesKey
from cnosdb_tpu.models.strcol import DictArray
from cnosdb_tpu.ops import device_decode
from cnosdb_tpu.storage import codecs
from cnosdb_tpu.storage.scan import scan_vnode
from cnosdb_tpu.storage.vnode import VnodeStorage


# ---------------------------------------------------------------------------
# kernel parity: device lane output must be BIT-identical to codecs.decode
# ---------------------------------------------------------------------------
def _device_decode_block(block: bytes, vt: ValueType) -> np.ndarray:
    """Round one encoded block through the device lane and return the
    decoded values, shaped like codecs.decode's output."""
    plan, reason = codecs.split_for_device(block, vt)
    assert plan is not None, f"split rejected: {reason}"
    n = plan["n"]
    lane = device_decode.DeviceDecodeLane()
    if vt in (ValueType.STRING, ValueType.GEOMETRY):
        got = {}

        def sink(dense, _plan=plan):
            got["vals"] = np.asarray(_plan["values"])[dense]

        lane.submit(plan, "tok", vt, 0, n, None, None, None, sink=sink)
        assert lane.run() == []
        return got["vals"]
    out_vals = np.zeros(n, dtype=vt.numpy_dtype())
    out_valid = np.zeros(n, dtype=bool)
    lane.submit(plan, "tok", vt, 0, n, None, out_vals, out_valid)
    assert lane.run() == []
    assert out_valid.all()
    return out_vals


def _assert_bit_identical(dev: np.ndarray, host: np.ndarray):
    assert dev.dtype == host.dtype
    if dev.dtype == np.float64:
        # NaN payloads included: compare the raw bit patterns
        np.testing.assert_array_equal(dev.view(np.uint64),
                                      host.view(np.uint64))
    else:
        np.testing.assert_array_equal(dev, host)


_LENGTHS = [1, 2, 3, 127, 128, 129, 1000, 4096]


@pytest.mark.parametrize("n", _LENGTHS)
def test_delta_i64_parity(rng, n):
    vals = rng.integers(-(1 << 40), 1 << 40, n).cumsum()
    block = codecs.encode(vals, ValueType.INTEGER, Encoding.DELTA)
    host = codecs.decode(block, ValueType.INTEGER)
    _assert_bit_identical(_device_decode_block(block, ValueType.INTEGER),
                          host)


def test_delta_i64_extreme_values(rng):
    vals = np.array([np.iinfo(np.int64).min, -1, 0, 1,
                     np.iinfo(np.int64).max, 7, -(1 << 62)], np.int64)
    block = codecs.encode(vals, ValueType.INTEGER, Encoding.DELTA)
    host = codecs.decode(block, ValueType.INTEGER)
    _assert_bit_identical(_device_decode_block(block, ValueType.INTEGER),
                          host)


@pytest.mark.parametrize("n", _LENGTHS)
def test_delta_ts_const_stride_parity(rng, n):
    ts = int(rng.integers(0, 1 << 50)) \
        + np.arange(n, dtype=np.int64) * 30_000_000
    block = codecs.encode_timestamps(ts)
    host = codecs.decode_timestamps(block)
    _assert_bit_identical(_device_decode_block(block, ValueType.INTEGER),
                          host)


@pytest.mark.parametrize("n", _LENGTHS)
def test_unsigned_parity(rng, n):
    vals = rng.integers(0, np.iinfo(np.uint64).max, n, dtype=np.uint64)
    block = codecs.encode(vals, ValueType.UNSIGNED, Encoding.DELTA)
    host = codecs.decode(block, ValueType.UNSIGNED)
    _assert_bit_identical(_device_decode_block(block, ValueType.UNSIGNED),
                          host)


@pytest.mark.parametrize("n", _LENGTHS)
def test_gorilla_f64_parity(rng, n):
    vals = rng.normal(20.0, 5.0, n).round(3)
    block = codecs.encode(vals, ValueType.FLOAT, Encoding.GORILLA)
    host = codecs.decode(block, ValueType.FLOAT)
    _assert_bit_identical(_device_decode_block(block, ValueType.FLOAT),
                          host)


def test_gorilla_f64_special_values(rng):
    vals = np.array([0.0, -0.0, np.nan, np.inf, -np.inf, 5e-324,
                     np.finfo(np.float64).max, 1.0, 1.0, 1.0], np.float64)
    block = codecs.encode(vals, ValueType.FLOAT, Encoding.GORILLA)
    host = codecs.decode(block, ValueType.FLOAT)
    _assert_bit_identical(_device_decode_block(block, ValueType.FLOAT),
                          host)


@pytest.mark.parametrize("n", _LENGTHS)
def test_bitpack_bool_parity(rng, n):
    vals = rng.random(n) < 0.5
    block = codecs.encode(vals, ValueType.BOOLEAN, Encoding.BITPACK)
    host = codecs.decode(block, ValueType.BOOLEAN)
    _assert_bit_identical(_device_decode_block(block, ValueType.BOOLEAN),
                          host)


@pytest.mark.parametrize("n", [1, 127, 1000])
def test_dict_string_parity(rng, n):
    words = np.array(["", "ok", "wärn", "err", "crité"], dtype=object)
    vals = words[rng.integers(0, len(words), n)]
    block = codecs.encode(vals, ValueType.STRING)
    host = codecs.decode(block, ValueType.STRING).materialize()
    dev = _device_decode_block(block, ValueType.STRING)
    np.testing.assert_array_equal(dev, np.asarray(host, dtype=object))


# ---------------------------------------------------------------------------
# rejection accounting: split_for_device + the lane's outcome counters
# ---------------------------------------------------------------------------
def test_split_rejects_with_reasons(rng):
    ints = rng.integers(0, 100, 50)
    plan, reason = codecs.split_for_device(
        codecs.encode(ints, ValueType.INTEGER, Encoding.QUANTILE),
        ValueType.INTEGER)
    assert plan is None and reason == "encoding"
    plan, reason = codecs.split_for_device(
        codecs.encode(np.empty(0, np.int64), ValueType.INTEGER,
                      Encoding.DELTA), ValueType.INTEGER)
    assert plan is None and reason == "empty"
    plan, reason = codecs.split_for_device(b"", ValueType.INTEGER)
    assert plan is None and reason == "empty"
    plan, reason = codecs.split_for_device(
        codecs.encode(rng.normal(size=10), ValueType.FLOAT,
                      Encoding.QUANTILE), ValueType.FLOAT)
    assert plan is None and reason == "encoding"


def test_declined_pages_book_host_outcomes():
    before = device_decode.outcomes_snapshot().get(("host", "encoding"), 0)
    lane = device_decode.DeviceDecodeLane()
    assert not lane.accepts(int(ValueType.INTEGER), int(Encoding.QUANTILE))
    lane.declined("encoding", 3)
    snap = device_decode.outcomes_snapshot()
    assert snap[("host", "encoding")] == before + 3


def test_decoded_pages_book_device_outcomes(rng):
    before = device_decode.outcomes_snapshot().get(("device", "ok"), 0)
    eng_before = device_decode.engagements()
    block = codecs.encode(rng.integers(0, 9, 64), ValueType.INTEGER,
                          Encoding.DELTA)
    _device_decode_block(block, ValueType.INTEGER)
    assert device_decode.outcomes_snapshot()[("device", "ok")] > before
    assert device_decode.engagements() > eng_before


def test_set_counter_exports_counter_type_without_accumulating():
    """The /metrics export of externally-accumulated totals: counter
    TYPE (rate() works), assignment semantics (a re-scrape must not
    double-count the running sum the way incr would)."""
    from cnosdb_tpu.server.metrics import MetricsRegistry

    m = MetricsRegistry()
    m.set_counter("cnosdb_device_decode_total", 5,
                  lane="host", reason="encoding")
    m.set_counter("cnosdb_device_decode_total", 7,
                  lane="host", reason="encoding")
    text = m.prometheus_text()
    assert "# TYPE cnosdb_device_decode_total counter" in text
    assert 'cnosdb_device_decode_total{lane="host",reason="encoding"} 7' \
        in text


# ---------------------------------------------------------------------------
# end-to-end: the scan's third lane under CNOSDB_DEVICE_DECODE=1
# ---------------------------------------------------------------------------
def _schema():
    return {"m": TskvTableSchema.new_measurement(
        "t", "db", "m", tags=["host"],
        fields=[("f", ValueType.FLOAT), ("i", ValueType.INTEGER),
                ("b", ValueType.BOOLEAN), ("s", ValueType.STRING)])}


def _write(v, host, ts, **cols):
    types = {"f": ValueType.FLOAT, "i": ValueType.INTEGER,
             "b": ValueType.BOOLEAN, "s": ValueType.STRING,
             "u": ValueType.UNSIGNED, "n": ValueType.INTEGER}
    fields = {name: (int(types[name]),
                     [None if x is None
                      else (x.item() if isinstance(x, np.generic) else x)
                      for x in xs])
              for name, xs in cols.items() if xs is not None}
    wb = WriteBatch()
    wb.add_series("m", SeriesRows(SeriesKey("m", {"host": host}),
                                  list(ts), fields))
    v.write(wb)


def _assert_batches_equal(a, b):
    assert a.n_rows == b.n_rows
    np.testing.assert_array_equal(a.series_ids, b.series_ids)
    np.testing.assert_array_equal(a.ts, b.ts)
    np.testing.assert_array_equal(a.sid_ordinal, b.sid_ordinal)
    assert set(a.fields) == set(b.fields)
    for name in a.fields:
        vt_a, vals_a, valid_a = a.fields[name]
        vt_b, vals_b, valid_b = b.fields[name]
        assert vt_a == vt_b
        np.testing.assert_array_equal(valid_a, valid_b)
        if isinstance(vals_a, DictArray) or isinstance(vals_b, DictArray):
            obj_a = np.asarray(vals_a.materialize()
                               if isinstance(vals_a, DictArray) else vals_a)
            obj_b = np.asarray(vals_b.materialize()
                               if isinstance(vals_b, DictArray) else vals_b)
            np.testing.assert_array_equal(obj_a[valid_a], obj_b[valid_b])
        else:
            np.testing.assert_array_equal(vals_a[valid_a], vals_b[valid_b])


def _device_scan(v, **kw):
    got = scan_vnode(v, "m",
                     decode_hook=device_decode.DeviceDecodeLane, **kw)
    os.environ["CNOSDB_NO_NATIVE_SCAN"] = "1"
    try:
        want = scan_vnode(v, "m", **kw)
    finally:
        del os.environ["CNOSDB_NO_NATIVE_SCAN"]
    return got, want


def test_scan_device_lane_equivalence(tmp_engine_dir, rng):
    v = VnodeStorage(1, tmp_engine_dir, schemas=_schema())
    n = 1200
    _write(v, "h1", range(n), f=rng.normal(size=n),
           i=rng.integers(-50, 50, n), b=rng.integers(0, 2, n) > 0,
           s=[f"v{x}" for x in rng.integers(0, 5, n)])
    _write(v, "h2", range(500, 900), f=rng.normal(size=400))
    v.flush()
    before = device_decode.engagements()
    got, want = _device_scan(v)
    assert device_decode.engagements() > before, \
        "scan did not engage the device-decode lane"
    _assert_batches_equal(got, want)
    v.close()


def test_scan_device_lane_with_nulls(tmp_engine_dir):
    v = VnodeStorage(1, tmp_engine_dir, schemas=_schema())
    n = 500
    _write(v, "h1", range(n),
           f=[float(x) if x % 2 == 0 else None for x in range(n)],
           i=[int(x) if x % 3 == 0 else None for x in range(n)],
           s=[f"s{x}" if x % 5 == 0 else None for x in range(n)])
    v.flush()
    got, want = _device_scan(v)
    _assert_batches_equal(got, want)
    vt, vals, valid = got.fields["f"]
    assert valid.sum() == (n + 1) // 2
    v.close()


def test_scan_device_lane_multi_flush_and_trim(tmp_engine_dir, rng):
    from cnosdb_tpu.models.predicate import TimeRange, TimeRanges

    v = VnodeStorage(1, tmp_engine_dir, schemas=_schema())
    for base in (0, 1000, 2000):
        _write(v, "h1", range(base, base + 500),
               f=np.arange(base, base + 500) * 0.5,
               i=rng.integers(0, 99, 500))
        v.flush()
    got, want = _device_scan(
        v, time_ranges=TimeRanges([TimeRange(250, 2200)]))
    _assert_batches_equal(got, want)
    v.close()


def test_scan_device_lane_stages_decoded_columns(tmp_engine_dir, rng):
    """Columns the device lane decoded whole have no native task to ship
    them: the scan stages them through EagerUploader.put as soon as the
    lane has landed — one column over several pages in two page groups,
    the last page short — and the staged values match the host arrays
    exactly, zero pad included."""
    v = VnodeStorage(1, tmp_engine_dir, schemas=_schema())
    sizes = (800, 700, 130)          # length buckets 1024, 1024, 256
    n = sum(sizes)
    f = rng.normal(size=n)
    i = rng.integers(-1000, 1000, n)
    off = 0
    for k, size in enumerate(sizes):
        _write(v, f"h{k}", range(size), f=f[off:off + size],
               i=i[off:off + size])
        off += size
    v.flush()
    got, lane, _counts = _profiled_device_scan(v)
    assert len({lane._group_key(j) for j in lane._jobs
                if j.token[2] == "i"}) == 2
    pre = getattr(got, "_preuploaded", None)
    assert pre is not None, "no columns were staged on device"
    n_pad, cols = pre
    assert n_pad == 2048
    for name in ("f", "i"):
        assert name in cols, f"column {name} not device-resident"
        vt, dev_vals, dev_valid, all_valid = cols[name]
        assert all_valid and dev_valid is None
        host_vals = got.fields[name][1]
        staged = np.asarray(dev_vals)
        assert staged.shape == (n_pad,) and staged.dtype == host_vals.dtype
        np.testing.assert_array_equal(staged[:n], host_vals)
        assert not staged[n:].any()
    np.testing.assert_array_equal(got.fields["i"][1], i)
    v.close()


# ---------------------------------------------------------------------------
# the page group is the lane's unit of device work
# ---------------------------------------------------------------------------
def _mixed_schema():
    return {"m": TskvTableSchema.new_measurement(
        "t", "db", "m", tags=["host"],
        fields=[("i", ValueType.INTEGER), ("u", ValueType.UNSIGNED),
                ("b", ValueType.BOOLEAN), ("s", ValueType.STRING),
                ("n", ValueType.INTEGER)])}


def _write_mixed(v, n_series, rng, flush_each=False):
    """n_series series of 6 pages each (time, i, u, b, s, n), rows
    alternating 90 / 300 so every column lies in two length buckets."""
    for k in range(n_series):
        if flush_each and k:
            v.flush()
        rows = 300 if k % 2 else 90
        _write(v, f"h{k:03d}", range(rows),
               i=rng.integers(-10**6, 10**6, rows),
               u=rng.integers(2**63, 2**64, rows, dtype=np.uint64),
               b=rng.integers(0, 2, rows) > 0,
               s=[f"v{x}" for x in rng.integers(0, 7, rows)],
               n=[int(x) if x % 3 else None for x in range(rows)])
    v.flush()


def _profiled_device_scan(v):
    """→ (batch, its lane, the scan's stage counts), columns staged
    through an EagerUploader as the coordinator scans."""
    from cnosdb_tpu.ops.device_cache import EagerUploader
    from cnosdb_tpu.utils import stages

    lanes = []

    def hook():
        lanes.append(device_decode.DeviceDecodeLane())
        return lanes[-1]

    prof = stages.QueryProfile()
    with stages.profile_scope(prof):
        got = scan_vnode(v, "m", upload_hook=EagerUploader,
                         decode_hook=hook)
    lane, = lanes
    return got, lane, prof.counts


def _host_scan(v):
    os.environ["CNOSDB_NO_NATIVE_SCAN"] = "1"
    try:
        return scan_vnode(v, "m")
    finally:
        del os.environ["CNOSDB_NO_NATIVE_SCAN"]


@pytest.mark.parametrize("n_series", [2, 7, 70])
def test_device_calls_follow_groups_not_pages(tmp_engine_dir, rng, n_series):
    """12, 42 and 420 pages in the same page groups: the scan is bit for
    bit the host lanes', and the lane calls the device once per operand
    put, kernel launch and pull of a GROUP — never per page."""
    v = VnodeStorage(1, tmp_engine_dir, schemas=_mixed_schema())
    _write_mixed(v, n_series, rng)
    got, lane, counts = _profiled_device_scan(v)
    _assert_batches_equal(got, _host_scan(v))
    assert got.fields["u"][1].dtype == np.uint64
    assert got.fields["b"][1].dtype == np.bool_
    assert not got.fields["n"][2].all()

    pages = 6 * n_series
    assert counts["device_decode_engagements"] == pages == lane.pending()
    groups = {lane._group_key(j) for j in lane._jobs}
    # time (const stride), i/u/n deltas, bits, codes — each column in two
    # length buckets, the null-masked one in narrower ones of its own
    assert {k[0] for k in groups} == {"delta_const", "delta", "bitpack",
                                      "dict"}
    assert {k[2] for k in groups if k[0] == "delta"} == {128, 256, 512}
    puts = {"delta_const": 2, "delta": 2}
    want = sum(puts.get(k[0], 1) + 2 for k in groups)
    assert counts["device_decode.device_calls"] == want <= 4 * len(groups)
    # every numeric column landed whole, so the scan stages each from its
    # host array, the null-masked one with its validity
    n_pad, cols = got._preuploaded
    assert set(cols) == {"i", "u", "b", "n"}
    for name, (vt, dev_vals, dev_valid, all_valid) in cols.items():
        _vt, host_vals, host_valid = got.fields[name]
        assert all_valid == (name != "n") == (dev_valid is None)
        staged = np.asarray(dev_vals)
        if vt == ValueType.BOOLEAN:
            assert staged.dtype == np.int64
            staged = staged.astype(np.bool_)
        assert staged.dtype == host_vals.dtype
        np.testing.assert_array_equal(staged[:got.n_rows], host_vals)
        assert not staged[got.n_rows:].any()
        if dev_valid is not None:
            np.testing.assert_array_equal(
                np.asarray(dev_valid)[:got.n_rows], host_valid)
    v.close()


def test_failure_at_the_pull_routes_the_group_to_the_python_lane(
        tmp_engine_dir, rng, monkeypatch):
    """Dispatch is asynchronous: a kernel's failure can surface only when
    its batch is pulled. The group's pages then decode on the Python lane
    with `kernel_error` booked, the other groups stay on the device, and
    the scan still answers."""
    from cnosdb_tpu.utils import stages

    class _FailsAtPull:
        shape = (1, 1)

        def __array__(self, *_a, **_k):
            raise RuntimeError("device halted")

    launch = device_decode.DeviceDecodeLane._launch_group

    def launch_group(self, kind, lane_len, operands):
        out = launch(self, kind, lane_len, operands)
        return _FailsAtPull() if kind == "delta" else out

    monkeypatch.setattr(device_decode.DeviceDecodeLane, "_launch_group",
                        launch_group)
    v = VnodeStorage(1, tmp_engine_dir, schemas=_mixed_schema())
    _write_mixed(v, 5, rng)
    key = ("host", "kernel_error")
    before = device_decode.outcomes_snapshot().get(key, 0)
    errs = stages.errors_snapshot().get("device_decode.kernel", 0)
    got, lane, counts = _profiled_device_scan(v)
    _assert_batches_equal(got, _host_scan(v))
    n_delta = sum(1 for j in lane._jobs if j.plan["kind"] == "delta")
    assert n_delta == 3 * 5        # i, u, n of every series
    assert device_decode.outcomes_snapshot()[key] - before == n_delta
    n_groups = len({lane._group_key(j) for j in lane._jobs
                    if j.plan["kind"] == "delta"})
    assert stages.errors_snapshot()["device_decode.kernel"] - errs \
        == n_groups > 1
    assert counts["device_decode_engagements"] == 6 * 5 - n_delta
    # a column with a page still to decode is not staged yet
    assert set(got._preuploaded[1]) == {"b"}
    v.close()


# ---------------------------------------------------------------------------
# the route of a page: a page is decoded where its values land
# ---------------------------------------------------------------------------
def _coordinator_hook_on_a_tpu(monkeypatch):
    """The coordinator's decode hook as it decides where the scan device
    is a TPU; the lane's kernels then run on the CPU backend."""
    import types

    from cnosdb_tpu.ops import placement
    from cnosdb_tpu.parallel.coordinator import Coordinator

    with monkeypatch.context() as m:
        m.setattr(placement, "scan_device",
                  lambda: types.SimpleNamespace(platform="tpu"))
        return Coordinator.__new__(Coordinator)._decode_hook()


# id → (fields scanned, how the scan gets its lane, cold reader?, the
# columns whose pages still reach the device lane; None = the time column)
_EVERY = ("i", "u", "b", "n", "s")
_ROUTES = {
    "integer": (("i",), "auto", False, ()),
    "unsigned_above_2_63": (("u",), "auto", False, ()),
    "boolean": (("b",), "auto", False, ()),
    "null_masked": (("n",), "auto", False, ()),
    "const_stride_time": ((), "auto", False, ()),
    "string_has_no_native_lane": (("i", "s"), "auto", False, ("s",)),
    "cold_reader": (("i", "n"), "auto", True, (None, "i", "n")),
    "forced": (_EVERY, "forced", False, (None,) + _EVERY),
    "lane_handed_in": (_EVERY, "direct", False, (None,) + _EVERY),
}


@pytest.mark.parametrize("case", list(_ROUTES))
def test_route_of_a_page(tmp_engine_dir, tmp_path, rng, monkeypatch, case):
    """Through the coordinator's hook in auto mode on a TPU, a numeric or
    time page the native decoder can take is decoded by it — booked
    host / native_first, no device call — and what it cannot take (a cold
    reader's pages, strings) still reaches the device lane; forced, or
    handed to the scan directly, the lane is device-first as ever. Every
    page is booked exactly once, and the batch is bit for bit the forced
    device lane's and the Python lane's."""
    from cnosdb_tpu.storage import tiering
    from cnosdb_tpu.utils import stages

    fields, mode, cold, on_device = _ROUTES[case]
    n_series = 5
    v = VnodeStorage(1, tmp_engine_dir, schemas=_mixed_schema())
    _write_mixed(v, n_series, rng, flush_each=cold)
    if cold:
        # five flushes compact into the one sealed file that may tier
        tiering.configure(str(tmp_path / "bucket"))
        v.compact_full()
        assert tiering.tier_vnode(v, boundary_ns=10 ** 18) == 1
    if mode == "forced":
        monkeypatch.setenv("CNOSDB_DEVICE_DECODE", "1")
    else:
        monkeypatch.delenv("CNOSDB_DEVICE_DECODE", raising=False)
    hook = device_decode.DeviceDecodeLane if mode == "direct" \
        else _coordinator_hook_on_a_tpu(monkeypatch)
    assert (hook == device_decode.DeviceDecodeLane) == (mode != "auto")

    try:
        before = device_decode.outcomes_snapshot()
        prof = stages.QueryProfile()
        with stages.profile_scope(prof):
            got = scan_vnode(v, "m", field_names=list(fields),
                             decode_hook=hook)
        after = device_decode.outcomes_snapshot()
        rise = {k: after[k] - before.get(k, 0) for k in after
                if after[k] != before.get(k, 0)}
        pages = n_series * (1 + len(fields))
        device_pages = n_series * len(on_device)
        want = {("host", "native_first"): pages - device_pages,
                ("device", "ok"): device_pages}
        assert rise == {k: n for k, n in want.items() if n}
        assert sum(rise.values()) == pages
        assert prof.counts.get("device_decode_engagements", 0) \
            == device_pages
        assert (prof.counts.get("device_decode.device_calls", 0) > 0) \
            == bool(on_device)
        if not on_device:
            assert not [k for k in list(prof.ms) + list(prof.counts)
                        if k.startswith("device_decode")]

        forced = scan_vnode(v, "m", field_names=list(fields),
                            decode_hook=device_decode.DeviceDecodeLane)
        os.environ["CNOSDB_NO_NATIVE_SCAN"] = "1"
        try:
            python = scan_vnode(v, "m", field_names=list(fields))
        finally:
            del os.environ["CNOSDB_NO_NATIVE_SCAN"]
        for other in (forced, python):
            _assert_batches_equal(got, other)
            for name in fields:
                assert got.fields[name][1].dtype \
                    == other.fields[name][1].dtype or name == "s"
        if "u" in fields:
            assert got.fields["u"][1].min() >= 2**63
        if "n" in fields:
            assert not got.fields["n"][2].all()
    finally:
        v.close()
        if cold:
            tiering.configure(None)
            tiering.counters_reset()
            tiering.block_cache_clear()
