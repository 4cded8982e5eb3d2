"""The trace reduction against a hand-made event list (overlapping events
on two lines and two devices) and against an event list recorded on the
chip in this PR (`data/chip_trace_events.json`), the latter checked by a
second, independent way of measuring a union: painting microsecond bins."""
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks.lib import trace_reduce as tr  # noqa: E402

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def test_union_merges_clips_and_drops():
    assert tr.union([(5, 7), (1, 3), (2, 4), (6, 6), (9, 8)], 0, 10) \
        == [(1, 4), (5, 7)]
    assert tr.union([(-5, 2), (8, 50)], 0, 10) == [(0, 2), (8, 10)]
    assert tr.union([(1, 9), (2, 3)], 0, 10) == [(1, 9)]       # nested
    assert tr.union([(20, 30)], 0, 10) == []
    assert tr.union([(1, 2), (2, 3)], 0, 10) == [(1, 3)]       # touching


# device 0: two lines; the module line covers the ops line, so busy time is
# read from "XLA Ops" alone: [10,30) ∪ [20,40) ∪ [60,70) = 40 ns, and the
# event that straddles the window's end counts only its inside part.
# device 1: one unnamed line: [0,50) clipped to [5,50) = 45, + [90,100) = 10
HAND = [
    (0, "XLA Modules", "jit_f", 10, 70),
    (0, "XLA Ops", "fusion.1", 10, 20),
    (0, "XLA Ops", "fusion.2", 20, 20),
    (0, "XLA Ops", "copy.3", 60, 10),
    (0, "XLA Ops", "fusion.1", 95, 30),       # [95,125) → [95,100) inside
    (1, "Stream #1", "fusion.1", 0, 50),
    (1, "Stream #1", "all-reduce.4", 90, 10),
    (1, "Stream #2", "fusion.2", 30, 10),     # overlaps stream 1: no more
]
WINDOW = (5, 100)


def test_hand_made_two_lines_two_devices():
    out = tr.reduce_events(HAND, WINDOW)
    per = {d["device"]: d for d in out["per_device"]}
    assert per[0]["busy_s"] == pytest.approx(45e-9)     # 40 + 5
    assert per[0]["lines_used"] == "XLA Ops"
    assert per[1]["busy_s"] == pytest.approx(55e-9)     # 45 + 10
    # the mean over the devices, never the sum
    assert out["busy_s"] == pytest.approx(50e-9)
    assert out["window_s"] == pytest.approx(95e-9)
    assert 0 < out["busy_s"] <= out["window_s"]
    ops = dict(out["device_ops"])
    # fusion.1: dev0 20 + 5 (clipped) ; dev1 45 (clipped at the start)
    assert ops["fusion.1"] == pytest.approx(70e-9)
    assert ops["fusion.2"] == pytest.approx(30e-9)      # dev0 20 + dev1 10
    assert "jit_f" not in ops                           # the coarser line
    assert out["device_ops"][0][0] == "fusion.1"
    # gaps: dev0 [5,10) [40,60) [70,95) ; dev1 [50,90)
    longest = [g for g in out["idle_gaps"] if g[0].startswith("longest:")]
    assert [round(s * 1e9) for _n, s in longest] == [40, 25, 20, 5]
    assert longest[0][0] == "longest:dev1:unattributed"
    total = [g for g in out["idle_gaps"] if g[0].startswith("total:")]
    assert total == [["total:unattributed", pytest.approx(45e-9)]]


def test_gaps_are_named_by_what_the_host_was_doing():
    spans = [("q-a", 0, 45), ("q-b", 42, 80), ("write", 75, 200)]
    out = tr.reduce_events(HAND, WINDOW, spans)
    named = dict((n, round(s * 1e9)) for n, s in out["idle_gaps"])
    assert named["longest:dev1:q-b"] == 40              # midpoint 70
    assert named["longest:dev0:write"] == 25            # midpoint 82.5
    assert named["longest:dev0:q-b"] == 20              # midpoint 50
    assert named["longest:dev0:q-a"] == 5               # midpoint 7.5
    # totals are the mean over the two devices: (40 + 20) / 2, ...
    assert named["total:q-b"] == 30 and named["total:write"] == 12


def test_no_device_or_no_operation_fails_loudly():
    with pytest.raises(tr.TraceError):
        tr.reduce_events([], WINDOW)
    with pytest.raises(tr.TraceError):
        tr.reduce_events([(0, "XLA Ops", "x", 500, 10)], WINDOW)
    with pytest.raises(tr.TraceError):
        tr.reduce_events(HAND, (10, 10))


def recorded():
    path = os.path.join(DATA, "chip_trace_events.json")
    if not os.path.exists(path):
        pytest.skip("no trace recorded on the chip in this checkout")
    with open(path) as f:
        d = json.load(f)
    names = d["names"]
    return [(dev, names[line], names[name], start, dur)
            for dev, line, name, start, dur in d["events"]], \
        tuple(d["window"])


def test_recorded_chip_trace_against_painted_bins():
    events, window = recorded()
    out = tr.reduce_events(events, window)
    assert 0 < out["busy_s"] <= out["window_s"]
    assert all(d["lines_used"] == "XLA Ops" for d in out["per_device"])
    # the same union, measured another way: paint 1 us bins
    lo, hi = window
    bins = bytearray((hi - lo) // 1000 + 1)
    for _dev, line, _name, start, dur in events:
        if line == "XLA Ops":
            a = max(start, lo) - lo
            b = min(start + dur, hi) - lo
            if b > a:
                for i in range(a // 1000, -(-b // 1000)):
                    bins[i] = 1
    painted = sum(bins) * 1e-6
    assert len(out["per_device"]) == 1
    # bins round every event outward to whole microseconds
    n = sum(1 for e in events if e[1] == "XLA Ops")
    assert out["busy_s"] <= painted <= out["busy_s"] + 2e-6 * n
    assert out["device_ops"] and out["idle_gaps"]
