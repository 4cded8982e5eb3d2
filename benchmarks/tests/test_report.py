"""`report.validate()` is the contract in code: what it lets through is
what the driver reads. Run by hand: `python -m pytest benchmarks/tests -q`
(not part of tests/)."""
import copy
import json
import math
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks.lib import layer_metrics, report  # noqa: E402

MANIFEST = report.load_manifest()
CELLS = [w["name"] for w in MANIFEST["workloads"]]


def a_line(cell: str, trace: bool) -> dict:
    units = report.metrics_of(MANIFEST, cell, trace)
    return report.last_line(
        correct=True, attempted=40, failed=0,
        values={n: 1.5 for n in units}, units=units,
        device={"platform": "tpu", "kind": "TPU v5 lite",
                "count": report.cell_of(MANIFEST, cell)["chips"]},
        memory_peak_bytes=123456789,
        trace={"busy_s": 1.25, "window_s": 10.0} if trace else None,
        breakdown={"device_ops": [["fusion.1", 0.5]],
                   "idle_gaps": [["longest:x", 0.25]]} if trace else None)


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("trace", [False, True])
def test_a_whole_line_passes(cell, trace):
    line = a_line(cell, trace)
    report.validate(line, MANIFEST, cell, trace)
    assert json.loads(report.dumps(line)) == line


def broken(mutate, cell=CELLS[0], trace=True):
    line = copy.deepcopy(a_line(cell, trace))
    mutate(line)
    with pytest.raises(report.ContractError):
        report.validate(line, MANIFEST, cell, trace)


def test_refuses_nan():
    def f(line):
        name = next(iter(line["metrics"]))
        line["metrics"][name]["value"] = math.nan
    broken(f)
    broken(f, trace=False)


def test_refuses_infinity_and_none():
    broken(lambda l: l["metrics"][next(iter(l["metrics"]))].update(
        value=math.inf))
    broken(lambda l: l["metrics"][next(iter(l["metrics"]))].update(
        value=None))


def test_refuses_missing_metric():
    broken(lambda l: l["metrics"].pop(next(iter(l["metrics"]))))
    broken(lambda l: l["metrics"].pop("setup_s"), trace=False)


def test_refuses_unlisted_metric_and_wrong_unit():
    broken(lambda l: l["metrics"].update(
        surprise={"value": 1.0, "unit": "ms"}))
    broken(lambda l: l["metrics"][next(iter(l["metrics"]))].update(
        unit="furlongs"))


def test_refuses_busy_zero_and_busy_over_window():
    broken(lambda l: l["device"].update(busy_s=0))
    broken(lambda l: l["device"].update(busy_s=0.0))
    broken(lambda l: l["device"].update(busy_s=10.5))
    broken(lambda l: l["device"].update(busy_s=math.nan))
    broken(lambda l: l["device"].pop("busy_s"))
    broken(lambda l: l["device"].pop("window_s"))


def test_refuses_wrong_keys_and_counts():
    broken(lambda l: l.pop("device"))
    broken(lambda l: l.update(extra=1))
    broken(lambda l: l.update(breakdown={}), trace=False)
    broken(lambda l: l["device"].update(count=3))
    broken(lambda l: l["device"].update(memory_peak_bytes=0))
    broken(lambda l: l.update(failed=41))
    broken(lambda l: l.update(attempted=0, failed=0))
    broken(lambda l: l.update(correct="yes"))
    broken(lambda l: l["breakdown"].update(
        device_ops=[["x", 1.0]] * 11))
    broken(lambda l: l["breakdown"].update(idle_gaps=[["x", math.nan]]))


def test_dumps_refuses_nan():
    with pytest.raises(ValueError):
        report.dumps({"x": math.nan})


def test_every_listed_per_layer_metric_has_a_reader_that_agrees():
    for m in MANIFEST["per_layer"]:
        spec = layer_metrics.load_spec(m["name"])
        assert spec["unit"] == m["unit"], m["name"]
        assert spec["layer"] == m["layer"], m["name"]
        assert spec["moves"] == m["moves"], m["name"]
        cells = spec["cells"]
        assert (cells == "all") == ("workloads" not in m), m["name"]
        if cells != "all":
            assert cells == m["workloads"], m["name"]


def test_moves_is_reported_wherever_the_metric_is():
    for m in MANIFEST["per_layer"]:
        for cell in m.get("workloads", CELLS):
            assert m["moves"] in report.metrics_of(MANIFEST, cell, False), \
                (m["name"], cell)


def window(queries, **kw):
    return layer_metrics.Window(
        queries, kw.get("client", {}), kw.get("before", {}),
        kw.get("after", {}), kw.get("trace"))


def test_readers_return_nothing_where_there_is_nothing():
    q = [{"ms": 10.0, "profile": {"wall_ms": 8.0, "stages": {
        "decode_ms": 6.0, "finalize_ms": 1.0}}},
        {"ms": 20.0, "profile": {"wall_ms": 15.0, "stages": {
            "decode_ms": 10.0}}}]
    w = window(q, client={"queries": 2},
               after={("pages", (("lane", "device"),)): 8.0,
                      ("pages", (("lane", "host"),)): 2.0},
               before={("pages", (("lane", "device"),)): 4.0},
               trace={"busy_s": 2.0, "window_s": 8.0})
    ev = layer_metrics.evaluate
    assert ev({"aggregation": "mean_per_query",
               "source": ["profile.ms[decode_ms]"]}, w) == 8.0
    assert ev({"aggregation": "mean_per_query", "source": ["client.ms"],
               "minus": ["profile.wall_ms"]}, w) == 3.5
    # a sum of stages is read where any of them was booked ...
    assert ev({"aggregation": "mean_per_query", "source": [
        "profile.ms[merge_ms]", "profile.ms[finalize_ms]"]}, w) == 0.5
    # ... and a stage no query booked is nothing, not 0 and not NaN
    assert ev({"aggregation": "mean_per_query",
               "source": ["profile.ms[upload_ms]"]}, w) is None
    assert ev({"aggregation": "ratio", "numerator": ["profile.ms[decode_ms]"],
               "denominator": ["prom[pages]"], "scale": 1000}, w) \
        == pytest.approx(16.0 / 6.0 * 1000)
    assert ev({"aggregation": "sum",
               "source": ["prom[pages{lane=device}]"]}, w) == 4.0
    assert ev({"aggregation": "sum", "source": ["prom[absent]"]}, w) is None
    assert ev({"aggregation": "ratio", "numerator": ["trace.window_s"],
               "numerator_minus": ["trace.busy_s"],
               "denominator": ["trace.window_s"], "scale": 100}, w) == 75.0
    assert ev({"aggregation": "ratio", "numerator": ["client.queries"],
               "denominator": ["prom[absent]"]}, w) is None
    no_trace = window(q)
    assert ev({"aggregation": "sum", "source": ["trace.busy_s"]},
              no_trace) is None
    assert ev({"aggregation": "mean_per_query",
               "source": ["profile.ms[decode_ms]"]}, window([])) is None
    with pytest.raises(layer_metrics.MetricSpecError):
        ev({"aggregation": "median", "source": ["client.ms"]}, w)
    with pytest.raises(layer_metrics.MetricSpecError):
        ev({"aggregation": "sum", "source": ["nonsense"]}, w)
