"""Every reader file under `layer_metrics/`, listed in BENCHMARK.json or
waiting for the PR after the one that booked its span: closed form, known
cells, a `moves` its cells report, and a value on a window that holds its
terms. Run by hand: `python -m pytest benchmarks/tests -q`."""
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks.lib import layer_metrics, report  # noqa: E402

MANIFEST = report.load_manifest()
CELLS = [w["name"] for w in MANIFEST["workloads"]]
LISTED = {m["name"]: m for m in MANIFEST["per_layer"]}
READERS = sorted(f[:-len(".json")] for f in os.listdir(layer_metrics.METRIC_DIR)
                 if f.endswith(".json"))
TERM_KEYS = ("source", "minus", "numerator", "numerator_minus", "denominator")


def terms_of(spec):
    return [t for k in TERM_KEYS for t in layer_metrics._terms(spec, k)]


def a_window(spec):
    """A window in which every term of `spec` reads 3 (a prom series
    rises by 3, the trace was busy 3 s of 3)."""
    stages, client = {}, {"queries": 3.0}
    before, after = {}, {}
    for t in terms_of(spec):
        m = layer_metrics._TERM.match(t)
        if m and m.group(1) in ("profile.ms", "profile.counts"):
            stages[m.group(2)] = 3.0
        elif m:
            sel = layer_metrics._PROM.match(m.group(2))
            labels = tuple(tuple(kv.split("=")) for kv in
                           filter(None, (sel.group(2) or "").split(",")))
            before[(sel.group(1), labels)] = 1.0
            after[(sel.group(1), labels)] = 4.0
        elif t.startswith("client.") and t != "client.ms":
            client[t[len("client."):]] = 3.0
    q = [{"ms": 3.0, "profile": {"wall_ms": 3.0, "stages": dict(stages)}}]
    return layer_metrics.Window(q, client, before, after,
                                {"busy_s": 3.0, "window_s": 3.0})


def test_the_waiting_readers_are_there():
    waiting = set(READERS) - set(LISTED)
    assert {"upload_meta_ms", "upload_stage_ms", "upload_put_ms",
            "kernel_pad_ms", "kernel_dispatch_ms", "kernel_other_ms.fused",
            "kernel_other_ms.host", "mesh_mask_ms", "mesh_layout_ms",
            "mesh_stage_ms", "scan_alloc_ms", "decode_cpu_ms",
            "kernel_cpu_ms", "upload_cpu_ms", "mesh_plan_cpu_ms",
            "render_cpu_ms", "segment_runs_fallback",
            "render_percell_columns", "scan_merged_series",
            "scan_index_builds"} <= waiting, sorted(waiting)


@pytest.mark.parametrize("name", READERS)
def test_reader_file_is_in_the_closed_form(name):
    spec = layer_metrics.load_spec(name)
    assert spec["name"] == name
    assert spec["aggregation"] in ("mean_per_query", "sum", "ratio")
    assert spec["unit"] and spec["layer"] and spec["about"]
    cells = CELLS if spec["cells"] == "all" else spec["cells"]
    assert cells and set(cells) <= set(CELLS), cells
    # its cells report the end-to-end metric it is to move (a listed one
    # is held to that by test_report.py; the one kept reader whose
    # `moves` waits for a `benchmark` PR is write_ack_p50_ms)
    if name != "write_ack_p50_ms":
        for cell in cells:
            assert spec["moves"] in report.metrics_of(MANIFEST, cell, False)
    if name in LISTED:
        m = LISTED[name]
        assert (spec["unit"], spec["layer"], spec["moves"]) \
            == (m["unit"], m["layer"], m["moves"])
    for t in terms_of(spec):
        assert layer_metrics._TERM.match(t) or t in (
            "profile.wall_ms", "client.ms", "trace.busy_s",
            "trace.window_s") or t.startswith("client."), t
    value = layer_metrics.evaluate(spec, a_window(spec))
    assert value is not None and value == value, name
    # where nothing was booked the reader returns nothing, never 0
    empty = layer_metrics.Window(
        [{"ms": 3.0, "profile": {"wall_ms": 3.0, "stages": {}}}],
        {}, {}, {}, None)
    if any(t.startswith(("profile.ms", "profile.counts", "prom", "trace."))
           for t in terms_of(spec)):
        assert layer_metrics.evaluate(spec, empty) is None, name


def test_a_remainder_needs_every_part_it_takes_away():
    """`kernel_other_ms.*` take the named parts from `kernel_ms`: a cell
    in which one part is booked by no query gets nothing, not a remainder
    that silently holds the part."""
    spec = layer_metrics.load_spec("kernel_other_ms.host")
    q = {"ms": 9.0, "profile": {"wall_ms": 9.0, "stages": {
        "kernel_ms": 9.0, "kernel.pad_ms": 1.0, "kernel.dispatch_ms": 4.0,
        "kernel.fetch_ms": 3.0}}}
    w = layer_metrics.Window([q], {}, {}, {}, None)
    assert layer_metrics.evaluate(spec, w) == pytest.approx(1.0)
    del q["profile"]["stages"]["kernel.pad_ms"]
    w = layer_metrics.Window([q], {}, {}, {}, None)
    assert layer_metrics.evaluate(spec, w) is None
    fused = layer_metrics.load_spec("kernel_other_ms.fused")
    assert layer_metrics.evaluate(fused, w) is None     # no upload_ms
    q["profile"]["stages"]["upload_ms"] = 1.5
    assert layer_metrics.evaluate(
        fused, layer_metrics.Window([q], {}, {}, {}, None)) \
        == pytest.approx(0.5)


def test_listed_entries_came_in_at_the_end_and_changed_nothing():
    """PR 37 lists 21 metrics whose spans its parent books: appended,
    each with its `workloads`, none of the 21 accepted entries touched."""
    names = [m["name"] for m in MANIFEST["per_layer"]]
    assert names[:21] == [
        "http_overhead_ms", "queued_share", "cache_answered_share",
        "decode_work_ms", "decode_us_per_page", "upload_ms",
        "kernel_work_ms", "merge_finalize_ms", "device_idle_share",
        "window_compiles", "mesh_collective_ms", "mesh_plan_ms",
        "mesh_upload_ms", "mesh_assemble_ms", "shard_kernel_ms",
        "shard_upload_ms", "shard_merge_ms", "shard_launches",
        "mesh_declined_share", "ingest_ack_p50_ms", "ingest_batches_acked"]
    assert len(names) == len(set(names)) >= 42
    for m in MANIFEST["per_layer"][21:]:
        assert m["workloads"] and set(m["workloads"]) <= set(CELLS), m
        assert json.dumps(m)      # plain data
