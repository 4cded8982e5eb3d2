"""The last line of a run, and the contract it has to meet.

`last_line()` builds the one JSON object; `validate()` encodes what the
driver reads, as the builder's instructions state it:

  - exactly the keys `correct`, `attempted`, `failed`, `metrics`, `device`
    (+ `breakdown`, optional, in a traced run);
  - `metrics` holds EVERY metric `BENCHMARK.json` lists for this cell in
    this kind of run (`--trace 0`: its end-to-end metrics; `--trace 1`: its
    per-layer metrics), each as a finite number and its unit, and no other;
  - `device` holds `platform`, `kind`, `count`, `memory_peak_bytes`, and in
    a traced run `window_s` and `busy_s` with 0 < busy_s <= window_s;
  - `breakdown` holds `device_ops` and `idle_gaps`, at most ten
    [name, seconds] pairs each;
  - the line is JSON (`allow_nan=False`: a NaN is not JSON).

`run.py` validates what it is about to print and exits non-zero on a
violation, so a fault is met in a rehearsal and not in the driver's check.
"""
from __future__ import annotations

import json
import math
import os

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
TOP_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
DEVICE_KEYS = {"platform", "kind", "count", "memory_peak_bytes"}
TRACE_KEYS = {"window_s", "busy_s"}


class ContractError(Exception):
    """The line about to be printed is not what the driver reads."""


def load_manifest(path: str | None = None) -> dict:
    with open(path or os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def cell_of(manifest: dict, workload: str) -> dict:
    for w in manifest["workloads"]:
        if w["name"] == workload:
            return w
    raise ContractError(
        f"no workload {workload!r} in BENCHMARK.json; it has "
        + ", ".join(w["name"] for w in manifest["workloads"]))


def metrics_of(manifest: dict, workload: str, trace: bool) -> dict:
    """{name: unit} of the metrics this cell reports in this kind of run:
    a metric with no `workloads` key belongs to every cell."""
    return {m["name"]: m["unit"]
            for m in manifest["per_layer" if trace else "end_to_end"]
            if "workloads" not in m or workload in m["workloads"]}


def _finite(x) -> bool:
    return isinstance(x, (int, float)) and not isinstance(x, bool) \
        and math.isfinite(x)


def validate(line: dict, manifest: dict, workload: str, trace: bool) -> None:
    """Raise ContractError unless `line` is what the driver reads."""
    keys = set(line)
    allowed = TOP_KEYS | ({"breakdown"} if trace else set())
    if not TOP_KEYS <= keys or not keys <= allowed:
        raise ContractError(f"top-level keys {sorted(keys)}; need "
                            f"{sorted(TOP_KEYS)}"
                            + (" and optionally breakdown" if trace else ""))
    if not isinstance(line["correct"], bool):
        raise ContractError("correct is not a boolean")
    for k in ("attempted", "failed"):
        if not isinstance(line[k], int) or isinstance(line[k], bool) \
                or line[k] < 0:
            raise ContractError(f"{k} is not a count: {line[k]!r}")
    if line["attempted"] < 1 or line["failed"] > line["attempted"]:
        raise ContractError(f"attempted {line['attempted']}, failed "
                            f"{line['failed']}")
    want = metrics_of(manifest, workload, trace)
    got = line["metrics"]
    if set(got) != set(want):
        raise ContractError(
            f"metrics missing {sorted(set(want) - set(got))}, "
            f"unlisted {sorted(set(got) - set(want))}")
    for name, m in got.items():
        if set(m) != {"value", "unit"}:
            raise ContractError(f"metric {name}: keys {sorted(m)}")
        if not _finite(m["value"]):
            raise ContractError(f"metric {name}: value {m['value']!r} is "
                                "not a finite number")
        if m["unit"] != want[name]:
            raise ContractError(f"metric {name}: unit {m['unit']!r}, "
                                f"BENCHMARK.json says {want[name]!r}")
    dev = line["device"]
    need = DEVICE_KEYS | (TRACE_KEYS if trace else set())
    if set(dev) != need:
        raise ContractError(f"device keys {sorted(dev)}; need {sorted(need)}")
    if not isinstance(dev["platform"], str) or not isinstance(
            dev["kind"], str):
        raise ContractError("device platform / kind are not strings")
    if not isinstance(dev["count"], int) or dev["count"] != \
            cell_of(manifest, workload)["chips"]:
        raise ContractError(f"device count {dev['count']!r}; the cell asks "
                            f"for {cell_of(manifest, workload)['chips']}")
    if not _finite(dev["memory_peak_bytes"]) or dev["memory_peak_bytes"] <= 0:
        raise ContractError(
            f"memory_peak_bytes {dev['memory_peak_bytes']!r}")
    if trace:
        if not _finite(dev["busy_s"]) or not _finite(dev["window_s"]) \
                or not 0 < dev["busy_s"] <= dev["window_s"]:
            raise ContractError(f"busy_s {dev['busy_s']!r} / window_s "
                                f"{dev['window_s']!r}: need 0 < busy_s <= "
                                "window_s")
        bd = line.get("breakdown")
        if bd is not None:
            if set(bd) != {"device_ops", "idle_gaps"}:
                raise ContractError(f"breakdown keys {sorted(bd)}")
            for k, rows in bd.items():
                if len(rows) > 10:
                    raise ContractError(f"breakdown.{k}: {len(rows)} rows")
                for row in rows:
                    if len(row) != 2 or not isinstance(row[0], str) \
                            or not _finite(row[1]):
                        raise ContractError(f"breakdown.{k}: row {row!r}")
    try:
        json.dumps(line, allow_nan=False)
    except ValueError as e:
        raise ContractError(f"not JSON: {e}")


def last_line(*, correct: bool, attempted: int, failed: int, values: dict,
              units: dict, device: dict, memory_peak_bytes: int,
              trace: dict | None = None,
              breakdown: dict | None = None) -> dict:
    """values: {metric: number} as measured (a metric whose reader found
    nothing is simply absent, and validate() then names it); units:
    {metric: unit} from BENCHMARK.json; trace: {busy_s, window_s}."""
    line = {"correct": bool(correct), "attempted": int(attempted),
            "failed": int(failed),
            "metrics": {n: {"value": v, "unit": units[n]}
                        for n, v in values.items() if n in units},
            "device": {"platform": device["platform"],
                       "kind": device["kind"], "count": device["count"],
                       "memory_peak_bytes": memory_peak_bytes}}
    if trace is not None:
        line["device"]["window_s"] = trace["window_s"]
        line["device"]["busy_s"] = trace["busy_s"]
        if breakdown is not None:
            line["breakdown"] = breakdown
    return line


def dumps(line: dict) -> str:
    return json.dumps(line, allow_nan=False)
