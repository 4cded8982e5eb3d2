#!/usr/bin/env python3
"""From a `jax.profiler` trace to device busy time, the operations that
took most of it, and the longest idle gaps.

    busy_s    per device, the length of the UNION of the intervals in which
              an operation ran on it, clipped to the traced window; over
              several devices the MEAN of the devices' unions, never a sum
    window_s  the traced window on the trace's own clock: the span of the
              `benchmark.trace_window` mark the server child wrote

The arithmetic (`union`, `reduce_events`) works on plain tuples and imports
nothing, so it is checked against hand-made event lists. `load_xplane`
reads the `.xplane.pb` with `jax.profiler.ProfileData`, which needs the
jax package but no device: `run.py` runs this file as a helper process
with JAX_PLATFORMS=cpu and a time limit, after the server has gone.

    python benchmarks/lib/trace_reduce.py <trace dir> [--rehearse]
        [--dump-events out.json --dump-seconds 0.25]
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys
import time

WINDOW_MARK = "benchmark.trace_window"
DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):(\d+)")
# a device plane draws the same time on several lines (a module's span
# over its operations' spans, steps over modules); busy time is read from
# the finest line there is
LINE_PREFERENCE = ("XLA Ops", "XLA Modules")
# host lines that stand in for a device in a rehearsal on the CPU backend,
# where XLA's operations run on host threads and there is no device plane
CPU_STAND_IN = ("tf_XLAPjRtCpuClient", "tf_XLAEigen", "tf_XLATfrtCpuClient")
TOP_OPS, TOP_GAPS = 10, 5


_OPCODE = re.compile(r"(?<=\s)([a-z][a-z0-9\-]*)\(")
_SHAPE = re.compile(r"[a-z]+\d*\[[\d,]*\]")
_DETAIL = re.compile(r"(?:custom_call_target=\"([^\"]+)\"|calls=(%[\w.\-]+))")


def short_op_name(name: str) -> str:
    """The trace names a TPU operation by its whole HLO instruction, layouts
    and operands included (the program names no kernel yet). Keep what
    tells operations apart: result name, opcode, custom-call target or
    called computation, result shape — `%fusion.2 fusion %fused_computation.2
    u32[8192]`. Anything that is not an HLO instruction is cut to 120."""
    lhs, eq, rhs = name.partition(" = ")
    op = _OPCODE.search(rhs) if eq else None
    if op is None:
        return name[:120]
    detail = _DETAIL.search(rhs)
    shape = _SHAPE.search(rhs)
    parts = [lhs, op.group(1),
             (detail.group(1) or detail.group(2)) if detail else "",
             shape.group(0) if shape else ""]
    return " ".join(p for p in parts if p)[:120]


class TraceError(Exception):
    """The trace cannot give an honest busy time."""


# ------------------------------------------------------------ arithmetic
def union(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """Merged, sorted intervals of [(start, end), ...] clipped to
    [lo, hi]; empty and inverted intervals drop out."""
    clipped = sorted((max(s, lo), min(e, hi)) for s, e in intervals
                     if min(e, hi) > max(s, lo))
    out: list[tuple[float, float]] = []
    for s, e in clipped:
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def attribute(gaps, spans) -> list[str]:
    """What the host was doing in each gap: the labels of the host spans
    (label, start, end) that cover the gap's midpoint, joined; a sweep over
    both lists in time order."""
    order = sorted(range(len(gaps)), key=lambda i: gaps[i][1] + gaps[i][2])
    spans = sorted(spans, key=lambda s: s[1])
    out, active, j = [""] * len(gaps), [], 0
    for i in order:
        mid = (gaps[i][1] + gaps[i][2]) / 2
        while j < len(spans) and spans[j][1] <= mid:
            active.append(spans[j])
            j += 1
        active = [a for a in active if a[2] >= mid]
        out[i] = "+".join(sorted({a[0] for a in active})) \
            or "no_request_in_flight"
    return out


def reduce_events(events, window: tuple[float, float], spans=None) -> dict:
    """events: (device, line, name, start_ns, duration_ns) tuples of the
    device planes; window: (lo_ns, hi_ns) on the same clock; spans:
    optional host spans (label, start_ns, end_ns) on that clock too.

    → busy_s (mean over devices of each device's union), window_s,
    per_device, device_ops (top names by summed time inside the window),
    idle_gaps ([name, seconds]: the longest gaps one by one, then the idle
    time totalled by what the host was doing in it)."""
    lo, hi = window
    if not hi > lo:
        raise TraceError(f"empty trace window {window}")
    by_device: dict = {}
    for dev, line, name, start, dur in events:
        by_device.setdefault(dev, {}).setdefault(line, []).append(
            (name, start, start + dur))
    per_device, op_time, gaps = [], {}, []
    for dev in sorted(by_device, key=str):
        lines = by_device[dev]
        used = next((n for n in LINE_PREFERENCE if n in lines), None)
        chosen = [lines[used]] if used else list(lines.values())
        merged = union(((s, e) for evs in chosen for _n, s, e in evs),
                       lo, hi)
        busy = sum(e - s for s, e in merged)
        for evs in chosen:
            for name, s, e in evs:
                inside = min(e, hi) - max(s, lo)
                if inside > 0:
                    short = short_op_name(name)
                    op_time[short] = op_time.get(short, 0.0) + inside
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        gaps.extend((dev, edges[i], edges[i + 1])
                    for i in range(0, len(edges), 2)
                    if edges[i + 1] > edges[i])
        per_device.append({"device": dev, "busy_s": busy / 1e9,
                           "lines_used": used or sorted(lines),
                           "events": sum(len(v) for v in chosen)})
    if not per_device:
        raise TraceError("the trace holds no device plane")
    busy_s = sum(d["busy_s"] for d in per_device) / len(per_device)
    if not busy_s > 0:
        raise TraceError(
            f"no device operation inside the traced window [{lo}, {hi}] ns; "
            f"the device events span [{min(e[3] for e in events)}, "
            f"{max(e[3] + e[4] for e in events)}] ns")
    gaps.sort(key=lambda g: g[1] - g[2])
    labels = attribute(gaps, spans) if spans is not None \
        else ["unattributed"] * len(gaps)
    many = len(per_device) > 1
    idle = [[f"longest:{f'dev{d}:' if many else ''}{lab}", (e - s) / 1e9]
            for (d, s, e), lab in zip(gaps[:TOP_GAPS], labels)]
    by_host: dict = {}
    for (_d, s, e), lab in zip(gaps, labels):
        by_host[lab] = by_host.get(lab, 0.0) + (e - s)
    # totals are per device: the mean over devices, like busy_s
    idle += [[f"total:{lab}", t / 1e9 / len(per_device)] for lab, t in
             sorted(by_host.items(), key=lambda kv: -kv[1])[:TOP_GAPS]]
    top = sorted(op_time.items(), key=lambda kv: -kv[1])[:TOP_OPS]
    return {"busy_s": busy_s, "window_s": (hi - lo) / 1e9,
            "per_device": per_device, "n_gaps": len(gaps),
            "device_ops": [[n, t / 1e9] for n, t in top],
            "idle_gaps": idle}


# ---------------------------------------------------------------- reading
def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise TraceError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load_xplane(path: str, rehearse: bool = False):
    """→ (device events, (lo_ns, hi_ns) of the window mark or None,
    stand_in). Streams the planes once; keeps tuples only."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    events, host_events, window = [], [], None
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            stand_in = m is None and rehearse \
                and line.name.startswith(CPU_STAND_IN)
            for ev in line.events:
                if m is not None:
                    events.append((int(m.group(2)), line.name, ev.name,
                                   ev.start_ns, ev.duration_ns))
                elif ev.name == WINDOW_MARK:
                    window = (ev.start_ns, ev.start_ns + ev.duration_ns)
                elif stand_in and ev.duration_ns > 0 and not \
                        ev.name.startswith(("ThreadpoolListener", "end: ")):
                    host_events.append(("cpu-stand-in", "host threads",
                                        ev.name, ev.start_ns,
                                        ev.duration_ns))
    if not events and rehearse:
        return host_events, window, True
    return events, window, False


def reduce_trace(trace_dir: str, rehearse: bool = False,
                 host_spans: dict | None = None,
                 dump: tuple[str, float] | None = None) -> dict:
    """host_spans: {"mark_wall_s": wall time at which the child opened the
    window mark, "spans": [[label, wall start, wall end], ...]} — the
    client's request log; both processes read one host's clock, so the
    mark aligns it with the trace's. dump: (path, seconds) keeps the first
    seconds of the window as a small JSON event list."""
    path = find_xplane(trace_dir)
    events, window, stand_in = load_xplane(path, rehearse)
    if window is None:
        raise TraceError(f"no {WINDOW_MARK!r} span in the trace: the "
                         "window cannot be read on the trace's clock")
    spans = None
    if host_spans is not None:
        off = window[0] - host_spans["mark_wall_s"] * 1e9
        spans = [(lab, s * 1e9 + off, e * 1e9 + off)
                 for lab, s, e in host_spans["spans"]]
    out = reduce_events(events, window, spans)
    out.update(window_ns=list(window), xplane_bytes=os.path.getsize(path),
               n_events=len(events), stand_in=stand_in)
    if dump is not None:
        dump_events(events, window, *dump)
    return out


def dump_events(events, window, out_path: str, seconds: float) -> None:
    """A recorded trace as a small JSON event list (the first `seconds`
    of the window), for the tests: the `.xplane.pb` is too large to keep."""
    lo = window[0]
    hi = min(window[1], lo + int(seconds * 1e9))
    names: dict = {}
    rows = []
    for dev, line, name, start, dur in events:
        if start < hi and start + dur > lo:
            rows.append([dev, names.setdefault(line, len(names)),
                         names.setdefault(name, len(names)),
                         int(start - lo), int(dur)])
    with open(out_path, "w") as f:
        json.dump({"window": [0, int(hi - lo)],
                   "names": sorted(names, key=names.get), "events": rows,
                   "format": "[device, line, name, start_ns, duration_ns]; "
                             "line and name index into names"}, f,
                  separators=(",", ":"))


def describe(trace_dir: str) -> dict:
    """{plane: {line: events}} — what a trace that could not be reduced
    holds, for the error line."""
    try:
        from jax.profiler import ProfileData

        data = ProfileData.from_file(find_xplane(trace_dir))
        return {p.name: {ln.name: sum(1 for _ in ln.events)
                         for ln in p.lines} for p in data.planes}
    except Exception as e:      # the error path's own boundary
        return {"unreadable": repr(e)}


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("trace_dir")
    p.add_argument("--rehearse", action="store_true")
    p.add_argument("--host-spans", help="JSON file: the client's request "
                   "log, to name the idle gaps")
    p.add_argument("--dump-events")
    p.add_argument("--dump-seconds", type=float, default=0.25)
    args = p.parse_args(argv)
    t0 = time.monotonic()
    try:
        host_spans = None
        if args.host_spans:
            with open(args.host_spans) as f:
                host_spans = json.load(f)
        dump = (args.dump_events, args.dump_seconds) \
            if args.dump_events else None
        out = reduce_trace(args.trace_dir, args.rehearse, host_spans, dump)
    except TraceError as e:
        print(json.dumps({"error": str(e),
                          "trace_holds": describe(args.trace_dir)}))
        return 1
    out["reduce_seconds"] = time.monotonic() - t0
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
