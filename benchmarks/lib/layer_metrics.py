"""Per-layer metrics: each is a small file of its own,
`benchmarks/layer_metrics/<metric>.json`, read by one general evaluator
over a closed set of readers. A later PR that adds a counter to the
program adds a JSON file here and edits nothing.

A file holds `layer`, `unit`, `moves`, `cells` (as `BENCHMARK.json` has
them), an `aggregation` and its terms:

    mean_per_query  mean over the window's profiled queries of
                    (sum of `source` terms - sum of `minus` terms)
    sum             sum of `source` terms - sum of `minus` terms, each
                    term totalled over the window
    ratio           (`numerator` - `numerator_minus`) / `denominator`,
                    each a list of terms totalled over the window

all times `scale` (default 1). A term is one of

    profile.ms[<key>]  profile.counts[<key>]  profile.wall_ms
        per query, from the `X-CnosDB-Profile-Summary` of each query of the
        window (work done, summed over the server's worker threads — not
        elapsed time)
    client.ms          per query, the client's clock, send to last byte
    client.<name>      a number the client measured over the window
                       (`queries`, `writes`, `write_ack_p50_ms`, ...)
    prom[<name>{<label>=<value>,...}]
        the rise over the window of every `/metrics` series of that name
        whose labels include the given ones
    trace.busy_s  trace.window_s
        from the profiler trace (`trace_reduce.py`)

A reader that finds nothing to read — a stage no query of the window
booked, a series the program does not export, no trace — returns None, and
the harness leaves the metric out of the line (`report.validate` then says
which listed metric is missing: a metric is listed for a cell only if every
run of that cell defines it).
"""
from __future__ import annotations

import json
import math
import os
import re

LIB = os.path.dirname(os.path.abspath(__file__))
METRIC_DIR = os.path.join(os.path.dirname(LIB), "layer_metrics")
_TERM = re.compile(r"^(profile\.ms|profile\.counts|prom)\[(.+)\]$")
_PROM = re.compile(r"^([A-Za-z_:][A-Za-z0-9_:]*)(?:\{(.*)\})?$")


class MetricSpecError(Exception):
    """A layer-metric file is not in the closed form above."""


class Window:
    """What one measured window leaves behind for the readers."""

    def __init__(self, queries: list[dict], client: dict, prom_before: dict,
                 prom_after: dict, trace: dict | None):
        # queries: [{"ms": client ms, "profile": summary dict or None}]
        self.queries = [q for q in queries if q.get("profile")]
        self.client = client
        self.prom_before, self.prom_after = prom_before, prom_after
        self.trace = trace

    # ---- per-query terms → list of values (None where nothing to read)
    def per_query(self, term: str):
        if term == "client.ms":
            return [q["ms"] for q in self.queries]
        if term == "profile.wall_ms":
            vals = [q["profile"].get("wall_ms") for q in self.queries]
            return None if any(v is None for v in vals) else vals
        m = _TERM.match(term)
        if m and m.group(1) in ("profile.ms", "profile.counts"):
            key = m.group(2)
            stages = [q["profile"].get("stages") or {} for q in self.queries]
            if not any(key in s for s in stages):
                return None
            return [s.get(key, 0) for s in stages]
        return NotImplemented

    # ---- a term totalled over the window → number or None
    def total(self, term: str):
        vals = self.per_query(term)
        if vals is not NotImplemented:
            return None if vals is None else float(sum(vals))
        if term.startswith("trace."):
            return None if self.trace is None \
                else self.trace.get(term[len("trace."):])
        if term.startswith("client."):
            return self.client.get(term[len("client."):])
        m = _TERM.match(term)
        if m and m.group(1) == "prom":
            return self._prom_rise(m.group(2))
        raise MetricSpecError(f"unknown term {term!r}")

    def _prom_rise(self, selector: str):
        m = _PROM.match(selector.strip())
        if not m:
            raise MetricSpecError(f"bad prom selector {selector!r}")
        name = m.group(1)
        want = set()
        for kv in filter(None, (m.group(2) or "").split(",")):
            k, _, v = kv.partition("=")
            want.add((k.strip(), v.strip().strip('"')))
        if not any(n == name for n, _l in self.prom_after):
            return None
        return sum(v - self.prom_before.get((n, labels), 0.0)
                   for (n, labels), v in self.prom_after.items()
                   if n == name and want <= set(labels))


def _terms(spec: dict, key: str) -> list[str]:
    v = spec.get(key) or []
    return [v] if isinstance(v, str) else list(v)


def _signed_total(w: Window, plus: list[str], minus: list[str]):
    vals = [w.total(t) for t in plus + minus]
    if not plus or any(v is None for v in vals):
        return None
    return sum(vals[:len(plus)]) - sum(vals[len(plus):])


def evaluate(spec: dict, w: Window):
    """→ the metric's value, or None where there is nothing to read."""
    agg = spec.get("aggregation")
    scale = float(spec.get("scale", 1))
    if agg == "mean_per_query":
        plus, minus = _terms(spec, "source"), _terms(spec, "minus")
        cols = [w.per_query(t) for t in plus + minus]
        if any(c is NotImplemented for c in cols):
            raise MetricSpecError(f"{spec.get('name')}: mean_per_query "
                                  "takes per-query terms only")
        # a sum of stages is read where any of them was booked; what is
        # taken away has to be there
        if not w.queries or all(c is None for c in cols[:len(plus)]) \
                or any(c is None for c in cols[len(plus):]):
            return None
        total = sum(sum(c) for c in cols[:len(plus)] if c is not None) \
            - sum(map(sum, cols[len(plus):]))
        value = total / len(w.queries)
    elif agg == "sum":
        value = _signed_total(w, _terms(spec, "source"),
                              _terms(spec, "minus"))
    elif agg == "ratio":
        num = _signed_total(w, _terms(spec, "numerator"),
                            _terms(spec, "numerator_minus"))
        den = _signed_total(w, _terms(spec, "denominator"), [])
        value = None if num is None or not den else num / den
    else:
        raise MetricSpecError(f"{spec.get('name')}: unknown aggregation "
                              f"{agg!r}")
    if value is None or not math.isfinite(value):
        return None
    return value * scale


def load_spec(name: str) -> dict:
    path = os.path.join(METRIC_DIR, name + ".json")
    try:
        with open(path) as f:
            spec = json.load(f)
    except FileNotFoundError:
        raise MetricSpecError(f"per-layer metric {name!r} has no reader: "
                              f"{path} is missing")
    spec.setdefault("name", name)
    return spec


def evaluate_all(names, w: Window) -> dict:
    """{metric: value} for the readers that found something."""
    out = {}
    for name in names:
        value = evaluate(load_spec(name), w)
        if value is not None:
            out[name] = value
    return out
