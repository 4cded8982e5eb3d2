"""Metrics registry with Prometheus text exposition.

Role-parity with common/metrics (metric_register.rs, prom_reporter.rs):
typed counters/gauges/histograms exported at GET /metrics.
"""
from __future__ import annotations

import threading
from collections import defaultdict
from ..utils import lockwatch


class _Hist:
    """Streaming histogram: per-bucket counts + sum + count, O(1) memory
    per series regardless of observation volume (the previous sample-list
    representation grew without bound on long-lived servers)."""

    __slots__ = ("buckets", "total", "count")

    def __init__(self, n_bounds: int):
        self.buckets = [0] * n_bounds   # non-cumulative, per bound
        self.total = 0.0
        self.count = 0


class MetricsRegistry:
    def __init__(self):
        self._lock = lockwatch.Lock("metrics.registry")
        self._counters: dict[tuple[str, tuple], float] = defaultdict(float)
        self._gauges: dict[tuple[str, tuple], float] = {}
        self._hist_bounds = [0.001, 0.005, 0.01, 0.05, 0.1, 0.5, 1, 5, 10, 60]
        self._histograms: dict[tuple[str, tuple], _Hist] = {}

    def incr(self, name: str, value: float = 1, **labels):
        with self._lock:
            self._counters[(name, _lk(labels))] += value

    def set_gauge(self, name: str, value: float, **labels):
        with self._lock:
            self._gauges[(name, _lk(labels))] = value

    def set_counter(self, name: str, value: float, **labels):
        """Export an externally-accumulated monotonic total as a counter
        series. For sources that keep their own running sum (e.g. the
        scan planes' fallback/outcome tallies): `incr` would re-add the
        whole total on every scrape, `set_gauge` would mistype it and
        break rate() — this assigns, and exposition stays `counter`."""
        with self._lock:
            self._counters[(name, _lk(labels))] = value

    def declare_histogram(self, name: str, **labels):
        """Export the series at 0 before its first observation (a reader
        that takes the rise of `_sum` / `_count` over a window needs both
        ends to exist)."""
        with self._lock:
            self._histograms.setdefault(
                (name, _lk(labels)), _Hist(len(self._hist_bounds)))

    def observe(self, name: str, value: float, **labels):
        with self._lock:
            h = self._histograms.get((name, _lk(labels)))
            if h is None:
                h = self._histograms[(name, _lk(labels))] = \
                    _Hist(len(self._hist_bounds))
            for i, b in enumerate(self._hist_bounds):
                if value <= b:
                    h.buckets[i] += 1
                    break
            h.total += value
            h.count += 1

    def prometheus_text(self) -> str:
        out = []
        with self._lock:
            for (name, labels), v in sorted(self._counters.items()):
                out.append(f"# TYPE {name} counter")
                out.append(f"{name}{_fmt(labels)} {v}")
            for (name, labels), v in sorted(self._gauges.items()):
                out.append(f"# TYPE {name} gauge")
                out.append(f"{name}{_fmt(labels)} {v}")
            for (name, labels), h in sorted(self._histograms.items()):
                out.append(f"# TYPE {name} histogram")
                cum = 0
                for i, b in enumerate(self._hist_bounds):
                    cum += h.buckets[i]
                    out.append(f'{name}_bucket{_fmt(labels, le=b)} {cum}')
                out.append(f'{name}_bucket{_fmt(labels, le="+Inf")} {h.count}')
                out.append(f"{name}_sum{_fmt(labels)} {h.total}")
                out.append(f"{name}_count{_fmt(labels)} {h.count}")
        return "\n".join(out) + "\n"


def _lk(labels: dict) -> tuple:
    return tuple(sorted(labels.items()))


def _fmt(labels: tuple, **extra) -> str:
    items = list(labels) + sorted(extra.items())
    if not items:
        return ""
    inner = ",".join(f'{k}="{v}"' for k, v in items)
    return "{" + inner + "}"
