"""Span primitives: spans, context propagation, in-process collection.

The one span type and the one collector of the process. They live under
`utils/` so the stage plane (`utils/stages.py`) can open a child span per
stage of a traced request without importing `server/`; every ingress
(`server/http.py`, `parallel/net.py`) starts its root span here, and the
OTLP exporter (`server/trace.py`) is a sink of the collector.

Spans carry (trace_id, span_id, parent_id, name, tags, start/duration)
and propagate across processes via a `cnos-trace-id` header on both the
user HTTP API and the node-to-node RPC plane (reference
common/trace/src/span_ext.rs, http/http_ctx.rs). Collection is an
in-memory ring per process, queryable through `GET /debug/traces`.

Clocks: `start_ns` is the wall clock (`time.time_ns()`), so spans of
several processes — and a profiler trace aligned by its wall-time mark —
share an axis; `duration_ns` is a difference of the monotonic
`time.perf_counter_ns()`, so a clock step never bends an interval.
"""
from __future__ import annotations

import contextvars
import random
import secrets
import time
from contextlib import contextmanager

from . import lockwatch

TRACE_HEADER = "cnos-trace-id"

# A span id names a span inside its trace: an identifier, not a secret. It
# is drawn in user space, from a generator seeded once from the OS —
# `secrets.token_hex` is a system call that releases the GIL, a traced
# request opens 45–150 stage spans, and beside the two thread-clock reads
# of a traced stage it was a third of what the stage cost on the chip's
# host and a GIL hand-over a span under concurrent clients (PERF.md §6,
# PR 37)
_span_ids = random.Random()


def _new_span_id() -> str:
    return "%08x" % _span_ids.getrandbits(32)

_current_span: contextvars.ContextVar = contextvars.ContextVar(
    "cnos_current_span", default=None)


class Span:
    __slots__ = ("trace_id", "span_id", "parent_id", "name", "tags",
                 "start_ns", "duration_ns", "_t0", "_collector", "_token")

    def __init__(self, trace_id: str, span_id: str, parent_id: str | None,
                 name: str, collector: "TraceCollector"):
        self.trace_id = trace_id
        self.span_id = span_id
        self.parent_id = parent_id
        self.name = name
        self.tags: dict = {}
        self.start_ns = time.time_ns()
        self._t0 = time.perf_counter_ns()
        self.duration_ns = 0
        self._collector = collector
        self._token = None

    def set_tag(self, key: str, value):
        self.tags[key] = value
        return self

    def __enter__(self):
        self._token = _current_span.set(self)
        return self

    def __exit__(self, exc_type, exc, tb):
        if self._token is not None:
            _current_span.reset(self._token)
        self.finish(None if exc is None else str(exc))
        return False

    @contextmanager
    def activate(self):
        """Make this the context span for the block WITHOUT finishing it:
        for a span that several threads work under in turn (an HTTP
        request: the worker thread executes, the event loop renders) and
        whose owner calls `finish()` once, at the end."""
        token = _current_span.set(self)
        try:
            yield self
        finally:
            _current_span.reset(token)

    def finish(self, error: str | None = None) -> None:
        self.duration_ns = time.perf_counter_ns() - self._t0
        if error is not None:
            self.tags["error"] = error
        self._collector.record(self)

    def to_dict(self) -> dict:
        return {"trace_id": self.trace_id, "span_id": self.span_id,
                "parent_id": self.parent_id, "name": self.name,
                "tags": dict(self.tags), "start_ns": self.start_ns,
                "duration_ns": self.duration_ns}


class TraceCollector:
    """Bounded ring of finished spans (reference keeps them in minitrace's
    collector until OTLP flush; embedded deployments query them back)."""

    def __init__(self, capacity: int = 4096):
        self.capacity = capacity
        self._spans: list[dict] = []
        self._lock = lockwatch.Lock("trace.collector")
        self.sinks: list = []   # extra consumers (OTLP exporter)

    def record(self, span: Span):
        d = span.to_dict()
        with self._lock:
            self._spans.append(d)
            if len(self._spans) > self.capacity:
                del self._spans[:self.capacity // 4]
        for sink in self.sinks:
            try:
                sink(d)
            except Exception:
                pass   # a broken exporter must never fail the traced work

    def spans(self, trace_id: str | None = None,
              limit: int = 500) -> list[dict]:
        with self._lock:
            out = self._spans if trace_id is None else \
                [s for s in self._spans if s["trace_id"] == trace_id]
            return list(out[-limit:])

    # ------------------------------------------------------------- spans
    def span(self, name: str, trace_id: str | None = None,
             parent_id: str | None = None) -> Span:
        """Start a child of the context span, or a root with the given (or
        a fresh) trace id — `Span::from_context` in the reference."""
        cur = _current_span.get()
        if trace_id is None and cur is not None:
            trace_id = cur.trace_id
            parent_id = cur.span_id
        if trace_id is None:
            trace_id = secrets.token_hex(8)
        return Span(trace_id, _new_span_id(), parent_id, name, self)

    def from_headers(self, headers, name: str) -> Span:
        """Continue a trace propagated over HTTP/RPC: header value is
        `trace_id[:parent_span_id]` (reference http_ctx.rs)."""
        raw = headers.get(TRACE_HEADER, "") if headers else ""
        trace_id = parent = None
        if raw:
            trace_id, _, parent = raw.partition(":")
            parent = parent or None
        return self.span(name, trace_id=trace_id, parent_id=parent)


GLOBAL_COLLECTOR = TraceCollector()


def current_span() -> Span | None:
    """The context-active span, if any (profile plane attaches per-stage
    timings to the root span it finds here)."""
    return _current_span.get()


def current_trace_header() -> str | None:
    """Outgoing propagation value for the active span, if any."""
    cur = _current_span.get()
    if cur is None:
        return None
    return f"{cur.trace_id}:{cur.span_id}"
