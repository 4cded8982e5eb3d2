"""Per-query stage profiling — the always-on instrumentation plane.

Every `stage()` / `count()` call lands in the *active query's*
:class:`QueryProfile` (a contextvar installed at ingress by the SQL
executor or by `EXPLAIN ANALYZE`). With no profile in
scope both are a single contextvar read — cheap enough to leave on in
production. Profiles propagate:

  * across the shared scan/decode pools (utils/executor.py re-runs each
    task inside the submitting thread's contextvars.Context), and
  * across RPC hops (parallel/net.py adds a `_profile` marker to the
    payload; the remote handler runs inside its own node-local profile
    and returns it in the reply, where the caller folds it into the
    active profile's `subprofiles`, keyed by node/vnode/method).

Consumers: `EXPLAIN ANALYZE` renders the merged per-stage/per-node
breakdown, HTTP exposes an opt-in summary header plus
`GET /debug/profile?qid=` over the bounded `PROFILES` ring, finished
profiles attach to their root trace span as tags, and the slow-query
log writes threshold-exceeding profiles into usage_schema.

One timeline per request: a profile whose `traced` flag is set (HTTP
ingress sets it when the client sent `X-CnosDB-Profile` or a
`cnos-trace-id` header) makes every `stage()` an interval as well as a
sum — a child `Span` of the context span in the one collector
(`utils/spans.py`; `GET /debug/traces?trace_id=` shows the tree), a
`jax.profiler.TraceAnnotation("cnosdb.<stage>")` on the thread that did
the work (so the interval lies in a profiler trace, on the device
operations' clock), and a bare (start, end) pair the profile holds until
`finish()` has derived `untraced_ms` from it. With the flag off a stage
is one `perf_counter` pair and one `add_ms` (`_Stage`), and reads no
other clock.

Work told from wait: a traced stage also reads `time.thread_time()` at
its two ends and books the difference under `cpu.<stage>` (ms, beside
the stage wherever a stage is shown, and as the span's `cpu_ms` tag).
That is the CPU time of the thread that entered the stage, native code
it calls with the GIL released included; `wall - cpu` is time that
thread waited — for the GIL, a lock, the device, a pool's result or the
disk. A stage that blocks on pool tasks (`scan.native_ms`, `kernel_ms`
around the fan-out) therefore reads ~0 CPU, and the tasks' own stages
carry theirs. Like the walls, `cpu.*` sums over every thread that
entered the stage. `book()` books none (another thread's interval), and
a request that is not traced reads no thread clock.

Stage catalog — every *literal* name passed to stage()/count() must
appear in STAGE_CATALOG (enforced by the `stage-catalog` lint rule in
cnosdb_tpu/analysis); dynamically-built names must use a prefix from
DYNAMIC_STAGE_PREFIXES. Keys ending in `_ms` are durations, `_bytes`
byte totals; everything else is a plain count.
"""
from __future__ import annotations

import contextvars
import sys
import threading
import time
from collections import deque
from contextlib import nullcontext
from . import lockwatch, spans

# The documented profile schema. A name missing here is invisible to
# every consumer of a profile, so the lint plane refuses it.
STAGE_CATALOG: dict[str, str] = {
    "scan_hit": "coordinator scan-snapshot cache hits",
    "scan_miss": "coordinator scan-snapshot cache misses (full decode)",
    "delta_hit": "stale cache entries refreshed by decoding only the "
                 "new TSM files / memcache rows since their token",
    "delta_rows": "rows decoded by delta scans (a full rescan's worth "
                  "means tokens are being invalidated)",
    "scan.cache_ms": "a local scan's scan-cache half, hit or miss: the "
                     "series lookup, the cache key, the vnode's cut and "
                     "the probe — the whole scan on a hit, what precedes "
                     "decode_ms on a miss",
    "decode_ms": "TSM read+decode (cache-miss and delta scans)",
    "scan.plan_ms": "a scan's plan, inside decode_ms: the files, the page "
                    "index lookups, time admission and page constraints, "
                    "row offsets, column typing, the output arrays and "
                    "the native descriptors a (file, column) — with the "
                    "read and merge of every series metadata cannot plan "
                    "(memcache_ms lies inside it)",
    "scan.alloc_ms": "inside scan.plan_ms: the output arrays' allocation "
                     "and clearing (one values + one validity array a "
                     "column, the batch's timestamps)",
    "scan.native_ms": "a scan's native.decode_pages tasks, inside "
                      "decode_ms: one a (file, column), on the decode "
                      "pool (wall, not thread-summed)",
    "scan.trim_ms": "a scan's assembly, inside decode_ms: pages no fast "
                    "lane took, the merged series' splice, the series "
                    "ordinals, string dictionaries, and the row-level "
                    "time trim / survivor gather of every column",
    "scan_plan.indexed_series": "series of the batch planned from the "
                                "files' page indexes by array operations",
    "scan_plan.merged_series": "series that took the per-series read and "
                               "merge instead: unflushed rows in range, a "
                               "matching tombstone, chunks overlapping "
                               "across files, or pages not aligned",
    "scan_plan.index_builds": "page indexes built (one a (file, table), "
                              "by the first scan that touches it; 0 on "
                              "a warmed, frozen store)",
    "memcache_ms": "the scan's memcache share, inside decode_ms: "
                   "materialize() of each touched series' unflushed "
                   "batches up to the cut, and the merge of those rows "
                   "into the series (summed over scan threads)",
    "memcache_wait_ms": "waiting for the vnode's cut lock — held by a "
                        "writer for a batch's publication, the switch or "
                        "a flushed file's entry, never for an apply",
    "memcache.series": "series the scan read through a memcache (each "
                       "takes the per-series path, not the page plan)",
    "memcache.rows": "rows the scan materialized from memcaches, before "
                     "the time predicate",
    "write.parse_ms": "a write request: parse_lines of the body (on a "
                      "thread of the write pool, not the event loop's)",
    "write.lock_wait_ms": "a write request: waiting for the vnode lock "
                          "behind other writers (and an inline flush)",
    "write.wal_ms": "a write request: schema stamp + encode + WAL append "
                    "(+ sync where set), under the vnode lock",
    "write.apply_ms": "a write request: series ids + memcache apply + "
                      "the batch's publication, less any flush",
    "write.flush_ms": "a write request: the inline flush its batch "
                      "triggered by filling the memcache (booked only "
                      "when one ran)",
    "device_decode_ms": "batched device codec kernels within a scan "
                        "(the accelerator half of decode_ms)",
    "device_decode_engagements": "pages decoded by the device-decode "
                                 "lane instead of a host lane",
    "device_decode.put_ms": "device-decode lane: host→device puts of the "
                            "packed group buffers",
    "device_decode.launch_ms": "device-decode lane: the group's codec "
                               "kernel launch (dispatch, asynchronous)",
    "device_decode.pull_ms": "device-decode lane: the blocking "
                             "device→host pull of each group's batch",
    "device_decode.device_calls": "calls the device-decode lane made to "
                                  "the device: each put, kernel launch "
                                  "and group pull (÷ device_decode_"
                                  "engagements = round trips a page)",
    "plan_ms": "parse + analyze + plan_select, the serving plane's "
               "fingerprint / plan-cache / result-cache lookups included",
    "ingress_wait_ms": "HTTP handler entry → worker thread past the "
                       "admission gate (executor hand-off + queue wait)",
    "render_ms": "result set → CSV / JSON / table text",
    "render.percell_columns": "CSV columns of the answer rendered a cell "
                              "at a time: no by-column rule took their "
                              "dtype (0 on int / float / bool / str "
                              "answers)",
    "untraced_ms": "wall_ms minus the union of the request's stage "
                   "intervals: time no span covers (traced requests only)",
    "upload_ms": "host→device column uploads",
    "upload.meta_ms": "inside upload_ms: DeviceBatch._init_meta less its "
                      "pads and puts — the one-pass i32 (sec, ns) split "
                      "— and, where a first/last query first asks "
                      "(rank_dev), the rank's sort (upload.rank_ms)",
    "upload.rank_ms": "inside upload.meta_ms: the first/last time-order "
                      "rank's stable argsort and scatter over every row "
                      "of the batch, where rank_dev() builds it (host "
                      "arithmetic; absent where no first/last query asks)",
    "upload.rank_builds": "first/last time-order ranks sorted and put: 0 "
                          "at every DeviceBatch's build, 1 where a query's "
                          "aggregates first ask for the rank (0 for avg / "
                          "sum / count / min / max)",
    "upload.twin_builds": "device twins built by device_batch(): 1 where "
                          "a batch's twin is built and put, 0 where the "
                          "cached batch's twin is handed back (a delta "
                          "merge's twin from merged_device_batch included)",
    "upload.stage_ms": "inside upload_ms: a column's astype, pad to the "
                       "row size class and valid.all() — host copies",
    "upload.put_ms": "inside upload_ms: the device_put calls alone — the "
                     "enqueue and whatever the runtime does "
                     "synchronously; the transfer itself is waited for "
                     "in kernel.fetch_ms",
    "upload_bytes": "bytes moved host→device by those uploads",
    "fused_launches": "fused filter/bucket/segment programs launched",
    "fused.row_gathers": "fused launches whose program gathers a group id "
                         "for every row outside the run path's fallback "
                         "(first / last, a floating reduction, no run "
                         "bound, the regular mode): 1 such a launch, 0 "
                         "one that maps only its run ends",
    "fused.rank_columns": "aggregated columns a fused launch reduces by "
                          "rank selection (first / last: a segment max "
                          "or min of the rank, its gather a row, the "
                          "selected values' sum); booked at every fused "
                          "launch, 0 where none asks",
    "segment_runs.engaged": "device segment reductions (fused launches, "
                            "segment_aggregate calls, mesh merge programs) "
                            "that reduced contiguous equal-segment runs",
    "segment_runs.fallback": "device segment reductions compiled with the "
                             "run path that counted more runs than their "
                             "static bound and took the row scatter in "
                             "the same program (rows not run-contiguous; "
                             "should read 0)",
    "f64_kept_on_host": "aggregations / top-k selections over FLOAT "
                        "columns kept on the host kernels because the "
                        "scan device does not hold f64 exactly "
                        "(ops/placement.f64_exact)",
    "kernel_ms": "fused segment-aggregate kernels",
    "kernel.pad_ms": "inside kernel_ms: aggregate_column_host's pads of "
                     "values, validity, segment ids and rank to a row "
                     "size class",
    "kernel.dispatch_ms": "inside kernel_ms: the call of a jitted "
                          "aggregate program up to its return — "
                          "segment_aggregate with its implicit puts, the "
                          "fused program with the wait for its dispatch "
                          "lock; a compile lands here",
    "kernel.fetch_ms": "the aggregate's blocking result fetch: what is "
                       "left of the device's run + the device→host "
                       "transfer",
    "merge_ms": "cross-vnode partial merge / device delta-merge",
    "finalize_ms": "vectorized finalizers + output rendering",
    "factorize_ms": "group-key factorization (values → dense codes)",
    "group_count": "output group cardinality per query",
    "matview.hit": "aggregate queries rewritten to read sealed buckets "
                   "from a materialized rollup",
    "matview.miss": "rewrite-eligible aggregate queries no registered "
                    "view subsumed (raw scan)",
    "matview.seed_groups": "accumulator groups seeded from sealed view "
                           "buckets by rewritten queries",
    "ngram_pages_skipped": "string pages pruned before decode by trigram "
                           "signatures (ops/strkernels)",
    "compressed_ms": "compressed-domain lane: page classification + "
                     "closed-form jobs (storage/compressed_domain)",
    "compressed.pages_answered": "pages whose aggregate contribution "
                                 "came from stats/closed forms — never "
                                 "decoded into rows",
    "compressed.jobs_dominated": "first / last closed-form jobs (page, "
                                 "column) the lane did not queue: another "
                                 "planned page proves a newer (last) or "
                                 "older (first) surviving value; booked "
                                 "at every lane, 0 where none",
    "compressed.bytes_materialized": "page bytes that DID enter a decode "
                                     "lane (the ≥5× drop the lane exists "
                                     "to produce on selective scans)",
    "topk.host": "ORDER BY+LIMIT answered by np.partition select-then-"
                 "gather instead of a full sort",
    "topk.device": "ORDER BY+LIMIT thresholds computed by jax.lax.top_k",
    "topk.declined": "ORDER BY+LIMIT shapes outside the top-k fast path "
                     "(nulls/NaN/object keys, k≥n) — full sort",
    "cold.pages_pruned": "cold pages eliminated locally by sidecar zone "
                         "maps/constraints — zero bytes downloaded",
    "serving.plan_hit": "SELECTs answered from a cached analyzed plan "
                        "(parse+analyze+plan all skipped)",
    "serving.plan_rebind": "template fingerprint hits re-bound with new "
                           "literal params (parse+analyze skipped, "
                           "plan_select re-run)",
    "serving.plan_miss": "fingerprintable SELECTs that paid a full "
                         "parse+analyze+plan (then seeded the cache)",
    "serving.result_hit": "SELECTs answered from the ScanToken-validated "
                          "result cache (engine untouched)",
    "serving.result_miss": "result-cache probes whose entry was absent "
                           "or token-stale",
    "serving.result_bypass": "executed SELECTs whose result was not "
                             "cacheable (system/relational path, remote "
                             "vnodes, oversized result)",
    "serving.fused": "point queries executed inside a fused micro-batch "
                     "(shared scan + stacked filter masks)",
    "serving.solo": "batchable point queries that ran alone (no gate "
                    "pressure, or the window closed empty)",
    "serving.fused_scan_ms": "shared scan wall time paid once per fused "
                             "batch (booked to the leader's profile)",
    "serving.remote_fp": "scan_vnode RPCs carrying a serving-plane "
                         "fingerprint (cluster-wide cache attribution)",
    "serving.fused_hedges": "hedged scan attempts fired during a fused "
                            "micro-batch's shared scan (booked to the "
                            "leader; process-wide delta, so concurrent "
                            "queries' hedges can bleed in)",
    "mesh.plan_ms": "mesh exec lane: global segment/label layout + "
                    "shard-major staging (ops/mesh_exec._build_prep)",
    "mesh.mask_ms": "mesh exec lane, inside mesh.plan_ms: host_row_mask "
                    "of every batch",
    "mesh.layout_ms": "mesh exec lane, inside mesh.plan_ms: "
                      "host_group_layout of every batch, the global "
                      "label / field / bucket tables, per-row global "
                      "segment ids, presence and the first/last rank",
    "mesh.stage_ms": "mesh exec lane, inside mesh.plan_ms: the "
                     "shard-major padded staging arrays (segments, "
                     "validity, rank, every column) and the f64 run "
                     "plans",
    "mesh.upload_ms": "mesh exec lane: sharded host→device uploads "
                      "(NamedSharding over the shard axis)",
    "mesh.collective_ms": "mesh exec lane: collective merge programs — "
                          "per-shard partials folded over the mesh in "
                          "batch order (distributed_agg.mesh_merge_"
                          "kernel) + the replicated-result fetch",
    "mesh.launch_ms": "mesh exec lane, inside mesh.collective_ms: "
                      "dispatch of the per-column merge programs (host "
                      "time; nothing is waited for)",
    "mesh.fetch_ms": "mesh exec lane, inside mesh.collective_ms: the "
                     "blocking pulls of the replicated outputs — the "
                     "devices' run of the programs plus the transfer",
    "mesh.columns": "collective merge programs launched (one per "
                    "aggregated column)",
    "mesh.assemble_ms": "mesh exec lane: merged partials → the legacy "
                        "vec-merge AggResult shape",
    "mesh.rows": "rows aggregated through the mesh lane per query",
    "mesh.shards": "mesh devices participating in the collective merge",
    "fanout.launch_ms": "per-vnode aggregate fan-out, inside kernel_ms: "
                        "launch_scan_aggregate of every batch (group "
                        "layout, upload, dispatch), summed over the "
                        "pool's threads",
    "fanout.fetch_ms": "per-vnode aggregate fan-out, inside kernel_ms: "
                       "finish_scan_aggregate of every batch (the "
                       "blocking pull of its partials + assembly), "
                       "summed over the pool's threads",
    "fanout.vnodes": "scan batches fanned out to per-vnode aggregate "
                     "launches (the mesh lane declined or was not asked)",
    "merge.groups": "groups out of the vectorized host merge of "
                    "per-vnode partials (_merge_results_vec)",
    "hedge.fired": "hedged scan attempts launched at a next-ranked "
                   "replica after the adaptive p95 trigger elapsed",
    "hedge.won": "scans answered by a hedge attempt instead of the "
                 "primary (the tail the plane exists to cut)",
    "hedge.cancelled": "losing hedge/primary attempts cancelled through "
                       "the cancel_scan(qid) fan-out after a winner",
    "hedge.suppressed": "hedge triggers that elapsed without firing "
                        "(limiter / no budget / no alternate — proves "
                        "hedging stays tail-only)",
}

# Prefixes for names composed at runtime (skipped by the literal lint
# check but still part of the documented schema):
#   rpc_<method>_ms — server-side wall time of one RPC handler dispatch
#   string_path.<path> — string predicates per strkernels lane
#     (per_unique / ngram_skip / host_fallback)
#   cpu.<stage> — a traced stage's thread CPU time in ms (module docstring)
DYNAMIC_STAGE_PREFIXES = ("rpc_", "string_path.", "cpu.")

_profile: contextvars.ContextVar = contextvars.ContextVar(
    "cnos_query_profile", default=None)

# Error counters are ALWAYS on and process-global (unlike stages): a
# swallowed RPC handler exception with no counter is invisible in
# production. Keyed "area.method" (e.g. "rpc.write_replica"); surfaced
# via /metrics.
_err_lock = lockwatch.Lock("stages.errors")
_errors: dict[str, int] = {}

# a traced profile holds at most this many stage intervals for
# `untraced_ms`; the rest are counted in `dropped` (their sums and spans
# are kept all the same)
MAX_INTERVALS = 1024


class QueryProfile:
    """Stage timings/counters + device telemetry for ONE query.

    Thread-safe: scan/decode pool workers and RPC reply threads all
    accumulate into the submitting query's profile concurrently. The
    lock is a plain leaf mutex (never held across any other acquire).
    """

    __slots__ = ("qid", "sql", "trace_id", "node_id", "started_at",
                 "wall_ms", "error", "ms", "counts", "device",
                 "subprofiles", "traced", "annotate", "intervals",
                 "dropped", "_lock")

    def __init__(self, qid: str | None = None, node_id=None,
                 sql: str | None = None):
        self.qid = qid
        self.sql = sql
        self.trace_id: str | None = None
        self.node_id = node_id
        self.started_at = time.time()
        # one timeline per request: set at ingress (module docstring)
        self.traced = False
        # a write request's profile: its stages are sums (one histogram
        # observation a batch) and, while a profiler trace runs, a
        # `TraceAnnotation("cnosdb.<stage>")` — no collector span, no
        # interval (a TraceAnnotation outside a trace records nothing)
        self.annotate = False
        # (start, end) of each stage on the perf_counter clock, whatever
        # thread it ran on; emptied once finish() has read them
        self.intervals: list[tuple] = []
        self.dropped = 0
        self.wall_ms: float | None = None
        self.error: str | None = None
        self.ms: dict[str, float] = {}
        self.counts: dict[str, int] = {}
        self.device: dict = {}
        # remote per-node sub-profiles: [{node, addr, method, vnode,
        # ms, counts}, ...] — appended by net.rpc_call as replies land
        self.subprofiles: list[dict] = []
        self._lock = threading.Lock()

    # ------------------------------------------------------- accumulation
    def add_ms(self, name: str, dt_ms: float) -> None:
        with self._lock:
            self.ms[name] = self.ms.get(name, 0.0) + dt_ms

    def add_count(self, name: str, n: int = 1) -> None:
        with self._lock:
            self.counts[name] = self.counts.get(name, 0) + n

    def add_interval(self, t0: float, t1: float) -> None:
        if self.wall_ms is not None:
            return      # sealed: what follows (rendering) is not its wall
        with self._lock:
            if len(self.intervals) < MAX_INTERVALS:
                self.intervals.append((t0, t1))
            else:
                self.dropped += 1

    def merge_remote(self, entry: dict) -> None:
        """Fold one remote node's wire sub-profile in (keyed by
        node/vnode/method — the coordinator-side merge keeps them
        separate so EXPLAIN ANALYZE can attribute per node)."""
        with self._lock:
            self.subprofiles.append(entry)

    def merge_child(self, child: "QueryProfile") -> None:
        """Fold a nested profile (e.g. EXPLAIN ANALYZE's inner query)
        into this one so its stages aren't lost to the outer scope."""
        with child._lock:
            ms = dict(child.ms)
            counts = dict(child.counts)
            subs = list(child.subprofiles)
        with self._lock:
            for k, v in ms.items():
                self.ms[k] = self.ms.get(k, 0.0) + v
            for k, v in counts.items():
                self.counts[k] = self.counts.get(k, 0) + v
            self.subprofiles.extend(subs)

    # ---------------------------------------------------------- rendering
    def snapshot(self) -> dict:
        """Local stage map: rounded `*_ms` floats merged with integer
        counters, sorted by key."""
        with self._lock:
            out = {k: round(v, 2) for k, v in sorted(self.ms.items())}
            out.update(sorted(self.counts.items()))
            return out

    def to_wire(self) -> dict:
        """Compact reply-envelope form for the RPC plane."""
        with self._lock:
            return {"node": self.node_id,
                    "ms": {k: round(v, 3) for k, v in self.ms.items()},
                    "counts": dict(self.counts)}

    def node_stages(self) -> dict[str, dict]:
        """Merged per-node view: node label → {stage: value}. Local
        stages land under this profile's node id; each remote
        sub-profile folds into its originating node's cell."""
        local = str(self.node_id) if self.node_id is not None else "local"
        with self._lock:
            out: dict[str, dict] = {local: {}}
            for k, v in self.ms.items():
                out[local][k] = round(out[local].get(k, 0.0) + v, 3)
            for k, v in self.counts.items():
                out[local][k] = out[local].get(k, 0) + v
            for sub in self.subprofiles:
                node = sub.get("node")
                label = str(node) if node is not None \
                    else str(sub.get("addr", "remote"))
                cell = out.setdefault(label, {})
                for k, v in (sub.get("ms") or {}).items():
                    cell[k] = round(cell.get(k, 0.0) + v, 3)
                for k, v in (sub.get("counts") or {}).items():
                    cell[k] = cell.get(k, 0) + v
            return out

    def stage_totals(self) -> dict:
        """Cluster-wide totals: every node's stages summed per name."""
        totals: dict = {}
        for cell in self.node_stages().values():
            for k, v in cell.items():
                totals[k] = round(totals.get(k, 0) + v, 3)
        return totals

    def to_dict(self) -> dict:
        with self._lock:
            return {"qid": self.qid, "sql": self.sql,
                    "trace_id": self.trace_id, "node_id": self.node_id,
                    "started_at": self.started_at, "wall_ms": self.wall_ms,
                    "error": self.error,
                    "ms": {k: round(v, 3) for k, v in sorted(self.ms.items())},
                    "counts": dict(sorted(self.counts.items())),
                    "device": dict(self.device),
                    "subprofiles": [dict(s) for s in self.subprofiles],
                    "traced": self.traced, "dropped": self.dropped}

    # ---------------------------------------------------------- lifecycle
    def finish(self, wall_ms: float | None = None,
               error: str | None = None) -> "QueryProfile":
        """Stamp wall time + device telemetry. Captures only from
        modules that are ALREADY imported — finishing a profile must
        never drag the jax stack in on a cold text-only query."""
        if wall_ms is not None:
            self.wall_ms = round(wall_ms, 3)
            if self.traced:
                # the request ran [now - wall, now]; what no stage's
                # interval covers, whatever thread it ran on
                hi = time.perf_counter()
                with self._lock:
                    ivs, self.intervals = self.intervals, []
                    self.ms["untraced_ms"] = uncovered_ms(
                        wall_ms, ivs, hi - wall_ms / 1e3, hi)
        if error is not None:
            self.error = error
        dd = sys.modules.get("cnosdb_tpu.ops.device_decode")
        if dd is not None:
            try:
                self.device["device_decode_enabled"] = dd.enabled()
                self.device["device_decode_disabled_reason"] = \
                    dd.disabled_reason()
            except Exception:  # telemetry stamp must never fail the query
                pass
        pl = sys.modules.get("cnosdb_tpu.ops.placement")
        if pl is not None:
            # the resolved scan device: a server resolves it at start, so
            # every served query's profile says what it ran on
            ops = sys.modules["cnosdb_tpu.ops"]
            try:
                self.device.update(pl.device_stamp())
                self.device["compile_cache_dir"] = ops.compile_cache_dir()
            except Exception:  # telemetry stamp must never fail the query
                pass
        return self


def current_profile() -> QueryProfile | None:
    return _profile.get()


class profile_scope:
    """Install `profile` as the active query profile for the block
    (None clears the scope — e.g. background work inside a request
    that must not bill to it)."""

    __slots__ = ("profile", "_token")

    def __init__(self, profile: QueryProfile | None):
        self.profile = profile
        self._token = None

    def __enter__(self):
        self._token = _profile.set(self.profile)
        return self.profile

    def __exit__(self, *exc):
        if self._token is not None:
            _profile.reset(self._token)
        return False


class ProfileRing:
    """Bounded ring of recently finished profiles (dict snapshots),
    queryable by qid — the trace collector's shape, applied to
    profiles so `GET /debug/profile?qid=` works after the fact."""

    def __init__(self, capacity: int = 256):
        self._ring: deque = deque(maxlen=capacity)
        self._lock = lockwatch.Lock("stages.profile_ring")

    def record(self, profile: QueryProfile) -> None:
        with self._lock:
            self._ring.append(profile.to_dict())

    def get(self, qid: str) -> dict | None:
        with self._lock:
            for d in reversed(self._ring):
                if d.get("qid") == str(qid):
                    return d
        return None

    def recent(self, limit: int = 50) -> list[dict]:
        with self._lock:
            out = list(self._ring)[-limit:]
        return [{"qid": d.get("qid"), "sql": d.get("sql"),
                 "trace_id": d.get("trace_id"), "wall_ms": d.get("wall_ms"),
                 "started_at": d.get("started_at"), "error": d.get("error")}
                for d in out]


PROFILES = ProfileRing()


# --------------------------------------------------------------- recording
def uncovered_ms(wall_ms: float, intervals, lo: float, hi: float) -> float:
    """wall_ms minus the length of the union of `intervals` ((start, end)
    in seconds) clipped to [lo, hi] — overlapping threads count once."""
    covered, end = 0.0, lo
    for a, b in sorted(intervals):
        a, b = max(a, end), min(b, hi)
        if b > a:
            covered += b - a
            end = b
    return max(0.0, wall_ms - covered * 1e3)


def _child_span(prof: "QueryProfile", name: str):
    """→ a span under the context span or, with none, a root of the
    profile's trace."""
    return spans.GLOBAL_COLLECTOR.span(
        name, trace_id=None if spans.current_span() is not None
        else prof.trace_id)


def _annotation(prof: "QueryProfile", name: str):
    """→ the profiler annotation of one stage, None where jax is not
    loaded (never import it from here: a text-only query stays jax-free)."""
    jax = sys.modules.get("jax")
    if jax is None:
        return None
    return jax.profiler.TraceAnnotation("cnosdb." + name, qid=str(prof.qid))


class _TracedStage:
    """One stage of a traced request: the sum, the thread's CPU time
    beside it, the collector span, the profiler annotation, and the
    (start, end) pair for `untraced_ms`."""

    __slots__ = ("prof", "name", "span", "ann", "t0", "c0")

    def __init__(self, prof: QueryProfile, name: str):
        self.prof, self.name = prof, name

    def __enter__(self):
        self.span = _child_span(self.prof, self.name)
        self.span.__enter__()
        self.ann = _annotation(self.prof, self.name)
        if self.ann is not None:
            self.ann.__enter__()
        # the thread clock is read inside the wall's two ends: cpu <= wall
        self.t0 = time.perf_counter()
        self.c0 = time.thread_time()
        return self

    def __exit__(self, exc_type, exc, tb):
        cpu_ms = (time.thread_time() - self.c0) * 1e3
        t1 = time.perf_counter()
        if self.ann is not None:
            self.ann.__exit__(exc_type, exc, tb)
        self.span.set_tag("cpu_ms", round(cpu_ms, 3))
        self.span.__exit__(exc_type, exc, tb)
        self.prof.add_ms(self.name, (t1 - self.t0) * 1e3)
        self.prof.add_ms("cpu." + self.name, cpu_ms)
        self.prof.add_interval(self.t0, t1)
        return False


class _Stage:
    """One stage of a request that is not traced: the sum alone — and,
    for a write request's profile, the profiler annotation."""

    __slots__ = ("prof", "name", "ann", "t0")

    def __init__(self, prof: QueryProfile, name: str):
        self.prof, self.name = prof, name

    def __enter__(self):
        self.ann = _annotation(self.prof, self.name) \
            if self.prof.annotate else None
        if self.ann is not None:
            self.ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb):
        self.prof.add_ms(self.name, (time.perf_counter() - self.t0) * 1e3)
        if self.ann is not None:
            self.ann.__exit__(exc_type, exc, tb)
        return False


# with no profile in scope a stage books nothing
_NO_STAGE = nullcontext()


def stage(name: str):
    """→ the context manager of one stage of the active profile."""
    prof = _profile.get()
    if prof is None:
        return _NO_STAGE
    if prof.traced:
        return _TracedStage(prof, name)
    return _Stage(prof, name)


def book(name: str, t0: float) -> None:
    """Book [t0, now] (`perf_counter` seconds), an interval the caller
    timed itself because it starts on another thread (the ingress wait).
    The sum always; for a traced request also the span and the pair for
    `untraced_ms`. No profiler annotation: that cannot be back-dated. No
    `cpu.<stage>`: the interval is not this thread's."""
    prof = _profile.get()
    if prof is None:
        return
    t1 = time.perf_counter()
    prof.add_ms(name, (t1 - t0) * 1e3)
    if not prof.traced:
        return
    span = _child_span(prof, name)
    span.duration_ns = int((t1 - t0) * 1e9)
    span.start_ns -= span.duration_ns
    spans.GLOBAL_COLLECTOR.record(span)
    prof.add_interval(t0, t1)


def count(name: str, n: int = 1) -> None:
    prof = _profile.get()
    if prof is not None:
        prof.add_count(name, n)


def count_error(name: str, n: int = 1) -> None:
    """Always-on process-global failure counter (never profile-scoped)."""
    with _err_lock:
        _errors[name] = _errors.get(name, 0) + n


def errors_snapshot() -> dict[str, int]:
    with _err_lock:
        return dict(sorted(_errors.items()))


def reset() -> None:
    """Clear the process-global error counters (test isolation)."""
    with _err_lock:
        _errors.clear()
