"""High-QPS serving plane: plan cache, result cache, fused micro-batches.

Millions of users means thousands of *small* concurrent queries, not one
big scan — and without this plane every request re-parses, re-plans and
pays its own scan + dispatch. Three layers compose (each independently
sound, each skippable):

  1. **Fingerprint + prepared-plan cache.** `fingerprint()` normalizes a
     SELECT at the token level — number/string literals hoist into a
     parameter vector, everything else renders canonically — so every
     member of a dashboard/point-query family shares one fingerprint.
     The plan cache keys analyzed statements + plans on
     ``(tenant, db, fingerprint, params)``; an exact hit skips
     parse+analyze+plan entirely, and a *template* hit (same fingerprint,
     new params) re-binds the literals into the cached analyzed AST and
     pays only `plan_select`.
  2. **Result cache.** Keyed on ``(tenant, db, fingerprint, params)``
     with the table's ScanToken map (`Coordinator.table_tokens`) captured
     BEFORE execution — the same conservative token-before-decode
     ordering the coordinator scan cache uses, so a racing write makes a
     stored entry miss, never serve stale. A probe revalidates the
     current token map: any flush / delete / compaction / tier / DDL
     event bumps a token (or the schema version) and the entry dies — no
     TTL guessing. Destructive write paths additionally push eager
     eviction through :func:`invalidate` (fault point
     ``serving.invalidate``); correctness never depends on that push,
     only hygiene does.
  3. **Fused micro-batching.** Under admission-gate pressure, compatible
     concurrent point queries (same table / schema / scanned columns /
     time ranges — filter-only differences) rendezvous in
     :class:`MicroBatcher`: one shared scan, one stacked-mask filter
     evaluation (`ops.tpu_exec.stacked_filter_masks`), then per-member
     demux under each member's own deadline + QueryProfile so EXPLAIN
     ANALYZE inside a fused batch still reports honestly and a member
     whose deadline dies mid-batch sheds alone.

``CNOSDB_SERVING=0`` disables all three layers (the executor then never
constructs a ServingPlane — byte-identical legacy behavior). Telemetry:
``cnosdb_serving_total{layer,outcome}`` + cache entry/byte gauges on
/metrics, ``serving.*`` stage-catalog counters in per-query profiles.
"""
from __future__ import annotations

import dataclasses
import os
import re
import threading
import time
import weakref
from collections import OrderedDict

import numpy as np

from .. import faults
from ..utils import deadline as deadline_mod
from ..utils import lockwatch, stages

faults.register_point(
    "serving.invalidate", __name__,
    desc="between a destructive mutation committing and the serving "
         "result cache evicting its entries (eviction lost = crash "
         "analog; token revalidation must still prevent stale reads)")

# --------------------------------------------------------------- telemetry
# process-global {(layer, outcome): n} counters behind cnosdb_serving_total
_counters_lock = lockwatch.Lock("serving.counters")
_COUNTERS: dict[tuple[str, str], int] = {}
_WIDTHS: dict[int, int] = {}          # fused-batch width histogram


def _count_serving(layer: str, outcome: str, n: int = 1) -> None:
    with _counters_lock:
        k = (layer, outcome)
        _COUNTERS[k] = _COUNTERS.get(k, 0) + n


def counters_snapshot() -> dict[tuple[str, str], int]:
    with _counters_lock:
        return dict(_COUNTERS)


def width_histogram() -> dict[int, int]:
    with _counters_lock:
        return dict(_WIDTHS)


def reset_counters() -> None:
    """Test isolation for the process-global serving counters."""
    with _counters_lock:
        _COUNTERS.clear()
        _WIDTHS.clear()


# planes register here so storage/DDL-side invalidation hooks (which have
# no executor reference) can fan eviction in
_PLANES: "weakref.WeakSet" = weakref.WeakSet()


def cache_stats() -> dict[str, tuple[int, int]]:
    """{cache: (entries, bytes)} across registered planes, for /metrics."""
    plan_e = plan_b = res_e = res_b = 0
    for p in list(_PLANES):
        e, b = p.plan_cache.stats()
        plan_e += e
        plan_b += b
        e, b = p.result_cache.stats()
        res_e += e
        res_b += b
    return {"plan_cache": (plan_e, plan_b),
            "result_cache": (res_e, res_b)}


def invalidate(tenant: str, db: str, table: str | None = None) -> int:
    """Push eager eviction for a destructive event (DDL / DELETE /
    matview refresh / compaction / tiering). Correctness does NOT depend
    on this call — result-cache probes revalidate ScanTokens — so a
    crash or injected fault here loses only hygiene, which is exactly
    what the ``serving.invalidate`` fault point exists to prove."""
    if faults.ENABLED:
        faults.fire("serving.invalidate",
                    tenant=tenant, db=db, table=table or "")
    n = 0
    for p in list(_PLANES):
        n += p.result_cache.invalidate(tenant, db, table)
        n += p.plan_cache.invalidate(tenant, db, table)
    if n:
        _count_serving("result_cache", "invalidate", n)
    return n


def invalidate_owner(owner: str, table: str | None = None) -> int:
    """Owner-string (``tenant.db``) entry point for storage-side hooks
    (compaction, tiering) that never see tenant/db separately."""
    tenant, _, db = owner.partition(".")
    return invalidate(tenant, db, table)


def _serving_bytes_used() -> int:
    s = cache_stats()
    return s["plan_cache"][1] + s["result_cache"][1]


def _serving_reclaim(target_bytes: int) -> int:
    """Broker reclaim: shrink result caches LRU-first across every
    registered plane — plan caches are entry-capped and tiny, results
    hold the bytes."""
    freed = 0
    for p in list(_PLANES):
        if freed >= target_bytes:
            break
        freed += p.result_cache.reclaim(target_bytes - freed)
    return freed


def _register_serving_pool() -> None:
    from . import memory as _memory

    _memory.register_pool("serving",
                          usage_fn=_serving_bytes_used,
                          reclaim=_serving_reclaim)


_register_serving_pool()


# ------------------------------------------------------------ fingerprint
# scalars whose value depends on call time / session — a cached plan or
# result would freeze them (the executor folds the current_* family at
# plan time, and now() bakes into plan-time time ranges)
_UNCACHEABLE_FUNCS = frozenset({
    "now", "current_timestamp", "current_time", "current_date", "today",
    "current_user", "current_tenant", "current_database", "current_role",
    "random", "uuid", "sleep"})

_SELECT_RE = re.compile(r"^\s*select\b", re.IGNORECASE)


def fingerprint(sql: str):
    """→ ``(fingerprint, params)`` or None when not fingerprintable.

    Token-level normalization over `sql.parser.tokenize`: number/string
    literals become placeholders (values collected in token order),
    idents render lowercased (quoted idents keep their quotes so
    ``"a b"`` can never collide with ``a b``). Declined shapes — anything
    that isn't a single SELECT, session variables, and the
    time/session-dependent scalar family — return None and take the
    legacy path."""
    if not _SELECT_RE.match(sql):
        return None
    from ..sql.parser import tokenize

    try:
        toks = tokenize(sql)
    except Exception:
        return None     # the real parser will produce the real error
    parts: list[str] = []
    params: list = []
    it = iter(range(len(toks)))
    for i in it:
        t = toks[i]
        if t.kind == "eof":
            break
        if t.kind == "op" and t.value == ";":
            # a single trailing ';' is fine; anything after it means a
            # multi-statement request — not fingerprintable
            if any(toks[j].kind != "eof" for j in range(i + 1, len(toks))):
                return None
            break
        if t.kind == "number":
            parts.append("?")
            params.append(_num_value(t.value))
        elif t.kind == "string":
            parts.append("?s")
            params.append(t.value)
        elif t.kind == "sysvar":
            return None     # session-scoped variable
        elif t.kind == "ident":
            if t.value in _UNCACHEABLE_FUNCS:
                return None
            if sql[t.pos] in "\"`":
                parts.append(f'"{t.value}"')
            else:
                parts.append(t.value)
        else:
            parts.append(str(t.value))
    return " ".join(parts), tuple(params)


def _num_value(text: str):
    if re.fullmatch(r"\d+", text):
        return int(text)
    return float(text)


def _vkey(v):
    """Type-tagged equality key: 1, 1.0 and True must not unify when
    matching token params against AST literal values."""
    return (type(v).__name__, v)


# --------------------------------------------------- AST literal rebinding
def _walk_literals(node, out: list) -> None:
    from ..sql.expr import Literal

    if isinstance(node, Literal):
        out.append(node)
        return
    if dataclasses.is_dataclass(node) and not isinstance(node, type):
        for f in dataclasses.fields(node):
            _walk_literals(getattr(node, f.name), out)
        return
    if isinstance(node, (list, tuple)):
        for v in node:
            _walk_literals(v, out)


def _rebuild_literals(node, repl: dict[int, object], idx: list):
    """Structural copy of `node` with literal ordinal i replaced by
    Literal(repl[i]); untouched subtrees are shared, and the walk order
    is identical to `_walk_literals` so ordinals line up."""
    from ..sql.expr import Literal

    if isinstance(node, Literal):
        i = idx[0]
        idx[0] += 1
        if i in repl:
            return Literal(repl[i])
        return node
    if dataclasses.is_dataclass(node) and not isinstance(node, type):
        changes = {}
        for f in dataclasses.fields(node):
            v = getattr(node, f.name)
            nv = _rebuild_literals(v, repl, idx)
            if nv is not v:
                changes[f.name] = nv
        return dataclasses.replace(node, **changes) if changes else node
    if isinstance(node, list):
        nl = [_rebuild_literals(v, repl, idx) for v in node]
        if any(a is not b for a, b in zip(nl, node)):
            return nl
        return node
    if isinstance(node, tuple):
        nt = tuple(_rebuild_literals(v, repl, idx) for v in node)
        if any(a is not b for a, b in zip(nt, node)):
            return nt
        return node
    return node


def _template_slots(stmt, params: tuple):
    """→ (slots, n_ast_literals) mapping the analyzed statement's literal
    positions (AST literals in walk order, then LIMIT, then OFFSET) onto
    token-param indices, or None when the statement is not rebindable —
    param values must be pairwise distinct (else a value→slot map is
    ambiguous) and the literal multiset must equal the param multiset
    (parser constant-folding / interval+timestamp transforms break the
    literal↔token correspondence, which this check detects)."""
    lits: list = []
    _walk_literals(stmt, lits)
    values = [lit.value for lit in lits]
    n_ast = len(values)
    if stmt.limit is not None:
        values.append(stmt.limit)
    if stmt.offset is not None:
        values.append(stmt.offset)
    pkeys = [_vkey(p) for p in params]
    if len(set(pkeys)) != len(pkeys):
        return None
    if sorted(map(repr, pkeys)) != sorted(repr(_vkey(v)) for v in values):
        return None
    index = {k: i for i, k in enumerate(pkeys)}
    slots = [index[_vkey(v)] for v in values]
    return slots, n_ast


def _rebind(entry: "_PlanEntry", new_params: tuple):
    """Template hit → a new analyzed statement with `new_params` bound.
    Returns None (caller re-parses) when a param changed python type —
    the analyzer's type checks were only run for the template's types."""
    for old, new in zip(entry.params, new_params):
        if type(old) is not type(new):
            return None
    slots, n_ast = entry.slots
    repl = {}
    limit = offset = None
    for j, slot in enumerate(slots):
        if j < n_ast:
            repl[j] = new_params[slot]
        elif j == n_ast and entry.stmt.limit is not None:
            limit = new_params[slot]
        else:
            offset = new_params[slot]
    if (limit is not None and not isinstance(limit, int)) \
            or (offset is not None and not isinstance(offset, int)):
        return None
    stmt = _rebuild_literals(entry.stmt, repl, [0])
    changes = {}
    if limit is not None:
        changes["limit"] = limit
    if offset is not None:
        changes["offset"] = offset
    return dataclasses.replace(stmt, **changes) if changes else stmt


# ------------------------------------------------------------- plan cache
class _PlanEntry:
    __slots__ = ("stmt", "plan", "tenant", "db", "table", "schema_version",
                 "params", "slots")

    def __init__(self, stmt, plan, tenant, db, table, schema_version,
                 params, slots):
        self.stmt = stmt
        self.plan = plan
        self.tenant = tenant
        self.db = db
        self.table = table
        self.schema_version = schema_version
        self.params = params
        self.slots = slots      # (slot list, n_ast_literals) or None


class PlanCache:
    """Bounded LRU of analyzed+planned SELECTs keyed
    ``(tenant, db, fingerprint, params)`` plus one rebindable template
    per fingerprint. Entries pin nothing mutable: execution revalidates
    the schema version and re-runs the privilege check."""

    def __init__(self, max_entries: int = 512):
        self.max_entries = max(8, int(max_entries))
        self._lock = lockwatch.Lock("serving.plan_cache")
        self._entries: OrderedDict = OrderedDict()
        self._templates: dict = {}    # (tenant, db, fp) -> _PlanEntry

    def get_exact(self, key) -> "_PlanEntry | None":
        with self._lock:
            e = self._entries.get(key)
            if e is not None:
                self._entries.move_to_end(key)
            return e

    def get_template(self, tenant, db, fp) -> "_PlanEntry | None":
        with self._lock:
            return self._templates.get((tenant, db, fp))

    def store(self, key, entry: "_PlanEntry") -> None:
        tenant, db, fp, _params = key
        with self._lock:
            self._entries[key] = entry
            self._entries.move_to_end(key)
            while len(self._entries) > self.max_entries:
                old_key, old = self._entries.popitem(last=False)
                _count_serving("plan_cache", "evict")
                tk = (old_key[0], old_key[1], old_key[2])
                if self._templates.get(tk) is old:
                    del self._templates[tk]
            if entry.slots is not None:
                self._templates[(tenant, db, fp)] = entry

    def evict(self, key) -> None:
        with self._lock:
            e = self._entries.pop(key, None)
            if e is not None:
                _count_serving("plan_cache", "evict")
            tk = (key[0], key[1], key[2])
            if self._templates.get(tk) is e and e is not None:
                del self._templates[tk]

    def invalidate(self, tenant, db, table=None) -> int:
        with self._lock:
            dead = [k for k, e in self._entries.items()
                    if e.tenant == tenant and e.db == db
                    and (table is None or e.table == table)]
            for k in dead:
                e = self._entries.pop(k)
                tk = (k[0], k[1], k[2])
                if self._templates.get(tk) is e:
                    del self._templates[tk]
            return len(dead)

    def stats(self) -> tuple[int, int]:
        with self._lock:
            return len(self._entries), 0


# ----------------------------------------------------------- result cache
class _ResultEntry:
    __slots__ = ("rs", "tokens", "stmt", "tenant", "db", "table", "nbytes")

    def __init__(self, rs, tokens, stmt, tenant, db, table, nbytes):
        self.rs = rs
        self.tokens = tokens
        self.stmt = stmt
        self.tenant = tenant
        self.db = db
        self.table = table
        self.nbytes = nbytes


def _rs_nbytes(rs) -> int:
    n = 256
    for c in rs.columns:
        n += int(getattr(c, "nbytes", 0) or 0)
        if getattr(c, "dtype", None) == object:
            n += 64 * len(c)    # boxed-object estimate
    return n


class ResultCache:
    """Byte-capped LRU of finished ResultSets keyed
    ``(tenant, db, fingerprint, params)``; every entry carries the
    ScanToken map captured before its execution and is revalidated
    against the live map on probe. Errors are never stored (negative-
    entry suppression) — a failing query re-executes every time."""

    def __init__(self, max_bytes: int, max_entries: int = 4096):
        self.max_bytes = max(1 << 20, int(max_bytes))
        self.max_entries = max(16, int(max_entries))
        self._lock = lockwatch.Lock("serving.result_cache")
        self._entries: OrderedDict = OrderedDict()
        self._bytes = 0

    def get(self, key) -> "_ResultEntry | None":
        with self._lock:
            e = self._entries.get(key)
            if e is not None:
                self._entries.move_to_end(key)
            return e

    def store(self, key, entry: "_ResultEntry") -> bool:
        if entry.nbytes > self.max_bytes // 8:
            return False    # one giant result must not wipe the cache
        with self._lock:
            old = self._entries.pop(key, None)
            if old is not None:
                self._bytes -= old.nbytes
            while self._entries and (
                    len(self._entries) >= self.max_entries
                    or self._bytes + entry.nbytes > self.max_bytes):
                _k, ev = self._entries.popitem(last=False)
                self._bytes -= ev.nbytes
                _count_serving("result_cache", "evict")
            self._entries[key] = entry
            self._bytes += entry.nbytes
        return True

    def evict(self, key) -> None:
        with self._lock:
            e = self._entries.pop(key, None)
            if e is not None:
                self._bytes -= e.nbytes

    def reclaim(self, target_bytes: int) -> int:
        """Memory-broker shrink: pop LRU entries until `target_bytes`
        are freed — a lost entry is just a cache miss."""
        freed = 0
        with self._lock:
            while self._entries and freed < target_bytes:
                _k, ev = self._entries.popitem(last=False)
                freed += ev.nbytes
                _count_serving("result_cache", "evict")
            self._bytes = max(0, self._bytes - freed)
        return freed

    def invalidate(self, tenant, db, table=None) -> int:
        with self._lock:
            dead = [k for k, e in self._entries.items()
                    if e.tenant == tenant and e.db == db
                    and (table is None or e.table == table)]
            for k in dead:
                self._bytes -= self._entries.pop(k).nbytes
            return len(dead)

    def stats(self) -> tuple[int, int]:
        with self._lock:
            return len(self._entries), self._bytes


# -------------------------------------------------------- fused batching
class _Member:
    __slots__ = ("plan", "field_names", "prof", "ctx", "result", "error")

    def __init__(self, plan, field_names):
        self.plan = plan
        self.field_names = field_names
        self.prof = stages.current_profile()
        self.ctx = deadline_mod.current()
        self.result = None
        self.error = None


class _Group:
    __slots__ = ("key", "members", "closed", "done", "failed")

    def __init__(self, key):
        self.key = key
        self.members: list[_Member] = []
        self.closed = False
        self.done = threading.Event()
        self.failed = False


class MicroBatcher:
    """Group-commit rendezvous for compatible point queries.

    The first submitter of a compatibility key becomes leader, holds a
    ~`window_ms` collection window, then executes ONE shared scan and
    demuxes per-member results (`QueryExecutor._exec_raw_batches` with
    precomputed stacked masks). Followers joining an open group are free;
    opening a NEW group only happens under admission-gate pressure (or
    ``CNOSDB_SERVING_BATCH_FORCE=1``), so an idle node never pays the
    window latency. A group-level failure falls every member back to its
    solo path — fusion is an optimization, never a new failure mode."""

    def __init__(self, plane, window_ms: float = 2.0, max_width: int = 32):
        self._plane = plane
        self.window_s = max(0.0, float(window_ms)) / 1e3
        self.max_width = max(2, int(max_width))
        self.force = os.environ.get(
            "CNOSDB_SERVING_BATCH_FORCE", "0") == "1"
        self._lock = lockwatch.Lock("serving.batcher")
        self._groups: dict = {}
        self._gate = None

    def attach_gate(self, gate) -> None:
        self._gate = gate

    def _pressure(self) -> bool:
        g = self._gate
        if g is None:
            return False
        running, queued = g.pressure()
        return queued > 0 or running >= g.max_concurrent

    def decline(self, reason: str) -> None:
        """Book an unfusable shape — only while batching is engaged, so
        idle traffic doesn't drown the decline counters."""
        if self.force or self._pressure():
            _count_serving("batch", f"declined_{reason}")

    def submit(self, executor, plan, tenant: str, db: str,
               field_names: list[str]):
        """→ the member's ResultSet, or None = run the solo path."""
        key = (tenant, db, plan.table,
               getattr(plan.schema, "schema_version", None),
               tuple(field_names),
               tuple((r.min_ts, r.max_ts) for r in plan.time_ranges.ranges))
        m = _Member(plan, list(field_names))
        g = None
        leader = False
        with self._lock:
            open_g = self._groups.get(key)
            if open_g is not None and not open_g.closed \
                    and len(open_g.members) < self.max_width:
                open_g.members.append(m)
                g = open_g
            elif self.force or self._pressure():
                g = _Group(key)
                g.members.append(m)
                self._groups[key] = g
                leader = True
        if g is None:
            _count_serving("batch", "solo")
            stages.count("serving.solo")
            return None
        if leader:
            _count_serving("batch", "leader_open")
            return self._lead(executor, g, m, tenant, db)
        return self._await_member(g, m)

    def _lead(self, executor, g: _Group, m: _Member, tenant, db):
        if self.window_s:
            time.sleep(self.window_s)
        with self._lock:
            g.closed = True
            if self._groups.get(g.key) is g:
                del self._groups[g.key]
            members = list(g.members)
        if len(members) == 1:
            g.done.set()
            _count_serving("batch", "solo")
            stages.count("serving.solo")
            return None     # nobody joined: run the plain solo path
        try:
            _fused_exec(executor, members, tenant, db)
            with _counters_lock:
                _WIDTHS[len(members)] = _WIDTHS.get(len(members), 0) + 1
            _count_serving("batch", "fused", len(members))
        except BaseException:
            g.failed = True
            _count_serving("batch", "declined_leader_error", len(members))
            raise
        finally:
            g.done.set()
        if m.error is not None:
            raise m.error
        return m.result

    def _await_member(self, g: _Group, m: _Member):
        while not g.done.wait(0.05):
            if m.ctx is not None:
                # shed ONLY this member: leader results for it are
                # discarded, the deadline error propagates now
                m.ctx.check()
        if g.failed:
            self.decline("leader_error")
            return None     # leader-side failure: fall back to solo
        if m.error is not None:
            raise m.error
        return m.result


def _fused_exec(executor, members: list[_Member], tenant: str, db: str):
    """Leader body: one shared scan (widened tag domains when members
    disagree — each member's residual filter re-checks its own tags),
    one stacked-mask evaluation per batch, then per-member projection
    under that member's own deadline scope + QueryProfile."""
    from contextlib import nullcontext

    from ..models.predicate import ColumnDomains
    from ..ops.tpu_exec import stacked_filter_masks
    from ..sql.executor import _batches_bytes, _schema_padding

    plan0 = members[0].plan
    doms = plan0.tag_domains
    for m in members[1:]:
        if m.plan.tag_domains is not doms \
                and repr(m.plan.tag_domains) != repr(doms):
            doms = ColumnDomains.all()
            break
    # hedged members under fused batches: the shared scan runs ONCE under
    # the leader's deadline, so any hedges its remote splits fire serve
    # every member of the group — book the delta to the leader's profile
    # (process-wide counters, so concurrent queries' hedges can bleed in;
    # the count is attribution telemetry, not an exact invariant)
    from ..parallel import health as health_mod

    h0 = sum(v for (o, _r), v in health_mod.counters_snapshot()[0].items()
             if o == "fired")
    with stages.stage("serving.fused_scan_ms"):
        batches = executor.coord.scan_table(
            tenant, db, plan0.table, time_ranges=plan0.time_ranges,
            tag_domains=doms, field_names=members[0].field_names)
    h1 = sum(v for (o, _r), v in health_mod.counters_snapshot()[0].items()
             if o == "fired")
    if h1 > h0:
        stages.count("serving.fused_hedges", h1 - h0)
    filters = [m.plan.filter for m in members]
    filter_cols = set()
    for f in filters:
        if f is not None:
            filter_cols |= f.columns()
    with executor.memory_pool.reservation(
            _batches_bytes(batches), f"fused scan of {plan0.table}"):
        shared = []
        for b in batches:
            env = executor._raw_batch_env(plan0.schema, b)
            for c in filter_cols:
                if c not in env:
                    env[c] = _schema_padding(plan0.schema, c, b.n_rows)
                    env[f"__valid__:{c}"] = np.zeros(b.n_rows, dtype=bool)
            masks = stacked_filter_masks(env, filters, b.n_rows,
                                         set(b.fields))
            shared.append((b.n_rows, env, masks))
        for i, m in enumerate(members):
            scope = (stages.profile_scope(m.prof)
                     if m.prof is not stages.current_profile()
                     else nullcontext())
            with scope:
                try:
                    if m.ctx is not None:
                        m.ctx.check()    # shed only this member
                    stages.count("serving.fused")
                    prepared = [(env, masks[i], n)
                                for (n, env, masks) in shared]
                    m.result = executor._exec_raw_batches(
                        m.plan, None, prepared=prepared)
                except BaseException as e:
                    m.error = e


# ------------------------------------------------------------ the plane
class ServingPlane:
    """Per-executor serving tier; all state process-local. Constructed by
    QueryExecutor unless ``CNOSDB_SERVING=0``."""

    def __init__(self, executor):
        self._executor = weakref.ref(executor)
        self.plan_cache = PlanCache(max_entries=int(os.environ.get(
            "CNOSDB_SERVING_PLAN_ENTRIES", "512")))
        self.result_cache = ResultCache(max_bytes=int(float(os.environ.get(
            "CNOSDB_SERVING_RESULT_MB", "64")) * (1 << 20)))
        self.batcher = MicroBatcher(self, window_ms=float(os.environ.get(
            "CNOSDB_SERVING_BATCH_WINDOW_MS", "2")))
        self._tls = threading.local()
        self._fp_lock = lockwatch.Lock("serving.fp_memo")
        self._fp_memo: OrderedDict = OrderedDict()
        _PLANES.add(self)

    def attach_gate(self, gate) -> None:
        self.batcher.attach_gate(gate)

    # ---------------------------------------------------------- fingerprint
    def _fingerprint(self, sql: str):
        if not _SELECT_RE.match(sql):
            return None     # DML/DDL: not even worth a memo slot
        with self._fp_lock:
            hit = self._fp_memo.get(sql)
            if hit is not None:
                self._fp_memo.move_to_end(sql)
                return None if hit == "uncacheable" else hit
        fpp = fingerprint(sql)
        with self._fp_lock:
            self._fp_memo[sql] = fpp if fpp is not None else "uncacheable"
            self._fp_memo.move_to_end(sql)
            while len(self._fp_memo) > 1024:
                self._fp_memo.popitem(last=False)
        return fpp

    # ------------------------------------------------------------- serving
    def try_execute(self, sql: str, session):
        """Serving-plane fast path for one request; → list[ResultSet] or
        None = take the legacy parse/plan/execute path. Every early None
        books an outcome (serving-accounting lint rule)."""
        ex = self._executor()
        if ex is None:
            _count_serving("result_cache", "bypass")
            return None
        # same kill window the legacy loop has before each statement — a
        # KILLed query must not be answered from cache
        ex.tracker.check_cancelled(ex._tls.qid)
        if not _SELECT_RE.match(sql):
            # DML/DDL: invisible to the serving plane by design — kept a
            # separate outcome so SELECT bypasses stay a useful signal
            _count_serving("result_cache", "non_select")
            return None
        # the lookups are the plan layer's time: plan_ms covers them and,
        # on a miss, the parse + analyze + plan_select behind them
        with stages.stage("plan_ms"):
            fpp = self._fingerprint(sql)
            if fpp is None:
                # non-fingerprintable SELECT (session-dependent scalar,
                # multi-statement, session var): invisible to all three
                # layers
                _count_serving("result_cache", "bypass")
                return None
            fp, params = fpp
            key = (session.tenant, session.database, fp, params)
            ent = self.result_cache.get(key)
            if ent is not None:
                rs = self._probe_result(ex, ent, key, session)
                if rs is not None:
                    _count_serving("result_cache", "hit")
                    stages.count("serving.result_hit")
                    return [rs]
            else:
                _count_serving("result_cache", "miss")
                stages.count("serving.result_miss")
        return self._execute_miss(ex, key, sql, session)

    def _probe_result(self, ex, ent: _ResultEntry, key, session):
        cur = ex.coord.table_tokens(ent.tenant, ent.db, ent.table)
        if cur is None or cur != ent.tokens:
            self.result_cache.evict(key)
            _count_serving("result_cache", "invalidate")
            _count_serving("result_cache", "miss")
            stages.count("serving.result_miss")
            return None
        ex._check_privilege(ent.stmt, session)   # may raise: never cached
        return ent.rs

    def _execute_miss(self, ex, key, sql: str, session):
        from ..sql import ast
        from ..sql.parser import parse_sql

        tenant, db0, fp, params = key
        state = {"key": key, "tenant": tenant, "db": db0,
                 "tokens": None, "bypass": None, "stmt": None}
        # ---- plan cache
        with stages.stage("plan_ms"):
            pe = self.plan_cache.get_exact(key)
            how = "hit"
            if pe is None:
                tpl = self.plan_cache.get_template(tenant, db0, fp)
                if tpl is not None:
                    pe = self._rebind_template(ex, tpl, params, key)
                    how = "rebind"
        if pe is not None:
            rs = self._exec_planned(ex, pe, key, session, state, how)
            if rs is not None:
                return rs
            # schema drift / stale template: fall through to a full parse
        _count_serving("plan_cache", "miss")
        stages.count("serving.plan_miss")
        # ---- full path, instrumented: parse here (once), let _select's
        # observation hook capture the analyzed stmt + plan + tokens
        try:
            with stages.stage("plan_ms"):
                stmts = parse_sql(sql)
        except Exception:
            _count_serving("result_cache", "bypass")
            raise               # same error the legacy path would raise
        if len(stmts) != 1 or not isinstance(stmts[0], ast.SelectStmt):
            _count_serving("result_cache", "bypass")
            return None         # UNION etc: legacy path re-parses
        stmt = stmts[0]
        # through execute_statement (not _select directly): it owns the
        # privilege check and honors instance-level instrumentation, so
        # the serving full path stays behaviorally identical to legacy
        self._tls.state = state
        self._tls.fp = fp
        try:
            rs = ex.execute_statement(stmt, session)
        finally:
            self._tls.state = None   # errors are never cached
            self._tls.fp = None
        self._store_result(key, rs, state)
        return [rs]

    def _rebind_template(self, ex, tpl: _PlanEntry, params, key):
        """Template fingerprint hit with new params → a fresh exact
        entry, or None when rebinding is unsound for these params."""
        from ..errors import PlanError
        from ..sql.planner import plan_select

        stmt = _rebind(tpl, params)
        if stmt is None:
            self.decline_rebind("param_type")
            return None
        schema = ex.meta.table_opt(tpl.tenant, tpl.db, tpl.table)
        if schema is None or getattr(schema, "schema_version", None) \
                != tpl.schema_version:
            self.decline_rebind("schema_drift")
            return None
        try:
            plan = plan_select(stmt, schema)
        except PlanError:
            self.decline_rebind("plan_error")
            return None
        pe = _PlanEntry(stmt, plan, tpl.tenant, tpl.db, tpl.table,
                        tpl.schema_version, params, tpl.slots and
                        _template_slots(stmt, params))
        self.plan_cache.store(key, pe)
        _count_serving("plan_cache", "hit_rebind")
        stages.count("serving.plan_rebind")
        return pe

    def decline_rebind(self, reason: str) -> None:
        _count_serving("plan_cache", f"rebind_declined_{reason}")

    def _exec_planned(self, ex, pe: _PlanEntry, key, session, state, how):
        """Execute a cached plan: revalidate schema version, re-run the
        privilege check, capture invalidation tokens BEFORE the scan,
        then dispatch straight to the executor's batch methods."""
        from ..sql.planner import AggregatePlan

        schema = ex.meta.table_opt(pe.tenant, pe.db, pe.table)
        if schema is None or getattr(schema, "schema_version", None) \
                != pe.schema_version:
            self.plan_cache.evict(key)
            return None     # caller books plan_cache miss + reparses
        ex._check_privilege(pe.stmt, session)
        if how == "hit":
            _count_serving("plan_cache", "hit")
            stages.count("serving.plan_hit")
        state["tokens"] = ex.coord.table_tokens(pe.tenant, pe.db, pe.table)
        state["stmt"] = pe.stmt
        state["table"] = pe.table
        state["db"] = pe.db
        self._tls.fp = key[2]
        try:
            if isinstance(pe.plan, AggregatePlan):
                rs = ex._exec_aggregate(pe.plan, pe.tenant, pe.db)
            else:
                rs = ex._exec_raw(pe.plan, pe.tenant, pe.db)
        finally:
            self._tls.fp = None
        self._store_result(key, rs, state)
        return [rs]

    # ----------------------------------------------- _select observation
    def claim(self):
        """Consume-once TLS handoff: armed by `_execute_miss` for the
        OUTER statement only — nested _select calls (subquery
        resolution) claim nothing and stay invisible to the caches."""
        state = getattr(self._tls, "state", None)
        self._tls.state = None
        return state

    def current_fp(self) -> str | None:
        """Fingerprint of the serving-instrumented request executing on
        THIS thread, if any — tags remote scan RPCs for cluster-wide
        cache attribution."""
        return getattr(self._tls, "fp", None)

    def observe_plan(self, state, stmt, plan, session, db, table,
                     schema) -> None:
        """_select hook, fired right after `plan_select` on the claimed
        outer statement: learn the plan + capture result-cache tokens
        (pre-scan, so a racing write causes a miss, never staleness)."""
        if session.tenant != state["tenant"] or db == "usage_schema":
            # tenant-swapped system view: the analyzed stmt embeds the
            # caller's tenant filter — never reusable across sessions
            state["bypass"] = "tenant_view"
            _count_serving("result_cache", "bypass")
            return
        tenant, db0, fp, params = state["key"]
        slots = _template_slots(stmt, params)
        pe = _PlanEntry(stmt, plan, tenant, db, table,
                        getattr(schema, "schema_version", None),
                        params, slots)
        self.plan_cache.store(state["key"], pe)
        state["stmt"] = stmt
        state["table"] = table
        state["db"] = db
        state["tokens"] = self._executor().coord.table_tokens(
            session.tenant, db, table)
        if state["tokens"] is None:
            state["bypass"] = "remote_vnodes"
            _count_serving("result_cache", "bypass")

    def _store_result(self, key, rs, state) -> None:
        if state.get("tokens") is None:
            if state.get("bypass") is None:
                # never reached the plan hook (relational/system/constant
                # path): the result is not token-invalidatable
                _count_serving("result_cache", "bypass")
                stages.count("serving.result_bypass")
            return
        ent = _ResultEntry(rs, state["tokens"], state["stmt"],
                           state["tenant"], state["db"], state["table"],
                           _rs_nbytes(rs))
        if not self.result_cache.store(key, ent):
            _count_serving("result_cache", "bypass")
            stages.count("serving.result_bypass")
