"""Query execution: plans → results over the coordinator + TpuExec.

Role-parity with the reference's execution layer (query_server/query/src/
execution/: SqlQueryExecution optimize→schedule→stream, execution/ddl/*
one executor per DDL op): aggregates fan out per placed vnode, each vnode
runs the fused device kernel, partials merge on the host by group key
(count/sum add, min/max combine, mean from sum+count, first/last by actual
timestamp) — the single-node form of the partial→final AggregateExec
split, with the ICI path in parallel/distributed_agg doing the same inside
one mesh.
"""
from __future__ import annotations

import os
import re
from dataclasses import dataclass, field

import numpy as np

from ..errors import (
    CnosError, ExecutionError, FunctionError, PlanError, QueryError,
    TableNotFound,
)
from ..models.points import WriteBatch
from ..models.predicate import TimeRanges
from ..models.schema import (
    ColumnType, DatabaseOptions, DatabaseSchema, Duration, Precision,
    TenantOptions, TskvTableSchema, ValueType,
)
from ..models.codec import Encoding
from ..models.strcol import DictArray, as_object_array
from ..ops.tpu_exec import AggSpec, TpuQuery, execute_scan_aggregate
from ..parallel.coordinator import Coordinator
from ..parallel.meta import MetaStore
from ..server import memory as memgov
from ..utils import stages
from ..utils import lockwatch
from .. import faults

faults.register_point("memory.spill", __name__,
                      desc="group-state spill file publish "
                           "(tmp+fsync+rename)")
from . import ast
from . import expr as expr_mod
from . import relational as rel
from .expr import (
    Column, Expr, Func, InList, InSubquery, Literal, Subquery, WindowFunc,
)
from .parser import parse_sql
from .planner import AGG_FUNCS, AggregatePlan, RawScanPlan, plan_select


@dataclass
class Session:
    tenant: str = "cnosdb"
    database: str = "public"
    user: str = "root"


@dataclass
class ResultSet:
    names: list[str]
    columns: list[np.ndarray]
    types: list[str] = field(default_factory=list)

    @property
    def n_rows(self) -> int:
        return len(self.columns[0]) if self.columns else 0

    @staticmethod
    def column_values(c) -> list:
        # float32 stays a numpy scalar so renderers can keep f32
        # precision (tolist() would widen to python float = f64)
        return list(c) if getattr(c, "dtype", None) == np.float32 \
            else c.tolist()

    def rows(self) -> list[tuple]:
        if not self.columns:
            return []
        return list(zip(*map(self.column_values, self.columns)))

    def to_dict(self) -> dict:
        return {n: c for n, c in zip(self.names, self.columns)}

    @classmethod
    def empty(cls, names=()):
        return cls(list(names), [np.empty(0, dtype=object) for _ in names])

    @classmethod
    def message(cls, text: str):
        return cls(["result"], [np.array([text], dtype=object)])


class QueryTracker:
    """Running-query registry with cooperative kill (reference
    dispatcher/query_tracker.rs:32)."""

    def __init__(self):
        import threading

        self._lock = lockwatch.Lock("executor.query_tracker")
        self._next = 1
        self.running: dict[int, dict] = {}

    def register(self, sql: str, session: "Session",
                 ctx=None) -> int:
        import time as _t

        with self._lock:
            qid = self._next
            self._next += 1
            self.running[qid] = {"sql": sql, "user": session.user,
                                 "tenant": session.tenant,
                                 "db": session.database,
                                 "start": _t.time(), "cancelled": False,
                                 "ctx": ctx}
            if ctx is not None:
                # link the request-lifecycle context (utils/deadline.py)
                # so KILL QUERY / disconnect can cancel in-flight remote
                # work, not just the between-statement checks
                ctx.qid = str(qid)
            return qid

    def finish(self, qid: int):
        with self._lock:
            self.running.pop(qid, None)

    def kill(self, qid: int) -> bool:
        with self._lock:
            q = self.running.get(qid)
            if q is None:
                return False
            q["cancelled"] = True
            ctx = q.get("ctx")
        if ctx is not None:
            ctx.cancel("killed")
        return True

    def ctx_of(self, qid: int):
        with self._lock:
            q = self.running.get(qid)
            return q.get("ctx") if q is not None else None

    def check_cancelled(self, qid: int):
        q = self.running.get(qid)
        if q is not None and q["cancelled"]:
            raise QueryError(f"query {qid} cancelled")
        ctx = q.get("ctx") if q is not None else None
        if ctx is not None:
            ctx.check()  # deadline expiry / disconnect-cancel

    def snapshot(self) -> list[tuple[int, dict]]:
        with self._lock:
            return [(qid, dict(q)) for qid, q in self.running.items()]


class QueryExecutor:
    def __init__(self, meta: MetaStore, coord: Coordinator,
                 memory_pool=None):
        import threading as _th

        from ..utils.memory_pool import DEFAULT_POOL

        self.meta = meta
        self.coord = coord
        self.tracker = QueryTracker()
        self.memory_pool = memory_pool or DEFAULT_POOL
        self._stream_engine = None
        self._stream_lock = _th.Lock()
        self._matview_engine = None
        self._matview_lock = _th.Lock()
        # planner consults materialized rollups unless disabled (the
        # rewrite is bit-identical, so this is an escape hatch, not a
        # correctness knob)
        self.matview_rewrite_enabled = \
            os.environ.get("CNOSDB_MATVIEW_REWRITE", "1") != "0"
        # serving plane (plan cache / result cache / fused batching);
        # CNOSDB_SERVING=0 restores byte-identical legacy behavior
        self.serving = None
        if os.environ.get("CNOSDB_SERVING", "1") != "0":
            from ..server.serving import ServingPlane

            self.serving = ServingPlane(self)

    # ------------------------------------------------------------------ api
    def execute_sql(self, sql: str, session: Session | None = None) -> list[ResultSet]:
        session = session or Session()
        from contextlib import nullcontext

        from ..utils import spans as _trace
        from ..utils import deadline as _deadline_mod

        # adopt the ambient request context (installed at HTTP ingress);
        # embedded/direct callers without one keep today's no-deadline
        # behavior — only the cooperative kill applies
        ctx = _deadline_mod.current()
        qid = self.tracker.register(sql, session, ctx=ctx)
        import threading as _th
        import time as _t

        if not hasattr(self, "_tls"):
            self._tls = _th.local()
        prev_qid = getattr(self._tls, "qid", None)
        self._tls.qid = qid
        # always-on per-query profile: adopt an ambient one (EXPLAIN
        # ANALYZE / a caller-installed scope) or own a fresh one
        prof = stages.current_profile()
        own_prof = prof is None
        if own_prof:
            prof = stages.QueryProfile(
                node_id=getattr(self.coord, "node_id", None))
        prof.qid = str(qid)
        if prof.sql is None:
            prof.sql = sql[:512]
        span = _trace.current_span()
        if span is not None:
            prof.trace_id = span.trace_id
        t0 = _t.perf_counter()
        error: str | None = None
        try:
            with (stages.profile_scope(prof) if own_prof
                  else nullcontext()):
                if self.serving is not None:
                    out = self.serving.try_execute(sql, session)
                    if out is not None:
                        self._record_query_usage(sql, session)
                        return out
                out = []
                with stages.stage("plan_ms"):
                    stmts = parse_sql(sql)
                for s in stmts:
                    self.tracker.check_cancelled(qid)
                    out.append(self.execute_statement(s, session))
                self._record_query_usage(sql, session)
                return out
        except BaseException as e:
            error = f"{type(e).__name__}: {e}"[:200]
            raise
        finally:
            wall_ms = (_t.perf_counter() - t0) * 1e3
            try:
                self._finish_profile(prof, wall_ms, error, span, session)
            except Exception:
                stages.count_error("swallow.executor.profile")
            self._tls.qid = prev_qid
            self.tracker.finish(qid)

    def _finish_profile(self, prof, wall_ms: float, error: str | None,
                        span, session: Session) -> None:
        """Seal one query's profile: stamp wall time + device telemetry,
        publish to the bounded PROFILES ring (`GET /debug/profile`),
        attach stage timings to the root trace span, and feed the
        slow-query log. Runs in execute_sql's `finally`, so KILLed and
        deadline-exceeded queries are recorded too."""
        prof.finish(wall_ms=wall_ms, error=error)
        stages.PROFILES.record(prof)
        if span is not None:
            for k, v in prof.snapshot().items():
                span.set_tag(f"stage.{k}", v)
            span.set_tag("profile.qid", prof.qid)
        threshold = int(getattr(self, "slow_query_threshold_ms", 0) or 0)
        if threshold > 0 and wall_ms >= threshold:
            self._slow_query_log(prof, wall_ms, error, session)

    def _slow_query_log(self, prof, wall_ms: float, error: str | None,
                        session: Session) -> None:
        """usage_schema.slow_queries: one row per threshold-exceeding
        query (value = wall ms) tagged with qid/trace id/user and the
        dominant stage costs, so the log is SQL-queryable next to the
        rest of the self-telemetry plane. Never fails the query."""
        try:
            totals = prof.stage_totals()
            tags = {"tenant": session.tenant, "database": session.database,
                    "node_id": str(self.coord.node_id),
                    "user": session.user, "qid": str(prof.qid),
                    "trace_id": prof.trace_id or "",
                    "sql": (prof.sql or "")[:180],
                    "error": (error or "")[:120],
                    "decode_ms": str(totals.get("decode_ms", 0)),
                    "kernel_ms": str(totals.get("kernel_ms", 0)),
                    "merge_ms": str(totals.get("merge_ms", 0))}
            self.coord.record_usage("slow_queries", tags, int(wall_ms))
        except Exception:
            stages.count_error("swallow.executor.slow_query_log")

    def _record_query_usage(self, sql: str, session: Session):
        """usage_schema counters for the SQL plane (reference
        usage_schema.rs sql_data_in / coord_queries reporters) — 1-second
        throttled cumulative rows; never fails the query."""
        try:
            tags = {"tenant": session.tenant, "database": session.database,
                    "node_id": str(self.coord.node_id)}
            self.coord.record_usage("sql_data_in", tags, len(sql),
                                    throttle=True, cumulative=True)
            self.coord.record_usage("coord_queries", tags, 1,
                                    throttle=True, cumulative=True)
        except Exception:
            pass

    def _poll_cancel(self):
        qid = getattr(getattr(self, "_tls", None), "qid", None)
        if qid is not None:
            self.tracker.check_cancelled(qid)

    def _serving_invalidate(self, tenant: str, db: str,
                            table: str | None = None) -> None:
        """Push serving-plane eviction after a destructive mutation
        (DELETE / DROP / ALTER). Hygiene only — result-cache probes
        revalidate ScanTokens, so losing this push (fault point
        serving.invalidate, or a crash right here) can never cause a
        stale read; it just leaves dead entries for LRU to age out."""
        try:
            from ..server import serving

            serving.invalidate(tenant, db, table)
        except Exception:
            stages.count_error("serving.invalidate")

    def execute_one(self, sql: str, session: Session | None = None) -> ResultSet:
        rs = self.execute_sql(sql, session)
        return rs[-1] if rs else ResultSet.empty()

    def execute_statement(self, stmt, session: Session) -> ResultSet:
        self._check_privilege(stmt, session)
        if isinstance(stmt, ast.SelectStmt):
            return self._select(stmt, session)
        if isinstance(stmt, ast.UnionStmt):
            return self._union(stmt, session)
        if isinstance(stmt, ast.ExplainStmt):
            return self._explain(stmt, session)
        if isinstance(stmt, ast.CreateDatabase):
            return self._create_database(stmt, session)
        if isinstance(stmt, ast.AlterDatabase):
            return self._alter_database(stmt, session)
        if isinstance(stmt, ast.DropDatabase):
            self.coord.drop_database(session.tenant, stmt.name,
                                     if_exists=stmt.if_exists)
            self._serving_invalidate(session.tenant, stmt.name)
            return ResultSet.message("ok")
        if isinstance(stmt, ast.CreateTable):
            return self._create_table(stmt, session)
        if isinstance(stmt, ast.CreateStreamTable):
            opts = {k.lower(): v for k, v in stmt.options.items()}
            missing = {"db", "table", "event_time_column"} - set(opts)
            if missing:
                raise ExecutionError(
                    f"CREATE STREAM TABLE requires WITH options "
                    f"{sorted(missing)}")
            if stmt.engine != "tskv":
                raise ExecutionError(
                    f"unsupported stream table engine {stmt.engine!r}")
            self.meta.create_stream_table(
                session.tenant, session.database, stmt.name,
                {"db": opts["db"], "table": opts["table"],
                 "event_time_column": opts["event_time_column"],
                 "columns": list(stmt.columns), "engine": stmt.engine},
                if_not_exists=stmt.if_not_exists)
            return ResultSet.message("ok")
        if isinstance(stmt, ast.DropTable):
            db = stmt.database or session.database
            # an external table and a tskv table cannot share a name, so
            # whichever exists is the drop target
            if self.meta.drop_external_table(session.tenant, db, stmt.name):
                return ResultSet.message("ok")
            # a stream table only answers DROP when no tskv table claims
            # the name (the real table always wins)
            try:
                self.meta.table(session.tenant, db, stmt.name)
            except Exception:
                if self.meta.drop_stream_table(session.tenant, db,
                                               stmt.name):
                    return ResultSet.message("ok")
            self.meta.drop_table(session.tenant, db, stmt.name,
                                 if_exists=stmt.if_exists)
            self._serving_invalidate(session.tenant, db, stmt.name)
            return ResultSet.message("ok")
        if isinstance(stmt, ast.AlterTable):
            return self._alter_table(stmt, session)
        if isinstance(stmt, ast.ShowStmt):
            return self._show(stmt, session)
        if isinstance(stmt, ast.DescribeStmt):
            return self._describe(stmt, session)
        if isinstance(stmt, ast.InsertStmt):
            return self._insert(stmt, session)
        if isinstance(stmt, ast.DeleteStmt):
            return self._delete(stmt, session)
        if isinstance(stmt, ast.UpdateStmt):
            return self._update(stmt, session)
        if isinstance(stmt, ast.CreateTenant):
            from ..models.schema import Duration
            from ..parallel.meta import build_limiter_config

            try:
                self.meta.create_tenant(stmt.name, TenantOptions(
                    comment=stmt.comment,
                    limiter=(build_limiter_config(stmt.limiter_groups)
                             if stmt.limiter_groups else None),
                    drop_after=(Duration.parse(stmt.drop_after)
                                if stmt.drop_after else None)))
            except Exception:
                if not stmt.if_not_exists:
                    raise
            return ResultSet.message("ok")
        if isinstance(stmt, ast.DropTenant):
            self.meta.drop_tenant(stmt.name, if_exists=stmt.if_exists,
                                  after=stmt.after)
            return ResultSet.message("ok")
        if isinstance(stmt, ast.AlterTenantOpts):
            self.meta.alter_tenant_options(stmt.tenant, stmt.changes)
            return ResultSet.message("ok")
        if isinstance(stmt, ast.CreateUser):
            try:
                self.meta.create_user(
                    stmt.name, stmt.password, admin=stmt.granted_admin,
                    comment=stmt.comment,
                    must_change_password=stmt.must_change_password)
            except Exception:
                if not stmt.if_not_exists:
                    raise
            return ResultSet.message("ok")
        if isinstance(stmt, ast.DropUser):
            self.meta.drop_user(stmt.name, if_exists=stmt.if_exists)
            return ResultSet.message("ok")
        if isinstance(stmt, ast.AlterUser):
            if stmt.name == "root" and session.user != "root":
                # only the initial admin may alter itself — a GRANTED
                # admin altering root would be privilege escalation
                # (dcl_user.slt pins comment/password/granted_admin)
                raise ExecutionError("only root may alter user root")
            self.meta.alter_user(stmt.name, changes=stmt.changes)
            return ResultSet.message("ok")
        if isinstance(stmt, ast.CreateRole):
            from ..errors import MetaError

            try:
                self.meta.create_role(session.tenant, stmt.name, stmt.inherit)
            except MetaError as e:
                # IF NOT EXISTS only forgives the already-exists case —
                # bad INHERIT or a missing tenant must still surface
                if not (stmt.if_not_exists and "exists" in str(e)):
                    raise
            return ResultSet.message("ok")
        if isinstance(stmt, ast.DropRole):
            from ..errors import MetaError

            if stmt.name not in self.meta.list_roles(session.tenant):
                if stmt.if_exists:
                    return ResultSet.message("ok")
                raise MetaError(f"unknown role {stmt.name!r}")
            self.meta.drop_role(session.tenant, stmt.name)
            return ResultSet.message("ok")
        if isinstance(stmt, ast.GrantRevoke):
            if stmt.grant:
                self.meta.grant_db_privilege(session.tenant, stmt.role,
                                             stmt.database, stmt.level)
            else:
                self.meta.revoke_db_privilege(session.tenant, stmt.role,
                                              stmt.database)
            return ResultSet.message("ok")
        if isinstance(stmt, ast.AlterTenantMember):
            if stmt.add:
                self.meta.add_member(stmt.tenant, stmt.user, stmt.role)
            else:
                self.meta.remove_member(stmt.tenant, stmt.user)
            return ResultSet.message("ok")
        if isinstance(stmt, ast.CreateExternalTable):
            xdb, xname = stmt.name.rsplit(".", 1) \
                if "." in stmt.name else (session.database, stmt.name)
            self.meta.create_external_table(
                session.tenant, xdb, xname, stmt.path,
                stmt.fmt, stmt.header, stmt.if_not_exists, stmt.options,
                stmt.columns)
            return ResultSet.message("ok")
        if isinstance(stmt, ast.CopyStmt):
            return self._copy(stmt, session)
        if isinstance(stmt, ast.VnodeAdmin):
            return self._vnode_admin(stmt)
        if isinstance(stmt, ast.RecoverStmt):
            if stmt.kind == "tenant":
                self.meta.recover_tenant(stmt.name)
            elif stmt.kind == "database":
                self.meta.recover_database(session.tenant, stmt.name)
            else:
                self.meta.recover_table(
                    session.tenant, stmt.database or session.database,
                    stmt.name)
            return ResultSet.message("ok")
        if isinstance(stmt, ast.CreateStream):
            return self._create_stream(stmt, session)
        if isinstance(stmt, ast.DropStream):
            se = self.stream_engine()
            if stmt.name not in se.streams and not stmt.if_exists:
                raise ExecutionError(f"unknown stream {stmt.name!r}")
            se.drop(stmt.name)
            self.meta.drop_stream(stmt.name)
            return ResultSet.message("ok")
        if isinstance(stmt, ast.CreateMatView):
            return self._create_matview(stmt, session)
        if isinstance(stmt, ast.DropMatView):
            return self._drop_matview(stmt)
        if isinstance(stmt, ast.KillQuery):
            ctx = self.tracker.ctx_of(stmt.query_id)
            ok = self.tracker.kill(stmt.query_id)
            if ok and ctx is not None:
                # fan best-effort cancel_scan out to every node still
                # working for this query, so remote vnode scans stop
                # DURING the fetch instead of running to completion
                try:
                    self.coord.cancel_remote_scans(ctx)
                except Exception:
                    pass  # kill remains cooperative-best-effort
            return ResultSet.message("ok" if ok else "no such query")
        if isinstance(stmt, ast.CompactStmt):
            self.coord.engine.compact_all()
            return ResultSet.message("ok")
        if isinstance(stmt, ast.FlushStmt):
            self.coord.engine.flush_all()
            return ResultSet.message("ok")
        if isinstance(stmt, ast.BackupStmt):
            entry = self.coord.backup_database(
                session.tenant, stmt.database,
                incremental=stmt.incremental)
            return ResultSet.message(
                f"backup {entry['id']}: {entry['vnodes']} vnodes, "
                f"{entry['objects_uploaded']} objects uploaded, "
                f"{entry['objects_reused']} reused")
        if isinstance(stmt, ast.RestoreStmt):
            out = self.coord.restore_database(
                session.tenant, stmt.database, backup_id=stmt.backup_id,
                to_ts=stmt.to_ts, new_name=stmt.new_name)
            # every cached plan/result over the target db read bytes that
            # the install just replaced
            self._serving_invalidate(session.tenant, out["database"])
            return ResultSet.message(
                f"restored {out['database']} from {out['backup_id']}: "
                f"{len(out['vnodes'])} vnodes")
        raise ExecutionError(f"unsupported statement {type(stmt).__name__}")

    # privilege needed per statement class
    _READ_STMTS = (ast.SelectStmt, ast.UnionStmt, ast.ShowStmt,
                   ast.DescribeStmt, ast.ExplainStmt)
    _WRITE_STMTS = (ast.InsertStmt, ast.DeleteStmt, ast.UpdateStmt)
    # instance-level administration: NEVER grantable through tenant roles
    # (a tenant owner resetting the system admin's password would be a
    # full privilege escalation). CopyStmt/CreateExternalTable touch the
    # server's LOCAL FILESYSTEM — that is instance scope too, or any
    # tenant owner could read /etc/passwd through an external table.
    _ADMIN_STMTS = (ast.CreateUser, ast.DropUser, ast.AlterUser,
                    ast.CreateTenant, ast.DropTenant, ast.AlterTenantOpts,
                    ast.CopyStmt, ast.CreateExternalTable,
                    # cluster-topology mutation reaches every tenant's
                    # vnodes via the global placement map: instance scope
                    ast.VnodeAdmin, ast.CompactStmt, ast.FlushStmt,
                    # BACKUP/RESTORE move whole databases through the
                    # shared archive store and wipe/install vnode dirs
                    ast.BackupStmt, ast.RestoreStmt)

    def _check_privilege(self, stmt, session: Session):
        """RBAC gate (reference auth/auth_control.rs AccessControlImpl →
        privilege checks on the logical plan): reads need read, DML needs
        write, tenant-scoped DDL needs tenant-owner, instance admin needs
        an admin user. Admin users and unauthenticated embedded sessions
        (user 'root') pass through."""
        from ..errors import AuthError

        user = session.user
        tenants = getattr(self.meta, "tenants", None)
        if tenants is not None and session.tenant not in tenants:
            # even an admin cannot act inside a tenant that does not
            # exist (cluster_schema/tenants.slt: select 1 errors)
            raise AuthError(f"tenant {session.tenant!r} not found")
        u = self.meta.users.get(user)
        if u is None or u.get("admin"):
            return  # unknown → authentication already failed upstream
        if isinstance(stmt, self._ADMIN_STMTS):
            raise AuthError(
                f"user {user!r} is not an admin (instance administration)")
        if isinstance(stmt, ast.RecoverStmt) and stmt.kind == "tenant":
            # RECOVER TABLE/DATABASE undo tenant-scoped DDL (checked below
            # like any DDL); only RECOVER TENANT is instance scope
            raise AuthError(
                f"user {user!r} is not an admin (instance administration)")
        if isinstance(stmt, ast.AlterTenantMember):
            # scope the check to the TARGET tenant, not the session's
            if not self.meta.check_db_privilege(user, stmt.tenant, "", "all"):
                raise AuthError(
                    f"user {user!r} is not an owner of tenant "
                    f"{stmt.tenant!r}")
            return
        if isinstance(stmt, self._READ_STMTS):
            if isinstance(stmt, ast.SelectStmt) and stmt.table is None \
                    and stmt.from_item is None:
                # constant SELECT (current_user() etc.) touches no
                # database resource — no privilege needed
                # (function/session.slt: a grantless member runs it)
                return
            need = "read"
        elif isinstance(stmt, self._WRITE_STMTS):
            need = "write"
        else:
            need = "all"
        db = getattr(stmt, "database", None) or session.database
        from .system_tables import is_system_db_for

        if is_system_db_for(db, session) and need == "read":
            return
        if not self.meta.check_db_privilege(user, session.tenant, db, need):
            raise AuthError(
                f"user {user!r} lacks {need} privilege on "
                f"{session.tenant}.{db}")

    # ------------------------------------------------------------------ streams
    def stream_engine(self):
        if self._stream_engine is None:
            with self._stream_lock:
                if self._stream_engine is None:
                    import os

                    from .stream import StreamEngine

                    self._stream_engine = StreamEngine(
                        self, os.path.join(self.coord.engine.data_dir, "streams"))
        return self._stream_engine

    def _create_stream(self, stmt: ast.CreateStream, session: Session,
                       persist: bool = True):
        from .stream import StreamQuery

        se = self.stream_engine()
        if stmt.name in se.streams:
            if stmt.if_not_exists:
                return ResultSet.message("ok")
            raise ExecutionError(f"stream {stmt.name!r} exists")
        # validate the template NOW: missing tables/columns must fail the
        # CREATE, not silently kill every future trigger
        db = stmt.select.database or session.database
        schema = self.meta.table(session.tenant, db, stmt.select.table)
        plan_select(stmt.select, schema)
        if persist:
            self.meta.create_stream(stmt.name, {
                "target": stmt.target, "select_sql": stmt.select_sql,
                "interval_s": stmt.interval_s, "delay_ns": stmt.delay_ns,
                "tenant": session.tenant, "database": session.database,
                "user": session.user})
        se.register(StreamQuery(
            name=stmt.name, sql=stmt.select_sql, stmt=stmt.select,
            interval_s=stmt.interval_s, delay_ns=stmt.delay_ns,
            session=Session(session.tenant, session.database, session.user),
            sink=("table", stmt.target)), start_ns=0)
        return ResultSet.message("ok")

    def restore_streams(self):
        """Re-register persisted streams on boot (watermarks resume)."""
        for name, d in list(self.meta.streams.items()):
            try:
                sel = parse_sql(d["select_sql"])[0]
                stmt = ast.CreateStream(
                    name, d["target"], sel, d["select_sql"],
                    d.get("interval_s", 10.0), d.get("delay_ns", 0))
                self._create_stream(
                    stmt, Session(d.get("tenant", "cnosdb"),
                                  d.get("database", "public"),
                                  d.get("user", "root")), persist=False)
            except Exception:
                import logging

                logging.getLogger("cnosdb.stream").exception(
                    "failed to restore stream %s", name)

    # ------------------------------------------------------- materialized views
    def matview_engine(self):
        if self._matview_engine is None:
            with self._matview_lock:
                if self._matview_engine is None:
                    from .matview import MatviewEngine

                    self._matview_engine = MatviewEngine(
                        self, os.path.join(self.coord.engine.data_dir,
                                           "matviews"))
        return self._matview_engine

    def _create_matview(self, stmt: ast.CreateMatView, session: Session):
        from .matview import compile_view

        me = self.matview_engine()
        me.sync_from_meta()
        if stmt.name in me.views:
            if stmt.if_not_exists:
                return ResultSet.message("ok")
            raise ExecutionError(
                f"materialized view {stmt.name!r} exists")
        db = stmt.select.database or session.database
        # eligibility is validated NOW (aggregate shape, mergeable
        # partials) — an ineligible view must fail the CREATE
        vdef = compile_view(stmt.name, stmt.select, stmt.select_sql,
                            stmt.delay_ns, session.tenant, db, self.meta)
        vdef.user = session.user
        self.meta.create_matview(stmt.name, vdef.definition())
        me.register(vdef)
        return ResultSet.message("ok")

    def _drop_matview(self, stmt: ast.DropMatView):
        me = self.matview_engine()
        me.sync_from_meta()
        if stmt.name not in me.views and not stmt.if_exists:
            raise ExecutionError(
                f"unknown materialized view {stmt.name!r}")
        self.meta.drop_matview(stmt.name)
        me.drop(stmt.name)
        return ResultSet.message("ok")

    def restore_matviews(self):
        """Instantiate the maintainer on boot so persisted views resume
        flush-driven maintenance (cheap: no jax imports)."""
        self.matview_engine().sync_from_meta()

    # ------------------------------------------------------------------ DDL
    def _create_database(self, stmt: ast.CreateDatabase, session: Session):
        opts = DatabaseOptions()
        o = stmt.options
        if "ttl" in o:
            opts.ttl = Duration.parse(o["ttl"])
        if "shard_num" in o:
            opts.shard_num = o["shard_num"]
        if "vnode_duration" in o:
            opts.vnode_duration = Duration.parse(o["vnode_duration"])
        if "replica" in o:
            opts.replica = o["replica"]
        if "precision" in o:
            opts.precision = Precision.parse(o["precision"])
        if "config" in o:
            opts.config = dict(o["config"])
        self.meta.create_database(
            DatabaseSchema(session.tenant, stmt.name, opts), stmt.if_not_exists)
        return ResultSet.message("ok")

    def _alter_database(self, stmt: ast.AlterDatabase, session: Session):
        kw = {}
        o = stmt.options
        if "ttl" in o:
            kw["ttl"] = Duration.parse(o["ttl"])
        if "shard_num" in o:
            kw["shard_num"] = o["shard_num"]
        if "vnode_duration" in o:
            kw["vnode_duration"] = Duration.parse(o["vnode_duration"])
        if "replica" in o:
            kw["replica"] = o["replica"]
        self.meta.alter_database(session.tenant, stmt.name, **kw)
        return ResultSet.message("ok")

    def _create_table(self, stmt: ast.CreateTable, session: Session):
        db = stmt.database or session.database
        fields = []
        for f in stmt.fields:
            vt = ValueType.parse(f.type_name)
            fields.append((f.name, vt, f.codec))
        schema = TskvTableSchema.new_measurement(
            session.tenant, db, stmt.name, stmt.tags,
            [(n, vt) for n, vt, _ in fields],
            precision=self.meta.database(session.tenant, db)
            .options.precision, sort_tags=False)
        for f in stmt.fields:
            tn = f.type_name.upper()
            if tn.startswith("GEOMETRY("):
                schema.column(f.name).geom_subtype = \
                    tn[len("GEOMETRY("):].split(",")[0].strip()
        for n, _vt, codec in fields:
            if codec:
                schema.column(n).encoding = Encoding.from_str(codec)
                schema.column(n).explicit_codec = True
        self.meta.create_table(schema, stmt.if_not_exists)
        return ResultSet.message("ok")

    def _alter_table(self, stmt: ast.AlterTable, session: Session):
        db = session.database
        name = stmt.name
        if "." in name:   # ALTER TABLE db.tbl
            db, name = name.split(".", 1)
        schema = self.meta.table(session.tenant, db, name)
        if stmt.action == "add_field":
            col = schema.add_column(stmt.column.name,
                                    ColumnType.field(ValueType.parse(stmt.column.type_name)))
            if stmt.column.codec and stmt.column.codec != "DEFAULT":
                col.encoding = Encoding.from_str(stmt.column.codec)
                col.explicit_codec = True
            else:
                col.encoding = col.default_encoding()
        elif stmt.action == "add_tag":
            schema.add_column(stmt.column.name, ColumnType.tag())
        elif stmt.action == "alter_codec":
            # ALTER <col> SET CODEC: fields only (reference alter_table.slt
            # pins tag/time as errors); CODEC(DEFAULT) restores the
            # type-default rendering
            col = schema.column(stmt.column.name)
            if not col.column_type.is_field:
                raise ExecutionError(
                    "only FIELD columns take a compression codec")
            if stmt.column.codec == "DEFAULT":
                col.encoding = col.default_encoding()
                col.explicit_codec = False
            else:
                from ..models.codec import codecs_for

                enc = Encoding.from_str(stmt.column.codec)
                if enc not in codecs_for(col.column_type.value_type.name):
                    raise ExecutionError(
                        f"codec {stmt.column.codec} does not apply to "
                        f"{col.column_type.value_type.name}")
                col.encoding = enc
                col.explicit_codec = True
            schema.schema_version += 1
        elif stmt.action == "rename":
            # RENAME COLUMN old TO new (reference rename_field/tag.slt:
            # time never renames; target must be free) — invariants live
            # in TskvTableSchema.rename_column; buffered rows re-key so
            # they follow the column like id-resolved TSM chunks do
            col = schema.rename_column(stmt.drop_name, stmt.rename_to)
            owner = f"{session.tenant}.{db}"
            if col.column_type.is_field:
                for v in self.coord.engine.local_vnodes(owner):
                    v.rename_mem_field(name, stmt.drop_name,
                                       stmt.rename_to)
            elif col.column_type.is_tag:
                # tag values live in index series keys, which carry tag
                # NAMES — rewrite them so historic series follow the
                # column (same WAL-logged machinery as tag UPDATE)
                from ..models.series import SeriesKey

                for v in self.coord.engine.local_vnodes(owner):
                    old_keys, new_keys = [], []
                    for sid in v.index.table_series_ids(name):
                        k = v.index.get_series_key(int(sid))
                        if k is None or k.tag_value(stmt.drop_name) is None:
                            continue
                        tags = {(stmt.rename_to if tk == stmt.drop_name
                                 else tk): tv
                                for tk, tv in k.tag_dict().items()}
                        old_keys.append(k)
                        new_keys.append(SeriesKey(name, tags))
                    if old_keys:
                        v.update_tags(name, old_keys, new_keys)
        elif stmt.action == "drop":
            tgt = schema.column(stmt.drop_name)
            if tgt is not None and tgt.column_type.is_field:
                n_fields = sum(1 for c in schema.columns
                               if c.column_type.is_field)
                if n_fields <= 1:
                    # a table must keep at least one field
                    # (alter_table.slt pins DROP of the only field)
                    raise ExecutionError(
                        "cannot drop the only field column")
            if tgt is not None and tgt.column_type.is_tag:
                # the reference's ALTER TABLE DROP never removes TAG
                # columns (create_table.slt pins DROP column7 on a
                # two-tag table as an error)
                raise ExecutionError("cannot drop a tag column")
            dropped = schema.drop_column(stmt.drop_name)
            if dropped.column_type.is_field:
                owner = f"{session.tenant}.{db}"
                for v in self.coord.engine.local_vnodes(owner):
                    v.drop_mem_field(name, stmt.drop_name)
        self.meta.update_table(schema)
        self._serving_invalidate(session.tenant, db, name)
        return ResultSet.message("ok")

    # ------------------------------------------------------------------ SHOW
    def _show(self, stmt: ast.ShowStmt, session: Session):
        if stmt.kind == "databases":
            names = self.meta.list_databases(session.tenant)
            return ResultSet(["database_name"], [np.array(names, dtype=object)])
        if stmt.kind == "tables":
            db = stmt.on_database or session.database
            names = self.meta.list_tables(session.tenant, db)
            return ResultSet(["table_name"], [np.array(names, dtype=object)])
        if stmt.kind == "tag_values":
            # (key, value) rows per the reference
            # (planner.rs:2819 show_tag_value_projections)
            db = stmt.on_database or session.database
            schema = self.meta.table(session.tenant, db, stmt.table)
            if stmt.where is not None:
                bad = stmt.where.columns() - set(schema.tag_names()) \
                    - {"time"}
                if bad:
                    raise PlanError(
                        f"SHOW TAG VALUES WHERE supports tag/time "
                        f"predicates only, got {sorted(bad)}")
            for name, _asc in stmt.order_by:
                if name not in ("key", "value"):
                    raise PlanError(
                        f"SHOW TAG VALUES can only ORDER BY key/value, "
                        f"got {name!r}")
            tags = schema.tag_names()
            op, names = stmt.tag_with or ("eq", [stmt.tag_key])
            keys = {"eq": [t for t in tags if t in names],
                    "ne": [t for t in tags if t not in names],
                    "in": [t for t in tags if t in names],
                    "notin": [t for t in tags if t not in names]}[op]
            pairs: set[tuple] = set()
            if stmt.where is not None:
                # derive values from the WHERE-surviving series only
                skeys = self._filtered_series(session.tenant, db,
                                              stmt.table, stmt.where)
                for k in skeys:
                    for key in keys:
                        v = k.tag_value(key)
                        if v is not None:
                            pairs.add((key, v))
            else:
                for key in keys:
                    for v in self.coord.tag_values(
                            session.tenant, db, stmt.table, key):
                        pairs.add((key, v))
            rows = sorted(pairs)
            for name, asc in reversed(stmt.order_by):
                idx = 0 if name == "key" else 1
                rows.sort(key=lambda r: r[idx], reverse=not asc)
            if stmt.offset:
                rows = rows[stmt.offset:]
            if stmt.limit is not None:
                rows = rows[:stmt.limit]
            return ResultSet(["key", "value"],
                             [np.array([r[0] for r in rows], dtype=object),
                              np.array([r[1] for r in rows], dtype=object)])
        if stmt.kind == "tag_keys":
            schema = self.meta.table(session.tenant, session.database, stmt.table)
            return ResultSet(["tag_key"],
                             [np.array(schema.tag_names(), dtype=object)])
        if stmt.kind == "series":
            db = stmt.on_database or session.database
            if stmt.where is not None:
                keys = self._filtered_series(session.tenant, db,
                                             stmt.table, stmt.where)
            else:
                keys = self.coord.series_keys(session.tenant, db,
                                              stmt.table)
            reprs = [repr(k) for k in keys]
            for name, asc in reversed(stmt.order_by):
                if name != "key":
                    raise PlanError(
                        f"SHOW SERIES can only ORDER BY key, got {name!r}")
                reprs.sort(reverse=not asc)
            if stmt.offset:
                reprs = reprs[stmt.offset:]
            if stmt.limit is not None:
                reprs = reprs[:stmt.limit]
            return ResultSet(["key"], [np.array(reprs, dtype=object)])
        if stmt.kind == "queries":
            import time as _t

            ids, texts, users, durs = [], [], [], []
            for qid, q in self.tracker.snapshot():
                ids.append(qid)
                texts.append(q["sql"][:200])
                users.append(q["user"])
                durs.append(round(_t.time() - q["start"], 3))
            return ResultSet(
                ["query_id", "query_text", "user_name", "duration"],
                [np.array(ids, dtype=np.int64),
                 np.array(texts, dtype=object),
                 np.array(users, dtype=object),
                 np.array(durs)])
        if stmt.kind == "backups":
            entries = []
            for db in self.meta.list_databases(session.tenant):
                entries.extend(
                    self.meta.list_backups(f"{session.tenant}.{db}"))
            entries.sort(key=lambda e: e["created_ts"])
            import datetime as _dt

            created = [_dt.datetime.fromtimestamp(
                e["created_ts"], _dt.timezone.utc).isoformat()
                for e in entries]
            return ResultSet(
                ["backup_id", "database", "incremental", "created_at",
                 "vnodes", "objects_uploaded", "objects_reused", "bytes"],
                [np.array([e["id"] for e in entries], dtype=object),
                 np.array([e["owner"].split(".", 1)[1] for e in entries],
                          dtype=object),
                 np.array([bool(e["incremental"]) for e in entries],
                          dtype=bool),
                 np.array(created, dtype=object),
                 np.array([e["vnodes"] for e in entries], dtype=np.int64),
                 np.array([e["objects_uploaded"] for e in entries],
                          dtype=np.int64),
                 np.array([e["objects_reused"] for e in entries],
                          dtype=np.int64),
                 np.array([e["bytes"] for e in entries], dtype=np.int64)])
        if stmt.kind == "streams":
            se = self.stream_engine()
            names = sorted(se.streams)
            return ResultSet(
                ["stream_name", "target", "interval_s", "query"],
                [np.array(names, dtype=object),
                 np.array([se.streams[n].sink[1] if isinstance(se.streams[n].sink, tuple)
                           else "<callback>" for n in names], dtype=object),
                 np.array([se.streams[n].interval_s for n in names]),
                 np.array([se.streams[n].sql[:120] for n in names], dtype=object)])
        if stmt.kind == "matviews":
            me = self.matview_engine()
            me.sync_from_meta()
            names = sorted(me.views)
            views = [me.views[n] for n in names]
            return ResultSet(
                ["view_name", "table", "delay_ns", "query"],
                [np.array(names, dtype=object),
                 np.array([v.table for v in views], dtype=object),
                 np.array([v.delay_ns for v in views], dtype=np.int64),
                 np.array([v.select_sql[:120] for v in views],
                          dtype=object)])
        if stmt.kind == "roles":
            roles = self.meta.list_roles(session.tenant)
            names = sorted(roles)
            return ResultSet(
                ["role_name", "inherit", "privileges"],
                [np.array(names, dtype=object),
                 np.array([roles[n].get("inherit", "") for n in names],
                          dtype=object),
                 np.array([", ".join(f"{db}:{lv}" for db, lv in
                                     sorted(roles[n].get("privileges", {})
                                            .items()))
                           for n in names], dtype=object)])
        if stmt.kind == "users":
            users = sorted(self.meta.users)
            return ResultSet(
                ["user_name", "is_admin"],
                [np.array(users, dtype=object),
                 np.array([bool(self.meta.users[u].get("admin"))
                           for u in users])])
        raise ExecutionError(f"unsupported SHOW {stmt.kind}")

    def _filtered_series(self, tenant: str, db: str, table: str, where):
        """Series keys surviving a SHOW SERIES / SHOW TAG VALUES WHERE:
        tag predicates evaluate against the series keys, a `time`
        conjunct against each series' data extent (reference
        ShowTagBody.selection); field predicates are rejected."""
        keys = self.coord.series_keys(tenant, db, table)
        schema = self.meta.table(tenant, db, table)
        tag_names = set(schema.tag_names())
        bad = where.columns() - tag_names - {"time"}
        if bad:
            raise PlanError(
                f"SHOW ... WHERE supports tag/time predicates only, "
                f"got {sorted(bad)}")
        n = len(keys)
        env: dict = {}
        for c in where.columns() - {"time"}:
            env[c] = np.array([k.tag_value(c) for k in keys], dtype=object)
            env[f"__valid__:{c}"] = np.array(
                [k.tag_value(c) is not None for k in keys], dtype=bool)
        if "time" in where.columns():
            from .planner import split_where

            trs, _doms, _res = split_where(where, schema)
            mask = self._series_in_time(tenant, db, table, keys, trs)
            tag_only = _strip_time_conjuncts(where)
            if tag_only is not None:
                m2 = np.asarray(tag_only.eval(env, np), dtype=bool)
                if m2.shape == ():
                    m2 = np.full(n, bool(m2))
                mask = mask & m2
        else:
            mask = np.asarray(where.eval(env, np), dtype=bool)
            if mask.shape == ():
                mask = np.full(n, bool(mask))
        return [k for k, m in zip(keys, mask) if m]

    def _series_in_time(self, tenant: str, db: str, table: str, keys,
                        trs) -> np.ndarray:
        """Mask of series with ≥1 point inside the time ranges (reference
        SHOW SERIES scans; `WHERE time < now()` keeps live series)."""
        present = set()
        for b in self.coord.scan_table(tenant, db, table, time_ranges=trs):
            for k in b.series_keys:
                if k is not None and b.n_rows:
                    present.add(repr(k))
        return np.array([repr(k) in present for k in keys], dtype=bool)

    def _describe(self, stmt: ast.DescribeStmt, session: Session):
        if stmt.kind == "database":
            d = self.meta.database(session.tenant, stmt.name)
            o = d.options
            # reference row (describe_database.slt):
            # ttl, shard, vnode_duration, replica, precision, then the
            # storage-config constants the reference surfaces per-db
            return ResultSet(
                ["ttl", "shard", "vnode_duration", "replica", "precision",
                 "max_memcache_size", "memcache_partitions",
                 "wal_max_file_size", "wal_sync", "strict_write",
                 "max_cache_readers"],
                [np.array([o.ttl.humantime()], dtype=object),
                 np.array([o.shard_num]),
                 np.array([o.vnode_duration.humantime()], dtype=object),
                 np.array([o.replica]),
                 np.array([o.precision.name], dtype=object),
                 np.array([_size_display(o.config.get(
                     "max_memcache_size", "128 MiB"))], dtype=object),
                 np.array([o.config.get("memcache_partitions", 16)]),
                 np.array([_size_display(o.config.get(
                     "wal_max_file_size", "128 MiB"))], dtype=object),
                 np.array([bool(o.config.get("wal_sync", False))]),
                 np.array([bool(o.config.get("strict_write", False))]),
                 np.array([o.config.get("max_cache_readers", 32)])])
        ext = self.meta.external_opt(
            session.tenant, stmt.database or session.database, stmt.name)
        if ext is not None:
            # external tables DESCRIBE with arrow type names and no
            # codec (create_external_table.slt: "Decimal128(10, 6)")
            names = [c[0] for c in ext.get("columns") or []]
            types = [_arrow_type_name(c[1])
                     for c in ext.get("columns") or []]
            return ResultSet(
                ["column_name", "data_type", "column_type",
                 "compression_codec"],
                [np.array(names, dtype=object),
                 np.array(types, dtype=object),
                 np.array(["FIELD"] * len(names), dtype=object),
                 np.array([None] * len(names), dtype=object)])
        schema = self.meta.table(session.tenant,
                                 stmt.database or session.database, stmt.name)
        names, types, kinds, codecs = [], [], [], []
        for c in schema.columns:
            names.append(c.name)
            ct = c.column_type
            if ct.is_time:
                types.append("TIMESTAMP("
                             + {"NS": "NANOSECOND", "US": "MICROSECOND",
                                "MS": "MILLISECOND"}[ct.precision.name]
                             + ")")
                kinds.append("TIME")
            elif ct.is_tag:
                types.append("STRING")
                kinds.append("TAG")
            else:
                types.append(ct.value_type.sql_name())
                kinds.append("FIELD")
            codecs.append(None if c.encoding.name == "NULL"
                          else (c.encoding.name if c.explicit_codec
                                else "DEFAULT"))
        return ResultSet(
            ["column_name", "data_type", "column_type", "compression_codec"],
            [np.array(x, dtype=object) for x in (names, types, kinds, codecs)])

    # ------------------------------------------------------------------ DML
    def _insert(self, stmt: ast.InsertStmt, session: Session):
        db = stmt.database or session.database
        schema = self.meta.table(session.tenant, db, stmt.table)
        cols = stmt.columns or [c.name for c in schema.columns]
        # unquoted SQL identifiers are case-insensitive: fold each column
        # to its schema-cased name (`TIME` → `time`; reference cases
        # write INSERT tbl(TIME, ...))
        by_lower = {c.name.lower(): c.name for c in schema.columns}
        cols = [by_lower.get(c.lower(), c) if not schema.contains_column(c)
                else c for c in cols]
        implicit_time = "time" not in cols
        if implicit_time:
            # reference fills now() when the time column is omitted
            # (math_function/random.slt inserts VALUES (random()), …);
            # one timestamp per statement — rows collide on identical
            # series keys exactly as upstream
            cols = list(cols) + ["time"]
        # SQL INSERT is schema-strict (the schemaless path is line
        # protocol); unknown columns are an error, not an auto-evolution
        unknown = [c for c in cols
                   if c != "time" and not schema.contains_column(c)]
        if unknown:
            raise ExecutionError(
                f"unknown column(s) {unknown} in INSERT INTO {stmt.table}")
        tag_names = [c for c in cols if schema.contains_column(c)
                     and schema.column(c).column_type.is_tag]
        field_types = {c: schema.column(c).column_type.value_type
                       for c in cols if schema.contains_column(c)
                       and schema.column(c).column_type.is_field}
        prec_factor = self.meta.database(
            session.tenant, db).options.precision.to_ns_factor()
        scale_time = (prec_factor != 1 and stmt.select is None
                      and not implicit_time)
        src_rows = stmt.rows
        if stmt.select is not None:
            # INSERT ... SELECT: run the query, map columns positionally
            # (reference: insert_select.slt — SELECT from VALUES etc.)
            rsel = self.execute_statement(stmt.select, session)
            if len(rsel.names) != len(cols):
                raise ExecutionError(
                    f"INSERT SELECT arity mismatch: {len(cols)} target "
                    f"column(s), query yields {len(rsel.names)}")
            src_rows = [
                [None if (isinstance(v, float) and v != v) else
                 (v.item() if isinstance(v, np.generic) else v)
                 for v in row]
                for row in zip(*[c.tolist() if hasattr(c, "tolist") else c
                                 for c in rsel.columns])]
        if implicit_time:
            import time as _time

            now_ns = int(_time.time() * 1e9)
            src_rows = [list(r) + [now_ns] for r in src_rows]
        if stmt.select is None and len(src_rows) > 1:
            # DataFusion types the VALUES list itself: mixing literal
            # classes in one column position is an error before any
            # schema coercion ("Inconsistent data type across values
            # list" — sqlancer/function.slt)
            for j in range(len(cols)):
                seen_cls = None
                for i, r in enumerate(src_rows):
                    v = r[j] if j < len(r) else None
                    if v is None:
                        continue
                    cls = (bool if isinstance(v, bool) else
                           int if isinstance(v, int) else
                           float if isinstance(v, float) else
                           str if isinstance(v, str) else type(v))
                    if seen_cls is None:
                        seen_cls = cls
                    elif cls is not seen_cls:
                        raise ExecutionError(
                            f"Inconsistent data type across values list "
                            f"at row {i} column {j}")
        rows = []
        for raw in src_rows:
            if len(raw) != len(cols):
                raise ExecutionError("INSERT row arity mismatch")
            row = dict(zip(cols, raw))
            t = row["time"]
            if isinstance(t, str):
                from .parser import parse_timestamp_string

                row["time"] = parse_timestamp_string(t)
            elif isinstance(t, float):
                # a fractional time literal is a type error
                # (create_table.slt pins VALUES (0.1, ...))
                raise ExecutionError(
                    f"INSERT time must be an integer timestamp, got {t!r}")
            if row["time"] is None:
                raise ExecutionError("INSERT time must not be NULL")
            if scale_time and not isinstance(t, str):
                # EXPLICIT integer time literals are interpreted in the
                # DATABASE's precision (db_precision.slt); implicit-now
                # and INSERT..SELECT times are already ns and never scale
                scaled = int(row["time"]) * prec_factor
                if abs(scaled) > 2**63 - 1:
                    raise ExecutionError(
                        "timestamp overflows the ns domain at this "
                        "database's precision")
                row["time"] = scaled
            # a point with no field value is unrepresentable (same rule as
            # line protocol; reference rejects all-NULL-field INSERT rows)
            if not any(row.get(c) is not None for c in field_types):
                raise ExecutionError(
                    "INSERT row has no non-NULL field value")
            for c, vt in field_types.items():
                v = row.get(c)
                if v is not None:
                    row[c] = _insert_coerce(vt, v, c)
            for c in field_types:
                sub = schema.column(c).geom_subtype \
                    if schema.contains_column(c) else None
                v = row.get(c)
                if sub and v is not None:
                    from .gis import parse_wkt

                    g = parse_wkt(str(v))
                    if g.kind != sub:
                        raise ExecutionError(
                            f"geometry column {c!r} expects {sub}, got "
                            f"{g.kind}")
            rows.append(row)
        wb = WriteBatch.from_rows(stmt.table, rows, tag_names, field_types)
        self.coord.write_points(session.tenant, db, wb)
        return ResultSet(["rows"], [np.array([len(rows)])])

    def _delete(self, stmt: ast.DeleteStmt, session: Session):
        schema = self.meta.table(session.tenant,
                                 stmt.database or session.database,
                                 stmt.table)
        from .planner import split_where

        trs, tag_domains, residual = split_where(stmt.where, schema)
        if residual is not None:
            # reference: non-constant expressions in a DELETE predicate
            # are unimplemented ("operator || in delete statement" —
            # cases/dml/delete.slt); only direct tag/time comparisons
            from .expr import Func as _Func
            from .expr import iter_child_exprs

            def _no_funcs(e):
                if isinstance(e, _Func):
                    raise ExecutionError(
                        f"function {e.name}() in a DELETE predicate is "
                        "not supported")
                for c in iter_child_exprs(e):
                    _no_funcs(c)
            _no_funcs(residual)
            dom_cols = set(tag_domains.domains) if not tag_domains.is_all else set()
            extra = residual.columns() - dom_cols - set(schema.tag_names())
            if extra:
                raise ExecutionError(
                    f"DELETE supports time/tag predicates only, got {sorted(extra)}")
        lo = trs.min_ts if not trs.is_all else -(2**63)
        hi = trs.max_ts if not trs.is_all else 2**63 - 1
        self.coord.delete_from_table(session.tenant,
                                     stmt.database or session.database,
                                     stmt.table, tag_domains, lo, hi)
        self._serving_invalidate(session.tenant,
                                 stmt.database or session.database,
                                 stmt.table)
        return ResultSet.message("ok")

    def _update(self, stmt: ast.UpdateStmt, session: Session):
        db = stmt.database or session.database
        schema = self.meta.table(session.tenant, db, stmt.table)
        tag_names = set(schema.tag_names())
        assigned = set(stmt.assignments)
        if "time" in assigned:
            raise ExecutionError("UPDATE cannot assign the time column")
        if stmt.where is None:
            raise ExecutionError(
                "updating the entire table is disabled; add `where true` "
                "to continue")
        if assigned <= set(schema.field_names()):
            return self._update_fields(stmt, schema, session, db)
        if not assigned <= tag_names:
            raise ExecutionError(
                "UPDATE assigns either tag columns or field columns, "
                "not a mix")
        bad = stmt.where.columns() - tag_names
        if bad:
            # tag UPDATE rewrites whole series; a time/field condition
            # would need per-row splits (reference: "Where clause cannot
            # contain field/time column")
            raise ExecutionError(
                f"tag UPDATE WHERE cannot reference field/time columns, "
                f"found: {sorted(bad)}")
        from .planner import split_where

        _, tag_domains, _ = split_where(stmt.where, schema)
        new_vals = {}
        for k, e in stmt.assignments.items():
            if not isinstance(e, Literal):
                raise ExecutionError("UPDATE tag values must be literals")
            # NULL removes the tag from the series key; the reference
            # allows it as long as ≥1 tag remains (update_tag.slt: both
            # tags → error, one of two → ok)
            v = e.value
            if isinstance(v, bool):
                v = "true" if v else "false"   # SQL bool rendering
            new_vals[k] = None if v is None else str(v)
        owner = f"{session.tenant}.{db}"
        from ..models.series import SeriesKey, Tag

        count = 0
        for v in self.coord.engine.local_vnodes(owner):
            sids = v.index.get_series_ids_by_domains(stmt.table, tag_domains)
            old_keys, new_keys = [], []
            for sid in sids:
                k = v.index.get_series_key(int(sid))
                if k is None:
                    continue
                tags = k.tag_dict()
                tags.update(new_vals)
                tags = {tk: tv for tk, tv in tags.items() if tv is not None}
                if not tags:
                    raise ExecutionError(
                        "UPDATE would leave a series with no tags")
                old_keys.append(k)
                new_keys.append(SeriesKey(stmt.table, tags))
            if old_keys:
                v.update_tags(stmt.table, old_keys, new_keys)
                count += len(old_keys)
        return ResultSet(["series_updated"], [np.array([count])])

    def _update_fields(self, stmt: ast.UpdateStmt, schema, session, db):
        """UPDATE of FIELD columns: scan the matching rows, evaluate the
        assignment expressions over them, write the assigned fields back
        at the same (series, time) — the LSM read path is last-write-wins
        per field, so unassigned fields keep their old values (reference
        dml update_field.slt semantics)."""
        tag_names = schema.tag_names()
        needed: set[str] = set()
        for e in stmt.assignments.values():
            if isinstance(e, Expr):
                needed |= e.columns()
        unknown = needed - set(schema.field_names()) - set(tag_names) \
            - {"time"}
        if unknown:
            raise ExecutionError(
                f"UPDATE expression references unknown column(s) "
                f"{sorted(unknown)}")
        items = [ast.SelectItem(Column("time"), None)]
        for t in tag_names:
            items.append(ast.SelectItem(Column(t), None))
        for c in sorted(needed - {"time"} - set(tag_names)):
            items.append(ast.SelectItem(Column(c), None))
        sel = ast.SelectStmt(items=items, table=stmt.table,
                             where=stmt.where, database=db)
        rs = self._select(sel, session)
        n = rs.n_rows
        if n == 0:
            return ResultSet(["count"], [np.array([0], dtype=np.int64)])
        env = {nm: col for nm, col in zip(rs.names, rs.columns)}
        rows: list[dict] = []
        for i in range(n):
            row: dict = {"time": int(env["time"][i])}
            for t in tag_names:
                v = env[t][i]
                if v is not None:
                    row[t] = v
            rows.append(row)
        field_types = {}
        for fname, e in stmt.assignments.items():
            field_types[fname] = schema.column(fname).column_type.value_type
            vals = e.eval(env, np) if isinstance(e, Expr) else e
            if np.isscalar(vals) or vals is None \
                    or getattr(vals, "shape", None) == ():
                vals = [vals] * n
            for i, row in enumerate(rows):
                v = vals[i]
                if isinstance(v, np.generic):
                    v = v.item()
                if isinstance(v, float) and v != v:
                    v = None
                row[fname] = v
        wb = WriteBatch.from_rows(stmt.table, rows,
                                  [t for t in tag_names], field_types)
        self.coord.write_points(session.tenant, db, wb)
        return ResultSet(["count"], [np.array([n], dtype=np.int64)])

    # ------------------------------------------------------------------ SELECT
    def _explain(self, stmt: ast.ExplainStmt, session: Session):
        if isinstance(stmt.inner, ast.CopyStmt):
            src_txt = stmt.inner.source if isinstance(stmt.inner.source,
                                                      str) else "<query>"
            return ResultSet.message(
                f"CopyExec target={stmt.inner.target} source={src_txt} "
                f"format={stmt.inner.fmt}")
        if isinstance(stmt.inner, ast.InsertStmt) \
                and stmt.inner.select is not None:
            return ResultSet.message(
                f"InsertExec table={stmt.inner.table} source=<query>")
        if not isinstance(stmt.inner, ast.SelectStmt):
            raise ExecutionError("EXPLAIN supports SELECT only")
        sel = stmt.inner
        if sel.table is None:
            return ResultSet.message("Projection (no table)")
        tbl, db = sel.table, sel.database or session.database
        st = None
        if sel.database is None and self.meta.table_opt(
                session.tenant, db, tbl) is None:
            st = self.meta.stream_table(session.tenant, db, tbl)
        if st is not None:
            tbl, db = st["table"], st["db"]
        schema = self.meta.table(session.tenant, db, tbl)
        try:
            plan = plan_select(sel, schema)
        except CnosError as e:
            # EXPLAIN (no execution) tolerates only DEFERRED-to-runtime
            # value errors, the way the reference does (DataFusion defers
            # `time >= 'xxx'` casts to execution); schema/semantic errors
            # still raise
            if stmt.analyze or "bad timestamp" not in str(e):
                raise
            return ResultSet.message(f"PlanningError (deferred): {e}")
        lines = []
        if stmt.analyze:
            import time as _t

            db = sel.database or session.database
            # the inner query runs inside its OWN profile so the rendered
            # breakdown covers exactly this execution; it then folds into
            # any ambient profile (the enclosing statement's) so the
            # stages aren't lost to the outer scope
            prof = stages.QueryProfile(
                node_id=getattr(self.coord, "node_id", None),
                sql=sel.to_sql() if hasattr(sel, "to_sql") else None)
            t0 = _t.perf_counter()
            # execute the SAME plan object that gets printed below
            with stages.profile_scope(prof):
                if isinstance(plan, AggregatePlan):
                    rs = self._exec_aggregate(plan, session.tenant, db)
                else:
                    rs = self._exec_raw(plan, session.tenant, db)
            elapsed = (_t.perf_counter() - t0) * 1e3
            prof.finish(wall_ms=elapsed)
            outer = stages.current_profile()
            if outer is not None:
                outer.merge_child(prof)
            lines.append(f"Execution: {rs.n_rows} rows in {elapsed:.2f}ms")
            # per-stage, per-node breakdown (the reference's DataFusion
            # EXPLAIN ANALYZE metrics rows, merged across the cluster)
            for node, cell in sorted(prof.node_stages().items()):
                for name, value in sorted(cell.items()):
                    lines.append(f"stage node={node} name={name} "
                                 f"value={value}")
            for k, v in sorted(prof.device.items()):
                lines.append(f"device {k}={v}")
        if isinstance(plan, AggregatePlan):
            lines.append("TpuAggregateExec")
            lines.append(f"  table={plan.table}")
            lines.append(f"  time_ranges={plan.time_ranges!r}")
            lines.append(f"  tag_domains={plan.tag_domains!r}")
            lines.append(f"  filter={plan.filter.to_sql() if plan.filter else None}")
            lines.append(f"  group_tags={plan.group_tags}"
                         + (f" group_fields={plan.group_fields}"
                            if plan.group_fields else "")
                         + f" bucket={plan.bucket}")
            lines.append(f"  partial_aggs={[(a.func, a.column) for a in plan.aggs]}")
        else:
            lines.append("TpuScanExec")
            lines.append(f"  table={plan.table}")
            lines.append(f"  time_ranges={plan.time_ranges!r}")
            lines.append(f"  filter={plan.filter.to_sql() if plan.filter else None}")
            lines.append(f"  projection={[n for n, _ in plan.output]}")
        return ResultSet(["plan"], [np.array(lines, dtype=object)])

    def _select(self, stmt: ast.SelectStmt, session: Session):
        from .analyzer import analyze

        # consume-once serving-plane handoff: non-None only for the OUTER
        # statement of a serving-instrumented request — subquery
        # resolution re-enters _select and must stay invisible to the
        # plan/result caches
        sv_state = self.serving.claim() if self.serving is not None \
            else None
        with stages.stage("plan_ms"):
            stmt = self._fold_session_scalars(stmt, session)
        # (subqueries execute here: their scans book their own stages)
        stmt = self._resolve_subqueries(stmt, session)
        with stages.stage("plan_ms"):
            stmt = analyze(stmt)
        if stmt.from_item is not None or self._needs_relational(stmt):
            return self._select_relational(stmt, session)
        if stmt.table is not None:
            stmt = self._strip_table_qualifiers(stmt)
        if stmt.table is None:
            # constant SELECT (SELECT 1)
            from .planner import validate_scalar_sigs_env

            names, cols = [], []
            for i, it in enumerate(stmt.items):
                validate_scalar_sigs_env(it.expr, {})
                v = self._const_aggregate(it.expr) \
                    if self._is_const_agg(it.expr) else it.expr.eval({}, np)
                names.append(it.alias or it.expr.to_sql())
                if isinstance(v, (bytes, bytearray)) or v is None:
                    c = np.empty(1, dtype=object)   # numpy 'S' dtype
                    c[0] = v                        # truncates NUL bytes
                    cols.append(c)
                else:
                    cols.append(np.array([v]))
            return ResultSet(names, cols)
        table = stmt.table
        db = stmt.database or session.database
        st = None
        if stmt.database is None and self.meta.table_opt(
                session.tenant, db, table) is None:
            st = self.meta.stream_table(session.tenant, db, table)
        if st is not None:
            # a stream table reads through to its bound tskv table
            # (reference stream table provider over the base scan); the
            # plan must carry the bound name — the scan reads plan.table
            import dataclasses

            table, db = st["table"], st["db"]
            stmt = dataclasses.replace(stmt, table=table, database=db)
        from .system_tables import is_system_db_for, system_table

        if db == "usage_schema" and table in self.meta.tables.get(
                "cnosdb.usage_schema", {}):
            # usage_schema is a REAL database under the system tenant
            # (metric tables + user tables); other tenants read it as a
            # view filtered to their own rows
            # (usage_schema_privilege.slt, coord_metrics.slt)
            if session.tenant != "cnosdb":
                import dataclasses

                from .expr import BinOp

                tagf = BinOp("=", Column("tenant"),
                             Literal(session.tenant))
                stmt = dataclasses.replace(
                    stmt, where=(tagf if stmt.where is None
                                 else BinOp("and", stmt.where, tagf)))
                session = Session(tenant="cnosdb",
                                  database=session.database,
                                  user=session.user)
        elif is_system_db_for(db, session):
            names, cols = system_table(self, db, table, session)
            has_agg = stmt.group_by or any(
                rel.collect_aggs(it.expr, AGG_FUNCS)
                for it in stmt.items if isinstance(it.expr, Expr))
            if has_agg:
                scope = rel.Scope(names, cols)
                if stmt.where is not None:
                    m = np.asarray(stmt.where.eval(scope.env, np))
                    if not m.shape:
                        m = np.full(scope.n, bool(m))
                    scope = scope.filter(m)
                import dataclasses as _dc

                inner = _dc.replace(stmt, where=None)
                rs, env, order_by = self._host_group_aggregate(inner,
                                                               scope)
                rs = _order_limit(rs, order_by, stmt.limit, stmt.offset,
                                  env)
                return self._distinct(rs) if stmt.distinct else rs
            return self._select_over_env(stmt, names, cols)
        if self.meta.external_opt(session.tenant, db, table) is not None:
            # relational pipeline: aggregates/joins/windows all work over
            # the materialized file (handled in _materialize_from)
            return self._select_relational(stmt, session)
        if (len(stmt.items) == 1 and isinstance(stmt.items[0].expr, Func)
                and stmt.items[0].expr.name.lower() in _REPAIR_FUNCS):
            return self._ts_gen_func(stmt, session)
        schema = self.meta.table(session.tenant, db, table)
        try:
            with stages.stage("plan_ms"):
                plan = plan_select(stmt, schema)
                if sv_state is not None:
                    self.serving.observe_plan(sv_state, stmt, plan, session,
                                              db, table, schema)
            if isinstance(plan, AggregatePlan):
                return self._exec_aggregate(plan, session.tenant, db)
            return self._exec_raw(plan, session.tenant, db)
        except PlanError as e:
            if getattr(e, "fallback_relational", False):
                # e.g. GROUP BY on a field column the segment kernels
                # can't key (non-string field, cardinality blow-up): the
                # relational pipeline groups by arbitrary expressions
                return self._select_relational(stmt, session)
            raise

    def _ts_gen_func(self, stmt: ast.SelectStmt, session: Session):
        """Row-set-valued data repair (reference ts_gen_func/data_repair/:
        timestamp_repair/value_fill/value_repair run as a dedicated exec
        node over the scanned series; here a raw time-ordered scan feeds
        the numpy implementations in sql.tsfuncs).

        Form: SELECT <fn>(time, value[, 'k=v,k=v']) FROM t [WHERE ...]"""
        from . import tsfuncs

        f = stmt.items[0].expr
        name = f.name.lower()
        if stmt.group_by or stmt.having is not None or stmt.distinct:
            raise PlanError(
                f"{name} does not support GROUP BY/HAVING/DISTINCT — "
                "restrict the series with WHERE instead")
        args = list(f.args)
        opts: dict[str, str] = {}
        if args and isinstance(args[-1], Literal) \
                and isinstance(args[-1].value, str):
            # urlencoded-style 'k=v&k=v' (the reference deserializes the
            # option string with deny_unknown_fields: unknown or repeated
            # fields are execution errors); ',' is accepted as a
            # separator alias
            allowed = {"timestamp_repair": {"method", "interval",
                                            "start_mode"},
                       "value_fill": {"method"},
                       "value_repair": {"method", "min_speed", "max_speed",
                                        "center", "sigma"}}[name]
            raw = args.pop().value
            for kv in re.split(r"[&,]", raw):
                kv = kv.strip()
                if not kv:
                    continue
                k, eq, v = kv.partition("=")
                k = k.strip()
                if not eq or k not in allowed:
                    raise PlanError(
                        f"Fail to parse argument: unknown field `{k}`, "
                        f"expected one of "
                        f"{', '.join(sorted(allowed))}")
                if k in opts:
                    raise PlanError(
                        f"Fail to parse argument: duplicate field `{k}`")
                opts[k] = v.strip()
        if len(args) != 2 or not isinstance(args[1], Column):
            raise PlanError(f"{name}(time, value[, 'options']) expected")
        value_col = args[1].name
        base = ast.SelectStmt(
            items=[ast.SelectItem(Column("time")),
                   ast.SelectItem(Column(value_col))],
            table=stmt.table, where=stmt.where, database=stmt.database,
            order_by=[(Column("time"), True)])
        rs = self._select(base, session)
        ts = rs.columns[0].astype(np.int64)
        vals = rs.columns[1].astype(np.float64)

        def _method(valid: set, default: str | None) -> str | None:
            m = opts.get("method", default)
            if m is not None and m.lower() not in valid:
                raise PlanError(f"Invalid method: {m}")
            return m.lower() if m is not None else None

        if name == "timestamp_repair":
            start_mode = opts.get("start_mode")
            if start_mode is not None \
                    and start_mode.lower() not in ("linear", "mode"):
                raise PlanError(f"Invalid start_mode: {start_mode}")
            try:
                interval = int(opts["interval"]) if "interval" in opts \
                    else None
            except ValueError as e:
                raise PlanError(f"Fail to parse argument: {e}")
            # an explicit interval takes precedence and method is then
            # never even validated (timestamp_repair.rs:70-85 checks
            # arg.interval first)
            method = None if interval is not None \
                else _method({"median", "mode", "cluster"}, None)
            new_ts, new_vals = tsfuncs.timestamp_repair(
                ts, vals, method=method, interval=interval,
                start_mode=start_mode.lower() if start_mode else None)
        elif name == "value_fill":
            new_ts = ts
            new_vals = tsfuncs.value_fill(
                ts, vals,
                method=_method({"mean", "previous", "linear", "ar", "ma"},
                               "linear"))
        else:
            new_ts = ts

            def fopt(k):
                try:
                    return float(opts[k]) if k in opts else None
                except ValueError as e:
                    raise PlanError(f"Fail to parse argument: {e}")

            new_vals = tsfuncs.value_repair(
                ts, vals,
                method=_method({"screen", "lsgreedy"}, "screen"),
                min_speed=fopt("min_speed"), max_speed=fopt("max_speed"),
                center=fopt("center"), sigma=fopt("sigma"))
        alias = stmt.items[0].alias or value_col
        out = ResultSet(["time", alias], [new_ts, new_vals])
        env = {"time": new_ts, alias: new_vals, value_col: new_vals}
        return _order_limit(out, stmt.order_by, stmt.limit, stmt.offset, env)

    def _vnode_admin(self, stmt: ast.VnodeAdmin) -> ResultSet:
        """Vnode/replica elasticity ops (reference ast.rs:56-73 +
        raft/manager.rs:323-566)."""
        if stmt.op == "move":
            self.coord.move_vnode(stmt.vnode_id, stmt.node_id)
            return ResultSet.message("ok")
        if stmt.op == "copy":
            new_id = self.coord.copy_vnode(stmt.vnode_id, stmt.node_id)
            return ResultSet(["new_vnode_id"],
                             [np.array([new_id], dtype=np.int64)])
        if stmt.op == "compact":
            self.coord.compact_vnode(stmt.vnode_id)
            return ResultSet.message("ok")
        if stmt.op == "replica_add":
            new_id = self.coord.copy_vnode_to_set(stmt.replica_set_id,
                                                  stmt.node_id)
            return ResultSet(["new_vnode_id"],
                             [np.array([new_id], dtype=np.int64)])
        if stmt.op == "replica_remove":
            self.coord.drop_replica(stmt.vnode_id)
            return ResultSet.message("ok")
        if stmt.op == "replica_promote":
            self.meta.promote_replica(stmt.vnode_id)
            return ResultSet.message("ok")
        if stmt.op == "replica_destory":
            self.coord.destroy_replica_set(stmt.replica_set_id)
            return ResultSet.message("ok")
        if stmt.op == "checksum":
            rows = self.coord.checksum_group(stmt.replica_set_id)
            return ResultSet(
                ["vnode_id", "node_id", "checksum"],
                [np.array([r[0] for r in rows], dtype=np.int64),
                 np.array([r[1] for r in rows], dtype=np.int64),
                 np.array([r[2] for r in rows], dtype=object)])
        raise ExecutionError(f"unsupported vnode admin {stmt.op}")

    def _copy(self, stmt: ast.CopyStmt, session: Session):
        """COPY INTO (reference execution/ddl/copy + object-store sinks):
        export a table to CSV/parquet, or import a file into a table.
        s3:// gcs:// azblob:// paths ride utils.objstore with the
        statement's CONNECTION options."""
        import io

        import pyarrow as pa

        if stmt.target_is_path:
            if isinstance(stmt.source, (ast.SelectStmt, ast.UnionStmt)):
                rs = self.execute_statement(stmt.source, session)
            else:
                rs = self._select(ast.SelectStmt(
                    items=[ast.SelectItem("*")], table=stmt.source),
                    session)
            arrays, fields = [], []
            for n, c in zip(rs.names, rs.columns):
                if c.dtype == object:
                    arrays.append(pa.array(
                        [None if v is None else str(v) for v in c]))
                else:
                    arrays.append(pa.array(c))
                fields.append(n)
            table = pa.table(dict(zip(fields, arrays)))
            from ..utils import objstore

            target = stmt.target
            if target.startswith("file://"):
                target = target[len("file://"):]
            remote = objstore.is_remote(target)
            if not remote:
                # a '/'-terminated target is a directory sink (reference
                # writes part files under the prefix)
                if target.endswith("/") or os.path.isdir(target):
                    os.makedirs(target, exist_ok=True)
                    # append the next part file (re-exports into the same
                    # prefix accumulate, as the reference's sink does)
                    part = 0
                    while os.path.exists(os.path.join(
                            target, f"part-{part}.{stmt.fmt}")):
                        part += 1
                    target = os.path.join(target,
                                          f"part-{part}.{stmt.fmt}")
                else:
                    os.makedirs(os.path.dirname(target) or ".",
                                exist_ok=True)
            sink = io.BytesIO() if remote else target
            if stmt.fmt == "parquet":
                import pyarrow.parquet as pq

                pq.write_table(table, sink)
            else:
                import pyarrow.csv as pc

                pc.write_csv(table, sink)
            if remote:
                objstore.write_uri(stmt.target, sink.getvalue(),
                                   stmt.options)
            return ResultSet(["rows_exported"],
                             [np.array([rs.n_rows], dtype=np.int64)])
        # import: file/object → table (schema must exist; map by name)
        from ..utils import objstore

        source = stmt.source
        if isinstance(source, str) and source.startswith("file://"):
            source = source[len("file://"):]
        if isinstance(source, str) and os.path.isdir(source):
            # directory import: concatenate every part file (reference
            # lists the prefix); parquet readers take the dir directly
            if stmt.fmt != "parquet":
                parts = sorted(
                    os.path.join(source, f) for f in os.listdir(source)
                    if not f.startswith("."))
                import pyarrow.csv as pc

                tables = [pc.read_csv(p) for p in parts]
                table = pa.concat_tables(tables)
                return self._copy_import(stmt, session, table)
        src = objstore.open_source(source, stmt.options)
        if stmt.fmt == "parquet":
            import pyarrow.parquet as pq

            table = pq.read_table(src)
        elif stmt.fmt == "json":
            import pyarrow.json as pj

            table = pj.read_json(src)
        else:
            import pyarrow.csv as pc

            table = pc.read_csv(src)
        return self._copy_import(stmt, session, table)

    def _copy_import(self, stmt: ast.CopyStmt, session: Session, table):
        schema = self.meta.table(session.tenant, session.database,
                                 stmt.target)
        auto_infer = bool((stmt.options.get("__copy_options__") or {})
                          .get("auto_infer_schema"))
        if stmt.columns:
            # COPY INTO t(col, ...): positional mapping of file columns
            if len(stmt.columns) != len(table.column_names):
                raise ExecutionError(
                    f"COPY INTO column list has {len(stmt.columns)} "
                    f"name(s), file has {len(table.column_names)}")
            cols = {stmt.columns[i]: table.column(i).to_pylist()
                    for i in range(len(stmt.columns))}
        elif stmt.fmt == "csv" or auto_infer:
            # csv (and auto_infer_schema mode): positional mapping to the
            # table's declared column order (reference parses the file
            # against the target schema — copy_into_table.slt expects a
            # parse error when the layout doesn't line up, and
            # auto_infer_schema errors on a column-count mismatch)
            order = [c.name for c in schema.columns]
            if len(table.column_names) != len(order):
                raise ExecutionError(
                    f"COPY INTO {stmt.target}: insert columns and source "
                    f"columns not match ({len(table.column_names)} vs "
                    f"{len(order)})")
            cols = {order[i]: table.column(i).to_pylist()
                    for i in range(len(order))}
        else:
            # named formats (parquet/json): map by column NAME; columns
            # absent from the file stay NULL (reference json import)
            cols = {n: table.column(n).to_pylist()
                    for n in table.column_names}
            unknown = [c for c in cols
                       if c != "time" and not schema.contains_column(c)]
            if unknown:
                raise ExecutionError(
                    f"COPY INTO {stmt.target}: file column(s) "
                    f"{sorted(unknown)} not in target schema")
        if "time" not in cols:
            raise ExecutionError("COPY INTO table requires a time column")
        n = len(cols["time"])
        tag_names = [c for c in cols if schema.contains_column(c)
                     and schema.column(c).column_type.is_tag]
        field_types = {c: schema.column(c).column_type.value_type
                       for c in cols if schema.contains_column(c)
                       and schema.column(c).column_type.is_field}
        rows = [{c: cols[c][i] for c in cols} for i in range(n)]
        wb = WriteBatch.from_rows(stmt.target, rows, tag_names, field_types)
        self.coord.write_points(session.tenant, session.database, wb)
        return ResultSet(["rows_imported"], [np.array([n], dtype=np.int64)])

    # ------------------------------------------------------- relational path
    def _needs_relational(self, stmt: ast.SelectStmt) -> bool:
        """Window functions and aggregates over computed expressions
        (sum(a*b)) route through the relational pipeline — it evaluates
        aggregate arguments as expressions; plain single-table queries
        keep the fused-kernel path."""
        exprs = [it.expr for it in stmt.items if isinstance(it.expr, Expr)]
        exprs += [e for e in (stmt.where, stmt.having) if e is not None]
        exprs += [e for e, _ in stmt.order_by if isinstance(e, Expr)]
        exprs += [g for g in stmt.group_by if isinstance(g, Expr)]
        if any(rel.contains_window(e) for e in exprs):
            return True
        if stmt.table is not None or stmt.from_item is not None:
            tw = []
            for e in exprs:
                rel.walk_exprs(e, lambda x: tw.append(x)
                               if isinstance(x, Func)
                               and x.name.lower() == "time_window" else None)
            if tw:
                # TIME_WINDOW row expansion lives in the relational
                # pipeline (_expand_time_window); the no-FROM constant
                # form evaluates via the scalar Func registration
                return True
        for e in exprs:
            for f in rel.collect_aggs(e, AGG_FUNCS):
                args = f.args
                if args and isinstance(args[0], Literal) \
                        and args[0].value == "__distinct__":
                    args = args[1:]
                if any(not isinstance(a, (Column, Literal))
                       for a in args):
                    # computed argument ANYWHERE (corr(f1, -f1)): the
                    # relational path evaluates expressions
                    return True
        return False

    def _catalog_columns(self, from_item, table: str | None,
                         session: Session) -> set | None:
        """Column-name set of a FROM clause, resolved from catalog
        metadata only (no execution) — None when any relation's columns
        can't be known statically. Lets decorrelation classify
        UNQUALIFIED outer references (tpch q2/q17/q20 correlate on bare
        column names)."""
        def of_item(item):
            if item is None:
                return set()
            if isinstance(item, ast.TableRef):
                db = item.database or session.database
                sch = self.meta.table_opt(session.tenant, db, item.name)
                if sch is not None:
                    return set(sch.field_names()) | set(sch.tag_names()) \
                        | {"time"}
                ext = self.meta.external_opt(session.tenant, db, item.name)
                if ext is not None and ext.get("columns"):
                    return {c[0] for c in ext["columns"]}
                return None
            if isinstance(item, ast.Join):
                a = of_item(item.left)
                b = of_item(item.right)
                return None if a is None or b is None else a | b
            return None   # derived tables / VALUES: undeterminable here

        if from_item is not None:
            return of_item(from_item)
        if table is not None:
            return of_item(ast.TableRef(table, None, None))
        return set()

    def _split_correlation(self, q, session: Session,
                           outer_cols: set | None = None):
        """Shared decorrelation front end: analyze the subquery body and
        split its WHERE into correlated equality pairs and a local
        residual (reference: DataFusion's subquery optimizer rules,
        query_server/query/src/sql/logical/optimizer.rs:66-108).
        → (analyzed_q, [(outer_expr, inner_expr)], residual) or None when
        the body has no extractable correlation (uncorrelated, or
        correlation in an unsupported position)."""
        if not isinstance(q, ast.SelectStmt) or q.where is None:
            return None
        # Normalize first (exact_count→count, topk→ORDER BY+LIMIT, …) so
        # the guards see the executable shape — an un-analyzed
        # exact_count would slip past the aggregate checks.
        from .analyzer import analyze

        q = analyze(q)
        local_quals = self._from_qualifiers(q)
        if not local_quals:
            return None
        # column-level resolution for UNQUALIFIED names: a bare column
        # that is NOT in the subquery's own relations but IS in the outer
        # query's is a correlated reference (catalog-only check; when the
        # inner columns can't be known statically, bare names stay local,
        # the pre-existing conservative behavior)
        local_cols = self._catalog_columns(q.from_item, q.table, session)

        def col_outer(c: str) -> bool:
            if "." in c:
                return c.split(".", 1)[0] not in local_quals
            return (local_cols is not None and outer_cols
                    and c not in local_cols and c in outer_cols)

        def is_outer(expr: Expr) -> bool:
            cols = expr.columns()
            return bool(cols) and all(col_outer(c) for c in cols)

        def is_local(expr: Expr) -> bool:
            return not any(col_outer(c) for c in expr.columns())

        pairs = []            # [(outer_expr, inner_expr)]
        residual = []         # fully-local conjuncts
        cross = []            # conjuncts mixing inner and outer columns
        from .relational import _split_conjuncts

        for c in _split_conjuncts(q.where):
            took = False
            if isinstance(c, expr_mod.BinOp) and c.op == "=":
                for outer, inner in ((c.left, c.right), (c.right, c.left)):
                    if is_outer(outer) and is_local(inner) \
                            and inner.columns():
                        pairs.append((outer, inner))
                        took = True
                        break
            if not took:
                if is_local(c) and not is_outer(c):
                    residual.append(c)
                else:
                    cross.append(c)
        if not pairs:
            return None
        return q, pairs, residual, cross, col_outer

    @staticmethod
    def _py_rows(rs):
        """ResultSet columns → per-row python tuples, normalized through
        the SAME helper the probe side uses (expr._rows_of: np-scalar
        unwrap, NaN→None) so build/probe key equality can't drift."""
        from .expr import _rows_of

        if not rs.columns:
            return []
        n = rs.n_rows
        cols = [_rows_of(c, n) for c in rs.columns]
        return list(zip(*cols))

    def _decorrelate_exists(self, e, session: Session,
                            outer_cols: set | None = None):
        """Correlated EXISTS (`EXISTS (SELECT .. FROM u WHERE u.k = t.k
        AND <local preds>)`) → semi-join: one equality conjunct becomes
        an IN over the inner key set, several become a KeyInSet over key
        tuples; NOT EXISTS → the anti-join form (outer NULL keys stay,
        unlike NOT IN's 3VL). Returns the replacement Expr or None."""
        split = self._split_correlation(e.select, session, outer_cols)
        if split is None:
            return None
        q, pairs, residual, cross, col_outer = split
        if q.group_by or q.having is not None or q.order_by or \
                q.limit is not None or q.offset:
            return None   # EXISTS bodies with those don't need them anyway
        contains_agg = any(rel.collect_aggs(it.expr, AGG_FUNCS)
                           for it in q.items if isinstance(it.expr, Expr))
        import copy as _copy
        import dataclasses

        if contains_agg:
            # An ungrouped aggregate subquery yields exactly one row no
            # matter what the WHERE matches, so EXISTS is unconditionally
            # true (and NOT EXISTS false) — never a semi-join. Execute the
            # body with the correlation conjunct dropped first so invalid
            # names (bad table/column) still raise instead of being
            # silently short-circuited away. Name resolution happens at
            # plan time, so a constant-false time bound prunes the probe's
            # scan to nothing (single-table bodies only: in a join body an
            # unqualified `time` would be ambiguous).
            probe_where = self._conjoin(residual)
            if q.from_item is None:
                never = expr_mod.BinOp("<", Column("time"),
                                       Literal(-(2 ** 62)))
                probe_where = never if probe_where is None \
                    else expr_mod.BinOp("and", probe_where, never)
            probe = dataclasses.replace(q, where=probe_where)
            self._select(probe, session)
            return Literal(not e.negated)
        if cross:
            # cross-correlation conjuncts (inner col vs outer col, tpch
            # q21): semi-join on the equality keys, then evaluate the
            # remaining conjuncts per (outer row, inner candidate)
            return self._decorrelate_exists_cross(
                e, q, pairs, residual, cross, col_outer, session)
        inner_q = dataclasses.replace(
            _copy.copy(q),
            items=[ast.SelectItem(inner, f"__ck{i}")
                   for i, (_o, inner) in enumerate(pairs)],
            where=self._conjoin(residual))
        rs = self._select(inner_q, session)
        if len(pairs) == 1:
            outer_expr = pairs[0][0]
            vals = [v.item() if hasattr(v, "item") else v
                    for v in rs.columns[0]]
            non_null = [v for v in vals if v is not None
                        and not (isinstance(v, float) and v != v)]
            keys = sorted(set(non_null), key=repr)
            if e.negated:
                # anti-join: a NULL outer key has no match → row KEPT (3VL
                # NOT IN would drop it, so spell the NULL case explicitly)
                return expr_mod.BinOp(
                    "or", expr_mod.IsNull(outer_expr),
                    InList(outer_expr, keys, negated=True))
            return InList(outer_expr, keys, False)
        # composite correlation key: tuple-membership semi/anti-join
        keys = {row for row in self._py_rows(rs)
                if not any(k is None for k in row)}
        return expr_mod.KeyInSet([o for o, _i in pairs], keys, e.negated)

    def _decorrelate_exists_cross(self, e, q, pairs, residual, cross,
                                  col_outer, session: Session):
        """EXISTS with mixed inner/outer conjuncts → CorrExists: inner
        rows bucket by the equality keys carrying the columns the cross
        conjuncts need; those conjuncts re-evaluate per candidate."""
        import copy as _copy
        import dataclasses

        inner_cols: list[str] = []
        outer_cols_used: list[str] = []
        for c in cross:
            for col in sorted(c.columns()):
                if col_outer(col):
                    if col not in outer_cols_used:
                        outer_cols_used.append(col)
                elif col not in inner_cols:
                    inner_cols.append(col)
        inner_map = {c: f"__cc{i}" for i, c in enumerate(inner_cols)}
        outer_map = {c: f"__oc{i}" for i, c in enumerate(outer_cols_used)}

        def rw(conj):
            return rel.rewrite_exprs(
                conj,
                lambda x: isinstance(x, Column)
                and (x.name in inner_map or x.name in outer_map),
                lambda x: Column(inner_map.get(x.name)
                                 or outer_map[x.name]))

        cross_rw = [rw(c) for c in cross]
        items = [ast.SelectItem(inner, f"__ck{i}")
                 for i, (_o, inner) in enumerate(pairs)]
        items += [ast.SelectItem(Column(c), inner_map[c])
                  for c in inner_cols]
        inner_q = dataclasses.replace(
            _copy.copy(q), items=items, where=self._conjoin(residual))
        rs = self._select(inner_q, session)
        n_eq = len(pairs)
        inner_rows: dict = {}
        for row in self._py_rows(rs):
            key = row[:n_eq]
            if any(k is None for k in key):
                continue
            inner_rows.setdefault(key, []).append(
                {inner_map[c]: v
                 for c, v in zip(inner_cols, row[n_eq:])})
        args = [o for o, _i in pairs] + [Column(c)
                                         for c in outer_cols_used]
        return expr_mod.CorrExists(
            args, n_eq, [outer_map[c] for c in outer_cols_used],
            inner_rows, cross_rw, e.negated)

    def _decorrelate_scalar(self, e, session: Session,
                            outer_cols: set | None = None):
        """Correlated scalar subquery → grouped-aggregate lookup
        (scalar-subquery-to-join): run the body once GROUPED BY its
        correlation columns, then map each outer row's key through the
        result. COUNT-shaped bodies default to 0 on missing keys, others
        to NULL; non-aggregate bodies enforce at-most-one-row per probed
        key. Returns a CorrLookup or None when not this pattern."""
        split = self._split_correlation(e.select, session, outer_cols)
        if split is None:
            return None
        q, pairs, residual, cross, _co = split
        if cross:
            return None   # mixed inner/outer conjuncts: EXISTS-only form
        if q.group_by or q.having is not None or q.order_by or \
                q.limit is not None or q.offset or len(q.items) != 1:
            return None
        item = q.items[0].expr
        if not isinstance(item, Expr):
            return None
        import copy as _copy
        import dataclasses

        key_items = [ast.SelectItem(inner, f"__ck{i}")
                     for i, (_o, inner) in enumerate(pairs)]
        outer_exprs = [o for o, _i in pairs]
        aggs = rel.collect_aggs(item, AGG_FUNCS)
        if aggs:
            if isinstance(item, Func) \
                    and item.name.lower() in ("count", "exact_count",
                                              "approx_distinct"):
                default = 0
            elif any(a.name.lower() in ("count", "exact_count",
                                        "approx_distinct") for a in aggs):
                # an expression AROUND count (count(*)+1) needs the
                # empty-group value of the whole expression — punt
                return None
            else:
                default = None
            inner_q = dataclasses.replace(
                _copy.copy(q),
                items=key_items + [ast.SelectItem(item, "__v")],
                where=self._conjoin(residual),
                group_by=[inner for _o, inner in pairs])
            rs = self._select(inner_q, session)
            mapping = {row[:-1]: row[-1] for row in self._py_rows(rs)
                       if not any(k is None for k in row[:-1])}
            return expr_mod.CorrLookup(outer_exprs, mapping, default)
        # non-aggregate body: at most one inner row may match any probed
        # key — group and keep a duplicate sentinel that raises only if
        # an outer row actually probes it
        inner_q = dataclasses.replace(
            _copy.copy(q),
            items=key_items + [ast.SelectItem(item, "__v")],
            where=self._conjoin(residual))
        rs = self._select(inner_q, session)
        mapping: dict = {}
        for row in self._py_rows(rs):
            key = row[:-1]
            if any(k is None for k in key):
                continue
            if key in mapping:
                mapping[key] = expr_mod._SCALAR_DUP
            else:
                mapping[key] = row[-1]
        return expr_mod.CorrLookup(outer_exprs, mapping, None)

    def _decorrelate_in(self, e, session: Session,
                        outer_cols: set | None = None):
        """Correlated IN subquery (`a [NOT] IN (SELECT v FROM u WHERE
        u.k = t.k ..)`) → per-key membership with full three-valued
        logic (CorrIn). Returns the replacement Expr or None."""
        split = self._split_correlation(e.select, session, outer_cols)
        if split is None:
            return None
        q, pairs, residual, cross, _co = split
        if cross:
            return None   # mixed inner/outer conjuncts: EXISTS-only form
        if q.group_by or q.having is not None or q.order_by or \
                q.limit is not None or q.offset or len(q.items) != 1:
            return None
        item = q.items[0].expr
        if not isinstance(item, Expr) or rel.collect_aggs(item, AGG_FUNCS):
            return None
        import copy as _copy
        import dataclasses

        inner_q = dataclasses.replace(
            _copy.copy(q),
            items=[ast.SelectItem(inner, f"__ck{i}")
                   for i, (_o, inner) in enumerate(pairs)]
            + [ast.SelectItem(item, "__v")],
            where=self._conjoin(residual))
        rs = self._select(inner_q, session)
        pairs_set: set = set()
        keyed: set = set()
        null_keys: set = set()
        for row in self._py_rows(rs):
            key, v = row[:-1], row[-1]
            if any(k is None for k in key):
                continue
            keyed.add(key)
            if v is None:
                null_keys.add(key)
            else:
                pairs_set.add(key + (v,))
        return expr_mod.CorrIn([e.expr] + [o for o, _i in pairs],
                               pairs_set, keyed, null_keys, e.negated)

    @staticmethod
    def _conjoin(cs):
        out = None
        for c in cs:
            out = c if out is None else expr_mod.BinOp("and", out, c)
        return out

    @staticmethod
    def _from_qualifiers(q: ast.SelectStmt) -> set:
        """Relation qualifiers visible inside a subquery's own FROM."""
        quals: set = set()

        def visit(item):
            if item is None:
                return
            if isinstance(item, ast.TableRef):
                quals.add(item.alias or item.name)
            elif isinstance(item, ast.SubqueryRef):
                quals.add(item.alias)
            elif isinstance(item, ast.Join):
                visit(item.left)
                visit(item.right)

        visit(q.from_item)
        if q.table:
            quals.add(q.table)
        return quals

    def _resolve_subqueries(self, stmt: ast.SelectStmt, session: Session):
        """Execute uncorrelated scalar / IN subqueries and splice their
        results in as literals; correlated EXISTS decorrelates to
        semi/anti-joins (reference: DataFusion subquery rules)."""
        # fold NOT over EXISTS into the node FIRST: anti-join NULL
        # semantics differ from 3VL NOT over the semi-join replacement
        def fold_pred(e):
            return isinstance(e, expr_mod.UnaryOp) and e.op == "not" \
                and isinstance(e.operand, expr_mod.Exists)

        def fold(e):
            return expr_mod.Exists(e.operand.select,
                                   not e.operand.negated)

        import dataclasses as _dc

        stmt = _dc.replace(
            stmt,
            items=[ast.SelectItem(
                rel.rewrite_exprs(it.expr, fold_pred, fold)
                if isinstance(it.expr, Expr) else it.expr, it.alias)
                for it in stmt.items],
            where=(rel.rewrite_exprs(stmt.where, fold_pred, fold)
                   if stmt.where is not None else None),
            having=(rel.rewrite_exprs(stmt.having, fold_pred, fold)
                    if stmt.having is not None else None))
        found = []

        def spot(e):
            if isinstance(e, (Subquery, InSubquery, expr_mod.Exists)):
                found.append(e)

        exprs = [it.expr for it in stmt.items if isinstance(it.expr, Expr)]
        exprs += [e for e in (stmt.where, stmt.having) if e is not None]
        for e in exprs:
            rel.walk_exprs(e, spot)
        if not found:
            return stmt

        outer_cols = self._catalog_columns(stmt.from_item, stmt.table,
                                           session)

        def replace(e):
            q = e.select
            if isinstance(e, expr_mod.Exists):
                corr = self._decorrelate_exists(e, session, outer_cols)
                if corr is not None:
                    return corr
            elif isinstance(e, Subquery):
                corr = self._decorrelate_scalar(e, session, outer_cols)
                if corr is not None:
                    return corr
            elif isinstance(e, InSubquery):
                corr = self._decorrelate_in(e, session, outer_cols)
                if corr is not None:
                    return corr
            rs = self._union(q, session) if isinstance(q, ast.UnionStmt) \
                else self._select(q, session)
            if isinstance(e, expr_mod.Exists):
                hit = rs.n_rows > 0
                return Literal((not hit) if e.negated else hit)
            if isinstance(e, Subquery):
                if len(rs.columns) != 1 or rs.n_rows > 1:
                    raise QueryError(
                        "scalar subquery must return a single value")
                if rs.n_rows == 0:
                    return Literal(None)
                v = rs.columns[0][0]
                return Literal(v.item() if hasattr(v, "item") else v)
            if len(rs.columns) != 1:
                raise QueryError("IN subquery must return a single column")
            vals = [v.item() if hasattr(v, "item") else v
                    for v in rs.columns[0]]
            non_null = [v for v in vals if v is not None]
            return InList(e.expr, non_null, e.negated,
                          null_present=len(non_null) != len(vals))

        import copy as _copy

        out = _copy.copy(stmt)
        pred = lambda e: isinstance(  # noqa: E731
            e, (Subquery, InSubquery, expr_mod.Exists))
        out.items = [ast.SelectItem(rel.rewrite_exprs(it.expr, pred, replace)
                                    if isinstance(it.expr, Expr) else it.expr,
                                    it.alias) for it in stmt.items]
        if stmt.where is not None:
            out.where = rel.rewrite_exprs(stmt.where, pred, replace)
        if stmt.having is not None:
            out.having = rel.rewrite_exprs(stmt.having, pred, replace)
        return out

    def _is_const_agg(self, e) -> bool:
        from .planner import AGG_FUNCS

        return (isinstance(e, Func) and e.name.lower() in AGG_FUNCS
                and all(isinstance(a, Literal) for a in e.args))

    def _const_aggregate(self, e: Func):
        """Aggregate over a literal with no FROM: one conceptual row
        (reference: `select mode(null)` is NULL, `select count(null)`
        is 0 — function/common/mode.slt, count.slt)."""
        name = e.name.lower()
        if not e.args:
            raise PlanError(f"{e.name}() requires an argument")
        if name in ("approx_percentile_cont",
                    "approx_percentile_cont_with_weight") \
                and len(e.args) < 2:
            raise PlanError(
                f"{e.name} requires a column and a constant quantile")
        v = e.args[0].value
        if name in ("count", "count_distinct", "approx_distinct"):
            return 0 if v is None else 1
        if v is None:
            return None
        if name in ("avg", "mean", "median", "sum", "stddev_pop",
                    "var_pop", "approx_median"):
            return float(v) if name != "sum" else v
        return v

    def _fold_session_scalars(self, stmt: ast.SelectStmt, session):
        """current_user()/current_tenant()/current_database()/
        current_role() fold to the SESSION's values (reference
        session.rs scalars are session-bound; current_role is NULL in
        the single-role default)."""
        from datetime import datetime, timezone

        from .expr import DateLit, TimeOfDayLit

        role = self.meta.members.get(session.tenant, {}).get(session.user)
        now = datetime.now(timezone.utc)
        vals = {"current_user": session.user,
                "current_tenant": session.tenant,
                "current_database": session.database,
                "current_role": role}
        # date/time scalars fold ONCE per statement (reference:
        # current_time() = current_time() is true within a query —
        # time_functions/current_time.slt)
        typed = {"current_date": DateLit(now.strftime("%Y-%m-%d")),
                 "current_time": TimeOfDayLit(
                     now.strftime("%H:%M:%S.%f"))}

        def hit(x):
            return isinstance(x, Func) and not x.args \
                and x.name.lower() in (*vals, *typed, "arrow_typeof")

        def sub(x):
            n = x.name.lower()
            if n in typed:
                return typed[n]
            return Literal(vals[n])

        def hit_typeof(x):
            return isinstance(x, Func) and x.name.lower() == \
                "arrow_typeof" and len(x.args) == 1

        def sub_typeof(x):
            a = x.args[0]
            if isinstance(a, DateLit):
                t = "Date32"
            elif isinstance(a, TimeOfDayLit):
                t = "Time64(Nanosecond)"
            elif isinstance(a, Literal):
                v = a.value
                t = ("Boolean" if isinstance(v, bool) else
                     "Int64" if isinstance(v, int) else
                     "Float64" if isinstance(v, float) else
                     "Utf8" if isinstance(v, str) else "Null")
            elif isinstance(a, Column) and a.name.endswith("time"):
                t = 'Timestamp(Nanosecond, None)'
            else:
                raise ExecutionError("arrow_typeof over expressions is "
                                     "not supported")
            return Literal(t)

        import dataclasses

        def fold(e):
            if not isinstance(e, Expr):
                return e
            e = rel.rewrite_exprs(e, hit, sub)
            return rel.rewrite_exprs(e, hit_typeof, sub_typeof)

        changed = dataclasses.replace(
            stmt,
            items=[ast.SelectItem(fold(it.expr), it.alias)
                   for it in stmt.items],
            where=fold(stmt.where) if stmt.where is not None else None,
            having=fold(stmt.having) if stmt.having is not None else None)
        return changed

    def _strip_table_qualifiers(self, stmt: ast.SelectStmt):
        """`SELECT m2.f0 FROM m2 WHERE m2.f1 > 0` — a single-table query
        may qualify columns with the table (or db.table) name; resolve to
        bare names before planning (joins handle qualifiers in the
        relational scope instead)."""
        import dataclasses

        quals = [stmt.table + "."]
        if stmt.database:
            quals.append(f"{stmt.database}.{stmt.table}.")

        def strip(e):
            if not isinstance(e, Expr):
                return e
            out = e
            for q in quals:
                out = rel.rewrite_exprs(
                    out, lambda x: isinstance(x, Column)
                    and x.name.startswith(q),
                    lambda x: Column(x.name[len(q):]))
            return out

        changed = dataclasses.replace(
            stmt,
            items=[ast.SelectItem(strip(it.expr), it.alias)
                   for it in stmt.items],
            where=strip(stmt.where), having=strip(stmt.having),
            order_by=[(strip(oe), asc) for oe, asc in stmt.order_by],
            group_by=[strip(g) for g in stmt.group_by])
        return changed

    def _strip_alias(self, e: Expr, alias: str | None) -> Expr:
        """alias.col → col for pushdown into the aliased base relation."""
        if alias is None or e is None:
            return e
        prefix = alias + "."
        return rel.rewrite_exprs(
            e, lambda x: isinstance(x, Column) and x.name.startswith(prefix),
            lambda x: Column(x.name[len(prefix):]))

    def _materialize_from(self, item, session: Session,
                          pushed_where: Expr | None = None) -> rel.Scope:
        """FROM item → Scope. Base tables materialize through the normal
        single-table path (predicate pushdown, fused kernels, system
        tables); joins compose host-side (reference: TskvExec leaves under
        DataFusion join operators)."""
        if isinstance(item, ast.TableRef):
            # an unaliased table is addressable by its own name
            # (`FROM o JOIN c ON o.cust = c.cust` — standard SQL); an
            # explicit alias REPLACES the table name as the qualifier
            qual = item.alias or item.name
            ext = self.meta.external_opt(
                session.tenant, item.database or session.database, item.name)
            if ext is not None:
                names, cols = _load_external(ext)
                scope = rel.Scope.from_relation(names, cols, qual)
                if pushed_where is not None:
                    w = self._strip_alias(pushed_where, qual)
                    m = np.asarray(w.eval(scope.env, np))
                    if not m.shape:
                        m = np.full(scope.n, bool(m))
                    scope = scope.filter(m)
                return scope
            sub = ast.SelectStmt(
                items=[ast.SelectItem("*")], table=item.name,
                where=self._strip_alias(pushed_where, qual),
                database=item.database)
            rs = self._select(sub, session)
            return rel.Scope.from_relation(rs.names, rs.columns, qual)
        if isinstance(item, ast.ValuesRef):
            width = len(item.rows[0]) if item.rows else 0
            names = item.columns or [f"column{i + 1}"
                                     for i in range(width)]
            cols = []
            for i in range(width):
                vals = [r[i] for r in item.rows]
                if all(isinstance(v, bool) for v in vals):
                    cols.append(np.array(vals, dtype=bool))
                elif all(isinstance(v, int) and not isinstance(v, bool)
                         for v in vals):
                    cols.append(np.array(vals, dtype=np.int64))
                elif all(isinstance(v, (int, float))
                         and not isinstance(v, bool) for v in vals):
                    cols.append(np.array(vals, dtype=np.float64))
                else:
                    c = np.empty(len(vals), dtype=object)
                    c[:] = vals
                    cols.append(c)
            scope = rel.Scope.from_relation(names, cols, item.alias)
            if pushed_where is not None:
                w = self._strip_alias(pushed_where, item.alias)
                m = np.asarray(w.eval(scope.env, np))
                if not m.shape:
                    m = np.full(scope.n, bool(m))
                scope = scope.filter(m)
            return scope
        if isinstance(item, ast.SubqueryRef):
            q = item.select
            rs = self._union(q, session) if isinstance(q, ast.UnionStmt) \
                else self._select(q, session)
            names = rs.names
            aliases = getattr(item, "col_aliases", None)
            if aliases:
                # derived-table column list renames positionally
                # (tpch.slt q13: FROM (...) AS c_orders (c_custkey, c_count))
                if len(aliases) > len(names):
                    raise PlanError(
                        f"derived table {item.alias} declares "
                        f"{len(aliases)} columns, query returns "
                        f"{len(names)}")
                names = list(aliases) + names[len(aliases):]
            # pushed_where (if any) applies post-materialization
            scope = rel.Scope.from_relation(names, rs.columns, item.alias)
            if pushed_where is not None:
                w = self._strip_alias(pushed_where, item.alias)
                m = np.asarray(w.eval(scope.env, np))
                if not m.shape:
                    m = np.full(scope.n, bool(m))
                scope = scope.filter(m)
            return scope
        if isinstance(item, ast.Join):
            scope = self._join_optimized(item, session)
            if scope is None:
                left = self._materialize_from(item.left, session)
                right = self._materialize_from(item.right, session)
                scope = rel.hash_join(left, right, item.kind, item.on)
            if pushed_where is not None:
                m = np.asarray(pushed_where.eval(scope.env, np))
                if not m.shape:
                    m = np.full(scope.n, bool(m))
                scope = scope.filter(m)
            return scope
        raise PlanError(f"unsupported FROM item {type(item).__name__}")

    def _join_optimized(self, item: ast.Join, session: Session):
        """Cost-based ordering for maximal inner-join trees (exact
        cardinalities — relations are materialized; sql/join_order.py).
        None → structure not proven safe, caller runs written order."""
        from . import join_order

        leaf_items, conjuncts = join_order.flatten_inner(item)
        if len(leaf_items) < 3:   # nothing to reorder; don't materialize twice
            return None
        leaves = [self._materialize_from(li, session) for li in leaf_items]
        if not join_order.reorderable(leaves, conjuncts):
            # structural decline AFTER materialization: replay the written
            # tree over the already-materialized leaves (no double scan)
            it = iter(leaves)
            return self._join_written(item, it)
        return join_order.order_and_join(leaves, conjuncts)

    def _join_written(self, item, leaf_iter) -> rel.Scope:
        # outer-join subtrees are LEAVES of the flattened inner region
        # (they materialized as one scope) — only inner joins recurse
        if isinstance(item, ast.Join) and item.kind == "inner":
            left = self._join_written(item.left, leaf_iter)
            right = self._join_written(item.right, leaf_iter)
            return rel.hash_join(left, right, item.kind, item.on)
        return next(leaf_iter)

    def _select_relational(self, stmt: ast.SelectStmt, session: Session):
        item = stmt.from_item or ast.TableRef(stmt.table, None, stmt.database)
        where = stmt.where
        pushed = None
        if isinstance(item, ast.TableRef) and where is not None \
                and not rel.contains_window(where):
            pushed, where = where, None   # full pushdown into the base scan
        scope = self._materialize_from(item, session, pushed)
        # schema-aware scalar signature checks over the materialized
        # scope (the single-table path validates in plan_select)
        from .planner import validate_scalar_sigs_env

        for it in stmt.items:
            if isinstance(it.expr, Expr):
                validate_scalar_sigs_env(it.expr, scope.env)
        for _e in (stmt.where, stmt.having):
            if _e is not None:
                validate_scalar_sigs_env(_e, scope.env)
        if where is not None:
            if rel.contains_window(where):
                raise PlanError("window functions are not allowed in WHERE")
            m = np.asarray(where.eval(scope.env, np))
            if not m.shape:
                m = np.full(scope.n, bool(m))
            scope = scope.filter(m)

        scope, stmt = self._expand_time_window(stmt, scope)

        has_agg = any(
            rel.collect_aggs(it.expr, AGG_FUNCS)
            for it in stmt.items if isinstance(it.expr, Expr))
        if stmt.group_by or has_agg:
            win_exprs = [it.expr for it in stmt.items
                         if isinstance(it.expr, Expr)]
            win_exprs += [e for e, _ in stmt.order_by if isinstance(e, Expr)]
            if any(rel.contains_window(e) for e in win_exprs):
                raise PlanError(
                    "window functions cannot mix with GROUP BY in one "
                    "SELECT — wrap the aggregate in a subquery")
            rs, env, order_by = self._host_group_aggregate(stmt, scope)
            rs = _order_limit(rs, order_by, stmt.limit, stmt.offset, env)
            return self._distinct(rs) if stmt.distinct else rs

        # window evaluation over the filtered scope, then projection
        win_map: dict[int, str] = {}
        wfs: list[WindowFunc] = []
        for it in stmt.items:
            if isinstance(it.expr, Expr):
                rel.walk_exprs(it.expr, lambda e: wfs.append(e)
                               if isinstance(e, WindowFunc) else None)
        for e, _ in stmt.order_by:
            if isinstance(e, Expr):
                rel.walk_exprs(e, lambda x: wfs.append(x)
                               if isinstance(x, WindowFunc) else None)
        env = dict(scope.env)
        for i, wf in enumerate(wfs):
            alias = f"__win{i}"
            env[alias] = rel.eval_window(wf, scope.env, scope.n)
            win_map[id(wf)] = alias

        def unwin(e):
            if not isinstance(e, Expr):
                return e
            return rel.rewrite_exprs(
                e, lambda x: isinstance(x, WindowFunc),
                lambda x: Column(win_map[id(x)]))

        out_names, out_cols = [], []
        for it in stmt.items:
            if it.expr == "*":
                out_names.extend(scope.names)
                out_cols.extend(scope.cols)
                continue
            v = unwin(it.expr).eval(env, np)
            if np.isscalar(v) or getattr(v, "shape", None) == ():
                v = np.full(scope.n, v)
            out_names.append(_out_name(it))
            out_cols.append(np.asarray(v))
        rs = ResultSet(out_names, out_cols)
        env_all = dict(env)
        for nm, c in zip(out_names, out_cols):
            env_all.setdefault(nm, c)
        order_by = [(unwin(e), asc) for e, asc in stmt.order_by]
        rs = _order_limit(rs, order_by, stmt.limit, stmt.offset, env_all)
        return self._distinct(rs) if stmt.distinct else rs

    def _expand_time_window(self, stmt: ast.SelectStmt, scope: rel.Scope):
        """Row-expanding TIME_WINDOW (reference transform_time_window.rs:
        TIME_WINDOW → Expand): every row joins each sliding window that
        contains its timestamp; the call sites are rewritten to a struct
        column ({start, end} dicts) and all scope columns re-index by the
        expansion. One distinct call per SELECT (upstream restriction)."""
        calls: list[Func] = []

        def spot(e):
            if isinstance(e, Func) and not isinstance(e, WindowFunc) \
                    and e.name.lower() == "time_window":
                calls.append(e)

        exprs = [it.expr for it in stmt.items if isinstance(it.expr, Expr)]
        exprs += [g for g in stmt.group_by if isinstance(g, Expr)]
        exprs += [e for e, _ in stmt.order_by if isinstance(e, Expr)]
        if stmt.having is not None:
            exprs.append(stmt.having)
        for e in exprs:
            rel.walk_exprs(e, spot)
        if not calls:
            return scope, stmt
        sigs = {c.to_sql() for c in calls}
        if len(sigs) > 1:
            raise PlanError(
                "only one TIME_WINDOW expression per SELECT is supported")
        f = calls[0]
        if not 2 <= len(f.args) <= 4:
            raise PlanError(
                "time_window(time, window[, slide[, start_time]])")
        t = np.asarray(f.args[0].eval(scope.env, np))
        if t.dtype == object:
            # struct-field access (tsbench windows over window.start of
            # an inner time_window) yields object ints; NULL rows drop
            keep0 = np.array([isinstance(x, (int, np.integer))
                              and not isinstance(x, (bool, np.bool_))
                              for x in t], dtype=bool)
            if not keep0.all():
                scope = scope.filter(keep0)
                t = t[keep0]
            t = t.astype(np.int64) if len(t) else \
                np.zeros(0, dtype=np.int64)
        if t.dtype.kind not in "iu":
            raise PlanError(
                "time_window's first argument must be a timestamp")
        t = t.astype(np.int64)
        window = self._tw_interval(f.args[1])
        slide = self._tw_interval(f.args[2]) if len(f.args) > 2 else window
        origin = 0
        if len(f.args) > 3:
            a = f.args[3]
            v = a.eval({}, np) if isinstance(a, (Literal, expr_mod.Cast)) \
                else None
            if isinstance(v, str):
                from .parser import parse_timestamp_string

                v = parse_timestamp_string(v)
            if not isinstance(v, (int, np.integer)):
                raise PlanError("time_window start_time must be a "
                                "timestamp constant")
            origin = int(v)
        if window <= 0 or slide <= 0:
            raise PlanError("time_window durations must be positive")

        # reference formula (transform_time_window.rs:248-393):
        #   st_mod = start_time MOD window          (window, not slide!)
        #   last_start = t - ((t - st_mod + slide) MOD slide)
        #   window_start_i = last_start - i·slide, i ∈ [0, ⌈window/slide⌉)
        # MOD is Rust's truncating remainder. EVERY i is emitted per row
        # (a row can land in a window not covering its timestamp — the
        # pinned 10ms/6ms rows show it); but when window % slide != 0
        # the reference filters out SOURCE ROWS with t outside their own
        # i=0 window (t ≥ last_start + window, possible when slide >
        # window) — all copies of such a row drop together.
        n_win = -(window // -slide)   # ceil
        if n_win > 100:
            raise PlanError(f"Too many overlapping windows: {n_win}")
        st_mod = expr_mod.trunc_mod(origin, window)
        last_start = t - np.fmod(t - st_mod + slide, slide)
        if window % slide != 0:
            rkeep = t < last_start + window
            if not rkeep.all():
                t = t[rkeep]
                last_start = last_start[rkeep]
                scope = scope.filter(rkeep)
        n0 = len(t)
        idx = np.repeat(np.arange(n0, dtype=np.int64), n_win)
        ks = np.tile(np.arange(n_win, dtype=np.int64), n0)
        starts_all = last_start[idx] - ks * slide
        win_col = np.empty(len(idx), dtype=object)
        for i, s in enumerate(starts_all):
            win_col[i] = {"kind": "window", "start": int(s),
                          "end": int(s) + window}
        new_scope = rel.Scope(
            scope.names, [c[idx] for c in scope.cols],
            {k2: v[idx] for k2, v in scope.env.items()})
        new_scope.quals = set(scope.quals)
        new_scope.env["__time_window__"] = win_col

        def rw(e):
            if not isinstance(e, Expr):
                return e
            return rel.rewrite_exprs(
                e, lambda x: isinstance(x, Func)
                and not isinstance(x, WindowFunc)
                and x.name.lower() == "time_window",
                lambda x: Column("__time_window__"))

        import dataclasses

        stmt = dataclasses.replace(
            stmt,
            items=[ast.SelectItem(rw(it.expr), it.alias)
                   for it in stmt.items],
            group_by=[rw(g) for g in stmt.group_by],
            order_by=[(rw(e), asc) for e, asc in stmt.order_by],
            having=rw(stmt.having) if stmt.having is not None else None)
        return new_scope, stmt

    @staticmethod
    def _tw_interval(arg) -> int:
        """Interval constant for time_window durations: INTERVAL literal
        or CAST(str AS INTERVAL)."""
        if isinstance(arg, Literal) and hasattr(arg.value, "ns"):
            return int(arg.value.ns)
        if isinstance(arg, expr_mod.Cast) \
                and arg.target.upper() == "INTERVAL" \
                and isinstance(arg.expr, Literal) \
                and isinstance(arg.expr.value, str):
            from .parser import parse_interval_string

            return int(parse_interval_string(arg.expr.value))
        raise PlanError(
            "time_window durations must be INTERVAL constants")

    def _host_group_aggregate(self, stmt: ast.SelectStmt, scope: rel.Scope):
        """GROUP BY + aggregates over a joined/derived relation — the
        host-side final-aggregate (single tables use the fused kernel)."""
        alias_map = {it.alias: it.expr for it in stmt.items
                     if it.alias and isinstance(it.expr, Expr)}
        key_exprs: list[Expr] = []
        for g in stmt.group_by:
            if isinstance(g, int):
                e = stmt.items[g - 1].expr
                if not isinstance(e, Expr):
                    raise PlanError("GROUP BY ordinal refers to *")
                key_exprs.append(e)
            elif isinstance(g, Expr):
                if isinstance(g, Column) and g.name not in scope.env \
                        and g.name in alias_map:
                    g = alias_map[g.name]   # GROUP BY a SELECT alias
                key_exprs.append(g)
            else:
                name = str(g)
                if name not in scope.env and name in alias_map:
                    key_exprs.append(alias_map[name])
                else:
                    key_exprs.append(Column(name))
        key_cols = [np.asarray(e.eval(scope.env, np)) for e in key_exprs]
        gid, first_idx = rel.group_indices(key_cols, scope.n)
        n_groups = len(first_idx)
        if n_groups == 0 and not key_exprs:
            # a GLOBAL aggregate over zero rows still yields one row
            # (count 0 / NULL sums — tpch q6 over an empty filter)
            n_groups = 1

        agg_cache: dict[str, np.ndarray] = {}
        # Gather per-group representatives only for names the
        # post-aggregate exprs (keys/items/HAVING/ORDER BY) can reach —
        # gathering every env column was O(columns × groups) object
        # traffic on wide scans. Aggregate args read scope.env directly.
        needed: set[str] = set()
        for e in key_exprs:
            needed |= e.columns()
        for it in stmt.items:
            if isinstance(it.expr, Expr):
                needed |= it.expr.columns()
        if stmt.having is not None:
            needed |= stmt.having.columns()
        for oe, _asc in stmt.order_by:
            if isinstance(oe, Expr):
                needed |= oe.columns()
            elif isinstance(oe, str):
                needed.add(oe)
        for name in list(needed):
            if "." in name:   # struct access resolves through the base col
                needed.add(name.rpartition(".")[0])
        genv = {}
        for k, v in scope.env.items():
            base = k[10:] if k.startswith("__valid__:") else k
            if base not in needed:
                continue
            gv = v[first_idx]
            if n_groups and len(gv) < n_groups:   # synthesized empty group
                gv = np.full(n_groups, None, dtype=object)
            genv[k] = gv

        def agg_col(f: Func) -> str:
            distinct = bool(f.args) and isinstance(f.args[0], Literal) \
                and f.args[0].value == "__distinct__"
            args = f.args[1:] if distinct else f.args
            star = (len(args) == 1 and isinstance(args[0], Literal)
                    and args[0].value == "*")
            key = f.to_sql() + ("D" if distinct else "")
            if key not in agg_cache:
                col = None if (star or not args) else \
                    np.asarray(args[0].eval(scope.env, np))
                col2 = param = None
                name = f.name.lower()
                if name in ("corr", "covar", "covar_pop", "covar_samp") \
                        and len(args) == 2:
                    col2 = np.asarray(args[1].eval(scope.env, np))
                elif name == "approx_percentile_cont" and len(args) == 2:
                    param = args[1].eval(scope.env, np)
                elif name == "approx_percentile_cont_with_weight" \
                        and len(args) == 3:
                    col2 = np.asarray(args[1].eval(scope.env, np))
                    param = args[2].eval(scope.env, np)
                elif name == "sample":
                    if len(args) != 2 or not isinstance(args[1], Literal):
                        raise PlanError(
                            "sample(column, k) takes a column and a "
                            "constant size")
                    param = args[1].eval(scope.env, np)
                    col = np.asarray(args[0].eval(scope.env, np))
                elif name in ("gauge_agg", "state_agg",
                              "compact_state_agg") and len(args) == 2:
                    # (time, value): the timestamp column rides in col2
                    col = np.asarray(args[1].eval(scope.env, np))
                    col2 = np.asarray(args[0].eval(scope.env, np))
                agg_cache[key] = rel.host_aggregate(
                    f.name, col, gid, n_groups, distinct,
                    col2=col2, param=param)
            return key

        def rewrite(e):
            return rel.rewrite_exprs(
                e, lambda x: isinstance(x, Func)
                and not isinstance(x, WindowFunc)
                and x.name.lower() in AGG_FUNCS,
                lambda x: Column(agg_col(x)))

        rewritten = [(it, rewrite(it.expr) if isinstance(it.expr, Expr)
                      else it.expr) for it in stmt.items]
        having = rewrite(stmt.having) if stmt.having is not None else None
        genv.update(agg_cache)

        if having is not None:
            hm = np.asarray(having.eval(genv, np))
            if not hm.shape:
                hm = np.full(n_groups, bool(hm))
            genv = {k: v[hm] for k, v in genv.items()}
            n_groups = int(hm.sum())

        out_names, out_cols = [], []
        for it, e in rewritten:
            if e == "*":
                raise PlanError("SELECT * is invalid with GROUP BY")
            v = e.eval(genv, np)
            if np.isscalar(v) or getattr(v, "shape", None) == ():
                v = np.full(n_groups, v)
            out_names.append(_out_name(it))
            out_cols.append(np.asarray(v))
        rs = ResultSet(out_names, out_cols)
        env_all = dict(genv)
        for nm, c in zip(out_names, out_cols):
            env_all.setdefault(nm, c)
        # ORDER BY count(*) etc. must see the same aggregate rewrites
        order_by = [(rewrite(e) if isinstance(e, Expr) else e, asc)
                    for e, asc in stmt.order_by]
        if not key_exprs:
            # a GLOBAL aggregate exposes only its aggregate outputs:
            # ORDER BY a raw column is a schema error (sqlancer pins
            # "No field named m0.t0" for ORDER BY under SUM(...))
            allowed = set(out_names) | set(agg_cache)
            for oe, _asc in order_by:
                cols_ref = oe.columns() if isinstance(oe, Expr) else \
                    ({oe} if isinstance(oe, str) else set())
                bad = [c for c in cols_ref if c not in allowed]
                if bad:
                    raise PlanError(
                        f"No field named {bad[0]} in the aggregate "
                        f"output")
        return rs, env_all, order_by

    def _distinct(self, rs: ResultSet) -> ResultSet:
        seen = set()
        keep = []
        for i, key in enumerate(_row_keys(rs.columns)):
            if key not in seen:
                seen.add(key)
                keep.append(i)
        idx = np.asarray(keep, dtype=np.int64)
        return ResultSet(rs.names, [c[idx] for c in rs.columns])

    def _union(self, stmt: ast.UnionStmt, session: Session) -> ResultSet:
        """Set-operation chain. INTERSECT-precedence nesting is resolved at
        parse time (a nested chain arrives as a UnionStmt branch); operators
        at one level apply left to right. NULLs are not distinct from each
        other in set-op row matching (SQL; reference via DataFusion)."""
        from .analyzer import analyze

        stmt = analyze(stmt)   # union-level ORDER BY desugaring

        def run(s):
            return self._union(s, session) if isinstance(s, ast.UnionStmt) \
                else self._select(s, session)

        results = [run(s) for s in stmt.selects]
        width = len(results[0].names)
        for r in results[1:]:
            if len(r.names) != width:
                raise QueryError(
                    "set-operation branches must have equal arity")
        names = results[0].names
        acc = [results[0].columns[i] for i in range(width)]
        ops = stmt.ops or ["union"] * len(stmt.alls)
        for r, all_, op in zip(results[1:], stmt.alls, ops):
            if op == "union":
                acc = [_concat_cols(acc[i], r.columns[i])
                       for i in range(width)]
                if not all_:
                    acc = list(self._distinct(ResultSet(names, acc)).columns)
            else:
                acc = _set_op_cols(acc, list(r.columns), op, all_)
        rs = ResultSet(names, acc)
        env = {n: c for n, c in zip(names, acc)}
        return _order_limit(rs, stmt.order_by, stmt.limit, stmt.offset, env)

    def _select_over_env(self, stmt: ast.SelectStmt, names: list[str], cols):
        """Generic SELECT over an in-memory table (system schemas)."""
        env = {n: c for n, c in zip(names, cols)}
        n = len(cols[0]) if cols else 0
        mask = np.ones(n, dtype=bool)
        if stmt.where is not None:
            m = stmt.where.eval(env, np)
            mask = np.full(n, bool(m)) if np.isscalar(m) or m.shape == () else m
        env = {k: v[mask] for k, v in env.items()}
        n = int(mask.sum())
        out_names, out_cols = [], []
        for it in stmt.items:
            if it.expr == "*":
                out_names.extend(names)
                out_cols.extend(env[x] for x in names)
                continue
            v = it.expr.eval(env, np)
            if np.isscalar(v) or getattr(v, "shape", None) == ():
                v = np.full(n, v)
            out_names.append(it.alias or (it.expr.name if isinstance(it.expr, Column)
                                          else it.expr.to_sql()))
            out_cols.append(np.asarray(v))
        rs = ResultSet(out_names, out_cols)
        env_all = dict(env)
        for nm, c in zip(out_names, out_cols):
            env_all[nm] = c
        return _order_limit(rs, stmt.order_by, stmt.limit, stmt.offset, env_all)

    # ---------------------------------------------------------- aggregates
    def _exec_aggregate(self, plan: AggregatePlan, tenant: str, db: str):
        if self.serving is not None:
            # aggregates never fuse (segment kernels own their whole
            # batch); book the decline so batch telemetry stays honest
            self.serving.batcher.decline("aggregate")
        phys_aggs, finalize = _decompose_aggs(plan.aggs)
        second_cols = set()
        for a in phys_aggs:
            # collect2 / count_multi carry companion columns in param
            if a.func == "collect2" and isinstance(a.param, str):
                second_cols.add(a.param)
            elif a.func == "count_multi":
                second_cols.update(a.param or ())
        needed_fields = sorted({a.column for a in phys_aggs if a.column}
                               | second_cols
                               | set(plan.group_fields)
                               | (plan.filter.columns()
                                  & set(plan.schema.field_names())
                                  if plan.filter else set()))
        rw = self._matview_rewrite(plan, phys_aggs, tenant, db)
        if rw is not None:
            # sealed buckets come pre-aggregated from the view; only the
            # unsealed tail / unaligned range edges hit raw storage
            batches = [] if rw.scan_ranges.is_empty else \
                self.coord.scan_table(
                    tenant, db, plan.table, time_ranges=rw.scan_ranges,
                    tag_domains=plan.tag_domains,
                    field_names=needed_fields, page_filter=plan.filter)
            nbytes = _batches_bytes(batches)
            memgov.charge_query(nbytes, "scan")
            with self.memory_pool.reservation(nbytes,
                                              f"scan of {plan.table}"):
                return self._exec_aggregate_seeded(plan, batches,
                                                   phys_aggs, finalize,
                                                   rw.acc)
        # compressed-domain lane: fully-answerable pages come back as
        # pre-aggregated partials instead of rows (storage decides
        # per-page; a None spec books why the query can't engage)
        from ..storage import compressed_domain

        cspec = compressed_domain.build_spec(plan, phys_aggs)
        batches = self.coord.scan_table(
            tenant, db, plan.table, time_ranges=plan.time_ranges,
            tag_domains=plan.tag_domains, field_names=needed_fields,
            page_filter=plan.filter, compressed_spec=cspec)
        nbytes = _batches_bytes(batches)
        memgov.charge_query(nbytes, "scan")
        with self.memory_pool.reservation(nbytes,
                                          f"scan of {plan.table}"):
            return self._exec_aggregate_batches(plan, batches, phys_aggs,
                                                finalize)

    def _group_spiller(self, plan, phys_aggs):
        """Per-aggregate group-state guard: a GroupSpiller when the
        memory plane is on, else the branch-free no-op (legacy path is
        byte-identical — the hooks do nothing)."""
        if not memgov.enabled() or memgov.GROUP_BYTES <= 0:
            return _NoSpill()
        return GroupSpiller(plan, phys_aggs, memgov.GROUP_BYTES)

    def _matview_rewrite(self, plan, phys_aggs, tenant: str, db: str):
        """Try the materialized-rollup subsumption rewrite; None keeps
        the raw-scan path. Zero-cost while the catalog has no views."""
        if not self.matview_rewrite_enabled or plan.gapfill:
            return None
        try:
            if not getattr(self.meta, "matviews", None):
                return None
        except Exception:
            return None
        from .matview import MERGEABLE_FUNCS

        if any(a.func not in MERGEABLE_FUNCS for a in phys_aggs):
            return None
        try:
            return self.matview_engine().rewrite(plan, phys_aggs,
                                                 tenant, db)
        except Exception:
            # the rewrite is an optimization: any failure inside it must
            # degrade to the (always-correct) raw scan, visibly counted
            stages.count_error("matview.rewrite")
            return None

    def _exec_aggregate_seeded(self, plan, batches, phys_aggs, finalize,
                               acc: dict):
        """Finish an aggregate whose accumulator was seeded from sealed
        view buckets: fold the residual raw batches through the same
        partial-merge path, then finalize normally (bit-identical to a
        full scan)."""
        from ..ops.tpu_exec import finish_scan_aggregate, launch_scan_aggregate

        ncpu = os.cpu_count() or 1
        q = TpuQuery(filter=plan.filter, group_tags=plan.group_tags,
                     group_fields=plan.group_fields,
                     time_bucket=plan.bucket,
                     kernel_threads=max(1, ncpu // max(1, min(8,
                                                              len(batches) or 1))),
                     aggs=phys_aggs)
        jobs = [launch_scan_aggregate(batch, q) for batch in batches]
        spiller = self._group_spiller(plan, phys_aggs)
        try:
            with stages.stage("merge_ms"):
                for job in jobs:
                    self._poll_cancel()
                    r = finish_scan_aggregate(job)
                    _merge_partial(acc, r, plan, phys_aggs)
                    spiller.observe(acc)
            acc = spiller.finish(acc)
        finally:
            spiller.close()
        if not acc and not plan.group_tags \
                and not plan.group_fields and plan.bucket is None:
            acc[()] = {}  # SQL: a global aggregate always yields one row
        return self._finalize_aggregate(plan, acc, finalize)

    def _exec_aggregate_batches(self, plan, batches, phys_aggs, finalize):
        host_funcs = ("count_distinct", "collect", "collect_ts",
                      "collect2", "count_multi")
        import os

        ncpu = os.cpu_count() or 1
        q = TpuQuery(filter=plan.filter, group_tags=plan.group_tags,
                     group_fields=plan.group_fields,
                     time_bucket=plan.bucket,
                     # batches run kernels concurrently on a pool below:
                     # give each native call its fair share of cores
                     kernel_threads=max(1, ncpu // max(1, min(8,
                                                              len(batches)))),
                     aggs=[a for a in phys_aggs if a.func not in host_funcs])
        distinct_specs = [a for a in phys_aggs if a.func in host_funcs]

        # launch every vnode's device kernel before fetching any result:
        # fetches carry fixed device→host latency, launches are async
        from ..ops.tpu_exec import finish_scan_aggregate, launch_scan_aggregate

        from ..utils import stages

        if any(getattr(b, "compressed_partials", None) for b in batches):
            # compressed-domain partials join the generic accumulator
            # path: kernels run only over batches that still have rows,
            # page partials fold in with _merge_partial-identical
            # semantics (order-independent, so bit-identical)
            kernel_batches = [b for b in batches if b.n_rows]
            with stages.stage("kernel_ms"):
                self._poll_cancel()
                results = [finish_scan_aggregate(
                    launch_scan_aggregate(b, q)) for b in kernel_batches]
            acc: dict[tuple, dict] = {}
            spiller = self._group_spiller(plan, phys_aggs)
            try:
                with stages.stage("merge_ms"):
                    for r in results:
                        _merge_partial(acc, r, plan, phys_aggs)
                        spiller.observe(acc)
                    for b in batches:
                        _merge_compressed_partials(acc, b, plan, phys_aggs)
                        spiller.observe(acc)
                acc = spiller.finish(acc)
            finally:
                spiller.close()
            if not acc and not plan.group_tags \
                    and not plan.group_fields and plan.bucket is None:
                acc[()] = {}  # SQL: a global aggregate always yields one row
            return self._finalize_aggregate(plan, acc, finalize)
        if len(batches) == 1 and not distinct_specs:
            # single-vnode fast path: finalize vectorized straight from
            # the kernel's arrays, no per-group python merge
            with stages.stage("kernel_ms"):
                r = finish_scan_aggregate(
                    launch_scan_aggregate(batches[0], q))
            with stages.stage("finalize_ms"):
                return self._finalize_single(plan, r, phys_aggs, finalize)
        if not distinct_specs:
            if len(batches) > 1:
                # mesh-native lane: all batches upload sharded over the
                # execution mesh and partials merge through XLA
                # collectives in ONE program — no per-batch host partial,
                # no host merge. Bit-identical to the fan-out + vec merge
                # below; any decline (off-mesh replica, unsupported
                # shape, device loss mid-collective) books its reason in
                # cnosdb_mesh_total and falls through unchanged.
                from ..ops import mesh_exec

                self._poll_cancel()
                mres = mesh_exec.try_mesh_aggregate(batches, q)
                if mres is not None:
                    with stages.stage("finalize_ms"):
                        return self._finalize_single(plan, mres, phys_aggs,
                                                     finalize)
            with stages.stage("kernel_ms"):
                self._poll_cancel()
                if len(batches) > 1:
                    # per-vnode kernel prep (bucket/segment derivation +
                    # reductions) is independent: run on a pool, like the
                    # scan fan-out — each task inside the submitter's
                    # contextvars.Context, so the batches' stages, counts
                    # and spans reach this query's profile and trace
                    import contextvars
                    from concurrent.futures import ThreadPoolExecutor

                    def one(b):
                        # prep, put and dispatch, then the blocking pull:
                        # summed over the pool's threads, both inside
                        # this kernel_ms
                        with stages.stage("fanout.launch_ms"):
                            job = launch_scan_aggregate(b, q)
                        with stages.stage("fanout.fetch_ms"):
                            return finish_scan_aggregate(job)

                    stages.count("fanout.vnodes", len(batches))
                    with ThreadPoolExecutor(
                            max_workers=min(8, len(batches))) as tp:
                        results = [f.result() for f in [
                            tp.submit(contextvars.copy_context().run, one, b)
                            for b in batches]]
                else:
                    results = [finish_scan_aggregate(
                        launch_scan_aggregate(b, q)) for b in batches]
            with stages.stage("merge_ms"):
                merged = _merge_results_vec(results, plan, phys_aggs)
            if merged is not None:
                stages.count("merge.groups", merged.n_rows)
                with stages.stage("finalize_ms"):
                    return self._finalize_single(plan, merged, phys_aggs,
                                                 finalize)
            acc: dict[tuple, dict] = {}
            spiller = self._group_spiller(plan, phys_aggs)
            try:
                for r in results:
                    _merge_partial(acc, r, plan, phys_aggs)
                    spiller.observe(acc)
                acc = spiller.finish(acc)
            finally:
                spiller.close()
            if not acc and not plan.group_tags \
                    and not plan.group_fields and plan.bucket is None:
                acc[()] = {}
            return self._finalize_aggregate(plan, acc, finalize)
        # host-aggregate (distinct/collect) path: launch all kernels
        # first, then merge per batch
        jobs = [launch_scan_aggregate(batch, q) for batch in batches]
        acc: dict[tuple, dict] = {}
        spiller = self._group_spiller(plan, phys_aggs)
        try:
            for batch, job in zip(batches, jobs):
                self._poll_cancel()  # KILL QUERY lands between vnode fetches
                r = finish_scan_aggregate(job)
                _merge_partial(acc, r, plan, phys_aggs)
                for spec in distinct_specs:
                    _merge_distinct(acc, batch, plan, spec)
                spiller.observe(acc)
            acc = spiller.finish(acc)
        finally:
            spiller.close()
        if not acc and not plan.group_tags \
                and not plan.group_fields and plan.bucket is None:
            acc[()] = {}  # SQL: a global aggregate always yields one row

        return self._finalize_aggregate(plan, acc, finalize)

    def _finalize_single(self, plan: AggregatePlan, r, phys_aggs, finalize):
        n = r.n_rows
        stages.count("group_count", n)
        if n == 0 and not plan.group_tags and not plan.group_fields \
                and plan.bucket is None:
            # SQL: a global aggregate always yields one row
            return self._finalize_aggregate(plan, {(): {}}, finalize)
        env: dict[str, np.ndarray] = {}
        for t in plan.group_tags + plan.group_fields:
            env[t] = r.columns[t]
        if plan.bucket is not None:
            env["time"] = r.columns["time"]
        # vectorized finalizers over whole partial columns
        parts_env = {}
        for a in phys_aggs:
            if a.alias in r.columns:
                col = r.columns[a.alias]
                valid = r.valid.get(a.alias)
                parts_env[a.alias] = (col, valid)
        for alias, spec in finalize.items():
            vals, valids = _vector_finalize(spec, parts_env, n)
            env[alias] = vals
            env[f"__valid__:{alias}"] = valids

        if plan.having is not None and n:
            mask = np.asarray(plan.having.eval(env, np), dtype=bool)
            env = {k: v[mask] if isinstance(v, np.ndarray) and len(v) == n else v
                   for k, v in env.items()}
            n = int(mask.sum())

        rs = ResultSet(*_render_output(plan, env, n))
        if plan.gapfill and rs.n_rows:
            rs = _apply_gapfill(plan, rs)
        env_out = dict(env)
        for nm, c in zip(rs.names, rs.columns):
            env_out[nm] = c
        return _order_limit(rs, plan.order_by, plan.limit, plan.offset, env_out)

    def _finalize_aggregate(self, plan: AggregatePlan, acc: dict, finalize):
        keys = list(acc.keys())
        n = len(keys)
        stages.count("group_count", n)
        env: dict[str, np.ndarray] = {}
        for i, t in enumerate(plan.group_tags + plan.group_fields):
            env[t] = np.array([k[i] for k in keys], dtype=object)
        if plan.bucket is not None:
            env["time"] = np.array([k[-1] for k in keys], dtype=np.int64) \
                if n else np.empty(0, dtype=np.int64)
        for alias, spec in finalize.items():
            vals, valids = [], []
            for k in keys:
                v = _apply_finalizer(spec, acc[k])
                vals.append(v)
                valids.append(v is not None)
            if any(isinstance(v, (dict, list, str)) for v in vals):
                # composite results (gauge/state data, samples): object col
                arr = np.empty(len(vals), dtype=object)
                arr[:] = vals
            else:
                arr = np.array([v if v is not None else np.nan for v in vals])
            env[alias] = arr
            env[f"__valid__:{alias}"] = np.array(valids, dtype=bool)

        if plan.having is not None and n:
            mask = np.asarray(plan.having.eval(env, np), dtype=bool)
            env = {k: v[mask] if isinstance(v, np.ndarray) and len(v) == n else v
                   for k, v in env.items()}
            n = int(mask.sum())

        rs = ResultSet(*_render_output(plan, env, n))
        if plan.gapfill and rs.n_rows:
            rs = _apply_gapfill(plan, rs)
        # ORDER BY may reference output aliases (e.g. the bucket alias)
        env_out = dict(env)
        for nm, c in zip(rs.names, rs.columns):
            env_out[nm] = c
        rs = _order_limit(rs, plan.order_by, plan.limit, plan.offset, env_out)
        return rs

    # ---------------------------------------------------------- raw scans
    def _exec_raw(self, plan: RawScanPlan, tenant: str, db: str):
        needed = set()
        for _n, e in plan.output:
            needed |= e.columns()
        if plan.filter is not None:
            needed |= plan.filter.columns()
        field_names = sorted(needed & set(plan.schema.field_names()))
        if not field_names:
            field_names = plan.schema.field_names()
        sv = self.serving
        if sv is not None:
            # fused micro-batching rendezvous: compatible concurrent
            # point queries share one scan; None = run the solo path
            rs = sv.batcher.submit(self, plan, tenant, db, field_names)
            if rs is not None:
                return rs
        batches = self.coord.scan_table(
            tenant, db, plan.table, time_ranges=plan.time_ranges,
            tag_domains=plan.tag_domains, field_names=field_names,
            fingerprint=sv.current_fp() if sv is not None else None)
        nbytes = _batches_bytes(batches)
        memgov.charge_query(nbytes, "scan")
        with self.memory_pool.reservation(nbytes,
                                          f"scan of {plan.table}"):
            return self._exec_raw_batches(plan, batches)

    def _raw_batch_env(self, schema, b) -> dict:
        """Filter/projection eval environment for one ScanBatch: time +
        field columns with their `__valid__:` masks + per-row tag values
        gathered through the series ordinals."""
        env = {"time": b.ts}
        for fname, (vt, vals, valid) in b.fields.items():
            env[fname] = vals
            env[f"__valid__:{fname}"] = valid
        for t in schema.tag_names():
            per_series = np.array(
                [(k.tag_value(t) if k is not None else None)
                 for k in b.series_keys], dtype=object)
            env[t] = per_series[b.sid_ordinal] if b.n_series else \
                np.empty(0, dtype=object)
        return env

    def _exec_raw_batches(self, plan: RawScanPlan, batches, prepared=None):
        """`prepared` (serving-plane fused batches) short-circuits the
        scan→env→mask stage with precomputed ``(env, mask, n_rows)``
        triples — the member's own filter mask over a SHARED env; the
        projection half below is identical either way."""
        frames = []
        if prepared is not None:
            for env, mask, total in prepared:
                if not bool(mask.all()):
                    env = {k: (v[mask]
                               if isinstance(v, (np.ndarray, DictArray))
                               and len(v) == total else v)
                           for k, v in env.items()}
                frames.append((env, int(mask.sum())))
            batches = []
        for b in batches:
            env = self._raw_batch_env(plan.schema, b)
            mask = np.ones(b.n_rows, dtype=bool)
            if plan.filter is not None:
                missing = [c for c in plan.filter.columns() if c not in env]
                for c in missing:
                    env[c] = _schema_padding(plan.schema, c, b.n_rows)
                    env[f"__valid__:{c}"] = np.zeros(b.n_rows, dtype=bool)
                mask = np.asarray(plan.filter.eval(env, np), dtype=bool)
                if mask.shape == ():
                    mask = np.full(b.n_rows, bool(mask))
                # 3VL: comparison leaves are masked in sql.expr; this
                # post-hoc pass covers bare/NOT-wrapped predicates and is
                # only sound for conjunctive (OR-free) filters —
                # per-column, skipping columns under an explicit IS NULL
                from ..ops.tpu_exec import is_conjunctive, is_null_columns

                if is_conjunctive(plan.filter):
                    skip = is_null_columns(plan.filter)
                    for c in plan.filter.columns() - skip:
                        vk = f"__valid__:{c}"
                        if c in b.fields:
                            mask &= env[vk]
            # filter BEFORE projection (DataFusion order): expressions must
            # only see surviving rows — CAST over a filtered-out Inf row
            # must not abort, and selective scans shrink the eval cost
            if not bool(mask.all()):
                env = {k: (v[mask] if isinstance(v, (np.ndarray, DictArray))
                           and len(v) == b.n_rows else v)
                       for k, v in env.items()}
            frames.append((env, int(mask.sum())))

        # ORDER BY keys may reference non-projected columns: evaluate them
        # per frame as hidden columns
        ord_items = [(f"__ord{i}", oe, asc)
                     for i, (oe, asc) in enumerate(plan.order_by)]
        names = [n for n, _ in plan.output]
        out_cols: list[list[np.ndarray]] = [[] for _ in names]
        valid_cols: list[list[np.ndarray]] = [[] for _ in names]
        ord_cols: list[list[np.ndarray]] = [[] for _ in ord_items]
        for env, n_rows in frames:
            for j, (_hn, oe, _asc) in enumerate(ord_items):
                missing = [c for c in oe.columns() if c not in env]
                for c in missing:
                    env[c] = _schema_padding(plan.schema, c, n_rows)
                    env[f"__valid__:{c}"] = np.zeros(n_rows, dtype=bool)
                ov = oe.eval(env, np)
                if isinstance(ov, DictArray):
                    ov = ov.materialize()
                if ov is None:
                    ov = np.full(n_rows, None, dtype=object)
                elif np.isscalar(ov) or getattr(ov, "shape", None) == ():
                    ov = np.full(n_rows, ov)
                ov = np.asarray(ov)
                # NULL slots in typed columns carry garbage values — sort
                # keys must see the NULLs (rendered as None/nan) or NULLs
                # order by their slot garbage
                ovv = np.ones(n_rows, dtype=bool)
                for c in expr_mod.propagating_columns(oe):
                    vk = f"__valid__:{c}"
                    if vk in env:
                        ovv &= env[vk]
                if not ovv.all():
                    if np.issubdtype(ov.dtype, np.floating):
                        ov = ov.copy()
                        ov[~ovv] = np.nan
                    else:
                        ov = ov.astype(object)
                        ov[~ovv] = None
                ord_cols[j].append(ov)
            for i, (name, expr) in enumerate(plan.output):
                missing = [c for c in expr.columns() if c not in env]
                for c in missing:
                    env[c] = _schema_padding(plan.schema, c, n_rows)
                    env[f"__valid__:{c}"] = np.zeros(n_rows, dtype=bool)
                v = expr.eval(env, np)
                if isinstance(v, DictArray):
                    v = v.materialize()
                if v is None:   # e.g. TRY_CAST failure: an all-NULL column
                    v = np.full(n_rows, None, dtype=object)
                elif np.isscalar(v) or getattr(v, "shape", None) == ():
                    v = np.full(n_rows, v)
                out_cols[i].append(np.asarray(v))
                vv = np.ones(n_rows, dtype=bool)
                for c in expr_mod.propagating_columns(expr):
                    vk = f"__valid__:{c}"
                    if vk in env:
                        vv &= env[vk]
                valid_cols[i].append(vv)

        cols = [np.concatenate(c) if c else np.empty(0) for c in out_cols]
        valids = [np.concatenate(c) if c else np.empty(0, dtype=bool)
                  for c in valid_cols]
        # render NULLs: object columns get None, floats get nan
        rendered = []
        for col, valid in zip(cols, valids):
            if valid.all():
                rendered.append(col)
            elif col.dtype == object:
                c2 = col.copy()
                c2[~valid] = None
                rendered.append(c2)
            else:
                # NULL slots become None; valid NaN values STAY NaN —
                # the reference distinguishes them (acos(2) renders NaN,
                # a NULL renders empty)
                c2 = col.astype(object)
                c2[~valid] = None
                rendered.append(c2)
        hid = [np.concatenate(c) if c else np.empty(0) for c in ord_cols]
        rs = ResultSet(names, rendered)
        if plan.distinct and rs.n_rows:
            seen = {}
            for i, row in enumerate(zip(*[c.tolist() for c in rendered])):
                seen.setdefault(row, i)
            idx = np.array(sorted(seen.values()), dtype=np.int64)
            rs = ResultSet(names, [c[idx] for c in rendered])
            hid = [c[idx] for c in hid]
        env_all = {n: c for n, c in zip(names, rs.columns)}
        for (hn, _oe, _asc), c in zip(ord_items, hid):
            env_all[hn] = c
        order_by = [(Column(hn), asc) for (hn, _oe, asc) in ord_items]
        rs = _order_limit(rs, order_by, plan.limit, plan.offset, env_all)
        return rs


# ---------------------------------------------------------------------------
# partial-aggregate decomposition + merging
# ---------------------------------------------------------------------------
def _decompose_aggs(aggs: list[AggSpec]):
    """mean → sum+count partials; → (physical specs, finalizers)."""
    phys: list[AggSpec] = []
    finalize: dict = {}
    seen: dict[tuple, str] = {}

    def want(func, col, param=None):
        key = (func, col, repr(param))
        if key not in seen:
            alias = f"__p{len(phys)}"
            phys.append(AggSpec(func, col, alias, param))
            seen[key] = alias
        return seen[key]

    for a in aggs:
        if a.func in ("mean", "avg"):
            s = want("sum", a.column)
            c = want("count", a.column)
            finalize[a.alias] = ("mean", s, c)
        elif a.func == "count":
            c = want("count", a.column)
            finalize[a.alias] = ("int", c)
        elif a.func == "count_null_const":
            # count(NULL): zero per group, but groups still materialize
            c = want("count", a.column)
            finalize[a.alias] = ("zero", c)
        elif a.func == "count_multi":
            # count(a, b, ...): rows where every column is non-NULL
            finalize[a.alias] = ("int", want("count_multi", a.column,
                                             a.param))
        elif a.func.startswith("const_agg:"):
            # aggregate over a constant literal (avg(3) → 3.0)
            c = want("count", None)
            finalize[a.alias] = ("const_agg", a.func.split(":", 1)[1],
                                 c, a.param)
        elif a.func == "sum":
            finalize[a.alias] = ("pass", want("sum", a.column))
        elif a.func in ("min", "max", "first", "last"):
            finalize[a.alias] = ("pass", want(a.func, a.column))
        elif a.func in ("count_distinct", "approx_distinct"):
            finalize[a.alias] = ("distinct", want("count_distinct", a.column))
        elif a.func == "array_agg" and isinstance(a.param, tuple) \
                and a.param and a.param[0] == "const_array":
            finalize[a.alias] = ("array_const", want("collect_ts", a.column),
                                 a.param[1])
        elif a.func == "array_agg" and isinstance(a.param, tuple) \
                and a.param and a.param[0] == "order_time":
            finalize[a.alias] = ("array_ts", want("collect_ts", a.column),
                                 a.param[1], a.column == "time")
        elif a.func in ("median", "approx_median", "stddev",
                        "stddev_samp", "stddev_pop", "var", "var_samp",
                        "var_pop", "mode", "array_agg",
                        "bit_and", "bit_or", "bit_xor"):
            kind = {"approx_median": "median", "stddev_samp": "stddev",
                    "var": "var_samp"}.get(a.func, a.func)
            finalize[a.alias] = (kind, want("collect", a.column))
        elif a.func == "approx_percentile_cont":
            finalize[a.alias] = ("percentile", want("collect", a.column),
                                 a.param)
        elif a.func == "approx_percentile_cont_with_weight":
            wcol, q = a.param
            if isinstance(wcol, tuple) and wcol[0] == "__const_w__":
                finalize[a.alias] = ("percentile_w_const",
                                     want("collect", a.column),
                                     wcol[1], q)
            else:
                finalize[a.alias] = ("percentile_w",
                                     want("collect2", a.column, wcol), q)
        elif a.func in ("corr", "covar", "covar_pop", "covar_samp"):
            kind = "covar_samp" if a.func == "covar" else a.func
            finalize[a.alias] = (kind,
                                 want("collect2", a.column, a.param))
        elif a.func in _SERIES_AGGS:
            # whole-series aggregates: need the group's full time-ordered
            # (ts, value) sequence (reference runs these as DataFusion
            # accumulators, not decomposable partials)
            finalize[a.alias] = ("series", a.func,
                                 want("collect_ts", a.column), a.param)
        else:
            raise PlanError(f"aggregate {a.func!r} not supported yet")
    return phys, finalize


# aggregates finalized from the full (ts, value) sequence via sql.tsfuncs
_SERIES_AGGS = {"increase", "sample", "gauge_agg", "state_agg",
                "compact_state_agg", "completeness", "consistency",
                "timeliness", "validity"}

# row-set-valued repair transforms (reference ts_gen_func)
_REPAIR_FUNCS = {"timestamp_repair", "value_fill", "value_repair"}


def _load_external(ext: dict) -> tuple[list[str], list[np.ndarray]]:
    """Materialize an external table (reference create_external_table.rs
    reads through object_store + DataFusion listing providers; here a
    local path reads directly and s3://, gcs://, azblob:// locations go
    through utils.objstore with the table's stored connection options)."""
    from ..utils import objstore

    path = ext["path"]
    # relative locations resolve against CNOSDB_EXTERNAL_DATA_ROOT when
    # absent from the cwd (test corpora reference fixture trees by
    # repo-relative path)
    root = os.environ.get("CNOSDB_EXTERNAL_DATA_ROOT")
    if root and "://" not in path and not os.path.isabs(path) \
            and not os.path.exists(path) \
            and os.path.exists(os.path.join(root, path)):
        path = os.path.join(root, path)
    src = objstore.open_source(path, ext.get("options"))
    if ext["fmt"] == "parquet":
        import pyarrow.parquet as pq

        table = pq.read_table(src)   # accepts files and directories
    elif ext["fmt"] in ("ndjson", "json"):
        import pyarrow.json as pj

        table = pj.read_json(src)
    else:
        import pyarrow as pa
        import pyarrow.csv as pc

        ropts = pc.ReadOptions(autogenerate_column_names=not ext.get(
            "header", True))
        if isinstance(src, str) and os.path.isdir(src):
            parts = sorted(os.path.join(src, f) for f in os.listdir(src)
                           if not f.startswith("."))
            table = pa.concat_tables(
                [pc.read_csv(p, read_options=ropts) for p in parts])
        else:
            table = pc.read_csv(src, read_options=ropts)
    names, cols = [], []
    for name in table.column_names:
        col = table.column(name)
        arr = col.to_numpy(zero_copy_only=False)
        if col.null_count and arr.dtype.kind == "f":
            # arrow NULLs land as NaN in to_numpy; NULL ≠ NaN — carry
            # them as object None so they render as empty cells
            nulls = np.asarray(col.is_null())
            arr = arr.astype(object)
            arr[nulls] = None
            names.append(name)
            cols.append(arr)
            continue
        if arr.dtype.kind == "M":
            # arrow timestamp columns (CSV type inference) → this
            # engine's i64 ns representation
            arr = arr.astype("datetime64[ns]").astype(np.int64)
        elif arr.dtype == object and len(arr) \
                and type(arr[0]).__name__ == "Timestamp":
            arr = np.array([int(v.value) for v in arr], dtype=np.int64)
        elif arr.dtype == object or arr.dtype.kind in ("U", "S"):
            arr = np.array([None if v is None else str(v)
                            for v in col.to_pylist()], dtype=object)
        names.append(name)
        cols.append(arr)
    declared = ext.get("columns") or []
    if declared:
        # declared column list (tpch.slt): positional rename + coercion
        names = [c[0] for c in declared[:len(cols)]] + names[len(declared):]
        for i, (_cn, sql_type) in enumerate(declared[:len(cols)]):
            t = sql_type.upper()
            a = cols[i]
            try:
                if t in ("NUMERIC", "DOUBLE", "FLOAT", "DECIMAL", "REAL"):
                    if a.dtype != object:
                        cols[i] = a.astype(np.float64)
                elif t in ("INTEGER", "INT", "BIGINT"):
                    if a.dtype != object and a.dtype.kind != "f":
                        cols[i] = a.astype(np.int64)
                elif t in ("VARCHAR", "STRING", "TEXT", "CHAR"):
                    if a.dtype != object:
                        cols[i] = np.array([str(v) for v in a],
                                           dtype=object)
            except (TypeError, ValueError):
                pass   # keep the inferred dtype on impossible coercions
    return names, cols


def _strip_time_conjuncts(e):
    """Drop top-level AND conjuncts that reference only `time`, returning
    the tag-only remainder (None when nothing remains). SHOW SERIES
    evaluates time separately against each series' data extent."""
    from .expr import BinOp

    if "time" not in e.columns():
        return e
    if isinstance(e, BinOp) and e.op == "and":
        left = _strip_time_conjuncts(e.left)
        right = _strip_time_conjuncts(e.right)
        if left is None:
            return right
        if right is None:
            return left
        return BinOp("and", left, right)
    if e.columns() <= {"time"}:
        return None
    raise PlanError(
        "SHOW SERIES: time predicates must be top-level AND conjuncts")


def _schema_padding(schema, col: str, n: int) -> np.ndarray:
    """All-invalid padding for a field absent from a vnode's batch, typed
    from the schema so cross-batch concatenation keeps the declared dtype
    (a BIGINT column must not decay to float64 because one vnode never
    saw it; reference returns typed arrow arrays with null validity)."""
    try:
        dt = schema.column(col).column_type.value_type.numpy_dtype()
    except Exception:
        dt = np.float64
    if dt is object:
        return np.full(n, None, dtype=object)
    return np.zeros(n, dtype=dt)


def _batches_bytes(batches) -> int:
    """Rough working-set estimate of scan batches for memory-pool gating."""
    total = 0
    for b in batches:
        total += b.ts.nbytes + b.sid_ordinal.nbytes
        for _vt, vals, valid in b.fields.values():
            total += getattr(vals, "nbytes", 0) + getattr(valid, "nbytes", 0)
    return total


# ------------------------------------------------- group-state spilling
def _acc_group_bytes(acc: dict) -> int:
    """Rough live bytes of a group accumulator (keys + partial values;
    sets/collect chunks dominate wide states)."""
    total = 0
    for key, parts in acc.items():
        total += 64 + 16 * len(key)
        for v in parts.values():
            if isinstance(v, set):
                total += 64 + 64 * len(v)
            elif isinstance(v, list):
                total += 64
                for ch in v:
                    if isinstance(ch, tuple):
                        total += sum(int(getattr(c, "nbytes", 16) or 16)
                                     for c in ch)
                    else:
                        total += int(getattr(ch, "nbytes", 16) or 16)
            else:
                total += 16 + int(getattr(v, "nbytes", 0) or 0)
    return total


def _merge_spill_entry(dst: dict, src: dict, phys_aggs):
    """Fold a LATER spill fragment's parts into an EARLIER one for the
    same group key. Semantics mirror _merge_partial per func exactly
    (count add, sum left-fold, min/max combine, first/last by strict
    timestamp so the earlier epoch wins ties, distinct-set union,
    collect-chunk extend in arrival order) — spilled and in-memory
    execution finalize bit-identically."""
    for a in phys_aggs:
        al = a.alias
        if a.func in ("first", "last"):
            if al not in src:
                continue
            v = src[al]
            ts = src.get(al + "__ts")
            cur = dst.get(al)
            cur_ts = dst.get(al + "__ts")
            better = (cur is None or cur_ts is None
                      or (a.func == "first" and ts < cur_ts)
                      or (a.func == "last" and ts > cur_ts))
            if better:
                dst[al] = v
                dst[al + "__ts"] = ts
            continue
        if al not in src:
            continue
        v = src[al]
        cur = dst.get(al)
        if a.func in ("count", "count_multi"):
            dst[al] = (cur or 0) + int(v)
        elif a.func == "sum":
            dst[al] = v if cur is None else cur + v
        elif a.func == "min":
            dst[al] = v if cur is None else min(cur, v)
        elif a.func == "max":
            dst[al] = v if cur is None else max(cur, v)
        elif a.func == "count_distinct":
            if cur is None:
                dst[al] = v
            else:
                cur.update(v)
        elif a.func in ("collect", "collect_ts", "collect2"):
            if cur is None:
                dst[al] = v
            else:
                cur.extend(v)


class _NoSpill:
    """Disabled-plane spiller: the aggregate paths call the same three
    hooks unconditionally, so the legacy path stays branch-free."""

    spill_count = 0
    spilled_bytes = 0

    def observe(self, acc) -> None:
        pass

    def finish(self, acc) -> dict:
        return acc

    def close(self) -> None:
        pass


class GroupSpiller:
    """Bounds group-by accumulator memory by spilling partial state to
    disk, bit-identically to the in-memory fold.

    Epoch discipline: the first time the live accumulator crosses the
    budget, its whole contents spill as epoch 0 and EVERY subsequent
    observe() spills unconditionally — each later epoch therefore holds
    at most one batch's contribution per key, so replaying epochs in
    order reproduces the exact left-fold association the in-memory path
    would have used (float sums stay bit-identical, first/last ties
    resolve to the same arrival). Entries carry their (epoch, position)
    of first appearance; the finished accumulator is rebuilt in global
    (epoch, pos) order, which is first-appearance insertion order —
    _finalize_aggregate's row order is unchanged.

    Files publish atomically (tmp + fsync + rename) behind the
    ``memory.spill`` fault point; key space is partitioned by stable
    hash so finish() holds one partition in memory at a time."""

    PARTITIONS = 8

    def __init__(self, plan, phys_aggs, budget_bytes: int):
        self.plan = plan
        self.phys_aggs = phys_aggs
        self.budget = int(budget_bytes)
        self._dir: str | None = None
        self._epoch = 0
        self._engaged = False
        self._booked = 0
        self._closed = False
        self.spill_count = 0
        self.spilled_bytes = 0

    # ------------------------------------------------------------ hooks
    def observe(self, acc: dict) -> None:
        est = _acc_group_bytes(acc)
        if self._engaged or (self.budget and est > self.budget):
            self._spill(acc, est)
            return
        delta = est - self._booked
        if delta > 0:
            memgov.book("query_groups", delta, action="grow")
            self._booked = est
            # charge BEFORE growing further: an over-budget query dies
            # here with MemoryExceeded while in-budget neighbors run on
            memgov.charge_query(delta, "group_state")

    def finish(self, acc: dict) -> dict:
        if not self._engaged:
            return acc
        self._spill(acc, _acc_group_bytes(acc))   # live tail → last epoch
        merged: list[tuple[int, int, tuple, dict]] = []
        for p in range(self.PARTITIONS):
            merged.extend(self._merge_partition(p))
        merged.sort(key=lambda e: (e[0], e[1]))
        out = {key: parts for _e, _pos, key, parts in merged}
        memgov.count("query_groups", "unspill")
        return out

    def close(self) -> None:
        if self._closed:
            return
        self._closed = True
        if self._booked:
            memgov.unbook("query_groups", self._booked)
            memgov.release_query(self._booked)
            self._booked = 0
        if self._dir is not None:
            import shutil

            shutil.rmtree(self._dir, ignore_errors=True)
            self._dir = None

    # --------------------------------------------------------- internals
    def _spill(self, acc: dict, est: int) -> None:
        if not acc:
            return
        self._engaged = True
        if self._dir is None:
            import tempfile

            self._dir = tempfile.mkdtemp(prefix="cnosdb-spill-")
        by_part: dict[int, list] = {}
        for pos, (key, parts) in enumerate(acc.items()):
            by_part.setdefault(hash(key) % self.PARTITIONS, []) \
                .append((pos, key, parts))
        import pickle

        for p, entries in by_part.items():
            path = os.path.join(self._dir,
                                f"p{p:02d}_e{self._epoch:06d}.spill")
            tmp = path + ".tmp"
            with open(tmp, "wb") as f:
                pickle.dump(entries, f, protocol=pickle.HIGHEST_PROTOCOL)
                f.flush()
                os.fsync(f.fileno())
            if faults.ENABLED:
                faults.fire("memory.spill", path=path, epoch=self._epoch)
            os.rename(tmp, path)
            self.spilled_bytes += os.path.getsize(path)
        self._epoch += 1
        self.spill_count += 1
        memgov.count("query_groups", "spill")
        acc.clear()
        if self._booked:
            memgov.unbook("query_groups", self._booked)
            memgov.release_query(self._booked)
            self._booked = 0

    def _merge_partition(self, p: int) -> list[tuple[int, int, tuple, dict]]:
        import pickle

        assert self._dir is not None
        names = sorted(n for n in os.listdir(self._dir)
                       if n.startswith(f"p{p:02d}_e")
                       and n.endswith(".spill"))
        part: dict[tuple, list] = {}   # key → [epoch, pos, parts]
        for name in names:
            epoch = int(name[len(f"p{p:02d}_e"):-len(".spill")])
            with open(os.path.join(self._dir, name), "rb") as f:
                entries = pickle.load(f)
            for pos, key, parts in entries:
                cur = part.get(key)
                if cur is None:
                    part[key] = [epoch, pos, parts]
                else:
                    _merge_spill_entry(cur[2], parts, self.phys_aggs)
        return [(e, pos, key, parts)
                for key, (e, pos, parts) in part.items()]


def _out_name(it: ast.SelectItem) -> str:
    """Display name for a select item: SQL strips the relation qualifier
    from a plain column reference (SELECT c.host → column \"host\")."""
    if it.alias:
        return it.alias
    if isinstance(it.expr, Column):
        return it.expr.name.rsplit(".", 1)[-1]
    return it.expr.to_sql()


def _series_finalize(func: str, ts: np.ndarray, vals: np.ndarray, param):
    from . import tsfuncs

    order = np.argsort(ts, kind="stable")
    ts, vals = ts[order], np.asarray(vals)[order]
    if isinstance(param, tuple) and len(param) == 2 \
            and param[0] == "const_state":
        vals = np.full(len(ts), param[1], dtype=object)
        param = None
    if func == "increase":
        return tsfuncs.increase(ts, vals)
    if func == "sample":
        return tsfuncs.sample(vals, int(param) if param is not None else 1)
    if func == "gauge_agg":
        return tsfuncs.gauge_data(ts, vals)
    if func == "state_agg":
        return tsfuncs.state_data(ts, vals, compact=False)
    if func == "compact_state_agg":
        return tsfuncs.state_data(ts, vals, compact=True)
    # a degenerate group (<2 finite values) FAILS the query, matching the
    # reference's "At least two non-NaN values are needed" execution error
    # (function/data_quality.slt pins statement error for 1-row input)
    return tsfuncs.data_quality(func, ts, vals)


def _iso_ns(ns: int) -> str:
    """arrow timestamp rendering: ISO, fraction trimmed of trailing
    zeros, omitted when zero."""
    from datetime import datetime, timezone

    secs, frac = divmod(int(ns), 1_000_000_000)
    dt = datetime.fromtimestamp(secs, tz=timezone.utc)
    base = dt.strftime("%Y-%m-%dT%H:%M:%S")
    if frac:
        digits = f"{frac:09d}"
        while digits.endswith("000"):   # trim ns→us→ms like arrow
            digits = digits[:-3]
        base += "." + digits
    return base


def _insert_coerce(vt, v, col: str):
    """INSERT value → column type, with DataFusion's CAST semantics
    (type_conversion/between.slt pins 23.456 into BIGINT as 23;
    boolean.slt pins 1/0 into BOOLEAN as true/false)."""
    from ..models.schema import ValueType as VT

    is_bool = isinstance(v, (bool, np.bool_))
    try:
        if vt == VT.FLOAT:
            if is_bool:
                raise ValueError("BOOLEAN into DOUBLE")
            return float(v)
        if vt in (VT.INTEGER, VT.UNSIGNED):
            if is_bool:
                raise ValueError("BOOLEAN into BIGINT")
            if isinstance(v, float):
                if v != v or v in (float("inf"), float("-inf")):
                    raise ValueError("NaN/Inf into BIGINT")
                v = int(v)   # truncation toward zero (CAST semantics)
            elif isinstance(v, str):
                v = int(v.strip())
            v = int(v)
            if vt == VT.UNSIGNED and v < 0:
                raise ValueError("negative into UNSIGNED")
            return v
        if vt == VT.BOOLEAN:
            if is_bool:
                return bool(v)
            if isinstance(v, (int, float)):
                return v != 0
            if isinstance(v, str):
                from .expr import _parse_bool_str

                return _parse_bool_str(v)
            raise ValueError(f"{type(v).__name__} into BOOLEAN")
        if vt in (VT.STRING, VT.GEOMETRY):
            return v if isinstance(v, str) else str(v)
    except (ValueError, OverflowError) as e:
        raise ExecutionError(
            f"INSERT value {v!r} cannot be cast to the {vt.name} "
            f"column {col!r}: {e}")
    return v


def _arrow_type_name(sql_type: str) -> str:
    """Declared external-column SQL type → the arrow type name the
    reference's DESCRIBE prints (create_external_table.slt)."""
    t = sql_type.strip().upper()
    m = re.match(r"^DECIMAL\((\d+),\s*(\d+)\)$", t)
    if m:
        return f"Decimal128({m.group(1)}, {m.group(2)})"
    return {
        "BIGINT": "Int64", "BIGINT UNSIGNED": "UInt64",
        "INT": "Int32", "INTEGER": "Int32", "SMALLINT": "Int16",
        "TINYINT": "Int8", "DOUBLE": "Float64", "FLOAT": "Float32",
        "BOOLEAN": "Boolean", "STRING": "Utf8", "VARCHAR": "Utf8",
        "TEXT": "Utf8", "TIMESTAMP": "Timestamp(Nanosecond, None)",
        "DATE": "Date32",
    }.get(t, t.capitalize())


def _size_display(v) -> str:
    """'128MiB'/'300M' → the reference's byte-size rendering: parse to
    bytes (decimal K/M/G vs binary Ki/Mi/Gi suffixes), then humanize in
    BINARY units with full float precision — describe_database.slt pins
    wal_max_file_size '300M' as '286.102294921875 MiB'."""
    s = str(v).strip()
    m = re.match(r"^(\d+(?:\.\d+)?)\s*([KMGTP]?)(I?B?)$", s, re.I)
    if not m:
        return s
    num = float(m.group(1))
    unit, tail = m.group(2).upper(), m.group(3).upper()
    power = " KMGTP".index(unit) if unit else 0
    base = 1024 if (unit and tail.startswith("I")) else 1000
    nbytes = num * base ** power
    for p, uname in ((5, "PiB"), (4, "TiB"), (3, "GiB"), (2, "MiB"),
                     (1, "KiB")):
        if nbytes >= 1024 ** p:
            val = nbytes / 1024 ** p
            txt = str(int(val)) if val == int(val) else repr(val)
            return f"{txt} {uname}"
    txt = str(int(nbytes)) if nbytes == int(nbytes) else repr(nbytes)
    return f"{txt} B"


def _median_value(vals: np.ndarray):
    """Median with DataFusion's type semantics: integer inputs compute
    the even-count middle as (a + b) / 2 in INTEGER arithmetic
    (truncating division — approx_median.slt pins median([1,4,5,6]) = 4),
    floats interpolate."""
    def all_int(a):
        if np.issubdtype(a.dtype, np.integer):
            return True
        return a.dtype == object and len(a) and all(
            isinstance(x, (int, np.integer))
            and not isinstance(x, (bool, np.bool_)) for x in a)

    if all_int(vals):
        s = sorted(int(x) for x in vals)
        m = len(s)
        if m % 2:
            return s[m // 2]
        t = s[m // 2 - 1] + s[m // 2]
        return t // 2 if t >= 0 else -((-t) // 2)   # truncate toward 0
    return float(np.median(vals.astype(np.float64)))


def _cell_repr(v) -> str:
    """array_agg element rendering (bare values, arrow list style)."""
    if v is None:
        return "NULL"
    if isinstance(v, (float, np.floating)):
        return repr(float(v))
    if isinstance(v, (bool, np.bool_)):
        return "true" if v else "false"
    if isinstance(v, np.integer):
        return str(int(v))
    return str(v)


def _apply_finalizer(spec, parts: dict):
    """Scalar (per-group-dict) interpretation of a finalizer spec."""
    kind = spec[0]
    if kind == "mean":
        cnt = parts.get(spec[2], 0)
        if not cnt:
            return None
        return parts.get(spec[1], 0.0) / cnt
    if kind == "int":
        return int(parts.get(spec[1], 0))
    if kind == "zero":
        return 0
    if kind == "pass":
        return parts.get(spec[1])
    if kind == "distinct":
        vals = parts.get(spec[1])
        return len(vals) if vals is not None else 0
    if kind in ("median", "stddev", "stddev_pop", "var_samp", "var_pop",
                "mode", "array_agg", "bit_and", "bit_or", "bit_xor"):
        chunks = parts.get(spec[1])
        if not chunks:
            return None
        vals = np.concatenate(chunks)
        if kind in ("bit_and", "bit_or", "bit_xor"):
            return rel.bit_reduce(kind, vals)
        if kind == "median":
            return _median_value(vals)
        if kind == "stddev":
            return float(np.std(vals.astype(np.float64), ddof=1)) \
                if len(vals) > 1 else None
        if kind == "stddev_pop":
            return float(np.std(vals.astype(np.float64), ddof=0))
        if kind == "var_samp":
            return float(np.var(vals.astype(np.float64), ddof=1)) \
                if len(vals) > 1 else None
        if kind == "var_pop":
            return float(np.var(vals.astype(np.float64), ddof=0))
        if kind == "array_agg":
            # rendered like arrow's list repr (reference array_agg.slt)
            return "[" + ", ".join(_cell_repr(v) for v in vals) + "]"
        uniq, counts = np.unique(vals, return_counts=True)
        return uniq[np.argmax(counts)]
    if kind == "array_ts":
        chunks = parts.get(spec[1])
        if not chunks:
            return None
        ts = np.concatenate([c[0] for c in chunks])
        vals = np.concatenate([np.asarray(c[1], dtype=object)
                               for c in chunks])
        order = np.argsort(ts, kind="stable")
        if not spec[2]:
            order = order[::-1]
        vals = vals[order]
        if spec[3]:   # array_agg(time ...): elements render as arrow ts
            return "[" + ", ".join(_iso_ns(int(t)) for t in ts[order]) \
                + "]"
        return "[" + ", ".join(_cell_repr(v) for v in vals) + "]"
    if kind == "array_const":
        chunks = parts.get(spec[1])
        if not chunks:
            return None
        n_rows = sum(len(c[0]) for c in chunks)
        return "[" + ", ".join([_cell_repr(spec[2])] * n_rows) + "]"
    if kind == "percentile":
        chunks = parts.get(spec[1])
        if not chunks:
            return None
        vals = np.concatenate(chunks).astype(np.float64)
        return float(np.quantile(vals, spec[2]))
    if kind == "percentile_w_const":
        chunks = parts.get(spec[1])
        if not chunks:
            return None
        vals = np.concatenate(chunks).astype(np.float64)
        w = np.full(len(vals), float(spec[2]))
        order = np.argsort(vals)
        vals, w = vals[order], w[order]
        cum = np.cumsum(w)
        if cum[-1] <= 0:
            return None
        target = spec[3] * cum[-1]
        return float(vals[np.searchsorted(cum, target, side="left")
                          .clip(0, len(vals) - 1)])
    if kind == "percentile_w":
        chunks = parts.get(spec[1])
        if not chunks:
            return None
        vals = np.concatenate([c[0] for c in chunks]).astype(np.float64)
        w = np.concatenate([c[1] for c in chunks]).astype(np.float64)
        order = np.argsort(vals)
        vals, w = vals[order], w[order]
        cum = np.cumsum(w)
        if cum[-1] <= 0:
            return None
        target = spec[2] * cum[-1]
        return float(vals[np.searchsorted(cum, target, side="left")
                          .clip(0, len(vals) - 1)])
    if kind in ("corr", "covar_samp", "covar_pop"):
        chunks = parts.get(spec[1])
        if not chunks:
            return None
        x = np.concatenate([c[0] for c in chunks]).astype(np.float64)
        y = np.concatenate([c[1] for c in chunks]).astype(np.float64)
        if kind == "corr":
            if len(x) < 2 or np.std(x) == 0 or np.std(y) == 0:
                return None
            return float(np.corrcoef(x, y)[0, 1])
        ddof = 1 if kind == "covar_samp" else 0
        if len(x) <= ddof:
            return None
        return float(np.cov(x, y, ddof=ddof)[0, 1])
    if kind == "const_agg":
        rows = int(parts.get(spec[2], 0))
        func, value = spec[1], spec[3]
        if value is None:
            return None
        if func == "sum":
            return value * rows if rows else None
        if rows == 0:
            return None
        if func in ("avg", "mean", "median"):
            return float(value)
        if func in ("min", "max", "first", "last",
                    "bit_and", "bit_or", "bit_xor"):
            return value
        if func in ("stddev", "stddev_samp", "var", "var_samp"):
            return 0.0 if rows > 1 else None
        if func in ("stddev_pop", "var_pop"):
            return 0.0
        if func == "zero":
            return 0.0
        return None   # const_agg:null and unknown constants → NULL
    if kind == "series":
        chunks = parts.get(spec[2])
        if not chunks:
            return None
        ts = np.concatenate([c[0] for c in chunks])
        vals = np.concatenate([np.asarray(c[1]) for c in chunks])
        return _series_finalize(spec[1], ts, vals, spec[3])
    raise ExecutionError(f"bad finalizer {spec!r}")


def _render_output(plan, env: dict, n: int):
    """Evaluate output expressions and RENDER NULLs: a slot whose source
    aggregate is invalid (e.g. sum over an all-NULL group) must surface
    as NULL/NaN, not its 0 accumulator."""
    names, cols = [], []
    for name, expr in plan.output:
        if n == 0:
            names.append(name)
            cols.append(np.empty(0))
            continue
        v = expr.eval(env, np)
        if isinstance(v, DictArray):
            v = v.materialize()
        if np.isscalar(v) or getattr(v, "shape", None) == ():
            v = np.full(n, v)
        arr = np.asarray(v)
        vv = np.ones(n, dtype=bool)
        for c in expr_mod.propagating_columns(expr):
            vk = f"__valid__:{c}"
            if vk in env and len(env[vk]) == n:
                vv &= env[vk]
        if not vv.all():
            arr = arr.astype(object)
            arr[~vv] = None
        names.append(name)
        cols.append(arr)
    return names, cols


def _vector_finalize(spec, parts_env: dict, n: int):
    """Vectorized interpretation over whole partial columns.
    parts_env: alias → (values array, valid array|None)."""
    kind = spec[0]

    def col(alias, default=0.0):
        entry = parts_env.get(alias)
        if entry is None:
            return np.full(n, default), np.zeros(n, dtype=bool)
        v, valid = entry
        return v, (valid if valid is not None else np.ones(n, dtype=bool))

    if kind == "mean":
        s, sv = col(spec[1])
        c, _cv = col(spec[2], 0)
        c = c.astype(np.int64)
        with np.errstate(invalid="ignore", divide="ignore"):
            out = np.where(c > 0, s.astype(np.float64) / np.maximum(c, 1), np.nan)
        return out, c > 0
    if kind == "int":
        c, _ = col(spec[1], 0)
        return c.astype(np.int64), np.ones(n, dtype=bool)
    if kind == "zero":
        return np.zeros(n, dtype=np.int64), np.ones(n, dtype=bool)
    if kind == "const_agg":
        rows, _ = col(spec[2], 0)
        rows = rows.astype(np.int64)
        func, value = spec[1], spec[3]
        ok = rows > 0
        if value is None:
            return np.full(n, None, dtype=object), np.zeros(n, dtype=bool)
        if func == "sum":
            return np.where(ok, value * rows, 0), ok
        if func in ("avg", "mean", "median"):
            return np.where(ok, float(value), np.nan), ok
        if func in ("min", "max", "first", "last",
                    "bit_and", "bit_or", "bit_xor"):
            return np.where(ok, value, 0), ok
        if func in ("stddev", "stddev_samp", "var", "var_samp"):
            return np.zeros(n), rows > 1
        if func in ("stddev_pop", "var_pop"):
            return np.zeros(n), ok
        if func == "zero":
            return np.zeros(n), ok
        if func == "null":
            return np.full(n, None, dtype=object), np.zeros(n, dtype=bool)
        raise ExecutionError(f"bad const_agg {func!r}")
    if kind == "pass":
        return col(spec[1])
    if kind == "distinct":
        c, v = col(spec[1], 0)
        return c, v
    raise ExecutionError(f"bad finalizer {spec!r}")


# one shared NaN so cross-vnode NaN group keys collapse to a single dict
# entry (NaN != NaN defeats tuple keys; dict identity matches this object)
_NAN_KEY = float("nan")


def _canon_group_key(v):
    if isinstance(v, float) and v != v:
        return _NAN_KEY
    if isinstance(v, np.floating) and v != v:
        return _NAN_KEY
    return v


_VEC_MERGE_FUNCS = {"count", "sum", "min", "max", "first", "last"}


def _merge_results_vec(results, plan: AggregatePlan,
                       phys_aggs: list[AggSpec]):
    """Vectorized cross-vnode partial merge → one synthetic AggResult, or
    None when ineligible (string-field group axes, host aggregates,
    object-valued agg columns). This is the multi-vnode half of the 5×
    headline: the per-row python dict merge costs more than the kernels
    themselves at 100M-row scale (reference merges partials inside
    DataFusion's final AggregateExec, also columnar)."""
    from ..ops.tpu_exec import AggResult

    if plan.group_fields:
        return None
    if any(a.func not in _VEC_MERGE_FUNCS for a in phys_aggs):
        return None
    results = [r for r in results if r.n_rows]
    if not results:
        cols = {t: np.empty(0, dtype=object) for t in plan.group_tags}
        if plan.bucket is not None:
            cols["time"] = np.empty(0, dtype=np.int64)
        for a in phys_aggs:
            cols[a.alias] = np.empty(0)
        return AggResult(cols, 0)
    if any(r.gid is None for r in results):
        return None
    for r in results:
        for a in phys_aggs:
            col = r.columns.get(a.alias)
            if col is not None and col.dtype == object:
                return None   # string min/max etc: generic path
    # ---- global tag-group ids (label tables are tiny: one entry per
    # distinct tag combination per vnode)
    glab: dict[tuple, int] = {}
    gid_parts = []
    for r in results:
        lut = np.empty(len(r.labels), dtype=np.int64)
        for i, lab in enumerate(r.labels):
            lut[i] = glab.setdefault(lab, len(glab))
        gid_parts.append(lut[r.gid])
    gids = np.concatenate(gid_parts)
    n_lab = max(len(glab), 1)
    # ---- bucket-time codes
    if plan.bucket is not None:
        times = np.concatenate([r.columns["time"] for r in results])
        utimes, tcode = np.unique(times, return_inverse=True)
        n_t = len(utimes)
    else:
        utimes, tcode, n_t = None, np.zeros(len(gids), dtype=np.int64), 1
    code = gids * n_t + tcode
    k = n_lab * n_t
    occupied = np.zeros(k, dtype=bool)
    occupied[code] = True
    sel = np.nonzero(occupied)[0]
    pos = np.empty(k, dtype=np.int64)
    pos[sel] = np.arange(len(sel))
    out_cols: dict[str, np.ndarray] = {}
    out_valid: dict[str, np.ndarray] = {}
    # group label columns
    if plan.group_tags:
        lab_table = [None] * len(glab)
        for lab, g in glab.items():
            lab_table[g] = lab
        for i, t in enumerate(plan.group_tags):
            col = np.empty(len(glab), dtype=object)
            col[:] = [lab[i] for lab in lab_table]
            out_cols[t] = col[sel // n_t]
    if plan.bucket is not None:
        out_cols["time"] = utimes[sel % n_t]
    n_out = len(sel)
    for a in phys_aggs:
        vals = np.concatenate([
            np.asarray(r.columns[a.alias]) if a.alias in r.columns
            else np.zeros(r.n_rows) for r in results])
        valid = np.concatenate([
            r.valid[a.alias] if a.alias in r.valid
            else (np.ones(r.n_rows, dtype=bool) if a.alias in r.columns
                  else np.zeros(r.n_rows, dtype=bool))
            for r in results])
        vcode = pos[code[valid]]
        vv = vals[valid]
        if a.func == "count":
            acc = np.zeros(n_out, dtype=np.int64)
            np.add.at(acc, vcode, vv.astype(np.int64))
            out_cols[a.alias] = acc
        elif a.func == "sum":
            acc = np.zeros(n_out, dtype=vv.dtype if vv.dtype.kind in "iuf"
                           else np.float64)
            np.add.at(acc, vcode, vv)
            has = np.zeros(n_out, dtype=bool)
            has[vcode] = True
            out_cols[a.alias] = acc
            out_valid[a.alias] = has
        elif a.func in ("min", "max"):
            if vv.dtype.kind == "f":
                init = np.inf if a.func == "min" else -np.inf
            elif vv.dtype.kind == "u":
                init = np.iinfo(vv.dtype).max if a.func == "min" else 0
            else:
                ii = np.iinfo(np.int64)
                init = ii.max if a.func == "min" else ii.min
            acc = np.full(n_out, init, dtype=vv.dtype)
            red = np.minimum if a.func == "min" else np.maximum
            red.at(acc, vcode, vv)
            has = np.zeros(n_out, dtype=bool)
            has[vcode] = True
            out_cols[a.alias] = acc
            out_valid[a.alias] = has
        else:   # first / last by actual timestamp
            ts_key = a.alias + "__ts"
            ts = np.concatenate([
                np.asarray(r.columns[ts_key]) if ts_key in r.columns
                else np.zeros(r.n_rows, dtype=np.int64)
                for r in results])[valid]
            order = np.lexsort((ts, vcode))
            if a.func == "last":
                order = order[::-1]
            codes_sorted = vcode[order]
            _, firsts = np.unique(codes_sorted, return_index=True)
            rows = order[firsts]
            acc = np.zeros(n_out, dtype=vv.dtype)
            acc[vcode[rows]] = vv[rows]
            tacc = np.zeros(n_out, dtype=np.int64)
            tacc[vcode[rows]] = ts[rows]
            has = np.zeros(n_out, dtype=bool)
            has[vcode] = True
            out_cols[a.alias] = acc
            out_cols[ts_key] = tacc
            out_valid[a.alias] = has
    return AggResult(out_cols, n_out, out_valid)


def _merge_partial(acc: dict, result, plan: AggregatePlan,
                   phys_aggs: list[AggSpec]):
    n = result.n_rows
    if n == 0:
        return
    cols = result.columns
    gt = plan.group_tags + plan.group_fields
    for i in range(n):
        key = tuple(_canon_group_key(cols[t][i]) for t in gt)
        if plan.bucket is not None:
            key = key + (int(cols["time"][i]),)
        parts = acc.setdefault(key, {})
        for a in phys_aggs:
            if a.func == "count_distinct":
                continue
            if a.alias not in cols:
                continue
            valid = result.valid.get(a.alias)
            if valid is not None and not valid[i]:
                continue
            v = cols[a.alias][i]
            cur = parts.get(a.alias)
            if a.func == "count":
                parts[a.alias] = (cur or 0) + int(v)
            elif a.func == "sum":
                parts[a.alias] = v if cur is None else cur + v
            elif a.func == "min":
                parts[a.alias] = v if cur is None else min(cur, v)
            elif a.func == "max":
                parts[a.alias] = v if cur is None else max(cur, v)
            elif a.func in ("first", "last"):
                ts_col = cols.get(a.alias + "__ts")
                ts = int(ts_col[i]) if ts_col is not None else 0
                cur_ts = parts.get(a.alias + "__ts")
                better = (cur is None or cur_ts is None
                          or (a.func == "first" and ts < cur_ts)
                          or (a.func == "last" and ts > cur_ts))
                if better:
                    parts[a.alias] = v
                    parts[a.alias + "__ts"] = ts


def _merge_compressed_partials(acc: dict, batch, plan: AggregatePlan,
                               phys_aggs: list[AggSpec]):
    """Fold a batch's compressed-domain page partials into the generic
    accumulator. Key layout and merge semantics are _merge_partial's
    exactly — group tags from the partial's series key (same values
    _tag_group_layout labels carry), bucket time appended — so lane
    partials and kernel partials interleave bit-identically regardless
    of which pages the lane answered."""
    cp = getattr(batch, "compressed_partials", None)
    if not cp:
        return
    skeys = cp["series_keys"]
    for (sid, bts), parts in cp["rows"].items():
        sk = skeys.get(sid)
        tags = sk.tag_dict() if sk is not None else {}
        key = tuple(_canon_group_key(tags.get(t))
                    for t in plan.group_tags)
        if plan.bucket is not None:
            key = key + (int(bts),)
        dst = acc.setdefault(key, {})
        for a in phys_aggs:
            if a.alias not in parts:
                continue
            v = parts[a.alias]
            cur = dst.get(a.alias)
            if a.func == "count":
                dst[a.alias] = (cur or 0) + int(v)
            elif a.func == "sum":
                dst[a.alias] = v if cur is None else cur + v
            elif a.func == "min":
                dst[a.alias] = v if cur is None else min(cur, v)
            elif a.func == "max":
                dst[a.alias] = v if cur is None else max(cur, v)
            elif a.func in ("first", "last"):
                ts = int(parts.get(a.alias + "__ts", 0))
                cur_ts = dst.get(a.alias + "__ts")
                better = (cur is None or cur_ts is None
                          or (a.func == "first" and ts < cur_ts)
                          or (a.func == "last" and ts > cur_ts))
                if better:
                    dst[a.alias] = v
                    dst[a.alias + "__ts"] = ts


def _batch_column(batch, plan, col, native: bool = False):
    """(values, valid) for a field / tag / time column of a scan batch,
    or (None, None) when absent from this vnode. native=True skips the
    object-array conversion (the vectorized DISTINCT path factorizes
    native dtypes — and DictArray codes — directly)."""
    if col in batch.fields:
        vt, vals, valid = batch.fields[col]
        if native:
            return vals, valid
        return as_object_array(vals), valid
    if col in plan.schema.tag_names():
        per_series = np.array(
            [(k.tag_value(col) if k is not None else None)
             for k in batch.series_keys], dtype=object)
        vals = per_series[batch.sid_ordinal]
        return vals, np.array([v is not None for v in vals], dtype=bool)
    if col == "time":
        return batch.ts, np.ones(batch.n_rows, dtype=bool)
    return None, None


def _merge_distinct(acc: dict, batch, plan: AggregatePlan, spec: AggSpec):
    """Host-side COUNT(DISTINCT col) + collect/count_multi partials per
    group.

    Vectorized: rows map to combined (tag × field × bucket) segment ids
    through ops.tpu_exec.host_group_layout — the same per-batch cached
    factorization the segment kernels use, so warm rescans pay nothing —
    and every per-group update happens in bulk: count_multi via bincount,
    collect via one stable argsort + run slicing, DISTINCT via sorted
    unique (group, value) code pairs (ops.group_agg). Python work is
    O(occupied groups), not O(rows). The per-row fold survives only as
    the fallback for unfactorizable payloads."""
    native = spec.func == "count_distinct"
    vals, valid = _batch_column(batch, plan, spec.column, native=native)
    if vals is None:
        return
    vals2 = None
    if spec.func == "collect2":
        vals2, valid2 = _batch_column(batch, plan, spec.param)
        if vals2 is None:
            return
        valid = valid & valid2
    if spec.func == "count_multi":
        for extra in spec.param or []:
            _ev, evalid = _batch_column(batch, plan, extra)
            if _ev is None:
                return
            valid = valid & evalid
    # reuse the group/bucket mapping by building keys per row
    from ..ops.tpu_exec import _filter_env

    mask = np.ones(batch.n_rows, dtype=bool)
    if plan.filter is not None:
        env = _filter_env(batch, needed=plan.filter.columns())
        missing = [c for c in plan.filter.columns() if c not in env]
        for c in missing:
            env[c] = np.zeros(batch.n_rows)
            env[f"__valid__:{c}"] = np.zeros(batch.n_rows, dtype=bool)
        mask = np.asarray(plan.filter.eval(env, np), dtype=bool)
        if mask.shape == ():
            mask = np.full(batch.n_rows, bool(mask))
    mask = mask & valid
    buckets = None
    if plan.bucket is not None:
        origin, interval = plan.bucket
        buckets = origin + ((batch.ts - origin) // interval) * interval
    if _merge_distinct_vec(acc, batch, plan, spec, vals, vals2, mask):
        return
    # ------------------------------------------- scalar fallback
    if isinstance(vals, DictArray):
        vals = as_object_array(vals)
    tagmaps = []
    for k in batch.series_keys:
        tags = k.tag_dict() if k is not None else {}
        tagmaps.append(tuple(tags.get(t) for t in plan.group_tags))
    gf_cols = []
    for fc in plan.group_fields:
        gv, gok = _batch_column(batch, plan, fc)
        if gv is None:
            gv = np.empty(batch.n_rows, dtype=object)
            gok = np.zeros(batch.n_rows, dtype=bool)
        gf_cols.append((gv, gok))

    def row_key(i):
        key = tagmaps[batch.sid_ordinal[i]]
        for gv, gok in gf_cols:
            key = key + ((_canon_group_key(gv[i]) if gok[i] else None),)
        if plan.bucket is not None:
            key = key + (int(buckets[i]),)
        return key

    collect = spec.func in ("collect", "collect_ts", "collect2")
    idxs = np.nonzero(mask)[0]
    if spec.func == "count_multi":
        if plan.bucket is not None or plan.group_tags or plan.group_fields:
            for i in idxs:
                parts = acc.setdefault(row_key(i), {})
                parts[spec.alias] = parts.get(spec.alias, 0) + 1
        else:
            parts = acc.setdefault((), {})
            parts[spec.alias] = parts.get(spec.alias, 0) + len(idxs)
        return
    if collect:
        # group indices first, slice values in bulk per group
        group_rows: dict[tuple, list[int]] = {}
        for i in idxs:
            group_rows.setdefault(row_key(i), []).append(i)
        arr = np.asarray(vals)
        with_ts = spec.func == "collect_ts"
        arr2 = np.asarray(vals2) if vals2 is not None else None
        for key, rows in group_rows.items():
            parts = acc.setdefault(key, {})
            if spec.func == "collect2":
                chunk = (arr[rows], arr2[rows])
            elif with_ts:
                chunk = (batch.ts[rows], arr[rows])
            else:
                chunk = arr[rows]
            parts.setdefault(spec.alias, []).append(chunk)
        return
    for i in idxs:
        parts = acc.setdefault(row_key(i), {})
        s = parts.setdefault(spec.alias, set())
        s.add(vals[i])


def _merge_distinct_vec(acc: dict, batch, plan: AggregatePlan,
                        spec: AggSpec, vals, vals2,
                        mask: np.ndarray) -> bool:
    """Bulk per-group merge of one host aggregate over one batch.
    Returns False when the payload defeats factorization (caller keeps
    the scalar fold). Segment layout (and its decode tables) comes from
    the ScanToken-persistent caches shared with the kernel path."""
    from ..ops import group_agg as _ga
    from ..ops.tpu_exec import host_group_layout

    try:
        layout = host_group_layout(batch, plan.group_tags,
                                   plan.group_fields, plan.bucket)
    except Exception:
        stages.count_error("executor.group_layout")
        return False
    if layout is None:
        return False        # empty batch: scalar path keeps global-key rows
    idx = np.nonzero(mask)[0]
    globl = not (plan.bucket is not None or plan.group_tags
                 or plan.group_fields)
    if spec.func == "count_multi" and globl:
        # global count_multi creates its row even when no rows match
        parts = acc.setdefault((), {})
        parts[spec.alias] = parts.get(spec.alias, 0) + len(idx)
        return True
    # occupied segments only — never allocate num_segments-sized arrays
    # (tag × bucket cardinality is unbounded on this host path)
    useg, inv = np.unique(layout.seg_ids[idx].astype(np.int64),
                          return_inverse=True)
    inv = inv.astype(np.int64).ravel()

    def seg_keys(segs: np.ndarray) -> list[tuple]:
        """Decode combined segment ids → group key tuples (tag values,
        field values, bucket start) — the exact key layout
        _merge_partial builds from the kernel's label columns."""
        nb = max(layout.n_buckets, 1)
        bkt = segs % nb
        rem = segs // nb
        peeled = []
        for dim, dic in zip(reversed(layout.gf_dims),
                            reversed(layout.gf_dicts)):
            peeled.append((rem % dim, dic))
            rem = rem // dim
        peeled.reverse()
        keys = []
        bs = layout.bucket_starts
        for i in range(len(segs)):
            key = layout.group_labels[int(rem[i])]
            for codes_arr, dic in peeled:
                c = int(codes_arr[i])
                key = key + ((_canon_group_key(dic[c]) if c < len(dic)
                              else None),)
            if plan.bucket is not None:
                key = key + (int(bs[int(bkt[i])]),)
            keys.append(key)
        return keys

    if spec.func == "count_multi":
        cnt = np.bincount(inv, minlength=len(useg))
        for key, c in zip(seg_keys(useg), cnt):
            parts = acc.setdefault(key, {})
            parts[spec.alias] = parts.get(spec.alias, 0) + int(c)
        return True
    if spec.func in ("collect", "collect_ts", "collect2"):
        order, bounds, run_codes = _ga.grouped_order(inv)
        arr = np.asarray(vals)
        arr2 = np.asarray(vals2) if vals2 is not None else None
        with_ts = spec.func == "collect_ts"
        keys = seg_keys(useg[run_codes.astype(np.int64)])
        for k, key in enumerate(keys):
            rows = idx[order[bounds[k]:bounds[k + 1]]]
            if spec.func == "collect2":
                chunk = (arr[rows], arr2[rows])
            elif with_ts:
                chunk = (batch.ts[rows], arr[rows])
            else:
                chunk = arr[rows]
            acc.setdefault(key, {}).setdefault(spec.alias, []).append(chunk)
        return True
    # ---- count(DISTINCT): sorted unique (group, value) code pairs
    if isinstance(vals, DictArray):
        # dictionary codes ARE the factorization (values unique by
        # construction — the gf group axis makes the same assumption)
        codes = vals.codes.astype(np.int64)[idx]
        dic = vals.values
        nv = len(dic)
    else:
        f = _ga.factorize(np.asarray(vals)[idx])
        if f is None:
            return False
        codes, dic, nv = f.codes, f.values, f.n_values
    pairs = _ga.distinct_pairs(inv, codes, nv)
    _ga._count("distinct_sort")
    nvm = max(nv, 1)
    pseg = pairs // nvm
    pval = pairs % nvm
    if not len(pairs):
        return True
    starts = np.nonzero(np.concatenate(
        ([True], pseg[1:] != pseg[:-1])))[0]
    ends = np.append(starts[1:], len(pairs))
    for k, key in enumerate(seg_keys(useg[pseg[starts]])):
        s = acc.setdefault(key, {}).setdefault(spec.alias, set())
        s.update(dic[pval[starts[k]:ends[k]]].tolist())
    return True


def _apply_gapfill(plan: AggregatePlan, rs: ResultSet) -> ResultSet:
    """Expand to a dense (group × bucket) grid; fill per locf/interpolate
    (reference extension/expr scalar_function gapfill/locf/interpolate).

    Vectorized over the grid: rows scatter into a (n_groups, n_buckets)
    matrix in one fancy-indexed assignment, locf is a row-wise
    maximum.accumulate of last-known indices (object columns included —
    locf's semantics there are positional, not arithmetic), and
    interpolate stays np.interp per group. Python work is O(result rows
    + groups), never O(groups × grid)."""
    origin, interval = plan.bucket
    cols = {n: c for n, c in zip(rs.names, rs.columns)}
    # outputs may alias the bucket ("t") and tags: resolve via plan.output
    time_name = None
    tag_name_of: dict[str, str] = {}
    for name, expr in plan.output:
        if isinstance(expr, Column):
            if expr.name == "time":
                time_name = name
            elif expr.name in plan.group_tags:
                tag_name_of[expr.name] = name
    if time_name is None or time_name not in cols or rs.n_rows == 0:
        return rs
    times = cols[time_name].astype(np.int64)
    # grid bounds: the query's time range when bounded, else observed range
    lo = times.min()
    hi = times.max()
    if not plan.time_ranges.is_all:
        qlo, qhi = plan.time_ranges.min_ts, plan.time_ranges.max_ts
        if qlo > -(2**62):
            lo = origin + ((qlo - origin) // interval) * interval
        if qhi < 2**62:
            hi = origin + ((qhi - origin) // interval) * interval
    grid = np.arange(lo, hi + 1, interval, dtype=np.int64)
    G = len(grid)
    gt = [tag_name_of.get(t, t) for t in plan.group_tags if
          tag_name_of.get(t, t) in cols]
    group_keys = list(zip(*[cols[t] for t in gt])) if gt else [()] * rs.n_rows
    # group ids per row (tag keys are arbitrary objects: dict factorize),
    # renumbered into the output order (sorted by stringified key)
    gmap: dict[tuple, int] = {}
    gids = np.empty(rs.n_rows, dtype=np.int64)
    for i, k in enumerate(group_keys):
        gids[i] = gmap.setdefault(tuple(k), len(gmap))
    sorted_keys = sorted(gmap, key=lambda k: tuple(str(x) for x in k))
    rank = np.empty(len(gmap), dtype=np.int64)
    for pos, key in enumerate(sorted_keys):
        rank[gmap[key]] = pos
    ng = len(sorted_keys)
    bi = (times - lo) // interval
    ok = (bi >= 0) & (bi < G)
    # later rows win duplicate (group, bucket) cells — same as the old
    # dict-of-rows construction
    flat = rank[gids[ok]] * G + bi[ok]

    def _locf2d(vals: np.ndarray, known: np.ndarray) -> np.ndarray:
        """Row-wise forward fill: carry the last known column index."""
        src_col = np.where(known, np.arange(G)[None, :], -1)
        src_col = np.maximum.accumulate(src_col, axis=1)
        filled = src_col >= 0
        rows = np.broadcast_to(np.arange(ng)[:, None], (ng, G))
        out = vals.copy()
        out[filled] = vals[rows[filled], src_col[filled]]
        return out

    agg_names = [n for n in rs.names if n not in gt and n != time_name]
    out_cols_by_name: dict[str, np.ndarray] = {}
    for name in agg_names:
        src = cols[name]
        method = plan.fill_methods.get(name)
        if src.dtype == object:
            # string-valued aggregates: grid holes stay None; only locf
            # makes sense for them
            vals = np.full(ng * G, None, dtype=object)
            vals[flat] = src[ok]
            vals = vals.reshape(ng, G)
            if method == "locf":
                known = np.frompyfunc(
                    lambda v: v is not None, 1, 1)(vals).astype(bool)
                vals = _locf2d(vals, known)
            out_cols_by_name[name] = vals.ravel()
            continue
        vals = np.full(ng * G, np.nan)
        vals[flat] = src[ok].astype(np.float64)
        vals = vals.reshape(ng, G)
        if method == "locf":
            vals = _locf2d(vals, ~np.isnan(vals))
        elif method == "interpolate":
            gridf = grid.astype(np.float64)
            for r in range(ng):
                row = vals[r]
                known = ~np.isnan(row)
                if known.sum() < 2:
                    continue
                missing = ~known
                interp = np.interp(gridf[missing], gridf[known], row[known])
                # strict interpolation: no extrapolation beyond endpoints
                mlo, mhi = grid[known][0], grid[known][-1]
                inside = (grid[missing] >= mlo) & (grid[missing] <= mhi)
                fill = np.full(int(missing.sum()), np.nan)
                fill[inside] = interp[inside]
                row[missing] = fill
        out_cols_by_name[name] = vals.ravel()
    new_cols = []
    for n in rs.names:
        if n == time_name:
            new_cols.append(np.tile(grid, ng))
        elif n in gt:
            i = gt.index(n)
            col = np.empty(ng * G, dtype=object)
            for pos, key in enumerate(sorted_keys):
                col[pos * G:(pos + 1) * G] = key[i]
            new_cols.append(col)
        else:
            new_cols.append(out_cols_by_name[n])
    return ResultSet(rs.names, new_cols)


# NULLS LAST ascending, FIRST descending — DataFusion's defaults, which
# the reference inherits; shared with the window-function order keys
_null_safe_key = rel.null_safe_key


def _positional_order(order_by, rs: ResultSet):
    """ORDER BY n (a bare integer literal) is positional over the output
    columns in every SQL dialect; resolve it to the column array itself so
    each _order_limit caller (set-op chain, relational join path, scan
    path) gets it without needing the name in its env."""
    out = []
    for oe, asc in order_by:
        pos = oe.value if isinstance(oe, Literal) else oe
        if isinstance(pos, int) and not isinstance(pos, bool):
            if not 1 <= pos <= len(rs.names):
                raise QueryError(f"ORDER BY position {pos} is out of range")
            oe = np.asarray(rs.columns[pos - 1])
        out.append((oe, asc))
    return out


def _order_limit(rs: ResultSet, order_by, limit, offset, env) -> ResultSet:
    n = rs.n_rows
    if n and order_by:
        order_by = _positional_order(order_by, rs)
        keys = []
        for oe, asc in reversed(order_by):
            v = oe if isinstance(oe, np.ndarray) \
                else oe.eval(env, np) if isinstance(oe, Expr) else env[oe]
            vals, nulls = _null_safe_key(np.asarray(v))
            keys.append(vals)
            if nulls is not None:
                keys.append(nulls)  # later key = higher priority in lexsort
        idx = None
        if limit is not None and len(order_by) == 1 and len(keys) == 1:
            # ORDER BY key LIMIT k: select-then-gather top-k
            # (ops/strkernels; device threshold on TPU) instead of a full
            # sort — bit-identical tie order, or None → full sort below
            from ..ops import strkernels

            idx = strkernels.topk_order_indices(
                keys[0], None, order_by[0][1], (offset or 0) + limit)
        if idx is None:
            idx = np.lexsort(keys)
            # lexsort is ascending on all; apply desc by flipping per-key
            # is complex — handle single-key desc and uniform direction
            # fast paths
            if all(not asc for _, asc in order_by):
                idx = idx[::-1]
            elif not all(asc for _, asc in order_by):
                idx = _mixed_order(order_by, env, n)
        rs = ResultSet(rs.names, [c[idx] for c in rs.columns])
    if offset:
        rs = ResultSet(rs.names, [c[offset:] for c in rs.columns])
    if limit is not None:
        rs = ResultSet(rs.names, [c[:limit] for c in rs.columns])
    return rs


def _concat_cols(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """Concatenate union branches; mixed dtypes fall back to object."""
    if a.dtype == b.dtype:
        return np.concatenate([a, b])
    if a.dtype != object and b.dtype != object:
        try:
            return np.concatenate([a.astype(np.float64),
                                   b.astype(np.float64)])
        except (TypeError, ValueError):
            pass
    return np.concatenate([a.astype(object), b.astype(object)])


_NAN_KEY = object()  # NULL/NaN rows compare equal in DISTINCT and set ops


def _row_keys(columns) -> list:
    """Hashable per-row keys over a column set. Float NaN (the NULL /
    outer-join padding value) maps to a shared token so NULLs are not
    distinct from each other — SQL DISTINCT / set-operation semantics."""
    if not columns:
        return []
    keys = []
    for i in range(len(columns[0])):
        key = []
        for c in columns:
            v = c[i] if c.dtype == object else c[i].item()
            if v is None or (isinstance(v, float) and v != v):
                v = _NAN_KEY  # None (object col) and NaN (float col) are
                # both NULL; they must match across branch dtypes
            key.append(v)
        keys.append(tuple(key))
    return keys


def _set_op_cols(left: list, right: list, op: str, all_: bool) -> list:
    """INTERSECT/EXCEPT over column sets, preserving left-operand row
    order. Bag semantics for ALL (INTERSECT ALL keeps min(l,r) copies of
    a row, EXCEPT ALL keeps l−r); the distinct forms dedupe the output.
    The reference lowers these to DataFusion semi/anti joins + distinct
    (query_server inherits them from its forked sqlparser/DataFusion)."""
    from collections import Counter

    budget = Counter(_row_keys(right))
    keep: list[int] = []
    if all_:
        for i, k in enumerate(_row_keys(left)):
            if budget[k] > 0:
                budget[k] -= 1
                if op == "intersect":
                    keep.append(i)
            elif op == "except":
                keep.append(i)
    else:
        seen = set()
        for i, k in enumerate(_row_keys(left)):
            if k in seen:
                continue
            seen.add(k)
            if (budget[k] > 0) == (op == "intersect"):
                keep.append(i)
    idx = np.array(keep, dtype=np.int64)
    return [c[idx] for c in left]


def _mixed_order(order_by, env, n):
    """Mixed asc/desc via one lexsort over rank-inverted keys.

    Reversing a stable ascending argsort would reverse ties and break
    lower-priority keys; instead descending keys become negated dense
    ranks (np.unique inverse), which lexsort ascends over correctly."""
    keys = []
    for oe, asc in reversed(order_by):
        v = oe if isinstance(oe, np.ndarray) \
            else oe.eval(env, np) if isinstance(oe, Expr) else env[oe]
        vals, nulls = _null_safe_key(np.asarray(v))
        if not asc:
            _, inv = np.unique(vals, return_inverse=True)
            vals = -inv.astype(np.int64)
        keys.append(vals)
        if nulls is not None:
            keys.append(nulls if asc else -nulls)
    return np.lexsort(keys)
