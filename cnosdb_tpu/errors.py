"""Error taxonomy.

Mirrors the reference's per-crate error enums (e.g. tskv/src/error.rs,
meta/src/error.rs, query_server/spi/src/lib.rs QueryError) collapsed into a
single hierarchy with stable error codes, matching the numbered error-code
scheme the reference derives via derive_traits/error_code.
"""
from __future__ import annotations


class CnosError(Exception):
    """Base error. `code` is a stable string like the reference's 010001."""

    code = "000000"

    def __init__(self, message: str = "", **ctx):
        self.message = message
        self.ctx = ctx
        super().__init__(message)

    def __str__(self) -> str:  # pragma: no cover - repr sugar
        if self.ctx:
            kv = ", ".join(f"{k}={v!r}" for k, v in self.ctx.items())
            return f"[{self.code}] {self.message} ({kv})"
        return f"[{self.code}] {self.message}"


class ConfigError(CnosError):
    code = "010001"


class MetaError(CnosError):
    code = "020001"


class TenantNotFound(MetaError):
    code = "020002"


class DatabaseNotFound(MetaError):
    code = "020003"


class DatabaseAlreadyExists(MetaError):
    code = "020004"


class TableNotFound(MetaError):
    code = "020005"


class TableAlreadyExists(MetaError):
    code = "020006"


class BucketNotFound(MetaError):
    code = "020007"


class StorageError(CnosError):
    code = "030001"


class WalError(StorageError):
    code = "030002"


class TsmError(StorageError):
    code = "030003"


class ChecksumMismatch(StorageError):
    code = "030004"


class CodecError(StorageError):
    code = "030005"


class IndexError_(StorageError):
    code = "030006"


class SchemaError(CnosError):
    code = "040001"


class FieldTypeMismatch(SchemaError):
    code = "040002"


class ColumnNotFound(SchemaError):
    code = "040003"


class QueryError(CnosError):
    code = "050001"


class ParserError(QueryError):
    code = "050002"


class PlanError(QueryError):
    code = "050003"


class ExecutionError(QueryError):
    code = "050004"


class FunctionError(QueryError):
    code = "050005"


class CoordinatorError(CnosError):
    code = "060001"


class ReplicationError(CnosError):
    code = "070001"


class AuthError(CnosError):
    code = "080001"


class LimiterError(CnosError):
    """Per-tenant rate/quota budget exhausted. HTTP 429 + Retry-After:
    only THIS tenant needs to back off (contrast AdmissionRejected)."""

    code = "090001"

    def __init__(self, message: str = "", retry_after: float = 1.0, **ctx):
        super().__init__(message, **ctx)
        self.retry_after = retry_after


class DeadlineExceeded(CnosError):
    """Request ran past its deadline budget (header or config timeout).

    Deliberately NOT a QueryError subclass: retry/failover loops that
    swallow query- or RPC-level errors must not absorb it — once the
    budget is gone the only correct move is to unwind to the client
    (HTTP 504)."""

    code = "100001"


class AdmissionRejected(CnosError):
    """Shed by the per-node admission gate (queue full, or queue wait
    would outlive the request's own deadline). HTTP 503 + Retry-After —
    distinct from the per-tenant LimiterError 429."""

    code = "100002"

    def __init__(self, message: str = "", retry_after: float = 1.0, **ctx):
        super().__init__(message, **ctx)
        self.retry_after = retry_after


class MemoryExceeded(CnosError):
    """A single request outgrew its memory budget (per-query kill), or
    the node is above its hard memory watermark and must fail closed.

    Deliberately NOT a QueryError subclass, for the same reason as
    DeadlineExceeded: retry/failover loops must not absorb it — the
    request itself is the problem and retrying it elsewhere just moves
    the OOM. HTTP 413 (payload too large — the request, not the node,
    is oversized), so clients can tell it apart from the node-saturated
    503."""

    code = "100003"


class WriteBackpressure(AdmissionRejected):
    """Write shed by memory backpressure: the broker delayed the write
    waiting for flush progress, the delay budget ran out, and the node
    is still above its soft watermark. HTTP 503 + Retry-After (derived
    from flush progress) like its parent, with a code of its own so
    clients can tell a memory squeeze from an admission-queue
    overflow."""

    code = "100004"
