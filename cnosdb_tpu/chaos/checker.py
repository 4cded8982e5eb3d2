"""Consistency checks over a client history + post-recovery observations.

Every check is a pure function: history (and observed state) in, a
CheckResult out — no I/O, no clocks, so a failing verdict replays
identically from the recorded artifacts alone.

Key model (kept deliberately small so verdicts are airtight):
  - a row is identified by an opaque string key chosen by the workload
    (e.g. "h1:120000000000"); each key is written at most once and
    deleted at most once across the whole history (the workloads
    guarantee this), so "the write of key k" is unambiguous.
  - write/delete invokes carry {"keys": [...]}; read oks carry
    {"keys": [...]} (what the client actually saw).
  - an invoke with no outcome is ambiguous: its effects are allowed in
    the observed state but never required.

Checks:
  no-lost-acked-write    every acked write's keys survive to the final
                         observed state unless a delete targeted them
  no-resurrection        acked-deleted keys never reappear; nor do keys
                         no write (even an ambiguous one) ever produced
  read-your-writes       a session's read sees every key that session
                         acked-wrote earlier (minus delete targets)
  monotonic-reads        within a session, each read over the monotonic
                         probe space contains the previous one (minus
                         delete targets)
  matview-parity         view-rewrite rows == raw-scan rows, bit-exact
  checksum-convergence   all replicas report the same per-group checksum
"""
from __future__ import annotations

from dataclasses import dataclass

from . import note_verdict
from .history import History


@dataclass
class CheckResult:
    name: str
    ok: bool
    detail: str = ""

    def __bool__(self) -> bool:
        return self.ok


def _sample(keys, n: int = 5) -> str:
    ks = sorted(keys)
    extra = f" (+{len(ks) - n} more)" if len(ks) > n else ""
    return ", ".join(ks[:n]) + extra


def _delete_targets(history: History, before_e: int | None = None) -> set:
    """Keys any delete *attempted* (invoke, acked or not) — a key in this
    set may legitimately be absent later, whatever the delete's fate."""
    out: set = set()
    for op in history.by_op("delete"):
        if before_e is None or op.invoke_e < before_e:
            out.update(op.data.get("keys", ()))
    return out


def check_no_lost_acked_writes(history: History, observed: set,
                               before_ts: float | None = None) -> CheckResult:
    """`before_ts` (wall seconds) restricts the obligation to writes
    acked at-or-before that instant — the point-in-time-restore form: a
    restore to T (or to the archived watermark after total node loss)
    owes only the writes acked by then. The bound is conservative: an
    ok event's stamp lands *after* the durable append it acknowledges,
    so ok_ts ≤ watermark implies the write's entries are archived. An
    ok event with no stamp (older history format) stays required."""
    acked: set = set()
    for op in history.by_op("write"):
        if not op.acked:
            continue
        if before_ts is not None and op.outcome_ts is not None \
                and op.outcome_ts > before_ts:
            continue
        acked.update(op.data.get("keys", ()))
    lost = acked - observed - _delete_targets(history)
    return CheckResult(
        "no_lost_acked_writes", not lost,
        f"{len(lost)} acked keys missing after recovery: {_sample(lost)}"
        if lost else f"{len(acked)} acked keys all present")


def check_no_resurrection(history: History, observed: set) -> CheckResult:
    # every key any write may have produced — even a "fail"/ambiguous
    # write may have partially landed before its error surfaced, so rows
    # from it are not resurrections
    written: set = set()
    for op in history.by_op("write"):
        written.update(op.data.get("keys", ()))
    acked_deleted: set = set()
    for op in history.by_op("delete"):
        if op.acked:
            acked_deleted.update(op.data.get("keys", ()))
    undead = observed & acked_deleted
    from_nowhere = observed - written
    bad = undead | from_nowhere
    detail = []
    if undead:
        detail.append(f"{len(undead)} acked-deleted keys reappeared: "
                      f"{_sample(undead)}")
    if from_nowhere:
        detail.append(f"{len(from_nowhere)} keys observed that no write "
                      f"produced: {_sample(from_nowhere)}")
    return CheckResult("no_resurrection", not bad,
                       "; ".join(detail) or
                       f"{len(acked_deleted)} deleted keys stayed gone")


def check_read_your_writes(history: History) -> CheckResult:
    bad: list[str] = []
    for session in history.sessions():
        mine = [o for o in history.ops if o.session == session]
        for read in mine:
            if read.op != "read" or not read.acked:
                continue
            seen = set(read.ok_data.get("keys", ()))
            due: set = set()
            for w in mine:
                if w.op == "write" and w.acked \
                        and w.outcome_e < read.invoke_e:
                    due.update(w.data.get("keys", ()))
            missing = due - seen - _delete_targets(history, read.invoke_e)
            if missing:
                bad.append(f"session {session} read e={read.invoke_e} "
                           f"missed own acked keys {_sample(missing)}")
    return CheckResult("read_your_writes", not bad, "; ".join(bad[:3]))


def check_monotonic_reads(history: History) -> CheckResult:
    """Reads tagged mono=True in their invoke form each session's probe
    sequence; each must contain its predecessor (minus delete targets)."""
    bad: list[str] = []
    for session in history.sessions():
        prev: set | None = None
        prev_e = -1
        for read in history.ops:
            if read.session != session or read.op != "read" \
                    or not read.data.get("mono") or not read.acked:
                continue
            seen = set(read.ok_data.get("keys", ()))
            if prev is not None:
                gone = prev - seen - _delete_targets(history)
                if gone:
                    bad.append(f"session {session}: read e={read.invoke_e}"
                               f" lost keys seen at e={prev_e}: "
                               f"{_sample(gone)}")
            prev, prev_e = seen, read.invoke_e
    return CheckResult("monotonic_reads", not bad, "; ".join(bad[:3]))


def check_matview_parity(view_rows, scan_rows) -> CheckResult:
    a = sorted(map(repr, view_rows))
    b = sorted(map(repr, scan_rows))
    ok = a == b
    detail = "" if ok else (f"view={len(a)} rows, scan={len(b)} rows; "
                            f"first diff: "
                            f"{next((x for x, y in zip(a, b) if x != y), 'length')}")
    return CheckResult("matview_parity", ok, detail)


def check_checksum_convergence(per_node: dict) -> CheckResult:
    """per_node: node_id → {group_key → checksum}. All nodes holding a
    group must agree on its checksum (anti-entropy has converged)."""
    diverged = []
    groups: set = set()
    for sums in per_node.values():
        groups.update(sums)
    for g in sorted(groups):
        vals = {n: sums[g] for n, sums in per_node.items() if g in sums}
        if len(set(vals.values())) > 1:
            diverged.append(f"{g}: {vals}")
    return CheckResult("checksum_convergence", not diverged,
                       "; ".join(diverged[:3]) or
                       f"{len(groups)} groups converged")


def run_client_checks(history: History, observed: set,
                      before_ts: float | None = None) -> list[CheckResult]:
    """The four history-only invariants, in severity order. `before_ts`
    bounds the no-lost-acked-writes obligation for point-in-time
    restores (see check_no_lost_acked_writes)."""
    return [check_no_lost_acked_writes(history, observed, before_ts),
            check_no_resurrection(history, observed),
            check_read_your_writes(history),
            check_monotonic_reads(history)]


def book(results: list[CheckResult]) -> list[CheckResult]:
    """Fold verdicts into the chaos counters (→ /metrics); returns
    `results` unchanged for chaining."""
    for r in results:
        note_verdict(r.name, r.ok)
    return results
