"""Compressed-domain execution: answer filters and aggregates from the
encoded page representation, decode only surviving rows.

The lane slots in AHEAD of the three decode lanes (device → native →
py_jobs) in storage/scan: after `_plan_series` proves a series' chunks are
row-aligned and merge-free ("n" entries), every admitted page is classified
per (page, query) against a `CompressedSpec` the executor derived from the
aggregate plan:

  skip    a conjunct is provably false for every row (or the conjunct
          column is absent ⇒ all-NULL ⇒ fails) — the page leaves the plan:
          never fetched, never decoded, zero rows.
  answer  every projected aggregate is computable without materializing
          the page's value rows:
            meta    pure PageMeta algebra — count from n_rows/n_values,
                    int sum/min/max from the exact page stats, the page's
                    time-bucket from min_ts/max_ts (page inside one
                    bucket).
            closed  a deferred job reads the page BYTES (block cache /
                    ranged GET) and applies a per-codec closed form on the
                    still-encoded stream: DELTA last = first + Σdeltas
                    (int64 wrap is associative ⇒ bit-identical to the
                    cumsum decode), constant-stride DELTA_TS answers
                    bucket boundaries arithmetically (no cumsum
                    materialization), GORILLA first/last via byte-plane
                    XOR algebra, BITPACK via the packed bits. Handlers
                    register per split-plan "kind" (codecs._CODEC_TABLE's
                    plans) — no fourth dispatch ladder.
          The page leaves the plan; its contribution rides the batch as a
          pre-aggregated partial sql/executor merges like matview
          partials.
  mask    a string/bool conjunct is mixed on the page but decidable in
          code space: the predicate is mapped onto the page DICTIONARY
          (once per page, PR 10 per-unique style) or the packed BOOLEAN
          bits (unpackbits fused into the mask AND — never widened to an
          int64 column), producing a row mask. The page still decodes,
          but only rows surviving every mask are gathered into the batch
          (late materialization) — assembly ANDs the mask into the trim
          gather.
  mat     anything unprovable materializes normally. Fallback is
          PER-PAGE, never per-query, and total: every bail books a
          (lane, reason) outcome (cnosdb_compressed_domain_total on
          /metrics; compressed.* stage counters carry per-query byte
          books). Enforced by the compressed-domain-accounting lint rule.

Answerability rules (why the table looks the way it does):
  count(*)            n_rows; count(col) = n_values — exact from meta.
  int/uint sum        page stat_sum is int(values.sum()) — same wrapping
                      int64/uint64 arithmetic as the kernel's np.add.at,
                      and integer addition is associative ⇒ bit-identical.
  int/uint min/max    exact page stats.
  float sum           DECLINED (float_assoc): fp addition is not
                      associative; a closed form cannot reproduce the
                      decode lane's reduction order bit-for-bit.
  float min/max       DECLINED (float_nan): the kernel propagates NaN,
                      page stats exclude it, and NaN presence is not
                      provable from metadata.
  bool/string aggs    DECLINED (bool_agg/string_agg): kernel dtype
                      semantics aren't reproducible from stats.
  first/last          closed forms per codec; need the companion
                      timestamp, answered from the time page (constant
                      stride arithmetically, else from the delta stream).
  predicates          interval tri-state on exact int stats (TRUE needs
                      no-NULLs: NULL fails every conjunct, matching the
                      kernel's 3VL mask); floats only ever prove
                      "!=" TRUE / everything-else FALSE (hidden NaN);
                      strings/bools go to the mask path.

`CNOSDB_COMPRESSED_DOMAIN=0` disables the lane (parity/oracle switch):
every query then takes the decode lanes, which this lane must match
bit-for-bit (tests/test_compressed_domain.py property suite).
"""
from __future__ import annotations

import os

import numpy as np

from ..models.codec import Encoding
from ..models.schema import ValueType
from ..utils import lockwatch, stages

__all__ = [
    "enabled", "count_outcome", "outcomes_snapshot", "build_spec",
    "CompressedSpec", "ScanLane", "register_closed",
]


def enabled() -> bool:
    return os.environ.get("CNOSDB_COMPRESSED_DOMAIN", "1").lower() \
        not in ("0", "off", "false")


# ---------------------------------------------------------------------------
# accounting — every lane outcome is booked (lint-enforced totality)
# ---------------------------------------------------------------------------
_OUTCOME_LOCK = lockwatch.Lock("compressed_domain.outcomes")
_OUTCOMES: dict[tuple[str, str], int] = {}


def count_outcome(lane: str, reason: str, n: int = 1) -> None:
    """Book one (lane, reason) outcome: lane ∈ {spec, skip, meta, closed,
    closed_decode, mask, mat}. Surfaced as
    cnosdb_compressed_domain_total{lane,reason} on /metrics."""
    with _OUTCOME_LOCK:
        _OUTCOMES[(lane, reason)] = _OUTCOMES.get((lane, reason), 0) + n


def outcomes_snapshot() -> dict[tuple[str, str], int]:
    with _OUTCOME_LOCK:
        return dict(sorted(_OUTCOMES.items()))


def _declined(reason: str):
    """Query-level decline: the whole query takes the decode lanes. The
    booked reason keeps 'why is the lane idle' answerable from /metrics."""
    count_outcome("spec", reason)
    return None


# ---------------------------------------------------------------------------
# query-level spec
# ---------------------------------------------------------------------------
_AGG_FUNCS = frozenset({"count", "sum", "min", "max", "first", "last"})
_NUM_OPS = frozenset({"=", "!=", "<", "<=", ">", ">=", "between", "in"})
_STR_OPS = frozenset({"str_eq", "str_ne", "str_in"})
_BOOL_OPS = frozenset({"bool_eq", "bool_ne"})
_INT_VTS = (ValueType.INTEGER, ValueType.UNSIGNED)


class CompressedSpec:
    """What one aggregate query asks of the lane: physical aggs, bucket
    geometry, and the FULL conjunction of its filter (build_spec declines
    unless the filter is exhaustively decomposable — an answered page must
    be provably all-true, which a partially-understood filter can't be)."""

    __slots__ = ("aggs", "bucket", "conjuncts", "col_types", "key")

    def __init__(self, aggs, bucket, conjuncts, col_types):
        self.aggs = aggs                # ((func, column|None, alias), ...)
        self.bucket = bucket            # (origin_ns, interval_ns) | None
        self.conjuncts = conjuncts      # {col: [(op, value), ...]}
        self.col_types = col_types      # {col: ValueType}
        self.key = repr((aggs, bucket,
                         sorted((c, [(op, repr(v)) for op, v in cons])
                                for c, cons in conjuncts.items())))


def _extract_conjuncts(filt, schema):
    """Decompose an AND-only filter tree into per-column conjuncts, or a
    decline reason string. Every reachable leaf must convert — unlike
    scan._page_constraints (where ignoring a conjunct is sound for
    pruning), answering a page requires understanding the WHOLE filter."""
    from ..sql.expr import Between, BinOp, Column, InList, Literal

    out: dict[str, list] = {}
    fields = set(schema.field_names())

    def numeric(v):
        return isinstance(v, (int, float, np.integer, np.floating)) \
            and not isinstance(v, bool)

    def colname(e):
        if not isinstance(e, Column):
            return None
        if e.name == "time":
            return "time"
        return e.name if e.name in fields else None

    def walk(e):
        if isinstance(e, BinOp) and e.op == "and":
            return walk(e.left) or walk(e.right)
        if isinstance(e, BinOp) and e.op in ("=", "!=", "<", "<=", ">", ">="):
            col = lit = op = None
            if isinstance(e.left, Column) and isinstance(e.right, Literal):
                col, lit, op = colname(e.left), e.right.value, e.op
            elif isinstance(e.right, Column) and isinstance(e.left, Literal):
                flip = {"<": ">", "<=": ">=", ">": "<", ">=": "<=",
                        "=": "=", "!=": "!="}
                col, lit, op = colname(e.right), e.left.value, flip[e.op]
            if col == "time":
                return "filter_time"
            if col is None:
                return "filter_col"
            if isinstance(lit, bool):
                if op not in ("=", "!="):
                    return "filter_shape"
                out.setdefault(col, []).append(
                    ("bool_eq" if op == "=" else "bool_ne", bool(lit)))
                return None
            if numeric(lit):
                out.setdefault(col, []).append((op, lit))
                return None
            if isinstance(lit, str):
                if op not in ("=", "!="):
                    return "filter_shape"
                out.setdefault(col, []).append(
                    ("str_eq" if op == "=" else "str_ne", lit))
                return None
            return "filter_shape"
        if isinstance(e, Between) and not e.negated \
                and isinstance(e.low, Literal) and isinstance(e.high, Literal):
            col = colname(e.expr)
            if col in (None, "time"):
                return "filter_time" if col == "time" else "filter_col"
            if not (numeric(e.low.value) and numeric(e.high.value)):
                return "filter_shape"
            out.setdefault(col, []).append(
                ("between", (e.low.value, e.high.value)))
            return None
        if isinstance(e, InList) and not e.negated and e.values:
            col = colname(e.expr)
            if col in (None, "time"):
                return "filter_time" if col == "time" else "filter_col"
            if all(numeric(v) for v in e.values):
                out.setdefault(col, []).append(("in", list(e.values)))
                return None
            if all(isinstance(v, str) for v in e.values):
                out.setdefault(col, []).append(("str_in", tuple(e.values)))
                return None
            return "filter_shape"
        return "filter_shape"

    why = walk(filt)
    return (None, why) if why else (out, None)


def build_spec(plan, phys_aggs):
    """AggregatePlan + decomposed physical aggs → CompressedSpec, or None
    (reason booked) when the query can't engage the lane at all. The
    gates here are QUERY-level; pages still fall back individually."""
    if not enabled():
        return _declined("disabled")
    if plan.group_fields:
        # field group keys need per-row values — nothing to answer
        return _declined("group_fields")
    funcs = {a.func for a in phys_aggs}
    if not funcs <= _AGG_FUNCS:
        return _declined("agg_func")
    if any(a.column == "time" for a in phys_aggs):
        # min(time)/max(time) aggregate the time axis, not a field page;
        # the decode lane owns that path
        return _declined("time_agg")
    schema = plan.schema
    conjuncts: dict[str, list] = {}
    if plan.filter is not None:
        conjuncts, why = _extract_conjuncts(plan.filter, schema)
        if conjuncts is None:
            return _declined(why)
    col_types: dict[str, ValueType] = {}
    for name in ({a.column for a in phys_aggs if a.column}
                 | set(conjuncts)):
        try:
            col_types[name] = schema.column(name).column_type.value_type
        except Exception:
            return _declined("schema")
    aggs = tuple((a.func, a.column, a.alias) for a in phys_aggs)
    return CompressedSpec(aggs, plan.bucket, conjuncts, col_types)


# ---------------------------------------------------------------------------
# per-codec closed forms, registered against codecs.split_for_device plans
# ---------------------------------------------------------------------------
def _widen(width, raw):
    from . import codecs

    return codecs._widen(width, raw)


def _delta_stream(plan):
    from . import codecs

    return codecs.unzigzag(_widen(plan["width"], plan["raw"]))[:plan["n"] - 1]


def _delta_first(plan):
    return np.int64(plan["first"])


def _delta_last(plan):
    # int64 addition wraps associatively: first + Σdeltas is bit-identical
    # to the decode lane's cumsum final element
    if plan["n"] == 1:
        return np.int64(plan["first"])
    return np.int64(plan["first"]) + _delta_stream(plan).sum()


def _delta_const_first(plan):
    return np.int64(plan["first"])


def _delta_const_last(plan):
    return np.int64(plan["first"] + plan["stride"] * (plan["n"] - 1))


def _gorilla_planes(plan):
    return np.frombuffer(plan["raw"], dtype=np.uint8).reshape(8, plan["n"])


def _gorilla_first(plan):
    b = np.ascontiguousarray(_gorilla_planes(plan)[:, 0])
    return np.frombuffer(b.tobytes(), dtype="<f8")[0]


def _gorilla_last(plan):
    # value k is the XOR-prefix of each byte plane; the last value is the
    # whole-plane XOR reduction — no scan materialized
    b = np.bitwise_xor.reduce(_gorilla_planes(plan), axis=1)
    return np.frombuffer(np.ascontiguousarray(b).tobytes(), dtype="<f8")[0]


def _bitpack_bits(plan):
    return np.unpackbits(np.frombuffer(plan["raw"], dtype=np.uint8),
                         count=plan["n"])


def _bitpack_first(plan):
    return np.bool_(_bitpack_bits(plan)[0])


def _bitpack_last(plan):
    return np.bool_(_bitpack_bits(plan)[-1])


_CLOSED: dict[str, tuple] = {}


def register_closed(kind: str, first_fn, last_fn) -> None:
    """Register first/last closed forms for one split-plan kind — new
    codecs extend the lane here, not with another if/elif chain."""
    _CLOSED[kind] = (first_fn, last_fn)


register_closed("delta", _delta_first, _delta_last)
register_closed("delta_const", _delta_const_first, _delta_const_last)
register_closed("gorilla", _gorilla_first, _gorilla_last)
register_closed("bitpack", _bitpack_first, _bitpack_last)


def _time_value_at(tplan, k: int) -> int:
    """Timestamp at row k from a still-encoded time plan (prefix-sum
    algebra — Σ of a delta slice, never a cumsum array)."""
    if tplan["kind"] == "delta_const":
        return int(tplan["first"] + tplan["stride"] * k)
    if k == 0:
        return int(tplan["first"])
    return int(np.int64(tplan["first"]) + _delta_stream(tplan)[:k].sum())


# ---------------------------------------------------------------------------
# page-level predicate tri-state
# ---------------------------------------------------------------------------
_TRUE, _FALSE, _MIXED = 1, 0, -1


def _interval_verdict(op, val, lo, hi, is_float: bool) -> int:
    """Tri-state over the page's exact non-null interval [lo, hi]. For
    floats a hidden NaN row fails every comparison except '!=', so TRUE
    is only provable for '!=' and FALSE never is for '!='."""
    if op == ">":
        if not is_float and lo > val:
            return _TRUE
        return _FALSE if hi <= val else _MIXED
    if op == ">=":
        if not is_float and lo >= val:
            return _TRUE
        return _FALSE if hi < val else _MIXED
    if op == "<":
        if not is_float and hi < val:
            return _TRUE
        return _FALSE if lo >= val else _MIXED
    if op == "<=":
        if not is_float and hi <= val:
            return _TRUE
        return _FALSE if lo > val else _MIXED
    if op == "=":
        if val < lo or val > hi:
            return _FALSE
        if not is_float and lo == hi == val:
            return _TRUE
        return _MIXED
    if op == "!=":
        if val < lo or val > hi:
            return _TRUE
        if not is_float and lo == hi == val:
            return _FALSE
        return _MIXED
    if op == "between":
        blo, bhi = val
        if bhi < lo or blo > hi:
            return _FALSE
        if not is_float and lo >= blo and hi <= bhi:
            return _TRUE
        return _MIXED
    if op == "in":
        if all(v < lo or v > hi for v in val):
            return _FALSE
        if not is_float and lo == hi and any(v == lo for v in val):
            return _TRUE
        return _MIXED
    return _MIXED


def _fold_partial(parts: dict, func: str, alias: str, value,
                  ts: int | None = None) -> None:
    """Merge one page's contribution — same semantics as the executor's
    _merge_partial, so lane partials and kernel partials interleave
    bit-identically in any order."""
    cur = parts.get(alias)
    if func == "count":
        parts[alias] = (cur or 0) + int(value)
    elif func == "sum":
        parts[alias] = value if cur is None else cur + value
    elif func == "min":
        parts[alias] = value if cur is None else min(cur, value)
    elif func == "max":
        parts[alias] = value if cur is None else max(cur, value)
    else:   # first / last
        cur_ts = parts.get(alias + "__ts")
        better = (cur is None or cur_ts is None
                  or (func == "first" and ts < cur_ts)
                  or (func == "last" and ts > cur_ts))
        if better:
            parts[alias] = value
            parts[alias + "__ts"] = ts


_NP_STAT = {ValueType.INTEGER: np.int64, ValueType.UNSIGNED: np.uint64}


def _stat_value(vt: ValueType, v):
    # numpy-typed so executor-side merges (cur + v, min/max) run the same
    # wrapping int64/uint64 arithmetic as the kernel partials
    return _NP_STAT[vt](v)


_DELTA_ENCODINGS = (int(Encoding.DELTA), int(Encoding.DELTA_TS))


class ScanLane:
    """Per-(vnode scan, query) lane state: classify pages out of the
    native plan, collect meta partials, run deferred closed-form jobs
    after the cold prefetch, and build survivor row masks."""

    def __init__(self, spec: CompressedSpec, trs, index):
        self.spec = spec
        self.trs = trs
        self.index = index
        self.partials: dict[tuple, dict] = {}   # (sid, bts|None) → parts
        self.series_keys: dict[int, object] = {}
        self.jobs: list[tuple] = []             # (sid, r, tp, [(pm, aggs)], bts, straddle)
        self.mask_pages: dict[tuple, list] = {}  # (id(cm), i) → builders
        self._mask_keep: dict[int, object] = {}  # keep cm refs alive for id()
        self.row_mask: np.ndarray | None = None
        self.pages_answered = 0
        self.pages_skipped = 0
        self.pages_masked = 0
        self.bytes_materialized = 0   # job page bytes the lane DID read

    # -- plan filtering ---------------------------------------------------
    @property
    def engaged(self) -> bool:
        return bool(self.pages_answered or self.pages_skipped
                    or self.pages_masked)

    @property
    def has_masks(self) -> bool:
        return bool(self.mask_pages)

    def filter_pages(self, inside: np.ndarray, page_at) -> np.ndarray:
        """Classify the scan's planned pages → bool over them, False
        where a page leaves the plan (skipped or answered). `inside[k]`:
        page k lies inside one of the query's time ranges; `page_at(k)`
        → (sid, reader, ChunkMeta, {query column: ColumnMeta}, the
        page's position in the chunk)."""
        keep = np.ones(len(inside), dtype=bool)
        # rows outside the query's time ranges can't be answered away:
        # the page must materialize so assembly's trim drops them
        n_trim = len(inside) - int(np.count_nonzero(inside))
        if n_trim:
            count_outcome("mat", "trim", n_trim)
        for k in np.flatnonzero(inside).tolist():
            keep[k] = not self._classify(*page_at(k))
        return keep

    def _classify(self, sid, r, cm, cols, i) -> int:
        """Classify page i of a chunk, one inside a time range → the
        rows it takes out of the plan (0: it must materialize)."""
        spec = self.spec
        tp = cm.time_pages[i]

        def _mat(reason):
            count_outcome("mat", reason)
            return 0

        # ---- predicate tri-state over the full conjunction
        verdict = _TRUE
        mask_builders = []
        for colname, cons in spec.conjuncts.items():
            colmeta = cols.get(colname)
            if colmeta is None:
                # column absent from the chunk ⇒ all rows NULL ⇒ every
                # conjunct on it fails ⇒ no row of the page survives
                count_outcome("skip", "null_column")
                self.pages_skipped += 1
                return tp.n_rows
            pm = colmeta.pages[i]
            evt = spec.col_types[colname]
            if pm.value_type != int(evt):
                return _mat("schema_change")
            v = self._conjunct_verdict(r, pm, evt, cons, mask_builders)
            if v == _FALSE:
                count_outcome("skip", "pred_false")
                self.pages_skipped += 1
                return tp.n_rows
            if v == _MIXED:
                verdict = _MIXED

        if verdict == _MIXED:
            if mask_builders and len(mask_builders) == sum(
                    1 for colname, cons in spec.conjuncts.items()
                    if self._col_mixed(r, cols, i, colname, cons)):
                # every mixed conjunct is maskable in code space: the page
                # materializes but only surviving rows are gathered
                count_outcome("mask", "code_space")
                self.pages_masked += 1
                self._mask_keep[id(cm)] = cm
                self.mask_pages.setdefault((id(cm), i), []).extend(
                    mask_builders)
                return 0
            return _mat("pred_mixed")

        # ---- all conjuncts TRUE: try to answer every aggregate
        return self._answer(sid, r, cm, cols, i, tp, _mat)

    def _col_mixed(self, r, cols, i, colname, cons) -> bool:
        pm = cols[colname].pages[i]
        evt = self.spec.col_types[colname]
        return self._conjunct_verdict(r, pm, evt, cons, []) == _MIXED

    def _conjunct_verdict(self, r, pm, evt: ValueType, cons,
                          mask_builders: list) -> int:
        """Tri-state for ALL of one column's conjuncts on one page; mixed
        string/bool conjuncts append a deferred mask builder."""
        verdict = _TRUE
        no_nulls = pm.n_values == pm.n_rows
        is_float = evt == ValueType.FLOAT
        legacy_float = is_float and getattr(pm, "stats_version", 0) < 1
        maskable_ops = []
        for op, val in cons:
            if op in _NUM_OPS:
                if pm.stat_min is None or pm.stat_max is None:
                    if pm.n_values == 0:
                        # all-NULL page: every comparison fails
                        return _FALSE
                    verdict = _MIXED
                    continue
                if legacy_float:
                    # finite-only stats may omit ±inf rows: no verdict
                    verdict = _MIXED
                    continue
                if evt == ValueType.BOOLEAN:
                    verdict = _MIXED
                    continue
                v = _interval_verdict(op, val, pm.stat_min, pm.stat_max,
                                      is_float)
                if v == _FALSE:
                    return _FALSE
                if v == _TRUE and not no_nulls:
                    v = _MIXED   # NULL rows fail the conjunct
                if v == _MIXED:
                    verdict = _MIXED
            elif op in _BOOL_OPS:
                if evt != ValueType.BOOLEAN:
                    verdict = _MIXED   # planner type confusion: no verdict
                    continue
                if pm.n_values == 0:
                    return _FALSE
                maskable = pm.encoding == int(Encoding.BITPACK)
                if pm.stat_min is None:
                    verdict = _MIXED
                    if maskable:
                        maskable_ops.append((op, val))
                    continue
                want = val if op == "bool_eq" else (not val)
                if bool(pm.stat_min) == bool(pm.stat_max):
                    if bool(pm.stat_min) != want:
                        return _FALSE
                    if no_nulls:
                        continue   # TRUE for this conjunct
                verdict = _MIXED
                if maskable:
                    maskable_ops.append((op, val))
            elif op in _STR_OPS:
                if evt not in (ValueType.STRING, ValueType.GEOMETRY):
                    verdict = _MIXED
                    continue
                if pm.n_values == 0:
                    return _FALSE
                # decided in code space after the cold prefetch: the
                # dictionary lives in the page bytes
                verdict = _MIXED
                maskable_ops.append((op, val))
            else:
                verdict = _MIXED
        if verdict == _MIXED and maskable_ops:
            mask_builders.append((r, pm, evt, tuple(maskable_ops)))
        return verdict

    # -- aggregate answering ---------------------------------------------
    def _answer(self, sid, r, cm, cols, i, tp, _mat) -> int:
        spec = self.spec
        straddle = False
        bts = None
        if spec.bucket is not None:
            origin, interval = spec.bucket
            blo = (tp.min_ts - origin) // interval
            bhi = (tp.max_ts - origin) // interval
            straddle = blo != bhi
            bts = int(origin + blo * interval)

        meta_parts: list[tuple] = []    # (func, alias, value)
        job_aggs: list[tuple] = []      # (func, col, alias, pm, evt)
        count_aliases: list[str] = []   # straddle counts (per-bucket job)
        for func, col, alias in spec.aggs:
            colmeta = cols.get(col) if col is not None else None
            pm = colmeta.pages[i] if colmeta is not None else None
            evt = spec.col_types.get(col) if col is not None else None
            if pm is not None and pm.value_type != int(evt):
                return _mat("schema_change")
            if func == "count":
                n = tp.n_rows if col is None else \
                    (pm.n_values if pm is not None else 0)
                if not straddle:
                    meta_parts.append((func, alias, n))
                elif col is None or (pm is not None
                                     and pm.n_values == pm.n_rows):
                    # no NULLs ⇒ per-bucket count(col) == per-bucket rows
                    count_aliases.append(alias)
                elif pm is None:
                    pass   # absent column: counts 0 into every bucket
                else:
                    return _mat("bucket_straddle")
                continue
            if straddle:
                return _mat("bucket_straddle")
            if colmeta is None or pm.n_values == 0:
                continue   # no values: no contribution (kernel: invalid)
            if func in ("sum", "min", "max"):
                if evt == ValueType.FLOAT:
                    return _mat("float_assoc" if func == "sum"
                                else "float_nan")
                if evt not in _INT_VTS:
                    return _mat("bool_agg" if evt == ValueType.BOOLEAN
                                else "string_agg")
                stat = {"sum": pm.stat_sum, "min": pm.stat_min,
                        "max": pm.stat_max}[func]
                if stat is None:
                    return _mat("no_stats")
                meta_parts.append((func, alias, _stat_value(evt, stat)))
                continue
            # first / last: per-codec closed form over the page bytes
            if evt in _INT_VTS:
                if pm.encoding not in _DELTA_ENCODINGS:
                    return _mat("encoding")
            elif evt == ValueType.FLOAT:
                if pm.encoding != int(Encoding.GORILLA):
                    return _mat("encoding")
            elif evt == ValueType.BOOLEAN:
                if pm.encoding != int(Encoding.BITPACK):
                    return _mat("encoding")
            else:
                return _mat("string_agg")
            if tp.encoding not in _DELTA_ENCODINGS:
                return _mat("encoding")
            job_aggs.append((func, col, alias, pm, evt))
        if count_aliases and tp.encoding not in _DELTA_ENCODINGS:
            return _mat("encoding")

        # answered: remove the page from the plan, book its contribution
        self.pages_answered += 1
        self.series_keys.setdefault(sid, self.index.get_series_key(sid))
        key = (sid, bts)
        parts = self.partials.setdefault(key, {})
        for func, alias, value in meta_parts:
            _fold_partial(parts, func, alias, value)
        if not job_aggs and not count_aliases:
            count_outcome("meta", "stats")
        if job_aggs or count_aliases:
            self.jobs.append((sid, r, tp,
                              tuple(job_aggs), tuple(count_aliases), bts))
        return tp.n_rows

    # -- deferred jobs ----------------------------------------------------
    def extend_cold_wants(self, cold_wants: dict) -> None:
        """Add the page bytes the closed-form jobs will read to the cold
        prefetch, so they ride the same coalesced ranged GETs."""
        for _sid, r, tp, job_aggs, count_aliases, _bts in self.jobs:
            if not getattr(r, "is_cold", False):
                continue
            lst = cold_wants.setdefault(id(r), (r, []))[1]
            lst.append(tp)
            for _f, _c, _a, pm, _t in job_aggs:
                lst.append(pm)

    def run_jobs(self) -> None:
        from . import codecs

        tplan_cache: dict[tuple, dict | None] = {}
        for sid, r, tp, job_aggs, count_aliases, bts in self.jobs:
            tkey = (id(r), tp.offset)
            if tkey not in tplan_cache:
                self.bytes_materialized += tp.size
                tplan, why = codecs.split_for_device(
                    r._read_page(tp), ValueType.INTEGER)
                if tplan is None:
                    count_outcome("closed_decode", "time_" + why)
                tplan_cache[tkey] = tplan
            tplan = tplan_cache[tkey]
            if count_aliases:
                self._job_bucket_counts(r, tp, tplan, sid, count_aliases)
            for func, _col, alias, pm, evt in job_aggs:
                self._job_first_last(r, tp, tplan, pm, evt, func, alias,
                                     (sid, bts))

    def _bucket_counts(self, tplan, tp) -> tuple[np.ndarray, int] | None:
        """Per-bucket row counts for a straddling time page, straight
        from the encoded stream. → (counts, first_bucket) or None."""
        origin, interval = self.spec.bucket
        blo = (tp.min_ts - origin) // interval
        bhi = (tp.max_ts - origin) // interval
        n = tplan["n"]
        if tplan["kind"] == "delta_const" and tplan["stride"] > 0:
            first, stride = tplan["first"], tplan["stride"]
            # row k lands in bucket (first + k*stride - origin) // interval;
            # bucket boundaries are solved arithmetically — no cumsum
            edges = origin + np.arange(blo + 1, bhi + 1,
                                       dtype=np.int64) * interval
            ks = -((first - edges) // stride)    # ceil((edge-first)/stride)
            ks = np.clip(ks, 0, n)
            bounds = np.concatenate(([0], ks, [n]))
            return np.diff(bounds), int(blo)
        if tplan["kind"] == "delta":
            # non-constant stride: one cumsum of the already-decompressed
            # delta stream (the page bytes were read anyway)
            count_outcome("closed_decode", "delta_cumsum")
            ts = np.empty(n, dtype=np.int64)
            ts[0] = tplan["first"]
            if n > 1:
                np.cumsum(_delta_stream(tplan), out=ts[1:])
                ts[1:] += np.int64(tplan["first"])
            buckets = (ts - origin) // interval
            counts = np.bincount((buckets - blo).astype(np.int64),
                                 minlength=int(bhi - blo + 1))
            return counts, int(blo)
        return None

    def _job_bucket_counts(self, r, tp, tplan, sid, aliases) -> None:
        origin, interval = self.spec.bucket
        if tplan is not None:
            got = self._bucket_counts(tplan, tp)
        else:
            got = None
        if got is None:
            count_outcome("closed_decode", "time_decode")
            ts = r.read_time_page(tp)
            blo = (tp.min_ts - origin) // interval
            buckets = (ts - origin) // interval
            counts = np.bincount((buckets - blo).astype(np.int64))
            got = counts, int(blo)
        else:
            count_outcome("closed", "bucket_arith")
        counts, blo = got
        self.series_keys.setdefault(sid, self.index.get_series_key(sid))
        for j, c in enumerate(counts.tolist()):
            if c == 0:
                continue
            bts = int(origin + (blo + j) * interval)
            parts = self.partials.setdefault((sid, bts), {})
            for alias in aliases:
                _fold_partial(parts, "count", alias, c)

    def _job_first_last(self, r, tp, tplan, pm, evt, func, alias,
                        key) -> None:
        from . import codecs

        self.bytes_materialized += pm.size
        block, nm = r.read_field_page_split(pm)
        plan, why = codecs.split_for_device(block, evt)
        handlers = _CLOSED.get(plan["kind"]) if plan is not None else None
        if handlers is None:
            # exact decode-compute fallback (first/last are order
            # lookups — no float reduction, so still bit-identical)
            count_outcome("closed_decode", why or "kind")
            dense, nm2 = r.read_field_page(pm)
            if len(dense) == 0:
                return
            value = dense[0] if func == "first" else dense[-1]
            nm = nm2
        else:
            count_outcome("closed", plan["kind"])
            value = handlers[0 if func == "first" else 1](plan)
            if evt == ValueType.UNSIGNED:
                # delta closed forms run in wrapping int64 (like the
                # decode lane), which then VIEWS the result as uint64
                value = np.uint64(int(value) & 0xFFFFFFFFFFFFFFFF)
        if nm is None:
            row = 0 if func == "first" else pm.n_rows - 1
        else:
            nn = np.flatnonzero(~nm)
            if len(nn) == 0:
                return
            row = int(nn[0] if func == "first" else nn[-1])
        if tplan is not None and tplan["kind"] in ("delta", "delta_const"):
            ts = _time_value_at(tplan, row)
        else:
            ts = int(r.read_time_page(tp)[row])
        parts = self.partials.setdefault(key, {})
        _fold_partial(parts, func, alias, value, ts)

    # -- survivor row masks ----------------------------------------------
    def apply_page_masks(self, cm, i, off: int, total: int) -> None:
        builders = self.mask_pages.get((id(cm), i))
        if not builders:
            return
        if self.row_mask is None:
            self.row_mask = np.ones(total, dtype=bool)
        for (r, pm, evt, ops) in builders:
            m = self._page_row_mask(r, pm, evt, ops)
            if m is not None:
                self.row_mask[off:off + pm.n_rows] &= m

    def _page_row_mask(self, r, pm, evt, ops) -> np.ndarray | None:
        """Row survivor mask from the encoded page, or None (reason
        booked) — a None mask keeps every row, which is always sound
        because the executor re-applies the full filter."""
        from . import codecs

        try:
            block, nm = r.read_field_page_split(pm)
            plan, why = codecs.split_for_device(block, evt)
        except Exception:
            count_outcome("mask", "read_error")
            return None
        if plan is None:
            count_outcome("mat" if why == "string_v1" else "mask", why)
            return None
        if plan["kind"] == "bitpack":
            bits = _bitpack_bits(plan).astype(bool)
            dense = np.ones(plan["n"], dtype=bool)
            for op, val in ops:
                want = val if op == "bool_eq" else (not val)
                dense &= bits if want else ~bits
        elif plan["kind"] == "dict":
            uniq = plan["values"]
            lut = np.ones(len(uniq), dtype=bool)
            for op, val in ops:
                if op == "str_eq":
                    lut &= np.array([u == val for u in uniq], dtype=bool)
                elif op == "str_ne":
                    lut &= np.array([u != val for u in uniq], dtype=bool)
                else:   # str_in
                    vals = set(val)
                    lut &= np.array([u in vals for u in uniq], dtype=bool)
            codes = _widen(plan["width"], plan["raw"])[:plan["n"]]
            dense = lut[codes.astype(np.int64)]
        else:
            count_outcome("mask", "kind")
            return None
        if nm is None:
            return dense
        rows = np.zeros(pm.n_rows, dtype=bool)
        rows[~nm] = dense   # NULL rows fail the conjunct (kernel 3VL)
        return rows

    # -- batch attachment -------------------------------------------------
    def attach(self, batch) -> None:
        """Hang the lane's results + books on the finished ScanBatch."""
        if self.partials:
            batch.compressed_partials = {
                "rows": self.partials,
                "series_keys": self.series_keys,
                "aggs": self.spec.aggs,
            }
        batch._compressed_engaged = self.engaged
        if self.pages_answered:
            stages.count("compressed.pages_answered", self.pages_answered)
