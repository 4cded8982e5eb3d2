"""Vectorized string/search plane (ROADMAP item 4).

String predicates on dictionary-encoded columns (`models.strcol.DictArray`)
are evaluated once per UNIQUE value and broadcast to rows through the
codes — the encoded-data evaluation argument of "GPU Acceleration of SQL
Analytics on Compressed Data" (PAPERS.md) applied to strings. Three lanes,
all reason-booked into ``cnosdb_string_filter_total{path,reason}``:

``per_unique``
    A LIKE pattern is compiled into one of five predicate classes —
    ``exact`` / ``prefix`` / ``suffix`` / ``contains`` (vectorized
    ``np.char`` kernels over the unique table) or ``regex`` (the host
    regex once per unique) — producing a boolean mask over the
    dictionary that a single integer gather (``mask[codes]``, or
    ``ops.kernels.dict_mask_gather`` when the codes live on device)
    turns into the row mask.  ``cmp`` is the same trick for comparison
    predicates over str-func chains (substr-equality et al), driven from
    ``sql.expr``.

``ngram_skip``
    Per-page trigram bloom signatures (built by ``storage.tsm`` at
    flush/compaction time, checked by ``storage.scan._page_admits``)
    prune whole string pages before decode for ``LIKE '%x%'``-shaped
    filters.  Format: byte trigrams over the UTF-8 encoding of each
    distinct page value, inserted into ``utils.bloom.BloomFilter`` sized
    at 16 bits/trigram (pow2-rounded, capped at 8 KiB per page); an
    empty signature means the page provably holds no 3-byte substring.

``host_fallback``
    The per-row host evaluator ran; the reason names why the per-unique
    lane could not (``unencoded_rows``, ``dynamic_pattern``,
    ``non_string_uniques``, ``lane_disabled``).

The module also hosts the select-then-gather top-K used by
``executor._order_limit`` (ORDER BY <key> LIMIT k): a k-th order
statistic (``np.partition`` on host, ``jax.lax.top_k`` on TPU) selects
candidate rows, which are then ordered with exactly the stable-lexsort
tie semantics of the full sort.

Accounting invariant (enforced by the ``string-filter-accounting`` lint
rule): every early return out of the lane books a path/reason — silent
per-row fallbacks are the regression this plane exists to remove.
"""
from __future__ import annotations

import functools
import os
import re
import threading

import numpy as np

from ..utils import stages
from ..utils.bloom import BloomFilter

# ---------------------------------------------------------------------------
# outcome accounting (mirrors ops.device_decode)
# ---------------------------------------------------------------------------
_LOCK = threading.Lock()
_outcomes: dict[tuple[str, str], int] = {}


def enabled() -> bool:
    """CNOSDB_STR_LANE=0 routes LIKE back to the per-unique regex path
    (the pre-plane behavior) — the parity-oracle knob."""
    return os.environ.get("CNOSDB_STR_LANE", "1").lower() \
        not in ("0", "off", "false")


def note_path(path: str, reason: str, n: int = 1) -> None:
    """Book n predicate evaluations as handled by `path` for `reason` —
    the raw series behind cnosdb_string_filter_total."""
    with _LOCK:
        _outcomes[(path, reason)] = _outcomes.get((path, reason), 0) + n
    stages.count(f"string_path.{path}", n)


def outcomes_snapshot() -> dict[tuple[str, str], int]:
    with _LOCK:
        return dict(sorted(_outcomes.items()))


# ---------------------------------------------------------------------------
# LIKE compilation
# ---------------------------------------------------------------------------
def compile_like(pattern: str):
    """The host LIKE automaton (sql.expr.Like._compile, pinned bit-for-bit
    by tests/test_strkernels.py): % → .*, _ → ., everything else literal,
    DOTALL-anchored — note `$` also accepts a trailing newline, which the
    vectorized classes below must (and do) reproduce."""
    out = []
    for ch in pattern:
        if ch == "%":
            out.append(".*")
        elif ch == "_":
            out.append(".")
        else:
            out.append(re.escape(ch))
    return re.compile("^" + "".join(out) + "$", re.DOTALL)


def classify(pattern: str) -> tuple[str, str | None]:
    """→ (kind, needle): 'exact'/'prefix'/'suffix'/'contains' with the
    wildcard-free needle, or ('generic', None) for anything with `_` or
    an interior `%` (those take the per-unique regex lane)."""
    if "_" in pattern:
        return "generic", None
    a = 0
    while a < len(pattern) and pattern[a] == "%":
        a += 1
    core = pattern[a:]
    b = 0
    while core and core[-1] == "%":
        core = core[:-1]
        b += 1
    if "%" in core:
        return "generic", None
    if a and b:
        return "contains", core
    if a:
        return "suffix", core
    if b:
        return "prefix", core
    return "exact", core


def _all_str(values: np.ndarray) -> bool:
    return all(isinstance(x, str) for x in values.tolist())


def unique_mask(values: np.ndarray, pattern: str,
                rx=None) -> tuple[np.ndarray, str]:
    """Boolean LIKE mask over a dictionary's unique table → (mask, reason).

    Vectorized np.char kernels for the four literal classes; the host
    regex once per unique otherwise.  Bit-identical to the host
    evaluator, including its `$`-accepts-trailing-newline quirk (an
    exact/suffix needle also matches `needle + "\\n"`)."""
    kind, needle = classify(pattern)
    if kind != "generic" and _all_str(values):
        u = np.asarray(values, dtype=str)
        if kind == "exact":
            mask = (u == needle) | (u == needle + "\n")
        elif kind == "prefix":
            mask = np.char.startswith(u, needle)
        elif kind == "suffix":
            mask = np.char.endswith(u, needle) \
                | np.char.endswith(u, needle + "\n")
        else:   # contains
            mask = np.char.find(u, needle) >= 0
        note_path("per_unique", kind)
        return mask, kind
    if rx is None:
        rx = compile_like(pattern)
    mask = np.fromiter(
        (bool(rx.match(x)) if isinstance(x, str) else False for x in values),
        dtype=bool, count=len(values))
    reason = "regex" if kind == "generic" else "non_string_uniques"
    note_path("per_unique", reason)
    return mask, reason


def broadcast_codes(mask: np.ndarray, codes) -> np.ndarray:
    """Per-unique mask → row mask. Host codes take the numpy gather;
    device-resident codes stay on device via ops.kernels."""
    if isinstance(codes, np.ndarray):
        return mask[codes]
    from . import kernels

    return kernels.dict_mask_gather(mask, codes)


def like_rows(da, pattern: str, rx=None, negated: bool = False) -> np.ndarray:
    """Row mask for ``da LIKE pattern`` over a DictArray (sql.expr.Like's
    dictionary routing target). Negation applies to the unique mask — it
    commutes with the gather."""
    mask, _reason = unique_mask(da.values, pattern, rx)
    if negated:
        mask = ~mask
    return broadcast_codes(mask, da.codes)


def unique_surrogate(da):
    """A one-row-per-unique twin of `da`: evaluating any scalar expr tree
    against it yields per-unique results to gather through `da.codes` —
    how substr-equality and friends ride the per-unique lane without
    reimplementing host scalar semantics."""
    from ..models.strcol import DictArray

    return DictArray(np.arange(len(da.values), dtype=np.int32), da.values)


# ---------------------------------------------------------------------------
# trigram page-skip signatures
# ---------------------------------------------------------------------------
NGRAM = 3
_MAX_QUERY_TRIGRAMS = 32          # probes per page check (subset = sound)
_SIG_MIN_BITS = 1 << 10
_SIG_MAX_BITS = 1 << 16           # 8 KiB/page ceiling
_BITS_PER_TRIGRAM = 16            # fp ≈ 0.2% at k=4


def _trigrams(b: bytes) -> set[bytes]:
    return {b[i:i + NGRAM] for i in range(len(b) - (NGRAM - 1))}


def literal_runs(pattern: str) -> list[str]:
    """Wildcard-free literal substrings any match must contain, in order
    (`%` and `_` both break runs — `_` matches one arbitrary char, so
    trigrams across it are not required)."""
    runs: list[str] = []
    cur: list[str] = []
    for ch in pattern:
        if ch in ("%", "_"):
            if cur:
                runs.append("".join(cur))
                cur = []
        else:
            cur.append(ch)
    if cur:
        runs.append("".join(cur))
    return runs


@functools.lru_cache(maxsize=512)
def value_trigrams(s: str) -> tuple[bytes, ...]:
    """Required trigrams for string EQUALITY with `s` (no wildcard
    semantics — a literal '%' in s is just a byte). Memoized per
    literal: the page-admit pass re-renders the same needle for every
    page of every vnode it probes."""
    tris = _trigrams(s.encode("utf-8", "surrogatepass"))
    return tuple(sorted(tris)[:_MAX_QUERY_TRIGRAMS])


@functools.lru_cache(maxsize=512)
def required_trigrams(pattern: str) -> tuple[bytes, ...] | None:
    """Byte trigrams (over UTF-8) every LIKE match must contain, or None
    when the pattern has no ≥3-byte literal run (unusable for skipping).
    Capped at _MAX_QUERY_TRIGRAMS probes — a subset only admits more."""
    tris: set[bytes] = set()
    for run in literal_runs(pattern):
        tris |= _trigrams(run.encode("utf-8", "surrogatepass"))
    if not tris:
        return None
    return tuple(sorted(tris)[:_MAX_QUERY_TRIGRAMS])


def build_page_signature(uniques) -> bytes:
    """Bloom signature over the byte trigrams of every distinct value in
    a string page. b'' ⇒ the page provably contains no 3-byte substring
    (short strings / all-null) and any trigram probe prunes it."""
    tris: set[bytes] = set()
    for s in uniques:
        if isinstance(s, str):
            tris |= _trigrams(s.encode("utf-8", "surrogatepass"))
    if not tris:
        return b""
    bf = BloomFilter(min(_SIG_MAX_BITS,
                         max(_SIG_MIN_BITS, _BITS_PER_TRIGRAM * len(tris))))
    for t in tris:
        bf.insert(t)
    return bf.to_bytes()


def signature_admits(sig: bytes | None, trigrams) -> bool:
    """False only when the signature PROVES a required trigram absent —
    a page written before signatures existed (sig None) always admits."""
    if sig is None or not trigrams:
        return True
    if len(sig) == 0:
        return False
    bf = BloomFilter.from_bytes(sig)
    return all(bf.maybe_contains(t) for t in trigrams)


# ---------------------------------------------------------------------------
# top-K selection (ORDER BY key LIMIT k)
# ---------------------------------------------------------------------------
def _topk_device_wanted() -> bool:
    mode = os.environ.get("CNOSDB_TPU_TOPK", "auto").lower()
    if mode in ("1", "on", "true"):
        return True
    if mode in ("0", "off", "false"):
        return False
    try:
        import jax

        return jax.default_backend() == "tpu"
    except Exception:
        return False


def topk_order_indices(vals: np.ndarray, nulls, asc: bool,
                       k: int) -> np.ndarray | None:
    """Select-then-gather top-k: indices of the k extreme rows, ordered
    EXACTLY as the full stable-lexsort path orders them (descending ties
    break to the larger original index, ascending to the smaller), or
    None when the shape is outside the fast path (caller full-sorts).

    The k-th order statistic comes from jax.lax.top_k on TPU (only the
    scalar threshold crosses back) or np.partition on host; candidate
    rows at-or-past the threshold are then sorted exactly."""
    n = len(vals)
    if k <= 0 or k >= n:
        stages.count("topk.declined", 1)
        return None
    if nulls is not None and np.any(nulls):
        # NULLS FIRST/LAST ordering interleaves two keys — full sort
        stages.count("topk.declined", 1)
        return None
    if vals.dtype == object or vals.dtype.kind not in "iufMmbUS":
        stages.count("topk.declined", 1)
        return None
    if vals.dtype.kind == "f" and np.isnan(vals).any():
        # NaNs sort last/first asymmetrically vs the >= threshold select
        stages.count("topk.declined", 1)
        return None
    if vals.dtype.kind in "Mm" and np.isnat(vals).any():
        # NaT: np.partition sorts it last, np.lexsort by raw i64 (first)
        stages.count("topk.declined", 1)
        return None
    thr = None
    if not asc and vals.dtype.kind in "iuf" and _topk_device_wanted():
        from .placement import f64_exact

        if vals.dtype.kind == "f" and not f64_exact():
            # a threshold the device rounded up would drop the k-th row
            stages.count("f64_kept_on_host")
        else:
            try:
                from . import kernels

                thr = kernels.topk_threshold(vals, k)   # 0-d np scalar
                stages.count("topk.device", 1)
            except Exception:
                thr = None
    if thr is None:
        stages.count("topk.host", 1)
        part = np.partition(vals, k - 1 if asc else n - k)
        thr = part[k - 1] if asc else part[n - k]
    cand = np.flatnonzero(vals <= thr) if asc else np.flatnonzero(vals >= thr)
    order = np.lexsort((cand, vals[cand]))
    if not asc:
        order = order[::-1]
    return cand[order][:k]
