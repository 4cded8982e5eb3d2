"""String/search plane (ops/strkernels + the planes it feeds).

Parity is the contract everywhere: the vectorized per-unique lanes, the
n-gram page skipper and the device top-K must be bit-identical to the
host paths they replace — the property tests below drive randomized
patterns (wildcards, regex metachars, unicode, empty strings, trailing
newlines) through both and diff the outputs, and the skipper is checked
against a never-drops-a-matching-page oracle with the index disabled.
"""
import os
import re

import numpy as np
import pytest

from cnosdb_tpu.models.strcol import (DictArray, dict_encode_strict,
                                      unify_dictionaries)
from cnosdb_tpu.ops import strkernels
from cnosdb_tpu.utils import stages


@pytest.fixture
def rng():
    return np.random.default_rng(20260805)


# alphabet stresses every lane: wildcards, regex metachars the translator
# must escape, multi-byte unicode, and the `$`-quirk newline
_ALPHA = list("ab%_.*+()[^\\") + ["é", "日", "\n", ""]


def _rand_strings(rng, n, maxlen=6):
    out = []
    for _ in range(n):
        k = int(rng.integers(0, maxlen))
        out.append("".join(rng.choice(_ALPHA) for _ in range(k)))
    return np.array(out, dtype=object)


def _host_like(pattern):
    """From-scratch reference for the host LIKE automaton (mirrors
    sql.expr.Like._compile deliberately, quirk and all)."""
    out = []
    for ch in pattern:
        out.append(".*" if ch == "%" else "." if ch == "_"
                   else re.escape(ch))
    rx = re.compile("^" + "".join(out) + "$", re.DOTALL)
    return lambda s: bool(rx.match(s))


# ---------------------------------------------------------------- classify
def test_classify_kinds():
    assert strkernels.classify("abc") == ("exact", "abc")
    assert strkernels.classify("abc%") == ("prefix", "abc")
    assert strkernels.classify("%abc") == ("suffix", "abc")
    assert strkernels.classify("%abc%") == ("contains", "abc")
    assert strkernels.classify("%%abc%%") == ("contains", "abc")
    assert strkernels.classify("") == ("exact", "")
    assert strkernels.classify("%") == ("suffix", "")
    assert strkernels.classify("%%") == ("suffix", "")
    # `_` anywhere, or an interior `%`, forces the regex lane
    assert strkernels.classify("a_c")[0] == "generic"
    assert strkernels.classify("a%c")[0] == "generic"
    assert strkernels.classify("%a%c%")[0] == "generic"


# -------------------------------------------------- per-unique mask parity
def test_unique_mask_matches_host_like_property(rng):
    for _ in range(60):
        values = np.array(sorted(set(_rand_strings(rng, 40).tolist())),
                          dtype=object)
        k = int(rng.integers(0, 5))
        pattern = "".join(rng.choice(_ALPHA) for _ in range(k))
        want = np.array([_host_like(pattern)(v) for v in values])
        got, reason = strkernels.unique_mask(values, pattern)
        np.testing.assert_array_equal(
            got, want, err_msg=f"pattern={pattern!r} ({reason})")


def test_unique_mask_trailing_newline_quirk():
    values = np.array(["abc", "abc\n", "abc\n\n", "xabc", "abcx"],
                      dtype=object)
    for pattern, want in [
        ("abc", [True, True, False, False, False]),     # $ eats one \n
        ("%abc", [True, True, False, True, False]),
        ("abc%", [True, True, True, False, True]),      # prefix: no quirk
        ("%abc%", [True, True, True, True, True]),
    ]:
        got, _ = strkernels.unique_mask(values, pattern)
        assert got.tolist() == want, pattern


def test_like_rows_negation_and_lane_ab(rng, monkeypatch):
    values = np.array(sorted({"", "ab", "abc", "abc\n", "xaby", "日本"}),
                      dtype=object)
    codes = rng.integers(0, len(values), 200).astype(np.int32)
    da = DictArray(codes, values)
    for pattern in ["ab%", "%b%", "_b_", "", "%", "日%"]:
        for negated in (False, True):
            fast = strkernels.like_rows(da, pattern, negated=negated)
            ref = np.array([_host_like(pattern)(v)
                            for v in da.materialize()])
            np.testing.assert_array_equal(
                fast, ~ref if negated else ref,
                err_msg=f"pattern={pattern!r} negated={negated}")


def test_like_eval_e2e_lane_ab_with_nulls(db, monkeypatch):
    """Full pipeline A/B: the dictionary lane (default) vs the per-row
    host fallback (CNOSDB_STR_LANE=0) must return identical rows, NULLs
    and NOT LIKE included."""
    db.execute_one("CREATE TABLE logs (body STRING, n BIGINT, TAGS(svc))")
    rows = []
    bodies = ["error: timeout", "ok", "error: disk", None, "warn", ""]
    for i, b in enumerate(bodies * 5):
        t = 1672531200000000000 + i * 1_000_000_000
        sv = "'" + b + "'" if b is not None else "NULL"
        rows.append(f"({t}, 's{i % 2}', {sv}, {i})")
    db.execute_one("INSERT INTO logs (time, svc, body, n) VALUES "
                   + ", ".join(rows))
    for sql in [
        "SELECT count(*) FROM logs WHERE body LIKE '%error%'",
        "SELECT count(*) FROM logs WHERE body NOT LIKE '%error%'",
        "SELECT time, body FROM logs WHERE body LIKE 'e%r: __me%' "
        "ORDER BY time",
        "SELECT svc, count(*) FROM logs WHERE body LIKE '%o%' "
        "GROUP BY svc ORDER BY svc",
    ]:
        monkeypatch.setenv("CNOSDB_STR_LANE", "1")
        fast = db.execute_one(sql, _session()).rows()
        monkeypatch.setenv("CNOSDB_STR_LANE", "0")
        slow = db.execute_one(sql, _session()).rows()
        assert fast == slow, sql


# --------------------------------------------------------- per-unique cmp
def test_per_unique_cmp_e2e(db, monkeypatch):
    db.execute_one("CREATE TABLE urls (url STRING, TAGS(site))")
    vals = [f"http://h{i % 7}/p{i % 11}" for i in range(40)] \
        + [f"ftp://h{i}" for i in range(5)]
    rows = [f"({1672531200000000000 + i * 1_000_000_000}, 's', '{u}')"
            for i, u in enumerate(vals)]
    db.execute_one("INSERT INTO urls (time, site, url) VALUES "
                   + ", ".join(rows))
    for sql in [
        "SELECT count(*) FROM urls WHERE substr(url, 1, 4) = 'http'",
        "SELECT count(*) FROM urls WHERE lower(url) != upper(url)",
        "SELECT count(*) FROM urls WHERE length(url) > 12",
    ]:
        prof = stages.QueryProfile()
        monkeypatch.setenv("CNOSDB_STR_LANE", "1")
        with stages.profile_scope(prof):
            fast = db.execute_one(sql, _session()).rows()
        monkeypatch.setenv("CNOSDB_STR_LANE", "0")
        slow = db.execute_one(sql, _session()).rows()
        assert fast == slow, sql
        assert prof.snapshot().get("string_path.per_unique", 0) > 0, sql


# ------------------------------------------------------------ n-gram index
def test_trigram_soundness_property(rng):
    """host-LIKE match ⇒ required_trigrams(pattern) ⊆ value trigrams.
    This is the invariant page skipping rests on."""
    for _ in range(200):
        k = int(rng.integers(0, 8))
        pattern = "".join(rng.choice(_ALPHA) for _ in range(k))
        req = strkernels.required_trigrams(pattern)
        if req is None:
            continue
        for v in _rand_strings(rng, 20, maxlen=10):
            if _host_like(pattern)(v):
                have = set(strkernels._trigrams(
                    v.encode("utf-8", "surrogatepass")))
                assert set(req) <= have, (pattern, v)


def test_signature_never_rejects_a_matching_page(rng):
    for _ in range(80):
        uniques = _rand_strings(rng, 12, maxlen=8)
        sig = strkernels.build_page_signature(uniques)
        k = int(rng.integers(1, 6))
        pattern = "%" + "".join(rng.choice(_ALPHA) for _ in range(k)) + "%"
        req = strkernels.required_trigrams(pattern)
        if any(_host_like(pattern)(v) for v in uniques):
            assert strkernels.signature_admits(sig, req), \
                (pattern, uniques.tolist())


def test_signature_edges():
    # no value reaches 3 bytes → b"" → any trigram probe prunes
    sig = strkernels.build_page_signature(np.array(["ab", "", "xy"],
                                                   dtype=object))
    assert sig == b""
    assert not strkernels.signature_admits(sig, (b"abc",))
    # legacy page (pre-signature file) always admits
    assert strkernels.signature_admits(None, (b"abc",))
    # empty probe set admits anything
    assert strkernels.signature_admits(sig, ())
    assert strkernels.signature_admits(b"", None)
    # multi-byte unicode spans several byte-trigrams and must round-trip
    sig = strkernels.build_page_signature(np.array(["日本語"], dtype=object))
    assert strkernels.signature_admits(
        sig, strkernels.required_trigrams("%日本%"))
    # patterns with no 3-byte literal run can't probe at all
    assert strkernels.required_trigrams("%ab%") is None
    assert strkernels.required_trigrams("a_c") is None
    assert strkernels.required_trigrams("%") is None


def test_pagemeta_signature_roundtrip(tmp_path):
    from cnosdb_tpu.models.codec import Encoding
    from cnosdb_tpu.models.schema import ValueType
    from cnosdb_tpu.storage.tsm import PageMeta, TsmReader, TsmWriter

    p = str(tmp_path / "sig.tsm")
    w = TsmWriter(p)
    ts = np.arange(10, dtype=np.int64)
    strs = np.array([f"needle_{i}" for i in range(10)], dtype=object)
    w.write_series("t", 5, ts, {
        "s": (1, ValueType.STRING, Encoding.ZSTD, strs, None),
        "f": (2, ValueType.FLOAT, Encoding.GORILLA,
              np.arange(10.0), None),
    })
    w.finish()
    r = TsmReader(p)
    pm = r.chunk("t", 5).column("s").pages[0]
    assert isinstance(pm.ngram, bytes) and len(pm.ngram) > 0
    assert strkernels.signature_admits(
        pm.ngram, strkernels.required_trigrams("%needle%"))
    assert not strkernels.signature_admits(
        pm.ngram, strkernels.required_trigrams("%haystack%"))
    # numeric pages carry no signature
    assert r.chunk("t", 5).column("f").pages[0].ngram is None
    r.close()
    # a 12-field list (pre-signature file) hydrates with ngram=None
    legacy = PageMeta.from_list(pm.to_list()[:12])
    assert legacy.ngram is None


def test_ngram_scan_never_drops_matching_pages(tmp_path, rng):
    """E2E oracle: the pruned scan (device-decode lane engaged, signatures
    live) returns exactly the batch the index-disabled scan returns,
    while provably skipping pages."""
    from cnosdb_tpu.models.points import SeriesRows, WriteBatch
    from cnosdb_tpu.models.schema import TskvTableSchema, ValueType
    from cnosdb_tpu.models.series import SeriesKey
    from cnosdb_tpu.ops import device_decode
    from cnosdb_tpu.sql.expr import Column, Like
    from cnosdb_tpu.storage.scan import _page_constraints, scan_vnode
    from cnosdb_tpu.storage.vnode import VnodeStorage

    schemas = {"m": TskvTableSchema.new_measurement(
        "t", "db", "m", tags=["host"],
        fields=[("s", ValueType.STRING)])}
    v = VnodeStorage(1, str(tmp_path), schemas=schemas)
    # several flushes → several pages; the needle lives in ONE of them
    for base, words in [(0, ["alpha", "beta"]), (5000, ["rare_needle"]),
                        (10000, ["gamma", "delta"])]:
        n = 1500
        wb = WriteBatch()
        wb.add_series("m", SeriesRows(
            SeriesKey("m", {"host": "h"}), list(range(base, base + n)),
            {"s": (int(ValueType.STRING),
                   [words[i % len(words)] for i in range(n)])}))
        v.write(wb)
        v.flush()
    flt = Like(Column("s"), "%rare_needle%")
    cons = _page_constraints(flt, ["s"])
    assert any(c[0] == "ngram" for c in cons.get("s", ())), cons

    def run(skip_on):
        os.environ["CNOSDB_NGRAM_SKIP"] = "1" if skip_on else "0"
        prof = stages.QueryProfile()
        try:
            with stages.profile_scope(prof):
                b = scan_vnode(
                    v, "m",
                    page_constraints=_page_constraints(flt, ["s"]),
                    decode_hook=device_decode.DeviceDecodeLane)
        finally:
            del os.environ["CNOSDB_NGRAM_SKIP"]
        return b, prof.snapshot().get("ngram_pages_skipped", 0)

    pruned, skipped = run(True)
    oracle, skipped_off = run(False)
    assert skipped > 0 and skipped_off == 0

    def matching_rows(b):
        """(ts, value) pairs the LIKE actually selects — the only rows a
        pruned batch is contracted to preserve."""
        vals = b.fields["s"][1]
        vals = np.asarray(vals.materialize()
                          if isinstance(vals, DictArray) else vals)
        like = _host_like("%rare_needle%")
        keep = np.array([like(x) for x in vals])
        return list(zip(b.ts[keep].tolist(), vals[keep].tolist()))

    assert matching_rows(pruned) == matching_rows(oracle)
    assert len(matching_rows(pruned)) == 1500
    # pruning actually shrank the decode set: only the needle page decoded
    assert pruned.n_rows < oracle.n_rows
    v.close()


# ------------------------------------------------------------- LIKE domain
def test_like_domain_algebra_and_wire():
    from cnosdb_tpu.models.predicate import (AllDomain, LikeDomain,
                                             NoneDomain, RangeDomain,
                                             SetDomain, domain_from_wire,
                                             domain_to_wire)

    d = LikeDomain("%err%")
    assert d.contains_value("an error") and not d.contains_value("ok")
    assert not d.contains_value(7)   # non-strings never match
    got = d.intersect(SetDomain(["xerrx", "nope"]))
    assert isinstance(got, SetDomain) and got.values == SetDomain(
        ["xerrx"]).values
    assert isinstance(d.intersect(SetDomain(["nope"])), NoneDomain)
    r = RangeDomain.of("a", True, "z", True)
    assert r.intersect(d) is r           # sound over-approximation
    assert isinstance(r.union(d), AllDomain)
    assert isinstance(d.union(NoneDomain()), LikeDomain)
    rt = domain_from_wire(domain_to_wire(d))
    assert rt == d


def test_like_domain_regex_matches_host_compile(rng):
    from cnosdb_tpu.models.predicate import LikeDomain

    for _ in range(40):
        k = int(rng.integers(0, 6))
        pattern = "".join(rng.choice(_ALPHA) for _ in range(k))
        dom = LikeDomain(pattern)
        for v in _rand_strings(rng, 15):
            assert dom.contains_value(v) == _host_like(pattern)(v), \
                (pattern, v)


def test_extract_like_pushdown_domains():
    from cnosdb_tpu.models.predicate import LikeDomain, SetDomain
    from cnosdb_tpu.sql.expr import Column, Like, extract_domains

    # wildcard-free → exact set incl. the trailing-newline twin
    doms = extract_domains(Like(Column("t"), "abc"), {"t"})
    d = doms.domains["t"]
    assert isinstance(d, SetDomain) and set(d.values) == {"abc", "abc\n"}
    doms = extract_domains(Like(Column("t"), "ab%"), {"t"})
    assert isinstance(doms.domains["t"], LikeDomain)
    # negated patterns must NOT constrain the column
    doms = extract_domains(
        Like(Column("t"), "ab%", negated=True), {"t"})
    assert "t" not in doms.domains


def test_tag_like_pushdown_e2e(db):
    db.execute_one("CREATE TABLE m (v DOUBLE, TAGS(host))")
    rows = []
    for i, h in enumerate(["web-1", "web-2", "db-1", "cache-1"]):
        rows.append(f"({1672531200000000000 + i}, '{h}', {i}.0)")
    db.execute_one("INSERT INTO m (time, host, v) VALUES "
                   + ", ".join(rows))
    rs = db.execute_one(
        "SELECT host, v FROM m WHERE host LIKE 'web%' ORDER BY host",
        _session())
    assert rs.rows() == [("web-1", 0.0), ("web-2", 1.0)]
    rs = db.execute_one(
        "SELECT count(*) FROM m WHERE host LIKE '%-1'", _session())
    assert rs.rows() == [(3,)]


# ------------------------------------------------------------- device top-K
def test_topk_order_indices_matches_lexsort_property(rng):
    for _ in range(200):
        n = int(rng.integers(2, 60))
        k = int(rng.integers(1, n))
        asc = bool(rng.integers(0, 2))
        if rng.integers(0, 2):
            vals = rng.integers(-5, 5, n)       # dense ties
        else:
            vals = rng.normal(size=n).round(1)
        got = strkernels.topk_order_indices(vals, None, asc, k)
        assert got is not None
        ref = np.lexsort((vals,))
        if not asc:
            ref = ref[::-1]
        np.testing.assert_array_equal(got, ref[:k],
                                      err_msg=f"asc={asc} k={k}")


def test_topk_declines():
    vals = np.arange(10.0)

    def declined(*a):
        prof = stages.QueryProfile()
        with stages.profile_scope(prof):
            out = strkernels.topk_order_indices(*a)
        return out is None and prof.snapshot().get("topk.declined", 0) > 0

    assert declined(vals, np.zeros(10, bool) | (np.arange(10) == 3),
                    True, 2)                       # NULLs present
    assert declined(np.array([1.0, np.nan, 2.0]), None, True, 1)
    assert declined(np.array(["a", "b"], dtype=object), None, True, 1)
    nat = np.array(["2020-01-01", "NaT"], dtype="datetime64[ns]")
    assert declined(nat, None, True, 1)
    assert strkernels.topk_order_indices(vals, None, True, 0) is None
    assert strkernels.topk_order_indices(vals, None, True, 10) is None
    # clean datetimes are eligible
    ts = np.array(["2020-01-02", "2020-01-01", "2020-01-03"],
                  dtype="datetime64[ns]")
    got = strkernels.topk_order_indices(ts, None, True, 2)
    np.testing.assert_array_equal(got, [1, 0])


def test_topk_e2e_order_limit(db):
    db.execute_one("CREATE TABLE hits (d BIGINT, TAGS(page))")
    rows = []
    for i in range(50):
        rows.append(f"({1672531200000000000 + i * 1000000}, "
                    f"'p{i % 7}', {(i * 37) % 50})")
    db.execute_one("INSERT INTO hits (time, page, d) VALUES "
                   + ", ".join(rows))
    sql = ("SELECT page, max(d) AS m FROM hits GROUP BY page "
           "ORDER BY m DESC LIMIT 3")
    prof = stages.QueryProfile()
    with stages.profile_scope(prof):
        rs = db.execute_one(sql, _session())
    snap = prof.snapshot()
    assert snap.get("topk.host", 0) + snap.get("topk.device", 0) > 0
    ms = [r[1] for r in rs.rows()]
    assert ms == sorted(ms, reverse=True) and len(ms) == 3


# ------------------------------------------- dictionary machinery parity
def test_unify_dictionaries_matches_np_unique(rng):
    das = []
    for _ in range(4):
        vals = np.array(sorted(set(_rand_strings(rng, 20).tolist())),
                        dtype=object)
        das.append(DictArray(
            rng.integers(0, len(vals), 30).astype(np.int32), vals))
    das.append(DictArray(das[0].codes.copy(), das[0].values))  # shared dict
    union = unify_dictionaries(das)
    want = np.unique(np.concatenate([d.values for d in das]))
    np.testing.assert_array_equal(union, want)
    assert union.dtype == object


def test_dict_encode_strict_parity(rng):
    vals = _rand_strings(rng, 300, maxlen=4)
    enc = dict_encode_strict(vals)
    if enc is None:   # pyarrow absent in this env: fallback path covers
        pytest.skip("pyarrow unavailable")
    np.testing.assert_array_equal(enc.materialize(), vals)
    # values sorted + codes are ranks (the DictArray invariant)
    assert list(enc.values) == sorted(set(vals.tolist()))
    # nulls and non-strings refuse (caller falls back to np.unique)
    assert dict_encode_strict(np.array(["a", None], dtype=object)) is None
    assert dict_encode_strict(np.arange(3)) is None


def test_group_indices_dict_vs_legacy(rng):
    from cnosdb_tpu.sql.relational import group_indices

    vals = np.array(["x", "y", "z\x00", "z"], dtype=object)
    obj = vals[rng.integers(0, 4, 500)]
    da = dict_encode_strict(obj)
    nums = rng.integers(0, 3, 500)
    gid_obj, rep_obj = group_indices([obj, nums], 500)
    np.testing.assert_array_equal(obj[rep_obj][gid_obj], obj)
    np.testing.assert_array_equal(nums[rep_obj][gid_obj], nums)
    if da is not None:
        gid_da, rep_da = group_indices([da, nums], 500)
        np.testing.assert_array_equal(gid_obj, gid_da)
        np.testing.assert_array_equal(rep_obj, rep_da)


# ------------------------------------------------------- fallback booking
def test_every_fallback_books_a_reason(monkeypatch):
    base = dict(strkernels.outcomes_snapshot())
    monkeypatch.setenv("CNOSDB_STR_LANE", "0")
    assert not strkernels.enabled()
    monkeypatch.setenv("CNOSDB_STR_LANE", "1")
    values = np.array([1, 2, None], dtype=object)   # non-string uniques
    strkernels.unique_mask(values, "a%")
    snap = strkernels.outcomes_snapshot()
    key = ("per_unique", "non_string_uniques")
    assert snap.get(key, 0) > base.get(key, 0)
    assert all(isinstance(p, str) and isinstance(r, str)
               for p, r in snap)


def _session():
    from cnosdb_tpu.sql.executor import Session

    return Session(database="public")


@pytest.fixture
def db(tmp_path):
    from cnosdb_tpu.parallel.coordinator import Coordinator
    from cnosdb_tpu.parallel.meta import MetaStore
    from cnosdb_tpu.sql.executor import QueryExecutor
    from cnosdb_tpu.storage.engine import TsKv

    meta = MetaStore(str(tmp_path / "meta.json"))
    engine = TsKv(str(tmp_path / "data"))
    coord = Coordinator(meta, engine)
    ex = QueryExecutor(meta, coord)
    yield ex
    engine.close()
