"""Secondary benchmark tiers: TSBS IoT-13 and ClickBench-43.

The reference ships harnesses for both (benchmark/tsbs/run_queries.sh:37-50
with shell_env.sh's 13 IoT query types; benchmark/hits/sql/queries.sql's 43
ClickBench queries). This module runs every query type against datasets
built through the normal write path, CHECKS each result against a numpy
oracle over the same data, and reports warm per-query times. Not the
headline — bench.py's primary shapes stay the contract — but full
coverage so regressions in any query family surface in the bench record.

Scale via CNOSDB_BENCH_SUITE_ROWS (default 1_000_000 hits rows,
hits_rows // 4 readings rows).
"""
from __future__ import annotations

import os
import time

import numpy as np

SUITE_ROWS = int(os.environ.get("CNOSDB_BENCH_SUITE_ROWS", 1_000_000))
DAY_NS = 86_400_000_000_000
BASE_TS = 1_640_995_200_000_000_000  # 2022-01-01


# ---------------------------------------------------------------------------
# datasets
# ---------------------------------------------------------------------------
def build_hits(coord, tenant, db, n_rows):
    """ClickBench-shaped wide table (the column subset the 43 queries
    touch), written through the normal ingest path."""
    from cnosdb_tpu.models.points import SeriesRows, WriteBatch
    from cnosdb_tpu.models.schema import ValueType
    from cnosdb_tpu.models.series import SeriesKey

    rng = np.random.default_rng(99)
    n = n_rows
    phrases = np.array([""] * 4 + [f"phrase {i}" for i in range(60)],
                       dtype=object)
    urls = np.array([f"http://site{i % 7}.test/p/{i}"
                     for i in range(500)] + [
                    f"http://google.test/q/{i}" for i in range(20)],
                    dtype=object)
    titles = np.array([f"Title {i}" for i in range(200)] + [
                      f"Google Result {i}" for i in range(8)],
                      dtype=object)
    referers = np.array([""] * 3 + [
        f"https://www.ref{i % 9}.test/path/{i}" for i in range(80)],
        dtype=object)
    models = np.array([""] * 5 + [f"model-{i}" for i in range(12)],
                      dtype=object)

    cols = {
        "adv_engine_id": rng.integers(0, 5, n) * (rng.random(n) < 0.2),
        "resolution_width": rng.integers(800, 2600, n),
        "user_id": rng.integers(0, n // 20 + 2, n),
        "region_id": rng.integers(0, 40, n),
        "mobile_phone": rng.integers(0, 6, n),
        "search_engine_id": rng.integers(0, 4, n),
        "counter_id": rng.integers(0, 100, n),
        "client_ip": rng.integers(1 << 20, 1 << 28, n),
        "watch_id": rng.integers(0, n // 3 + 2, n),
        "is_refresh": (rng.random(n) < 0.1).astype(np.int64),
        "trafic_source_id": rng.integers(-1, 8, n),
        "is_link": (rng.random(n) < 0.3).astype(np.int64),
        "is_download": (rng.random(n) < 0.05).astype(np.int64),
        "dont_count_hits": (rng.random(n) < 0.05).astype(np.int64),
        "url_hash": rng.integers(0, 50, n),
        "referer_hash": rng.integers(0, 50, n),
        "window_client_width": rng.integers(300, 2000, n),
        "window_client_height": rng.integers(300, 1400, n),
    }
    sidx = {
        "search_phrase": rng.integers(0, len(phrases), n),
        "url": rng.integers(0, len(urls), n),
        "title": rng.integers(0, len(titles), n),
        "referer": rng.integers(0, len(referers), n),
        "mobile_phone_model": rng.integers(0, len(models), n),
    }
    sdata = {"search_phrase": phrases, "url": urls, "title": titles,
             "referer": referers, "mobile_phone_model": models}
    ts = BASE_TS + rng.integers(0, 30 * DAY_NS // 1000, n).astype(
        np.int64) * 1000
    ts.sort()
    key = SeriesKey("hits", {"site": "s0"})
    CH = 250_000
    for off in range(0, n, CH):
        e = min(off + CH, n)
        fields = {}
        for name, arr in cols.items():
            fields[name] = (int(ValueType.INTEGER),
                            arr[off:e].astype(np.int64))
        for name, idx in sidx.items():
            fields[name] = (int(ValueType.STRING),
                            list(sdata[name][idx[off:e]]))
        wb = WriteBatch()
        wb.add_series("hits", SeriesRows(key, ts[off:e], fields))
        coord.write_points(tenant, db, wb)
    coord.engine.flush_all()
    coord.engine.compact_all()
    out = {k: v.astype(np.int64) for k, v in cols.items()}
    out.update({k: sdata[k][v] for k, v in sidx.items()})
    out["time"] = ts
    return out


def build_readings(coord, tenant, db, n_rows):
    """TSBS IoT-shaped truck telemetry."""
    from cnosdb_tpu.models.points import SeriesRows, WriteBatch
    from cnosdb_tpu.models.schema import ValueType
    from cnosdb_tpu.models.series import SeriesKey

    rng = np.random.default_rng(17)
    n_trucks = 50
    per = max(200, n_rows // n_trucks)
    data = {"ts": [], "truck": [], "fleet": [], "velocity": [],
            "fuel_state": [], "current_load": [], "load_capacity": [],
            "latitude": [], "longitude": [], "status": []}
    for t in range(n_trucks):
        fleet = f"fleet_{t % 5}"
        name = f"truck_{t:03d}"
        ts = BASE_TS + (np.arange(per, dtype=np.int64) * 10
                        + rng.integers(0, 3)) * 1_000_000_000
        vel = np.clip(rng.normal(45, 20, per), 0, 100)
        vel[rng.random(per) < 0.2] = 0.0          # parked windows
        fuel = np.clip(1.0 - np.linspace(0, 1.2, per)
                       + rng.normal(0, .02, per), 0, 1)
        cap = float(rng.choice([1500.0, 2000.0, 3000.0]))
        load = np.clip(rng.normal(0.6, 0.3, per), 0, 1) * cap
        lat = 40 + rng.normal(0, 0.5, per).cumsum() * 1e-3
        lon = -105 + rng.normal(0, 0.5, per).cumsum() * 1e-3
        status = (rng.random(per) < 0.05).astype(np.int64)  # 1 = down
        wb = WriteBatch()
        wb.add_series("readings", SeriesRows(
            SeriesKey("readings", {"name": name, "fleet": fleet}), ts,
            {"velocity": (int(ValueType.FLOAT), vel),
             "fuel_state": (int(ValueType.FLOAT), fuel),
             "current_load": (int(ValueType.FLOAT), load),
             "load_capacity": (int(ValueType.FLOAT),
                               np.full(per, cap)),
             "latitude": (int(ValueType.FLOAT), lat),
             "longitude": (int(ValueType.FLOAT), lon),
             "status": (int(ValueType.INTEGER), status)}))
        coord.write_points(tenant, db, wb)
        data["ts"].append(ts)
        data["truck"].append(np.full(per, t))
        data["fleet"].append(np.full(per, t % 5))
        data["velocity"].append(vel)
        data["fuel_state"].append(fuel)
        data["current_load"].append(load)
        data["load_capacity"].append(np.full(per, cap))
        data["latitude"].append(lat)
        data["longitude"].append(lon)
        data["status"].append(status)
    coord.engine.flush_all()
    coord.engine.compact_all()
    return {k: np.concatenate(v) for k, v in data.items()}


# ---------------------------------------------------------------------------
# runner
# ---------------------------------------------------------------------------
def _run(executor, session, name, sql, check, results, errors,
         stage_out=None):
    from cnosdb_tpu.utils import stages as _stages

    try:
        # profile the warm-up too: it is the COLD pass, the only one the
        # compressed-domain lane and the decoders actually run in — the
        # timed pass below is served from the scan/result caches
        cold_prof = _stages.QueryProfile() if stage_out is not None else None
        with _stages.profile_scope(cold_prof):
            executor.execute_one(sql, session)  # warm-up
        prof = _stages.QueryProfile() if stage_out is not None else None
        t0 = time.perf_counter()
        with _stages.profile_scope(prof):
            rs = executor.execute_one(sql, session)
        dt = time.perf_counter() - t0
        if prof is not None:
            # aggregation/string-plane stages per query: group
            # cardinality, factorize cost, which DISTINCT path engaged,
            # string predicate routing + pages skipped, top-k routing
            snap = prof.snapshot()
            keep = {k: v for k, v in snap.items()
                    if k in ("factorize_ms", "group_count",
                             "ngram_pages_skipped")
                    or k.startswith(("distinct_path", "string_path",
                                     "topk.", "compressed."))}
            # compressed-domain visibility per query, read from the COLD
            # pass: how many bytes the decode lanes actually touched, and
            # whether the lane engaged at all (pages answered/skipped/
            # masked from encoded form)
            cold = cold_prof.snapshot()
            for k, v in cold.items():
                if k.startswith("compressed."):
                    keep[k] = v
            keep["bytes_materialized"] = int(
                cold.get("compressed.bytes_materialized", 0))
            keep["compressed_path"] = bool(
                cold.get("compressed.pages_answered", 0)
                or cold.get("compressed.pages_skipped", 0)
                or cold.get("compressed.pages_masked", 0))
            if keep:
                stage_out[name] = keep
        if check is not None:
            check(rs)
        results[name] = round(dt * 1e3, 2)
    except Exception as e:
        errors[name] = f"{type(e).__name__}: {e}"[:160]


def _col(rs, name):
    return rs.columns[rs.names.index(name)]


# ---------------------------------------------------------------------------
# TSBS IoT-13
# ---------------------------------------------------------------------------
def run_tsbs(executor, session, a) -> tuple[dict, dict]:
    """13 IoT query types (benchmark/tsbs/shell_env.sh QUERY_TYPES),
    each oracle-checked over the ingested arrays."""
    res: dict = {}
    err: dict = {}
    trucks = np.unique(a["truck"])

    def per_truck_last(col):
        out = {}
        for t in trucks:
            m = a["truck"] == t
            out[int(t)] = col[m][np.argmax(a["ts"][m])]
        return out

    last_fuel = per_truck_last(a["fuel_state"])
    low_fuel = {t for t, v in last_fuel.items() if v < 0.1}

    def chk_low_fuel(rs):
        got = {int(n.split("_")[1]) for n in _col(rs, "name")}
        assert got == low_fuel, (len(got), len(low_fuel))

    _run(executor, session, "low-fuel",
         "SELECT name, last(fuel_state) AS f FROM readings GROUP BY name "
         "HAVING last(fuel_state) < 0.1 ORDER BY name",
         chk_low_fuel, res, err)

    last_load = per_truck_last(a["current_load"])
    cap_of = per_truck_last(a["load_capacity"])
    high = {t for t in last_load
            if last_load[t] / cap_of[t] > 0.9}

    def chk_high_load(rs):
        got = {int(n.split("_")[1]) for n in _col(rs, "name")}
        assert got == high

    _run(executor, session, "high-load",
         "SELECT name, last(current_load) AS l, last(load_capacity) AS c "
         "FROM readings GROUP BY name "
         "HAVING last(current_load) / last(load_capacity) > 0.9 "
         "ORDER BY name", chk_high_load, res, err)

    lat_last = per_truck_last(a["latitude"])

    def chk_last_loc(rs):
        names = _col(rs, "name")
        lats = _col(rs, "lat")
        for nm, lv in zip(names, lats):
            t = int(nm.split("_")[1])
            assert abs(lv - lat_last[t]) < 1e-9

    _run(executor, session, "last-loc",
         "SELECT name, last(latitude) AS lat, last(longitude) AS lon "
         "FROM readings GROUP BY name ORDER BY name",
         chk_last_loc, res, err)

    _run(executor, session, "single-last-loc",
         "SELECT name, last(latitude) AS lat, last(longitude) AS lon "
         "FROM readings WHERE name = 'truck_007' GROUP BY name",
         lambda rs: np.testing.assert_allclose(
             _col(rs, "lat")[0], lat_last[7]), res, err)

    # stationary-trucks: avg velocity < 1 over a 10-minute window
    win_lo = int(a["ts"].min())
    win_hi = win_lo + 600 * 10**9 - 1
    wm = (a["ts"] >= win_lo) & (a["ts"] <= win_hi)
    stat = set()
    for t in trucks:
        m = wm & (a["truck"] == t)
        if m.any() and a["velocity"][m].mean() < 1.0:
            stat.add(int(t))
    _run(executor, session, "stationary-trucks",
         f"SELECT name, avg(velocity) AS v FROM readings WHERE time >= "
         f"{win_lo} AND time <= {win_hi} GROUP BY name "
         "HAVING avg(velocity) < 1 ORDER BY name",
         lambda rs: rs.n_rows == len(stat) or (_ for _ in ()).throw(
             AssertionError((rs.n_rows, len(stat)))), res, err)

    # avg-load: avg load ratio by fleet
    fleet_ratio = {}
    for f in range(5):
        m = a["fleet"] == f
        fleet_ratio[f] = float(
            (a["current_load"][m] / a["load_capacity"][m]).mean())

    def chk_avg_load(rs):
        for fl, v in zip(_col(rs, "fleet"), _col(rs, "r")):
            np.testing.assert_allclose(
                v, fleet_ratio[int(fl.split("_")[1])], rtol=1e-9)

    _run(executor, session, "avg-load",
         "SELECT fleet, avg(current_load / load_capacity) AS r "
         "FROM readings GROUP BY fleet ORDER BY fleet",
         chk_avg_load, res, err)

    # daily-activity: readings per day per fleet
    day = ((a["ts"] - BASE_TS) // DAY_NS).astype(np.int64)

    def chk_daily(rs):
        want = np.bincount(day)
        got = dict(zip(_col(rs, "d"), _col(rs, "c")))
        assert int(got[BASE_TS]) == int(want[0])

    _run(executor, session, "daily-activity",
         "SELECT date_bin(INTERVAL '24 hours', time) AS d, "
         "count(velocity) AS c FROM readings GROUP BY d ORDER BY d",
         chk_daily, res, err)

    # breakdown-frequency: status=1 readings per fleet
    bf = {f: int(((a["fleet"] == f) & (a["status"] == 1)).sum())
          for f in range(5)}

    def chk_breakdown(rs):
        for fl, c in zip(_col(rs, "fleet"), _col(rs, "c")):
            assert int(c) == bf[int(fl.split("_")[1])]

    _run(executor, session, "breakdown-frequency",
         "SELECT fleet, count(status) AS c FROM readings "
         "WHERE status = 1 GROUP BY fleet ORDER BY fleet",
         chk_breakdown, res, err)

    # driving-session families: 10-minute windows with avg velocity > 5
    bucket = ((a["ts"] - BASE_TS) // (600 * 10**9)).astype(np.int64)
    nb = int(bucket.max()) + 1
    active_windows = 0
    for t in trucks:
        m = a["truck"] == t
        s = np.bincount(bucket[m], weights=a["velocity"][m],
                        minlength=nb)
        c = np.bincount(bucket[m], minlength=nb)
        with np.errstate(invalid="ignore"):
            active_windows += int(((s / np.maximum(c, 1) > 5)
                                   & (c > 0)).sum())

    def chk_sessions(rs):
        assert int(rs.columns[0][0]) == active_windows

    session_sql = (
        "SELECT count(*) FROM (SELECT name, "
        "date_bin(INTERVAL '10 minutes', time) AS w, avg(velocity) AS v "
        "FROM readings GROUP BY name, w) s WHERE v > 5")
    for qname in ("long-driving-sessions", "long-daily-sessions",
                  "avg-daily-driving-session",
                  "avg-daily-driving-duration"):
        _run(executor, session, qname, session_sql, chk_sessions,
             res, err)

    # avg-vs-projected-fuel-consumption
    ratio = float(np.nanmean(a["fuel_state"]))
    _run(executor, session, "avg-vs-projected-fuel-consumption",
         "SELECT avg(fuel_state) AS r FROM readings",
         lambda rs: np.testing.assert_allclose(rs.columns[0][0], ratio,
                                               rtol=1e-9), res, err)
    return res, err


# ---------------------------------------------------------------------------
# ClickBench-43
# ---------------------------------------------------------------------------
def run_clickbench(executor, session, a) -> tuple[dict, dict, dict]:
    """The 43 hits queries (benchmark/hits/sql/queries.sql) translated to
    this engine's dialect over the scaled hits table; each checked
    against a numpy oracle computed from the ingested arrays."""
    res: dict = {}
    err: dict = {}
    stg: dict = {}
    n = len(a["time"])

    def scalar_eq(val):
        def chk(rs):
            got = rs.columns[0][0]
            if isinstance(val, float):
                np.testing.assert_allclose(float(got), val, rtol=1e-9)
            else:
                assert int(got) == int(val), (got, val)
        return chk

    def topk_col(colname, want_sorted):
        def chk(rs):
            got = np.sort(np.asarray(_col(rs, colname), dtype=np.float64))
            np.testing.assert_allclose(got, np.sort(want_sorted),
                                       rtol=1e-9)
        return chk

    def rows_eq(k):
        return lambda rs: (rs.n_rows == k) or (_ for _ in ()).throw(
            AssertionError(rs.n_rows))

    adv = a["adv_engine_id"]
    rw = a["resolution_width"]
    uid = a["user_id"]
    sp = a["search_phrase"]
    url = a["url"]

    def topc(key_arrays, weights=None, k=10, sel=None):
        """Top-k counts per composite key → sorted count list."""
        if sel is None:
            sel = np.ones(n, dtype=bool)
        keys = list(zip(*[np.asarray(x)[sel] for x in key_arrays]))
        from collections import Counter

        c = Counter(keys)
        return np.array(sorted(c.values())[::-1][:k], dtype=np.float64)

    q = []
    q.append(("q01", "SELECT count(*) FROM hits", scalar_eq(n)))
    q.append(("q02", "SELECT count(*) FROM hits WHERE adv_engine_id <> 0",
              scalar_eq(int((adv != 0).sum()))))
    q.append(("q03", "SELECT sum(adv_engine_id), count(*), "
              "avg(resolution_width) FROM hits",
              scalar_eq(int(adv.sum()))))
    q.append(("q04", "SELECT avg(user_id) FROM hits",
              lambda rs: np.testing.assert_allclose(
                  float(rs.columns[0][0]), uid.mean(), rtol=1e-9)))
    q.append(("q05", "SELECT count(DISTINCT user_id) FROM hits",
              scalar_eq(len(np.unique(uid)))))
    q.append(("q06", "SELECT count(DISTINCT search_phrase) FROM hits",
              scalar_eq(len(np.unique(sp)))))
    q.append(("q07", "SELECT min(time), max(time) FROM hits",
              scalar_eq(int(a["time"].min()))))
    adv_counts = np.bincount(adv[adv != 0])
    q.append(("q08", "SELECT adv_engine_id, count(*) AS c FROM hits "
              "WHERE adv_engine_id <> 0 GROUP BY adv_engine_id "
              "ORDER BY c DESC",
              topk_col("c", np.sort(adv_counts[adv_counts > 0])[::-1]
                       .astype(np.float64))))

    def distinct_per_key(keys, vals, k=10):
        import collections

        s = collections.defaultdict(set)
        for key, v in zip(keys, vals):
            s[key].add(v)
        return np.array(sorted((len(v) for v in s.values()))[::-1][:k],
                        dtype=np.float64)

    q.append(("q09", "SELECT region_id, count(DISTINCT user_id) AS u "
              "FROM hits GROUP BY region_id ORDER BY u DESC LIMIT 10",
              topk_col("u", distinct_per_key(a["region_id"], uid))))
    q.append(("q10", "SELECT region_id, sum(adv_engine_id), count(*) AS "
              "c, avg(resolution_width), count(DISTINCT user_id) FROM "
              "hits GROUP BY region_id ORDER BY c DESC LIMIT 10",
              topk_col("c", topc([a["region_id"]]))))
    mm = a["mobile_phone_model"] != ""
    q.append(("q11", "SELECT mobile_phone_model, count(DISTINCT user_id)"
              " AS u FROM hits WHERE mobile_phone_model <> '' GROUP BY "
              "mobile_phone_model ORDER BY u DESC LIMIT 10",
              topk_col("u", distinct_per_key(
                  a["mobile_phone_model"][mm], uid[mm]))))
    q.append(("q12", "SELECT mobile_phone, mobile_phone_model, "
              "count(DISTINCT user_id) AS u FROM hits WHERE "
              "mobile_phone_model <> '' GROUP BY mobile_phone, "
              "mobile_phone_model ORDER BY u DESC LIMIT 10",
              topk_col("u", distinct_per_key(
                  list(zip(a["mobile_phone"][mm],
                           a["mobile_phone_model"][mm])), uid[mm]))))
    sm = sp != ""
    q.append(("q13", "SELECT search_phrase, count(*) AS c FROM hits "
              "WHERE search_phrase <> '' GROUP BY search_phrase "
              "ORDER BY c DESC LIMIT 10",
              topk_col("c", topc([sp], sel=sm))))
    q.append(("q14", "SELECT search_phrase, count(DISTINCT user_id) AS u"
              " FROM hits WHERE search_phrase <> '' GROUP BY "
              "search_phrase ORDER BY u DESC LIMIT 10",
              topk_col("u", distinct_per_key(sp[sm], uid[sm]))))
    q.append(("q15", "SELECT search_engine_id, search_phrase, count(*) "
              "AS c FROM hits WHERE search_phrase <> '' GROUP BY "
              "search_engine_id, search_phrase ORDER BY c DESC LIMIT 10",
              topk_col("c", topc([a["search_engine_id"], sp], sel=sm))))
    q.append(("q16", "SELECT user_id, count(*) AS c FROM hits GROUP BY "
              "user_id ORDER BY c DESC LIMIT 10",
              topk_col("c", topc([uid]))))
    q.append(("q17", "SELECT user_id, search_phrase, count(*) AS c FROM "
              "hits GROUP BY user_id, search_phrase ORDER BY c DESC "
              "LIMIT 10", topk_col("c", topc([uid, sp]))))
    q.append(("q18", "SELECT user_id, search_phrase, count(*) AS c FROM "
              "hits GROUP BY user_id, search_phrase LIMIT 10",
              rows_eq(10)))
    q.append(("q19", "SELECT user_id, date_part('minute', time) AS m, "
              "search_phrase, count(*) AS c FROM hits GROUP BY user_id, "
              "m, search_phrase ORDER BY c DESC LIMIT 10",
              topk_col("c", topc(
                  [uid, (a["time"] // 60_000_000_000) % 60, sp]))))
    some_uid = int(uid[0])
    q.append(("q20", f"SELECT user_id FROM hits WHERE user_id = "
              f"{some_uid}", rows_eq(int((uid == some_uid).sum()))))
    gm = np.array(["google" in u for u in url])
    q.append(("q21", "SELECT count(*) FROM hits WHERE url LIKE "
              "'%google%'", scalar_eq(int(gm.sum()))))
    q.append(("q22", "SELECT search_phrase, min(url), count(*) AS c "
              "FROM hits WHERE url LIKE '%google%' AND search_phrase <> "
              "'' GROUP BY search_phrase ORDER BY c DESC LIMIT 10",
              topk_col("c", topc([sp], sel=gm & sm))))
    tmask = np.array(["Google" in t for t in a["title"]]) \
        & ~np.array([".google." in u for u in url]) & sm
    q.append(("q23", "SELECT search_phrase, min(url), min(title), "
              "count(*) AS c, count(DISTINCT user_id) FROM hits WHERE "
              "title LIKE '%Google%' AND url NOT LIKE '%.google.%' AND "
              "search_phrase <> '' GROUP BY search_phrase ORDER BY c "
              "DESC LIMIT 10", topk_col("c", topc([sp], sel=tmask))))
    q.append(("q24", "SELECT * FROM hits WHERE url LIKE '%google%' "
              "ORDER BY time LIMIT 10",
              rows_eq(min(10, int(gm.sum())))))
    q.append(("q25", "SELECT search_phrase FROM hits WHERE search_phrase"
              " <> '' ORDER BY time LIMIT 10", rows_eq(10)))
    q.append(("q26", "SELECT search_phrase FROM hits WHERE search_phrase"
              " <> '' ORDER BY search_phrase LIMIT 10", rows_eq(10)))
    q.append(("q27", "SELECT search_phrase FROM hits WHERE search_phrase"
              " <> '' ORDER BY time, search_phrase LIMIT 10",
              rows_eq(10)))
    um = url != ""
    q.append(("q28", "SELECT counter_id, avg(length(url)) AS l, count(*)"
              " AS c FROM hits WHERE url <> '' GROUP BY counter_id "
              "HAVING count(*) > 1000 ORDER BY l DESC LIMIT 25",
              None))
    q.append(("q29", "SELECT regexp_replace(referer, "
              "'^https?://(?:www\\.)?([^/]+)/.*$', '\\1') AS k, "
              "avg(length(referer)) AS l, count(*) AS c, min(referer) "
              "FROM hits WHERE referer <> '' GROUP BY k HAVING count(*) "
              "> 1000 ORDER BY l DESC LIMIT 25", None))
    q.append(("q30", "SELECT " + ", ".join(
        f"sum(resolution_width + {i})" for i in range(0, 90, 30))
        + " FROM hits", scalar_eq(int(rw.sum()))))
    q.append(("q31", "SELECT search_engine_id, client_ip, count(*) AS c,"
              " sum(is_refresh), avg(resolution_width) FROM hits WHERE "
              "search_phrase <> '' GROUP BY search_engine_id, client_ip "
              "ORDER BY c DESC LIMIT 10",
              topk_col("c", topc([a["search_engine_id"],
                                  a["client_ip"]], sel=sm))))
    q.append(("q32", "SELECT watch_id, client_ip, count(*) AS c, "
              "sum(is_refresh), avg(resolution_width) FROM hits WHERE "
              "search_phrase <> '' GROUP BY watch_id, client_ip "
              "ORDER BY c DESC LIMIT 10",
              topk_col("c", topc([a["watch_id"], a["client_ip"]],
                                 sel=sm))))
    q.append(("q33", "SELECT url, count(*) AS c FROM hits GROUP BY url "
              "ORDER BY c DESC LIMIT 10", topk_col("c", topc([url]))))
    q.append(("q34", "SELECT 1 AS one, url, count(*) AS c FROM hits "
              "GROUP BY one, url ORDER BY c DESC LIMIT 10",
              topk_col("c", topc([url]))))
    q.append(("q35", "SELECT client_ip, client_ip - 1, client_ip - 2, "
              "client_ip - 3, count(*) AS c FROM hits GROUP BY "
              "client_ip, client_ip - 1, client_ip - 2, client_ip - 3 "
              "ORDER BY c DESC LIMIT 10",
              topk_col("c", topc([a["client_ip"]]))))
    lo = BASE_TS + 5 * DAY_NS
    hi = BASE_TS + 12 * DAY_NS
    range_m = ((a["time"] >= lo) & (a["time"] <= hi)
               & (a["counter_id"] == 62))
    q36m = range_m & (a["dont_count_hits"] == 0) \
        & (a["is_refresh"] == 0) & um
    q.append(("q36", f"SELECT url, count(*) AS pv FROM hits WHERE "
              f"counter_id = 62 AND time >= {lo} AND time <= {hi} AND "
              "dont_count_hits = 0 AND is_refresh = 0 AND url <> '' "
              "GROUP BY url ORDER BY pv DESC LIMIT 10",
              topk_col("pv", topc([url], sel=q36m))))
    q37m = range_m & (a["dont_count_hits"] == 0) & (a["is_refresh"] == 0)
    q.append(("q37", f"SELECT title, count(*) AS pv FROM hits WHERE "
              f"counter_id = 62 AND time >= {lo} AND time <= {hi} AND "
              "dont_count_hits = 0 AND is_refresh = 0 AND title <> '' "
              "GROUP BY title ORDER BY pv DESC LIMIT 10",
              topk_col("pv", topc([a["title"]], sel=q37m))))
    q.append(("q38", f"SELECT url, count(*) AS pv FROM hits WHERE "
              f"counter_id = 62 AND time >= {lo} AND time <= {hi} AND "
              "is_refresh = 0 AND is_link <> 0 AND is_download = 0 "
              "GROUP BY url ORDER BY pv DESC LIMIT 10 OFFSET 100",
              None))
    q.append(("q39", "SELECT trafic_source_id, search_engine_id, "
              "adv_engine_id, CASE WHEN (search_engine_id = 0 AND "
              "adv_engine_id = 0) THEN referer ELSE '' END AS src, url "
              f"AS dst, count(*) AS pv FROM hits WHERE counter_id = 62 "
              f"AND time >= {lo} AND time <= {hi} AND is_refresh = 0 "
              "GROUP BY trafic_source_id, search_engine_id, "
              "adv_engine_id, src, dst ORDER BY pv DESC LIMIT 10 "
              "OFFSET 100", None))
    q.append(("q40", f"SELECT url_hash, date_bin(INTERVAL '24 hours', "
              f"time) AS d, count(*) AS pv FROM hits WHERE counter_id = "
              f"62 AND time >= {lo} AND time <= {hi} AND is_refresh = 0 "
              "AND trafic_source_id IN (-1, 6) AND referer_hash = 33 "
              "GROUP BY url_hash, d ORDER BY pv DESC LIMIT 10 OFFSET 10",
              None))
    q.append(("q41", f"SELECT window_client_width, window_client_height,"
              f" count(*) AS pv FROM hits WHERE counter_id = 62 AND "
              f"time >= {lo} AND time <= {hi} AND is_refresh = 0 AND "
              "dont_count_hits = 0 AND url_hash = 22 GROUP BY "
              "window_client_width, window_client_height ORDER BY pv "
              "DESC LIMIT 10 OFFSET 100", None))
    q42m = ((a["time"] >= BASE_TS + 7 * DAY_NS)
            & (a["time"] <= BASE_TS + 9 * DAY_NS)
            & (a["counter_id"] == 62) & (a["is_refresh"] == 0)
            & (a["dont_count_hits"] == 0))
    q.append(("q42", "SELECT date_trunc('minute', time) AS m, count(*) "
              f"AS pv FROM hits WHERE counter_id = 62 AND time >= "
              f"{BASE_TS + 7 * DAY_NS} AND time <= "
              f"{BASE_TS + 9 * DAY_NS} AND is_refresh = 0 AND "
              "dont_count_hits = 0 GROUP BY m ORDER BY m LIMIT 10 "
              "OFFSET 10", None))
    q.append(("q43", "SELECT count(*) FROM hits WHERE time >= "
              f"{BASE_TS + 7 * DAY_NS} AND time <= "
              f"{BASE_TS + 9 * DAY_NS}",
              scalar_eq(int(((a["time"] >= BASE_TS + 7 * DAY_NS)
                             & (a["time"] <= BASE_TS + 9 * DAY_NS))
                            .sum()))))

    for name, sql, check in q:
        _run(executor, session, name, sql, check, res, err, stage_out=stg)
    return res, err, stg


# ---------------------------------------------------------------------------
# dashboard steady-state (materialized rollup plane)
# ---------------------------------------------------------------------------
def build_spans(coord, tenant, db, n_rows):
    """OTLP-shaped trace/span table: log search is the workload the
    string plane unlocks. Bodies are templated log lines with rare
    needles ('timeout', 'deadline exceeded') so n-gram page skipping has
    something to prune; span/trace ids exercise prefix and exact lanes."""
    from cnosdb_tpu.models.points import SeriesRows, WriteBatch
    from cnosdb_tpu.models.schema import ValueType
    from cnosdb_tpu.models.series import SeriesKey

    rng = np.random.default_rng(7)
    n = n_rows
    spans = np.array([f"GET /api/v{i}" for i in range(12)] +
                     [f"POST /api/v{i}" for i in range(6)] +
                     ["db.query", "cache.get", "auth.check"], dtype=object)
    bodies = np.array(
        [f"INFO request handled path=/p{i} status=200" for i in range(160)]
        + [f"WARN slow upstream path=/p{i} retry=1" for i in range(24)]
        + ["ERROR upstream timeout path=/p3 attempt=2",
           "ERROR deadline exceeded calling billing",
           "WARN connection reset by peer"], dtype=object)
    body_w = np.concatenate([np.full(160, 1.0), np.full(24, 0.08),
                             np.full(3, 0.004)])
    body_w /= body_w.sum()
    span_idx = rng.integers(0, len(spans), n)
    body_idx = rng.choice(len(bodies), n, p=body_w)
    trace_idx = rng.integers(0, max(n // 8, 2), n)
    dur = rng.integers(50, 500_000, n).astype(np.int64)
    status = np.where(rng.random(n) < 0.97, "OK", "ERROR").astype(object)
    ts = BASE_TS + rng.integers(0, 7 * DAY_NS // 1000, n).astype(
        np.int64) * 1000
    ts.sort()
    CH = 250_000
    for svc in range(4):
        sel = np.flatnonzero(span_idx % 4 == svc)
        key = SeriesKey("trace_spans", {"service": f"svc_{svc}"})
        for off in range(0, len(sel), CH):
            ix = sel[off:off + CH]
            fields = {
                "trace_id": (int(ValueType.STRING),
                             [f"tr-{i:08d}" for i in trace_idx[ix]]),
                "span_name": (int(ValueType.STRING),
                              list(spans[span_idx[ix]])),
                "status_code": (int(ValueType.STRING), list(status[ix])),
                "body": (int(ValueType.STRING), list(bodies[body_idx[ix]])),
                "duration_us": (int(ValueType.INTEGER), dur[ix]),
            }
            wb = WriteBatch()
            wb.add_series("trace_spans", SeriesRows(key, ts[ix], fields))
            coord.write_points(tenant, db, wb)
    coord.engine.flush_all()
    coord.engine.compact_all()
    return {
        "service": np.array([f"svc_{i % 4}" for i in span_idx],
                            dtype=object),
        "trace_id": np.array([f"tr-{i:08d}" for i in trace_idx],
                             dtype=object),
        "span_name": spans[span_idx],
        "status_code": status,
        "body": bodies[body_idx],
        "duration_us": dur,
        "time": ts,
    }


def run_logsearch(executor, session, a) -> tuple[dict, dict, dict]:
    """Log/trace search shapes over the OTLP-style spans table, each
    oracle-checked against numpy over the ingested arrays (the oracle
    never goes through the string plane)."""
    res: dict = {}
    err: dict = {}
    stg: dict = {}
    body = a["body"]
    span = a["span_name"]

    def contains(hay, needle):
        return np.char.find(hay.astype(str), needle) >= 0

    n_timeout = int(contains(body, "timeout").sum())
    n_error = int(np.char.startswith(body.astype(str), "ERROR").sum())
    err_by_svc = {}
    em = contains(body, "ERROR")
    for s in np.unique(a["service"][em]):
        err_by_svc[s] = int((a["service"][em] == s).sum())
    n_span = int((span == "db.query").sum())
    tr_prefix = a["trace_id"][0][:6]
    n_trace = int(np.char.startswith(a["trace_id"].astype(str),
                                     tr_prefix).sum())

    def scalar_eq(val):
        def chk(rs):
            got = int(np.asarray(rs.columns[0])[0])
            assert got == val, f"{got} != {val}"
        return chk

    def chk_topdur(rs):
        d = a["duration_us"]
        maxes = {s: float(d[span == s].max()) for s in np.unique(span)}
        got = list(zip(_col(rs, "span_name"),
                       (float(v) for v in _col(rs, "d"))))
        assert len(got) == 5, got
        assert all(maxes[s] == v for s, v in got), got
        vals = [v for _s, v in got]
        floor = sorted(maxes.values(), reverse=True)[4]
        assert vals == sorted(vals, reverse=True) and vals[-1] >= floor, got

    def chk_errsvc(rs):
        got = dict(zip(_col(rs, "service"),
                       (int(v) for v in _col(rs, "c"))))
        assert got == err_by_svc, f"{got} != {err_by_svc}"

    _run(executor, session, "ls1_needle",
         "SELECT count(*) FROM trace_spans WHERE body LIKE '%timeout%'",
         scalar_eq(n_timeout), res, err, stg)
    _run(executor, session, "ls2_prefix",
         "SELECT count(*) FROM trace_spans WHERE body LIKE 'ERROR%'",
         scalar_eq(n_error), res, err, stg)
    _run(executor, session, "ls3_exact",
         "SELECT count(*) FROM trace_spans WHERE span_name LIKE 'db.query'",
         scalar_eq(n_span), res, err, stg)
    _run(executor, session, "ls4_err_by_service",
         "SELECT service, count(*) AS c FROM trace_spans "
         "WHERE body LIKE '%ERROR%' GROUP BY service ORDER BY service",
         chk_errsvc, res, err, stg)
    _run(executor, session, "ls5_slow_spans",
         "SELECT span_name, max(duration_us) AS d FROM trace_spans "
         "GROUP BY span_name ORDER BY d DESC LIMIT 5",
         chk_topdur, res, err, stg)
    _run(executor, session, "ls6_trace_prefix",
         f"SELECT count(*) FROM trace_spans "
         f"WHERE trace_id LIKE '{tr_prefix}%'",
         scalar_eq(n_trace), res, err, stg)
    return res, err, stg


def run_dashboard(executor, coord, tenant, db, session) -> dict:
    """The workload materialized rollups exist for: a dashboard panel
    re-issuing the same full-history time-bucketed group-by as history
    grows 10×. Each step appends a chunk, flushes, advances the view
    watermark deterministically, then times the panel query with the
    subsumption rewrite on vs off (both oracle-checked against numpy
    over the full arrays). With the view, only the unsealed tail is
    scanned raw, so view_ms should stay flat while noview_ms grows
    with history; view_growth is last/first view_ms as the headline."""
    from cnosdb_tpu.models.points import SeriesRows, WriteBatch
    from cnosdb_tpu.models.schema import ValueType
    from cnosdb_tpu.models.series import SeriesKey
    from cnosdb_tpu.sql import matview as _mv

    rng = np.random.default_rng(23)
    n_hosts = 8
    steps = 10
    chunk = max(1000, SUITE_ROWS // 100)      # ×10 over the run
    delay_ns = 10 * 1_000_000_000

    # the dataset is historical (BASE_TS = 2022): the wall-clock
    # background maintainer would seal past the data's end and strand
    # appended rows below the hwm — refresh deterministically instead
    prev_auto = os.environ.get("CNOSDB_MATVIEW_AUTO")
    os.environ["CNOSDB_MATVIEW_AUTO"] = "0"

    executor.execute_one(
        "CREATE TABLE IF NOT EXISTS dash (value DOUBLE, TAGS(host))",
        session)
    executor.execute_one(
        "CREATE MATERIALIZED VIEW bench_dash WATERMARK DELAY '10s' AS "
        "SELECT date_bin(INTERVAL '1 minute', time) AS t, host, "
        "sum(value) AS s, count(value) AS c FROM dash GROUP BY t, host",
        session)
    me = executor.matview_engine()

    sql = ("SELECT date_bin(INTERVAL '1 minute', time) AS t, host, "
           "sum(value) AS s, count(value) AS c FROM dash "
           "GROUP BY t, host ORDER BY t, host")
    out: dict = {"history_rows": [], "view_ms": [], "noview_ms": []}
    all_ts: list = []
    all_host: list = []
    all_val: list = []
    written = 0
    for _step in range(steps):
        per = chunk // n_hosts
        for h in range(n_hosts):
            ts = BASE_TS + (written // n_hosts + np.arange(per,
                            dtype=np.int64)) * 1_000_000_000
            val = rng.normal(50, 10, per)
            wb = WriteBatch()
            wb.add_series("dash", SeriesRows(
                SeriesKey("dash", {"host": f"host_{h}"}), ts,
                {"value": (int(ValueType.FLOAT), val)}))
            coord.write_points(tenant, db, wb)
            all_ts.append(ts)
            all_host.append(np.full(per, h))
            all_val.append(val)
        written += per * n_hosts
        coord.engine.flush_all()
        me.refresh("bench_dash",
                   now_ns=int(max(t[-1] for t in all_ts)) + delay_ns + 1)

        ts_a = np.concatenate(all_ts)
        host_a = np.concatenate(all_host)
        val_a = np.concatenate(all_val)
        bucket = ts_a // 60_000_000_000 * 60_000_000_000

        def check(rs, host_a=host_a, val_a=val_a, bucket=bucket):
            assert rs.n_rows == len(set(zip(bucket.tolist(),
                                            host_a.tolist()))), \
                f"group count {rs.n_rows}"
            assert np.isclose(float(np.sum(_col(rs, "s"))),
                              float(val_a.sum()), rtol=1e-9), "sum drift"
            assert int(np.sum(_col(rs, "c"))) == len(val_a), "count drift"

        hits0 = _mv.counters_snapshot().get("rewrite_hit", 0)
        timings = {}
        for mode, enabled in (("view_ms", True), ("noview_ms", False)):
            executor.matview_rewrite_enabled = enabled
            executor.execute_one(sql, session)            # warm-up
            t0 = time.perf_counter()
            rs = executor.execute_one(sql, session)
            timings[mode] = round((time.perf_counter() - t0) * 1e3, 2)
            check(rs)
        executor.matview_rewrite_enabled = True
        hits = _mv.counters_snapshot().get("rewrite_hit", 0) - hits0
        out["history_rows"].append(written)
        out["view_ms"].append(timings["view_ms"])
        out["noview_ms"].append(timings["noview_ms"])
        out.setdefault("view_hits", []).append(hits)

    # 2 rewriteable queries per step (warm-up + timed) in view mode
    out["view_hit_ratio"] = round(sum(out["view_hits"]) / (2 * steps), 3)
    out["view_growth"] = round(out["view_ms"][-1]
                               / max(out["view_ms"][0], 1e-6), 2)
    out["noview_growth"] = round(out["noview_ms"][-1]
                                 / max(out["noview_ms"][0], 1e-6), 2)
    executor.execute_one("DROP MATERIALIZED VIEW bench_dash", session)
    if prev_auto is None:
        os.environ.pop("CNOSDB_MATVIEW_AUTO", None)
    else:
        os.environ["CNOSDB_MATVIEW_AUTO"] = prev_auto
    return out


def run_coldscan(executor, coord, tenant, db, session) -> dict:
    """Mixed hot/cold scan (tiered object-store plane): half the history
    ages into a LocalStore "bucket", then the same oracle-checked
    group-by runs all-hot, mixed with a cold block cache, and mixed
    warm. Headline: cold_over_hot (acceptance: ≤ 3×) plus the near-data
    pruning counters — pages pruned locally, bytes downloaded vs stored,
    block-cache hit ratio."""
    import tempfile

    from cnosdb_tpu.models.points import SeriesRows, WriteBatch
    from cnosdb_tpu.models.schema import ValueType
    from cnosdb_tpu.models.series import SeriesKey
    from cnosdb_tpu.storage import tiering

    rng = np.random.default_rng(31)
    n_hosts = 4
    chunk = max(2000, SUITE_ROWS // 50)
    per = chunk // n_hosts
    boundary = BASE_TS + 30 * DAY_NS      # old half < boundary < new half

    executor.execute_one(
        "CREATE TABLE IF NOT EXISTS cold_m (value DOUBLE, TAGS(host))",
        session)
    total = {"n": 0, "s": 0.0}
    # old half: 5 sealed files compacted to L1 (what tiers); new half:
    # recent deltas left at L0 so compaction can't merge across the
    # boundary and tiering (level ≥ 1) only ages the old file
    for compact, t0 in ((True, BASE_TS), (False, boundary + DAY_NS)):
        for step in range(5):
            for h in range(n_hosts):
                ts = t0 + (step * per + np.arange(per, dtype=np.int64)) \
                    * 1_000_000_000
                val = rng.normal(50, 10, per)
                wb = WriteBatch()
                wb.add_series("cold_m", SeriesRows(
                    SeriesKey("cold_m", {"host": f"host_{h}"}), ts,
                    {"value": (int(ValueType.FLOAT), val)}))
                coord.write_points(tenant, db, wb)
                total["n"] += per
                total["s"] += float(val.sum())
            coord.engine.flush_all()
        if compact:
            coord.engine.compact_all()

    sql = ("SELECT host, count(value) AS c, sum(value) AS s FROM cold_m "
           "GROUP BY host ORDER BY host")

    def timed():
        with coord._scan_cache_lock:
            coord._scan_cache.clear()
        t0 = time.perf_counter()
        rs = executor.execute_one(sql, session)
        ms = round((time.perf_counter() - t0) * 1e3, 2)
        assert int(np.sum(_col(rs, "c"))) == total["n"], "count drift"
        assert np.isclose(float(np.sum(_col(rs, "s"))), total["s"],
                          rtol=1e-9), "sum drift"
        return ms

    out: dict = {"rows": total["n"]}
    timed()                                   # warm-up, decoders jitted
    out["hot_ms"] = timed()

    bucket = tempfile.mkdtemp(prefix="cnosdb_cold_bench_")
    tiering.configure(bucket)
    tiering.counters_reset()
    tiering.block_cache_clear()
    try:
        vnodes = list(coord.engine.vnodes.values())
        tiered = sum(tiering.tier_vnode(v, boundary_ns=boundary)
                     for v in vnodes)
        out["files_tiered"] = tiered
        snap = tiering.cold_tier_snapshot()
        out["bytes_tiered"] = snap.get(("tier", "bytes_uploaded"), 0)

        tiering.counters_reset()
        out["cold_ms"] = timed()              # cold block cache
        snap = tiering.cold_tier_snapshot()
        out["cold_range_gets"] = snap.get(("fetch", "range_gets"), 0)
        out["cold_pages_fetched"] = snap.get(("fetch", "pages_fetched"), 0)
        out["cold_bytes_downloaded"] = snap.get(
            ("fetch", "bytes_downloaded"), 0)
        out["cold_pages_pruned"] = snap.get(("prune", "pages_pruned"), 0)

        # near-data pruning: a recent-window query must answer without
        # touching the store — every cold page is excluded locally
        tiering.counters_reset()
        with coord._scan_cache_lock:
            coord._scan_cache.clear()
        tiering.block_cache_clear()
        rs = executor.execute_one(
            f"SELECT count(value) AS c FROM cold_m "
            f"WHERE time >= {boundary}", session)
        assert int(np.sum(_col(rs, "c"))) == total["n"] // 2, "window drift"
        snap = tiering.cold_tier_snapshot()
        out["window_pages_pruned"] = snap.get(("prune", "pages_pruned"), 0)
        out["window_bytes_downloaded"] = snap.get(
            ("fetch", "bytes_downloaded"), 0)

        # compressed-domain A/B on the cold half: a stats-answerable
        # aggregate must come back bit-identical with the lane on and
        # off (CNOSDB_COMPRESSED_DOMAIN=0 = the decode-lane oracle), and
        # the lane run must download a fraction of the oracle's bytes —
        # answered pages never leave the object store
        from cnosdb_tpu.storage import compressed_domain as _cd

        def cold_once(alias):
            # a distinct alias per pass keeps the serving-plane result
            # cache out of the A/B — same SQL text would be served from
            # the token-revalidated cache with zero bytes downloaded
            with coord._scan_cache_lock:
                coord._scan_cache.clear()
            tiering.block_cache_clear()
            tiering.counters_reset()
            t0 = time.perf_counter()
            rs = executor.execute_one(
                f"SELECT count(value) AS {alias} FROM cold_m", session)
            ms = round((time.perf_counter() - t0) * 1e3, 2)
            snap2 = tiering.cold_tier_snapshot()
            return (int(np.sum(_col(rs, alias))), ms,
                    snap2.get(("fetch", "bytes_downloaded"), 0))

        before_cd = _cd.outcomes_snapshot()
        lane_c, out["compressed_ms"], lane_dl = cold_once("c_lane")
        after_cd = _cd.outcomes_snapshot()
        out["compressed_pages_answered"] = sum(
            n - before_cd.get(k, 0) for k, n in after_cd.items()
            if k[0] in ("meta", "closed", "skip"))
        prev_cd = os.environ.get("CNOSDB_COMPRESSED_DOMAIN")
        os.environ["CNOSDB_COMPRESSED_DOMAIN"] = "0"
        try:
            oracle_c, out["compressed_oracle_ms"], oracle_dl = \
                cold_once("c_oracle")
        finally:
            if prev_cd is None:
                os.environ.pop("CNOSDB_COMPRESSED_DOMAIN", None)
            else:
                os.environ["CNOSDB_COMPRESSED_DOMAIN"] = prev_cd
        assert lane_c == oracle_c == total["n"], "compressed A/B drift"
        out["compressed_bytes_downloaded"] = lane_dl
        out["compressed_oracle_bytes_downloaded"] = oracle_dl
        out["compressed_bytes_ratio"] = round(
            oracle_dl / max(lane_dl, 1), 1)

        timed()                               # refill the block cache
        tiering.counters_reset()
        out["cold_warm_ms"] = timed()         # served from the block cache
        snap = tiering.cold_tier_snapshot()
        hits = snap.get(("cache", "hit"), 0)
        misses = snap.get(("cache", "miss"), 0)
        out["block_cache_hit_ratio"] = round(
            hits / max(hits + misses, 1), 3)
        out["warm_bytes_downloaded"] = snap.get(
            ("fetch", "bytes_downloaded"), 0)
        out["cold_over_hot"] = round(
            out["cold_ms"] / max(out["hot_ms"], 1e-6), 2)
    finally:
        # hand the engine back hot so later phases never need the bucket
        for v in list(coord.engine.vnodes.values()):
            tiering.rehydrate_vnode(v)
        tiering.configure(None)
    return out


def run_pointqps(executor, coord, tenant, db, session) -> dict:
    """High-QPS serving-plane benchmark: a closed loop of threads
    re-issuing point-query shapes against a hosts×rows table. Warm
    requests should land in the ScanToken-keyed result cache (target:
    ≥10k qps, p99 < 20 ms, hit ratio ≥ 0.9); a second phase issues
    unique-literal variants under forced micro-batching so the fused
    path and its width histogram get exercised too. Counters are read
    as deltas — the serving counters are process-global."""
    import threading as _threading

    from cnosdb_tpu.models.points import SeriesRows, WriteBatch
    from cnosdb_tpu.models.schema import ValueType
    from cnosdb_tpu.models.series import SeriesKey
    from cnosdb_tpu.server import serving as _serving

    sv = getattr(executor, "serving", None)
    if sv is None:
        return {"disabled": True}       # CNOSDB_SERVING=0 A/B runs
    rng = np.random.default_rng(47)
    n_hosts = 64
    per = 64
    executor.execute_one(
        "CREATE TABLE IF NOT EXISTS pq (value DOUBLE, TAGS(host))",
        session)
    for h in range(n_hosts):
        ts = BASE_TS + np.arange(per, dtype=np.int64) * 1_000_000_000
        wb = WriteBatch()
        wb.add_series("pq", SeriesRows(
            SeriesKey("pq", {"host": f"host_{h}"}), ts,
            {"value": (int(ValueType.FLOAT), rng.normal(50, 10, per))}))
        coord.write_points(tenant, db, wb)
    coord.engine.flush_all()

    qs = [f"SELECT time, value FROM pq WHERE host = 'host_{h}'"
          for h in range(n_hosts)]
    for q in qs:                        # warm plan + result caches
        rs = executor.execute_one(q, session)
        assert rs.n_rows == per, f"point query returned {rs.n_rows}"

    threads = 4
    per_thread = 5000
    orders = [rng.integers(0, n_hosts, per_thread) for _ in range(threads)]
    lat: list[list[float]] = [[] for _ in range(threads)]
    gate = _threading.Barrier(threads + 1)
    c0 = _serving.counters_snapshot()

    def worker(i):
        mine = lat[i]
        gate.wait()
        for j in orders[i]:
            t0 = time.perf_counter()
            executor.execute_one(qs[j], session)
            mine.append(time.perf_counter() - t0)

    ths = [_threading.Thread(target=worker, args=(i,))
           for i in range(threads)]
    for t in ths:
        t.start()
    gate.wait()
    t0 = time.perf_counter()
    for t in ths:
        t.join()
    elapsed = time.perf_counter() - t0

    c1 = _serving.counters_snapshot()

    def delta(layer, outcome):
        return (c1.get((layer, outcome), 0) - c0.get((layer, outcome), 0))

    hits, misses = delta("result_cache", "hit"), delta("result_cache",
                                                       "miss")
    all_lat = np.sort(np.concatenate([np.asarray(x) for x in lat]))
    total = int(len(all_lat))
    out = {
        "threads": threads,
        "requests": total,
        "point_qps": round(total / max(elapsed, 1e-9), 1),
        "p50_ms": round(float(np.percentile(all_lat, 50)) * 1e3, 3),
        "p99_ms": round(float(np.percentile(all_lat, 99)) * 1e3, 3),
        "hit_ratio": round(hits / max(hits + misses, 1), 4),
        "plan_rebinds": delta("plan_cache", "hit_rebind"),
    }

    # ---- fused micro-batching phase: unique literals defeat the result
    # cache so every request reaches the batch rendezvous
    w0 = _serving.width_histogram()
    prev_force, prev_win = sv.batcher.force, sv.batcher.window_s
    sv.batcher.force = True
    sv.batcher.window_s = 0.002
    fthreads, fper = 8, 40
    fgate = _threading.Barrier(fthreads + 1)
    ferr: list = []

    def fworker(i):
        fgate.wait()
        for k in range(fper):
            u = i * fper + k
            try:
                executor.execute_one(
                    f"SELECT time, value FROM pq WHERE "
                    f"host = 'host_{u % n_hosts}' AND value > -{u}.0",
                    session)
            except Exception as e:      # surfaced in the report
                ferr.append(repr(e)[:120])
                return
    fths = [_threading.Thread(target=fworker, args=(i,))
            for i in range(fthreads)]
    for t in fths:
        t.start()
    fgate.wait()
    ft0 = time.perf_counter()
    for t in fths:
        t.join()
    felapsed = time.perf_counter() - ft0
    sv.batcher.force, sv.batcher.window_s = prev_force, prev_win
    w1 = _serving.width_histogram()
    c2 = _serving.counters_snapshot()
    out["fused_widths"] = {str(k): w1.get(k, 0) - w0.get(k, 0)
                           for k in sorted(w1)
                           if w1.get(k, 0) - w0.get(k, 0)}
    out["fused_queries"] = (c2.get(("batch", "fused"), 0)
                            - c1.get(("batch", "fused"), 0))
    out["fused_qps"] = round(fthreads * fper / max(felapsed, 1e-9), 1)
    if ferr:
        out["fused_errors"] = ferr[:5]
    return out


def run_straggler() -> dict:
    """Gray-failure tail-latency suite (parallel/health.py plane): a
    2-replica straggler bed (chaos/straggler.py — real wire, real
    engine, synthetic placement) scanned in three phases:

      * healthy, hedging on — the tail must NOT pay for the insurance:
        `healthy_hedges_fired` is expected to be 0 (suppression + the
        adaptive p95 trigger prove hedging is tail-only);
      * the PINNED primary browned out by `straggle_delay_ms`, hedging
        on — a short unmeasured adaptation stage first
        (`adaptation_hedges` + `adapt_p99_ms`), then the measured
        window: the primary slot follows the raft leader for
        read-your-writes and is never re-routed by health, so every
        scan's first attempt lands on the straggler and the hedge lane
        must rescue it — `hedged_p99_ms` ≈ hedge trigger + the healthy
        replica's latency (tens of ms, NOT the brownout delay), with
        ~one fired/won/cancelled hedge per scan in `hedged`;
      * same brownout, CNOSDB_HEDGE=0 — the unprotected legacy tail the
        plane exists to cut (p99 ≈ the injected delay; the headline is
        `nohedge_over_healthy` vs `straggler_over_healthy`).

    The scorer keeps its warm sketches into the brownout (a real
    cluster has them when a replica browns out), so the adaptive
    trigger — max(floor, min(p95, 4×p50)), not the raw config floor —
    prices the hedges, and won hedges feed the loser's elapsed-so-far
    back as censored samples that keep the failover/hedge ordering of
    the ALTERNATES honest."""
    import tempfile

    from cnosdb_tpu.chaos.straggler import StragglerBed, batch_bytes
    from cnosdb_tpu.parallel import health

    iters = int(os.environ.get("CNOSDB_BENCH_STRAGGLER_ITERS", "60"))
    delay_ms = float(os.environ.get("CNOSDB_BENCH_STRAGGLER_DELAY_MS",
                                    "120"))
    prev_hedge = os.environ.pop("CNOSDB_HEDGE", None)
    root = tempfile.mkdtemp(prefix="cnosdb_straggler_")
    bed = StragglerBed(root, rows=4000)
    out: dict = {"iters": iters, "straggle_delay_ms": delay_ms}

    def phase(tag, n):
        lat = []
        for i in range(n):
            t0 = time.perf_counter()
            bed.scan_once(qid=f"{tag}-{i}")
            lat.append(time.perf_counter() - t0)
        a = np.sort(np.asarray(lat))
        return (round(float(np.percentile(a, 50)) * 1e3, 2),
                round(float(np.percentile(a, 99)) * 1e3, 2))

    def hedge_counts():
        hedge, _ = health.counters_snapshot()
        agg: dict = {}
        for (outcome, _reason), v in hedge.items():
            agg[outcome] = agg.get(outcome, 0) + v
        return {k: agg.get(k, 0)
                for k in ("fired", "won", "lost", "cancelled",
                          "suppressed")}

    try:
        ref = batch_bytes(bed.scan_once(qid="warm-ref"))
        health.SCORER.reset()
        bed.warm_replicas()               # honest warm samples everywhere
        phase("warm", 12)                 # real p95s in the sketches
        health.reset_counters()
        out["healthy_p50_ms"], out["healthy_p99_ms"] = phase(
            "healthy", iters)
        out["healthy_hedges"] = hedge_counts()

        # brown out the PINNED primary (split targets the leader first
        # — read-your-writes — so health never re-routes the first
        # attempt): the worst case, every scan must be hedge-rescued
        victim = bed.replicas[0]
        victim.delay_s = delay_ms / 1e3
        health.reset_counters()
        _, out["adapt_p99_ms"] = phase("adapt", 8)
        time.sleep(delay_ms / 1e3 + 0.05)   # hedge-loser replies land,
        out["adaptation_hedges"] = hedge_counts()   # scorer sees them
        health.reset_counters()
        out["hedged_p50_ms"], out["hedged_p99_ms"] = phase(
            "straggle", iters)
        out["hedged"] = hedge_counts()
        assert batch_bytes(bed.scan_once(qid="parity")) == ref, \
            "hedged scan result drifted from the healthy baseline"

        os.environ["CNOSDB_HEDGE"] = "0"
        health.SCORER.reset()
        out["nohedge_p50_ms"], out["nohedge_p99_ms"] = phase(
            "legacy", iters)

        out["straggler_over_healthy"] = round(
            out["hedged_p99_ms"] / max(out["healthy_p99_ms"], 1e-6), 2)
        out["nohedge_over_healthy"] = round(
            out["nohedge_p99_ms"] / max(out["healthy_p99_ms"], 1e-6), 2)
    finally:
        if prev_hedge is None:
            os.environ.pop("CNOSDB_HEDGE", None)
        else:
            os.environ["CNOSDB_HEDGE"] = prev_hedge
        bed.close()
    return out


def run_overload(executor, coord, tenant, db, session) -> dict:
    """Memory-governance overload suite (server/memory.py plane): a
    closed-loop mix of ingest writers and wide count(DISTINCT) group-by
    storms, run three times with the broker budget set so the same
    workload sits at 0.5×, 1× and 2× of its measured footprint. Per
    phase it reports the degradation ladder's actions straight from the
    broker counters — pool reclaims, delayed / backpressure-shed /
    fail-closed writes, queued-query sheds, group-state spills — plus
    client-observed p99s and reject counts.

    The correctness headline is `bit_identical`: EVERY storm result in
    every phase (including the 2× phase, where the accumulator spills
    to disk) must equal the legacy `CNOSDB_MEMORY=0` oracle row-for-row
    — memory pressure may slow or shed work, never change an answer.
    The storm queries carry a unique no-op tag predicate so the serving
    result cache cannot answer them; each one reaches the accumulator
    (and its spiller) for real."""
    import threading as _threading

    from cnosdb_tpu.errors import CnosError
    from cnosdb_tpu.models.points import SeriesRows, WriteBatch
    from cnosdb_tpu.models.schema import ValueType
    from cnosdb_tpu.models.series import SeriesKey
    from cnosdb_tpu.server import memory as memgov

    if not memgov.enabled():
        return {"disabled": True}       # CNOSDB_MEMORY=0 A/B runs
    rng = np.random.default_rng(53)
    n_hosts, per = 256, 200
    executor.execute_one(
        "CREATE TABLE IF NOT EXISTS ov (value DOUBLE, TAGS(host))",
        session)
    for h in range(n_hosts):
        ts = BASE_TS + np.arange(per, dtype=np.int64) * 1_000_000_000
        wb = WriteBatch()
        wb.add_series("ov", SeriesRows(
            SeriesKey("ov", {"host": f"host_{h:03d}"}), ts,
            {"value": (int(ValueType.FLOAT), rng.normal(50, 10, per))}))
        coord.write_points(tenant, db, wb)
    coord.engine.flush_all()

    def storm_sql(u: int) -> str:
        # the u-varying predicate matches every row (no host is 'zzN'):
        # same answer, but a fresh ScanToken defeats the result cache
        return (f"SELECT host, count(DISTINCT value), sum(value), "
                f"min(value), max(value) FROM ov WHERE host <> 'zz{u}' "
                f"GROUP BY host")

    # oracle: the governance-off legacy path, once, on the static table
    prev_env = os.environ.get("CNOSDB_MEMORY")
    os.environ["CNOSDB_MEMORY"] = "0"
    try:
        baseline = executor.execute_one(storm_sql(0), session).rows()
    finally:
        if prev_env is None:
            os.environ.pop("CNOSDB_MEMORY", None)
        else:
            os.environ["CNOSDB_MEMORY"] = prev_env
    assert len(baseline) == n_hosts

    def ingest_batch(tag: int) -> WriteBatch:
        ts = (BASE_TS + np.arange(64, dtype=np.int64) * 1_000_000
              + tag * 100_000_000_000)
        wb = WriteBatch()
        for s in range(4):
            wb.add_series("ov_ing", SeriesRows(
                SeriesKey("ov_ing", {"host": f"ing_{(tag + s) % 32}"}), ts,
                {"value": (int(ValueType.FLOAT),
                           rng.normal(0, 1, ts.size))}))
        return wb

    # footprint reference: one dry mixed round at the resting budget
    coord.write_points(tenant, db, ingest_batch(0))
    executor.execute_one(storm_sql(1), session)
    ref_used = max(memgov.BROKER.used(), 1 << 20)
    # group-state estimate mirrors sql/executor._acc_group_bytes — the
    # count(DISTINCT) sets dominate: 64 + 64*len per group
    est_state = n_hosts * (64 + 16 + 64 + 64 * per + 3 * 24)

    prev_group = memgov.GROUP_BYTES
    prev_delay = memgov.WRITE_DELAY_MS
    memgov.WRITE_DELAY_MS = 100     # keep the shed path fast, not 2s
    q_threads, q_iters = 2, 5
    w_threads, w_iters = 2, 10
    out: dict = {"table_rows": n_hosts * per, "ref_used_bytes": ref_used,
                 "group_state_est_bytes": est_state, "phases": {}}
    all_identical = True
    try:
        for factor in (0.5, 1.0, 2.0):
            budget = max(int(ref_used / factor), 1 << 16)
            gbudget = int(est_state / factor)
            memgov.BROKER.resize(budget)
            memgov.GROUP_BYTES = gbudget
            coord.engine.flush_all()    # comparable resting state
            c0 = memgov.counters_snapshot()
            qlat: list[list[float]] = [[] for _ in range(q_threads)]
            wlat: list[list[float]] = [[] for _ in range(w_threads)]
            rejects = [0] * w_threads
            errs: list[str] = []
            bad = [0]
            gate = _threading.Barrier(q_threads + w_threads)

            def qworker(i, tag=int(factor * 10)):
                gate.wait()
                for k in range(q_iters):
                    u = tag * 1000 + i * q_iters + k
                    t0 = time.perf_counter()
                    try:
                        rows = executor.execute_one(
                            storm_sql(u), session).rows()
                    except CnosError as e:
                        errs.append(repr(e)[:120])
                        continue
                    qlat[i].append(time.perf_counter() - t0)
                    if rows != baseline:
                        bad[0] += 1

            def wworker(i, tag=int(factor * 10)):
                gate.wait()
                for k in range(w_iters):
                    t0 = time.perf_counter()
                    try:
                        coord.write_points(
                            tenant, db,
                            ingest_batch(tag * 1000 + i * w_iters + k))
                    except CnosError:   # typed shed — the ladder working
                        rejects[i] += 1
                        time.sleep(0.05)
                        continue
                    wlat[i].append(time.perf_counter() - t0)

            ths = [_threading.Thread(target=qworker, args=(i,))
                   for i in range(q_threads)]
            ths += [_threading.Thread(target=wworker, args=(i,))
                    for i in range(w_threads)]
            for t in ths:
                t.start()
            for t in ths:
                t.join()

            c1 = memgov.counters_snapshot()

            def delta(pool, action):
                return c1.get((pool, action), 0) - c0.get((pool, action), 0)

            qs = np.sort(np.concatenate(
                [np.asarray(x) for x in qlat] or [np.zeros(0)]))
            ws = np.sort(np.concatenate(
                [np.asarray(x) for x in wlat] or [np.zeros(0)]))
            identical = bad[0] == 0 and not errs
            all_identical = all_identical and identical
            out["phases"][f"{factor:g}x"] = {
                "budget_bytes": budget,
                "group_budget_bytes": gbudget,
                "query_ok": int(qs.size),
                "query_p99_ms": round(
                    float(np.percentile(qs, 99)) * 1e3, 2) if qs.size
                else None,
                "write_ok": int(ws.size),
                "write_p99_ms": round(
                    float(np.percentile(ws, 99)) * 1e3, 2) if ws.size
                else None,
                "write_rejects": sum(rejects),
                "spills": delta("query_groups", "spill"),
                "unspills": delta("query_groups", "unspill"),
                "write_delayed": delta("write", "delayed"),
                "write_backpressure_shed": delta("write",
                                                 "backpressure_shed"),
                "write_fail_hard": delta("write", "fail_hard"),
                "queued_shed": delta("admission", "shed_queued"),
                "reclaims": sum(
                    v - c0.get(k, 0) for k, v in c1.items()
                    if k[1] == "reclaim"),
                "bit_identical": identical,
                **({"query_errors": errs[:3]} if errs else {}),
            }
    finally:
        memgov.BROKER.resize(0)         # back to config/auto
        memgov.GROUP_BYTES = prev_group
        memgov.WRITE_DELAY_MS = prev_delay
    out["bit_identical"] = all_identical
    return out


def run_mesh(executor, coord, tenant, db, session) -> dict:
    """Mesh execution plane scaling suite (ops/mesh_exec.py +
    parallel/distributed_agg.py): the TSBS `double_groupby` shape
    (host × 1h-bucket, count/sum/min/max) over an 8-shard table, swept
    across 1 → 2 → 4 → 8 mesh devices via CNOSDB_MESH_DEVICES (get_mesh
    re-reads it per query, so the sweep runs in-process against the same
    scan snapshot), plus the CNOSDB_MESH=0 legacy per-batch kernel
    fan-out + host `_merge_results_vec` as the host-merge baseline.

    Timings are warm steady state: the scan cache and the lane's prep
    cache are hot, so every mesh iteration measures collective + assemble
    and every legacy iteration measures kernel fan-out + host merge —
    the per-stage breakdown (`mesh.collective_ms` vs `kernel_ms` +
    `merge_ms`) is the collective-vs-host-merge comparison the sweep
    exists for.

    Correctness headlines: `bit_identical` (every mesh config's answer
    repr-equals the legacy oracle, so NaN/-0.0/dtype drift would fail)
    and `zero_host_merges` (every engaged query booked
    `cnosdb_mesh_total{merge,collective}` and no host-merge hop).
    `speedup_8x` is p50(1 device) / p50(8 devices); on hosts with fewer
    physical cores than mesh devices the virtual devices timeshare and
    the sweep cannot scale — `host_cores` + `speedup_note` record that
    instead of pretending."""
    from cnosdb_tpu.models.points import SeriesRows, WriteBatch
    from cnosdb_tpu.models.schema import ValueType
    from cnosdb_tpu.models.series import SeriesKey
    from cnosdb_tpu.ops.placement import mesh_devices
    from cnosdb_tpu.parallel import mesh
    from cnosdb_tpu.sql.executor import Session
    from cnosdb_tpu.utils import stages as _stages

    rows = int(os.environ.get("CNOSDB_BENCH_MESH_ROWS", "1000000"))
    iters = int(os.environ.get("CNOSDB_BENCH_MESH_ITERS", "5"))
    n_hosts = 32
    executor.execute_one(
        "CREATE DATABASE IF NOT EXISTS meshbench WITH SHARD 8 REPLICA 1",
        session)
    ms = Session(database="meshbench")
    per = max(64, rows // n_hosts)
    span_ns = 48 * 3_600_000_000_000            # ~48 one-hour buckets
    step = max(span_ns // per, 1)
    rng = np.random.default_rng(41)
    for h in range(n_hosts):
        ts = BASE_TS + np.arange(per, dtype=np.int64) * step + h
        wb = WriteBatch()
        wb.add_series("dg", SeriesRows(
            SeriesKey("dg", {"host": f"host_{h:02d}"}), ts,
            {"v": (int(ValueType.FLOAT), rng.normal(50, 10, per))}))
        coord.write_points(tenant, "meshbench", wb)
    coord.engine.flush_all()
    coord.engine.compact_all()

    q = ("SELECT host, date_bin(INTERVAL '1 hour', time) AS t, "
         "count(*) AS c, sum(v) AS sv, min(v) AS mn, max(v) AS mx "
         "FROM dg GROUP BY host, t")

    def norm(rs):
        return (rs.names, [repr(c.tolist()) for c in rs.columns],
                [str(c.dtype) for c in rs.columns])

    keep_stages = ("kernel_ms", "merge_ms", "finalize_ms",
                   "mesh.plan_ms", "mesh.upload_ms", "mesh.collective_ms",
                   "mesh.assemble_ms", "mesh.plan_cache_hit",
                   "mesh.plan_cache_miss")

    def timed_pass():
        """→ (p50_ms, p99_ms, mean per-stage ms, outcome deltas, norm)."""
        executor.execute_one(q, ms)     # scan + prep caches, jit warm
        executor.execute_one(q, ms)     # settled steady state
        c0 = mesh.outcomes_snapshot()
        lat, snaps, rs = [], [], None
        for _ in range(iters):
            prof = _stages.QueryProfile()
            t0 = time.perf_counter()
            with _stages.profile_scope(prof):
                rs = executor.execute_one(q, ms)
            lat.append(time.perf_counter() - t0)
            snaps.append(prof.snapshot())
        c1 = mesh.outcomes_snapshot()
        a = np.sort(np.asarray(lat))
        stg = {}
        for k in keep_stages:
            tot = sum(s.get(k, 0) for s in snaps)
            if tot:
                stg[k] = round(tot / iters, 3)
        outcomes = {f"{lane}:{reason}": v - c0.get((lane, reason), 0)
                    for (lane, reason), v in c1.items()
                    if v - c0.get((lane, reason), 0)}
        return (round(float(np.percentile(a, 50)) * 1e3, 2),
                round(float(np.percentile(a, 99)) * 1e3, 2),
                stg, outcomes, norm(rs))

    knobs = ("CNOSDB_MESH", "CNOSDB_MESH_DEVICES",
             "CNOSDB_MESH_MIN_DEVICES", "CNOSDB_MESH_MIN_ROWS")
    prev_env = {k: os.environ.get(k) for k in knobs}
    prev_serving = executor.serving
    # repeats must reach the aggregate path, not the serving result cache
    executor.serving = None
    avail = len(mesh_devices())
    out: dict = {"rows": n_hosts * per, "hosts": n_hosts, "iters": iters,
                 "host_cores": len(os.sched_getaffinity(0)),
                 "devices_available": avail, "devices": {}}
    identical = True
    zero_host = True
    try:
        os.environ["CNOSDB_MESH_MIN_ROWS"] = "0"
        os.environ["CNOSDB_MESH_MIN_DEVICES"] = "1"

        # legacy host-merge baseline: per-batch kernels + vec merge
        os.environ["CNOSDB_MESH"] = "0"
        p50, p99, stg, outc, oracle = timed_pass()
        assert outc.get("exec:engaged", 0) == 0, outc
        out["legacy"] = {"p50_ms": p50, "p99_ms": p99, "stages": stg}

        os.environ["CNOSDB_MESH"] = "1"
        for d in (1, 2, 4, 8):
            if d > avail:
                out["devices"][str(d)] = {
                    "skipped": f"only {avail} devices in the pool"}
                continue
            os.environ["CNOSDB_MESH_DEVICES"] = str(d)
            p50, p99, stg, outc, got = timed_pass()
            engaged = outc.get("exec:engaged", 0)
            ok = engaged == iters \
                and outc.get("merge:collective", 0) == engaged \
                and not outc.get("merge:host", 0)
            zero_host = zero_host and ok
            identical = identical and got == oracle
            out["devices"][str(d)] = {
                "p50_ms": p50, "p99_ms": p99, "stages": stg,
                "outcomes": outc, "bit_identical": got == oracle}
        d1 = out["devices"].get("1", {}).get("p50_ms")
        d8 = out["devices"].get("8", {}).get("p50_ms")
        if d1 and d8:
            out["speedup_8x"] = round(d1 / d8, 2)
            out["speedup_vs_host_merge"] = round(
                out["legacy"]["p50_ms"] / d8, 2)
            if out["speedup_8x"] < 3.0 and out["host_cores"] < 8:
                out["speedup_note"] = (
                    f"{out['host_cores']} physical core(s) timeshare all "
                    f"8 virtual devices — the collective runs its shard "
                    f"programs serially here; scaling needs >= one core "
                    f"per mesh device")
    finally:
        executor.serving = prev_serving
        for k, v in prev_env.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    out["bit_identical"] = identical
    out["zero_host_merges"] = zero_host
    return out


def run_suites(executor, coord, tenant, db, session) -> dict:
    out: dict = {}
    t0 = time.perf_counter()
    hits = build_hits(coord, tenant, db, SUITE_ROWS)
    readings = build_readings(coord, tenant, db, SUITE_ROWS // 4)
    out["suite_build_s"] = round(time.perf_counter() - t0, 1)
    cb, cb_err, cb_stg = run_clickbench(executor, session, hits)
    ts, ts_err = run_tsbs(executor, session, readings)
    out["clickbench_ms"] = cb
    out["clickbench_stages"] = cb_stg
    out["tsbs_iot_ms"] = ts
    errs = {**{f"cb:{k}": v for k, v in cb_err.items()},
            **{f"tsbs:{k}": v for k, v in ts_err.items()}}
    if errs:
        out["suite_errors"] = errs
    out["clickbench_pass"] = f"{len(cb)}/43"
    out["tsbs_pass"] = f"{len(ts)}/13"
    try:
        spans = build_spans(coord, tenant, db, SUITE_ROWS // 4)
        ls, ls_err, ls_stg = run_logsearch(executor, session, spans)
        out["logsearch_ms"] = ls
        out["logsearch_stages"] = ls_stg
        out["logsearch_pass"] = f"{len(ls)}/6"
        if ls_err:
            out.setdefault("suite_errors", {}).update(
                {f"ls:{k}": v for k, v in ls_err.items()})
    except Exception as e:   # string-plane failure must not sink the run
        out["logsearch_pass"] = {"error": repr(e)[:200]}
    try:
        out["dashboard"] = run_dashboard(executor, coord, tenant, db,
                                         session)
    except Exception as e:   # rollup-tier failure must not sink the run
        out["dashboard"] = {"error": repr(e)[:200]}
    try:
        out["coldscan"] = run_coldscan(executor, coord, tenant, db,
                                       session)
    except Exception as e:   # cold-tier failure must not sink the run
        out["coldscan"] = {"error": repr(e)[:200]}
    try:
        out["pointqps"] = run_pointqps(executor, coord, tenant, db,
                                       session)
    except Exception as e:   # serving-plane failure must not sink the run
        out["pointqps"] = {"error": repr(e)[:200]}
    try:
        out["straggler"] = run_straggler()   # self-contained bed
    except Exception as e:   # gray-failure plane must not sink the run
        out["straggler"] = {"error": repr(e)[:200]}
    try:
        out["overload"] = run_overload(executor, coord, tenant, db,
                                       session)
    except Exception as e:   # memory-governance plane must not sink it
        out["overload"] = {"error": repr(e)[:200]}
    try:
        out["mesh"] = run_mesh(executor, coord, tenant, db, session)
    except Exception as e:   # mesh execution plane must not sink the run
        out["mesh"] = {"error": repr(e)[:200]}
    return out
