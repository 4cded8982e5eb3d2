// Fused segment aggregation: the CPU twin of the device scan-aggregate
// kernel (ops/fused.py) and the replacement for the numpy host pipeline's
// multi-pass derivation (bucket ids → segment ids → masked reductions).
//
// One pass over the scan batch computes, per segment
//   seg = group_lut[sid_ordinal[i]] * n_buckets
//         + (ts[i] - origin) / interval - bmin
// the presence (rows), count (valid rows), sum, min and max of a float64
// column — parallelized over row ranges with per-thread accumulators and
// a tree-free final reduce. This is the hot loop of the reference's
// read pipeline (tskv/src/reader/iterator.rs:94-121 + DataFusion partial
// AggregateExec) collapsed into one cache-friendly sweep.
//
// Exact-int sums: int64 columns accumulate into int64 (wrap-checked by
// the caller's fallback policy); float columns accumulate into f64.

#include <cstdint>
#include <cstring>
#include <thread>
#include <vector>
#include <algorithm>
#include <cmath>

namespace {

struct Acc {
    std::vector<int64_t> presence;
    std::vector<int64_t> count;
    std::vector<double> sum;
    std::vector<double> mn;
    std::vector<double> mx;
    std::vector<int64_t> first_ts;
    std::vector<double> first_v;
    std::vector<int64_t> last_ts;
    std::vector<double> last_v;
};

inline int64_t floordiv(int64_t a, int64_t b) {
    int64_t q = a / b, r = a % b;
    return (r != 0 && ((r < 0) != (b < 0))) ? q - 1 : q;
}

}  // namespace

extern "C" {

// returns 0 on success, -1 on a row whose segment falls out of range
// (caller falls back to the generic path).
int fused_seg_agg_f64(
    const int64_t* ts, const int32_t* sid_ord, const int64_t* group_lut,
    int64_t n_rows, int64_t origin, int64_t interval, int64_t bmin,
    int64_t n_buckets,              // 0 = no time bucketing
    const double* vals,             // may be null: presence only
    const uint8_t* valid,           // may be null: all valid
    const uint8_t* row_mask,        // may be null: all rows
    int64_t num_segments,
    int64_t* out_presence,          // may be null
    int64_t* out_count,             // may be null
    double* out_sum,                // may be null
    double* out_min,                // may be null
    double* out_max,                // may be null
    int64_t* out_seg,               // may be null: per-row segment ids
    double* out_first,              // may be null: value at earliest ts
    int64_t* out_first_ts,          // required with out_first
    double* out_last,               // may be null: value at latest ts
    int64_t* out_last_ts,           // required with out_last
    int n_threads) {
    if (n_threads < 1) n_threads = 1;
    if (n_threads > 16) n_threads = 16;
    // small inputs: threading overhead dominates
    if (n_rows < (1 << 20)) n_threads = 1;

    std::vector<Acc> accs(n_threads);
    std::vector<int> rcs(n_threads, 0);
    const bool bucketed = n_buckets > 0;
    const int64_t nb = bucketed ? n_buckets : 1;

    auto work = [&](int t) {
        Acc& a = accs[t];
        a.presence.assign(num_segments, 0);
        if (out_count || out_sum) a.count.assign(num_segments, 0);
        if (out_sum) a.sum.assign(num_segments, 0.0);
        if (out_min)
            a.mn.assign(num_segments,
                        std::numeric_limits<double>::infinity());
        if (out_max)
            a.mx.assign(num_segments,
                        -std::numeric_limits<double>::infinity());
        if (out_first) {
            a.first_ts.assign(num_segments, INT64_MAX);
            a.first_v.assign(num_segments, 0.0);
        }
        if (out_last) {
            a.last_ts.assign(num_segments, INT64_MIN);
            a.last_v.assign(num_segments, 0.0);
        }
        int64_t lo = n_rows * t / n_threads;
        int64_t hi = n_rows * (t + 1) / n_threads;
        for (int64_t i = lo; i < hi; i++) {
            // seg ids are filter-independent: computed and emitted for
            // every row so the caller can seed its warm-path cache
            int64_t seg = group_lut[sid_ord[i]] * nb;
            if (bucketed)
                seg += floordiv(ts[i] - origin, interval) - bmin;
            if (seg < 0 || seg >= num_segments) { rcs[t] = -1; return; }
            if (out_seg) out_seg[i] = seg;
            if (row_mask && !row_mask[i]) continue;
            a.presence[seg]++;
            if (!vals) continue;
            if (valid && !valid[i]) continue;
            double v = vals[i];
            if (!a.count.empty()) a.count[seg]++;
            if (!a.sum.empty()) a.sum[seg] += v;
            if (!a.mn.empty() && v < a.mn[seg]) a.mn[seg] = v;
            if (!a.mx.empty() && v > a.mx[seg]) a.mx[seg] = v;
            if (!a.first_ts.empty() && ts[i] < a.first_ts[seg]) {
                a.first_ts[seg] = ts[i];
                a.first_v[seg] = v;
            }
            if (!a.last_ts.empty() && ts[i] > a.last_ts[seg]) {
                a.last_ts[seg] = ts[i];
                a.last_v[seg] = v;
            }
        }
    };

    if (n_threads == 1) {
        work(0);
    } else {
        std::vector<std::thread> threads;
        for (int t = 0; t < n_threads; t++) threads.emplace_back(work, t);
        for (auto& th : threads) th.join();
    }
    for (int t = 0; t < n_threads; t++)
        if (rcs[t] != 0) return -1;

    for (int64_t s = 0; s < num_segments; s++) {
        int64_t pres = 0, cnt = 0;
        double sum = 0.0;
        double mn = std::numeric_limits<double>::infinity();
        double mx = -std::numeric_limits<double>::infinity();
        for (int t = 0; t < n_threads; t++) {
            const Acc& a = accs[t];
            pres += a.presence[s];
            if (!a.count.empty()) cnt += a.count[s];
            if (!a.sum.empty()) sum += a.sum[s];
            if (!a.mn.empty() && a.mn[s] < mn) mn = a.mn[s];
            if (!a.mx.empty() && a.mx[s] > mx) mx = a.mx[s];
        }
        if (out_presence) out_presence[s] = pres;
        if (out_count) out_count[s] = cnt;
        if (out_sum) out_sum[s] = sum;
        if (out_min) out_min[s] = mn;
        if (out_max) out_max[s] = mx;
        if (out_first) {
            int64_t bt = INT64_MAX;
            double bv = 0.0;
            for (int t = 0; t < n_threads; t++) {
                const Acc& a = accs[t];
                if (!a.first_ts.empty() && a.first_ts[s] < bt) {
                    bt = a.first_ts[s];
                    bv = a.first_v[s];
                }
            }
            out_first[s] = bv;
            out_first_ts[s] = bt;
        }
        if (out_last) {
            int64_t bt = INT64_MIN;
            double bv = 0.0;
            for (int t = 0; t < n_threads; t++) {
                const Acc& a = accs[t];
                if (!a.last_ts.empty() && a.last_ts[s] > bt) {
                    bt = a.last_ts[s];
                    bv = a.last_v[s];
                }
            }
            out_last[s] = bv;
            out_last_ts[s] = bt;
        }
    }
    return 0;
}

// One pass over a scan batch's i64 ns timestamps → the i32 pair the
// device is handed (ops/device_cache.py: the device never touches an
// i64 timestamp): sec = floor((ts - epoch) / 1e9) into out_sec, the ns
// remainder into out_ns; either output may be null. Rows [0, n) are
// written, rows [n, n_pad) zeroed, so an output is put as it lies.
// Value for value numpy's `(rel // 1e9).astype(i32)` and
// `(rel - sec.astype(i64) * 1e9).astype(i32)`, wrap-around included.
// → 1 if any remainder is non-zero (the caller then asks for out_ns),
// else 0.
int split_ts_i32(const int64_t* ts, int64_t n, int64_t epoch,
                 int32_t* out_sec, int32_t* out_ns, int64_t n_pad,
                 int n_threads) {
    const int64_t NS = 1000000000;
    // a thread is worth starting for 2^18 rows or more
    int64_t want = (n + (1 << 18) - 1) >> 18;
    if (n_threads > 16) n_threads = 16;
    if ((int64_t)n_threads > want) n_threads = (int)want;
    if (n_threads < 1) n_threads = 1;
    std::vector<int> any(n_threads, 0);

    auto work = [&](int t) {
        int64_t lo = n * t / n_threads;
        int64_t hi = n * (t + 1) / n_threads;
        int32_t acc = 0;
        for (int64_t i = lo; i < hi; i++) {
            int64_t rel = (int64_t)((uint64_t)ts[i] - (uint64_t)epoch);
            int32_t sec = (int32_t)floordiv(rel, NS);
            int32_t ns = (int32_t)((uint64_t)rel
                                   - (uint64_t)(int64_t)sec * (uint64_t)NS);
            if (out_sec) out_sec[i] = sec;
            if (out_ns) out_ns[i] = ns;
            acc |= ns;
        }
        any[t] = acc != 0;
    };

    if (n_threads == 1) {
        work(0);
    } else {
        std::vector<std::thread> threads;
        for (int t = 0; t < n_threads; t++) threads.emplace_back(work, t);
        for (auto& th : threads) th.join();
    }
    if (n_pad > n) {
        size_t pad = (size_t)(n_pad - n) * sizeof(int32_t);
        if (out_sec) std::memset(out_sec + n, 0, pad);
        if (out_ns) std::memset(out_ns + n, 0, pad);
    }
    for (int t = 0; t < n_threads; t++)
        if (any[t]) return 1;
    return 0;
}

}  // extern "C"
