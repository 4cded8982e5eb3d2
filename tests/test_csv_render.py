"""`format_csv` renders a column at a time; its bytes are the per-cell
composition's. The plain reference below is the function as it stood
before the by-column rules: `_csv_escape(_cell(v))` over `rs.rows()`."""
import numpy as np
import pytest

from cnosdb_tpu.server.http import _cell, _csv_escape, format_csv
from cnosdb_tpu.sql.executor import ResultSet
from cnosdb_tpu.sql.tsfuncs import IntervalNs
from cnosdb_tpu.utils import stages


def reference_csv(rs: ResultSet) -> str:
    lines = [",".join(rs.names)]
    for row in rs.rows():
        lines.append(",".join(_csv_escape(_cell(v)) for v in row))
    return "\n".join(lines) + "\n"


def _obj(values):
    a = np.empty(len(values), dtype=object)
    a[:] = values
    return a


def _fleet(n_rows=6000, n_fields=10, seed=7):
    """(names, columns) of a `double-groupby` answer: hour, host, and the
    averages of 360 integer readings each."""
    rng = np.random.default_rng(seed)
    t = (1451606400 + np.arange(n_rows) // 1000 * 3600).astype(np.int64) \
        * 10**9
    host = _obj([f"host_{i % 1000}" for i in range(n_rows)])
    fields = [rng.integers(0, 36001, n_rows) / 360.0
              for _ in range(n_fields)]
    names = ["t", "hostname"] + [f"avg_usage_{i}" for i in range(n_fields)]
    return names, [t, host, *fields]


I64, U64 = np.iinfo(np.int64), np.iinfo(np.uint64)
F64_EDGES = [float("nan"), 0.0, -0.0, float("inf"), float("-inf"), 1e16,
             1e-05, 5e-324, 0.1 + 0.2, 1.7976931348623157e308, -1.5, 100.0,
             123456789.123456789, 1e22, 1e-7]
GAUGE = {"time": 1672531200000000000, "value": 1.5}
WINDOW = {"start": 0, "end": 10**9}

# name -> (column names, columns, columns that take the per-cell path)
CASES = {
    "int64_extremes": (["v"], [np.array([I64.min, -1, 0, 1, I64.max],
                                        dtype=np.int64)], 0),
    "int32": (["v"], [np.array([-2**31, 0, 7, 2**31 - 1],
                               dtype=np.int32)], 0),
    "int8_uint16": (["a", "b"], [np.array([-128, 127], dtype=np.int8),
                                 np.array([0, 65535], dtype=np.uint16)], 0),
    "uint64_extremes": (["v"], [np.array([0, 1, 2**63, U64.max],
                                         dtype=np.uint64)], 0),
    "float64_edges": (["v"], [np.array(F64_EDGES, dtype=np.float64)], 0),
    "float64_random_bits": (["v"], [np.random.default_rng(3).integers(
        0, 2**64, 4096, dtype=np.uint64).view(np.float64)], 0),
    "float64_all_nan": (["v"], [np.full(5, np.nan)], 0),
    "float64_strided_view": (["v"], [np.arange(20, dtype=np.float64)[::3]
                                     - 3.0], 0),
    "float32_edges": (["v"], [np.array(
        [np.nan, 0.0, -0.0, np.inf, -np.inf, 1.5707964, 6e-06, 1e16, 0.1,
         3.4028235e38, 1e-45, 16777216.0], dtype=np.float32)], 0),
    "float32_random_bits": (["v"], [np.random.default_rng(4).integers(
        0, 2**32, 2048, dtype=np.uint32).view(np.float32)], 0),
    "float16_falls_back": (["v"], [np.array([0.5, np.nan, -0.0],
                                            dtype=np.float16)], 1),
    "bool": (["v"], [np.array([True, False, True])], 0),
    "str_plain": (["s"], [_obj(["host_0", "", "a b", "x"])], 0),
    "str_escapes": (["s"], [_obj(["a,b", 'say "hi"', "line\nbreak", "",
                                  "plain", '",\n', "'single'", " "])], 0),
    "str_non_ascii": (["s"], [_obj(["héllo", "主机,一", "🙂", "naïve\"q"])], 0),
    "str_numeric_looking": (["s"], [_obj(["1", "-0.0", "NaN", "true"])], 0),
    "obj_with_none": (["s"], [_obj(["a", None, "b,c"])], 1),
    "obj_all_none": (["s"], [_obj([None, None])], 1),
    "obj_gauge_composite": (["g"], [_obj([GAUGE, None, GAUGE])], 1),
    "obj_window_composite": (["w"], [_obj([WINDOW, WINDOW])], 1),
    "obj_interval": (["i"], [_obj([IntervalNs(5_000_000),
                                   IntervalNs(90 * 10**9), None])], 1),
    "obj_bytes": (["b"], [_obj([b"\x01\x02", bytearray(b"\xff"), b""])], 1),
    "obj_numpy_scalars": (["n"], [_obj(
        [np.float32(1.5707964), np.float64(0.1), np.float64("nan"),
         np.float32(-0.0), np.int64(-3), np.uint8(9), np.bool_(True),
         np.bool_(False), np.str_("np,str")])], 1),
    "obj_np_str_only": (["s"], [_obj([np.str_("a"), np.str_("b,c")])], 1),
    "obj_python_scalars": (["x"], [_obj([1, -2.5, 0.0, -0.0, float("nan"),
                                         True, False, 2**70])], 1),
    "obj_mix": (["x"], [_obj(["s,1", 2, 3.5, None, GAUGE, b"\x00",
                              IntervalNs(1), np.float32(2.5), True])], 1),
    "datetime64": (["d"], [np.array(["2023-01-01T00:00:00",
                                     "1970-01-01T00:00:01.5", "NaT"],
                                    dtype="datetime64[ns]")], 1),
    "datetime64_s": (["d"], [np.array(["2023-01-01T00:00:00"],
                                      dtype="datetime64[s]")], 1),
    "timedelta64": (["d"], [np.array([1, 2], dtype="timedelta64[ms]")], 1),
    "unicode_dtype": (["u"], [np.array(["a", "b,c", ""])], 1),
    "bytes_dtype": (["b"], [np.array([b"a", b"b,c"])], 1),
    "two_dimensional": (["m"], [np.arange(6, dtype=np.int64).reshape(3, 2)],
                        1),
    "zero_rows_typed": (["t", "h", "v"], [np.empty(0, dtype=np.int64),
                                          np.empty(0, dtype=object),
                                          np.empty(0, dtype=np.float64)], 0),
    "zero_rows_empty_result": (list(ResultSet.empty(["a", "b"]).names),
                               ResultSet.empty(["a", "b"]).columns, 0),
    "zero_columns": ([], [], 0),
    "zero_columns_with_names": (["ghost"], [], 0),
    "one_column_one_row": (["result"], ResultSet.message("ok").columns, 0),
    "message_with_comma": (["result"],
                           ResultSet.message('done, "really"').columns, 0),
    "header_is_not_escaped": (['a,b', 'c"d'], [np.array([1]),
                                               np.array([2.0])], 0),
    "ragged_columns_cut_to_shortest": (["a", "b"], [np.arange(5),
                                                    np.arange(3) * 0.5], 0),
    "every_rule_side_by_side": (
        ["t", "h", "f", "f32", "b", "u", "g"],
        [np.array([1, 2, 3], dtype=np.int64), _obj(["a", "b,c", ""]),
         np.array([np.nan, -0.0, 2.5]), np.array([1.1, 0.0, np.nan],
                                                 dtype=np.float32),
         np.array([True, False, True]), np.array([1, 2, 3], dtype=np.uint64),
         _obj([GAUGE, None, "x"])], 1),
    "panel_8x11": (*_fleet(8, 10), 0),
    "panel_60x6": (*_fleet(60, 5), 0),
    "fleet_6000x3": (*_fleet(6000, 1), 0),
    "fleet_6000x12": (*_fleet(6000, 10), 0),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_bytes_are_the_per_cell_reference(case):
    names, columns, _percell = CASES[case]
    rs = ResultSet(list(names), list(columns))
    assert format_csv(rs) == reference_csv(rs)


@pytest.mark.parametrize("case", sorted(CASES))
def test_percell_columns_counts_the_fallback(case):
    names, columns, percell = CASES[case]
    prof = stages.QueryProfile(sql=case)
    with stages.profile_scope(prof):
        format_csv(ResultSet(list(names), list(columns)))
    assert prof.counts["render.percell_columns"] == percell


def test_known_bytes():
    """The reference itself is held to literal bytes, so both cannot
    drift together."""
    rs = ResultSet(
        ["t", "h", "v", "ok"],
        [np.array([1, -2], dtype=np.int64), _obj(["a,b", 'q"']),
         np.array([np.nan, -0.0]), np.array([True, False])])
    assert format_csv(rs) == ('t,h,v,ok\n1,"a,b",NaN,true\n'
                              '-2,"q""",0.0,false\n')
    assert format_csv(ResultSet.empty()) == "\n"
    assert format_csv(ResultSet(["a"], [np.empty(0, dtype=np.int64)])) \
        == "a\n"


def test_fleet_shape_books_zero_and_a_composite_books_one():
    fleet = ResultSet(*_fleet(6000, 10))
    prof = stages.QueryProfile(sql="fleet")
    with stages.profile_scope(prof):
        format_csv(fleet)
    assert prof.counts["render.percell_columns"] == 0
    fleet.names.append("g")
    fleet.columns.append(_obj([GAUGE] * fleet.n_rows))
    prof = stages.QueryProfile(sql="fleet + gauge")
    with stages.profile_scope(prof):
        format_csv(fleet)
    assert prof.counts["render.percell_columns"] == 1


def test_no_profile_no_count():
    # outside a request (tests, Flight SQL callers) the count is a no-op
    assert format_csv(ResultSet(*_fleet(4, 1))).count("\n") == 5
