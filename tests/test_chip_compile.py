"""Compile rehearsal: the served path's device programs, lowered and
compiled for a described (not attached) TPU v5e at the shapes chip_smoke.py
gives them — 1000 hosts x 6 h of TSBS devops cpu (2.16 M rows, a 4 Mi row
size class, 8192 segments, 4096-value pages).

The chip's compiler is installed in the sandbox and refuses here what it
would refuse on the chip: a program that does not fit the device's
memory, an operand it cannot tile or partition. Nothing runs, so nothing
here says anything about results or times.

One file, and the topology is described inside a module-scoped fixture:
only one process may load the TPU's library, the suite runs under several
workers, and every worker imports every test file.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from cnosdb_tpu.ops import device_decode as dd
from cnosdb_tpu.ops import fused, kernels
from cnosdb_tpu.sql.expr import BinOp, Column, Literal

ROWS = 1 << 22           # pad_rows(2_160_000)
SEGMENTS = 8192          # pad_segments(1000 hosts x 6 hourly buckets)
PAGES, PAGE_LEN = 16384, 4096    # one page per series per field, 2160 rows
FIELDS = tuple(f"usage_{i}" for i in range(10))
ALL_SIX = dict(want_count=True, want_sum=True, want_min=True, want_max=True,
               want_first=True, want_last=True)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep it off around these
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def spec(topo):
    """shape, dtype → a ShapeDtypeStruct placed on one described chip."""
    one_chip = SingleDeviceSharding(topo.devices[0])

    def make(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    return make


def _compile(fn, *args, **static):
    return fn.lower(*args, **static).compile()


# ------------------------------------------------------- segment aggregate
@pytest.mark.parametrize("dtype", [jnp.float64, jnp.int64])
def test_xla_segment_aggregate(spec, dtype):
    _compile(kernels.segment_aggregate,
             spec((ROWS,), dtype), spec((ROWS,), jnp.bool_),
             spec((ROWS,), jnp.int32), spec((ROWS,), jnp.int32),
             num_segments=SEGMENTS, **ALL_SIX)


# rows → segments, bound on runs (kernels.run_pad_for): the run path at the
# benchmark's shapes — the fleet request, a panel over 40 hosts x 5 h, and
# the shortest batch that takes it at the fewest rows a run
RUN_SHAPES = {"fleet": (1 << 21, 8192, 8192), "panel-64k": (1 << 16, 64, 256),
              "shortest": (1 << 16, 8192, 8192)}


@pytest.mark.parametrize("shape", list(RUN_SHAPES))
def test_xla_segment_aggregate_by_runs(spec, shape):
    rows, segments, run_pad = RUN_SHAPES[shape]
    assert kernels.run_pad_for(rows, run_pad) == run_pad
    compiled = _compile(
        kernels.segment_aggregate,
        spec((rows,), jnp.int64), spec((rows,), jnp.bool_),
        spec((rows,), jnp.int32), spec((rows,), jnp.int32),
        num_segments=segments, run_pad=run_pad, want_count=True,
        want_sum=True, want_min=False, want_max=True)
    # one program holds both branches: the run path and the row scatter
    assert " conditional(" in compiled.as_text()


# ------------------------------------------------------------ fused program
def _fused_args(spec, n_cols, use_bucket, need_rank, n_series=1000,
                rows=ROWS):
    # launch_fused's argument order for an irregular, second-aligned batch:
    # [ts_sec], sid_ordinal, [rank], packed params, the value columns
    args = [spec((rows,), jnp.int32)] * (1 + use_bucket + need_rank)
    args.append(spec((fused._SCALARS + n_series,), jnp.int32))  # packed params
    args += [spec((rows,), jnp.int64)] * n_cols
    return args


FUSED_SHAPES = {
    # avg of every field by hour x host
    "double-groupby-all": (None, {f: {"want_sum": True} for f in FIELDS},
                           6, True),
    # the pushed-down value filter under an aggregate
    "filtered": (BinOp(">", Column(FIELDS[0]), Literal(90.0)),
                 {FIELDS[0]: {"want_max": True, "want_sum": True}}, 6, True),
    # last value of every field per host: rank selection, no buckets
    "lastpoint": (None, {f: {"want_last": True} for f in FIELDS}, 1, False),
}


@pytest.mark.parametrize("run_pad", [0, 8192], ids=["by-rows", "by-runs"])
@pytest.mark.parametrize("shape", list(FUSED_SHAPES))
def test_fused_program(spec, shape, run_pad):
    flt, col_wants, _n_buckets, use_bucket = FUSED_SHAPES[shape]
    present = tuple(sorted(col_wants))
    need_rank = any(w.get("want_last") for w in col_wants.values())
    fn, manifest = fused._build_kernel(
        flt, col_wants, present, SEGMENTS, use_bucket, 3600,
        need_rank, (False,) * len(present), False, False, ROWS, run_pad)
    _compile(fn, *_fused_args(spec, len(present), use_bucket, need_rank))
    assert len(manifest) > len(present)


def _fleet_program(spec, n_cols):
    """devops-fleet-groupby's program (avg of n_cols fields by hour x host
    over 2^21 rows, 1 000 series, a bound of 8 192 runs), compiled."""
    rows = 1 << 21
    run_pad = kernels.run_pad_for(rows, 1000 * 6 + 1)
    assert run_pad == 8192
    col_wants = {f: {"want_sum": True} for f in FIELDS[:n_cols]}
    fn, manifest = fused._build_kernel(
        None, col_wants, tuple(sorted(col_wants)), SEGMENTS, True, 3600,
        False, (False,) * n_cols, False, False, rows, run_pad)
    assert manifest[-1] == ("__runs__", "engaged")
    return _compile(fn, *_fused_args(spec, n_cols, True, False, rows=rows))


def test_fused_program_searches_its_runs_once(spec):
    """Every column's reduction asks for the run structure of the same
    segment ids; the compiler folds the copies, so ten columns cost ten
    prefix sums and one 22-step search for the run ends, not ten."""
    one = _fleet_program(spec, 1).as_text().count(" gather(")
    ten = _fleet_program(spec, 10).as_text().count(" gather(")
    assert one >= 22                 # the search is there at all
    assert ten - one < 22, (one, ten)


# ----------------------------------------------------------- decode kernels
def test_delta_kernel(spec):
    _compile(dd._delta_kernel, spec((PAGES, PAGE_LEN), jnp.uint8),
             spec((PAGES,), jnp.int64))


def test_delta_const_kernel(spec):
    _compile(dd._delta_const_kernel, spec((1024,), jnp.int64),
             spec((1024,), jnp.int64), length=PAGE_LEN)


@pytest.mark.parametrize("pages,lane_len", [(8, dd._MIN_LANE),
                                            (1024, PAGE_LEN), (8, 1 << 16)])
def test_gorilla_xla_kernel(spec, pages, lane_len):
    """The Gorilla lane's only scan, from the smallest bucket to a 2^16
    one."""
    _compile(dd._gorilla_xla_kernel, spec((pages, 8, lane_len), jnp.uint8))


def test_bitpack_kernel(spec):
    _compile(dd._bitpack_kernel, spec((1024, PAGE_LEN // 8), jnp.uint8))


def test_codes_kernel(spec):
    _compile(dd._codes_kernel, spec((1024, PAGE_LEN), jnp.uint16))


# ------------------------------------------------- the mesh lane, four chips
@pytest.mark.parametrize("row_run_pad", [0, 2048], ids=["by-rows", "by-runs"])
@pytest.mark.parametrize("wants", [("count", "sum"), ("count", "last")])
def test_mesh_merge_kernel_on_four_chips(topo, wants, row_run_pad):
    """chip_smoke.py --chips 4 and the mesh4 cell: eight shards over a 2x2
    host, two batches per device ([2^19] rows → 2 x 8 192 slots; 2 x ~125
    series x 6 buckets + 1 = a bound of 2 048 runs), one shard_map program
    per column with all_gather folds."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from cnosdb_tpu.parallel.distributed_agg import mesh_merge_kernel
    from cnosdb_tpu.parallel.mesh import SHARD_AXIS

    mesh = Mesh(np.array(topo.devices), (SHARD_AXIS,))
    rows = NamedSharding(mesh, P(SHARD_AXIS))
    total = len(topo.devices) * (1 << 19)    # 2 x 225k rows per device

    def arr(n, dtype):
        return jax.ShapeDtypeStruct((n,), dtype, sharding=rows)

    compiled = _compile(
        mesh_merge_kernel, arr(total, jnp.int64), arr(total, jnp.bool_),
        arr(total, jnp.int32), arr(total, jnp.int32),
        arr(len(topo.devices), jnp.int32), arr(len(topo.devices), jnp.int32),
        mesh=mesh, slots=2, num_segments=SEGMENTS, wants=wants, run_pad=0,
        row_run_pad=row_run_pad)
    assert "all-gather" in compiled.as_text()


# ------------------------------------------- distinct / top-k, one size class
# The smallest classes only: a 64-bit sort's compile time for this chip
# grows steeply with the size class (top_k f64: 0.5 s at 2^12, 9 s at 2^14,
# 48 s at 2^16 in this sandbox), and the smoke's queries use neither.
def test_segment_distinct(spec):
    _compile(kernels._segment_distinct, spec((1 << 12,), jnp.int64),
             spec((), jnp.int64), num_segments=1024)


@pytest.mark.parametrize("dtype", [jnp.float64, jnp.int64])
def test_topk_threshold(spec, dtype):
    _compile(kernels._topk_threshold, spec((1 << 12,), dtype), k=10)
