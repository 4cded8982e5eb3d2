"""ASAN+UBSAN harness for the native codec kernels (SURVEY §5: the
reference relies on Rust's ownership guarantees; the rebuild's C++ surface
gets sanitizers). Builds `libcnosdb_codecs_asan.so` and drives codec
round-trips through it in a SUBPROCESS with the sanitizer runtime
preloaded — any heap overflow / UB aborts the child and fails the test."""
import os
import subprocess
import sys

import pytest

NATIVE_DIR = os.path.join(os.path.dirname(__file__), "..", "native")
ASAN_LIB = os.path.join(os.path.dirname(__file__), "..", "cnosdb_tpu",
                        "_native", "libcnosdb_codecs_asan.so")

CHILD = r"""
import os, sys
import numpy as np

# route the bindings at the sanitized build
os.environ["CNOSDB_NATIVE_LIB"] = sys.argv[1]
from cnosdb_tpu.storage import codecs, native
from cnosdb_tpu.models.schema import ValueType

assert native.available(), "sanitized native lib failed to load"

rng = np.random.default_rng(7)
# exercise every codec family through encode→decode round-trips at odd
# sizes (boundary conditions are where memory bugs live)
for n in (0, 1, 7, 63, 64, 65, 1000, 4097):
    ts = np.cumsum(rng.integers(1, 1000, max(n, 1)).astype(np.int64))[:n]
    out = codecs.decode_timestamps(codecs.encode_timestamps(ts))
    assert np.array_equal(out, ts), f"ts roundtrip n={n}"

    f = rng.normal(0, 1e6, n)
    out = codecs.decode(codecs.encode(f, ValueType.FLOAT), ValueType.FLOAT)
    assert np.array_equal(out, f), f"f64 roundtrip n={n}"

    i = rng.integers(-2**40, 2**40, max(n, 1)).astype(np.int64)[:n]
    out = codecs.decode(codecs.encode(i, ValueType.INTEGER),
                        ValueType.INTEGER)
    assert np.array_equal(out, i), f"i64 roundtrip n={n}"

# line-protocol parser under sanitizers: valid, malformed, and
# adversarial inputs (truncated escapes, unbalanced quotes, huge tokens)
from cnosdb_tpu.protocol import native_lp
assert native_lp.available()
cases = [
    "cpu,host=a usage=1.5,b=t,s=\"x\",c=3i,u=7u 1000\n" * 50,
    "m v=1",                       # no trailing newline
    "m \\",                        # trailing escape
    'm s="unterminated 5\n',
    "m,t=1 v=1 99999999999999999999999\n",   # ts overflow
    "m," + "k=v," * 500 + "z=1 v=1 5\n",
    "m v=" + "9" * 400 + "i 5\n",
    "\x00\xff bin=1 5\n",
    "#only comments\n\n\n",
    "",
]
for c in cases:
    native_lp.try_parse(c, 0, 1)   # must not crash; result may be None
rnd = np.random.default_rng(11)
for _ in range(200):               # random byte soup
    blob = rnd.integers(32, 127, rnd.integers(1, 300)).astype(np.uint8)
    native_lp.try_parse(blob.tobytes().decode("ascii"), 0, 1)
print("SANITIZED ROUNDTRIPS OK")
"""


@pytest.mark.skipif(not os.path.exists(os.path.join(NATIVE_DIR, "codecs.cpp")),
                    reason="native source absent")
def test_codecs_under_asan(tmp_path):
    build = subprocess.run(["make", "-C", NATIVE_DIR, "asan"],
                           capture_output=True, text=True)
    if build.returncode != 0:
        pytest.skip(f"asan build unavailable: {build.stderr[-300:]}")
    # find the asan runtime to preload (python itself isn't instrumented)
    probe = subprocess.run(
        ["g++", "-print-file-name=libasan.so"], capture_output=True,
        text=True)
    asan_rt = probe.stdout.strip()
    cxx = subprocess.run(
        ["g++", "-print-file-name=libstdc++.so"], capture_output=True,
        text=True).stdout.strip()
    env = dict(os.environ)
    # libstdc++ after libasan: the __cxa_throw interceptor must find the
    # real symbol at init or sanitized C++ exceptions abort
    env["LD_PRELOAD"] = f"{asan_rt} {cxx}"
    env["ASAN_OPTIONS"] = "detect_leaks=0,abort_on_error=1"
    env["JAX_PLATFORMS"] = "cpu"
    child = subprocess.run(
        [sys.executable, "-c", CHILD, os.path.abspath(ASAN_LIB)],
        capture_output=True, text=True, env=env, timeout=300)
    assert child.returncode == 0, \
        f"sanitizer run failed:\n{child.stdout}\n{child.stderr[-2000:]}"
    assert "SANITIZED ROUNDTRIPS OK" in child.stdout
