"""server/process.py: a server keeps the host memory it frees and takes
its start-up objects out of full collections — what ended the two speeds
of PERF.md §6 (PR 29). Every case runs in a child: both calls change the
process they are made in for good."""
import json
import os
import re
import subprocess
import sys
import time
import urllib.request

import pytest

from cluster_harness import _env, free_port

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
glibc = pytest.mark.skipif(not sys.platform.startswith("linux"),
                           reason="mallopt is glibc's")


def child(code: str, *argv: str, **env) -> dict:
    """Run `code` in a fresh interpreter → the JSON object it prints last."""
    p = subprocess.run([sys.executable, "-c", code, *argv], cwd=ROOT,
                       env={**_env(), **env}, capture_output=True,
                       text=True, timeout=120)
    assert p.returncode == 0, p.stderr[-2000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


# three 18 MB buffers, touched and dropped: above glibc's default mmap
# threshold, so an untuned process unmaps them at the free; under the
# 32 MB the server sets, so a tuned one takes them from its heap
CHURN = """
import json, sys, threading
import numpy as np
from cnosdb_tpu.server import process

def rss_mb():
    with open("/proc/self/statm") as f:
        return int(f.read().split()[1]) * 4096 / 1e6

def churn():
    bufs = [np.ones(18 << 20, np.uint8) for _ in range(3)]
    del bufs

out = {"kept": process.keep_freed_memory() if sys.argv[1] == "keep" else None}
np.ones(1 << 20, np.uint8)
out["before"] = rss_mb()
for name in ("main", "thread"):
    if name == "main":
        churn()
    else:
        t = threading.Thread(target=churn); t.start(); t.join()
    out[name] = rss_mb()
print(json.dumps(out))
"""


@glibc
def test_keep_freed_memory_keeps_what_main_and_pool_threads_free():
    out = child(CHURN, "keep")
    assert out["kept"] is True
    # 54 MB came and went on the main heap, then on a thread's arena:
    # both are still the process's, ready for the next request
    assert out["main"] - out["before"] > 45, out
    assert out["thread"] - out["main"] > 45, out


@glibc
def test_an_untuned_process_gives_the_buffers_back():
    """The control: without the call the same churn leaves nothing
    behind, which is what made a request's speed depend on the heap's
    state. If this fails the C library changed and the case above proves
    nothing."""
    out = child(CHURN, "untuned")
    assert out["main"] - out["before"] < 20, out
    assert out["thread"] - out["before"] < 20, out


@glibc
@pytest.mark.parametrize("name", ["GLIBC_TUNABLES", "MALLOC_TRIM_THRESHOLD_",
                                  "MALLOC_TOP_PAD_",
                                  "MALLOC_MMAP_THRESHOLD_"])
def test_an_operators_own_allocator_setting_is_left_alone(name):
    value = "glibc.malloc.top_pad=131072" if name == "GLIBC_TUNABLES" \
        else "131072"
    out = child("import json\n"
                "from cnosdb_tpu.server import process\n"
                "print(json.dumps({'kept': process.keep_freed_memory()}))",
                **{name: value})
    assert out["kept"] is False


def test_freeze_startup_objects_takes_the_living_out_of_full_collections():
    out = child("""
import gc, json, time
from cnosdb_tpu.server import process
keep = [[i] for i in range(200_000)]          # the imports' stand-in
t0 = time.perf_counter(); gc.collect(); before = time.perf_counter() - t0
n = process.freeze_startup_objects()
t0 = time.perf_counter(); gc.collect(); after = time.perf_counter() - t0
cyc = []; cyc.append(cyc); del cyc             # later garbage still goes
print(json.dumps({"n": n, "frozen": gc.get_freeze_count(),
                  "before": before, "after": after,
                  "collected": gc.collect()}))
""")
    assert out["n"] == out["frozen"] >= 200_000
    assert out["collected"] >= 1
    # a full collection no longer walks the frozen objects
    assert out["after"] < out["before"] / 3, out


def test_the_server_does_both_before_it_listens(tmp_path):
    port = free_port()
    log = open(tmp_path / "server.log", "wb")
    proc = subprocess.Popen(
        [sys.executable, "-m", "cnosdb_tpu.server.main", "run",
         "--data-dir", str(tmp_path / "data"), "--http-port", str(port)],
        cwd=ROOT, env=_env(), stdout=log, stderr=subprocess.STDOUT)
    try:
        deadline = time.monotonic() + 120
        while True:
            assert proc.poll() is None, (tmp_path / "server.log").read_text()
            try:
                urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/api/v1/ping", timeout=2)
                break
            except OSError:
                assert time.monotonic() < deadline, "server not ready"
                time.sleep(0.2)
        text = (tmp_path / "server.log").read_text()
    finally:
        proc.kill()
        proc.wait(timeout=10)
        log.close()
    m = re.search(r"process: freed memory (kept|left to the allocator), "
                  r"(\d+) start-up objects frozen", text)
    assert m, text
    if sys.platform.startswith("linux"):
        assert m.group(1) == "kept"
    assert int(m.group(2)) > 50_000     # the imports alone are far more
    assert text.index("process: freed memory") \
        < text.index("cnosdb-tpu listening")
