#!/usr/bin/env python3
"""The one process that holds the chip: the server, plus a control thread.

`run.py` starts this file as its only child. It starts a small control
thread and then calls `cnosdb_tpu.server.main.main(["run", ...])` on the
main thread (the server's own SIGINT handling needs the main thread). The
control thread answers one JSON request per connection on a loopback
socket:

    {"op": "device"}            platform, kind, count as JAX reports them
    {"op": "memory"}            peak_bytes_in_use, the fullest local device
    {"op": "trace_start", "dir": ...}   jax.profiler.start_trace
    {"op": "trace_stop"}                jax.profiler.stop_trace

so the profiler trace and the memory reading are taken inside the process
that runs the device, and no product code learns about the benchmark. JAX
is imported on the first request only, and `run.py` sends none before the
server has answered its first query: the program's own `ops/__init__.py`
(x64, compile cache) configures JAX first.
"""
from __future__ import annotations

import argparse
import json
import os
import socket
import sys
import threading
import time
import traceback

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

# the one span of the trace window, written into the trace itself so the
# reduction reads the window on the trace's own clock
from benchmarks.lib.trace_reduce import WINDOW_MARK  # noqa: E402


class Control:
    """Answers requests one at a time on one thread, so the window mark
    starts and ends on the thread that owns it."""

    def __init__(self, port: int):
        self._sock = socket.socket()
        self._sock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._sock.bind(("127.0.0.1", port))
        self._sock.listen(4)
        self._mark = None
        self._tracing = False

    def serve(self) -> None:
        while True:
            conn, _addr = self._sock.accept()
            with conn:
                try:
                    conn.settimeout(30)
                    buf = b""
                    while not buf.endswith(b"\n"):
                        chunk = conn.recv(65536)
                        if not chunk:
                            break
                        buf += chunk
                    req = json.loads(buf)
                    out = getattr(self, "op_" + str(req.pop("op")))(**req)
                except Exception:   # the boundary: report, keep serving
                    out = {"error": traceback.format_exc()[-2000:]}
                try:
                    conn.settimeout(None)
                    conn.sendall(json.dumps(out).encode() + b"\n")
                except OSError:
                    pass

    # ---- requests
    def op_device(self) -> dict:
        import jax

        ds = jax.local_devices()
        return {"platform": ds[0].platform, "kind": ds[0].device_kind,
                "count": len(ds), "jax": jax.__version__}

    def op_memory(self) -> dict:
        import jax

        peaks = []
        for d in jax.local_devices():
            stats = d.memory_stats()
            if stats and stats.get("peak_bytes_in_use") is not None:
                peaks.append(int(stats["peak_bytes_in_use"]))
        if peaks:
            return {"peak_bytes": max(peaks), "source": "memory_stats",
                    "per_device": peaks}
        # a backend without memory_stats (the CPU, in a rehearsal): the
        # process's peak resident set, named as such
        import resource

        return {"peak_bytes": resource.getrusage(
                    resource.RUSAGE_SELF).ru_maxrss * 1024,
                "source": "ru_maxrss"}

    def op_trace_start(self, dir: str) -> dict:
        import jax

        if self._tracing:
            raise RuntimeError("a trace is already running")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0     # no Python frames: size and cost
        opts.host_tracer_level = 1       # the window mark, little else
        jax.profiler.start_trace(dir, profiler_options=opts)
        self._tracing = True
        self._mark = jax.profiler.TraceAnnotation(WINDOW_MARK)
        wall = time.time()
        self._mark.__enter__()
        return {"started": True, "mark": WINDOW_MARK, "mark_wall_s": wall}

    def op_trace_stop(self) -> dict:
        import jax

        if not self._tracing:
            raise RuntimeError("no trace is running")
        self._mark.__exit__(None, None, None)
        wall = time.time()
        t0 = time.monotonic()
        jax.profiler.stop_trace()
        self._tracing = False
        return {"stopped": True, "mark_end_wall_s": wall,
                "stop_seconds": time.monotonic() - t0}


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--data-dir", required=True)
    p.add_argument("--http-port", type=int, required=True)
    p.add_argument("--control-port", type=int, required=True)
    args = p.parse_args(argv)
    control = Control(args.control_port)
    threading.Thread(target=control.serve, name="benchmark-control",
                     daemon=True).start()
    from cnosdb_tpu.server.main import main as server_main

    return server_main(["run", "--data-dir", args.data_dir,
                        "--http-port", str(args.http_port)])


if __name__ == "__main__":
    sys.exit(main())
