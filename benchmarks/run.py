#!/usr/bin/env python3
"""The benchmark: TSBS devops over the served path, one cell per run.

    python benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

This process is the client and the only one that prints. It never imports
JAX or cnosdb_tpu. It makes data and requests from --seed, starts one
server child (`lib/server_child.py`, the process that holds the chip),
loads over HTTP line protocol, FLUSHes, warms up at the window's
concurrency, measures for --seconds, checks every answer against the numpy
reference (`lib/devops.py`) after the window, and prints one JSON object
as its last line (`lib/report.py`). See benchmarks/README.md.

    --trace 0   end-to-end metrics; profiles not requested, tracing off
    --trace 1   per-layer metrics; every query asks for its profile, and a
                jax.profiler trace of `trace_seconds` is taken in the child
    --rehearse  toy size on whatever backend there is; every line is
                labelled a dry run and no conforming last line is printed

No chip, no run: unless the child reports platform `tpu` and as many
devices as the cell's `chips`, the run exits non-zero before measuring.
"""
from __future__ import annotations

import time

_T0 = time.monotonic()          # set-up runs from the start of the process

import argparse                                           # noqa: E402
import json                                               # noqa: E402
import os                                                 # noqa: E402
import shutil                                             # noqa: E402
import statistics                                         # noqa: E402
import subprocess                                         # noqa: E402
import sys                                                # noqa: E402
import tempfile                                           # noqa: E402
import threading                                          # noqa: E402
import traceback                                          # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

from benchmarks.lib import devops, layer_metrics, report, traffic  # noqa: E402
from benchmarks.lib.server import (Connection, Fail, Server,       # noqa: E402
                                   build_native, device_errors, metric,
                                   post_write)

REDUCE_TIMEOUT_S = 150
DRY_RUN = False


def emit(obj: dict) -> None:
    """An earlier line: a JSON object with a `phase`. Never the result."""
    if DRY_RUN:
        obj = {"dry_run": True, **obj}
    print(json.dumps(obj, default=str), flush=True)


def load_json(*parts: str) -> dict:
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


# ------------------------------------------------------------------ set-up
def server_env(config: dict, chips: int, workdir: str, rehearse: bool) -> dict:
    env = dict(os.environ)
    env.setdefault("PYTHONUNBUFFERED", "1")
    env.update(config.get("server_env") or {})
    if rehearse:
        # a rehearsal leaves nothing in the checkout, gives the CPU backend
        # the cell's number of devices, and drives the device lanes
        env.setdefault("JAX_COMPILATION_CACHE_DIR",
                       os.path.join(workdir, "jax_cache"))
        env.setdefault("CNOSDB_TPU_FORCE_DEVICE_PATH", "1")
        env.setdefault("CNOSDB_DEVICE_DECODE", "1")
        if chips > 1 and "xla_force_host_platform_device_count" \
                not in env.get("XLA_FLAGS", ""):
            env["XLA_FLAGS"] = (
                env.get("XLA_FLAGS", "") + " --xla_force_host_platform_"
                f"device_count={chips}").strip()
    return env


def read_device(srv: Server, chips: int, rehearse: bool) -> dict:
    """The device, from the child's control thread (JAX's own report) and
    from the stamp the program puts on query profiles; they must agree."""
    _text, summary = srv.sql("public", "SHOW DATABASES", profile=True)
    if not summary or summary.get("qid") is None:
        raise Fail("the first query came back without a profile summary")
    stamp = srv.full_profile(summary["qid"]).get("device") or {}
    dev = srv.control("device")
    device = {"platform": dev["platform"], "kind": dev["kind"],
              "count": dev["count"]}
    stamped = {"platform": stamp.get("platform"),
               "kind": stamp.get("device_kind"),
               "count": stamp.get("device_count")}
    emit({"phase": "device", **device, "jax": dev.get("jax"),
          "profile_stamp": stamp})
    if stamped != device:
        raise Fail(f"the program stamps {stamped} on its profiles, JAX in "
                   f"the same process reports {device}")
    if device["platform"] != "tpu" and not rehearse:
        raise Fail(f"the server runs on {device['platform']!r}, not on a "
                   "tpu: nothing is measured")
    if device["count"] != chips:
        raise Fail(f"{device['count']} devices; the cell asks for {chips}")
    if stamp.get("compile_cache_dir") is None:
        raise Fail("the server runs with the persistent compile cache off")
    return device


def load_data(srv: Server, db: str, ds: devops.Dataset, load: dict) -> dict:
    """The loaded range over /api/v1/write, every batch acknowledged before
    that writer's next, `writers` connections side by side."""
    step = int(load["batch_steps"])
    starts = list(range(0, ds.loaded_steps, step))
    lock = threading.Lock()
    state = {"retries": 0, "bytes": 0, "error": None}

    def writer() -> None:
        conn = Connection(srv.port, timeout=300.0)
        while state["error"] is None:
            with lock:
                if not starts:
                    break
                a = starts.pop(0)
            body = ds.lines(a, min(a + step, ds.loaded_steps))
            acked, retries, error = post_write(conn, db, body, max_sleep=2.0)
            with lock:
                state["retries"] += retries
                state["bytes"] += len(body)
                if not acked:
                    state["error"] = error
        conn.close()

    t0 = time.monotonic()
    threads = [threading.Thread(target=writer, daemon=True)
               for _ in range(int(load["writers"]))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if state["error"] is not None:
        srv.check_alive()
        raise Fail(f"load: a batch was not acknowledged: {state['error']}")
    dt = time.monotonic() - t0
    rows = ds.hosts * ds.loaded_steps
    return {"phase": "load", "rows": rows, "writers": int(load["writers"]),
            "line_protocol_bytes": state["bytes"],
            "backpressure_retries": state["retries"],
            "seconds": dt, "rows_per_s": rows / dt}


def count_and_max_time(srv: Server, db: str) -> tuple[int, int]:
    text, _ = srv.sql(db, "SELECT count(*), max(time) FROM cpu")
    n, t = text.splitlines()[1].split(",")[:2]
    return int(n), parse_time_ns(t)


def parse_time_ns(cell: str) -> int:
    """The server's timestamp cell → ns since the epoch."""
    cell = cell.strip().strip('"')
    if cell.lstrip("-").isdigit():
        return int(cell)
    import datetime as dt

    body, _, frac = cell.rstrip("Z").replace("T", " ").partition(".")
    whole = dt.datetime.strptime(body, "%Y-%m-%d %H:%M:%S").replace(
        tzinfo=dt.timezone.utc)
    return int(whole.timestamp()) * devops.NS \
        + int((frac + "000000000")[:9] or 0)


def check_answers(ds: devops.Dataset, records: list[dict]) -> list[str]:
    """Every answer against the reference → the failures, in words."""
    bad = []
    for r in records:
        if r["status"] != 200:
            bad.append(f"{r['req'].cls}: status {r['status']}: "
                       f"{(r['text'] or '')[:200]}")
            r["ok"] = False
            continue
        try:
            devops.check_answer(ds, r["req"], r["text"])
            r["ok"] = True
        except (devops.Mismatch, ValueError) as e:
            bad.append(str(e))
            r["ok"] = False
    return bad


# --------------------------------------------------------------- the trace
def trace_during(srv: Server, trace_dir: str, seconds: float, out: dict):
    """→ the `during` hook of the window: start the trace in the child,
    let it run `seconds`, stop it. Runs on the main thread while the
    loops send."""
    def during(gate) -> None:
        out["start"] = srv.control("trace_start", dir=trace_dir)
        time.sleep(max(0.0, seconds))
        out["stop"] = srv.control("trace_stop", timeout=600.0)
    return during


def reduce_trace(trace_dir: str, spans_path: str, rehearse: bool) -> dict:
    """`lib/trace_reduce.py` as a helper process that cannot touch the
    chip (JAX_PLATFORMS=cpu; it only parses the file), with a time limit."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cmd = [sys.executable, os.path.join(HERE, "lib", "trace_reduce.py"),
           trace_dir, "--host-spans", spans_path]
    if rehearse:
        cmd.append("--rehearse")
    keep = os.environ.get("BENCH_KEEP_TRACE_EVENTS")
    if keep:
        cmd += ["--dump-events", keep]
    try:
        p = subprocess.run(cmd, capture_output=True, text=True, env=env,
                           timeout=REDUCE_TIMEOUT_S)
    except subprocess.TimeoutExpired:
        raise Fail(f"trace reduction took over {REDUCE_TIMEOUT_S}s")
    lines = p.stdout.strip().splitlines()
    try:
        out = json.loads(lines[-1]) if lines else {}
    except ValueError:
        out = {}
    if p.returncode != 0 or "busy_s" not in out:
        raise Fail(f"trace reduction failed (rc={p.returncode}): "
                   f"{out.get('error') or p.stderr[-1500:]} :: the trace "
                   f"holds {json.dumps(out.get('trace_holds'))[:2000]}")
    return out


# ---------------------------------------------------------------- one run
def set_up(args, cell: dict, config: dict, mix: dict, workdir: str,
           state: dict):
    """Everything before the window → (server, data, device, database,
    write bodies or None, problems found so far)."""
    chips = int(cell["chips"])
    size = config["rehearse"] if args.rehearse else config
    steps = max(12, int(round(size["hours"] * 3600 / devops.STEP_S)))
    emit({"phase": "start", "workload": cell["name"],
          "config": cell["config"], "traffic": cell["traffic"],
          "chips": chips, "seed": args.seed, "seconds": args.seconds,
          "trace": args.trace, "hosts": size["hosts"],
          "hours": steps * devops.STEP_S / 3600,
          "jax_in_client": "jax" in sys.modules})
    built = build_native()
    if built is not None:
        emit({"phase": "native_build", "seconds": built})
    t0 = time.monotonic()
    ds = devops.Dataset(args.seed, int(size["hosts"]), steps)
    emit({"phase": "data", "rows": ds.hosts * ds.steps,
          "field_values": ds.hosts * ds.steps * len(devops.FIELDS),
          "seconds": time.monotonic() - t0})

    srv = state["server"] = Server(
        os.path.join(workdir, "data"), os.path.join(workdir, "server.log"),
        server_env(config, chips, workdir, args.rehearse))
    emit({"phase": "server_start", "seconds": srv.start()})
    device = state["device"] = read_device(srv, chips, args.rehearse)

    db = config["database"]["name"]
    if config["database"].get("create"):
        srv.sql("public", config["database"]["create"])
    emit(load_data(srv, db, ds, config["load"]))
    t0 = time.monotonic()
    srv.sql(db, "FLUSH")
    n, _t = count_and_max_time(srv, db)
    emit({"phase": "flush", "seconds": time.monotonic() - t0,
          "count_star": n})
    if n != ds.hosts * ds.loaded_steps:
        raise Fail(f"after load + FLUSH count(*) = {n}, generated "
                   f"{ds.hosts * ds.loaded_steps}")

    batches = None
    if mix.get("writers"):
        w = mix["writers"]
        batches = traffic.Batches(ds, int(w["batch_steps"]),
                                  int(w["batches"]))
        emit({"phase": "write_bodies", "batches": int(w["batches"]),
              "rows": batches.rows, "seconds": batches.build_seconds,
              "generator_rows_per_s": batches.rows / batches.build_seconds,
              "note": "what the generator alone can offer; the bodies are "
                      "built before the window"})

    t0 = time.monotonic()
    warm = traffic.run_phase(srv.port, db, ds, mix, args.seed,
                             traffic.WARMUP, seconds=None,
                             profile=bool(args.trace))
    problems = check_answers(ds, warm["queries"])
    emit({"phase": "warmup", "queries": len(warm["queries"]),
          "seconds": time.monotonic() - t0, "wrong": problems[:3]})
    srv.check_alive()
    return srv, ds, device, db, batches, problems


def check_guarantees(srv: Server, db: str, ds: devops.Dataset, config: dict,
                     mix: dict, win: dict, batches, prom_before: dict,
                     prom_after: dict) -> list[str]:
    """What the window must not have done, and what must be read back."""
    problems = []
    queries, writes = win["queries"], win["writes"]
    errs = device_errors(prom_after)
    if errs:
        problems.append(f"device lanes booked errors: {errs}")
    if (config.get("checks") or {}).get("mesh_engaged"):
        engaged = metric(prom_after, "cnosdb_mesh_total", lane="exec",
                         reason="engaged") - metric(
            prom_before, "cnosdb_mesh_total", lane="exec", reason="engaged")
        if engaged < len(queries):
            problems.append(f"the mesh lane engaged for {int(engaged)} of "
                            f"{len(queries)} queries")
    problems += [f"write: {w['error']}" for w in writes if not w["acked"]]
    if batches is None:
        return problems
    if batches.exhausted:
        problems.append("the prepared write bodies ran out inside the "
                        "window: the writers were starved")
    acked = [w for w in writes if w["acked"]]
    loaded_rows = ds.hosts * ds.loaded_steps
    want = (loaded_rows + sum(w["rows"] for w in acked),
            ds.step_ns(max([ds.loaded_steps - 1]
                           + [w["last_step"] for w in acked])))
    checks = {"read_back": count_and_max_time(srv, db)}
    if "kill_restart_read_back" in (mix.get("after_window") or []):
        t0 = time.monotonic()
        srv.stop(kill=True)
        restart_s = srv.start()
        checks["after_kill_restart"] = count_and_max_time(srv, db)
        emit({"phase": "kill_restart", "start_seconds": restart_s,
              "seconds": time.monotonic() - t0})
    problems += [f"{label}: count(*), max(time) = {got}; loaded + "
                 f"acknowledged = {want}"
                 for label, got in checks.items() if got != want]
    emit({"phase": "read_back", "acknowledged_rows": want[0] - loaded_rows,
          "checks": {k: list(v) for k, v in checks.items()}})
    return problems


def end_to_end(setup_s: float, win: dict) -> dict:
    """The client's own numbers; the line keeps those BENCHMARK.json lists
    for the cell. A failed or wrong request is slower than any limit."""
    queries = win["queries"]
    ms = [q["ms"] if q["ok"] else float("inf") for q in queries]
    done = [q["done"] for q in queries if q["ok"]]
    acked = [w for w in win["writes"] if w["acked"]]
    values = {"setup_s": setup_s, "query_p50_ms": statistics.median(ms),
              "query_p95_ms": traffic.percentile(ms, 95)}
    if done:
        values["queries_per_s"] = len(done) / (max(done) - win["t_start"])
    if acked:
        values["ingest_rows_per_s"] = sum(w["rows"] for w in acked) / (
            max(w["done"] for w in acked) - win["t_start"])
    return values


def per_layer(names, win: dict, prom_before: dict, prom_after: dict,
              reduced: dict) -> dict:
    ok = [q for q in win["queries"] if q["ok"]]
    acked = [w for w in win["writes"] if w["acked"]]
    client = {"queries": len(ok), "writes": len(acked)}
    if acked:
        client["write_ack_p50_ms"] = statistics.median(
            w["ms"] for w in acked)
    return layer_metrics.evaluate_all(names, layer_metrics.Window(
        ok, client, prom_before, prom_after, reduced))


def traced(workdir: str, trace_dir: str, trace_ctl: dict, win: dict,
           rehearse: bool) -> dict:
    """Reduce the trace, with the client's request log to name the gaps."""
    spans = [[q["req"].cls, q["sent_wall"], q["done_wall"]]
             for q in win["queries"]]
    spans += [["write", w["done_wall"] - w["ms"] / 1e3, w["done_wall"]]
              for w in win["writes"]]
    spans_path = os.path.join(workdir, "host_spans.json")
    with open(spans_path, "w") as f:
        json.dump({"mark_wall_s": trace_ctl["start"]["mark_wall_s"],
                   "spans": spans}, f)
    reduced = reduce_trace(trace_dir, spans_path, rehearse)
    emit({"phase": "trace",
          "stop_seconds": trace_ctl["stop"]["stop_seconds"],
          **{k: reduced[k] for k in (
              "busy_s", "window_s", "per_device", "n_events", "n_gaps",
              "xplane_bytes", "stand_in", "reduce_seconds") if k in reduced}})
    return reduced


def run(args, workdir: str, state: dict) -> tuple[dict, dict]:
    """→ (the line to print, the manifest it is held to)."""
    manifest = report.load_manifest()
    cell = report.cell_of(manifest, args.workload)
    config = load_json("configs", cell["config"] + ".json")
    mix = load_json("traffic", cell["traffic"] + ".json")
    trace = bool(args.trace)
    srv, ds, device, db, batches, problems = set_up(
        args, cell, config, mix, workdir, state)

    # ---- the window
    prom_before = srv.metrics()
    trace_dir = os.path.join(workdir, "trace")
    trace_ctl: dict = {}
    during = None
    if trace:
        during = trace_during(
            srv, trace_dir,
            min(float(mix["trace_seconds"]), 0.6 * args.seconds), trace_ctl)
    setup_s = time.monotonic() - _T0
    win = traffic.run_phase(srv.port, db, ds, mix, args.seed, traffic.WINDOW,
                            seconds=args.seconds, profile=trace,
                            batches=batches, during=during)
    srv.check_alive()
    prom_after = srv.metrics()
    memory = srv.control("memory")
    queries, writes = win["queries"], win["writes"]
    booked: dict = {}        # stage → queries of the window that booked it
    for q in queries:
        for k in ((q["profile"] or {}).get("stages") or {}):
            booked[k] = booked.get(k, 0) + 1
    emit({"phase": "window", "setup_s": setup_s, "queries": len(queries),
          "writes": len(writes), "memory": memory,
          "stages_booked": booked})
    if memory["source"] != "memory_stats" and not args.rehearse:
        raise Fail(f"no memory_stats from the device: {memory}")
    if not queries:
        raise Fail("no query was sent inside the window")
    problems += check_guarantees(srv, db, ds, config, mix, win, batches,
                                 prom_before, prom_after)
    srv.stop()
    state["server"] = None

    # ---- the reference, after the window and after the server
    t0 = time.monotonic()
    problems += check_answers(ds, queries)
    emit({"phase": "reference", "answers": len(queries),
          "seconds": time.monotonic() - t0})
    for p in problems[:10]:
        emit({"phase": "problem", "what": p})

    # ---- the line
    reduced = breakdown = None
    if trace:
        reduced = traced(workdir, trace_dir, trace_ctl, win, args.rehearse)
        values = per_layer(report.metrics_of(manifest, cell["name"], True),
                           win, prom_before, prom_after, reduced)
        breakdown = {"device_ops": reduced["device_ops"],
                     "idle_gaps": reduced["idle_gaps"]}
    else:
        values = end_to_end(setup_s, win)
    failed = sum(1 for q in queries if not q["ok"]) \
        + sum(1 for w in writes if not w["acked"])
    return report.last_line(
        correct=not problems and failed == 0,
        attempted=len(queries) + len(writes), failed=failed, values=values,
        units=report.metrics_of(manifest, cell["name"], trace),
        device=device, memory_peak_bytes=memory["peak_bytes"],
        trace=reduced, breakdown=breakdown), manifest


def main(argv=None) -> int:
    global DRY_RUN
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=1)
    p.add_argument("--seconds", type=float, default=None)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--rehearse", action="store_true",
                   help="toy size, any backend; a dry run, labelled so")
    p.add_argument("--keep", action="store_true",
                   help="keep the work directory (data, log, trace)")
    args = p.parse_args(argv)
    DRY_RUN = args.rehearse
    if not os.path.isdir(os.path.join(ROOT, "cnosdb_tpu")):
        print("benchmarks/run.py: no cnosdb_tpu package beside benchmarks/: "
              "there is no system to measure", file=sys.stderr)
        return 2
    if args.seconds is None:
        args.seconds = float(report.load_manifest()["run_seconds"])
    workdir = tempfile.mkdtemp(prefix="cnosdb_bench_")
    state: dict = {"server": None, "device": None}
    line = error = None
    try:
        line, manifest = run(args, workdir, state)
        report.validate(line, manifest, args.workload, bool(args.trace))
    except (Fail, report.ContractError, layer_metrics.MetricSpecError) as e:
        error = f"{type(e).__name__}: {e}"
    except Exception:    # a fault of the harness itself fails the run too
        error = traceback.format_exc()
    finally:
        if state["server"] is not None:
            if error is not None:
                emit({"phase": "server_log",
                      "tail": state["server"].log_tail(3000)})
            state["server"].stop()
        if args.keep:
            print(f"benchmarks/run.py: work directory kept: {workdir}",
                  file=sys.stderr)
        else:
            shutil.rmtree(workdir, ignore_errors=True)
    if error is not None:
        emit({"phase": "no_result", "error": error[-3000:],
              "refused_line": line})
        return 1
    if args.rehearse:
        emit({"phase": "would_print", "line": line,
              "note": "a rehearsal: not a result, no number here is a "
                      "device's"})
        return 0
    print(report.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
