"""`run.py --rehearse` end to end for every cell of BENCHMARK.json, with
`--trace 0` and `--trace 1`, on the CPU backend (four virtual devices for
the four-chip cell); each would-be last line through `validate()`. And the
two refusals: no TPU, and a checkout with no system in it. Each case
starts a server, so the file takes a few minutes:
`python -m pytest benchmarks/tests/test_rehearse.py -q`."""
import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks.lib import report  # noqa: E402

MANIFEST = report.load_manifest()
RUN = os.path.join(ROOT, "benchmarks", "run.py")


def run(args, cwd=ROOT, script=RUN, timeout=900):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    p = subprocess.run([sys.executable, script, *args], cwd=cwd, env=env,
                       capture_output=True, text=True, timeout=timeout)
    lines = [json.loads(x) for x in p.stdout.strip().splitlines()]
    return p.returncode, lines, p.stderr


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", [w["name"] for w in MANIFEST["workloads"]])
def test_rehearsal_line_meets_the_contract(cell, trace):
    rc, lines, err = run(["--workload", cell, "--seed", "2147483659",
                          "--seconds", "6", "--trace", str(trace),
                          "--rehearse"])
    assert rc == 0, (lines[-1], err[-2000:])
    # labelled a dry run on every line, and no line is a result
    assert all(x.get("dry_run") is True for x in lines)
    assert all(set(x) != report.TOP_KEYS for x in lines)
    last = lines[-1]
    assert last["phase"] == "would_print"
    line = last["line"]
    report.validate(line, MANIFEST, cell, bool(trace))
    assert line["correct"] is True and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"
    if trace:
        assert 0 < line["device"]["busy_s"] <= line["device"]["window_s"]
        assert line["breakdown"]["device_ops"]


def test_without_a_tpu_there_is_no_result():
    rc, lines, _err = run(["--workload", MANIFEST["workloads"][0]["name"],
                           "--seed", "1", "--seconds", "3", "--trace", "0"])
    assert rc != 0
    assert lines[-1]["phase"] == "no_result"
    assert "not on a tpu" in lines[-1]["error"]
    assert not report.TOP_KEYS <= set(lines[-1])


def test_benchmark_alone_is_refused(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmarks"), tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    rc, lines, err = run(["--workload", MANIFEST["workloads"][0]["name"],
                          "--seed", "1", "--seconds", "3", "--trace", "0"],
                         cwd=tmp_path,
                         script=str(tmp_path / "benchmarks" / "run.py"))
    assert rc != 0 and not lines
    assert "no cnosdb_tpu package" in err
