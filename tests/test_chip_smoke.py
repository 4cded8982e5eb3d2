"""chip_smoke.py rehearsed off the chip: the same phases at toy size on the
CPU backend must pass, and the script must still refuse to call that a
chip run."""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def rehearsal():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    # the suite runs with the compile cache off; the rehearsal exercises it
    # (in its own work directory, which it removes)
    env.pop("JAX_ENABLE_COMPILATION_CACHE", None)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chip_smoke.py"), "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    lines = [json.loads(ln) for ln in p.stdout.splitlines() if ln.strip()]
    return p, lines


def test_rehearsal_passes_its_phases_and_is_not_a_chip_run(rehearsal):
    p, lines = rehearsal
    assert {"phases_passed": True}.items() <= lines[-2].items(), \
        p.stdout[-2000:] + p.stderr[-2000:]
    last = lines[-1]
    assert last["ok"] is False
    assert last["device"]["platform"] == "cpu"
    assert p.returncode != 0
    assert not any(ln.get("ok") is True for ln in lines)


def test_parent_never_imports_jax(rehearsal):
    _p, lines = rehearsal
    seen = [ln["jax_in_parent"] for ln in lines if "jax_in_parent" in ln]
    assert seen and not any(seen)
