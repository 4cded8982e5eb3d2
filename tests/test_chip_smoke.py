"""chip_smoke.py rehearsed off the chip: the same phases at toy size on the
CPU backend must pass, and the script must still refuse to call that a
chip run."""
import importlib.util
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod   # dataclasses resolves types through it
    spec.loader.exec_module(mod)
    yield mod
    del sys.modules[spec.name]


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


_TPU = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
_CPU = {"platform": "cpu", "kind": "cpu", "count": 1}


def _decode_table(**booked):
    return {("cnosdb_device_decode_total",
             (("lane", key.partition("_")[0]),
              ("reason", key.partition("_")[2]))): float(n)
            for key, n in booked.items()}


@pytest.mark.parametrize("device, force_dec, booked, fault", [
    # auto mode on a TPU: the lane stands behind the native decoder, so no
    # page need be decoded on the device, but every page is booked
    (_TPU, False, {"host_native_first": 44}, None),
    (_TPU, False, {"host_native_first": 40, "device_ok": 4}, None),
    (_TPU, False, {"host_native_first": 43}, "books fewer"),
    (_TPU, False, {}, "books fewer"),
    # forced: device-first, on any backend
    (_TPU, True, {"device_ok": 44}, None),
    (_CPU, True, {"device_ok": 44}, None),
    (_TPU, True, {"host_native_first": 44}, "no page was decoded"),
    (_CPU, True, {}, "no page was decoded"),
    # a CPU in auto mode has no lane and books nothing
    (_CPU, False, {}, None),
])
def test_decode_lane_check_follows_the_mode(smoke, device, force_dec,
                                            booked, fault):
    m = _decode_table(**booked)
    if fault is None:
        smoke.check_decode_lanes(m, device, force_dec, pages_floor=44)
    else:
        with pytest.raises(smoke.Fail, match=fault):
            smoke.check_decode_lanes(m, device, force_dec, pages_floor=44)
