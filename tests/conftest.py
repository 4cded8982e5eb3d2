"""Test harness: run all tests on a virtual 8-device CPU mesh.

Env must be set before jax (or anything importing jax) loads, so this sits
at the very top of conftest.
"""
import os

# tests run on the CPU backend with 8 virtual devices (the mesh tests need
# them) whatever the machine has, and with the persistent compile cache off:
# six workers would fill <checkout>/.jax_cache, which the chip tool copies.
# Through the environment, so the servers the tests spawn inherit all three.
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "0"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture
def rng():
    return np.random.default_rng(42)


@pytest.fixture
def tmp_engine_dir(tmp_path):
    d = tmp_path / "engine"
    d.mkdir()
    return str(d)


@pytest.fixture(autouse=True)
def _isolate_health_state():
    """The gray-failure plane keeps process-global node state (latency
    scorer, slow-start ramps, hedge/breaker counters). Left standing, a
    breaker tripped in one test throttles RPCs in the next."""
    from cnosdb_tpu.parallel import health

    health.SCORER.reset()
    health.SLOW_START.reset()
    health.reset_counters()
    yield
