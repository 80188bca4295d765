"""Test configuration: force an 8-device virtual CPU platform.

All sharding/mesh tests run against 8 virtual CPU devices
(xla_force_host_platform_device_count), mirroring how the reference tests
its framework logic with zero GPUs (SURVEY.md §4: mocker-based e2e).
This must run before jax is imported anywhere.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()
# No persistent compile cache here: engine/device.py keeps it off on the CPU
# backend, and a directory inherited from outside would switch it on for
# the tests that jit without an engine (and fill it from the suite).
os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
os.environ.setdefault("DYN_LOG", "warning")

import asyncio  # noqa: E402
import inspect  # noqa: E402

import pytest  # noqa: E402


def pytest_pyfunc_call(pyfuncitem):
    """Minimal async test support (pytest-asyncio is not in the image):
    coroutine test functions run under asyncio.run."""
    fn = pyfuncitem.obj
    if inspect.iscoroutinefunction(fn):
        kwargs = {name: pyfuncitem.funcargs[name] for name in pyfuncitem._fixtureinfo.argnames}
        asyncio.run(fn(**kwargs))
        return True
    return None


def pytest_configure(config):
    config.addinivalue_line("markers", "asyncio: async test (handled by conftest)")
    config.addinivalue_line("markers", "slow: multi-process e2e tests")
    config.addinivalue_line(
        "markers", "chaos: fault-injection tests (deterministic seed)")


@pytest.fixture(scope="session")
def cpu_devices():
    import jax

    return jax.devices()


@pytest.fixture
def chaos_seed():
    """Deterministic seed for chaos tests, overridable for replay debugging:
    DYN_CHAOS_SEED=1234 pytest -m chaos reruns every scenario with the
    failing seed. Always resets the in-process chaos engine afterwards so a
    configured plan can never leak into unrelated tests."""
    from dynamo_tpu import chaos

    seed = int(os.environ.get(chaos.SEED_ENV, "42"))
    yield seed
    chaos.reset()
