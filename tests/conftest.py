"""Test configuration: force JAX onto a virtual 8-device CPU mesh.

Mirrors the reference's multi-node-on-one-machine test strategy
(/root/reference/python/ray/tests/conftest.py ray_start_cluster +
cluster_utils.Cluster): distributed behavior is exercised locally, here with
8 virtual XLA host devices standing in for a TPU slice.
"""

import os

# JAX_PLATFORMS is the one switch between CPU and chip, and every process
# the suite starts inherits it: tests are hermetic on the virtual CPU mesh.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("RAY_TPU_OBJECT_STORE_MEMORY_MB", "256")
os.environ.setdefault("RAY_TPU_WORKER_POOL_INITIAL_SIZE", "1")
# Per-node dashboard agents default ON in production; in the suite they
# would add a subprocess per nodelet across hundreds of cluster boots.
# The dedicated agent test re-enables them via GlobalConfig.update.
os.environ.setdefault("RAY_TPU_DASHBOARD_AGENT", "0")
# No persistent compile cache here: processes pinned to the CPU get no
# default (core/accelerator.py), and the suite's wall time is runtime
# waits, not compiles.

import asyncio  # noqa: E402
import inspect  # noqa: E402

import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line("markers", "asyncio: run test on a fresh event loop")
    config.addinivalue_line(
        "markers",
        "slow: heavy multi-process scenario excluded from tier-1 "
        "(-m 'not slow'); `make chaos` runs them")


@pytest.hookimpl(tryfirst=True)
def pytest_pyfunc_call(pyfuncitem):
    """Minimal stand-in for pytest-asyncio (not in the image)."""
    fn = pyfuncitem.obj
    if inspect.iscoroutinefunction(fn):
        kwargs = {k: pyfuncitem.funcargs[k]
                  for k in pyfuncitem._fixtureinfo.argnames}
        asyncio.run(fn(**kwargs))
        return True
    return None


@pytest.fixture(autouse=True, scope="module")
def _no_runtime_left_behind():
    """A module that initialised the runtime implicitly (any API call does)
    and never shut it down would make every later `ray_tpu.init()` on the
    same xdist worker fail with "called twice"."""
    yield
    import ray_tpu
    if ray_tpu.is_initialized():
        from ray_tpu import serve
        serve.shutdown()
        ray_tpu.shutdown()


#: a process may hold `vm.max_map_count` (65530) memory mappings, and every
#: program XLA compiles for the CPU keeps a few: an xdist worker that is
#: handed three model families' files in a row passes it, and the next
#: compile dies (a segmentation fault, an abort or a MemoryError)
_MAPS_HIGH = 30000


@pytest.fixture(autouse=True, scope="module")
def _compiled_programs_let_go():
    """After a module, where this process's mappings have grown past
    `_MAPS_HIGH`, drop JAX's caches of compiled programs (the next module
    compiles what it runs anyway)."""
    yield
    import gc
    import sys
    if "jax" not in sys.modules:
        return
    try:
        with open("/proc/self/maps") as f:
            maps = sum(1 for _ in f)
    except OSError:
        return
    if maps > _MAPS_HIGH:
        sys.modules["jax"].clear_caches()
        gc.collect()


@pytest.fixture
def local_cluster():
    """A started single-node runtime, shut down afterwards."""
    import ray_tpu
    ray_tpu.init(num_cpus=4, object_store_memory=256 * 1024 * 1024)
    yield
    ray_tpu.shutdown()
