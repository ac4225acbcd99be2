"""Test harness: force an 8-device virtual CPU mesh.

Tests run on the CPU backend with 8 virtual devices (sharding coverage
without 8 real chips); set both before any backend is initialized.
"""

import os

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
)
# Child processes spawned by tests (dryrun workers, bench children, fleet
# replicas) must ALSO land on CPU: the in-process jax.config.update below
# doesn't reach subprocesses.
os.environ["JAX_PLATFORMS"] = "cpu"
# One persistent compile cache for the session, in a fresh directory that
# is removed at exit: the suite builds the same tiny programs dozens of
# times behind new closures (every engine, decoder and trainer jits its
# own), and XLA:CPU compiles on one core. A fresh directory keeps a run
# on a used checkout identical to one on a new checkout; children the
# tests spawn inherit it through the environment.
if "JAX_COMPILATION_CACHE_DIR" not in os.environ:
    import atexit
    import shutil
    import tempfile

    _cache = tempfile.mkdtemp(prefix="lumina_test_jax_cache_")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = _cache
    atexit.register(shutil.rmtree, _cache, ignore_errors=True)
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "0")

import jax

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402

# Fast-tier support: the files below hold the mesh-heavy / multi-process /
# end-to-end tests that dominate suite wall-clock (pipeline parity grids,
# two-OS-process multihost runs). The DEFAULT run is
# unchanged — full coverage — but `pytest -q -m "not slow"` gives a
# fast iteration tier, and multi-core machines can add `-n auto`
# (pytest-xdist) for parallel full runs.
_SLOW_FILES = {
    "test_pipeline.py",
    "test_multihost.py",
    "test_sharding.py",
    "test_ring_attention.py",
    "test_scan_layers.py",
    "test_orchestrator.py",
    "test_adaptive.py",
    "test_adapters.py",
}


def pytest_collection_modifyitems(config, items):
    for item in items:
        if item.fspath.basename in _SLOW_FILES:
            item.add_marker(pytest.mark.slow)


@pytest.fixture(scope="session", autouse=True)
def _assert_cpu_mesh():
    devices = jax.devices()
    assert devices[0].platform == "cpu" and len(devices) == 8, devices
