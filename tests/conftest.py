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


@pytest.fixture(scope="session", autouse=True)
def _assert_cpu_mesh():
    devices = jax.devices()
    assert devices[0].platform == "cpu" and len(devices) == 8, devices


@pytest.fixture(scope="module", autouse=True)
def _leave_the_pause_recorder():
    """Most files make schedulers and never close them (the worker is a
    daemon thread that never ends). Each keeps its place in the
    process-wide pause recorder, whose one heartbeat thread would go on
    feeding every registry this worker process ever made: after each
    file, leave on their behalf (the last one out takes the collector's
    hook off and joins the thread)."""
    yield
    from luminaai_tpu.monitoring.watchdog import ProcessPauses

    ProcessPauses.shutdown()
