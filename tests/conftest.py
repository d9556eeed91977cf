import gc
import os
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

# The test suite targets a deterministic 8-device virtual CPU mesh: the
# sharding tests need multiple devices, and unit tests must not depend on
# a chip being attached. XLA reads these variables when the first backend
# initializes, so they are set here, before any test module imports the
# engine. Set AUTOMERGE_TPU_TESTS_ON_TPU=1 to run on the real chip instead.

from automerge_tpu._env import setup_compile_cache, virtual_cpu_env  # noqa: E402

if os.environ.get("AUTOMERGE_TPU_TESTS_ON_TPU") != "1":
    _env = virtual_cpu_env(8)
    os.environ["JAX_PLATFORMS"] = _env["JAX_PLATFORMS"]
    os.environ["XLA_FLAGS"] = _env["XLA_FLAGS"]
setup_compile_cache()


@pytest.fixture
def collector_paused():
    """No automatic garbage collection during the test. With obs tracing
    on, every collection is a ``host/gc`` ring record, which a test that
    counts the ring's records exactly must not meet."""
    was = gc.isenabled()
    gc.disable()
    yield
    if was:
        gc.enable()
