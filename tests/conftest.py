"""Test rig: 8 virtual XLA CPU devices in one process.

The analog of the reference's `mpirun -np N ./multiverso_test` trick
(SURVEY.md §5): N ranks simulated on one machine. Here the N "ranks" are N
simulated XLA CPU devices forming a mesh in a single process.

Must set the env vars before jax initialises its backends, hence the
os.environ writes at import time (conftest imports before any test module).

Wall-clock note: the suite is CPU-bound (tracing and compiling), so
pytest-xdist makes it slower (workers re-trace every jit they run and
split the in-process jit cache). Speedups must come from doing less
work — e.g. the multihost child runs its P-invariant LDA variants at
P=2 only.
"""

import os

os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=8").strip()
# The suite runs on the virtual CPU mesh ONLY, whatever the caller's
# environment says: a chip belongs to one process at a time, so a test
# run that initialised the TPU backend would take it from (or lose it
# to) whatever else runs on the host. Pinned in code as well as by the
# tier-1 command's JAX_PLATFORMS=cpu.

import jax  # noqa: E402
import pytest  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_default_device", None)
# core.init() places the persistent compile cache in the checkout
# (<checkout>/.jax_cache). The suite and the processes it starts neither
# read nor fill it: a test must not pass because of what an earlier run
# left on disk.
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
jax.config.update("jax_enable_compilation_cache", False)


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices("cpu")
    assert len(devs) == 8, f"expected 8 virtual CPU devices, got {devs}"
    return devs


@pytest.fixture()
def mesh8(devices):
    """A 4x2 (data x model) mesh over the 8 virtual devices."""
    from multiverso_tpu import core
    m = core.init(devices=devices, data_parallel=4, model_parallel=2)
    yield m
    core.shutdown()


@pytest.fixture()
def mesh_dp8(devices):
    """Pure data-parallel 8x1 mesh."""
    from multiverso_tpu import core
    m = core.init(devices=devices, data_parallel=8, model_parallel=1)
    yield m
    core.shutdown()
