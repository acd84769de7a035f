"""Test fixtures.

Mirrors the reference's workhorse fixtures
(``python/ray/tests/conftest.py``: ``ray_start_regular``,
``ray_start_cluster``): a fresh runtime per test, plus an in-process
multi-node simulation.  JAX runs on a virtual 8-device CPU mesh so sharding
paths compile without TPU hardware (``chip_smoke.py`` runs on the real chip).
"""

import os
import sys

# Must run before jax initializes its backend: tests always run on the
# virtual 8-device CPU mesh, never on a chip (chip_smoke.py owns that).
# Asking for the CPU by name is also what turns Pallas interpret mode on
# (ops/substrate.py:use_interpret); the live config is updated too in
# case something imported jax before this file ran.
os.environ["JAX_PLATFORMS"] = "cpu"
if "xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8").strip()
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import pytest  # noqa: E402


@pytest.fixture(autouse=True, scope="module")
def _fresh_startup_record():
    """The start-up record (``ray_tpu/util/tracing.py``) is a process's,
    and a test worker is one process for many files: each file starts
    with an empty one, so that what it reads there (a step's compile in
    the exported timeline) never hangs on how many jits the files
    before it left under the record's cap."""
    from ray_tpu.util import tracing
    tracing.clear_recorded(startup=True)


@pytest.fixture
def ray_start_regular():
    import ray_tpu
    ray_tpu.init(num_cpus=4)
    yield ray_tpu
    ray_tpu.shutdown()


@pytest.fixture
def ray_start_2_cpus():
    import ray_tpu
    ray_tpu.init(num_cpus=2)
    yield ray_tpu
    ray_tpu.shutdown()


@pytest.fixture
def ray_start_cluster():
    """Head + helper to add simulated nodes (extra node-manager processes)."""
    import ray_tpu
    from ray_tpu._private.worker import global_node
    ray_tpu.init(num_cpus=2)
    node = global_node()
    yield node
    ray_tpu.shutdown()
