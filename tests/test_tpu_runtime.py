"""How the runtime hands TPU chips to processes (no chip needed: the
node is told it has two, and the workers — like the whole suite — run
jax on the CPU, so only the bookkeeping and the environment are real).
"""

import os
import time

import pytest

_SUBSET_VARS = ("TPU_VISIBLE_CHIPS", "TPU_CHIPS_PER_PROCESS_BOUNDS",
                "TPU_PROCESS_BOUNDS", "TPU_PROCESS_PORT")


@pytest.fixture
def two_chip_node():
    import ray_tpu
    ray_tpu.init(num_cpus=2, num_tpus=2)
    yield ray_tpu
    ray_tpu.shutdown()


def _wait_for_free_chips(ray, n, timeout=30.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if ray.available_resources().get("TPU", 0) == n:
            return
        time.sleep(0.05)
    raise AssertionError(
        f"TPU never returned to {n}: {ray.available_resources()}")


def test_tpu_worker_takes_chips_once_and_frees_them_by_exiting(
        two_chip_node):
    ray = two_chip_node

    def report():
        return os.getpid(), {k: os.environ.get(k) for k in _SUBSET_VARS}

    one = ray.remote(num_tpus=1)(report)
    pid_a, env_a = ray.get(one.remote(), timeout=60)
    pid_b, env_b = ray.get(one.remote(), timeout=60)
    # a process that has had chips is never pointed at others
    assert pid_a != pid_b
    for env in (env_a, env_b):
        # a proper subset of the host: named to libtpu, one process wide
        assert env["TPU_VISIBLE_CHIPS"] in ("0", "1")
        assert env["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"
        assert env["TPU_PROCESS_BOUNDS"] == "1,1,1"
        assert int(env["TPU_PROCESS_PORT"]) > 0
    # the chips come back when the holders have exited, and only then
    # can a task that needs both of them start
    both = ray.remote(num_tpus=2)(report)
    pid_c, env_c = ray.get(both.remote(), timeout=60)
    assert pid_c not in (pid_a, pid_b)
    # every chip of the host is libtpu's default: nothing is set
    assert env_c == dict.fromkeys(_SUBSET_VARS)
    _wait_for_free_chips(ray, 2)
    for pid in (pid_a, pid_b, pid_c):
        with pytest.raises(ProcessLookupError):
            os.kill(pid, 0)


def test_chips_reach_a_worker_through_a_placement_group(two_chip_node):
    """Every JaxTrainer worker asks through a bundle, whose resources
    are renamed ``pg_<id>_<index>_TPU``."""
    ray = two_chip_node
    from ray_tpu.train import ScalingConfig
    from ray_tpu.train.worker_group import WorkerGroup

    # unset use_tpu: the workers take the cluster's chips, split evenly
    res = ScalingConfig(num_workers=1)._resources
    assert res["TPU"] == 2.0
    assert ScalingConfig(num_workers=2)._resources["TPU"] == 1.0
    assert "TPU" not in ScalingConfig(use_tpu=False)._resources

    group = WorkerGroup(1, res)
    try:
        env = group.execute(
            lambda: {k: os.environ.get(k) for k in
                     ("JAX_PLATFORMS", "TPU_VISIBLE_CHIPS")})[0]
    finally:
        group.shutdown()
    # a TPU worker is not pinned to the CPU by its node manager: it has
    # the driver's JAX_PLATFORMS (the suite's "cpu"), and all the chips
    assert env["JAX_PLATFORMS"] == os.environ["JAX_PLATFORMS"]
    assert env["TPU_VISIBLE_CHIPS"] is None
    _wait_for_free_chips(ray, 2)


def test_detection_counts_device_nodes_without_jax(monkeypatch):
    from ray_tpu.accelerators import tpu

    monkeypatch.setattr(tpu, "_count_device_nodes", lambda: 4)
    for var in ("JAX_PLATFORMS", "TPU_CHIP_COUNT", "TPU_NUM_DEVICES",
                "TPU_VISIBLE_CHIPS"):
        monkeypatch.delenv(var, raising=False)
    assert tpu.detect_num_tpus() == 4
    # the CPU was asked for: the chips are not this session's
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert tpu.detect_num_tpus() == 0
