"""Multi-host transport: every control/data RPC over TCP.

With ``use_tcp`` the control plane and every node manager bind
``tcp://127.0.0.1:<port>`` instead of unix sockets, so nothing in the RPC
path depends on a shared filesystem — the cluster works across hosts
(reference: ``src/ray/rpc/grpc_server.cc`` binds TCP;
``object_manager.proto`` Push/Pull run over it).
"""

import numpy as np
import pytest


@pytest.fixture
def tcp_cluster():
    import ray_tpu
    from ray_tpu._private.worker import global_node
    ray_tpu.init(num_cpus=1, _system_config={"use_tcp": True})
    node = global_node()
    node_b = node.add_node(num_cpus=2)
    yield ray_tpu, node, node_b
    ray_tpu.shutdown()


def test_addresses_are_tcp(tcp_cluster):
    ray, node, node_b = tcp_cluster
    assert node.cp_sock_path.startswith("tcp://")
    for info in node.control_plane.list_nodes():
        assert info["sock_path"].startswith("tcp://"), info


def test_cross_node_object_pull_over_tcp(tcp_cluster):
    ray, node, node_b = tcp_cluster
    from ray_tpu._private.worker import global_worker
    from ray_tpu.util.scheduling_strategies import (
        NodeAffinitySchedulingStrategy)

    @ray.remote(scheduling_strategy=NodeAffinitySchedulingStrategy(
        node_id=node_b.hex(), soft=False))
    def make_big():
        return np.arange(4_000_000, dtype=np.int64)      # 32 MB, not inline

    before = global_worker().num_remote_pulls
    arr = ray.get(make_big.remote(), timeout=120)
    assert int(arr[-1]) == 3_999_999
    assert global_worker().num_remote_pulls == before + 1


def test_actor_calls_over_tcp(tcp_cluster):
    ray, node, node_b = tcp_cluster
    from ray_tpu.util.scheduling_strategies import (
        NodeAffinitySchedulingStrategy)

    @ray.remote
    class Counter:
        def __init__(self):
            self.n = 0

        def add(self, k):
            self.n += k
            return self.n

    c = Counter.options(scheduling_strategy=NodeAffinitySchedulingStrategy(
        node_id=node_b.hex(), soft=False)).remote()
    assert ray.get([c.add.remote(1) for _ in range(5)][-1], timeout=60) == 5


def test_tcp_rpc_roundtrip_unit():
    """Protocol-level: server on an ephemeral TCP port, client calls it."""
    from ray_tpu._private import protocol

    class Handler:
        def echo(self, x):
            return x

        def boom(self):
            raise ValueError("boom")

    server = protocol.RpcServer("tcp://127.0.0.1:0", Handler(), name="t")
    assert server.address.startswith("tcp://127.0.0.1:")
    client = protocol.RpcClient(server.address)
    payload = b"x" * (8 * 1024 * 1024)
    assert client.call("echo", payload) == payload
    with pytest.raises(ValueError):
        client.call("boom")
    client.close()
    server.shutdown()


_REMOTE_DRIVER = """
import os, sys
import numpy as np
os.environ["RAY_TPU_REMOTE_ATTACH"] = "1"   # simulate another host
import ray_tpu
ray_tpu.init(address=sys.argv[1])

# put: primary copy must land on the cluster (pushed through the head
# NM), so a cluster worker can consume it
arr = np.arange(300_000, dtype=np.float32)   # > inline threshold
ref = ray_tpu.put(arr)

@ray_tpu.remote
def total(a):
    return float(a.sum())

assert ray_tpu.get(total.remote(ref), timeout=120) == float(arr.sum())

# get: a large result produced on the cluster pulls into the client's
# private store over TCP
@ray_tpu.remote
def make():
    return np.ones(300_000, dtype=np.float32)

out = ray_tpu.get(make.remote(), timeout=120)
assert out.shape == (300_000,) and float(out[0]) == 1.0

@ray_tpu.remote
class Counter:
    def __init__(self):
        self.n = 0
    def bump(self, k):
        self.n += k
        return self.n

c = Counter.remote()
assert ray_tpu.get(c.bump.remote(5), timeout=120) == 5
assert ray_tpu.get(c.bump.remote(2), timeout=120) == 7
ray_tpu.shutdown()
print("REMOTE_DRIVER_OK")
"""


def test_cross_host_driver_attach(tcp_cluster, tmp_path):
    """A driver on 'another host' (no path access to the session dir,
    forced via RAY_TPU_REMOTE_ATTACH): puts push chunks through the head
    node manager, gets ride the pull protocol into a private store,
    tasks and actors work end to end."""
    import os
    import subprocess
    import sys

    ray, node, node_b = tcp_cluster
    script = tmp_path / "remote_driver.py"
    script.write_text(_REMOTE_DRIVER)
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    # a plain CPU client process that finds the package by path
    env["PYTHONPATH"] = os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))
    out = subprocess.run(
        [sys.executable, str(script), node.cp_sock_path],
        capture_output=True, text=True, timeout=300, env=env)
    assert out.returncode == 0, (out.stdout[-2000:], out.stderr[-2000:])
    assert "REMOTE_DRIVER_OK" in out.stdout
