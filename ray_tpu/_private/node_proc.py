"""Extra node-manager process (multi-node simulation on one host).

Started by :meth:`ray_tpu._private.node.HeadNode.add_node`; runs one
NodeManager with its own worker pool and its OWN shm store root against
the shared control plane.  Objects created on other nodes arrive via the
chunked pull protocol (``NodeManager.fetch_object_chunk``), mirroring the
reference's node-to-node object manager
(``src/ray/object_manager/object_manager.cc`` Push/Pull).
"""

from __future__ import annotations

import json
import os
import signal
import threading

from ray_tpu._private import protocol
from ray_tpu._private.node_manager import NodeManager
from ray_tpu._private.object_store import ShmStore


def build_env(*, session_dir: str, cp_addr: str, node_id: bytes,
              shm_root: str, spill_dir: str, resources: dict,
              use_tcp: bool, node_ip: str = "127.0.0.1") -> dict:
    """The node_proc env contract, in ONE place (used by
    HeadNode.add_node and the ``ray-tpu start --address`` CLI)."""
    return {
        "RAY_TPU_SESSION_DIR": session_dir,
        "RAY_TPU_CP_SOCK": cp_addr,
        "RAY_TPU_USE_TCP": "1" if use_tcp else "0",
        "RAY_TPU_NODE_ID": node_id.hex(),
        "RAY_TPU_SHM_ROOT": shm_root,
        "RAY_TPU_SPILL_DIR": spill_dir,
        "RAY_TPU_NODE_RESOURCES": json.dumps(resources),
        "RAY_TPU_NODE_IP": node_ip,
    }


def main():
    from ray_tpu.util import tracing
    tracing.set_role("node")    # its ``setup/worker_spawn`` records
    session_dir = os.environ["RAY_TPU_SESSION_DIR"]
    cp_sock = os.environ["RAY_TPU_CP_SOCK"]
    node_id = bytes.fromhex(os.environ["RAY_TPU_NODE_ID"])
    resources = json.loads(os.environ["RAY_TPU_NODE_RESOURCES"])
    cp = protocol.RpcClient(cp_sock)
    store = ShmStore(os.environ["RAY_TPU_SHM_ROOT"],
                     spill_dir=os.environ.get("RAY_TPU_SPILL_DIR") or None)
    nm = NodeManager(node_id=node_id, session_dir=session_dir,
                     control_plane=cp, cp_sock_path=cp_sock,
                     shm_store=store, resources=resources,
                     node_ip=os.environ.get("RAY_TPU_NODE_IP",
                                            "127.0.0.1"))
    stop = threading.Event()

    def _term(signum, frame):
        stop.set()

    signal.signal(signal.SIGTERM, _term)
    signal.signal(signal.SIGINT, _term)
    stop.wait()
    nm.stop()
    store.destroy()


if __name__ == "__main__":
    main()
