"""ray_tpu — a TPU-native distributed computing framework.

Same capability surface as the reference (tasks, actors, objects, placement
groups, Train/Tune/Data/Serve/RLlib) with the tensor plane re-based on
JAX/XLA: device meshes + pjit/shard_map collectives over ICI/DCN instead of
NCCL, Pallas kernels for the hot ops, and host-side objects in a
shared-memory store.

Public API parity target: ``python/ray/_private/worker.py`` (init, remote,
get, put, wait, ...), ``python/ray/actor.py``, ``python/ray/exceptions.py``.
"""

from __future__ import annotations

import os
import threading
from typing import Any, Dict, List, Optional, Sequence, Union

from ray_tpu import exceptions
from ray_tpu._private import worker as _worker_mod
from ray_tpu._private.config import GLOBAL_CONFIG
from ray_tpu._private.worker import global_worker, is_initialized
from ray_tpu.actor import (ActorClass, ActorHandle, get_actor, method)
from ray_tpu.object_ref import ObjectRef, ObjectRefGenerator
from ray_tpu.remote_function import RemoteFunction

__version__ = "0.1.0"

_init_lock = threading.RLock()


def init(address: Optional[str] = None,
         num_cpus: Optional[float] = None,
         num_tpus: Optional[float] = None,
         resources: Optional[Dict[str, float]] = None,
         namespace: str = "default",
         ignore_reinit_error: bool = False,
         _system_config: Optional[Dict[str, Any]] = None,
         **kwargs) -> "RuntimeContext":
    """Start a ray_tpu runtime — or, with ``address``, connect to a
    running one as an additional driver ("auto", a session directory,
    or a control-plane address; parity: ``ray.init(address=...)``)."""
    with _init_lock:
        if is_initialized():
            if ignore_reinit_error:
                return get_runtime_context()
            raise RuntimeError(
                "ray_tpu.init() called twice; pass "
                "ignore_reinit_error=True to ignore")
        if address is None:
            # job entrypoints etc. inherit the cluster via env
            # (parity: RAY_ADDRESS)
            address = os.environ.get("RAY_TPU_ADDRESS") or None
        if address is not None:
            if any(v is not None for v in (num_cpus, num_tpus,
                                           resources, _system_config)):
                import warnings
                warnings.warn(
                    "init(address=...) attaches to an existing cluster; "
                    "num_cpus/num_tpus/resources/_system_config are "
                    "ignored (reference parity: ray.init warns too)",
                    stacklevel=2)
        from ray_tpu.util import tracing
        # the start-up record's first span (``util/tracing.py``): the
        # node's own parts are its children, ``setup/init/<part>``
        with tracing.span("setup/init") as sp:
            if address is not None:
                from ray_tpu._private.node import AttachedNode
                node = AttachedNode(address, namespace=namespace)
            else:
                from ray_tpu._private.node import HeadNode
                node = HeadNode(num_cpus=num_cpus, num_tpus=num_tpus,
                                resources=resources, namespace=namespace,
                                system_config=_system_config,
                                session_name=kwargs.pop("session_name",
                                                        None))
                sp.set(chips=int(node.resources.get("TPU", 0)))
            _worker_mod.set_global_worker(node.worker, node)
        return get_runtime_context()


def shutdown() -> None:
    with _init_lock:
        node = _worker_mod.global_node()
        _worker_mod.set_global_worker(None, None)
        if node is not None:
            node.shutdown()
        GLOBAL_CONFIG.reset()


def remote(*args, **kwargs):
    """``@remote`` decorator for functions and classes.

    Usage: ``@ray_tpu.remote`` or ``@ray_tpu.remote(num_cpus=2, ...)``.
    """
    def make(target):
        import inspect
        if inspect.isclass(target):
            return ActorClass(target, **kwargs)
        return RemoteFunction(target, **kwargs)

    if len(args) == 1 and not kwargs and callable(args[0]):
        return make(args[0])
    if args:
        raise TypeError("remote() takes keyword arguments only")
    return make


def get(refs: Union[ObjectRef, Sequence[ObjectRef]],
        *, timeout: Optional[float] = None) -> Any:
    return global_worker().get(refs, timeout=timeout)


def put(value: Any) -> ObjectRef:
    return global_worker().put(value)


def wait(refs: Sequence[ObjectRef], *, num_returns: int = 1,
         timeout: Optional[float] = None, fetch_local: bool = True):
    return global_worker().wait(refs, num_returns=num_returns,
                                timeout=timeout, fetch_local=fetch_local)


def kill(actor: ActorHandle, *, no_restart: bool = True) -> None:
    global_worker().kill_actor(actor._actor_id, no_restart)


def cancel(ref: ObjectRef, *, force: bool = False,
           recursive: bool = True) -> None:
    global_worker().cancel_task(ref)


def timeline(filename: Optional[str] = None) -> str:
    """Chrome-trace of task events (parity: ``ray.timeline``): returns
    the JSON string; also writes it to ``filename`` when given."""
    from ray_tpu._private.profiling import timeline as _tl
    return _tl(filename)


def nodes() -> List[Dict[str, Any]]:
    out = []
    for info in global_worker().cp.list_nodes():
        out.append({
            "NodeID": info["node_id"].hex(),
            "Alive": info["state"] == "ALIVE",
            "NodeManagerAddress": info.get("ip", "127.0.0.1"),
            "Resources": info.get("resources_total", {}),
            "Labels": info.get("labels", {}),
        })
    return out


def cluster_resources() -> Dict[str, float]:
    total: Dict[str, float] = {}
    for info in global_worker().cp.list_nodes():
        if info["state"] != "ALIVE":
            continue
        for k, v in info.get("resources_total", {}).items():
            total[k] = total.get(k, 0.0) + v
    return total


def available_resources() -> Dict[str, float]:
    total: Dict[str, float] = {}
    for info in global_worker().cp.list_nodes():
        if info["state"] != "ALIVE":
            continue
        for k, v in info.get("resources_available", {}).items():
            total[k] = total.get(k, 0.0) + v
    return total


class RuntimeContext:
    """Parity: ``python/ray/runtime_context.py``."""

    @property
    def worker(self):
        return global_worker()

    def get_node_id(self) -> str:
        return global_worker().node_id.hex()

    def get_job_id(self) -> str:
        return global_worker().job_id.hex()

    def get_worker_id(self) -> str:
        return global_worker().worker_id.hex()

    def get_actor_id(self) -> Optional[str]:
        aid = global_worker().current_actor_id
        return aid.hex() if aid else None

    def get_task_id(self) -> Optional[str]:
        tid = global_worker().current_task_id
        return tid.hex() if tid else None

    @property
    def namespace(self) -> str:
        return global_worker().namespace

    def get_assigned_resources(self) -> Dict[str, float]:
        return {}


def get_runtime_context() -> RuntimeContext:
    return RuntimeContext()


def _lazy_submodules():
    return {"data", "train", "tune", "serve", "rllib", "util", "workflow",
            "dag", "air"}


def __getattr__(name: str):
    if name in _lazy_submodules():
        import importlib
        mod = importlib.import_module(f"ray_tpu.{name}")
        globals()[name] = mod
        return mod
    raise AttributeError(f"module 'ray_tpu' has no attribute {name!r}")


__all__ = [
    "init", "shutdown", "is_initialized", "remote", "get", "put", "wait",
    "kill", "cancel", "get_actor", "method", "nodes", "timeline",
    "cluster_resources",
    "available_resources", "get_runtime_context", "ObjectRef",
    "ObjectRefGenerator", "ActorClass", "ActorHandle", "RemoteFunction",
    "exceptions", "__version__",
]
