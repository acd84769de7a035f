"""TPU accelerator manager.

Parity target: reference ``python/ray/_private/accelerators/tpu.py``
(``TPUAcceleratorManager``) — chip detection, per-process chip ownership
through libtpu's environment variables, pod metadata.  Re-designed for a
JAX-first stack: detection counts the host's device nodes (as the
reference does), falls back to GCE/GKE metadata env vars, and never
imports jax (a driver that initialises jax takes the chips its workers
need).
"""

from __future__ import annotations

import glob
import os
import socket
import sys
from typing import List, Optional

RESOURCE_NAME = "TPU"
VISIBLE_CHIPS_ENV = "TPU_VISIBLE_CHIPS"
# GKE injects these; GCE metadata equivalents handled via env for now.
_TPU_CHIP_COUNT_ENVS = ("TPU_CHIP_COUNT", "TPU_NUM_DEVICES")
_TPU_TYPE_ENVS = ("TPU_ACCELERATOR_TYPE", "ACCELERATOR_TYPE")

# What libtpu reads when a process is to own a subset of its host's
# chips (the set jax's own multi-process TPU tests export,
# jax/_src/test_multiprocess.py): which chips, the shape of that subset,
# a process grid of one (each worker is its own jax world), a runtime
# port of its own, and leave to load beside another process's libtpu.
_SUBSET_ENVS = (VISIBLE_CHIPS_ENV, "TPU_CHIPS_PER_PROCESS_BOUNDS",
                "TPU_PROCESS_BOUNDS", "TPU_PROCESS_ADDRESSES",
                "TPU_PROCESS_PORT", "CLOUD_TPU_TASK_ID",
                "ALLOW_MULTIPLE_LIBTPU_LOAD")
_SUBSET_BOUNDS = {1: "1,1,1", 2: "1,2,1", 4: "2,2,1"}


def _count_device_nodes() -> int:
    """TPU chips on this host by their device nodes: ``/dev/accel<N>``
    (v2–v4) or the VFIO groups ``/dev/vfio/<N>`` (v5e and later) — what
    the reference's ``TPUAcceleratorManager`` and libtpu itself look
    for.  No jax, no libtpu: nothing here can take a chip."""
    accel = glob.glob("/dev/accel[0-9]*")
    if accel:
        return len(accel)
    return len([p for p in glob.glob("/dev/vfio/[0-9]*")
                if os.path.basename(p).isdigit()])


def _jax_backend_initialized() -> bool:
    try:
        from jax._src import xla_bridge
        return bool(xla_bridge._backends)
    except Exception:  # noqa: BLE001
        return False


_GCE_METADATA_URL = ("http://metadata.google.internal/computeMetadata"
                     "/v1/instance/attributes/")


_gce_cache: dict = {}


def _gce_metadata(attr: str, timeout: float = 0.5) -> Optional[str]:
    """Probe the GCE metadata server for a TPU-VM attribute
    (``accelerator-type``, ``agent-worker-number``, ``instance-id`` …).
    Reference: ``python/ray/_private/accelerators/tpu.py`` queries the
    same endpoints.  Short timeout + total failure tolerance: most
    deployments (tests, GKE with env injection, bare metal) have no
    metadata server."""
    if os.environ.get("RAY_TPU_DISABLE_GCE_METADATA") == "1":
        return None
    if attr in _gce_cache:          # negatives cached too: a host with
        return _gce_cache[attr]     # no metadata server never re-probes
    _gce_cache[attr] = None
    try:
        import urllib.request
        req = urllib.request.Request(
            _GCE_METADATA_URL + attr,
            headers={"Metadata-Flavor": "Google"})
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            _gce_cache[attr] = resp.read().decode().strip()
    except Exception:  # noqa: BLE001 - no metadata server here
        pass
    return _gce_cache[attr]


class TPUAcceleratorManager:
    @staticmethod
    def get_resource_name() -> str:
        return RESOURCE_NAME

    @staticmethod
    def get_visible_accelerator_ids_env_var() -> str:
        return VISIBLE_CHIPS_ENV

    @staticmethod
    def get_current_node_num_accelerators() -> int:
        # 0. the CPU was asked for: the chips are not this session's
        platforms = os.environ.get("JAX_PLATFORMS", "")
        if platforms and "tpu" not in platforms.split(","):
            return 0
        # 1. explicit override
        for env in _TPU_CHIP_COUNT_ENVS:
            value = os.environ.get(env)
            if value:
                try:
                    return int(value)
                except ValueError:
                    pass
        # 2. restricted visibility
        visible = os.environ.get(VISIBLE_CHIPS_ENV)
        if visible:
            return len([c for c in visible.split(",") if c != ""])
        # 3. the host's device nodes
        nodes = _count_device_nodes()
        if nodes:
            return nodes
        # 4. jax — but only if this process ALREADY initialized the
        #    backend.  jax.devices() would otherwise claim the chips for
        #    this process, starving workers that need them.
        jax = sys.modules.get("jax")
        if jax is not None and _jax_backend_initialized():
            try:
                return len([d for d in jax.devices()
                            if d.platform not in ("cpu", "gpu")])
            except Exception:  # noqa: BLE001
                return 0
        return 0

    @staticmethod
    def get_current_node_accelerator_type() -> Optional[str]:
        for env in _TPU_TYPE_ENVS:
            value = os.environ.get(env)
            if value:
                return value
        jax = sys.modules.get("jax")
        if jax is not None and _jax_backend_initialized():
            try:
                devs = [d for d in jax.devices()
                        if d.platform not in ("cpu", "gpu")]
                if devs:
                    return getattr(devs[0], "device_kind", "TPU")
            except Exception:  # noqa: BLE001
                pass
        return _gce_metadata("accelerator-type")

    @staticmethod
    def get_current_pod_name() -> Optional[str]:
        """Name of the TPU pod slice this host belongs to (env first,
        then GCE metadata).  Surfaced as a ``TPU-{pod_name}`` node
        resource so gang tasks can target one slice."""
        name = (os.environ.get("TPU_NAME")
                or os.environ.get("TPU_POD_NAME"))
        return name or _gce_metadata("instance-id")

    @staticmethod
    def get_pod_worker_id() -> int:
        value = os.environ.get("TPU_WORKER_ID")
        if value:
            try:
                return int(value)
            except ValueError:
                pass
        meta = _gce_metadata("agent-worker-number")
        try:
            return int(meta) if meta else 0
        except ValueError:
            return 0

    @staticmethod
    def get_pod_slice_resources() -> dict:
        """Extra node resources advertising pod membership:
        ``TPU-{pod_name}`` on every slice host (reference:
        ``ray.util.accelerators.tpu`` pod resources)."""
        out = {}
        pod = TPUAcceleratorManager.get_current_pod_name()
        if pod:
            out[f"TPU-{pod}"] = 1.0
        return out

    @staticmethod
    def set_visible_accelerator_ids(ids: List[int],
                                    host_chips: int) -> None:
        """Make chips ``ids`` (of the host's ``host_chips``) the ones
        libtpu gives this process.  Must run before jax initialises its
        backend here; afterwards the process owns what it took until it
        exits.

        All of the host's chips is libtpu's default, so nothing is set
        for it (and a subset inherited from a parent is scrubbed).  A
        proper subset is named through :data:`_SUBSET_ENVS`."""
        if len(ids) >= host_chips:
            for var in _SUBSET_ENVS:
                os.environ.pop(var, None)
            return
        bounds = _SUBSET_BOUNDS.get(len(ids))
        if bounds is None:
            raise ValueError(
                f"cannot give one process {len(ids)} of a host's "
                f"{host_chips} TPU chips: libtpu takes a subset of "
                f"{sorted(_SUBSET_BOUNDS)} chips, or all of them")
        with socket.socket() as s:       # a free port for this runtime
            s.bind(("127.0.0.1", 0))
            port = s.getsockname()[1]
        os.environ.update({
            VISIBLE_CHIPS_ENV: ",".join(str(i) for i in ids),
            "TPU_CHIPS_PER_PROCESS_BOUNDS": bounds,
            "TPU_PROCESS_BOUNDS": "1,1,1",
            "TPU_PROCESS_ADDRESSES": f"localhost:{port}",
            "TPU_PROCESS_PORT": str(port),
            "CLOUD_TPU_TASK_ID": "0",
            "ALLOW_MULTIPLE_LIBTPU_LOAD": "1",
        })

    @staticmethod
    def get_current_process_visible_accelerator_ids() -> Optional[List[int]]:
        visible = os.environ.get(VISIBLE_CHIPS_ENV)
        if visible is None:
            return None
        if visible == "":
            return []
        return [int(c) for c in visible.split(",")]

    @staticmethod
    def get_pod_worker_count() -> int:
        value = os.environ.get("TPU_WORKER_COUNT")
        return int(value) if value else 1

    @staticmethod
    def get_pod_head_resource_name() -> Optional[str]:
        """``TPU-<pod_type>-head`` resource on worker 0 of a pod slice.

        Mirrors the reference's pod-slice head resource so gang schedulers
        can target the host that must run the coordinator.
        """
        pod_type = TPUAcceleratorManager.get_current_node_accelerator_type()
        if pod_type and os.environ.get("TPU_WORKER_ID", "0") == "0":
            return f"TPU-{pod_type}-head"
        return None


def detect_num_tpus() -> int:
    return TPUAcceleratorManager.get_current_node_num_accelerators()
