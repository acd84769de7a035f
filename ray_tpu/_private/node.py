"""Head-node / session bootstrap.

TPU-native analogue of ``python/ray/_private/node.py`` + ``services.py``:
creates the session directory, starts the control plane and the head node
manager (in-process rather than as separate daemons — one host needs no
process boundary; extra nodes run :mod:`ray_tpu._private.node_proc`).
"""

from __future__ import annotations

import atexit
import getpass
import json
import logging
import os
import shutil
import subprocess
import sys
import tempfile
import threading
import time
from typing import Any, Dict, Optional, Tuple

from ray_tpu._private import protocol
from ray_tpu._private.config import GLOBAL_CONFIG
from ray_tpu._private.control_plane import ControlPlane
from ray_tpu._private.ids import JobID, NodeID, WorkerID
from ray_tpu._private.node_manager import NodeManager
from ray_tpu._private.object_store import ShmStore
from ray_tpu._private.worker import CoreWorker
from ray_tpu.util import tracing

logger = logging.getLogger(__name__)


def _default_tmp_root() -> str:
    return os.path.join(tempfile.gettempdir(),
                        f"ray_tpu_{getpass.getuser()}")


def _shm_root(session_name: str) -> str:
    base = "/dev/shm" if os.path.isdir("/dev/shm") else tempfile.gettempdir()
    return os.path.join(base, f"ray_tpu_{session_name}")


def _gc_stale_sessions(keep: Optional[str] = None) -> None:
    """Remove session/shm dirs whose head process is gone.

    Session names embed the head pid (``session_<ts>_<pid>``); a dead pid
    means a crashed driver left state behind (reference equivalent: session
    dir cleanup in ``ray start``).  ``keep`` preserves a named session —
    the head-restart path re-enters a dead head's session dir to replay
    its control-plane journal.
    """
    import glob
    import re
    for path in (glob.glob(os.path.join(_default_tmp_root(), "session_*"))
                 + glob.glob(_shm_root("session_*"))
                 # cross-host client stores: client_<session>_<clientpid>
                 + glob.glob(os.path.join(_default_tmp_root(), "client_*"))):
        # the kept session's dirs, its added nodes' shm roots among them
        if keep and (path.endswith(keep) or f"{keep}_node_" in path):
            continue
        m = re.search(r"_(\d+)$", path)
        if not m:
            continue
        pid = int(m.group(1))
        try:
            os.kill(pid, 0)
        except ProcessLookupError:
            shutil.rmtree(path, ignore_errors=True)
        except (PermissionError, OverflowError):
            pass    # not ours / a node id's digits, no pid at all


def default_resources(num_cpus: Optional[float],
                      num_tpus: Optional[float],
                      resources: Optional[Dict[str, float]]) -> Dict[str,
                                                                     float]:
    from ray_tpu.accelerators.tpu import (TPUAcceleratorManager,
                                          detect_num_tpus)
    out: Dict[str, float] = {}
    out["CPU"] = float(num_cpus) if num_cpus is not None else float(
        os.cpu_count() or 1)
    tpus = float(num_tpus) if num_tpus is not None else float(
        detect_num_tpus())
    if tpus:
        out["TPU"] = tpus
        head_res = TPUAcceleratorManager.get_pod_head_resource_name()
        if head_res:
            out[head_res] = 1.0
        out.update(TPUAcceleratorManager.get_pod_slice_resources())
    out.update({k: float(v) for k, v in (resources or {}).items()})
    out.setdefault("node:__internal_head__", 1.0)
    return out


def _session_candidates(tmp_root: Optional[str] = None):
    """(cp_address, session_dir) candidates, newest session first."""
    import glob
    root = tmp_root or _default_tmp_root()

    def mtime(path):
        try:
            return os.path.getmtime(path)
        except OSError:  # deleted between glob and stat
            return 0.0

    out = []
    for session in sorted(glob.glob(os.path.join(root, "session_*")),
                          key=mtime, reverse=True):
        addr_file = os.path.join(session, "cp_address")
        try:
            if os.path.exists(addr_file):
                with open(addr_file) as f:
                    out.append((f.read().strip(), session))
                continue
        except OSError:
            continue
        sock = os.path.join(session, "sockets", "cp.sock")
        if os.path.exists(sock):
            out.append((sock, session))
    return out


def find_session_cp_address(tmp_root: Optional[str] = None
                            ) -> Optional[Tuple[str, str]]:
    """Newest session's (cp_address, session_dir) on this host (may be
    stale — AttachedNode probes candidates with ping)."""
    candidates = _session_candidates(tmp_root)
    return candidates[0] if candidates else None


class _ClientStore(ShmStore):
    """Store for a CROSS-HOST attached driver.

    The session's shm arena isn't path-attachable from another machine,
    so this driver keeps a *private* local store (reads: the existing
    chunked pull protocol fetches remote objects into it) and mirrors
    every put to the head node manager chunk-by-chunk — the primary copy
    must live where cluster workers can pull it (reference shape:
    ``python/ray/util/client/server/proxier.py`` routing object I/O
    through a server-side worker).
    """

    def __init__(self, root: str, head_nm_client, **kwargs):
        super().__init__(root, **kwargs)
        self._head_nm = head_nm_client
        self._push_chunk = GLOBAL_CONFIG.object_transfer_chunk_bytes

    def put_serialized(self, object_id: bytes, obj) -> int:
        size = super().put_serialized(object_id, obj)
        view = self.get_view(object_id)
        if view is None:
            raise RuntimeError(
                f"object {object_id.hex()} vanished from the client store "
                "before it could be pushed to the cluster")
        total = len(view)
        if total == 0:
            self._head_nm.call("push_object_chunk", object_id, 0, 0, b"")
            return size
        off = 0
        while off < total:
            n = min(self._push_chunk, total - off)
            # slice per chunk: one chunk-sized copy live at a time
            self._head_nm.call("push_object_chunk", object_id,
                               total, off, bytes(view[off:off + n]))
            off += n
        del view
        # drop the mmap this get_view cached: a mapped object is skipped
        # by eviction, and a put-mostly client would otherwise pin every
        # pushed object in its private store forever
        self.release_mapping(object_id)
        return size


class AttachedNode:
    """A second driver connected to an EXISTING cluster.

    The client-mode the reference reaches with ``ray.init(address=...)``
    (``python/ray/_private/worker.py`` connect-to-existing): this
    process gets its own CoreWorker/job but rides the running session's
    control plane and head node manager.  On the same host the shm
    store is attached by path; from another host (detected by the
    session directory not existing locally, or forced with
    ``RAY_TPU_REMOTE_ATTACH=1``) object I/O routes through the head
    node manager over TCP: puts push chunks up, gets ride the standard
    pull protocol into a private local store.

    ``shutdown()`` detaches — it never tears the session down.
    """

    def __init__(self, address: str = "auto",
                 namespace: str = "default"):
        if address == "auto":
            # probe newest-first: a cleanly-shut-down session leaves its
            # dir (and cp_address file) behind, so ping until live
            cp_addr = session_dir = None
            for cand_addr, cand_dir in _session_candidates():
                try:
                    protocol.RpcClient(cand_addr,
                                       connect_timeout=2.0).ping()
                    cp_addr, session_dir = cand_addr, cand_dir
                    break
                except Exception:  # noqa: BLE001 — dead session
                    continue
            if cp_addr is None:
                raise ConnectionError(
                    "address='auto': no live ray_tpu session on this "
                    "host")
        elif os.path.isdir(address):  # a session directory
            with open(os.path.join(address, "cp_address")) as f:
                cp_addr = f.read().strip()
            session_dir = address
        else:  # explicit cp address (tcp:// or socket path)
            cp_addr = address
            session_dir = None
        self.cp_sock_path = cp_addr
        cp = protocol.RpcClient(cp_addr)
        cp.ping()  # fail fast on a dead session
        # the head node hosts the shared store + default scheduler
        head = None
        for info in cp.list_nodes():
            if info.get("state") != "ALIVE":
                continue
            if "node:__internal_head__" in (
                    info.get("resources_total") or {}):
                head = info
                break
        if head is None:
            raise ConnectionError("no ALIVE head node in session")
        self.session_dir = session_dir or head["session_dir"]
        self.session_name = os.path.basename(self.session_dir)
        if os.path.isdir(self.session_dir):
            tracing.use_session_dir(self.session_dir)
        self.node_id = head["node_id"]
        nm = protocol.RpcClient(head["sock_path"])
        remote_host = (os.environ.get("RAY_TPU_REMOTE_ATTACH") == "1"
                       or not os.path.isdir(self.session_dir))
        self._client_root = None
        if remote_host:
            # cross-host: private local store + push/pull through the
            # head NM (requires a tcp:// session).  The client gets its
            # OWN node id: pulls of head-resident objects must not be
            # skipped as "local" (worker._pull_remote compares node ids).
            self.node_id = NodeID.from_random().binary()
            # reap private stores left by drivers that died without a
            # clean shutdown — on a client-only host no HeadNode ever
            # runs this GC for us
            _gc_stale_sessions()
            client_root = os.path.join(
                _default_tmp_root(),
                f"client_{self.session_name}_{os.getpid()}")
            self._client_root = client_root
            store = _ClientStore(
                client_root, nm,
                spill_dir=GLOBAL_CONFIG.object_spill_dir
                or os.path.join(client_root, "spill"))
        else:
            # same host: attach the session's shm root by path —
            # per-object files + multi-process-safe arena.  spill_dir
            # mirrors the head's default so spilled objects stay
            # readable here.
            store = ShmStore(_shm_root(self.session_name),
                             spill_dir=GLOBAL_CONFIG.object_spill_dir
                             or os.path.join(self.session_dir, "spill"))
        self.store = store
        self.control_plane = cp
        self.job_id = JobID.from_random()
        self.worker = CoreWorker(
            mode="driver", job_id=self.job_id,
            worker_id=WorkerID.from_random(), node_id=self.node_id,
            control_plane=cp, node_manager=nm, shm_store=store,
            session_dir=self.session_dir, namespace=namespace,
            nm_addr=head["sock_path"])
        if remote_host:
            # puts are mirrored to the head's store: advertise THAT as
            # the committed location so cluster workers pull from it
            self.worker.commit_node_id = head["node_id"]
        from ray_tpu._private.ref_tracker import install_tracker
        install_tracker(self.worker.worker_id.binary(), cp,
                        node_id=self.node_id)
        self.log_monitor = None
        if GLOBAL_CONFIG.log_to_driver:
            from ray_tpu._private.log_streaming import DriverLogMonitor
            self.log_monitor = DriverLogMonitor(cp)
            self.log_monitor.start()
        self._stopped = False

    def shutdown(self):
        if self._stopped:
            return
        self._stopped = True
        from ray_tpu._private.ref_tracker import uninstall_tracker
        uninstall_tracker()
        try:
            # release every ref this driver still holds — nothing else
            # purges an attached driver's holder id (a crashed attach
            # leaks its pins until session end; bounded, but clean
            # detach should not)
            self.control_plane.purge_holder(self.worker.worker_id.binary())
        except Exception:  # noqa: BLE001 — session may be gone
            pass
        if self.log_monitor is not None:
            self.log_monitor.stop()
        if self._client_root:
            shutil.rmtree(self._client_root, ignore_errors=True)


class HeadNode:
    """Everything a single-host cluster needs, hosted in the driver."""

    def __init__(self, num_cpus: Optional[float] = None,
                 num_tpus: Optional[float] = None,
                 resources: Optional[Dict[str, float]] = None,
                 namespace: str = "default",
                 system_config: Optional[Dict[str, Any]] = None,
                 session_name: Optional[str] = None):
        GLOBAL_CONFIG.apply_system_config(system_config or {})
        with tracing.span("setup/init/session"):
            _gc_stale_sessions(keep=session_name)
            self.session_name = session_name or (
                f"session_{time.strftime('%Y%m%d_%H%M%S')}_{os.getpid()}")
            self.session_dir = os.path.join(_default_tmp_root(),
                                            self.session_name)
            os.makedirs(os.path.join(self.session_dir, "sockets"),
                        exist_ok=True)
            os.makedirs(os.path.join(self.session_dir, "logs"),
                        exist_ok=True)
            tracing.use_session_dir(self.session_dir)
        self.shm_root = _shm_root(self.session_name)
        self.spill_dir = (GLOBAL_CONFIG.object_spill_dir
                          or os.path.join(self.session_dir, "spill"))
        with tracing.span("setup/init/control_plane"):
            self._start_control_plane()
        with tracing.span("setup/init/object_store"):
            self.store = ShmStore(self.shm_root, spill_dir=self.spill_dir)
        if self.store.native_error:
            # said once, by the head: every worker falls back the same way
            logger.warning("object store: using the Python file store, the "
                           "native arena is unavailable (%s)",
                           self.store.native_error)
        self.node_id = NodeID.from_random().binary()
        self.resources = default_resources(num_cpus, num_tpus, resources)
        with tracing.span("setup/init/node_manager"):
            self.node_manager = NodeManager(
                node_id=self.node_id, session_dir=self.session_dir,
                control_plane=self.control_plane,
                cp_sock_path=self.cp_sock_path, shm_store=self.store,
                resources=self.resources)
        self.job_id = JobID.from_random()
        with tracing.span("setup/init/core_worker"):
            self.worker = CoreWorker(
                mode="driver", job_id=self.job_id,
                worker_id=WorkerID.from_random(), node_id=self.node_id,
                control_plane=self.control_plane,
                node_manager=self.node_manager, shm_store=self.store,
                session_dir=self.session_dir, namespace=namespace,
                nm_addr=self.node_manager.sock_path)
            from ray_tpu._private.ref_tracker import install_tracker
            install_tracker(self.worker.worker_id.binary(),
                            self.control_plane, node_id=self.node_id)
        self._extra_nodes: list = []
        self._stopped = False
        self._health_thread = threading.Thread(
            target=self._health_loop, daemon=True, name="head-health")
        self._health_thread.start()
        self._gc_thread = threading.Thread(
            target=self._gc_loop, daemon=True, name="head-object-gc")
        self._gc_thread.start()
        self.log_monitor = None
        if GLOBAL_CONFIG.log_to_driver:
            from ray_tpu._private.log_streaming import DriverLogMonitor
            with tracing.span("setup/init/log_monitor"):
                self.log_monitor = DriverLogMonitor(self.control_plane)
                self.log_monitor.start()
        atexit.register(self.shutdown)

    def _start_control_plane(self):
        self.control_plane = ControlPlane()
        self.cp_journal = None
        if GLOBAL_CONFIG.cp_persistence:
            from ray_tpu._private.persistence import (Journal,
                                                      restore_control_plane)
            journal_path = os.path.join(self.session_dir, "cp_journal.bin")
            restored = 0
            if os.path.exists(journal_path):
                restored = restore_control_plane(self.control_plane,
                                                 journal_path)
            self.cp_journal = Journal(journal_path,
                                      sync=GLOBAL_CONFIG.cp_journal_sync)
            self.control_plane.attach_journal(self.cp_journal)
            if restored:
                # compact on every restart so a crash loop can't grow the
                # journal (replays re-append on top of the old log)
                self.control_plane.compact_journal()
        if GLOBAL_CONFIG.use_tcp:
            self.cp_sock_path = f"tcp://{GLOBAL_CONFIG.node_ip}:0"
        else:
            self.cp_sock_path = os.path.join(self.session_dir, "sockets",
                                             "cp.sock")
        self.cp_server = protocol.RpcServer(self.cp_sock_path,
                                            self.control_plane, name="cp")
        self.cp_sock_path = self.cp_server.address
        with open(os.path.join(self.session_dir, "cp_address"), "w") as f:
            f.write(self.cp_sock_path)

    # ------------------------------------------------------------------
    def add_node(self, num_cpus: float = 1.0, num_tpus: float = 0.0,
                 resources: Optional[Dict[str, float]] = None,
                 env: Optional[Dict[str, str]] = None) -> bytes:
        """Spawn an extra node-manager process (multi-node simulation).

        Parity: reference ``python/ray/cluster_utils.py`` ``Cluster.add_node``
        (real raylet processes on one machine).
        """
        node_id = NodeID.from_random().binary()
        res = {"CPU": float(num_cpus)}
        if num_tpus:
            res["TPU"] = float(num_tpus)
        res.update(resources or {})
        proc_env = dict(os.environ)
        proc_env.update(env or {})
        from ray_tpu._private.node_proc import build_env
        # Every node owns a DISTINCT shm root: objects move between
        # nodes only via the chunked pull protocol (node_manager
        # fetch_object_chunk), never via a shared filesystem.  This is
        # what makes the single-host simulation faithful to multi-host
        # (reference: per-node plasma + object_manager Push/Pull).
        proc_env.update(build_env(
            session_dir=self.session_dir, cp_addr=self.cp_sock_path,
            node_id=node_id,
            shm_root=f"{self.shm_root}_node_{node_id.hex()[:12]}",
            spill_dir=os.path.join(self.spill_dir,
                                   f"node_{node_id.hex()[:12]}"),
            resources=res, use_tcp=GLOBAL_CONFIG.use_tcp,
            node_ip=GLOBAL_CONFIG.node_ip))
        log = open(os.path.join(self.session_dir, "logs",
                                f"node-{node_id.hex()[:12]}.log"), "ab")
        proc = subprocess.Popen(
            [sys.executable, "-m", "ray_tpu._private.node_proc"],
            env=proc_env, stdout=log, stderr=subprocess.STDOUT)
        log.close()
        self._extra_nodes.append((node_id, proc))
        deadline = time.time() + 30
        while time.time() < deadline:
            info = self.control_plane.get_node(node_id)
            if info is not None:
                return node_id
            if proc.poll() is not None:
                raise RuntimeError(
                    f"node process exited with {proc.returncode}")
            time.sleep(0.05)
        raise TimeoutError("extra node failed to register")

    def remove_node(self, node_id: bytes) -> None:
        for nid, proc in self._extra_nodes:
            if nid == node_id:
                proc.terminate()
                try:
                    proc.wait(timeout=5)
                except subprocess.TimeoutExpired:
                    proc.kill()
                self.control_plane.mark_node_dead(node_id, "removed")
                self._on_node_dead(node_id)
                return
        raise KeyError(node_id.hex())

    # ------------------------------------------------------------------
    def _health_loop(self):
        timeout = GLOBAL_CONFIG.health_check_timeout_s
        period = GLOBAL_CONFIG.health_check_period_s
        while not self._stopped:
            time.sleep(period)
            if self._stopped:
                return
            now = time.time()
            for info in self.control_plane.list_nodes():
                if info["state"] != "ALIVE":
                    continue
                if info["node_id"] == self.node_id:
                    continue
                if now - info.get("last_heartbeat", now) > timeout:
                    self.control_plane.mark_node_dead(
                        info["node_id"], "missed heartbeats")
                    try:
                        self._on_node_dead(info["node_id"])
                    except Exception:  # noqa: BLE001
                        import traceback
                        traceback.print_exc()

    def _on_node_dead(self, node_id: bytes):
        """Recover cluster state owned by a dead node.

        Reference behavior: ``gcs_actor_manager.cc`` (restart or kill the
        node's actors), ``gcs_placement_group_manager`` (reschedule
        bundles), and owner-side task retry.  Here the head drives all
        three from control-plane state.
        """
        cp = self.control_plane
        dead_hex = node_id.hex()
        # 0. refcounts: the dead node's workers flushed counts to the CP
        # and to owner NMs cluster-wide; their own NM died before it
        # could purge them, so the head broadcasts the purge
        cp.purge_node_holders(node_id)
        self.node_manager.purge_owned_node_holders(node_id)
        for info in cp.list_nodes():
            if (info.get("state") != "ALIVE"
                    or info["node_id"] == self.node_id):
                continue
            try:
                protocol.RpcClient(info["sock_path"]).call(
                    "purge_owned_node_holders", node_id)
            except (OSError, ConnectionError):
                pass
        # 1. actors hosted on the dead node: restart elsewhere or kill
        for info in cp.list_actors():
            if info.get("node_id") != node_id:
                continue
            if info.get("state") not in ("ALIVE", "PENDING", "RESTARTING"):
                continue
            aid = info["actor_id"]
            spec = info.get("creation_spec")
            max_restarts = info.get("max_restarts", 0)
            used = info.get("num_restarts", 0)
            if spec is not None and (max_restarts == -1
                                     or used < max_restarts):
                cp.update_actor(aid, state="RESTARTING",
                                num_restarts=used + 1, nm_sock=None,
                                node_id=None)
                self.node_manager.submit_actor_creation(spec)
            else:
                cp.update_actor(
                    aid, state="DEAD",
                    death_reason=f"node {dead_hex[:12]} died")
        # 2. normal tasks that were queued/running there: re-execute from
        # lineage (their callers still wait on the return objects)
        for ev in cp.tasks_last_state():
            if ev.get("node") != dead_hex:
                continue
            if ev.get("state") not in ("PENDING", "RUNNING", "RETRY"):
                continue
            spec = cp.get_lineage(bytes.fromhex(ev["task_id"]))
            if spec is not None and not spec.actor_creation \
                    and spec.actor_id is None:
                self.node_manager.submit_task(spec)
        # 3. placement groups with bundles on the dead node: release the
        # surviving reservations and re-reserve the whole group
        from ray_tpu.util import placement_group as pg_mod
        nodes_by_hex = {n["node_id"].hex(): n for n in cp.list_nodes()}
        for pg in cp.list_placement_groups():
            bundle_nodes = pg.get("bundle_nodes") or []
            if dead_hex not in bundle_nodes or pg.get("state") in (
                    "REMOVED", "FAILED"):
                continue
            for index, (bundle, nid_hex) in enumerate(
                    zip(pg.get("bundles", []), bundle_nodes)):
                node = nodes_by_hex.get(nid_hex)
                if node is None or node["state"] != "ALIVE":
                    continue
                try:
                    pg_mod._call(
                        pg_mod._nm_client_for(self.worker, node),
                        "return_bundle", pg["pg_id"], index, bundle)
                except (OSError, ConnectionError):
                    pass
            cp.update_placement_group(pg["pg_id"], state="RESCHEDULING",
                                      bundle_nodes=[])
            threading.Thread(
                target=pg_mod._reserve_loop,
                args=(pg["pg_id"], pg.get("bundles", []),
                      pg.get("strategy", "PACK")),
                daemon=True, name="pg-reschedule").start()

    def _gc_loop(self):
        """Periodic object GC: free unreferenced objects + fan out shm
        deletions to every node's store (reference: owner-driven
        free + plasma deletion)."""
        period = GLOBAL_CONFIG.object_gc_period_s
        grace = GLOBAL_CONFIG.object_gc_grace_s
        while not self._stopped:
            time.sleep(period)
            if self._stopped:
                return
            try:
                freed = self.control_plane.gc_sweep(grace)
                self.control_plane.maybe_compact(
                    GLOBAL_CONFIG.cp_journal_compact_records)
            except Exception:  # noqa: BLE001
                continue
            if not freed:
                continue
            self.node_manager.delete_objects(freed)
            for info in self.control_plane.list_nodes():
                if (info["state"] != "ALIVE"
                        or info["node_id"] == self.node_id):
                    continue
                try:
                    protocol.RpcClient(info["sock_path"]).call(
                        "delete_objects", freed)
                except (OSError, ConnectionError):
                    pass

    def shutdown(self):
        if self._stopped:
            return
        self._stopped = True
        from ray_tpu._private.ref_tracker import uninstall_tracker
        uninstall_tracker()
        for nid, proc in self._extra_nodes:
            proc.terminate()
        for nid, proc in self._extra_nodes:
            try:
                proc.wait(timeout=3)
            except subprocess.TimeoutExpired:
                proc.kill()
        self.node_manager.stop()
        if self.log_monitor is not None:
            self.log_monitor.stop()
        self.cp_server.shutdown()
        if self.cp_journal is not None:
            self.cp_journal.close()
        self.store.destroy()
        shutil.rmtree(self.spill_dir, ignore_errors=True)
        # extra-node stores (SIGKILLed nodes never ran their own cleanup)
        import glob
        for path in glob.glob(f"{self.shm_root}_node_*"):
            shutil.rmtree(path, ignore_errors=True)
