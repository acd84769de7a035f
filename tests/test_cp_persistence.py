"""Control-plane persistence: journal replay + head restart.

Parity target: the reference's GCS Redis persistence + rehydration
(``src/ray/gcs/store_client/redis_store_client.cc``,
``gcs_init_data.cc``) and the NotifyGCSRestart reconnect flow
(``node_manager.proto:352``).
"""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.parametrize("keep", [False, True])
def test_gc_stale_sessions_spares_node_roots(tmp_path, monkeypatch, keep):
    """An added node's shm root (``..._node_<id>``) carries no pid: the
    sweep leaves it where the node may outlive its head, does not take
    an id of twelve digits for one (``os.kill`` overflowed, and no
    ``init`` on the host got past the sweep), and a restarted head keeps
    every dir of the session it re-enters."""
    import tempfile

    from ray_tpu._private import node
    monkeypatch.setattr(tempfile, "gettempdir", lambda: str(tmp_path))
    monkeypatch.setattr(node, "_shm_root",
                        lambda name: str(tmp_path / f"ray_tpu_{name}"))
    dead = subprocess.Popen([sys.executable, "-c", "pass"])
    dead.wait()
    old = f"session_20260928_053528_{dead.pid}"
    other = f"session_20260928_053527_{dead.pid}"
    root = node._default_tmp_root()
    head_dirs = [os.path.join(root, old), node._shm_root(old)]
    node_roots = [node._shm_root(f"{old}_node_ab12cd34ef56"),
                  node._shm_root(f"{old}_node_935230712118")]
    gone = [os.path.join(root, other), node._shm_root(other),
            os.path.join(root, f"client_{old}_{dead.pid}")]
    kept = [os.path.join(root, f"session_20260928_053529_{os.getpid()}"),
            os.path.join(root, f"client_{old}_{os.getpid()}")]
    for path in head_dirs + node_roots + gone + kept:
        os.makedirs(path)
    node._gc_stale_sessions(keep=old if keep else None)
    if keep:
        kept += head_dirs + node_roots
    else:
        gone += head_dirs
        kept += node_roots
    assert [p for p in gone if os.path.exists(p)] == []
    assert [p for p in kept if not os.path.exists(p)] == []


def test_journal_roundtrip(tmp_path):
    from ray_tpu._private.control_plane import ControlPlane
    from ray_tpu._private.persistence import Journal, restore_control_plane

    path = str(tmp_path / "journal.bin")
    cp = ControlPlane(journal=Journal(path))
    cp.kv_put(b"k1", b"v1")
    cp.kv_put(b"k2", b"v2", namespace="ns")
    cp.kv_put(b"gone", b"x")
    cp.kv_del(b"gone")
    cp.put_inline(b"oid1", b"payload", owner=b"me")
    cp.commit_shm(b"oid2", 128, node_id=b"n1")
    cp.register_actor(b"a1", {"name": "counter", "state": "ALIVE"})
    cp.register_actor(b"a2", {"state": "ALIVE"})
    cp.update_actor(b"a2", state="DEAD")
    cp.register_node(b"n1", {"ip": "127.0.0.1", "sock_path": "/s"})
    cp.register_placement_group(b"pg1", {"bundles": [{"CPU": 1}]})
    cp.update_placement_group(b"pg1", state="CREATED")

    cp2 = ControlPlane()
    n = restore_control_plane(cp2, path)
    assert n >= 11
    assert cp2.kv_get(b"k1") == b"v1"
    assert cp2.kv_get(b"k2", namespace="ns") == b"v2"
    assert cp2.kv_get(b"gone") is None
    assert cp2.get_inline(b"oid1") == b"payload"
    assert cp2.get_location(b"oid2")["size"] == 128
    assert cp2.resolve_named_actor("counter") == b"a1"
    assert cp2.get_actor_info(b"a2")["state"] == "DEAD"
    assert cp2.get_node(b"n1")["ip"] == "127.0.0.1"
    assert cp2.get_placement_group(b"pg1")["state"] == "CREATED"


@pytest.mark.slow
def test_journal_compaction(tmp_path):
    from ray_tpu._private.control_plane import ControlPlane
    from ray_tpu._private.persistence import Journal, restore_control_plane

    path = str(tmp_path / "journal.bin")
    cp = ControlPlane(journal=Journal(path))
    for i in range(50):
        cp.kv_put(f"k{i}".encode(), b"v")
    size_before = os.path.getsize(path)
    assert cp.maybe_compact(threshold=10)
    assert os.path.getsize(path) < size_before
    cp.kv_put(b"post", b"compact")

    cp2 = ControlPlane()
    restore_control_plane(cp2, path)
    assert cp2.kv_get(b"k49") == b"v"
    assert cp2.kv_get(b"post") == b"compact"


def test_journal_truncated_tail(tmp_path):
    from ray_tpu._private.control_plane import ControlPlane
    from ray_tpu._private.persistence import Journal, restore_control_plane

    path = str(tmp_path / "journal.bin")
    cp = ControlPlane(journal=Journal(path))
    cp.kv_put(b"a", b"1")
    cp.kv_put(b"b", b"2")
    with open(path, "ab") as f:  # crash mid-write
        f.write(b"\xff\xff\xff\x7f partial garbage")
    cp2 = ControlPlane()
    restore_control_plane(cp2, path)
    assert cp2.kv_get(b"a") == b"1" and cp2.kv_get(b"b") == b"2"


def test_journal_reopen_truncates_torn_tail(tmp_path):
    """Records appended *after* a torn tail must not be lost: reopening
    the journal truncates to the last valid boundary first."""
    from ray_tpu._private.control_plane import ControlPlane
    from ray_tpu._private.persistence import Journal, restore_control_plane

    path = str(tmp_path / "journal.bin")
    j1 = Journal(path)
    j1.append("kv_put", (b"a", b"1", True, "default"))
    j1.close()
    with open(path, "ab") as f:  # crash mid-write
        f.write(b"\xff\xff\xff\x7f torn")
    # next session reopens the journal and keeps writing
    j2 = Journal(path)
    j2.append("kv_put", (b"b", b"2", True, "default"))
    j2.close()
    cp = ControlPlane()
    restore_control_plane(cp, path)
    assert cp.kv_get(b"a") == b"1"
    assert cp.kv_get(b"b") == b"2", "record behind torn tail was lost"


def test_post_restore_marks_old_head_dead():
    """After a head restart the previous head's node entry must not keep
    advertising node:__internal_head__ as ALIVE (init(address='auto')
    would attach to the dead head)."""
    from ray_tpu._private.control_plane import ControlPlane

    cp = ControlPlane()
    cp.register_node(b"oldhead", {
        "resources_total": {"CPU": 4, "node:__internal_head__": 1.0}})
    cp.register_node(b"worker1", {"resources_total": {"CPU": 4}})
    state = cp.dump_state()
    cp2 = ControlPlane()
    cp2.load_state(state)
    cp2.post_restore()
    assert cp2.get_node(b"oldhead")["state"] == "DEAD"
    assert cp2.get_node(b"worker1")["state"] == "ALIVE"


_PHASE1 = """
import os, sys
import ray_tpu
ray_tpu.init(num_cpus=2, _system_config={"cp_persistence": True})
from ray_tpu._private.worker import global_node
node = global_node()

@ray_tpu.remote
class Counter:
    def ping(self):
        return "pong"

Counter.options(name="survivor", lifetime="detached").remote()
ref = ray_tpu.put(b"x" * 200000)   # above inline threshold -> shm
small = ray_tpu.put({"answer": 42})
from ray_tpu._private.worker import global_worker
global_worker().cp.kv_put(b"mykey", b"myvalue")
print("SESSION=" + node.session_name)
print("SHMREF=" + ref.binary().hex())
print("SMALLREF=" + small.binary().hex())
sys.stdout.flush()
os._exit(0)   # head dies without any cleanup
"""

_PHASE2 = """
import os, sys
session, shm_hex, small_hex = sys.argv[1], sys.argv[2], sys.argv[3]
import ray_tpu
ray_tpu.init(num_cpus=2, session_name=session,
             _system_config={"cp_persistence": True})
from ray_tpu._private.worker import global_worker
cp = global_worker().cp
assert cp.kv_get(b"mykey") == b"myvalue", "kv lost"
aid = cp.resolve_named_actor("survivor")
assert aid is not None, "named actor directory lost"
info = cp.get_actor_info(aid)
assert info is not None and info.get("state") in ("ALIVE", "PENDING",
                                                  "RESTARTING"), info
from ray_tpu.object_ref import ObjectRef
small = ObjectRef(bytes.fromhex(small_hex))
assert ray_tpu.get(small, timeout=10) == {"answer": 42}, "inline data lost"
shm = ObjectRef(bytes.fromhex(shm_hex))
loc = cp.get_location(shm.binary())
assert loc is not None and loc["where"] == "shm", loc
data = ray_tpu.get(shm, timeout=10)
assert bytes(data) == b"x" * 200000, "shm data lost"
print("RESTORE_OK")
ray_tpu.shutdown()
"""


def test_head_restart_restores_cluster_state(tmp_path):
    """Kill the head mid-run; a new head on the same session restores
    named actors, KV, and the object directory — including shm payloads
    that outlived the head process."""
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    p1 = subprocess.run([sys.executable, "-c", _PHASE1], env=env,
                        capture_output=True, text=True, timeout=120,
                        cwd=REPO)
    assert p1.returncode == 0, p1.stderr
    out = dict(line.split("=", 1) for line in p1.stdout.splitlines()
               if "=" in line)
    assert "SESSION" in out, p1.stdout

    p2 = subprocess.run(
        [sys.executable, "-c", _PHASE2, out["SESSION"], out["SHMREF"],
         out["SMALLREF"]],
        env=env, capture_output=True, text=True, timeout=120, cwd=REPO)
    assert p2.returncode == 0, p2.stderr + p2.stdout
    assert "RESTORE_OK" in p2.stdout


_PHASE1_SURVIVOR = """
import os, sys, time
import ray_tpu
ray_tpu.init(num_cpus=1, _system_config={"cp_persistence": True})
from ray_tpu._private.worker import global_node
node = global_node()
nid = node.add_node(num_cpus=2, resources={"pin": 1.0})

@ray_tpu.remote(resources={"pin": 0.5})
class Pinned:
    def __init__(self):
        self.n = 0
    def bump(self):
        self.n += 1
        return self.n

a = Pinned.options(name="pinned", lifetime="detached").remote()
assert ray_tpu.get(a.bump.remote(), timeout=60) == 1
print("SESSION=" + node.session_name)
print("NODEPID=%d" % node._extra_nodes[0][1].pid)
sys.stdout.flush()
os._exit(0)   # head dies; the extra node process survives
"""

_PHASE2_SURVIVOR = """
import os, signal, sys, time
session, nodepid = sys.argv[1], int(sys.argv[2])
import ray_tpu
try:
    ray_tpu.init(num_cpus=1, session_name=session,
                 _system_config={"cp_persistence": True})
    # surviving node managers reconnect via the rebound CP socket; the
    # detached actor on that node keeps its in-memory state
    a = ray_tpu.get_actor("pinned")
    val = ray_tpu.get(a.bump.remote(), timeout=60)
    assert val == 2, f"actor state lost: bump() == {val}"
    print("SURVIVOR_OK")
    ray_tpu.shutdown()
finally:
    try:
        os.kill(nodepid, signal.SIGKILL)
    except ProcessLookupError:
        pass
"""


def test_head_restart_live_actor_survives(tmp_path):
    """A detached actor on a separate node process keeps running across a
    head crash + restart: the node manager reconnects to the rebound CP
    socket and the actor's in-memory state is intact (reference flow:
    GCS FT + NotifyGCSRestart, gcs_server.cc / node_manager.proto:352)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    p1 = subprocess.run([sys.executable, "-c", _PHASE1_SURVIVOR], env=env,
                        capture_output=True, text=True, timeout=150,
                        cwd=REPO)
    assert p1.returncode == 0, p1.stderr
    out = dict(line.split("=", 1) for line in p1.stdout.splitlines()
               if "=" in line)
    p2 = subprocess.run(
        [sys.executable, "-c", _PHASE2_SURVIVOR, out["SESSION"],
         out["NODEPID"]],
        env=env, capture_output=True, text=True, timeout=150, cwd=REPO)
    assert p2.returncode == 0, p2.stderr + p2.stdout
    assert "SURVIVOR_OK" in p2.stdout
