"""Node shared-memory object store.

TPU-native equivalent of the reference's Plasma store
(``src/ray/object_manager/plasma/store.cc``): immutable, sealed objects in
shared memory, read zero-copy by every process on the node.

Design: instead of a store *daemon* owning one big dlmalloc'd mmap and a
socket protocol (the reference's design, built for a world without
``memfd``/tmpfs maturity), each object is a file in a per-session tmpfs
directory (``/dev/shm``).  Creation is atomic (write to ``*.tmp``, then
``rename``), reads are ``mmap(MAP_SHARED, PROT_READ)`` so numpy buffers
deserialize as zero-copy views.  Capacity accounting + LRU eviction +
spill-to-disk are handled by :class:`ShmStore`; a C++ fastpath
(``src/shmstore``) accelerates bulk copies when built, with this module as
the always-available fallback.

The *tensor plane does not live here*: jax device arrays stay in HBM and
move over ICI/DCN via XLA collectives.  This store carries host-side task
args/returns, dataset blocks, and checkpoints.
"""

from __future__ import annotations

import mmap
import os
import shutil
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

from ray_tpu._private import serialization
from ray_tpu._private.config import GLOBAL_CONFIG
from ray_tpu.exceptions import ObjectStoreFullError


def _default_capacity() -> int:
    cap = GLOBAL_CONFIG.shm_store_capacity_bytes
    if cap:
        return cap
    try:
        total = os.sysconf("SC_PAGE_SIZE") * os.sysconf("SC_PHYS_PAGES")
    except (ValueError, OSError):
        total = 8 << 30
    return int(total * 0.3)


class _MappedObject:
    """Keeps the mmap alive as long as any deserialized view references it."""

    __slots__ = ("mm", "path")

    def __init__(self, path: str):
        self.path = path
        fd = os.open(path, os.O_RDONLY)
        try:
            size = os.fstat(fd).st_size
            self.mm = mmap.mmap(fd, size, prot=mmap.PROT_READ)
        finally:
            os.close(fd)

    def view(self) -> memoryview:
        return memoryview(self.mm)


class ShmStore:
    """Per-node object store rooted at a tmpfs directory."""

    # objects at or below this size go to the native arena when available
    ARENA_MAX_OBJECT = 4 * 1024 * 1024
    # in-flight pushed objects idle this long are assumed abandoned
    PUSH_STALE_S = 300.0

    def __init__(self, root: str, capacity: Optional[int] = None,
                 spill_dir: Optional[str] = None,
                 on_evict=None):
        self.root = root
        os.makedirs(root, exist_ok=True)
        self.capacity = capacity or _default_capacity()
        self.spill_dir = spill_dir
        # best-effort notification that a *dropped* (not spilled) copy
        # left this node — broadcast-chain bookkeeping hangs off it
        self.on_evict = on_evict
        self._lock = threading.Lock()
        # id -> (size, last_access); rebuilt lazily from disk on miss
        self._index: Dict[bytes, Tuple[int, float]] = {}
        self._used = 0
        # Sealed mmaps cached per process so repeated gets share one mapping.
        self._mapped: Dict[bytes, _MappedObject] = {}
        # In-flight pushed objects: id -> {offsets, total, ts}
        # (offset-keyed so an RPC-level chunk retry can't double-count).
        # In-flight bytes are reserved against capacity so two concurrent
        # big pushes can't jointly overfill the tmpfs, and pushes whose
        # client died mid-stream are purged after PUSH_STALE_S.
        self._push_progress: Dict[bytes, Dict[str, Any]] = {}
        self._push_reserved = 0
        # Native C++ arena fastpath (src/shmstore): one mmap shared by all
        # node processes; first process creates, the rest attach.
        self._arena = None
        # why the arena is missing where it was wanted
        self.native_error: Optional[str] = None
        if os.environ.get("RAY_TPU_DISABLE_NATIVE_STORE") != "1":
            try:
                from ray_tpu._private.shmstore_native import NativeArena
                arena_cap = min(self.capacity // 4, 2 << 30)
                self._arena = NativeArena(
                    os.path.join(root, "arena"), capacity=arena_cap,
                    create=True)
            except Exception as e:  # noqa: BLE001 - python file path still works
                self.native_error = f"{type(e).__name__}: {e}"

    @property
    def backend(self) -> str:
        """``"native"`` (C++ arena for small objects, files above it) or
        ``"python"`` (files only)."""
        return "python" if self._arena is None else "native"

    # -------------------------------------------------------- paths -----
    def _path(self, object_id: bytes) -> str:
        return os.path.join(self.root, object_id.hex())

    def _spill_path(self, object_id: bytes) -> str:
        assert self.spill_dir
        return os.path.join(self.spill_dir, object_id.hex())

    # -------------------------------------------------------- write -----
    def put_serialized(self, object_id: bytes,
                       obj: "serialization.SerializedObject") -> int:
        """Create + seal an object; returns its sealed size."""
        size = obj.total_bytes
        if self._arena is not None and size <= self.ARENA_MAX_OBJECT:
            if self._arena.put(object_id, obj.write_into, size):
                return size
        self._ensure_capacity(size)
        path = self._path(object_id)
        tmp = path + f".tmp.{os.getpid()}"
        with open(tmp, "w+b") as f:
            f.truncate(size)
            with mmap.mmap(f.fileno(), size) as mm:
                obj.write_into(memoryview(mm))
        os.rename(tmp, path)  # seal: atomic visibility
        with self._lock:
            self._index[object_id] = (size, time.monotonic())
            self._used += size
        return size

    def put_bytes(self, object_id: bytes, data: bytes) -> int:
        self._ensure_capacity(len(data))
        path = self._path(object_id)
        tmp = path + f".tmp.{os.getpid()}.{threading.get_ident()}"
        with open(tmp, "wb") as f:
            f.write(data)
        os.rename(tmp, path)
        with self._lock:
            self._index[object_id] = (len(data), time.monotonic())
            self._used += len(data)
        return len(data)

    def write_push_chunk(self, object_id: bytes, total: int,
                         offset: int, data: bytes) -> bool:
        """Assemble an object PUSHED by a remote client, chunk by chunk
        (the write side of the pull protocol — reference:
        ``object_manager/push_manager.cc``).  Returns True once every
        byte arrived and the object sealed."""
        path = self._path(object_id)
        tmp = path + ".push"
        now = time.monotonic()
        with self._lock:
            # reap pushes abandoned by a crashed client
            for oid, st in list(self._push_progress.items()):
                if now - st["ts"] > self.PUSH_STALE_S:
                    self._push_progress.pop(oid, None)
                    self._push_reserved -= st["total"]
                    try:
                        os.unlink(self._path(oid) + ".push")
                    except OSError:
                        pass
            if object_id in self._index:        # already sealed: re-push no-op
                return True
            st = self._push_progress.get(object_id)
            fresh = st is None
            if fresh:
                st = {"offsets": set(), "total": total, "ts": now}
                self._push_progress[object_id] = st
            else:
                st["ts"] = now
        if fresh:
            try:
                self._ensure_capacity(total)
                with self._lock:
                    self._push_reserved += total
            except Exception:
                with self._lock:
                    self._push_progress.pop(object_id, None)
                raise
        mode = "w+b" if fresh else "r+b"
        with open(tmp, mode) as f:
            if fresh:
                f.truncate(total)
            f.seek(offset)
            f.write(data)
        with self._lock:
            st = self._push_progress.get(object_id)
            if st is None:                       # concurrent sealer won
                return object_id in self._index
            st["offsets"].add((offset, len(data)))
            done = sum(n for _, n in st["offsets"]) >= total
            if done:
                self._push_progress.pop(object_id, None)
                self._push_reserved -= total
        if done:
            os.rename(tmp, path)  # seal
            with self._lock:
                if object_id not in self._index:
                    self._index[object_id] = (total, time.monotonic())
                    self._used += total
        return done

    def put_stream(self, object_id: bytes, size: int, chunks) -> int:
        """Create + seal an object from an iterator of byte chunks.

        Write path of the node-to-node pull protocol: chunks arrive over
        RPC and stream straight into the tmpfs file, sealed by rename.
        """
        self._ensure_capacity(size)
        path = self._path(object_id)
        # Per-writer tmp name: two threads pulling the same object
        # concurrently must not interleave into one file.
        tmp = path + f".tmp.{os.getpid()}.{threading.get_ident()}"
        written = 0
        try:
            with open(tmp, "wb") as f:
                for chunk in chunks:
                    f.write(chunk)
                    # visible watermark: the broadcast chain re-serves
                    # this partial file to downstream pullers as chunks
                    # land
                    f.flush()
                    written += len(chunk)
        except BaseException:
            # a failed source mid-stream must not orphan the tmp file:
            # downstream chain pullers read any .tmp.* as "pull in
            # progress here" and would poll this node pointlessly
            try:
                os.unlink(tmp)
            except OSError:
                pass
            raise
        if written != size:
            os.unlink(tmp)
            raise IOError(f"object {object_id.hex()}: streamed {written} "
                          f"bytes, expected {size}")
        with self._lock:
            if object_id in self._index:
                # a concurrent pull of this (immutable) object won the race
                os.unlink(tmp)
                return size
            os.rename(tmp, path)
            self._index[object_id] = (size, time.monotonic())
            self._used += size
        return size

    def read_chunk(self, object_id: bytes, offset: int,
                   length: int) -> Optional[bytes]:
        """Serve one chunk of a sealed object (pull-protocol read side)."""
        view = self.get_view(object_id)
        if view is None:
            return None
        return bytes(view[offset:offset + length])

    def sealed_path(self, object_id: bytes) -> Optional[str]:
        """Filesystem path of a sealed object (same-host fastpath: a
        co-hosted node copies the file kernel-side instead of pulling
        RPC chunks)."""
        path = self._path(object_id)
        if os.path.exists(path):
            return path
        if self.spill_dir is not None:
            sp = self._spill_path(object_id)
            if os.path.exists(sp):
                return sp
        return None

    def read_partial_chunk(self, object_id: bytes, offset: int,
                           length: int) -> Optional[bytes]:
        """Serve a chunk from an IN-PROGRESS pull of this object.

        Broadcast-chain read side (reference: push_manager.cc re-serves
        chunks as they arrive): a downstream puller reads the prefix a
        concurrent upstream pull has already written.  Returns None if
        no writer has reached offset+length yet (caller polls)."""
        import glob as _glob
        sealed = self.read_chunk(object_id, offset, length)
        if sealed is not None:
            return sealed
        best: Optional[str] = None
        best_size = -1
        for cand in _glob.glob(self._path(object_id) + ".tmp.*"):
            try:
                size = os.path.getsize(cand)
            except OSError:
                continue
            if size > best_size:
                best, best_size = cand, size
        if best is None or best_size < offset + length:
            return None
        try:
            with open(best, "rb") as f:
                f.seek(offset)
                data = f.read(length)
            return data if len(data) == length else None
        except OSError:
            return None

    def has_any_copy(self, object_id: bytes) -> bool:
        """Sealed, spilled, or in-progress-pull presence of the object
        on this node (broadcast-chain "is the parent worth polling")."""
        import glob as _glob
        if os.path.exists(self._path(object_id)):
            return True
        if self.spill_dir and os.path.exists(self._spill_path(object_id)):
            return True
        # an active pull flushes every chunk, so its tmp mtime stays
        # fresh; a tmp orphaned by a SIGKILLed writer goes stale and
        # must not read as "in progress" forever
        now = time.time()
        for cand in _glob.glob(self._path(object_id) + ".tmp.*"):
            try:
                if now - os.path.getmtime(cand) < 60.0:
                    return True
            except OSError:
                continue
        return False

    def put_file_copy(self, object_id: bytes, src_path: str,
                      size: int) -> bool:
        """Seal a local secondary copy from another store's sealed file
        (same-host transfer: one kernel-side copy, no RPC)."""
        import shutil
        self._ensure_capacity(size)
        path = self._path(object_id)
        tmp = path + f".tmp.{os.getpid()}.{threading.get_ident()}"
        try:
            shutil.copyfile(src_path, tmp)
            if os.path.getsize(tmp) != size:
                os.unlink(tmp)
                return False
        except OSError:
            try:
                os.unlink(tmp)
            except OSError:
                pass
            return False
        with self._lock:
            if object_id in self._index:
                os.unlink(tmp)
                return True
            os.rename(tmp, path)
            self._index[object_id] = (size, time.monotonic())
            self._used += size
        return True

    # --------------------------------------------------------- read -----
    def contains(self, object_id: bytes) -> bool:
        if self._arena is not None and self._arena.contains(object_id):
            return True
        return os.path.exists(self._path(object_id)) or (
            self.spill_dir is not None
            and os.path.exists(self._spill_path(object_id)))

    def get_view(self, object_id: bytes) -> Optional[memoryview]:
        """Zero-copy view of a sealed object; None if absent."""
        if self._arena is not None:
            view = self._arena.get(object_id)
            if view is not None:
                return view
        with self._lock:
            mapped = self._mapped.get(object_id)
            if mapped is not None:
                self._touch(object_id)
                return mapped.view()
        path = self._path(object_id)
        if not os.path.exists(path):
            if not self._restore_from_spill(object_id):
                return None
        try:
            mapped = _MappedObject(path)
        except (FileNotFoundError, ValueError):
            return None
        with self._lock:
            self._mapped[object_id] = mapped
            self._touch(object_id)
        return mapped.view()

    def get_object(self, object_id: bytes) -> Optional[Any]:
        view = self.get_view(object_id)
        if view is None:
            return None
        return serialization.deserialize_frame(view)

    def size_of(self, object_id: bytes) -> Optional[int]:
        try:
            return os.stat(self._path(object_id)).st_size
        except FileNotFoundError:
            return None

    # ------------------------------------------------------- delete -----
    def delete(self, object_id: bytes) -> bool:
        with self._lock:
            # the native call must not race destroy()'s detach — the
            # NM heartbeat's owner sweep can be mid-delete when the
            # session tears the store down
            arena_removed = (self._arena is not None
                             and self._arena.delete(object_id))
            self._mapped.pop(object_id, None)
            entry = self._index.pop(object_id, None)
            if entry:
                self._used -= entry[0]
        removed = arena_removed
        for path in ([self._path(object_id)]
                     + ([self._spill_path(object_id)] if self.spill_dir
                        else [])):
            try:
                os.unlink(path)
                removed = True
            except FileNotFoundError:
                pass
        return removed

    # ----------------------------------------------- eviction / spill ----
    def _touch(self, object_id: bytes) -> None:
        entry = self._index.get(object_id)
        if entry:
            self._index[object_id] = (entry[0], time.monotonic())

    def _ensure_capacity(self, need: int) -> None:
        if need > self.capacity:
            raise ObjectStoreFullError(
                f"object of {need} bytes exceeds store capacity "
                f"{self.capacity}")
        with self._lock:
            committed = self._used + self._push_reserved
            if committed + need <= self.capacity:
                return
            headroom = int(self.capacity * GLOBAL_CONFIG.shm_eviction_headroom)
            target = committed + need - self.capacity + headroom
            victims = sorted(self._index.items(), key=lambda kv: kv[1][1])
        freed = 0
        for oid, (size, _) in victims:
            if freed >= target:
                break
            if self._evict_one(oid):
                freed += size
        with self._lock:
            if self._used + self._push_reserved + need > self.capacity:
                raise ObjectStoreFullError(
                    f"cannot free {need} bytes (used={self._used}, "
                    f"in-flight pushes={self._push_reserved}, "
                    f"capacity={self.capacity})")

    def _evict_one(self, object_id: bytes) -> bool:
        """Spill to disk if configured, else drop (directory will recommit)."""
        path = self._path(object_id)
        with self._lock:
            if object_id in self._mapped:
                return False  # actively mapped in this process; skip
            entry = self._index.pop(object_id, None)
            if entry:
                self._used -= entry[0]
        if self.spill_dir:
            os.makedirs(self.spill_dir, exist_ok=True)
            try:
                shutil.move(path, self._spill_path(object_id))
                return True
            except FileNotFoundError:
                return False
        try:
            os.unlink(path)
        except FileNotFoundError:
            return False
        if self.on_evict is not None:
            try:
                self.on_evict(object_id)
            except Exception:  # noqa: BLE001 — notification best-effort
                pass
        return True

    def _restore_from_spill(self, object_id: bytes) -> bool:
        if not self.spill_dir:
            return False
        spath = self._spill_path(object_id)
        if not os.path.exists(spath):
            return False
        size = os.stat(spath).st_size
        self._ensure_capacity(size)
        shutil.move(spath, self._path(object_id))
        with self._lock:
            self._index[object_id] = (size, time.monotonic())
            self._used += size
        return True

    # -------------------------------------------------------- stats -----
    def stats(self) -> Dict[str, int]:
        with self._lock:
            out = {"used_bytes": self._used,
                   "capacity_bytes": self.capacity,
                   "num_objects": len(self._index),
                   "num_mapped": len(self._mapped)}
        if self._arena is not None:
            out["arena"] = self._arena.stats()
        return out

    def release_mappings(self) -> None:
        with self._lock:
            self._mapped.clear()

    def release_mapping(self, object_id: bytes) -> None:
        """Drop one cached mmap (existing views keep the map alive)."""
        with self._lock:
            self._mapped.pop(object_id, None)

    def destroy(self) -> None:
        self.release_mappings()
        with self._lock:
            arena, self._arena = self._arena, None
        if arena is not None:
            arena.detach()
        shutil.rmtree(self.root, ignore_errors=True)
