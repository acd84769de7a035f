"""ctypes binding for the native arena store (src/shmstore/shmstore.cc).

Builds the .so on first use if the toolchain is available (the build is a
single translation unit, sub-second); callers fall back to the pure-python
file store when unavailable.
"""

from __future__ import annotations

import ctypes
import mmap as _mmap
import os
import subprocess
import threading
from typing import Optional

_LIB_LOCK = threading.Lock()
_LIB: Optional[ctypes.CDLL] = None
# why this process has no native library (None: it has one, or has not
# tried); the build is attempted once per process
_UNAVAILABLE: Optional[str] = None


def unavailable_reason() -> Optional[str]:
    """Why :func:`get_lib` returned None, in the toolchain's own words."""
    return _UNAVAILABLE


def _native_dir() -> str:
    return os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "_native")


def _lib_path() -> str:
    return os.path.join(_native_dir(), "libshmstore.so")


def _src_dir() -> str:
    return os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), "src", "shmstore")


def _ensure_built() -> Optional[str]:
    global _UNAVAILABLE
    path = _lib_path()
    src = os.path.join(_src_dir(), "shmstore.cc")
    if os.path.exists(path) and os.path.exists(src) and \
            os.path.getmtime(path) >= os.path.getmtime(src):
        return path
    if not os.path.exists(src):
        _UNAVAILABLE = f"no source at {src}"
    elif _UNAVAILABLE is None:
        # a name of its own: processes that start together and all find
        # the library missing must not link into one another's output
        tmp = f"{path}.tmp.{os.getpid()}"
        try:
            os.makedirs(_native_dir(), exist_ok=True)
            subprocess.run(
                ["g++", "-O2", "-fPIC", "-std=c++17", "-shared", "-o",
                 tmp, src, "-lpthread"],
                check=True, capture_output=True, timeout=120)
            os.replace(tmp, path)
            return path
        except subprocess.CalledProcessError as e:
            _UNAVAILABLE = (f"g++ exited {e.returncode}: "
                            f"{e.stderr.decode(errors='replace')[-500:]}")
        except (subprocess.SubprocessError, OSError) as e:
            _UNAVAILABLE = f"build failed: {type(e).__name__}: {e}"
    return path if os.path.exists(path) else None


def get_lib() -> Optional[ctypes.CDLL]:
    global _LIB, _UNAVAILABLE
    with _LIB_LOCK:
        if _LIB is not None:
            return _LIB
        path = _ensure_built()
        if path is None:
            return None
        try:
            lib = ctypes.CDLL(path)
        except OSError as e:
            _UNAVAILABLE = f"cannot load {path}: {e}"
            return None
        lib.shmstore_create.restype = ctypes.c_void_p
        lib.shmstore_create.argtypes = [ctypes.c_char_p, ctypes.c_uint64,
                                        ctypes.c_uint32]
        lib.shmstore_attach.restype = ctypes.c_void_p
        lib.shmstore_attach.argtypes = [ctypes.c_char_p]
        lib.shmstore_create_object.restype = ctypes.c_int64
        lib.shmstore_create_object.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p, ctypes.c_uint64]
        lib.shmstore_seal.restype = ctypes.c_int
        lib.shmstore_seal.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
        lib.shmstore_get.restype = ctypes.c_int64
        lib.shmstore_get.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                     ctypes.POINTER(ctypes.c_uint64),
                                     ctypes.c_int]
        lib.shmstore_get_copy.restype = ctypes.c_int64
        lib.shmstore_get_copy.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                          ctypes.c_char_p, ctypes.c_uint64]
        lib.shmstore_evict.restype = ctypes.c_int
        lib.shmstore_evict.argtypes = [ctypes.c_void_p, ctypes.c_uint64]
        lib.shmstore_release.restype = ctypes.c_int
        lib.shmstore_release.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
        lib.shmstore_delete.restype = ctypes.c_int
        lib.shmstore_delete.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
        lib.shmstore_contains.restype = ctypes.c_int
        lib.shmstore_contains.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
        lib.shmstore_stats.argtypes = [ctypes.c_void_p,
                                       ctypes.POINTER(ctypes.c_uint64 * 6)]
        lib.shmstore_base.restype = ctypes.c_void_p
        lib.shmstore_base.argtypes = [ctypes.c_void_p]
        lib.shmstore_detach.argtypes = [ctypes.c_void_p]
        _LIB = lib
        return _LIB


class NativeArena:
    """One mmap'd arena; create on the head, attach everywhere else."""

    def __init__(self, path: str, capacity: int = 0,
                 max_entries: int = 65536, create: bool = False):
        lib = get_lib()
        if lib is None:
            raise RuntimeError(
                f"native shmstore unavailable ({unavailable_reason()})")
        self.lib = lib
        self.path = path
        if create:
            self.handle = lib.shmstore_create(path.encode(), capacity,
                                              max_entries)
            if not self.handle:
                # lost a create race: attach instead (the C side retries
                # until the winner's release-store publishes the magic)
                self.handle = lib.shmstore_attach(path.encode())
        else:
            # the creator may not have created the file yet; retry briefly
            import time as _time
            self.handle = None
            for _ in range(100):
                self.handle = lib.shmstore_attach(path.encode())
                if self.handle:
                    break
                _time.sleep(0.01)
        if not self.handle:
            raise RuntimeError(f"cannot open arena at {path}")
        base = lib.shmstore_base(self.handle)
        size = os.path.getsize(path)
        # one python memoryview over the whole arena for zero-copy reads
        self._view = (ctypes.c_ubyte * size).from_address(base)
        self.mem = memoryview(self._view).cast("B")

    def put(self, object_id: bytes, payload_writer, size: int) -> bool:
        """payload_writer(memoryview) fills the reserved slice."""
        off = self.lib.shmstore_create_object(self.handle, object_id, size)
        if off < 0:
            return False
        payload_writer(self.mem[off:off + size])
        self.lib.shmstore_seal(self.handle, object_id)
        return True

    def put_bytes(self, object_id: bytes, data: bytes) -> bool:
        return self.put(object_id, lambda m: m.__setitem__(
            slice(None), data), len(data))

    def get(self, object_id: bytes) -> Optional[memoryview]:
        """Copy the object out under the store mutex.

        Deliberately NOT zero-copy: a borrowed view into the arena can
        outlive the entry (delete + reallocate corrupts it from under the
        reader — round-1 advisory).  Arena objects are small (see
        ``ShmStore.ARENA_MAX_OBJECT``), so the locked memcpy is cheap;
        large objects take the file-mmap path, which IS zero-copy and
        unlink-safe.
        """
        while True:
            size = self.lib.shmstore_get_copy(self.handle, object_id,
                                              None, 0)
            if size < 0:
                return None
            buf = ctypes.create_string_buffer(size)
            rc = self.lib.shmstore_get_copy(self.handle, object_id, buf,
                                            size)
            if rc == -2:
                continue  # recreated bigger between the two calls; retry
            if rc < 0:
                return None
            return memoryview(buf)[:rc].toreadonly()

    def contains(self, object_id: bytes) -> bool:
        return bool(self.lib.shmstore_contains(self.handle, object_id))

    def delete(self, object_id: bytes) -> bool:
        return self.lib.shmstore_delete(self.handle, object_id) == 0

    def stats(self) -> dict:
        out = (ctypes.c_uint64 * 6)()
        self.lib.shmstore_stats(self.handle, ctypes.byref(out))
        return {"used_bytes": out[0], "capacity_bytes": out[1],
                "num_objects": out[2], "num_puts": out[3],
                "num_gets": out[4], "num_evictions": out[5]}

    def detach(self):
        if self.handle:
            self.lib.shmstore_detach(self.handle)
            self.handle = None
