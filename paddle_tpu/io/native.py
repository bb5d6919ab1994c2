"""ctypes bridge to the C++ IO runtime (csrc/libptio.so).

The native library provides the host-side runtime the reference implements
in C++ (paddle/fluid/operators/reader/buffered_reader.cc and the
shared-memory DataLoader queue): bounded blocking queues whose
wait/notify machinery runs outside the GIL, an aligned reusable buffer
pool for staging batches, and GIL-free memcpy/row-gather for collation.
Python objects can't cross the ctypes boundary, so the prefetcher stores
numpy payloads in a Python-side slot table and pushes slot ids through the
native queue.

Source checkouts build csrc/ with `make` on first use — from the
sources as committed, rebuilding whenever a .so is older than its .cc
(make's own rule), so a stale binary left in the working tree is never
what runs. Without sources (an installed package) a prebuilt
paddle_tpu/lib/*.so is used. When neither yields a library the loader
returns None and says why; callers then take the pure-python queue.
"""
from __future__ import annotations

import ctypes
import os
import subprocess
import threading
import warnings

import numpy as np

_LIB = None
_TRIED = False
_PKG = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_REPO = os.path.dirname(_PKG)


def native_lib_path(name):
    """Path of csrc's `lib<name>.so`, built (or refreshed) from the
    committed sources, else the copy shipped inside the package; None
    with a warning when the build fails."""
    src_dir = os.path.join(_REPO, "csrc")
    if os.path.exists(os.path.join(src_dir, f"{name}.cc")):
        so = os.path.join(src_dir, "build", f"lib{name}.so")
        try:
            r = subprocess.run(["make", "-C", src_dir, f"build/lib{name}.so"],
                               capture_output=True, timeout=120, text=True)
            err = r.stderr if r.returncode else ""
        except (OSError, subprocess.TimeoutExpired) as e:
            err = str(e)
        if not err and os.path.exists(so):
            return so
        warnings.warn(f"native {name} build failed, using the "
                      f"pure-python fallback:\n{err[-500:]}")
        return None
    shipped = os.path.join(_PKG, "lib", f"lib{name}.so")
    return shipped if os.path.exists(shipped) else None


def _load():
    global _LIB, _TRIED
    if _TRIED:
        return _LIB
    _TRIED = True
    so = native_lib_path("ptio")
    if so is None:
        return None
    try:
        lib = ctypes.CDLL(so)
    except OSError:
        return None
    lib.ptio_queue_create.restype = ctypes.c_void_p
    lib.ptio_queue_create.argtypes = [ctypes.c_int]
    lib.ptio_queue_push.argtypes = [ctypes.c_void_p, ctypes.c_long]
    lib.ptio_queue_push.restype = ctypes.c_int
    lib.ptio_queue_pop.argtypes = [ctypes.c_void_p]
    lib.ptio_queue_pop.restype = ctypes.c_long
    lib.ptio_queue_size.argtypes = [ctypes.c_void_p]
    lib.ptio_queue_size.restype = ctypes.c_int
    lib.ptio_queue_close.argtypes = [ctypes.c_void_p]
    lib.ptio_queue_destroy.argtypes = [ctypes.c_void_p]
    lib.ptio_pool_create.restype = ctypes.c_void_p
    lib.ptio_pool_create.argtypes = [ctypes.c_int, ctypes.c_size_t]
    lib.ptio_pool_acquire.restype = ctypes.c_void_p
    lib.ptio_pool_acquire.argtypes = [ctypes.c_void_p]
    lib.ptio_pool_release.argtypes = [ctypes.c_void_p, ctypes.c_void_p]
    lib.ptio_pool_release.restype = ctypes.c_int
    lib.ptio_pool_buffer_bytes.argtypes = [ctypes.c_void_p]
    lib.ptio_pool_buffer_bytes.restype = ctypes.c_size_t
    lib.ptio_pool_close.argtypes = [ctypes.c_void_p]
    lib.ptio_pool_destroy.argtypes = [ctypes.c_void_p]
    lib.ptio_memcpy.argtypes = [ctypes.c_void_p, ctypes.c_void_p,
                                ctypes.c_size_t]
    lib.ptio_gather_rows.argtypes = [ctypes.c_void_p,
                                     ctypes.POINTER(ctypes.c_void_p),
                                     ctypes.c_int, ctypes.c_size_t]
    _LIB = lib
    return _LIB


def native_available():
    return _load() is not None


class NativePrefetcher:
    """Bounded queue whose blocking machinery lives in C++ (outside the
    GIL). put() returns False once the queue is closed (consumer gone);
    get() returns the sentinel `NativePrefetcher.CLOSED` after close."""

    CLOSED = object()

    @classmethod
    def create(cls, depth):
        lib = _load()
        if lib is None:
            return None
        return cls(lib, depth)

    def __init__(self, lib, depth):
        self._lib = lib
        self._q = lib.ptio_queue_create(depth)
        self._slots = {}
        self._next = 0
        self._lock = threading.Lock()

    def put(self, item) -> bool:
        if self._q is None:
            return False
        with self._lock:
            sid = self._next
            self._next += 1
            self._slots[sid] = item
        if not self._lib.ptio_queue_push(self._q, sid):
            with self._lock:
                self._slots.pop(sid, None)
            return False
        return True

    def get(self):
        if self._q is None:
            return self.CLOSED
        sid = self._lib.ptio_queue_pop(self._q)
        if sid < 0:
            return self.CLOSED
        with self._lock:
            return self._slots.pop(sid)

    def close(self):
        """Wake every blocked producer/consumer; the queue stays alive so
        racing put/get calls stay safe. Call destroy() after joining all
        user threads to free the native object."""
        if self._q is not None:
            self._lib.ptio_queue_close(self._q)

    def destroy(self):
        """CONTRACT: no other thread may still call put/get (close first,
        then join) — the handle is freed here."""
        if self._q is not None:
            q, self._q = self._q, None
            self._lib.ptio_queue_destroy(q)


class BufferPool:
    """Aligned reusable staging buffers (ref: pinned-memory
    buffered_reader staging). acquire() -> (address, capacity_bytes)."""

    @classmethod
    def create(cls, n_buffers, nbytes):
        lib = _load()
        if lib is None:
            return None
        return cls(lib, n_buffers, nbytes)

    def __init__(self, lib, n_buffers, nbytes):
        self._lib = lib
        self._p = lib.ptio_pool_create(n_buffers, nbytes)
        self._nbytes = nbytes

    def acquire(self):
        if self._p is None:
            return None
        addr = self._lib.ptio_pool_acquire(self._p)
        return (addr, self._nbytes) if addr else None

    def release(self, addr):
        if self._p is not None:
            self._lib.ptio_pool_release(self._p, addr)

    def close(self):
        """Wake blocked acquirers; buffers stay valid until destroy()."""
        if self._p is not None:
            self._lib.ptio_pool_close(self._p)

    def destroy(self):
        """CONTRACT: no thread blocked in acquire, no buffer in use."""
        if self._p is not None:
            p, self._p = self._p, None
            self._lib.ptio_pool_destroy(p)


def gather_rows(samples, out=None, pool_addr=None):
    """Collate equal-shape C-contiguous numpy samples into one batch array
    with a single native gather (no Python-level copy loop).

    samples: list of np.ndarray with identical shape/dtype.
    out: optional preallocated [n, ...] array; pool_addr: optional raw
    staging address from BufferPool to gather into (returns a view)."""
    lib = _load()
    n = len(samples)
    first = np.ascontiguousarray(samples[0])
    row_bytes = first.nbytes
    shape = (n,) + first.shape
    rows = [np.ascontiguousarray(s) for s in samples]
    if lib is None:
        if out is not None:
            np.stack(rows, out=out)
            return out
        return np.stack(rows)
    ptrs = (ctypes.c_void_p * n)(
        *[r.ctypes.data_as(ctypes.c_void_p).value for r in rows])
    if pool_addr is not None:
        buf = (ctypes.c_char * (row_bytes * n)).from_address(pool_addr)
        batch = np.frombuffer(buf, dtype=first.dtype).reshape(shape)
        dst = pool_addr
    else:
        batch = out if out is not None else np.empty(shape, first.dtype)
        dst = batch.ctypes.data_as(ctypes.c_void_p)
    lib.ptio_gather_rows(dst, ptrs, n, row_bytes)
    return batch
