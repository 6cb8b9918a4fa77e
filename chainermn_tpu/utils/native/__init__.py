"""ctypes binding + build for the native data-loader core.

Compiled on first use with g++ (cached beside the source, for the
generic target so the file is valid on any machine of the same
architecture).  ``load_library()`` answers None when it cannot build —
callers that merely probe fall back to the pure-Python iterators;
``NativeLoader``, which a caller asks for by name, raises with the
build error.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading

import numpy as np

__all__ = ["load_library", "bind_signatures", "NativeLoader"]

_lock = threading.Lock()
_lib = None
_tried = False
_error = None


def _build(src, out):
    subprocess.run(
        ["g++", "-O3", "-shared", "-fPIC", "-std=c++17",
         "-pthread", src, "-o", out],
        check=True, capture_output=True)


def load_library():
    """Build (if needed) and load the shared library; None on failure."""
    global _lib, _tried, _error
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        here = os.path.dirname(__file__)
        src = os.path.join(here, "dataloader.cpp")
        out = os.path.join(here, "_dataloader.so")
        try:
            if not os.path.exists(out) or \
                    os.path.getmtime(out) < os.path.getmtime(src):
                _build(src, out)
            lib = ctypes.CDLL(out)
        except (OSError, subprocess.CalledProcessError) as e:
            stderr = getattr(e, "stderr", None)
            _error = f"{e}" + (f"\n{stderr.decode(errors='replace')}"
                               if stderr else "")
            return None
        bind_signatures(lib)
        _lib = lib
        return _lib


def bind_signatures(lib):
    """Declare the C ABI on a loaded library handle.  The single source
    of truth for the loader's ctypes signatures — also used by
    tools/tsan_check_dataloader.sh on its sanitizer-built variant, so a
    signature change cannot silently drift between the two."""
    lib.loader_create.restype = ctypes.c_void_p
    lib.loader_create.argtypes = [
        ctypes.c_void_p, ctypes.c_int64, ctypes.c_int64,
        ctypes.c_int64, ctypes.c_int, ctypes.c_int, ctypes.c_void_p]
    lib.loader_submit.restype = ctypes.c_int
    lib.loader_submit.argtypes = [
        ctypes.c_void_p, ctypes.POINTER(ctypes.c_int64),
        ctypes.c_int64]
    lib.loader_next.restype = ctypes.c_int
    lib.loader_next.argtypes = [
        ctypes.c_void_p, ctypes.POINTER(ctypes.c_void_p),
        ctypes.POINTER(ctypes.c_int64)]
    lib.loader_release.argtypes = [ctypes.c_void_p, ctypes.c_int]
    lib.loader_destroy.argtypes = [ctypes.c_void_p]
    return lib


class NativeLoader:
    """One gather engine over a contiguous [N, ...] numpy array.

    Ring-slot memory is allocated HERE as a numpy array and lent to the
    C++ engine: batch views are numpy slices whose ``.base`` chain keeps
    the ring alive, so a view held past ``close()`` (or interpreter
    shutdown teardown order) can go stale in CONTENT but never dangle —
    the use-after-free class of bugs is excluded by ownership."""

    def __init__(self, array: np.ndarray, max_batch: int, n_buffers=3,
                 n_threads=4):
        lib = load_library()
        if lib is None:
            raise RuntimeError(
                f"native loader unavailable: building "
                f"dataloader.cpp failed: {_error}")
        self._lib = lib
        self._array = np.ascontiguousarray(array)  # keep alive
        self.row_shape = self._array.shape[1:]
        self.dtype = self._array.dtype
        self._row_bytes = int(self._array.dtype.itemsize
                              * np.prod(self.row_shape, dtype=np.int64))
        self.max_batch = max_batch
        self._ring = np.empty((n_buffers, max_batch * self._row_bytes),
                              dtype=np.uint8)
        self._handle = lib.loader_create(
            self._array.ctypes.data_as(ctypes.c_void_p),
            self._array.shape[0], self._row_bytes, max_batch,
            n_buffers, n_threads,
            self._ring.ctypes.data_as(ctypes.c_void_p))

    def submit(self, indices: np.ndarray):
        idx = np.ascontiguousarray(indices, dtype=np.int64)
        rc = self._lib.loader_submit(
            self._handle, idx.ctypes.data_as(
                ctypes.POINTER(ctypes.c_int64)), idx.size)
        if rc != 0:
            raise ValueError("invalid indices or batch too large")

    def _next_raw(self):
        ptr = ctypes.c_void_p()
        rows = ctypes.c_int64()
        buf_id = self._lib.loader_next(self._handle, ctypes.byref(ptr),
                                       ctypes.byref(rows))
        if buf_id < 0:
            raise RuntimeError("loader stopped")
        n = rows.value
        # slice of the python-owned ring (not a raw-pointer frombuffer):
        # the view's .base keeps the memory alive beyond close()
        view = self._ring[buf_id, :n * self._row_bytes] \
            .view(self.dtype).reshape((n,) + self.row_shape)
        return view, buf_id

    def next(self) -> np.ndarray:
        """Owned batch copy (ring slot released immediately)."""
        view, buf_id = self._next_raw()
        batch = view.copy()
        self._lib.loader_release(self._handle, buf_id)
        return batch

    def next_view(self):
        """Zero-copy ``(view, buf_id)`` of the ring slot — the DLPack
        hand-off path.  The view aliases loader-owned memory: the caller
        must ``release(buf_id)`` once the batch has been consumed, and
        must not touch the view afterwards."""
        return self._next_raw()

    def release(self, buf_id):
        self._lib.loader_release(self._handle, buf_id)

    def close(self):
        if getattr(self, "_handle", None):
            self._lib.loader_destroy(self._handle)
            self._handle = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass
