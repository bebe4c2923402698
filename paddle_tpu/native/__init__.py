"""Native (C++) runtime bindings.

Builds ``src/ptruntime.cc`` into a shared library on first import (g++,
cached beside the source; the ``.so`` is build output, git-ignored and
rebuilt from the committed source when absent) and binds it with ctypes —
the image has no pybind11, and the C ABI keeps the boundary trivial. When
the build or load fails the pure-Python paths keep working
(``AVAILABLE = False``), and the reason is printed once on stderr.
"""
from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import sys
import threading

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_HERE, "src", "ptruntime.cc")

AVAILABLE = False
_lib = None
_lock = threading.Lock()


def _build() -> str:
    with open(_SRC, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    out = os.path.join(_HERE, f"_ptruntime_{digest}.so")
    if os.path.exists(out):
        return out
    tmp = out + ".tmp"
    subprocess.run(
        ["g++", "-O3", "-std=c++17", "-shared", "-fPIC", "-pthread",
         _SRC, "-o", tmp],
        check=True, capture_output=True)
    os.replace(tmp, out)
    return out


def _load():
    global _lib, AVAILABLE
    with _lock:
        if _lib is not None or AVAILABLE:
            return _lib
        try:
            lib = ctypes.CDLL(_build())
        except (OSError, subprocess.CalledProcessError) as e:
            # _load runs once, at import: this is the one report
            detail = getattr(e, "stderr", b"") or b""
            print(f"paddle_tpu.native: no native runtime, using the "
                  f"pure-Python paths ({type(e).__name__}: {e}) "
                  f"{detail.decode(errors='replace')[-500:]}",
                  file=sys.stderr)
            return None
        lib.pt_collate.argtypes = [
            ctypes.POINTER(ctypes.c_void_p), ctypes.c_int64,
            ctypes.c_int64, ctypes.c_void_p, ctypes.c_int]
        lib.pt_host_alloc.restype = ctypes.c_void_p
        lib.pt_host_alloc.argtypes = [ctypes.c_int64]
        lib.pt_host_free.argtypes = [ctypes.c_void_p]
        for fn in ("pt_host_allocated", "pt_host_peak",
                   "pt_host_alloc_count"):
            getattr(lib, fn).restype = ctypes.c_int64
        _lib = lib
        AVAILABLE = True
        return lib


_load()


def collate_stack(arrays, n_threads: int = 0) -> np.ndarray:
    """Stack same-shape numpy arrays into one contiguous batch using the
    native parallel memcpy; equivalent to np.stack(arrays)."""
    lib = _lib
    if lib is None:
        return np.stack(arrays)
    # validate BEFORE any allocation/copies: shape (not just nbytes) and
    # dtype must match, else defer to np.stack (which raises on ragged)
    shape, dtype = arrays[0].shape, arrays[0].dtype
    for a in arrays:
        if a.shape != shape or a.dtype != dtype:
            return np.stack(arrays)
    n = len(arrays)
    contigs = [np.ascontiguousarray(a) for a in arrays]
    out = np.empty((n,) + shape, dtype)
    ptrs = (ctypes.c_void_p * n)(
        *[a.ctypes.data_as(ctypes.c_void_p).value for a in contigs])
    if n_threads <= 0:
        n_threads = min(max(os.cpu_count() // 2, 1), 8)
    lib.pt_collate(ptrs, n, contigs[0].nbytes,
                   out.ctypes.data_as(ctypes.c_void_p), n_threads)
    return out


def host_memory_stats() -> dict:
    """Host allocation stats of the native tracked allocator (reference
    memory/stats.cc facade)."""
    if _lib is None:
        return {"allocated": 0, "peak": 0, "alloc_count": 0,
                "native": False}
    return {"allocated": int(_lib.pt_host_allocated()),
            "peak": int(_lib.pt_host_peak()),
            "alloc_count": int(_lib.pt_host_alloc_count()),
            "native": True}


class HostBuffer:
    """A tracked, 64-byte-aligned host buffer (native allocator).

    Views handed out by :meth:`as_array` are tracked (weakly); ``free()``
    refuses while any view is alive so the memory can never be pulled out
    from under a live ndarray."""

    def __init__(self, nbytes: int):
        if _lib is None:
            raise RuntimeError("native runtime unavailable")
        self._ptr = _lib.pt_host_alloc(nbytes)
        if not self._ptr:
            raise MemoryError(f"pt_host_alloc({nbytes}) failed")
        self.nbytes = nbytes
        self._views = []

    def as_array(self, shape, dtype) -> np.ndarray:
        import weakref
        if not self._ptr:
            raise RuntimeError("buffer already freed")
        n = int(np.prod(shape)) * np.dtype(dtype).itemsize
        if n > self.nbytes:
            raise ValueError("buffer too small")
        buf = (ctypes.c_char * self.nbytes).from_address(self._ptr)
        arr = np.frombuffer(buf, dtype=dtype,
                            count=int(np.prod(shape))).reshape(shape)
        self._views = [r for r in self._views if r() is not None]
        self._views.append(weakref.ref(arr))
        return arr

    def _live_views(self) -> int:
        self._views = [r for r in self._views if r() is not None]
        return len(self._views)

    def free(self):
        if self._ptr:
            if self._live_views():
                raise RuntimeError(
                    f"{self._live_views()} live array view(s) reference "
                    "this buffer; drop them before free()")
            _lib.pt_host_free(self._ptr)
            self._ptr = None

    def __del__(self):
        # leak rather than dangle if views outlive the buffer object
        try:
            if self._ptr and not self._live_views():
                _lib.pt_host_free(self._ptr)
                self._ptr = None
        except Exception:
            pass


__all__ = ["AVAILABLE", "collate_stack", "host_memory_stats", "HostBuffer"]
