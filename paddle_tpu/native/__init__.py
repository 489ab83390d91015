"""Native runtime components (C++), loaded via ctypes.

The reference implements its data pipeline/runtime in C++
(fluid/operators/reader buffered readers, BlockingQueue, pin-memory staging);
this package is the TPU-native equivalent: a small C++ core compiled on
first use with the system toolchain (g++), with pure-python fallbacks when
no compiler is available.

Public surface:
    available()                -> bool
    unavailable_reason()       -> why not (None while available)
    shuffle_indices(n, seed)   -> np.ndarray[int64]  (Fisher-Yates, C++)
    collate_stack(samples)     -> np.ndarray         (threaded batch memcpy)
    TokenRing(capacity)        -> blocking MPMC ring (GIL-free waits)
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import sys
import threading
from typing import List, Optional, Sequence

import numpy as np

__all__ = ["available", "unavailable_reason", "shuffle_indices",
           "collate_stack", "TokenRing", "load_library"]

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "src", "dataloader_core.cpp")
_LIB_PATH = os.path.join(_DIR, "libpt_dataloader.so")

_lib: Optional[ctypes.CDLL] = None
_tried = False
_error: Optional[str] = None      # why the C++ core is not loaded
_lock = threading.Lock()


def _build() -> Optional[str]:
    """Compile the C++ core if needed.  Multi-process safe: each process
    compiles to a private temp file and atomically renames it into place,
    so concurrent launcher ranks never dlopen a half-written .so.
    A failed build (no g++, a compile error) is said once on stderr and
    kept for :func:`unavailable_reason`; callers then take the python
    fallbacks."""
    global _error
    try:
        have_lib = os.path.exists(_LIB_PATH)
        have_src = os.path.exists(_SRC)
        if have_lib and (not have_src or os.path.getmtime(_LIB_PATH)
                         >= os.path.getmtime(_SRC)):
            return _LIB_PATH
        if not have_src:
            _error = f"{_SRC} is missing and no built library exists"
            return None
        tmp = f"{_LIB_PATH}.{os.getpid()}.tmp"
        cmd = ["g++", "-O3", "-shared", "-fPIC", "-std=c++17", "-pthread",
               _SRC, "-o", tmp]
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
        os.replace(tmp, _LIB_PATH)  # atomic on POSIX
        return _LIB_PATH
    except (OSError, subprocess.SubprocessError) as e:
        detail = (getattr(e, "stderr", None) or b"").decode(
            errors="replace").strip()
        _error = f"building {_SRC} failed: {type(e).__name__}: {e}" \
            + (f"\n{detail}" if detail else "")
        print(f"paddle_tpu.native: {_error}; using the python fallbacks",
              file=sys.stderr)
        return None


def load_library() -> Optional[ctypes.CDLL]:
    global _lib, _tried, _error
    with _lock:
        if _tried:
            return _lib
        _tried = True
        path = _build()
        if path is None:
            return None
        try:
            lib = ctypes.CDLL(path)
            _bind(lib)
        except (OSError, AttributeError) as e:
            _error = f"loading {path} failed: {type(e).__name__}: {e}"
            print(f"paddle_tpu.native: {_error}; using the python "
                  "fallbacks", file=sys.stderr)
            return None
        _lib = lib
        return _lib


def _bind(lib: ctypes.CDLL) -> None:
    lib.pt_shuffle_indices.argtypes = [
        ctypes.c_int64, ctypes.c_uint64, ctypes.POINTER(ctypes.c_int64)]
    lib.pt_collate_copy.argtypes = [
        ctypes.POINTER(ctypes.c_void_p), ctypes.c_int64,
        ctypes.c_int64, ctypes.c_void_p, ctypes.c_int32]
    lib.pt_ring_create.restype = ctypes.c_void_p
    lib.pt_ring_create.argtypes = [ctypes.c_int32]
    lib.pt_ring_push.restype = ctypes.c_int32
    lib.pt_ring_push.argtypes = [ctypes.c_void_p, ctypes.c_int64]
    lib.pt_ring_pop.restype = ctypes.c_int32
    lib.pt_ring_pop.argtypes = [ctypes.c_void_p,
                                ctypes.POINTER(ctypes.c_int64)]
    lib.pt_ring_close.argtypes = [ctypes.c_void_p]
    lib.pt_ring_size.restype = ctypes.c_int32
    lib.pt_ring_size.argtypes = [ctypes.c_void_p]
    lib.pt_ring_destroy.argtypes = [ctypes.c_void_p]


def available() -> bool:
    return load_library() is not None


def unavailable_reason() -> Optional[str]:
    """Why :func:`available` is False (a missing compiler, a failed
    build or load), or None while the C++ core is loaded."""
    return None if available() else _error


def shuffle_indices(n: int, seed: int) -> np.ndarray:
    """Permutation of range(n); C++ Fisher-Yates when available."""
    lib = load_library()
    if lib is None:
        rng = np.random.default_rng(seed)
        return rng.permutation(n).astype(np.int64)
    out = np.empty(n, np.int64)
    lib.pt_shuffle_indices(
        n, ctypes.c_uint64(seed),
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)))
    return out


def collate_stack(samples: Sequence[np.ndarray],
                  num_threads: int = 4) -> np.ndarray:
    """np.stack(samples) with the copies done by C++ threads (GIL-free)."""
    lib = load_library()
    first = samples[0]
    if (lib is None or not first.flags.c_contiguous
            or first.nbytes < (1 << 12)
            or any(s.shape != first.shape or s.dtype != first.dtype
                   for s in samples)):
        # heterogeneous batches fall through so np.stack raises/promotes
        # instead of the C memcpy reading out of bounds
        return np.stack(samples)
    n = len(samples)
    contig = [s if s.flags.c_contiguous else np.ascontiguousarray(s)
              for s in samples]
    out = np.empty((n,) + first.shape, first.dtype)
    srcs = (ctypes.c_void_p * n)(
        *[c.ctypes.data_as(ctypes.c_void_p).value for c in contig])
    lib.pt_collate_copy(srcs, n, first.nbytes,
                        out.ctypes.data_as(ctypes.c_void_p), num_threads)
    return out


class TokenRing:
    """Bounded blocking MPMC ring of int64 tokens backed by the C++ core;
    falls back to queue.Queue.  Blocking waits happen outside the GIL."""

    def __init__(self, capacity: int):
        self._lib = load_library()
        if self._lib is not None:
            self._ring = self._lib.pt_ring_create(capacity)
            self._q = None
        else:
            import queue
            self._ring = None
            self._q = queue.Queue(maxsize=capacity)
            self._closed = False

    def push(self, token: int) -> bool:
        if self._ring is not None:
            return bool(self._lib.pt_ring_push(self._ring, token))
        if self._closed:
            return False
        self._q.put(token)
        return True

    def pop(self) -> Optional[int]:
        if self._ring is not None:
            out = ctypes.c_int64()
            ok = self._lib.pt_ring_pop(self._ring, ctypes.byref(out))
            return out.value if ok else None
        item = self._q.get()
        return None if item is None else item

    def close(self):
        if self._ring is not None:
            self._lib.pt_ring_close(self._ring)
        else:
            self._closed = True
            self._q.put(None)

    def leak(self):
        """Abandon the native ring without freeing it — used when a waiter
        thread may still be blocked inside it (leak beats use-after-free)."""
        self._ring = None

    def __len__(self):
        if self._ring is not None:
            return int(self._lib.pt_ring_size(self._ring))
        return self._q.qsize()

    # Touches only the ctypes handle — no Python locks, threads, or
    # queues — so LK005 stays silent here by construction; the disable
    # documents that this finalizer was audited, not just missed.
    def __del__(self):  # locklint: disable=LK005
        if getattr(self, "_ring", None) is not None:
            try:
                self._lib.pt_ring_close(self._ring)
                self._lib.pt_ring_destroy(self._ring)
            # finalizer: ctypes lib handle may already be unloaded at exit
            except Exception:  # tracelint: disable=TL006
                pass
            self._ring = None
