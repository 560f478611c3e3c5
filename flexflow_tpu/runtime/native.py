"""ctypes bindings for the native runtime components (``native/*.cc``).

The shared library is compiled on demand with g++ (no pybind11 in this
image; flat C ABI + ctypes instead, per the reference's cffi approach to
its C API, ``src/c/flexflow_c.cc``).  The build is cached next to the
source and keyed on the source mtime; any failure degrades gracefully —
callers fall back to the pure-Python path.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading
from typing import Optional, Sequence

import numpy as np

_NATIVE_DIR = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "native")
_BUILD_DIR = os.path.join(_NATIVE_DIR, "build")

_lib = None
_lib_lock = threading.Lock()
_lib_failed = False


def _build_and_load() -> Optional[ctypes.CDLL]:
    src = os.path.join(_NATIVE_DIR, "ffdl.cc")
    if not os.path.exists(src):
        return None
    os.makedirs(_BUILD_DIR, exist_ok=True)
    so = os.path.join(_BUILD_DIR, "libffnative.so")
    if not os.path.exists(so) or os.path.getmtime(so) < os.path.getmtime(src):
        tmp = so + ".tmp"
        cmd = ["g++", "-O3", "-std=c++17", "-shared", "-fPIC", "-pthread",
               src, "-o", tmp]
        subprocess.run(cmd, check=True, capture_output=True)
        os.replace(tmp, so)
    lib = ctypes.CDLL(so)
    lib.ffdl_create.restype = ctypes.c_void_p
    lib.ffdl_create.argtypes = [ctypes.c_uint64, ctypes.c_uint64,
                                ctypes.c_int, ctypes.c_uint64]
    lib.ffdl_add_array.restype = ctypes.c_int
    lib.ffdl_add_array.argtypes = [ctypes.c_void_p, ctypes.c_void_p,
                                   ctypes.c_uint64, ctypes.c_uint64]
    lib.ffdl_num_batches.restype = ctypes.c_uint64
    lib.ffdl_num_batches.argtypes = [ctypes.c_void_p]
    lib.ffdl_reset.restype = None
    lib.ffdl_reset.argtypes = [ctypes.c_void_p]
    lib.ffdl_next.restype = ctypes.c_int64
    lib.ffdl_next.argtypes = [ctypes.c_void_p,
                              ctypes.POINTER(ctypes.c_void_p)]
    lib.ffdl_destroy.restype = None
    lib.ffdl_destroy.argtypes = [ctypes.c_void_p]
    return lib


def get_lib() -> Optional[ctypes.CDLL]:
    """The native library, building it on first use; None if unavailable."""
    global _lib, _lib_failed
    if _lib is not None or _lib_failed:
        return _lib
    with _lib_lock:
        if _lib is None and not _lib_failed:
            try:
                _lib = _build_and_load()
            except Exception:
                _lib_failed = True
    return _lib


def native_available() -> bool:
    return get_lib() is not None


class NativeBatchIterator:
    """Drop-in for :class:`flexflow_tpu.dataloader.BatchIterator` backed by
    the C++ prefetching loader: a producer thread assembles (optionally
    shuffled) batches for all arrays into a ring of contiguous buffers.
    ``prefetch_depth`` is the ring size — ``FFModel.fit`` wires it from
    ``--prefetch-depth``, the same look-ahead the pure-Python fallback's
    producer thread and the device-placement stage use, so the 3-stage
    input pipeline has one depth knob end to end.

    Returned numpy arrays are **owned copies** of the ring slots.  They
    must not be views: the CPU backend zero-copy-aliases aligned host
    buffers in ``device_put``/``asarray``, and a consumer that defers
    synchronization (e.g. ``fit`` with no metrics) can have steps still
    queued when the producer recycles the slot — or when this iterator is
    garbage-collected and ``ffdl_destroy`` frees the ring (use-after-free,
    observed as NaN weights in the round-1 DP-consistency test).  The copy
    is a memcpy; the producer thread still overlaps gather/shuffle with
    the step loop, which is where the win is.

    ``fit`` builds this loader for shuffled epochs only.  An unshuffled
    batch is a contiguous slice of the caller's arrays, and ``fit`` feeds
    it as a numpy view (``dataloader.ViewIterator``) with no copy: a view
    holds a reference to the caller's array, which nothing recycles or
    frees while a placed batch still uses it, where a ring slot is
    rewritten ``prefetch_depth - 1`` batches later.
    """

    def __init__(self, arrays: Sequence[np.ndarray], batch_size: int,
                 shuffle: bool = False, seed: int = 0,
                 prefetch_depth: int = 3) -> None:
        lib = get_lib()
        assert lib is not None, "native loader unavailable"
        self._lib = lib
        # keep contiguous copies alive for the loader's lifetime
        self.arrays = [np.ascontiguousarray(a) for a in arrays]
        self.batch_size = batch_size
        self._h = lib.ffdl_create(batch_size, seed, int(shuffle), prefetch_depth)
        self._shapes = []
        self._dtypes = []
        for a in self.arrays:
            row_bytes = a.dtype.itemsize * int(np.prod(a.shape[1:], dtype=np.int64))
            rc = lib.ffdl_add_array(self._h, a.ctypes.data_as(ctypes.c_void_p),
                                    a.shape[0], row_bytes)
            assert rc >= 0, f"ffdl_add_array failed: {rc}"
            self._shapes.append((batch_size,) + a.shape[1:])
            self._dtypes.append(a.dtype)
        self.num_batches = int(lib.ffdl_num_batches(self._h))
        self._out = (ctypes.c_void_p * len(self.arrays))()

    def reset(self) -> None:
        self._lib.ffdl_reset(self._h)

    def __iter__(self):
        while True:
            idx = self._lib.ffdl_next(self._h, self._out)
            if idx < 0:
                return
            batch = []
            for i, (shape, dtype) in enumerate(zip(self._shapes, self._dtypes)):
                n = int(np.prod(shape, dtype=np.int64))
                buf = (ctypes.c_char * (n * dtype.itemsize)).from_address(self._out[i])
                # copy: see class docstring — views into ring slots are
                # unsafe under async dispatch + zero-copy device_put
                batch.append(np.frombuffer(buf, dtype=dtype).reshape(shape).copy())
            yield tuple(batch)

    def __del__(self):
        h = getattr(self, "_h", None)
        if h and getattr(self, "_lib", None) is not None:
            self._lib.ffdl_destroy(h)
            self._h = None
