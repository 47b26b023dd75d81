"""ctypes bindings for the native (C++) batch assembler in native/dataloader.cc.

The library is built on demand with g++ (no pybind11 in this image — C ABI via
ctypes per the environment constraints) and cached next to the source. All
callers must tolerate `load_native() is None` and fall back to the numpy path:
the native loader is a throughput optimization, not a correctness dependency.
"""

from __future__ import annotations

import ctypes
import logging
import os
import threading
from typing import Iterator, Mapping, Optional

import numpy as np

from distributed_vgg_f_tpu import telemetry

log = logging.getLogger(__name__)

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_build_failed = False

_I32P = ctypes.POINTER(ctypes.c_int32)
_F32P = ctypes.POINTER(ctypes.c_float)

#: Must match dvgg_abi_version() in native/dataloader.cc — single source
#: for the load gate and the ABI contract checker (tools/abi_check.py).
DATA_ABI_VERSION = 1


def load_native() -> Optional[ctypes.CDLL]:
    """Load (building if needed) the native library; None if unavailable.
    Build/cache mechanics are shared with the jpeg loader — see
    data/native_build.py (pid-temp compile + atomic rename + mtime check)."""
    global _lib, _build_failed
    with _lock:
        if _lib is not None:
            return _lib
        if _build_failed:
            return None
        from distributed_vgg_f_tpu.data.native_build import build_native_lib
        so_path = build_native_lib("dataloader.cc", "libdvgg_data.so")
        if so_path is None:
            _build_failed = True
            return None
        try:
            lib = ctypes.CDLL(so_path)
            # Exhaustive argtypes/restype on EVERY export (r15): ctypes'
            # silent defaults (int restype, unchecked arity) are the exact
            # corruption vector the ABI checker exists to close — it
            # cross-checks these against the C signatures.
            lib.dvgg_loader_create.restype = ctypes.c_void_p
            lib.dvgg_loader_create.argtypes = [
                ctypes.c_void_p, _I32P, ctypes.c_int64,
                ctypes.c_int, ctypes.c_int, ctypes.c_int, ctypes.c_int,
                ctypes.c_int, ctypes.c_int, ctypes.c_uint64,
                _F32P, _F32P, ctypes.c_int,
            ]
            lib.dvgg_loader_next.restype = None
            lib.dvgg_loader_next.argtypes = [ctypes.c_void_p, _F32P, _I32P]
            lib.dvgg_loader_destroy.restype = None
            lib.dvgg_loader_destroy.argtypes = [ctypes.c_void_p]
            lib.dvgg_abi_version.restype = ctypes.c_int
            lib.dvgg_abi_version.argtypes = []
            if lib.dvgg_abi_version() != DATA_ABI_VERSION:
                raise OSError("ABI version mismatch")
        except (OSError, AttributeError) as e:
            log.warning("native dataloader load failed: %s", e)
            _build_failed = True
            return None
        _lib = lib
        return _lib


class NativeBatchIterator:
    """Iterator over augmented, normalized float32 batches produced by the
    native double-buffered assembler. Holds references to the source arrays
    (the C++ side does not copy them)."""

    def __init__(self, images: np.ndarray, labels: np.ndarray, batch_size: int,
                 *, train: bool, seed: int, mean, std, pad: int = 4,
                 num_threads: Optional[int] = None):
        lib = load_native()
        if lib is None:
            raise RuntimeError("native dataloader unavailable")
        assert images.dtype == np.uint8 and images.ndim == 4
        self._lib = lib
        # keep alive: the native loader reads these buffers directly
        self._images = np.ascontiguousarray(images)
        self._labels = np.ascontiguousarray(labels.astype(np.int32))
        n, h, w, c = self._images.shape
        self.batch_size = batch_size
        self._shape = (batch_size, h, w, c)
        mean3 = (ctypes.c_float * 3)(*[float(m) for m in mean][:3])
        std3 = (ctypes.c_float * 3)(*[float(s) for s in std][:3])
        if num_threads is None:
            num_threads = min(4, os.cpu_count() or 1)
        self._handle = lib.dvgg_loader_create(
            self._images.ctypes.data_as(ctypes.c_void_p),
            self._labels.ctypes.data_as(_I32P),
            n, h, w, c, batch_size, pad if train else 0, int(train),
            seed, mean3, std3, num_threads)
        if not self._handle:
            raise RuntimeError("dvgg_loader_create failed")
        self._buf_ring: list = []
        self._buf_i = 0

    @property
    def reuses_output_buffers(self) -> bool:
        """Same ownership contract as the jpeg loader (data/native_jpeg.py):
        True once the output-array ring is armed — device prefetch refuses
        such iterators (data/prefetch.py)."""
        return bool(self._buf_ring)

    def enable_output_buffer_reuse(self, depth: int = 3) -> None:
        """Recycle `depth` preallocated output arrays instead of allocating
        a multi-MB batch array per `next()` — batches are then only valid
        until `depth` further calls. Bench-only (synchronous consumers)."""
        if depth < 2:
            raise ValueError(f"ring depth must be >= 2, got {depth}")
        self._buf_ring = [(np.empty(self._shape, np.float32),
                           np.empty((self.batch_size,), np.int32))
                          for _ in range(depth)]
        self._buf_i = 0

    def __iter__(self) -> Iterator[Mapping[str, np.ndarray]]:
        return self

    def __next__(self) -> Mapping[str, np.ndarray]:
        if not self._handle:
            raise RuntimeError("NativeBatchIterator used after close()")
        if self._buf_ring:
            images, labels = self._buf_ring[self._buf_i % len(self._buf_ring)]
            self._buf_i += 1
        else:
            # fresh arrays per call: the C side memcpys out of its staging
            # buffer, so these are immediately safe to hand to the caller —
            # one copy total
            images = np.empty(self._shape, np.float32)
            labels = np.empty((self.batch_size,), np.int32)
        # per-BATCH, not per-image: the time blocked on the native
        # double-buffer is the loader's contribution to an infeed stall
        with telemetry.span("native_loader_next", "infeed_source"):
            self._lib.dvgg_loader_next(
                self._handle,
                images.ctypes.data_as(_F32P),
                labels.ctypes.data_as(_I32P))
        telemetry.inc("native_loader/batches")
        return {"image": images, "label": labels}

    def close(self) -> None:
        handle, self._handle = self._handle, None
        if handle:
            self._lib.dvgg_loader_destroy(handle)

    def __del__(self):  # pragma: no cover - GC timing
        try:
            self.close()
        except Exception:
            pass
