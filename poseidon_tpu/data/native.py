"""ctypes binding for the native data plane (native/poseidon_dataplane.cc).

Builds the shared library from source on first use, and again whenever the
source is newer (g++, no external deps), and exposes ``NativeLMDBBatcher``:
indexed batch assembly (LMDB read + Datum decode + crop/mirror/mean/scale)
running multithreaded in C++ with the GIL released — the reference's C++
data-layer role. Without a working compiler ``available()`` returns False,
the failure is logged once, and callers use the Python path.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading
import weakref
from typing import List, Optional, Tuple

import numpy as np

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
_SRC = os.path.join(_REPO_ROOT, "native", "poseidon_dataplane.cc")
_LIB = os.path.join(_REPO_ROOT, "native", "build",
                    "libposeidon_dataplane.so")

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_build_failed = False


class _TransformSpec(ctypes.Structure):
    _fields_ = [
        ("crop_size", ctypes.c_int32),
        ("mirror", ctypes.c_int32),
        ("train", ctypes.c_int32),
        ("scale", ctypes.c_float),
        ("mean_mode", ctypes.c_int32),
        ("mean", ctypes.POINTER(ctypes.c_float)),
    ]


def _stale() -> bool:
    """The library is built from what git tracks (the ``.cc``); the
    ``.so`` is a build output (``native/build/`` is git-ignored) and is
    rebuilt whenever the source is newer."""
    return (not os.path.exists(_LIB)
            or os.path.getmtime(_SRC) > os.path.getmtime(_LIB))


def _build() -> None:
    os.makedirs(os.path.dirname(_LIB), exist_ok=True)
    # tmp + rename: two processes of one job may both find the library
    # stale; neither may ever dlopen a half-written file
    tmp = f"{_LIB}.tmp.{os.getpid()}"
    try:
        subprocess.run(
            ["g++", "-O3", "-std=c++17", "-fPIC", "-pthread", "-Wall",
             "-shared", "-o", tmp, _SRC],
            check=True, capture_output=True, text=True)
        os.replace(tmp, _LIB)
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)


def _load() -> Optional[ctypes.CDLL]:
    global _lib, _build_failed
    with _lock:
        if _lib is not None or _build_failed:
            return _lib
        if _stale():
            try:
                _build()
            except (subprocess.CalledProcessError, FileNotFoundError) as e:
                _build_failed = True
                from ..runtime.metrics import log
                log(f"native data plane: build FAILED "
                    f"({getattr(e, 'stderr', None) or e}); DATA layers use "
                    f"the (much slower) Python reader")
                return None
        lib = ctypes.CDLL(_LIB)
        lib.pdp_open.restype = ctypes.c_void_p
        lib.pdp_open.argtypes = [ctypes.c_char_p]
        lib.pdp_error.restype = ctypes.c_char_p
        lib.pdp_error.argtypes = [ctypes.c_void_p]
        lib.pdp_count.restype = ctypes.c_int64
        lib.pdp_count.argtypes = [ctypes.c_void_p]
        lib.pdp_shape.argtypes = [ctypes.c_void_p] + \
            [ctypes.POINTER(ctypes.c_int32)] * 3
        lib.pdp_batch.restype = ctypes.c_int32
        lib.pdp_batch.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_int64), ctypes.c_int32,
            ctypes.POINTER(_TransformSpec), ctypes.c_uint64,
            ctypes.POINTER(ctypes.c_float), ctypes.POINTER(ctypes.c_int32),
            ctypes.c_int32,
        ]
        lib.pdp_close.argtypes = [ctypes.c_void_p]
        lib.pdp_batch_u8.restype = ctypes.c_int32
        lib.pdp_batch_u8.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_int64),
            ctypes.c_int32, ctypes.c_int32, ctypes.c_int32,
            ctypes.c_int32, ctypes.c_uint64,
            ctypes.POINTER(ctypes.c_uint8),
            ctypes.POINTER(ctypes.c_int32), ctypes.c_int32,
        ]
        lib.pdp_snappy_uncompress.restype = ctypes.c_int64
        lib.pdp_snappy_uncompress.argtypes = [
            ctypes.c_char_p, ctypes.c_int64,
            ctypes.POINTER(ctypes.c_uint8), ctypes.c_int64,
        ]
        _lib = lib
        return _lib


def available() -> bool:
    return _load() is not None


# A corrupt header must not force a huge zero-filled allocation before the
# body is ever validated; LevelDB blocks are ~4-64 KiB, so this is generous.
_SNAPPY_MAX_OUT = 256 << 20


def snappy_uncompress(buf: bytes) -> Optional[bytes]:
    """Native snappy decode; None when the library is unavailable, raises
    on malformed input (same contract as the pure-Python codec)."""
    lib = _load()
    if lib is None:
        return None
    need = lib.pdp_snappy_uncompress(buf, len(buf), None, 0)
    if need < 0:
        raise ValueError("native snappy: malformed header")
    if need > _SNAPPY_MAX_OUT:
        raise ValueError(
            f"native snappy: declared size {need} exceeds the "
            f"{_SNAPPY_MAX_OUT}-byte block cap (corrupt header?)")
    out = (ctypes.c_uint8 * need)()
    got = lib.pdp_snappy_uncompress(buf, len(buf), out, need)
    if got != need:
        raise ValueError(f"native snappy: malformed stream (rc={got})")
    return bytes(out)


class _BatchBuffers:
    """Recycled output memory for one batcher's batches.

    A batch of 512 AlexNet crops is 316 MB of float32. Taken fresh from
    the allocator every batch it arrives as untouched pages, and the
    kernel's page faults (and the unmap of the batch before) cost several
    times what decoding the records does, on whatever number of threads:
    on the v5e's host 170 ms a batch fresh against 15 ms into memory that
    has been written before (PR 24, PERF.md). So a buffer goes back on the
    free list when the LAST reference to the array handed out — or to any
    view of it — is dropped, and never earlier: a consumer that keeps a
    batch keeps its memory, and ``jax.device_put`` keeps its reference
    until the transfer has landed. The owner of the memory is a
    ``bytearray``, not an ndarray, so that numpy chains every view's
    ``base`` to the one array the finalizer watches."""

    KEEP = 8    # free buffers kept; beyond it memory returns to the allocator

    def __init__(self):
        self._free: List[bytearray] = []

    def _give_back(self, raw: bytearray) -> None:
        if len(self._free) < self.KEEP:
            self._free.append(raw)

    def take(self, shape: Tuple[int, ...], dtype) -> np.ndarray:
        nbytes = int(np.prod(shape)) * np.dtype(dtype).itemsize
        raw = None
        while self._free and raw is None:
            raw = self._free.pop()
            if len(raw) != nbytes:      # another batch size: let it go
                raw = None
        if raw is None:
            raw = bytearray(nbytes)
        flat = np.frombuffer(raw, dtype)
        weakref.finalize(flat, self._give_back, raw).atexit = False
        return flat.reshape(shape)


class NativeLMDBBatcher:
    def __init__(self, path: str, *, crop_size: int = 0, mirror: bool = False,
                 train: bool = True, scale: float = 1.0,
                 mean: Optional[np.ndarray] = None,
                 mean_values: Optional[np.ndarray] = None,
                 n_threads: int = 0):
        lib = _load()
        if lib is None:
            raise RuntimeError("native data plane unavailable (no compiler?)")
        self._lib = lib
        self._h = lib.pdp_open(path.encode())
        err = lib.pdp_error(self._h)
        if err:
            msg = err.decode()
            lib.pdp_close(self._h)
            self._h = None
            raise IOError(f"{path}: {msg}")
        c = ctypes.c_int32()
        h = ctypes.c_int32()
        w = ctypes.c_int32()
        lib.pdp_shape(self._h, ctypes.byref(c), ctypes.byref(h),
                      ctypes.byref(w))
        self.record_shape = (c.value, h.value, w.value)
        self.n = int(lib.pdp_count(self._h))
        self.n_threads = n_threads or min(8, os.cpu_count() or 1)
        self._buffers = _BatchBuffers()

        if crop_size and (crop_size > self.record_shape[1]
                          or crop_size > self.record_shape[2]):
            self._lib.pdp_close(self._h)
            self._h = None
            raise ValueError(
                f"crop_size {crop_size} exceeds record "
                f"{self.record_shape[1]}x{self.record_shape[2]}")
        mean_mode = 0
        self._mean_buf = None
        if mean is not None:
            m = np.ascontiguousarray(np.asarray(mean, np.float32).reshape(-1))
            if m.size != int(np.prod(self.record_shape)):
                raise ValueError("mean array size mismatch")
            self._mean_buf = m
            mean_mode = 2
        elif mean_values is not None and len(mean_values):
            m = np.asarray(mean_values, np.float32)
            if m.size == 1:
                m = np.repeat(m, self.record_shape[0])
            if m.size != self.record_shape[0]:
                raise ValueError("mean_values arity mismatch")
            self._mean_buf = np.ascontiguousarray(m)
            mean_mode = 1
        mean_ptr = self._mean_buf.ctypes.data_as(
            ctypes.POINTER(ctypes.c_float)) if self._mean_buf is not None \
            else ctypes.POINTER(ctypes.c_float)()
        self._spec = _TransformSpec(
            crop_size=crop_size, mirror=int(mirror), train=int(train),
            scale=scale, mean_mode=mean_mode, mean=mean_ptr)
        ch, hh, ww = self.record_shape
        self.out_shape = (ch, crop_size or hh, crop_size or ww)

    def __len__(self) -> int:
        return self.n

    def batch_u8(self, indices: np.ndarray,
                 seed: int = 0) -> Tuple[np.ndarray, np.ndarray]:
        """Decode + crop + mirror to uint8 — mean/scale happen on device
        (see pipeline.device_transform). Same crop/mirror RNG stream as
        ``batch``, so the two paths see identical pixels. Raises IOError
        on float_data-backed records (rc=-4): callers fall back to f32."""
        idx = np.ascontiguousarray(indices, np.int64)
        n = len(idx)
        data = self._buffers.take((n,) + self.out_shape, np.uint8)
        labels = np.empty((n,), np.int32)
        rc = self._lib.pdp_batch_u8(
            self._h, idx.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)), n,
            self._spec.crop_size, self._spec.mirror, self._spec.train,
            ctypes.c_uint64(seed),
            data.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
            labels.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            self.n_threads)
        if rc == -2:
            raise IndexError("batch index out of range")
        if rc == -3:
            raise ValueError("crop_size exceeds record dimensions")
        if rc == -4:
            raise IOError("float_data records cannot ship as uint8")
        if rc != 0:
            raise IOError(f"native batch failed: bad record (rc={rc})")
        return data, labels

    def batch(self, indices: np.ndarray,
              seed: int = 0) -> Tuple[np.ndarray, np.ndarray]:
        idx = np.ascontiguousarray(indices, np.int64)
        n = len(idx)
        data = self._buffers.take((n,) + self.out_shape, np.float32)
        labels = np.empty((n,), np.int32)
        rc = self._lib.pdp_batch(
            self._h, idx.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)), n,
            ctypes.byref(self._spec), ctypes.c_uint64(seed),
            data.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
            labels.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            self.n_threads)
        if rc == -2:
            raise IndexError("batch index out of range")
        if rc == -3:
            raise ValueError("crop_size exceeds record dimensions")
        if rc != 0:
            raise IOError(f"native batch failed: bad record (rc={rc})")
        return data, labels

    def close(self):
        if self._h is not None:
            self._lib.pdp_close(self._h)
            self._h = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass
