"""bigdl_tpu.native — C++ host runtime (≙ the reference's native layer:
MKL threading / hadoop CRC32C / seq-file readers, rebuilt for the TPU host:
crc32c fast path + a prefetching mmap record pipeline).

The shared library builds on demand with `make` (g++); every entry point
has a pure-python fallback so the framework works without a toolchain.
"""
from __future__ import annotations

import ctypes
import os
import subprocess
import threading
from typing import List, Optional, Sequence

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))
# BIGDL_NATIVE_LIB points the loader at an alternative build of the
# runtime — the sanitizer-instrumented libraries (`make asan` /
# `make ubsan`) in CI's native-sanitizers job, or a locally-patched
# build.  When set, it is authoritative: no on-demand `make` of the
# stock library, so a sanitizer run can never silently test the
# uninstrumented build.
_LIB_ENV = "BIGDL_NATIVE_LIB"
_LIB_OVERRIDE = os.environ.get(_LIB_ENV) or None
_LIB_PATH = _LIB_OVERRIDE or os.path.join(_HERE, "libbigdl_tpu_rt.so")
_lib = None
_lib_lock = threading.Lock()


def build(force: bool = False) -> bool:
    """Compile the native library in place. Returns True on success.
    ``force`` rebuilds from ``src/*.cpp`` even when a library is there."""
    if _LIB_OVERRIDE is not None:
        return os.path.exists(_LIB_PATH)
    if os.path.exists(_LIB_PATH) and not force:
        return True
    try:
        subprocess.run(["make", "-C", _HERE] + (["-B"] if force else []),
                       check=True, capture_output=True, timeout=120)
        return os.path.exists(_LIB_PATH)
    except (subprocess.SubprocessError, FileNotFoundError):
        return False


def load() -> Optional[ctypes.CDLL]:
    """Load (building if needed) the native library; None if unavailable."""
    global _lib
    with _lib_lock:
        if _lib is not None:
            return _lib
        if not os.path.exists(_LIB_PATH) and not build():
            if _LIB_OVERRIDE is not None:
                raise FileNotFoundError(
                    f"{_LIB_ENV}={_LIB_PATH} does not exist — refusing "
                    "the silent fallback (a sanitizer run against the "
                    "wrong library proves nothing)")
            return None
        try:
            lib = ctypes.CDLL(_LIB_PATH)
        except OSError:
            if _LIB_OVERRIDE is not None:
                raise
            return None
        lib.bigdl_crc32c.restype = ctypes.c_uint32
        lib.bigdl_crc32c.argtypes = [ctypes.c_char_p, ctypes.c_size_t,
                                     ctypes.c_uint32]
        lib.bigdl_crc32c_masked.restype = ctypes.c_uint32
        lib.bigdl_crc32c_masked.argtypes = [ctypes.c_char_p, ctypes.c_size_t]
        lib.pf_create.restype = ctypes.c_void_p
        lib.pf_create.argtypes = [ctypes.POINTER(ctypes.c_char_p),
                                  ctypes.c_int, ctypes.c_uint64,
                                  ctypes.c_uint64, ctypes.c_uint64,
                                  ctypes.c_int, ctypes.c_int]
        lib.pf_next.restype = ctypes.c_uint64
        lib.pf_next.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
        lib.pf_buffered.restype = ctypes.c_uint64
        lib.pf_buffered.argtypes = [ctypes.c_void_p]
        lib.pf_destroy.argtypes = [ctypes.c_void_p]
        lib.ip_prepare_batch.restype = ctypes.c_int
        lib.ip_prepare_batch.argtypes = [
            ctypes.c_void_p, ctypes.c_int, ctypes.c_int, ctypes.c_int,
            ctypes.c_int, ctypes.c_void_p, ctypes.c_void_p,
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int, ctypes.c_int,
            ctypes.c_void_p, ctypes.c_int]
        _lib = lib
        return _lib


def available() -> bool:
    return load() is not None


def crc32c(data: bytes, crc: int = 0) -> int:
    """Native crc32c with python fallback."""
    lib = load()
    if lib is None:
        from ..utils.crc32c import crc32c as py_crc32c
        return py_crc32c(data, crc)
    return lib.bigdl_crc32c(data, len(data), crc)


def masked_crc32c(data: bytes) -> int:
    lib = load()
    if lib is None:
        from ..utils.crc32c import masked_crc32c as py_masked
        return py_masked(data)
    return lib.bigdl_crc32c_masked(data, len(data))


class NativePrefetcher:
    """Multi-threaded mmap record reader over shard files; records surface
    as numpy uint8 views.  Falls back to a python reader when the native
    library is unavailable."""

    def __init__(self, paths: Sequence[str], record_bytes: int,
                 header_bytes: int = 0, capacity: int = 64,
                 n_workers: int = 2, loop: bool = False):
        self.paths = [os.fspath(p) for p in paths]
        self.record_bytes = record_bytes
        self.header_bytes = header_bytes
        self.loop = loop
        self._lib = load()
        self._handle = None
        self._py_iter = None
        if self._lib is not None:
            arr = (ctypes.c_char_p * len(self.paths))(
                *[p.encode() for p in self.paths])
            self._handle = self._lib.pf_create(
                arr, len(self.paths), record_bytes, header_bytes,
                capacity, n_workers, int(loop))
            if not self._handle:
                raise RuntimeError("native prefetcher creation failed")
        else:
            self._py_iter = self._python_reader()
        self._buf = ctypes.create_string_buffer(record_bytes)

    def _python_reader(self):
        while True:
            for p in self.paths:
                size = os.path.getsize(p)
                with open(p, "rb") as f:
                    f.seek(self.header_bytes)
                    while f.tell() + self.record_bytes <= size:
                        yield f.read(self.record_bytes)
            if not self.loop:
                return

    def next(self) -> Optional[bytes]:
        """Next record or None at end-of-stream."""
        if self._handle is not None:
            n = self._lib.pf_next(self._handle, self._buf)
            if n == 0:
                return None
            return self._buf.raw[:n]
        try:
            return next(self._py_iter)
        except StopIteration:
            return None

    def buffered(self) -> int:
        if self._handle is not None:
            return self._lib.pf_buffered(self._handle)
        return 0

    def __iter__(self):
        while True:
            r = self.next()
            if r is None:
                return
            yield r

    def close(self):
        if self._handle is not None:
            self._lib.pf_destroy(self._handle)
            self._handle = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


def prepare_image_batch(images, crop_h, crop_w, offsets=None, flips=None,
                        mean=(0.0, 0.0, 0.0), std=(1.0, 1.0, 1.0),
                        n_threads=4):
    """One-pass batched crop + flip + normalize + HWC->CHW
    (≙ the chained BGRImgCropper/HFlip/BGRImgNormalizer/BGRImgToBatch hot
    loop, without the intermediate materializations).

    images: (N, H, W, C) uint8; offsets: (N, 2) int32 crop (y, x) or None
    (top-left); flips: (N,) bool/uint8 or None.  Returns
    (N, C, crop_h, crop_w) float32.  Falls back to numpy when the native
    library is unavailable — same numerics either way.
    """
    import numpy as np
    images = np.ascontiguousarray(images, np.uint8)
    n, in_h, in_w, c = images.shape
    mean_a = np.ascontiguousarray(mean, np.float32)
    std_a = np.ascontiguousarray(std, np.float32)
    if mean_a.size != c or std_a.size != c:
        raise ValueError(f"mean/std must have {c} entries")
    offs_a = None if offsets is None else \
        np.ascontiguousarray(offsets, np.int32)
    flips_a = None if flips is None else \
        np.ascontiguousarray(flips, np.uint8)
    lib = load()
    if lib is not None:
        out = np.empty((n, c, crop_h, crop_w), np.float32)
        rc = lib.ip_prepare_batch(
            images.ctypes.data, n, in_h, in_w, c,
            offs_a.ctypes.data if offs_a is not None else None,
            flips_a.ctypes.data if flips_a is not None else None,
            mean_a.ctypes.data, std_a.ctypes.data, crop_h, crop_w,
            out.ctypes.data, n_threads)
        if rc != 0:
            raise ValueError("ip_prepare_batch: bad arguments")
        return out
    # numpy fallback (same semantics)
    out = np.empty((n, c, crop_h, crop_w), np.float32)
    inv = np.where(std_a != 0, 1.0 / std_a, 1.0)
    for i in range(n):
        oy, ox = (offs_a[i] if offs_a is not None else (0, 0))
        patch = images[i, oy:oy + crop_h, ox:ox + crop_w].astype(np.float32)
        if flips_a is not None and flips_a[i]:
            patch = patch[:, ::-1]
        patch = (patch - mean_a) * inv
        out[i] = np.transpose(patch, (2, 0, 1))
    return out
