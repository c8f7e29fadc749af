"""ctypes bindings for the native host data-path library.

Builds the library from the in-tree C++ sources on first import (g++ is
part of the toolchain; no pybind11 in this image, so the binding is a plain
C ABI + ctypes) into ``_build/`` beside them — a fixed, git-ignored
directory in the checkout — under a name keyed by a hash of the sources.
Only a library built from exactly the tracked sources is ever loaded: no
prebuilt ``.so`` beside them, and no file times as evidence of freshness
(a copied tree keeps neither). Every entry point has a numpy fallback —
importing this package NEVER fails because of a missing/broken toolchain;
check ``AVAILABLE`` to know which path is live.
"""

from __future__ import annotations

import ctypes
import hashlib
import logging
import os
import subprocess

import numpy as np

log = logging.getLogger(__name__)

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRCS = [
    os.path.join(_DIR, "cifar_codec.cpp"),
    os.path.join(_DIR, "prefetcher.cpp"),
]
# headers count toward the build key, not toward the compile line
_HDRS = [os.path.join(_DIR, "parallel_for.h")]
_BUILD_DIR = os.path.join(_DIR, "_build")

AVAILABLE = False
_lib = None


def _library_path() -> str:
    """``_build/libcifar_codec-<hash of the sources>.so``."""
    digest = hashlib.sha256()
    for path in _SRCS + _HDRS:
        digest.update(os.path.basename(path).encode())
        with open(path, "rb") as f:
            digest.update(f.read())
    return os.path.join(
        _BUILD_DIR, f"libcifar_codec-{digest.hexdigest()[:16]}.so")


def _build_and_load():
    global AVAILABLE, _lib
    out = _library_path()
    # Build to a process-unique temp name, then rename atomically so a
    # concurrent importer never dlopens a half-written file.
    tmp_out = f"{out}.{os.getpid()}.tmp"
    try:
        if not os.path.exists(out):
            os.makedirs(_BUILD_DIR, exist_ok=True)
            subprocess.run(
                ["g++", "-O3", "-shared", "-fPIC", "-std=c++17",
                 "-o", tmp_out, *_SRCS, "-lpthread"],
                check=True, capture_output=True, timeout=120,
            )
            os.replace(tmp_out, out)
        _lib = ctypes.CDLL(out)
    except (OSError, subprocess.SubprocessError) as e:
        # toolchain missing/failed, or an unwritable checkout
        log.warning("native cifar_codec build failed (%s); numpy fallback", e)
        if os.path.exists(tmp_out):
            os.unlink(tmp_out)
        return
    try:  # an unusable library must degrade to numpy, not raise
        _lib.cifar_decode_normalize.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64,
            ctypes.c_void_p, ctypes.c_void_p,
        ]
        _lib.gather_rows_f32.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
            ctypes.c_int64, ctypes.c_int64,
        ]
        _lib.gather_rows_i32.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
            ctypes.c_int64, ctypes.c_int64,
        ]
        _lib.bp_create.restype = ctypes.c_void_p
        _lib.bp_create.argtypes = [
            ctypes.c_int, ctypes.c_int64, ctypes.c_int64,
        ]
        _lib.bp_submit.restype = ctypes.c_int
        _lib.bp_submit.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
            ctypes.c_void_p, ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
        ]
        _lib.bp_acquire.restype = ctypes.c_int
        _lib.bp_acquire.argtypes = [
            ctypes.c_void_p,
            ctypes.POINTER(ctypes.c_void_p),
            ctypes.POINTER(ctypes.c_void_p),
        ]
        _lib.bp_release.argtypes = [ctypes.c_void_p, ctypes.c_int]
        _lib.bp_destroy.argtypes = [ctypes.c_void_p]
        _lib.cifar_codec_abi_version.restype = ctypes.c_int
        if _lib.cifar_codec_abi_version() != 2:
            raise RuntimeError("cifar_codec ABI version mismatch")
    except Exception as e:
        log.warning("native cifar_codec unusable (%s); numpy fallback", e)
        _lib = None
        return
    AVAILABLE = True


_build_and_load()


def decode_normalize(raw: np.ndarray, mean: np.ndarray, std: np.ndarray) -> np.ndarray:
    """(N, 3072) uint8 planar-RGB -> (N, 32, 32, 3) float32 normalized."""
    raw = np.ascontiguousarray(raw, np.uint8)
    n = raw.shape[0]
    assert raw.shape[1] == 3072
    mean32 = np.ascontiguousarray(mean, np.float32)
    std32 = np.ascontiguousarray(std, np.float32)
    if AVAILABLE:
        out = np.empty((n, 32, 32, 3), np.float32)
        _lib.cifar_decode_normalize(
            raw.ctypes.data, out.ctypes.data, n, mean32.ctypes.data,
            std32.ctypes.data,
        )
        return out
    # numpy fallback: identical transform (/255 "ToTensor" then per-channel
    # stats), honoring the SAME mean/std arguments as the native path
    x = raw.reshape(n, 3, 32, 32).transpose(0, 2, 3, 1).astype(np.float32) / 255.0
    return (x - mean32) / std32


# Below this, the per-call std::thread fan-out costs more than the copy.
_NATIVE_GATHER_MIN_BYTES = 1 << 20


def gather_rows(src: np.ndarray, idx: np.ndarray) -> np.ndarray:
    """dst[j] = src[idx[j]] along axis 0, multithreaded for large f32/i32
    gathers; numpy otherwise (small copies, other dtypes, negative/OOB
    indices — numpy raises/wraps exactly as fancy indexing always did)."""
    idx64 = np.ascontiguousarray(idx, np.int64)
    if (
        AVAILABLE
        and src.dtype in (np.float32, np.int32)
        and src.flags.c_contiguous
        and idx64.size > 0
        # native path has no bounds/sign handling: numpy covers those
        and int(idx64.min()) >= 0
        and int(idx64.max()) < len(src)
    ):
        row_elems = int(np.prod(src.shape[1:], dtype=np.int64)) if src.ndim > 1 else 1
        if idx64.size * row_elems * src.itemsize >= _NATIVE_GATHER_MIN_BYTES:
            out = np.empty((len(idx64),) + src.shape[1:], src.dtype)
            fn = (
                _lib.gather_rows_f32
                if src.dtype == np.float32
                else _lib.gather_rows_i32
            )
            fn(src.ctypes.data, idx64.ctypes.data, out.ctypes.data,
               len(idx64), row_elems)
            return out
    return src[idx64]
