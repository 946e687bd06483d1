"""ctypes bindings for the native host codec (``native/codec.cpp``).

The library is built on first use from the TRACKED source, under a file
name that carries a hash of that source and the compiler flags
(``native/libfedtpu_native-<hash>.so``, git-ignored): a binary left in the
tree by another checkout, another flag set or another machine's
``-march=native`` build is simply never opened. The flags are portable
(no ``-march=native``), so a built tree can be copied between machines.

Every entry point has a numpy fallback, taken — with one WARNING — only
when the build fails (no compiler): :func:`codec_name` says which of the
two ran. The native path makes the DCN-edge sparsification O(n)
single-pass instead of numpy-temporary-heavy.

No pybind11 in this environment, hence plain-C ABI + ctypes.
"""

from __future__ import annotations

import ctypes
import hashlib
import logging
import os
import subprocess
from typing import Optional, Tuple

import numpy as np

log = logging.getLogger("fedtpu.native")

_NATIVE_DIR = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "native")
_SOURCE = os.path.join(_NATIVE_DIR, "codec.cpp")
_CXX = ("g++", "-O3", "-std=c++17", "-fPIC", "-shared")

_lib: Optional[ctypes.CDLL] = None
_load_attempted = False

_f32p = ctypes.POINTER(ctypes.c_float)
_i32p = ctypes.POINTER(ctypes.c_int32)
_i8p = ctypes.POINTER(ctypes.c_int8)


def _bind(lib: ctypes.CDLL) -> ctypes.CDLL:
    lib.fedtpu_kth_magnitude.restype = ctypes.c_float
    lib.fedtpu_kth_magnitude.argtypes = [_f32p, ctypes.c_int64, ctypes.c_int64]
    lib.fedtpu_pack_sparse.restype = ctypes.c_int64
    lib.fedtpu_pack_sparse.argtypes = [
        _f32p, ctypes.c_int64, ctypes.c_float, _i32p, _f32p, ctypes.c_int64,
    ]
    lib.fedtpu_unpack_sparse.restype = None
    lib.fedtpu_unpack_sparse.argtypes = [_i32p, _f32p, ctypes.c_int64, _f32p]
    lib.fedtpu_quant_int8.restype = None
    lib.fedtpu_quant_int8.argtypes = [_f32p, ctypes.c_int64, ctypes.c_float, _i8p]
    lib.fedtpu_dequant_int8.restype = None
    lib.fedtpu_dequant_int8.argtypes = [_i8p, ctypes.c_int64, ctypes.c_float, _f32p]
    lib.fedtpu_pack_sparse_with_residual.restype = ctypes.c_int64
    lib.fedtpu_pack_sparse_with_residual.argtypes = [
        _f32p, ctypes.c_int64, ctypes.c_float, _i32p, _f32p, ctypes.c_int64, _f32p,
    ]
    return lib


def _lib_path() -> str:
    digest = hashlib.sha256()
    with open(_SOURCE, "rb") as f:
        digest.update(f.read())
    digest.update(" ".join(_CXX).encode())
    return os.path.join(
        _NATIVE_DIR, f"libfedtpu_native-{digest.hexdigest()[:16]}.so"
    )


def _build(path: str) -> None:
    """Compile ``codec.cpp`` to ``path``. Written under a per-process temp
    name and renamed, so processes racing on first use (four clients
    starting together) each see either no library or a whole one."""
    tmp = f"{path}.{os.getpid()}.tmp"
    try:
        subprocess.run(
            [*_CXX, "-o", tmp, _SOURCE],
            check=True, capture_output=True, text=True, timeout=120,
        )
        os.replace(tmp, path)
    finally:
        if os.path.exists(tmp):
            os.remove(tmp)


def load() -> Optional[ctypes.CDLL]:
    """The native library for THIS source tree (built now if its
    hash-named file is absent), or None after a failed build."""
    global _lib, _load_attempted
    if _lib is None and not _load_attempted:
        _load_attempted = True
        path = _lib_path()
        try:
            if not os.path.exists(path):
                _build(path)
            _lib = _bind(ctypes.CDLL(path))
        except (OSError, subprocess.SubprocessError) as e:
            detail = getattr(e, "stderr", None) or e
            log.warning(
                "native codec unavailable, host codec is numpy: %s", detail
            )
    return _lib


def available() -> bool:
    return load() is not None


def codec_name() -> str:
    """Which host codec this process runs: ``native:<library file>`` or
    ``numpy``."""
    return f"native:{os.path.basename(_lib_path())}" if available() else "numpy"


def _as_f32(x: np.ndarray) -> np.ndarray:
    return np.ascontiguousarray(x, np.float32)


# ------------------------------------------------------------------ kernels
def kth_magnitude(x: np.ndarray, k: int) -> float:
    """k-th largest |x| (k>=1) — the top-k keep threshold."""
    x = _as_f32(x).ravel()
    if x.size == 0:
        return 0.0
    k = min(max(int(k), 1), x.size)
    lib = load()
    if lib is not None:
        return float(
            lib.fedtpu_kth_magnitude(x.ctypes.data_as(_f32p), x.size, k)
        )
    return float(np.partition(np.abs(x), x.size - k)[x.size - k])


def pack_sparse(x: np.ndarray, thresh: float) -> Tuple[np.ndarray, np.ndarray]:
    """(idx int32, vals f32) of entries with |x| >= thresh."""
    x = _as_f32(x).ravel()
    lib = load()
    if lib is not None:
        idx = np.empty(x.size, np.int32)
        vals = np.empty(x.size, np.float32)
        m = lib.fedtpu_pack_sparse(
            x.ctypes.data_as(_f32p), x.size, ctypes.c_float(thresh),
            idx.ctypes.data_as(_i32p), vals.ctypes.data_as(_f32p), x.size,
        )
        return idx[:m].copy(), vals[:m].copy()
    keep = np.abs(x) >= thresh
    return np.flatnonzero(keep).astype(np.int32), x[keep]


def unpack_sparse(idx: np.ndarray, vals: np.ndarray, n: int) -> np.ndarray:
    out = np.zeros(n, np.float32)
    idx = np.ascontiguousarray(idx, np.int32)
    vals = _as_f32(vals)
    lib = load()
    if lib is not None:
        lib.fedtpu_unpack_sparse(
            idx.ctypes.data_as(_i32p), vals.ctypes.data_as(_f32p),
            idx.size, out.ctypes.data_as(_f32p),
        )
        return out
    out[idx] = vals
    return out


def pack_sparse_with_residual(
    x: np.ndarray, thresh: float
) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """(idx, vals, residual): kept entries + the dropped mass (error
    feedback), one fused pass natively."""
    x = _as_f32(x).ravel()
    lib = load()
    if lib is not None:
        idx = np.empty(x.size, np.int32)
        vals = np.empty(x.size, np.float32)
        residual = np.empty(x.size, np.float32)
        m = lib.fedtpu_pack_sparse_with_residual(
            x.ctypes.data_as(_f32p), x.size, ctypes.c_float(thresh),
            idx.ctypes.data_as(_i32p), vals.ctypes.data_as(_f32p), x.size,
            residual.ctypes.data_as(_f32p),
        )
        return idx[:m].copy(), vals[:m].copy(), residual
    keep = np.abs(x) >= thresh
    residual = np.where(keep, 0.0, x).astype(np.float32)
    return np.flatnonzero(keep).astype(np.int32), x[keep], residual


def quant_int8(x: np.ndarray) -> Tuple[np.ndarray, float]:
    """(codes int8, scale). scale = max|x| / 127."""
    x = _as_f32(x).ravel()
    scale = float(np.abs(x).max() / 127.0) if x.size else 0.0
    lib = load()
    if lib is not None:
        out = np.empty(x.size, np.int8)
        lib.fedtpu_quant_int8(
            x.ctypes.data_as(_f32p), x.size, ctypes.c_float(scale),
            out.ctypes.data_as(_i8p),
        )
        return out, scale
    if scale <= 0:
        return np.zeros(x.size, np.int8), 0.0
    return np.clip(np.rint(x / scale), -127, 127).astype(np.int8), scale


def dequant_int8(codes: np.ndarray, scale: float, n: int) -> np.ndarray:
    codes = np.ascontiguousarray(codes, np.int8)
    lib = load()
    if lib is not None:
        out = np.empty(n, np.float32)
        lib.fedtpu_dequant_int8(
            codes.ctypes.data_as(_i8p), n, ctypes.c_float(scale),
            out.ctypes.data_as(_f32p),
        )
        return out
    return scale * codes.astype(np.float32)
