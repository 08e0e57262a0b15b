"""ctypes loader for the native host image preprocessor.

`defer_tpu/native/imageproc.cpp` fuses bilinear resize + center crop +
per-channel affine into one multithreaded C++ pass (the native
data-loader component; the reference leans on PIL/numpy on the driver,
reference src/test.py:13-16). `imagenet_preprocess` in
defer_tpu/runtime/data.py uses it transparently for uint8 input and
falls back to the numpy path when the native build is unavailable —
both produce the same values (tested to ~1e-3 absolute).
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading

import numpy as np

from defer_tpu.utils.logging import get_logger

log = get_logger(__name__)

_NATIVE_DIR = os.path.join(os.path.dirname(__file__), os.pardir, "native")
_SRC = os.path.abspath(os.path.join(_NATIVE_DIR, "imageproc.cpp"))
_SO = os.path.abspath(os.path.join(_NATIVE_DIR, "libdeferimage.so"))

_lock = threading.Lock()
_lib: ctypes.CDLL | None = None
_lib_tried = False

# mode -> (scale, per-OUTPUT-channel offsets, swap_rb)
_MODES: dict[str, tuple[float, tuple[float, float, float], int]] = {
    "scale": (1.0 / 127.5, (-1.0, -1.0, -1.0), 0),
    "unit": (1.0 / 255.0, (0.0, 0.0, 0.0), 0),
    "caffe": (1.0, (-103.939, -116.779, -123.68), 1),
}


def _build() -> bool:
    cmd = [
        "g++", "-O3", "-shared", "-fPIC", _SRC, "-o", _SO, "-pthread",
    ]
    try:
        proc = subprocess.run(cmd, capture_output=True, text=True, timeout=120)
    except (OSError, subprocess.TimeoutExpired) as e:
        log.warning("native imageproc build failed to run: %s", e)
        return False
    if proc.returncode != 0:
        log.warning("native imageproc build failed:\n%s", proc.stderr[-2000:])
        return False
    return True


def _load() -> ctypes.CDLL | None:
    global _lib, _lib_tried
    with _lock:
        if _lib is not None or _lib_tried:
            return _lib
        _lib_tried = True
        stale = not os.path.exists(_SO) or (
            os.path.exists(_SRC)
            and os.path.getmtime(_SRC) > os.path.getmtime(_SO)
        )
        if stale and not _build():
            return None  # no compiler: the numpy path serves
        try:
            lib = ctypes.CDLL(_SO)
        except OSError as e:
            log.warning("native imageproc load failed: %s", e)
            return None
        lib.defer_preprocess.restype = ctypes.c_int
        lib.defer_preprocess.argtypes = [
            ctypes.c_void_p,  # src
            ctypes.c_int64,  # n
            ctypes.c_int64,  # h
            ctypes.c_int64,  # w
            ctypes.c_int64,  # c
            ctypes.c_int64,  # size
            ctypes.POINTER(ctypes.c_float),  # scale
            ctypes.POINTER(ctypes.c_float),  # offset
            ctypes.c_int,  # swap_rb
            ctypes.c_int,  # out_bf16
            ctypes.c_int64,  # num_threads
            ctypes.c_void_p,  # dst
        ]
        _lib = lib
        return _lib


def native_available() -> bool:
    return _load() is not None


def _is_bf16(dtype) -> bool:
    try:
        import ml_dtypes

        return np.dtype(dtype) == np.dtype(ml_dtypes.bfloat16)
    except ImportError:  # pragma: no cover
        return False


def native_preprocess(
    images: np.ndarray,
    *,
    size: int,
    mode: str,
    out_dtype=None,
    num_threads: int | None = None,
) -> np.ndarray | None:
    """Fused resize+crop+affine via the C++ library.

    Returns None when the native path cannot handle the request (no
    library, non-uint8 input, unknown mode, unsupported out_dtype) —
    the caller falls back to numpy.
    """
    if mode not in _MODES:
        return None
    x = np.asarray(images)
    if x.ndim == 3:
        x = x[None]
    if x.ndim != 4 or x.dtype != np.uint8 or x.shape[-1] != 3:
        return None
    out_dtype = np.float32 if out_dtype is None else out_dtype
    bf16 = _is_bf16(out_dtype)
    if not bf16 and np.dtype(out_dtype) != np.dtype(np.float32):
        return None
    lib = _load()
    if lib is None:
        return None

    x = np.ascontiguousarray(x)
    n, h, w, c = x.shape
    scale_v, offsets, swap = _MODES[mode]
    scale_arr = (ctypes.c_float * c)(*([scale_v] * c))
    offset_arr = (ctypes.c_float * c)(*offsets)
    out = np.empty((n, size, size, c), dtype=out_dtype)
    if num_threads is None:
        num_threads = max(1, (os.cpu_count() or 2) // 2)
    rc = lib.defer_preprocess(
        x.ctypes.data_as(ctypes.c_void_p),
        n,
        h,
        w,
        c,
        size,
        scale_arr,
        offset_arr,
        swap,
        1 if bf16 else 0,
        num_threads,
        out.ctypes.data_as(ctypes.c_void_p),
    )
    if rc != 0:
        log.warning("native preprocess returned rc=%d; falling back", rc)
        return None
    return out
