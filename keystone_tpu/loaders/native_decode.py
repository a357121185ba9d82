"""ctypes binding for the native C++ JPEG decoder (native/ingest.cpp).

The shared library is built lazily with the system toolchain on first use
(g++ + libjpeg, both baked into the image) under a name derived from its
source (``utils.platform.build_native_library``).
ctypes releases the GIL for the duration of each decode call, so the
thread-pool loader in image_loaders.py parallelizes across host cores with
no Python image library on the hot path.  ``KEYSTONE_NATIVE_DECODE=0``
disables the native path; anything unbuildable or undecodable falls back
to PIL transparently.
"""

from __future__ import annotations

import ctypes
import logging
import os
import threading

import numpy as np

from ..utils.platform import build_native_library

_logger = logging.getLogger(__name__)

_SRC = os.path.join(
    os.path.dirname(__file__), "..", "native", "ingest.cpp"
)

_lock = threading.Lock()
_lib: ctypes.CDLL | None = None
_tried = False


def _load() -> ctypes.CDLL | None:
    """Build (first use only) + dlopen the native decoder.

    Call this (via :func:`available`) BEFORE entering a decode hot path:
    the one-time g++ build runs under the module lock, so a lazy first call
    from inside a thread-pool loader would stall every worker behind it.
    The loaders do so (image_loaders._iter_tar_images); fallback to PIL is
    logged once so a silent slow path is attributable."""
    global _lib, _tried
    with _lock:
        if _tried:
            return _lib
        _tried = True
        if os.environ.get("KEYSTONE_NATIVE_DECODE", "").strip() == "0":
            return None
        path = build_native_library(_SRC, "kstingest", link=("-ljpeg",))
        if path is None:
            _logger.warning(
                "native JPEG decoder build failed; falling back to PIL"
            )
            return None
        try:
            lib = ctypes.CDLL(path)
        except OSError:
            _logger.warning(
                "native JPEG decoder unavailable; falling back to PIL"
            )
            return None
        lib.kst_decode_jpeg.argtypes = [
            ctypes.c_char_p,
            ctypes.c_long,
            ctypes.POINTER(ctypes.POINTER(ctypes.c_float)),
            ctypes.POINTER(ctypes.c_int),
            ctypes.POINTER(ctypes.c_int),
        ]
        lib.kst_decode_jpeg.restype = ctypes.c_int
        lib.kst_free.argtypes = [ctypes.POINTER(ctypes.c_float)]
        lib.kst_free.restype = None
        _lib = lib
        return _lib


def available() -> bool:
    return _load() is not None


def reset() -> None:
    """Forget the cached build/load outcome (under the module lock) so the
    next decode re-evaluates the ``KEYSTONE_NATIVE_DECODE`` gate and the
    library state.  Public hook for benchmarks/tests that toggle the env
    var to compare native-vs-PIL paths — poking ``_tried``/``_lib``
    directly would race any live decode thread."""
    global _lib, _tried
    with _lock:
        _tried = False
        _lib = None


def decode_jpeg_native(data: bytes) -> np.ndarray | None:
    """JPEG bytes -> f32[H, W, 3] BGR in [0, 255], or None when the stream
    is corrupt, rejected (<36 px), or the native library is unavailable.
    Matches image_loaders.decode_image semantics bit-for-... well, within
    libjpeg-version IDCT differences of PIL (see tests)."""
    lib = _load()
    if lib is None:
        return None
    out = ctypes.POINTER(ctypes.c_float)()
    h = ctypes.c_int()
    w = ctypes.c_int()
    rc = lib.kst_decode_jpeg(data, len(data), ctypes.byref(out), ctypes.byref(h), ctypes.byref(w))
    if rc != 0:
        return None
    try:
        arr = np.ctypeslib.as_array(out, shape=(h.value, w.value, 3)).copy()
    finally:
        lib.kst_free(out)
    return arr
