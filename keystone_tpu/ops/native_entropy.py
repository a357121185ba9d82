"""ctypes binding for the native entropy-decode hot loop (native/entropy.cpp).

The device-resident decode path keeps header parsing, Huffman LUT
compilation, and restart-segment splitting in Python
(ops/jpeg_device.entropy_decode) and hands ONLY the O(compressed-bytes)
symbol loop to this library — the same split libjpeg draws between its
marker reader and ``decode_mcu``.  The shared library is built lazily with
the system g++ on first use (no libjpeg or any other dependency) under a
name derived from its source (``utils.platform.build_native_library``,
the contract loaders/native_decode.py shares): a transient build failure
retries with backoff, a real one degrades to the pure-Python loop counted
``native_entropy_unavailable`` and logged once per process — the stream
stays bit-equal either way, because both loops implement the identical
algorithm (tier-1 asserts it).

ctypes releases the GIL for the duration of each ``decode_scan`` call, so
the ingest thread pool finally scales the entropy pass across host cores
— the pure-Python loop serialized every producer behind the GIL.

``KEYSTONE_NATIVE_ENTROPY=0`` forces the Python pass; the gate lives in
:func:`enabled` (re-read per call, NOT latched at first load) so tests and
benchmarks can toggle backends without :func:`reset`.
"""

from __future__ import annotations

import ctypes
import logging
import os
import threading

import numpy as np

from ..utils.platform import build_native_library

_logger = logging.getLogger(__name__)

#: Env knob: ``0`` forces the pure-Python entropy pass (portable
#: fallback); anything else builds/loads the native loop on first use.
NATIVE_ENTROPY_ENV = "KEYSTONE_NATIVE_ENTROPY"

_SRC = os.path.join(
    os.path.dirname(__file__), "..", "native", "entropy.cpp"
)

_lock = threading.Lock()
_lib: ctypes.CDLL | None = None
_tried = False
_reported = False  # degradation counted/logged once per process

#: C return code -> the EXACT JpegEntropyCorrupt message the Python loop
#: raises (keep in sync with the KST_E* enum in native/entropy.cpp).
#: Formatted with err_info[0] (mcu), err_info[1] (DC category) and
#: total_mcus.
_ERR_MESSAGES = {
    1: "invalid Huffman code or truncated scan (mcu {e0}/{total})",
    2: "ZRL overflows the block",
    3: "AC run overflows the block",
    4: "DC category {e1} out of range",
    5: "truncated scan mid-coefficient",
    6: "DC predictor out of int16 range",
    7: "decoded {e0} of {total} MCUs (truncated scan)",
}


def _build() -> str | None:
    return build_native_library(_SRC, "kstentropy")


def _report_unavailable(why: str) -> None:
    """Count + log the native->Python degradation ONCE per process — a
    silently slow entropy pass would look exactly like a regression."""
    global _reported
    if _reported:
        return
    _reported = True
    _logger.warning(
        "native entropy decoder unavailable (%s); using the pure-Python "
        "pass — streams stay bit-equal, throughput drops", why,
    )
    try:
        from ..core.resilience import counters

        counters.record(
            "native_entropy_unavailable",
            f"{why}: entropy decode degraded to the pure-Python pass",
        )
    except Exception:  # noqa: BLE001 — accounting must never block decode
        pass


def _load() -> ctypes.CDLL | None:
    """Build (first use only) + dlopen the native entropy loop.

    Call this (via :func:`available`) BEFORE entering a decode hot path:
    the one-time g++ build runs under the module lock, so a lazy first
    call from inside the ingest thread pool would stall every producer
    behind it (core.ingest prewarms in the device-mode producer).  The
    env gate is deliberately NOT consulted here — callers check
    :func:`enabled` per call so toggling the knob needs no reset."""
    global _lib, _tried
    with _lock:
        if _tried:
            return _lib
        _tried = True
        path = _build()
        if path is None:
            _report_unavailable("build failed")
            return None
        try:
            lib = ctypes.CDLL(path)
        except OSError:
            _report_unavailable("load failed")
            return None
        u8pp = ctypes.POINTER(ctypes.c_char_p)
        lib.kst_entropy_decode.argtypes = [
            u8pp,                                    # segs
            ctypes.POINTER(ctypes.c_longlong),       # seg_lens
            ctypes.c_int,                            # nseg
            ctypes.POINTER(ctypes.POINTER(ctypes.c_short)),  # planes
            ctypes.POINTER(ctypes.c_int),            # row_width
            ctypes.POINTER(ctypes.c_int),            # mcu_blocks
            ctypes.c_int,                            # n_mcu_blocks
            u8pp,                                    # lut_len
            u8pp,                                    # lut_sym
            ctypes.c_char_p,                         # zigzag
            ctypes.c_int,                            # ncomp
            ctypes.c_longlong,                       # mcus_x
            ctypes.c_longlong,                       # total_mcus
            ctypes.c_longlong,                       # interval
            ctypes.POINTER(ctypes.c_longlong),       # err_info
        ]
        lib.kst_entropy_decode.restype = ctypes.c_int
        _lib = lib
        return _lib


def enabled() -> bool:
    """The env gate, re-read on every call: ``KEYSTONE_NATIVE_ENTROPY=0``
    forces the Python pass without touching the cached build state."""
    return os.environ.get(NATIVE_ENTROPY_ENV, "").strip() != "0"


def available() -> bool:
    """True when the native loop is enabled AND built/loadable.  Triggers
    the lazy build, so call it from setup code (not per image) where the
    one-time g++ cost is acceptable."""
    return enabled() and _load() is not None


def reset() -> None:
    """Forget the cached build/load outcome (under the module lock) so the
    next call re-evaluates the library state, and re-arm the once-per-
    process degradation report.  Public hook for tests that simulate
    build failure — poking ``_tried``/``_lib`` directly would race any
    live decode thread."""
    global _lib, _tried, _reported
    with _lock:
        _tried = False
        _lib = None
        _reported = False


def _zigzag_bytes() -> bytes:
    from .jpeg_device import ZIGZAG

    return ZIGZAG.astype(np.uint8).tobytes()


_zz_cache: bytes | None = None


def decode_scan(
    segments, planes, mcu_blocks, ncomp, mcus_x, total_mcus, interval
) -> bool:
    """Native drop-in for ops/jpeg_device._decode_scan — identical
    arguments, identical plane writes, identical typed errors.

    Returns False (planes untouched) when the library is unavailable so
    the caller runs the Python loop; True after a successful native
    decode.  A damaged scan raises :class:`JpegEntropyCorrupt` with the
    same message the Python loop produces for the same stream."""
    global _zz_cache
    lib = _load()
    if lib is None:
        return False

    nseg = len(segments)
    seg_arr = (ctypes.c_char_p * nseg)(*segments)
    len_arr = (ctypes.c_longlong * nseg)(*(len(s) for s in segments))

    plane_ptrs = (ctypes.POINTER(ctypes.c_short) * len(planes))(
        *(p.ctypes.data_as(ctypes.POINTER(ctypes.c_short)) for p in planes)
    )
    widths = (ctypes.c_int * len(planes))(*(p.shape[1] for p in planes))

    # Dedup the _HuffLUT objects (the LUT byte tables are 64 KiB each and
    # shared across blocks/components) and flatten mcu_blocks to the 7-int
    # rows the C loop indexes.
    lut_index: dict[int, int] = {}
    lut_len: list[bytes] = []
    lut_sym: list[bytes] = []

    def _lut(lut) -> int:
        idx = lut_index.get(id(lut))
        if idx is None:
            idx = len(lut_len)
            lut_index[id(lut)] = idx
            lut_len.append(lut.length_b)
            lut_sym.append(lut.symbol_b)
        return idx

    flat = []
    for ci, v, h, by, bx, dc_lut, ac_lut in mcu_blocks:
        flat.extend((ci, v, h, by, bx, _lut(dc_lut), _lut(ac_lut)))
    mb_arr = (ctypes.c_int * len(flat))(*flat)
    len_ptrs = (ctypes.c_char_p * len(lut_len))(*lut_len)
    sym_ptrs = (ctypes.c_char_p * len(lut_sym))(*lut_sym)

    if _zz_cache is None:
        _zz_cache = _zigzag_bytes()
    err = (ctypes.c_longlong * 2)(0, 0)

    rc = lib.kst_entropy_decode(
        seg_arr, len_arr, nseg,
        plane_ptrs, widths,
        mb_arr, len(mcu_blocks),
        len_ptrs, sym_ptrs, _zz_cache,
        ncomp, mcus_x, total_mcus, interval, err,
    )
    if rc == 0:
        return True
    from .jpeg_device import JpegEntropyCorrupt

    msg = _ERR_MESSAGES.get(rc, "native entropy decode error {e0}")
    raise JpegEntropyCorrupt(
        msg.format(e0=int(err[0]), e1=int(err[1]), total=total_mcus)
    )
