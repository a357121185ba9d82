"""Device-resident JPEG decode: split baseline JPEG at the entropy boundary.

The decode wall (bench round r05, 2026-07-30, record removed in PR 21:
~900 images/sec host decode vs 15-17k
images/sec device featurize) has been attacked three times — threaded
overlap (PR 4), the process pool and the snapshot cache (PR 7) — but the
host still performed ALL pixel work: Huffman entropy decode, dequant,
IDCT, chroma upsample, colorspace.  Only the first of those is inherently
serial bit-twiddling; everything after the entropy decoder is dense
batched linear algebra — exactly what the accelerator is for.  This
module splits the decoder at that boundary:

* **host entropy pass** (:func:`entropy_decode`, numpy + a table-driven
  bit reader): parse markers, Huffman-decode the entropy-coded scan into
  per-component quantized DCT coefficient blocks (`int16`, natural
  order), and emit a :class:`CoeffImage` — coefficients plus a geometry
  descriptor and the image's quantization tables.  No IDCT, no upsample,
  no colorspace: the heavy O(pixels) math never runs on the host.
* **device batch pass** (:func:`decode_batch`, one jitted program per
  geometry): dequantize, 8x8 IDCT (Pallas kernel on TPU, jnp einsum
  elsewhere, see :func:`idct_blocks`), libjpeg-style *fancy* (triangular) chroma
  upsampling, YCbCr->RGB, clamp/round — pixels are born on device, in
  the same BGR f32 layout :func:`~..loaders.image_loaders.decode_image`
  produces, and can be FUSED straight into a featurize program
  (:func:`fused_apply`) so coefficient batches turn into features in one
  dispatch.

Scope is deliberately the baseline subset (sequential DCT, Huffman, 8-bit,
grayscale or YCbCr with 4:4:4 / 4:2:2 / 4:2:0 sampling, restart markers):
everything else raises a typed :class:`JpegDecodeUnsupported` carrying a
``reason`` so ``core.ingest`` routes it to the host decode path as a
COUNTED ``device_decode_fallback_<reason>`` — never a silent wrong pixel.
Corrupt entropy data (truncated scan, invalid Huffman code, early marker)
raises :class:`JpegEntropyCorrupt` — a typed, counted skip upstream.

Parity contract: device output matches the native libjpeg decoder within
IDCT-rounding tolerance (:data:`GOLDEN_MAX_ABS` / :data:`GOLDEN_MEAN_ABS`)
— the same class of difference ``core.snapshot`` already keys snapshots by
(native-vs-PIL decoders differ in IDCT rounding, so the snapshot key folds
the decoder in; device decode is a third decoder in that sense and the
device-format snapshot tier stores its OWN pixels, see core/snapshot.py).
"""

from __future__ import annotations

import dataclasses
import functools
import logging
import os

import numpy as np

from . import native_entropy

_logger = logging.getLogger(__name__)

#: Golden-parity tolerance vs the host (libjpeg/PIL) decoder, in 8-bit
#: sample levels.  Budget: libjpeg's fixed-point ``jpeg_idct_islow`` is
#: IEEE-1180-accurate (~±1) on conforming blocks, fancy upsampling and the
#: fixed-point color conversion each round within ±1 — but heavily
#: quantized noise blocks whose IDCT overshoots [0, 255] sit outside the
#: 1180 test range, where the fixed-point path drifts a few more levels
#: from the exact float IDCT (measured max 6 over the bench corpus at
#: quality 85).  The MEAN bound is the tight one; the max bound budgets
#: the clamp-corner outliers.
GOLDEN_MAX_ABS = 8.0
GOLDEN_MEAN_ABS = 1.0

#: ``KEYSTONE_PALLAS_IDCT``: ``1`` forces the Pallas IDCT kernel, ``0``
#: forces the jnp einsum path; unset = Pallas on TPU backends, jnp
#: elsewhere.  Off-TPU the kernel only runs under the caller's
#: ``pltpu.force_tpu_interpret_mode()`` — a correctness oracle, not a fast
#: path (tier-1 holds the two within :data:`IDCT_ATOL`).
PALLAS_IDCT_ENV = "KEYSTONE_PALLAS_IDCT"

#: ``KEYSTONE_NATIVE_ENTROPY``: ``0`` forces the pure-Python entropy pass;
#: unset/anything else lazy-builds the native loop (ops/native_entropy)
#: and degrades to Python counted when the toolchain is absent.  Both
#: passes are bit-identical over the supported subset (tier-1 asserts it
#: whenever the toolchain is available).
NATIVE_ENTROPY_ENV = native_entropy.NATIVE_ENTROPY_ENV

def _zigzag_order() -> np.ndarray:
    """zigzag scan position -> natural (row-major) position within the
    8x8 (built by walking the pattern — a 64-entry literal is unreadable
    and unverifiable by eye)."""
    order = np.empty(64, np.int32)
    row = col = 0
    for k in range(64):
        order[k] = row * 8 + col
        if (row + col) % 2 == 0:  # moving up-right
            if col == 7:
                row += 1
            elif row == 0:
                col += 1
            else:
                row -= 1
                col += 1
        else:  # moving down-left
            if row == 7:
                col += 1
            elif col == 0:
                row += 1
            else:
                row += 1
                col -= 1
    return order


ZIGZAG = _zigzag_order()


class JpegDecodeUnsupported(ValueError):
    """The stream is a JPEG the device path does not claim (progressive,
    arithmetic-coded, CMYK, exotic subsampling, 12-bit, multi-scan...).
    Carries ``reason`` — a short slug the ingest fallback counter is keyed
    by (``device_decode_fallback_<reason>``)."""

    def __init__(self, reason: str, detail: str = ""):
        super().__init__(f"{reason}: {detail}" if detail else reason)
        self.reason = reason


class JpegEntropyCorrupt(ValueError):
    """The entropy-coded scan is damaged (truncated data, invalid Huffman
    code, a marker where MCUs should be, coefficient overrun).  The caller
    must skip-and-count — decoding further would fabricate pixels."""


@dataclasses.dataclass(frozen=True)
class JpegGeometry:
    """Everything the DEVICE stage needs that is shape-static: images with
    equal geometry batch into one jitted decode program (quant tables ride
    as per-image data — quality may vary within a batch)."""

    height: int
    width: int
    #: per-component (h, v) sampling factors, e.g. ((2, 2), (1, 1), (1, 1))
    sampling: tuple
    #: per-component padded block-grid shape (blocks_y, blocks_x)
    block_shape: tuple

    @property
    def n_components(self) -> int:
        return len(self.sampling)

    def coeff_shapes(self) -> tuple:
        """Per-component coefficient array shapes [by, bx, 8, 8]."""
        return tuple((by, bx, 8, 8) for by, bx in self.block_shape)

    def coeff_bytes(self) -> int:
        """int16 coefficient payload bytes for ONE image — the wire cost
        of the entropy-boundary split (telemetry: ``ingest_coeff_bytes``)."""
        return sum(by * bx * 64 * 2 for by, bx in self.block_shape)


@dataclasses.dataclass
class CoeffImage:
    """One entropy-decoded image: quantized coefficients + geometry."""

    geom: JpegGeometry
    #: per-component [by, bx, 8, 8] int16, natural (row-major) order
    coeffs: tuple
    #: [ncomp, 8, 8] float32 dequant tables (natural order)
    qt: np.ndarray


# -- host entropy pass ---------------------------------------------------------


class _HuffLUT:
    """Canonical Huffman table compiled to a 16-bit-peek lookup: one index
    decodes (symbol, code length) — the classic libjpeg fast path, built
    once per table per image.  Stored as ``bytes`` (not ndarrays): the
    scan loop indexes them per symbol, and ``bytes[i]`` is a plain int at
    a fraction of a numpy scalar's cost."""

    __slots__ = ("length_b", "symbol_b")

    def __init__(self, counts: np.ndarray, symbols: np.ndarray):
        length = np.zeros(1 << 16, np.uint8)
        symbol = np.zeros(1 << 16, np.uint8)
        code = 0
        k = 0
        for bits in range(1, 17):
            n = int(counts[bits - 1])
            for _ in range(n):
                if code >= (1 << bits):
                    raise JpegEntropyCorrupt(
                        f"overfull Huffman table at code length {bits}"
                    )
                lo = code << (16 - bits)
                hi = lo + (1 << (16 - bits))
                length[lo:hi] = bits
                symbol[lo:hi] = symbols[k]
                code += 1
                k += 1
            code <<= 1
        self.length_b = length.tobytes()
        self.symbol_b = symbol.tobytes()


@functools.lru_cache(maxsize=64)
def _huff_lut(counts: bytes, symbols: bytes) -> _HuffLUT:
    """LUT compilation cached by table content: most encoders emit the
    Annex-K standard tables, so a tar of thousands of JPEGs compiles four
    LUTs once instead of four per image."""
    return _HuffLUT(
        np.frombuffer(counts, np.uint8), np.frombuffer(symbols, np.uint8)
    )


def _decode_scan(
    segments, planes, mcu_blocks, ncomp, mcus_x, total_mcus, interval
):
    """The hot loop: Huffman-decode every MCU of the (already unstuffed,
    restart-split) scan into the per-component coefficient planes.

    Deliberately ONE function with the bit reader inlined as plain locals
    (acc/accbits/pos) and the Huffman LUTs indexed as ``bytes`` — this is
    the only O(compressed-bytes) Python in the device-decode path, and
    attribute access per symbol costs more than the decode itself.  Running
    out of bits or hitting an invalid code raises
    :class:`JpegEntropyCorrupt` (libjpeg pads with 1s and warns; this
    path's contract is typed-or-correct, so a truncated scan is an error,
    not a grey image)."""
    zz = ZIGZAG.tolist()
    flat = [p.reshape(-1, 64) for p in planes]
    row_width = [p.shape[1] for p in planes]
    from_bytes = int.from_bytes
    mcu = 0
    for seg_bytes in segments:
        acc = 0
        accbits = 0
        pos = 0
        nbytes = len(seg_bytes)
        preds = [0] * ncomp
        seg_end = min(mcu + interval, total_mcus)
        while mcu < seg_end:
            my, mx = divmod(mcu, mcus_x)
            for ci, v, h, by, bx, dc_lut, ac_lut in mcu_blocks:
                row = flat[ci][
                    (my * v + by) * row_width[ci] + mx * h + bx
                ]
                pred = preds[ci]
                lenb, symb = dc_lut.length_b, dc_lut.symbol_b
                ac = False
                k = 0
                while True:
                    # -- decode one Huffman symbol ------------------------
                    if accbits < 16 and pos < nbytes:
                        take = seg_bytes[pos : pos + 6]
                        acc = (acc << (8 * len(take))) | from_bytes(
                            take, "big"
                        )
                        accbits += 8 * len(take)
                        pos += len(take)
                    peek = (
                        (acc << (16 - accbits))
                        if accbits < 16
                        else (acc >> (accbits - 16))
                    ) & 0xFFFF
                    nb = lenb[peek]
                    if nb == 0 or nb > accbits:
                        raise JpegEntropyCorrupt(
                            "invalid Huffman code or truncated scan "
                            f"(mcu {mcu}/{total_mcus})"
                        )
                    accbits -= nb
                    acc &= (1 << accbits) - 1
                    sym = symb[peek]
                    # -- interpret it ------------------------------------
                    if ac:
                        run, size = sym >> 4, sym & 0xF
                        if size == 0:
                            if run == 15:
                                k += 16
                                if k > 63:
                                    raise JpegEntropyCorrupt(
                                        "ZRL overflows the block"
                                    )
                                continue
                            break  # EOB
                        k += run + 1
                        if k > 63:
                            raise JpegEntropyCorrupt(
                                "AC run overflows the block"
                            )
                    else:
                        size = sym
                        if size > 15:
                            raise JpegEntropyCorrupt(
                                f"DC category {size} out of range"
                            )
                    # -- receive the value bits --------------------------
                    val = 0
                    if size:
                        if accbits < size:
                            take = seg_bytes[pos : pos + 6]
                            acc = (acc << (8 * len(take))) | from_bytes(
                                take, "big"
                            )
                            accbits += 8 * len(take)
                            pos += len(take)
                            if accbits < size:
                                raise JpegEntropyCorrupt(
                                    "truncated scan mid-coefficient"
                                )
                        accbits -= size
                        val = (acc >> accbits) & ((1 << size) - 1)
                        acc &= (1 << accbits) - 1
                        if val < (1 << (size - 1)):  # EXTEND
                            val = val - (1 << size) + 1
                    if ac:
                        row[zz[k]] = val
                        if k == 63:
                            break
                    else:
                        pred += val
                        if not -32768 <= pred <= 32767:
                            # only reachable on a damaged stream: a valid
                            # baseline DC predictor is 11-bit — raise
                            # typed instead of numpy's OverflowError
                            raise JpegEntropyCorrupt(
                                "DC predictor out of int16 range"
                            )
                        row[0] = pred
                        ac = True
                        lenb, symb = ac_lut.length_b, ac_lut.symbol_b
                preds[ci] = pred
            mcu += 1
    if mcu != total_mcus:
        raise JpegEntropyCorrupt(
            f"decoded {mcu} of {total_mcus} MCUs (truncated scan)"
        )


_native_fallback_logged = False


def _run_scan(
    segments, planes, mcu_blocks, ncomp, mcus_x, total_mcus, interval,
    backend,
):
    """Backend dispatch for the scan hot loop — returns the backend that
    actually ran (``"native"`` / ``"python"``).

    ``backend=None`` (production) prefers the native loop when the
    ``KEYSTONE_NATIVE_ENTROPY`` gate allows it and the library builds,
    and otherwise runs the pure-Python pass — bit-equal by contract.  An
    UNEXPECTED native failure (not a typed corrupt-stream error) degrades
    this one image to the Python pass, counted ``native_entropy_fallback``
    — never a crash, never a silent difference.  Explicit ``"native"`` /
    ``"python"`` pin a backend for tests and benches; a pinned native
    backend raises rather than degrade, so parity harnesses cannot
    silently compare Python against itself.

    ``native_entropy.decode_scan`` is resolved as a module attribute at
    call time so the chaos harness can inject failures at the boundary.
    """
    if backend == "python":
        _decode_scan(
            segments, planes, mcu_blocks, ncomp, mcus_x, total_mcus,
            interval,
        )
        return "python"
    if backend == "native":
        if not native_entropy.decode_scan(
            segments, planes, mcu_blocks, ncomp, mcus_x, total_mcus,
            interval,
        ):
            raise RuntimeError(
                "entropy backend pinned to 'native' but the native "
                "library is unavailable (check g++ / "
                f"{NATIVE_ENTROPY_ENV})"
            )
        return "native"
    if backend is not None:
        raise ValueError(f"unknown entropy backend {backend!r}")
    if native_entropy.enabled():
        try:
            if native_entropy.decode_scan(
                segments, planes, mcu_blocks, ncomp, mcus_x, total_mcus,
                interval,
            ):
                return "native"
        except JpegEntropyCorrupt:
            raise  # typed classification — identical to the Python pass
        except Exception as exc:  # noqa: BLE001 — degrade, never crash
            global _native_fallback_logged
            if not _native_fallback_logged:
                _native_fallback_logged = True
                _logger.warning(
                    "native entropy decode failed (%s: %s); this image "
                    "degrades to the pure-Python pass (counted "
                    "native_entropy_fallback; logged once)",
                    type(exc).__name__, exc,
                )
            try:
                from ..core.resilience import counters

                counters.record(
                    "native_entropy_fallback",
                    f"{type(exc).__name__}: {exc}",
                )
            except Exception:  # noqa: BLE001
                pass
            # the native call may have written a partial image before
            # failing — re-zero so the Python re-decode starts clean
            for p in planes:
                p[...] = 0
    _decode_scan(
        segments, planes, mcu_blocks, ncomp, mcus_x, total_mcus, interval
    )
    return "python"


def entropy_backend() -> str:
    """The backend the auto dispatch would pick right now (``"native"`` /
    ``"python"``) — for bench records and ingest telemetry.  Triggers the
    lazy native build, so call it from setup paths, not per image."""
    return "native" if native_entropy.available() else "python"


def _u16(data: bytes, i: int) -> int:
    return (data[i] << 8) | data[i + 1]


@dataclasses.dataclass
class _Frame:
    height: int = 0
    width: int = 0
    comps: list = dataclasses.field(default_factory=list)  # (id, h, v, tq)
    restart_interval: int = 0
    qt: dict = dataclasses.field(default_factory=dict)  # tq -> [64] u16 zigzag
    huff_dc: dict = dataclasses.field(default_factory=dict)
    huff_ac: dict = dataclasses.field(default_factory=dict)
    scan_comps: list = dataclasses.field(default_factory=list)  # (ci, td, ta)
    scan_at: int = 0  # offset of first entropy-coded byte
    adobe_transform: int | None = None  # APP14 color transform, if present


_SUPPORTED_LUMA = {(1, 1), (2, 1), (2, 2)}


def _parse_headers(data: bytes) -> _Frame:
    if len(data) < 4 or data[0] != 0xFF or data[1] != 0xD8:
        raise JpegDecodeUnsupported("not_jpeg", "missing SOI marker")
    f = _Frame()
    i = 2
    n = len(data)
    while True:
        # seek the next marker (fill bytes 0xFF may repeat)
        while i < n and data[i] != 0xFF:
            i += 1
        while i < n and data[i] == 0xFF:
            i += 1
        if i >= n:
            raise JpegEntropyCorrupt("ran out of data before SOS")
        marker = data[i]
        i += 1
        if marker in (0x01,) or 0xD0 <= marker <= 0xD8:
            continue  # standalone markers
        if marker == 0xD9:
            raise JpegEntropyCorrupt("EOI before any scan data")
        if i + 2 > n:
            raise JpegEntropyCorrupt("truncated marker segment header")
        seg_len = _u16(data, i)
        if seg_len < 2 or i + seg_len > n:
            raise JpegEntropyCorrupt(f"truncated segment FF{marker:02X}")
        seg = data[i + 2 : i + seg_len]
        i += seg_len
        if marker == 0xC2:
            raise JpegDecodeUnsupported("progressive")
        if marker in (0xC9, 0xCA, 0xCB, 0xCD, 0xCE, 0xCF):
            raise JpegDecodeUnsupported("arithmetic")
        if marker in (0xC3, 0xC5, 0xC6, 0xC7):
            raise JpegDecodeUnsupported(
                "sof_unsupported", f"SOF marker FF{marker:02X}"
            )
        if marker in (0xC0, 0xC1):  # baseline / extended sequential Huffman
            if seg[0] != 8:
                raise JpegDecodeUnsupported(
                    "precision", f"{seg[0]}-bit samples"
                )
            f.height = _u16(seg, 1)
            f.width = _u16(seg, 3)
            ncomp = seg[5]
            if ncomp == 4:
                raise JpegDecodeUnsupported("cmyk", "4-component frame")
            if ncomp not in (1, 3):
                raise JpegDecodeUnsupported(
                    "components", f"{ncomp}-component frame"
                )
            for c in range(ncomp):
                cid, hv, tq = seg[6 + 3 * c : 9 + 3 * c]
                f.comps.append((cid, hv >> 4, hv & 0xF, tq))
            if ncomp == 3 and tuple(c[0] for c in f.comps) == (
                0x52, 0x47, 0x42,
            ):
                # component ids spell "RGB": channels are stored RGB, and
                # the YCbCr matrix below would hue-shift them silently
                raise JpegDecodeUnsupported(
                    "rgb_colorspace", "RGB component ids"
                )
            if ncomp == 3:
                (_, h0, v0, _), (_, h1, v1, _), (_, h2, v2, _) = f.comps
                if (
                    (h0, v0) not in _SUPPORTED_LUMA
                    or (h1, v1) != (1, 1)
                    or (h2, v2) != (1, 1)
                ):
                    raise JpegDecodeUnsupported(
                        "subsampling",
                        f"Y={h0}x{v0} Cb={h1}x{v1} Cr={h2}x{v2}",
                    )
            continue
        if marker == 0xDB:  # DQT — possibly several tables per segment
            j = 0
            while j < len(seg):
                pq, tq = seg[j] >> 4, seg[j] & 0xF
                j += 1
                if pq == 0:
                    f.qt[tq] = np.frombuffer(
                        seg, np.uint8, 64, j
                    ).astype(np.uint16)
                    j += 64
                else:
                    f.qt[tq] = np.frombuffer(
                        seg[j : j + 128], ">u2", 64
                    ).astype(np.uint16)
                    j += 128
            continue
        if marker == 0xC4:  # DHT
            j = 0
            while j < len(seg):
                tc, th = seg[j] >> 4, seg[j] & 0xF
                counts = np.frombuffer(seg, np.uint8, 16, j + 1)
                total = int(counts.sum())
                table = _huff_lut(
                    bytes(counts), seg[j + 17 : j + 17 + total]
                )
                (f.huff_dc if tc == 0 else f.huff_ac)[th] = table
                j += 17 + total
            continue
        if marker == 0xDD:  # DRI
            f.restart_interval = _u16(seg, 0)
            continue
        if marker == 0xEE and seg[:5] == b"Adobe" and len(seg) >= 12:
            f.adobe_transform = seg[11]
            continue
        if marker == 0xDA:  # SOS
            ns = seg[0]
            if not f.comps:
                raise JpegEntropyCorrupt("SOS before SOF")
            if len(f.comps) == 3 and f.adobe_transform == 0:
                # Adobe APP14 transform=0: three components stored RGB —
                # the YCbCr conversion would silently hue-shift them
                raise JpegDecodeUnsupported(
                    "rgb_colorspace", "Adobe APP14 transform=0"
                )
            if ns != len(f.comps):
                raise JpegDecodeUnsupported(
                    "multi_scan", f"{ns} of {len(f.comps)} components in scan"
                )
            for s in range(ns):
                cs, tdta = seg[1 + 2 * s : 3 + 2 * s]
                ci = next(
                    (k for k, c in enumerate(f.comps) if c[0] == cs), None
                )
                if ci is None:
                    raise JpegEntropyCorrupt(
                        f"scan names unknown component {cs}"
                    )
                f.scan_comps.append((ci, tdta >> 4, tdta & 0xF))
            ss, se = seg[1 + 2 * ns], seg[2 + 2 * ns]
            if (ss, se) != (0, 63):
                raise JpegDecodeUnsupported(
                    "spectral_selection", f"Ss={ss} Se={se}"
                )
            f.scan_at = i
            return f
        # APPn / COM / anything else: skipped


def _split_scan(data: bytes, start: int) -> list[bytes]:
    """Slice the entropy-coded data into UNSTUFFED restart segments.
    ``0xFF00`` is byte stuffing (kept as a data ``0xFF``), ``0xFFD0-D7``
    are restart markers (segment boundaries), any other marker ends the
    scan."""
    arr = np.frombuffer(data, np.uint8, len(data) - start, start)
    ff = np.flatnonzero(arr[:-1] == 0xFF)
    nxt = arr[ff + 1]
    segments: list[bytes] = []
    raw = arr.tobytes()
    seg_start = 0
    end = len(raw)
    cut_points: list[int] = []
    for pos, code in zip(ff.tolist(), nxt.tolist()):
        if pos < seg_start:
            continue  # inside an already-consumed marker pair
        if code == 0x00:
            continue  # stuffing, handled by the replace below
        if code == 0xFF:
            continue  # fill byte; the NEXT 0xFF position classifies it
        if 0xD0 <= code <= 0xD7:
            cut_points.append(pos)
            seg_start = pos + 2
            continue
        end = pos  # real marker: scan ends here
        break
    out = []
    prev = 0
    for cut in cut_points:
        if cut >= end:
            break
        out.append(raw[prev:cut].replace(b"\xff\x00", b"\xff"))
        prev = cut + 2
    out.append(raw[prev:end].replace(b"\xff\x00", b"\xff"))
    return out


def entropy_decode(data: bytes, *, backend: str | None = None) -> CoeffImage:
    """Baseline-JPEG bytes -> :class:`CoeffImage` (host entropy pass only).

    Raises :class:`JpegDecodeUnsupported` (typed fallback routing) for
    streams outside the claimed subset and :class:`JpegEntropyCorrupt`
    (typed counted skip) for damaged scans.

    ``backend`` pins the scan hot loop: ``"native"`` (the lazily-built C
    loop, raises if unbuildable), ``"python"`` (the portable pass), or
    ``None`` — native when available, Python otherwise, bit-identical
    output either way (see :func:`_run_scan`)."""
    f = _parse_headers(data)
    ncomp = len(f.comps)
    hmax = max(c[1] for c in f.comps)
    vmax = max(c[2] for c in f.comps)
    mcus_x = -(-f.width // (8 * hmax))
    mcus_y = -(-f.height // (8 * vmax))
    if f.height == 0 or f.width == 0:
        raise JpegEntropyCorrupt("zero-sized frame")

    # per-component coefficient planes, MCU-padded, zigzag written flat
    planes = []
    qts = np.zeros((ncomp, 8, 8), np.float32)
    for k, (_cid, h, v, tq) in enumerate(f.comps):
        planes.append(np.zeros((mcus_y * v, mcus_x * h, 64), np.int16))
        if tq not in f.qt:
            raise JpegEntropyCorrupt(f"missing quant table {tq}")
        nat = np.zeros(64, np.float32)
        nat[ZIGZAG] = f.qt[tq].astype(np.float32)
        qts[k] = nat.reshape(8, 8)

    for ci, td, ta in f.scan_comps:
        if td not in f.huff_dc or ta not in f.huff_ac:
            raise JpegEntropyCorrupt(
                f"scan references missing Huffman table dc={td} ac={ta}"
            )

    segments = _split_scan(data, f.scan_at)
    total_mcus = mcus_x * mcus_y
    interval = f.restart_interval or total_mcus
    expected_segments = -(-total_mcus // interval)
    if len(segments) < expected_segments:
        raise JpegEntropyCorrupt(
            f"scan holds {len(segments)} restart segment(s), geometry "
            f"needs {expected_segments}"
        )

    # per-MCU (component, block-row, block-col, dc_lut, ac_lut) unrolled
    # once so the hot loop below carries no per-block geometry arithmetic
    mcu_blocks = []
    for ci, td, ta in f.scan_comps:
        _cid, h, v, _tq = f.comps[ci]
        for by in range(v):
            for bx in range(h):
                mcu_blocks.append(
                    (ci, v, h, by, bx, f.huff_dc[td], f.huff_ac[ta])
                )
    _run_scan(
        segments[:expected_segments], planes, mcu_blocks, ncomp,
        mcus_x, total_mcus, interval, backend,
    )

    geom = JpegGeometry(
        height=f.height,
        width=f.width,
        sampling=tuple((h, v) for _cid, h, v, _tq in f.comps),
        block_shape=tuple(p.shape[:2] for p in planes),
    )
    coeffs = tuple(
        p.reshape(p.shape[0], p.shape[1], 8, 8) for p in planes
    )
    return CoeffImage(geom=geom, coeffs=coeffs, qt=qts)


# -- device batch pass ---------------------------------------------------------


def _idct_basis() -> np.ndarray:
    """Orthonormal 8-point DCT-II basis A (A @ A.T = I), float64: spatial
    samples x = A.T @ X @ A for coefficient block X."""
    k = np.arange(8)[:, None].astype(np.float64)
    n = np.arange(8)[None, :].astype(np.float64)
    a = np.cos((2 * n + 1) * k * np.pi / 16.0) * 0.5
    a[0] *= 1.0 / np.sqrt(2.0)
    return a


#: 8x8 blocks packed side by side into one 128-lane row of the Pallas
#: kernel's operand.
_IDCT_PACK = 2

#: Max |pallas - jnp| the two IDCT formulations may differ by on
#: dequantized coefficients (|X| up to ~2^11): f32 rounding of 64-term
#: sums in two different association orders.  Far below the 0.5 that
#: could move a rounded 8-bit sample by more than one level.
IDCT_ATOL = 2e-2


@functools.lru_cache(maxsize=1)
def _idct_kron() -> np.ndarray:
    """[128, 128] f32: two copies of kron(A, A) on the diagonal.  With the
    8x8 block flattened row-major, x = A.T @ X @ A is
    vec(x) = vec(X) @ kron(A, A); two blocks share a row so the operand is
    128 lanes wide and the contraction is one native MXU tile."""
    a = _idct_basis()
    return np.kron(np.eye(_IDCT_PACK), np.kron(a, a)).astype(np.float32)


def _pallas_wanted() -> bool:
    raw = os.environ.get(PALLAS_IDCT_ENV, "").strip()
    if raw == "1":
        return True
    if raw == "0":
        return False
    import jax

    return jax.default_backend() == "tpu"


def idct_blocks_jnp(blocks):
    """[..., 8, 8] dequantized coefficients -> spatial samples (no level
    shift) — the separable reference the Pallas kernel is checked against
    (:data:`IDCT_ATOL`).  ``HIGHEST`` precision: the TPU's default f32
    matmul rounds its operands to bf16, which is several 8-bit levels on a
    DC coefficient."""
    import jax
    import jax.numpy as jnp

    a = jnp.asarray(_idct_basis(), jnp.float32)
    return jnp.einsum(
        "ij,...jk,kl->...il", a.T, blocks, a,
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )


def _idct_kernel(k_ref, x_ref, o_ref):
    import jax
    import jax.numpy as jnp

    o_ref[...] = jnp.dot(
        x_ref[...], k_ref[...],
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )


def idct_blocks_pallas(blocks, *, rows_per_step: int = 1024,
                       interpret: bool = False):
    """Pallas IDCT over [..., 8, 8] blocks in lane-dense form: the blocks
    are flattened two to a 128-lane row and each grid step multiplies a
    ``[rows_per_step, 128]`` tile by :func:`_idct_kron` on the MXU
    (compiled by Mosaic on a TPU v5e, 2,048 x 36 blocks within 2.4e-4 of
    :func:`idct_blocks_jnp` — chip run, PR 21).
    ``interpret`` is the caller's to say (tests and rehearsals run the
    interpreter); it is never inferred from the backend."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    lead = blocks.shape[:-2]
    n = int(np.prod(lead, dtype=np.int64)) * 64
    lanes = 64 * _IDCT_PACK
    rows = -(-n // lanes)
    tile = min(rows_per_step, -(-rows // 8) * 8)
    rows_pad = -(-rows // tile) * tile
    x = jnp.pad(
        blocks.astype(jnp.float32).reshape(n), (0, rows_pad * lanes - n)
    ).reshape(rows_pad, lanes)
    out = pl.pallas_call(
        _idct_kernel,
        grid=(rows_pad // tile,),
        in_specs=[
            pl.BlockSpec(
                (lanes, lanes), lambda i: (0, 0), memory_space=pltpu.VMEM
            ),
            pl.BlockSpec(
                (tile, lanes), lambda i: (i, 0), memory_space=pltpu.VMEM
            ),
        ],
        out_specs=pl.BlockSpec(
            (tile, lanes), lambda i: (i, 0), memory_space=pltpu.VMEM
        ),
        out_shape=jax.ShapeDtypeStruct((rows_pad, lanes), jnp.float32),
        interpret=interpret,
    )(jnp.asarray(_idct_kron()), x)
    return out.reshape(rows_pad * lanes)[:n].reshape(*lead, 8, 8)


def idct_blocks(blocks):
    """The production chooser: the Pallas kernel on TPU backends (or
    wherever ``KEYSTONE_PALLAS_IDCT=1`` — off-TPU that needs the caller's
    ``pltpu.force_tpu_interpret_mode()``), the jnp einsum otherwise."""
    if _pallas_wanted():
        return idct_blocks_pallas(blocks)
    return idct_blocks_jnp(blocks)


def _upsample2_h(plane):
    """libjpeg ``h2v1`` fancy (triangular) upsample along the last axis:
    out[2i] = (3*s[i] + s[i-1]) / 4, out[2i+1] = (3*s[i] + s[i+1]) / 4,
    edges replicated."""
    import jax.numpy as jnp

    left = jnp.concatenate([plane[..., :1], plane[..., :-1]], axis=-1)
    right = jnp.concatenate([plane[..., 1:], plane[..., -1:]], axis=-1)
    even = (3.0 * plane + left) * 0.25
    odd = (3.0 * plane + right) * 0.25
    out = jnp.stack([even, odd], axis=-1)
    return out.reshape(*plane.shape[:-1], plane.shape[-1] * 2)


def _upsample2_v(plane):
    import jax.numpy as jnp

    up = jnp.swapaxes(_upsample2_h(jnp.swapaxes(plane, -1, -2)), -1, -2)
    return up


def _blocks_to_plane(x):
    """[B, by, bx, 8, 8] -> [B, by*8, bx*8]."""
    b, by, bx = x.shape[:3]
    return x.transpose(0, 1, 3, 2, 4).reshape(b, by * 8, bx * 8)


def _decode_pixels(geom: JpegGeometry, coeffs, qt):
    """The jitted body: coefficient arrays (+ per-image quant tables) ->
    [B, H, W, 3] BGR f32 pixel batch, integral values in [0, 255]."""
    import jax.numpy as jnp

    h_img, w_img = geom.height, geom.width
    hmax = max(h for h, _v in geom.sampling)
    vmax = max(v for _h, v in geom.sampling)
    planes = []
    for c in range(geom.n_components):
        x = coeffs[c].astype(jnp.float32) * qt[:, c][:, None, None]
        x = idct_blocks(x) + 128.0
        plane = _blocks_to_plane(x)
        ch, cv = geom.sampling[c]
        # crop to the component's true sample grid BEFORE upsampling: the
        # MCU pad region holds encoder filler whose values must not bleed
        # into real pixels through the triangular filter
        comp_h = -(-h_img * cv // vmax)
        comp_w = -(-w_img * ch // hmax)
        plane = plane[:, :comp_h, :comp_w]
        if ch < hmax:
            plane = _upsample2_h(plane)
        if cv < vmax:
            plane = _upsample2_v(plane)
        planes.append(plane[:, :h_img, :w_img])
    y = planes[0]
    if geom.n_components == 1:
        rgb = (y, y, y)
    else:
        cb = planes[1] - 128.0
        cr = planes[2] - 128.0
        rgb = (
            y + 1.40200 * cr,
            y - 0.344136 * cb - 0.714136 * cr,
            y + 1.77200 * cb,
        )
    # BGR channel order + round-to-integral — the decode_image contract
    bgr = jnp.stack([rgb[2], rgb[1], rgb[0]], axis=-1)
    return jnp.clip(jnp.round(bgr), 0.0, 255.0).astype(jnp.float32)


@functools.lru_cache(maxsize=256)
def _decode_jit(geom: JpegGeometry):
    import jax

    return jax.jit(functools.partial(_decode_pixels, geom))


def decode_batch(geom: JpegGeometry, coeffs, qt):
    """Batched device decode: per-component coefficient arrays
    ([B, by, bx, 8, 8], int16 or f32, host or device) + [B, ncomp, 8, 8]
    quant tables -> [B, H, W, 3] BGR f32 pixels.  One compiled program per
    geometry (cached)."""
    return _decode_jit(geom)(tuple(coeffs), qt)


def stack_coeff_images(images: list) -> tuple:
    """Stack same-geometry :class:`CoeffImage`s into the batched arrays
    ``decode_batch`` consumes: ``(coeffs_tuple, qt)``."""
    geom = images[0].geom
    coeffs = tuple(
        np.stack([img.coeffs[c] for img in images])
        for c in range(geom.n_components)
    )
    qt = np.stack([img.qt for img in images])
    return coeffs, qt


# -- fused decode+featurize ----------------------------------------------------


#: transform -> {geometry -> (fused_jit, admitted)}.  Keyed on the
#: transform OBJECT (not id(): a dead transform's id can be reissued to a
#: new callable, which would silently serve the old fused program) with
#: STRONG references and oldest-first eviction at a small cap — weak
#: keying cannot work here because the cached fused jit closes over the
#: transform, so the value would keep its own key alive forever (an
#: unbounded leak across short-lived transforms).
_fused_cache: dict = {}
_FUSED_CACHE_MAX = 64


def fused_apply(transform, geom: JpegGeometry, coeffs, qt, *,
                label: str = "stream"):
    """Run ``transform(pixels)`` with the device decode FUSED in: one
    jitted program turns coefficient arrays into features — XLA sees
    dequant, IDCT, upsample, colorspace, and the featurize as a single
    module, so pixels never round-trip through HBM-resident f32 batches
    between two dispatches.

    The fused program is HBM-admitted once per (transform, geometry)
    through ``core.memory.plan_program`` (the fused decode+featurize is
    what actually resides during a device-decode epoch); a denial is
    counted (``device_decode_admission_denied``) and degrades to the
    two-dispatch path — decode, then featurize — whose peak is smaller
    because the coefficient buffers die before the featurize runs."""
    import jax

    try:
        per_transform = _fused_cache.get(transform)
        if per_transform is None:
            while len(_fused_cache) >= _FUSED_CACHE_MAX:
                _fused_cache.pop(next(iter(_fused_cache)))
            per_transform = _fused_cache[transform] = {}
    except TypeError:
        # unhashable transform: fuse without caching (recompiles per
        # chunk — correct, just slower)
        per_transform = {}
    entry = per_transform.get(geom)
    if entry is None:
        fused = jax.jit(
            lambda c, q: transform(_decode_pixels(geom, c, q))
        )
        from ..core import memory as kmem
        from ..core.resilience import counters

        sds = (
            tuple(
                jax.ShapeDtypeStruct(
                    (qt.shape[0],) + s, np.dtype(np.int16)
                )
                for s in geom.coeff_shapes()
            ),
            jax.ShapeDtypeStruct(tuple(qt.shape), np.dtype(np.float32)),
        )
        plan = kmem.plan_program(
            fused, *sds, label=f"device_decode+featurize:{label}"
        )
        if plan.error is not None and not kmem.is_oom_text(plan.error):
            # Only memory pressure degrades to the two-dispatch path; a
            # program the compiler refuses would be refused there too.
            raise RuntimeError(
                f"{label}: fused decode+featurize failed to compile at "
                f"{geom.height}x{geom.width} — {plan.error}"
            )
        admitted = plan.admitted
        if not admitted:
            counters.record(
                "device_decode_admission_denied",
                f"{label}: fused decode+featurize denied at "
                f"{geom.height}x{geom.width} — running unfused",
            )
        entry = (fused, admitted)
        per_transform[geom] = entry
    fused, admitted = entry
    if not admitted:
        return transform(decode_batch(geom, coeffs, qt))
    from ..core import profiler as kprof

    if not kprof.enabled():
        return fused(tuple(coeffs), qt)
    # Device cost attribution (ISSUE 14): the fused decode+featurize
    # dispatch lands in the per-program MFU ledger with a synced wall
    # (cost memoized per (fused jit, geometry)).  Syncing serializes the
    # consumer's double buffer for this chunk — profiling costs
    # pipelining, never correctness (values unchanged; the
    # profiler_crash chaos family pins bit-equality).
    return kprof.attributed_call(
        f"fused_decode:{label}:{geom.height}x{geom.width}",
        geom, fused, tuple(coeffs), qt,
    )
