"""Native entropy-decode backend (ops/native_entropy + the jpeg_device
dispatch): the C hot loop must be INDISTINGUISHABLE from the pure-Python
pass — bit-identical CoeffImages over the golden corpus, identical typed
error classification on damaged scans, identical survivor order through
the device-mode stream — and every way it can be absent (env-gated off,
unbuildable toolchain, mid-call failure) must degrade to the Python pass
counted, bit-equal, never a crash.

Tests that PIN the native backend carry ``@pytest.mark.native_entropy``
and auto-skip where the library cannot build (conftest, like ``dist``);
the degradation tests run everywhere — they are the contract for minimal
hosts.
"""

import numpy as np
import pytest

import faults
from test_jpeg_device import _corpus, _jpeg, _make_tar, _stream

from keystone_tpu.core.resilience import counters
from keystone_tpu.ops import jpeg_device as jd
from keystone_tpu.ops import native_entropy as ne


def _coeff_equal(a, b):
    assert a.geom == b.geom
    assert np.array_equal(a.qt, b.qt)
    assert len(a.coeffs) == len(b.coeffs)
    for ca, cb in zip(a.coeffs, b.coeffs):
        assert ca.dtype == cb.dtype == np.int16
        assert np.array_equal(ca, cb)


# -- bit-identity + error parity (native backend pinned) -----------------------


@pytest.mark.native_entropy
def test_golden_corpus_bit_equality(rng):
    """Every corpus member (4:4:4/4:2:2/4:2:0 x quality, odd dims, gray,
    restart markers) decodes to the SAME CoeffImage — geometry, int16
    coefficient planes, quant tables — through both hot loops."""
    for label, data in _corpus(rng):
        py = jd.entropy_decode(data, backend="python")
        nat = jd.entropy_decode(data, backend="native")
        try:
            _coeff_equal(py, nat)
        except AssertionError as exc:
            raise AssertionError(f"{label}: {exc}") from exc


@pytest.mark.native_entropy
def test_error_classification_parity(rng):
    """Damaged scans classify IDENTICALLY: same exception type, same
    message, at every truncation point and under both fault modes — the
    native loop mirrors the Python loop check-for-check."""
    base = _jpeg(
        rng.integers(0, 256, (48, 48, 3)).astype(np.uint8),
        quality=90, subsampling=2, restart_marker_blocks=2,
    )
    bads = [faults.corrupt_jpeg_entropy(base, m)
            for m in ("truncate", "marker")]
    bads += [base[:cut] for cut in range(len(base) - 40, len(base), 7)]

    def outcome(data, backend):
        try:
            jd.entropy_decode(data, backend=backend)
            return ("ok", "")
        except jd.JpegDecodeUnsupported as exc:
            return ("unsupported", exc.reason)
        except jd.JpegEntropyCorrupt as exc:
            return ("corrupt", str(exc))

    for i, bad in enumerate(bads):
        assert outcome(bad, "python") == outcome(bad, "native"), i


@pytest.mark.native_entropy
def test_native_stream_bit_equal_to_python_stream(rng, tmp_path, monkeypatch):
    """The same mixed tar (good members + one entropy-corrupt) through
    decode_mode="device" with the native backend on vs forced-Python
    (``KEYSTONE_NATIVE_ENTROPY=0``): identical survivor names, BIT-equal
    features, the same counted corrupt skip — and the stats record which
    backend ran."""
    good = [
        (f"{i:02d}.jpg",
         _jpeg(rng.integers(0, 256, (48, 48, 3)).astype(np.uint8),
               quality=90, subsampling=(0, 1, 2)[i % 3]))
        for i in range(7)
    ]
    corrupt = faults.corrupt_jpeg_entropy(good[2][1], "truncate")
    members = good[:3] + [("03_bad.jpg", corrupt)] + good[3:]
    tar = str(tmp_path / "mix.tar")
    _make_tar(tar, members)

    monkeypatch.delenv(ne.NATIVE_ENTROPY_ENV, raising=False)
    nf, nn, ns = _stream(tar, 4, decode_mode="device")
    assert ns.entropy_backend == "native"
    monkeypatch.setenv(ne.NATIVE_ENTROPY_ENV, "0")
    pf, pn, ps = _stream(tar, 4, decode_mode="device")
    assert ps.entropy_backend == "python"

    assert nn == pn
    assert np.array_equal(nf, pf)
    assert ns.entropy_corrupt == ps.entropy_corrupt == 1
    assert ns.entropy_decoded == ps.entropy_decoded == 7


@pytest.mark.native_entropy
def test_thread_and_process_backend_ingest_bit_identity(rng, tmp_path):
    """decode_backend thread vs process with the native pass on: the
    entropy pass always runs on the (GIL-releasing) thread pool, so both
    settings must produce bit-identical device-mode streams."""
    members = [
        (f"{i}.jpg",
         _jpeg(rng.integers(0, 256, (48, 48, 3)).astype(np.uint8),
               quality=90))
        for i in range(6)
    ]
    tar = str(tmp_path / "t.tar")
    _make_tar(tar, members)
    tf, tn, ts = _stream(tar, 3, decode_mode="device",
                         decode_backend="thread")
    pf, pn, ps = _stream(tar, 3, decode_mode="device",
                         decode_backend="process")
    assert tn == pn
    assert np.array_equal(tf, pf)
    assert ts.entropy_backend == ps.entropy_backend == "native"


# -- degradation contract (runs on every host, toolchain or not) ---------------


def test_env_zero_forces_python_pass(rng, monkeypatch):
    """``KEYSTONE_NATIVE_ENTROPY=0`` keeps the native loop out of the
    call path entirely (no build attempt, no library call) and the output
    stays correct."""
    data = _jpeg(
        rng.integers(0, 256, (40, 40, 3)).astype(np.uint8), quality=90
    )
    oracle = jd.entropy_decode(data, backend="python")
    calls = []

    def spy(*a, **kw):
        calls.append(a)
        return False

    monkeypatch.setattr(ne, "decode_scan", spy)
    monkeypatch.setenv(ne.NATIVE_ENTROPY_ENV, "0")
    _coeff_equal(oracle, jd.entropy_decode(data))
    assert calls == []
    assert not ne.available()
    assert jd.entropy_backend() == "python"


def test_forced_native_failure_degrades_per_image_counted(rng, monkeypatch):
    """An UNEXPECTED native failure mid-call (not a typed corrupt error)
    degrades that image to the Python pass — bit-equal output, counted
    ``native_entropy_fallback``, never a crash.  Injected at the
    decode_scan boundary so the test runs with or without a toolchain."""
    data = _jpeg(
        rng.integers(0, 256, (44, 36, 3)).astype(np.uint8), quality=88
    )
    oracle = jd.entropy_decode(data, backend="python")

    def boom(segments, planes, *a, **kw):
        # scribble on the planes first: the dispatch must re-zero them
        # before the Python re-decode or the fallback would be wrong
        for p in planes:
            p[...] = 7
        raise RuntimeError("injected native fault")

    monkeypatch.setattr(ne, "decode_scan", boom)
    monkeypatch.delenv(ne.NATIVE_ENTROPY_ENV, raising=False)
    before = counters.snapshot().get("native_entropy_fallback", 0)
    _coeff_equal(oracle, jd.entropy_decode(data))
    after = counters.snapshot().get("native_entropy_fallback", 0)
    assert after == before + 1


def test_typed_corrupt_error_from_native_is_not_a_fallback(rng, monkeypatch):
    """JpegEntropyCorrupt raised by the native loop IS the classification
    — it must propagate as the counted skip, not trigger a Python
    re-decode (which would double-classify the stream)."""
    data = _jpeg(
        rng.integers(0, 256, (40, 40, 3)).astype(np.uint8), quality=90
    )

    def typed(*a, **kw):
        raise jd.JpegEntropyCorrupt("injected corrupt classification")

    monkeypatch.setattr(ne, "decode_scan", typed)
    monkeypatch.delenv(ne.NATIVE_ENTROPY_ENV, raising=False)
    before = counters.snapshot().get("native_entropy_fallback", 0)
    with pytest.raises(jd.JpegEntropyCorrupt, match="injected corrupt"):
        jd.entropy_decode(data)
    assert counters.snapshot().get("native_entropy_fallback", 0) == before


def test_unbuildable_library_degrades_counted_once(rng):
    """No g++ / failed build: the stream stays bit-equal on the Python
    pass with ``native_entropy_unavailable`` counted ONCE per process
    (not per image), and a PINNED native backend raises instead of
    silently comparing Python against itself."""
    data = _jpeg(
        rng.integers(0, 256, (40, 40, 3)).astype(np.uint8), quality=90
    )
    oracle = jd.entropy_decode(data, backend="python")
    orig_build = ne._build
    ne.reset()
    ne._build = lambda: None
    try:
        before = counters.snapshot().get("native_entropy_unavailable", 0)
        _coeff_equal(oracle, jd.entropy_decode(data))
        _coeff_equal(oracle, jd.entropy_decode(data))
        after = counters.snapshot().get("native_entropy_unavailable", 0)
        assert after == before + 1  # once per process, not per image
        assert jd.entropy_backend() == "python"
        with pytest.raises(RuntimeError, match="native"):
            jd.entropy_decode(data, backend="native")
    finally:
        ne._build = orig_build
        ne.reset()


def test_backend_argument_is_validated(rng):
    data = _jpeg(
        rng.integers(0, 256, (24, 24, 3)).astype(np.uint8), quality=90
    )
    with pytest.raises(ValueError, match="unknown entropy backend"):
        jd.entropy_decode(data, backend="cuda")
