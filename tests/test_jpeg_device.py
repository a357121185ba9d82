"""Device-resident JPEG decode (ops.jpeg_device + core.ingest
decode_mode="device" + the core.snapshot device-format tier, ISSUE 13).

Golden-parity corpus: seeded baseline JPEGs covering 4:4:4 / 4:2:2 /
4:2:0 subsampling, restart markers, odd dimensions, grayscale, and mixed
qualities — the device decode (host entropy pass -> batched dequant +
IDCT + fancy chroma upsample + YCbCr->BGR on the accelerator) must match
the host decoder (native libjpeg, PIL fallback) within the IDCT-rounding
tolerance the snapshot cache already keys decoders by.  The Pallas IDCT
kernel must be BIT-equal to the jnp einsum path in interpret mode.
"""

import io
import os
import tarfile

import numpy as np
import pytest

import faults

from keystone_tpu.core import ingest
from keystone_tpu.core import snapshot as ksnap
from keystone_tpu.core import trace
from keystone_tpu.core.resilience import counters
from keystone_tpu.loaders.image_loaders import decode_image
from keystone_tpu.ops import jpeg_device as jd
from keystone_tpu.workloads.fv_common import scatter_features_streaming


def _jpeg(arr, **kw) -> bytes:
    from PIL import Image

    buf = io.BytesIO()
    Image.fromarray(arr).save(buf, format="JPEG", **kw)
    return buf.getvalue()


def _device_decode_one(data: bytes) -> np.ndarray:
    ci = jd.entropy_decode(data)
    coeffs, qt = jd.stack_coeff_images([ci])
    return np.asarray(jd.decode_batch(ci.geom, coeffs, qt))[0]


def _corpus(rng):
    """(label, jpeg bytes) over the claimed baseline subset.  Noise images
    are the adversarial case (every AC coefficient populated); the smooth
    gradient catches DC/upsample bugs noise would mask."""
    noise = rng.integers(0, 256, (64, 64, 3)).astype(np.uint8)
    yy, xx = np.mgrid[0:64, 0:64]
    smooth = (
        np.stack([(np.sin(yy / 9) + np.cos(xx / 7)) * 60 + 128] * 3, -1)
        .clip(0, 255)
        .astype(np.uint8)
    )
    odd = rng.integers(0, 256, (47, 53, 3)).astype(np.uint8)
    gray = rng.integers(0, 256, (40, 44)).astype(np.uint8)
    cases = []
    for label, arr in (("noise", noise), ("smooth", smooth)):
        for ss in (0, 1, 2):  # 4:4:4, 4:2:2, 4:2:0
            for q in (85, 90, 95):
                cases.append(
                    (f"{label}/ss{ss}/q{q}",
                     _jpeg(arr, quality=q, subsampling=ss))
                )
    for ss in (0, 1, 2):
        cases.append((f"odd/ss{ss}", _jpeg(odd, quality=90, subsampling=ss)))
    cases.append(("gray", _jpeg(gray, quality=90)))
    cases.append(
        ("restart",
         _jpeg(noise, quality=90, subsampling=2, restart_marker_blocks=2))
    )
    return cases


def test_zigzag_is_a_permutation():
    assert sorted(jd.ZIGZAG.tolist()) == list(range(64))


def test_golden_parity_corpus(rng):
    """Device decode vs the host decoder (whatever decode_image resolves —
    native libjpeg or PIL) within GOLDEN_MAX_ABS / GOLDEN_MEAN_ABS per
    corpus member, same shapes, BGR channel order, integral f32."""
    for label, data in _corpus(rng):
        dev = _device_decode_one(data)
        ref = decode_image(data)
        assert ref is not None, label
        assert dev.shape == ref.shape, label
        assert dev.dtype == np.float32
        assert np.array_equal(dev, np.round(dev)), f"{label}: non-integral"
        diff = np.abs(dev - ref)
        assert diff.max() <= jd.GOLDEN_MAX_ABS, (
            f"{label}: max abs {diff.max()} > {jd.GOLDEN_MAX_ABS}"
        )
        assert diff.mean() <= jd.GOLDEN_MEAN_ABS, (
            f"{label}: mean abs {diff.mean()} > {jd.GOLDEN_MEAN_ABS}"
        )


def test_mixed_quality_batch_uses_per_image_quant_tables(rng):
    """Same geometry, different quality: one batched program, per-image
    dequant tables — each image must still match ITS host decode."""
    arr = rng.integers(0, 256, (48, 48, 3)).astype(np.uint8)
    datas = [
        _jpeg(arr, quality=q, subsampling=2) for q in (85, 90, 95)
    ]
    cis = [jd.entropy_decode(d) for d in datas]
    assert len({ci.geom for ci in cis}) == 1  # one geometry bucket
    coeffs, qt = jd.stack_coeff_images(cis)
    batch = np.asarray(jd.decode_batch(cis[0].geom, coeffs, qt))
    for i, data in enumerate(datas):
        diff = np.abs(batch[i] - decode_image(data))
        assert diff.max() <= jd.GOLDEN_MAX_ABS


def test_pallas_idct_matches_jnp_in_interpret_mode(rng):
    """The lane-dense kron kernel against the separable einsum reference:
    two association orders of the same 64-term sums, so IDCT_ATOL, not
    bit-equality."""
    import jax.numpy as jnp

    blocks = jnp.asarray(
        rng.normal(size=(37, 8, 8)).astype(np.float32) * 50.0
    )
    a = np.asarray(jd.idct_blocks_jnp(blocks))
    b = np.asarray(jd.idct_blocks_pallas(blocks, interpret=True))
    np.testing.assert_allclose(b, a, rtol=0, atol=jd.IDCT_ATOL)
    # leading batch dims, an odd block count and several grid steps
    # survive the pack/pad round trip
    blocks4 = jnp.asarray(
        rng.normal(size=(3, 3, 5, 8, 8)).astype(np.float32) * 500.0
    )
    a4 = np.asarray(jd.idct_blocks_jnp(blocks4))
    b4 = np.asarray(
        jd.idct_blocks_pallas(blocks4, rows_per_step=8, interpret=True)
    )
    np.testing.assert_allclose(b4, a4, rtol=0, atol=jd.IDCT_ATOL)


def test_idct_env_chooser(rng, monkeypatch):
    """``KEYSTONE_PALLAS_IDCT=1`` selects the kernel and never the
    interpreter: off-TPU it is the caller who asks for interpret mode."""
    import jax.numpy as jnp
    from jax.experimental.pallas import tpu as pltpu

    blocks = jnp.asarray(rng.normal(size=(9, 8, 8)).astype(np.float32))
    monkeypatch.setenv(jd.PALLAS_IDCT_ENV, "1")
    with pytest.raises(ValueError, match="interpret"):
        jd.idct_blocks(blocks)
    with pltpu.force_tpu_interpret_mode():
        via_pallas = np.asarray(jd.idct_blocks(blocks))
    monkeypatch.setenv(jd.PALLAS_IDCT_ENV, "0")
    via_jnp = np.asarray(jd.idct_blocks(blocks))
    np.testing.assert_allclose(via_pallas, via_jnp, rtol=0, atol=jd.IDCT_ATOL)


def test_unsupported_reasons_are_typed(rng):
    noise = rng.integers(0, 256, (48, 48, 3)).astype(np.uint8)
    base = _jpeg(noise, quality=90, subsampling=0)

    with pytest.raises(jd.JpegDecodeUnsupported) as ei:
        jd.entropy_decode(_jpeg(noise, quality=90, progressive=True))
    assert ei.value.reason == "progressive"

    from PIL import Image

    buf = io.BytesIO()
    Image.fromarray(noise).convert("CMYK").save(buf, "JPEG", quality=90)
    with pytest.raises(jd.JpegDecodeUnsupported) as ei:
        jd.entropy_decode(buf.getvalue())
    assert ei.value.reason == "cmyk"

    # arithmetic coding: SOF0 marker patched to SOF9 (header-level reject)
    with pytest.raises(jd.JpegDecodeUnsupported) as ei:
        jd.entropy_decode(base.replace(b"\xff\xc0", b"\xff\xc9", 1))
    assert ei.value.reason == "arithmetic"

    # exotic sampling: Y factors patched to 4x1 in the SOF segment
    sof = base.find(b"\xff\xc0")
    comp0_hv = sof + 2 + 2 + 6 + 1  # marker+len | P,H,W,Nf | C1 id
    assert base[comp0_hv] == 0x11  # 4:4:4 -> (1,1)
    patched = base[:comp0_hv] + b"\x41" + base[comp0_hv + 1 :]
    with pytest.raises(jd.JpegDecodeUnsupported) as ei:
        jd.entropy_decode(patched)
    assert ei.value.reason == "subsampling"

    with pytest.raises(jd.JpegDecodeUnsupported) as ei:
        jd.entropy_decode(b"\x89PNG not a jpeg at all")
    assert ei.value.reason == "not_jpeg"

    # Adobe APP14 transform=0: three components stored RGB — the YCbCr
    # matrix would silently hue-shift them, so it must route to fallback
    app14 = b"\xff\xee\x00\x0eAdobe\x00\x64\x00\x00\x00\x00\x00"
    with pytest.raises(jd.JpegDecodeUnsupported) as ei:
        jd.entropy_decode(base[:2] + app14 + base[2:])
    assert ei.value.reason == "rgb_colorspace"


def test_entropy_corruption_is_typed(rng):
    data = _jpeg(
        rng.integers(0, 256, (48, 48, 3)).astype(np.uint8), quality=90
    )
    for mode in ("truncate", "marker"):
        bad = faults.corrupt_jpeg_entropy(data, mode)
        with pytest.raises(jd.JpegEntropyCorrupt):
            jd.entropy_decode(bad)


# -- the ingest decode_mode="device" path --------------------------------------


def _make_tar(path, members):
    with tarfile.open(path, "w") as tf:
        for name, data in members:
            info = tarfile.TarInfo(name)
            info.size = len(data)
            tf.addfile(info, io.BytesIO(data))


def _feat():
    import jax
    import jax.numpy as jnp

    return jax.jit(
        lambda x: jnp.stack(
            [jnp.mean(x, axis=(1, 2, 3)), jnp.max(x, axis=(1, 2, 3))],
            axis=1,
        )
    )


def _stream(tar_path, batch, **cfg_kw):
    cfg_kw.setdefault("snapshot_dir", "")
    cfg = ingest.StreamConfig.from_env(**cfg_kw)
    with ingest.stream_batches(tar_path, batch, config=cfg) as st:
        feats, names = scatter_features_streaming(st, _feat(), 2)
    assert st.join(10.0), "ingest threads leaked"
    return feats, names, st.stats


def test_device_stream_matches_host_stream(rng, tmp_path):
    """Same tar through decode_mode host and device: identical survivor
    order; per-image pixels within golden tolerance (collected through
    ``dev()``), coefficient chunks visible in the stats."""
    members = [
        (f"{i}.jpg",
         _jpeg(rng.integers(0, 256, (48, 48, 3)).astype(np.uint8),
               quality=90, subsampling=(0, 1, 2)[i % 3]))
        for i in range(9)
    ]
    tar = str(tmp_path / "t.tar")
    _make_tar(tar, members)
    df, dn, ds = _stream(tar, 4, decode_mode="device")
    hf, hn, hs = _stream(tar, 4, decode_mode="host")
    assert dn == hn
    assert ds.entropy_decoded == 9 and ds.device_fallbacks == 0
    assert ds.coeff_bytes > 0
    # features within decode tolerance of the host path (means over
    # pixels in [0,255]: a loose 1.0 bound still catches wrong images)
    assert np.abs(df - hf).max() <= 1.0


def test_mixed_tar_fallbacks_counted_and_bit_correct(rng, tmp_path):
    """A mixed tar (baseline + progressive + PNG + entropy-corrupt):
    fallback members decode BIT-identically to the host path (they ARE
    host-decoded), each fallback is counted per reason, the corrupt scan
    is a typed counted skip, and the survivor order matches the host
    stream's."""
    good = [
        _jpeg(rng.integers(0, 256, (48, 48, 3)).astype(np.uint8),
              quality=90)
        for _ in range(5)
    ]
    prog = _jpeg(
        rng.integers(0, 256, (48, 48, 3)).astype(np.uint8),
        quality=90, progressive=True,
    )
    from PIL import Image

    png_buf = io.BytesIO()
    Image.fromarray(
        rng.integers(0, 256, (48, 48, 3)).astype(np.uint8)
    ).save(png_buf, "PNG")
    corrupt = faults.corrupt_jpeg_entropy(good[0], "truncate")
    members = [
        ("00.jpg", good[0]),
        ("01_prog.jpg", prog),
        ("02.jpg", good[1]),
        ("03_corrupt.jpg", corrupt),
        ("04.png", png_buf.getvalue()),
        ("05.jpg", good[2]),
        ("06.jpg", good[3]),
        ("07.jpg", good[4]),
    ]
    tar = str(tmp_path / "mixed.tar")
    _make_tar(tar, members)
    before = counters.snapshot()
    df, dn, ds = _stream(tar, 4, decode_mode="device")
    delta = {
        k: v - before.get(k, 0) for k, v in counters.snapshot().items()
    }
    assert delta.get("device_decode_fallback", 0) == 2
    assert delta.get("device_decode_fallback_progressive", 0) == 1
    assert delta.get("device_decode_fallback_not_jpeg", 0) == 1
    assert delta.get("jpeg_corrupt_entropy", 0) == 1
    assert ds.device_fallbacks == 2 and ds.entropy_corrupt == 1
    # host oracle over the SURVIVORS only: libjpeg tolerates a truncated
    # scan (pads missing MCUs and warns) where the device path's contract
    # is typed-or-correct — so the corrupt member is excluded from the
    # oracle tar rather than compared against libjpeg's grey fill.
    tar_ok = str(tmp_path / "mixed_ok.tar")
    _make_tar(tar_ok, [m for m in members if m[0] != "03_corrupt.jpg"])
    hf, hn, hs = _stream(tar_ok, 4, decode_mode="host")
    assert dn == hn  # survivor order preserved across the modes
    # the fallback members' feature rows are bit-equal (host decode on
    # both sides); device-decoded members within tolerance
    fallback_rows = [dn.index("01_prog.jpg"), dn.index("04.png")]
    for r in fallback_rows:
        assert np.array_equal(df[r], hf[r])
    assert np.abs(df - hf).max() <= 1.0


def test_decoded_snapshot_disabled_under_device_decode(rng, tmp_path):
    """decode_mode=device + snapshot_mode=decoded is a contradiction
    (host-cached pixels differ within IDCT rounding): the cache must be
    disabled COUNTED, never silently served or silently inert."""
    tar = str(tmp_path / "t.tar")
    _make_tar(
        tar,
        [("0.jpg",
          _jpeg(rng.integers(0, 256, (48, 48, 3)).astype(np.uint8)))],
    )
    before = counters.get("snapshot_mode_unsupported")
    _f, _n, stats = _stream(
        tar, 4, decode_mode="device",
        snapshot_dir=str(tmp_path / "snap"), snapshot_mode="decoded",
    )
    assert counters.get("snapshot_mode_unsupported") - before == 1
    assert stats.snapshot_chunks_written == 0
    assert not list(ksnap.list_snapshots(str(tmp_path / "snap")))


# -- the device-format snapshot tier -------------------------------------------


def test_device_snapshot_warm_epoch_is_pure_dma(rng, tmp_path):
    """Cold pass: host decode + device-format tee (padded, dtype-final,
    uncompressed shards).  Warm pass: BIT-equal features with ZERO host
    decode/transform — no entropy decode, no fallback, no pixel decode;
    shard bytes flow straight to device_put (dma gauge > 0)."""
    members = [
        (f"{i}.jpg",
         _jpeg(rng.integers(0, 256, (48, 48, 3)).astype(np.uint8),
               quality=90))
        for i in range(10)
    ]
    tar = str(tmp_path / "t.tar")
    _make_tar(tar, members)
    snap_root = str(tmp_path / "snap")

    cf, cn, cs = _stream(
        tar, 4, snapshot_dir=snap_root, snapshot_mode="device"
    )
    assert cs.snapshot_chunks_written == 3
    [snap] = [s for s in ksnap.list_snapshots(snap_root) if s["valid"]]
    assert snap["mode"] == "device" and snap["images"] == 10

    # shards: f32 dtype-final, batch dim padded (8-row quantum capped at
    # the stream batch size),
    # uncompressed, valid count recorded
    import glob

    shards = sorted(
        glob.glob(os.path.join(snap_root, snap["dir"], "chunk_*.npz"))
    )
    with np.load(shards[-1]) as zf:
        assert zf["payload"].dtype == np.float32
        assert zf["payload"].shape[0] == 4  # padded (10 = 4+4+2)
        assert int(zf["valid"]) == 2
        assert "payload_cast" not in zf.files  # never compacted

    wf, wn, ws = _stream(
        tar, 4, snapshot_dir=snap_root, snapshot_mode="device"
    )
    assert np.array_equal(cf, wf) and cn == wn
    assert ws.snapshot_chunks_read == 3
    assert ws.snapshot_dma_bytes > 0
    # the acceptance bar: zero host-side decode/transform on the warm
    # epoch — entropy gauge and fallback/decode counters untouched
    assert ws.entropy_decoded == 0
    assert ws.device_fallbacks == 0
    assert ws.coeff_bytes == 0
    gauges = trace.metrics.snapshot().get("gauges", {})
    assert gauges.get("ingest_entropy_decoded", 0) == 0
    assert gauges.get("ingest_snapshot_dma_bytes", 0) > 0


def test_device_snapshot_corrupt_shard_falls_back_counted(rng, tmp_path):
    """A bit-flipped device-format shard mid-read: counted
    ``snapshot_fallback`` to live (host) decode, features bit-equal to
    the cold pass, snapshot self-healed."""
    import glob

    members = [
        (f"{i}.jpg",
         _jpeg(rng.integers(0, 256, (48, 48, 3)).astype(np.uint8)))
        for i in range(8)
    ]
    tar = str(tmp_path / "t.tar")
    _make_tar(tar, members)
    snap_root = str(tmp_path / "snap")
    cf, cn, _cs = _stream(
        tar, 4, snapshot_dir=snap_root, snapshot_mode="device"
    )
    [snap] = [s for s in ksnap.list_snapshots(snap_root) if s["valid"]]
    target = sorted(
        glob.glob(os.path.join(snap_root, snap["dir"], "chunk_*.npz"))
    )[1]
    blob = bytearray(open(target, "rb").read())
    blob[len(blob) // 2] ^= 0xFF
    open(target, "wb").write(bytes(blob))

    before = counters.get("snapshot_fallback")
    wf, wn, _ws = _stream(
        tar, 4, snapshot_dir=snap_root, snapshot_mode="device"
    )
    assert counters.get("snapshot_fallback") - before == 1
    assert np.array_equal(cf, wf) and cn == wn


def test_fused_admission_denied_degrades_counted(rng, tmp_path, monkeypatch):
    """An impossible HBM budget denies the fused decode+featurize program:
    counted ``device_decode_admission_denied``, the stream still completes
    (unfused two-dispatch path) with correct output."""
    members = [
        (f"{i}.jpg",
         _jpeg(rng.integers(0, 256, (50, 50, 3)).astype(np.uint8)))
        for i in range(4)
    ]
    tar = str(tmp_path / "t.tar")
    _make_tar(tar, members)
    hf, hn, _hs = _stream(tar, 4, decode_mode="host")
    monkeypatch.setenv("KEYSTONE_HBM_BUDGET", "1")
    before = counters.get("device_decode_admission_denied")
    df, dn, _ds = _stream(tar, 4, decode_mode="device")
    assert counters.get("device_decode_admission_denied") - before >= 1
    assert dn == hn
    assert np.abs(df - hf).max() <= 1.0


def test_fused_compile_failure_propagates(rng, monkeypatch):
    """A fused program the compiler refuses for any reason but memory is
    an error, not a quiet switch to the unfused path."""
    from keystone_tpu.core import memory as kmem

    ci = jd.entropy_decode(
        _jpeg(rng.integers(0, 256, (48, 48, 3)).astype(np.uint8))
    )
    coeffs, qt = jd.stack_coeff_images([ci])
    monkeypatch.setattr(
        kmem, "plan_program",
        lambda *a, **k: kmem.MemoryPlan(
            "x", admitted=False, reason="lower/compile failed",
            error="MosaicError: unsupported shape cast",
        ),
    )
    with pytest.raises(RuntimeError, match="unsupported shape cast"):
        jd.fused_apply(lambda px: px.sum(axis=(1, 2, 3)), ci.geom, coeffs, qt)


def test_cifar_train_stream_loader_pins_host_decode(rng, tmp_path):
    """An env-seeded KEYSTONE_DEVICE_DECODE=1 must not crash (or change)
    the streamed TRAIN loader: its contract is host-resident pixels
    bit-identical to the eager loader, so device decode is ignored
    COUNTED (``device_decode_unsupported``)."""
    from keystone_tpu.workloads.cifar_random_patch import (
        cifar_tar_loader,
        cifar_tar_stream_loader,
    )

    members = [
        (f"{i % 4}/img_{i:03d}.jpg",
         _jpeg(rng.integers(0, 256, (48, 48, 3)).astype(np.uint8)))
        for i in range(8)
    ]
    tar = str(tmp_path / "train.tar")
    _make_tar(tar, members)
    eager = cifar_tar_loader(tar)
    before = counters.get("device_decode_unsupported")
    cfg = ingest.StreamConfig.from_env(
        decode_mode="device", snapshot_dir=""
    )
    streamed = cifar_tar_stream_loader(tar, batch=4, config=cfg)
    assert counters.get("device_decode_unsupported") - before == 1
    np.testing.assert_array_equal(streamed.images, eager.images)
    np.testing.assert_array_equal(streamed.labels, eager.labels)


def test_featurized_snapshot_key_folds_decode_mode(rng, tmp_path, monkeypatch):
    """Features computed from device-decoded pixels differ (IDCT rounding)
    from host-decoded ones — a host-decode run must MISS a featurized
    snapshot written under device decode, never silently replay it."""
    import dataclasses as _dc

    from keystone_tpu.loaders.cifar import LabeledImageBatch
    from keystone_tpu.workloads.cifar_random_patch import (
        RandomCifarConfig,
        run,
    )

    members = []
    labels = []
    for i in range(12):
        c = i % 4
        arr = np.clip(
            rng.uniform(40, 215, 3)[None, None, :]
            + rng.normal(0, 25, (48, 48, 3)),
            0, 255,
        ).astype(np.uint8)
        members.append((f"{c}/img_{i:03d}.jpg", _jpeg(arr, quality=90)))
        labels.append(c)
    tar = str(tmp_path / "t.tar")
    _make_tar(tar, members)
    from keystone_tpu.loaders.image_loaders import _iter_tar_images

    decoded = list(_iter_tar_images(tar, num_threads=1))
    train = LabeledImageBatch(
        np.stack([img for _, img in decoded]),
        np.asarray(labels, np.int32),
    )
    snap_dir = str(tmp_path / "snap")
    monkeypatch.setenv("KEYSTONE_SNAPSHOT_MODE", "featurized")
    conf = RandomCifarConfig(
        num_filters=4, patch_steps=6, lam=10.0, whitener_size=64,
        featurize_chunk=4, num_classes=4, stream_test_tar=tar,
        snapshot_dir=snap_dir,
    )
    run(_dc.replace(conf, device_decode=True), train, train)
    [dev_snap] = [
        s for s in ksnap.list_snapshots(snap_dir) if s["valid"]
    ]
    before = counters.get("snapshot_stale")
    run(conf, train, train)  # host decode: must MISS (stale), not replay
    assert counters.get("snapshot_stale") - before >= 1
    snaps = [s for s in ksnap.list_snapshots(snap_dir) if s["valid"]]
    assert len(snaps) == 2  # a second, differently-keyed snapshot
    assert {s["dir"] for s in snaps} > {dev_snap["dir"]}
