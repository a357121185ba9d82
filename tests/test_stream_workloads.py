"""Workload-level streaming-ingest wiring (ISSUE 4 tentpole): the streaming
descriptor/featurize paths of VOCSIFTFisher, ImageNetSiftLcsFV and
RandomPatchCifar must produce features (and downstream predictions)
identical to the eager decode-everything-first paths on the same tar
fixture.

Images here are >= 36 px: the loaders' MIN_DIM rule (reference
ImageUtils.loadImage) rejects smaller ones, so a true-32px CIFAR JPEG tar
would decode to nothing — the streamed CIFAR fixtures use 48 px.
"""

import dataclasses
import io
import tarfile

import jax
import numpy as np
import pytest

from test_fisher_pipelines import (
    _class_image,
    _img_bytes,
    write_imagenet_tar,
)

from keystone_tpu.loaders.image_loaders import (
    _iter_tar_images,
    imagenet_loader,
)
from keystone_tpu.workloads.cifar_random_patch import (
    RandomCifarConfig,
    build_conv_pipeline,
    cifar_tar_label,
    featurize_chunked,
    featurize_stream,
    learn_filters,
)
from keystone_tpu.workloads.imagenet_sift_lcs_fv import (
    ImageNetSiftLcsFVConfig,
    ImageNetStreamSource,
    lcs_descriptor_buckets,
    sift_descriptor_buckets,
)
from keystone_tpu.core.ingest import stream_batches
from keystone_tpu.loaders.cifar import LabeledImageBatch


def _buckets_equal(a: dict, b: dict):
    assert set(a) == set(b)
    for shape in a:
        idx_a, desc_a = a[shape]
        idx_b, desc_b = b[shape]
        np.testing.assert_array_equal(np.asarray(idx_a), np.asarray(idx_b))
        np.testing.assert_array_equal(np.asarray(desc_a), np.asarray(desc_b))


def test_imagenet_streaming_branches_equal_eager(tmp_path, rng):
    labels_path = str(tmp_path / "labels.txt")
    write_imagenet_tar(str(tmp_path), labels_path, rng, classes=(0, 1), per_class=4)
    conf = ImageNetSiftLcsFVConfig(
        desc_dim=8, vocab_size=4, lcs_stride=8, lcs_border=16, lcs_patch=6
    )

    data = imagenet_loader(str(tmp_path), labels_path)
    eager_sift = sift_descriptor_buckets(conf, data.images)
    eager_lcs = lcs_descriptor_buckets(conf, data.images)

    src = ImageNetStreamSource(str(tmp_path), labels_path, batch_size=3)
    stream_sift = sift_descriptor_buckets(conf, src.images)
    # the second branch pass must observe the identical survivor order
    # (record_names asserts it — a drift would zip mismatched features)
    stream_lcs = lcs_descriptor_buckets(conf, src.images)

    _buckets_equal(eager_sift, stream_sift)
    _buckets_equal(eager_lcs, stream_lcs)
    assert len(src) == len(data)
    np.testing.assert_array_equal(src.labels, data.labels)


def _write_cifar_tar(path, n, rng, num_classes=4, size=48):
    labels = rng.integers(0, num_classes, n)
    with tarfile.open(path, "w") as tf:
        for i, c in enumerate(labels):
            data = _img_bytes(_class_image(rng, int(c), size=size))
            info = tarfile.TarInfo(f"{int(c)}/img_{i:04d}.jpg")
            info.size = len(data)
            tf.addfile(info, io.BytesIO(data))
    return labels.astype(np.int32)


def test_cifar_featurize_stream_equals_chunked(tmp_path, rng):
    tar = str(tmp_path / "cifar48.tar")
    labels = _write_cifar_tar(tar, 12, rng)
    decoded = list(_iter_tar_images(tar, num_threads=1))
    images = np.stack([img for _, img in decoded])
    conf = RandomCifarConfig(
        num_filters=4, patch_steps=6, whitener_size=64, featurize_chunk=4
    )
    filters, whitener = learn_filters(conf, images)
    feat_fn = jax.jit(build_conv_pipeline(conf, filters, whitener).__call__)

    eager = np.asarray(featurize_chunked(feat_fn, images, conf.featurize_chunk))
    with stream_batches(tar, conf.featurize_chunk) as st:
        streamed, names = featurize_stream(feat_fn, st, conf.featurize_chunk)

    np.testing.assert_array_equal(streamed, eager)
    assert names == [name for name, _ in decoded]
    np.testing.assert_array_equal(
        np.asarray([cifar_tar_label(n) for n in names], np.int32), labels
    )


def test_cifar_tar_stream_loader_bit_identical_to_eager(tmp_path, rng):
    """Streamed TRAIN path (ISSUE 9 satellite, ROADMAP carry-over): the
    resident train subset decoded through core.ingest — and through the
    snapshot cache on a warm repeat — must equal the eager tar loader
    bit-for-bit: same images, same labels, same (tar member) order."""
    from keystone_tpu.workloads.cifar_random_patch import (
        cifar_tar_loader,
        cifar_tar_stream_loader,
    )
    from keystone_tpu.workloads.fv_common import stream_config_from_flags

    tar = str(tmp_path / "cifar48.tar")
    _write_cifar_tar(tar, 11, rng)  # odd count: a ragged final batch
    eager = cifar_tar_loader(tar)
    streamed = cifar_tar_stream_loader(tar, batch=4)
    np.testing.assert_array_equal(streamed.images, eager.images)
    np.testing.assert_array_equal(streamed.labels, eager.labels)

    # Snapshot-cache path: cold pass materializes, warm pass streams the
    # shards at IO speed — both bit-identical to the eager loader.
    snap = str(tmp_path / "snap")
    cfg = lambda: stream_config_from_flags(snapshot_dir=snap)  # noqa: E731
    cold = cifar_tar_stream_loader(tar, batch=4, config=cfg())
    warm = cifar_tar_stream_loader(tar, batch=4, config=cfg())
    np.testing.assert_array_equal(cold.images, eager.images)
    np.testing.assert_array_equal(warm.images, eager.images)
    np.testing.assert_array_equal(warm.labels, eager.labels)


def test_cifar_run_from_streamed_train_matches_eager(tmp_path, rng):
    """RandomPatchCifar fit from the STREAMED train split: filter learning
    and the solve see the same resident subset, so predictions equal the
    eager-loaded run's bit-for-bit."""
    from keystone_tpu.workloads.cifar_random_patch import (
        cifar_tar_loader,
        cifar_tar_stream_loader,
        run,
    )

    tar = str(tmp_path / "cifar48.tar")
    _write_cifar_tar(tar, 16, rng)
    conf = RandomCifarConfig(
        num_filters=4,
        patch_steps=6,
        lam=10.0,
        whitener_size=64,
        featurize_chunk=8,
        num_classes=4,
    )
    eager_train = cifar_tar_loader(tar)
    streamed_train = cifar_tar_stream_loader(tar, batch=8)
    base = run(conf, eager_train, eager_train)
    res = run(conf, streamed_train, eager_train)
    np.testing.assert_array_equal(
        res["test_predictions"], base["test_predictions"]
    )


@pytest.mark.slow
def test_cifar_run_with_stream_test_tar_matches_eager(tmp_path, rng):
    """Full RandomPatchCifar run with the streamed test path: predictions
    must equal the eager run's bit-for-bit (same model, same features)."""
    from keystone_tpu.workloads.cifar_random_patch import run

    tar = str(tmp_path / "cifar48.tar")
    labels = _write_cifar_tar(tar, 20, rng)
    decoded = list(_iter_tar_images(tar, num_threads=1))
    images = np.stack([img for _, img in decoded])
    train = LabeledImageBatch(images, labels)
    conf = RandomCifarConfig(
        num_filters=4,
        patch_steps=6,
        lam=10.0,
        whitener_size=64,
        featurize_chunk=8,
        num_classes=4,
    )
    base = run(conf, train, train)
    res = run(dataclasses.replace(conf, stream_test_tar=tar), train, train)
    np.testing.assert_array_equal(
        res["test_predictions"], base["test_predictions"]
    )


def test_cifar_stream_featurized_snapshot_roundtrip(tmp_path, rng, monkeypatch):
    """RandomPatchCifar --streamTestTar --snapshotDir under
    KEYSTONE_SNAPSHOT_MODE=featurized: the first run materializes the conv
    FEATURES keyed by the fitted featurizer's digest; a rerun serves them
    from the shards and must score bit-identically.  A different model
    (new filters) must MISS the cache, never replay stale features."""
    from keystone_tpu.core import snapshot as ksnap
    from keystone_tpu.workloads.cifar_random_patch import run

    monkeypatch.setenv("KEYSTONE_SNAPSHOT_MODE", "featurized")
    tar = str(tmp_path / "cifar48.tar")
    labels = _write_cifar_tar(tar, 12, rng)
    decoded = list(_iter_tar_images(tar, num_threads=1))
    images = np.stack([img for _, img in decoded])
    train = LabeledImageBatch(images, labels)
    snap_root = str(tmp_path / "cache")
    conf = RandomCifarConfig(
        num_filters=4,
        patch_steps=6,
        lam=10.0,
        whitener_size=64,
        featurize_chunk=4,
        num_classes=4,
        stream_test_tar=tar,
        snapshot_dir=snap_root,
    )
    cold = run(conf, train, train)
    committed = [
        s for s in ksnap.list_snapshots(snap_root)
        if s.get("valid") and s["mode"] == "featurized"
    ]
    assert len(committed) == 1
    warm = run(conf, train, train)
    np.testing.assert_array_equal(
        warm["test_predictions"], cold["test_predictions"]
    )
    # a refit with different filters keys a NEW snapshot (digest moved)
    refit = run(dataclasses.replace(conf, num_filters=6), train, train)
    assert refit["test_predictions"].shape[0] == len(labels)
    keys = {
        s["key"]
        for s in ksnap.list_snapshots(snap_root)
        if s.get("valid") and s["mode"] == "featurized"
    }
    assert len(keys) == 2
