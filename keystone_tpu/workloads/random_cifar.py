"""RandomCifar — random (unwhitened) convolutional filters + linear solve
(reference src/main/scala/pipelines/images/cifar/RandomCifar.scala:17-70).

Like RandomPatchCifar but the filter bank is i.i.d. Gaussian instead of
ZCA-whitened patches, and the solver is a single LinearMapEstimator rather
than the blocked BCD: CIFAR load -> [Convolver(random filters, patch
normalization) -> SymmetricRectifier -> Pooler -> ImageVectorizer ->
StandardScaler] -> LinearMapEstimator(λ) -> MaxClassifier -> evaluator.
"""

from __future__ import annotations

import argparse
import time
from dataclasses import dataclass

import jax
import numpy as np

from ..core.logging import Logging, configure_logging
from ..core.pipeline import Pipeline
from ..core.resilience import assert_all_finite, numerics_guard_enabled
from ..evaluation.multiclass import MulticlassClassifierEvaluator
from ..loaders.cifar import LabeledImageBatch, cifar_loader
from ..ops.images import (
    Convolver,
    ImageVectorizer,
    Pooler,
    SymmetricRectifier,
)
from ..ops.stats import StandardScaler
from ..ops.util import ClassLabelIndicatorsFromIntLabels, MaxClassifier
from ..parallel.mesh import parse_mesh
from ..solvers.linear import LinearMapEstimator
from ..utils.platform import init_device
from .cifar_random_patch import featurize_chunked


@dataclass
class RandomCifarWorkloadConfig:
    """Flag-parity with the reference scopt config (:72-95)."""

    train_location: str = ""
    test_location: str = ""
    num_filters: int = 100
    patch_size: int = 6
    pool_size: int = 14
    pool_stride: int = 13
    alpha: float = 0.25
    lam: float | None = None
    sample_frac: float | None = None
    seed: int = 42
    num_classes: int = 10
    num_channels: int = 3
    featurize_chunk: int = 2048


class _Log(Logging):
    pass


def run(
    conf: RandomCifarWorkloadConfig,
    train: LabeledImageBatch,
    test: LabeledImageBatch,
    mesh=None,
) -> dict:
    configure_logging()
    log = _Log()
    t0 = time.perf_counter()

    if conf.sample_frac is not None:
        rng = np.random.default_rng(conf.seed)
        keep = rng.random(len(train)) < conf.sample_frac
        train = LabeledImageBatch(train.images[keep], train.labels[keep])

    # Random Gaussian filter bank (reference :33: DenseMatrix.rand gaussian).
    key = jax.random.PRNGKey(conf.seed)
    filters = jax.random.normal(
        key,
        (
            conf.num_filters,
            conf.patch_size * conf.patch_size * conf.num_channels,
        ),
    )

    conv_pipe = Pipeline(
        [
            Convolver(
                filters,
                normalize_patches=True,
                img_channels=conf.num_channels,
            ),
            SymmetricRectifier(alpha=conf.alpha),
            Pooler(conf.pool_stride, conf.pool_size, None, "sum"),
            ImageVectorizer(),
        ]
    )
    feat_fn = jax.jit(conv_pipe.__call__)

    train_conv = featurize_chunked(
        feat_fn, train.images, conf.featurize_chunk, mesh=mesh
    )
    scaler = StandardScaler().fit(train_conv)
    train_features = scaler(train_conv)

    labels = ClassLabelIndicatorsFromIntLabels(conf.num_classes)(train.labels)
    model = LinearMapEstimator(lam=conf.lam, mesh=mesh).fit(train_features, labels)
    if numerics_guard_enabled():
        # Typed failure (FloatingPointError) instead of NaN predictions.
        assert_all_finite(model, "random-cifar model")

    def predict(features):
        return MaxClassifier()(model(features))

    train_eval = MulticlassClassifierEvaluator(
        predict(train_features)[: len(train)], train.labels, conf.num_classes
    )
    test_conv = featurize_chunked(
        feat_fn, test.images, conf.featurize_chunk, mesh=mesh
    )
    test_eval = MulticlassClassifierEvaluator(
        predict(scaler(test_conv))[: len(test)], test.labels, conf.num_classes
    )

    results = {
        "train_error": 100.0 * train_eval.total_error,
        "test_error": 100.0 * test_eval.total_error,
        "seconds": time.perf_counter() - t0,
    }
    log.log_info("Training error is: %s", train_eval.total_error)
    log.log_info("Test error is: %s", test_eval.total_error)
    return results


def main(argv=None):
    p = argparse.ArgumentParser("RandomCifar")
    p.add_argument("--trainLocation", required=True)
    p.add_argument("--testLocation", required=True)
    p.add_argument("--numFilters", type=int, default=100)
    p.add_argument("--patchSize", type=int, default=6)
    p.add_argument("--poolSize", type=int, default=14)
    p.add_argument("--poolStride", type=int, default=13)
    p.add_argument("--alpha", type=float, default=0.25)
    p.add_argument("--lambda", dest="lam", type=float, default=None)
    p.add_argument("--sampleFrac", type=float, default=None)
    p.add_argument(
        "--mesh",
        default=None,
        help="device mesh, e.g. '8' (data) or '4x2' (data x model)",
    )
    a = p.parse_args(argv)
    configure_logging()
    init_device()
    conf = RandomCifarWorkloadConfig(
        train_location=a.trainLocation,
        test_location=a.testLocation,
        num_filters=a.numFilters,
        patch_size=a.patchSize,
        pool_size=a.poolSize,
        pool_stride=a.poolStride,
        alpha=a.alpha,
        lam=a.lam,
        sample_frac=a.sampleFrac,
    )
    train = cifar_loader(conf.train_location)
    test = cifar_loader(conf.test_location)
    return run(conf, train, test, mesh=parse_mesh(a.mesh))


if __name__ == "__main__":
    main()
