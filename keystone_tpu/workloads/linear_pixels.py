"""LinearPixels — the simplest image workload: grayscale pixels straight
into a linear solver
(reference src/main/scala/pipelines/images/cifar/LinearPixels.scala:14-55).

Pipeline: CIFAR load -> GrayScaler -> ImageVectorizer -> LinearMapEstimator
-> MaxClassifier -> MulticlassClassifierEvaluator; logs total train/test
accuracy exactly as the reference (:50-51).
"""

from __future__ import annotations

import argparse
import time
from dataclasses import dataclass

import jax.numpy as jnp

from ..core.logging import Logging, configure_logging
from ..core.pipeline import Pipeline
from ..evaluation.multiclass import MulticlassClassifierEvaluator
from ..loaders.cifar import LabeledImageBatch, cifar_loader
from ..ops.images import GrayScaler, ImageVectorizer
from ..ops.util import ClassLabelIndicatorsFromIntLabels, MaxClassifier
from ..parallel.mesh import parse_mesh
from ..solvers.linear import LinearMapEstimator
from ..utils.platform import init_device


@dataclass
class LinearPixelsConfig:
    """Flag-parity with the reference scopt config (:57-62)."""

    train_location: str = ""
    test_location: str = ""
    num_classes: int = 10


class _Log(Logging):
    pass


def run(
    conf: LinearPixelsConfig,
    train: LabeledImageBatch,
    test: LabeledImageBatch,
    mesh=None,
) -> dict:
    configure_logging()
    log = _Log()
    t0 = time.perf_counter()

    featurizer = Pipeline([GrayScaler(), ImageVectorizer()])
    train_features = featurizer(jnp.asarray(train.images))
    labels = ClassLabelIndicatorsFromIntLabels(conf.num_classes)(train.labels)

    model = LinearMapEstimator(mesh=mesh).fit(train_features, labels)
    prediction = featurizer.then(model).then(MaxClassifier())

    n_train, n_test = len(train), len(test)
    train_pred = prediction(jnp.asarray(train.images))[:n_train]
    train_eval = MulticlassClassifierEvaluator(
        train_pred, train.labels, conf.num_classes
    )
    test_pred = prediction(jnp.asarray(test.images))[:n_test]
    test_eval = MulticlassClassifierEvaluator(
        test_pred, test.labels, conf.num_classes
    )

    results = {
        "train_accuracy": train_eval.total_accuracy,
        "test_accuracy": test_eval.total_accuracy,
        "seconds": time.perf_counter() - t0,
    }
    log.log_info("Training accuracy: \n%s", results["train_accuracy"])
    log.log_info("Test accuracy: \n%s", results["test_accuracy"])
    return results


def main(argv=None):
    p = argparse.ArgumentParser("LinearPixels")
    p.add_argument("--trainLocation", required=True)
    p.add_argument("--testLocation", required=True)
    p.add_argument(
        "--mesh",
        default=None,
        help="device mesh, e.g. '8' (data) or '4x2' (data x model)",
    )
    a = p.parse_args(argv)
    configure_logging()
    init_device()
    conf = LinearPixelsConfig(
        train_location=a.trainLocation, test_location=a.testLocation
    )
    train = cifar_loader(conf.train_location)
    test = cifar_loader(conf.test_location)
    return run(conf, train, test, mesh=parse_mesh(a.mesh))


if __name__ == "__main__":
    main()
