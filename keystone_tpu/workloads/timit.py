"""TimitPipeline — phone classification with cosine random features and a
multi-epoch block solver
(reference src/main/scala/pipelines/speech/TimitPipeline.scala:20-115).

Per batch b of ``numCosines``: CosineRandomFeatures(440 -> 4096,
Gaussian or Cauchy W) then StandardScaler — the batches are the solver's
feature blocks; BlockLeastSquares runs ``numEpochs`` BCD sweeps over them;
evaluation streams through ``apply_and_evaluate`` exactly as the reference
does (:105-113): one compiled step a block, then the evaluator's round trip.
"""

from __future__ import annotations

import argparse
import time
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from ..core import trace
from ..core.logging import Logging, configure_logging, stage_timer
from ..core.memory import log_fit_report
from ..core.pipeline import Pipeline
from ..evaluation.multiclass import MulticlassClassifierEvaluator
from ..loaders.timit import TIMIT_DIMENSION, TIMIT_NUM_CLASSES, TimitFeaturesData, timit_features_loader
from ..ops.stats import CosineRandomFeatures, StandardScaler
from ..ops.util import ClassLabelIndicatorsFromIntLabels, MaxClassifier
from ..parallel.mesh import mask_pad_rows, padded_shard_rows, parse_mesh
from ..solvers.block import BlockLeastSquaresEstimator
from ..utils.platform import init_device


@dataclass
class TimitConfig:
    """Flag-parity with the reference scopt config (:23-34)."""

    train_data_location: str = ""
    train_labels_location: str = ""
    test_data_location: str = ""
    test_labels_location: str = ""
    num_cosines: int = 50
    gamma: float = 0.05555
    rf_type: str = "gaussian"  # or "cauchy"
    lam: float = 0.0
    num_epochs: int = 5
    num_cosine_features: int = 4096
    seed: int = 123
    num_classes: int = TIMIT_NUM_CLASSES
    dimension: int = TIMIT_DIMENSION


class _Log(Logging):
    pass


class FeaturizerBlock(Pipeline):
    """One block's chain, cosine features then their scaler.  A call runs
    its handful of small programs eagerly; its time is the open stage's
    host section ``dispatch``."""

    def __call__(self, batch):
        with trace.host("dispatch", "featurizer_block"):
            return super().__call__(batch)


jax.tree_util.register_pytree_node(
    FeaturizerBlock,
    lambda p: (p.nodes, None),
    lambda _, nodes: FeaturizerBlock(list(nodes)),
)


def build_batch_featurizers(conf: TimitConfig, train_data, nvalid=None) -> list:
    """numCosines [CosineRandomFeatures -> StandardScaler] chains (:65-84).

    ``nvalid``: true row count when ``train_data`` carries zero pad rows —
    cos maps zero rows to nonzero ``cos(b)``, so pad rows are masked back to
    zero before the scaler's moment sums.
    """
    key = jax.random.PRNGKey(conf.seed)
    featurizers = []
    for _ in range(conf.num_cosines):
        with trace.host("dispatch", "fit_featurizer_block"):
            key, sub = jax.random.split(key)
            rf = CosineRandomFeatures.create(
                conf.dimension,
                conf.num_cosine_features,
                conf.gamma,
                sub,
                w_dist=conf.rf_type,
            )
            feats = mask_pad_rows(rf(train_data), nvalid)
            scaler = StandardScaler().fit(feats, nvalid=nvalid)
            featurizers.append(FeaturizerBlock([rf, scaler]))
    return featurizers


def run(conf: TimitConfig, data: TimitFeaturesData, mesh=None) -> dict:
    """With ``mesh``, features are row-sharded over the data axis and the
    multi-epoch BCD solver runs distributed — the reference runs this over
    partitioned RDDs end to end (TimitPipeline.scala:58-113)."""
    configure_logging()
    log = _Log()
    t0 = time.perf_counter()
    results: dict = {}
    n_test = len(data.test.labels)

    def evaluator(pred):
        predicted = MaxClassifier()(pred[:n_test])
        ev = MulticlassClassifierEvaluator(
            predicted, data.test.labels, conf.num_classes
        )
        results["test_error"] = 100.0 * ev.total_error
        log.log_info("TEST Error is %s%%", results["test_error"])

    # one root span a fit; the three stages tile it but for glue
    with trace.span("fit", cat="fit", rows=len(data.train.labels)):
        if mesh is not None:
            train_data, nvalid = padded_shard_rows(data.train.data, mesh)
            test_data, _ = padded_shard_rows(data.test.data, mesh)
        else:
            train_data, nvalid = jnp.asarray(data.train.data), None
            test_data = jnp.asarray(data.test.data)

        with stage_timer("featurize"):
            batch_featurizer = build_batch_featurizers(conf, train_data, nvalid)
            training_batches = [
                mask_pad_rows(f(train_data), nvalid) for f in batch_featurizer
            ]
            labels = ClassLabelIndicatorsFromIntLabels(conf.num_classes)(
                data.train.labels
            )
            test_batches = [f(test_data) for f in batch_featurizer]

        with stage_timer("solve"):
            solver = BlockLeastSquaresEstimator(
                conf.num_cosine_features, conf.num_epochs, conf.lam, mesh=mesh
            )
            model = solver.fit(training_batches, labels, nvalid=nvalid)
            log_fit_report(solver, label="timit cosine solve")

        with stage_timer("eval"):
            model.apply_and_evaluate(test_batches, evaluator)
    results["seconds"] = time.perf_counter() - t0
    return results


def main(argv=None):
    p = argparse.ArgumentParser("Timit")
    p.add_argument("--trainDataLocation", required=True)
    p.add_argument("--trainLabelsLocation", required=True)
    p.add_argument("--testDataLocation", required=True)
    p.add_argument("--testLabelsLocation", required=True)
    p.add_argument("--numCosines", type=int, default=50)
    p.add_argument("--numEpochs", type=int, default=5)
    p.add_argument("--gamma", type=float, default=0.05555)
    p.add_argument("--lambda", dest="lam", type=float, default=0.0)
    p.add_argument("--rfType", choices=["gaussian", "cauchy"], default="gaussian")
    p.add_argument(
        "--mesh",
        default=None,
        help="device mesh, e.g. '8' (data) or '4x2' (data x model)",
    )
    a = p.parse_args(argv)
    configure_logging()
    init_device()
    conf = TimitConfig(
        train_data_location=a.trainDataLocation,
        train_labels_location=a.trainLabelsLocation,
        test_data_location=a.testDataLocation,
        test_labels_location=a.testLabelsLocation,
        num_cosines=a.numCosines,
        gamma=a.gamma,
        rf_type=a.rfType,
        lam=a.lam,
        num_epochs=a.numEpochs,
    )
    data = timit_features_loader(
        conf.train_data_location,
        conf.train_labels_location,
        conf.test_data_location,
        conf.test_labels_location,
    )
    return run(conf, data, mesh=parse_mesh(a.mesh))


if __name__ == "__main__":
    main()
