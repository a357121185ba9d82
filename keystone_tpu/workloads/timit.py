"""TimitPipeline — phone classification with cosine random features and a
multi-epoch block solver
(reference src/main/scala/pipelines/speech/TimitPipeline.scala:20-115).

Per batch b of ``numCosines``: CosineRandomFeatures(440 -> 4096,
Gaussian or Cauchy W) then StandardScaler — the batches are the solver's
feature blocks; BlockLeastSquares runs ``numEpochs`` BCD sweeps over them;
evaluation streams through ``apply_and_evaluate`` exactly as the reference
does (:105-113): one compiled step a block, then the evaluator's round trip.

The reference's ``batchFeaturizer`` is a ``Seq`` of lazy chains, and so is
this one: the solver is handed a ``solvers.block.BlockSource`` (the rows and
the stacked chains), the scalers are fitted from one moments pass that keeps
no block, and the test split's blocks are made as the evaluation loop
reaches them.  At the documented 50 blocks the 204,800-column design matrix
never exists; where it fits the device the solver holds it, by its own rule.
"""

from __future__ import annotations

import argparse
import functools
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
from ..solvers.block import BlockLeastSquaresEstimator, BlockSource, block_moments
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


@functools.partial(
    jax.jit, static_argnames=("num_blocks", "dimension", "width", "w_dist")
)
def _draw_cosine_blocks(key, gamma, *, num_blocks, dimension, width, w_dist):
    """``num_blocks`` CosineRandomFeatures drawn by one program, stacked
    along a leading block axis: ``key, sub = split(key)`` a block, then
    ``CosineRandomFeatures.create(..., sub)``, the per-block loop's recipe."""

    def one(key, _):
        key, sub = jax.random.split(key)
        return key, CosineRandomFeatures.create(
            dimension, width, gamma, sub, w_dist=w_dist
        )

    return jax.lax.scan(one, key, None, length=num_blocks)[1]


def fit_block_featurizers(conf: TimitConfig, train_data, nvalid=None):
    """The numCosines [CosineRandomFeatures -> StandardScaler] chains
    (:65-84) as ONE ``FeaturizerBlock`` whose leaves carry a leading block
    axis, what ``BlockSource`` takes.  The scalers' means and deviations
    come from one moments pass over the made cosine blocks
    (``solvers.block.block_moments``): no block is held.

    ``nvalid``: true row count when ``train_data`` carries zero pad rows —
    cos maps zero rows to nonzero ``cos(b)``, so the pass leaves them out."""
    with trace.host("dispatch", "draw_featurizers"):
        cosines = _draw_cosine_blocks(
            jax.random.PRNGKey(conf.seed), conf.gamma,
            num_blocks=conf.num_cosines, dimension=conf.dimension,
            width=conf.num_cosine_features, w_dist=conf.rf_type,
        )
    with trace.host("dispatch", "block_moments"):
        n = int(train_data.shape[0]) if nvalid is None else nvalid
        s, sq = block_moments(BlockSource(train_data, cosines), n)
        scalers = StandardScaler().from_moments(n, s, sq)
    return FeaturizerBlock([cosines, scalers])


def build_batch_featurizers(conf: TimitConfig, train_data, nvalid=None) -> list:
    """:func:`fit_block_featurizers`' chains as a list, a block each (what
    a caller that holds the blocks applies one by one)."""
    stacked = fit_block_featurizers(conf, train_data, nvalid)
    with trace.host("dispatch", "unstack_featurizers"):
        return [
            jax.tree.map(lambda a: a[i], stacked) for i in range(conf.num_cosines)
        ]


def run(conf: TimitConfig, data: TimitFeaturesData, mesh=None) -> dict:
    """With ``mesh``, features are row-sharded over the data axis and the
    multi-epoch BCD solver runs distributed — the reference runs this over
    partitioned RDDs end to end (TimitPipeline.scala:58-113); the blocks are
    then held, a block source having no mesh form yet.

    Hands back, beside ``test_error`` and ``seconds``, what a caller needs
    to check the fit: ``model``, ``featurizers`` (the stacked chains; under
    a mesh the list of them), ``test_scores`` / ``test_predictions`` as the
    evaluator last saw them, and ``fit_report``."""
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
        results.update(
            test_error=100.0 * ev.total_error,
            test_scores=pred,
            test_predictions=predicted,
        )
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
            labels = ClassLabelIndicatorsFromIntLabels(conf.num_classes)(
                data.train.labels
            )
            if mesh is None:
                featurizers = fit_block_featurizers(conf, train_data, nvalid)
                # the chains end in their scalers: a made block's columns
                # have mean zero, and the solver need not take them again
                training_batches = BlockSource(
                    train_data, featurizers,
                    means=jnp.zeros(featurizers.nodes[-1].mean.shape),
                )
                test_batches = BlockSource(test_data, featurizers)
            else:
                featurizers = build_batch_featurizers(conf, train_data, nvalid)
                training_batches = [
                    mask_pad_rows(f(train_data), nvalid) for f in featurizers
                ]
                test_batches = [f(test_data) for f in featurizers]

        with stage_timer("solve"):
            solver = BlockLeastSquaresEstimator(
                conf.num_cosine_features, conf.num_epochs, conf.lam, mesh=mesh
            )
            model = solver.fit(training_batches, labels, nvalid=nvalid)
            log_fit_report(solver, label="timit cosine solve")

        with stage_timer("eval"):
            model.apply_and_evaluate(test_batches, evaluator)
    results.update(
        seconds=time.perf_counter() - t0,
        model=model,
        featurizers=featurizers,
        fit_report=solver.last_fit_report,
    )
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
