"""MnistRandomFFT — the first end-to-end workload
(reference src/main/scala/pipelines/images/mnist/MnistRandomFFT.scala:17-127).

Pipeline: CSV load -> per-FFT-batch [RandomSign -> PaddedFFT -> LinearRectifier]
-> ZipVectors -> BlockLeastSquares(blockSize, 1 iter, λ) -> MaxClassifier ->
MulticlassClassifierEvaluator.  784-pixel inputs give 512 PaddedFFT features
per FFT, so blockSize/512 FFTs land in each solver block, exactly as the
reference computes fftsPerBatch/numFFTBatches (:31-33).

A solver block's chains are one node, ``ops.stats.RandomFFTBlock``, and the
solver is handed what makes the blocks (``solvers.block.BlockSource``), so
the documented 200 FFTs (102,400 columns, 24.6 GB at MNIST's 60,000 rows)
need no design matrix: each block is made where a pass consumes it.
"""

from __future__ import annotations

import argparse
from dataclasses import dataclass
import functools
import math
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..core import optimize, trace
from ..core.checkpoint import checkpoint_exists, load_pipeline, save_pipeline
from ..core.logging import Logging, configure_logging, stage_timer
from ..core.memory import log_fit_report
from ..core.pipeline import Pipeline
from ..core.resilience import assert_all_finite, numerics_guard_enabled
from ..evaluation.multiclass import MulticlassClassifierEvaluator
from ..loaders.csv_loader import LabeledData, csv_data_loader
from ..ops.stats import LinearRectifier, PaddedFFT, RandomFFTBlock, RandomSignNode
from ..ops.util import (
    ClassLabelIndicatorsFromIntLabels,
    GroupConcatFeaturizer,
    MaxClassifier,
    ZipVectors,
)
from ..parallel.mesh import padded_shard_rows, parse_mesh
from ..solvers.block import BlockLeastSquaresEstimator, BlockSource
from ..utils.platform import init_device
from . import serve_common


@dataclass
class MnistRandomFFTConfig:
    """Flag-compatible with the reference scopt config (:94-101)."""

    train_location: str = ""
    test_location: str = ""
    num_ffts: int = 200
    block_size: int = 2048
    lam: float | None = None
    seed: int = 0
    mnist_image_size: int = 784
    num_classes: int = 10
    #: BCD solve fault tolerance (single-device fits only): a checkpoint
    #: path/callback (state persisted after every completed block) and an
    #: optional state to resume a preempted solve from — both forwarded to
    #: ``BlockLeastSquaresEstimator.fit(checkpoint=, resume_from=)``.
    solve_checkpoint: object = None
    solve_resume: object = None
    #: Cost-based auto-Cacher (core.optimize): decide from the MEASURED
    #: featurize cost whether the FFT feature batches stay resident through
    #: the train-split evaluation (reuse=2: solve + eval) or are freed
    #: after the solve and recomputed at eval — under a tight
    #: ``KEYSTONE_HBM_BUDGET`` the optimizer picks recompute instead of
    #: OOMing on residency.  Decision table in ``results["cache_plan"]``.
    auto_cache: bool = False
    #: Whole-fitted-SERVABLE-pipeline checkpoint stem (core.checkpoint):
    #: load-or-fit of ``GroupConcatFeaturizer >> model >> MaxClassifier``
    #: — the artifact the serving endpoint warm-loads.
    pipeline_file: str | None = None
    #: Serving modes (core.serve via serve_common): ``serve`` answers the
    #: test split through the warm endpoint and asserts bit-equality;
    #: ``serve_bench`` runs the concurrent-client SLO bench.  Both require
    #: ``pipeline_file``.
    serve: bool = False
    serve_bench: bool = False
    serve_clients: int = 4
    serve_requests: int = 256
    #: ``--serveMesh DxM``: serve on an explicit mesh — the checkpoint
    #: reshards onto it and buckets AOT-compile mesh-native (ISSUE 16).
    serve_mesh: str | None = None


def _fft_batches(conf: MnistRandomFFTConfig) -> tuple[int, int]:
    """(numFFTBatches, fftsPerBatch) as the reference computes them (:31-33)."""
    ffts_per_batch = conf.block_size // 512
    return math.ceil(conf.num_ffts / ffts_per_batch), ffts_per_batch


def build_featurizer_batches(conf: MnistRandomFFTConfig):
    """The per-batch featurizers (:44-48): blockSize/512 FFT chains per
    batch, unstacked from :func:`draw_block_featurizers`' signs (the list a
    caller that holds the blocks applies one by one)."""
    signs = draw_block_featurizers(conf).signs
    with trace.host("dispatch", "unstack_featurizers"):
        return [
            [
                Pipeline([RandomSignNode(s), PaddedFFT(), LinearRectifier(0.0)])
                for s in group
            ]
            for group in signs
        ]


@functools.partial(jax.jit, static_argnames=("num_blocks", "ffts_per_block", "size"))
def _draw_sign_blocks(key, *, num_blocks, ffts_per_block, size):
    """Every FFT's signs drawn by one program, in block order: ``key, sub =
    split(key)``, then ``RandomSignNode.create(size, sub)``; stacked
    ``[num_blocks, ffts_per_block, size]``."""

    def one(key, _):
        key, sub = jax.random.split(key)
        return key, RandomSignNode.create(size, sub).signs

    signs = jax.lax.scan(one, key, None, length=num_blocks * ffts_per_block)[1]
    return RandomFFTBlock(signs.reshape(num_blocks, ffts_per_block, size))


def draw_block_featurizers(conf: MnistRandomFFTConfig) -> RandomFFTBlock:
    """The RandomSign draws of every FFT (:44-48) from ``PRNGKey(seed)``, as
    ONE ``RandomFFTBlock`` whose leaf carries a leading block axis: what
    ``BlockSource`` takes."""
    num_blocks, ffts_per_block = _fft_batches(conf)
    with trace.host("dispatch", "draw_featurizers"):
        return _draw_sign_blocks(
            jax.random.PRNGKey(conf.seed), num_blocks=num_blocks,
            ffts_per_block=ffts_per_block, size=conf.mnist_image_size,
        )


def _made_form(conf: MnistRandomFFTConfig, mesh) -> bool:
    """Whether the solver is handed a ``BlockSource``: everywhere but where
    the caller asks for what a source fit does not do yet (a mesh, a
    checkpointed or resumed solve, the auto-Cacher's decision over held
    feature batches)."""
    return mesh is None and not (
        conf.solve_checkpoint is not None or conf.solve_resume is not None
        or conf.auto_cache
    )


def run(
    conf: MnistRandomFFTConfig,
    train: LabeledData,
    test: LabeledData,
    mesh=None,
) -> dict:
    """With ``mesh``, train/test batches are row-sharded over the data axis
    and the block solver runs fully distributed (sharded grams + model-axis
    sharded solves) — the reference runs this pipeline over partitioned RDDs
    end to end (MnistRandomFFT.scala:36-88).

    Without one, the reference's ``batchFeaturizer`` is a ``Seq`` of lazy
    chains, and so is this one: the solver is handed a ``BlockSource`` (the
    rows and ONE ``RandomFFTBlock`` of every block's signs), takes the block
    means itself, and both splits' blocks are made as the evaluation loop
    reaches them, so at the documented 200 FFTs the 102,400-column design
    matrix never exists; where it fits the device the solver holds it, by
    its own rule.  A caller that asks for what a source fit does not do yet
    gets the blocks as arrays (:func:`_made_form`).

    Hands back, beside the errors and ``seconds``, ``model``,
    ``featurizers`` (the stacked node, or the list of per-batch chains),
    ``train_scores`` / ``train_predictions`` and ``test_scores`` /
    ``test_predictions`` as the evaluators last saw them, and
    ``fit_report``."""
    configure_logging()
    log = _Log()
    t0 = time.perf_counter()

    if conf.pipeline_file is not None and checkpoint_exists(conf.pipeline_file):
        # Deploy-once/apply-many: the fitted servable chain restores whole
        # (featurize groups + model + classifier), training data is never
        # touched, and the run scores/serves with the restored pipeline.
        return _run_restored(conf, test, log, t0)

    made = _made_form(conf, mesh)
    n_train, n_test = len(train.labels), len(test.labels)
    results: dict = {}
    cache_plan = None
    keep_features = True
    # one root span a fit; the three stages tile it but for glue
    with trace.span("fit", cat="fit", rows=n_train):
        if mesh is not None:
            # Featurization is elementwise per row: zero pad rows stay zero
            # through RandomSign/FFT/rectifier, so no masking is needed.
            train_data, nvalid = padded_shard_rows(train.data, mesh)
            test_data, _ = padded_shard_rows(test.data, mesh)
        else:
            train_data, nvalid = jnp.asarray(train.data), None
            test_data = jnp.asarray(test.data)

        def featurize_training():
            batches = [
                ZipVectors.apply([chain(train_data) for chain in chains])
                for chains in featurizers
            ]
            # Sync inside the stage: jnp dispatch is async, and an unsynced
            # featurize span would read ~0 while the compute leaked into
            # the solve span's time.
            jax.block_until_ready(batches)
            return batches

        t_feat = time.perf_counter()
        with stage_timer("featurize"):
            labels = ClassLabelIndicatorsFromIntLabels(conf.num_classes)(
                train.labels
            )
            if made:
                featurizers = draw_block_featurizers(conf)
                training_batches = BlockSource(train_data, featurizers)
            else:
                featurizers = build_featurizer_batches(conf)
                training_batches = featurize_training()
        feat_secs = time.perf_counter() - t_feat

        if conf.auto_cache:
            # Auto-Cacher decision on the featurized training batches: they
            # are consumed twice (the block solve, then the train-split
            # streaming eval).  Caching = the status-quo residency; a denial
            # frees them after the solve and recomputes at eval time —
            # measured featurize seconds vs materialized bytes, admitted
            # per-chip under a mesh.
            cache_plan = optimize.plan_caches(
                [
                    optimize.CacheCandidate(
                        index=0,
                        name="fft_features",
                        seconds=feat_secs,
                        output_bytes=sum(int(b.nbytes) for b in training_batches),
                        reuse=2,
                    )
                ],
                mesh=mesh,
                dataset_rows=n_train,
            )
            keep_features = cache_plan.decisions[0].cached
            log.log_info("%s", cache_plan.summary())

        with stage_timer("solve"):
            solver = BlockLeastSquaresEstimator(
                conf.block_size, 1, conf.lam or 0.0, mesh=mesh
            )
            model = solver.fit(
                training_batches,
                labels,
                nvalid=nvalid,
                checkpoint=conf.solve_checkpoint,
                resume_from=conf.solve_resume,
            )
            log_fit_report(solver, label="mnist random-fft solve")
            if numerics_guard_enabled():
                # Fail typed (FloatingPointError) instead of serving NaN
                # scores — a poisoned batch or diverged solve must never
                # look like a model.
                assert_all_finite(model, "mnist random-fft model")

        if not keep_features:
            # The plan priced residency above a recompute: release the
            # feature batches' memory through the solve->eval gap and
            # rebuild them at eval (bit-identical — the featurizers are
            # deterministic).
            training_batches = None

        if cache_plan is not None:
            results["cache_plan"] = cache_plan.record()

        def evaluator(split: str, truth, n: int):
            def seen(pred):
                predicted = MaxClassifier()(pred[:n])
                ev = MulticlassClassifierEvaluator(predicted, truth, conf.num_classes)
                results[f"{split}_error"] = 100.0 * ev.total_error
                results[f"{split}_scores"] = pred[:n]
                results[f"{split}_predictions"] = predicted

            return seen

        # Streaming evaluation after each block, train split first, as the
        # reference does (:70-86); the last invocation sees the full model.
        with stage_timer("eval"):
            if made:
                test_batches = BlockSource(test_data, featurizers)
            else:
                if training_batches is None:
                    training_batches = featurize_training()
                test_batches = [
                    ZipVectors.apply([chain(test_data) for chain in chains])
                    for chains in featurizers
                ]
            model.apply_and_evaluate(
                training_batches, evaluator("train", train.labels, n_train)
            )
            model.apply_and_evaluate(
                test_batches, evaluator("test", test.labels, n_test)
            )
            predicted = results["test_predictions"]
            with trace.d2h("test_predictions", predicted.nbytes):
                # the streaming evaluator's last call saw the complete model:
                # the chaos harness diffs these against the fault-free run
                results["test_predictions"] = np.asarray(predicted)
    log.log_info("Train Error is %s%%", results["train_error"])
    log.log_info("TEST Error is %s%%", results["test_error"])

    if conf.pipeline_file is not None:
        from ..core import numerics as knum

        # The fitted SERVABLE chain: the same featurize groups as one node,
        # whose concatenated output the model's VectorSplitter cuts back
        # into exactly the per-group blocks — served scores bit-equal the
        # fit-path apply.  Checkpointed whole for the serving endpoint to
        # warm-load, with the fit-time predicted-class distribution the
        # serving tier's output-drift monitor judges live
        # answers against.
        groups = (
            [[jax.tree.map(lambda a: a[i], featurizers)] for i in range(len(model.xs))]
            if made else featurizers
        )
        servable = Pipeline(
            [GroupConcatFeaturizer(groups), model, MaxClassifier()]
        )
        save_pipeline(
            conf.pipeline_file,
            servable,
            numerics_baseline=knum.OutputSketch.for_outputs(
                results["test_predictions"]
            ).record(),
        )
        log.log_info("saved fitted servable pipeline to %s", conf.pipeline_file)
    _maybe_serve(conf, test, results, log)

    results.update(
        seconds=time.perf_counter() - t0,
        model=model,
        featurizers=featurizers,
        fit_report=solver.last_fit_report,
    )
    log.log_info("Pipeline took %.3f s", results["seconds"])
    return results


def _run_restored(conf: MnistRandomFFTConfig, test, log, t0: float) -> dict:
    """Score (and serve) with the restored servable pipeline — no refit."""
    log.log_info(
        "restoring fitted servable pipeline from %s", conf.pipeline_file
    )
    servable = load_pipeline(conf.pipeline_file)
    predicted = servable(jnp.asarray(test.data))
    ev = MulticlassClassifierEvaluator(
        predicted, test.labels, conf.num_classes
    )
    results: dict = {
        "restored": True,
        "test_error": 100.0 * ev.total_error,
        "test_predictions": np.asarray(predicted),
    }
    log.log_info("TEST Error is %s%% (restored pipeline)", results["test_error"])
    _maybe_serve(conf, test, results, log)
    results["seconds"] = time.perf_counter() - t0
    return results


def _maybe_serve(conf: MnistRandomFFTConfig, test, results: dict, log) -> None:
    if not (conf.serve or conf.serve_bench):
        return
    if conf.pipeline_file is None:
        raise ValueError(
            "--serve/--serveBench need --pipelineFile — the endpoint "
            "warm-loads the fitted artifact, it never refits"
        )
    requests = np.asarray(test.data[: conf.serve_requests], np.float32)
    results["serving"] = serve_common.serve_fitted(
        conf.pipeline_file,
        jax.ShapeDtypeStruct((requests.shape[1],), np.float32),
        requests,
        label="mnist_random_fft",
        bench=conf.serve_bench,
        clients=conf.serve_clients,
        mesh=serve_common.resolve_serve_mesh(conf.serve_mesh),
    )


class _Log(Logging):
    pass


def main(argv=None):
    p = argparse.ArgumentParser("MnistRandomFFT")
    p.add_argument("--trainLocation", required=True)
    p.add_argument("--testLocation", required=True)
    p.add_argument("--numFFTs", type=int, default=200)
    p.add_argument("--blockSize", type=int, default=2048)
    p.add_argument("--lambda", dest="lam", type=float, default=None)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument(
        "--mesh",
        default=None,
        help="device mesh, e.g. '8' (data) or '4x2' (data x model)",
    )
    p.add_argument(
        "--solveCheckpoint",
        default=None,
        help="path for resumable per-block BCD solve state (single-device "
        "fits; state written atomically after every completed block)",
    )
    p.add_argument(
        "--resumeFrom",
        default=None,
        help="BCD solve state path to resume a preempted fit from",
    )
    p.add_argument(
        "--autoCache",
        action="store_true",
        help="cost-based auto-Cacher (core.optimize): decide feature-batch "
        "residency from measured featurize cost vs HBM budget "
        "(KEYSTONE_AUTOCACHE=1 equivalent)",
    )
    p.add_argument(
        "--pipelineFile",
        default=None,
        help="fitted-SERVABLE-pipeline checkpoint stem: load-or-fit of "
        "featurize groups + model + classifier in one artifact (what "
        "--serve/--serveBench warm-load)",
    )
    serve_common.add_serve_args(p)
    p.add_argument(
        "--trace",
        default=None,
        metavar="PATH",
        help="write a Chrome-trace JSON (Perfetto-loadable; .jsonl for the "
        "JSONL event log) of the run — the KEYSTONE_TRACE env equivalent",
    )
    a = p.parse_args(argv)
    if a.trace:
        trace.enable(a.trace)
    # Before the load stage timer, so its log line has a handler to land on
    # (run() re-applies the same idempotent configuration).
    configure_logging()
    init_device()
    if a.blockSize <= 0 or a.blockSize % 512 != 0:
        p.error("--blockSize must be a positive multiple of 512")
    conf = MnistRandomFFTConfig(
        train_location=a.trainLocation,
        test_location=a.testLocation,
        num_ffts=a.numFFTs,
        block_size=a.blockSize,
        lam=a.lam,
        seed=a.seed,
        solve_checkpoint=a.solveCheckpoint,
        solve_resume=a.resumeFrom,
        auto_cache=a.autoCache or optimize.auto_cache_env(),
        pipeline_file=a.pipelineFile,
        serve=a.serve,
        serve_bench=a.serveBench,
        serve_clients=a.serveClients,
        serve_requests=a.serveRequests,
        serve_mesh=a.serveMesh,
    )
    if (a.serve or a.serveBench) and not a.pipelineFile:
        p.error("--serve/--serveBench require --pipelineFile")
    # Labels in the files are 1-indexed (reference :40-42)
    with stage_timer("load"):
        train = LabeledData.from_rows(
            csv_data_loader(conf.train_location), one_indexed=True
        )
        test = LabeledData.from_rows(
            csv_data_loader(conf.test_location), one_indexed=True
        )
    # The reference hardcodes mnistImageSize=784 (:24); inferring the width
    # from the data keeps flag parity while admitting any pixel count
    # (e.g. the 64-pixel sklearn digits used for real-data accuracy runs).
    conf.mnist_image_size = train.data.shape[1]
    try:
        return run(conf, train, test, mesh=parse_mesh(a.mesh))
    finally:
        if a.trace:
            trace.flush()


if __name__ == "__main__":
    main()
