"""VOCSIFTFisher — multi-label VOC 2007 classification via SIFT + Fisher
vectors (reference src/main/scala/pipelines/images/voc/VOCSIFTFisher.scala:18-165).

Flow: VOC load -> grayscale -> dense SIFT -> [PCA fit or load] -> BatchPCA ->
[GMM fit or load] -> FisherVector -> vectorize/normalize/hellinger/normalize
-> BlockLeastSquares(4096, 1, λ) -> per-class scores -> 11-point MAP.

A fit never holds the training descriptors (189 GB at VOC 2007's sizes): a
sampling pass keeps the columns drawn for the PCA and GMM samples, a
featurizing pass keeps each chunk's Fisher features, and both run SIFT chunk
by chunk (``fv_common``, "the chunked two-pass fit").  Two passes need the
images twice and the draw needs the bucket counts first, so a split is
loaded whole by the eager loader, not streamed.

The pcaFile/gmm*File flags implement the reference's load-or-fit artifact
checkpoint pattern (SURVEY §5).
"""

from __future__ import annotations

import argparse
import os
import time
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from ..core import trace
from ..core.checkpoint import checkpoint_exists, load_pipeline, save_pipeline
from ..core.logging import Logging, configure_logging, stage_timer
from ..core.memory import log_fit_report
from ..core.pipeline import FunctionTransformer, Pipeline
from ..core.resilience import assert_all_finite
from ..evaluation.map import MeanAveragePrecisionEvaluator
from ..loaders.image_loaders import (
    VOC_NUM_CLASSES,
    MultiLabeledImages,
    voc_loader,
)
from ..ops.sift import SIFTExtractor
from ..ops.util import ClassLabelIndicatorsFromIntArrayLabels
from ..parallel.mesh import parse_mesh
from ..solvers.block import BlockLeastSquaresEstimator
from ..solvers.gmm import GaussianMixtureModel, GaussianMixtureModelEstimator
from ..solvers.pca import BatchPCATransformer, compute_pca
from ..utils.platform import init_device
from . import serve_common
from .fv_common import (
    bucket_by_shape,
    draw_columns,
    featurize_chunks,
    fisher_feature_pipeline,
    grayscale,
    plan_chunks,
    sample_descriptor_columns,
)


@dataclass
class SIFTFisherConfig:
    """Flag-parity with the reference scopt config (:113-127)."""

    train_location: str = ""
    test_location: str = ""
    label_path: str = ""
    lam: float = 0.5
    desc_dim: int = 80
    vocab_size: int = 256
    scale_step: int = 0
    pca_file: str | None = None
    gmm_mean_file: str | None = None
    gmm_var_file: str | None = None
    gmm_wts_file: str | None = None
    num_pca_samples: int = int(1e6)
    num_gmm_samples: int = int(1e6)
    sift_step_size: int = 3
    seed: int = 42
    # Whole-fitted-pipeline checkpoint stem (core.checkpoint): load-or-fit of
    # PCA + GMM + linear model in one artifact — the generalization of the
    # per-node pcaFile/gmm*File CSV flags.
    pipeline_file: str | None = None
    # Resumable-solve state path: the BCD fit checkpoints after every block
    # and restarts from the last completed block if the state file exists.
    solve_checkpoint: str | None = None
    # Serving modes (core.serve via serve_common): warm-load the
    # pipeline_file bundle, assemble the servable chain (grayscale ->
    # SIFT -> PCA -> Fisher features -> model), and answer/SLO-bench
    # requests drawn from the eager test split's modal image shape (one
    # engine per shape — the static-shape discipline).
    serve: bool = False
    serve_bench: bool = False
    serve_clients: int = 4
    serve_requests: int = 64
    #: ``--serveMesh DxM``: serve on an explicit mesh — the checkpoint
    #: reshards onto it and buckets AOT-compile mesh-native (ISSUE 16).
    serve_mesh: str | None = None


class _Log(Logging):
    pass


def sift_node(conf: SIFTFisherConfig) -> SIFTExtractor:
    # bf16 intermediates, the measured-throughput configuration; VOC
    # leave-2-out CV (tools/voc_leave2out_cv.py, mean MAP 0.85) validated
    # the accuracy surrogate under this dtype.  Op default stays f32.
    return SIFTExtractor(
        step_size=conf.sift_step_size,
        scale_step=conf.scale_step,
        compute_dtype=jnp.bfloat16,
    )


def run(
    conf: SIFTFisherConfig,
    train: MultiLabeledImages,
    test: MultiLabeledImages,
    mesh=None,
) -> dict:
    """With ``mesh``: every chunk is row-sharded over the data axis and the
    block least-squares solve runs distributed ((data, model) shardings via
    the ambient mesh) — the analog of the reference running this pipeline
    over partitioned RDDs (VOCSIFTFisher.scala:18-111).

    The run is one root span ``fit``; its stages (``stage_timer``) tile it
    but for glue, and each occurs once a fit.  Beside ``map`` and ``aps``
    the results hold the fitted chain (``pipeline``: pca, gmm, model) and
    the raw test scores in image order (``test_scores``)."""
    configure_logging()
    with trace.span("fit", cat="fit", rows=len(train)):
        return _fit_and_score(conf, train, test, mesh)


def _fit_and_score(conf: SIFTFisherConfig, train, test, mesh) -> dict:
    log = _Log()
    t0 = time.perf_counter()
    sift = sift_node(conf)
    solver_report = gmm_iterations = None

    # Load-or-fit of the WHOLE fitted pipeline (SURVEY §5 generalized): when
    # the checkpoint exists, training featurization and all fits are skipped
    # and the run scores test data with the restored PCA + GMM + model.
    restored = conf.pipeline_file is not None and checkpoint_exists(conf.pipeline_file)
    if restored:
        log.log_info("restoring fitted pipeline from %s", conf.pipeline_file)
        ck = load_pipeline(conf.pipeline_file)
        batch_pca, gmm, model = ck["pca"], ck["gmm"], ck["model"]
    else:
        plan = plan_chunks(train.images, sift, conf.desc_dim, conf.vocab_size, mesh)
        log.log_info(
            "chunks %s of buckets %s (budget %s)",
            plan.chunk, {s: len(i) for s, i in plan.index.items()}, plan.budget,
        )
        train_labels = ClassLabelIndicatorsFromIntArrayLabels(VOC_NUM_CLASSES)(
            [train.labels[i] for i in plan.order]
        )

        # Part 1+2: the sampling pass (reference :40-50, :59-70 sample the
        # descriptors of every image; here only the drawn columns outlive
        # their chunk).  A loaded PCA or GMM needs no sample.
        with stage_timer("sample_descriptors"):
            draws = {}
            if conf.pca_file is None:
                draws["pca"] = draw_columns(plan.totals, conf.num_pca_samples, conf.seed)
            if conf.gmm_mean_file is None:
                draws["gmm"] = draw_columns(
                    plan.totals, conf.num_gmm_samples, conf.seed + 1
                )
            samples = dict(zip(draws, sample_descriptor_columns(
                plan, train.images, sift, list(draws.values()), mesh
            )))
            if draws:
                trace.metrics.inc("fv.descriptor_passes", len(train))
                trace.wait(samples, "sample_descriptors")

        # Part 1a: PCA — fit on the sampled descriptors, or load (:40-50)
        with stage_timer("pca"):
            if conf.pca_file is not None:
                pca_mat = jnp.asarray(
                    np.loadtxt(conf.pca_file, delimiter=",", ndmin=2).T,
                    jnp.float32,
                )
            else:
                pca_mat = trace.wait(
                    compute_pca(samples.pop("pca"), conf.desc_dim), "pca"
                )
            batch_pca = BatchPCATransformer(pca_mat)

        # Part 2a: GMM — fit on the projected samples, or load (:59-70)
        with stage_timer("gmm"):
            if conf.gmm_mean_file is not None:
                gmm = GaussianMixtureModel.load(
                    conf.gmm_mean_file, conf.gmm_var_file, conf.gmm_wts_file
                )
            else:
                est = GaussianMixtureModelEstimator(conf.vocab_size)
                gmm = est.fit(samples.pop("gmm") @ pca_mat)
                with trace.d2h("gmm_iterations", 4):
                    gmm_iterations = int(est.last_iterations)
                trace.metrics.inc("gmm.iterations", gmm_iterations)
            assert_all_finite(gmm, "VOC GMM fit")

        # Part 3: the featurizing pass (:72-82)
        with stage_timer("featurize"):
            train_features = trace.wait(
                featurize_chunks(plan, train.images, sift, batch_pca, gmm, mesh),
                "featurize",
            )
            trace.metrics.inc("fv.descriptor_passes", len(train))

        # Part 4: linear model (:84-86) — mesh-distributed when given one;
        # with a solve checkpoint the BCD fit persists per-block state and
        # resumes from it after preemption.
        solve_kwargs = {}
        state_path = None
        if conf.solve_checkpoint is not None:
            from ..solvers.block import bcd_checkpoint_path

            solve_kwargs["checkpoint"] = conf.solve_checkpoint
            state_path = bcd_checkpoint_path(conf.solve_checkpoint)
            if os.path.exists(state_path):
                solve_kwargs["resume_from"] = conf.solve_checkpoint
        with stage_timer("solve"):
            solver = BlockLeastSquaresEstimator(4096, 1, conf.lam, mesh=mesh)
            model = solver.fit(
                train_features, train_labels,
                num_features=2 * conf.desc_dim * conf.vocab_size,
                **solve_kwargs,
            )
            log_fit_report(solver, label="VOC SIFT-Fisher solve")
            assert_all_finite(model, "VOC block least-squares fit")
            solver_report = solver.last_fit_report
        del train_features
        if state_path is not None and os.path.exists(state_path):
            # The per-block state is a RESUME artifact, not a model cache:
            # leaving the completed state behind would make a later rerun
            # with different features silently resume into the stale model.
            os.unlink(state_path)

    # Test path (:92-106)
    with stage_timer("eval"):
        test_plan = plan_chunks(test.images, sift, conf.desc_dim, conf.vocab_size, mesh)
        with stage_timer("featurize_test"):
            test_features = trace.wait(
                featurize_chunks(test_plan, test.images, sift, batch_pca, gmm, mesh),
                "featurize_test",
            )
        scores = model(test_features)
        del test_features
        with trace.d2h("test_scores", scores.nbytes):
            in_chunk_order = np.asarray(scores)
        test_scores = np.empty_like(in_chunk_order)
        test_scores[test_plan.order] = in_chunk_order
        aps = MeanAveragePrecisionEvaluator(test.labels, test_scores, VOC_NUM_CLASSES)
    results = {
        "aps": aps,
        "map": float(np.mean(aps)),
        "test_scores": test_scores,
        "pipeline": {"pca": batch_pca, "gmm": gmm, "model": model},
    }
    if gmm_iterations is not None:
        results["gmm_iterations"] = gmm_iterations
    if solver_report is not None:
        # Which tier ran, against what budget, after which step-downs.
        results["solver"] = {
            "tier": solver_report.chosen,
            "budget_bytes": solver_report.budget_bytes,
            "denials": list(solver_report.denials),
            "oom_retries": list(solver_report.oom_retries),
        }
    if conf.pipeline_file is not None and not restored:
        with stage_timer("checkpoint"):
            save_pipeline(conf.pipeline_file, results["pipeline"])
        log.log_info("saved fitted pipeline to %s", conf.pipeline_file)
    results["seconds"] = time.perf_counter() - t0
    _maybe_serve(conf, test, results, log)
    log.log_info("TEST APs are: %s", ",".join(str(a) for a in aps))
    log.log_info("TEST MAP is: %s", results["map"])
    return results


def servable_pipeline(conf: SIFTFisherConfig, bundle: dict) -> Pipeline:
    """Assemble the fitted apply-chain from a ``--pipelineFile`` bundle
    ({pca, gmm, model}) into ONE servable Transformer: grayscale -> dense
    SIFT -> BatchPCA -> Fisher features -> per-class scores.  The SIFT
    node is reconstructed from config (it holds no fitted state); the
    fitted arrays ride in the bundle's registered nodes, so the chain
    flows through jit as a pytree."""
    fisher = fisher_feature_pipeline(bundle["gmm"])
    return Pipeline(
        [
            FunctionTransformer(grayscale, name="grayscale"),
            sift_node(conf),
            bundle["pca"],
            FunctionTransformer(fisher, name="fisher_features"),
            bundle["model"],
        ]
    )


def _maybe_serve(conf: SIFTFisherConfig, test, results: dict, log) -> None:
    if not (conf.serve or conf.serve_bench):
        return
    if conf.pipeline_file is None:
        raise ValueError(
            "--serve/--serveBench need --pipelineFile — the endpoint "
            "warm-loads the fitted {pca, gmm, model} bundle, it never refits"
        )
    images = test.images
    # One engine serves ONE request shape (the static-shape discipline the
    # shape-bucketed featurize already follows): requests come from the
    # test split's most populous shape bucket.
    buckets = bucket_by_shape(images)
    shape, (idx, batch) = max(buckets.items(), key=lambda kv: len(kv[1][0]))
    requests = np.asarray(batch, np.float32)[: conf.serve_requests]
    record = serve_common.serve_fitted(
        conf.pipeline_file,
        jax.ShapeDtypeStruct(tuple(requests.shape[1:]), np.float32),
        requests,
        label="voc_sift_fisher",
        wrap=lambda bundle: servable_pipeline(conf, bundle),
        bench=conf.serve_bench,
        clients=conf.serve_clients,
        mesh=serve_common.resolve_serve_mesh(conf.serve_mesh),
    )
    record["request_shape"] = list(requests.shape[1:])
    record["shape_buckets_total"] = len(buckets)
    results["serving"] = record


def main(argv=None):
    p = argparse.ArgumentParser("VOCSIFTFisher")
    p.add_argument("--trainLocation", required=True)
    p.add_argument("--testLocation", required=True)
    p.add_argument("--labelPath", required=True)
    p.add_argument("--lambda", dest="lam", type=float, default=0.5)
    p.add_argument("--descDim", type=int, default=80)
    p.add_argument("--vocabSize", type=int, default=256)
    p.add_argument("--scaleStep", type=int, default=0)
    p.add_argument("--pcaFile", default=None)
    p.add_argument("--gmmMeanFile", default=None)
    p.add_argument("--gmmVarFile", default=None)
    p.add_argument("--gmmWtsFile", default=None)
    p.add_argument("--numPcaSamples", type=int, default=int(1e6))
    p.add_argument("--numGmmSamples", type=int, default=int(1e6))
    p.add_argument(
        "--pipelineFile",
        default=None,
        help="fitted-pipeline checkpoint stem: load-or-fit of PCA+GMM+model",
    )
    p.add_argument(
        "--solveCheckpoint",
        default=None,
        help="resumable BCD state path: per-block checkpoint + auto-resume",
    )
    serve_common.add_serve_args(p)
    p.add_argument(
        "--mesh",
        default=None,
        help="device mesh, e.g. '8' (data) or '4x2' (data x model)",
    )
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
    configure_logging()
    init_device()
    if (a.serve or a.serveBench) and not a.pipelineFile:
        p.error("--serve/--serveBench require --pipelineFile")
    conf = SIFTFisherConfig(
        train_location=a.trainLocation,
        test_location=a.testLocation,
        label_path=a.labelPath,
        lam=a.lam,
        desc_dim=a.descDim,
        vocab_size=a.vocabSize,
        scale_step=a.scaleStep,
        pca_file=a.pcaFile,
        gmm_mean_file=a.gmmMeanFile,
        gmm_var_file=a.gmmVarFile,
        gmm_wts_file=a.gmmWtsFile,
        num_pca_samples=a.numPcaSamples,
        num_gmm_samples=a.numGmmSamples,
        pipeline_file=a.pipelineFile,
        solve_checkpoint=a.solveCheckpoint,
        serve=a.serve,
        serve_bench=a.serveBench,
        serve_clients=a.serveClients,
        serve_requests=a.serveRequests,
        serve_mesh=a.serveMesh,
    )
    if conf.pipeline_file is not None and checkpoint_exists(conf.pipeline_file):
        # Restored runs never touch training data — skip decoding the
        # entire training tar (the dominant reload-path cost).
        train = MultiLabeledImages([], [], [])
    else:
        train = voc_loader(conf.train_location, conf.label_path)
    test = voc_loader(conf.test_location, conf.label_path)
    try:
        return run(conf, train, test, mesh=parse_mesh(a.mesh))
    finally:
        if a.trace:
            trace.flush()


if __name__ == "__main__":
    main()
