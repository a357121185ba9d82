"""VOCSIFTFisher — multi-label VOC 2007 classification via SIFT + Fisher
vectors (reference src/main/scala/pipelines/images/voc/VOCSIFTFisher.scala:18-165).

Flow: VOC load -> grayscale -> dense SIFT -> [PCA fit or load] -> BatchPCA ->
[GMM fit or load] -> FisherVector -> vectorize/normalize/hellinger/normalize
-> BlockLeastSquares(4096, 1, λ) -> per-class scores -> 11-point MAP.

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

from ..core import optimize, trace
from ..core.checkpoint import checkpoint_exists, load_pipeline, save_pipeline
from ..core.ingest import stream_batches
from ..core.logging import Logging, configure_logging, stage_timer
from ..core.memory import log_fit_report
from ..core.pipeline import FunctionTransformer, Pipeline
from ..core.resilience import assert_all_finite
from ..evaluation.map import MeanAveragePrecisionEvaluator
from ..loaders.image_loaders import (
    VOC_NUM_CLASSES,
    MultiLabeledImages,
    voc_labels_map,
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
    collect_autotune,
    fisher_feature_pipeline,
    grayscale,
    plan_pca_materialization,
    record_stream_autotune,
    sample_columns,
    scatter_features,
    searched_bucket_featurize,
    stream_config_from_flags,
    stream_descriptor_buckets,
)


@dataclass
class VOCStreamSource:
    """Streaming stand-in for :class:`MultiLabeledImages` (core.ingest):
    images are decoded from the tar WHILE the device featurizes — SIFT on
    batch *i* overlaps decode of batch *i+1* — instead of the eager
    decode-everything-first path.  ``labels``/``len`` become available
    after the descriptor pass records the decode-survival order."""

    data_path: str
    labels_path: str
    name_prefix: str = "VOCdevkit/VOC2007/JPEGImages/"
    batch_size: int = 64
    #: closed-loop ingest autotuner on this source's streams (--autoTune)
    autotune: bool = False
    #: decode backend (--decodeBackend): None defers to env
    decode_backend: str | None = None
    #: snapshot cache root (--snapshotDir): decoded chunks keyed by tar +
    #: decode config + this source's member filter (prefix + label file)
    snapshot_dir: str | None = None
    #: device-resident decode (--deviceDecode): entropy pass on the host,
    #: pixels born on-device fused into the SIFT featurize
    device_decode: bool = False

    def __post_init__(self):
        self._names: list | None = None
        self._labels_map: dict | None = None

    @property
    def images(self) -> "VOCStreamSource":
        # The workload passes ``data.images`` into the descriptor
        # extractors; for a stream source the "images" ARE the source.
        return self

    def labels_map(self) -> dict:
        if self._labels_map is None:
            self._labels_map = voc_labels_map(self.labels_path)
        return self._labels_map

    def record_names(self, names: list) -> None:
        self._names = names

    @property
    def labels(self) -> list:
        if self._names is None:
            raise RuntimeError(
                "VOCStreamSource.labels before the descriptor pass — the "
                "streaming extract must run first (it records image order)"
            )
        lm = self.labels_map()
        return [lm[n] for n in self._names]

    def __len__(self) -> int:
        if self._names is None:
            raise RuntimeError(
                "len(VOCStreamSource) before the descriptor pass"
            )
        return len(self._names)


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
    # Cost-based auto-Cacher (core.optimize): decide from a measured probe
    # whether the PCA-projected descriptors stay resident between GMM
    # sampling and Fisher featurization, or are re-projected per consumer
    # under a tight HBM budget.  Decision table in results["cache_plan"].
    auto_cache: bool = False
    # Placement search (core.autoshard): force the cost-model-ranked
    # candidate search for the block solve (on by default via
    # KEYSTONE_AUTOSHARD); the searched table lands in
    # results["placement"] whenever a search ran.
    auto_shard: bool = False
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


def extract_sift_buckets(
    conf: SIFTFisherConfig, images: list, mesh=None, placement_out=None
) -> dict:
    """Per shape bucket: grayscale + dense SIFT -> [n, 128, cols].  With a
    mesh the PLACEMENT (row-sharded over which factorization, or single
    device) is chosen by the same cost-model-ranked search as the solve
    (fv_common.searched_bucket_featurize; the hand row-sharded layout is
    the untrained head, pad rows are dropped downstream).  A caller-passed
    ``placement_out`` dict receives the searched record under
    ``"featurize"``."""
    # bf16 intermediates, the measured-throughput configuration; VOC
    # leave-2-out CV (tools/voc_leave2out_cv.py, mean MAP 0.85) validated
    # the accuracy surrogate under this dtype.  Op default stays f32.
    sift = SIFTExtractor(
        step_size=conf.sift_step_size,
        scale_step=conf.scale_step,
        compute_dtype=jnp.bfloat16,
    )
    if isinstance(images, VOCStreamSource):
        # Streaming ingest: decode of batch i+1 overlaps SIFT of batch i
        # (core.ingest ring buffer + double-buffered H2D).  Label-less and
        # non-JPEGImages members are filtered before decode.
        src = images
        lm = src.labels_map()

        def keep(name: str) -> bool:
            return name.startswith(src.name_prefix) and name in lm

        # The keep filter selects the member set, so it must be part of the
        # snapshot key: prefix + label-file identity (a changed labels CSV
        # changes the survivor set -> new snapshot).  Computed
        # unconditionally (one os.stat): inert when snapshots are off,
        # and an env-only KEYSTONE_SNAPSHOT_DIR is never silently inert
        # (the stream disables snapshots for unkeyed keep filters).
        from ..core import snapshot as ksnap

        extra = (
            f"voc:{src.name_prefix}:"
            f"{ksnap.file_identity(src.labels_path)}"
        )
        cfg = stream_config_from_flags(
            autotune=src.autotune,
            decode_backend=src.decode_backend,
            snapshot_dir=src.snapshot_dir,
            snapshot_extra=extra,
            device_decode=src.device_decode,
        )
        with stream_batches(
            src.data_path, src.batch_size, keep=keep, config=cfg
        ) as st:
            buckets, names = stream_descriptor_buckets(
                st, lambda dev: sift(grayscale(dev))
            )
        src.record_names(names)
        record_stream_autotune(src, st)
        return buckets
    out, placement = searched_bucket_featurize(
        "voc_sift_featurize", images, lambda dev: sift(grayscale(dev)), mesh
    )
    if placement_out is not None and placement is not None:
        placement_out["featurize"] = placement
    return out


def run(
    conf: SIFTFisherConfig,
    train: MultiLabeledImages,
    test: MultiLabeledImages,
    mesh=None,
) -> dict:
    """With ``mesh``: featurization buckets are row-sharded over the data
    axis and the block least-squares solve runs distributed ((data, model)
    shardings via the ambient mesh) — the analog of the reference running
    this pipeline over partitioned RDDs (VOCSIFTFisher.scala:18-111)."""
    configure_logging()
    log = _Log()
    t0 = time.perf_counter()

    feat_dim = 2 * conf.desc_dim * conf.vocab_size
    results_cache_plan = results_placement = None
    feat_placements: dict = {}

    # Load-or-fit of the WHOLE fitted pipeline (SURVEY §5 generalized): when
    # the checkpoint exists, training featurization and all fits are skipped
    # and the run scores test data with the restored PCA + GMM + model.
    if conf.pipeline_file is not None and checkpoint_exists(conf.pipeline_file):
        log.log_info("restoring fitted pipeline from %s", conf.pipeline_file)
        ck = load_pipeline(conf.pipeline_file)
        batch_pca, gmm, model = ck["pca"], ck["gmm"], ck["model"]
        fisher = fisher_feature_pipeline(gmm)
    else:
        # Part 1+2: SIFT descriptors per shape bucket (reference :36-57).
        # Runs BEFORE the label node: a streaming source only knows its
        # image order (and therefore labels) after the descriptor pass.
        with stage_timer("sift"):
            train_desc = extract_sift_buckets(
                conf, train.images, mesh, placement_out=feat_placements
            )

        label_node = ClassLabelIndicatorsFromIntArrayLabels(VOC_NUM_CLASSES)
        train_labels = label_node(train.labels)

        # Part 1a: PCA — fit on sampled descriptor columns, or load (:40-50)
        with stage_timer("pca"):
            if conf.pca_file is not None:
                pca_mat = jnp.asarray(
                    np.loadtxt(conf.pca_file, delimiter=",", ndmin=2).T,
                    jnp.float32,
                )
            else:
                samples = sample_columns(
                    train_desc, conf.num_pca_samples, conf.seed
                )
                pca_mat = compute_pca(samples.T, conf.desc_dim)
            batch_pca = BatchPCATransformer(pca_mat)

            def make_pca_desc() -> dict:
                return {
                    shape: (idx, batch_pca(descs))
                    for shape, (idx, descs) in train_desc.items()
                }

            materialize = True
            if conf.auto_cache:
                # Auto-Cacher decision: the projected set is consumed by
                # GMM sampling (when fitting one) and Fisher featurization.
                reuse = (0 if conf.gmm_mean_file is not None else 1) + 1
                cache_plan, materialize = plan_pca_materialization(
                    train_desc, batch_pca, reuse, mesh=mesh,
                    label="voc_pca_descriptors",
                )
                log.log_info("%s", cache_plan.summary())
                results_cache_plan = cache_plan.record()
            # Cached: one resident projection feeds both consumers (the
            # status quo).  Denied: each consumer projects on the fly —
            # deterministic, so samples and features are bit-identical.
            pca_desc = make_pca_desc() if materialize else None

        # Part 2a: GMM — fit on sampled PCA'd columns, or load (:59-70)
        with stage_timer("gmm"):
            if conf.gmm_mean_file is not None:
                gmm = GaussianMixtureModel.load(
                    conf.gmm_mean_file, conf.gmm_var_file, conf.gmm_wts_file
                )
            else:
                gmm_samples = sample_columns(
                    pca_desc if pca_desc is not None else make_pca_desc(),
                    conf.num_gmm_samples, conf.seed + 1,
                )
                gmm = GaussianMixtureModelEstimator(conf.vocab_size).fit(
                    gmm_samples.T
                )
            assert_all_finite(gmm, "VOC GMM fit")

        # Part 3: Fisher features (:72-82)
        with stage_timer("fisher_features"):
            fisher = fisher_feature_pipeline(gmm)
            train_features = jnp.asarray(
                scatter_features(
                    pca_desc if pca_desc is not None else make_pca_desc(),
                    fisher, len(train), feat_dim,
                )
            )

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
                train_features, train_labels, num_features=feat_dim,
                plan=True if conf.auto_shard else None,
                **solve_kwargs,
            )
            log_fit_report(solver, label="VOC SIFT-Fisher solve")
            assert_all_finite(model, "VOC block least-squares fit")
            rep = solver.last_fit_report
            results_placement = rep.placement if rep is not None else None
        if state_path is not None and os.path.exists(state_path):
            # The per-block state is a RESUME artifact, not a model cache:
            # leaving the completed state behind would make a later rerun
            # with different features silently resume into the stale model.
            os.unlink(state_path)

        if conf.pipeline_file is not None:
            save_pipeline(
                conf.pipeline_file,
                {"pca": batch_pca, "gmm": gmm, "model": model},
            )
            log.log_info("saved fitted pipeline to %s", conf.pipeline_file)

    # Test path (:92-106)
    with stage_timer("eval"):
        test_desc = extract_sift_buckets(conf, test.images, mesh)
        test_features = scatter_features(
            test_desc, lambda d: fisher(batch_pca(d)), len(test), feat_dim
        )

        predictions = np.asarray(model(jnp.asarray(test_features)))
    aps = MeanAveragePrecisionEvaluator(test.labels, predictions, VOC_NUM_CLASSES)
    results = {
        "aps": aps,
        "map": float(np.mean(aps)),
        "seconds": time.perf_counter() - t0,
    }
    if results_cache_plan is not None:
        results["cache_plan"] = results_cache_plan
    if results_placement is not None or feat_placements:
        # The searched placement tables — the block solve's candidates,
        # deny/score rationale, chosen plan's predicted-vs-actual cost,
        # and (under a mesh) the searched FEATURIZE placement: one audit
        # home for every ranked placement decision the run made.
        if feat_placements:
            results["placement"] = {
                "solver": results_placement, **feat_placements
            }
        else:
            results["placement"] = results_placement
    autotune = collect_autotune(train, test)
    if autotune:
        results["autotune"] = autotune
        log.log_info("ingest autotune: %s", autotune)
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
    sift = SIFTExtractor(
        step_size=conf.sift_step_size,
        scale_step=conf.scale_step,
        compute_dtype=jnp.bfloat16,
    )
    fisher = fisher_feature_pipeline(bundle["gmm"])
    return Pipeline(
        [
            FunctionTransformer(grayscale, name="grayscale"),
            sift,
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
    images = getattr(test, "images", None)
    if isinstance(images, VOCStreamSource) or not hasattr(images, "__len__"):
        raise ValueError(
            "serving draws requests from the EAGER test split — run "
            "--serve/--serveBench without --streamIngest"
        )
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
    p.add_argument(
        "--streamIngest",
        action="store_true",
        help="streaming ingest (core.ingest): decode the tar WHILE the "
        "device runs SIFT, instead of decoding everything first",
    )
    p.add_argument(
        "--streamBatchSize",
        type=int,
        default=64,
        help="images per streamed device batch (--streamIngest only)",
    )
    p.add_argument(
        "--autoCache",
        action="store_true",
        help="cost-based auto-Cacher (core.optimize): probe-measured "
        "decision on PCA-descriptor residency vs re-projection "
        "(KEYSTONE_AUTOCACHE=1 equivalent)",
    )
    p.add_argument(
        "--autoShard",
        action="store_true",
        help="placement search (core.autoshard): force the cost-model "
        "ranked mesh/strategy candidate search for the block solve and "
        "record the searched plan in results['placement'] (on by "
        "default; KEYSTONE_AUTOSHARD=0 disables it except here)",
    )
    p.add_argument(
        "--autoTune",
        action="store_true",
        help="closed-loop ingest autotuner on --streamIngest streams: "
        "retune decode width / ring depth / decode-ahead mid-stream "
        "(KEYSTONE_AUTOTUNE=1 equivalent)",
    )
    p.add_argument(
        "--decodeBackend",
        default=None,
        choices=("thread", "process"),
        help="decode backend for --streamIngest: 'process' decodes on "
        "spawned worker processes via shared memory "
        "(KEYSTONE_DECODE_BACKEND equivalent)",
    )
    p.add_argument(
        "--snapshotDir",
        default=None,
        help="snapshot cache root for --streamIngest streams "
        "(core.snapshot): first pass materializes decoded chunks, repeat "
        "runs stream the shards at IO speed "
        "(KEYSTONE_SNAPSHOT_DIR equivalent)",
    )
    p.add_argument(
        "--deviceDecode",
        action="store_true",
        help="device-resident JPEG decode for --streamIngest "
        "(ops.jpeg_device): host entropy pass only, pixels born on-device "
        "fused into the SIFT featurize; unsupported JPEGs fall back to "
        "host decode counted per reason (KEYSTONE_DEVICE_DECODE=1 "
        "equivalent)",
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
    if (a.serve or a.serveBench) and a.streamIngest:
        p.error(
            "--serve/--serveBench draw requests from the eager test split "
            "— drop --streamIngest for serving runs"
        )
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
        auto_cache=a.autoCache or optimize.auto_cache_env(),
        auto_shard=a.autoShard,
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
    elif a.streamIngest:
        train = VOCStreamSource(
            conf.train_location, conf.label_path,
            batch_size=a.streamBatchSize, autotune=a.autoTune,
            decode_backend=a.decodeBackend, snapshot_dir=a.snapshotDir,
            device_decode=a.deviceDecode,
        )
    else:
        train = voc_loader(conf.train_location, conf.label_path)
    if a.streamIngest:
        test = VOCStreamSource(
            conf.test_location, conf.label_path,
            batch_size=a.streamBatchSize, autotune=a.autoTune,
            decode_backend=a.decodeBackend, snapshot_dir=a.snapshotDir,
            device_decode=a.deviceDecode,
        )
    else:
        test = voc_loader(conf.test_location, conf.label_path)
    try:
        return run(conf, train, test, mesh=parse_mesh(a.mesh))
    finally:
        if a.trace:
            trace.flush()


if __name__ == "__main__":
    main()
