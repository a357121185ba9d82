"""ImageNetSiftLcsFV — the north-star workload: SIFT + LCS Fisher-vector
features, 256k-dim class-weighted block solve, top-5 error
(reference src/main/scala/pipelines/images/imagenet/ImageNetSiftLcsFV.scala:25-268).

Per branch (SIFT / LCS):
  featurize -> [SIFT: signed-sqrt] -> PCA(descDim) fit-or-load -> BatchPCA ->
  GMM(vocabSize) fit-or-load -> FisherVector -> vectorize -> L2 -> signed-sqrt
  -> L2.
Branches are concatenated (ZipVectors) and solved with
BlockWeightedLeastSquares(4096, 1, λ, w); evaluation is top-5 error.

Images held in memory fit through ``fv_common``'s chunked two-pass fit, as
VOCSIFTFisher does: one trip of a chunk's bytes to the device feeds both
branches' programs, a sampling pass keeps the drawn columns, a featurizing
pass a chunk's ``[chunk, 2·2·descDim·vocabSize]`` rows, and the features stay
on the device to the solver.  A streamed source (``ImageNetStreamSource``)
keeps the older resident form, which holds a branch's descriptors whole.
"""

from __future__ import annotations

import argparse
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
from ..core.pipeline import Pipeline
from ..core.resilience import assert_all_finite
from ..loaders.image_loaders import (
    LabeledImages,
    imagenet_labels_map,
    imagenet_loader,
)
from ..ops.lcs import LCSExtractor
from ..ops.sift import SIFTExtractor
from ..ops.stats import SignedHellingerMapper
from ..ops.util import ClassLabelIndicatorsFromIntLabels, TopKClassifier
from ..parallel.mesh import parse_mesh
from ..solvers.gmm import GaussianMixtureModel, GaussianMixtureModelEstimator
from ..solvers.pca import BatchPCATransformer, DescriptorCentre, compute_pca
from ..solvers.weighted import BlockWeightedLeastSquaresEstimator
from ..utils.stats import get_err_percent
from ..utils.platform import init_device
from .fv_common import (
    bucket_by_shape,
    collect_autotune,
    draw_columns,
    featurize_chunks,
    fisher_feature_pipeline,
    grayscale,
    lcs_branch,
    plan_chunks,
    plan_pca_materialization,
    record_stream_autotune,
    sample_columns,
    sample_descriptor_columns,
    scatter_features,
    shard_batch,
    sift_branch,
    stream_config_from_flags,
    stream_descriptor_buckets,
)

# Hard cap on the GMM EM training set (reference ImageNetSiftLcsFV.scala:85-86).
GMM_FIT_CAP = 1_000_000


@dataclass
class ImageNetStreamSource:
    """Streaming stand-in for :class:`LabeledImages` (core.ingest): each
    descriptor branch streams the tar — decode of batch *i+1* overlaps the
    device featurize of batch *i* — instead of decoding everything into
    host RAM first.  Both branches must observe the SAME survivor order
    (features are concatenated row-wise), which :meth:`record_names`
    asserts across passes."""

    data_path: str
    labels_path: str
    batch_size: int = 32
    #: closed-loop ingest autotuner on this source's streams (--autoTune)
    autotune: bool = False
    #: decode backend (--decodeBackend): None defers to env
    decode_backend: str | None = None
    #: snapshot cache root (--snapshotDir): decoded chunks keyed by tar +
    #: decode config + the synset filter's label-file identity
    snapshot_dir: str | None = None
    #: device-resident decode (--deviceDecode): entropy pass on the host,
    #: pixels born on-device fused into each descriptor branch
    device_decode: bool = False

    def __post_init__(self):
        self._names: list | None = None
        self._labels_map: dict | None = None

    @property
    def images(self) -> "ImageNetStreamSource":
        return self

    def labels_map(self) -> dict:
        if self._labels_map is None:
            self._labels_map = imagenet_labels_map(self.labels_path)
        return self._labels_map

    def record_names(self, names: list) -> None:
        if self._names is None:
            self._names = names
        elif self._names != names:
            raise RuntimeError(
                "streaming ingest order drifted between descriptor passes "
                f"({len(self._names)} vs {len(names)} survivors) — the "
                "SIFT and LCS branches would zip features of different "
                "images"
            )

    @property
    def labels(self) -> np.ndarray:
        if self._names is None:
            raise RuntimeError(
                "ImageNetStreamSource.labels before the descriptor pass"
            )
        lm = self.labels_map()
        return np.asarray(
            [lm[n.split("/")[0]] for n in self._names], np.int32
        )

    def __len__(self) -> int:
        if self._names is None:
            raise RuntimeError(
                "len(ImageNetStreamSource) before the descriptor pass"
            )
        return len(self._names)


def _streaming_buckets(src: ImageNetStreamSource, per_batch) -> dict:
    """One branch's descriptor pass over the stream (synset-filtered)."""
    lm = src.labels_map()

    def keep(name: str) -> bool:
        return name.split("/")[0] in lm

    # The synset filter derives from the labels file — its identity keys
    # the snapshot (a changed labels file changes the survivor set).
    # Computed unconditionally (one os.stat): inert when snapshots are
    # off, and an env-only KEYSTONE_SNAPSHOT_DIR is never silently inert.
    from ..core import snapshot as ksnap

    extra = f"imagenet:{ksnap.file_identity(src.labels_path)}"
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
        buckets, names = stream_descriptor_buckets(st, per_batch)
    src.record_names(names)
    record_stream_autotune(src, st)
    return buckets


@dataclass
class ImageNetSiftLcsFVConfig:
    """Flag-parity with the reference scopt config (:195-224)."""

    train_location: str = ""
    test_location: str = ""
    label_path: str = ""
    lam: float = 6e-5
    mixture_weight: float = 0.25
    desc_dim: int = 64
    vocab_size: int = 16
    sift_scale_step: int = 1
    lcs_stride: int = 4
    lcs_border: int = 16
    lcs_patch: int = 6
    sift_pca_file: str | None = None
    sift_gmm_mean_file: str | None = None
    sift_gmm_var_file: str | None = None
    sift_gmm_wts_file: str | None = None
    lcs_pca_file: str | None = None
    lcs_gmm_mean_file: str | None = None
    lcs_gmm_var_file: str | None = None
    lcs_gmm_wts_file: str | None = None
    num_pca_samples: int = int(1e7)
    num_gmm_samples: int = int(1e7)
    num_classes: int = 1000
    seed: int = 42
    # Whole-fitted-pipeline checkpoint stem (core.checkpoint): both
    # branches' PCA + GMM plus the weighted block solve in one artifact.
    pipeline_file: str | None = None
    # Cost-based auto-Cacher (core.optimize): per-branch probe-measured
    # decision on whether PCA-projected descriptors stay resident through
    # the GMM EM fit or are re-projected per consumer under a tight HBM
    # budget.  Decision tables in results["cache_plan"].
    auto_cache: bool = False


class _Log(Logging):
    pass


def gmm_sample_count(conf: ImageNetSiftLcsFVConfig) -> int:
    """Columns drawn for a branch's EM.  The reference samples numGmmSamples
    and fits on a shuffled 1e6 of them whatever the flag says
    (shuffleArray(...).take(1e6), ImageNetSiftLcsFV.scala:85-86): a uniform
    draw of a uniform draw is a uniform draw, so the capped count is drawn
    outright and no larger sample ever exists."""
    return min(conf.num_gmm_samples, GMM_FIT_CAP)


def sift_node(conf: ImageNetSiftLcsFVConfig) -> SIFTExtractor:
    # bf16 intermediates: measured +35% chain throughput at 99.5%-within-1
    # quantized-descriptor agreement (see SIFTExtractor docstring) — the
    # throughput workload opts in; the op default stays f32.
    return SIFTExtractor(scale_step=conf.sift_scale_step, compute_dtype=jnp.bfloat16)


def lcs_node(conf: ImageNetSiftLcsFVConfig) -> LCSExtractor:
    return LCSExtractor(conf.lcs_stride, conf.lcs_border, conf.lcs_patch)


def descriptor_branches(conf: ImageNetSiftLcsFVConfig) -> list:
    """The chunk programs' two branches, SIFT's first (its features are the
    first half of a row)."""
    return [sift_branch(sift_node(conf)), lcs_branch(lcs_node(conf))]


def branch_draws(conf: ImageNetSiftLcsFVConfig, plan, branch: int, name: str) -> dict:
    """The branch's column draws, ``{"pca": ..., "gmm": ...}``: SIFT's from
    ``seed`` and ``seed + 1``, LCS's a hundred on (the reference gives each
    sampler its own generator, :52-57, :81-86, :108-113, :136-141)."""
    seed = conf.seed + (100 if name == "lcs" else 0)
    totals = plan.totals_of(branch)
    return {
        "pca": draw_columns(totals, conf.num_pca_samples, seed),
        "gmm": draw_columns(totals, gmm_sample_count(conf), seed + 1),
    }


def branch_preparation(name: str, centre=None):
    """What a branch's descriptors pass through on their way to its PCA:
    SIFT's the signed square root (reference :48); LCS's go as they are
    (:104), less the mean of the branch's PCA sample where there is one (a
    frame the Fisher vector does not see and the chip's products do:
    ``DescriptorCentre``)."""
    if name == "sift":
        return SignedHellingerMapper()
    return DescriptorCentre(jnp.zeros((1,), jnp.float32) if centre is None else centre)


def branch_projection(name: str, batch_pca, centre=None):
    """A branch's descriptors -> what its Fisher vector takes."""
    return Pipeline([branch_preparation(name, centre), batch_pca])


@jax.jit
def _prepare_rows(preparation, rows):
    """A branch's sampled descriptors as its PCA and EM see them."""
    return preparation(rows)


def _fit_branch(
    conf: ImageNetSiftLcsFVConfig, desc_buckets: dict, pca_file, gmm_files,
    seed: int, label: str = "branch", mesh=None,
):
    """Fit (or load) the branch's PCA + GMM from TRAIN descriptors only —
    the reference fits once and applies the same featurizer to test
    (ImageNetSiftLcsFV.scala:69,91,145).

    Returns (batch_pca, gmm, train_pca_desc, cache_plan): the PCA-projected
    train buckets are returned so callers never re-project the training
    set.  With ``conf.auto_cache`` the optimizer decides whether that
    projection stays resident through the GMM EM fit (the HBM-heavy phase)
    or is deferred and re-projected — the reference's always-cache becomes
    a measured choice; ``cache_plan`` is the decision table (None when the
    pass is off)."""
    if pca_file is not None:
        pca_mat = jnp.asarray(
            np.loadtxt(pca_file, delimiter=",", ndmin=2).T, jnp.float32
        )
    else:
        samples = sample_columns(desc_buckets, conf.num_pca_samples, seed)
        pca_mat = compute_pca(samples.T, conf.desc_dim)
    batch_pca = BatchPCATransformer(pca_mat)

    def make_pca_desc() -> dict:
        return {
            shape: (idx, batch_pca(descs))
            for shape, (idx, descs) in desc_buckets.items()
        }

    mean_f, var_f, wts_f = gmm_files
    cache_plan = None
    materialize = True
    if conf.auto_cache:
        reuse = (0 if mean_f is not None else 1) + 1
        cache_plan, materialize = plan_pca_materialization(
            desc_buckets, batch_pca, reuse, mesh=mesh,
            label=f"{label}_pca_descriptors",
        )
    pca_desc = make_pca_desc() if materialize else None

    if mean_f is not None:
        gmm = GaussianMixtureModel.load(mean_f, var_f, wts_f)
    else:
        gmm_samples = sample_columns(
            pca_desc if pca_desc is not None else make_pca_desc(),
            gmm_sample_count(conf), seed + 1,
        )
        gmm = GaussianMixtureModelEstimator(conf.vocab_size).fit(gmm_samples.T)
    assert_all_finite(gmm, "branch GMM fit")

    if pca_desc is None:
        # Deferred projection: materialized only now, AFTER the EM fit
        # released its working set — the recompute the plan priced in.
        pca_desc = make_pca_desc()
    return batch_pca, gmm, pca_desc, cache_plan


def _resident_buckets(images, per_batch, mesh) -> dict:
    """``{shape: (ordinals, descriptors of the whole bucket)}``: the stream's
    batches as they decode, or a list's shape buckets."""
    if isinstance(images, ImageNetStreamSource):
        return _streaming_buckets(images, per_batch)
    return {
        shape: (idx, per_batch(shard_batch(batch, mesh)))
        for shape, (idx, batch) in bucket_by_shape(images).items()
    }


def sift_descriptor_buckets(
    conf: ImageNetSiftLcsFVConfig, images: list, mesh=None,
) -> dict:
    """SIFT branch descriptors held whole (:40-94): SIFT ->
    BatchSignedHellinger, a bucket at a time, row-sharded under a mesh."""
    sift = sift_node(conf)
    hell = SignedHellingerMapper()
    return _resident_buckets(images, lambda dev: hell(sift(grayscale(dev))), mesh)


def lcs_descriptor_buckets(
    conf: ImageNetSiftLcsFVConfig, images: list, mesh=None,
) -> dict:
    """LCS branch descriptors held whole (:96-148): raw LCS straight into
    PCA."""
    return _resident_buckets(images, lcs_node(conf), mesh)


def branch_features(
    conf: ImageNetSiftLcsFVConfig,
    train_images: list,
    test_images: list,
    descriptor_fn,
    pca_file,
    gmm_files,
    seed: int,
    mesh=None,
):
    """The resident form of a branch: fit transformers on train, apply to
    train AND test, every descriptor of a split held at once.  Returns the
    fitted (batch_pca, gmm) too so callers can checkpoint the branch, and
    the auto-Cacher decision table (None when the pass is off)."""
    train_desc = descriptor_fn(conf, train_images, mesh)
    batch_pca, gmm, train_pca_desc, cache_plan = _fit_branch(
        conf, train_desc, pca_file, gmm_files, seed,
        label=descriptor_fn.__name__.replace("_descriptor_buckets", ""),
        mesh=mesh,
    )
    fisher = fisher_feature_pipeline(gmm)
    feat_dim = 2 * conf.desc_dim * conf.vocab_size
    train_feats = scatter_features(
        train_pca_desc, fisher, len(train_images), feat_dim
    )
    test_desc = descriptor_fn(conf, test_images, mesh)
    test_feats = scatter_features(
        test_desc, lambda d: fisher(batch_pca(d)), len(test_images), feat_dim
    )
    return train_feats, test_feats, batch_pca, gmm, cache_plan


def branch_test_features(
    conf: ImageNetSiftLcsFVConfig,
    test_images: list,
    descriptor_fn,
    batch_pca,
    gmm,
    mesh=None,
):
    """Apply an already-fitted branch (restored from a checkpoint) to test
    images only — the reload half of load-or-fit."""
    fisher = fisher_feature_pipeline(gmm)
    feat_dim = 2 * conf.desc_dim * conf.vocab_size
    test_desc = descriptor_fn(conf, test_images, mesh)
    return scatter_features(
        test_desc, lambda d: fisher(batch_pca(d)), len(test_images), feat_dim
    )


def run(
    conf: ImageNetSiftLcsFVConfig,
    train: LabeledImages,
    test: LabeledImages,
    mesh=None,
) -> dict:
    """With ``mesh``: every chunk is row-sharded over the data axis, each
    chip its share of the one-chip chunk (SIFT's assembly and the Fisher
    vector's statistics as their kernels under ``shard_map`` where only
    ``data`` splits the mesh), the sampled columns gathered to every chip so
    that PCA and EM run replicated, and the 2·2·descDim·vocabSize-feature
    class-weighted solve runs distributed — row-sharded population grams
    summed across the chips, and with a ``model`` axis of 1 each chip
    solving its own classes from its own rows (``solvers/weighted.py``; the
    reference runs this over partitioned RDDs + treeReduce,
    ImageNetSiftLcsFV.scala:150-195).

    The run is one root span ``fit``; its stages (``stage_timer``) tile it
    but for glue, and each occurs once a fit.  Beside the errors the results
    hold the fitted chain (``pipeline``: both branches' PCA and GMM, the
    model) and the raw test scores in image order (``test_scores``)."""
    configure_logging()
    if isinstance(train, ImageNetStreamSource) or isinstance(test, ImageNetStreamSource):
        return _run_resident(conf, train, test, mesh)
    with trace.span("fit", cat="fit", rows=len(train)):
        return _fit_and_score(conf, train, test, mesh)


def _fit_and_score(conf: ImageNetSiftLcsFVConfig, train, test, mesh) -> dict:
    log = _Log()
    t0 = time.perf_counter()
    branches = descriptor_branches(conf)
    names = [b.name for b in branches]
    solver_report = None
    gmm_iterations: dict = {}

    restored = conf.pipeline_file is not None and checkpoint_exists(conf.pipeline_file)
    if restored:
        # Load-or-fit of the whole fitted pipeline: skip training
        # featurization and every fit; score test with restored state.
        log.log_info("restoring fitted pipeline from %s", conf.pipeline_file)
        ck = load_pipeline(conf.pipeline_file)
        pcas = [ck[f"{name}_pca"] for name in names]
        gmms = [ck[f"{name}_gmm"] for name in names]
        centres = {"lcs": ck["lcs_centre"].centre} if "lcs_centre" in ck else {}
        model = ck["model"]
    else:
        plan = plan_chunks(train.images, branches, conf.desc_dim, conf.vocab_size, mesh)
        log.log_info(
            "chunks %s of buckets %s (budget %s)",
            plan.chunk, {s: len(i) for s, i in plan.index.items()}, plan.budget,
        )
        train_labels = ClassLabelIndicatorsFromIntLabels(conf.num_classes)(
            np.asarray(train.labels)[plan.order]
        )
        pca_files = {"sift": conf.sift_pca_file, "lcs": conf.lcs_pca_file}
        gmm_files = {
            "sift": (conf.sift_gmm_mean_file, conf.sift_gmm_var_file, conf.sift_gmm_wts_file),
            "lcs": (conf.lcs_gmm_mean_file, conf.lcs_gmm_var_file, conf.lcs_gmm_wts_file),
        }

        # The sampling pass (:52-57, :81-86, :108-113, :136-141): both
        # branches' PCA and GMM columns from one trip of each chunk.  A
        # loaded PCA or GMM needs no sample.
        with stage_timer("sample_descriptors"):
            draws = []
            for b, name in enumerate(names):
                loaded = {"pca": pca_files[name], "gmm": gmm_files[name][0]}
                sets = branch_draws(conf, plan, b, name)
                draws.append({k: d for k, d in sets.items() if loaded[k] is None})
            drawn = sample_descriptor_columns(
                plan, train.images, branches, [list(d.values()) for d in draws], mesh
            )
            samples = {
                name: dict(zip(sets, rows)) for name, sets, rows in zip(names, draws, drawn)
            }
            if any(draws):
                trace.metrics.inc("fv.image_passes", len(train))
                for name, sets in zip(names, draws):
                    if sets:
                        trace.metrics.inc(f"fv.descriptor_passes.{name}", len(train))
                trace.wait(samples, "sample_descriptors")

        # PCA a branch: fit on its sampled descriptors, or load (:52-60, :108-116)
        pcas, centres = [], {}
        with stage_timer("pca"):
            for name in names:
                with trace.span("pca", cat="dictionary", branch=name):
                    if pca_files[name] is not None:
                        pca_mat = jnp.asarray(
                            np.loadtxt(pca_files[name], delimiter=",", ndmin=2).T,
                            jnp.float32,
                        )
                    else:
                        rows = samples[name].pop("pca")
                        if name == "lcs":
                            centres[name] = jnp.mean(rows, axis=0)
                        rows = _prepare_rows(branch_preparation(name, centres.get(name)), rows)
                        pca_mat = trace.wait(compute_pca(rows, conf.desc_dim), "pca")
                    pcas.append(BatchPCATransformer(pca_mat))

        # GMM a branch: EM on its projected samples, or load (:81-91, :136-145)
        gmms = []
        with stage_timer("gmm"):
            for name, pca in zip(names, pcas):
                with trace.span("gmm", cat="dictionary", branch=name):
                    mean_f, var_f, wts_f = gmm_files[name]
                    if mean_f is not None:
                        gmm = GaussianMixtureModel.load(mean_f, var_f, wts_f)
                    else:
                        rows = _prepare_rows(
                            branch_preparation(name, centres.get(name)), samples[name].pop("gmm")
                        )
                        est = GaussianMixtureModelEstimator(conf.vocab_size)
                        gmm = est.fit(rows @ pca.pca_mat)
                        with trace.d2h("gmm_iterations", 4):
                            gmm_iterations[name] = int(est.last_iterations)
                        trace.metrics.inc("gmm.iterations", gmm_iterations[name])
                        trace.metrics.inc(f"gmm.iterations.{name}", gmm_iterations[name])
                    assert_all_finite(gmm, f"{name} branch GMM fit")
                    gmms.append(gmm)
        del samples

    projections = [
        branch_projection(name, pca, centres.get(name)) for name, pca in zip(names, pcas)
    ]

    if not restored:
        # The featurizing pass: SIFT's Fisher features then LCS's, a row
        # (ZipVectors, :179-183), on the device from here to the solver
        with stage_timer("featurize"):
            train_features = trace.wait(
                featurize_chunks(plan, train.images, branches, projections, gmms, mesh),
                "featurize",
            )
            trace.metrics.inc("fv.image_passes", len(train))
            for name in names:
                trace.metrics.inc(f"fv.descriptor_passes.{name}", len(train))

        # 2·2·descDim·vocabSize features (:186-188)
        with stage_timer("solve"):
            solver = BlockWeightedLeastSquaresEstimator(
                4096, 1, conf.lam, conf.mixture_weight, mesh=mesh
            )
            model = solver.fit(
                train_features, train_labels,
                num_features=2 * 2 * conf.desc_dim * conf.vocab_size,
            )
            log_fit_report(solver, label="ImageNet weighted block solve")
            assert_all_finite(model, "ImageNet weighted block solve")
            solver_report = solver.last_fit_report
        del train_features

    with stage_timer("eval"):
        test_plan = plan_chunks(test.images, branches, conf.desc_dim, conf.vocab_size, mesh)
        with stage_timer("featurize_test"):
            test_features = trace.wait(
                featurize_chunks(test_plan, test.images, branches, projections, gmms, mesh),
                "featurize_test",
            )
        scores = model(test_features)
        del test_features
        k = min(5, conf.num_classes)
        topk = TopKClassifier(k)(scores)
        with trace.d2h("test_scores", scores.nbytes + topk.nbytes):
            in_chunk_order = np.asarray(scores)
            topk_in_chunk_order = np.asarray(topk)
        test_scores = np.empty_like(in_chunk_order)
        test_scores[test_plan.order] = in_chunk_order
        top = np.empty_like(topk_in_chunk_order)
        top[test_plan.order] = topk_in_chunk_order
        err = get_err_percent(top, test.labels, k)
    results = {
        "top5_err_percent": err,
        "top1_err_percent": get_err_percent(top, test.labels, 1),
        "test_scores": test_scores,
        "pipeline": {
            **{f"{name}_pca": pca for name, pca in zip(names, pcas)},
            **{f"{name}_gmm": gmm for name, gmm in zip(names, gmms)},
            **{f"{name}_centre": DescriptorCentre(c) for name, c in centres.items()},
            "model": model,
        },
    }
    if gmm_iterations:
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
    log.log_info("TEST Top-%d error is: %s %%", k, err)
    return results


def _run_resident(conf: ImageNetSiftLcsFVConfig, train, test, mesh) -> dict:
    """The fit of a streamed source: a branch's descriptors are held whole
    between its passes (``branch_features``), so it runs at sizes whose
    descriptors fit the device."""
    log = _Log()
    t0 = time.perf_counter()

    sift_plan = lcs_plan = None
    if conf.pipeline_file is not None and checkpoint_exists(conf.pipeline_file):
        log.log_info("restoring fitted pipeline from %s", conf.pipeline_file)
        ck = load_pipeline(conf.pipeline_file)
        test_sift = branch_test_features(
            conf, test.images, sift_descriptor_buckets,
            ck["sift_pca"], ck["sift_gmm"], mesh,
        )
        test_lcs = branch_test_features(
            conf, test.images, lcs_descriptor_buckets,
            ck["lcs_pca"], ck["lcs_gmm"], mesh,
        )
        model = ck["model"]
        test_features = jnp.asarray(
            np.concatenate([test_sift, test_lcs], axis=1)
        )
    else:
        with stage_timer("sift_branch"):
            train_sift, test_sift, sift_pca, sift_gmm, sift_plan = branch_features(
                conf,
                train.images,
                test.images,
                sift_descriptor_buckets,
                conf.sift_pca_file,
                (conf.sift_gmm_mean_file, conf.sift_gmm_var_file, conf.sift_gmm_wts_file),
                conf.seed,
                mesh,
            )
        with stage_timer("lcs_branch"):
            train_lcs, test_lcs, lcs_pca, lcs_gmm, lcs_plan = branch_features(
                conf,
                train.images,
                test.images,
                lcs_descriptor_buckets,
                conf.lcs_pca_file,
                (conf.lcs_gmm_mean_file, conf.lcs_gmm_var_file, conf.lcs_gmm_wts_file),
                conf.seed + 100,
                mesh,
            )

        # ZipVectors (:179-183) — kept host-side; the solver shards its blocks
        train_features = np.concatenate([train_sift, train_lcs], axis=1)
        test_features = jnp.asarray(np.concatenate([test_sift, test_lcs], axis=1))

        labels = ClassLabelIndicatorsFromIntLabels(conf.num_classes)(train.labels)

        # 2·2·descDim·vocabSize features (:186-188)
        with stage_timer("solve"):
            solver = BlockWeightedLeastSquaresEstimator(
                4096, 1, conf.lam, conf.mixture_weight, mesh=mesh
            )
            model = solver.fit(
                train_features, labels,
                num_features=2 * 2 * conf.desc_dim * conf.vocab_size,
            )
            log_fit_report(solver, label="ImageNet weighted block solve")
            assert_all_finite(model, "ImageNet weighted block solve")

        if conf.pipeline_file is not None:
            save_pipeline(
                conf.pipeline_file,
                {
                    "sift_pca": sift_pca,
                    "sift_gmm": sift_gmm,
                    "lcs_pca": lcs_pca,
                    "lcs_gmm": lcs_gmm,
                    "model": model,
                },
            )
            log.log_info("saved fitted pipeline to %s", conf.pipeline_file)

    with stage_timer("eval"):
        test_scores = model(test_features)
        k = min(5, conf.num_classes)
        topk = np.asarray(TopKClassifier(k)(test_scores))
        err = get_err_percent(topk, test.labels, k)
    results = {
        "top5_err_percent": err,
        "top1_err_percent": get_err_percent(topk, test.labels, 1),
        "seconds": time.perf_counter() - t0,
    }
    plans = {
        name: plan.record()
        for name, plan in (("sift", sift_plan), ("lcs", lcs_plan))
        if plan is not None
    }
    if plans:
        results["cache_plan"] = plans
        for name, plan in (("sift", sift_plan), ("lcs", lcs_plan)):
            if plan is not None:
                log.log_info("%s branch %s", name, plan.summary())
    autotune = collect_autotune(train, test)
    if autotune:
        results["autotune"] = autotune
    log.log_info("TEST Top-%d error is: %s %%", k, err)
    return results


def main(argv=None):
    p = argparse.ArgumentParser("ImageNetSiftLcsFV")
    p.add_argument("--trainLocation", required=True)
    p.add_argument("--testLocation", required=True)
    p.add_argument("--labelPath", required=True)
    p.add_argument("--lambda", dest="lam", type=float, default=6e-5)
    p.add_argument("--mixtureWeight", type=float, default=0.25)
    p.add_argument("--descDim", type=int, default=64)
    p.add_argument("--vocabSize", type=int, default=16)
    p.add_argument("--siftScaleStep", type=int, default=1)
    p.add_argument("--lcsStride", type=int, default=4)
    p.add_argument("--lcsBorder", type=int, default=16)
    p.add_argument("--lcsPatch", type=int, default=6)
    p.add_argument("--numPcaSamples", type=int, default=int(1e7))
    p.add_argument("--numGmmSamples", type=int, default=int(1e7))
    p.add_argument("--numClasses", type=int, default=1000)
    p.add_argument(
        "--pipelineFile",
        default=None,
        help="fitted-pipeline checkpoint stem: load-or-fit of both branches' "
        "PCA+GMM and the weighted solve",
    )
    p.add_argument(
        "--streamIngest",
        action="store_true",
        help="streaming ingest (core.ingest): decode tars WHILE the device "
        "featurizes, instead of decoding everything first",
    )
    p.add_argument(
        "--streamBatchSize",
        type=int,
        default=32,
        help="images per streamed device batch (--streamIngest only)",
    )
    p.add_argument(
        "--autoCache",
        action="store_true",
        help="cost-based auto-Cacher (core.optimize): per-branch "
        "probe-measured decision on PCA-descriptor residency vs "
        "re-projection (KEYSTONE_AUTOCACHE=1 equivalent)",
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
        "fused into each descriptor branch; unsupported JPEGs fall back "
        "to host decode counted per reason (KEYSTONE_DEVICE_DECODE=1 "
        "equivalent)",
    )
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
    for flag in (
        "siftPcaFile", "siftGmmMeanFile", "siftGmmVarFile", "siftGmmWtsFile",
        "lcsPcaFile", "lcsGmmMeanFile", "lcsGmmVarFile", "lcsGmmWtsFile",
    ):
        p.add_argument(f"--{flag}", default=None)
    a = p.parse_args(argv)
    if a.trace:
        trace.enable(a.trace)
    configure_logging()
    init_device()
    conf = ImageNetSiftLcsFVConfig(
        train_location=a.trainLocation,
        test_location=a.testLocation,
        label_path=a.labelPath,
        lam=a.lam,
        mixture_weight=a.mixtureWeight,
        desc_dim=a.descDim,
        vocab_size=a.vocabSize,
        sift_scale_step=a.siftScaleStep,
        lcs_stride=a.lcsStride,
        lcs_border=a.lcsBorder,
        lcs_patch=a.lcsPatch,
        sift_pca_file=a.siftPcaFile,
        sift_gmm_mean_file=a.siftGmmMeanFile,
        sift_gmm_var_file=a.siftGmmVarFile,
        sift_gmm_wts_file=a.siftGmmWtsFile,
        lcs_pca_file=a.lcsPcaFile,
        lcs_gmm_mean_file=a.lcsGmmMeanFile,
        lcs_gmm_var_file=a.lcsGmmVarFile,
        lcs_gmm_wts_file=a.lcsGmmWtsFile,
        num_pca_samples=a.numPcaSamples,
        num_gmm_samples=a.numGmmSamples,
        num_classes=a.numClasses,
        pipeline_file=a.pipelineFile,
        auto_cache=a.autoCache or optimize.auto_cache_env(),
    )
    if conf.pipeline_file is not None and checkpoint_exists(conf.pipeline_file):
        # Restored runs never touch training data — skip decoding the
        # entire training tar set (the dominant reload-path cost).
        train = LabeledImages([], np.zeros(0, np.int32), [])
    elif a.streamIngest:
        train = ImageNetStreamSource(
            conf.train_location, conf.label_path,
            batch_size=a.streamBatchSize, autotune=a.autoTune,
            decode_backend=a.decodeBackend, snapshot_dir=a.snapshotDir,
            device_decode=a.deviceDecode,
        )
    else:
        train = imagenet_loader(conf.train_location, conf.label_path)
    if a.streamIngest:
        test = ImageNetStreamSource(
            conf.test_location, conf.label_path,
            batch_size=a.streamBatchSize, autotune=a.autoTune,
            decode_backend=a.decodeBackend, snapshot_dir=a.snapshotDir,
            device_decode=a.deviceDecode,
        )
    else:
        test = imagenet_loader(conf.test_location, conf.label_path)
    try:
        return run(conf, train, test, mesh=parse_mesh(a.mesh))
    finally:
        if a.trace:
            trace.flush()


if __name__ == "__main__":
    main()
