"""VOCSIFTFisher as the benchmark drives it: whole fits through
``keystone_tpu.workloads.voc_sift_fisher.run``, which hands back the fitted
chain and the raw test scores beside the MAP.  What a fit does not keep (the
sampled descriptors, the Fisher features) is made again after the window by
the program's own chunk helpers from the fitted chain and the fit's seed: the
same programs on the same images."""

from __future__ import annotations

import glob
import os

import numpy as np

# The entry points this cell needs, by name: under a program without the
# chunked fit the harness fails here, as it loads this file, before any data
# is made.
from keystone_tpu.workloads.fv_common import (  # noqa: F401
    featurize_chunks,
    plan_chunks,
    sample_descriptor_columns,
)

REFERENCE = "voc_fv"
COUNTS = "voc_fv"
DATAGEN = "voc_like"

#: device programs by layer, as regular expressions on the XLA module's name
PROGRAMS = {
    # SIFT of a chunk, the two passes' halves of a chunk (drawn columns; PCA
    # and Fisher features), the gather of the samples, the chunks'
    # concatenation and the slices that drop a last chunk's pad rows
    "featurizers": [
        r"^jit__describe_chunk", r"^jit__sample_chunk", r"^jit__encode_chunk",
        r"^jit__gather_samples", r"^jit_concatenate$", r"^jit_dynamic_slice$", r"^jit_slice$",
    ],
    # EM as one program, apart from the dictionary's other programs so that
    # gmm_roofline's time is EM's alone
    "em": [r"^jit__em_fit"],
    # PCA (centring, SVD, sign rule, projection of the GMM's samples) and
    # EM's seeded start
    "dictionary": [
        r"^jit_svd$", r"^jit__mean$", r"^jit__var$", r"^jit_subtract$",
        r"^jit_matmul$", r"^jit_transpose$", r"^jit_gather$", r"^jit__take$",
        r"^jit_broadcast_in_dim$", r"^jit_multiply$", r"^jit__where$", r"^jit_equal$",
        r"^jit_abs$", r"^jit__reduce_max$", r"^jit_true_divide$",
    ],
    "solvers": [r"^jit__fused_bcd_impl$", r"^jit__bcd_", r"^jit__hs_block", r"^jit__pad$"],
    # the model's apply on the test features
    "evaluation": [r"^jit_add$", r"^jit_dot_general$"],
}


def program_seed(seed: int) -> int:
    return seed % (2**32 - 5)


def place_data(data: dict) -> dict:
    """``run`` stacks and moves the images chunk by chunk itself, so they stay
    on the host."""
    return data


def _config(conf: dict, seed: int, stem: str | None):
    from keystone_tpu.workloads import voc_sift_fisher as voc

    return voc.SIFTFisherConfig(
        lam=conf["lam"],
        desc_dim=conf["desc_dim"],
        vocab_size=conf["vocab_size"],
        scale_step=conf["scale_step"],
        num_pca_samples=conf["num_pca_samples"],
        num_gmm_samples=conf["num_gmm_samples"],
        sift_step_size=conf["sift_step"],
        seed=program_seed(seed),
        pipeline_file=stem,
    )


def _split(part: dict):
    from keystone_tpu.loaders.image_loaders import MultiLabeledImages

    return MultiLabeledImages(part["x"], list(part["y"]), [str(i) for i in range(len(part["x"]))])


def fit(conf: dict, data: dict, seed: int, stem: str) -> dict:
    from keystone_tpu.workloads import voc_sift_fisher as voc

    for old in glob.glob(stem + ".*"):
        os.remove(old)
    results = voc.run(_config(conf, seed, stem), _split(data["train"]), _split(data["test"]))
    return {"results": results, "seed": seed, "rows": len(data["train"]["x"])}


def fit_report(out: dict) -> dict:
    solver = out["results"].get("solver") or {}
    return {
        "tier": solver.get("tier"),
        "denials": list(solver.get("denials", ())),
        "oom_retries": list(solver.get("oom_retries", ())),
    }


def _in_image_order(features, order: np.ndarray):
    """Rows emitted bucket by bucket, put back in image order, on the device."""
    import jax.numpy as jnp

    return features[jnp.asarray(np.argsort(order))]


def produced(out: dict, conf: dict, data: dict, seed: int) -> dict:
    """The fitted chain and the test scores as the fit handed them back; the
    samples it drew and the features it solved on, made again by the
    program's chunk helpers with the fit's seed and chain: the chunk programs
    of the window on the same images, so the sampled rows are the timed
    SIFT's descriptors at the drawn (image, column) places and the feature
    rows the timed SIFT -> PCA -> Fisher vector's.  One EM step of the program
    from the fitted mixture.  What is large (samples, features: 2.6 GB at the
    cell's sizes) stays on the device, where the reference reads it: the
    machine's device-to-host copies ran at ~40 MB/s (PERF.md, PR 28)."""
    import jax.numpy as jnp

    from keystone_tpu.solvers.gmm import _em_step
    from keystone_tpu.workloads import fv_common, voc_sift_fisher as voc

    from benchmark.lib.manifest import load_module

    reference = load_module("reference", REFERENCE)
    res = out["results"]
    chain = res["pipeline"]
    pca, gmm = chain["pca"], chain["gmm"]
    vc = _config(conf, out["seed"], None)
    sift = voc.sift_node(vc)
    train, test = data["train"]["x"], data["test"]["x"]

    plan = fv_common.plan_chunks(train, sift, vc.desc_dim, vc.vocab_size)
    draws = [
        fv_common.draw_columns(plan.totals, vc.num_pca_samples, vc.seed),
        fv_common.draw_columns(plan.totals, vc.num_gmm_samples, vc.seed + 1),
    ]
    pca_samples, gmm_raw = fv_common.sample_descriptor_columns(plan, train, sift, draws)
    gmm_samples = gmm_raw @ pca.pca_mat
    floor = 1e-3 * jnp.mean(jnp.var(gmm_samples, axis=0))
    stepped = _em_step(gmm_samples, gmm.means, gmm.variances, gmm.weights, floor, 1 << 18)[:3]

    train_features = _in_image_order(
        fv_common.featurize_chunks(plan, train, sift, pca, gmm), plan.order
    )
    test_plan = fv_common.plan_chunks(test, sift, vc.desc_dim, vc.vocab_size)
    test_features = _in_image_order(
        fv_common.featurize_chunks(test_plan, test, sift, pca, gmm), test_plan.order
    )
    return {
        "compare_rows": reference.compare_rows(conf, train, seed),
        "sample_seed": vc.seed,
        "pca_samples": pca_samples,
        "gmm_raw": gmm_raw,
        "pca_mat": np.asarray(pca.pca_mat),
        "gmm_samples": gmm_samples,
        "gmm": (np.asarray(gmm.means), np.asarray(gmm.variances), np.asarray(gmm.weights)),
        "gmm_iterations": res.get("gmm_iterations", 0),
        "em_step": stepped,
        "train_features": train_features,
        "test_features": test_features,
        "test_scores": np.asarray(res["test_scores"]),
        "aps": np.asarray(res["aps"]),
        "map": float(res["map"]),
        "test_error": 100.0 * (1.0 - float(res["map"])),
    }
