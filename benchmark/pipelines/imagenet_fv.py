"""ImageNetSiftLcsFV as the benchmark drives it: whole fits through
``keystone_tpu.workloads.imagenet_sift_lcs_fv.run``, which hands back the
fitted chain (both branches' PCA and GMM, the weighted model) and the raw
test scores beside the errors.  What a fit does not keep (the sampled
descriptors, the Fisher features) is made again after the window by the
program's own chunk helpers from the fitted chain and the fit's seed: the
same programs on the same images."""

from __future__ import annotations

import glob
import os

import numpy as np

# The entry points this cell needs, by name: under a program without the
# two-branch chunked fit the harness fails here, as it loads this file,
# before any data is made.
from keystone_tpu.workloads.fv_common import (  # noqa: F401
    DescriptorBranch,
    featurize_chunks,
    lcs_branch,
    plan_chunks,
    sample_descriptor_columns,
)
from keystone_tpu.workloads.imagenet_sift_lcs_fv import (  # noqa: F401
    branch_draws,
    branch_preparation,
    branch_projection,
    descriptor_branches,
    gmm_sample_count,
)

REFERENCE = "imagenet_fv"
COUNTS = "imagenet_fv"
DATAGEN = "imagenet_like"

#: device programs by layer, as regular expressions on the XLA module's name.
#: A program goes to the first layer that matches it.
PROGRAMS = {
    # both descriptor nodes of a chunk (SIFT; LCS), the two passes' halves of
    # a chunk (drawn columns; PCA and Fisher features of both branches), the
    # gather of the samples, the chunks' concatenation and the slices that
    # drop a last chunk's pad rows
    "featurizers": [
        r"^jit__describe_chunk", r"^jit__describe_lcs_chunk", r"^jit__sample_chunk",
        r"^jit__encode_chunk", r"^jit__gather_samples", r"^jit_concatenate$",
        r"^jit_dynamic_slice$", r"^jit_slice$",
    ],
    # the second branch's descriptor node alone (``lcs_dev_ms``, which reads
    # this key's patterns itself: the layer above takes the program first)
    "lcs": [r"^jit__describe_lcs_chunk"],
    # EM as one program, both branches
    "em": [r"^jit__em_fit"],
    # both branches' PCA (SIFT's signed square root and LCS's centre, the SVD's own centring, SVD, sign rule,
    # projection of the GMM's samples) and EM's seeded start
    "dictionary": [
        r"^jit__prepare_rows", r"^jit_svd$", r"^jit__mean$", r"^jit__var$", r"^jit_subtract$",
        r"^jit_matmul$", r"^jit_transpose$", r"^jit_gather$", r"^jit__take$",
        r"^jit_broadcast_in_dim$", r"^jit_multiply$", r"^jit__where$", r"^jit_equal$",
        r"^jit_abs$", r"^jit__reduce_max$", r"^jit_true_divide$",
    ],
    # the weighted solve's one program, the class sort's gather and mask, the
    # column pad, the labels' argmax
    "solvers": [
        r"^jit__fused_bwls", r"^jit__class_solves", r"^jit__bwls_", r"^jit__pad$",
        r"^jit__take$", r"^jit_argmax$", r"^jit__scatter_cols$",
    ],
    # the model's apply on the test features and the top five
    "evaluation": [r"^jit__block_apply", r"^jit__block_step", r"^jit_top_k$", r"^jit___call__$"],
}


def program_seed(seed: int) -> int:
    return seed % (2**32 - 5)


def place_data(data: dict) -> dict:
    """``run`` stacks and moves the images chunk by chunk itself, so they stay
    on the host."""
    return data


def _config(conf: dict, seed: int, stem: str | None):
    from keystone_tpu.workloads import imagenet_sift_lcs_fv as inet

    return inet.ImageNetSiftLcsFVConfig(
        lam=conf["lam"],
        mixture_weight=conf["mixture_weight"],
        desc_dim=conf["desc_dim"],
        vocab_size=conf["vocab_size"],
        sift_scale_step=conf["sift_scale_step"],
        lcs_stride=conf["lcs_stride"],
        lcs_border=conf["lcs_border"],
        lcs_patch=conf["lcs_patch"],
        num_pca_samples=conf["num_pca_samples"],
        num_gmm_samples=conf["num_gmm_samples"],
        num_classes=conf["num_classes"],
        seed=program_seed(seed),
        pipeline_file=stem,
    )


def _split(part: dict):
    from keystone_tpu.loaders.image_loaders import LabeledImages

    return LabeledImages(part["x"], np.asarray(part["y"], np.int32), [str(i) for i in range(len(part["x"]))])


def _weighted_solve_unchanged():
    """``benchmark/lib/faults.state_unchanged`` plants its fault in the block
    solver's dispatch (``solvers.block._execute_fused_bcd``), which this
    pipeline's solver never calls.  While that fault is planted, the same
    fault stands in the weighted solver's own dispatch: the models handed
    back at their initial zeros, the intercept the labels' joint mean.
    Returns a function that takes it out again."""
    import jax.numpy as jnp

    from keystone_tpu.solvers import block, weighted

    if block._execute_fused_bcd.__module__ == block.__name__:
        return lambda: None
    real = weighted._execute_fused_bwls

    def unchanged(plan, args, statics):
        labels_sorted, joint_label_mean = args[1], args[7]
        widths = statics[4]
        return (
            jnp.zeros((len(widths), max(widths), labels_sorted.shape[1]), labels_sorted.dtype),
            joint_label_mean,
        )

    weighted._execute_fused_bwls = unchanged

    def restore():
        weighted._execute_fused_bwls = real

    return restore


def fit(conf: dict, data: dict, seed: int, stem: str) -> dict:
    from keystone_tpu.core.trace import metrics
    from keystone_tpu.workloads import imagenet_sift_lcs_fv as inet

    for old in glob.glob(stem + ".*"):
        os.remove(old)
    before = metrics.get("bwls.class_solves")
    restore = _weighted_solve_unchanged()
    try:
        results = inet.run(_config(conf, seed, stem), _split(data["train"]), _split(data["test"]))
    finally:
        restore()
    blocks = -(-2 * 2 * conf["desc_dim"] * conf["vocab_size"] // conf["solver_block"])
    solved, due = metrics.get("bwls.class_solves") - before, conf["num_classes"] * blocks * conf["num_epochs"]
    if solved != due:
        raise RuntimeError(f"the fit solved {solved} class systems, not {due}")
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
    """The fitted chain, the model and the test scores as the fit handed them
    back; the samples it drew and the features it solved on, made again by
    the program's chunk helpers with the fit's seed and chain (the chunk
    programs of the window on the same images); one EM step of the program
    from each branch's fitted mixture.  What is large stays on the device,
    where the reference reads it."""
    import jax.numpy as jnp

    from keystone_tpu.solvers.gmm import _em_step
    from keystone_tpu.workloads import fv_common, imagenet_sift_lcs_fv as inet

    from benchmark.lib.manifest import load_module

    reference = load_module("reference", REFERENCE)
    res = out["results"]
    chain = res["pipeline"]
    ic = _config(conf, out["seed"], None)
    branches = inet.descriptor_branches(ic)
    names = [b.name for b in branches]
    train, test = data["train"]["x"], data["test"]["x"]

    plan = fv_common.plan_chunks(train, branches, ic.desc_dim, ic.vocab_size)
    draws = [list(inet.branch_draws(ic, plan, b, name).values()) for b, name in enumerate(names)]
    sampled = fv_common.sample_descriptor_columns(plan, train, branches, draws)
    fitted, centres = {}, {}
    for name, (pca_samples, gmm_raw) in zip(names, sampled):
        pca, gmm = chain[f"{name}_pca"], chain[f"{name}_gmm"]
        centre = chain[f"{name}_centre"].centre if f"{name}_centre" in chain else None
        centres[name] = centre
        gmm_samples = inet._prepare_rows(inet.branch_preparation(name, centre), gmm_raw) @ pca.pca_mat
        floor = 1e-3 * jnp.mean(jnp.var(gmm_samples, axis=0))
        stepped = _em_step(gmm_samples, gmm.means, gmm.variances, gmm.weights, floor, 1 << 18)[:3]
        fitted[name] = {
            "pca_samples": pca_samples,
            "gmm_raw": gmm_raw,
            "pca_mat": np.asarray(pca.pca_mat),
            "centre": None if centre is None else np.asarray(centre),
            "gmm_samples": gmm_samples,
            "gmm": (np.asarray(gmm.means), np.asarray(gmm.variances), np.asarray(gmm.weights)),
            "gmm_iterations": res.get("gmm_iterations", {}).get(name, 0),
            "em_step": stepped,
        }
    del sampled

    pcas = [inet.branch_projection(name, chain[f"{name}_pca"], centres[name]) for name in names]
    gmms = [chain[f"{name}_gmm"] for name in names]
    train_features = _in_image_order(
        fv_common.featurize_chunks(plan, train, branches, pcas, gmms), plan.order
    )
    test_plan = fv_common.plan_chunks(test, branches, ic.desc_dim, ic.vocab_size)
    test_features = _in_image_order(
        fv_common.featurize_chunks(test_plan, test, branches, pcas, gmms), test_plan.order
    )
    model = chain["model"]
    return {
        "compare_rows": reference._voc.compare_rows(conf, train, seed),
        "sample_seed": ic.seed,
        "branches": fitted,
        "train_features": train_features,
        "test_features": test_features,
        "weights": np.concatenate([np.asarray(x) for x in model.xs], axis=0),
        "intercept": np.asarray(model.b),
        "test_scores": np.asarray(res["test_scores"]),
        "top5_error": float(res["top5_err_percent"]) / 100.0,
        "test_error": float(res["top5_err_percent"]),
    }
