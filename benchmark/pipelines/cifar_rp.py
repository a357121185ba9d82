"""RandomPatchCifar as the benchmark drives it: whole fits through
``keystone_tpu.workloads.cifar_random_patch.run`` with a checkpoint stem of
its own for every fit, so that ``run`` fits and does not restore."""

from __future__ import annotations

import glob
import os

import numpy as np

#: names under which this pipeline's references, counts and data are found
REFERENCE = "cifar_rp"
COUNTS = "cifar_rp"
DATAGEN = "class_images"

#: device programs by layer, as regular expressions on the XLA module's
#: name (today's names; the ``tracing`` issue gives them ``named_scope``s)
PROGRAMS = {
    # the jit of conv_pipe.__call__ (its module is named ``jit___call``), the
    # chunks' concatenation, the scaler's moments and its eager apply, and
    # filter learning's eager steps
    "featurizers": [
        r"^jit___call", r"^jit_concatenate$", r"^jit_sharded_moments_jit$",
        r"^jit_true_divide$", r"^jit_subtract$", r"^jit_svd$", r"^jit__shuffle$",
        r"^jit_gather$", r"^jit__take$", r"^jit__var$", r"^jit__mean$",
        r"^jit_reshape$", r"^jit_sqrt$",
    ],
    "solvers": [r"^jit__fused_bcd_impl$", r"^jit__bcd_", r"^jit__hs_block", r"^jit__pad$"],
    # the model's eager apply and the argmax: no metric reads them yet
    "evaluation": [r"^jit_matmul$", r"^jit_dynamic_slice$", r"^jit_add$", r"^jit__argmax$", r"^jit_argmax$"],
}


def program_seed(seed: int) -> int:
    return seed % (2**32 - 5)


def place_data(data: dict) -> dict:
    """``run`` slices the images with numpy and moves them chunk by chunk
    itself, so they stay on the host."""
    return data


def fit(conf: dict, data: dict, seed: int, stem: str) -> dict:
    from keystone_tpu.loaders.cifar import LabeledImageBatch
    from keystone_tpu.workloads import cifar_random_patch as cifar

    for old in glob.glob(stem + ".*"):
        os.remove(old)
    rc = cifar.RandomCifarConfig(
        num_filters=conf["num_filters"],
        patch_size=conf["patch_size"],
        patch_steps=conf["patch_steps"],
        pool_size=conf["pool_size"],
        pool_stride=conf["pool_stride"],
        alpha=conf["alpha"],
        lam=conf["lam"],
        seed=program_seed(seed),
        num_classes=conf["num_classes"],
        whitener_size=conf["whitener_size"],
        featurize_chunk=conf["featurize_chunk"],
        pipeline_file=stem,
    )
    train = LabeledImageBatch(data["train"]["x"], data["train"]["y"])
    test = LabeledImageBatch(data["test"]["x"], data["test"]["y"])
    results = cifar.run(rc, train, test)
    return {"results": results, "stem": stem, "rows": len(train)}


def fit_report(out: dict) -> dict:
    solver = out["results"].get("solver") or {}
    return {
        "tier": solver.get("tier"),
        "denials": list(solver.get("denials", ())),
        "oom_retries": list(solver.get("oom_retries", ())),
    }


def _served_scores(conv, scaler, model, images: np.ndarray, chunk: int):
    """The saved chain's raw scores on ``images`` through the program's own
    nodes, chunk by chunk at the timed chunk shape, as the program's
    restored path applies a chain (``_apply_servable_chunked``), short of
    the argmax that ``run`` keeps to itself."""
    import jax
    import jax.numpy as jnp

    feat = jax.jit(conv.__call__)
    outs = []
    for i in range(0, images.shape[0], chunk):
        block = images[i : i + chunk]
        pad = chunk - block.shape[0]
        if pad:
            block = np.pad(block, ((0, pad), (0, 0), (0, 0), (0, 0)))
        scores = np.asarray(model(scaler(feat(jnp.asarray(block)))))
        outs.append(scores[: chunk - pad] if pad else scores)
    return np.concatenate(outs, axis=0)


def produced(out: dict, conf: dict, data: dict, seed: int) -> dict:
    """What the fit made: the chain it saved, read back from its checkpoint,
    the predictions and errors it reported, and the saved chain's scores on
    a sample of test rows drawn from the seed."""
    from keystone_tpu.core.checkpoint import load_pipeline

    from benchmark.lib.sample import pick_rows

    conv, scaler, model = load_pipeline(out["stem"]).nodes[:3]
    filters = np.asarray(conv.conv.filters)
    res = out["results"]
    rows = pick_rows(len(data["test"]["y"]), conf["compare"]["score_rows"], seed)
    return {
        "score_rows": rows,
        "test_scores_sample": _served_scores(
            conv, scaler, model, data["test"]["x"][rows], conf["featurize_chunk"]
        ),
        "filters": filters.reshape(filters.shape[0], -1),
        "wmeans": np.asarray(conv.conv.whitener_means),
        "scaler_mean": np.asarray(scaler.mean),
        "scaler_std": np.asarray(scaler.std),
        "weights": np.concatenate([np.asarray(x) for x in model.xs], axis=0),
        "feature_means": np.concatenate(
            [np.asarray(s.mean) for s in model.feature_scalers]
        ),
        "intercept": np.asarray(model.b),
        "test_predictions": np.asarray(res["test_predictions"]),
        "test_error": float(res["test_error"]),
        "train_error": float(res["train_error"]),
    }

