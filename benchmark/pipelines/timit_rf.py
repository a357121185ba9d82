"""TimitPipeline as the benchmark drives it.

``keystone_tpu.workloads.timit.run`` hands back only ``test_error``, and what
is compared has to be what the timed path produced.  So ``fit`` below is a
copy of ``run``'s own calls in ``run``'s order (no mesh), keeping the model
and the scores its evaluator saw.  Once ``run`` hands those back, drive
``run`` itself (PERF.md, Open questions)."""

from __future__ import annotations

import numpy as np

REFERENCE = "timit_rf"
COUNTS = "timit_rf"
DATAGEN = "gaussian_classes"

PROGRAMS = {
    # the eager steps of ``cos(x W^T + b)`` and of the scaler.  The
    # evaluator's eager subtract, matmul and add carry the same module names
    # and are counted here too, so ``featurize_dev_ms`` errs high
    "featurizers": [
        r"^jit_cos$", r"^jit_matmul$", r"^jit_add$", r"^jit_subtract$",
        r"^jit_true_divide$", r"^jit_sharded_moments_jit$", r"^jit_transpose$",
        r"^jit_multiply$", r"^jit_sqrt$", r"^jit__normal$", r"^jit__uniform$",
    ],
    "solvers": [r"^jit__fused_bcd_impl$", r"^jit__bcd_", r"^jit__hs_block", r"^jit_concatenate$"],
    "evaluation": [r"^jit__argmax$", r"^jit_argmax$", r"^jit_scatter-add$", r"^jit_dynamic_slice$"],
}


def program_seed(seed: int) -> int:
    return seed % (2**32 - 5)


def place_data(data: dict) -> dict:
    """The rows are made on the device and stay there."""
    return data


def fit(conf: dict, data: dict, seed: int, stem: str) -> dict:
    import jax
    import jax.numpy as jnp

    from keystone_tpu.core.logging import stage_timer
    from keystone_tpu.evaluation.multiclass import MulticlassClassifierEvaluator
    from keystone_tpu.ops.util import ClassLabelIndicatorsFromIntLabels, MaxClassifier
    from keystone_tpu.solvers.block import BlockLeastSquaresEstimator
    from keystone_tpu.workloads import timit

    tc = timit.TimitConfig(
        num_cosines=conf["num_cosines"],
        gamma=conf["gamma"],
        rf_type=conf["rf_type"],
        lam=conf["lam"],
        num_epochs=conf["num_epochs"],
        num_cosine_features=conf["num_cosine_features"],
        seed=program_seed(seed),
        num_classes=conf["num_classes"],
        dimension=conf["dimension"],
    )
    test_labels = data["test"]["y"]
    n_test = len(test_labels)
    train_data = jnp.asarray(data["train"]["x"])
    test_data = jnp.asarray(data["test"]["x"])

    # run()'s calls, in run()'s order; the spans are the benchmark's own
    with stage_timer("featurize"):
        batch_featurizer = timit.build_batch_featurizers(tc, train_data, None)
        training_batches = [f(train_data) for f in batch_featurizer]
        labels = ClassLabelIndicatorsFromIntLabels(tc.num_classes)(
            data["train"]["y"]
        )
        test_batches = [f(test_data) for f in batch_featurizer]
    with stage_timer("solve"):
        solver = BlockLeastSquaresEstimator(
            tc.num_cosine_features, tc.num_epochs, tc.lam
        )
        model = solver.fit(training_batches, labels, nvalid=None)
    seen: dict = {}

    def evaluator(pred):
        predicted = MaxClassifier()(pred[:n_test])
        ev = MulticlassClassifierEvaluator(predicted, test_labels, tc.num_classes)
        seen["scores"] = pred
        seen["predicted"] = predicted
        seen["test_error"] = 100.0 * ev.total_error

    with stage_timer("eval"):
        model.apply_and_evaluate(test_batches, evaluator)
        jax.block_until_ready(seen["scores"])
    return {
        "model": model,
        "featurizers": batch_featurizer,
        "seen": seen,
        "report": solver.last_fit_report,
        "rows": int(train_data.shape[0]),
    }


def fit_report(out: dict) -> dict:
    rep = out["report"]
    return {
        "tier": rep.chosen if rep is not None else None,
        "denials": list(rep.denials) if rep is not None else [],
        "oom_retries": list(rep.oom_retries) if rep is not None else [],
    }


def produced(out: dict, conf: dict, data: dict, seed: int) -> dict:
    scalers = [f.nodes[-1] for f in out["featurizers"]]
    seen = out["seen"]
    return {
        "test_scores": np.asarray(seen["scores"]),
        "test_predictions": np.asarray(seen["predicted"]),
        "test_error": float(seen["test_error"]),
        "feature_mean": np.stack([np.asarray(s.mean) for s in scalers]),
        "feature_std": np.stack([np.asarray(s.std) for s in scalers]),
    }

