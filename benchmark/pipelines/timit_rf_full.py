"""TimitPipeline at its documented 50 blocks, as the benchmark drives it:
whole fits through ``keystone_tpu.workloads.timit.run`` itself (the entry the
CLI reaches), which hands the solver a block source and streams the test
split, and hands back the model, the chains, the scores its evaluator last
saw and the ``FitReport``.  ``pipelines/timit_rf.py`` drives a copy of an
older ``run``'s calls and holds every block; this file shares its seed rule
and its data's place, nothing else."""

from __future__ import annotations

import numpy as np

REFERENCE = "timit_rf_full"
COUNTS = "timit_rf_full"
DATAGEN = "gaussian_classes"

#: the moments pass and the programs that make a test block and draw the
#: chains are the featurizer's; the fused solve (which now holds the making
#: of every training block) and the stepwise tier's programs the solver's;
#: the streamed apply's step, the argmax and the confusion counts the
#: evaluation's
PROGRAMS = {
    "featurizers": [r"^jit__block_moments$", r"^jit__make_block$", r"^jit__draw_cosine_blocks$"],
    "solvers": [r"^jit__fused_bcd_impl$", r"^jit__bcd_", r"^jit__hold_blocks$"],
    "evaluation": [
        r"^jit__block_step$", r"^jit__confusion_counts$", r"^jit__argmax$", r"^jit_argmax$",
        r"^jit_dynamic_slice$",
    ],
}


def program_seed(seed: int) -> int:
    return seed % (2**32 - 5)


def place_data(data: dict) -> dict:
    """The rows are made on the device and stay there."""
    return data


def require_block_source():
    """The configuration states that no design matrix is held.  A program
    whose solver takes only blocks that exist makes all fifty of both splits
    first and runs out of device memory before the thirtieth (29 x 537 MB);
    such a program cannot run this configuration, and says so before any
    work."""
    from keystone_tpu.solvers import block

    if not hasattr(block, "BlockSource"):
        raise SystemExit(
            "this program's block solver takes no block source "
            "(solvers/block.BlockSource): it cannot run timit_rf_50, whose "
            "26.8 GB design matrix fits no chip"
        )
    return block


def _state_unchanged_reaches_a_source(block):
    """``benchmark/lib/faults.state_unchanged`` stands in for the block
    solver's dispatch and reads the block means off a design matrix
    (``jnp.mean(x, axis=0)``).  A made fit's ``x`` is a block source; while
    that fault is planted, it is handed the source's own means as a one-row
    matrix, whose column mean they are.  Returns a function that takes the
    shim out again."""
    planted = block._execute_fused_bcd
    if planted.__module__ == block.__name__:
        return lambda: None

    def through(plan, dn, x, *rest):
        if isinstance(x, block.BlockSource):
            x = x.means.reshape(1, -1)
        return planted(plan, dn, x, *rest)

    block._execute_fused_bcd = through

    def restore():
        block._execute_fused_bcd = planted

    return restore


def fit(conf: dict, data: dict, seed: int, stem: str) -> dict:
    import jax

    block = require_block_source()
    from keystone_tpu.loaders.timit import TimitFeaturesData, TimitSplit
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
    split = {k: TimitSplit(data[k]["x"], data[k]["y"]) for k in ("train", "test")}
    restore = _state_unchanged_reaches_a_source(block)
    try:
        results = timit.run(tc, TimitFeaturesData(split["train"], split["test"]))
    finally:
        restore()
    jax.block_until_ready(results["test_scores"])
    return {"results": results, "rows": int(data["train"]["x"].shape[0])}


def fit_report(out: dict) -> dict:
    rep = out["results"]["fit_report"]
    return {
        "tier": rep.chosen,
        "denials": list(rep.denials),
        "oom_retries": list(rep.oom_retries),
    }


def produced(out: dict, conf: dict, data: dict, seed: int) -> dict:
    results = out["results"]
    scaler = results["featurizers"].nodes[-1]  # the stacked chains' scalers
    return {
        "test_scores": np.asarray(results["test_scores"]),
        "test_predictions": np.asarray(results["test_predictions"]),
        "test_error": float(results["test_error"]),
        "feature_mean": np.asarray(scaler.mean),
        "feature_std": np.asarray(scaler.std),
    }
