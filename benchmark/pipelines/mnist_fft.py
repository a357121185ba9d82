"""MnistRandomFFT at its option parser's defaults, as the benchmark drives
it: whole fits through ``keystone_tpu.workloads.mnist_random_fft.run``
itself (the entry the CLI reaches), which hands the solver a block source,
streams both splits through the fitted model, and hands back the model, the
stacked featurizer, the scores its evaluators last saw and the
``FitReport``."""

from __future__ import annotations

import numpy as np

from benchmark.lib.manifest import load_module

#: ``lib/faults.state_unchanged`` reaching a source-fed fit, as TIMIT's made
#: cell stands it in
_state_unchanged_reaches_a_source = load_module(
    "pipelines", "timit_rf_full"
)._state_unchanged_reaches_a_source

REFERENCE = "mnist_fft"
COUNTS = "mnist_fft"
DATAGEN = "digits_like"

#: the moments pass, the program that makes a block for either split's
#: streamed apply and the draw of the signs are the featurizer's; the fused
#: solve (which holds the making of every training block) and the stepwise
#: tier's programs the solver's; the streamed apply's step, the argmax and
#: the confusion counts the evaluation's
PROGRAMS = {
    "featurizers": [r"^jit__block_moments$", r"^jit__make_block$", r"^jit__draw_sign_blocks$"],
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
    whose MnistRandomFFT run hands the solver its blocks as arrays makes all
    fifty of the training split first and runs out of device memory before
    the thirtieth (29 x 492 MB beside the rest); such a program cannot run
    this configuration, and says so before any work."""
    from keystone_tpu.solvers import block
    from keystone_tpu.workloads import mnist_random_fft

    if not hasattr(mnist_random_fft, "draw_block_featurizers") or not hasattr(
        block, "BlockSource"
    ):
        raise SystemExit(
            "this program's MnistRandomFFT run hands the solver its blocks as "
            "arrays (no workloads.mnist_random_fft.draw_block_featurizers): it "
            "cannot run mnist_fft_200, whose 24.6 GB design matrix fits no chip"
        )
    return block


def fit(conf: dict, data: dict, seed: int, stem: str) -> dict:
    import jax

    block = require_block_source()
    from keystone_tpu.loaders.csv_loader import LabeledData
    from keystone_tpu.workloads import mnist_random_fft

    mc = mnist_random_fft.MnistRandomFFTConfig(
        num_ffts=conf["num_ffts"],
        block_size=conf["block_size"],
        lam=conf["lam"],
        seed=program_seed(seed),
        mnist_image_size=conf["mnist_image_size"],
        num_classes=conf["num_classes"],
    )
    split = {k: LabeledData(labels=data[k]["y"], data=data[k]["x"]) for k in ("train", "test")}
    restore = _state_unchanged_reaches_a_source(block)
    try:
        results = mnist_random_fft.run(mc, split["train"], split["test"])
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
    """What the last timed fit produced, and the features of the blocks
    ``compare.feature_blocks`` on the first ``compare.feature_rows``
    training rows, made by the program that makes a block for the streamed
    apply."""
    import jax.numpy as jnp

    from keystone_tpu.solvers import block

    results = out["results"]
    model, featurizers = results["model"], results["featurizers"]
    rows = data["train"]["x"][: conf["compare"]["feature_rows"]]
    source = block.BlockSource(rows, featurizers)
    return {
        "test_scores": np.asarray(results["test_scores"]),
        "test_predictions": np.asarray(results["test_predictions"]),
        "test_error": float(results["test_error"]),
        "block_means": np.stack([np.asarray(s.mean) for s in model.feature_scalers]),
        "fft_features": np.stack(
            [np.asarray(block._make_block(source, jnp.int32(i))) for i in conf["compare"]["feature_blocks"]]
        ),
    }
