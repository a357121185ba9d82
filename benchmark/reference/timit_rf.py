"""Plain reference of TimitPipeline (reference TimitPipeline.scala:20-115).

Per block of cosine features: ``cos(x W^T + b)`` with W Gaussian times
gamma and b uniform on [0, 2 pi), then a column scaler; block coordinate
descent over the blocks; scores on the test rows.  Float32 with
full-precision products.  The draws of W and b follow the configuration's
``sampling`` recipe (a chain of ``jax.random.split`` from ``PRNGKey(seed)``);
nothing else is shared with the program.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.lib.manifest import load_module
from benchmark.lib.precision import mm

_linear = load_module("reference", "linear")
block_least_squares = _linear.block_least_squares
indicators = _linear.indicators
fit_scaler = _linear.fit_scaler
_rel = _linear.rel_gap


def random_features(conf: dict, seed: int) -> list:
    """[(W [D, dim], b [D])] per block, by the configuration's recipe."""
    key = jax.random.PRNGKey(seed)
    shape = (conf["num_cosine_features"], conf["dimension"])
    out = []
    for _ in range(conf["num_cosines"]):
        key, sub = jax.random.split(key)
        kw, kb = jax.random.split(sub)
        if conf["rf_type"] != "gaussian":
            raise ValueError("the reference draws Gaussian W only")
        w = jax.random.normal(kw, shape, jnp.float32) * conf["gamma"]
        b = jax.random.uniform(kb, shape[:1], jnp.float32) * (2.0 * jnp.pi)
        out.append((w, b))
    return out


def fit(conf: dict, data: dict, seed: int, precision: str = "highest") -> dict:
    xtr, xte = jnp.asarray(data["train"]["x"]), jnp.asarray(data["test"]["x"])
    blocks, test_blocks, means, stds = [], [], [], []
    for w, b in random_features(conf, seed):
        feats = jnp.cos(mm(xtr, w.T, precision) + b)
        mean, std = fit_scaler(feats)
        blocks.append((feats - mean) / std)
        test_blocks.append((jnp.cos(mm(xte, w.T, precision) + b) - mean) / std)
        means.append(mean)
        stds.append(std)
    y = indicators(data["train"]["y"], conf["num_classes"])
    models, mus, intercept = block_least_squares(
        blocks, y, conf["lam"], conf["num_epochs"], precision
    )
    scores = (
        sum(mm(t - mu, m, precision) for t, mu, m in zip(test_blocks, mus, models))
        + intercept
    )
    pred = np.asarray(jnp.argmax(scores, axis=1))
    return {
        "test_scores": np.asarray(scores),
        "test_predictions": pred,
        "test_error": 100.0 * float(np.mean(pred != data["test"]["y"])),
        "feature_mean": np.asarray(jnp.stack(means)),
        "feature_std": np.asarray(jnp.stack(stds)),
    }


def compare(conf: dict, data: dict, seed: int, produced: dict, ref: dict) -> dict:
    """Every test row's scores, as the timed fit's evaluator saw them,
    against the reference's."""
    theirs = ref["test_scores"].astype(np.float64)
    diff = produced["test_scores"].astype(np.float64) - theirs
    rms = float(np.sqrt(np.mean(theirs**2)))
    return {
        "feature_mean_gap": _rel(produced["feature_mean"], ref["feature_mean"]),
        "feature_std_gap": _rel(produced["feature_std"], ref["feature_std"]),
        "scores_rms_gap": float(np.sqrt(np.mean(diff**2))) / rms,
        "scores_max_gap": float(np.max(np.abs(diff))) / rms,
        "pred_disagree": float(
            np.mean(produced["test_predictions"] != ref["test_predictions"])
        ),
        "test_error_gap": abs(float(produced["test_error"]) - ref["test_error"]),
    }
