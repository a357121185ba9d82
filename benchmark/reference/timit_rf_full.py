"""Plain reference of TimitPipeline at its documented 50 blocks
(``timit_rf_50``: 204,800 cosine features of a 440-wide row; reference
TimitPipeline.scala:20-115), where no design matrix can exist: 32,768 x
204,800 float32 is 26.8 GB.

The mathematics is ``reference/timit_rf.py``'s and ``reference/linear.py``'s,
loaded from those files and not copied: the draws of W and b by the
configuration's ``sampling`` recipe, the product in a stated precision, the
column scaler, the block's factor and step, ``compare``.  What differs is
the order of the work, **a block at a time**, as the reference's own
``BlockLeastSquaresEstimator.fit(Seq[RDD], ...)`` takes its lazy chains
(BlockLinearMapper.scala:156-203):

* a block's features are *made again* from the rows whenever a sweep
  reaches the block, and dropped after its step: ``cos(x W^T + b)``, the
  block's own scaler, its own centring;
* the scaler's mean and deviation and the block's mean are a column's own,
  so they are computed when the block is first made and kept (three vectors
  a block), as is the block's Cholesky factor (the reference persists its
  grams the same way);
* in the last epoch a block's model is final when its step ends, and its
  share of the test scores is added then, from the test rows' block made
  there.

**Why the answer is the same:** every number is the same sum of the same
products as in the whole form, the scores' sum over the blocks in the same
order too; the forms differ in what is alive at once.
``tests/test_timit_full_cell.py`` holds the two together at 3 blocks.  Float32 with full-precision products;
``precision`` other than ``highest`` turns it into the control (every
product's operands rounded a tensor at a time).  Nothing of the program is
shared.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from benchmark.lib.manifest import load_module
from benchmark.lib.precision import mm

_whole = load_module("reference", "timit_rf")
_linear = load_module("reference", "linear")
compare = _whole.compare
random_features = _whole.random_features


def fit(conf: dict, data: dict, seed: int, precision: str = "highest") -> dict:
    xtr, xte = jnp.asarray(data["train"]["x"]), jnp.asarray(data["test"]["x"])
    y = _linear.indicators(data["train"]["y"], conf["num_classes"])
    intercept = jnp.mean(y, axis=0)
    residual = y - intercept
    lam = jnp.float32(conf["lam"])
    draws = random_features(conf, seed)  # 7.2 MB a block: kept
    state = [None] * len(draws)  # (mean, std, mu, chol, model) a block
    scores = jnp.zeros((xte.shape[0], y.shape[1]), jnp.float32)
    epochs = conf["num_epochs"]
    for epoch in range(epochs):
        for i, (w, b) in enumerate(draws):
            raw = jnp.cos(mm(xtr, w.T, precision) + b)
            if epoch == 0:
                mean, std = _linear.fit_scaler(raw)
                mu = jnp.mean((raw - mean) / std, axis=0)
                a = (raw - mean) / std - mu
                chol = _linear._block_factor(a, lam, precision=precision)
                model = jnp.zeros((w.shape[0], y.shape[1]), jnp.float32)
            else:
                mean, std, mu, chol, model = state[i]
                a = (raw - mean) / std - mu
            del raw
            residual, model = _linear._block_step(
                a, chol, residual, model, precision=precision
            )
            del a
            state[i] = (mean, std, mu, chol, model)
            if epoch == epochs - 1:
                test = (jnp.cos(mm(xte, w.T, precision) + b) - mean) / std
                scores = scores + mm(test - mu, model, precision)
                del test
    scores = scores + intercept
    pred = np.asarray(jnp.argmax(scores, axis=1))
    return {
        "test_scores": np.asarray(scores),
        "test_predictions": pred,
        "test_error": 100.0 * float(np.mean(pred != data["test"]["y"])),
        "feature_mean": np.asarray(jnp.stack([s[0] for s in state])),
        "feature_std": np.asarray(jnp.stack([s[1] for s in state])),
    }
