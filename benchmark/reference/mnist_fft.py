"""Plain reference of MnistRandomFFT at its option parser's defaults
(``mnist_fft_200``: 200 random-sign FFTs of a 784-pixel row, 102,400
features in fifty blocks of 2,048; reference MnistRandomFFT.scala:17-127),
where no design matrix can exist: 60,000 x 102,400 float32 is 24.6 GB.

* **The signs** by the configuration's ``sampling`` recipe (a chain of
  ``jax.random.split`` from ``PRNGKey(seed)``, one Bernoulli draw an FFT).
* **PaddedFFT by its definition**, not by an FFT routine: the real part of
  bin ``k < n/2`` of the row zero-padded to ``n = next_pow2(d)`` is
  ``sum_j x_j cos(2 pi j k / n)`` over the ``d`` pixels, so an FFT's
  features are the signed rows times a ``[d, n/2]`` cosine table built in
  float64 on the host (PaddedFFT.scala:13-21), then ``max(0, .)``
  (LinearRectifier.scala:11-16).
* **The solve a block at a time** (BlockLinearMapper.scala:147-204 with one
  iteration, as the reference's ``fit(Seq[RDD], ...)`` takes its lazy
  chains): a block's features made, its own column means, its gram and
  float32 Cholesky, one step, the block dropped; the test rows' block made
  then and its share of the scores added.  ``reference/linear.py``'s
  factor and step, loaded from that file and not copied.  A column zero on
  every training row (a rectifier never positive) leaves the system
  singular at lambda 0: it is given a unit diagonal, so its weight is 0,
  as the configuration's ``zero_columns`` states.

Float32 with full-precision products; ``precision`` other than ``highest``
turns it into the control (every product's operands rounded a tensor at a
time).  Nothing of the program is shared.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.lib.manifest import load_module
from benchmark.lib.precision import mm

_linear = load_module("reference", "linear")
_rel = _linear.rel_gap


def padded_width(d: int) -> int:
    return 1 << (d - 1).bit_length()


def cosine_table(d: int) -> jnp.ndarray:
    """``cos(2 pi j k / n)`` for ``j < d``, ``k < n / 2``, in float64 on the
    host, handed over as float32."""
    n = padded_width(d)
    j, k = np.meshgrid(np.arange(d), np.arange(n // 2), indexing="ij")
    return jnp.asarray(np.cos(2.0 * np.pi * ((j * k) % n) / n), jnp.float32)


def sign_blocks(conf: dict, seed: int) -> list:
    """``[f, d]`` signs a block, by the configuration's recipe."""
    f = conf["block_size"] // 512
    blocks = -(-conf["num_ffts"] // f)
    key = jax.random.PRNGKey(seed)
    out = []
    for _ in range(blocks):
        rows = []
        for _ in range(f):
            key, sub = jax.random.split(key)
            bits = jax.random.bernoulli(sub, 0.5, (conf["mnist_image_size"],))
            rows.append(bits.astype(jnp.float32) * 2.0 - 1.0)
        out.append(jnp.stack(rows))
    return out


def block_features(x, signs, table, precision: str):
    """A block's features: each FFT's signed rows times the cosine table,
    rectified, side by side."""
    return jnp.concatenate(
        [jnp.maximum(0.0, mm(x * s, table, precision)) for s in signs], axis=1
    )


def fit(conf: dict, data: dict, seed: int, precision: str = "highest") -> dict:
    xtr, xte = jnp.asarray(data["train"]["x"]), jnp.asarray(data["test"]["x"])
    table = cosine_table(conf["mnist_image_size"])
    y = _linear.indicators(data["train"]["y"], conf["num_classes"])
    intercept = jnp.mean(y, axis=0)
    residual = y - intercept
    lam = jnp.float32(conf["lam"])
    cmp = conf["compare"]
    scores = jnp.zeros((xte.shape[0], y.shape[1]), jnp.float32)
    means, features = [], {}
    for i, signs in enumerate(sign_blocks(conf, seed)):
        raw = block_features(xtr, signs, table, precision)
        if i in cmp["feature_blocks"]:
            features[i] = np.asarray(raw[: cmp["feature_rows"]])
        mu = jnp.mean(raw, axis=0)
        a = raw - mu
        zero = jnp.all(raw == 0, axis=0).astype(jnp.float32)
        del raw
        chol = _linear._block_factor(a, lam + zero, precision=precision)
        model = jnp.zeros((a.shape[1], y.shape[1]), jnp.float32)
        residual, model = _linear._block_step(a, chol, residual, model, precision=precision)
        del a, chol
        test = block_features(xte, signs, table, precision)
        scores = scores + mm(test - mu, model, precision)
        del test
        means.append(mu)
    scores = scores + intercept
    pred = np.asarray(jnp.argmax(scores, axis=1))
    return {
        "test_scores": np.asarray(scores),
        "test_predictions": pred,
        "test_error": 100.0 * float(np.mean(pred != data["test"]["y"])),
        "block_means": np.asarray(jnp.stack(means)),
        "fft_features": np.stack([features[i] for i in cmp["feature_blocks"]]),
    }


def compare(conf: dict, data: dict, seed: int, produced: dict, ref: dict) -> dict:
    """The compared blocks' features, the solver's block means and every
    test row's scores, as the timed fit's evaluator saw them, against the
    reference's."""
    ours = produced["fft_features"].astype(np.float64)
    theirs_f = ref["fft_features"].astype(np.float64)
    theirs = ref["test_scores"].astype(np.float64)
    diff = produced["test_scores"].astype(np.float64) - theirs
    rms = float(np.sqrt(np.mean(theirs**2)))
    return {
        "fft_feature_gap": float(np.sqrt(np.mean((ours - theirs_f) ** 2) / np.mean(theirs_f**2))),
        "block_mean_gap": _rel(produced["block_means"], ref["block_means"]),
        "scores_rms_gap": float(np.sqrt(np.mean(diff**2))) / rms,
        "scores_max_gap": float(np.max(np.abs(diff))) / rms,
        "pred_disagree": float(
            np.mean(produced["test_predictions"] != ref["test_predictions"])
        ),
        "test_error_gap": abs(float(produced["test_error"]) - ref["test_error"]),
    }
