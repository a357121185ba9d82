"""Plain reference of RandomPatchCifar at widths whose features no one chip
holds (``cifar_rp_10k_mesh4``: 25,000 x 80,000 float32 is 8 GB, beside the
copies a scaler and a solve make of it).

The mathematics is ``reference/cifar_rp.py``'s and ``reference/linear.py``'s,
loaded from those files and not copied: filter learning, the patch matrix,
the row normalisation, the pool bounds, the product in a stated precision,
the column scaler, the block's factor and step, ``compare``.  What differs
is the order of the work, **a solver block at a time**, on one device, with
no mesh and nothing of the program:

* the features are *made again* a block at a time, and never kept, neither
  on the host (8 GB would have to cross device to host and back, at a rate
  this machine has not shown) nor on the device.  A block is 4,096
  neighbouring columns of the whole form's ``[n, pool y, pool x, sign,
  filter]`` order, so it is at most a few runs of (one pool cell, one sign,
  a range of filters): each run is the patches of that pool cell alone,
  against that range of filters alone, rectified on that side alone and
  summed;
* the scaler's mean and deviation are a column's own, so a block's are
  computed when the block is made;
* block coordinate descent visits a block once an epoch in order and keeps
  only the residual between blocks, so the block's gram, Cholesky factor
  and step run on the block just made; in the last epoch the block's
  model is final when its step ends, and its share of the training and
  test scores is added then.

**Why the answer is the same:** every number is the same sum of the same
products as in the whole form; only float32 sums over the patches of a pool
cell (a slice summed whole, here a cropped image's patches summed whole) and
over the blocks of the scores run in another order.  ``tests/
test_mesh_cell.py`` holds the two forms together at a size where both fit.

``precision`` other than ``highest`` turns it into the control, as in the
whole form (the products' operands rounded a tensor at a time: here a
chunk's patches and a window of filters).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from benchmark.lib.manifest import load_module
from benchmark.lib.precision import mm

_whole = load_module("reference", "cifar_rp")
_linear = load_module("reference", "linear")
compare = _whole.compare
learn_filters = _whole.learn_filters


def _bounds(conf: dict) -> list:
    out = conf["image_size"] - conf["patch_size"] + 1
    return _whole._pool_bounds(out, conf["pool_size"], conf["pool_stride"])


def block_runs(conf: dict, lo: int, hi: int) -> list:
    """Columns ``[lo, hi)`` of the whole form's feature order as runs
    ``(pool y, pool x, sign, first filter, end filter)``: a column is
    ``((py * pools + px) * 2 + side) * F + filter``."""
    f = conf["num_filters"]
    pools = len(_bounds(conf))
    runs = []
    col = lo
    while col < hi:
        group, f0 = divmod(col, f)
        f1 = min(f, f0 + hi - col)
        cell, side = divmod(group, 2)
        py, px = divmod(cell, pools)
        runs.append((py, px, 1.0 if side == 0 else -1.0, f0, f1))
        col += f1 - f0
    return runs


@functools.partial(
    jax.jit, static_argnames=("ps", "span", "window", "precision")
)
def _run_features(chunks, filters, means, alpha, y0, x0, f0, sign, *, ps, span, window, precision):
    """``chunks`` [c, m, H, W, C] -> [c * m, window]: of every image the
    sum over one pool cell (``span`` output positions a side from
    ``(y0, x0)``) of ``max(0, sign * z - alpha)`` for the ``window``
    filters from ``f0``.  The cell, the side and the filters are values,
    not shapes: one program serves every run of a split."""
    filt = lax.dynamic_slice_in_dim(filters, f0, window, axis=0)
    _, m, _, _, c = chunks.shape
    side = span + ps - 1

    def one(images):
        crop = lax.dynamic_slice(images, (0, y0, x0, 0), (m, side, side, c))
        rows = _whole._patch_matrix(crop, ps, x_outer=False)
        rows = _whole._normalize_rows(rows, 10.0) - means
        z = sign * mm(rows, filt.T, precision)
        return jnp.maximum(0.0, z - alpha).sum(axis=1)

    return lax.map(one, chunks).reshape(-1, window)


class _Split:
    """One split's images on the device, in chunks, and its rows."""

    def __init__(self, images: np.ndarray, chunk: int):
        self.rows = images.shape[0]
        pad = (-self.rows) % chunk
        if pad:
            images = np.pad(images, ((0, pad), (0, 0), (0, 0), (0, 0)))
        self.chunks = jnp.asarray(images.reshape((-1, chunk) + images.shape[1:]))


def block_features(conf, split: _Split, filters, means, lo: int, hi: int, precision: str):
    """Columns ``[lo, hi)`` of the whole form's features of ``split``."""
    bounds = _bounds(conf)
    f = conf["num_filters"]
    window = min(conf["solver_block"], f)
    parts = []
    for py, px, sign, f0, f1 in block_runs(conf, lo, hi):
        (y0, y1), (x0, x1) = bounds[py], bounds[px]
        if y1 - y0 != x1 - x0:
            raise ValueError("the reference pools square cells only")
        start = min(f0, f - window)
        got = _run_features(
            split.chunks, filters, means, conf["alpha"], y0, x0, start, sign,
            ps=conf["patch_size"], span=y1 - y0, window=window, precision=precision,
        )
        parts.append(got[: split.rows, f0 - start : f1 - start])
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)


def fit(conf: dict, data: dict, seed: int, precision: str = "highest") -> dict:
    """The reference's fitted chain and its answers on the test split, as
    numpy arrays under the names the pipeline's ``produced`` uses."""
    chunk = conf.get("reference_chunk", 250)
    width = conf["solver_block"]
    train, test = data["train"], data["test"]
    filters, wmeans = learn_filters(conf, train["x"], seed, precision)
    filters = filters.reshape(filters.shape[0], -1)
    pools = len(_bounds(conf))
    d = pools * pools * 2 * conf["num_filters"]
    cuts = [(lo, min(lo + width, d)) for lo in range(0, d, width)]
    tr, te = _Split(train["x"], chunk), _Split(test["x"], chunk)

    y = _linear.indicators(train["y"], conf["num_classes"])
    intercept = jnp.mean(y, axis=0)
    residual = y - intercept
    lam = jnp.float32(conf["lam"])
    state = [None] * len(cuts)  # (mean, std, mu, chol, model) a block
    train_scores = jnp.zeros_like(y) + intercept
    test_scores = jnp.zeros((te.rows, y.shape[1]), jnp.float32) + intercept
    epochs = conf["num_epochs"]
    for epoch in range(epochs):
        for i, (lo, hi) in enumerate(cuts):
            raw = block_features(conf, tr, filters, wmeans, lo, hi, precision)
            if epoch == 0:
                mean, std = _linear.fit_scaler(raw)
                mu = jnp.mean((raw - mean) / std, axis=0)
                a = (raw - mean) / std - mu
                chol = _linear._block_factor(a, lam, precision=precision)
                model = jnp.zeros((hi - lo, y.shape[1]), jnp.float32)
            else:
                mean, std, mu, chol, model = state[i]
                a = (raw - mean) / std - mu
            del raw
            residual, model = _linear._block_step(
                a, chol, residual, model, precision=precision
            )
            state[i] = (mean, std, mu, chol, model)
            if epoch == epochs - 1:
                train_scores = train_scores + mm(a, model, precision)
                del a
                raw = block_features(conf, te, filters, wmeans, lo, hi, precision)
                test_scores = test_scores + mm((raw - mean) / std - mu, model, precision)
                del raw
    chain = {
        "filters": filters, "wmeans": wmeans,
        "scaler_mean": jnp.concatenate([s[0] for s in state]),
        "scaler_std": jnp.concatenate([s[1] for s in state]),
        "weights": jnp.concatenate([s[4] for s in state], axis=0),
        "feature_means": jnp.concatenate([s[2] for s in state]),
        "intercept": intercept,
    }
    train_pred = np.asarray(jnp.argmax(train_scores, axis=1))
    test_pred = np.asarray(jnp.argmax(test_scores, axis=1))
    out = {k: np.asarray(v) for k, v in chain.items()}
    out.update(
        test_predictions=test_pred,
        test_error=100.0 * float(np.mean(test_pred != test["y"])),
        train_error=100.0 * float(np.mean(train_pred != train["y"])),
        test_scores=np.asarray(test_scores),
    )
    return out
