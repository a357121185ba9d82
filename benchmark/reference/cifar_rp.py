"""Plain reference of RandomPatchCifar (reference RandomPatchCifar.scala).

Straightforward ``jax.numpy`` in float32 with full-precision products: a
patch matrix per image, each patch row normalised and whitened, one product
with the filter bank, the two-sided rectifier, sum pooling, the column
scaler, and block coordinate descent on the centred blocks.  It imports
nothing of the program and takes nothing the program made; what it shares
with the program is the configuration's recipe for which patches are drawn
(``sampling`` in the configuration's file).

``precision`` other than ``highest`` turns it into the control: the same
mathematics with every product's operands rounded lower.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.lib.manifest import load_module
from benchmark.lib.precision import mm
from benchmark.lib.sample import pick_rows

_linear = load_module("reference", "linear")
block_least_squares = _linear.block_least_squares
indicators = _linear.indicators
fit_scaler = _linear.fit_scaler
_rel = _linear.rel_gap


# -- patches -------------------------------------------------------------------


def _patch_matrix(images, ps: int, x_outer: bool):
    """[n, P, ps*ps*C] patch rows, element order (y, x, c).  ``x_outer``
    orders the P patches column first, as the reference's Windower does;
    otherwise row first, as a convolution's output is laid out."""
    n, h, w, c = images.shape
    oy, ox = h - ps + 1, w - ps + 1
    cols = [
        images[:, dy : dy + oy, dx : dx + ox, :]
        for dy in range(ps)
        for dx in range(ps)
    ]
    pat = jnp.stack(cols, axis=3)  # [n, oy, ox, ps*ps, c]
    if x_outer:
        pat = pat.transpose(0, 2, 1, 3, 4)
    return pat.reshape(n, oy * ox, ps * ps * c)


def _normalize_rows(mat, alpha: float):
    mean = jnp.mean(mat, axis=-1, keepdims=True)
    var = jnp.var(mat, axis=-1, keepdims=True, ddof=1)
    return (mat - mean) / jnp.sqrt(var + alpha)


def _sample_rows(mat, size: int, seed: int):
    """The configuration's ``sampling`` recipe: ``size`` rows without
    replacement by ``jax.random.choice`` under ``PRNGKey(seed)``."""
    n = mat.shape[0]
    if n <= size:
        return mat
    idx = jax.random.choice(jax.random.PRNGKey(seed), n, (size,), replace=False)
    return jnp.take(mat, idx, axis=0)


def learn_filters(conf: dict, train_images: np.ndarray, seed: int, precision: str):
    """(filters [F, d], whitener means [d])."""
    n, h, w, _ = train_images.shape
    ps = conf["patch_size"]
    per_image = (h - ps + 1) * (w - ps + 1)
    need = min(n, max(1, -(-4 * conf["whitener_size"] // per_image)))
    idx = np.random.default_rng(seed).permutation(n)[:need]
    subset = jnp.asarray(train_images[idx])
    patches = _patch_matrix(subset, ps, x_outer=True).reshape(need * per_image, -1)
    sampled = _sample_rows(patches, conf["whitener_size"], seed)
    base = _normalize_rows(sampled, 10.0)

    means = jnp.mean(base, axis=0)
    centred = base - means
    cov = mm(centred.T, centred, precision) / (base.shape[0] - 1.0)
    evals, evecs = jnp.linalg.eigh(cov)
    scale = (jnp.maximum(evals, 0.0) + 0.1) ** -0.5
    whitener = mm(evecs * scale, evecs.T, precision)

    picked = _sample_rows(base, conf["num_filters"], seed + 1)
    unnorm = mm(picked - means, whitener, precision)
    norms = jnp.linalg.norm(unnorm, axis=1, keepdims=True)
    filters = mm(unnorm / (norms + 1e-10), whitener.T, precision)
    return filters, means


# -- featurizer ----------------------------------------------------------------


def _pool_bounds(dim: int, size: int, stride: int) -> list:
    if size % 2:
        raise ValueError("the reference pools even sizes only")
    count = math.ceil((dim - size // 2) / stride)
    return [(i * stride, min(i * stride + size, dim)) for i in range(count)]


@functools.partial(jax.jit, static_argnames=("ps", "pool", "stride", "precision"))
def _featurize_chunk(images, filters, means, alpha, *, ps, pool, stride, precision):
    n, h, w, _ = images.shape
    oy, ox = h - ps + 1, w - ps + 1
    rows = _patch_matrix(images, ps, x_outer=False)
    rows = _normalize_rows(rows, 10.0) - means
    z = mm(rows, filters.T, precision).reshape(n, oy, ox, -1)
    rect = jnp.concatenate(
        [jnp.maximum(0.0, z - alpha), jnp.maximum(0.0, -z - alpha)], axis=-1
    )
    pooled = [
        [rect[:, y0:y1, x0:x1, :].sum(axis=(1, 2)) for x0, x1 in _pool_bounds(ox, pool, stride)]
        for y0, y1 in _pool_bounds(oy, pool, stride)
    ]
    out = jnp.stack([jnp.stack(r, axis=1) for r in pooled], axis=1)
    return out.reshape(n, -1)


def featurize(conf, images: np.ndarray, filters, means, precision: str, chunk: int):
    outs = []
    for i in range(0, images.shape[0], chunk):
        block = images[i : i + chunk]
        pad = chunk - block.shape[0]
        if pad:
            block = np.pad(block, ((0, pad), (0, 0), (0, 0), (0, 0)))
        feats = _featurize_chunk(
            jnp.asarray(block), filters, means, conf["alpha"],
            ps=conf["patch_size"], pool=conf["pool_size"],
            stride=conf["pool_stride"], precision=precision,
        )
        outs.append(feats[: chunk - pad] if pad else feats)
    return jnp.concatenate(outs, axis=0)


# -- the whole fit -------------------------------------------------------------


def chain_scores(conf, chain: dict, images: np.ndarray, precision: str, chunk: int):
    """Raw class scores of a fitted chain, by this file's mathematics:
    ``chain`` holds filters, wmeans, scaler_mean, scaler_std, weights [d, k],
    feature_means [d] and intercept [k] as arrays."""
    feats = featurize(
        conf, images, jnp.asarray(chain["filters"]), jnp.asarray(chain["wmeans"]),
        precision, chunk,
    )
    scaled = (feats - chain["scaler_mean"]) / chain["scaler_std"]
    centred = scaled - chain["feature_means"]
    return mm(centred, jnp.asarray(chain["weights"]), precision) + chain["intercept"]


def fit(conf: dict, data: dict, seed: int, precision: str = "highest") -> dict:
    """The reference's fitted chain and its answers on the test split, as
    numpy arrays under the names the pipeline's ``produced`` uses."""
    chunk = conf.get("reference_chunk", 500)
    block = conf["solver_block"]
    train, test = data["train"], data["test"]
    filters, wmeans = learn_filters(conf, train["x"], seed, precision)
    filters = filters.reshape(filters.shape[0], -1)
    x = featurize(conf, train["x"], filters, wmeans, precision, chunk)
    mean, std = fit_scaler(x)
    x = (x - mean) / std
    d = x.shape[1]
    blocks = [x[:, i : i + block] for i in range(0, d, block)]
    y = indicators(train["y"], conf["num_classes"])
    models, mus, intercept = block_least_squares(
        blocks, y, conf["lam"], conf["num_epochs"], precision
    )
    chain = {
        "filters": filters, "wmeans": wmeans, "scaler_mean": mean,
        "scaler_std": std, "weights": jnp.concatenate(models, axis=0),
        "feature_means": jnp.concatenate(mus), "intercept": intercept,
    }
    train_scores = (
        sum(mm(b - mu, m, precision) for b, mu, m in zip(blocks, mus, models))
        + intercept
    )
    train_error = float(jnp.mean(jnp.argmax(train_scores, axis=1) != jnp.asarray(train["y"])))
    del x, blocks, train_scores
    test_scores = chain_scores(conf, chain, test["x"], precision, chunk)
    test_pred = np.asarray(jnp.argmax(test_scores, axis=1))
    out = {k: np.asarray(v) for k, v in chain.items()}
    out.update(
        test_predictions=test_pred,
        test_error=100.0 * float(np.mean(test_pred != test["y"])),
        train_error=100.0 * train_error,
        test_scores=np.asarray(test_scores),
    )
    return out


# -- the comparison ------------------------------------------------------------


def compare(conf: dict, data: dict, seed: int, produced: dict, ref: dict) -> dict:
    """name -> value of every number compared.  ``produced`` is what the
    timed fit made: its saved chain, its predictions, and the chain's scores
    on a sample of test rows drawn from the seed (a reference put in the
    program's place brings every row's, and the sample is taken here)."""
    test = data["test"]
    rows = pick_rows(len(test["y"]), conf["compare"]["score_rows"], seed)
    if "test_scores_sample" in produced:
        mine = np.asarray(produced["test_scores_sample"], np.float64)
    else:
        mine = np.asarray(produced["test_scores"], np.float64)[rows]
    theirs = ref["test_scores"][rows].astype(np.float64)
    rms = float(np.sqrt(np.mean(theirs**2)))
    diff = mine - theirs
    out = {
        "filters_gap": _rel(produced["filters"], ref["filters"]),
        "feature_mean_gap": _rel(produced["scaler_mean"], ref["scaler_mean"]),
        "feature_std_gap": _rel(produced["scaler_std"], ref["scaler_std"]),
        "scores_rms_gap": float(np.sqrt(np.mean(diff**2))) / rms,
        "scores_max_gap": float(np.max(np.abs(diff))) / rms,
        "pred_disagree": float(
            np.mean(produced["test_predictions"] != ref["test_predictions"])
        ),
        "test_error_gap": abs(float(produced["test_error"]) - ref["test_error"]),
    }
    return out
