"""Plain linear algebra that the pipelines' references share: the column
scaler, the +-1 class indicators and block coordinate descent least squares
(reference BlockLinearMapper.scala:147-204), in float32 with full-precision
products, or rounded lower for a control."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import jax.scipy.linalg as jsl
import numpy as np

from benchmark.lib.precision import mm


@functools.partial(jax.jit, static_argnames=("precision",))
def _block_factor(a, lam, *, precision):
    gram = mm(a.T, a, precision) + lam * jnp.eye(a.shape[1], dtype=a.dtype)
    return jsl.cho_factor(gram)[0]


@functools.partial(jax.jit, static_argnames=("precision",))
def _block_step(a, chol, residual, m_old, *, precision):
    r_i = residual + mm(a, m_old, precision)
    m_new = jsl.cho_solve((chol, False), mm(a.T, r_i, precision))
    return r_i - mm(a, m_new, precision), m_new


def block_least_squares(blocks: list, labels, lam: float, epochs: int, precision: str):
    """Block coordinate descent on centred blocks (reference
    BlockLinearMapper.scala:147-204).  ``blocks``: list of [N, w] arrays.
    Returns (weights per block, block means, intercept)."""
    intercept = jnp.mean(labels, axis=0)
    residual = labels - intercept
    mus = [jnp.mean(b, axis=0) for b in blocks]
    chols = [
        _block_factor(b - mu, jnp.float32(lam), precision=precision)
        for b, mu in zip(blocks, mus)
    ]
    models = [jnp.zeros((b.shape[1], labels.shape[1]), jnp.float32) for b in blocks]
    for _ in range(epochs):
        for i, (b, mu) in enumerate(zip(blocks, mus)):
            residual, models[i] = _block_step(
                b - mu, chols[i], residual, models[i], precision=precision
            )
    return models, mus, intercept


def indicators(labels: np.ndarray, classes: int):
    return 2.0 * jnp.eye(classes, dtype=jnp.float32)[jnp.asarray(labels)] - 1.0


def fit_scaler(x):
    """Column mean and sample standard deviation, two passes."""
    mean = jnp.mean(x, axis=0)
    std = jnp.sqrt(jnp.sum((x - mean) ** 2, axis=0) / (x.shape[0] - 1.0))
    bad = ~jnp.isfinite(std) | (jnp.abs(std) < 1e-12)
    return mean, jnp.where(bad, 1.0, std)


def rel_gap(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))
