"""Statistical feature nodes (reference src/main/scala/nodes/stats/).

All nodes operate on batches ``[N, d]``; per-partition ``rowsToMatrix`` gemm
batching in the reference (e.g. CosineRandomFeatures.scala:24-32) disappears —
arrays are already dense and HBM-resident, and the matmul hits the MXU
directly.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ..core import trace
from ..core.pipeline import Estimator, Transformer, node
from ..parallel.collectives import sharded_moments


@node(data_fields=("mean", "std"))
class StandardScalerModel(Transformer):
    """Subtract column means, optionally divide by column std
    (reference nodes/stats/StandardScaler.scala:16-35)."""

    def __init__(self, mean, std=None):
        self.mean = mean
        self.std = std

    def __call__(self, batch):
        out = batch - self.mean
        if self.std is not None:
            out = out / self.std
        return out


class StandardScaler(Estimator):
    """Distributed column mean/std via one fused reduction
    (reference nodes/stats/StandardScaler.scala:39-60: treeAggregate of a
    MultivariateOnlineSummarizer -> here a single psum of (count, Σx, Σx²)).

    Matches the reference's guards: sample (n-1) variance; any std that is
    NaN/Inf/<eps becomes 1.0.
    """

    def __init__(self, normalize_std_dev: bool = True, eps: float = 1e-12):
        self.normalize_std_dev = normalize_std_dev
        self.eps = eps

    def fit(self, data, nvalid: int | None = None) -> StandardScalerModel:
        n = nvalid if nvalid is not None else data.shape[0]
        _, s, sq = sharded_moments(data)
        return self.from_moments(n, s, sq)

    def from_moments(self, n: int, s, sq) -> StandardScalerModel:
        """The model from the column sums ``s`` and sums of squares ``sq``
        over ``n`` true rows, however they were taken (of any shape: a
        stack of blocks' moments gives a stack of scalers)."""
        cnt = jnp.asarray(n, s.dtype)  # true row count (excludes pad rows)
        mean = s / cnt
        if not self.normalize_std_dev:
            return StandardScalerModel(mean, None)
        var = (sq - cnt * mean * mean) / (cnt - 1.0)
        std = jnp.sqrt(var)
        bad = jnp.isnan(std) | jnp.isinf(std) | (jnp.abs(std) < self.eps)
        std = jnp.where(bad, 1.0, std)
        return StandardScalerModel(mean, std)


@node(data_fields=("W", "b"))
class CosineRandomFeatures(Transformer):
    """Random Fourier features ``cos(x Wᵀ + b)``
    (reference nodes/stats/CosineRandomFeatures.scala:18-57).  One [N,d]x[d,D]
    gemm on the MXU replaces the per-partition batching."""

    def __init__(self, W, b):
        if b.shape[0] != W.shape[0]:
            raise ValueError("# rows of W must match size of b")
        self.W = W
        self.b = b

    def __call__(self, batch):
        return jnp.cos(batch @ self.W.T + self.b)

    @staticmethod
    def create(
        num_input_features: int,
        num_output_features: int,
        gamma: float,
        key,
        w_dist: str = "gaussian",
        dtype=jnp.float32,
    ) -> "CosineRandomFeatures":
        """Gaussian (RBF kernel) or Cauchy (Laplacian kernel) W, uniform b
        (reference CosineRandomFeatures.scala:46-57)."""
        kw, kb = jax.random.split(key)
        shape = (num_output_features, num_input_features)
        if w_dist == "gaussian":
            W = jax.random.normal(kw, shape, dtype)
        elif w_dist == "cauchy":
            W = jax.random.cauchy(kw, shape, dtype)
        else:
            raise ValueError(f"unknown w_dist {w_dist!r}")
        b = jax.random.uniform(kb, (num_output_features,), dtype) * (2.0 * jnp.pi)
        return CosineRandomFeatures(W * gamma, b)


def next_power_of_two(i: int) -> int:
    return 1 << (i - 1).bit_length()


@node(data_fields=(), meta_fields=())
class PaddedFFT(Transformer):
    """Zero-pad to the next power of two; return the real part of the first
    half of the FFT (reference nodes/stats/PaddedFFT.scala:13-21).
    d -> next_pow2(d)/2."""

    def __call__(self, batch):
        padded = next_power_of_two(batch.shape[-1])
        return jnp.fft.rfft(batch, n=padded, axis=-1).real[..., : padded // 2]


@node(data_fields=("signs",))
class RandomSignNode(Transformer):
    """Elementwise random ±1 mask (reference nodes/stats/RandomSignNode.scala:11-25)."""

    def __init__(self, signs):
        self.signs = signs

    def __call__(self, batch):
        return batch * self.signs

    @staticmethod
    def create(size: int, key, dtype=jnp.float32) -> "RandomSignNode":
        signs = jax.random.bernoulli(key, 0.5, (size,)).astype(dtype) * 2.0 - 1.0
        return RandomSignNode(signs)


@node(data_fields=(), meta_fields=("max_val", "alpha"))
class LinearRectifier(Transformer):
    """``max(maxVal, x - alpha)`` (reference nodes/stats/LinearRectifier.scala:11-16)."""

    def __init__(self, max_val: float = 0.0, alpha: float = 0.0):
        self.max_val = max_val
        self.alpha = alpha

    def __call__(self, batch):
        return jnp.maximum(self.max_val, batch - self.alpha)


@node(data_fields=("signs",), meta_fields=())
class RandomFFTBlock(Transformer):
    """One solver block of MnistRandomFFT's features (reference
    MnistRandomFFT.scala:44-48): for each row of ``signs`` ``[f, d]`` the
    chain RandomSign -> PaddedFFT -> LinearRectifier(0), the ``f`` outputs
    side by side in ZipVectors' column order, ``[N, d] -> [N, f * n / 2]``
    with ``n = next_pow2(d)``.  One node whose one leaf may carry a leading
    block axis: what ``solvers.block.BlockSource`` takes.

    Before the rectifier one FFT's chain is linear in the row, so it is
    ``x @ T`` with ``T[j, k] = s_j cos(2 pi j k / n)``: the chain's first
    two nodes applied to the identity's rows give the table ``[d, f * n/2]``
    and the rows meet it in one float32 product at ``HIGHEST``.  XLA's
    transform writes each of its stages to HBM: on a TPU v5e, 60,000 rows of
    ``d`` = 784 through four FFTs took 34.1 ms of device time by the
    transform and 7.0 ms by the product.  The table is built on every call,
    ``f * d`` row transforms, so a call of a few rows pays for it."""

    def __init__(self, signs):
        self.signs = signs

    def __call__(self, batch):
        f, d = self.signs.shape
        n = next_power_of_two(d)
        identity = jnp.eye(d, dtype=batch.dtype)[:, None, :]
        # one [d, f * n/2] operand, so the product writes the block's 2-D
        # layout: a [rows, f, n/2] result tiles f = 4 as 8 on a TPU and turns
        # the layout of a stack of made blocks, which a program that keeps
        # them then copies whole
        table = PaddedFFT()(RandomSignNode(self.signs)(identity)).reshape(d, f * (n // 2))
        # counted where a program that makes these blocks is traced
        trace.metrics.inc("fft_form.product")
        trace.instant(
            "fft_form", rows=math.prod(batch.shape[:-1]), n=n, ffts=f, width=d,
            dtype=str(batch.dtype), table_bytes=table.nbytes,
        )
        out = jnp.matmul(batch, table, precision=jax.lax.Precision.HIGHEST)
        return LinearRectifier(0.0)(out)


@node(data_fields=(), meta_fields=())
class NormalizeRows(Transformer):
    """L2-normalize each row, norm floored at machine epsilon
    (reference nodes/stats/NormalizeRows.scala:10-15)."""

    def __call__(self, batch):
        norm = jnp.linalg.norm(batch, axis=-1, keepdims=True)
        return batch / jnp.maximum(norm, 2.2e-16)


@node(data_fields=(), meta_fields=())
class SignedHellingerMapper(Transformer):
    """Signed square-root power normalization ``sign(x)·sqrt(|x|)``
    (reference nodes/stats/SignedHellingerMapper.scala:12-22).  Applies
    elementwise, so the batch form doubles as BatchSignedHellingerMapper."""

    def __call__(self, batch):
        return jnp.sign(batch) * jnp.sqrt(jnp.abs(batch))


# Batch alias matching the reference's separate matrix node.
BatchSignedHellingerMapper = SignedHellingerMapper


class Sampler:
    """``takeSample``-style row sampler (reference nodes/stats/Sampling.scala:25-37)."""

    def __init__(self, size: int, seed: int = 42):
        self.size = size
        self.seed = seed

    def __call__(self, data):
        n = data.shape[0]
        if n <= self.size:
            return data
        idx = jax.random.choice(
            jax.random.PRNGKey(self.seed), n, (self.size,), replace=False
        )
        return jnp.take(data, idx, axis=0)


class ColumnSampler:
    """Sample columns from a batch of descriptor matrices
    (reference nodes/stats/Sampling.scala:12-22).  Input [N, d, cols] or a
    list of [d, cols_i]; output [d, num_samples]."""

    def __init__(self, num_samples: int, seed: int = 42):
        self.num_samples = num_samples
        self.seed = seed

    def __call__(self, mats):
        if isinstance(mats, (list, tuple)):
            cols = jnp.concatenate([m for m in mats], axis=1)
        else:
            n, d, c = mats.shape
            cols = jnp.moveaxis(mats, 1, 0).reshape(d, n * c)
        total = cols.shape[1]
        if total <= self.num_samples:
            return cols
        idx = jax.random.choice(
            jax.random.PRNGKey(self.seed), total, (self.num_samples,), replace=False
        )
        return jnp.take(cols, idx, axis=1)
